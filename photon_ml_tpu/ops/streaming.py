"""Out-of-core GLM training: chunked host→device objective evaluation.

Reference parity: the reference streams arbitrarily large datasets through
Spark partitions — each L-BFGS/TRON iteration broadcasts coefficients and
treeAggregates per-partition (value, gradient) sums back to the driver
(``photon-api::ml.function.glm.DistributedGLMLossFunction``, SURVEY.md
§2.2, §7 hard parts: "Streaming 1B rows through host RAM with
double-buffering").

TPU-native redesign: when a dataset exceeds device HBM, the batch lives in
host RAM as a list of uniform-shape chunks; each objective evaluation
streams chunks through the device, accumulating partial (value, gradient)
sums on device. Transfers are double-buffered — chunk ``i+1``'s
``device_put`` is issued before chunk ``i``'s compute is consumed, so the
DMA overlaps the matmuls (JAX dispatch is asynchronous). The per-chunk
kernel is ONE compiled program re-entered for every chunk of every
iteration (uniform chunk shapes are a hard requirement for that).

The optimizers driving this are host-side L-BFGS and TRON
(``optim.host_lbfgs`` / ``optim.host_tron``): the device-resident
``lax.while_loop`` optimizers cannot stream host data from inside a
compiled loop, so the loop structure intentionally mirrors the reference's
driver-resident Breeze loop — one streamed pass per value+gradient
evaluation (plus one per CG step for TRON). For data that fits HBM, the fully
device-resident optimizers in ``photon_ml_tpu.optim`` remain the fast
path; ``fits_in_memory`` below is the decision rule.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Iterable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.ops.batch import Batch, DenseBatch, SparseBatch
from photon_ml_tpu.ops.glm import make_objective
from photon_ml_tpu.ops.losses import PointwiseLoss

Array = jnp.ndarray


def chunk_batch(batch_arrays: dict, chunk_rows: int) -> list[dict]:
    """Split host arrays (a dict of same-leading-dim numpy arrays) into
    uniform ``chunk_rows``-row chunks; the last chunk is padded with
    zero-weight rows so every chunk compiles to the same program."""
    n = len(batch_arrays["labels"])
    chunks = []
    for lo in range(0, n, chunk_rows):
        hi = min(lo + chunk_rows, n)
        chunk = {k: v[lo:hi] for k, v in batch_arrays.items()}
        pad = chunk_rows - (hi - lo)
        if pad:
            for k, v in chunk.items():
                fill = np.zeros((pad,) + v.shape[1:], v.dtype)
                chunk[k] = np.concatenate([v, fill])
            # padded rows carry weight 0 → inert in the objective
            chunk["weights"][hi - lo:] = 0.0
        chunks.append(chunk)
    return chunks


def dense_chunks(
    X: np.ndarray,
    labels: np.ndarray,
    chunk_rows: int,
    offsets: np.ndarray | None = None,
    weights: np.ndarray | None = None,
) -> list[dict]:
    n = X.shape[0]
    return chunk_batch(
        {
            "X": X,
            "labels": labels,
            "offsets": np.zeros(n, X.dtype) if offsets is None else offsets,
            "weights": np.ones(n, X.dtype) if weights is None else weights,
        },
        chunk_rows,
    )


def sparse_chunks(
    indices: np.ndarray,
    values: np.ndarray,
    labels: np.ndarray,
    chunk_rows: int,
    offsets: np.ndarray | None = None,
    weights: np.ndarray | None = None,
) -> list[dict]:
    n = indices.shape[0]
    return chunk_batch(
        {
            "indices": indices,
            "values": values,
            "labels": labels,
            "offsets": np.zeros(n, values.dtype) if offsets is None else offsets,
            "weights": np.ones(n, values.dtype) if weights is None else weights,
        },
        chunk_rows,
    )


def _to_batch(chunk: dict, num_features: int | None) -> Batch:
    if "X" in chunk:
        return DenseBatch(
            X=chunk["X"], labels=chunk["labels"],
            offsets=chunk["offsets"], weights=chunk["weights"],
        )
    return SparseBatch(
        indices=chunk["indices"], values=chunk["values"], labels=chunk["labels"],
        offsets=chunk["offsets"], weights=chunk["weights"],
        num_features=num_features,
    )


def _fe_nnz_histogram(chunks: Sequence[dict], num_features: int) -> np.ndarray:
    """Global per-feature nnz counts over sparse chunk dicts (padded
    zero-value slots excluded — they never pack or contribute). Under
    feature-range sharding rows are replicated, so the LOCAL histogram is
    the global one and every process derives the identical partition."""
    nnz = np.zeros(num_features, np.int64)
    for c in chunks:
        idx = np.asarray(c["indices"]).ravel()
        val = np.asarray(c["values"]).ravel()
        live = idx[val != 0.0]
        if live.size:
            nnz += np.bincount(live, minlength=num_features)
    return nnz


def _fe_restrict_chunks(
    chunks: Sequence[dict], lo: int, hi: int
) -> tuple[list[dict], int]:
    """Column-restrict sparse chunk dicts to the feature range [lo, hi):
    out-of-range entries zero out (index 0, value 0 — inert in both matvec
    directions), in-range indices shift by -lo, and every chunk compacts
    to ONE common per-row width (kept entries first, stable order) so the
    restricted chunks stay uniform-shape for the one-kernel discipline —
    and so the raw host→device stream shrinks with the range, not just
    the packed tile-COO stream. labels/offsets/weights are SHARED with
    the input chunks (same storage: per-pass streaming sees live values,
    and the prefetch chunk cache keys keep hitting)."""
    keeps = []
    k_max = 1
    for c in chunks:
        idx = np.asarray(c["indices"])
        val = np.asarray(c["values"])
        keep = (idx >= lo) & (idx < hi) & (val != 0.0)
        if keep.size:
            k_max = max(k_max, int(keep.sum(axis=1).max()))
        keeps.append(keep)
    out = []
    for c, keep in zip(chunks, keeps):
        idx = np.asarray(c["indices"])
        val = np.asarray(c["values"])
        order = np.argsort(~keep, axis=1, kind="stable")
        idx_loc = np.take_along_axis(
            np.where(keep, idx - lo, 0).astype(idx.dtype), order, axis=1
        )[:, :k_max]
        val_loc = np.take_along_axis(
            np.where(keep, val, 0.0).astype(val.dtype), order, axis=1
        )[:, :k_max]
        out.append(dict(
            c,
            indices=np.ascontiguousarray(idx_loc),
            values=np.ascontiguousarray(val_loc),
        ))
    return out, k_max


def device_hbm_budget_bytes(
    default: float = 8e9, fraction: float = 0.75, device=None
) -> float:
    """The HBM budget for dataset residency, QUERIED from the device
    (``memory_stats()['bytes_limit']`` scaled by ``fraction`` to leave room
    for coefficients, optimizer state and XLA scratch). On a TPU a device
    that reports no limit raises: every layout and streaming decision
    downstream would otherwise be sized for a chip that is not the one
    attached. Only the CPU backend, which exposes no memory stats, takes
    ``default`` (the test suite's path).

    Which source won is recorded (``hbm.budget_bytes`` /
    ``hbm.budget_queried`` gauges + a one-per-run ``hbm_budget`` event):
    a fallback-budget run on a memory-stats-less backend is
    distinguishable from a device-quoted one in ``report`` output."""
    if device is None:
        device = jax.local_devices()[0]
    stats = device.memory_stats() or {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    queried = fraction * float(limit) if limit else None
    if queried is None and device.platform != "cpu":
        raise RuntimeError(
            f"{device.platform} device {device.device_kind!r} reports no "
            f"bytes_limit in memory_stats(); refusing to assume "
            f"{default:.3g} bytes of HBM"
        )
    budget = default if queried is None else queried
    from photon_ml_tpu.obs import devcost

    devcost.record_hbm_budget(budget, queried is not None)
    return budget


def fits_in_memory(num_rows: int, num_features: int, itemsize: int = 4,
                   hbm_budget_bytes: float | None = None) -> bool:
    """Decision rule between the device-resident fast path and streaming.
    ``hbm_budget_bytes=None`` queries the device (``device_hbm_budget_bytes``)."""
    if hbm_budget_bytes is None:
        hbm_budget_bytes = device_hbm_budget_bytes()
    return num_rows * num_features * itemsize <= hbm_budget_bytes


@dataclass
class StreamingGLMObjective:
    """GLM objective over host-resident chunks (uniform shapes).

    Exposes the same ``value`` / ``value_and_grad`` contract as
    ``GLMObjective``, so ``host_lbfgs_minimize`` (or any host-driven
    optimizer) consumes it directly. Per-chunk math reuses ``GLMObjective``
    with the L2 term stripped (added once at the end); per-chunk
    normalization-space gradients sum correctly because
    ``grad_to_model_space`` is linear in its (g_raw, r_sum) inputs.
    """

    chunks: Sequence[dict]  # host numpy chunk dicts (uniform shapes)
    loss: PointwiseLoss
    num_features: int
    l2_weight: float = 0.0
    intercept_index: int | None = None
    norm: NormalizationContext | None = None
    # multi-host: sum partial (value, grad) across ALL processes per
    # evaluation (each host streams only its own chunks — the treeAggregate
    # analog). The L2 term is added once, AFTER the cross-process sum.
    cross_process: bool = False
    # incremental training: (d,) Gaussian MAP prior in the SOLVER's
    # coefficient space (normalized space when ``norm`` is set — build via
    # ``GaussianPrior.from_coefficients``, same as the device objective).
    # The regularizer becomes 0.5·λ₂·Σ maskⱼ·precⱼ·(wⱼ−μⱼ)²; plain L2 is
    # the μ=0, prec=1 default. Like the L2 term, the prior lands ONCE
    # outside the per-chunk stream (it does not depend on the data).
    prior_mean: Array | None = None
    prior_precision: Array | None = None
    # tile-COO chunk kernels for SPARSE chunks (VERDICT r4 missing #4: the
    # streamed objective lowered its sparse chunks through the known-slow
    # XLA gather/scatter path). None = auto: tile on TPU when the chunks
    # are sparse and high-dimensional (the same rule as the in-memory
    # ingest decision). Layouts build ONCE from the first chunks'
    # indices/values and live on device; a later ``chunks`` swap must
    # preserve indices/values (the GAME trainer's per-visit swap only
    # changes offsets — a fingerprint check rejects anything else).
    tile_sparse: bool | None = None
    # feature-range sharding (PHOTON_FE_SHARD): None = follow the knob
    # (sparse chunks only); True/False force it per objective (the GAME
    # trainer passes False — its entity axis is already sharded, mixed
    # entity×feature sharding is future work). When active, this process
    # holds ONLY its contiguous feature range [lo, hi): restricted
    # column-sliced chunks, a (hi-lo,) coefficient/gradient contract
    # toward the optimizer, and ONE fixed-ascending-range-order margin
    # reduction per streamed pass. Requires replicated rows across
    # processes (every process streams ALL rows; the win is the feature
    # axis) — the complement of ``cross_process`` row sharding, and
    # mutually exclusive with it.
    fe_shard: bool | None = None

    def __post_init__(self):
        if not self.chunks and not self.cross_process:
            raise ValueError("streaming objective needs at least one chunk")
        mask = jnp.ones((self.num_features,), jnp.float32)
        if self.intercept_index is not None:
            mask = mask.at[self.intercept_index].set(0.0)
        # public: the host OWL-QN twin applies scalar L1 over this mask,
        # exactly like the device objective's reg_mask contract (the
        # LOCAL range slice under feature-range sharding)
        self.reg_mask = mask
        if self.prior_mean is not None:
            self.prior_mean = jnp.asarray(self.prior_mean, jnp.float32)
        if self.prior_precision is not None:
            self.prior_precision = jnp.asarray(self.prior_precision, jnp.float32)
        self._tile_layouts = None
        self._tile_meta = None
        self._tile_fingerprints = None
        self._fe_plan = None
        self._fe_range = None  # (pid, lo, hi, P) when sharded
        self._fe_chunks = None
        self._fe_dim = self.num_features
        from photon_ml_tpu.ops.sparse_tiled import auto_tile_streaming

        sparse = bool(self.chunks) and "indices" in self.chunks[0]
        from photon_ml_tpu.data.index_map import fe_shard_enabled

        want_fe = (
            self.fe_shard
            if self.fe_shard is not None
            else (sparse and fe_shard_enabled())
        )
        if want_fe:
            self._init_fe_shard(sparse)
        want_tiling = (
            self.tile_sparse
            if self.tile_sparse is not None
            else auto_tile_streaming(sparse, self.num_features)
        )
        if want_tiling and sparse:
            self._build_tile_layouts()
        if self._fe_range is not None:
            self._build_fe_kernels()

        def chunk_value_grad(batch: Batch, w: Array):
            obj = make_objective(
                batch, self.loss, l2_weight=0.0, norm=self.norm,
                intercept_index=self.intercept_index,
            )
            return obj.value_and_grad(w)

        def chunk_value(batch: Batch, w: Array):
            obj = make_objective(
                batch, self.loss, l2_weight=0.0, norm=self.norm,
                intercept_index=self.intercept_index,
            )
            return obj.value(w)

        def chunk_hvp(batch: Batch, wv: tuple[Array, Array]):
            obj = make_objective(
                batch, self.loss, l2_weight=0.0, norm=self.norm,
                intercept_index=self.intercept_index,
            )
            return obj.hvp(wv[0], wv[1])

        def chunk_hessian_diag(batch: Batch, w: Array):
            obj = make_objective(
                batch, self.loss, l2_weight=0.0, norm=self.norm,
                intercept_index=self.intercept_index,
            )
            return obj.hessian_diag(w)

        def chunk_hessian(batch: Batch, w: Array):
            from photon_ml_tpu.ops.batch import SparseBatch, densify

            if isinstance(batch, SparseBatch):
                # FULL variance only runs under the d-bound, where a
                # chunk-rows × d dense view is small; densifying per chunk
                # keeps ONE hessian implementation
                batch = densify(batch)
            obj = make_objective(
                batch, self.loss, l2_weight=0.0, norm=self.norm,
                intercept_index=self.intercept_index,
            )
            return obj.hessian(w)

        # ONE compiled kernel per contract, re-entered for every chunk
        self._chunk_vg = jax.jit(chunk_value_grad)
        self._chunk_v = jax.jit(chunk_value)
        self._chunk_hvp = jax.jit(chunk_hvp)
        self._chunk_hd = jax.jit(chunk_hessian_diag)
        self._chunk_h = jax.jit(chunk_hessian)

    def _build_tile_layouts(self):
        """Tile every sparse chunk ONCE (host transform): per-chunk
        write-slab-major layouts, padded to a common stream length so one
        compiled kernel serves every chunk, staged to device where they
        stay for the whole objective lifetime (only labels/offsets/weights
        ride the per-pass host→device stream — the packed index/value
        streams replace the raw indices/values entirely). The per-chunk
        pack goes through the PROCESS-WIDE layout cache
        (``ops/tile_cache``): a rebuilt objective over the same data —
        GAME trainers rebuild per fit, drivers per sweep — reuses the
        packed streams instead of re-sorting every nonzero."""
        from photon_ml_tpu.ops import tile_cache
        from photon_ml_tpu.ops.batch import SparseBatch
        from photon_ml_tpu.ops.sparse_tiled import pad_chunks_to_common_groups

        tbs = []
        fps = []
        # under feature-range sharding the layouts pack the RESTRICTED
        # column-sliced chunks (zeroed out-of-range entries drop at pack
        # time, so the packed streams genuinely shrink to ~range nnz) and
        # the range identity joins both the cache key and the batch meta
        for c in (self._fe_chunks if self._fe_chunks is not None
                  else self.chunks):
            sb = SparseBatch(
                indices=c["indices"], values=c["values"], labels=c["labels"],
                offsets=c["offsets"], weights=c["weights"],
                num_features=self._fe_dim,
            )
            fp = self._chunk_fingerprint(c)
            tbs.append(
                tile_cache.tiled_layout_for(
                    sb, keep_empty_chunks=True,
                    # same hash serves the swap guard (structure) and the
                    # cache key (structure + feature width) — computed once
                    fingerprint=(fp[0], self._fe_dim, fp[1], fp[2]),
                    fe_range=self._fe_range,
                )
            )
            fps.append(fp)
        layouts = pad_chunks_to_common_groups(tbs)
        ref = tbs[0]
        self._tile_layouts = [
            tuple(layouts[j][i] for j in range(len(ref.chunks)))
            for i in range(len(tbs))
        ]
        self._tile_meta = (
            ref.num_rows_real, ref.n_pad_total, ref.d_pad_total
        )
        self._tile_fingerprints = fps

    def _init_fe_shard(self, sparse: bool) -> None:
        """Partition the feature space and restrict this process to its
        range (PHOTON_FE_SHARD). The plan reads ONLY the global per-feature
        nnz histogram and the effective process count — deterministic
        pure-host arithmetic on inputs identical on every process (rows are
        replicated under this mode), so every process derives the same
        boundaries with zero communication. The regularizer surfaces
        (reg_mask, priors) slice to the range: the ranges are DISJOINT, so
        local quadratic terms sum to the global regularizer exactly."""
        from photon_ml_tpu.data.index_map import plan_feature_ranges
        from photon_ml_tpu.obs.metrics import REGISTRY
        from photon_ml_tpu.parallel.multihost import (
            effective_process_count,
            effective_process_index,
        )

        if not sparse:
            raise ValueError(
                "PHOTON_FE_SHARD requires sparse chunks (dense chunks fit "
                "one chip's HBM by construction)"
            )
        if self.cross_process:
            raise ValueError(
                "PHOTON_FE_SHARD shards the FEATURE axis over replicated "
                "rows; cross_process shards rows — the two are mutually "
                "exclusive on one objective"
            )
        if self.norm is not None:
            raise NotImplementedError(
                "PHOTON_FE_SHARD supports identity normalization only "
                "(norm=None): normalization shifts couple all ranges "
                "through the margin correction"
            )
        p_count = effective_process_count()
        pid = effective_process_index()
        plan = plan_feature_ranges(
            _fe_nnz_histogram(self.chunks, self.num_features), p_count
        )
        lo, hi = plan.range_of(pid)
        self._fe_plan = plan
        self._fe_range = (pid, lo, hi, p_count)
        self._fe_dim = hi - lo
        self._fe_chunks, _ = _fe_restrict_chunks(self.chunks, lo, hi)
        self.reg_mask = self.reg_mask[lo:hi]
        if self.prior_mean is not None:
            self.prior_mean = self.prior_mean[lo:hi]
        if self.prior_precision is not None:
            self.prior_precision = self.prior_precision[lo:hi]
        REGISTRY.gauge_set("fe_shard.ranges", float(p_count))
        REGISTRY.gauge_set("fe_shard.width", float(self._fe_dim))
        REGISTRY.gauge_set("fe_shard.nnz_local", float(plan.weights[pid]))
        REGISTRY.gauge_set("fe_shard.nnz_balance", float(plan.balance))

    def _build_fe_kernels(self) -> None:
        """The sharded per-chunk programs (ONE compiled kernel per
        contract, re-entered for every chunk — the same discipline as the
        replicated kernels). Phase A computes the range-local partial
        matvec(s); phase B re-streams the chunks against the COMBINED
        margins, which ride as one device array sliced per chunk (chunk
        shapes are uniform, so the chunk index is the only per-chunk
        value and stays a traced scalar)."""
        loss = self.loss
        n_chunk = int(np.asarray(self.chunks[0]["labels"]).shape[0])

        def weighted(batch, x):
            wts = batch.weights
            return jnp.where(wts != 0.0, wts * x, 0.0)

        def m_at(full, i):
            return jax.lax.dynamic_slice(full, (i * n_chunk,), (n_chunk,))

        def fe_margin(batch, ws):
            return jnp.stack([batch.matvec(w) for w in ws])

        def fe_value(batch, mi):
            m = m_at(mi[0][0], mi[1]) + batch.offsets
            return jnp.sum(weighted(batch, loss.value(m, batch.labels)))

        def fe_value_grad(batch, mi):
            m = m_at(mi[0][0], mi[1]) + batch.offsets
            val = jnp.sum(weighted(batch, loss.value(m, batch.labels)))
            r = weighted(batch, loss.d1(m, batch.labels))
            return val, batch.rmatvec(r)

        def fe_hvp(batch, mi):
            m = m_at(mi[0][0], mi[1]) + batch.offsets
            q = weighted(batch, loss.d2(m, batch.labels)) * m_at(mi[0][1], mi[1])
            return batch.rmatvec(q)

        def fe_hessian_diag(batch, mi):
            m = m_at(mi[0][0], mi[1]) + batch.offsets
            return batch.rmatvec_sq(
                weighted(batch, loss.d2(m, batch.labels))
            )

        self._fe_k_m = jax.jit(fe_margin)
        self._fe_k_v = jax.jit(fe_value)
        self._fe_k_vg = jax.jit(fe_value_grad)
        self._fe_k_hvp = jax.jit(fe_hvp)
        self._fe_k_hd = jax.jit(fe_hessian_diag)

    def _fe_combine_margins(self, ws: tuple, l2_w=None):
        """Phase A of a sharded evaluation: stream the range-local partial
        matvec(s) over the restricted chunks, then ONE cross-range
        reduction in FIXED ASCENDING RANGE ORDER (``allreduce_sum_host``
        allgathers and sums in process order — psum-equivalent under a
        healthy mesh, the framed-P2P raw-ndarray codec when degraded), so
        every process holds bit-identical combined margins. ``l2_w``
        piggybacks the local regularizer scalar on the same collective —
        a sharded pass costs exactly one margin-sized reduction."""
        from photon_ml_tpu.parallel.multihost import allreduce_sum_host

        ws = tuple(jnp.asarray(w) for w in ws)
        parts = self._stream(
            ws, self._fe_k_m, lambda acc, out: acc + [np.asarray(out)], [],
            devcost_fn=self._fe_k_m, devcost_label="streaming.fe_margins",
        )
        partial = np.concatenate(parts, axis=1)
        if l2_w is None:
            return jnp.asarray(allreduce_sum_host(partial))
        l2_local = np.asarray(self._l2_term(jnp.asarray(l2_w)), np.float32)
        m, l2 = allreduce_sum_host(partial, l2_local)
        return jnp.asarray(m), jnp.asarray(l2)

    @property
    def fe_active(self) -> bool:
        """True when this objective's coefficient contract is a
        feature-range shard (w, gradients and curvature vectors are the
        LOCAL (hi-lo,) segment; values and line-search scalars are
        global)."""
        return self._fe_range is not None

    def fe_slice(self, w_full) -> np.ndarray:
        """This process's range segment of a full-space vector (warm
        starts, priors already sliced at build)."""
        _pid, lo, hi, _p = self._fe_range
        return np.asarray(w_full)[lo:hi]

    def fe_gather(self, w_local) -> np.ndarray:
        """EXACT full-space assembly of per-range segments: an ascending-
        range-order allgather + concatenation — pure data movement, no
        arithmetic, so the assembled vector is bitwise the segments.
        Collective (framed-P2P: segments are variable-width); identity at
        a single range."""
        w_local = np.asarray(w_local)
        if self._fe_range[3] <= 1:
            return w_local
        from photon_ml_tpu.parallel.multihost import allgather_obj_p2p

        parts = allgather_obj_p2p(w_local, tag="fe_gather")
        return np.concatenate([np.asarray(p) for p in parts])

    def fe_dot(self, a, b) -> float:
        """Global inner product of two range-local vectors: local dot,
        then a scalar all-reduce — the ONLY wire traffic the optimizers'
        line searches add. Every process receives the identical sum
        (fixed-order reduction), so host-side control flow stays in
        lockstep."""
        from photon_ml_tpu.parallel.multihost import allreduce_sum_host

        local = np.asarray(
            np.dot(np.asarray(a, np.float64), np.asarray(b, np.float64))
        )
        return float(allreduce_sum_host(local))

    @staticmethod
    def _chunk_fingerprint(chunk: dict) -> tuple:
        # one hash serves both the swap guard and (widened with the
        # feature count) the process-wide layout cache key
        from photon_ml_tpu.ops import tile_cache

        return tile_cache.structure_fingerprint(
            chunk["indices"], chunk["values"]
        )

    @staticmethod
    def _same_storage(a, b) -> bool:
        """True when ``a`` and ``b`` are numpy arrays over the SAME memory
        (identical object, or fresh views of one base with the same data
        pointer/shape/strides). The GAME trainer re-slices its feature
        arrays every visit — each swap passes NEW view objects over
        unchanged storage, so a plain ``is`` check would re-hash the whole
        design matrix once per coordinate visit."""
        if a is b:
            return True
        if not (isinstance(a, np.ndarray) and isinstance(b, np.ndarray)):
            return False
        ai, bi = a.__array_interface__, b.__array_interface__
        return (
            ai["data"] == bi["data"]
            and ai["shape"] == bi["shape"]
            and ai["strides"] == bi["strides"]
            and a.dtype == b.dtype
        )

    def __setattr__(self, name, value):
        if (
            name == "chunks"
            and getattr(self, "_fe_chunks", None) is not None
        ):
            # the restricted column slices (and the plan they were cut
            # by) were derived from the PREVIOUS chunks; no caller swaps
            # chunks on a sharded objective today (the GAME trainer opts
            # out with fe_shard=False), so refuse loudly instead of
            # silently re-deriving a possibly different plan
            raise ValueError(
                "chunk swap under feature-range sharding (PHOTON_FE_SHARD); "
                "rebuild the StreamingGLMObjective"
            )
        if (
            name == "chunks"
            and getattr(self, "_tile_layouts", None) is not None
        ):
            # the cached layouts were built from the PREVIOUS chunks'
            # indices/values; a swap may only change labels/offsets/weights
            # (the GAME trainer's per-visit residual swap). Same-storage
            # check first: the common swap re-slices the same arrays, and
            # the byte-exact hash is only worth paying for fresh storage.
            old_chunks = getattr(self, "chunks", None)
            for i, c in enumerate(value):
                prev = (
                    old_chunks[i]
                    if old_chunks is not None and i < len(old_chunks)
                    else None
                )
                if (
                    prev is not None
                    and self._same_storage(c.get("indices"), prev.get("indices"))
                    and self._same_storage(c.get("values"), prev.get("values"))
                ):
                    continue
                if (
                    i >= len(self._tile_fingerprints)
                    or self._chunk_fingerprint(c) != self._tile_fingerprints[i]
                ):
                    raise ValueError(
                        "chunk swap changed indices/values under cached "
                        "tile-COO layouts; rebuild the StreamingGLMObjective"
                    )
            if len(value) != len(self._tile_fingerprints):
                raise ValueError(
                    "chunk swap changed the chunk count under cached "
                    "tile-COO layouts; rebuild the StreamingGLMObjective"
                )
        object.__setattr__(self, name, value)

    def _chunk_batch(self, cur: dict, i: int) -> Batch:
        if self._tile_layouts is not None:
            from photon_ml_tpu.ops.sparse_tiled import TiledSparseBatch

            num_rows_real, n_pad, d_pad = self._tile_meta
            return TiledSparseBatch(
                chunks=self._tile_layouts[i],
                labels=cur["labels"], offsets=cur["offsets"],
                weights=cur["weights"],
                num_features=self._fe_dim,
                num_rows_real=num_rows_real,
                n_pad_total=n_pad, d_pad_total=d_pad,
                fe_range=self._fe_range,
            )
        return _to_batch(cur, self._fe_dim)

    def _stream(self, params, kernel: Callable, accumulate: Callable, init,
                devcost_fn=None, devcost_label: str | None = None,
                params_for: Callable | None = None):
        """Host→device chunk pipeline. Default (``PHOTON_PREFETCH_DEPTH``
        > 0): a bounded-depth background pipeline (``ops/prefetch``)
        prepares chunk ``i+k`` — host staging + ``device_put`` through the
        process-wide device-resident chunk cache, so optimizer passes 2..N
        replay already-resident buffers — on worker threads while the
        device computes chunk ``i``. Kernel calls and accumulation stay on
        THIS thread in chunk order, so outputs are bitwise identical to
        the synchronous schedule. Depth 0 restores the pre-prefetch
        double-buffered path bit-for-bit: the NEXT chunk's transfer is
        issued before the CURRENT chunk's compute result is consumed, so
        DMA overlaps compute (async dispatch). ``params`` is passed to
        ``kernel`` verbatim (an array or a tuple of arrays). Tiled chunks
        stream only labels/offsets/weights (the packed nonzero streams are
        device-resident).

        ``devcost_fn``/``devcost_label`` name the jitted per-chunk program
        for analytic cost capture (``obs/devcost``) — chunks are
        uniform-shape, so the FIRST chunk's signature covers every chunk
        of every pass, and the capture dedup means passes 2..N emit
        nothing.

        ``params_for`` (feature-range sharding's phase B) supplies
        PER-CHUNK params (chunk index → params) instead of the shared
        ``params`` — the combined margins ride as one device array and
        each chunk's kernel slices its rows by index."""
        slim = (
            (lambda c: {k: c[k] for k in ("labels", "offsets", "weights")})
            if self._tile_layouts is not None
            else (lambda c: c)
        )
        # under feature-range sharding the stream serves the RESTRICTED
        # column-sliced chunks; their labels/offsets/weights are the SAME
        # storage as self.chunks', so live per-pass values still ride
        src = self._fe_chunks if self._fe_chunks is not None else self.chunks
        acc = init
        if not src:
            return acc
        from photon_ml_tpu.obs import devcost
        from photon_ml_tpu.obs.metrics import REGISTRY
        from photon_ml_tpu.ops import prefetch

        # registry counters (one update per PASS, not per chunk: the
        # telemetry write must never show up on the chunk critical path)
        REGISTRY.counter_inc("stream.passes")
        REGISTRY.counter_inc("stream.chunks", len(src))

        from photon_ml_tpu.ops import stream_executor

        if stream_executor.stream_executor_enabled():
            # executor path: same pool, same in-order consume; device
            # residency rides the MULTI-TENANT arbiter keyed by chunk
            # CONTENT × pack dtype × fe_range, so a validation stream
            # replaying these chunks re-uses the resident buffers
            def prepare_x(i):
                return stream_executor.cached_device_put(
                    "objective", slim(src[i]), context=self._fe_range
                )

            for i, cur in enumerate(
                stream_executor.stream("objective", len(src), prepare_x)
            ):
                b = self._chunk_batch(cur, i)
                p_i = params_for(i) if params_for is not None else params
                if i == 0 and devcost_fn is not None:
                    devcost.capture(devcost_label, devcost_fn, (b, p_i))
                out = kernel(b, p_i)
                acc = accumulate(acc, out)
            return acc

        depth = prefetch.prefetch_depth()
        if depth <= 0:
            # pack_host_chunk: raw feature columns transfer at the
            # precision ladder's storage dtype here too (identity on the
            # f32 rung, so depth 0 stays the pre-prefetch path bit-for-bit)
            nxt = jax.device_put(prefetch.pack_host_chunk(slim(src[0])))
            for i in range(len(src)):
                cur = nxt
                if i + 1 < len(src):
                    nxt = jax.device_put(
                        prefetch.pack_host_chunk(slim(src[i + 1]))
                    )
                b = self._chunk_batch(cur, i)
                p_i = params_for(i) if params_for is not None else params
                if i == 0 and devcost_fn is not None:
                    devcost.capture(devcost_label, devcost_fn, (b, p_i))
                out = kernel(b, p_i)
                acc = accumulate(acc, out)
            return acc

        def prepare(i):
            return prefetch.cached_device_put(slim(src[i]))

        for i, cur in enumerate(
            prefetch.prefetch_iter(len(src), prepare, depth)
        ):
            b = self._chunk_batch(cur, i)
            p_i = params_for(i) if params_for is not None else params
            if i == 0 and devcost_fn is not None:
                devcost.capture(devcost_label, devcost_fn, (b, p_i))
            out = kernel(b, p_i)
            acc = accumulate(acc, out)
        return acc

    def _reg_delta(self, w: Array) -> Array:
        from photon_ml_tpu.ops.glm import reg_delta

        return reg_delta(w, self.prior_mean, self.prior_precision)

    def _reg_curvature(self, like: Array) -> Array:
        from photon_ml_tpu.ops.glm import reg_curvature

        return reg_curvature(like, self.prior_mean, self.prior_precision)

    def _l2_term(self, w: Array) -> Array:
        from photon_ml_tpu.ops.glm import reg_term

        return reg_term(
            jnp.asarray(w), jnp.float32(self.l2_weight), self.reg_mask,
            self.prior_mean, self.prior_precision,
        )

    def value(self, w: Array) -> Array:
        if self._fe_range is not None:
            return self._fe_value(w)
        total = self._stream(
            jnp.asarray(w), self._chunk_v, lambda acc, v: acc + v,
            jnp.float32(0.0),
            devcost_fn=self._chunk_v, devcost_label="streaming.chunk_value",
        )
        if self.cross_process:
            from photon_ml_tpu.parallel.multihost import allreduce_sum_host

            total = jnp.asarray(allreduce_sum_host(np.asarray(total)))
        return total + self._l2_term(jnp.asarray(w))

    def hvp(self, w: Array, v: Array) -> Array:
        """Gauss-Newton Hessian-vector product, streamed — TRON's CG inner
        loop costs one full-data pass per step, exactly the reference's
        treeAggregate accounting (SURVEY §2.1 TRON row)."""
        if self._fe_range is not None:
            return self._fe_hvp(w, v)
        w = jnp.asarray(w)
        v = jnp.asarray(v)
        init = jnp.zeros((self.num_features,), jnp.float32)
        hv = self._stream(
            (w, v),
            lambda batch, wv: self._chunk_hvp(batch, wv),
            lambda acc, out: acc + out,
            init,
            devcost_fn=self._chunk_hvp, devcost_label="streaming.chunk_hvp",
        )
        if self.cross_process:
            from photon_ml_tpu.parallel.multihost import allreduce_sum_host

            hv = jnp.asarray(allreduce_sum_host(np.asarray(hv)))
        return hv + (
            jnp.float32(self.l2_weight) * self.reg_mask
            * self._reg_curvature(v) * v
        )

    def hessian_diag(self, w: Array) -> Array:
        """diag(H), streamed — VarianceComputationType.SIMPLE at the
        solution costs one extra full-data pass (the in-memory formula is
        linear in the per-chunk data sums, so chunk partials add; the L2
        term lands once, after the cross-process sum)."""
        if self._fe_range is not None:
            return self._fe_hessian_diag(w)
        w = jnp.asarray(w)
        init = jnp.zeros((self.num_features,), jnp.float32)
        diag = self._stream(
            w,
            lambda batch, wi: self._chunk_hd(batch, wi),
            lambda acc, out: acc + out,
            init,
            devcost_fn=self._chunk_hd,
            devcost_label="streaming.chunk_hessian_diag",
        )
        if self.cross_process:
            from photon_ml_tpu.parallel.multihost import allreduce_sum_host

            diag = jnp.asarray(allreduce_sum_host(np.asarray(diag)))
        return diag + (
            jnp.float32(self.l2_weight) * self.reg_mask
            * self._reg_curvature(diag)
        )

    # d-bound on the streamed FULL Hessian: the (d, d) f32 accumulator is
    # d²·4 bytes ON DEVICE for the whole pass (8192 → 256 MB) and the host
    # inverts it afterwards — FULL variance is a small-to-mid-d feature in
    # the reference too (it inverts d×d on the driver)
    FULL_HESSIAN_MAX_D = 8192

    def hessian(self, w: Array) -> Array:
        """Full (d, d) Hessian at ``w``, streamed — FULL variance at the
        solution is ONE extra pass accumulating the per-chunk d×d Gram
        contractions (Σ Zᵀ(d2·Z), linear in the chunks, exactly like the
        streamed gradient), then a host-side inverse by the caller. The
        d-bound keeps the accumulator a bounded device buffer; beyond it
        FULL is refused eagerly with the limit in the message."""
        if self._fe_range is not None:
            raise NotImplementedError(
                "FULL variance is not supported under feature-range "
                "sharding (PHOTON_FE_SHARD) — the d×d Hessian couples all "
                "ranges; use SIMPLE variances"
            )
        if self._tile_layouts is not None:
            raise NotImplementedError(
                "FULL variance is not supported with tile-COO streamed "
                "chunks (the raw per-chunk indices are not retained); "
                "build the objective with tile_sparse=False or use SIMPLE"
            )
        if self.num_features > self.FULL_HESSIAN_MAX_D:
            raise NotImplementedError(
                f"streamed FULL variance supports d <= "
                f"{self.FULL_HESSIAN_MAX_D} (the dense d×d Hessian "
                f"accumulator would be {self.num_features}² floats); use "
                f"SIMPLE variances at this width"
            )
        w = jnp.asarray(w)
        init = jnp.zeros(
            (self.num_features, self.num_features), jnp.float32
        )
        h = self._stream(
            w,
            lambda batch, wi: self._chunk_h(batch, wi),
            lambda acc, out: acc + out,
            init,
            devcost_fn=self._chunk_h,
            devcost_label="streaming.chunk_hessian",
        )
        if self.cross_process:
            from photon_ml_tpu.parallel.multihost import allreduce_sum_host

            h = jnp.asarray(allreduce_sum_host(np.asarray(h)))
        return h + jnp.diag(
            jnp.float32(self.l2_weight) * self.reg_mask
            * self._reg_curvature(self.reg_mask)
        )

    def stream_scores(self, w: Array, num_rows: int) -> np.ndarray:
        """Margins (X·w, no offsets) over this objective's chunks, trimmed
        to ``num_rows`` — through the SAME device-resident tile-COO
        layouts the solve used when they exist (the GAME trainer scores
        every coordinate visit; re-running those scores through the XLA
        gather path forfeited the kernel the visit just trained on), else
        the plain per-chunk matvec.

        Under feature-range sharding ``w`` is the LOCAL range segment and
        the returned scores are the COMBINED full margins (identical on
        every process — the fixed-ascending-range-order reduction)."""
        if not self.chunks:
            return np.zeros(num_rows, np.float32)
        if self._fe_range is not None:
            m = self._fe_combine_margins((jnp.asarray(w),))
            return np.asarray(m[0])[:num_rows]
        w = jnp.asarray(w)
        from photon_ml_tpu.ops import prefetch

        depth = prefetch.prefetch_depth()
        # the one module-level scoring program (shared with the module
        # scorer below): objectives are rebuilt per GAME fit / per sweep,
        # and a per-objective jit would re-compile scoring on every
        # rebuild instead of re-entering the process-wide cache
        if depth <= 0:
            # raw (un-tiled) chunks score at the ladder's transfer dtype,
            # like the streamed objective's depth-0 path; tiled chunks
            # only consume labels/offsets/weights here (identity pack)
            pack = (
                (lambda c: c)
                if self._tile_layouts is not None
                else prefetch.pack_host_chunk
            )
            outs = [
                np.asarray(_score_matvec(self._chunk_batch(pack(c), i), w))
                for i, c in enumerate(self.chunks)
            ]
            return np.concatenate(outs)[:num_rows]

        from photon_ml_tpu.ops import stream_executor

        if stream_executor.stream_executor_enabled():

            def prepare_x(i):
                c = self.chunks[i]
                if self._tile_layouts is not None:
                    c = {k: c[k] for k in ("labels", "offsets", "weights")}
                return self._chunk_batch(
                    stream_executor.cached_device_put("scores", c), i
                )

            outs = [
                np.asarray(_score_matvec(b, w))
                for b in stream_executor.stream(
                    "scores", len(self.chunks), prepare_x, depth
                )
            ]
            return np.concatenate(outs)[:num_rows]

        def prepare(i):
            # stage through the device-resident chunk cache: per-visit
            # GAME scoring re-transfers only the columns that changed
            c = self.chunks[i]
            if self._tile_layouts is not None:
                c = {k: c[k] for k in ("labels", "offsets", "weights")}
            return self._chunk_batch(prefetch.cached_device_put(c), i)

        outs = [
            np.asarray(_score_matvec(b, w))
            for b in prefetch.prefetch_iter(len(self.chunks), prepare, depth)
        ]
        return np.concatenate(outs)[:num_rows]

    def value_and_grad(self, w: Array) -> tuple[Array, Array]:
        if self._fe_range is not None:
            return self._fe_value_and_grad(w)
        w = jnp.asarray(w)
        init = (jnp.float32(0.0), jnp.zeros((self.num_features,), jnp.float32))
        v, g = self._stream(
            w, self._chunk_vg,
            lambda acc, out: (acc[0] + out[0], acc[1] + out[1]),
            init,
            devcost_fn=self._chunk_vg,
            devcost_label="streaming.chunk_value_grad",
        )
        if self.cross_process:
            from photon_ml_tpu.parallel.multihost import allreduce_sum_host

            v, g = allreduce_sum_host(np.asarray(v), np.asarray(g))
            v, g = jnp.asarray(v), jnp.asarray(g)
        g = g + jnp.float32(self.l2_weight) * self.reg_mask * self._reg_delta(w)
        return v + self._l2_term(w), g

    # -- feature-range-sharded consumers (PHOTON_FE_SHARD) -------------------
    # Every evaluation is two streamed passes: phase A computes the
    # range-local partial matvec(s) and ONE fixed-ascending-range-order
    # reduction assembles the full margins (identical bits everywhere);
    # phase B derives the contract from the combined margins. The data
    # value is a full-data sum every process computes identically (no
    # second collective); gradient/curvature contractions are DISJOINT
    # range segments — the local slice IS this process's result, exact by
    # construction (pure concatenation reassembles the full vector, no
    # combine arithmetic at all). The regularizer terms are elementwise
    # over local slices of mask/priors, equally exact; only the L2 VALUE
    # scalar crosses the wire, piggybacked on the phase-A reduction.

    def _fe_value(self, w: Array) -> Array:
        w = jnp.asarray(w)
        m, l2 = self._fe_combine_margins((w,), l2_w=w)
        total = self._stream(
            None, self._fe_k_v, lambda acc, v: acc + v, jnp.float32(0.0),
            devcost_fn=self._fe_k_v,
            devcost_label="streaming.fe_chunk_value",
            params_for=lambda i: (m, jnp.int32(i)),
        )
        return total + l2

    def _fe_value_and_grad(self, w: Array) -> tuple[Array, Array]:
        w = jnp.asarray(w)
        m, l2 = self._fe_combine_margins((w,), l2_w=w)
        init = (jnp.float32(0.0), jnp.zeros((self._fe_dim,), jnp.float32))
        v, g = self._stream(
            None, self._fe_k_vg,
            lambda acc, out: (acc[0] + out[0], acc[1] + out[1]), init,
            devcost_fn=self._fe_k_vg,
            devcost_label="streaming.fe_chunk_value_grad",
            params_for=lambda i: (m, jnp.int32(i)),
        )
        g = g + jnp.float32(self.l2_weight) * self.reg_mask * self._reg_delta(w)
        return v + l2, g

    def _fe_hvp(self, w: Array, v: Array) -> Array:
        # BOTH partial matvecs (margins of w, direction image of v) stack
        # into one phase-A stream and one reduction
        w = jnp.asarray(w)
        v = jnp.asarray(v)
        m2 = self._fe_combine_margins((w, v))
        hv = self._stream(
            None, self._fe_k_hvp, lambda acc, out: acc + out,
            jnp.zeros((self._fe_dim,), jnp.float32),
            devcost_fn=self._fe_k_hvp,
            devcost_label="streaming.fe_chunk_hvp",
            params_for=lambda i: (m2, jnp.int32(i)),
        )
        return hv + (
            jnp.float32(self.l2_weight) * self.reg_mask
            * self._reg_curvature(v) * v
        )

    def _fe_hessian_diag(self, w: Array) -> Array:
        w = jnp.asarray(w)
        m = self._fe_combine_margins((w,))
        diag = self._stream(
            None, self._fe_k_hd, lambda acc, out: acc + out,
            jnp.zeros((self._fe_dim,), jnp.float32),
            devcost_fn=self._fe_k_hd,
            devcost_label="streaming.fe_chunk_hessian_diag",
            params_for=lambda i: (m, jnp.int32(i)),
        )
        return diag + (
            jnp.float32(self.l2_weight) * self.reg_mask
            * self._reg_curvature(diag)
        )


@functools.partial(jax.jit, static_argnames=("constants",))
def _score_matvec_keyed(b, wi, constants):
    return b.matvec(wi)


def _score_matvec(b, wi):
    """The one scoring program, re-entered across objectives/visits. The
    tuned kernel constants ride along as a STATIC key: a nested jit's
    statics are resolved at the OUTER trace, so without this a retune
    that reshapes nothing (GROUPS_PER_STEP 32 / SEGMENTS_PER_DMA 4 to
    16 / 8) would silently re-enter the stale executable — the same
    never-by-luck rule as ``_tiled_apply`` itself. Analytic cost capture
    shadows the same key (constants are part of the signature), so a
    fresh scoring executable's flops/bytes land in telemetry once."""
    from photon_ml_tpu.obs import devcost
    from photon_ml_tpu.ops import tile_cache

    constants = tile_cache.tuned_constants()
    devcost.capture(
        "streaming.score_matvec", _score_matvec_keyed, (b, wi),
        {"constants": constants},
    )
    return _score_matvec_keyed(b, wi, constants=constants)


# bounded storage-identity memo for chunk structure fingerprints: the
# per-visit GAME scorer passes fresh chunk DICTS over unchanged storage,
# and re-hashing every chunk's full index/value bytes per visit costs
# O(data) host sha256 just to look up an already-cached layout. Entries
# hold references (that is what makes the data-pointer comparison safe —
# a freed-and-reused address can never alias a live held array).
# Lock-guarded: prefetch workers fingerprint different chunks concurrently.
import threading as _threading

_FP_MEMO: list = []
_FP_MEMO_CAP = 16
_FP_MEMO_LOCK = _threading.Lock()


def _chunk_structure_fingerprint(indices, values) -> tuple:
    from photon_ml_tpu.ops import tile_cache

    same = StreamingGLMObjective._same_storage
    with _FP_MEMO_LOCK:
        for i, (pi, pv, fp) in enumerate(_FP_MEMO):
            if same(indices, pi) and same(values, pv):
                _FP_MEMO.append(_FP_MEMO.pop(i))
                return fp
    fp = tile_cache.structure_fingerprint(indices, values)  # outside the lock
    with _FP_MEMO_LOCK:
        # racing misses for the same chunk both hash; only ONE may insert,
        # or duplicates would consume memo capacity and evict live entries
        for pi, pv, _pf in _FP_MEMO:
            if same(indices, pi) and same(values, pv):
                return fp
        _FP_MEMO.append((indices, values, fp))
        del _FP_MEMO[:-_FP_MEMO_CAP]
    return fp


def stream_scores(
    chunks: Sequence[dict],
    w: np.ndarray,
    num_rows: int,
    num_features: int | None = None,
    tile_sparse: bool | None = None,
) -> np.ndarray:
    """Margins over all chunks (scoring an out-of-core dataset), trimmed to
    the dataset's true ``num_rows`` (the last chunk is padded).

    ``tile_sparse=None`` applies the streamed objective's auto rule: on
    TPU, genuinely high-dimensional sparse chunks score through tile-COO
    layouts from the PROCESS-WIDE cache (``ops/tile_cache``) — per-visit
    GAME validation scoring packs each chunk once and hits the cache every
    visit after, instead of re-running XLA's latency-bound gather."""
    if not chunks:
        return np.zeros(num_rows, np.float32)  # 0-row host shard
    from photon_ml_tpu.ops.sparse_tiled import auto_tile_streaming

    sparse = "indices" in chunks[0]
    from photon_ml_tpu.data.index_map import fe_shard_enabled

    if sparse and num_features is not None and fe_shard_enabled():
        return _stream_scores_fe(
            chunks, w, num_rows, num_features, tile_sparse
        )
    want_tiling = (
        tile_sparse
        if tile_sparse is not None
        else auto_tile_streaming(sparse, num_features)
    )
    w = jnp.asarray(w)

    def prepare(i):
        c = chunks[i]
        if not (want_tiling and sparse):
            # raw chunks score at the ladder's transfer dtype (identity
            # on the f32 rung); tiled chunks keep their f32 values — the
            # layout builder owns their storage-precision conversion
            c = prefetch.pack_host_chunk(c)
        b = _to_batch(c, num_features)
        if want_tiling and sparse:
            from photon_ml_tpu.ops import tile_cache

            # storage-identity memo: per-visit calls pass fresh chunk
            # dicts over unchanged arrays, and a cache HIT must not cost
            # a full re-hash of the chunk's index/value bytes
            shape, h_idx, h_val = _chunk_structure_fingerprint(
                c["indices"], c["values"]
            )
            b = tile_cache.tiled_layout_for(
                b, keep_empty_chunks=True,
                fingerprint=(shape, num_features, h_idx, h_val),
            )
        return b

    from photon_ml_tpu.ops import prefetch, stream_executor

    if stream_executor.stream_executor_enabled():
        # tiled chunks keep the tile_cache prepare verbatim (the layout
        # cache already owns their device residency); raw chunks ride
        # the multi-tenant arbiter so a replay of the training stream's
        # chunk CONTENT re-uses resident buffers
        if want_tiling and sparse:
            prepare_x = prepare
        else:

            def prepare_x(i):
                return _to_batch(
                    stream_executor.cached_device_put("scores", chunks[i]),
                    num_features,
                )

        outs = [
            np.asarray(_score_matvec(b, w))
            for b in stream_executor.stream("scores", len(chunks), prepare_x)
        ]
        return np.concatenate(outs)[:num_rows]

    # background prefetch prepares chunk i+k's batch (fingerprint memo +
    # layout-cache lookup — the host-pack cost) while the device scores
    # chunk i; depth 0 degenerates to the synchronous per-chunk loop.
    # Scoring/readback stays on this thread in chunk order.
    outs = [
        np.asarray(_score_matvec(b, w))
        for b in prefetch.prefetch_iter(len(chunks), prepare)
    ]
    return np.concatenate(outs)[:num_rows]


def _stream_scores_fe(
    chunks: Sequence[dict],
    w: np.ndarray,
    num_rows: int,
    num_features: int,
    tile_sparse: bool | None,
) -> np.ndarray:
    """Module scorer under PHOTON_FE_SHARD: ``w`` is the FULL coefficient
    vector; each process scores its feature range's partial matvec over
    column-restricted chunks and ONE fixed-ascending-range-order reduction
    assembles the full margins (identical on every process). COLLECTIVE —
    every process of the group must call it at the same point. The plan
    re-derives from the chunk nnz histogram (deterministic, the same rule
    the objective used), so scoring hits the layouts the solve packed."""
    from photon_ml_tpu.data.index_map import plan_feature_ranges
    from photon_ml_tpu.parallel.multihost import (
        allreduce_sum_host,
        effective_process_count,
        effective_process_index,
    )
    from photon_ml_tpu.ops import prefetch
    from photon_ml_tpu.ops.sparse_tiled import auto_tile_streaming

    p_count = effective_process_count()
    pid = effective_process_index()
    plan = plan_feature_ranges(
        _fe_nnz_histogram(chunks, num_features), p_count
    )
    lo, hi = plan.range_of(pid)
    restricted, _k = _fe_restrict_chunks(chunks, lo, hi)
    d_local = hi - lo
    fe_range = (pid, lo, hi, p_count)
    want_tiling = (
        tile_sparse
        if tile_sparse is not None
        else auto_tile_streaming(True, num_features)
    )
    w_loc = jnp.asarray(np.asarray(w)[lo:hi])

    def prepare(i):
        c = restricted[i]
        if not want_tiling:
            c = prefetch.pack_host_chunk(c)
        b = _to_batch(c, d_local)
        if want_tiling:
            from photon_ml_tpu.ops import tile_cache

            shape, h_idx, h_val = _chunk_structure_fingerprint(
                c["indices"], c["values"]
            )
            b = tile_cache.tiled_layout_for(
                b, keep_empty_chunks=True,
                fingerprint=(shape, d_local, h_idx, h_val),
                fe_range=fe_range,
            )
        return b

    from photon_ml_tpu.ops import stream_executor

    if stream_executor.stream_executor_enabled():
        if want_tiling:
            prepare_x = prepare
        else:

            def prepare_x(i):
                # fe_range rides the arbiter key: a column-restricted
                # chunk must never alias another range's resident entry
                return _to_batch(
                    stream_executor.cached_device_put(
                        "scores", restricted[i], context=fe_range
                    ),
                    d_local,
                )

        outs = [
            np.asarray(_score_matvec(b, w_loc))
            for b in stream_executor.stream(
                "scores", len(restricted), prepare_x
            )
        ]
    else:
        outs = [
            np.asarray(_score_matvec(b, w_loc))
            for b in prefetch.prefetch_iter(len(restricted), prepare)
        ]
    partial = np.concatenate(outs)
    return np.asarray(allreduce_sum_host(partial))[:num_rows]
