"""Device-resident sample batches for GLM training.

Reference parity: the role of ``photon-api::ml.data.LabeledPoint`` /
``LocalDataset`` (label, features, offset, weight per sample — SURVEY.md
§2.2), redesigned columnar for TPU:

- ``DenseBatch``: features as one ``(n, d)`` matrix — margins and gradient
  contractions are single MXU matmuls. Used when d is modest (after feature
  sharding / projection) or data is naturally dense.
- ``SparseBatch``: features as padded per-row ``(n, k)`` (index, value)
  pairs — the TPU-native CSR replacement (static shapes; XLA cannot tile
  ragged rows). Margins are gathers + row sums; gradients are scatter-adds
  (``.at[].add``) which XLA lowers to sorted segment sums. Padding uses
  index 0 with value 0, which contributes exactly 0 to every contraction,
  so no masking is needed in the kernels.

Both carry ``weights`` that double as the padding row mask (padded rows get
weight 0), so one code path handles ragged data under fixed shapes. The
objective forces zero-weight rows to contribute exactly 0 (``jnp.where``, not
``0 * x``), so padded rows may hold arbitrary — even loss-overflowing —
values without poisoning the sums.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.obs.spans import LAYOUT_OPTIMIZE, LAYOUT_TO_HOST, span, spanned
from photon_ml_tpu.obs.stages import RE_SPARSE_PASS, stage

Array = jnp.ndarray


@partial(jax.tree_util.register_dataclass, data_fields=["X", "labels", "offsets", "weights"], meta_fields=[])
@dataclass(frozen=True)
class DenseBatch:
    """Columnar batch with dense features.

    X: (n, d) float; labels/offsets/weights: (n,) float.
    Padded rows must have weights == 0 (and any finite values elsewhere).
    """

    X: Array
    labels: Array
    offsets: Array
    weights: Array

    @property
    def num_features(self) -> int:
        return self.X.shape[-1]

    @property
    def num_rows(self) -> int:
        return self.X.shape[0]

    def _mm(self, A: Array, v: Array) -> Array:
        """Matmul honoring bf16 storage: when ``X`` is kept bfloat16 (half
        the HBM traffic — the usual bottleneck), feed the MXU bf16 operands
        but accumulate float32; otherwise use plain promotion semantics."""
        if A.dtype == jnp.bfloat16:
            return jnp.matmul(
                A, v.astype(jnp.bfloat16), preferred_element_type=jnp.float32
            )
        return A @ v

    def matvec(self, w: Array) -> Array:
        """Margins X @ w — one MXU matmul."""
        return self._mm(self.X, w)

    def rmatvec(self, r: Array) -> Array:
        """Gradient contraction Xᵀ @ r — one MXU matmul."""
        return self._mm(self.X.T, r)

    def rmatvec_sq(self, r: Array) -> Array:
        """(X ⊙ X)ᵀ @ r — Hessian diagonal: Σ_i r_i x_ij²."""
        return self._mm((self.X * self.X).T, r)

    def value_grad_pass(self, u: Array, c: Array, loss, *, offsets, weights,
                        interpret: bool):
        """(Σ w·l, Xᵀr, Σr) at margins X@u + offsets − c in ONE read of X:
        ``ops/fused.fused_value_grad``, for an objective built ``fused``.
        ``offsets`` / ``weights``: this batch's, or None where the
        objective knows the stream to be all 0 / all 1."""
        from photon_ml_tpu.ops.fused import fused_value_grad

        return fused_value_grad(
            self.X, self.labels, offsets, weights, u, c, loss=loss,
            interpret=interpret,
        )


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["indices", "values", "labels", "offsets", "weights"],
    meta_fields=["num_features"],
)
@dataclass(frozen=True)
class SparseBatch:
    """Columnar batch with padded sparse rows.

    indices: (n, k) int32 feature ids, padded with 0.
    values:  (n, k) float feature values, padded with 0.0.
    num_features: static feature-space dimension d.
    """

    indices: Array
    values: Array
    labels: Array
    offsets: Array
    weights: Array
    num_features: int = field(metadata=dict(static=True))

    @property
    def num_rows(self) -> int:
        return self.indices.shape[0]

    def matvec(self, w: Array) -> Array:
        return jnp.sum(self.values * w[self.indices], axis=-1)

    def rmatvec(self, r: Array) -> Array:
        contrib = self.values * r[:, None]  # (n, k)
        return jnp.zeros((self.num_features,), dtype=contrib.dtype).at[self.indices].add(contrib)

    def rmatvec_sq(self, r: Array) -> Array:
        """Squares every stored value: equal to the matrix's (X ⊙ X)ᵀ r
        unless a row names a column twice (such slots add, so the entry is
        their sum; ``densify`` and the tile-COO build square that)."""
        contrib = self.values * self.values * r[:, None]
        return jnp.zeros((self.num_features,), dtype=contrib.dtype).at[self.indices].add(contrib)


@partial(jax.tree_util.register_dataclass, data_fields=["X", "labels", "offsets", "weights"], meta_fields=[])
@dataclass(frozen=True)
class SubspaceDenseBatch(DenseBatch):
    """One entity's rows densified over its own support (``LocalSparseBatch.
    densified``). The contractions are float32 multiply-reduces, not MXU
    matmuls: a matrix-vector product leaves the MXU idle anyway, a TPU's
    default float32 matmul rounds its operands to bfloat16, and the
    multiply-reduce reads X at the HBM's rate and is exact. Where the
    lane's objective is built ``fused`` (``game/random_effect.
    subspace_one_read``) its value-and-gradient is ``ops/fused``'s
    row-major float32 kernel, as exact and on the VPU too, batched over
    the lanes of a chunk."""

    # value and gradient at every trial point: one read of X a trial
    # through the kernel, where a value alone would cost that same read;
    # on the multiply-reduces a trial value costs one read and a
    # value-and-gradient two, so both at every trial (two reads when the
    # first trial is accepted, as it mostly is) beat value-then-gradient
    # (three)
    one_pass_value_grad = True

    def matvec(self, w: Array) -> Array:
        with stage(RE_SPARSE_PASS):
            return jnp.sum(self.X * w, axis=-1)

    def rmatvec(self, r: Array) -> Array:
        with stage(RE_SPARSE_PASS):
            return jnp.sum(self.X * r[:, None], axis=0)

    def rmatvec_sq(self, r: Array) -> Array:
        with stage(RE_SPARSE_PASS):
            return jnp.sum(self.X * self.X * r[:, None], axis=0)

    def value_grad_pass(self, u: Array, c: Array, loss, *, offsets, weights,
                        interpret: bool):
        return _subspace_value_grad(
            self.X, self.labels, offsets, weights, u, c, loss=loss,
            interpret=interpret,
        )


@partial(jax.jit, static_argnames=("loss", "interpret"))
def _subspace_value_grad(X, labels, offsets, weights, u, c, *, loss, interpret):
    """A subspace lane's one-read value-and-gradient. Under its own ``jit``
    so that L-BFGS's three evaluation sites (the start, a line search's
    first trial, its later ones) share ONE trace and ONE Mosaic lowering of
    a class's kernel: Python does both in every process, compile cache or
    not, and a visit holds a kernel for every (capacity, width) class."""
    from photon_ml_tpu.ops.fused import fused_value_grad

    with stage(RE_SPARSE_PASS):
        return fused_value_grad(
            X, labels, offsets, weights, u, c, loss=loss, interpret=interpret
        )


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["indices", "values", "labels", "offsets", "weights"],
    meta_fields=["num_features"],
)
@dataclass(frozen=True)
class LocalSparseBatch:
    """One entity's padded sparse rows in ITS OWN column subspace (a
    random effect over a sparse shard, ``game/projector.sparse_index_map``).

    indices: (C * nnz,) int32 slots in [0, num_features), row-major: row
    r's nonzeros are [r * nnz, (r + 1) * nnz); values likewise, 0.0 in
    padding. Flat, so that a bucket of lanes is stored (k, C * nnz) with
    the long axis minor: (k, C, nnz) costs 512 B a slot on a TPU, this 8.
    labels/offsets/weights: (C,). num_features: the lane's width P.
    """

    indices: Array
    values: Array
    labels: Array
    offsets: Array
    weights: Array
    num_features: int = field(metadata=dict(static=True))

    def densified(self) -> SubspaceDenseBatch:
        """The (C, P) matrix of this lane: one scatter a solve, after which
        every objective pass reads it at the HBM's rate (XLA's elementwise
        gathers run at 1.5e8 elements a second on a v5e, PERF.md)."""
        capacity = self.labels.shape[-1]
        slots = self.indices.shape[-1]
        rows = jnp.arange(slots, dtype=jnp.int32) // (slots // capacity)
        with stage(RE_SPARSE_PASS):
            X = jnp.zeros((capacity, self.num_features), self.values.dtype)
            X = X.at[rows, self.indices].add(self.values)
        return SubspaceDenseBatch(
            X=X, labels=self.labels, offsets=self.offsets, weights=self.weights
        )


Batch = DenseBatch | SparseBatch | LocalSparseBatch


def dense_batch_from_numpy(
    X: np.ndarray,
    labels: np.ndarray,
    offsets: np.ndarray | None = None,
    weights: np.ndarray | None = None,
    dtype=jnp.float32,
) -> DenseBatch:
    n = X.shape[0]
    return DenseBatch(
        X=jnp.asarray(X, dtype=dtype),
        labels=jnp.asarray(labels, dtype=dtype),
        offsets=jnp.zeros((n,), dtype) if offsets is None else jnp.asarray(offsets, dtype),
        weights=jnp.ones((n,), dtype) if weights is None else jnp.asarray(weights, dtype),
    )


def densify(batch: SparseBatch, dtype=jnp.float32) -> DenseBatch:
    """One-time scatter of a ``SparseBatch`` into a dense ``(n, d)`` matrix.

    TPU-first rationale: XLA's vector gather/scatter runs at ~10⁸ elem/s on
    TPU regardless of table size (no SparseCore path in vanilla XLA), so a
    sparse solve pays that latency-bound cost on EVERY objective pass. The
    dense layout pays one scatter at ingest and then every pass is an MXU
    matmul at HBM bandwidth — orders of magnitude faster whenever ``n·d``
    fits the memory budget. ``dtype=bfloat16`` halves the HBM traffic;
    contractions still accumulate in float32 (see ``DenseBatch.matvec``).
    """
    n, k = batch.indices.shape
    d = batch.num_features
    rows = jnp.repeat(jnp.arange(n, dtype=jnp.int32)[:, None], k, axis=1)
    X = jnp.zeros((n, d), dtype).at[rows, batch.indices].add(
        batch.values.astype(dtype)
    )
    return DenseBatch(
        X=X, labels=batch.labels, offsets=batch.offsets, weights=batch.weights
    )


def maybe_densify(
    batch: Batch,
    hbm_budget_bytes: float = 6e9,
    dtype=jnp.float32,
) -> Batch:
    """Densify a sparse batch when the dense matrix fits ``hbm_budget_bytes``
    (leave dense batches and over-budget sparse batches unchanged)."""
    if not isinstance(batch, SparseBatch):
        return batch
    dense_bytes = batch.num_rows * batch.num_features * jnp.dtype(dtype).itemsize
    if dense_bytes > hbm_budget_bytes:
        return batch
    return densify(batch, dtype)


@spanned(LAYOUT_OPTIMIZE)
def optimize_batch_layout(
    batch: Batch,
    hbm_budget_bytes: float = 6e9,
    dtype=jnp.float32,
) -> Batch:
    """The framework's full ingest layout decision for a single-device GLM
    solve: densify when the dense matrix fits the HBM budget (MXU matmuls
    beat everything at modest d), otherwise re-block genuinely
    high-dimensional sparse data into the tile-COO Pallas layout
    (``ops/sparse_tiled.py`` — ~9x over the XLA gather/scatter path), and
    leave everything else unchanged.

    Between the two lies the hybrid the tile-COO build chooses when it is
    handed the budget, as here (``sparse_tiled.tile_sparse_batch``): columns
    filled in more than ``sparse_tiled.HEAD_MIN_FILL`` of the rows, where a
    stored nonzero costs the kernels more than a dense float32 column costs
    in bytes, become a dense head, in blocks of 128 by descending count, as
    far as head, tail, the tail's relayout copy and the input batch fit
    ``hbm_budget_bytes``; the kernels then scatter the tail only. A matrix
    without popular columns is tiled whole, as before. Either way a row's
    repeated draws of a column are merged into one entry, and each chunk's
    streams take the form its cells' occupancy asks for
    (``sparse_tiled._cell_form``): whole runs where the 1,024 x 1,024 cells
    are full, granules of 16 slots with eight source slabs a group where
    they are near empty, as at 10^6 columns. Nothing here is a knob: the
    build reads the column counts, the cell counts and the budget."""
    out = maybe_densify(batch, hbm_budget_bytes, dtype)
    if isinstance(out, SparseBatch):
        from photon_ml_tpu.ops import tile_cache
        from photon_ml_tpu.ops.sparse_tiled import supports_tiling

        # the gate looks at the values, which brings them to the host: the
        # first of the transfers the build needs
        with span(LAYOUT_TO_HOST):
            tiled = supports_tiling(out)
        if tiled:
            # process-wide layout cache: identical sparsity structure
            # (re-ingested data, repeated fits) never re-packs
            return tile_cache.tiled_layout_for(
                out, hbm_budget_bytes=float(hbm_budget_bytes)
            )
    return out


def pad_batch(batch: Batch, target_rows: int) -> Batch:
    """Pad a batch to ``target_rows`` rows with zero-weight rows (static-shape
    requirement for sharding: row count must divide the data axis)."""
    n = batch.num_rows
    if n == target_rows:
        return batch
    if n > target_rows:
        raise ValueError(f"batch has {n} rows > target {target_rows}")
    pad = target_rows - n
    pad1 = lambda a: jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
    if isinstance(batch, DenseBatch):
        return DenseBatch(
            X=pad1(batch.X),
            labels=pad1(batch.labels),
            offsets=pad1(batch.offsets),
            weights=pad1(batch.weights),
        )
    return SparseBatch(
        indices=pad1(batch.indices),
        values=pad1(batch.values),
        labels=pad1(batch.labels),
        offsets=pad1(batch.offsets),
        weights=pad1(batch.weights),
        num_features=batch.num_features,
    )
