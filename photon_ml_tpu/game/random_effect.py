"""Batched per-entity random-effect training.

Reference parity (SURVEY.md §2.2, §3.1 HOT LOOP 2): the reference's
``RandomEffectCoordinate.trainModel`` runs ``activeData.mapValues { localDataset
=> SingleNodeOptimizationProblem.run }`` — millions of serial Breeze solves
inside Spark executors after a group-by-entity shuffle.

TPU-native redesign (SURVEY.md §7): entities are padded into fixed-capacity
buckets at ingest (``game.data``); each bucket's solves run as ONE
``vmap``-batched device kernel — the per-entity L-BFGS/OWL-QN/TRON
``lax.while_loop`` is *batched over entities*, so the MXU sees (k, C, d)
matmuls instead of k tiny (C, d) ones, and per-entity convergence is just
the batched loop's per-lane ``done`` mask. Entity lanes shard over the mesh
axis with zero communication (the problems are independent — the reference
exploits the same structure with its partitioner; here the "partitioner" is
a sharding annotation).
"""

from __future__ import annotations

import dataclasses
import os
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.config import OptimizerConfig
from photon_ml_tpu.game.data import (
    EntityBuckets,
    Features,
    DenseFeatures,
    NonzeroMajorSparseFeatures,
    SparseFeatures,
    class_buckets_by_width,
    gather_bucket_host,
    one_process_mesh,
)
from photon_ml_tpu.obs.stages import RE_OFFSETS, RE_SOLVE, RE_SUBSPACE, stage
from photon_ml_tpu.ops import fused as kernels
from photon_ml_tpu.ops.batch import Batch, DenseBatch, LocalSparseBatch
from photon_ml_tpu.ops.glm import fused_for_shape, make_objective
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.optim.common import (
    hash_expand_coefficients,
    hash_expand_variances,
    hash_fold_prior,
    hash_fold_warm_start,
    select_minimize_fn,
)
from photon_ml_tpu.types import VarianceComputationType

Array = jnp.ndarray


def _captured_jit_call(label, fn, *args, **kwargs):
    """Invoke a jitted bucket-solve boundary with analytic cost capture
    (``obs/devcost``). The solver entry points themselves only ever run
    INSIDE these jits (vmapped over the entity lane), where capture's
    tracer check skips — so THIS is where the RE solve's executable cost
    is captured, once per (knob tuple, bucket geometry). Under a
    fused-visit trace the args are tracers and capture skips itself."""
    from photon_ml_tpu.obs import devcost

    devcost.capture(label, fn, args, kwargs)
    return fn(*args, **kwargs)

# Convergence-aware bucket-solve knobs (the knob idiom: the env var
# wins over the module global, both read at CALL time so a job's
# environment and tests set them without import-order games).
#
# COMPACT_EVERY > 0 runs each bucket's batched while_loop in chunks of
# that many outer iterations; between chunks the per-lane done mask is
# snapshotted on host and the still-active entities are gathered into a
# dense front (pow2-rounded so the recompile count stays O(log k)), so
# retired lanes stop burning device iterations. 0 (default) = today's
# single-launch schedule bit-for-bit. FUSE_BUCKETS = 1 concatenates
# same-(C, d)-geometry buckets into one launch (amortized dispatch, and
# a wider front for compaction to keep MXU-shaped as lanes retire).
# Both transforms leave per-entity math untouched: results are BITWISE
# identical to the knob-off run (asserted in tests/test_re_compaction.py).
COMPACT_EVERY = 0  # outer iterations per chunk; 0 = single launch
FUSE_BUCKETS = 0  # 1 = fuse same-geometry buckets into one launch
# Cross-process combine transport for the owned-bucket schedule
# (PHOTON_RE_SHARD=1 under a mesh): "allreduce" (default) is the dense
# fixed-layout allgather — every process ships the whole (Σ lanes, d)
# buffer, O(P·E·d)/visit; "segments" ships only each owner's packed
# coefficient/variance/diagnostic segments over the framed-P2P ring,
# O(E·d)/visit, bitwise identical results (asserted on the gloo
# harness). The perf knob for the million-entity scale wall.
RE_COMBINE = "allreduce"
_RE_COMBINE_MODES = ("allreduce", "segments")


def compact_every() -> int:
    """``PHOTON_RE_COMPACT_EVERY`` (env > module global), 0 = off."""
    env = os.environ.get("PHOTON_RE_COMPACT_EVERY")
    if env is not None and env != "":
        return max(int(env), 0)
    return max(int(COMPACT_EVERY), 0)


def re_combine_mode() -> str:
    """``PHOTON_RE_COMBINE`` (env > module global), strict parse naming
    the valid modes — a typo fails loudly instead of silently benching
    the dense path (same discipline as PHOTON_KERNEL_DTYPE)."""
    env = os.environ.get("PHOTON_RE_COMBINE")
    mode = env if (env is not None and env != "") else str(RE_COMBINE)
    if mode not in _RE_COMBINE_MODES:
        raise ValueError(
            f"PHOTON_RE_COMBINE must be one of {_RE_COMBINE_MODES}, "
            f"got {mode!r}"
        )
    return mode


def fuse_buckets() -> bool:
    """``PHOTON_RE_FUSE_BUCKETS`` (env > module global)."""
    env = os.environ.get("PHOTON_RE_FUSE_BUCKETS")
    if env is not None and env != "":
        return int(env) != 0
    return int(FUSE_BUCKETS) != 0


def _iter_accounting_enabled() -> bool:
    """Whether single-launch solves read back per-lane iteration counts
    for the ``re_solve.*`` executed/useful counters. That readback is a
    host sync the deferred-diagnostics design otherwise avoids, so it is
    opt-in: on when a telemetry sink is active (observability runs accept
    the sync) or when ``PHOTON_RE_ITER_ACCOUNTING=1``;
    ``=0`` forces it off. The compacted path always counts — it syncs
    the done mask between chunks anyway."""
    env = os.environ.get("PHOTON_RE_ITER_ACCOUNTING")
    if env is not None and env != "":
        return int(env) != 0  # same strict parse as the sibling knobs
    from photon_ml_tpu.obs import sink

    return sink.is_active()


def _account_single_launch_host(it: np.ndarray, lanes: int) -> None:
    """Registry update for one single-launch bucket solve from already-
    materialized per-lane iteration counts: every lane executes the batched
    loop until the SLOWEST lane converges, so executed = lanes × max(it)
    and useful = Σ it."""
    from photon_ml_tpu.obs.metrics import REGISTRY

    it = np.asarray(it).astype(np.int64)
    trips = int(it.max()) if it.size else 0
    executed = trips * int(lanes)
    REGISTRY.counter_inc("re_solve.executed_entity_iterations", float(executed))
    REGISTRY.counter_inc("re_solve.useful_entity_iterations", float(it.sum()))
    if executed:
        REGISTRY.gauge_set(
            "re_solve.active_lane_fraction", float(it.sum()) / float(executed)
        )


def _account_single_launch(it_lane: Array, lanes: int) -> None:
    """Inline (blocking) accounting for one single-launch bucket solve —
    a one-shot defer-and-flush so the gating rules (launch counter,
    opt-in check, multihost-addressability skip) live in exactly one
    place, ``_DeferredLaunchAccounting.add``."""
    acct = _DeferredLaunchAccounting()
    acct.add(it_lane, lanes)
    acct.flush()


class _DeferredLaunchAccounting:
    """Single-launch accounting that never syncs inside an enqueue loop.

    ``add`` bumps the launch counter immediately (no readback) and stashes
    the per-lane iteration array; ``flush`` fetches every stashed array in
    ONE ``jax.device_get`` — by flush time the caller has already blocked
    on the final solve, so the fetch costs one round-trip of tiny arrays
    instead of a per-bucket pipeline stall (the dispatch loops' no-host-
    sync-between-buckets invariant holds even with a telemetry sink on)."""

    def __init__(self) -> None:
        self._pending: list[tuple[Array, int]] = []

    def add(self, it_lane: Array, lanes: int) -> None:
        from photon_ml_tpu.obs.metrics import REGISTRY

        REGISTRY.counter_inc("re_solve.launches")
        if not _iter_accounting_enabled():
            return
        if isinstance(it_lane, jax.Array) and not it_lane.is_fully_addressable:
            return  # multihost shard: per-process accounting double counts
        self._pending.append((it_lane, int(lanes)))

    def flush(self) -> None:
        if not self._pending:
            return
        its = jax.device_get([it for it, _ in self._pending])
        for it, (_, lanes) in zip(its, self._pending):
            _account_single_launch_host(it, lanes)
        self._pending.clear()


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


@dataclass(frozen=True)
class RandomEffectTrainingResult:
    """Per-entity models as one (E, d) coefficient matrix.

    The reference keeps ``RDD[(REId, GeneralizedLinearModel)]``; here the
    whole random-effect model is a single device matrix (plus optional
    variances), gathered per sample at scoring time. Entities with no active
    data keep their warm-start row (zeros for a cold start).

    Per-entity diagnostics are LAZY: the bucket solves leave their
    (loss, iterations, reason) outputs on device, and ``loss_values`` /
    ``iterations`` / ``converged`` materialize them on first access. A
    coordinate-descent visit that nobody inspects therefore enqueues with
    ZERO host syncs — on dispatch-latency-dominated platforms (remote-
    attached chips) the per-visit readback was the wall-clock floor
    (VERDICT r2 weak #2/#4: GAME configs dispatch-dominated)."""

    coefficients: Array  # (E, d)
    variances: Array | None  # (E, d) when SIMPLE variance is requested
    # (ent_ids, loss, iterations, reason) device refs per bucket
    diag_refs: tuple = ()
    num_entities: int = 0

    def _materialize(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        cached = self.__dict__.get("_diag_cache")
        if cached is None:
            if self.__dict__.get("_released"):
                raise RuntimeError(
                    "per-entity diagnostics were released for this "
                    "iteration's tracker (coordinate descent keeps them "
                    "only for each coordinate's LATEST visit to bound HBM "
                    "retention); read tracker.loss_values before the next "
                    "visit if you need per-iteration history"
                )
            loss_values = np.full((self.num_entities,), np.nan, np.float64)
            iterations = np.zeros((self.num_entities,), np.int64)
            converged = np.zeros((self.num_entities,), bool)
            # ALL buckets' refs fetch in ONE jax.device_get of the nested
            # list (one transfer round-trip instead of 3 serial pulls per
            # bucket); only non-fully-addressable (multihost) arrays fall
            # back to the per-array allgather path
            refs = [(f_b, it_b, r_b) for _, f_b, it_b, r_b in self.diag_refs]
            if any(
                isinstance(x, jax.Array) and not x.is_fully_addressable
                for t in refs for x in t
            ):
                # multihost (lane-sharded mesh) refs: ONE framed-P2P
                # segment allgather for every non-addressable array
                # instead of one process_allgather per array (3 jax
                # collectives per bucket, previously)
                host = _gather_refs_host(refs)
            else:
                host = jax.device_get(refs)
            for (ent_ids, *_), (f_h, it_h, reason_h) in zip(self.diag_refs, host):
                loss_values[ent_ids] = np.asarray(f_h).astype(np.float64)
                iterations[ent_ids] = np.asarray(it_h)
                converged[ent_ids] = np.asarray(reason_h) != 0  # != MAX_ITERATIONS
            cached = (loss_values, iterations, converged)
            object.__setattr__(self, "_diag_cache", cached)
        return cached

    @property
    def loss_values(self) -> np.ndarray:
        """(E,) final per-entity objective (NaN if untrained)."""
        return self._materialize()[0]

    @property
    def iterations(self) -> np.ndarray:
        """(E,) int solver iterations (0 if untrained)."""
        return self._materialize()[1]

    @property
    def converged(self) -> np.ndarray:
        """(E,) bool per-entity convergence."""
        return self._materialize()[2]

    def release_device_diagnostics(self) -> None:
        """Drop the device refs WITHOUT materializing (a host transfer here
        would stall the async enqueue pipeline — measured 20x in the
        round-5 bench, where each host sync cost 0.1 s or more). Coordinate descent calls this on the previous iteration's
        tracker when a coordinate is revisited, so HBM retention is bounded
        to the latest visit's O(E) diagnostic buffers regardless of
        iteration count; older visits' per-entity diagnostics become
        unavailable (reading them afterwards raises). Already-materialized
        values stay readable. Also drops this tracker's reference to the
        (E, d) coefficient/variance buffers (the MODEL keeps its own)."""
        object.__setattr__(self, "_released", True)
        object.__setattr__(self, "diag_refs", ())
        object.__setattr__(self, "coefficients", None)
        object.__setattr__(self, "variances", None)


def _pad_rows(k: int, n_dev: int) -> int:
    return -(-k // n_dev) * n_dev


@dataclass(frozen=True)
class PreparedBucket:
    """One bucket's device-resident static tensors, built ONCE at coordinate
    construction. Coordinate descent changes only the offsets, so ``train``
    gathers fresh offsets on device and re-enters the compiled solver — no
    host round-trip of features/labels/weights per iteration.

    ``columns`` (set when per-entity subspace projection is active) holds
    each entity's selected feature columns (k_pad, p); the static features
    are already gathered to that width, and solutions scatter back through
    it into the full (E, d) matrix."""

    entity_ids: np.ndarray  # (k,) original entity ids (host)
    ids: Array | None  # (k,) the same ids staged to device (W scatter key)
    static: Batch | None  # (k_pad, C, …) features/labels/weights
    # where the bucket's lanes sit in the offsets ``_bucket_offsets`` reads:
    # (k_pad,) int32 run starts, lane i holding the C entries from s_i. Into
    # the residual itself where ``order`` is None (every lane of the effect
    # is a run of the file's rows), else into the effect's ordered copy
    # ``offsets[order]``. ``prepare_buckets`` stages no other form; the
    # (k_pad, C) slot indices ``_bucket_offsets`` also reads are the plain
    # reading that tests hold these two against
    row_idx: Array | None
    mask: Array | None  # (k_pad, C) 1.0 where the slot holds a real sample
    num_real: int  # k (before device-count padding)
    columns: Array | None = None  # (k_pad, p) int32 per-entity column map
    # owning PROCESS under entity-sharded placement (PHOTON_RE_SHARD=1
    # with a mesh): this whole bucket solves on exactly one process and
    # the others receive its results through the post-loop combine.
    # None = the classic replicated/lane-sharded schedule. Buckets owned
    # ELSEWHERE keep host bookkeeping only — ids/static/row_idx/mask are
    # None (never gathered, never uploaded; the dispatch loop skips them
    # and the combine fills their results in).
    owner: int | None = None
    # index of this bucket's PARENT in the pre-split bucket list when
    # the PHOTON_RE_SPLIT rule produced sub-bucket placement atoms
    # (set for EVERY bucket of a split prep, split or not). None = an
    # unsplit prep (the bit-for-bit knob-off schedule). Within an
    # owner, same-parent sub-buckets re-concatenate into one launch
    # (``_parent_units``) so the launch geometry the unsplit run used
    # is restored wherever co-ownership allows.
    parent: int | None = None
    # owning LOCAL DEVICE ordinal under device-granularity placement
    # (PHOTON_RE_DEVICE_SPLIT=1, the second LPT level): this bucket's
    # staged tensors are committed to jax.local_devices()[device], its
    # solves thread through that device's (E, d) coefficient copy, and
    # a device-local combine folds its rows back before the process
    # combine. None = the single-unit-per-process schedule (knob off,
    # single-device host, or a bucket owned elsewhere).
    device: int | None = None
    # capacity-class projection spec (PHOTON_RE_PROJECT, host metadata:
    # game.projector.ClassProjection). Set on EVERY bucket of a
    # projected prep — including remotely-owned ones, whose spec the
    # owner-segment combine needs to reconstruct full-width rows from
    # the d_e-wide payload. None = the full-width (bitwise knob-off)
    # path for this bucket, either because the knob is off or because
    # the class's support is the full feature set.
    project: Any = None
    # the signed hash fold (PHOTON_RE_PROJECT=hash) as a staged (d_e, m)
    # device matrix — set only on locally-staged buckets whose class
    # folds (support wider than PHOTON_RE_PROJECT_DIM). The static
    # features are already folded to width m at prepare time; the
    # bucket step folds warm starts/priors through it and expands the
    # solved coefficients/variances back to the support before the
    # column scatter.
    hash_S: Array | None = None
    # the effect's own order (``_effect_order``), ONE int32 array that every
    # staged bucket of the effect shares: the rows its lanes hold, class by
    # class and lane by lane, behind a leading 0. A visit gathers
    # ``offsets[order]`` once and every class slices that. None where the
    # residual's own order already serves (``row_idx``)
    order: Array | None = None


def prepare_buckets(
    features: Features,
    labels: np.ndarray,
    weights: np.ndarray,
    buckets: EntityBuckets,
    mesh: Mesh | None = None,
    axis_name: str = "data",
    features_to_samples_ratio: float | None = None,
    intercept_index: int | None = None,
) -> list[PreparedBucket]:
    """Gather every bucket's static tensors to device (padding the entity
    lane to divide the mesh axis, and sharding over it when given).

    ``features_to_samples_ratio`` activates per-entity subspace projection
    (parity: ``numFeaturesToSamplesRatioUpperBound`` + ``IndexMapProjection``,
    SURVEY.md §2.2): each bucket solves at width
    p = min(d, ceil(ratio · capacity)) over each entity's most-frequent
    columns. Dense features only.

    A SPARSE shard always trains every entity in the subspace of the
    columns its own rows touch (plus the intercept), through the same
    column machinery: ``game/projector.sparse_index_map`` builds the
    per-entity maps on the host once, the buckets are classed by width
    rung beside capacity (``class_buckets_by_width``), the bucket tensors
    hold local indices (``LocalSparseBatch``) and solve at the rung's
    width, never the shard's. Exact for L2 at zero; a Gaussian prior with
    means outside an entity's support is not representable there. The
    returned buckets, not ``buckets``, are the classes that solve.

    ``PHOTON_RE_SHARD=1`` with a mesh switches to OWNED-BUCKET prep:
    buckets are staged whole (no entity-lane padding or mesh sharding)
    and a skew-aware placement plan (Σ active rows per bucket, LPT,
    fusion-group-atomic so same-geometry launch fusion keeps working per
    shard) assigns each bucket an owning process. Lanes stay fully
    addressable, which is exactly what lifts the "compaction/fusion gate
    off under mesh sharding" restriction — the PR-5 knobs apply per
    owned bucket.

    ``PHOTON_RE_PROJECT`` (support/hash) derives one projection spec per
    capacity class from the per-class column activity
    (``game.projector.projection_ladder``) and solves every bucket of
    the class in its d_e-wide support subspace through the SAME column
    machinery the ratio knob uses — the in-memory batch is replicated on
    every process, so the activity counts are already fleet-global and
    the ladder is process-count-independent by the same argument as the
    capacity ladder itself. Mutually exclusive with
    ``features_to_samples_ratio`` (two competing column maps); dense
    features only.
    """
    from photon_ml_tpu.game.projector import (
        class_activity,
        projection_ladder,
        re_project_dim,
        re_project_mode,
        subspace_columns,
    )
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.parallel.placement import (
        re_shard_enabled,
        re_split_factor,
        re_split_weight,
        record_projection_metrics,
    )

    project_mode = re_project_mode()
    ladder = None
    if project_mode != "0":
        if features_to_samples_ratio is not None:
            raise ValueError(
                "PHOTON_RE_PROJECT and features_to_samples_ratio are "
                "mutually exclusive (two competing per-entity column maps)"
            )
        if not isinstance(features, DenseFeatures):
            raise ValueError(
                "PHOTON_RE_PROJECT requires dense features (sparse rows "
                "are already width-bounded)"
            )
        classes, activity = class_activity(
            np.asarray(features.X), buckets.capacities, buckets.row_indices
        )
        ladder = projection_ladder(
            classes, activity, features.num_features, project_mode,
            re_project_dim(), intercept_index,
        )

    index_map = None
    if isinstance(features, SparseFeatures):
        index_map, buckets = _sparse_subspace(features, buckets, intercept_index)
        features = SparseFeatures(
            indices=index_map.local, values=np.asarray(features.values),
            num_features=features.num_features,
        )

    owned_prep = mesh is not None and re_shard_enabled()
    n_dev = mesh.shape[axis_name] if (mesh is not None and not owned_prep) else 1
    # owned prep decides placement BEFORE staging, so each process
    # gathers/uploads ONLY its owned buckets — device residency and
    # host→device transfer are O(owned shard), not O(total dataset).
    # Non-owned buckets keep host bookkeeping only (entity ids, lane
    # count, owner) — everything the post-solve combine needs.
    #
    # PHOTON_RE_SPLIT > 0 first refines the placement units below
    # bucket granularity: heavy capacity classes split into sub-bucket
    # atoms (game.data.split_entity_buckets — deterministic on the
    # global bucket contents, identical on every process), so the LPT
    # below can spread the Zipf tail class across owners instead of
    # pinning it whole on one. parents is None on an unsplit prep —
    # the knob-off path is bit-for-bit the pre-split code.
    # projected payload width per bucket (solved width: d_e, or m once
    # hashed), keyed off the capacity class — None when the projection
    # is off so every placement weight below stays bit-for-bit
    def _bucket_dims(bks: EntityBuckets) -> list[float] | None:
        if ladder is None:
            return None
        d_full = float(features.num_features)
        return [
            d_full if (s := ladder.get(int(c))) is None else float(s.dim)
            for c in bks.capacities
        ]

    owners = parents = devices = None
    if owned_prep:
        from photon_ml_tpu.game.data import split_entity_buckets

        buckets, parents, n_split = split_entity_buckets(
            buckets, re_split_factor(), weight=re_split_weight(),
            byte_dims=_bucket_dims(buckets),
        )
        lane_dims = _bucket_dims(buckets)
        owners = _plan_bucket_owners(
            buckets, parents, n_split, lane_dims=lane_dims
        )
        # second placement level (PHOTON_RE_DEVICE_SPLIT): this
        # process's owned buckets onto its LOCAL devices — None when
        # the knob is off or the host has one device (the knob-off
        # staging below is then bit-for-bit the single-level prep)
        devices = _plan_bucket_devices(
            buckets, parents, owners, lane_dims=lane_dims
        )
    # EFFECTIVE identity, not jax's: after an in-place descent degrade
    # the owners above were planned over the survivor group, and this
    # process dispatches under its survivor rank (identical to the jax
    # index on a healthy fleet, so the knob-off path is bit-for-bit)
    from photon_ml_tpu.parallel.multihost import effective_process_index

    own_pid = effective_process_index()
    zeros_off = np.zeros_like(np.asarray(labels))
    # real bucket rows a device of the lane mesh holds, over every class
    mesh_rows = None
    # read from the data, for the effect as a whole: one lane anywhere that
    # is no run of the file's rows and every class reads the ordered copy
    file_order = all(_run_starts(r) is not None for r in buckets.row_indices)
    # per staged bucket: its place in ``prepared``, its (k_pad, C) rows on
    # the host (-1 where a slot holds none), and where its tensors go
    staged: list[tuple[int, np.ndarray, Any]] = []
    prepared: list[PreparedBucket] = []
    for bi, (ent_ids, row_idx) in enumerate(
        zip(buckets.entity_ids, buckets.row_indices)
    ):
        k = len(ent_ids)
        if n_dev > 1 and one_process_mesh(mesh):
            # on one host's mesh (the fused visit's; a mesh that spans
            # processes keeps the id order its drills pin bit for bit):
            # which device solves an entity follows where its rows lie, not
            # the id it bears: a class's lanes in the order of their first
            # rows, DEALT over the devices (lane i of that order to device
            # i mod n). Relabelled entities then keep their device (the
            # devices meet at every exchange, so a visit waits for the
            # slowest, and which is slowest must not turn on a label), and
            # the deal evens what the order would pile up: a file sorted by
            # entity size, or an entity's first row lying the earlier the
            # more rows it has, puts a class's fullest lanes side by side
            order = np.argsort(row_idx[:, 0], kind="stable")
            order = np.concatenate([order[j::n_dev] for j in range(n_dev)])
            ent_ids, row_idx = ent_ids[order], row_idx[order]
        parent = None if parents is None else int(parents[bi])
        spec = None if ladder is None else ladder.get(int(row_idx.shape[1]))
        if owners is not None and owners[bi] != own_pid:
            prepared.append(
                PreparedBucket(
                    entity_ids=ent_ids, ids=None, static=None,
                    row_idx=None, mask=None, num_real=k,
                    owner=int(owners[bi]), parent=parent,
                    project=spec,
                )
            )
            continue
        cols = None
        lane_multiple = n_dev
        if index_map is not None:
            cols = index_map.bucket_columns(ent_ids, buckets.widths[bi])
            if n_dev == 1:
                lane_multiple = subspace_chunk_lanes(
                    row_idx.shape[1], cols.shape[1], k
                )
        # on the host until the end: the class is padded there and each
        # leaf then goes where its lanes belong, a mesh's devices a slice
        # each, so that no device ever holds a whole class
        static = gather_bucket_host(
            features, labels, zeros_off, weights, row_idx, columns=cols
        )
        # every slot is read by a run-start slice: of the residual, or of
        # the ordered copy that ``re_offsets.ordered_rows`` indices gather
        REGISTRY.counter_inc("re_offsets.slots", float(row_idx.size))
        REGISTRY.counter_inc("re_offsets.run_slots", float(row_idx.size))
        mask = (row_idx >= 0).astype(np.float32)
        columns = cols
        hash_S = None
        if spec is not None and isinstance(static, DenseBatch):
            # gather the static features to the class support (the same
            # take-along/columns machinery the ratio knob drives, but one
            # shared column set per capacity class instead of a
            # per-entity top-p), optionally folding through the signed
            # hash to PHOTON_RE_PROJECT_DIM — the solve itself, the
            # zero-then-scatter writeback and the fusion geometry key
            # all run on the projected width from here on
            cols = np.broadcast_to(
                spec.columns, (k, spec.support_dim)
            )  # (k, d_e) — identical rows; intercept (=d-1) at d_e-1
            Xs = np.take_along_axis(static.X, cols[:, None, :], axis=2)  # (k, C, d_e)
            if spec.hash_dim is not None:
                S = spec.hash_matrix()  # (d_e, m) dense signed fold
                Xs = Xs.astype(np.float32) @ S  # (k, C, m)
                hash_S = jnp.asarray(S)
            static = dataclasses.replace(static, X=Xs)
            columns = np.asarray(cols, np.int32)
        if (
            features_to_samples_ratio is not None
            and isinstance(static, DenseBatch)
        ):
            cols = subspace_columns(
                static.X, features_to_samples_ratio, intercept_index,
            )  # (k, p) sorted ascending → intercept (=d-1) lands at p-1
            if cols is not None:
                static = dataclasses.replace(
                    static,
                    X=np.take_along_axis(static.X, cols[:, None, :], axis=2),  # (k, C, p)
                )
                columns = np.asarray(cols, np.int32)
        if lane_multiple > 1:
            k_pad = _pad_rows(k, lane_multiple)
            if k_pad != k:
                pad0 = lambda a: np.concatenate(
                    [a, np.zeros((k_pad - k,) + a.shape[1:], a.dtype)]
                )
                static = jax.tree.map(pad0, static)
                mask = pad0(mask)
                row_idx = np.concatenate(
                    [row_idx, np.full((k_pad - k,) + row_idx.shape[1:], -1, row_idx.dtype)]
                )
                if columns is not None:
                    columns = pad0(columns)
        if n_dev > 1:
            put = partial(jax.device_put, device=NamedSharding(mesh, P(axis_name)))
            per_chip = (mask.reshape(n_dev, -1) != 0).sum(axis=1)
            mesh_rows = per_chip if mesh_rows is None else mesh_rows + per_chip
            REGISTRY.counter_inc("re_mesh.lanes", float(k))
            REGISTRY.counter_inc("re_mesh.padded_lanes", float(len(mask)))
        else:
            put = jnp.asarray
        static = jax.tree.map(put, static)
        mask = put(mask)
        if columns is not None:
            columns = put(columns)
        ids = jnp.asarray(ent_ids, jnp.int32)
        dev = None
        if devices is not None and int(devices[bi]) >= 0:
            # device-granularity staging: commit this owned bucket's
            # tensors to its assigned LOCAL device, so its solves (and
            # their donated (E, d) coefficient copy) run there — the
            # knob-off path never commits, keeping default placement
            dev = int(devices[bi])
            target = jax.local_devices()[dev]
            put = partial(jax.device_put, device=target)
            static = jax.tree.map(put, static)
            mask, ids = put(mask), put(ids)
            if columns is not None:
                columns = put(columns)
            if hash_S is not None:
                hash_S = put(hash_S)
        staged.append((len(prepared), row_idx, put))
        prepared.append(
            PreparedBucket(
                entity_ids=ent_ids,
                ids=ids,
                static=static, row_idx=None, mask=mask,
                num_real=k, columns=columns,
                owner=None if owners is None else int(owners[bi]),
                parent=parent,
                device=dev,
                project=spec,
                hash_S=hash_S,
            )
        )
    rows = [r for _, r, _ in staged]
    own_order = None
    if file_order or not staged:
        starts = [np.maximum(r[:, 0], 0) for r in rows]
    else:
        own_order, starts = _effect_order(rows, n_dev)
        own_order = (
            jax.device_put(own_order, NamedSharding(mesh, P(axis_name)))
            if n_dev > 1 else jnp.asarray(own_order)
        )
    # the indices a visit gathers one by one (a mesh's filler included)
    REGISTRY.counter_inc(
        "re_offsets.ordered_rows", 0.0 if own_order is None else float(own_order.size)
    )
    for (at, _, put), s in zip(staged, starts):
        prepared[at] = dataclasses.replace(
            prepared[at], row_idx=put(np.asarray(s, np.int32)), order=own_order
        )
    if mesh_rows is not None:
        # what cutting every class's lanes by the mesh leaves uneven
        REGISTRY.counter_inc("re_mesh.rows_max_chip", float(mesh_rows.max()))
        REGISTRY.counter_inc("re_mesh.rows_mean_chip", float(mesh_rows.mean()))
    if ladder is not None:
        d_full = int(features.num_features)
        record_projection_metrics(
            [
                (pb.num_real,
                 d_full if pb.project is None else int(pb.project.dim))
                for pb in prepared
            ],
            d_full,
        )
        _emit_re_event(
            "re_project",
            mode=project_mode,
            full_dim=d_full,
            classes=[
                {
                    "capacity": int(c),
                    "support_dim": (
                        d_full if s is None else int(s.support_dim)
                    ),
                    "dim": d_full if s is None else int(s.dim),
                    "hashed": bool(s is not None and s.hash_dim is not None),
                }
                for c, s in sorted(ladder.items())
            ],
        )
    return prepared


def shared_order(prepared: Sequence[PreparedBucket]) -> Array | None:
    """The one ``order`` the staged buckets of an effect share, or None."""
    return next((pb.order for pb in prepared if pb.order is not None), None)


def _run_starts(row_idx: np.ndarray) -> np.ndarray | None:
    """The (k,) first rows of a bucket whose every lane holds one run of
    consecutive rows (slot ``j`` of lane ``i`` holds row ``s_i + j`` wherever it
    holds a row: an input sorted by the effect's id, grouped stably), or
    None where one lane does not. A lane without rows starts at 0. Read
    from the data: an effect with one bucket that has none reads its
    offsets through ``_effect_order``."""
    first = np.maximum(row_idx[:, 0], 0)
    runs = first[:, None] + np.arange(row_idx.shape[1], dtype=row_idx.dtype)
    return first if np.array_equal(row_idx >= 0, row_idx == runs) else None


def _effect_order(
    rows: list[np.ndarray], n_dev: int = 1
) -> tuple[np.ndarray, list[np.ndarray]]:
    """The order in which an effect that is NOT laid out as runs of the file
    reads its residual offsets, from its classes' (k_pad, C) host rows (-1
    in a slot that holds none): ``(order, starts)``. ``order`` is int32 row
    numbers, a device's segment after the other (``n_dev`` of them, equally
    long; a class's lanes are cut over the devices in ``n_dev`` consecutive
    blocks). A segment opens with row 0, so that what a padded slot reads
    (``_bucket_offsets``: the first entry of what it is given) stays the
    residual's own first entry, sign of a zero included; then, class by
    class and lane by lane, the rows a lane holds, slot by slot up to its
    last (a slot without a row before that reads row 0 and is masked); then
    row 0 again up to the longest segment's length. ``starts[b]`` is, lane
    by lane, where class ``b``'s lane begins in ``order`` as a whole (a lane
    without rows: at its segment's start), so ``_bucket_offsets(offsets[order],
    starts[b], mask)`` is ``offsets[rows] * mask`` bit for bit, one index a
    real row in place of one a slot."""
    held = np.ones(n_dev, np.int64)  # a segment's entries so far: the leading 0
    parts: list[list[np.ndarray]] = [[] for _ in range(n_dev)]
    local: list[np.ndarray] = []
    for r in rows:
        cap = r.shape[1]
        real = r >= 0
        length = np.where(real.any(axis=1), cap - np.argmax(real[:, ::-1], axis=1), 0)
        flat = np.maximum(r[np.arange(cap) < length[:, None]], 0).astype(np.int32)
        by_dev = length.reshape(n_dev, -1)
        taken = by_dev.sum(axis=1)
        ahead = np.cumsum(by_dev, axis=1) - by_dev  # of a lane, in its device's block
        local.append(np.where(by_dev > 0, held[:, None] + ahead, 0))
        for part, piece in zip(parts, np.split(flat, np.cumsum(taken)[:-1])):
            part.append(piece)
        held += taken
    order = np.zeros((n_dev, int(held.max())), np.int32)
    for seg, part, n in zip(order, parts, held):
        seg[1:n] = np.concatenate(part)
    base = np.arange(n_dev)[:, None] * order.shape[1]
    return order.reshape(-1), [(s + base).reshape(-1) for s in local]


# Lanes of one (capacity, width) class that are densified and solved at a
# time: the dense (lanes, C, P) float32 block of a chunk stays under this.
_SUBSPACE_CHUNK_BYTES = 256 << 20


def subspace_chunk_lanes(capacity: int, width: int, lanes: int) -> int:
    """How many lanes of a (capacity, width) class one chunk of the
    subspace solve holds: the class in the fewest chunks the budget
    allows, all of one size. A function of the geometry alone, and the
    same for ``lanes`` padded to whole chunks, so that ``prepare_buckets``
    (which pads) and ``_solve_bucket`` agree."""
    most = max(1, _SUBSPACE_CHUNK_BYTES // (4 * capacity * width))
    chunks = -(-lanes // most)
    return -(-lanes // chunks)


def subspace_one_read(capacity: int, width: int, dtype=jnp.float32) -> bool:
    """Whether the lanes of a (capacity, width) subspace class evaluate
    value and gradient in ONE read of their densified matrix, by
    ``ops/fused``'s row-major value-and-gradient kernel batched over the
    lanes of a chunk, or in two by ``SubspaceDenseBatch``'s
    multiply-reduces. A function of what is static under the solve's
    ``vmap`` and nothing else: the backend (a TPU), the kernels' own gate,
    and a tile no longer than the lane, since every lane pays for a whole
    tile: a 64-row lane would contract 128 rows to use half of them, and
    stays on the sweeps."""
    return (
        fused_for_shape(capacity, width, dtype)
        and not kernels.reads_feature_major(width, dtype)
        and kernels.tile_rows(capacity, width, dtype) <= capacity
    )


def _sparse_subspace(
    features: SparseFeatures, buckets: EntityBuckets,
    intercept_index: int | None,
):
    """The per-entity index maps of a sparse shard over the rows its
    buckets train on, and the buckets re-classed by width rung. Counted in
    the registry: ``re_subspace.{entities, support_columns, padded_columns,
    width_classes}``, the timer ``re_subspace.build``, and the float32
    bytes of every class's densified lanes, ``re_subspace.dense_bytes``,
    beside those of the classes whose value-and-gradient reads them once,
    ``re_subspace.one_read_bytes`` (``subspace_one_read``)."""
    from photon_ml_tpu.game.projector import sparse_index_map
    from photon_ml_tpu.obs.metrics import REGISTRY

    t0 = time.perf_counter()
    row_entity = np.full(features.num_rows, -1, np.int64)
    for ents, rows in zip(buckets.entity_ids, buckets.row_indices):
        held = rows >= 0
        row_entity[rows[held]] = np.broadcast_to(ents[:, None], rows.shape)[held]
    num_entities = 1 + max((int(e.max()) for e in buckets.entity_ids), default=-1)
    index_map = sparse_index_map(
        np.asarray(features.indices), np.asarray(features.values), row_entity,
        num_entities, features.num_features, intercept_index,
    )
    buckets = class_buckets_by_width(buckets, index_map.rungs)
    REGISTRY.timer_add("re_subspace.build", time.perf_counter() - t0)
    REGISTRY.counter_inc("re_subspace.entities", float(buckets.num_entities))
    REGISTRY.counter_inc(
        "re_subspace.support_columns", float(index_map.widths.sum())
    )
    REGISTRY.counter_inc(
        "re_subspace.padded_columns", float(index_map.rungs.sum())
    )
    REGISTRY.counter_inc(
        "re_subspace.width_classes", float(len(set(buckets.widths)))
    )
    dense = one_read = 0.0
    for cap, width, ents in zip(
        buckets.capacities, buckets.widths, buckets.entity_ids
    ):
        size = 4.0 * len(ents) * cap * width
        dense += size
        if subspace_one_read(cap, width):
            one_read += size
    REGISTRY.counter_inc("re_subspace.dense_bytes", dense)
    REGISTRY.counter_inc("re_subspace.one_read_bytes", one_read)
    return index_map, buckets


def _plan_bucket_owners(
    buckets: EntityBuckets,
    parents: tuple[int, ...] | None = None,
    split_classes: int = 0,
    lane_dims: "Sequence[float] | None" = None,
) -> np.ndarray:
    """Skew-aware whole-bucket placement over the processes of the
    runtime, decided BEFORE any staging: balance shards by Σ active rows
    (NOT bucket or entity count — Zipf traffic puts most rows behind a
    few head entities), with fusion groups placed atomically (keyed by
    bucket capacity, which determines the geometry pre-staging: the
    subspace width is a deterministic function of capacity, and feature
    type/width are constant within one coordinate — the same sets
    plan_fusion_groups forms at launch time, so every fusable set stays
    co-owned). Deterministic pure-host arithmetic on replicated inputs —
    every process computes the identical plan with no communication.

    ``parents`` marks a PHOTON_RE_SPLIT prep: the bucket list holds
    sub-bucket placement atoms, and each atom places INDEPENDENTLY (the
    capacity-keyed co-ownership grouping would glue a split class right
    back into one unit — the geometry the fusion constraint protects is
    instead restored per owner by ``_parent_units``/``_fusion_units``
    re-concatenation, which is permutation-only and bit-preserving)."""
    from photon_ml_tpu.parallel.multihost import (
        effective_process_count,
        effective_process_index,
    )
    from photon_ml_tpu.parallel.placement import (
        plan_shard_placement,
        re_split_weight,
        record_placement_metrics,
    )

    # the CURRENT group's shape: survivor ranks after an in-place
    # degrade, the jax runtime's processes otherwise (identical then)
    P_ = effective_process_count()
    lanes = [len(e) for e in buckets.entity_ids]
    # PHOTON_RE_SPLIT_WEIGHT selects the balance axis: active rows
    # (default — solve compute) or lane count (combine wire bytes: one
    # segment row per lane regardless of its row count). With a
    # projection ladder the segment row is d_e wide, not d — lane_dims
    # carries the per-bucket width so bytes-mode LPT balances the
    # PROJECTED payload (lane_dims is None on an unprojected prep,
    # keeping the knob-off weights bit-for-bit).
    if re_split_weight() == "bytes":
        if lane_dims is not None:
            rows = [float(k) * float(w) for k, w in zip(lanes, lane_dims)]
        else:
            rows = [float(k) for k in lanes]
    else:
        rows = [
            int(np.sum(np.asarray(r) >= 0)) for r in buckets.row_indices
        ]
    if parents is None:
        keys = [int(r.shape[1]) for r in buckets.row_indices]
        groups = [idxs for idxs, _ in plan_fusion_groups(keys, lanes)]
    else:
        groups = None  # every sub-bucket atom is its own placement unit
    plan = plan_shard_placement(rows, P_, groups=groups)
    record_placement_metrics(
        plan,
        shard=effective_process_index(),
        atoms=len(groups) if groups is not None else len(lanes),
        split_classes=split_classes,
    )
    return plan.owner


def _plan_bucket_devices(
    buckets: EntityBuckets,
    parents: tuple[int, ...] | None,
    owners: np.ndarray,
    lane_dims: "Sequence[float] | None" = None,
) -> np.ndarray | None:
    """The SECOND placement level (``PHOTON_RE_DEVICE_SPLIT``): assign
    THIS process's owned buckets to its local devices with the same
    deterministic LPT rule and the same atomicity contract as the
    process level — fusion groups stay on one device on an unsplit
    prep (so same-device launch fusion reproduces the single-device
    launch geometry exactly) and sub-bucket atoms place independently
    on a split prep (``_parent_units`` re-concatenates per owner AND
    device; every atom is >= 2 lanes, so the lane-count-invariance
    that makes partial co-ownership bitwise covers partial
    co-residency too). Returns local-device ordinals (-1 for buckets
    owned elsewhere), or ``None`` when the knob is off or the host has
    a single local device — the knob-off prep is then bit-for-bit."""
    from photon_ml_tpu.parallel.multihost import effective_process_index
    from photon_ml_tpu.parallel.placement import (
        plan_device_placement,
        re_device_split_enabled,
        re_split_weight,
        record_device_placement_metrics,
    )

    if not re_device_split_enabled():
        return None
    n_dev = jax.local_device_count()
    if n_dev < 2:
        return None
    lanes = [len(e) for e in buckets.entity_ids]
    if re_split_weight() == "bytes":
        if lane_dims is not None:
            rows = [float(k) * float(w) for k, w in zip(lanes, lane_dims)]
        else:
            rows = [float(k) for k in lanes]
    else:
        rows = [
            int(np.sum(np.asarray(r) >= 0)) for r in buckets.row_indices
        ]
    if parents is None:
        keys = [int(r.shape[1]) for r in buckets.row_indices]
        groups = [idxs for idxs, _ in plan_fusion_groups(keys, lanes)]
    else:
        groups = None  # every sub-bucket atom is its own placement unit
    device, plan = plan_device_placement(
        rows, owners, effective_process_index(), n_dev, groups=groups
    )
    record_device_placement_metrics(plan)
    return device


@partial(
    jax.jit,
    static_argnames=(
        "minimize_fn", "loss", "config", "intercept_index", "variance_computation"
    ),
)
def _solve_bucket(
    bucket_batch: Batch,
    w0: Array,  # (k, d)
    l2_weight: Array,
    norm: Any,  # NormalizationContext | None (pytree)
    prior_mu: Array | None,  # (k, d) per-entity Gaussian-prior means
    prior_var: Array | None,  # (k, d) per-entity prior variances
    minimize_fn: Any,
    loss: PointwiseLoss,
    config: OptimizerConfig,
    intercept_index: int | None,
    variance_computation: VarianceComputationType,
    **minimize_kwargs,
):
    """One bucket = one compiled program: vmap the device-resident optimizer
    over the entity lane. Re-entered (not recompiled) every coordinate-descent
    iteration and for every bucket sharing this (C, d) geometry.

    Variances come from ``ops.glm.compute_variances`` — the SAME
    implementation (and numerical guards) as the fixed-effect path, vmapped
    over the entity lane. The returned ``var`` lane holds ready-to-use
    variances (zeros when NONE)."""
    from photon_ml_tpu.ops.glm import compute_variances

    def solve_one(batch: Batch, w0_e: Array, mu_e, var_e):
        obj = _lane_objective(
            batch, loss, l2_weight, norm, intercept_index, mu_e, var_e
        )
        res = minimize_fn(obj, w0_e, config, **minimize_kwargs)
        var = compute_variances(obj, res.w, variance_computation)
        if var is None:
            var = jnp.zeros_like(res.w)
        return res.w, res.value, res.iterations, res.reason, var

    # vmap maps the entity lane of every non-None prior array; None stays
    # None (static absence) across all lanes
    in_axes = (0, 0, None if prior_mu is None else 0,
               None if prior_var is None else 0)
    solve_lanes = jax.vmap(solve_one, in_axes=in_axes)
    with stage(RE_SOLVE):
        if not isinstance(bucket_batch, LocalSparseBatch):
            return solve_lanes(bucket_batch, w0, prior_mu, prior_var)
        # A subspace class is densified and solved a chunk of lanes at a
        # time, so the dense (chunk, C, P) block is the only one live. The
        # optimizer's vector dots at float32: batched over lanes they are
        # matmuls, which a TPU rounds to bfloat16 by default, and at widths
        # of hundreds to thousands that leaves line searches failing at
        # gradients of 5e-3 of their start (my chip runs, PR 27).
        lanes = w0.shape[0]
        chunk = subspace_chunk_lanes(
            bucket_batch.labels.shape[1], bucket_batch.num_features, lanes
        )
        while lanes % chunk:  # lanes not padded to whole chunks (a mesh)
            chunk -= 1
        with jax.default_matmul_precision("highest"):
            if chunk == lanes:
                return solve_lanes(bucket_batch, w0, prior_mu, prior_var)
            split = lambda a: a.reshape((lanes // chunk, chunk) + a.shape[1:])
            out = jax.lax.map(
                lambda args: solve_lanes(*args),
                jax.tree.map(split, (bucket_batch, w0, prior_mu, prior_var)),
            )
        return jax.tree.map(lambda a: a.reshape((lanes,) + a.shape[2:]), out)


# ---------------------------------------------------------------------------
# Convergence-aware lane compaction (PHOTON_RE_COMPACT_EVERY)
# ---------------------------------------------------------------------------
# The single-launch ``_solve_bucket`` runs every lane until the SLOWEST
# entity converges. The compacted twin runs the same batched loop in
# host-driven chunks through the solvers' chunked entry points
# (``optim.common.select_chunked_solver``): after each chunk the per-lane
# done mask is read back, converged lanes' solver state is committed to a
# full-size accumulator in original lane order, and the still-active
# entities (batch tensors, priors, solver state) are gathered into a
# dense pow2-rounded front for the next chunk. Per-lane math is
# untouched — a vmapped while_loop freezes done lanes via select either
# way — so final weights and diagnostics are BITWISE identical to the
# single launch; only the wasted lockstep iterations disappear.


def _lane_objective(batch, loss, l2_weight, norm, intercept_index, mu_e, var_e):
    """One entity lane's objective: the one constructor of
    ``_solve_bucket.solve_one`` and of the chunked init/run/finalize
    programs, so the compacted schedule cannot drift from the single
    launch. It runs under their ``vmap``, where ``batch`` holds tracers:
    whether a subspace lane's value-and-gradient goes through
    ``ops/fused``'s kernel is decided here from the class's static shape
    (``subspace_one_read``); a dense lane leaves it to ``make_objective``,
    which answers no for a tracer."""
    from photon_ml_tpu.ops.glm import GaussianPrior

    prior = None
    if mu_e is not None:
        prior = GaussianPrior(means=mu_e, variances=var_e)
    one_read = None
    if isinstance(batch, LocalSparseBatch):
        one_read = subspace_one_read(
            batch.labels.shape[-1], batch.num_features, batch.values.dtype
        )
        batch = batch.densified()
    return make_objective(
        batch, loss, l2_weight=l2_weight, norm=norm,
        intercept_index=intercept_index, prior=prior, fused=one_read,
    )


def _prior_axes(prior_mu, prior_var):
    return (None if prior_mu is None else 0, None if prior_var is None else 0)


@partial(jax.jit, static_argnames=("init_fn", "loss", "config", "intercept_index"))
def _lanes_init(
    bucket_batch, w0, l2_weight, norm, prior_mu, prior_var, *,
    init_fn, loss, config, intercept_index, **extra,
):
    def one(batch, w0_e, mu_e, var_e):
        obj = _lane_objective(
            batch, loss, l2_weight, norm, intercept_index, mu_e, var_e
        )
        return init_fn(obj, w0_e, config, **extra)

    in_axes = (0, 0) + _prior_axes(prior_mu, prior_var)
    with stage(RE_SOLVE):
        return jax.vmap(one, in_axes=in_axes)(
            bucket_batch, w0, prior_mu, prior_var
        )


@partial(jax.jit, static_argnames=("run_fn", "loss", "config", "intercept_index"))
def _lanes_run(
    bucket_batch, state, it_bound, l2_weight, norm, prior_mu, prior_var, *,
    run_fn, loss, config, intercept_index, **extra,
):
    def one(batch, st, mu_e, var_e):
        obj = _lane_objective(
            batch, loss, l2_weight, norm, intercept_index, mu_e, var_e
        )
        return run_fn(obj, st, config, it_bound, **extra)

    in_axes = (0, 0) + _prior_axes(prior_mu, prior_var)
    with stage(RE_SOLVE):
        return jax.vmap(one, in_axes=in_axes)(
            bucket_batch, state, prior_mu, prior_var
        )


@partial(
    jax.jit,
    static_argnames=(
        "fin_fn", "loss", "config", "intercept_index", "variance_computation"
    ),
)
def _lanes_finalize(
    bucket_batch, state, l2_weight, norm, prior_mu, prior_var, *,
    fin_fn, loss, config, intercept_index, variance_computation, **extra,
):
    from photon_ml_tpu.ops.glm import compute_variances

    def one(batch, st, mu_e, var_e):
        obj = _lane_objective(
            batch, loss, l2_weight, norm, intercept_index, mu_e, var_e
        )
        res = fin_fn(st)
        var = compute_variances(obj, res.w, variance_computation)
        if var is None:
            var = jnp.zeros_like(res.w)
        return res.w, res.value, res.iterations, res.reason, var

    in_axes = (0, 0) + _prior_axes(prior_mu, prior_var)
    with stage(RE_SOLVE):
        return jax.vmap(one, in_axes=in_axes)(
            bucket_batch, state, prior_mu, prior_var
        )


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def _solve_bucket_compacted(
    bucket_batch: Batch,
    w0: Array,
    l2_weight: Array,
    norm: Any,
    prior_mu: Array | None,
    prior_var: Array | None,
    *,
    chunked: Any,  # optim.common.ChunkedSolver
    loss: PointwiseLoss,
    config: OptimizerConfig,
    intercept_index: int | None,
    variance_computation: VarianceComputationType,
    compact_every_n: int,
    **minimize_kwargs,
):
    """Host-driven compacted twin of ``_solve_bucket``: same argument
    shapes, same ``(w, f, it, reason, var)`` output, BITWISE-identical
    values — only the launch schedule differs (init + one launch per
    chunk on a shrinking dense front + finalize, instead of one launch
    total). Requires fully-addressable lanes (no mesh sharding — callers
    gate on ``sharding is None``)."""
    from photon_ml_tpu.obs.metrics import REGISTRY

    k = int(bucket_batch.labels.shape[0])
    T = int(config.max_iterations)
    step = max(int(compact_every_n), 1)
    common = dict(loss=loss, config=config, intercept_index=intercept_index)

    full_state = _captured_jit_call(
        "re_solve.lanes_init",
        _lanes_init,
        bucket_batch, w0, l2_weight, norm, prior_mu, prior_var,
        init_fn=chunked.init, **common, **minimize_kwargs,
    )
    REGISTRY.counter_inc("re_solve.launches")

    state = full_state
    front_batch, front_mu, front_var = bucket_batch, prior_mu, prior_var
    slots = np.arange(k, dtype=np.int64)  # original slot of each REAL front lane
    n_real = k
    compacted = False
    it_prev = np.zeros(k, np.int64)
    executed_total = 0
    useful_total = 0
    bound = 0
    while True:
        bound = min(bound + step, T)
        state = _captured_jit_call(
            "re_solve.lanes_run",
            _lanes_run,
            front_batch, state, jnp.int32(bound), l2_weight, norm,
            front_mu, front_var, run_fn=chunked.run, **common,
            **minimize_kwargs,
        )
        REGISTRY.counter_inc("re_solve.launches")
        # the between-chunk host sync IS the design: the done snapshot
        # buys dropping retired lanes from every later chunk
        done_f, it_f = jax.device_get((state.done, state.it))
        front_lanes = int(np.asarray(done_f).shape[0])  # incl. pow2 padding
        done_f = np.asarray(done_f)[:n_real]
        it_f = np.asarray(it_f)[:n_real].astype(np.int64)
        delta = it_f - it_prev[slots]
        trips = int(delta.max()) if delta.size else 0
        executed_total += trips * front_lanes
        useful_total += int(delta.sum())
        REGISTRY.counter_inc(
            "re_solve.executed_entity_iterations", float(trips * front_lanes)
        )
        REGISTRY.counter_inc(
            "re_solve.useful_entity_iterations", float(delta.sum())
        )
        it_prev[slots] = it_f
        active = np.flatnonzero(~done_f)
        exit_loop = active.size == 0 or bound >= T
        if not exit_loop:
            # prospective packed-front size: pow2 bounds the distinct
            # front shapes — and thus recompiles — at O(log k), capped at
            # the current front so compaction never runs more lanes than
            # the schedule it replaces; never 1 lane for a multi-lane
            # bucket — XLA lowers batch-1 programs down a different
            # (squeezed) path whose per-lane arithmetic is NOT bitwise-
            # stable against the batched lowering (measured on CPU,
            # tests/test_re_compaction.py)
            front_n = _next_pow2(int(active.size))
            if k > 1:
                front_n = max(front_n, 2)
            front_n = min(front_n, front_lanes)
            if front_n == front_lanes:
                # the front cannot shrink (nothing retired, or the pow2
                # rounding lands on the same size): keep it — a re-gather
                # would copy every batch/state tensor just to run the
                # same lane count
                continue
        # commit the front's real lanes back into original slot order —
        # deferred to the chunks that actually read full_state (a shrink
        # gathers from it, the exit finalizes it); done lanes are frozen
        # by the while_loop select, so the deferred scatter commits the
        # same values every intermediate commit would have
        if not compacted:
            full_state = state
        else:
            slot_dev = jnp.asarray(slots, jnp.int32)
            full_state = jax.tree.map(
                lambda A, B: A.at[slot_dev].set(B[:n_real]), full_state, state
            )
        if exit_loop:
            break
        # gather the still-active entities into the smaller dense front;
        # padding lanes replay lane 0's data but are marked done, so the
        # while_loop select freezes them at zero extra trips
        orig_active = slots[active]
        n_real = int(orig_active.size)
        pad = front_n - n_real
        gather = (
            np.concatenate([orig_active, np.repeat(orig_active[:1], pad)])
            if pad else orig_active
        )
        gidx = jnp.asarray(gather, jnp.int32)
        state = jax.tree.map(lambda a: a[gidx], full_state)
        if pad:
            state = state._replace(done=state.done.at[n_real:].set(True))
        front_batch = jax.tree.map(lambda a: a[gidx], bucket_batch)
        front_mu = None if prior_mu is None else prior_mu[gidx]
        front_var = None if prior_var is None else prior_var[gidx]
        slots = orig_active
        compacted = True

    # gauge contract (shared with _account_single_launch): the solve's
    # whole-run useful/executed average, so knob-on and knob-off JSONL
    # snapshots compare like for like
    if executed_total:
        REGISTRY.gauge_set(
            "re_solve.active_lane_fraction",
            float(useful_total) / float(executed_total),
        )
    REGISTRY.counter_inc("re_solve.launches")
    return _captured_jit_call(
        "re_solve.lanes_finalize",
        _lanes_finalize,
        bucket_batch, full_state, l2_weight, norm, prior_mu, prior_var,
        fin_fn=chunked.finalize, variance_computation=variance_computation,
        **common, **minimize_kwargs,
    )


def solve_bucket_lanes(
    bucket_batch: Batch,
    w0: Array,
    l2_weight: Array,
    norm: Any,
    prior_mu: Array | None,
    prior_var: Array | None,
    *,
    minimize_fn: Any,
    loss: PointwiseLoss,
    config: OptimizerConfig,
    intercept_index: int | None,
    variance_computation: VarianceComputationType,
    accounting: "_DeferredLaunchAccounting | None" = None,
    **minimize_kwargs,
):
    """THE bucket-solve entry point for eager (host-driven) callers — the
    streamed trainer and any direct consumer. ``PHOTON_RE_COMPACT_EVERY=0``
    (default) dispatches to ``_solve_bucket`` with identical arguments:
    today's single-launch schedule bit-for-bit. A positive knob routes
    through the compacted chunk schedule (bitwise-identical results).

    ``accounting`` defers the single-launch iteration readback (a pipeline
    stall for callers that overlap bucket dispatches) to the caller's
    ``flush()``; the compacted schedule ignores it — its accounting rides
    the between-chunk syncs it performs anyway."""
    ce = compact_every()
    chunked = None
    if ce > 0:
        from photon_ml_tpu.optim.common import select_chunked_solver

        chunked, _ = select_chunked_solver(
            config, minimize_kwargs.get("l1_weight", 0.0)
        )
    if chunked is None:
        out = _captured_jit_call(
            "re_solve.bucket",
            _solve_bucket,
            bucket_batch,
            w0,
            l2_weight,
            norm,
            prior_mu,
            prior_var,
            minimize_fn=minimize_fn,
            loss=loss,
            config=config,
            intercept_index=intercept_index,
            variance_computation=variance_computation,
            **minimize_kwargs,
        )
        lanes = int(bucket_batch.labels.shape[0])
        if accounting is not None:
            accounting.add(out[2], lanes)
        else:
            _account_single_launch(out[2], lanes)
        return out
    return _solve_bucket_compacted(
        bucket_batch,
        w0,
        l2_weight,
        norm,
        prior_mu,
        prior_var,
        chunked=chunked,
        loss=loss,
        config=config,
        intercept_index=intercept_index,
        variance_computation=variance_computation,
        compact_every_n=ce,
        **minimize_kwargs,
    )


# ---------------------------------------------------------------------------
# Same-geometry launch fusion (PHOTON_RE_FUSE_BUCKETS)
# ---------------------------------------------------------------------------


def _bucket_geometry(pb: PreparedBucket):
    """The (C, d) compile key ``_solve_bucket`` already specializes on:
    buckets with equal keys share an executable, so concatenating their
    entity lanes into one launch changes dispatch count, not math."""
    static_leaves = tuple(
        (a.shape[1:], str(a.dtype)) for a in jax.tree.leaves(pb.static)
    )
    return (
        jax.tree.structure(pb.static),
        static_leaves,
        pb.mask.shape[1:],  # the capacity C
        None if pb.columns is None else pb.columns.shape[1],
        # hash-fold width (PHOTON_RE_PROJECT=hash): same capacity class
        # ⇒ same fold matrix, so equal keys still share one S — this
        # element just refuses to fuse a hashed bucket with an unhashed
        # one that happens to match on the shapes above (constant None
        # when the projection is off: grouping is unchanged)
        None if pb.hash_S is None else tuple(pb.hash_S.shape),
    )


def plan_fusion_groups(
    keys: list, lanes: list[int]
) -> list[tuple[list[int], list[tuple[int, int, int]]]]:
    """Shared fusion bookkeeping for BOTH fusion sites (the in-memory
    ``_fusion_units`` and the streamed ``_solve_re_buckets`` grouping):
    ordered group-by-key with per-member ``(index, lo, hi)`` lane ranges.
    Returns ``(idxs, members)`` per launch unit, first-seen key order.

    Buckets with fewer than 2 lanes NEVER fuse — they stay standalone
    units: XLA lowers batch-1 programs down a different (squeezed) path
    whose per-lane arithmetic is not bitwise-stable against the batched
    lowering (the same measured caveat the compaction path guards with
    its min-2 front), so merging a 1-lane bucket into a batched launch
    would change its results vs the knob-off schedule."""
    groups: dict[Any, list[int]] = {}
    for i, key in enumerate(keys):
        if lanes[i] < 2:
            key = ("__solo__", i)
        groups.setdefault(key, []).append(i)
    plan: list[tuple[list[int], list[tuple[int, int, int]]]] = []
    for idxs in groups.values():
        members: list[tuple[int, int, int]] = []
        lo = 0
        for i in idxs:
            members.append((i, lo, lo + lanes[i]))
            lo = members[-1][2]
        plan.append((idxs, members))
    return plan


def _fusion_units(
    prepared: list[PreparedBucket],
) -> list[tuple[PreparedBucket, list[tuple[int, int, int]]]]:
    """Group same-geometry buckets into fused launch units. Returns
    ``(fused_bucket, members)`` pairs where ``members`` lists each
    original bucket's ``(index, lo, hi)`` lane range in the fused order —
    the diag-refs bookkeeping is remapped through exactly this
    permutation. Entity ids partition across buckets, so the fused
    scatter into the (E, d) matrix touches the same disjoint rows in any
    order; single-member units pass through untouched. Callers gate on
    ``sharding is None`` (concatenation would break mesh lane padding)."""
    return _concat_units(
        prepared,
        [
            # remotely-owned buckets carry no staged tensors (and are
            # never dispatched here) — a unique key keeps each one a
            # passthrough solo unit instead of touching pb.static.
            # Device-granularity placement folds the device into the
            # key so only co-resident buckets concatenate (committed
            # tensors cannot mix devices); device placement is
            # fusion-group-atomic, so on an unsplit prep the device
            # key never changes which groups form — only where they run
            ("__remote__", i) if pb.static is None else (
                _bucket_geometry(pb) if pb.device is None
                else (_bucket_geometry(pb), pb.device)
            )
            for i, pb in enumerate(prepared)
        ],
    )


def _parent_units(
    prepared: list[PreparedBucket],
) -> list[tuple[PreparedBucket, list[tuple[int, int, int]]]]:
    """PHOTON_RE_SPLIT's launch grouping when geometry fusion is OFF:
    same-PARENT sub-buckets of one owner re-concatenate into a single
    launch — sub-buckets are contiguous in-order slices of their parent,
    so a fully co-owned parent launches with EXACTLY the unsplit lane
    order and geometry (bit-for-bit trivially), and a partially-owned
    one launches its owned lanes batched (per-lane vmapped solves are
    lane-count/permutation-invariant above the batch-1 floor the split
    rule enforces — the same invariant the sharded streamed path rests
    on). Unsplit and remote buckets stay solo passthrough units."""
    return _concat_units(
        prepared,
        [
            # the device joins the parent key under device-granularity
            # placement: same-parent atoms re-concatenate per (owner,
            # device) — each atom is >= 2 lanes, so the partial-
            # co-residency launch is covered by the same lane-count
            # invariance partial co-ownership already rests on
            ("__remote__", i) if pb.static is None
            else (
                (
                    ("__parent__", pb.parent) if pb.device is None
                    else ("__parent__", pb.parent, pb.device)
                ) if pb.parent is not None
                else ("__own_solo__", i)
            )
            for i, pb in enumerate(prepared)
        ],
    )


def _concat_units(
    prepared: list[PreparedBucket], keys: list
) -> list[tuple[PreparedBucket, list[tuple[int, int, int]]]]:
    """Shared unit builder for ``_fusion_units``/``_parent_units``:
    concatenate each ``plan_fusion_groups`` group's staged tensors into
    one launch unit, passing single-member groups through untouched."""
    plan = plan_fusion_groups(keys, [pb.num_real for pb in prepared])
    units: list[tuple[PreparedBucket, list[tuple[int, int, int]]]] = []
    for idxs, members in plan:
        if len(idxs) == 1:
            units.append((prepared[idxs[0]], members))
            continue
        lo = members[-1][2]
        cat = lambda *xs: jnp.concatenate(xs, axis=0)
        fused = PreparedBucket(
            entity_ids=np.concatenate([prepared[i].entity_ids for i in idxs]),
            ids=cat(*(prepared[i].ids for i in idxs)),
            static=jax.tree.map(cat, *(prepared[i].static for i in idxs)),
            row_idx=cat(*(prepared[i].row_idx for i in idxs)),
            mask=cat(*(prepared[i].mask for i in idxs)),
            num_real=lo,
            columns=(
                None if prepared[idxs[0]].columns is None
                else cat(*(prepared[i].columns for i in idxs))
            ),
            # every member shares one owner: placement is fusion-group-
            # atomic on unsplit preps, and on split preps only LOCALLY
            # staged buckets (owner == this process) ever group —
            # remote ones key solo above — so the unit inherits it
            # (and its device: both unit keys fold the device in, so
            # members are co-resident by construction)
            owner=prepared[idxs[0]].owner,
            parent=prepared[idxs[0]].parent,
            device=prepared[idxs[0]].device,
            # members share one capacity class (capacity is in both unit
            # keys via geometry/parent), hence one projection spec and
            # one staged fold matrix
            project=prepared[idxs[0]].project,
            hash_S=prepared[idxs[0]].hash_S,
            order=prepared[idxs[0]].order,  # the effect's: every member's
        )
        units.append((fused, members))
    return units


def train_random_effects(
    features: Features,
    labels: np.ndarray,
    offsets: np.ndarray | Array,
    weights: np.ndarray,
    buckets: EntityBuckets,
    num_entities: int,
    loss: PointwiseLoss,
    config: OptimizerConfig,
    l2_weight: float = 0.0,
    l1_weight: float = 0.0,
    intercept_index: int | None = None,
    initial_coefficients: Array | None = None,  # (E, d) warm start
    variance_computation: VarianceComputationType = VarianceComputationType.NONE,
    mesh: Mesh | None = None,
    axis_name: str = "data",
    norm: Any = None,
    prior_coefficients: Array | None = None,
    prior_variances: Array | None = None,
) -> RandomEffectTrainingResult:
    """Train all entities' GLMs; returns the (E, d) coefficient matrix.

    When ``mesh`` is given, each bucket's entity lane is sharded over
    ``axis_name`` (lanes padded with zero-weight entities to divide evenly);
    XLA partitions the batched solve with no collectives — the TPU analog of
    the reference's ``RandomEffectDatasetPartitioner`` balancing.
    """
    prepared = prepare_buckets(
        features, labels, weights, buckets, mesh, axis_name,
        intercept_index=intercept_index,
    )
    return train_prepared(
        prepared,
        jnp.asarray(offsets),
        features.num_features,
        num_entities,
        loss,
        config,
        l2_weight=l2_weight,
        l1_weight=l1_weight,
        intercept_index=intercept_index,
        initial_coefficients=initial_coefficients,
        variance_computation=variance_computation,
        mesh=mesh,
        axis_name=axis_name,
        norm=norm,
        prior_coefficients=prior_coefficients,
        prior_variances=prior_variances,
    )


def train_prepared(
    prepared: list[PreparedBucket],
    offsets: Array,  # (n,) current residual offsets (device)
    num_features: int,
    num_entities: int,
    loss: PointwiseLoss,
    config: OptimizerConfig,
    l2_weight: float = 0.0,
    l1_weight: float = 0.0,
    intercept_index: int | None = None,
    initial_coefficients: Array | None = None,
    variance_computation: VarianceComputationType = VarianceComputationType.NONE,
    mesh: Mesh | None = None,
    axis_name: str = "data",
    norm: Any = None,  # NormalizationContext | None (shared by all entities)
    prior_coefficients: Array | None = None,  # (E, d) per-entity MAP prior means
    prior_variances: Array | None = None,  # (E, d) per-entity prior variances
    fusion_units: list | None = None,  # precomputed _fusion_units(prepared)
) -> RandomEffectTrainingResult:
    """Solve every prepared bucket against the current offsets. Only the
    offsets are gathered per call (on device); everything else was staged by
    ``prepare_buckets``.

    ``fusion_units`` lets a caller that solves the SAME prepared list
    repeatedly (the eager coordinate-descent visit loop) stage the fused
    concatenation once instead of re-concatenating every bucket tensor
    per call; it must be ``_fusion_units(prepared)`` for this exact list
    (or ``_parent_units(prepared)`` on a PHOTON_RE_SPLIT prep with the
    fuse knob off) and is only consulted when a grouped launch schedule
    applies (fuse knob on, or split sub-buckets present).

    ``norm`` applies the shard's normalization inside every entity's
    objective (coefficients are mapped back to the original feature space
    on output — same contract as the fixed-effect solve). FULL variance
    inverts each entity's dense Hessian on device (batched ``linalg.inv``
    over the entity lane); dense features only, like the fixed effect's.
    """
    W, V, diag = _train_prepared_core(
        prepared,
        offsets,
        num_features,
        num_entities,
        loss,
        config,
        l2_weight=l2_weight,
        l1_weight=l1_weight,
        intercept_index=intercept_index,
        initial_coefficients=initial_coefficients,
        variance_computation=variance_computation,
        mesh=mesh,
        axis_name=axis_name,
        norm=norm,
        prior_coefficients=prior_coefficients,
        prior_variances=prior_variances,
        fusion_units=fusion_units,
    )
    diag_refs = tuple(
        (pb.entity_ids, f_k, it_k, reason_k)
        for pb, (f_k, it_k, reason_k) in zip(prepared, diag)
    )
    return RandomEffectTrainingResult(
        coefficients=W,
        variances=V,
        diag_refs=diag_refs,
        num_entities=num_entities,
    )


def _train_prepared_core(
    prepared: list[PreparedBucket],
    offsets: Array,
    num_features: int,
    num_entities: int,
    loss: PointwiseLoss,
    config: OptimizerConfig,
    l2_weight: float = 0.0,
    l1_weight: float = 0.0,
    intercept_index: int | None = None,
    initial_coefficients: Array | None = None,
    variance_computation: VarianceComputationType = VarianceComputationType.NONE,
    mesh: Mesh | None = None,
    axis_name: str = "data",
    norm: Any = None,
    prior_coefficients: Array | None = None,
    prior_variances: Array | None = None,
    fusion_units: list | None = None,
) -> tuple[Array, Array | None, list[tuple]]:
    """Pure computational core of ``train_prepared``: jax ops only (also
    traceable inside a caller's fused-visit jit), returning the coefficient
    matrix, variances, and per-bucket device diagnostics WITHOUT wrapping
    them in the (non-pytree) result object."""
    d = num_features
    compute_variance = variance_computation is not VarianceComputationType.NONE
    if norm is not None and any(pb.columns is not None for pb in prepared):
        # fail FAST (before any bucket solves), not data-dependently mid-loop
        raise NotImplementedError(
            "normalization is not supported together with per-entity "
            "subspace projection (the per-entity column maps would need "
            "per-entity normalization slices)"
        )
    minimize_fn, extra = select_minimize_fn(config, l1_weight)

    if initial_coefficients is None:
        W = jnp.zeros((num_entities, d), jnp.float32)
    else:
        # COPY, never alias: W is donated into the bucket-step programs, and
        # aliasing the caller's warm-start array (the live model's
        # coefficients) would invalidate it on donation-supporting backends
        W = jnp.array(initial_coefficients, jnp.float32, copy=True)
        if norm is not None:
            # warm start arrives in ORIGINAL feature space; the optimizer
            # works in normalized space
            W = jax.vmap(norm.model_from_original_space)(W)
    prior_mu = prior_var = None
    if prior_coefficients is not None:
        # per-entity Gaussian MAP prior (incremental training): arrives in
        # ORIGINAL feature space like the warm start; map into the solver's
        # (normalized) space through the shared transform
        from photon_ml_tpu.ops.glm import GaussianPrior

        p = GaussianPrior.from_coefficients(prior_coefficients, prior_variances, norm)
        prior_mu, prior_var = p.means, p.variances
    V = jnp.zeros((num_entities, d), jnp.float32) if compute_variance else None

    order = shared_order(prepared)
    if order is not None:
        # ONCE a visit: every bucket below slices this copy by its run starts
        offsets = _ordered_offsets(offsets, order)
    l2 = jnp.asarray(l2_weight, jnp.float32)
    # entity-sharded owned-bucket mode (PHOTON_RE_SHARD=1 under a mesh):
    # buckets were staged WHOLE by prepare_buckets, so lanes are fully
    # addressable (sharding=None below) — which both lifts the
    # compaction/fusion gate and lets each process dispatch ONLY the
    # buckets it owns; the post-loop combine exchanges owned results.
    owned_mode = any(pb.owner is not None for pb in prepared)
    sharding = (
        NamedSharding(mesh, P(axis_name))
        if (mesh is not None and not owned_mode) else None
    )

    # per-bucket diagnostics stay ON DEVICE — materialized lazily by the
    # result object on first access, so a descent visit that nobody
    # inspects costs ZERO host syncs (VERDICT weak #2)
    #
    # Launch planning: same-geometry buckets fuse into one launch under
    # PHOTON_RE_FUSE_BUCKETS (traceable — works inside the fused-visit
    # jit too), and PHOTON_RE_COMPACT_EVERY > 0 routes each launch
    # through the host-driven compacted chunk schedule (eager callers
    # only: compaction snapshots the done mask between chunks). Both
    # knobs off ⇒ the classic one-``_bucket_step``-per-bucket loop,
    # bit-for-bit. Mesh-sharded lanes keep the classic schedule (both
    # transforms would break the even lane partition).
    eager = not _is_tracer(offsets)
    chunked = None
    ce = compact_every()
    if ce > 0 and eager and sharding is None:
        from photon_ml_tpu.optim.common import select_chunked_solver

        chunked, _ = select_chunked_solver(config, l1_weight)
    fused = fuse_buckets() and sharding is None and len(prepared) > 1
    # PHOTON_RE_SPLIT sub-buckets re-concatenate per owner even with the
    # fuse knob off (parent-keyed instead of geometry-keyed): a fully
    # co-owned parent then launches with exactly the unsplit lane order
    # and geometry, so the split can only move WHERE lanes solve, never
    # how many launches a co-owned class costs
    split_mode = any(pb.parent is not None for pb in prepared)
    if fused:
        units = fusion_units if fusion_units is not None else _fusion_units(prepared)
    elif split_mode and sharding is None:
        units = (
            fusion_units if fusion_units is not None
            else _parent_units(prepared)
        )
    else:
        units = [(pb, [(i, 0, pb.num_real)]) for i, pb in enumerate(prepared)]
    diag: list[tuple[Array, Array, Array]] = [None] * len(prepared)
    accounting = _DeferredLaunchAccounting()

    if owned_mode:
        from photon_ml_tpu.parallel.multihost import effective_process_index

        own_pid = effective_process_index()
    else:
        own_pid = 0
    # device-granularity dispatch (PHOTON_RE_DEVICE_SPLIT): each local
    # device threads its OWN full (E, d) coefficient/variance copy —
    # committed inputs cannot mix devices, and a full device_put copy
    # carries the warm-start rows bitwise — so each device's queued
    # launches execute asynchronously while the host loop races ahead.
    # The device-local combine below folds the owned rows back into the
    # canonical matrix (permutation-only row copies, bit-preserving)
    # before the unchanged process-level combine. Knob off: no bucket
    # carries a device and this whole block is inert.
    dev_state: dict[int, dict] = {}
    if eager and any(pb.device is not None for pb in prepared):
        local_devs = jax.local_devices()
        for dv in sorted(
            {pb.device for pb in prepared if pb.device is not None}
        ):
            target = local_devs[dv]

            def put(a, _t=target):
                if a is None:
                    return None
                # force a DISTINCT buffer: device_put is a no-op when
                # the canonical array already lives on this device, and
                # the solver DONATES its W/V operands — donating an
                # alias of the canonical matrix would delete it out
                # from under the device-local combine below
                return jax.device_put(jnp.copy(jnp.asarray(a)), _t)

            dev_state[dv] = {
                "W": put(W), "V": put(V), "offsets": put(offsets),
                "prior_mu": put(prior_mu), "prior_var": put(prior_var),
            }
    for pb, members in units:
        if owned_mode and pb.owner is not None and pb.owner != own_pid:
            # another process owns this whole unit — its results arrive
            # through the combine below; nothing is dispatched here
            continue
        st = dev_state.get(pb.device) if pb.device is not None else None
        if st is not None:
            W_in, V_in = st["W"], st["V"]
            off_in = st["offsets"]
            mu_in, pv_in = st["prior_mu"], st["prior_var"]
        else:
            W_in, V_in, off_in, mu_in, pv_in = (
                W, V, offsets, prior_mu, prior_var
            )
        if chunked is not None:
            W_out, V_out, f_k, it_k, reason_k = _bucket_step_compacted(
                W_in,
                V_in,
                off_in,
                pb.static,
                pb.row_idx,
                pb.mask,
                pb.ids,
                pb.columns,
                pb.hash_S,
                l2,
                norm,
                mu_in,
                pv_in,
                chunked=chunked,
                loss=loss,
                config=config,
                intercept_index=intercept_index,
                variance_computation=variance_computation,
                k=pb.num_real,
                compact_every_n=ce,
                **extra,
            )
        else:
            W_out, V_out, f_k, it_k, reason_k = _captured_jit_call(
                "re_solve.bucket_step",
                _bucket_step,
                W_in,
                V_in,
                off_in,
                pb.static,
                pb.row_idx,
                pb.mask,
                pb.ids,
                pb.columns,
                pb.hash_S,
                l2,
                norm,
                mu_in,
                pv_in,
                minimize_fn=minimize_fn,
                loss=loss,
                config=config,
                intercept_index=intercept_index,
                variance_computation=variance_computation,
                k=pb.num_real,
                sharding=sharding,
                **extra,
            )
            if eager:
                # deferred: the loop's no-host-sync-between-buckets
                # invariant (the donate comment on _bucket_step) must
                # survive an active telemetry sink
                accounting.add(it_k, lanes=int(pb.static.labels.shape[0]))
        if st is not None:
            st["W"], st["V"] = W_out, V_out
        else:
            W, V = W_out, V_out
        total = pb.num_real
        for orig_i, lo, hi in members:
            if lo == 0 and hi == total:
                diag[orig_i] = (f_k, it_k, reason_k)  # unfused: no re-slice
            else:
                diag[orig_i] = (f_k[lo:hi], it_k[lo:hi], reason_k[lo:hi])

    accounting.flush()  # one batched readback, after every bucket enqueued
    if dev_state:
        # device-local combine: fold each device's threaded copy back
        # into the canonical matrix BEFORE the process-level transport
        # (which then runs unchanged — it reads exactly the rows this
        # process owns, wherever they solved)
        W, V = _combine_device_local(prepared, W, V, dev_state, own_pid)
    if owned_mode:
        from photon_ml_tpu.parallel.multihost import effective_process_count

        if effective_process_count() > 1:
            W, V, diag = _combine_owned_results(prepared, W, V, diag)
    if norm is not None:
        # back to the ORIGINAL feature space (W was held in normalized space
        # throughout so per-bucket warm starts stayed consistent)
        W = jax.vmap(lambda w: norm.model_to_original_space(w)[0])(W)
        if V is not None:
            # linear map u = f⊙w ⇒ variances scale by f² (diagonal approx.)
            V = norm.factors**2 * V

    return W, V, diag


def _combine_device_local(
    prepared: list[PreparedBucket],
    W: Array,
    V: Array | None,
    dev_state: dict[int, dict],
    own_pid: int,
) -> tuple[Array, Array | None]:
    """Intra-host combine for the device-split schedule: each local
    device threaded its own full (E, d) copy, so every owned bucket's
    coefficient/variance rows live on exactly one device and fold back
    into the canonical matrix by PERMUTATION-ONLY row copies (entity
    ids partition across buckets — disjoint rows, any order, bitwise).
    Host numpy on device_get'd arrays, the same transport discipline as
    ``_combine_owned_allreduce``; per-bucket diagnostics stay on their
    devices (readers device_get them lazily, wherever they live)."""
    W_h = np.array(jax.device_get(W))  # writable copy: the owned-row
    V_h = None if V is None else np.array(jax.device_get(V))  # folds below
    got: dict[int, tuple[np.ndarray, np.ndarray | None]] = {
        dv: (
            np.asarray(jax.device_get(st["W"])),
            None if st["V"] is None
            else np.asarray(jax.device_get(st["V"])),
        )
        for dv, st in dev_state.items()
    }
    for pb in prepared:
        if pb.device is None or (
            pb.owner is not None and pb.owner != own_pid
        ):
            continue
        Wd, Vd = got[pb.device]
        W_h[pb.entity_ids] = Wd[pb.entity_ids]
        if V_h is not None and Vd is not None:
            V_h[pb.entity_ids] = Vd[pb.entity_ids]
    return (
        jnp.asarray(W_h),
        None if V_h is None else jnp.asarray(V_h),
    )


def _emit_re_event(event: str, **payload) -> None:
    try:
        from photon_ml_tpu.obs.spans import emit_event

        emit_event(event, **payload)
    except Exception:
        pass  # telemetry must never take down the combine it observes


def _combine_owned_results(
    prepared: list[PreparedBucket],
    W: Array,
    V: Array | None,
    diag: list,
) -> tuple[Array, Array | None, list]:
    """Cross-process combine for the owned-bucket schedule: every process
    solved only its owned buckets, so each bucket's coefficient rows,
    variances and diagnostics live on exactly ONE process and must be
    delivered fleet-wide before the next visit. Transport is the
    ``PHOTON_RE_COMBINE`` knob: ``allreduce`` (default) is the dense
    fixed-layout path bit-for-bit, ``segments`` ships only owner
    segments over framed P2P — O(E·d) per process instead of O(P·E·d),
    bitwise-identical results (entity ids partition across buckets, so
    every row is written by exactly one owner either way)."""
    if re_combine_mode() == "segments":
        return _combine_owned_segments(prepared, W, V, diag)
    return _combine_owned_allreduce(prepared, W, V, diag)


def _combine_owned_allreduce(
    prepared: list[PreparedBucket],
    W: Array,
    V: Array | None,
    diag: list,
) -> tuple[Array, Array | None, list]:
    """Dense fixed-layout combine: a single allreduce (bucket order,
    ``num_real`` rows each; owners fill their segments, everyone else
    contributes zeros — and x + 0.0 is exact, so the summed result is
    the owner's values BITWISE) delivers every bucket everywhere;
    non-owned rows of the (E, d) matrices are then overwritten and
    non-owned diagnostics filled in.

    Known scale limit: the allgather moves the dense (Σ lanes, d)
    buffer from EVERY process — O(P·E·d) traffic per visit where owned
    segments (O(E·d) total) would do; ``PHOTON_RE_COMBINE=segments``
    (``_combine_owned_segments``) is that owner-segment path.
    """
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.parallel.multihost import (
        allreduce_sum_host,
        effective_process_count,
        effective_process_index,
    )

    pid = effective_process_index()
    ks = [pb.num_real for pb in prepared]
    offs = np.concatenate([[0], np.cumsum(ks)]).astype(np.int64)
    total = int(offs[-1])
    d = int(W.shape[1])
    Wc = np.zeros((total, d), np.float32)
    Vc = np.zeros((total, d), np.float32) if V is not None else None
    Fc = np.zeros(total, np.float64)
    Ic = np.zeros(total, np.int64)
    Rc = np.zeros(total, np.int64)
    W_h = np.asarray(jax.device_get(W)).copy()
    V_h = None if V is None else np.asarray(jax.device_get(V)).copy()
    owned = [i for i, pb in enumerate(prepared) if pb.owner == pid]
    owned_diag = jax.device_get([diag[i] for i in owned])
    for i, (f_h, it_h, r_h) in zip(owned, owned_diag):
        lo, hi = int(offs[i]), int(offs[i + 1])
        ent = prepared[i].entity_ids
        Wc[lo:hi] = W_h[ent]
        if Vc is not None:
            Vc[lo:hi] = V_h[ent]
        Fc[lo:hi] = np.asarray(f_h, np.float64)
        Ic[lo:hi] = np.asarray(it_h, np.int64)
        Rc[lo:hi] = np.asarray(r_h, np.int64)
    # analytic byte accounting for the combine A/B (same definition as
    # the segments arm's measured number: payload this process ships
    # over the interconnect — an allgather must move the full dense
    # buffer to each of the P−1 peers, the lower bound any algorithm
    # pays in aggregate per process)
    payload = Wc.nbytes + Fc.nbytes + Ic.nbytes + Rc.nbytes + (
        Vc.nbytes if Vc is not None else 0
    )
    bytes_sent = payload * max(effective_process_count() - 1, 0)
    REGISTRY.counter_inc("re_combine.exchanges")
    REGISTRY.counter_inc("re_combine.bytes_sent", float(bytes_sent))
    if Vc is None:
        Wc, Fc, Ic, Rc = allreduce_sum_host(Wc, Fc, Ic, Rc)
    else:
        Wc, Vc, Fc, Ic, Rc = allreduce_sum_host(Wc, Vc, Fc, Ic, Rc)
    _emit_re_event(
        "re_combine", mode="allreduce", bytes_sent=int(bytes_sent),
        buckets_owned=len(owned), buckets=len(prepared),
    )
    diag = list(diag)
    for i, pb in enumerate(prepared):
        if pb.owner == pid:
            continue  # locally-solved: device refs already in place
        lo, hi = int(offs[i]), int(offs[i + 1])
        W_h[pb.entity_ids] = Wc[lo:hi]
        if V_h is not None:
            V_h[pb.entity_ids] = Vc[lo:hi]
        diag[i] = (
            jnp.asarray(Fc[lo:hi], jnp.float32),
            jnp.asarray(Ic[lo:hi], jnp.int32),
            jnp.asarray(Rc[lo:hi], jnp.int32),
        )
    W = jnp.asarray(W_h)
    V = None if V_h is None else jnp.asarray(V_h)
    return W, V, diag


def _pack_wv_segments(
    prepared: list[PreparedBucket],
    W_h: np.ndarray,
    V_h: np.ndarray | None,
    owned: list[int],
) -> dict:
    """This owner's packed coefficient/variance segments: one
    (Σ owned num_real, d) block per matrix in OWNED-BUCKET order, plus
    the bucket index list that keys reassembly. Raw float32 rows — the
    framed codec ships them without pickling.

    On a projected prep (any bucket carries a ``PHOTON_RE_PROJECT``
    spec) the packing switches to VARIABLE-WIDTH: each owned bucket
    ships only its class-support columns, flattened into one 1-D frame
    (``num_real · d_e`` floats per bucket) — this is the tentpole's
    wire-byte cut, Σ k·d_e instead of Σ k·d per process. Receivers
    rebuild full rows from the spec every bucket carries; the zeros
    outside the support are bitwise the owner's (the solve's
    zero-then-scatter epilogue wrote exactly those zeros). Both sides
    branch on the same replicated metadata, so the wire format agrees
    by construction."""
    d = int(W_h.shape[1])
    ent = [prepared[i].entity_ids for i in owned]
    if any(pb.project is not None for pb in prepared):
        def pack(M):
            parts = [
                np.ascontiguousarray(
                    M[prepared[i].entity_ids]
                    if prepared[i].project is None
                    else M[prepared[i].entity_ids][
                        :, prepared[i].project.columns
                    ],
                    dtype=np.float32,
                ).ravel()
                for i in owned
            ]
            return (
                np.concatenate(parts) if parts else np.zeros(0, np.float32)
            )

        out = {"buckets": np.asarray(owned, np.int64), "W": pack(W_h)}
        if V_h is not None:
            out["V"] = pack(V_h)
        return out
    out = {
        "buckets": np.asarray(owned, np.int64),
        "W": (
            np.concatenate([W_h[e] for e in ent])
            if ent else np.zeros((0, d), np.float32)
        ),
    }
    if V_h is not None:
        out["V"] = (
            np.concatenate([V_h[e] for e in ent])
            if ent else np.zeros((0, d), np.float32)
        )
    return out


def _pack_diag_segments(owned_diag: list) -> dict:
    """Packed per-entity diagnostics for this owner's buckets, in the
    same owned-bucket order as ``_pack_wv_segments``. Dtypes mirror the
    dense combine's accumulators (f64/i64), so the float32/int32 casts
    at reassembly produce the allreduce arm's bits exactly."""
    return {
        "F": (
            np.concatenate(
                [np.asarray(f, np.float64) for f, _, _ in owned_diag]
            )
            if owned_diag else np.zeros(0, np.float64)
        ),
        "I": (
            np.concatenate(
                [np.asarray(it, np.int64) for _, it, _ in owned_diag]
            )
            if owned_diag else np.zeros(0, np.int64)
        ),
        "R": (
            np.concatenate(
                [np.asarray(r, np.int64) for _, _, r in owned_diag]
            )
            if owned_diag else np.zeros(0, np.int64)
        ),
    }


def _apply_owner_segments(
    prepared: list[PreparedBucket],
    W_h: np.ndarray,
    V_h: np.ndarray | None,
    diag: list,
    wv_views: list,
    diag_views: list,
    pid: int,
) -> list:
    """Scatter every rank's owner segments back into the full (E, d)
    matrices and the per-bucket diagnostics list (disjoint-row writes:
    entity ids partition across buckets and each bucket has exactly one
    owner). Locally-owned buckets are skipped — their device refs (and
    W rows) are already in place, same as the allreduce arm."""
    d = int(W_h.shape[1])
    projected = any(pb.project is not None for pb in prepared)
    seen: set[int] = set()
    for wv, dg in zip(wv_views, diag_views):
        buckets = np.asarray(wv["buckets"], np.int64)
        lo = 0  # row offset (dense frames) / flat offset (projected)
        dlo = 0  # diagnostics row offset (always one row per lane)
        for b in buckets:
            b = int(b)
            if b in seen:
                raise RuntimeError(
                    f"owner-segment combine: bucket {b} shipped by two "
                    "owners (placement plans disagree across processes)"
                )
            seen.add(b)
            pb = prepared[b]
            if projected:
                # variable-width frame: reconstruct full rows from the
                # spec this (replicated) bucket metadata carries — zeros
                # outside the support are bitwise the owner's zeros
                spec = pb.project
                width = d if spec is None else int(spec.support_dim)
                n = pb.num_real * width
                dhi = dlo + pb.num_real
                if pb.owner != pid:
                    def unpack(flat):
                        block = flat[lo:lo + n].reshape(pb.num_real, width)
                        if spec is None:
                            return block
                        rows = np.zeros((pb.num_real, d), np.float32)
                        rows[:, spec.columns] = block
                        return rows

                    W_h[pb.entity_ids] = unpack(wv["W"])
                    if V_h is not None:
                        V_h[pb.entity_ids] = unpack(wv["V"])
                    diag[b] = (
                        jnp.asarray(dg["F"][dlo:dhi], jnp.float32),
                        jnp.asarray(dg["I"][dlo:dhi], jnp.int32),
                        jnp.asarray(dg["R"][dlo:dhi], jnp.int32),
                    )
                lo += n
                dlo = dhi
                continue
            hi = lo + pb.num_real
            if pb.owner != pid:
                W_h[pb.entity_ids] = wv["W"][lo:hi]
                if V_h is not None:
                    V_h[pb.entity_ids] = wv["V"][lo:hi]
                diag[b] = (
                    jnp.asarray(dg["F"][lo:hi], jnp.float32),
                    jnp.asarray(dg["I"][lo:hi], jnp.int32),
                    jnp.asarray(dg["R"][lo:hi], jnp.int32),
                )
            lo = hi
    if len(seen) != len(prepared):
        missing = sorted(set(range(len(prepared))) - seen)
        raise RuntimeError(
            f"owner-segment combine: buckets {missing} shipped by no "
            "owner (placement plans disagree across processes)"
        )
    return diag


def _combine_owned_segments(
    prepared: list[PreparedBucket],
    W: Array,
    V: Array | None,
    diag: list,
) -> tuple[Array, Array | None, list]:
    """Owner-segment combine (``PHOTON_RE_COMBINE=segments``): each
    owner ships ONLY its packed (Σ owned num_real, d) coefficient /
    variance / diagnostic segments as raw ndarray frames over the
    framed-P2P ring allgather — per-process traffic O(E·d) instead of
    the dense arm's O(P·E·d). The (large) coefficient/variance frames
    are issued on the PR-8 async-exchange worker FIRST, so their socket
    sends overlap the diagnostics device readback + packing on the main
    thread; the (small) diagnostics frames follow on the same worker in
    submission order. Results are BITWISE the allreduce arm's (same
    owner bits, same f64/i64 → f32/i32 casts; asserted on the 2/4-
    process gloo harness)."""
    import time as _time

    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.parallel import multihost as mh

    pid = mh.effective_process_index()
    W_h = np.asarray(jax.device_get(W)).copy()
    V_h = None if V is None else np.asarray(jax.device_get(V)).copy()
    owned = [i for i, pb in enumerate(prepared) if pb.owner == pid]
    wv_stats: dict = {}
    diag_stats: dict = {}
    wv_handle = mh.allgather_obj_p2p_async(
        _pack_wv_segments(prepared, W_h, V_h, owned),
        tag="re_combine/wv", stats=wv_stats,
    )
    # overlapped under the coefficient-segment sends: the diagnostics
    # readback (a device sync) and its packing
    owned_diag = jax.device_get([diag[i] for i in owned])
    diag_handle = mh.allgather_obj_p2p_async(
        _pack_diag_segments(owned_diag),
        tag="re_combine/diag", stats=diag_stats,
    )
    t0 = _time.perf_counter()
    wv_views = wv_handle.result()
    diag_views = diag_handle.result()
    waited = _time.perf_counter() - t0
    bytes_sent = int(
        wv_stats.get("bytes_sent", 0) + diag_stats.get("bytes_sent", 0)
    )
    exchange_s = float(
        wv_stats.get("exchange_s", 0.0) + diag_stats.get("exchange_s", 0.0)
    )
    REGISTRY.counter_inc("re_combine.exchanges")
    REGISTRY.counter_inc("re_combine.bytes_sent", float(bytes_sent))
    REGISTRY.timer_add("re_combine.exchange_s", exchange_s)
    REGISTRY.timer_add("re_combine.wait_s", waited)
    if exchange_s > 0.0:
        REGISTRY.gauge_set(
            "re_combine.overlap_ratio",
            max(0.0, min(1.0, 1.0 - waited / exchange_s)),
        )
    _emit_re_event(
        "re_combine", mode="segments", bytes_sent=bytes_sent,
        exchange_s=exchange_s, wait_s=waited,
        buckets_owned=len(owned), buckets=len(prepared),
    )
    diag = _apply_owner_segments(
        prepared, W_h, V_h, list(diag), wv_views, diag_views, pid
    )
    W = jnp.asarray(W_h)
    V = None if V_h is None else jnp.asarray(V_h)
    return W, V, diag


_ROW = 128  # the lane width an aligned row of the offsets is gathered at


def _bucket_offsets(offsets: Array, row_idx: Array, mask: Array) -> Array:
    """A bucket's (k_pad, C) residual offsets, zero in the padded slots.
    SHARED by ``_bucket_step`` and ``_lane_prologue``. From (k_pad,) run
    starts, the form ``prepare_buckets`` stages, lane ``i`` is
    ``offsets[s_i : s_i + C]``, where ``offsets`` is the residual itself or an
    effect's ordered copy of it (``_ordered_offsets``). The slice is one
    gather of whole aligned 128-wide rows, ``ceil(C / 128) + 1`` from row
    ``s_i // 128``, moved left by ``s_i % 128`` in seven fixed-distance
    selects: no loop over lanes (``vmap`` of ``dynamic_slice`` lowers to one
    trip a lane). From (k_pad, C) slot indices it is the plain reading,
    ``offsets[row_idx]``, that tests hold the slices against: one scalar
    gather an index, and a v5e pays by the INDEX: 7.1 to 7.3 ns each in a
    one-chip program whether the indices point at scattered rows, sorted
    ones, consecutive ones or always row 0, and whether the table holds 5M
    rows or 20M (my chip runs, PRs 30 and 39); the same gather inside the
    four-chip descent read 12.4 ns an index (PR 38), which the table's size
    alone does not give. Bitwise one result: a real slot holds the same
    row's offset, and a padded slot reads ``offsets[0]`` in both forms
    before the zero mask, so zero signs agree (an ordered copy opens with
    the residual's own first entry for this)."""
    if row_idx.ndim == 2:
        return offsets[row_idx] * mask
    k, C = mask.shape
    rows = -(-C // _ROW) + 1
    nrow = -(-offsets.shape[0] // _ROW)
    # the same array for every bucket of a visit: formed once a program
    table = jnp.pad(offsets, (0, nrow * _ROW - offsets.shape[0])).reshape(nrow, _ROW)
    q, r = row_idx // _ROW, row_idx % _ROW
    # rows past the table's end repeat its last: only padded slots see them
    at = jnp.minimum(q[:, None] + jnp.arange(rows, dtype=q.dtype)[None, :], nrow - 1)
    win = table[at].reshape(k, rows * _ROW)
    for b in range(7):
        moved = jnp.concatenate(
            [win[:, 1 << b:], jnp.zeros((k, 1 << b), win.dtype)], axis=1
        )
        win = jnp.where((((r >> b) & 1) == 1)[:, None], moved, win)
    return jnp.where(mask != 0, win[:, :C], offsets[0]) * mask


@jax.jit
def _ordered_offsets(offsets: Array, order: Array) -> Array:
    """The residual offsets in an effect's own order (``_effect_order``): one
    scalar gather, an index a real row, for all the effect's classes."""
    with stage(RE_OFFSETS):
        return offsets[order]


def _extract_lanes(M, ids, columns, k, k_pad, d, pad_value=0.0, sharding=None):
    """Extract, pad, project, and (optionally) shard one bucket's rows of
    an (E, d) matrix — the warm-start/prior lane convention. SHARED by the
    fused ``_bucket_step`` and the chunked-compaction twin ``_lane_prologue``
    so the pad/project rules (including the unit prior-variance pad) cannot
    drift between the schedules and break their bitwise-parity contract."""
    if M is None:
        return None
    if columns is None:
        rows = M[ids]
        if k_pad != k:
            rows = jnp.concatenate(
                [rows, jnp.full((k_pad - k, d), pad_value, rows.dtype)]
            )
    else:
        with stage(RE_SUBSPACE):
            # straight to the (k_pad, p) lanes, no (k, d) rows between. A
            # padded lane reads row E, and a sparse shard's maps hold d
            # (one past the last column) in the slots between an entity's
            # support and its rung: out of bounds both, so the pad value
            lane_ids = ids
            if k_pad != k:
                lane_ids = jnp.concatenate(
                    [ids, jnp.full((k_pad - k,), M.shape[0], ids.dtype)]
                )
            rows = M.at[lane_ids[:, None], columns].get(
                mode="fill", fill_value=pad_value
            )
    if sharding is not None:
        rows = jax.lax.with_sharding_constraint(rows, sharding)
    return rows


def _scatter_lanes(W, V, ids, columns, w_b, var_b, k):
    """Scatter a solved bucket's lanes back into the (E, d) matrices —
    the zero-then-scatter subspace epilogue, SHARED by ``_bucket_step``
    and ``_lane_scatter`` (same drift guard as ``_extract_lanes``)."""
    if columns is not None:
        cols = columns[:k]
        # coefficients outside an entity's subspace are 0 (reference:
        # projected training never touches them); the slots a sparse
        # shard's maps pad with d are dropped
        with stage(RE_SUBSPACE):
            W = W.at[ids].set(0.0)
            W = W.at[ids[:, None], cols].set(w_b[:k], mode="drop")
            if V is not None:
                V = V.at[ids].set(0.0)
                V = V.at[ids[:, None], cols].set(var_b[:k], mode="drop")
    else:
        W = W.at[ids].set(w_b[:k])
        if V is not None:
            V = V.at[ids].set(var_b[:k])
    return W, V


def _hash_fold_lanes(w0, mu_l, var_l, hash_S):
    """Fold a bucket's extracted (support-width) warm-start and MAP-prior
    lanes down to the hash width — SHARED by ``_bucket_step`` and the
    compacted ``_lane_prologue`` so the fold rules can't drift between
    the schedules. The prior mean/variance pair folds jointly
    (precision-weighted) so the folded Gaussian penalty equals the full
    penalty restricted to the subspace."""
    w0 = hash_fold_warm_start(w0, hash_S)
    if mu_l is not None and var_l is not None:
        mu_l, var_l = hash_fold_prior(mu_l, var_l, hash_S)
    elif mu_l is not None:
        # no prior variances (uninformative, precision 1 per column):
        # fold the means alone; variances stay None so the solver keeps
        # its plain-L2-strength prior semantics
        mu_l = hash_fold_warm_start(mu_l, hash_S)
    return w0, mu_l, var_l


@partial(
    jax.jit,
    static_argnames=(
        "minimize_fn", "loss", "config", "intercept_index",
        "variance_computation", "k", "sharding",
    ),
    # W/V are rebound by the caller every bucket; donating them keeps peak
    # HBM at O(1) coefficient copies even though the deferred-readback loop
    # enqueues every bucket program without a host sync in between
    donate_argnums=(0, 1),
)
def _bucket_step(
    W: Array,  # (E, d) current coefficients (normalized space if norm)
    V: Array | None,  # (E, d) variances or None
    offsets: Array,  # (n,) residual offsets
    static_batch: Batch,
    row_idx: Array,
    mask: Array,
    ids: Array,  # (k,) this bucket's entity ids (device)
    columns: Array | None,
    hash_S: Array | None,  # (d_e, m) signed fold (PHOTON_RE_PROJECT=hash)
    l2_weight: Array,
    norm: Any,
    prior_mu: Array | None,  # (E, d) per-entity prior means, or None
    prior_var: Array | None,  # (E, d) per-entity prior variances, or None
    *,
    minimize_fn: Any,
    loss: PointwiseLoss,
    config: OptimizerConfig,
    intercept_index: int | None,
    variance_computation: VarianceComputationType,
    k: int,
    sharding: Any,
    **minimize_kwargs,
):
    """One bucket's whole step (offsets, warm-start lanes, the vmapped solve,
    the (E, d) scatter) as one program: the fused visit traces it inline, and
    an eager caller queues every bucket with no host sync between them."""
    d = W.shape[1]
    with stage(RE_OFFSETS):
        off_b = _bucket_offsets(offsets, row_idx, mask)
    with stage(RE_SOLVE):
        bucket_batch = dataclasses.replace(static_batch, offsets=off_b)
        k_pad = static_batch.labels.shape[0]

        def lane(M, pad_value=0.0):
            return _extract_lanes(M, ids, columns, k, k_pad, d, pad_value, sharding)

        w0 = lane(W)
        mu_l = lane(prior_mu)
        var_l = lane(prior_var, pad_value=1.0)  # padded lanes: harmless unit variance
        solve_intercept = intercept_index
        if columns is not None:
            # subspace projection solves at width p over each entity's own
            # columns; the intercept (always the last full-space column by
            # framework convention) lands at slot p-1
            if intercept_index is not None:
                solve_intercept = columns.shape[1] - 1
        if hash_S is not None:
            # hash-folded class: the solve runs at width m — fold the warm
            # start and MAP prior through the same signed matrix the static
            # features were folded through at prepare time (the intercept
            # owns slot m-1 alone by construction, so it stays addressable)
            w0, mu_l, var_l = _hash_fold_lanes(w0, mu_l, var_l, hash_S)
            if intercept_index is not None:
                solve_intercept = hash_S.shape[1] - 1

        w_b, f_b, it_b, reason_b, var_b = _solve_bucket(
            bucket_batch,
            w0,
            l2_weight,
            norm,
            mu_l,
            var_l,
            minimize_fn=minimize_fn,
            loss=loss,
            config=config,
            intercept_index=solve_intercept,
            variance_computation=variance_computation,
            **minimize_kwargs,
        )
        if hash_S is not None:
            # expand the folded solution back to the support width before
            # the column scatter: each support column takes its slot's
            # coefficient (times its sign); variances propagate through the
            # same linear map with |S| (diagonal approximation)
            w_b = hash_expand_coefficients(w_b, hash_S)
            var_b = hash_expand_variances(var_b, hash_S)
        W, V = _scatter_lanes(W, V, ids, columns, w_b, var_b, k)
        return W, V, f_b[:k], it_b[:k], reason_b[:k]


@partial(jax.jit, static_argnames=("k",))
def _lane_prologue(
    W, offsets, static_batch, row_idx, mask, ids, columns, hash_S,
    prior_mu, prior_var, *, k,
):
    """Eager-path twin of ``_bucket_step``'s prologue (offset gather +
    warm-start/prior lane extraction, plus the hash fold when the class
    is folded), as its own compiled program so the host-driven compaction
    loop pays one dispatch, not ~6. Same ops as the fused prologue with
    ``sharding=None`` — identical values."""
    d = W.shape[1]
    with stage(RE_OFFSETS):
        off_b = _bucket_offsets(offsets, row_idx, mask)
    with stage(RE_SOLVE):
        bucket_batch = dataclasses.replace(static_batch, offsets=off_b)
        k_pad = static_batch.labels.shape[0]

        def lane(M, pad_value=0.0):
            return _extract_lanes(M, ids, columns, k, k_pad, d, pad_value)

        w0 = lane(W)
        mu_l = lane(prior_mu)
        var_l = lane(prior_var, pad_value=1.0)
        if hash_S is not None:
            w0, mu_l, var_l = _hash_fold_lanes(w0, mu_l, var_l, hash_S)
        return bucket_batch, w0, mu_l, var_l


# W/V donation: same O(1)-coefficient-copies HBM discipline as _bucket_step —
# the compacted caller rebinds both, so holding the old (E, d) buffers alive
# through the scatter would double peak coefficient memory versus knob-off
@partial(jax.jit, static_argnames=("k",), donate_argnums=(0, 1))
def _lane_scatter(W, V, ids, columns, w_b, var_b, hash_S=None, *, k):
    """Eager-path twin of ``_bucket_step``'s (E, d) scatter epilogue
    (including the hash expansion back to the support width)."""
    with stage(RE_SOLVE):
        if hash_S is not None:
            w_b = hash_expand_coefficients(w_b, hash_S)
            var_b = hash_expand_variances(var_b, hash_S)
        return _scatter_lanes(W, V, ids, columns, w_b, var_b, k)


def _bucket_step_compacted(
    W: Array,
    V: Array | None,
    offsets: Array,
    static_batch: Batch,
    row_idx: Array,
    mask: Array,
    ids: Array,
    columns: Array | None,
    hash_S: Array | None,
    l2_weight: Array,
    norm: Any,
    prior_mu: Array | None,
    prior_var: Array | None,
    *,
    chunked: Any,
    loss: PointwiseLoss,
    config: OptimizerConfig,
    intercept_index: int | None,
    variance_computation: VarianceComputationType,
    k: int,
    compact_every_n: int,
    **minimize_kwargs,
):
    """``_bucket_step``'s host-driven compacted twin: identical math and
    outputs, but the solve runs through ``_solve_bucket_compacted``'s
    chunked schedule (which needs the host between launches, so the whole
    step cannot live inside one jit). Eager, unsharded callers only."""
    bucket_batch, w0, mu_l, var_l = _lane_prologue(
        W, offsets, static_batch, row_idx, mask, ids, columns, hash_S,
        prior_mu, prior_var, k=k,
    )
    solve_intercept = intercept_index
    if columns is not None and intercept_index is not None:
        solve_intercept = columns.shape[1] - 1
    if hash_S is not None and intercept_index is not None:
        solve_intercept = hash_S.shape[1] - 1
    w_b, f_b, it_b, reason_b, var_b = _solve_bucket_compacted(
        bucket_batch,
        w0,
        l2_weight,
        norm,
        mu_l,
        var_l,
        chunked=chunked,
        loss=loss,
        config=config,
        intercept_index=solve_intercept,
        variance_computation=variance_computation,
        compact_every_n=compact_every_n,
        **minimize_kwargs,
    )
    W, V = _lane_scatter(W, V, ids, columns, w_b, var_b, hash_S, k=k)
    return W, V, f_b[:k], it_b[:k], reason_b[:k]


def _to_host(x) -> np.ndarray:
    """Host copy of a device array that may be sharded across PROCESSES
    (multi-host): non-fully-addressable arrays are allgathered first —
    per-entity diagnostics are tiny, so the collective is cheap.
    Batch callers use ``_gather_refs_host`` (ONE collective for all
    arrays) instead."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        return _gather_unaddressable([x])[0]
    return np.asarray(x)


def _gather_unaddressable(arrays: list) -> list[np.ndarray]:
    """Full host copies of non-fully-addressable (cross-process
    sharded) device arrays through ONE framed-P2P segment allgather:
    every process ships its deduped addressable shards (start offsets +
    raw data — the segment codec frames the ndarrays without pickling)
    and reassembles each global array from the union. Collective: every
    process must call with the same number of arrays at the same
    program point — exactly the contract the per-array
    ``process_allgather`` fallback already imposed."""
    from photon_ml_tpu.parallel import multihost as mh

    payload = []
    for x in arrays:
        segs = []
        seen: set[tuple] = set()
        for sh in x.addressable_shards:
            starts = tuple(int(sl.start or 0) for sl in sh.index)
            if starts in seen:
                continue  # replicated across local devices: ship once
            seen.add(starts)
            segs.append((starts, np.asarray(sh.data)))
        payload.append(segs)
    views = mh.allgather_obj_p2p(payload, tag="re_diag_gather")
    out = []
    for k, x in enumerate(arrays):
        full = np.zeros(x.shape, x.dtype)
        for view in views:
            for starts, data in view[k]:
                sl = tuple(
                    slice(s, s + n) for s, n in zip(starts, data.shape)
                )
                full[sl] = data
        out.append(full)
    return out


def _gather_refs_host(refs: list[tuple]) -> list[tuple]:
    """Host copies of the per-bucket diagnostic triples when some live
    as cross-process sharded arrays: addressable arrays fetch in one
    local ``jax.device_get``, and ALL non-addressable ones ride a
    single segment allgather (previously one ``process_allgather`` per
    array — 3 collectives per bucket)."""
    flat = [x for t in refs for x in t]
    na_idx = [
        i for i, x in enumerate(flat)
        if isinstance(x, jax.Array) and not x.is_fully_addressable
    ]
    na_set = set(na_idx)
    local = jax.device_get([flat[i] for i in range(len(flat))
                            if i not in na_set])
    gathered = _gather_unaddressable([flat[i] for i in na_idx])
    host: list = [None] * len(flat)
    it_local = iter(local)
    it_na = iter(gathered)
    for i in range(len(flat)):
        host[i] = next(it_na) if i in na_set else np.asarray(next(it_local))
    return [tuple(host[3 * b:3 * b + 3]) for b in range(len(refs))]


def random_effect_scores(features: Features, entity_ids: Array, W: Array) -> Array:
    """Per-sample scores w_{e(i)}·x_i — one gather + row-dot on device.

    Replaces the reference's RDD join of data against the per-entity model
    RDD (§3.3 "shuffle/join boundary"): the model is a device matrix, so
    scoring is a memory gather, not a shuffle.
    """
    if isinstance(features, DenseFeatures):
        return jnp.einsum("nd,nd->n", features.X, W[entity_ids])
    if isinstance(features, NonzeroMajorSparseFeatures):
        return jnp.sum(
            features.values * W[entity_ids[None, :], features.indices], axis=0
        )
    return jnp.sum(features.values * W[entity_ids[:, None], features.indices], axis=-1)
