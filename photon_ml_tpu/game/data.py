"""GAME data structures: columnar batches, entity grouping, bucketing.

Reference parity (SURVEY.md §2.2):
- ``photon-api::ml.data.GameDatum`` (response, offset, weight, per-shard
  feature vectors, id-tag map) → ``GameBatch``: one columnar structure whose
  arrays live on device; id tags are integer-encoded at ingest.
- ``photon-api::ml.data.FixedEffectDataset`` → a ``Batch`` view over one
  feature shard (``GameBatch.batch_for``).
- ``photon-api::ml.data.RandomEffectDataset`` (activeData per-entity
  ``LocalDataset``s built by a group-by-entity shuffle, plus
  ``RandomEffectDatasetPartitioner`` balancing, ``numActiveDataPointsUpperBound``
  reservoir down-sampling) → ``EntityGrouping`` + ``EntityBuckets``: ONE
  host-side sort by entity id at ingest, then entities padded into
  fixed-capacity buckets so the per-entity solves run as a single vmapped
  kernel per bucket. No runtime shuffle exists (SURVEY.md §7 design table).

TPU-first notes:
- Bucket capacities are powers of two: every entity in a bucket is padded to
  the bucket's capacity with zero-weight rows, so each bucket is one static
  (k, C, d) tensor — XLA compiles ONE program per (C, d) geometry, reused
  across buckets and coordinate-descent iterations.
- Entities whose sample count exceeds ``active_upper_bound`` are reservoir
  down-sampled at ingest (active set); their remaining rows stay "passive":
  scored by the coordinate, never trained on — exactly the reference's
  active/passive split.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.obs.spans import (
    GAME_BATCH,
    GAME_BUCKET,
    GAME_GROUP,
    GAME_PLACE,
    spanned,
)
from photon_ml_tpu.ops.batch import (
    Batch,
    DenseBatch,
    LocalSparseBatch,
    SparseBatch,
)

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# Per-shard feature containers (features only; labels/offsets/weights are
# global columns of the GameBatch)
# ---------------------------------------------------------------------------
@partial(jax.tree_util.register_dataclass, data_fields=["X"], meta_fields=[])
@dataclass(frozen=True)
class DenseFeatures:
    """(n, d) dense feature block for one shard."""

    X: Array

    @property
    def num_features(self) -> int:
        return self.X.shape[-1]

    @property
    def num_rows(self) -> int:
        return self.X.shape[0]

    def to_batch(self, labels: Array, offsets: Array, weights: Array) -> DenseBatch:
        return DenseBatch(X=self.X, labels=labels, offsets=offsets, weights=weights)

    def score(self, w: Array) -> Array:
        return self.X @ w

    def take(self, idx: np.ndarray) -> "DenseFeatures":
        return DenseFeatures(X=jnp.asarray(np.asarray(self.X)[idx]))


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["indices", "values"],
    meta_fields=["num_features"],
)
@dataclass(frozen=True)
class SparseFeatures:
    """Padded sparse rows for one shard: (n, k) indices/values, pad = (0, 0.0)."""

    indices: Array
    values: Array
    num_features: int = field(metadata=dict(static=True))

    @property
    def num_rows(self) -> int:
        return self.indices.shape[0]

    def to_batch(self, labels: Array, offsets: Array, weights: Array) -> SparseBatch:
        return SparseBatch(
            indices=self.indices,
            values=self.values,
            labels=labels,
            offsets=offsets,
            weights=weights,
            num_features=self.num_features,
        )

    def score(self, w: Array) -> Array:
        return jnp.sum(self.values * w[self.indices], axis=-1)

    def take(self, idx: np.ndarray) -> "SparseFeatures":
        return SparseFeatures(
            indices=jnp.asarray(np.asarray(self.indices)[idx]),
            values=jnp.asarray(np.asarray(self.values)[idx]),
            num_features=self.num_features,
        )


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["indices", "values"],
    meta_fields=["num_features"],
)
@dataclass(frozen=True)
class NonzeroMajorSparseFeatures:
    """A sparse shard stored (nnz, n): the copy a random effect's fused visit
    scores from. Inside a program an (n, nnz) operand is laid out to 128
    lanes, 512 B a row for 16 nonzeros, and so is every temporary shaped
    like it (the gathered coefficients); (nnz, n) has the long axis minor
    and costs what it holds."""

    indices: Array
    values: Array
    num_features: int = field(metadata=dict(static=True))

    @classmethod
    def of(cls, features: SparseFeatures) -> "NonzeroMajorSparseFeatures":
        return cls(
            indices=jnp.asarray(features.indices).T,
            values=jnp.asarray(features.values).T,
            num_features=features.num_features,
        )


Features = DenseFeatures | SparseFeatures


# ---------------------------------------------------------------------------
# GameBatch — the GameDatum columnar equivalent
# ---------------------------------------------------------------------------
@partial(
    jax.tree_util.register_dataclass,
    data_fields=["labels", "offsets", "weights", "features", "id_tags"],
    meta_fields=["padded_rows"],
)
@dataclass(frozen=True)
class GameBatch:
    """Columnar GAME dataset (device-resident).

    ``features[shard_id]`` — per-shard feature container.
    ``id_tags[tag]`` — (n,) int32 entity ids; used both as random-effect
    entity columns and as grouping keys for Multi* evaluators (the
    reference's ``GameDatum.idTagToValueMap`` serves the same double duty).
    """

    labels: Array
    offsets: Array
    weights: Array
    features: dict[str, Features]
    id_tags: dict[str, Array]
    # rows at the END that ``place_game_batch`` added to fill a mesh: weight,
    # label, offset, features and entity id 0, so inert in every sum
    padded_rows: int = field(default=0, metadata=dict(static=True))

    @property
    def num_rows(self) -> int:
        return self.labels.shape[0]

    @property
    def num_real_rows(self) -> int:
        """The rows the caller gave: ``num_rows`` less ``padded_rows``."""
        return self.num_rows - self.padded_rows

    def batch_for(self, shard_id: str, offsets: Array | None = None) -> Batch:
        """A ``Batch`` view for one coordinate: shard features + global
        labels/weights + caller-supplied offsets (the residual scores during
        coordinate descent)."""
        off = self.offsets if offsets is None else offsets
        return self.features[shard_id].to_batch(self.labels, off, self.weights)

    def host_id_tags(self) -> dict[str, np.ndarray]:
        return {k: np.asarray(v) for k, v in self.id_tags.items()}


@spanned(GAME_BATCH)
def make_game_batch(
    labels: np.ndarray,
    features: Mapping[str, np.ndarray | Features],
    id_tags: Mapping[str, np.ndarray] | None = None,
    offsets: np.ndarray | None = None,
    weights: np.ndarray | None = None,
    dtype=jnp.float32,
    mesh=None,
    axis_name: str = "data",
) -> GameBatch:
    """Build a device GameBatch from host arrays. Dense 2-D feature arrays
    become ``DenseFeatures``; prebuilt containers pass through. With a
    ``mesh`` the rows are placed over it once (``place_game_batch``) without
    a whole column ever lying on one device; ``GameEstimator.fit`` places a
    batch that came without (``placeable_over`` says which it can). A placed
    batch is what lets the descent run fused under that mesh."""
    # under a mesh a host column stays on the host until it is placed: put
    # on the default device first it would be the whole column on one chip
    col = jnp.asarray if mesh is None else _host_column
    zeros, ones = (jnp.zeros, jnp.ones) if mesh is None else (np.zeros, np.ones)
    n = len(labels)
    feats: dict[str, Features] = {}
    for sid, f in features.items():
        if isinstance(f, (DenseFeatures, SparseFeatures)):
            feats[sid] = f
        else:
            feats[sid] = DenseFeatures(X=col(f, dtype))
    batch = GameBatch(
        labels=col(labels, dtype),
        offsets=zeros((n,), dtype) if offsets is None else col(offsets, dtype),
        weights=ones((n,), dtype) if weights is None else col(weights, dtype),
        features=feats,
        id_tags={k: col(v, jnp.int32) for k, v in (id_tags or {}).items()},
    )
    return batch if mesh is None else place_game_batch(batch, mesh, axis_name)


def _host_column(a, dtype):
    if isinstance(a, jax.Array):
        return a if a.dtype == dtype else a.astype(dtype)
    return np.asarray(a, dtype)


def one_process_mesh(mesh) -> bool:
    """Whether every device of ``mesh`` is this process's own (one host,
    fully addressable arrays)."""
    me = jax.process_index()
    return all(d.process_index == me for d in mesh.devices.flat)


def placeable_over(batch: GameBatch, mesh) -> bool:
    """Whether ``place_game_batch`` takes ``batch`` over ``mesh``: a mesh of
    this process's own devices and dense shards only. ``GameEstimator.fit``
    and ``AvroDataReader.read`` place where this holds, so a mesh alone
    chooses the fused descent; a mesh that spans processes or a sparse
    shard keeps the host loop's per-visit staging."""
    return one_process_mesh(mesh) and all(
        isinstance(f, DenseFeatures) for f in batch.features.values()
    )


def rows_placed_over(batch: GameBatch, mesh, axis_name: str = "data") -> bool:
    """Whether every per-row array of ``batch`` already lies row-sharded
    over ``mesh``'s ``axis_name`` (``place_game_batch`` left it so)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    want = NamedSharding(mesh, P(axis_name))
    return all(
        isinstance(a, jax.Array)
        and not isinstance(a, jax.core.Tracer)
        and a.sharding.is_equivalent_to(want, a.ndim)
        for a in jax.tree.leaves(batch)
    )


@spanned(GAME_PLACE)
def place_game_batch(batch: GameBatch, mesh, axis_name: str = "data") -> GameBatch:
    """``batch`` with its rows over ``mesh``'s ``axis_name``, placed ONCE:
    every per-row array (labels, offsets, weights, each dense shard's
    matrix, each id column) becomes one array row-sharded ``P(axis_name)``,
    a device holding one contiguous block of rows. Where the row count does
    not divide by the devices, rows are added at the end up to the next
    multiple: weight 0, label 0, offset 0, features 0 and entity id 0, so
    they are inert in every sum, score 0, and belong after the real rows
    (``batch.num_rows`` then counts them and ``batch.padded_rows`` says how
    many they are; entities are grouped over the ``num_real_rows`` before
    them, which keep their row numbers). Arrays that already lie so are left where
    they are. Dense shards only: a sparse shard's rows keep the path they
    have (``parallel/distributed.sharded_minimize`` shards them a visit).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from photon_ml_tpu.obs.metrics import REGISTRY

    for sid, f in batch.features.items():
        if not isinstance(f, DenseFeatures):
            raise NotImplementedError(
                f"place_game_batch: shard {sid!r} is not dense; only dense "
                "shards are placed over a mesh"
            )
    n_dev = mesh.shape[axis_name]
    n = batch.num_rows
    target = -(-n // n_dev) * n_dev
    sharding = NamedSharding(mesh, P(axis_name))

    def place(a):
        if isinstance(a, jax.Array) and a.sharding.is_equivalent_to(sharding, a.ndim):
            return a
        if target != n:
            # on the host: a padded copy on one device would be the whole
            # array on one device
            a = np.asarray(a)
            a = np.concatenate(
                [a, np.zeros((target - n,) + a.shape[1:], a.dtype)]
            )
        return jax.device_put(a, sharding)

    out = dataclasses.replace(
        jax.tree.map(place, batch), padded_rows=batch.padded_rows + target - n
    )
    REGISTRY.gauge_set(
        "mesh.batch_devices", float(len(out.labels.sharding.device_set))
    )
    return out


# ---------------------------------------------------------------------------
# Entity grouping — the ingest-time "shuffle"
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class EntityGrouping:
    """Per-entity segment layout of one random-effect coordinate's samples.

    Replaces the reference's group-by-entity Spark shuffle + custom
    partitioner: one argsort by entity id gives contiguous segments.
    ``active_rows[j]`` are the (at most ``active_upper_bound``) sample rows
    entity j trains on; passive rows are everything else (scored only).
    """

    num_entities: int
    counts: np.ndarray  # (E,) total samples per entity
    active_counts: np.ndarray  # (E,) samples actually trained on
    active_rows: list[np.ndarray]  # E arrays of row indices into the batch


@spanned(GAME_GROUP)
def group_by_entity(
    entity_ids: np.ndarray,
    num_entities: int | None = None,
    active_upper_bound: int | None = None,
    seed: int = 0,
) -> EntityGrouping:
    """Group sample rows by integer entity id (host-side, vectorized).

    ``active_upper_bound`` reservoir-samples each larger entity's rows
    (parity: ``numActiveDataPointsUpperBound`` in ``RandomEffectDataset``).
    """
    entity_ids = np.asarray(entity_ids)
    if len(entity_ids) and entity_ids.min() < 0:
        raise ValueError(
            "group_by_entity: negative entity ids (the unseen-entity sentinel "
            "-1 is a scoring-time concept; training ids must be dense >= 0)"
        )
    max_id = int(entity_ids.max()) + 1 if len(entity_ids) else 0
    if num_entities is None:
        num_entities = max_id
    elif num_entities < max_id:
        raise ValueError(
            f"group_by_entity: num_entities={num_entities} < max entity id + 1 = {max_id}"
        )
    order = np.argsort(entity_ids, kind="stable")
    counts = np.bincount(entity_ids, minlength=num_entities)

    rng = np.random.default_rng(seed)
    # one vectorized split into per-entity segments (the "shuffle");
    # np.split on zero segments still yields one empty array — guard E=0
    active_rows = (
        np.split(order, np.cumsum(counts)[:-1]) if num_entities else []
    )
    active_counts = np.minimum(
        counts, active_upper_bound if active_upper_bound is not None else counts.max(initial=0)
    )
    if active_upper_bound is not None:
        for e in np.flatnonzero(counts > active_upper_bound):
            active_rows[e] = rng.choice(
                active_rows[e], size=active_upper_bound, replace=False
            )
    return EntityGrouping(
        num_entities=num_entities,
        counts=counts,
        active_counts=active_counts,
        active_rows=active_rows,
    )


# ---------------------------------------------------------------------------
# Bucketing — variable-size entities → fixed-geometry tensors
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class EntityBuckets:
    """Entities grouped by padded sample capacity.

    For bucket b: ``entity_ids[b]`` is (k_b,), ``row_indices[b]`` is
    (k_b, C_b) with -1 padding. Gathering batch rows with these indices (and
    zeroing weight where index < 0) yields the (k_b, C_b, …) tensors the
    batched solver consumes. Each distinct C_b compiles one XLA program.

    ``widths`` is set when the entities were classed by subspace width as
    well (a random effect over a sparse shard): bucket b then holds
    entities of capacity class C_b AND width rung ``widths[b]``, and each
    distinct (C_b, widths[b]) compiles one program.
    """

    capacities: tuple[int, ...]
    entity_ids: list[np.ndarray]
    row_indices: list[np.ndarray]
    widths: tuple[int, ...] | None = None

    @property
    def num_entities(self) -> int:
        return sum(len(e) for e in self.entity_ids)


def default_capacities(max_count: int, smallest: int = 8, growth: int = 2) -> tuple[int, ...]:
    """Geometric capacity ladder: [8, 16, 32, ...] up to max_count.

    ``growth=2`` bounds per-entity padding at 2× worst-case. Since
    whole-outer fusion (``descent._build_fused_outer``) put every bucket
    inside ONE compiled program, launch count no longer scales with bucket
    count — padded compute (the in-loop offset gathers and masked Newton
    lanes) is what shows up on the profile, so the ladder is fine and the
    merge below trims geometry count, not the other way around. Profiled
    on bench config E (Zipf entities): the old growth-4 ladder merged to 4
    classes padded 5.0×; growth-2 merged to 8 classes pads 2.0×.
    """
    caps = [smallest]
    while caps[-1] < max_count:
        caps.append(caps[-1] * growth)
    return tuple(caps)


def _capacity_slots(
    active_counts: np.ndarray,
    capacities: tuple[int, ...] | None,
    target_buckets: int,
    max_padded_ratio: float,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The class-assignment front half SHARED by ``bucket_entities`` and
    ``capacity_classes``: (active entity indices, per-active-entity class
    slot, capacity ladder). One implementation on purpose — the sharded
    solve's bitwise-parity guarantee rests on every shard assigning each
    entity the capacity the whole-population bucketing would, so the two
    call sites must never drift."""
    counts = np.asarray(active_counts)
    active = np.flatnonzero(counts > 0)
    if len(active) == 0:
        return active, np.zeros(0, np.int64), np.zeros(0, np.int64)
    max_count = int(counts[active].max())
    explicit = capacities is not None
    if capacities is None:
        capacities = default_capacities(max_count)
    caps = np.asarray(sorted(capacities))
    if caps[-1] < max_count:
        raise ValueError(
            f"largest bucket capacity {caps[-1]} < max active entity size {max_count}"
        )
    # smallest capacity >= count, per entity
    slot = np.searchsorted(caps, counts[active])
    if not explicit:
        slot, caps = _merge_bucket_classes(
            slot, caps, counts[active], target_buckets, max_padded_ratio
        )
    return active, slot, caps


@spanned(GAME_BUCKET)
def bucket_entities(
    grouping: EntityGrouping,
    capacities: tuple[int, ...] | None = None,
    target_buckets: int = 8,
    max_padded_ratio: float = 0.5,
    widths: np.ndarray | None = None,
) -> EntityBuckets:
    """Assign each entity (with ≥1 active sample) to the smallest bucket
    capacity ≥ its active count; build padded row-index matrices.

    ``widths`` ((E,) ladder rung of each entity's column subspace,
    ``game/projector.EntityIndexMap.rungs``) classes the entities by width
    beside capacity: see ``class_buckets_by_width``.

    When ``capacities`` is not given, the fine geometric ladder is then
    GREEDILY MERGED down toward ``target_buckets`` classes, stopping when
    the padding ADDED by merging would exceed ``max_padded_ratio`` × the
    active sample count. Bucket count only costs XLA compile time (all
    buckets execute inside one fused program per descent iteration), while
    padded slots cost gather bytes and masked solver lanes EVERY iteration
    — so the budget is deliberately tight (0.5×) and the target loose (8):
    on bench config E this keeps total padding ≈2× active samples where the
    old launch-count-minimizing policy (4 classes, 4× budget) paid 5×."""
    active, slot, caps = _capacity_slots(
        grouping.active_counts, capacities, target_buckets, max_padded_ratio
    )
    if len(active) == 0:
        return EntityBuckets(capacities=(), entity_ids=[], row_indices=[])
    ent_ids: list[np.ndarray] = []
    row_idx: list[np.ndarray] = []
    used_caps: list[int] = []
    for b, cap in enumerate(caps):
        members = active[slot == b]
        if len(members) == 0:
            continue
        rows = np.full((len(members), cap), -1, dtype=np.int64)
        for i, e in enumerate(members):
            seg = grouping.active_rows[e]
            rows[i, : len(seg)] = seg
        used_caps.append(int(cap))
        ent_ids.append(members.astype(np.int64))
        row_idx.append(rows)
    buckets = EntityBuckets(capacities=tuple(used_caps), entity_ids=ent_ids, row_indices=row_idx)
    return buckets if widths is None else class_buckets_by_width(buckets, widths)


def class_buckets_by_width(buckets: EntityBuckets, widths: np.ndarray) -> EntityBuckets:
    """Split every capacity class by subspace width: the width ladder
    beside the capacity ladder. ``widths[e]`` is entity e's rung (a power
    of two from 128, ``game/projector.width_rungs``); classes come out by
    capacity, then width. A bucket's lanes then share one (C, P) geometry,
    so the solve runs at width P and not at the shard's.

    Inside a class the lanes are ordered by their entity's FIRST ROW, not
    by its id: such a class is solved a chunk of consecutive lanes at a
    time, each chunk until its slowest lane stops, so which lanes share a
    chunk decides the work, and that must follow the data and not the
    names the entities happen to bear (the same data under other ids took
    0.8% more or less time, my chip runs, PR 27)."""
    widths = np.asarray(widths)
    caps: list[int] = []
    rungs: list[int] = []
    ent_ids: list[np.ndarray] = []
    row_idx: list[np.ndarray] = []
    for cap, ents, rows in zip(
        buckets.capacities, buckets.entity_ids, buckets.row_indices
    ):
        w = widths[ents]
        for rung in np.unique(w):
            keep = np.flatnonzero(w == rung)
            keep = keep[np.argsort(rows[keep, 0], kind="stable")]
            caps.append(int(cap))
            rungs.append(int(rung))
            ent_ids.append(ents[keep])
            row_idx.append(rows[keep])
    return EntityBuckets(
        capacities=tuple(caps), entity_ids=ent_ids, row_indices=row_idx,
        widths=tuple(rungs),
    )


def capacity_classes(
    active_counts: np.ndarray,
    capacities: tuple[int, ...] | None = None,
    target_buckets: int = 8,
    max_padded_ratio: float = 0.5,
) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """The (used capacities, per-class entity populations) that
    ``bucket_entities`` would produce for this active-count population —
    WITHOUT building any row matrices.

    The point: after the greedy merge, every entity's class is the
    smallest SURVIVING capacity ≥ its active count (merging class lo
    into the next used class hi leaves no survivor between them), so
    bucketing any SUBSET of these entities with the returned capacities
    passed EXPLICITLY reproduces each entity's capacity exactly. That is
    what makes sharded bucket prep population-independent: every shard
    computes the ladder from the GLOBAL counts (one allreduced bincount)
    and buckets its owned entities against it, so an entity's bucket
    geometry — and therefore its solve, bitwise — does not depend on
    which shard owns it or on how many shards exist. The populations are
    the lane-floor input: a shard whose local class holds ONE entity of
    a globally ≥2-entity class must pad to 2 lanes (XLA's batch-1
    lowering is not bitwise-stable against the batched one — the PR-5
    caveat), while a globally-singleton class stays 1-lane everywhere.
    """
    active, slot, caps = _capacity_slots(
        active_counts, capacities, target_buckets, max_padded_ratio
    )
    if len(active) == 0:
        return (), ()
    pops = np.bincount(slot, minlength=len(caps))
    used = np.flatnonzero(pops > 0)
    return (
        tuple(int(caps[b]) for b in used),
        tuple(int(pops[b]) for b in used),
    )


def _split_runs(
    weights: np.ndarray,
    cap: float,
    byte_weights: np.ndarray | None = None,
    byte_cap: float = 0.0,
) -> list[tuple[int, int]]:
    """The SHARED sub-bucket split kernel: partition positions
    ``[0, len(weights))`` into contiguous runs whose summed weight stays
    at or under ``cap`` where possible, each run holding at least TWO
    positions (XLA's batch-1 lowering is not bitwise-stable against the
    batched one — the PR-5 caveat — so a placement atom must never
    force a 1-lane launch the unsplit run would have batched). Returns
    ``(lo, hi)`` half-open ranges covering every position in order.

    ``byte_weights``/``byte_cap`` add the SECOND weight axis
    (``PHOTON_RE_SPLIT_WEIGHT=bytes``): a run also closes when its
    summed lane BYTES would exceed ``byte_cap``, so atoms come out
    bounded on both the compute (rows) and the wire (per-lane segment
    bytes) axis. ``None`` (the default) keeps the single-axis rule
    bit-for-bit.

    Deterministic pure arithmetic on the weights alone: both split
    sites (``placement_atoms`` for the streamed owner map,
    ``split_entity_buckets`` for the in-memory prepared buckets) call
    THIS function in the same ascending-entity order, so the partition
    RULE can never drift between them. Each site weighs atoms by what
    its planner balances — total rows on the streamed path, active
    (capped) rows in-memory — so under ``active_data_upper_bound`` the
    two ladders may legitimately cut a class at different entities;
    each path is internally consistent, which is all its bitwise
    contract needs (the two never share an owner map)."""
    n = len(weights)
    if n < 4 or cap <= 0:
        # < 4 entities cannot form two >= 2-entity atoms: stay whole
        return [(0, n)] if n else []
    runs: list[tuple[int, int]] = []
    lo = 0
    acc = 0.0
    acc_b = 0.0
    for i in range(n):
        w = float(weights[i])
        b = 0.0 if byte_weights is None else float(byte_weights[i])
        over = acc + w > cap or (
            byte_weights is not None and acc_b + b > byte_cap
        )
        if i > lo + 1 and over:
            runs.append((lo, i))
            lo, acc, acc_b = i, w, b
        else:
            acc += w
            acc_b += b
    runs.append((lo, n))
    if len(runs) > 1 and runs[-1][1] - runs[-1][0] < 2:
        # a trailing singleton merges back into its neighbor (the lane
        # floor wins over the weight cap)
        prev_lo, _ = runs[-2]
        runs[-2:] = [(prev_lo, n)]
    return runs


def placement_atoms(
    active_counts: np.ndarray,
    weights: np.ndarray | None = None,
    capacities: tuple[int, ...] | None = None,
    target_buckets: int = 8,
    max_padded_ratio: float = 0.5,
    split: int = 0,
    byte_weights: np.ndarray | None = None,
) -> tuple[list[np.ndarray], tuple[int, ...], int]:
    """The sub-bucket placement-atom ladder (``PHOTON_RE_SPLIT``):
    partition the active entities into placement atoms — contiguous
    ascending-entity-id runs WITHIN each capacity class — such that any
    class whose total ``weights`` exceeds ``sum(weights) / split`` is
    split into runs of at most that cap (each >= 2 entities). Returns
    ``(atom_members, atom_capacities, split_class_count)`` where
    ``atom_members[a]`` are atom ``a``'s entity indices.

    ``split <= 0`` returns one atom per used capacity class — exactly
    the bucket-atomic granularity. ``weights`` defaults to the active
    counts (callers that balance TOTAL rows pass those instead).
    ``byte_weights`` (``PHOTON_RE_SPLIT_WEIGHT=bytes``) adds the lane-
    byte axis: a class also splits when its summed byte weight exceeds
    ``sum(byte_weights) / split``, and each run respects both caps —
    atoms come out bounded in compute AND wire bytes. ``None`` (the
    default) keeps the single-axis ladder bit-for-bit.

    Everything here is deterministic pure-host arithmetic on the GLOBAL
    bincount and the knob value only — the process count never enters —
    so every process and the single-process reference derive the
    identical ladder with zero extra communication, keeping bucket
    geometry process-count-independent (the PR-8 bitwise invariant)."""
    counts = np.asarray(active_counts)
    w = counts if weights is None else np.asarray(weights)
    if len(w) != len(counts):
        raise ValueError(
            f"placement_atoms: weights length {len(w)} != "
            f"active_counts length {len(counts)}"
        )
    bw = None if byte_weights is None else np.asarray(byte_weights)
    if bw is not None and len(bw) != len(counts):
        raise ValueError(
            f"placement_atoms: byte_weights length {len(bw)} != "
            f"active_counts length {len(counts)}"
        )
    active, slot, caps = _capacity_slots(
        counts, capacities, target_buckets, max_padded_ratio
    )
    if len(active) == 0:
        return [], (), 0
    cap_w = float(w[active].sum()) / split if split > 0 else 0.0
    cap_b = (
        float(bw[active].sum()) / split
        if split > 0 and bw is not None else 0.0
    )
    atoms: list[np.ndarray] = []
    atom_caps: list[int] = []
    split_classes = 0
    for b in np.flatnonzero(np.bincount(slot, minlength=len(caps))):
        members = active[slot == b]  # ascending entity index
        mw = np.asarray(w[members], np.float64)
        mb = None if bw is None else np.asarray(bw[members], np.float64)
        over = split > 0 and (
            mw.sum() > cap_w or (mb is not None and mb.sum() > cap_b)
        )
        runs = (
            _split_runs(mw, cap_w, byte_weights=mb, byte_cap=cap_b)
            if over
            else [(0, len(members))]
        )
        if len(runs) > 1:
            split_classes += 1
        for lo, hi in runs:
            atoms.append(members[lo:hi])
            atom_caps.append(int(caps[b]))
    return atoms, tuple(atom_caps), split_classes


def split_entity_buckets(
    buckets: EntityBuckets,
    split: int,
    weight: str = "rows",
    byte_dims: "Sequence[float] | None" = None,
) -> tuple[EntityBuckets, tuple[int, ...] | None, int]:
    """Apply the ``PHOTON_RE_SPLIT`` rule to an already-built
    ``EntityBuckets`` (the in-memory owned-bucket path): each bucket
    whose total active-row weight exceeds ``total_rows / split`` is
    split into contiguous sub-buckets (same capacity, entity/row slices
    — the ``_split_runs`` partition over the ascending-entity order
    ``bucket_entities`` built, weighted by ACTIVE rows: what the
    in-memory owner plan balances; ``placement_atoms`` computes the
    identical partition whenever it is given the same weights).
    Returns ``(buckets, parents, split_class_count)``: ``parents[b]``
    is output bucket ``b``'s index in the INPUT bucket list, or
    ``None`` in place of the whole tuple when nothing split (``split <=
    0`` or no bucket over the cap) — callers key the knob-off
    bit-for-bit path on that.

    ``weight="bytes"`` (``PHOTON_RE_SPLIT_WEIGHT``) adds the lane-byte
    axis: each LANE carries one combine segment row (coefficients +
    variances + diag) regardless of its row count, so the byte weight
    is 1 per lane and a bucket also splits when its lane count exceeds
    ``total_lanes / split`` — bounding the per-atom wire bytes the
    row-weighted rule leaves unbounded on a Zipf tail class.

    ``byte_dims`` (``PHOTON_RE_PROJECT``) reweighs the byte axis by the
    PROJECTED payload: entry ``b`` is input bucket ``b``'s per-lane
    segment width (its capacity class's solved dimension d_e), so a
    projected tail class — whose lanes ship d_e-wide segments — weighs
    proportionally less than an unprojected one. ``None`` (the default,
    and always when the projection knob is off) keeps the 1-per-lane
    rule bit-for-bit."""
    if split <= 0 or not buckets.entity_ids:
        return buckets, None, 0
    if weight not in ("rows", "bytes"):
        raise ValueError(
            f"split_entity_buckets: unknown weight axis {weight!r}"
        )
    per_bucket_w = [
        np.asarray((rows >= 0).sum(axis=1), np.float64)
        for rows in buckets.row_indices
    ]
    if byte_dims is not None and len(byte_dims) != len(per_bucket_w):
        raise ValueError(
            f"split_entity_buckets: byte_dims length {len(byte_dims)} != "
            f"bucket count {len(per_bucket_w)}"
        )
    total = float(sum(w.sum() for w in per_bucket_w))
    cap_w = total / split
    by_bytes = weight == "bytes"
    cap_b = 0.0
    if by_bytes:
        lane_w = (
            [1.0] * len(per_bucket_w) if byte_dims is None
            else [float(x) for x in byte_dims]
        )
        total_lanes = float(
            sum(len(w) * lw for w, lw in zip(per_bucket_w, lane_w))
        )
        cap_b = total_lanes / split
    ent_out: list[np.ndarray] = []
    row_out: list[np.ndarray] = []
    caps_out: list[int] = []
    parents: list[int] = []
    split_classes = 0
    for b, (ents, rows, w) in enumerate(
        zip(buckets.entity_ids, buckets.row_indices, per_bucket_w)
    ):
        bw = np.full(len(w), lane_w[b], np.float64) if by_bytes else None
        over = float(w.sum()) > cap_w or (
            by_bytes and float(bw.sum()) > cap_b
        )
        runs = (
            _split_runs(w, cap_w, byte_weights=bw, byte_cap=cap_b)
            if over
            else [(0, len(ents))]
        )
        if len(runs) > 1:
            split_classes += 1
        for lo, hi in runs:
            ent_out.append(ents[lo:hi])
            row_out.append(rows[lo:hi])
            caps_out.append(int(buckets.capacities[b]))
            parents.append(b)
    if split_classes == 0:
        return buckets, None, 0
    return (
        EntityBuckets(
            capacities=tuple(caps_out),
            entity_ids=ent_out,
            row_indices=row_out,
        ),
        tuple(parents),
        split_classes,
    )


def _merge_bucket_classes(
    slot: np.ndarray,
    caps: np.ndarray,
    active_counts: np.ndarray,
    target_buckets: int,
    max_padded_ratio: float,
) -> tuple[np.ndarray, np.ndarray]:
    """Greedily merge adjacent capacity classes (smallest added padding
    first) until at most ``target_buckets`` non-empty classes remain or the
    padding budget is exhausted. Returns the updated (slot, caps)."""
    total_active = float(active_counts.sum())
    budget = max_padded_ratio * total_active
    counts_per_class = np.bincount(slot, minlength=len(caps)).astype(np.int64)
    # budget the padding ADDED BY MERGING — the fine ladder's inherent
    # padding (up to the ladder's growth factor on skewed data) must not
    # consume the budget, or the merge never fires exactly where it matters
    added = 0.0

    while np.count_nonzero(counts_per_class) > max(target_buckets, 1):
        used = np.flatnonzero(counts_per_class)
        if len(used) < 2:
            break
        # cost of merging used class i into the NEXT used class above it
        costs = [
            (counts_per_class[lo] * (caps[hi] - caps[lo]), lo, hi)
            for lo, hi in zip(used[:-1], used[1:])
        ]
        add, lo, hi = min(costs)
        if added + add > budget:
            break
        slot = np.where(slot == lo, hi, slot)
        counts_per_class[hi] += counts_per_class[lo]
        counts_per_class[lo] = 0
        added += add
    return slot, caps


def gather_bucket(
    features: Features,
    labels: np.ndarray,
    offsets: np.ndarray,
    weights: np.ndarray,
    row_indices: np.ndarray,
    columns: np.ndarray | None = None,
) -> Batch:
    """Materialize one bucket's (k, C, …) batched Batch from host columns.

    Padded slots (row index -1) get weight 0 — inert in the objective
    (`GLMObjective._weighted` forces their loss/grad contributions to 0) —
    and ZEROED features (everything that reads the raw feature values,
    e.g. per-entity column-frequency counts, must not see a phantom copy
    of row 0). ``columns`` (subspace projection: per-entity (k, p) column
    maps) gathers the dense features to width p ON HOST, before the
    device upload pays for the full width. With sparse features and
    ``columns``, ``features.indices`` are taken to be LOCAL already (slots
    of each row's entity's map, ``EntityIndexMap.local``) and the bucket
    comes back as lanes of ``LocalSparseBatch`` at width p.
    """
    return jax.tree.map(
        jnp.asarray,
        gather_bucket_host(features, labels, offsets, weights, row_indices, columns),
    )


def gather_bucket_host(
    features: Features,
    labels: np.ndarray,
    offsets: np.ndarray,
    weights: np.ndarray,
    row_indices: np.ndarray,
    columns: np.ndarray | None = None,
) -> Batch:
    """``gather_bucket`` with its arrays still on the host (numpy leaves in
    the same containers): what ``prepare_buckets`` pads and then puts where
    the lanes belong, a mesh's devices a slice each."""
    idx = np.maximum(row_indices, 0)
    mask = (row_indices >= 0).astype(np.float32)
    lab = np.asarray(labels)[idx] * mask
    off = np.asarray(offsets)[idx] * mask
    wgt = np.asarray(weights)[idx] * mask
    if isinstance(features, DenseFeatures):
        X = np.asarray(features.X)[idx] * mask[:, :, None]  # (k, C, d)
        if columns is not None:
            X = np.take_along_axis(X, columns[:, None, :], axis=2)
        return DenseBatch(X=X, labels=lab, offsets=off, weights=wgt)
    ind = np.asarray(features.indices)[idx]  # (k, C, nnz)
    val = np.asarray(features.values)[idx] * mask[..., None]
    if columns is not None:
        k = len(row_indices)
        return LocalSparseBatch(
            indices=np.where(val != 0, ind, 0).reshape(k, -1).astype(np.int32),
            values=val.reshape(k, -1),
            labels=lab,
            offsets=off,
            weights=wgt,
            num_features=int(columns.shape[1]),
        )
    return SparseBatch(
        indices=ind,
        values=val,
        labels=lab,
        offsets=off,
        weights=wgt,
        num_features=features.num_features,
    )
