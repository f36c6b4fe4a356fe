"""Feature projectors for random-effect coordinates.

Reference parity: ``photon-api::ml.projector.*`` (SURVEY.md §2.2) —
``IndexMapProjection`` (per-entity: drop features the entity never saw,
train in its own subspace, map coefficients back) and ``RandomProjection``
(``ProjectionMatrix``/``ProjectionMatrixBroadcast``: one shared Gaussian
matrix per coordinate).

TPU-native redesign:
- **Per-entity subspace** (the index-map projection): instead of per-entity
  ragged column sets, each bucket gets a fixed-width column map
  ``columns (k, p)`` holding every entity's top-``p`` most-frequent feature
  columns; bucket features are gathered to ``(k, C, p)``, solved at width
  ``p``, and coefficients scattered back into the dense ``(E, d)`` matrix.
  ``p`` is derived from the reference's ``numFeaturesToSamplesRatioUpperBound``
  knob: p = min(d, ceil(ratio · C)) per bucket. One gather at prepare time,
  zero ragged shapes, and the MXU sees (C, p) instead of (C, d) matmuls.
- **Per-entity index map for sparse shards** (``sparse_index_map``): a
  ``SparseFeatures`` random effect always trains each entity in the
  subspace of the columns its own rows touch, with no switch: the map is
  built on the host once (one sort of the shard's (entity, column) keys),
  every nonzero gets its LOCAL index in its entity's sorted support, and
  widths sit on a ladder of powers of two from 128 so that few (capacity,
  width) geometries compile. Exact for L2 at zero: a column an entity
  never saw receives only the penalty and stays at 0.
- **Random projection**: one ``(d, p)`` Gaussian matrix per coordinate,
  applied to the shard features ONCE at prepare time (a single MXU matmul);
  trained coefficients map back exactly via ``w = P @ w_p`` (scores are
  identical: (XP)·w_p = X·(P w_p)), so the stored model stays in the
  original feature space and scoring is unchanged.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

Array = jnp.ndarray

# Per-entity feature projection (PHOTON_RE_PROJECT): "0" (default) keeps
# the full-width random-effect solves bit-for-bit. "support" derives each
# capacity class's active-column set from the GLOBAL per-entity column
# activity (the same global-bincount discipline as ``capacity_classes`` /
# ``placement_atoms`` — deterministic pure-host arithmetic, identical on
# every process) and solves every bucket of that class in the d_e-wide
# subspace, scattering coefficients back to full d for scoring — exact
# for L2-at-zero regularization (inactive columns receive only the
# penalty and stay at their zero init). "hash" additionally folds any
# class whose support still exceeds PHOTON_RE_PROJECT_DIM down to that
# cap with signed feature hashing — the genuine model change, gated by
# the quality-parity protocol like the int8 rung. Like every fleet knob
# it must be set identically on all processes.
RE_PROJECT = "0"

# Signed-hash target width (PHOTON_RE_PROJECT_DIM, power of two >= 2):
# the per-class cap the "hash" mode folds over-wide supports down to.
# The last slot is reserved for the intercept (framework convention:
# intercept at the last column), so hashed classes solve at exactly this
# width with the intercept exempt from collisions.
RE_PROJECT_DIM = 32

_RE_PROJECT_MODES = ("0", "support", "hash")


def re_project_mode() -> str:
    """``PHOTON_RE_PROJECT`` (env > module global), strict membership
    parse — an unknown mode fails loudly instead of silently benching
    the full-width solve."""
    env = os.environ.get("PHOTON_RE_PROJECT")
    raw = env if (env is not None and env != "") else RE_PROJECT
    mode = str(raw)
    if mode not in _RE_PROJECT_MODES:
        raise ValueError(
            f"PHOTON_RE_PROJECT must be one of {_RE_PROJECT_MODES}, "
            f"got {mode!r}"
        )
    return mode


def re_project_dim() -> int:
    """``PHOTON_RE_PROJECT_DIM`` (env > module global), strict int parse
    requiring a power of two >= 2 (the hash fold reserves the last slot
    for the intercept, so width 1 would leave no hash range)."""
    env = os.environ.get("PHOTON_RE_PROJECT_DIM")
    raw = env if (env is not None and env != "") else RE_PROJECT_DIM
    m = int(raw)
    if m < 2 or (m & (m - 1)) != 0:
        raise ValueError(
            f"PHOTON_RE_PROJECT_DIM must be a power of two >= 2, got {m}"
        )
    return m


def subspace_columns(
    X: np.ndarray,  # (k, C, d) host bucket features (zeroed padded slots)
    ratio: float,
    intercept_index: int | None,
) -> np.ndarray | None:
    """Per-entity subspace column maps for one bucket, shared by the
    in-memory ``prepare_buckets`` and the streamed trainer (one copy of
    the p formula + intercept convention): p = min(d, ceil(ratio · C));
    returns None when that keeps full width. Columns sort ascending, so a
    (required-last-column) intercept lands at slot p-1."""
    d = X.shape[-1]
    capacity = X.shape[1]
    p = min(d, max(1, int(np.ceil(ratio * capacity))))
    if p >= d:
        return None
    if intercept_index is not None and intercept_index != d - 1:
        raise ValueError(
            "subspace projection requires the intercept at the last "
            "column (framework convention)"
        )
    return entity_top_columns(X, p, always_include=intercept_index)


def entity_top_columns(
    X: np.ndarray,  # (k, C, d) bucket features (zero-padded slots)
    p: int,
    always_include: int | None = None,
) -> np.ndarray:
    """Each entity's ``p`` most-frequent (by nonzero count, ties → lower
    index) feature columns, sorted ascending. ``always_include`` (the
    intercept) is forced into every entity's set."""
    counts = (X != 0).sum(axis=1).astype(np.int64)  # (k, d)
    if always_include is not None:
        counts[:, always_include] = np.iinfo(np.int64).max
    # stable top-p: sort by (-count, index)
    order = np.argsort(-counts, axis=1, kind="stable")[:, :p]  # (k, p)
    return np.sort(order, axis=1)


# The narrowest rung of the subspace width ladder: one vreg's lanes.
SUBSPACE_MIN_WIDTH = 128


def width_rungs(widths: np.ndarray, num_features: int) -> np.ndarray:
    """The ladder rung of each support width: the smallest power of two
    >= the width, from ``SUBSPACE_MIN_WIDTH`` up, capped at the shard's
    full width (which need not be a power of two)."""
    w = np.maximum(np.asarray(widths, np.int64), 1)
    pow2 = np.int64(1) << np.ceil(np.log2(w)).astype(np.int64)
    return np.minimum(
        np.maximum(pow2, SUBSPACE_MIN_WIDTH), int(num_features)
    ).astype(np.int64)


@dataclass(frozen=True)
class EntityIndexMap:
    """Per-entity index maps of one sparse shard (parity:
    ``IndexMapProjection``, one per entity of a random-effect dataset).

    Entity ``e``'s support is ``columns[starts[e]:starts[e + 1]]``, sorted
    ascending, WITHOUT the intercept column; an intercept, when the shard
    has one, is in every support and always takes the last slot of the
    entity's rung (the framework's intercept-last convention, kept in the
    subspace). ``local`` holds, for every nonzero of every mapped row, its
    slot in its entity's map (0 beside a zero value: inert).
    """

    num_features: int
    intercept_index: int | None
    starts: np.ndarray  # (E + 1,) offsets into ``columns``
    columns: np.ndarray  # (sum of supports,) int32 original column ids
    local: np.ndarray  # (n, nnz) int32 slots
    widths: np.ndarray  # (E,) support width p_e, the intercept included
    rungs: np.ndarray  # (E,) the ladder rung P_e >= p_e (0: no mapped row)

    def support(self, entity: int) -> np.ndarray:
        """Original column ids entity ``entity`` trains on, ascending."""
        cols = self.columns[self.starts[entity]:self.starts[entity + 1]]
        if self.intercept_index is None or not self.rungs[entity]:
            return cols
        return np.append(cols, np.int32(self.intercept_index))

    def bucket_columns(self, entity_ids: np.ndarray, width: int) -> np.ndarray:
        """The ``(k, width)`` column map of one bucket: each entity's
        support from slot 0, the intercept in slot ``width - 1``, and
        ``num_features`` (one past the last column: gathers read 0 there
        and scatters drop it) in the slots between."""
        ents = np.asarray(entity_ids, np.int64)
        lo = self.starts[ents]
        n_cols = self.starts[ents + 1] - lo
        out = np.full((len(ents), int(width)), self.num_features, np.int32)
        slot = np.arange(int(n_cols.sum())) - np.repeat(
            np.cumsum(n_cols) - n_cols, n_cols
        )
        out[np.repeat(np.arange(len(ents)), n_cols), slot] = self.columns[
            np.repeat(lo, n_cols) + slot
        ]
        if self.intercept_index is not None:
            out[:, -1] = self.intercept_index
        return out


def sparse_index_map(
    indices: np.ndarray,  # (n, nnz) column ids, pad (0, 0.0)
    values: np.ndarray,  # (n, nnz)
    row_entity: np.ndarray,  # (n,) the entity a row trains, -1 for none
    num_entities: int,
    num_features: int,
    intercept_index: int | None = None,
) -> EntityIndexMap:
    """Build every entity's index map with ONE sort of the (entity, column)
    keys of the mapped rows' nonzeros. Deterministic: a support depends on
    its entity's rows alone, not on the entity's id or the rows' order."""
    if intercept_index is not None and intercept_index != num_features - 1:
        raise ValueError(
            "a sparse random effect requires the intercept at the last "
            "column (framework convention)"
        )
    idx = np.asarray(indices)
    ent = np.asarray(row_entity, np.int64)
    d = int(num_features)
    live = (np.asarray(values) != 0) & (ent >= 0)[:, None]
    is_icpt = np.zeros_like(live)
    if intercept_index is not None:
        is_icpt = live & (idx == intercept_index)
        live &= ~is_icpt
    keys = (ent[:, None] * d + idx)[live]
    uniq, inverse = np.unique(keys, return_inverse=True)
    owner = uniq // d
    starts = np.zeros(num_entities + 1, np.int64)
    np.cumsum(np.bincount(owner, minlength=num_entities), out=starts[1:])
    mapped = np.bincount(ent[ent >= 0], minlength=num_entities) > 0
    widths = np.diff(starts) + (mapped if intercept_index is not None else 0)
    rungs = np.where(mapped, width_rungs(widths, d), 0)
    local = np.zeros(idx.shape, np.int32)
    local[live] = inverse - starts[owner[inverse]]
    if intercept_index is not None:
        local[is_icpt] = np.broadcast_to(
            (rungs - 1)[np.maximum(ent, 0)][:, None], idx.shape
        )[is_icpt]
    return EntityIndexMap(
        num_features=d, intercept_index=intercept_index, starts=starts,
        columns=(uniq % d).astype(np.int32), local=local,
        widths=widths.astype(np.int64), rungs=rungs.astype(np.int64),
    )


# Knuth multiplicative hash constants — any fixed mixing function of the
# ORIGINAL column index works; what matters is that every process computes
# the identical (slot, sign) pair from pure arithmetic on the index alone.
_HASH_MULT = np.uint64(2654435761)
_SIGN_MULT = np.uint64(0x9E3779B1)


@dataclass(frozen=True)
class ClassProjection:
    """One capacity class's projection spec (``PHOTON_RE_PROJECT``).

    ``columns`` is the class's support — the ascending original-column
    indices any entity of this capacity activates anywhere in the fleet
    (global union, so the spec is process-count-independent). Support
    mode solves at width ``len(columns)``; hash mode additionally folds
    those columns onto ``hash_dim`` slots with signs (``hash_slots`` /
    ``hash_signs``), reserving slot ``hash_dim - 1`` for the intercept.
    Derived once per class by ``projection_ladder`` and shared by every
    bucket of the class — same capacity ⇒ same class ⇒ same spec, which
    is what keeps the spec safe under same-geometry launch fusion."""

    capacity: int
    full_dim: int
    columns: np.ndarray  # (d_e,) int64, ascending
    hash_slots: np.ndarray | None = None  # (d_e,) int64 in [0, hash_dim)
    hash_signs: np.ndarray | None = None  # (d_e,) float32, ±1
    hash_dim: int | None = None

    @property
    def support_dim(self) -> int:
        return int(len(self.columns))

    @property
    def dim(self) -> int:
        """The width the solver actually runs at (and the per-lane
        combine-segment width — the byte-denominated planners' unit)."""
        return int(self.hash_dim) if self.hash_dim is not None else self.support_dim

    def hash_matrix(self) -> np.ndarray:
        """The signed fold as a dense (d_e, m) float32 matrix S with
        ``S[j, hash_slots[j]] = hash_signs[j]`` — one tiny matmul folds
        features/warm-starts and its transpose expands coefficients
        (score-preserving on the support: (X S) w_h = X (S w_h))."""
        if self.hash_dim is None:
            raise ValueError("hash_matrix: spec has no hash fold")
        S = np.zeros((self.support_dim, int(self.hash_dim)), np.float32)
        S[np.arange(self.support_dim), self.hash_slots] = self.hash_signs
        return S


def _hash_fold(
    columns: np.ndarray, hash_dim: int, intercept_index: int | None
) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic (slot, sign) per support column: Knuth-mix the
    ORIGINAL column index into ``[0, m-1)`` (slot ``m-1`` is reserved so
    the intercept never collides); signs come from an independent mix.
    Pure arithmetic on the indices — identical on every process."""
    cols = np.asarray(columns, np.uint64)
    m = int(hash_dim)
    mixed = (cols * _HASH_MULT) % np.uint64(2**32)
    slots = (mixed % np.uint64(m - 1)).astype(np.int64)
    signs = np.where(
        ((cols * _SIGN_MULT) >> np.uint64(16)) & np.uint64(1),
        np.float32(1.0),
        np.float32(-1.0),
    ).astype(np.float32)
    if intercept_index is not None:
        at = np.flatnonzero(np.asarray(columns) == intercept_index)
        slots[at] = m - 1
        signs[at] = 1.0
    return slots, signs


def projection_ladder(
    capacities: tuple[int, ...] | list[int],
    activity: np.ndarray,  # (n_classes, d) nonzero-row counts per column
    full_dim: int,
    mode: str,
    hash_dim: int,
    intercept_index: int | None,
) -> dict[int, ClassProjection | None]:
    """The per-class projection specs (``PHOTON_RE_PROJECT``), keyed by
    bucket capacity. ``activity[i, j]`` counts the rows with a nonzero
    in column ``j`` over ALL entities of capacity class ``i`` —
    fleet-global (callers allreduce before calling), so like the
    capacity ladder itself the projection ladder is deterministic
    pure-host arithmetic on globally-identical inputs: every process
    derives the identical spec with zero extra communication.

    A class whose support is the full width maps to ``None`` — no
    projection, the untouched (bitwise) full-width path. An empty
    support (a class whose rows are all-zero) keeps one forced column
    (the intercept if present, else column 0) so the solve geometry
    stays valid; the lone coefficient stays at its zero init. ``hash``
    mode folds any support wider than ``hash_dim`` down to it."""
    if mode not in ("support", "hash"):
        raise ValueError(f"projection_ladder: unexpected mode {mode!r}")
    if intercept_index is not None and intercept_index != full_dim - 1:
        raise ValueError(
            "feature projection requires the intercept at the last "
            "column (framework convention)"
        )
    activity = np.asarray(activity)
    if activity.shape != (len(capacities), full_dim):
        raise ValueError(
            f"projection_ladder: activity shape {activity.shape} != "
            f"({len(capacities)}, {full_dim})"
        )
    ladder: dict[int, ClassProjection | None] = {}
    for i, cap in enumerate(capacities):
        cols = np.flatnonzero(activity[i] > 0).astype(np.int64)
        if intercept_index is not None and intercept_index not in cols:
            cols = np.sort(np.append(cols, np.int64(intercept_index)))
        if len(cols) == 0:
            cols = np.asarray([intercept_index if intercept_index is not None else 0], np.int64)
        if len(cols) >= full_dim:
            ladder[int(cap)] = None
            continue
        spec = ClassProjection(
            capacity=int(cap), full_dim=int(full_dim), columns=cols
        )
        if mode == "hash" and len(cols) > hash_dim:
            slots, signs = _hash_fold(cols, hash_dim, intercept_index)
            spec = ClassProjection(
                capacity=int(cap),
                full_dim=int(full_dim),
                columns=cols,
                hash_slots=slots,
                hash_signs=signs,
                hash_dim=int(hash_dim),
            )
        ladder[int(cap)] = spec
    return ladder


def class_activity(
    X: np.ndarray,  # (n, d) host feature matrix
    capacities: tuple[int, ...] | list[int],
    row_indices: list[np.ndarray],  # per-bucket (k, C) row maps, -1 pad
) -> tuple[tuple[int, ...], np.ndarray]:
    """Per-capacity-class column-activity counts from bucketed row maps
    (the in-memory consumer's half of the ladder input): returns
    ``(classes, activity)`` where ``classes`` is the ascending distinct
    capacity set and ``activity[i, j]`` counts this process's rows with
    a nonzero in column ``j`` over all buckets of capacity
    ``classes[i]``. Data-parallel callers hold the full replicated
    batch, so the counts are already global; sharded callers allreduce
    before building the ladder."""
    X = np.asarray(X)
    d = X.shape[-1]
    classes = tuple(sorted(set(int(c) for c in capacities)))
    pos = {c: i for i, c in enumerate(classes)}
    activity = np.zeros((len(classes), d), np.int64)
    for cap, rows in zip(capacities, row_indices):
        r = rows[rows >= 0]
        if len(r):
            activity[pos[int(cap)]] += (X[r] != 0).sum(axis=0).astype(np.int64)
    return classes, activity


@dataclass(frozen=True)
class RandomProjector:
    """Shared Gaussian projection for one coordinate (parity:
    ``ProjectionMatrix`` + ``ProjectionMatrixBroadcast`` — here the matrix
    is just a device array; pjit replicates it, no broadcast step)."""

    matrix: Array  # (d, p), entries ~ N(0, 1/p)

    @classmethod
    def build(cls, num_features: int, projected_dim: int, seed: int = 0) -> "RandomProjector":
        rng = np.random.default_rng(seed)
        P = rng.normal(scale=1.0 / np.sqrt(projected_dim),
                       size=(num_features, projected_dim)).astype(np.float32)
        return cls(matrix=jnp.asarray(P))

    @property
    def projected_dim(self) -> int:
        return self.matrix.shape[1]

    def project_features(self, X: Array) -> Array:
        """(…, d) → (…, p): one MXU matmul."""
        return X @ self.matrix

    def coefficients_to_original(self, w_projected: Array) -> Array:
        """(…, p) → (…, d), exactly score-preserving: (XP)w_p = X(Pw_p)."""
        return w_projected @ self.matrix.T
