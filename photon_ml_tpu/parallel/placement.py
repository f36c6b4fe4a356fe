"""Skew-aware shard placement for entity-sharded random-effect solves.

The random-effect phase is embarrassingly parallel over entities, so the
scale-out question is pure PLACEMENT: which process/chip owns which
entities (streamed path) or which whole buckets (in-memory path). The
naive rule — ``entity_id % P`` — balances entity COUNT, but under Zipf
traffic the head entities carry orders of magnitude more rows than the
tail, so one shard ends up solving (and receiving, every visit, through
the offset/score exchanges) a large multiple of the mean row load while
the others idle.

``plan_shard_placement`` balances by Σ per-entity rows instead: LPT
(longest-processing-time) greedy — heaviest placement unit first, each
onto the currently-lightest shard. Units may be GROUPS of items that
must land on one shard together: the same bookkeeping PR-5's
``plan_fusion_groups`` uses to fuse same-geometry bucket launches also
drives group-atomic placement, so the launch fusion keeps working per
shard (a fusion group split across shards could no longer concatenate
into one launch anywhere).

Everything here is deterministic pure-host arithmetic on inputs that are
identical on every process (globally-reduced row counts), so every
process computes the SAME plan with zero extra communication.

Knob: ``PHOTON_RE_SHARD`` (env > module global, strict int parse, read
at call time — the knob idiom). 0 (default) keeps today's
modular owner rule and exchange schedule bit-for-bit; 1 enables
skew-aware placement and the overlapped exchange schedule in the
consumers that opt in (``game/streaming.py``, ``game/random_effect.py``).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Sequence

import numpy as np

# Entity-sharded random-effect solves (placement + overlapped exchange).
# 0 = the pre-sharding schedule bit-for-bit (modular owners, blocking
# exchanges); 1 = skew-aware placement + overlapped P2P exchange.
RE_SHARD = 0

# Sub-bucket placement atoms (PHOTON_RE_SPLIT): 0 (default) keeps the
# bucket-atomic placement bit-for-bit — a whole capacity class is one
# placement unit, so the Zipf tail class pins its owner's combine
# segment and solve load at O(E) no matter how good LPT is. A positive
# value N is the target ATOM COUNT of the split rule
# (``game.data.placement_atoms``): any capacity class whose total row
# weight exceeds total_rows / N is split into contiguous sub-bucket
# atoms of at most that weight (each >= 2 entities — the batched-XLA
# lane floor), so the max-owner load is bounded by
# total/P + max-atom-weight instead of the heaviest class. The rule
# reads ONLY the global bincount and the knob — never the process
# count — so every process (and the single-process reference) derives
# the identical sub-bucket ladder with zero extra communication. Like
# every fleet knob it must be set identically on all processes.
RE_SPLIT = 0

# Device-granularity placement (PHOTON_RE_DEVICE_SPLIT): 0 (default)
# keeps the single-unit-per-process schedule bit-for-bit — a process
# solves all of its owned buckets on its default device, exactly the
# PR-13/PR-15 dispatch. 1 adds a SECOND LPT level: each process's owned
# placement atoms are assigned to its LOCAL devices
# (``plan_device_placement``), the consumers stage each owned bucket on
# its assigned device, and same-device buckets keep fusing through the
# existing permutation bookkeeping (device placement is fusion-group /
# atom atomic, so launch geometry — and bits — are preserved vs the
# single-device schedule; devices execute their queued launches
# asynchronously, which is the intra-host win). Results re-enter the
# canonical matrix through a device-local combine (permutation-only row
# copies) BEFORE the process-level PHOTON_RE_COMBINE transport, which
# is unchanged. Like every fleet knob it must be set identically on all
# processes.
RE_DEVICE_SPLIT = 0

# Placement weight axis (PHOTON_RE_SPLIT_WEIGHT): "rows" (default)
# balances Σ per-entity ROWS — the solve-compute axis, bit-for-bit the
# PR-13 rule. "bytes" balances per-lane WIRE bytes instead (each active
# entity contributes one coefficient/variance/diag segment row to the
# combine, so lane bytes are proportional to LANE COUNT, not row
# count), and the split rule caps BOTH axes so atoms stay bounded in
# compute too. The r09 capture shows why the axis exists: row-balanced
# placement reached 1.04x row balance while the MAX owner's combine
# bytes ran ~2x the mean (the Zipf tail's many tiny entities all carry
# the same per-lane segment cost no matter how few rows they have).
RE_SPLIT_WEIGHT = "rows"

# Telemetry-driven re-planning (PHOTON_RE_REPLAN_IMBALANCE): when the
# MEASURED per-process random-effect solve wall of a descent iteration
# is more imbalanced than this max/mean ratio, the streamed trainer
# re-runs the LPT planner over measured (wall-calibrated) entity costs
# and migrates entities at the next visit boundary — the PR-11
# peer-loss re-plan machinery driven by a telemetry trigger instead of
# a PeerLost. 0 (default) = off; meaningful values are > 1 (e.g. 1.5 =
# re-plan when the slowest shard runs 50% over the mean).
REPLAN_IMBALANCE = 0.0


def re_shard_enabled() -> bool:
    """``PHOTON_RE_SHARD`` (env > module global), strict parse like the
    sibling RE knobs — a typo fails loudly instead of silently benching
    the default schedule."""
    env = os.environ.get("PHOTON_RE_SHARD")
    if env is not None and env != "":
        return int(env) != 0
    return int(RE_SHARD) != 0


def re_split_factor() -> int:
    """``PHOTON_RE_SPLIT`` (env > module global), strict int parse like
    the sibling RE knobs — a typo fails loudly instead of silently
    benching bucket-atomic placement. <= 0 disables (the knob
    convention); a positive value is the split rule's target atom
    count (the per-atom weight cap is total_rows / value)."""
    env = os.environ.get("PHOTON_RE_SPLIT")
    raw = env if (env is not None and env != "") else RE_SPLIT
    return max(int(raw), 0)


def re_device_split_enabled() -> bool:
    """``PHOTON_RE_DEVICE_SPLIT`` (env > module global), strict parse
    like the sibling RE knobs — a typo fails loudly instead of silently
    benching the single-device-per-process schedule."""
    env = os.environ.get("PHOTON_RE_DEVICE_SPLIT")
    if env is not None and env != "":
        return int(env) != 0
    return int(RE_DEVICE_SPLIT) != 0


_SPLIT_WEIGHT_MODES = ("rows", "bytes")


def re_split_weight() -> str:
    """``PHOTON_RE_SPLIT_WEIGHT`` (env > module global), strict
    membership parse — an unknown axis name fails loudly instead of
    silently benching the row-weighted rule. ``rows`` (default)
    balances solve compute; ``bytes`` balances combine wire bytes
    (per-lane segment rows), with the split rule capping both axes."""
    env = os.environ.get("PHOTON_RE_SPLIT_WEIGHT")
    raw = env if (env is not None and env != "") else RE_SPLIT_WEIGHT
    mode = str(raw)
    if mode not in _SPLIT_WEIGHT_MODES:
        raise ValueError(
            f"PHOTON_RE_SPLIT_WEIGHT must be one of "
            f"{_SPLIT_WEIGHT_MODES}, got {mode!r}"
        )
    return mode


def replan_imbalance_threshold() -> float:
    """``PHOTON_RE_REPLAN_IMBALANCE`` (env > module global), strict
    float parse; <= 0 disables (the knob convention). Must be set
    consistently fleet-wide — the re-plan decision is computed from
    allgathered walls on every process and a knob mismatch would
    desync the collectives."""
    env = os.environ.get("PHOTON_RE_REPLAN_IMBALANCE")
    raw = env if (env is not None and env != "") else REPLAN_IMBALANCE
    v = float(raw)
    return v if v > 0.0 else 0.0


@dataclass(frozen=True)
class PlacementPlan:
    """One placement decision: ``owner[i]`` is the shard of item ``i``
    (an entity, or a bucket, depending on the caller's granularity);
    ``loads[s]`` is shard ``s``'s Σ rows under the plan."""

    owner: np.ndarray  # (n_items,) int64
    loads: np.ndarray  # (num_shards,) float64
    num_shards: int

    @property
    def balance(self) -> float:
        """max shard load / mean shard load (1.0 = perfectly even).
        The skew metric the 1.15× acceptance bound is written against;
        0-load plans (no rows anywhere) read as perfectly balanced."""
        mean = float(self.loads.mean()) if len(self.loads) else 0.0
        if mean <= 0.0:
            return 1.0
        return float(self.loads.max()) / mean

    def owned_items(self, shard: int) -> np.ndarray:
        """Ascending item indices owned by ``shard``."""
        return np.flatnonzero(self.owner == shard)


def plan_shard_placement(
    row_counts: Sequence[float] | np.ndarray,
    num_shards: int,
    groups: Sequence[Sequence[int]] | None = None,
    skew_aware: bool = True,
) -> PlacementPlan:
    """Assign items to ``num_shards`` shards, balancing Σ ``row_counts``.

    ``groups`` lists index sets that must be CO-LOCATED (placement is
    group-atomic — e.g. PR-5 fusion groups, so same-geometry launch
    fusion keeps working inside each shard). Unlisted items place as
    singleton groups. ``skew_aware=True`` is LPT greedy: groups by total
    rows descending (ties: first item index ascending — deterministic),
    each onto the lightest shard so far (ties: lowest shard id).
    ``skew_aware=False`` is the naive baseline: round-robin by group
    order — the comparison arm the bench records.

    Deterministic: identical inputs produce the identical plan on every
    process (no RNG, no dict-order dependence).
    """
    counts = np.asarray(row_counts, np.float64)
    if counts.ndim != 1:
        raise ValueError(f"row_counts must be 1-D, got shape {counts.shape}")
    P = int(num_shards)
    if P < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    n = len(counts)
    if groups is None:
        group_list = [[i] for i in range(n)]
    else:
        group_list = [list(g) for g in groups]
        seen = np.zeros(n, bool)
        for g in group_list:
            for i in g:
                if not (0 <= i < n):
                    raise ValueError(f"group member {i} out of range [0, {n})")
                if seen[i]:
                    raise ValueError(f"item {i} appears in two groups")
                seen[i] = True
        # items not named by any group place as singletons, after the
        # explicit groups (stable: ascending index)
        group_list += [[i] for i in np.flatnonzero(~seen)]
    owner = np.zeros(n, np.int64)
    loads = np.zeros(P, np.float64)
    if P == 1 or n == 0:
        return PlacementPlan(owner=owner, loads=_add_loads(loads, counts, owner), num_shards=P)
    totals = [float(counts[g].sum()) for g in group_list]
    if skew_aware:
        # LPT: heaviest group first onto the lightest shard, via a heap
        # keyed (load, shard id) — O(G log P) where a per-group argmin
        # would be O(G·P) Python work (G = entity count on the streamed
        # path). Ties break exactly like np.argmin did: equal loads go
        # to the lowest shard id; the sort key ties break toward the
        # earliest group (its first member's index).
        import heapq

        order = sorted(
            range(len(group_list)),
            key=lambda gi: (-totals[gi], group_list[gi][0] if group_list[gi] else -1),
        )
        heap = [(0.0, s) for s in range(P)]
        for gi in order:
            load, s = heapq.heappop(heap)
            load += totals[gi]
            loads[s] = load
            heapq.heappush(heap, (load, s))
            for i in group_list[gi]:
                owner[i] = s
    else:
        for gi, g in enumerate(group_list):
            s = gi % P
            loads[s] += totals[gi]
            for i in g:
                owner[i] = s
    return PlacementPlan(owner=owner, loads=loads, num_shards=P)


def _add_loads(loads: np.ndarray, counts: np.ndarray, owner: np.ndarray) -> np.ndarray:
    np.add.at(loads, owner, counts)
    return loads


def plan_from_owner(
    owner: np.ndarray,
    row_counts: Sequence[float] | np.ndarray,
    num_shards: int,
) -> PlacementPlan:
    """Reconstruct a ``PlacementPlan`` from an existing owner map + row
    counts (the load definition lives HERE, next to the planner — the
    re-planner and the forced-map shard rebuild both need the old/forced
    plan's loads and must agree with ``plan_shard_placement``'s).

    Validates shape and range instead of silently truncating: an owner
    map that disagrees in length with the row counts, or that names a
    shard outside ``[0, num_shards)``, is a desynced plan (the exact
    failure the deterministic-replication design exists to prevent) and
    must fail loudly with the offending value."""
    owner = np.asarray(owner, np.int64)
    counts = np.asarray(row_counts, np.float64)
    P = int(num_shards)
    if len(owner) != len(counts):
        raise ValueError(
            f"plan_from_owner: owner map length {len(owner)} != "
            f"row_counts length {len(counts)} — the map and the counts "
            "must describe the same items"
        )
    if len(owner) and (owner.min() < 0 or owner.max() >= P):
        bad = owner[(owner < 0) | (owner >= P)][0]
        raise ValueError(
            f"plan_from_owner: owner value {int(bad)} outside "
            f"[0, {P}) — the map names a shard this plan does not have"
        )
    loads = _add_loads(np.zeros(P, np.float64), counts, owner)
    return PlacementPlan(owner=owner, loads=loads, num_shards=P)


def plan_entity_placement(
    entity_row_counts: np.ndarray, num_shards: int, skew_aware: bool = True
) -> PlacementPlan:
    """Entity-granularity placement (the streamed trainer's unit): each
    entity is one atom — all of an entity's rows live at its owner, the
    invariant every per-visit exchange and the per-entity solves rely
    on."""
    return plan_shard_placement(
        entity_row_counts, num_shards, groups=None, skew_aware=skew_aware
    )


def replan_excluding(
    plan: PlacementPlan,
    lost_shards: Sequence[int],
    row_counts: Sequence[float] | np.ndarray,
    survivors: Sequence[int],
    groups: Sequence[Sequence[int]] | None = None,
    skew_aware: bool = True,
) -> tuple[PlacementPlan, np.ndarray]:
    """Re-plan around lost shards (the peer-loss recovery step): run the
    SAME deterministic LPT planner over ``len(survivors)`` shards and
    return ``(new_plan, migrated)`` where ``new_plan.owner`` is in
    SURVIVOR-RANK space (rank = position in the sorted survivor list —
    the degraded group's effective indices) and ``migrated`` flags the
    items whose owner changed between the old plan (original shard ids,
    mapped through the survivor ranks) and the new one.

    Like the original plan, this is pure host arithmetic on globally-
    identical inputs (the allreduced row counts every process already
    holds), so all survivors compute the IDENTICAL new plan with zero
    extra communication — the property that lets recovery re-shard
    without a coordinator.

    With an EMPTY lost set the survivor set may EXPAND past the old
    plan's shard range — the elastic-rejoin signature: re-plan over the
    current shards plus a returned one the degraded plan never had. A
    joining shard has no old items, so every item it receives counts as
    migrated — exactly the entities the re-planner moves back. With a
    non-empty lost set (a genuine degrade) survivors outside the old
    range remain a desynced plan and fail loudly, naming the value."""
    survivors = sorted(int(s) for s in survivors)
    lost = {int(s) for s in lost_shards}
    if set(survivors) & lost:
        raise ValueError(
            f"survivors {survivors} and lost shards {sorted(lost)} overlap"
        )
    if not survivors:
        raise ValueError("no surviving shards to re-plan onto")
    bound = None if not lost else int(plan.num_shards)
    out_of_range = [
        s for s in survivors
        if s < 0 or (bound is not None and s >= bound)
    ]
    if out_of_range:
        raise ValueError(
            f"replan_excluding: survivor {out_of_range[0]} outside the "
            f"old plan's shard range [0, {int(plan.num_shards)}) — the "
            "survivor list and the plan disagree about the topology "
            "(expansion past the range is legal only with an empty "
            "lost set, the rejoin signature)"
        )
    new_plan = plan_shard_placement(
        row_counts, len(survivors), groups=groups, skew_aware=skew_aware
    )
    # old owner (original shard id) -> survivor rank, lost -> -1; sized
    # for an EXPANDED survivor set too (rejoin: survivors the old plan
    # never had simply map no old items)
    rank_of = np.full(
        max(int(plan.num_shards), survivors[-1] + 1), -1, np.int64
    )
    for r, s in enumerate(survivors):
        rank_of[s] = r
    old_ranks = rank_of[plan.owner]
    migrated = old_ranks != new_plan.owner
    return new_plan, migrated


def plan_device_placement(
    row_counts: Sequence[float] | np.ndarray,
    owner: np.ndarray,
    shard: int,
    num_devices: int,
    groups: Sequence[Sequence[int]] | None = None,
    skew_aware: bool = True,
) -> tuple[np.ndarray, PlacementPlan]:
    """The SECOND placement level (``PHOTON_RE_DEVICE_SPLIT``): assign
    the items ``shard`` owns under the process-level ``owner`` map to
    its ``num_devices`` LOCAL devices, with the same deterministic LPT
    rule (and the same group-atomicity contract: ``groups`` lists index
    sets — fusion groups on an unsplit prep — that must stay on ONE
    device, so same-device launch fusion reproduces the single-device
    launch geometry exactly). Returns ``(device, plan)`` where
    ``device[i]`` is item ``i``'s local device ordinal for owned items
    and ``-1`` elsewhere, and ``plan`` is the device-space sub-plan
    (its ``balance`` is the ``re_shard.device_balance`` gauge).

    Group members must be wholly owned or wholly un-owned by ``shard``
    — the process-level plan is group-atomic too, so a straddling group
    is a desynced plan and fails loudly. Like the first level this is
    pure host arithmetic: recomputing it from a SURVIVOR topology's
    owner map (after an in-place degrade re-plan) needs no extra
    communication."""
    counts = np.asarray(row_counts, np.float64)
    owner = np.asarray(owner, np.int64)
    if len(owner) != len(counts):
        raise ValueError(
            f"plan_device_placement: owner map length {len(owner)} != "
            f"row_counts length {len(counts)}"
        )
    D = int(num_devices)
    if D < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")
    owned = np.flatnonzero(owner == int(shard))
    device = np.full(len(owner), -1, np.int64)
    # owned item index -> dense position in the sub-problem
    pos = np.full(len(owner), -1, np.int64)
    pos[owned] = np.arange(len(owned))
    sub_groups = None
    if groups is not None:
        sub_groups = []
        for g in groups:
            g = list(g)
            mine = [i for i in g if owner[i] == int(shard)]
            if mine and len(mine) != len(g):
                raise ValueError(
                    "plan_device_placement: group "
                    f"{g} straddles the owner boundary of shard "
                    f"{int(shard)} — the process-level plan is "
                    "group-atomic, so a straddling group is a desynced "
                    "plan"
                )
            if mine:
                sub_groups.append([int(pos[i]) for i in mine])
    plan = plan_shard_placement(
        counts[owned], D, groups=sub_groups, skew_aware=skew_aware
    )
    device[owned] = plan.owner
    return device, plan


def record_device_placement_metrics(
    plan: PlacementPlan, prefix: str = "re_shard"
) -> None:
    """Publish the device-level sub-plan's gauges:
    ``re_shard.device_balance`` (max/mean over THIS process's device
    loads — the intra-host twin of ``re_shard.balance``),
    ``re_shard.devices``, and per-device loads
    ``re_shard.device_rows.<d>`` (the per-device rows ``report fleet``
    renders). Pure gauges, published per process like the level-1
    metrics."""
    from photon_ml_tpu.obs.metrics import REGISTRY

    REGISTRY.gauge_set(f"{prefix}.device_balance", plan.balance)
    REGISTRY.gauge_set(f"{prefix}.devices", float(plan.num_shards))
    for d in range(plan.num_shards):
        REGISTRY.gauge_set(
            f"{prefix}.device_rows.{d}", float(plan.loads[d])
        )


def measured_entity_costs(
    entity_row_counts: np.ndarray,
    entity_owner: np.ndarray,
    shard_walls: np.ndarray,
) -> np.ndarray:
    """Per-entity MEASURED costs for a telemetry-driven re-plan: each
    entity's row count scaled by its current owner's measured
    seconds-per-row (``wall_p / Σ rows owned by p``). Entities living
    on a shard that measured slow cost proportionally more, so the LPT
    re-plan spreads them off it — row counts alone would reproduce the
    plan that produced the imbalance. Shards with no rows (or a zero
    wall: clock resolution, or a shard that did no solve work) fall
    back to the mean measured rate, keeping their entities
    row-proportional instead of free (a zero cost would make LPT dump
    every such entity onto one shard).

    Deterministic pure-host arithmetic on globally-identical inputs
    (allreduced row counts, allgathered walls) — every process computes
    the IDENTICAL costs with zero extra communication, the same
    property the original plan and ``replan_excluding`` rely on."""
    counts = np.asarray(entity_row_counts, np.float64)
    owner = np.asarray(entity_owner, np.int64)
    walls = np.asarray(shard_walls, np.float64)
    P = len(walls)
    if len(owner) != len(counts):
        raise ValueError(
            f"measured_entity_costs: owner map length {len(owner)} != "
            f"row_counts length {len(counts)}"
        )
    loads = np.zeros(P, np.float64)
    np.add.at(loads, owner, counts)
    rate = np.zeros(P, np.float64)
    ok = (loads > 0) & (walls > 0)
    rate[ok] = walls[ok] / loads[ok]
    fallback = float(rate[ok].mean()) if ok.any() else 1.0
    rate[~ok] = fallback
    return counts * rate[owner]


def record_projection_metrics(
    lane_dims: Sequence[tuple[int, int]],
    full_dim: int,
    prefix: str = "re_project",
) -> None:
    """Publish the feature-projection payload gauges
    (``PHOTON_RE_PROJECT``): ``re_project.mean_ratio`` — the
    lane-weighted mean solved width over the full width
    (Σ lanes·d_e / Σ lanes·d, the fraction of every byte-denominated
    cost the projection keeps) — and ``re_project.dims_saved_bytes`` —
    the float32 coefficient-row bytes one full combine pass no longer
    ships (Σ lanes·(d − d_e)·4). ``lane_dims`` is one ``(lanes,
    solved_width)`` pair per bucket this process solves. Both consumers
    (in-memory prepare, streamed shard build) publish through HERE so
    the gauge definition can't drift; callers only publish when the
    projection is active, keeping the gauges ABSENT — and the gate tier
    silent — on unprojected runs."""
    from photon_ml_tpu.obs.metrics import REGISTRY

    lanes_total = float(sum(k for k, _ in lane_dims))
    full = lanes_total * float(full_dim)
    kept = float(sum(k * d for k, d in lane_dims))
    ratio = kept / full if full > 0 else 1.0
    REGISTRY.gauge_set(f"{prefix}.mean_ratio", ratio)
    REGISTRY.gauge_set(f"{prefix}.dims_saved_bytes", (full - kept) * 4.0)


def record_placement_metrics(
    plan: PlacementPlan,
    shard: int | None = None,
    prefix: str = "re_shard",
    atoms: int | None = None,
    split_classes: int | None = None,
) -> None:
    """Publish the plan's load gauges through the PR-4 registry:
    ``re_shard.rows`` (THIS shard's Σ rows when ``shard`` is given, else
    the max — the number that bounds the critical path either way),
    ``re_shard.rows_max`` / ``rows_mean``, ``re_shard.balance``
    (max/mean), ``re_shard.shards``, and the placement-granularity
    gauges ``re_shard.atoms`` (how many independently-placeable units
    the plan distributed — defaults to the item count when the caller
    does not group) / ``re_shard.split_classes`` (how many capacity
    classes the ``PHOTON_RE_SPLIT`` rule split; 0 on an unsplit run).
    Pure gauges — safe to call from every process (each publishes its
    own view; only process 0's sink writes)."""
    from photon_ml_tpu.obs.metrics import REGISTRY

    loads = plan.loads
    rows_max = float(loads.max()) if len(loads) else 0.0
    rows_mean = float(loads.mean()) if len(loads) else 0.0
    own = rows_max if shard is None else float(loads[int(shard)])
    REGISTRY.gauge_set(f"{prefix}.rows", own)
    REGISTRY.gauge_set(f"{prefix}.rows_max", rows_max)
    REGISTRY.gauge_set(f"{prefix}.rows_mean", rows_mean)
    REGISTRY.gauge_set(f"{prefix}.balance", plan.balance)
    REGISTRY.gauge_set(f"{prefix}.shards", float(plan.num_shards))
    REGISTRY.gauge_set(
        f"{prefix}.atoms",
        float(len(plan.owner) if atoms is None else atoms),
    )
    REGISTRY.gauge_set(
        f"{prefix}.split_classes", float(split_classes or 0)
    )
