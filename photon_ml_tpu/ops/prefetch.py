"""Bounded-depth host-ingest prefetch pipeline + device-resident chunk cache.

The streamed consumers (``ops/streaming.py`` chunk objectives and scorer,
``game/streaming.py`` bucket ingest and visit scoring,
``supervised/cross_validation.py`` fold ingest) all share one critical-path
shape: a host-side *preparation* step per work item — feature slicing,
tile-COO layout build/cache lookup, host staging, ``device_put`` — followed
by device compute, repeated serially item after item, pass after pass.
Input-pipeline overlap (tf.data-style) is the standard fix: prepare item
``i+k`` on background worker threads while the device computes item ``i``.

Two invariants make the overlap safe to turn on by default:

- **Preparation only is reordered.** Workers produce *inputs* (host arrays
  staged, device buffers transferred); every kernel call and every
  accumulation happens on the consumer thread in the original item order,
  so all outputs are bitwise identical to the synchronous schedule (float
  summation order is untouched). ``PHOTON_PREFETCH_DEPTH=0`` restores the
  synchronous code path bit-for-bit (callers branch to their unchanged
  pre-prefetch loop).
- **Errors propagate, never deadlock.** A worker exception is re-raised in
  the consumer when that item's turn comes; remaining queued work is
  cancelled. The worker pool is process-wide and task-independent (no task
  ever waits on another task), so there is no lock-ordering to get wrong.

On top of the pipeline sits a process-wide **device-resident chunk cache**
(LRU, modeled on ``ops/tile_cache.py``): the streamed optimizers re-stage
the IDENTICAL chunk sequence on every objective pass — L-BFGS/TRON make
tens of passes over the same host arrays — so passes 2..N should replay
already-resident device buffers instead of re-paying ``device_put``. The
cache is byte-budgeted against ``device_hbm_budget_bytes`` (the same
query the streaming decision rule uses) and keyed by host-array STORAGE
identity (data pointer + layout, made safe by holding a reference to the
host array — a held array's address can never be reused by the allocator,
the ``_FP_MEMO`` argument in ``ops/streaming.py``). Entries evicted from
the device tier spill to a host-staged tier: the prepared host arrays are
retained so a later re-entry pays one ``device_put``, never a re-pack.
Cached host arrays are treated as immutable — the same contract the
tile-layout cache already imposes on indices/values. Lifecycle: entries
for discarded datasets age out by LRU as new traffic arrives (both tiers
are budget-bounded, so a dead objective can pin at most the budgets, not
grow without bound); a long-running driver that swaps datasets and wants
the memory back eagerly calls ``clear_cache()``.

Knobs (call-time-read discipline, like ``PHOTON_KERNEL_DTYPE``):
``PHOTON_PREFETCH_DEPTH`` (default 2; 0 = synchronous) and
``PHOTON_CHUNK_CACHE_BUDGET`` (bytes; default = the queried device
budget). The environment override is read at call time so a job's
environment and tests set them without import-order games.

Observability: the pipeline's stages report wall-seconds through
``utils/profiling.py`` stage counters — ``prefetch.host_pack_s`` (host
preparation inside workers), ``prefetch.device_put_s`` (transfer calls),
``prefetch.consumer_wait_s`` (time the CONSUMER blocked waiting for a
prepared item — the un-hidden remainder; ~0 means the pipeline fully hid
preparation) — so the overlap is observable, not asserted.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Iterator

import numpy as np

from photon_ml_tpu.obs.metrics import REGISTRY as _REGISTRY
from photon_ml_tpu.utils import profiling

# -- knobs (module globals read at CALL time; env override wins) ----------

PREFETCH_DEPTH = 2  # items prepared ahead of the consumer; 0 = synchronous
CHUNK_CACHE_BUDGET = None  # bytes; None = a minority fraction of HBM
# host-staged spill tier budget: evicted device entries keep their prepared
# host arrays up to this many bytes (re-entry pays a device_put, not a
# re-pack); numpy host RAM is the cheap tier
HOST_SPILL_BUDGET = None  # bytes; None = same as the device budget
# the chunk tier's default share of device HBM: deliberately a MINORITY
# fraction — the streamed paths run precisely when the dataset EXCEEDS the
# 0.75-fraction residency budget, so the cache must leave the bulk of HBM
# for kernels, coefficients and XLA scratch (the pre-cache path kept at
# most two chunks resident). When the chunk working set exceeds this, hits
# degrade toward plain per-pass transfers — never toward an allocation
# failure.
_DEFAULT_HBM_FRACTION = 0.25
# bytes_limit never changes mid-process: memoize the backend query so the
# per-array hot path (budget checks under the cache lock) costs a list
# read, not a device call
_device_budget_memo: list = []


def prefetch_depth() -> int:
    """The pipeline depth, read at CALL time (env wins over the module
    global, so a job's environment and tests set it without touching
    import order)."""
    env = os.environ.get("PHOTON_PREFETCH_DEPTH")
    if env is not None and env != "":
        return max(int(env), 0)
    return max(int(PREFETCH_DEPTH), 0)


def chunk_cache_budget_bytes() -> int:
    """Device-tier byte budget, read at CALL time (env > module global >
    memoized ``_DEFAULT_HBM_FRACTION`` of the queried device limit)."""
    env = os.environ.get("PHOTON_CHUNK_CACHE_BUDGET")
    if env is not None and env != "":
        return max(int(env), 0)
    if CHUNK_CACHE_BUDGET is not None:
        return max(int(CHUNK_CACHE_BUDGET), 0)
    if not _device_budget_memo:
        from photon_ml_tpu.ops.streaming import device_hbm_budget_bytes

        # ``default`` is the no-memory-stats fallback (CPU test backends)
        # and is NOT scaled by ``fraction`` — pass the already-scaled value
        # lint: waive(conc-unlocked-mutation) memoize-once of an immutable backend quote: racing appends store the same value and only [0] is read
        _device_budget_memo.append(int(device_hbm_budget_bytes(
            default=2e9, fraction=_DEFAULT_HBM_FRACTION,
        )))
    return _device_budget_memo[0]


def host_spill_budget_bytes() -> int:
    if HOST_SPILL_BUDGET is not None:
        return max(int(HOST_SPILL_BUDGET), 0)
    return chunk_cache_budget_bytes()


# -- the bounded-depth pipeline -------------------------------------------

# One process-wide worker pool, lazily built: tasks are independent
# preparations (no task waits on a task), so sharing a pool across
# concurrent streams cannot deadlock; per-call pools would pay thread
# creation on every optimizer pass.
_pool_lock = threading.Lock()
_pool: ThreadPoolExecutor | None = None


def _worker_pool() -> ThreadPoolExecutor:
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(
                max_workers=min(8, (os.cpu_count() or 2)),
                thread_name_prefix="photon-prefetch",
            )
        return _pool


# per-thread exclusion so host_pack_s and device_put_s stay DISJOINT: a
# prepare callable usually ends in a transfer, and nesting the put timer
# inside the pack timer would double-count it (the stage split would sum
# past worker wall time and misattribute transfer cost as pack cost)
_stage_tls = threading.local()


def timed_device_put(a):
    """``jax.device_put`` accounted under ``prefetch.device_put_s`` and
    EXCLUDED from any enclosing ``_timed_prepare`` pack time. Use for
    transfers inside prepare callables that bypass the chunk cache."""
    import time

    import jax

    t0 = time.perf_counter()
    try:
        return jax.device_put(a)
    finally:
        dt = time.perf_counter() - t0
        profiling.add_seconds("prefetch.device_put_s", dt)
        if hasattr(_stage_tls, "put_s"):
            _stage_tls.put_s += dt


def _timed_prepare(prepare: Callable[[int], Any], i: int) -> Any:
    import time

    t0 = time.perf_counter()
    _stage_tls.put_s = 0.0
    try:
        return prepare(i)
    finally:
        dt = time.perf_counter() - t0 - _stage_tls.put_s
        del _stage_tls.put_s
        profiling.add_seconds("prefetch.host_pack_s", max(dt, 0.0))


def prefetch_iter(
    num_items: int,
    prepare: Callable[[int], Any],
    depth: int | None = None,
) -> Iterator[Any]:
    """Yield ``prepare(0..num_items-1)`` IN ORDER, preparing up to
    ``depth`` items ahead on worker threads. ``depth=None`` reads the
    knob; ``depth<=0`` runs fully synchronously (no threads touched).
    A preparation error re-raises at that item's turn; queued later items
    are cancelled (already-running ones finish and are dropped)."""
    if depth is None:
        depth = prefetch_depth()
    if threading.current_thread().name.startswith("photon-prefetch"):
        # a pool worker consuming a NESTED pipeline would block on pool
        # tasks while occupying a pool slot — with enough such waiters the
        # pool starves. No consumer nests today; degrade to synchronous so
        # one never can.
        depth = 0
    if depth <= 0 or num_items <= 1:
        for i in range(num_items):
            yield prepare(i)
        return
    pool = _worker_pool()
    futs: deque = deque()
    nxt = 0
    try:
        while nxt < num_items and len(futs) < depth:
            futs.append(pool.submit(_timed_prepare, prepare, nxt))
            nxt += 1
        while futs:
            f = futs.popleft()
            with profiling.stage_timer("prefetch.consumer_wait_s"):
                out = f.result()  # re-raises a worker exception here
            if nxt < num_items:
                futs.append(pool.submit(_timed_prepare, prepare, nxt))
                nxt += 1
            yield out
    finally:
        for f in futs:  # consumer bailed (error or early close): drop tail
            f.cancel()


# -- the device-resident chunk cache --------------------------------------
# PER-ARRAY granularity: a GAME coordinate visit swaps only the residual
# offsets column of each chunk — per-array keys re-transfer exactly the
# changed column while labels/weights/features replay resident buffers.

_cache_lock = threading.Lock()
# key -> (host_ref, staged_ref, device_array, dev_nbytes, host_nbytes);
# insertion order = LRU. ``host_ref`` is the caller's original array (it
# OWNS the data-pointer key: holding it makes the key safe); ``staged_ref``
# is the transfer-dtype twin actually shipped (identical to host_ref on the
# f32 rung).
_device_tier: "OrderedDict[tuple, tuple]" = OrderedDict()
_device_bytes = 0
# aggregate HOST RAM pinned by device-resident entries (each entry's
# host_ref keeps a view's whole base alive): bounded against the host
# spill budget, so many small device entries can never pin unbounded
# host memory between them — the pre-ladder guarantee, kept in aggregate
_device_host_bytes = 0
# key -> (host_ref, staged_ref, host_nbytes): spilled entries (refs
# retained so a re-entry pays one device_put — never a re-slice/re-pack —
# and so the data-pointer key stays safe)
_host_tier: "OrderedDict[tuple, tuple]" = OrderedDict()
_host_bytes = 0
_cache_stats = {
    "device_hits": 0, "host_hits": 0, "misses": 0, "evictions": 0,
}

# Raw (un-tiled) streamed feature arrays packed at the transfer dtype:
# under the PHOTON_KERNEL_DTYPE precision ladder (ops/sparse_tiled), the
# tile-COO consumers already move their packed slabs at the storage dtype;
# these are the remaining fat columns of raw chunk dicts. Both reduced
# rungs transfer bf16 here (int8's symmetric scales exist only inside the
# packed tile layouts; a raw operand has no tile to carry them on) —
# labels/offsets/weights stay f32, so the f32 rung is byte-identical to
# the pre-ladder path.
_PACK_KEYS = ("values", "X")


def transfer_dtype() -> str:
    """The raw-chunk transfer rung derived from the kernel-dtype knob at
    CALL time: 'f32' (identity) or 'bf16'."""
    from photon_ml_tpu.ops.sparse_tiled import kernel_dtype

    return "f32" if kernel_dtype() == "f32" else "bf16"


def _pack_for_transfer(a: np.ndarray):
    """One feature array → its bf16 transfer twin (f32 inputs only; other
    dtypes pass through untouched)."""
    import ml_dtypes

    return a.astype(ml_dtypes.bfloat16) if a.dtype == np.float32 else a


def pack_host_chunk(host_tree: dict) -> dict:
    """Pack a prepared host chunk's feature arrays at the ladder's
    transfer dtype (no-op on the f32 rung). The synchronous depth-0
    streamed path uses this directly; the cached path packs per-array on
    cache miss so repeat passes key on the caller's ORIGINAL storage."""
    if transfer_dtype() == "f32":
        return host_tree
    return {
        k: _pack_for_transfer(np.asarray(v)) if k in _PACK_KEYS else v
        for k, v in host_tree.items()
    }


def _storage_key(a: np.ndarray) -> tuple:
    ai = a.__array_interface__
    return (ai["data"], a.shape, ai["strides"], str(a.dtype))


def _evict_over_budget_locked() -> None:
    global _device_bytes, _device_host_bytes, _host_bytes
    budget = chunk_cache_budget_bytes()
    host_budget = host_spill_budget_bytes()
    while _device_tier and (
        _device_bytes > budget or _device_host_bytes > host_budget
    ):
        key, (host_ref, staged, _dev, nb_dev, nb_host) = (
            _device_tier.popitem(last=False)
        )
        _device_bytes -= nb_dev
        _device_host_bytes -= nb_host
        _cache_stats["evictions"] += 1
        _REGISTRY.counter_inc("prefetch.cache.evictions")
        # spill: keep the staged host twin so re-entry is one device_put,
        # never a re-slice/re-pack upstream
        if key not in _host_tier:
            _host_bytes += nb_host
        _host_tier[key] = (host_ref, staged, nb_host)
        _host_tier.move_to_end(key)
    while _host_tier and _host_bytes > host_budget:
        _, (_ref, _staged, nb) = _host_tier.popitem(last=False)
        _host_bytes -= nb


def _cached_put_one(name, a):
    """One host array → its device-resident twin, through the LRU."""
    global _device_bytes, _device_host_bytes, _host_bytes
    a = np.asarray(a)
    tdt = transfer_dtype()
    packs = tdt != "f32" and name in _PACK_KEYS and a.dtype == np.float32
    # the transfer dtype is part of the key for packed arrays: a bf16-rung
    # entry must never serve an f32 pass (or vice versa) after the knob
    # toggles mid-process — same never-by-luck rule as the kernel caches
    key = _storage_key(a) + ((tdt,) if packs else ())
    staged = None
    with _cache_lock:
        hit = _device_tier.get(key)
        if hit is not None:
            _device_tier.move_to_end(key)
            _cache_stats["device_hits"] += 1
            # registry twins of the stats (hit/miss BYTES: the transfer
            # traffic the cache saved/paid — what a sweep actually diffs;
            # counted at the DEVICE size, i.e. post-pack dtype)
            _REGISTRY.counter_inc("prefetch.cache.hit_bytes", hit[3])
            return hit[2]
        spilled = _host_tier.pop(key, None)
        if spilled is not None:
            _host_bytes -= spilled[2]
            _cache_stats["host_hits"] += 1
            _REGISTRY.counter_inc(
                "prefetch.cache.host_hit_bytes", int(spilled[1].nbytes)
            )
            staged = spilled[1]
        else:
            _cache_stats["misses"] += 1
    if staged is None:
        staged = _pack_for_transfer(a) if packs else a
        # registry counters take their own lock — no cache state touched
        _REGISTRY.counter_inc("prefetch.cache.miss_bytes", int(staged.nbytes))
    # transfer OUTSIDE the lock (the expensive part; concurrent misses for
    # the same key both transfer — last insert wins, both correct)
    dev = timed_device_put(staged)
    # the DEVICE tier charges what the entry actually holds in HBM — the
    # post-pack device array's nbytes (a bf16 pass fits ~2x the chunks of
    # an f32 pass under the same budget, and a view's device copy is just
    # the slice). What the entry pins in HOST RAM (a view's whole base —
    # see _pinned_nbytes) is bounded separately against the HOST budget:
    # a few-KB slice of a base larger than the spill budget never caches,
    # so holding its ref can never pin unbounded host RAM past both
    # budgets (the pre-ladder guarantee, kept).
    nb_dev = int(dev.nbytes)
    nb_host = _pinned_nbytes(a) + (int(staged.nbytes) if staged is not a else 0)
    with _cache_lock:
        if (
            nb_dev <= chunk_cache_budget_bytes()
            and nb_host <= host_spill_budget_bytes()
        ):  # over-budget on either axis: never pinned
            prev = _device_tier.pop(key, None)
            if prev is not None:
                _device_bytes -= prev[3]
                _device_host_bytes -= prev[4]
            _device_tier[key] = (a, staged, dev, nb_dev, nb_host)
            _device_bytes += nb_dev
            _device_host_bytes += nb_host
            _device_tier.move_to_end(key)
            _evict_over_budget_locked()
    return dev


def _pinned_nbytes(a: np.ndarray) -> int:
    """A HOST-tier entry's budget charge: what holding the reference
    actually PINS. A numpy VIEW keeps its whole base array alive, so
    charging the slice's own nbytes would let a few-KB entry pin a
    multi-GB dataset past the spill budget; views are charged at their
    base's size (conservative — a base larger than the budget simply
    never spills, degrading to plain per-pass transfers)."""
    base = a.base
    if isinstance(base, np.ndarray):
        return int(base.nbytes)
    return int(a.nbytes)


def cached_device_put(host_tree: dict) -> dict:
    """Device-resident arrays for a prepared host chunk (dict of numpy
    arrays) through the process-wide per-array cache: a repeat pass over
    the SAME host storage returns already-resident device buffers
    (optimizer passes 2..N skip the transfer entirely), and a per-visit
    offsets swap re-transfers only the offsets column. Feature arrays
    (``values``/``X``) transfer at the precision ladder's storage dtype
    (``pack_host_chunk``), so a bf16 pass halves both the HBM footprint
    and the host→device traffic of raw chunks. Thread-safe — prefetch
    workers for different chunks race here by design. Keyed by the
    CALLER's storage identity (+ transfer dtype for packed arrays), so
    cached arrays must not be mutated in place (the framework never does;
    fresh arrays per visit get fresh keys)."""
    return {k: _cached_put_one(k, v) for k, v in host_tree.items()}


def cache_stats() -> dict:
    with _cache_lock:
        return dict(
            _cache_stats,
            device_entries=len(_device_tier),
            device_bytes=_device_bytes,
            device_host_pinned_bytes=_device_host_bytes,
            host_entries=len(_host_tier),
            host_bytes=_host_bytes,
        )


def clear_cache() -> None:
    global _device_bytes, _device_host_bytes, _host_bytes
    with _cache_lock:
        _device_tier.clear()
        _host_tier.clear()
        _device_bytes = 0
        _device_host_bytes = 0
        _host_bytes = 0
        for k in _cache_stats:
            _cache_stats[k] = 0
