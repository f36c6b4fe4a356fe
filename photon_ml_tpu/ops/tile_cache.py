"""Process-wide tile-COO layout cache.

Packing a ``SparseBatch`` into the write-slab-major tile-COO layout
(``ops/sparse_tiled.py``) is a host-side sort + scatter over every nonzero
— cheap next to a full solve, but it was being re-paid for IDENTICAL
sparsity structure all over the system: every ``StreamingGLMObjective``
re-tiled its chunks even when a previous objective over the same data had
already done so (GAME trainers rebuild objectives per fit; drivers rebuild
them per sweep), and every cross-validation invocation re-tiled its fold
subsets from scratch. The compiled kernel executable was similarly
re-specialized per call site.

This module is the one shared answer: a process-wide LRU keyed by

    (sparsity fingerprint, chunking mode, tuned kernel constants)

where the fingerprint hashes the nonzero STRUCTURE (indices/values bytes,
shape, feature count) and the tuned constants are the module-level
GROUPS_PER_STEP / SEGMENTS_PER_DMA / GROUPS_PER_RUN knobs (and
SUB_SLABS / SUB_GROUP_COST, by which the resident build chooses a
stream's form) read at call time — a retune invalidates by key,
never by luck.
Only the layout (the ``_TileChunk`` tuple, the dense head beside it and
the pad metadata) is cached;
labels/offsets/weights always come from the caller's batch, so GAME
coordinate visits that only swap residual offsets hit the cache by
construction. Executable reuse is the other half: ``_tiled_apply`` keys
its jit cache on the same tuned constants, so any two cache entries with
equal stream shapes re-enter one compiled kernel.

Thread-safe — the ``ops/prefetch`` pipeline's workers hit this cache
CONCURRENTLY (per-chunk layout lookups race by design; hammer-tested in
``tests/test_prefetch.py``): every LRU mutation, eviction and hit/miss
bookkeeping happens under the one module lock, with only the expensive
pack itself outside it. Bounded by BOTH entry count (``capacity()``, LRU)
and total packed-stream bytes (``byte_budget()``, maintained as a running
total so eviction never re-walks the table) — the entries pin
device-resident streams, so an entry cap alone would let a handful of
billion-nonzero layouts hold multiple GB of HBM for the process lifetime.
``clear()`` drops everything (tests, or to release device memory eagerly).
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict

import numpy as np

from photon_ml_tpu.obs.spans import LAYOUT_FINGERPRINT, LAYOUT_TO_HOST, span

_DEFAULT_CAPACITY = 32
# total packed-stream bytes the cache may pin across entries: an A2-scale
# layout (both directions) is ~0.5 GB, so the default holds a few large
# layouts or many small ones and evicts LRU beyond that — worst case a
# re-pack, never an OOM
_DEFAULT_BYTE_BUDGET = 2 * 1024**3

_lock = threading.Lock()
_entries: "OrderedDict[tuple, object]" = OrderedDict()
_entry_bytes: dict = {}
_total_bytes = 0
_stats = {"hits": 0, "misses": 0}
_capacity = _DEFAULT_CAPACITY
_byte_budget = _DEFAULT_BYTE_BUDGET


def tuned_constants() -> tuple:
    """The kernel-shaping constants, read at CALL time (the same
    discipline as the layout builder: import-time capture breaks
    retuning)."""
    import photon_ml_tpu.ops.sparse_tiled as st

    return (
        st.GROUP,
        st.SLAB,
        st.GROUPS_PER_STEP,
        st.SEGMENTS_PER_DMA,
        st.GROUPS_PER_RUN,
        # the dense head's rule decides which nonzeros the streams hold
        st.HEAD_MIN_FILL,
        # the sparse-cell form's granule and the cost at which the resident
        # build prefers it decide a stream's FORM (whether it is asked at
        # all rides the key as ``hbm_budget_bytes``)
        st.SUB_SLABS,
        st.SUB_GROUP_COST,
        # the precision rung RESHAPES the packed streams (f32 i32x3 /
        # int8 i32x1 + scales): a stale hit across a toggle
        # would hand the kernel streams of the wrong width
        st.kernel_dtype(),
        # effective device topology: a degrade-in-place shrinks the
        # group without restarting the process, and entries whose
        # device-resident streams predate the loss must miss by key —
        # while a same-topology re-entry hits everything it already
        # packed (the cheap-abort zero-growth contract)
        _effective_topology(),
    )


def _effective_topology() -> tuple:
    from photon_ml_tpu.parallel.multihost import effective_topology

    return effective_topology()


def structure_fingerprint(indices, values) -> tuple:
    """Byte-exact hash of the nonzero structure alone (shape + index and
    value bytes) — the streamed objective's swap guard uses exactly this
    (labels/offsets/weights are deliberately absent: the GAME trainer's
    per-visit residual swap keeps the same layout)."""
    with span(LAYOUT_TO_HOST):  # device arrays come to the host here
        idx = np.ascontiguousarray(np.asarray(indices))
        val = np.ascontiguousarray(np.asarray(values, np.float32))
    with span(LAYOUT_FINGERPRINT):
        return (
            idx.shape,
            hashlib.sha256(idx.tobytes()).hexdigest(),
            hashlib.sha256(val.tobytes()).hexdigest(),
        )


def sparsity_fingerprint(indices, values, num_features: int) -> tuple:
    """The full cache key half: structure + the feature-space width the
    layout pads to."""
    shape, h_idx, h_val = structure_fingerprint(indices, values)
    return (shape, int(num_features), h_idx, h_val)


def stats() -> dict:
    with _lock:
        return dict(
            _stats,
            entries=len(_entries),
            bytes=_total_bytes,
        )


def capacity() -> int:
    return _capacity


def byte_budget() -> int:
    return _byte_budget


def _evict_over_limits_locked() -> None:
    global _total_bytes
    while _entries and (
        len(_entries) > _capacity or _total_bytes > _byte_budget
    ):
        key, _ = _entries.popitem(last=False)
        _total_bytes -= _entry_bytes.pop(key, 0)


def set_capacity(n: int) -> None:
    global _capacity
    with _lock:
        _capacity = max(int(n), 1)
        _evict_over_limits_locked()


def set_byte_budget(n: int) -> None:
    global _byte_budget
    with _lock:
        _byte_budget = max(int(n), 0)
        _evict_over_limits_locked()


def clear() -> None:
    global _total_bytes
    with _lock:
        _entries.clear()
        _entry_bytes.clear()
        _total_bytes = 0
        _stats["hits"] = 0
        _stats["misses"] = 0


def _layout_nbytes(tb) -> int:
    """Device bytes a built layout pins: the chunks' streams and the head."""
    total = 0
    for c in tb.chunks:
        for arrays in (c.m_arrays, c.g_arrays):
            total += sum(int(a.nbytes) for a in arrays)
    if tb.head_X is not None:
        total += int(tb.head_X.nbytes) + int(tb.head_cols.nbytes)
    return total


def tiled_layout_for(batch, keep_empty_chunks: bool = False,
                     fingerprint: tuple | None = None,
                     fe_range: tuple | None = None,
                     hbm_budget_bytes: float | None = None):
    """A ``TiledSparseBatch`` for ``batch``, reusing the cached layout when
    an identical sparsity structure was already packed under the current
    tuned constants. The returned batch ALWAYS carries the caller's
    labels/offsets/weights (only the packed streams are shared).
    ``fingerprint`` lets callers that already hashed the chunk (the
    streamed objective's swap guard) skip the second hash. ``fe_range``
    is the feature-range identity ((pid, lo, hi, P)) of a range-sliced
    batch under PHOTON_FE_SHARD — it joins the cache key (a re-plan or
    P change invalidates by key, never by luck) and rides the built
    batch as its static ``fe_range`` meta field. ``hbm_budget_bytes``
    asks for the resident single-device layout, which may carry a dense
    head inside that budget and gives each chunk's streams the form their
    cells' occupancy asks for (``tile_sparse_batch``), so it joins the key
    too."""
    import photon_ml_tpu.ops.sparse_tiled as st

    if fingerprint is None:
        fingerprint = sparsity_fingerprint(
            batch.indices, batch.values, batch.num_features
        )
    key = (fingerprint, bool(keep_empty_chunks), fe_range, hbm_budget_bytes,
           tuned_constants())
    with _lock:
        cached = _entries.get(key)
        if cached is not None:
            _entries.move_to_end(key)
            _stats["hits"] += 1
    if cached is not None:
        # only the layout is cached — never the first caller's per-row
        # arrays (which a stored full batch would pin alive)
        chunks, head_X, head_cols, num_rows_real, n_pad_total, d_pad_total = cached
        return st.TiledSparseBatch(
            chunks=chunks,
            labels=batch.labels,
            offsets=batch.offsets,
            weights=batch.weights,
            num_features=batch.num_features,
            num_rows_real=num_rows_real,
            n_pad_total=n_pad_total,
            d_pad_total=d_pad_total,
            fe_range=fe_range,
            head_X=head_X,
            head_cols=head_cols,
        )
    # build OUTSIDE the lock (packing is the expensive part) through the
    # module attribute, so instrumented/monkeypatched builders see misses
    # (and keep the plain one-arg call shape they expect where nothing
    # else was asked for)
    asked = dict(keep_empty_chunks=keep_empty_chunks, fe_range=fe_range,
                 hbm_budget_bytes=hbm_budget_bytes)
    tb = st.tile_sparse_batch(
        batch,
        **{k: v for k, v in asked.items() if v is not None and v is not False},
    )
    nbytes = _layout_nbytes(tb)
    global _total_bytes
    with _lock:
        _stats["misses"] += 1
        # devcost accounting: once per PACK that produced a NEW resident
        # entry (concurrent misses on one key both pack, but only the
        # first insert records — a doubled packed-bytes total would
        # inflate the analytic bytes-moved record the dtype ladder's
        # claim rests on). Over-budget layouts are never pinned, so each
        # re-request genuinely re-packs and records again — that repeat
        # IS the real host work/traffic of running over budget.
        prev = _entry_bytes.pop(key, None)
        record_pack = prev is None
        if nbytes <= _byte_budget:  # over-budget layouts are never pinned
            if prev is not None:  # concurrent miss already inserted this key
                _total_bytes -= prev
            _entries[key] = (
                tb.chunks, tb.head_X, tb.head_cols,
                tb.num_rows_real, tb.n_pad_total, tb.d_pad_total,
            )
            _entry_bytes[key] = nbytes
            _total_bytes += nbytes
            _entries.move_to_end(key)
            _evict_over_limits_locked()
        elif prev is not None:
            # key was resident but the REBUILT layout is over budget
            # (budget shrank): drop the stale entry
            _total_bytes -= prev
            _entries.pop(key, None)
    if record_pack:
        from photon_ml_tpu.obs import devcost

        devcost.record_layout_pack(nbytes=nbytes, chunks=len(tb.chunks))
    return tb
