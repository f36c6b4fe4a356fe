"""One-pass fused GLM evaluation kernels (Pallas on TPU).

Why this exists: the GLM objective is HBM-bandwidth bound — at training
shapes the feature matrix ``X`` dwarfs everything else, so wall-clock is
set by how many times ``X`` streams from HBM per optimizer iteration and
by how well the streaming overlaps compute. These kernels tile ``X`` over
rows and, per tile resident in VMEM, compute margins (MXU), the pointwise
loss and its derivatives (VPU), and the transposed gradient contraction
(MXU) before moving on — ``X`` streams from HBM exactly ONCE per
evaluation (bfloat16 on the MXU; float32 on the VPU, below):

- ``fused_value_grad``: (Σ w·l, Xᵀr, Σr) in one pass.
- ``fused_hvp``: (Xᵀ(d2·(Xv)), Σ d2·(Xv)) in one pass — margins and
  ``X·v`` come from the same resident tile via one (2, d)·tileᵀ MXU dot.

Combined with the L-BFGS line search evaluating ``value_and_grad`` per
trial (``optim/lbfgs.py``), a typical accepted step costs ONE X read
instead of the XLA path's margins pass + gradient pass + line-search
value pass.

Hardware subtlety that shapes the code: a TPU stores a ``(rows, 1)`` f32
array at one 128-lane line (512 B) a row, in HBM and in VMEM alike, so a
per-row vector must never be a column. Labels, offsets and weights enter
as the flat f32 vector viewed ``(grid, bn/128, 128)`` — 4 B a row, a free
view of the caller's array when the tile divides ``n`` — and every per-row
quantity inside the kernel lives in one ``(1, bn)`` lane-dense row: the
margins come out that way from the transposed-operand dot ``u·tileᵀ`` (the
tile is the MXU's transposed operand, as in attention's ``q·kᵀ``), the
stream blocks are reshaped to it in VMEM, and the gradient is ``r_row @
tile``. The ``X`` double-buffer therefore has the VMEM budget to itself
(``_block_rows``: 4096 rows at d = 512 bf16), and a pass reads the bytes
it needs and no more: 5.86 ms for 4,194,304 x 512 bf16 and all three
streams on a v5e, 742 GB/s (PERF.md §6, PR 26). Callers still pass
``offsets=None`` / ``weights=None`` when a stream is identically 0 / 1
(the ingest layer's common case, detected once per objective
construction): the stream is then not read at all, 4 B a row saved.

Reference parity note: this replaces the per-partition fold inside the
reference's ``photon-api::ml.function.ValueAndGradientAggregator`` /
``HessianVectorAggregator`` (SURVEY.md §2.2) with a hand-scheduled TPU
kernel; the reduction across devices stays the objective's single
``lax.psum``.

Float32 storage means exact float32 contractions, and those stay off the
MXU: a matrix-vector product leaves all but one of its 128 columns idle,
and at ``highest`` precision it is six bf16 passes over splits of the tile.
Timed on a v5e at 400,000 rows (PERF.md §6, PR 34): XLA's two
multiply-reduce sweeps 8.51 ms a Hessian-vector pass, the ``highest`` MXU
dots 5.09 ms at 2,048 columns, the VPU form below 4.39 ms there, the
feature-major kernel 4.31 ms at 2,000. So one float32 form of each
contraction is kept a layout, both on the VPU:

- a width that is a multiple of 128 lies row-major on the chip and takes
  the row-major kernels (``_rm_pass``: multiply and add 128-lane blocks,
  one XLU transpose of a (128, 128) accumulator to turn row sums into
  lanes). ``game/random_effect`` runs them batched over the lanes of a
  subspace class (a ``vmap``: the lane axis becomes the outer grid axis),
  which is why a tile comes down to 128 rows (``tile_rows``) and its
  blocks are loops;
- any other width of at least one sublane group (LIBSVM epsilon's 2,000,
  the GLMix descent's 65-column fixed effect) lies FEATURE-MAJOR on the
  chip, features down the sublanes and rows along the lanes with not a
  byte of padding, so the feature-major kernels (``_margins_fm`` /
  ``_contract_fm``) read ``X.T``, which is that very array: margins are
  born along the lanes and nothing is transposed; a tile goes 512 rows of
  X at a time, in a loop where it holds more (``_fm_pass``);
- bfloat16 at a width that is no multiple of 128, or fewer than 8 float32
  columns, stays on the XLA path (``supports_fused``).

Semantics match ``GLMObjective`` exactly:
- zero-weight rows contribute exactly 0 (padding can hold any values),
- bfloat16 feature storage keeps bf16 MXU operands with float32
  accumulation (the vector operand is cast to bf16, like
  ``DenseBatch._mm``).

The kernels run in interpreter mode off-TPU, so CPU tests exercise the
identical code path the TPU runs compiled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jnp.ndarray

# VMEM budget for the pipelined X double-buffer (the per-row streams add
# 8 B a row to it), and the scoped limit the whole kernel is compiled
# under: Mosaic's default 16 MB cap undercounts the staging of the
# transposed operand; the chip has more physical VMEM than the cap.
_VMEM_BUDGET = 14 * 1024 * 1024
_VMEM_LIMIT = 32 * 1024 * 1024
_LANES = 128
_SUBLANES = 8
# rows of a float32 row-major tile contracted at a time on the VPU: one
# (128, 128) accumulator a coefficient row stays in vector registers across
# the tile's lane blocks, and is the square the XLU transposes
_F32_ROWS = 128
# 128-lane blocks of a row-major float32 tile's width a loop step contracts
_RM_UNROLL = 8
# The feature-major kernels (X read as Xᵀ: features down the sublanes, rows
# of X along the lanes): lanes contracted at a time (four registers a
# sublane group), sublane groups a loop step, and the grid steps whose
# (d, 128) gradient partials share one output block.
_FM_LANES = 512
_FM_UNROLL = 5
_FM_GROUP = 32
_MIN_BLOCK_ROWS = 256  # covers the bf16 (16, 128) min tile with headroom
_MAX_BLOCK_ROWS = 8192
# contract the minor (feature) dimension of both operands: (k, d)·(bn, d)ᵀ
_NT = (((1,), (1,)), ((), ()))


def supports_fused(n: int, d: int, dtype) -> bool:
    """Static gate: the widths and dtypes the kernels take. What decides is
    what the caller's array is, never a switch.

    - d a multiple of 128, bfloat16 or float32: the row-major kernels, whose
      (bn, d) tiles and (1, d) partials are whole 128-lane tiles (a TPU
      stores such a matrix row-major).
    - float32 of any other width of at least one sublane group (d >= 8:
      LIBSVM epsilon's 2,000, the descent cells' 65-column fixed effect):
      the feature-major kernels (``reads_feature_major``). A TPU stores
      that matrix feature-major, the rows along the lanes, with no padding;
      ``ops/glm.auto_fused`` checks that the array at hand is stored so.
      The floor is the kernels' unit of work: they contract whole groups of
      8 features and mask only the last, partial one (7 of 72 sublane rows
      at d = 65); under 8 columns the partial group is all there is.
    - bfloat16 at a width that is no multiple of 128 stays on the XLA path.

    And a double-buffered minimum tile of X must fit the VMEM budget: very
    high-d problems belong to the sparse path. The per-row streams do not
    enter: lane-dense, they are 4 B a row each.
    """
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if reads_feature_major(d, dtype):
        if d < _SUBLANES:
            return False
    elif d % _LANES != 0:
        return False
    return tile_rows(n, d, dtype) is not None


def reads_feature_major(d: int, dtype) -> bool:
    """Which kernels a dense (n, d) matrix takes: the feature-major ones
    (X read as Xᵀ, the rows along the lanes) for a float32 matrix whose
    width is no multiple of 128, the row-major ones otherwise. It follows
    the chip's own storage: a v5e keeps f32[400000, 2000] feature-major
    (``major_to_minor`` (1, 0): 2,000 sublane rows of 400,000 lanes, not a
    byte of padding) and f32[400000, 2048] row-major (PERF.md §6, PR 34),
    so either kernel reads its matrix where it lies, with no copy."""
    return dtype == jnp.float32 and d % _LANES != 0


def stored_feature_major(X) -> bool:
    """Whether the concrete array ``X`` lies feature-major on its device
    (its ``format``'s ``major_to_minor`` is (1, 0)); False where it cannot
    be told (a host array, a tracer)."""
    layout = getattr(getattr(X, "format", None), "layout", None)
    order = getattr(layout, "major_to_minor", None)
    return order is not None and tuple(order) == (1, 0)


def sublane_width(d: int) -> int:
    """d rounded up to whole 8-sublane groups: the rows a feature-major
    matrix of d features occupies."""
    return -(-d // _SUBLANES) * _SUBLANES


def tile_rows(n: int, d: int, dtype) -> int | None:
    """Rows of X a grid step holds for an (n, d) matrix of ``dtype`` (None
    where no tile fits): ``_block_rows`` at the width and the shortest tile
    of the kernels that matrix takes. A float32 row-major tile may come
    down to ``_F32_ROWS``, the unit its contractions work in, where the
    matrix is that short or the budget asks for it (128 rows of 8,192
    columns are 4 MiB; 256 would not fit twice); the MXU's bfloat16 tiles
    and the feature-major ones keep ``_MIN_BLOCK_ROWS``."""
    fm = reads_feature_major(d, dtype)
    shortest = _F32_ROWS if dtype == jnp.float32 and not fm else _MIN_BLOCK_ROWS
    return _block_rows(
        n, sublane_width(d) if fm else d, jnp.dtype(dtype).itemsize, shortest
    )


def _block_rows(n: int, d: int, itemsize: int,
                shortest: int = _MIN_BLOCK_ROWS) -> int | None:
    """Largest power-of-two row tile from ``shortest`` whose
    double-buffered X block fits the VMEM budget (None if even the
    shortest does not), and no longer than it takes to hold ``n`` rows."""
    best = None
    bn = shortest
    while bn <= _MAX_BLOCK_ROWS:
        tile = bn * d * itemsize
        if 2 * tile > _VMEM_BUDGET:
            break
        best = bn
        if bn >= n:
            break
        bn *= 2
    return best


def _split_refs(refs, has_off: bool, has_wt: bool):
    """(x, y, off|None, wt|None, rest...) from the positional ref list."""
    x_ref, y_ref = refs[0], refs[1]
    k = 2
    off_ref = wt_ref = None
    if has_off:
        off_ref = refs[k]
        k += 1
    if has_wt:
        wt_ref = refs[k]
        k += 1
    return (x_ref, y_ref, off_ref, wt_ref) + tuple(refs[k:])


def _tile(x_ref, n, masked):
    """The resident bfloat16 X tile and, for a ragged last tile, the
    (1, bn) mask of its in-range rows. Out-of-range tile rows hold
    unspecified values; they are zeroed so the contraction cannot pick up
    Inf/NaN garbage through 0·x."""
    x = x_ref[...]
    if not masked:
        return x, None
    bn = x_ref.shape[0]
    start = pl.program_id(0) * bn
    row = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1) + start < n
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0) + start
    return jnp.where(col < n, x, jnp.zeros_like(x)), row


def _margins(vecs_ref, shifts_ref, x):
    """(k, bn) lane-dense margins vecs·xᵀ − shifts of the k coefficient
    rows against a bfloat16 tile, every row's margin in its own lane: one
    MXU dot with the tile as the transposed operand (as attention's
    q·kᵀ)."""
    return jax.lax.dot_general(
        vecs_ref[...].astype(x.dtype), x, _NT,
        preferred_element_type=jnp.float32,
    ) - shifts_ref[...]


def _row(ref, rows=slice(None)):
    """A per-row stream's (bn/128, 128) block as the (1, bn) row the margins
    are in — a relayout of the resident block, no HBM traffic. ``rows``:
    the block's rows of one window of a feature-major tile (``_fm_pass``)."""
    return ref[rows, :].reshape(1, -1)


def _weighted(vals, wt_ref, mask, rows=slice(None)):
    """w·v per row with zero-weight and out-of-range rows exactly 0 (the
    wrapper pads the weight stream with zeros, so its padding needs no
    mask of its own)."""
    if wt_ref is not None:
        wt = _row(wt_ref, rows)
        return [jnp.where(wt != 0.0, wt * v, 0.0) for v in vals]
    if mask is not None:
        return [jnp.where(mask, v, 0.0) for v in vals]
    return vals


def _contract(r, x):
    """(1, d) rᵀX of a bfloat16 tile: the (1, bn)·(bn, d) MXU dot, r cast
    to the storage dtype."""
    return jnp.dot(r.astype(x.dtype), x, preferred_element_type=jnp.float32)


def _rm_each_block(d: int, body, carry):
    """``carry = body(lanes, carry)`` over every 128-lane block of a
    row-major float32 tile's width: a loop of ``_RM_UNROLL`` blocks a step
    where the width holds more, the rest (or all) as straight-line code."""
    blocks = d // _LANES
    steps = blocks // _RM_UNROLL if blocks > _RM_UNROLL else 0

    def step(s, carry):
        for t in range(_RM_UNROLL):
            first = pl.multiple_of((s * _RM_UNROLL + t) * _LANES, _LANES)
            carry = body(pl.ds(first, _LANES), carry)
        return carry

    if steps:
        carry = jax.lax.fori_loop(0, steps, step, carry)
    for b in range(steps * _RM_UNROLL, blocks):
        carry = body(pl.ds(b * _LANES, _LANES), carry)
    return carry


def _rm_pass(x_ref, n, masked, vecs_ref, shifts_ref, out_ref, acc_ref, count,
             point):
    """One pass over a row-major float32 tile (bn, d) on the VPU,
    ``_F32_ROWS`` rows at a time, exact with no ``highest`` splits and
    without the MXU, where a matrix-vector product is six bf16 passes (5.09
    ms a pass of 400,000 x 2,048 on a v5e against this form's 4.39;
    PERF.md §6, PR 34).

    A block of 128 rows: multiply each (128, 128) lane block by its slice
    of a coefficient row and add the blocks, then transpose the
    accumulator (XLU) and add its sublanes, which leaves the 128 margins in
    128 lanes; ``point(margins, mask, rows)`` gives the row to contract and
    the ``count`` rows to sum, as in ``_fm_pass``; that row, laid down the
    sublanes by one transpose of its sublane broadcast, multiplies every
    lane block again, each product folded to one (8, 128) register that is
    added into ``acc_ref`` (8, d). The eight sublanes are added once a
    tile, into ``out_ref`` (1, d). Returns the tile's (1, 128) lane sums of
    the rows to sum.

    Row blocks and lane blocks are loops, not straight-line code
    (``_rm_each_block``), so the kernel is traced and lowered once a block
    and not once a tile: a random effect's visit holds a kernel for every
    subspace class, and Python pays for each in every process (PERF.md §6,
    PR 37). A ragged last tile's out-of-range rows, whose x is unspecified,
    get margins 0 and products 0. What depends on the grid step is read
    before the loop (the HLO interpreter has no ``program_id`` inside
    one)."""
    bn, d = x_ref.shape
    k = vecs_ref.shape[0]
    start = pl.program_id(0) * bn if masked else None
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(b, sums):
        first = b * _F32_ROWS
        if not isinstance(b, int):
            first = pl.multiple_of(first, _F32_ROWS)
        rows = pl.ds(first, _F32_ROWS)
        mask = ok = None
        if masked:
            mask = (jax.lax.broadcasted_iota(jnp.int32, (1, _F32_ROWS), 1)
                    + (start + first) < n)
            # Mosaic transposes no booleans
            ok = jnp.broadcast_to(
                mask.astype(jnp.float32), (_LANES, _F32_ROWS)
            ).T > 0.5

        def margins(lanes, acc):
            xb = x_ref[rows, lanes]
            return tuple(
                a + xb * vecs_ref[i:i + 1, lanes] for i, a in enumerate(acc)
            )

        zero = jnp.zeros((_F32_ROWS, _LANES), jnp.float32)
        acc = _rm_each_block(d, margins, (zero,) * k)
        m = jnp.concatenate(
            [jnp.sum(a.T, axis=0, keepdims=True) for a in acc], axis=0
        ) - shifts_ref[...]
        if mask is not None:
            m = jnp.where(mask, m, 0.0)
        q, parts = point(m, mask, pl.ds(b, 1))
        qb = jnp.broadcast_to(q, (_LANES, _F32_ROWS)).T

        def contract(lanes, carry):
            p = x_ref[rows, lanes] * qb
            if ok is not None:
                p = jnp.where(ok, p, 0.0)
            acc_ref[:, lanes] += jnp.sum(
                p.reshape(_F32_ROWS // _SUBLANES, _SUBLANES, _LANES), axis=0
            )
            return carry

        _rm_each_block(d, contract, 0)
        return tuple(s + p for s, p in zip(sums, parts))

    sums = (jnp.zeros((1, _LANES), jnp.float32),) * count
    if bn == _F32_ROWS:
        sums = block(0, sums)
    else:
        sums = jax.lax.fori_loop(0, bn // _F32_ROWS, block, sums)
    out_ref[...] = jnp.sum(acc_ref[...], axis=0, keepdims=True)
    return sums


def _fm_each_group(d: int, body, carry):
    """``carry = body(first row of the group, carry)`` over every sublane
    group of 8 features of a feature-major tile: a loop of ``_FM_UNROLL``
    groups a step, the rest as straight-line code. The row is a Python
    integer in the straight-line part, which always holds a last group of
    fewer than 8 features (the one ``_margins_fm`` masks)."""
    groups = sublane_width(d) // _SUBLANES
    steps = (groups - (1 if d % _SUBLANES else 0)) // _FM_UNROLL

    def step(s, carry):
        for t in range(_FM_UNROLL):
            row = pl.multiple_of((s * _FM_UNROLL + t) * _SUBLANES, _SUBLANES)
            carry = body(row, carry)
        return carry

    if steps:
        carry = jax.lax.fori_loop(0, steps, step, carry)
    for g in range(steps * _FM_UNROLL, groups):
        carry = body(g * _SUBLANES, carry)
    return carry


def _margins_fm(vecs_ref, xt_ref, d, lanes):
    """(k, width) margins of the window ``lanes`` of a feature-major
    float32 tile ``xt`` (d8, bn): every sublane group of 8 features
    multiplied by its slice of a coefficient row, which comes laid across
    128 lanes ((k, d8, 128): no lane broadcast in the loop), and added to
    one (8, width) accumulator a coefficient row; the eight sublanes are
    added at the end. No transpose anywhere: the margins are born along
    the lanes. Feature rows past ``d`` (the block overruns the array where
    d is no multiple of 8) read as 0."""
    k = vecs_ref.shape[0]
    width = lanes.size

    def body(row, acc):
        xg = xt_ref[pl.ds(row, _SUBLANES), lanes]
        if isinstance(row, int) and row + _SUBLANES > d:
            sub = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, 1), 0)
            xg = jnp.where(sub < d - row, xg, 0.0)
        return tuple(
            a + xg * jnp.concatenate(
                [vecs_ref[i, pl.ds(row, _SUBLANES), :]] * (width // _LANES),
                axis=1,
            )
            for i, a in enumerate(acc)
        )

    zero = jnp.zeros((_SUBLANES, width), jnp.float32)
    acc = _fm_each_group(d, body, (zero,) * k)
    return jnp.concatenate(
        [jnp.sum(a, axis=0, keepdims=True) for a in acc], axis=0
    )


def _contract_fm(out_ref, r, xt_ref, mask, last, lanes):
    """Xᵀ·r of the window ``lanes`` of a feature-major float32 tile, added
    into the (d8, 128) output block that ``_FM_GROUP`` consecutive tiles
    share (``_fm_pass`` clears it): every sublane group of 8 features times
    r along the lanes, the lane blocks folded to one (8, 128) register of
    partial sums (VPU only). The 128 lanes and the blocks are added
    outside. Sequential adds into a block are as deep as 32 tiles have
    windows (32 at 2,000 columns, 512 at 65), so the partials keep the tree
    shape of the row-major kernels' per-tile slots. ``mask`` zeroes the
    products of a ragged last tile's out-of-range lanes, whose x is
    unspecified: on that tile (``last``) alone."""
    d8 = xt_ref.shape[0]
    width = lanes.size

    def add(masked):
        rb = jnp.broadcast_to(r, (_SUBLANES, width))
        ok = jnp.broadcast_to(mask, (_SUBLANES, width)) if masked else None

        def body(row, carry):
            p = xt_ref[pl.ds(row, _SUBLANES), lanes] * rb
            if ok is not None:
                p = jnp.where(ok, p, 0.0)
            f = p[:, :_LANES]
            for t in range(_LANES, width, _LANES):
                f = f + p[:, t:t + _LANES]
            out_ref[pl.ds(row, _SUBLANES), :] += f
            return carry

        _fm_each_group(d8, body, 0)

    if mask is None:
        add(False)
    else:
        pl.when(last)(lambda: add(True))
        pl.when(jnp.logical_not(last))(lambda: add(False))


def _fm_pass(xt_ref, n, d, masked, vecs_ref, shifts_ref, out_ref, count,
             point):
    """One pass over a feature-major float32 tile ``xt`` (d8, bn),
    ``_FM_LANES`` rows of X at a time: a window's margins
    (``_margins_fm``), ``point(margins, mask, rows)`` for the row to
    contract and the ``count`` rows to sum (``rows``: the window in a
    per-row stream's block), that row's Xᵀ· into ``out_ref``
    (``_contract_fm``). Returns the tile's (1, 128) lane sums of the rows
    to sum.

    Where the tile holds more than one window (a narrow matrix, whose tile
    is thousands of lanes long) the windows are a loop and not
    straight-line code, so the kernel is traced and lowered once a window's
    length, not once a tile's: sixteen times less Python at d = 65, where
    it was 20 s of every process's set-up (PERF.md §6, PR 35). What depends
    on the grid step is read before the loop (the HLO interpreter has no
    ``program_id`` inside one)."""
    bn = xt_ref.shape[1]
    width = min(_FM_LANES, bn)
    step = pl.program_id(0)
    last = step == pl.num_programs(0) - 1

    @pl.when(step % _FM_GROUP == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    def window(c, sums):
        first = c * width
        if not isinstance(c, int):
            first = pl.multiple_of(first, width)
        lanes = pl.ds(first, width)
        rows = pl.ds(c * (width // _LANES), width // _LANES)
        mask = None
        if masked:
            mask = (jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
                    + (step * bn + first) < n)
        m = _margins_fm(vecs_ref, xt_ref, d, lanes) - shifts_ref[...]
        if mask is not None:
            m = jnp.where(mask, m, 0.0)
        q, parts = point(m, mask, rows)
        _contract_fm(out_ref, q, xt_ref, mask, last, lanes)
        return tuple(s + _lane_sums(p) for s, p in zip(sums, parts))

    sums = (jnp.zeros((1, _LANES), jnp.float32),) * count
    if width == bn:
        return window(0, sums)
    return jax.lax.fori_loop(0, bn // width, window, sums)


def _lane_sums(v):
    """(1, bn) → (1, 128) per-lane partial sums (the lanes are added
    outside with the tiles)."""
    return jnp.sum(v.reshape(-1, _LANES), axis=0, keepdims=True)


def _vg_kernel(*refs, loss, n, d, fm, masked, has_off, has_wt):
    x_ref, y_ref, off_ref, wt_ref, u_ref, c_ref, val_ref, g_ref, rs_ref, *acc = (
        _split_refs(refs, has_off, has_wt)
    )

    def point(m, mask, rows=slice(None)):
        if has_off:
            m = m + _row(off_ref, rows)
        y = _row(y_ref, rows)
        lv, r = _weighted([loss.value(m, y), loss.d1(m, y)], wt_ref, mask, rows)
        return r, (lv, r)

    # Each tile writes its OWN output slot (the feature-major tiles one
    # slot a group of tiles); partials are tree-reduced in f32 outside the
    # kernel. A single running accumulator would add tile partials
    # sequentially, whose O(grid)·eps rounding is enough to stall the
    # optimizer's Armijo test near convergence (observed on-chip).
    if fm:
        val_ref[...], rs_ref[...] = _fm_pass(
            x_ref, n, d, masked, u_ref, c_ref, g_ref, 2, point
        )
        return
    if acc:
        val_ref[...], rs_ref[...] = _rm_pass(
            x_ref, n, masked, u_ref, c_ref, g_ref, acc[0], 2, point
        )
        return
    x, mask = _tile(x_ref, n, masked)
    r, (lv, _) = point(_margins(u_ref, c_ref, x), mask)
    g_ref[...] = _contract(r, x)
    val_ref[...] = _lane_sums(lv)
    rs_ref[...] = _lane_sums(r)


def _const_spec(shape):
    return pl.BlockSpec(shape, lambda i: (0, 0))


def _part_spec(width):
    """Per-tile output slot: tile i writes row block i of a (grid, 1,
    width) array; the squeezed leading axis leaves a (1, width) block whose
    dims equal the array's last two, as the TPU block rules ask."""
    return pl.BlockSpec((None, 1, width), lambda i: (i, 0, 0))


def _part_shape(grid, width):
    return jax.ShapeDtypeStruct((grid, 1, width), jnp.float32)


def _prep(X, labels, offsets, weights, vecs):
    """Shared wrapper setup: tile sizing, the X + per-row-stream + vector
    input lists and the gradient output (one copy, so value_grad and hvp
    can never diverge in tiling/specs). A per-row stream enters as the flat
    f32 vector viewed (grid, bn/128, 128): 4 B a row in HBM and in VMEM, a
    free view when the tile divides n and one zero-pad of the vector
    otherwise. ``vecs`` is the (k, d) float32 coefficient rows.

    Returns ``(grid, statics, ins, in_specs, g_spec, g_shape, scratch,
    finish)``; ``finish`` adds the gradient output's partials to the (d,)
    result, and ``scratch`` is the row-major float32 pass's (8, d)
    accumulator (``_rm_pass``; no other kernel has one).

    The feature-major kernels (``reads_feature_major``) take ``X.T``: the
    array itself where the chip stores X feature-major (a change of view,
    no copy), in (d8, bn) blocks, d8 the features rounded up to whole
    sublane groups (the block overruns the array there and the kernel
    masks it). Their coefficient rows come laid across 128 lanes, zero
    past d, and their gradient partials are (d8, 128) a group of tiles."""
    n, d = X.shape
    fm = reads_feature_major(d, X.dtype)
    width = sublane_width(d) if fm else d
    bn = tile_rows(n, d, X.dtype)
    if bn is None:
        raise ValueError(f"no VMEM-feasible tile for (n={n}, d={d})")
    grid = pl.cdiv(n, bn)

    def stream(a):
        a = a.astype(jnp.float32)
        if n % bn:
            a = jnp.pad(a, (0, grid * bn - n))
        return a.reshape(grid, bn // _LANES, _LANES)

    stream_spec = pl.BlockSpec((None, bn // _LANES, _LANES),
                               lambda i: (i, 0, 0))
    k = vecs.shape[0]
    if fm:
        ins = [X.T, stream(labels)]
        in_specs = [pl.BlockSpec((width, bn), lambda i: (0, i)), stream_spec]
        vecs = jnp.pad(vecs, ((0, 0), (0, width - d)))
        vecs = jnp.broadcast_to(vecs[:, :, None], (k, width, _LANES))
        vec_spec = pl.BlockSpec((k, width, _LANES), lambda i: (0, 0, 0))
        g_spec = pl.BlockSpec((None, width, _LANES),
                              lambda i: (i // _FM_GROUP, 0, 0))
        g_shape = jax.ShapeDtypeStruct(
            (pl.cdiv(grid, _FM_GROUP), width, _LANES), jnp.float32
        )
        finish = lambda g: jnp.sum(g, axis=(0, 2))[:d]
    else:
        ins = [X, stream(labels)]
        in_specs = [pl.BlockSpec((bn, d), lambda i: (i, 0)), stream_spec]
        vec_spec = _const_spec((k, d))
        g_spec, g_shape = _part_spec(d), _part_shape(grid, d)
        finish = lambda g: jnp.sum(g, axis=(0, 1))
    scratch = []
    if X.dtype == jnp.float32 and not fm:
        scratch = [pltpu.VMEM((_SUBLANES, d), jnp.float32)]
    for a in (offsets, weights):
        if a is not None:
            ins.append(stream(a))
            in_specs.append(stream_spec)
    ins.append(vecs)
    in_specs.append(vec_spec)
    statics = dict(n=n, d=d, fm=fm, masked=n % bn != 0,
                   has_off=offsets is not None, has_wt=weights is not None)
    return grid, statics, ins, in_specs, g_spec, g_shape, scratch, finish


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",),
    vmem_limit_bytes=_VMEM_LIMIT,
)


def fused_value_grad(X, labels, offsets, weights, u, c, *, loss,
                     interpret=False):
    """One X-read (Σᵢ wᵢ·l(mᵢ, yᵢ), Xᵀr, Σᵢ rᵢ) with r = w·l'(m, y) and
    margins m = X@u + offsets − c. ``offsets=None`` means identically 0,
    ``weights=None`` identically 1 (the stream is not read at all).
    Returns float32 (val, grad, r_sum)."""
    grid, statics, ins, in_specs, g_spec, g_shape, scratch, finish = _prep(
        X, labels, offsets, weights,
        u.reshape(1, X.shape[1]).astype(jnp.float32),
    )
    ins.append(jnp.asarray(c, jnp.float32).reshape(1, 1))
    in_specs.append(_const_spec((1, 1)))

    val, g, rs = pl.pallas_call(
        functools.partial(_vg_kernel, loss=loss, **statics),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[_part_spec(_LANES), g_spec, _part_spec(_LANES)],
        out_shape=[_part_shape(grid, _LANES), g_shape,
                   _part_shape(grid, _LANES)],
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*ins)
    return jnp.sum(val), finish(g), jnp.sum(rs)


def _hvp_kernel(*refs, loss, n, d, fm, masked, has_off, has_wt):
    x_ref, y_ref, off_ref, wt_ref, uv_ref, sc_ref, hv_ref, qs_ref, *acc = (
        _split_refs(refs, has_off, has_wt)
    )

    def point(muv, mask, rows=slice(None)):
        m, mv = muv[0:1], muv[1:2]  # margins and X·v − cv
        if has_off:
            m = m + _row(off_ref, rows)
        (d2,) = _weighted([loss.d2(m, _row(y_ref, rows))], wt_ref, mask, rows)
        q = d2 * mv
        return q, (q,)

    # per-tile partials, reduced outside (see _vg_kernel)
    if fm:
        (qs_ref[...],) = _fm_pass(
            x_ref, n, d, masked, uv_ref, sc_ref, hv_ref, 1, point
        )
        return
    if acc:
        (qs_ref[...],) = _rm_pass(
            x_ref, n, masked, uv_ref, sc_ref, hv_ref, acc[0], 1, point
        )
        return
    x, mask = _tile(x_ref, n, masked)
    q, _ = point(_margins(uv_ref, sc_ref, x), mask)
    hv_ref[...] = _contract(q, x)
    qs_ref[...] = _lane_sums(q)


def fused_hvp(X, labels, offsets, weights, u, v, c, cv, *, loss,
              interpret=False):
    """One X-read Gauss-Newton Hv: (Xᵀq, Σq) with q = w·l''(m, y)·(Xv − cv)
    and m = X@u + offsets − c. ``offsets``/``weights`` may be None as in
    ``fused_value_grad``. Returns float32 (hv, q_sum)."""
    grid, statics, ins, in_specs, g_spec, g_shape, scratch, finish = _prep(
        X, labels, offsets, weights, jnp.stack([u, v]).astype(jnp.float32),
    )
    ins.append(jnp.stack([jnp.asarray(c, jnp.float32),
                          jnp.asarray(cv, jnp.float32)]).reshape(2, 1))
    in_specs.append(_const_spec((2, 1)))

    hv, qs = pl.pallas_call(
        functools.partial(_hvp_kernel, loss=loss, **statics),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[g_spec, _part_spec(_LANES)],
        out_shape=[g_shape, _part_shape(grid, _LANES)],
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*ins)
    return finish(hv), jnp.sum(qs)
