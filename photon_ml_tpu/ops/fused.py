"""One-pass fused GLM evaluation kernels (Pallas on TPU).

Why this exists: the GLM objective is HBM-bandwidth bound — at training
shapes the feature matrix ``X`` dwarfs everything else, so wall-clock is
set by how many times ``X`` streams from HBM per optimizer iteration and
by how well the streaming overlaps compute. These kernels tile ``X`` over
rows and, per tile resident in VMEM, compute margins (MXU), the pointwise
loss and its derivatives (VPU), and the transposed gradient contraction
(MXU) before moving on — ``X`` streams from HBM exactly ONCE per
evaluation:

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
# An f32 tile's full-precision dots keep about four more tile-sized
# temporaries (the bf16 splits of the MXU-resident operand): 42 MB of
# scoped VMEM for a 7 MiB tile, by the v5e compiler's own count.
_F32_TILE_COPIES = 6
_LANES = 128
_MIN_BLOCK_ROWS = 256  # covers the bf16 (16, 128) min tile with headroom
_MAX_BLOCK_ROWS = 8192
# contract the minor (feature) dimension of both operands: (k, d)·(bn, d)ᵀ
_NT = (((1,), (1,)), ((), ()))


def supports_fused(n: int, d: int, dtype) -> bool:
    """Static gate: shapes/dtypes the kernels handle efficiently.

    d must be lane-aligned (the (1, d) partials and (bn, d) tiles are laid
    out in 128-wide lanes) and a double-buffered minimum row tile of X must
    fit the VMEM budget — very high-d problems belong to the sparse path.
    The per-row streams do not enter: lane-dense, they are 4 B a row each.
    """
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if d % _LANES != 0:
        return False
    return _block_rows(n, d, jnp.dtype(dtype).itemsize) is not None


def _block_rows(n: int, d: int, itemsize: int) -> int | None:
    """Largest power-of-two row tile whose double-buffered X block fits
    the VMEM budget, and whose f32 temporaries fit the scoped limit (None
    if even the minimum tile does not)."""
    best = None
    bn = _MIN_BLOCK_ROWS
    while bn <= _MAX_BLOCK_ROWS:
        tile = bn * d * itemsize
        if 2 * tile > _VMEM_BUDGET or (
                itemsize == 4 and _F32_TILE_COPIES * tile > _VMEM_LIMIT):
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
    """The resident X tile and, for a ragged last tile, the (1, bn) mask of
    its in-range rows. Out-of-range tile rows hold unspecified values; they
    are zeroed so the contraction cannot pick up Inf/NaN garbage through
    0·x."""
    x = x_ref[...]
    if not masked:
        return x, None
    bn = x.shape[0]
    start = pl.program_id(0) * bn
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0) + start
    row = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1) + start
    return jnp.where(col < n, x, jnp.zeros_like(x)), row < n


def _margins(vecs_ref, shifts_ref, x):
    """(k, bn) lane-dense margins vecs·xᵀ − shifts of the k coefficient
    rows against the tile: the tile is the transposed MXU operand (as
    attention's q·kᵀ), so every row's margin lands in its own lane."""
    return jax.lax.dot_general(
        vecs_ref[...].astype(x.dtype), x, _NT,
        preferred_element_type=jnp.float32, precision=_precision(x),
    ) - shifts_ref[...]


def _precision(x):
    # MXU f32 dots default to a single bf16 pass in Mosaic; request full
    # f32 precision when the data is stored f32 (bf16 storage keeps the
    # fast single pass — that is its point).
    return (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _row(ref):
    """A per-row stream's (bn/128, 128) block as the (1, bn) row the
    margins are in — a relayout of the resident block, no HBM traffic."""
    return ref[...].reshape(1, -1)


def _weighted(vals, wt_ref, mask):
    """w·v per row with zero-weight and out-of-range rows exactly 0 (the
    wrapper pads the weight stream with zeros, so its padding needs no
    mask of its own)."""
    if wt_ref is not None:
        wt = _row(wt_ref)
        return [jnp.where(wt != 0.0, wt * v, 0.0) for v in vals]
    if mask is not None:
        return [jnp.where(mask, v, 0.0) for v in vals]
    return vals


def _contract(r, x):
    """rᵀX as the (1, bn)·(bn, d) MXU dot, r cast to the storage dtype."""
    return jnp.dot(r.astype(x.dtype), x, preferred_element_type=jnp.float32,
                   precision=_precision(x))


def _lane_sums(v):
    """(1, bn) → (1, 128) per-lane partial sums (the lanes are added
    outside with the tiles)."""
    return jnp.sum(v.reshape(-1, _LANES), axis=0, keepdims=True)


def _vg_kernel(*refs, loss, n, masked, has_off, has_wt):
    x_ref, y_ref, off_ref, wt_ref, u_ref, c_ref, val_ref, g_ref, rs_ref = (
        _split_refs(refs, has_off, has_wt)
    )
    x, mask = _tile(x_ref, n, masked)
    m = _margins(u_ref, c_ref, x)
    if has_off:
        m = m + _row(off_ref)
    y = _row(y_ref)
    lv, r = _weighted([loss.value(m, y), loss.d1(m, y)], wt_ref, mask)
    # Each tile writes its OWN output slot; partials are tree-reduced in
    # f32 outside the kernel. A single running accumulator would add tile
    # partials sequentially, whose O(grid)·eps rounding is enough to stall
    # the optimizer's Armijo test near convergence (observed on-chip).
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


def _prep(X, labels, offsets, weights):
    """Shared wrapper setup: tile sizing and the X + per-row-stream input
    lists (one copy, so value_grad and hvp can never diverge in
    tiling/specs). A per-row stream enters as the flat f32 vector viewed
    (grid, bn/128, 128): 4 B a row in HBM and in VMEM, a free view when the
    tile divides n and one zero-pad of the vector otherwise."""
    n, d = X.shape
    bn = _block_rows(n, d, jnp.dtype(X.dtype).itemsize)
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
    ins = [X, stream(labels)]
    in_specs = [pl.BlockSpec((bn, d), lambda i: (i, 0)), stream_spec]
    for a in (offsets, weights):
        if a is not None:
            ins.append(stream(a))
            in_specs.append(stream_spec)
    statics = dict(n=n, masked=n % bn != 0, has_off=offsets is not None,
                   has_wt=weights is not None)
    return d, grid, statics, ins, in_specs


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
    d, grid, statics, ins, in_specs = _prep(X, labels, offsets, weights)
    ins += [u.reshape(1, d).astype(jnp.float32),
            jnp.asarray(c, jnp.float32).reshape(1, 1)]
    in_specs += [_const_spec((1, d)), _const_spec((1, 1))]

    val, g, rs = pl.pallas_call(
        functools.partial(_vg_kernel, loss=loss, **statics),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[_part_spec(_LANES), _part_spec(d), _part_spec(_LANES)],
        out_shape=[_part_shape(grid, _LANES), _part_shape(grid, d),
                   _part_shape(grid, _LANES)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*ins)
    return jnp.sum(val), jnp.sum(g, axis=(0, 1)), jnp.sum(rs)


def _hvp_kernel(*refs, loss, n, masked, has_off, has_wt):
    x_ref, y_ref, off_ref, wt_ref, uv_ref, sc_ref, hv_ref, qs_ref = (
        _split_refs(refs, has_off, has_wt)
    )
    x, mask = _tile(x_ref, n, masked)
    muv = _margins(uv_ref, sc_ref, x)  # (2, bn): margins and X·v − cv
    m, mv = muv[0:1], muv[1:2]
    if has_off:
        m = m + _row(off_ref)
    (d2,) = _weighted([loss.d2(m, _row(y_ref))], wt_ref, mask)
    q = d2 * mv
    # per-tile partials, reduced outside (see _vg_kernel)
    hv_ref[...] = _contract(q, x)
    qs_ref[...] = _lane_sums(q)


def fused_hvp(X, labels, offsets, weights, u, v, c, cv, *, loss,
              interpret=False):
    """One X-read Gauss-Newton Hv: (Xᵀq, Σq) with q = w·l''(m, y)·(Xv − cv)
    and m = X@u + offsets − c. ``offsets``/``weights`` may be None as in
    ``fused_value_grad``. Returns float32 (hv, q_sum)."""
    d, grid, statics, ins, in_specs = _prep(X, labels, offsets, weights)
    ins += [jnp.stack([u, v]).astype(jnp.float32),
            jnp.stack([jnp.asarray(c, jnp.float32),
                       jnp.asarray(cv, jnp.float32)]).reshape(2, 1)]
    in_specs += [_const_spec((2, d)), _const_spec((2, 1))]

    hv, qs = pl.pallas_call(
        functools.partial(_hvp_kernel, loss=loss, **statics),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[_part_spec(d), _part_spec(_LANES)],
        out_shape=[_part_shape(grid, d), _part_shape(grid, _LANES)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*ins)
    return jnp.sum(hv, axis=(0, 1)), jnp.sum(qs)
