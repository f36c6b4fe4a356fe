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
  ``X·v`` come from the same resident tile via one (d, 2) MXU dot.

Combined with the L-BFGS line search evaluating ``value_and_grad`` per
trial (``optim/lbfgs.py``), a typical accepted step costs ONE X read
instead of the XLA path's margins pass + gradient pass + line-search
value pass.

Hardware subtlety that shapes the code: per-row vectors (labels, offsets,
weights) enter the kernel as ``(bn, 1)`` column blocks, and a column block
pads to 128 VMEM lanes — 128x its HBM footprint. Three such aux inputs,
double-buffered, evict the budget that the ``X`` tile wants (bigger tiles
= better DMA/compute overlap; measured ~1.5x between bn=2048 and
bn=4096). So aux inputs are OPTIONAL at trace time: callers pass
``offsets=None`` / ``weights=None`` when they are identically 0 / 1 (the
ingest layer's common case, detected once per objective construction),
and ``_block_rows`` picks the largest power-of-two row tile whose
X-double-buffer + aux padding fits the VMEM budget.

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

# VMEM budget for pipelined inputs (X double-buffer + padded aux blocks).
# The chip has ~16 MB; leave headroom for accumulators and control.
_VMEM_BUDGET = 14 * 1024 * 1024
_LANE_PAD_BYTES = 128 * 4  # one aux row pads to a full 128-lane f32 line
_MIN_BLOCK_ROWS = 256  # covers the bf16 (16, 128) min tile with headroom
_MAX_BLOCK_ROWS = 8192


def supports_fused(n: int, d: int, dtype) -> bool:
    """Static gate: shapes/dtypes the kernels handle efficiently.

    d must be lane-aligned (the (1, d) accumulator and (bn, d) tiles are
    laid out in 128-wide lanes) and a minimum row tile plus the worst-case
    three aux inputs must fit the VMEM budget — very high-d problems
    belong to the sparse path.
    """
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if d % 128 != 0:
        return False
    return _block_rows(n, d, jnp.dtype(dtype).itemsize, naux=3) is not None


def _block_rows(n: int, d: int, itemsize: int, naux: int) -> int | None:
    """Largest power-of-two row tile whose double-buffered X block plus
    ``naux`` lane-padded aux blocks fit the VMEM budget (None if even the
    minimum tile does not fit)."""
    best = None
    bn = _MIN_BLOCK_ROWS
    while bn <= _MAX_BLOCK_ROWS:
        need = 2 * bn * (d * itemsize + naux * _LANE_PAD_BYTES)
        if need > _VMEM_BUDGET:
            break
        best = bn
        if bn >= n:
            break
        bn *= 2
    return best


def _row_mask(i, bn: int, n: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0) + i * bn
    return rows < n


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


def _vg_kernel(*refs, loss, n, bn, masked, has_off, has_wt):
    x_ref, y_ref, off_ref, wt_ref, u_ref, c_ref, val_ref, g_ref, rs_ref = (
        _split_refs(refs, has_off, has_wt)
    )
    i = pl.program_id(0)
    x = x_ref[...]
    # MXU f32 dots default to a single bf16 pass in Mosaic; request full
    # f32 precision when the data is stored f32 (bf16 storage keeps the
    # fast single pass — that is its point).
    prec = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    mask = _row_mask(i, bn, n) if masked else None
    if masked:
        # Out-of-range tile rows hold unspecified values; zero them so the
        # contraction below cannot pick up Inf/NaN garbage through 0·x.
        x = jnp.where(mask, x, jnp.zeros_like(x))
    m = jnp.dot(x, u_ref[...].astype(x.dtype),
                preferred_element_type=jnp.float32, precision=prec)
    m = m - c_ref[...]
    if has_off:
        m = m + off_ref[...]
    y = y_ref[...]
    lv = loss.value(m, y)
    r = loss.d1(m, y)
    if has_wt:
        wt = wt_ref[...]
        if masked:
            wt = jnp.where(mask, wt, 0.0)
        lv = jnp.where(wt != 0.0, wt * lv, 0.0)
        r = jnp.where(wt != 0.0, wt * r, 0.0)
    elif masked:
        lv = jnp.where(mask, lv, 0.0)
        r = jnp.where(mask, r, 0.0)
    # Each tile writes its OWN output slot; partials are tree-reduced in
    # f32 outside the kernel. A single running accumulator would add tile
    # partials sequentially, whose O(grid)·eps rounding is enough to stall
    # the optimizer's Armijo test near convergence (observed on-chip).
    g_ref[...] = jax.lax.dot_general(
        r.astype(x.dtype), x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    ).reshape(g_ref.shape)
    val_ref[...] = jnp.sum(lv).reshape(val_ref.shape)
    rs_ref[...] = jnp.sum(r).reshape(rs_ref.shape)


def _col_spec(bn):
    return pl.BlockSpec((bn, 1), lambda i: (i, 0))


def _const_spec(shape):
    return pl.BlockSpec(shape, lambda i: (0, 0))


def _part_spec(shape):
    """Per-tile output slot: tile i writes leading-index block i. The slot
    is a leading length-1 axis so the last two dims satisfy the TPU block
    rules exactly (they equal the overall array dims)."""
    return pl.BlockSpec((1,) + shape, lambda i: (i, 0, 0))


def _prep(X, labels, offsets, weights):
    """Shared wrapper setup: tile sizing and the X + aux-column input lists
    (one copy, so value_grad and hvp can never diverge in tiling/specs)."""
    n, d = X.shape
    itemsize = jnp.dtype(X.dtype).itemsize
    has_off, has_wt = offsets is not None, weights is not None
    naux = 1 + int(has_off) + int(has_wt)
    bn = _block_rows(n, d, itemsize, naux)
    if bn is None:
        raise ValueError(f"no VMEM-feasible tile for (n={n}, d={d})")
    grid = pl.cdiv(n, bn)
    masked = (n % bn) != 0

    col = lambda a: a.astype(jnp.float32).reshape(n, 1)
    ins = [X, col(labels)]
    in_specs = [pl.BlockSpec((bn, d), lambda i: (i, 0)), _col_spec(bn)]
    if has_off:
        ins.append(col(offsets))
        in_specs.append(_col_spec(bn))
    if has_wt:
        ins.append(col(weights))
        in_specs.append(_col_spec(bn))
    return n, d, bn, grid, masked, has_off, has_wt, ins, in_specs


# Mosaic's default 16MB scoped-vmem cap undercounts the transpose staging
# for the reverse contraction; the chip has more physical VMEM than the cap.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",),
    vmem_limit_bytes=32 * 1024 * 1024,
)


def fused_value_grad(X, labels, offsets, weights, u, c, *, loss,
                     interpret=False):
    """One X-read (Σᵢ wᵢ·l(mᵢ, yᵢ), Xᵀr, Σᵢ rᵢ) with r = w·l'(m, y) and
    margins m = X@u + offsets − c. ``offsets=None`` means identically 0,
    ``weights=None`` identically 1 (fewer VMEM-padded aux streams → larger
    X tiles). Returns float32 (val, grad, r_sum)."""
    n, d, bn, grid, masked, has_off, has_wt, ins, in_specs = _prep(
        X, labels, offsets, weights
    )
    ins += [u.reshape(d, 1).astype(jnp.float32),
            jnp.asarray(c, jnp.float32).reshape(1, 1)]
    in_specs += [_const_spec((d, 1)), _const_spec((1, 1))]

    kernel = functools.partial(
        _vg_kernel, loss=loss, n=n, bn=bn, masked=masked,
        has_off=has_off, has_wt=has_wt,
    )
    val, g, rs = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[_part_spec((1, 1)), _part_spec((1, d)), _part_spec((1, 1))],
        out_shape=[
            jax.ShapeDtypeStruct((grid, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, 1), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*ins)
    return jnp.sum(val), jnp.sum(g, axis=(0, 1)), jnp.sum(rs)


def _hvp_kernel(*refs, loss, n, bn, masked, has_off, has_wt):
    x_ref, y_ref, off_ref, wt_ref, u_ref, v_ref, sc_ref, hv_ref, qs_ref = (
        _split_refs(refs, has_off, has_wt)
    )
    i = pl.program_id(0)
    x = x_ref[...]
    prec = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    mask = _row_mask(i, bn, n) if masked else None
    if masked:
        x = jnp.where(mask, x, jnp.zeros_like(x))
    uv = jnp.concatenate([u_ref[...], v_ref[...]], axis=1).astype(x.dtype)
    muv = jnp.dot(x, uv, preferred_element_type=jnp.float32,
                  precision=prec)  # (bn, 2)
    m = muv[:, 0:1] - sc_ref[0:1, 0:1]
    if has_off:
        m = m + off_ref[...]
    mv = muv[:, 1:2] - sc_ref[0:1, 1:2]
    d2 = loss.d2(m, y_ref[...])
    if has_wt:
        wt = wt_ref[...]
        if masked:
            wt = jnp.where(mask, wt, 0.0)
        d2 = jnp.where(wt != 0.0, wt * d2, 0.0)
    elif masked:
        d2 = jnp.where(mask, d2, 0.0)
    q = d2 * mv
    # per-tile partials, reduced outside (see _vg_kernel)
    hv_ref[...] = jax.lax.dot_general(
        q.astype(x.dtype), x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    ).reshape(hv_ref.shape)
    qs_ref[...] = jnp.sum(q).reshape(qs_ref.shape)


def fused_hvp(X, labels, offsets, weights, u, v, c, cv, *, loss,
              interpret=False):
    """One X-read Gauss-Newton Hv: (Xᵀq, Σq) with q = w·l''(m, y)·(Xv − cv)
    and m = X@u + offsets − c. ``offsets``/``weights`` may be None as in
    ``fused_value_grad``. Returns float32 (hv, q_sum)."""
    n, d, bn, grid, masked, has_off, has_wt, ins, in_specs = _prep(
        X, labels, offsets, weights
    )
    sc = jnp.stack([jnp.asarray(c, jnp.float32),
                    jnp.asarray(cv, jnp.float32)]).reshape(1, 2)
    ins += [u.reshape(d, 1).astype(jnp.float32),
            v.reshape(d, 1).astype(jnp.float32), sc]
    in_specs += [_const_spec((d, 1)), _const_spec((d, 1)), _const_spec((1, 2))]

    kernel = functools.partial(
        _hvp_kernel, loss=loss, n=n, bn=bn, masked=masked,
        has_off=has_off, has_wt=has_wt,
    )
    hv, qs = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[_part_spec((1, d)), _part_spec((1, 1))],
        out_shape=[
            jax.ShapeDtypeStruct((grid, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((grid, 1, 1), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*ins)
    return jnp.sum(hv, axis=(0, 1)), jnp.sum(qs)
