"""Tile-COO sparse GLM kernels: MXU/VPU-bound high-dimensional sparse ops.

Why this exists (SURVEY.md §7 hard parts, "Sparse features on TPU";
VERDICT r2 missing #3): XLA lowers the padded-sparse ``SparseBatch``
margins/gradient to element-at-a-time dynamic gathers and scatters —
~6e7 elem/s on TPU, latency-bound, which put the high-dimensional sparse
config BELOW one CPU core. The reference's platform (Breeze on JVM) does
these as cache-friendly CSR loops; beating it needs the sparse pass to run
out of VMEM at vector rates.

Design — write-slab-major tile-COO, built ONCE at ingest:

- The source vector (w for margins, r for the gradient) lives in VMEM as a
  (len/128, 128) table; so does the output (m / g), accumulated in a VMEM
  scratch and written out at the last grid step.
- Every nonzero is assigned to a CELL = (write-slab, read-slab) where a
  slab is 1024 consecutive outputs/inputs = an (8, 128) block of the
  corresponding table. Nonzeros are sorted by cell (write-slab major) and
  each cell padded to a whole number of GROUPS_PER_RUN-group RUNS of
  GROUP=128 nonzeros (zero-valued fillers) — consecutive groups of one
  cell read ONE source slab, so the kernel loads each shared slab once
  per run and batches the gather over the whole run (see GROUPS_PER_RUN).
- Each WRITE SLAB's nonzeros are further padded to a multiple of
  GROUPS_PER_STEP groups, so one grid step processes GROUPS_PER_STEP
  groups that ALL write to the same (8, 128) output slab. Per group the
  kernel does only vector-rate work:
    * read:  slab = src[rslab] (one (8,128) dynamic slice; slab id comes
      from an SMEM-prefetched per-group array, not a vector lane read);
      ``take_along_axis(slab, lane, 1)`` pulls the wanted lane from all 8
      sublanes, an 8-way iota-compare select keeps the right sublane —
      exactly Mosaic's lane/8-sublane gather scope.
    * write: contributions are staged into an A matrix (8, G*128) masked
      by output sublane, and a TRANSPOSED one-hot B_T (128, G*128) with
      B_T[l, j] = (l == lane(j)). Building B transposed keeps the lane
      indices in the LANE dimension (the straightforward (G*128, 128)
      one-hot needs a lane->sublane transpose per group — measured ~2x
      slower end to end).
- One ``dot_general`` contracts A and B_T over their last dims: a single
  (8, G*128) x (128, G*128) -> (8, 128) MXU call scatters ALL of the
  step's nonzeros into the shared write slab (one matmul per G groups:
  a matmul per group is bound by the matmul issue count). B_T is exactly
  representable in bf16, and A is split into
  hi+mid+lo bf16 terms (Dekker-style, 24 mantissa bits), so the scatter
  runs at the MXU's bf16 rate while staying f32-exact (three passes
  instead of six for HIGHEST-f32).
- The one-hot operands stage per SEGMENT, not per group: the read
  gather hides behind the scatter pipeline, and staging A and B_T is the
  cost center (see ``_tile_kernel_seg``).
- margins (``matvec``) and gradient (``rmatvec``) each get their OWN
  layout — write=row/read=col and write=col/read=row respectively — the
  one-time ingest cost buys both directions their batched write slab.

- A DENSE HEAD beside the tile-COO tail (PR 28). The kernels cost ~37 ns a
  128-nonzero group whatever the group holds, so a column that is filled
  in more than ``HEAD_MIN_FILL`` of the rows is cheaper read as a dense
  float32 column at the HBM's rate. A caller that wants this passes the
  device bytes it may pin (``tile_sparse_batch(hbm_budget_bytes=...)``;
  ``ops/batch.optimize_batch_layout`` does, for the resident single-device
  layout): the build then counts every column's stored nonzeros and moves
  the most popular ones, in blocks of 128 lanes, out of the streams into
  one float32 matrix that both directions sweep (see ``_head_columns``).
  The rule reads the input and the budget and nothing else: no knob. A
  matrix without popular columns gets no head and the layout it always
  had; streamed chunks, per-device shards and feature-range slices pass no
  budget (their pytrees must share one structure) and are never asked.

- TWO FORMS OF A STREAM, chosen by the build from the cells it finds
  (PR 31). The nonzeros a cell holds fall as 1 over the matrix's width:
  711 at 47,236 columns, 20 at 10^6. A run of ``GROUPS_PER_RUN`` groups
  pads the first by 9% and the second 12.5 times over. The resident build
  (the one that is handed the budget, as for the head) therefore counts
  every chunk's cells (``_cell_form``: the ``np.unique`` counts the sort
  returns anyway) and, where whole runs would hold more than
  ``SUB_GROUP_COST`` times the slots of whole granules, lays the chunk
  out in the SPARSE-CELL form: a cell pads to a granule of ``GROUP //
  SUB_SLABS`` = 16 slots, ``rrun`` holds one read slab a granule, and the
  kernel's phase 1 loads and lane-gathers ``SUB_SLABS`` slabs a group,
  keeping from each the lanes of its granule (the slab ids stay in HBM and
  ride each DMA step into SMEM: one kernel call a stream of any length).
  Phase 2, the packed streams and the write-slab segments are the same in
  both forms; which form a stream has is read off ``rrun``'s length. A
  matrix whose cells are full gets the runs, the streams and the kernel
  it always had, byte for byte; shards, streamed chunks, feature-range
  slices and the int8 rung keep the run form (one structure to stack, a
  per-cell scale a run). The head's budget counts the tail at the padding
  its form will have (``_tail_form``), not at a fixed quarter.

- A THIRD FORM, THE FINE ONE (PR 40): at 2 x 10^7 columns and 36 nonzeros a
  row a cell holds one or two, and a 16-slot granule pads the tail 8
  times over. The fine form is the sparse-cell form with a granule of
  ``GROUP // FINE_SLABS`` = 2 slots: a group reads 64 slabs, rolled into
  loops (``_tile_kernel_seg``), and ``_cell_form`` prices it with
  ``FINE_GROUP_COST``. The resident build counts the whole tail's cells
  once, as the head's rule prices the tail (``_head_columns``), and gives
  every chunk that one form. Every build cuts its columns into chunks of
  one width and, where there are several, STACKS them into one
  ``_TileChunk`` a row chunk, end to end in every stream, whose pass is
  ONE kernel call a direction with a grid step a column chunk (XLA
  compiles each custom call of a program apart, and a call a chunk cost
  minutes; a loop of one call a chunk would slice and relay out the
  chunk's streams every pass). So the resident build takes
  ``_MAX_RESIDENT_COLS`` columns, past the 2^23 that a build keeping the
  run form stops at.

- ONE ENTRY A (ROW, COLUMN). A padded-sparse row may name a column twice,
  and such entries add. The build merges them (``_merge_repeats``: the
  first draw keeps the sum), so head and tail alike hold the MATRIX's
  entry and ``rmatvec_sq`` squares that entry everywhere, as
  ``ops/batch.densify`` does. A batch without repeats, as real rows are,
  is laid out value for value as before.

``TiledSparseBatch`` is a drop-in ``Batch``: ``GLMObjective`` consumes it
through ``matvec``/``rmatvec``/``rmatvec_sq`` unchanged. On the CPU
backend the kernels run in Pallas interpreter mode, so CPU tests trace the
code the TPU compiles (``tests/test_kernels_compile_tpu.py`` compiles it
for a v5e without a device — the interpreter accepts programs Mosaic
refuses). Shapes whose tables exceed one kernel's VMEM bounds are split
into row/col chunks: row chunks are calls of their own, outputs
concatenated, and a row chunk's column chunks the grid steps of one call,
margins summed in the kernel; where the chunks' scalar-prefetch streams
exceed SMEM the call is cut at whole chunks, and a chunk beyond it alone
runs as several calls over pieces of its stream, outputs summed.
Single-device by design:
under a mesh, shard rows first and build one tile-COO per shard (the
objective's psum handles the reduction).
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.obs.spans import (
    LAYOUT_HEAD,
    LAYOUT_MERGE,
    LAYOUT_PACK,
    LAYOUT_STAGE,
    LAYOUT_TO_HOST,
    span,
)
from photon_ml_tpu.obs.stages import GLM_HEAD, GLM_TAIL, stage

Array = jnp.ndarray

GROUP = 128  # nonzeros per group: one vreg row, shares one (write, read) cell
# 32-group segments halve (against 16) the number of sequential (matmul +
# accumulate) steps chained onto each write slab, which bound the
# gradient direction (the margins direction is insensitive), at +1.4%
# stream padding. Chosen on a remote-attached chip and never swept on the
# v5e (ROADMAP S2b). The DMA step stays at 128 groups (16K nnz per fetch).
GROUPS_PER_STEP = 32  # groups per SEGMENT: all share ONE write slab
SEGMENTS_PER_DMA = 4  # segments per DMA step (128 groups = 16K nnz per fetch)
# Slab-RUN batching: consecutive groups of one cell read the SAME source
# slab, so the builder pads every
# cell to whole runs of GROUPS_PER_RUN groups and the kernel loads the
# shared slab ONCE per run, gathering/staging all of the run's nonzeros in
# batched ops instead of per group. Bigger runs amortize more of the
# per-group skeleton but pad scattered cells harder (a cell always pads to
# a whole run): at the A2 shapes cells average ~2 groups, so 2 is the
# padding-neutral default — edit it per workload like the two constants
# above (must divide GROUPS_PER_STEP).
GROUPS_PER_RUN = 2  # groups per slab RUN: all read ONE source slab
# The SPARSE-CELL form of a stream (module docstring, "TWO FORMS"): a cell
# pads to a granule of GROUP // SUB_SLABS slots and a group reads up to
# SUB_SLABS source slabs. 8 won the chip's timing of 2 / 4 / 8 on cells of
# 20 nonzeros (a lane gather costs under 1 ns a group; 16 would pad 1.2
# against 1.39 at +7 ns a group and twice the code), and 128 groups x 8 ids
# fill the 1,024-word tile Mosaic slices a DMA step's ids by. A group then
# takes 44.2 ns against a run group's 36.8 (criteo_fit traced, and rcv1_fit
# with the form forced on its full cells: PERF.md, PR 31): the ratio is
# the weight by which ``_cell_form`` compares the two forms' padded slots.
SUB_SLABS = 8  # source slabs a group of the sparse-cell form may read
SUB_GROUP_COST = 1.2  # a sparse-cell group's kernel time over a run group's
# The FINE form (PR 40): the same stream with a granule of GROUP //
# FINE_SLABS = 2 slots, for cells of one or two nonzeros (2 x 10^7 columns
# at 36 a row: 16-slot granules pad such a tail 8 times over, 2-slot ones
# 1.33). A group then reads 64 slabs, rolled (``_tile_kernel_seg``), and
# takes 189 ns against a run group's 40.4 on kdda_fit's cells: the weight by
# which ``_cell_form`` prices it (my chip runs, PERF.md PR 40).
FINE_SLABS = 64  # source slabs a group of the fine form may read
FINE_GROUP_COST = 4.7  # a fine group's kernel time over a run group's
# The dense head's rule (see _head_columns): a column joins the head when it
# stores a nonzero in at least this share of the rows. Origin: a dense
# float32 column costs 8 B a row a pass (read once in each direction) at
# the ~750 GB/s XLA's multiply-reduce sweeps reach (756 over
# f32[5000066,128] in ml20m_fixed_only, 788 over the head itself), 10.7 ps
# a row; a stored nonzero costs the kernels 0.63 ns a pass
# (PERF_LEDGER.jsonl, PR 27, rcv1_fit: fit.objective_s_per_fit 1.424 s /
# 23 passes / 98,900,254 nonzeros); break-even is their ratio. Widths 256,
# 384 (the rule's own) and 512 forced on rcv1_fit read fit_s within 11%
# (PERF.md, PR 28): the threshold needs to be right to a factor of two.
HEAD_MIN_FILL = 0.017
HEAD_LANES = 128  # the head grows by whole lane blocks of columns
SLAB = 1024  # outputs/inputs per slab: an (8, 128) block of a table
# Storage rungs of the PACKED SLAB STREAMS and the gathered source
# operand: the reduced rung holds and moves a third of the bytes (on the
# v5e that buys device memory, not time: PERF.md, PR 29). A rung changes
# STORAGE only — the MXU contraction always accumulates in f32 through
# the 3-term Dekker split, and ``p_scratch``/``acc_scratch`` stay f32:
#
#   f32  — 12 B/nnz: full i32 write/read indices + f32 value bits. The
#          BITWISE-parity anchor: knob unset and knob=f32 are one layout
#          and one kernel.
#   int8 — ONE i32 stream (4 B/nnz): write-offset(10) | read-offset(10)
#          | symmetric-int8 value(8). The kernel only ever consumes the
#          low 10 bits of each index (lane + sublane; the slab id rides
#          the SMEM wslab/rrun streams). Per-CELL scale factors (one
#          (write-slab, read-slab) tile shares one scale, carried per
#          aligned RUN in the scalar-prefetched ``srun`` stream so the
#          kernel pays one SMEM read per run). Dequantized to f32 at
#          gather time; the gathered source is rounded to bf16 values (in
#          a 32-bit table); products are f32.
#
# int8 is NOT a bitwise rung — it gates on model-quality parity (AUC/RMSE
# deltas against the f32 fit). The dtype is a static key of the
# ``_tiled_apply`` jit cache, the tile-layout cache and the shared scoring
# program: toggling recompiles, never reuses. Set from the environment
# via PHOTON_KERNEL_DTYPE.
KERNEL_DTYPE = "f32"  # storage rung: "f32" (parity anchor) | "int8"
KERNEL_DTYPES = ("f32", "int8")


def validate_kernel_dtype(value) -> str:
    """Strict knob parse (the sibling PHOTON_RE_* knobs parse strict ints;
    a typo'd dtype must fail loudly, not fall back to f32 and silently
    bench the wrong rung)."""
    v = str(value).strip().lower()
    if v not in KERNEL_DTYPES:
        raise ValueError(
            f"PHOTON_KERNEL_DTYPE={value!r} is not a known precision rung; "
            f"valid rungs: {', '.join(KERNEL_DTYPES)}"
        )
    return v


def kernel_dtype() -> str:
    """The active storage rung, read at CALL time (env wins over the
    module global — the same discipline as the layout-shaping constants:
    an import-time capture would let layouts and kernel disagree)."""
    env = os.environ.get("PHOTON_KERNEL_DTYPE")
    if env is not None and env != "":
        return validate_kernel_dtype(env)
    return validate_kernel_dtype(KERNEL_DTYPE)


def _interpret() -> bool:
    """Interpreter mode is the CPU test path only: any accelerator backend
    compiles the kernel through Mosaic or fails loudly."""
    return jax.default_backend() == "cpu"


@dataclass(frozen=True)
class _Layout:
    """One direction's write-slab-major tiling (host numpy).

    ``packed`` interleaves the three per-nonzero streams — write index,
    read index, value bits — as (M/GROUP, 3, GROUP) int32, so the kernel
    fetches ONE contiguous block per DMA step. Measured on v5e: issuing
    one DMA per array per step capped the stream at ~20 GB/s (per-DMA
    issue/wait overhead ~1.5 us dominates 64 KB transfers); the packed
    single-DMA layout with 128-group steps is what made the stream cheap
    enough for the compute to be the limit again."""

    packed: np.ndarray  # (M/GROUP, S, GROUP) int32 streams; f32: S=3
    # [write, read, val bits], int8: S=1
    # [write off10 | read off10 << 10 | symmetric q8 << 20]
    wslab: np.ndarray  # (M/(GROUP*GROUPS_PER_STEP),) int32: per-segment slab
    rslab: np.ndarray  # (M/GROUP,) int32 read slab id per group (the
    # sparse-cell form: of the group's first granule; no kernel reads it)
    rrun: np.ndarray  # (M/(GROUP*GROUPS_PER_RUN),) int32: per-RUN read slab;
    # the sparse-cell form: (M/GROUP*SUB_SLABS,), per GRANULE. Which form a
    # stream has is read off this length, by the kernel too
    srun: np.ndarray  # (M/(GROUP*GROUPS_PER_RUN),) f32: per-RUN dequant
    # scale (each run is single-cell, so this carries the per-CELL int8
    # symmetric scale; all-ones for the f32 rung, never read there)
    cells: int = 0  # non-empty (write-slab, read-slab) cells of the stream


def detect_slab_runs(rslab: np.ndarray) -> np.ndarray:
    """Run-length metadata over a per-group read-slab stream: maximal runs
    of consecutive groups reading one slab, as an (n_runs, 3) int64 array
    of [start group, length, slab id]. This is the host-side view the
    fixed-size ``GROUPS_PER_RUN`` blocks are carved from (the kernel
    consumes the aligned ``rrun`` stream; this helper backs builder
    assertions, tests and padding diagnostics)."""
    r = np.asarray(rslab, np.int64)
    if not len(r):
        return np.zeros((0, 3), np.int64)
    starts = np.flatnonzero(np.concatenate([[True], r[1:] != r[:-1]]))
    lengths = np.diff(np.concatenate([starts, [len(r)]]))
    return np.stack([starts, lengths, r[starts]], axis=1)


def _cell_form(counts: np.ndarray, groups_per_run: int,
               storage: str) -> tuple[int, int]:
    """The form that serves cells holding ``counts`` nonzeros cheapest, as
    ``(sub_slabs, slots)``: 0 for the run form, ``SUB_SLABS`` for the
    sparse-cell form or ``FINE_SLABS`` for the fine form, and the slots its
    cells pad to. A cell pads to whole runs in the first and to whole
    granules in the others, and a group of a granule form costs its
    ``*_GROUP_COST`` run groups: slots times cost decides. So the sparse-cell
    form wins at 20 nonzeros a cell (12.5 against 1.7, the fine form 4.8)
    and not at 711 (1.09 against 1.02), the fine form at 2 (6.3 against the
    sparse-cell form's 9.6). The int8 rung keeps the run form (its per-cell scale
    rides the run), and so does a carve whose DMA step does not fill whole
    1,024-word tiles with slab ids, which Mosaic could not slice (the
    shipped carve does: 128 groups x 8 or x 64; the interpreter takes
    any)."""
    run_nnz = GROUP * groups_per_run
    run_slots = int((-(-counts // run_nnz) * run_nnz).sum())
    form, slots, cost = 0, run_slots, float(run_slots)
    if storage != "f32":
        return form, slots
    for sub, group_cost in ((SUB_SLABS, SUB_GROUP_COST),
                            (FINE_SLABS, FINE_GROUP_COST)):
        granule = GROUP // sub
        step_ids = GROUPS_PER_STEP * SEGMENTS_PER_DMA * sub
        sub_slots = int((-(-counts // granule) * granule).sum())
        if (_interpret() or step_ids % 1024 == 0) and sub_slots * group_cost < cost:
            form, slots, cost = sub, sub_slots, sub_slots * group_cost
    return form, slots


def build_write_major_layout(
    write_idx: np.ndarray,
    read_idx: np.ndarray,
    vals: np.ndarray,
    write_pad: int,
    read_pad: int,
    groups_per_step: int | None = None,
    groups_per_run: int | None = None,
    storage: str | None = None,
    sub_slabs: int = 0,
) -> _Layout:
    """Sort nonzeros by (write-slab, read-slab) cell, pad each cell to a
    whole number of ``groups_per_run``-group RUNS (every group of a cell
    reads the cell's slab, so an aligned run is single-slab by
    construction), then pad each write slab's group count to a multiple
    of ``groups_per_step`` (all vectorized — no Python per-cell loop).
    Fillers carry value 0 (they contribute exactly 0 through any slab).

    ``groups_per_step=None``/``groups_per_run=None``/``storage=None``
    read the module's GROUPS_PER_STEP / GROUPS_PER_RUN / kernel_dtype()
    at CALL time — a default-arg capture froze the import-time value, so
    layouts built after retuning the constant silently disagreed with
    the kernel consuming them (garbage outputs, caught by a parity
    probe). ``storage`` selects the packed-stream precision rung (see
    KERNEL_DTYPE): int8 narrows the streams and must be consumed by a
    kernel compiled for the same rung (the jit/layout caches key on
    it).

    ``sub_slabs`` is the form the caller chose from the cells
    (``_cell_form``): 0, the run form, or a granule form, where each cell
    pads to a granule of ``GROUP // sub_slabs`` slots instead of a run,
    ``rrun`` holds one read slab a GRANULE, and the kernel loads
    ``sub_slabs`` slabs a group. Shards and streamed chunks, whose streams
    must share one structure, are never given one."""
    if groups_per_step is None:
        groups_per_step = GROUPS_PER_STEP
    if groups_per_run is None:
        groups_per_run = GROUPS_PER_RUN
    if storage is None:
        storage = kernel_dtype()
    else:
        storage = validate_kernel_dtype(storage)
    if groups_per_step % groups_per_run:
        raise ValueError(
            f"GROUPS_PER_RUN={groups_per_run} must divide "
            f"GROUPS_PER_STEP={groups_per_step}: segments are carved into "
            f"whole aligned runs"
        )
    w = np.asarray(write_idx, np.int32)
    r = np.asarray(read_idx, np.int32)
    v = np.asarray(vals, np.float32)
    nws = write_pad // SLAB
    nrs = read_pad // SLAB
    ws_of = (w // SLAB).astype(np.int64)
    cell = ws_of * nrs + (r // SLAB)
    order = np.argsort(cell, kind="stable")
    w, r, v, cell = w[order], r[order], v[order], cell[order]

    uniq, start, counts = np.unique(cell, return_index=True, return_counts=True)
    # the slots that share one read slab: a run, or a granule of a group
    run_nnz = GROUP // sub_slabs if sub_slabs else GROUP * groups_per_run
    pc = (-(-counts // run_nnz) * run_nnz).astype(np.int64)  # padded cell nnz
    cell_ws = (uniq // nrs).astype(np.int64)
    cell_rs = (uniq % nrs).astype(np.int32)

    # write-slab blocks: sum of padded cell counts, padded to SEGMENT
    # multiple (a segment = groups_per_step groups sharing one write slab)
    step_nnz = groups_per_step * GROUP
    nnz_per_ws = np.zeros(nws, np.int64)
    np.add.at(nnz_per_ws, cell_ws, pc)
    ws_padded = -(-nnz_per_ws // step_nnz) * step_nnz  # empty slabs -> 0
    ws_out_start = np.concatenate([[0], np.cumsum(ws_padded)])
    M = int(ws_out_start[-1])
    # tail: the stream must divide into whole DMA steps — append filler
    # SEGMENTS (write slab 0, value 0: they accumulate exactly 0)
    dma_nnz = step_nnz * SEGMENTS_PER_DMA
    M_total = max(-(-M // dma_nnz) * dma_nnz, dma_nnz)

    # each cell's output offset: write-slab base + within-slab running sum
    pc_excl = np.cumsum(pc) - pc
    uws, uws_first, uws_ncells = np.unique(
        cell_ws, return_index=True, return_counts=True
    )
    within_ws = pc_excl - np.repeat(pc_excl[uws_first], uws_ncells)
    cell_out = ws_out_start[cell_ws] + within_ws

    # init with per-write-slab corner fillers, then scatter the real nnz
    out_w = np.zeros(M_total, np.int32)
    out_w[:M] = np.repeat(
        (np.arange(nws, dtype=np.int64) * SLAB), ws_padded
    ).astype(np.int32)
    out_r = np.zeros(M_total, np.int32)
    out_v = np.zeros(M_total, np.float32)
    within_cell = np.arange(len(cell), dtype=np.int64) - np.repeat(start, counts)
    pos = np.repeat(cell_out, counts) + within_cell
    out_w[pos] = w
    out_r[pos] = r
    out_v[pos] = v

    # per-run read slab: cells pad to whole runs (granules) and
    # write-slab/tail fillers start run-aligned, so every aligned run is
    # single-slab by construction — the invariant the kernel's one load a
    # run (a granule) rests on. Filler runs read slab 0: their values are 0
    n_groups = M_total // GROUP
    n_runs = M_total // run_nnz
    rrun = np.zeros(n_runs, np.int32)
    runs_per_cell = (pc // run_nnz).astype(np.int64)
    rpc_excl = np.cumsum(runs_per_cell) - runs_per_cell
    rpos = (
        np.repeat(cell_out // run_nnz, runs_per_cell)
        + np.arange(int(runs_per_cell.sum()), dtype=np.int64)
        - np.repeat(rpc_excl, runs_per_cell)
    )
    rrun[rpos] = np.repeat(cell_rs, runs_per_cell)
    # per-group read slab: every group of a run reads the run's; in the
    # sparse-cell form the first granule's (no kernel reads it)
    rslab = (
        np.ascontiguousarray(rrun[::sub_slabs]) if sub_slabs
        else np.repeat(rrun, groups_per_run)
    )

    wslab = (out_w[::step_nnz] // SLAB).astype(np.int32)
    # one scale a run (the fine form's: a run of groups, never read on f32)
    srun = np.ones(
        n_groups // groups_per_run if sub_slabs > SUB_SLABS else len(rrun),
        np.float32,
    )
    if storage == "f32":
        packed = np.stack(
            [
                out_w.reshape(n_groups, GROUP),
                out_r.reshape(n_groups, GROUP),
                out_v.view(np.int32).reshape(n_groups, GROUP),
            ],
            axis=1,
        )
    else:  # int8: one i32 stream [w off10 | r off10 << 10 | q8 << 20]
        out_q = np.zeros(M_total, np.int64)
        if len(uniq):
            # symmetric per-CELL scale: every nonzero of a (write-slab,
            # read-slab) tile quantizes against the tile's |v| max, and
            # every aligned run of the cell carries that scale in srun
            # (fillers are q=0, inert under any scale)
            amax = np.maximum.reduceat(np.abs(v), start)
            cell_scale = (amax / 127.0).astype(np.float32)
            cell_scale[cell_scale == 0.0] = 1.0
            q = np.clip(
                np.rint(v / np.repeat(cell_scale, counts)), -127, 127
            ).astype(np.int64)
            out_q[pos] = q
            srun[rpos] = np.repeat(cell_scale, runs_per_cell)
        packed = (
            (out_w.astype(np.int64) % SLAB)
            | ((out_r.astype(np.int64) % SLAB) << 10)
            | ((out_q & 0xFF) << 20)
        ).astype(np.int32).reshape(n_groups, 1, GROUP)
    return _Layout(
        packed=packed, wslab=wslab, rslab=rslab, rrun=rrun, srun=srun,
        cells=len(uniq),
    )


def _decode_packed(load, storage):
    """Phase 1's packed-stream decode: the per-rung bit layout.
    ``load(stream)`` returns one packed stream's 2-D block, so each rung
    loads ONLY the streams it consumes; returns ``(rd, vals)`` — i32
    within-slab read offsets and f32 values (RAW q for int8: the per-run
    scale is applied by the caller, after the optional square
    decision)."""
    if storage == "int8":
        pk = load(0)
        rd = (pk >> 10) & 1023
        q = (pk >> 20) & 255
        return rd, (q - ((q & 128) << 1)).astype(jnp.float32)
    rd = load(1)
    return rd, pltpu.bitcast(load(2), jnp.float32)


def _decode_write_offsets(wr, storage):
    """Phase 2's write-stream decode: normalize the per-rung storage to
    i32 write offsets (only their low 10 bits are consumed)."""
    if storage == "int8":
        return wr & 1023  # low 10 bits of the single packed stream
    return wr


class _Copies:
    """Async copies started and waited together."""

    def __init__(self, *copies):
        self.copies = copies

    def start(self):
        for c in self.copies:
            c.start()

    def wait(self):
        for c in self.copies:
            c.wait()


def _tile_kernel_seg(
    wslab_ref, rrun_ref, srun_ref, packed_hbm, src_ref, init_ref, out_ref,
    acc_scratch, p_scratch, pk_buf, dma_sem, ids_buf=None, ids_sem=None,
    *, n_steps, step0, chunk0, chunk_steps, fold, groups, segs, run_groups,
    square_vals, storage, sub_slabs=0,
):
    """The tile-COO kernel: a ``fori_loop`` over DMA steps, each step
    fetching ``segs * groups`` groups in ONE double-buffered DMA and
    running ``segs`` segments, whose groups all write one output slab.

    Phase 1 does per SEGMENT what costs most per group (packed-buffer
    loads, value bitcast, p-scratch store): ONE batched load/bitcast per
    segment, and the source slab loads once per ``run_groups``-group RUN
    (the layout builder guarantees aligned runs are single-slab); the
    lane gather and sublane select then run per group of the run, the
    shape Mosaic's gather takes, and the product per run. Phase 2 is
    the whole-segment scatter staging + 3-term Dekker bf16 MXU
    contraction: the read gather hides behind it, and staging the one-hot
    operands is the cost center, so they are staged once a segment — ONE
    relayout of the packed block to a (1, seg_nnz) row per stream, one
    batched one-hot compare, matmul operands built as VALUES (no VMEM
    scratch round-trip).

    A grid step is a column chunk: ``c = pl.program_id(0)``, chunk
    ``chunk0 + c`` of a stack whose chunks lie end to end in every stream,
    ``chunk_steps`` DMA steps each. Of each of its chunks the call covers
    DMA steps ``[step0, step0 + n_steps)``; the SMEM streams arrive sliced
    to those steps, chunk after chunk, so only the DMAs add ``step0`` and
    the chunk's offset in the whole streams. Where the source holds a
    block a chunk (the margins), the chunks' accumulators add into the one
    output block (``fold``): at ``c == 0`` it is the accumulator, or
    ``init`` plus it, and after that itself plus it — the order in which a
    loop over the chunks would add them.

    A step starts the next fetch, waits its own, and runs phase 1 then
    phase 2 of each segment in turn. There is no skew of segment s+1's
    phase 1 over segment s's phase 2: on the v5e it read 5% slower in
    ``rcv1_fit`` and 11% in ``criteo_fit`` (PERF.md section 6, PRs 29 and 33).

    ``storage`` selects the packed-stream precision rung (KERNEL_DTYPE):
    only the stream decode changes — f32 bitcasts the value stream (the
    bitwise anchor), int8 unpacks the single i32 stream and
    dequantizes by the per-run SMEM scale (``srun_ref``, None on the
    f32 rung). Products land in f32 ``p_scratch`` either way, and
    phase 2's Dekker-split f32 MXU accumulation is IDENTICAL across
    rungs.

    ``sub_slabs`` selects the SPARSE-CELL form's phase 1 (see SUB_SLABS):
    ``rrun_ref`` then holds a read slab a granule of ``GROUP // sub_slabs``
    lanes, and a group loads and lane-gathers each of its ``sub_slabs``
    slabs and keeps, lane range by lane range, the granule's own. At
    ``sub_slabs`` words a group that stream would not fit SMEM as a
    prefetch operand (32 B a group: 16 MB at 10^6 columns, eighteen kernel
    calls and as many compiles), so it stays in HBM and each DMA step
    brings its own words into the double-buffered ``ids_buf`` beside the
    packed block: one call a stream whatever its length. Mosaic tiles a
    1-D int32 array in HBM by 1,024, so a step's words must be a multiple
    of that: 128 groups x 8 slabs, the shipped carve. Phase 2 does not
    know the form.

    ``sub_slabs`` above ``SUB_SLABS`` is the FINE form (``FINE_SLABS``):
    the same ids in HBM, a granule of ``GROUP // sub_slabs`` lanes, and
    ``phase1_fine`` in place of ``phase1_sub``. Its 64 slabs a group
    unrolled over a step would be 8,192 slab sites a kernel, so segments
    and blocks of 8 groups are ``fori_loop``s there, and a group merges its
    slabs' gathers by a tree of selects, one lane bit a level."""
    step_groups = segs * groups
    seg_nnz = groups * GROUP
    seg_runs = groups // run_groups
    step_runs = step_groups // run_groups
    # int32 iota: this hardware supports no narrower iota (8- and 16-bit
    # both rejected by Mosaic) — the win here is the batching, not density
    iota8 = jax.lax.broadcasted_iota(jnp.int32, (8, GROUP), 0)
    iota8_seg = jax.lax.broadcasted_iota(jnp.int32, (8, seg_nnz), 0)
    iota_sub_seg = jax.lax.broadcasted_iota(jnp.int32, (GROUP, seg_nnz), 0)
    if 0 < sub_slabs <= SUB_SLABS:
        # lanes from granule u on: where slab u's gather replaces the others'
        lane8 = jax.lax.broadcasted_iota(jnp.int32, (8, GROUP), 1)
        from_granule = [lane8 >= u * (GROUP // sub_slabs) for u in range(sub_slabs)]
    c = pl.program_id(0)  # the chunk; read outside every loop
    first = (chunk0 + c) * chunk_steps + step0  # its first step in HBM
    base = c * n_steps  # and in the call's SMEM streams
    acc_scratch[...] = jnp.zeros_like(acc_scratch)

    def dma(slot, t):
        packed = pltpu.make_async_copy(
            packed_hbm.at[pl.ds((first + t) * step_groups, step_groups)],
            pk_buf.at[slot],
            dma_sem.at[slot],
        )
        if not sub_slabs:
            return packed
        step_words = step_groups * sub_slabs
        return _Copies(packed, pltpu.make_async_copy(
            rrun_ref.at[pl.ds(
                pl.multiple_of((first + t) * step_words, step_words), step_words
            )],
            ids_buf.at[pl.ds(
                pl.multiple_of(slot * step_words, step_words), step_words
            )],
            ids_sem.at[slot],
        ))

    def phase1(buf_slot, t, s2):
        """Batched gather/sublane-select/product of segment (t, s2) from
        ``pk_buf[buf_slot]`` into ``p_scratch``."""
        g0 = s2 * groups
        # per-group skeleton, hoisted: one packed-buffer load per
        # stream and one value decode for the WHOLE segment
        rd_all, vals_all = _decode_packed(
            lambda s: pk_buf[buf_slot, g0:g0 + groups, s, :], storage
        )  # (groups, GROUP) each
        lane_all = rd_all & 127
        sub_all = (rd_all >> 7) & 7
        if square_vals and storage != "int8":
            # int8 squares AFTER dequantization (below): (q·s)² needs the
            # per-run scale, and scale² must not leak into the raw q
            vals_all = vals_all * vals_all
        for b in range(seg_runs):
            gb = b * run_groups
            # ONE shared-slab load per run, hoisted out of its groups
            rslab = rrun_ref[t * step_runs + s2 * seg_runs + b]
            slab = src_ref[pl.ds(pl.multiple_of(rslab * 8, 8), 8), :]
            # per group of the run: an (8, GROUP)-on-(8, GROUP) lane
            # gather from the one hoisted slab, then the sublane select.
            # Mosaic's dynamic_gather takes indices of the operand's own
            # shape only, and it has no (run_nnz,) -> (run_groups, GROUP)
            # reshape, so the run's rows are built one (1, GROUP) row at
            # a time and joined along sublanes
            rows = []
            for j in range(run_groups):
                row = slice(gb + j, gb + j + 1)
                gathered = jnp.take_along_axis(
                    slab,
                    jnp.broadcast_to(lane_all[row, :], (8, GROUP)),
                    axis=1,
                )
                sel = (
                    iota8 == jnp.broadcast_to(sub_all[row, :], (8, GROUP))
                ).astype(jnp.float32)
                rows.append(jnp.sum(gathered * sel, axis=0, keepdims=True))
            src_vals = jnp.concatenate(rows, axis=0)  # (run_groups, GROUP)
            v = vals_all[gb:gb + run_groups, :]
            if storage == "int8":
                v = v * srun_ref[t * step_runs + s2 * seg_runs + b]
                if square_vals:
                    v = v * v
            p_scratch[gb:gb + run_groups, :] = v * src_vals

    def phase1_sub(buf_slot, t, s2):
        """Phase 1 of the sparse-cell form: a slab a GRANULE. Each group
        gathers its lanes from all ``sub_slabs`` of its slabs at once (one
        lane gather over the slabs stacked along sublanes) and keeps from
        slab u the lanes of granule u. Unrolled over the segment like the
        run form's: rolled into a ``fori_loop`` over blocks of 8 groups the
        kernel took 1.46 times as long (``criteo_fit`` ``fit_s`` 1.804
        against 1.239 s; my chip runs, PR 31). ``lax`` primitives where a
        ``jnp`` wrapper would do: at 5,000 sites a kernel each wrapper's
        own jit lookup is most of a minute of tracing a process."""
        g0 = s2 * groups
        rd_all, vals_all = _decode_packed(
            lambda s: pk_buf[buf_slot, g0:g0 + groups, s, :], storage
        )  # (groups, GROUP) each
        lane_all = rd_all & 127
        sub_all = (rd_all >> 7) & 7
        if square_vals:
            vals_all = vals_all * vals_all
        ids0 = (buf_slot * step_groups + g0) * sub_slabs
        stacked = (sub_slabs * 8, GROUP)

        def slab(k):
            """The source slab of granule ``k`` of the segment."""
            at = ids0 + k if isinstance(ids0, int) else jax.lax.add(
                ids0, np.asarray(k, ids0.dtype)
            )
            row = jax.lax.mul(ids_buf[at], np.int32(8))
            return src_ref[pl.ds(pl.multiple_of(row, 8), 8), :]

        for gb in range(0, groups, run_groups):
            rows = []
            for j in range(gb, gb + run_groups):
                lane_j = jax.lax.slice_in_dim(lane_all, j, j + 1, axis=0)
                sub_j = jax.lax.slice_in_dim(sub_all, j, j + 1, axis=0)
                slabs = jax.lax.concatenate(
                    [slab(j * sub_slabs + u) for u in range(sub_slabs)], 0
                )
                got = jnp.take_along_axis(
                    slabs, jax.lax.broadcast_in_dim(lane_j, stacked, (0, 1)),
                    axis=1,
                )
                gathered = jax.lax.slice_in_dim(got, 0, 8, axis=0)
                for u in range(1, sub_slabs):
                    gathered = jax.lax.select(
                        from_granule[u],
                        jax.lax.slice_in_dim(got, u * 8, u * 8 + 8, axis=0),
                        gathered,
                    )
                sel = jax.lax.convert_element_type(
                    jax.lax.eq(
                        iota8,
                        jax.lax.broadcast_in_dim(sub_j, (8, GROUP), (0, 1)),
                    ),
                    jnp.float32,
                )
                rows.append(jnp.sum(
                    jax.lax.mul(gathered, sel), axis=0, keepdims=True
                ))
            p_scratch[gb:gb + run_groups, :] = jax.lax.mul(
                jax.lax.slice_in_dim(vals_all, gb, gb + run_groups, axis=0),
                jax.lax.concatenate(rows, 0),
            )

    def phase1_fine(buf_slot, t, s2):
        """Phase 1 of the fine form: a slab a granule of ``GROUP //
        sub_slabs`` lanes. A ``fori_loop`` over blocks of 8 groups; a group
        lane-gathers each of its ``sub_slabs`` slabs and merges them as it
        goes, pairwise by a select on one lane bit a level (the bit that
        tells the upper half of the merged granules), so a handful of
        gathers are live at once, not ``sub_slabs``."""
        blk = min(8, groups)
        granule_bits = (GROUP // sub_slabs).bit_length() - 1
        lanes = jax.lax.broadcasted_iota(jnp.int32, (8, GROUP), 1)
        upper = [
            ((lanes >> (granule_bits + level)) & 1) == 1
            for level in range(sub_slabs.bit_length() - 1)
        ]

        def block(b, carry):
            gb = pl.multiple_of(s2 * groups + b * blk, blk)
            rd, vals = _decode_packed(
                lambda s: pk_buf[buf_slot, pl.ds(gb, blk), s, :], storage
            )  # (blk, GROUP) each
            lane_all = rd & 127
            sub_all = (rd >> 7) & 7
            if square_vals:
                vals = vals * vals
            ids0 = (buf_slot * step_groups + gb) * sub_slabs
            rows = []
            for j in range(blk):
                lane_j = jnp.broadcast_to(lane_all[j:j + 1, :], (8, GROUP))
                merged = []  # (level, gather), one entry a level at most
                for u in range(sub_slabs):
                    row = ids_buf[ids0 + (j * sub_slabs + u)] * 8
                    got = jnp.take_along_axis(
                        src_ref[pl.ds(pl.multiple_of(row, 8), 8), :],
                        lane_j, axis=1,
                    )
                    level = 0
                    while merged and merged[-1][0] == level:
                        got = jax.lax.select(upper[level], got, merged.pop()[1])
                        level += 1
                    merged.append((level, got))
                ((_, gathered),) = merged
                sel = (
                    iota8 == jnp.broadcast_to(sub_all[j:j + 1, :], (8, GROUP))
                ).astype(jnp.float32)
                rows.append(jnp.sum(gathered * sel, axis=0, keepdims=True))
            p_scratch[pl.ds(pl.multiple_of(b * blk, blk), blk), :] = (
                vals * jnp.concatenate(rows, axis=0)
            )
            return carry

        jax.lax.fori_loop(0, groups // blk, block, 0)

    def phase2(buf_slot, t, s2):
        """Whole-segment scatter staging + MXU contraction of segment
        (t, s2), reading phase 1's products from ``p_scratch``:
        one relayout per stream, int8 one-hot compares, operands as
        values."""
        g0 = s2 * groups
        seg = (
            slice(g0, g0 + groups) if isinstance(g0, int)
            else pl.ds(pl.multiple_of(g0, groups), groups)
        )
        wr = _decode_write_offsets(
            pk_buf[buf_slot, seg, 0, :], storage
        )  # (groups, GROUP)
        wr_row = wr.reshape(1, seg_nnz)
        lane_w = wr_row & 127
        sub_w = (wr_row >> 7) & 7
        p_row = p_scratch[...].reshape(1, seg_nnz)
        # explicit broadcasts + mask-multiply: the implicit (1, n) ->
        # (8, n) broadcast inside compare/select trips a Mosaic
        # "invalid relayout" on the i1 mask
        mask8 = iota8_seg == jnp.broadcast_to(sub_w, (8, seg_nnz))
        a = (
            jnp.broadcast_to(p_row, (8, seg_nnz))
            * mask8.astype(jnp.float32)
        )
        a_hi = a.astype(jnp.bfloat16)
        rem = a - a_hi.astype(jnp.float32)
        a_mid = rem.astype(jnp.bfloat16)
        a_lo = (rem - a_mid.astype(jnp.float32)).astype(jnp.bfloat16)
        bt = (
            iota_sub_seg == jnp.broadcast_to(lane_w, (GROUP, seg_nnz))
        ).astype(jnp.bfloat16)
        dims = (((1,), (1,)), ((), ()))
        ms = (
            jax.lax.dot_general(
                a_hi, bt, dims, preferred_element_type=jnp.float32
            )
            + jax.lax.dot_general(
                a_mid, bt, dims, preferred_element_type=jnp.float32
            )
            + jax.lax.dot_general(
                a_lo, bt, dims, preferred_element_type=jnp.float32
            )
        )
        ws = wslab_ref[t * segs + s2]
        idx = pl.ds(pl.multiple_of(ws * 8, 8), 8)
        acc_scratch[idx, :] = acc_scratch[idx, :] + ms

    phase1_of_form = phase1_sub if sub_slabs else phase1

    def step(t, carry):
        slot = jax.lax.rem(t, 2)
        nxt = jax.lax.rem(t + 1, 2)

        # start the next fetch first (its pk_buf slot was last read by the
        # previous step), so it overlaps this whole step
        @pl.when(t + 1 < n_steps)
        def _():
            dma(nxt, t + 1).start()

        dma(slot, t).wait()
        ts = base + t  # the step in the SMEM streams
        if sub_slabs > SUB_SLABS:
            def segment(s2, c):
                phase1_fine(slot, ts, s2)
                phase2(slot, ts, s2)
                return c

            jax.lax.fori_loop(0, segs, segment, 0)
            return carry
        for s2 in range(segs):
            phase1_of_form(slot, ts, s2)
            phase2(slot, ts, s2)
        return carry

    dma(0, 0).start()
    jax.lax.fori_loop(0, n_steps, step, 0)
    if not fold:
        out_ref[...] = acc_scratch[...]
        return

    @pl.when(c == 0)
    def _():
        out_ref[...] = (
            acc_scratch[...] if init_ref is None
            else init_ref[...] + acc_scratch[...]
        )

    @pl.when(c > 0)
    def _():
        out_ref[...] = out_ref[...] + acc_scratch[...]


@functools.partial(
    jax.jit,
    static_argnames=(
        "out_pad", "src_pad", "square_vals",
        "groups", "segs", "run_groups",
        "storage", "interpret", "topology", "stack",
    ),
)
def _tiled_apply_jit(
    layout_arrays, src, out_pad, src_pad, square_vals,
    groups, segs, run_groups, storage, interpret,
    topology=None, stack=1,
):
    packed, wslab, rslab, rrun, srun = layout_arrays
    sub_slabs, n_steps, prefetch, calls = _stream_plan(
        layout_arrays, stack, groups, segs, run_groups, storage
    )
    step_groups = segs * groups
    src_shape = (src_pad // 128, 128)
    out_shape = (out_pad // 128, 128)
    # the margins' source holds a block a chunk, and the chunks' outputs
    # add (fold); the gradient's is one block, and each chunk writes its own
    fold = int(src.shape[0]) != src_pad
    if fold and int(src.shape[0]) != stack * src_pad:
        raise ValueError(
            f"a source of {src.shape[0]} is neither one block of {src_pad} "
            f"nor one a chunk of {stack}"
        )
    src_mat = src.reshape(-1, 128)
    if storage != "f32":
        # the gathered operand carries bf16 VALUES under the reduced
        # rung (the source vector changes per call, so per-call int8
        # quantization would buy nothing) but stays in a 32-bit table:
        # Mosaic's dynamic_gather needs operand and i32 indices of one
        # bitwidth, and the table is d*4 bytes once per call against
        # 4 bytes per nonzero of packed stream
        src_mat = src_mat.astype(jnp.bfloat16).astype(jnp.float32)
    # packed-stream shape per rung (must match the layout builder):
    # f32 (.., 3, GROUP) | int8 (.., 1, GROUP), both i32 — a layout
    # built under one rung fails loudly under a kernel compiled for
    # another (the caches key on the rung, so the only way there is
    # hand-assembling mismatched pieces)
    n_streams = 1 if storage == "int8" else 3
    # p_scratch: phase 1's products of one segment, drained by its phase 2
    p_scratch = pltpu.VMEM((groups, GROUP), jnp.float32)
    pk_buf = pltpu.VMEM((2, step_groups, n_streams, GROUP), jnp.int32)
    scratch = [
        pltpu.VMEM(out_shape, jnp.float32),
        p_scratch, pk_buf, pltpu.SemaphoreType.DMA((2,)),
    ]
    hbm_inputs = [packed]
    if sub_slabs:
        # the sparse-cell form's slab ids stay in HBM: the kernel brings
        # each DMA step's words into SMEM itself (see _tile_kernel_seg)
        hbm_inputs.append(rrun)
        scratch = scratch + [
            pltpu.SMEM((2 * step_groups * sub_slabs,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    n_prefetch = len(prefetch)
    whole = lambda i, *_: (0, 0)

    def call(chunk0, chunks, step0, steps, init=None):
        """One kernel call over chunks ``[chunk0, chunk0 + chunks)``, a grid
        step each, and DMA steps ``[step0, step0 + steps)`` of each: the
        whole streams stay in HBM (no slice copy; the kernel's DMAs start
        at the chunk's offset and ``step0``) and only the SMEM streams are
        sliced."""
        kernel = functools.partial(
            _tile_kernel_seg, n_steps=steps, step0=step0, chunk0=chunk0,
            chunk_steps=n_steps, fold=fold and (chunks > 1 or init is not None),
            groups=groups, segs=segs,
            run_groups=run_groups, square_vals=square_vals,
            storage=storage, sub_slabs=sub_slabs,
        )

        def body(*refs):
            pre, refs = refs[:n_prefetch], refs[n_prefetch:]
            hbm, refs = refs[:len(hbm_inputs)], refs[len(hbm_inputs):]
            src_ref, init_ref = refs[0], refs[1] if init is not None else None
            refs = refs[1 if init is None else 2:]  # out, scratch
            rrun_ref = hbm[1] if sub_slabs else pre[1]
            srun_ref = pre[2] if storage == "int8" else None
            return kernel(
                pre[0], rrun_ref, srun_ref, hbm[0], src_ref, init_ref, *refs
            )

        blocked = [pl.BlockSpec(
            src_shape, (lambda i, *_: (chunk0 + i, 0)) if fold else whole
        )]
        if init is not None:
            blocked.append(pl.BlockSpec(out_shape, whole))
        f = pl.pallas_call(
            body,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=n_prefetch,
                grid=(chunks,),
                in_specs=[
                    pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
                    for _ in hbm_inputs
                ] + blocked,
                out_specs=pl.BlockSpec(
                    out_shape, whole if fold else lambda i, *_: (i, 0)
                ),
                scratch_shapes=scratch,
            ),
            out_shape=jax.ShapeDtypeStruct(
                (out_shape[0] * (1 if fold else chunks), 128), jnp.float32
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=120 * 1024 * 1024,
            ),
            interpret=interpret,
        )
        # the call's steps, chunk after chunk, of each SMEM stream
        lo, count = chunk0 * n_steps + step0, chunks * steps
        per_step = [int(a.shape[0]) // (stack * n_steps) for a in prefetch]
        sliced = [
            a[lo * c:(lo + count) * c] for a, c in zip(prefetch, per_step)
        ]
        extra = [] if init is None else [init]
        return f(*sliced, *hbm_inputs, src_mat, *extra)

    # Calls over whole chunks run in chunk order, each margins call adding
    # onto the calls before it in the kernel; the calls over one chunk's
    # step ranges (a chunk beyond SMEM) sum, and the margins' chunks then
    # add in order. Every sum is the one a loop over the chunks makes.
    parts = []
    for chunk0, chunks, step0, steps in calls:
        if step0:  # a later step range of the chunk before
            parts[-1] = parts[-1] + call(chunk0, chunks, step0, steps)
        elif fold and parts and steps == n_steps:
            parts.append(call(chunk0, chunks, 0, steps, init=parts.pop()))
        else:
            parts.append(call(chunk0, chunks, 0, steps))
    if not fold:
        return jnp.concatenate(parts).reshape(-1)
    out = parts[0]
    for part in parts[1:]:
        out = out + part
    return out.reshape(-1)


# One kernel call's scalar-prefetch streams must fit SMEM: XLA reports
# 1.00M of it on a v5e and refuses the program beyond. The budget leaves
# room for the kernel's own scalars. A stack's chunks share a call where
# their streams fit; only a chunk beyond the budget alone is cut into
# calls over its steps (``_stream_plan``).
_SMEM_PREFETCH_BUDGET = 896 * 1024


def _piece_bounds(n_units: int, smem_per_unit: int) -> list[int]:
    """End units (DMA steps, or a stack's chunks) of the kernel calls
    ``n_units`` run as: the fewest near-equal pieces whose scalar-prefetch
    bytes each fit ``_SMEM_PREFETCH_BUDGET`` (one piece where all fit)."""
    max_units = max(_SMEM_PREFETCH_BUDGET // smem_per_unit, 1)
    n_pieces = -(-n_units // max_units)
    return [n_units * (i + 1) // n_pieces for i in range(n_pieces)]


def _stream_plan(layout_arrays, stack, groups, segs, run_groups, storage):
    """What one direction's streams of ``stack`` chunks (end to end in every
    stream) hand the kernel: ``(sub_slabs, n_steps, prefetch, calls)``, the
    form read off the streams' own shapes, each chunk's DMA steps, the
    scalar-prefetch streams, and the kernel calls as ``(chunk0, chunks,
    step0, steps)``. The calls are the fewest over whole chunks whose
    prefetch streams fit SMEM; a chunk beyond it runs as calls over
    consecutive step ranges. The split is read off the built streams'
    own size — how far a layout pads depends on where its nonzeros fall (8
    uniform nonzeros a row at d=2^17 pad 4x, 32 pad 1.5x), which no
    shape-only bound predicts."""
    packed, wslab, rslab, rrun, srun = layout_arrays
    n_groups = int(packed.shape[0]) // stack
    n_steps = n_groups // (segs * groups)
    # the stream's form is in its own shape: a read slab a run of
    # run_groups groups, or (the sparse-cell form) several a group
    n_slab_ids = int(rrun.shape[0]) // stack
    sub_slabs = 0 if n_slab_ids * run_groups == n_groups else n_slab_ids // n_groups
    if sub_slabs and (
        sub_slabs * n_groups != n_slab_ids or GROUP % sub_slabs
        or storage != "f32"
    ):
        raise ValueError(
            f"a stream of {n_groups} groups with {n_slab_ids} read slabs on "
            f"the {storage} rung is no form this kernel reads"
        )
    # Scalar-prefetch operands live in SMEM (1 MiB on a v5e) for the whole
    # call, so the kernel is handed only the streams it reads: the write
    # slab per segment, the read slab per run, and the dequant scales on
    # the int8 rung alone. Passing all four cost 8.1 B a group
    # and put a shape of n=2^19 rows (~166k groups) over the limit.
    prefetch = [wslab] if sub_slabs else [wslab, rrun]
    if storage == "int8":
        prefetch.append(srun)
    chunk_bytes = sum(
        int(a.shape[0]) // stack * a.dtype.itemsize for a in prefetch
    )
    if chunk_bytes <= _SMEM_PREFETCH_BUDGET:
        ends = _piece_bounds(stack, chunk_bytes)
        calls = [(lo, hi - lo, 0, n_steps) for lo, hi in zip([0] + ends, ends)]
    else:
        ends = [0] + _piece_bounds(n_steps, chunk_bytes // n_steps)
        calls = [
            (c, 1, lo, hi - lo) for c in range(stack)
            for lo, hi in zip(ends, ends[1:])
        ]
    return sub_slabs, n_steps, prefetch, calls


def _tiled_apply(layout_arrays, src, out_pad, src_pad, square_vals=False,
                 stack=1):
    """Run one direction's kernel over ``stack`` column chunks whose streams
    lie end to end: the margins take a source of ``stack * src_pad`` and
    give ``out_pad``, the gradient the reverse.

    The tuned constants enter the jitted call as STATIC arguments, read
    from the module at CALL time: they are part of the executable's cache
    key, so a retune after a compile can never silently reuse a stale
    executable whose argument shapes happen to coincide (e.g. swapping
    GROUPS_PER_STEP=32/SEGMENTS_PER_DMA=4 for 16/8 keeps every stream
    shape identical while changing the kernel's segment carve). This is
    also what makes the compiled kernel a PROCESS-WIDE executable cache:
    any layout with the same stream shapes and constants — across
    streaming chunks, GAME visits and CV folds — re-enters the same
    compiled program. The KERNEL_DTYPE storage rung is part of the same
    static key: toggling it mid-process recompiles, never reuses.

    Analytic cost capture (``obs/devcost``) shadows the same key: an
    eager call whose (knob tuple, stream signature) is fresh captures the
    kernel executable's XLA flops/bytes once — calls under an outer
    trace (the optimizer/scoring jits) skip, and THAT enclosing
    executable is captured at its own boundary instead.

    ``tile_apply.calls`` and ``tile_apply.grid_chunks`` in the registry
    count, at every trace (and eager call), the kernel calls this builds
    and the column chunks they run as grid steps."""
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.parallel.multihost import effective_topology

    args = (
        layout_arrays, src, out_pad, src_pad, square_vals,
        GROUPS_PER_STEP, SEGMENTS_PER_DMA, GROUPS_PER_RUN,
        kernel_dtype(), _interpret(),
        # effective topology rides as a static key: a degrade-in-place
        # must never re-enter a pre-loss executable by shape coincidence,
        # and a same-topology re-entry compiles nothing new
        effective_topology(), stack,
    )
    calls = _stream_plan(layout_arrays, stack, *args[5:9])[-1]
    REGISTRY.counter_inc("tile_apply.calls", float(len(calls)))
    REGISTRY.counter_inc(
        "tile_apply.grid_chunks", float(sum(call[1] for call in calls))
    )
    from photon_ml_tpu.obs import devcost

    devcost.capture("sparse_tiled.tiled_apply", _tiled_apply_jit, args)
    return _tiled_apply_jit(*args)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["m_arrays", "g_arrays"],
    meta_fields=["row_start", "n_pad", "d_pad", "stack"],
)
@dataclass(frozen=True)
class _TileChunk:
    """One row chunk over every column: both direction layouts.

    A build whose width needs several column chunks of ``d_pad`` columns
    STACKS them: ``stack`` of them side by side from column 0, laid
    end to end in every stream, each as long as the others (``packed`` is
    ``(stack * groups, streams, GROUP)``, at the rank of a lone chunk's), so
    a chunk is one contiguous range of whole DMA steps. A pass is ONE
    kernel call a direction whose grid steps are the chunks: XLA compiles
    every custom call of a program apart, and ten chunks called one by
    one cost minutes of compile; a loop over the chunks would slice and
    relay out each chunk's streams on every pass. A lone column chunk is
    ``stack`` 1."""

    m_arrays: tuple  # margins: (packed, wslab, rslab, rrun, srun), write=row
    g_arrays: tuple  # gradient: same five streams, write=col
    row_start: int = field(metadata=dict(static=True))
    n_pad: int = field(metadata=dict(static=True))
    d_pad: int = field(metadata=dict(static=True))
    stack: int = field(default=1, metadata=dict(static=True))

    def matvec_part(self, w: Array) -> Array:
        return _tiled_apply(
            self.m_arrays, w, self.n_pad, self.d_pad, stack=self.stack
        )

    def rmatvec_part(self, r_full: Array, squared: bool) -> Array:
        r = jax.lax.dynamic_slice(r_full, (self.row_start,), (self.n_pad,))
        return _tiled_apply(
            self.g_arrays, r, self.d_pad, self.n_pad, square_vals=squared,
            stack=self.stack,
        )


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["chunks", "labels", "offsets", "weights", "head_X",
                 "head_cols"],
    meta_fields=["num_features", "num_rows_real", "n_pad_total", "d_pad_total",
                 "fe_range"],
)
@dataclass(frozen=True)
class TiledSparseBatch:
    """Drop-in ``Batch`` whose margins/gradient run the tile-COO Pallas
    kernels. ``labels``/``offsets``/``weights`` are (n,) with the ORIGINAL
    row indexing. Build with ``tile_sparse_batch``; shapes beyond one
    kernel's VMEM bounds arrive as multiple row/col chunks.

    ``head_X``/``head_cols`` are the dense head (module docstring): the
    (n, H) float32 matrix of the H most popular columns, H a multiple of
    128, and their (H,) int32 column ids; the chunks then hold the other
    columns' nonzeros only. None (both) where the build was not asked for
    a head or found none. Head and chunks hold one entry a (row, column),
    the sum of a row's draws of that column, so ``rmatvec_sq`` squares the
    matrix's entry everywhere, as ``ops/batch.densify`` has it."""

    chunks: tuple  # tuple[_TileChunk, ...]
    labels: Array
    offsets: Array
    weights: Array
    num_features: int = field(metadata=dict(static=True))
    num_rows_real: int = field(metadata=dict(static=True))
    n_pad_total: int = field(metadata=dict(static=True))
    d_pad_total: int = field(metadata=dict(static=True))
    # Feature-range identity under PHOTON_FE_SHARD: (pid, lo, hi, P) when
    # this batch's columns are the [lo, hi) slice of the global feature
    # space, else None. STATIC (a meta field) so the range id + boundaries
    # ride every jit key that takes the batch — the dtype-ladder
    # discipline: a re-plan invalidates by key, never by luck.
    fe_range: tuple | None = field(default=None, metadata=dict(static=True))
    head_X: Array | None = None
    head_cols: Array | None = None

    @property
    def num_rows(self) -> int:
        return self.labels.shape[0]

    def matvec(self, w: Array) -> Array:
        d = self.num_features
        w_pad = w if d == self.d_pad_total else jnp.pad(w, (0, self.d_pad_total - d))
        m = jnp.zeros((self.n_pad_total,), jnp.float32)
        with stage(GLM_TAIL):
            for c in self.chunks:
                m = jax.lax.dynamic_update_slice(
                    m,
                    jax.lax.dynamic_slice(m, (c.row_start,), (c.n_pad,))
                    + c.matvec_part(w_pad),
                    (c.row_start,),
                )
        m = m[: self.num_rows]
        if self.head_X is not None:
            # float32 multiply-reduces, not matmuls (which a TPU rounds to
            # bfloat16 by default): exact, and read at the HBM's rate
            with stage(GLM_HEAD):
                m = m + jnp.sum(self.head_X * w[self.head_cols], axis=1)
        return m

    def _rmatvec(self, r: Array, squared: bool) -> Array:
        n = self.num_rows
        r_pad = r if n == self.n_pad_total else jnp.pad(r, (0, self.n_pad_total - n))
        g = jnp.zeros((self.d_pad_total,), jnp.float32)
        with stage(GLM_TAIL):
            for c in self.chunks:  # row chunks, each over every column
                g = g + c.rmatvec_part(r_pad, squared)
        g = g[: self.num_features]
        if self.head_X is not None:
            with stage(GLM_HEAD):
                X = self.head_X * self.head_X if squared else self.head_X
                g = g.at[self.head_cols].add(jnp.sum(X * r[:, None], axis=0))
        return g

    def rmatvec(self, r: Array) -> Array:
        return self._rmatvec(r, squared=False)

    def rmatvec_sq(self, r: Array) -> Array:
        return self._rmatvec(r, squared=True)


# A chunk holds four tables in VMEM across its two kernels: the src block,
# the out block, and the f32 accumulation scratch (out-sized), plus the
# staged A/B_T step matrices. Bound each chunk's table sizes well inside
# the ~128 MB VMEM limit; bigger problems are built as multiple chunks.
_MAX_TABLE_ROWS = 1 << 22  # 4M rows -> out block + scratch = 2 x 16 MB
_MAX_TABLE_COLS = 1 << 21  # 2M cols -> 2 x 8 MB
def _pack_chunk(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
    n_pad: int, d_pad: int, sub_slabs: int = 0,
) -> tuple[tuple, tuple, int]:
    """One chunk's two layouts in the form ``sub_slabs`` (0: runs), as host
    streams ``(packed, wslab, rslab, rrun, srun)`` a direction, and how
    many cells hold a nonzero (the same cells in both directions)."""
    storage = kernel_dtype()  # ONE call-time read for both directions
    with span(LAYOUT_PACK):
        m = build_write_major_layout(rows, cols, vals, n_pad, d_pad,
                                     storage=storage, sub_slabs=sub_slabs)
        g = build_write_major_layout(cols, rows, vals, d_pad, n_pad,
                                     storage=storage, sub_slabs=sub_slabs)
    streams = lambda lay: (lay.packed, lay.wslab, lay.rslab, lay.rrun, lay.srun)
    return streams(m), streams(g), m.cells


def _stage_chunk(parts: list, row_start: int, n_pad: int,
                 d_pad: int) -> _TileChunk:
    """The column chunks ``parts`` of one row chunk (``_pack_chunk``'s host
    streams, in column order) on the device as ONE ``_TileChunk``: a lone
    one as it is, several stacked (``stack = len(parts)``), each
    direction's streams padded to one group count and laid end to end."""
    with span(LAYOUT_STAGE):
        sides = []
        for side in (0, 1):
            streams = [part[side] for part in parts]
            if len(streams) == 1:
                sides.append(tuple(map(jnp.asarray, streams[0])))
                continue
            target = max(s[0].shape[0] for s in streams)
            streams = [_pad_layout_groups(s, target) for s in streams]
            sides.append(tuple(
                jnp.asarray(np.concatenate([s[i] for s in streams]))
                for i in range(5)
            ))
        return _TileChunk(
            m_arrays=sides[0], g_arrays=sides[1], row_start=row_start,
            n_pad=n_pad, d_pad=d_pad, stack=len(parts),
        )


# bytes one packed slot of the tile-COO streams holds, by storage rung
_SLOT_BYTES = {"f32": 12, "int8": 4}


def _head_columns(counts: np.ndarray, num_rows: int, free_bytes: float,
                  tail_form) -> tuple[np.ndarray | None, int]:
    """The dense head's columns for a matrix whose column c stores
    ``counts[c]`` nonzeros in ``num_rows`` rows, and the form of the tail
    that head leaves: ``(cols, sub_slabs)``, ``cols`` int64 ids by
    descending count, a multiple of ``HEAD_LANES`` of them, or None for no
    head.

    Columns join by whole lane blocks, most popular first, while a block
    is filled in at least ``HEAD_MIN_FILL`` of its rows x lanes: the rule
    knows fills only, not sizes. The head then shrinks until what the
    layout will hold at a fit fits ``free_bytes``: the head's float32, and
    for every nonzero left to the tail a packed slot in each direction,
    times the padding the tail's form will have, and the same again for
    the copy into which XLA relayouts the streams once a fit (PERF.md
    finding 4). ``tail_form(head_cols)`` gives ``(sub_slabs, padding)``
    for the tail that a head of ``head_cols`` leaves: the form its cells
    get (``_cell_form``) and its slots over nonzeros. It is asked at the
    width the fills choose (a narrower head leaves the same cells fuller,
    so the padding errs high), and once more only where the budget or the
    eighth below changes the head, so the form returned is the built
    tail's: one count of the cells decides both (PR 40). Padding 1.09-1.22
    on cells of 700 nonzeros (PERF.md, PR 28), 1.42 on cells of 20 (PR 31).
    A head that would hold under an eighth of the nonzeros is not worth
    its second code path: None."""
    blocks = len(counts) // HEAD_LANES
    total = int(counts.sum())
    order = np.argsort(-counts, kind="stable")[: blocks * HEAD_LANES]
    if not blocks or not total:
        return None, tail_form(order[:0])[0]
    block_nnz = counts[order].reshape(blocks, HEAD_LANES).sum(axis=1)
    # counts descend, so the blocks that pay are a prefix
    width = priced = int(np.count_nonzero(
        block_nnz >= HEAD_MIN_FILL * HEAD_LANES * num_rows
    ))
    head_nnz = np.concatenate([[0], np.cumsum(block_nnz)])
    sub_slabs, padding = tail_form(order[: width * HEAD_LANES])
    # a tail nonzero: two slots, their padding, the relayout's copy
    tail_bytes = 2 * _SLOT_BYTES[kernel_dtype()] * padding * 2
    while width and (
        4.0 * num_rows * width * HEAD_LANES
        + tail_bytes * (total - head_nnz[width]) > free_bytes
    ):
        width -= 1
    if head_nnz[width] * 8 < total:
        width = 0
    if width != priced:
        sub_slabs, _ = tail_form(order[: width * HEAD_LANES])
    return (order[: width * HEAD_LANES] if width else None), sub_slabs


def _tail_form(indices: np.ndarray, live: np.ndarray, num_features: int,
               head_cols: np.ndarray) -> tuple[int, float]:
    """The form (``_cell_form``) of the tail that a head of ``head_cols``
    leaves of the ``live`` entries, and its slots over nonzeros: ONE form
    for every chunk, so that their kernels share one trace (row and column
    chunks are cut at slab boundaries, so the whole matrix's cells are the
    chunks'). One count over the entries, no sort."""
    in_tail = np.ones(num_features, bool)
    in_tail[head_cols] = False
    at = live & in_tail[indices]
    col_slabs = -(-num_features // SLAB)
    row_slab = np.arange(indices.shape[0], dtype=np.int64) // SLAB
    cells = np.bincount(((row_slab * col_slabs)[:, None] + indices // SLAB)[at])
    sub_slabs, slots = _cell_form(cells[cells > 0], GROUPS_PER_RUN, kernel_dtype())
    return sub_slabs, slots / max(int(np.count_nonzero(at)), 1)


@functools.partial(jax.jit, static_argnames=("num_features",))
def _head_matrix(indices, values, head_cols, num_features):
    """The (n, H) float32 matrix of the head's columns: one scatter-add of
    the padded-sparse rows on the device (a row's repeated draws of a
    column add; entries of other columns fall out of bounds and drop)."""
    n, k = indices.shape
    width = head_cols.shape[0]
    slot = jnp.full((num_features,), width, jnp.int32).at[head_cols].set(
        jnp.arange(width, dtype=jnp.int32)
    )
    rows = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, k))
    return jnp.zeros((n, width), jnp.float32).at[rows, slot[indices]].add(
        values.astype(jnp.float32), mode="drop"
    )


def _merge_repeats(indices: np.ndarray, values: np.ndarray,
                   live: np.ndarray, num_features: int) -> np.ndarray:
    """``values`` with every row's repeated draws of a column merged: of
    the ``live`` slots of a row that name one column, the first holds their
    sum and the others 0. Slots that are not ``live`` (padding, columns
    that went to the head) are left alone. ``values`` itself comes back
    where no row repeats a column, as in real rows."""
    n, k = indices.shape
    slot = np.arange(k, dtype=np.int32)
    # dead slots get keys no column has, distinct within their row
    keyed = np.where(live, indices.astype(np.int32), num_features + slot)
    by_col = np.sort(keyed, axis=1)
    rep = np.flatnonzero((by_col[:, 1:] == by_col[:, :-1]).any(axis=1))
    if not len(rep):
        return values
    # rows that repeat a column: sort (column, slot) pairs, sum each run of
    # one column and hand the sum to the run's earliest slot
    pairs = np.sort(keyed[rep].astype(np.int64) * k + slot, axis=1)
    col, at = pairs // k, pairs % k
    starts = np.ones(col.shape, bool)
    starts[:, 1:] = col[:, 1:] != col[:, :-1]
    starts = np.flatnonzero(starts.reshape(-1))
    sums = np.add.reduceat(
        np.take_along_axis(values[rep], at, axis=1).reshape(-1), starts
    )
    merged = np.zeros(len(rep) * k, values.dtype)
    merged[at.reshape(-1)[starts] + starts // k * k] = sums
    out = values.copy()
    out[rep] = merged.reshape(len(rep), k)
    return out


def tile_sparse_batch(batch, keep_empty_chunks: bool = False,
                      fe_range: tuple | None = None,
                      hbm_budget_bytes: float | None = None,
                      ) -> TiledSparseBatch:
    """Build a ``TiledSparseBatch`` from a padded-sparse ``SparseBatch``
    (host-side one-time transform; zero-valued padding slots are dropped
    before tiling, a row's repeated draws of a column merged into one
    entry). Shapes beyond the per-kernel VMEM bounds are split into
    row/col chunks along SLAB-aligned boundaries.

    ``keep_empty_chunks`` keeps nonzero-free chunks instead of skipping
    them — the per-device-shard builder needs every shard to carry the
    SAME chunk structure so the stacked pytrees line up under shard_map.

    ``hbm_budget_bytes`` asks for the resident single-device layout: the
    device bytes the caller may pin, the input batch included. With it the
    popular columns move into a dense head where ``_head_columns`` finds
    one, and the chunks tile the tail alone; without it (None) every
    nonzero is tiled. It cannot be combined with ``keep_empty_chunks`` or
    ``fe_range``: shards and streamed chunks must share one pytree
    structure, and a head under a feature range is not built.
    With it, too, the tail's streams take the form its cells' occupancy
    asks for (``_cell_form``, priced where the head's rule prices the
    tail): runs where cells are full, the sparse-cell form where they are
    near empty, the fine form where they hold one or two. Several column
    chunks are stacked in every build (``_stage_chunk``).
    ``tile_layout.{head_columns, head_nonzeros, tail_nonzeros}`` in the
    registry count where every build put the input's stored nonzeros (the
    streams hold ``tail_nonzeros`` less the repeats merged away),
    ``tile_layout.tail_cells`` the non-empty cells of the streams (one
    direction) and ``tile_layout.tail_slots`` their slots (both);
    ``tile_layout.col_chunks`` the column chunks, and
    ``tile_layout.fine_{cells, nonzeros, slots}`` what of the tail's cells,
    stored nonzeros and slots the fine form holds (all or nothing: one
    form a build).
    """
    from photon_ml_tpu.obs.metrics import REGISTRY

    # the build's phases are spans (obs/spans.py): rows to the host, the
    # head, the merge, and per chunk the pack and the staging
    if hbm_budget_bytes is not None and (keep_empty_chunks or fe_range is not None):
        raise ValueError(
            "hbm_budget_bytes selects the resident single-device layout; "
            "it cannot go with keep_empty_chunks or fe_range"
        )
    with span(LAYOUT_TO_HOST):
        indices = np.asarray(batch.indices)
        values = np.asarray(batch.values).astype(np.float32)
        n, k = indices.shape
        d = batch.num_features
        live = values != 0.0
        stored = int(np.count_nonzero(live))
    head_cols = head_X = None
    # the resident layout alone chooses the tail's form, from the cells the
    # head leaves (shards and streamed chunks must share one structure)
    sub_slabs = 0
    with span(LAYOUT_HEAD):
        if hbm_budget_bytes is not None:
            head_cols, sub_slabs = _head_columns(
                np.bincount(indices[live], minlength=d), n,
                hbm_budget_bytes - indices.nbytes - values.nbytes,
                functools.partial(_tail_form, indices, live, d),
            )
        if head_cols is not None:
            in_tail = np.ones(d, bool)
            in_tail[head_cols] = False
            live &= in_tail[indices]
            head_cols = jnp.asarray(head_cols, jnp.int32)
            head_X = _head_matrix(
                jnp.asarray(batch.indices), jnp.asarray(batch.values), head_cols, d
            )
        tail = int(np.count_nonzero(live))
    REGISTRY.counter_inc(
        "tile_layout.head_columns", 0.0 if head_cols is None else len(head_cols)
    )
    REGISTRY.counter_inc("tile_layout.head_nonzeros", float(stored - tail))
    REGISTRY.counter_inc("tile_layout.tail_nonzeros", float(tail))
    with span(LAYOUT_MERGE):
        values = _merge_repeats(indices, values, live, d)
    with span(LAYOUT_PACK):
        keep = (live & (values != 0.0)).reshape(-1)
        rows = np.repeat(np.arange(n, dtype=np.int64), k)[keep]
        cols = indices.reshape(-1).astype(np.int64)[keep]
        vals = values.reshape(-1)[keep]

    n_pad_total = -(-n // SLAB) * SLAB
    n_row_chunks = -(-n_pad_total // _MAX_TABLE_ROWS)
    # column chunks of one width (whole slabs), stacked: one shape for
    # every chunk's streams, one kernel call a direction in the program
    n_col_chunks = -(-d // _MAX_TABLE_COLS)
    col_width = -(-d // (SLAB * n_col_chunks)) * SLAB
    d_pad_total = n_col_chunks * col_width
    chunks, tail_cells = [], 0
    for rc in range(n_row_chunks):
        r0 = rc * _MAX_TABLE_ROWS
        r1 = min(r0 + _MAX_TABLE_ROWS, n_pad_total)
        in_r = (rows >= r0) & (rows < r1)
        if n_row_chunks > 1 and not keep_empty_chunks and not in_r.any():
            continue
        parts = []
        for cc in range(n_col_chunks):
            c0 = cc * col_width
            with span(LAYOUT_PACK):
                m = in_r & (cols >= c0) & (cols < c0 + col_width)
                mine = rows[m] - r0, cols[m] - c0, vals[m]
            *part, cells = _pack_chunk(
                *mine, n_pad=r1 - r0, d_pad=col_width, sub_slabs=sub_slabs
            )
            parts.append(part)
            tail_cells += cells
        chunks.append(_stage_chunk(parts, r0, r1 - r0, col_width))
    tail_slots = float(sum(
        # packed is (stack * groups, streams, GROUP)
        int(arrays[0].size) // int(arrays[0].shape[-2])
        for c in chunks for arrays in (c.m_arrays, c.g_arrays)
    ))
    fine = sub_slabs == FINE_SLABS
    REGISTRY.counter_inc("tile_layout.tail_cells", float(tail_cells))
    REGISTRY.counter_inc("tile_layout.tail_slots", tail_slots)
    REGISTRY.counter_inc("tile_layout.col_chunks", float(n_col_chunks))
    REGISTRY.counter_inc("tile_layout.fine_cells", float(tail_cells) * fine)
    REGISTRY.counter_inc("tile_layout.fine_nonzeros", float(tail) * fine)
    REGISTRY.counter_inc("tile_layout.fine_slots", tail_slots * fine)
    return TiledSparseBatch(
        chunks=tuple(chunks),
        labels=batch.labels,
        offsets=batch.offsets,
        weights=batch.weights,
        num_features=d,
        num_rows_real=n,
        n_pad_total=n_pad_total,
        d_pad_total=d_pad_total,
        fe_range=fe_range,
        head_X=head_X,
        head_cols=head_cols,
    )


# Beyond these totals the chunk count stops paying for itself against the
# streamed/sharded paths. A build that keeps the run form pads a cell to
# 256 slots whatever it holds, which at 2^23 columns is 60 times over; the
# resident build chooses its form from the cells and gives all its column
# chunks one kernel, so it goes to 16 column chunks (2 x 10^7 columns at 36
# nonzeros a row: the fine form, PERF.md PR 40).
_MAX_TOTAL_ROWS = 1 << 25  # 32M rows = 8 row chunks
_MAX_TOTAL_COLS = 1 << 23  # 8M cols = 4 col chunks
_MAX_RESIDENT_COLS = 1 << 25  # 32M cols = 16 col chunks, the resident build


def tiling_economical_features(num_features: int, resident: bool = False) -> bool:
    """The feature-dimension half of the tiling gate, shared with the
    streamed objective's auto rule (one decision, two ingest paths —
    duplicating it let the streamed rule drop the upper cap): genuinely
    high-dimensional, but within the chunk-count ceiling. This bounds what
    CAN be tiled, not what tiling costs: a cell's occupancy falls as 1 over
    the width, and only the ``resident`` build adapts to it (``_cell_form``;
    10^6 columns measured, PERF.md PR 31; 2 x 10^7, PR 40). A streamed or
    sharded build keeps the run form at any width and pads a 10^6-column
    chunk 12 times over, so its ceiling stays at ``_MAX_TOTAL_COLS``."""
    cap = _MAX_RESIDENT_COLS if resident else _MAX_TOTAL_COLS
    return 4096 <= num_features <= cap


def auto_tile_streaming(sparse: bool, num_features: int | None) -> bool:
    """The streamed paths' ONE auto rule for tile-COO chunk kernels — the
    chunked objective and the module scorer both call this (a drifted
    copy would tile shapes the other path no longer tiles): sparse
    chunks, genuinely high-dimensional, on a real TPU (interpret-mode
    tiling is test-only and opts in explicitly via tile_sparse=True)."""
    return (
        bool(sparse)
        and num_features is not None
        and tiling_economical_features(num_features)
        and jax.default_backend() == "tpu"
    )


def supports_tiling(batch, resident: bool = False) -> bool:
    """Static gate: shapes the tile-COO path can lay out — a genuinely
    sparse high-dimensional problem (the dense path beats it otherwise).
    Shapes beyond one kernel's VMEM bounds are row/col-chunked, so the
    ceiling here is the chunk count, not VMEM, and it is higher for the
    ``resident`` build (``tile_sparse_batch(hbm_budget_bytes=...)``). What
    the layout then costs depends on how full its cells are, which the
    resident build observes and this gate does not
    (``tiling_economical_features``)."""
    from photon_ml_tpu.ops.batch import SparseBatch

    return (
        isinstance(batch, SparseBatch)
        and tiling_economical_features(batch.num_features, resident)
        and SLAB <= batch.num_rows <= _MAX_TOTAL_ROWS
        # an all-padding batch tiles to 0 groups, and a 0-group kernel is
        # not compilable (s32[0,128] operand) — the XLA path handles it
        and bool(np.any(np.asarray(batch.values) != 0))
    )


def _pad_layout_groups(arrays: tuple, target_groups: int,
                       stack: int = 1) -> tuple:
    """Extend one direction's (packed, wslab, rslab, rrun, srun) stream
    with filler segments up to ``target_groups`` groups. Fillers use the
    builder's tail convention — write slab 0, read slab 0, value 0, scale
    1 — and contribute exactly 0; ``target_groups`` must be a
    whole-DMA-step multiple (every built stream already is, so the max
    over shards is too), and a DMA step is a whole number of runs. The
    slab ids and scales grow at the rate the stream's form has them (a
    run's, or a granule's). Stacked column chunks (``stack`` of them end to
    end, ``target_groups`` counting them all) grow alike, each at its own
    end."""
    packed = arrays[0]  # packed is (stack * n_groups, S, GROUP)
    n_groups = packed.shape[0] // stack
    add = target_groups // stack - n_groups
    if not add:
        return arrays
    xp = np if isinstance(packed, np.ndarray) else jnp  # host or device

    def grow(a, fill):
        per_chunk = a.reshape(stack, -1, *a.shape[1:])
        shape = list(per_chunk.shape)
        shape[1] = add * shape[1] // n_groups
        return xp.concatenate(
            [per_chunk, xp.full(shape, fill, a.dtype)], axis=1
        ).reshape(-1, *a.shape[1:])

    packed, wslab, rslab, rrun, srun = arrays
    return (
        grow(packed, 0), grow(wslab, 0), grow(rslab, 0), grow(rrun, 0),
        grow(srun, 1),
    )


def pad_chunks_to_common_groups(tbs: list) -> list[list]:
    """Pad every ``TiledSparseBatch`` in ``tbs`` (identical chunk
    structure) so that chunk j's streams have the SAME group count across
    all batches — the shared prerequisite for stacking per-shard layouts
    under ``shard_map`` and for serving every streamed chunk with one
    compiled kernel. Returns ``out[j][i]`` = batch i's padded chunk j."""
    n_chunks = len(tbs[0].chunks)
    assert all(len(tb.chunks) == n_chunks for tb in tbs)
    out = []
    for j in range(n_chunks):
        targets = {
            side: max(
                getattr(tb.chunks[j], side)[0].shape[0] for tb in tbs
            )
            for side in ("m_arrays", "g_arrays")
        }
        out.append([
            replace(
                tb.chunks[j],
                m_arrays=_pad_layout_groups(
                    tb.chunks[j].m_arrays, targets["m_arrays"],
                    tb.chunks[j].stack,
                ),
                g_arrays=_pad_layout_groups(
                    tb.chunks[j].g_arrays, targets["g_arrays"],
                    tb.chunks[j].stack,
                ),
            )
            for tb in tbs
        ])
    return out


def tile_sparse_batch_sharded(batch, n_dev: int):
    """Per-device tile-COO for a row-sharded mesh solve — the module
    docstring's own multi-device recipe ("shard rows first and build one
    tile-COO per shard; the objective's psum handles the reduction"),
    implemented as a host-side ingest transform:

    - rows pad to an ``n_dev`` multiple and split into ``n_dev``
      contiguous shards (equal row counts → identical chunk structure);
    - each shard tiles independently (``keep_empty_chunks`` so the chunk
      lists line up), streams pad to the max group count across shards;
    - every array leaf stacks on a LEADING DEVICE AXIS. The result is a
      ``TiledSparseBatch``-shaped pytree whose leaves are (n_dev, ...) —
      shard it with ``PartitionSpec(axis)`` and drop the unit leading axis
      inside ``shard_map`` to recover each device's local batch.

    Returns (stacked_batch, rows_per_shard).
    """
    from photon_ml_tpu.ops.batch import pad_batch

    n = batch.num_rows
    rows_per_shard = -(-n // n_dev)
    batch = pad_batch(batch, rows_per_shard * n_dev)
    shards = [
        jax.tree.map(
            lambda a: a[i * rows_per_shard:(i + 1) * rows_per_shard], batch
        )
        for i in range(n_dev)
    ]
    tbs = [tile_sparse_batch(sh, keep_empty_chunks=True) for sh in shards]
    ref = tbs[0]
    padded = pad_chunks_to_common_groups(tbs)

    stacked_chunks = [
        replace(
            ref.chunks[j],
            m_arrays=tuple(
                jnp.stack([c.m_arrays[i] for c in padded[j]]) for i in range(5)
            ),
            g_arrays=tuple(
                jnp.stack([c.g_arrays[i] for c in padded[j]]) for i in range(5)
            ),
        )
        for j in range(len(ref.chunks))
    ]
    stacked = TiledSparseBatch(
        chunks=tuple(stacked_chunks),
        labels=jnp.stack([tb.labels for tb in tbs]),
        offsets=jnp.stack([tb.offsets for tb in tbs]),
        weights=jnp.stack([tb.weights for tb in tbs]),
        num_features=ref.num_features,
        num_rows_real=ref.num_rows_real,
        n_pad_total=ref.n_pad_total,
        d_pad_total=ref.d_pad_total,
    )
    return stacked, rows_per_shard
