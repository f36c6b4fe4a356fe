"""A stacked row chunk's column chunks as the grid steps of one tile-COO
kernel call a direction, against a loop of one call a chunk (interpret
mode, retuned-down constants)."""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu.ops.sparse_tiled import SLAB, tile_sparse_batch
from tests.test_sparse_tiled import (
    _DIRECTIONS,
    _cell_batch,
    _plain_batch,
    _retune,
)


def _stacked_build(rng, monkeypatch, form, stack):
    """A tiled batch of two row slabs and ``stack`` column chunks of two
    slabs (the last one short) in ``form``: the run form where the build
    is handed no budget, the sparse-cell form at 20 nonzeros a cell, the
    fine form at one or two. Returns ``(batch, tiled)``."""
    import photon_ml_tpu.ops.sparse_tiled as st

    _retune(monkeypatch, step=2, dma=2)
    monkeypatch.setattr(st, "_MAX_TABLE_COLS", 2 * SLAB)
    n, d = 2 * SLAB - 100, (2 * stack - 1) * SLAB + 300
    slabs = -(-d // SLAB)
    if form == "runs":
        batch = _plain_batch(rng, n, d, 4)
        return batch, tile_sparse_batch(batch)
    counts = [[20] * slabs] * 2 if form == "sub" else (
        rng.integers(1, 3, size=(2, slabs)).tolist()
    )
    batch = _cell_batch(rng, n, d, counts)
    return batch, tile_sparse_batch(batch, hbm_budget_bytes=1e12)


def _per_chunk_streams(arrays, stack):
    """Each column chunk's own five streams, cut out of the stacked ones."""
    return [
        tuple(a.reshape(stack, -1, *a.shape[1:])[i] for a in arrays)
        for i in range(stack)
    ]


def _one_call_and_the_loop(chunk, direction, w, r):
    """One direction of a stacked chunk as the pass runs it, and as a loop
    of one kernel call a column chunk on that chunk's own streams: the
    margins added onto zeros in chunk order, the gradient's outputs side
    by side (the composition of a pass as a loop over the chunks)."""
    import photon_ml_tpu.ops.sparse_tiled as st

    k, n_pad, d_pad = chunk.stack, chunk.n_pad, chunk.d_pad
    if direction == "margins":
        got = chunk.matvec_part(w)
        want = jnp.zeros((n_pad,), jnp.float32)
        for i, arrays in enumerate(_per_chunk_streams(chunk.m_arrays, k)):
            want = want + st._tiled_apply(
                arrays, w[i * d_pad:(i + 1) * d_pad], n_pad, d_pad
            )
    else:
        sq = direction == "gradient_sq"
        got = chunk.rmatvec_part(r, sq)
        want = jnp.concatenate([
            st._tiled_apply(arrays, r[:n_pad], d_pad, n_pad, square_vals=sq)
            for arrays in _per_chunk_streams(chunk.g_arrays, k)
        ])
    return np.asarray(got), np.asarray(want)


def _assert_bitwise(got, want):
    assert got.dtype == want.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


@pytest.mark.kernel
class TestOneCallAPass:
    """A stacked row chunk's column chunks are the grid steps of ONE kernel
    call a direction: the kernel reads each chunk's range of the
    whole streams, the margins' chunks add into one output block in chunk
    order, and the gradient's write a block each. Its outputs are BITWISE
    those of a loop of one call a chunk on the chunk's own streams, in
    every form, direction and stack, cut into calls or not."""

    @pytest.mark.parametrize("direction", _DIRECTIONS)
    @pytest.mark.parametrize("stack", [1, 3, 6])
    @pytest.mark.parametrize("form", ["runs", "sub", "fine"])
    def test_one_call_is_bitwise_the_loop_over_chunks(
        self, rng, monkeypatch, form, stack, direction
    ):
        import photon_ml_tpu.ops.sparse_tiled as st

        _, tb = _stacked_build(rng, monkeypatch, form, stack)
        (chunk,) = tb.chunks
        assert chunk.stack == stack
        packed, rrun = chunk.m_arrays[0], chunk.m_arrays[3]
        sub = {"runs": 0, "sub": st.SUB_SLABS, "fine": st.FINE_SLABS}[form]
        assert rrun.shape[0] == (
            packed.shape[0] * sub if sub else packed.shape[0] // st.GROUPS_PER_RUN
        )
        w = jnp.asarray(rng.normal(size=stack * chunk.d_pad).astype(np.float32))
        r = jnp.asarray(rng.normal(size=chunk.n_pad).astype(np.float32))
        _assert_bitwise(*_one_call_and_the_loop(chunk, direction, w, r))

    @pytest.mark.parametrize("direction", ["margins", "gradient"])
    @pytest.mark.parametrize("cut", ["whole_chunks", "steps"])
    def test_a_call_cut_for_smem_is_bitwise_the_loop(
        self, rng, monkeypatch, cut, direction
    ):
        """With the SMEM budget at two chunks' prefetch words the call is
        cut at whole chunks (three calls of two, each margins call adding
        onto the one before); below one chunk's, every chunk runs as calls
        over its steps, as a lone chunk does."""
        import photon_ml_tpu.ops.sparse_tiled as st

        _, tb = _stacked_build(rng, monkeypatch, "runs", 6)
        (chunk,) = tb.chunks
        arrays = chunk.m_arrays if direction == "margins" else chunk.g_arrays
        chunk_bytes = 4 * (arrays[1].shape[0] + arrays[3].shape[0]) // 6
        monkeypatch.setattr(
            st, "_SMEM_PREFETCH_BUDGET",
            2 * chunk_bytes if cut == "whole_chunks" else chunk_bytes // 2,
        )
        plan = st._stream_plan(
            arrays, 6, st.GROUPS_PER_STEP, st.SEGMENTS_PER_DMA,
            st.GROUPS_PER_RUN, "f32",
        )
        n_steps, calls = plan[1], plan[-1]
        assert n_steps >= 2
        if cut == "whole_chunks":
            assert calls == [(c, 2, 0, n_steps) for c in (0, 2, 4)]
        else:
            assert len(calls) >= 12 and {c[1] for c in calls} == {1}
            assert [c[0] for c in calls] == sorted(c[0] for c in calls)
        # the budget is read at trace time and is not a jit key
        st._tiled_apply_jit.clear_cache()
        try:
            w = jnp.asarray(rng.normal(size=6 * chunk.d_pad).astype(np.float32))
            r = jnp.asarray(rng.normal(size=chunk.n_pad).astype(np.float32))
            _assert_bitwise(*_one_call_and_the_loop(chunk, direction, w, r))
        finally:
            st._tiled_apply_jit.clear_cache()

    @pytest.mark.parametrize("direction", _DIRECTIONS)
    def test_the_sharded_build_under_shard_map_is_bitwise_the_loop(
        self, rng, monkeypatch, direction
    ):
        """The sharded build's stacked chunks, each device's pass under
        ``shard_map``, against the loop over that device's own chunks; and
        the streamed build's halves (padded to common group counts, as the
        streamed objective does) against theirs."""
        import jax
        from jax.sharding import PartitionSpec as P

        import photon_ml_tpu.ops.sparse_tiled as st
        from photon_ml_tpu.parallel import data_mesh

        batch, _ = _stacked_build(rng, monkeypatch, "runs", 3)
        sharded, rows = st.tile_sparse_batch_sharded(batch, 2)
        (stacked,) = sharded.chunks
        assert stacked.stack == 3
        w = jnp.asarray(rng.normal(size=3 * stacked.d_pad).astype(np.float32))
        r = jnp.asarray(rng.normal(size=(2, stacked.n_pad)).astype(np.float32))

        def local(chunk, r):
            chunk = jax.tree.map(lambda a: a[0], chunk)
            if direction == "margins":
                return chunk.matvec_part(w)[None]
            return chunk.rmatvec_part(r[0], direction == "gradient_sq")[None]

        got = jax.shard_map(
            local, mesh=data_mesh(2), in_specs=(P("data"), P("data")),
            out_specs=P("data"), check_vma=False,
        )(stacked, r)
        for i in range(2):
            mine = jax.tree.map(lambda a: a[i], stacked)
            _, want = _one_call_and_the_loop(mine, direction, w, r[i])
            _assert_bitwise(np.asarray(got)[i], want)
        halves = [
            jax.tree.map(lambda a: a[lo:lo + rows], batch) for lo in (0, rows)
        ]
        padded = st.pad_chunks_to_common_groups(
            [tile_sparse_batch(h, keep_empty_chunks=True) for h in halves]
        )
        for chunk in padded[0]:
            _assert_bitwise(*_one_call_and_the_loop(chunk, direction, w, r[0]))

    def test_the_counters_read_the_calls_and_their_grid_chunks(
        self, rng, monkeypatch
    ):
        """One value-and-gradient trace: a call a direction, a grid step a
        column chunk (``tile_apply.*``, counted where the call is built)."""
        import jax

        from photon_ml_tpu.obs.metrics import REGISTRY
        from photon_ml_tpu.ops.glm import make_objective
        from photon_ml_tpu.ops.losses import loss_for_task
        from photon_ml_tpu.types import TaskType

        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        for stack, want in ((6, (2.0, 12.0)), (1, (2.0, 2.0))):
            _, tb = _stacked_build(rng, monkeypatch, "fine", stack)
            REGISTRY.reset(prefix="tile_apply.")
            jax.jit(lambda b, w: make_objective(b, loss).value_and_grad(w)).trace(
                tb, jnp.zeros((tb.num_features,), jnp.float32)
            )
            counters = REGISTRY.snapshot("tile_apply.")["counters"]
            assert (
                counters["tile_apply.calls"]["value"],
                counters["tile_apply.grid_chunks"]["value"],
            ) == want
