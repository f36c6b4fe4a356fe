"""Deviceless AOT compile of the Pallas kernels for a TPU v5e.

The CPU suite runs every kernel under ``interpret=True``, which accepts
programs Mosaic refuses: the shipped tile-COO constants and two storage
rungs went twenty PRs without ever lowering for a chip. libtpu can compile
for a ``v5e:2x2`` topology with no device attached, so these tests lower
and compile the real kernels (``interpret=False``) and fail tier-1 the day
one stops compiling. Nothing here executes, so nothing is ``kernel``-marked
(that marker retunes the constants DOWN, and the point is the shipped ones).
"""

from __future__ import annotations

import importlib.util
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

import photon_ml_tpu.ops.sparse_tiled as st
from photon_ml_tpu.ops import fused
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.types import TaskType

LOSS = loss_for_task(TaskType.LOGISTIC_REGRESSION)


@pytest.fixture(autouse=True)
def _x64_off_like_the_chip():
    """The suite enables x64 for its finite-difference checks; a chip run
    does not, and Mosaic has no 64-bit types."""
    with jax.enable_x64(False):
        yield


@pytest.fixture(scope="module")
def topo():
    """Skipped only where libtpu is not installed; an installed libtpu
    that cannot describe a v5e fails the module, or these tests would
    go quiet exactly when it is needed."""
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no deviceless TPU compile here")
    from jax.experimental import topologies

    t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    assert t.devices[0].device_kind == "TPU v5 lite"
    return t


def _spec(topo):
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _layout_specs(spec, storage, n=1 << 12, d=1 << 14, k=8):
    rng = np.random.default_rng(0)
    lay = st.build_write_major_layout(
        np.repeat(np.arange(n), k), rng.integers(0, d, n * k),
        rng.normal(size=n * k).astype(np.float32), n, d, storage=storage,
    )
    arrays = (lay.packed, lay.wslab, lay.rslab, lay.rrun, lay.srun)
    return tuple(spec(a.shape, a.dtype) for a in arrays), n, d


def _stream_specs(spec, groups, storage="f32"):
    """The five layout streams of a chunk of ``groups`` groups, as shapes
    only — no layout is built."""
    return (
        spec((groups, 1 if storage == "int8" else 3, st.GROUP), jnp.int32),
        spec((groups // st.GROUPS_PER_STEP,), jnp.int32),
        spec((groups,), jnp.int32),
        spec((groups // st.GROUPS_PER_RUN,), jnp.int32),
        spec((groups // st.GROUPS_PER_RUN,), jnp.float32),
    )


def _lower_shipped(spec, specs, n, d, storage="f32"):
    return st._tiled_apply_jit.lower(
        specs, spec((d,), jnp.float32), n, d, False,
        st.GROUPS_PER_STEP, st.SEGMENTS_PER_DMA, st.GROUPS_PER_RUN,
        storage, False, None,
    )


def _compile_tile(spec, storage, *, square=False):
    specs, n, d = _layout_specs(spec, storage)
    return st._tiled_apply_jit.lower(
        specs, spec((d,), jnp.float32), n, d, square,
        st.GROUPS_PER_STEP, st.SEGMENTS_PER_DMA, st.GROUPS_PER_RUN,
        storage, False, None,
    ).compile()


class TestTileCooCompiles:
    def test_shipped_constants_are_the_ones_compiled(self):
        assert (st.GROUPS_PER_STEP, st.SEGMENTS_PER_DMA) == (32, 4)
        assert st.GROUPS_PER_RUN == 2

    @pytest.mark.parametrize("storage", ["f32", "int8"])
    def test_shipped_kernel_on_both_rungs(self, topo, storage):
        _compile_tile(_spec(topo), storage)

    def test_hessian_diagonal_variant(self, topo):
        _compile_tile(_spec(topo), "f32", square=True)

    def test_a2_full_shape_fits_smem(self, topo):
        """A2's bench shape (n=2^19, d=2^17, 32 nonzeros a row, ~166k
        groups): with all four scalar streams prefetched it needed 1.29M
        of a v5e's 1.00M SMEM. The padded group count is the chip's own
        (200,448 at GROUPS_PER_RUN=2, PR 21)."""
        spec = _spec(topo)
        _lower_shipped(spec, _stream_specs(spec, 200448), 1 << 19, 1 << 17).compile()

    @pytest.mark.parametrize("storage", ["f32", "int8"])
    def test_stream_beyond_smem_compiles_as_pieces(self, topo, storage):
        """n=2^21 rows of 8 uniform nonzeros at d=2^17 pad 4.0x to 524,288
        groups: 1.11 MB of prefetch on f32 (2.16 MB on int8), more than
        one call's SMEM. The stream runs as several calls, and each
        compiles."""
        spec = _spec(topo)
        text = _lower_shipped(
            spec, _stream_specs(spec, 524288, storage), 1 << 21, 1 << 17, storage
        ).compile().as_text()
        assert text.count("custom_call_target=\"tpu_custom_call\"") == (
            2 if storage == "f32" else 3
        )


class TestSparseCellFormCompiles:
    """The tail's sparse-cell form (PR 31) at ``criteo_fit``'s table sizes
    (2,292,031 rows x 10^6 columns) and about its stream length: ``SUB_SLABS``
    slab ids a group stay in HBM and each DMA step brings its 1,024 words
    into SMEM, so a stream of any length is ONE kernel call (as a prefetch
    operand it would be 16 MB and eighteen calls). Mosaic tiles a 1-D int32
    array in HBM by 1,024: the shipped carve's 128 groups x 8 slabs."""

    N_PAD, D_PAD, GROUPS = 2_293_760, 1_000_448, 494_464

    def _specs(self, spec):
        g = self.GROUPS
        return (
            spec((g, 3, st.GROUP), jnp.int32),
            spec((g // st.GROUPS_PER_STEP,), jnp.int32),
            spec((g,), jnp.int32),
            spec((g * st.SUB_SLABS,), jnp.int32),
            spec((g * st.SUB_SLABS,), jnp.float32),
        )

    def test_a_steps_slab_ids_fill_whole_hbm_tiles(self):
        step_groups = st.GROUPS_PER_STEP * st.SEGMENTS_PER_DMA
        assert step_groups * st.SUB_SLABS % 1024 == 0

    @pytest.mark.parametrize("direction", ["margins", "gradient", "gradient_sq"])
    def test_one_call_a_stream_at_criteo_fit(self, topo, direction):
        spec = _spec(topo)
        out, src = (
            (self.N_PAD, self.D_PAD) if direction == "margins"
            else (self.D_PAD, self.N_PAD)
        )
        text = st._tiled_apply_jit.lower(
            self._specs(spec), spec((src,), jnp.float32), out, src,
            direction == "gradient_sq",
            st.GROUPS_PER_STEP, st.SEGMENTS_PER_DMA, st.GROUPS_PER_RUN,
            "f32", False, None,
        ).compile().as_text()
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1

    def test_the_int8_rung_has_no_sparse_cell_form(self, topo):
        spec = _spec(topo)
        with pytest.raises(ValueError, match="no form this kernel reads"):
            st._tiled_apply_jit.lower(
                self._specs(spec), spec((self.D_PAD,), jnp.float32),
                self.N_PAD, self.D_PAD, False,
                st.GROUPS_PER_STEP, st.SEGMENTS_PER_DMA, st.GROUPS_PER_RUN,
                "int8", False, None,
            )


class TestDenseHeadCompiles:
    """The dense head beside the tile-COO tail (PR 28) at ``rcv1_fit``'s
    shape and the width its rule picks there: each direction must stay one
    float32 multiply-reduce over the head as it is stored. A matmul would
    round to bfloat16 on a TPU, and a relayout copy of 2 GB a pass is what
    ``ops/fused`` paid before PR 26."""

    @pytest.mark.parametrize("method", ["matvec", "rmatvec", "rmatvec_sq"])
    def test_head_sweep_is_one_float32_fusion_without_a_copy(self, topo, method):
        spec = _spec(topo)
        n, d, width = 1_354_798, 47_236, 384
        row = spec((n,), jnp.float32)
        tb = st.TiledSparseBatch(
            chunks=(), labels=row, offsets=row, weights=row,
            num_features=d, num_rows_real=n,
            n_pad_total=-(-n // st.SLAB) * st.SLAB,
            d_pad_total=-(-d // st.SLAB) * st.SLAB,
            head_X=spec((n, width), jnp.float32),
            head_cols=spec((width,), jnp.int32),
        )
        arg = spec((d,), jnp.float32) if method == "matvec" else row
        compiled = jax.jit(
            lambda tb, x: getattr(tb, method)(x)
        ).lower(tb, arg).compile()
        text = compiled.as_text()
        assert len(re.findall(r"= f32\[[0-9]+\]\S* fusion\(%tb_head_X", text)) == 1
        assert "convolution(" not in text and " dot(" not in text
        assert "bf16[" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


class TestBf16IsNoRung:
    def test_bf16_layout_build_raises_naming_the_rungs(self):
        """The bf16 rung never compiled for a TPU (Mosaic cannot slice a
        3-stream int16 block for the per-step DMA) and is gone: a layout
        asked for it fails the strict parse — never a silent f32."""
        with pytest.raises(ValueError, match="valid rungs: f32, int8"):
            st.build_write_major_layout(
                np.arange(8), np.arange(8), np.ones(8, np.float32),
                st.SLAB, st.SLAB, storage="bf16",
            )


def _compile_fused_pair(topo, n, d, dtype, aux):
    """Lower and compile ``fused_value_grad`` and ``fused_hvp`` for one
    chip, with or without the optional offset/weight streams."""
    spec = _spec(topo)
    X, col = spec((n, d), dtype), spec((n,), jnp.float32)
    off = col if aux else None
    u, c = spec((d,), jnp.float32), spec((), jnp.float32)
    jax.jit(
        lambda X, y, off, wt, u, c: fused.fused_value_grad(
            X, y, off, wt, u, c, loss=LOSS
        )
    ).lower(X, col, off, off, u, c).compile()
    jax.jit(
        lambda X, y, off, wt, u, v, c, cv: fused.fused_hvp(
            X, y, off, wt, u, v, c, cv, loss=LOSS
        )
    ).lower(X, col, off, off, u, u, c, c).compile()


class TestFusedCompiles:
    """Both fused kernels, both storage dtypes, with and without the
    optional offset/weight streams, at the headline width."""

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("aux", [False, True])
    def test_value_grad_and_hvp(self, topo, dtype, aux):
        _compile_fused_pair(topo, 1 << 14, 512, dtype, aux)

    @pytest.mark.parametrize(
        "n,d,dtype",
        [
            (37, 128, jnp.float32),  # one ragged tile, n < 128
            (5000, 128, jnp.bfloat16),  # n < bn = 8192
            (100_000, 512, jnp.bfloat16),  # a ragged last tile of 4096
            (1000, 7168, jnp.float32),  # the widest f32 at a 256-row tile
            # the float32 row-major tile at 128 rows (PR 37): the widest the
            # gate lets in; and eight row blocks a tile, a loop, ragged
            (1000, 14336, jnp.float32),
            (5000, 1024, jnp.float32),
            (1000, 14336, jnp.bfloat16),  # the widest bf16
            # the feature-major kernels (a float32 width that is no
            # multiple of 128): epsilon_tron_fit's matrix, a ragged last
            # tile of 128 lanes; features that fill no whole sublane group
            # with fewer rows than one lane tile; the widest
            (400_000, 2000, jnp.float32),
            (37, 130, jnp.float32),
            (5000, 7160, jnp.float32),
            # the GLMix descent's fixed effect (PR 35): tiles of 8,192
            # lanes, their sixteen windows a loop with a ragged last tile
            (5_000_066, 65, jnp.float32),
        ],
    )
    def test_ragged_and_wide_shapes(self, topo, n, d, dtype):
        """Every shape ``supports_fused`` admits has to lower: the masked
        last tile, the stream reshaped in VMEM at each tile size, and the
        widest tiles the VMEM budget lets in."""
        assert fused.supports_fused(n, d, dtype)
        _compile_fused_pair(topo, n, d, dtype, aux=True)


def test_fixed_visit_reads_the_65_column_matrix_where_it_lies(topo, monkeypatch):
    """``ml20m_fixed_only``'s visit with the kernels taken: the chip hands
    f32[5000066, 65] over feature-major (XLA's ``{0,1}``), the kernels read
    its transpose, which is that array, and the score is one sweep of it in
    place. No copy of the matrix and no 128-lane image of it (2.56 GB, once
    a launch, on XLA's path) may come back."""
    from photon_ml_tpu.config import (
        OptimizationConfig,
        OptimizerConfig,
        RegularizationContext,
    )
    from photon_ml_tpu.game import DenseFeatures, FixedEffectCoordinate
    from photon_ml_tpu.game import coordinate as coordinate_module
    from photon_ml_tpu.game.data import GameBatch
    from photon_ml_tpu.ops import glm
    from photon_ml_tpu.ops.batch import DenseBatch
    from photon_ml_tpu.types import RegularizationType

    monkeypatch.setattr(glm, "_interpret_fused", lambda: False)
    monkeypatch.setattr(coordinate_module, "auto_fused", lambda batch: True)
    n, d = 5_000_066, 65
    spec = _spec(topo)
    row, X = spec((n,), jnp.float32), spec((n, d), jnp.float32)
    coordinate = FixedEffectCoordinate(
        coordinate_id="fixed",
        batch=GameBatch(labels=row, offsets=row, weights=row,
                        features={"global": DenseFeatures(X=X)}, id_tags={}),
        feature_shard_id="global",
        config=OptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=20, tolerance=1e-7),
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        ),
        task_type=TaskType.LOGISTIC_REGRESSION, intercept_index=d - 1,
    )
    base = DenseBatch(X=X, labels=row, offsets=row, weights=row)
    jax.clear_caches()  # ``lbfgs_minimize``'s cached trace: see below
    try:
        compiled = coordinate._build_visit_fn(base).lower(
            base, row, row, spec((d,), jnp.float32)
        ).compile()
    finally:
        jax.clear_caches()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert f"f32[{n},{d}]{{0,1" in text  # the entry layout: feature-major
    assert not re.search(rf"= f32\[{n},(?:{d}|128)\]\S* copy\(", text)
    assert f"f32[{n},128]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 27


def _compile_sharded_fused_solve(topo, monkeypatch, n, dtype, data_hints):
    """``DistributedTrainer``'s program: the whole L-BFGS loop under
    ``shard_map`` with the fused kernel (compiled, as a chip would) inside
    and one psum per evaluation, for the 2x2 mesh."""
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops import glm
    from photon_ml_tpu.ops.batch import DenseBatch
    from photon_ml_tpu.optim import lbfgs_minimize
    from photon_ml_tpu.parallel.distributed import _sharded_solve

    monkeypatch.setattr(glm, "_interpret_fused", lambda: False)
    mesh = Mesh(np.array(topo.devices), ("data",))
    rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    d = 512
    row = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=rows)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    batch = DenseBatch(X=row((n, d), dtype), labels=row((n,)),
                       offsets=row((n,)), weights=row((n,)))
    # ``lbfgs_minimize``'s cached trace holds whichever kernel mode the
    # process traced first: drop it before, and again for the tests after
    jax.clear_caches()
    try:
        return _sharded_solve.lower(
            batch, jax.ShapeDtypeStruct((d,), jnp.float32, sharding=rep),
            scalar, scalar, None, None,
            minimize_fn=lbfgs_minimize, loss=LOSS,
            config=OptimizerConfig(max_iterations=3, tolerance=0.0),
            intercept_index=None, axis_name="data", mesh=mesh, use_l1=False,
            fused=True, data_hints=data_hints,
        ).compile()
    finally:
        jax.clear_caches()


# The loop body may not lay a per-row vector out as a column again: 512 B a
# row of HBM, written before every pass (PERF.md §6, PR 22 finding 3).
_COLUMN_COPY = re.compile(r"= f32\[\d+,1\]\S* copy\(")


@pytest.mark.parametrize(
    "n,dtype,data_hints",
    [
        (1 << 14, jnp.float32, (True, False)),
        # dense_dp4_fit's shape: 2^22 rows x 512 bf16 a chip, all three streams
        (1 << 24, jnp.bfloat16, (False, False)),
        # what ISSUE 22 asked for and PR 22 found refused (20.1 GB a chip)
        (1 << 25, jnp.bfloat16, (False, False)),
    ],
    ids=["small", "cell_2p24", "2p25"],
)
def test_sharded_fused_solve_compiles_for_four_chips(
    topo, monkeypatch, n, dtype, data_hints
):
    compiled = _compile_sharded_fused_solve(topo, monkeypatch, n, dtype, data_hints)
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert not _COLUMN_COPY.search(text)
    if n == 1 << 24:
        # 6.45 GB a chip while the streams were (rows, 1) columns
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*$", re.M)


def _instructions(text: str) -> list[tuple[str, bool]]:
    """(name, is a Pallas custom call) of every instruction, in order."""
    return [
        (m.group(1), 'custom_call_target="tpu_custom_call"' in m.group(0))
        for m in _INSTRUCTION.finditer(text)
    ]


def _kernel_paths(text: str) -> list[str]:
    """The ``op_name`` of every Pallas custom call of a compiled program."""
    return [
        re.search(r'op_name="([^"]*)"', m.group(0)).group(1)
        for m in _INSTRUCTION.finditer(text)
        if 'custom_call_target="tpu_custom_call"' in m.group(0)
    ]


class TestStagesAreMetadataOnly:
    """``obs/stages.py`` scopes change the ``op_name`` metadata of the
    compiled TPU program and nothing else: the same instructions under the
    same names with the scopes as without, so nothing a benchmark measures
    can move. One exception, by XLA's own naming: a Pallas custom call is
    named after the innermost scope around it. The tile-COO kernels sit
    inside ``jit(_tiled_apply_jit)`` and keep their name (the benchmark's
    ``sparse_tiled_roofline`` finds them by it); the fused dense kernel has
    no jit of its own, so ``body.<n>`` becomes ``glm.objective.<n>``
    (``fused_roofline`` matches ``custom-call(``, which stays)."""

    def _compiled(self, topo, program: str) -> str:
        import stage_programs as programs

        mesh = Mesh(np.array(topo.devices), ("data",))
        fn, args, kwargs = programs.build(program, mesh)
        if program != "sharded":  # concrete arrays: shapes on the described chip
            args = programs.as_specs(
                args, SingleDeviceSharding(topo.devices[0])
            )
        return fn.lower(*args, **kwargs).compile().as_text()

    @pytest.mark.parametrize(
        "program",
        ["descent", "tile_fit", "sharded", "sparse_descent", "tron_fit",
         "sparse_descent_kernels"],
    )
    def test_same_instructions_with_and_without_scopes(
        self, topo, monkeypatch, program
    ):
        import stage_programs as programs
        from photon_ml_tpu.ops import glm

        # compile the kernels, as a chip would
        monkeypatch.setattr(st, "_interpret", lambda: False)
        monkeypatch.setattr(glm, "_interpret_fused", lambda: False)
        if program == "sparse_descent_kernels":
            programs.lanes_take_the_kernel(monkeypatch)
        jax.clear_caches()
        try:
            scoped = self._compiled(topo, program)
            jax.clear_caches()
            programs.without_scopes(monkeypatch)
            plain = self._compiled(topo, program)
        finally:
            jax.clear_caches()

        paths = set(re.findall(r'op_name="([^"]*)"', scoped))
        segments = {s for path in paths for s in path.split("/")}
        assert set(programs.PROGRAM_STAGES[program]) <= segments
        assert not set(programs.PROGRAM_STAGES[program]) & {
            s for path in re.findall(r'op_name="([^"]*)"', plain)
            for s in path.split("/")
        }

        with_, without = _instructions(scoped), _instructions(plain)
        assert len(with_) == len(without)
        renamed = [(a, b) for a, b in zip(with_, without) if a != b]
        kernels = [name for name, is_kernel in with_ if is_kernel]
        if program in ("sharded", "tron_fit"):
            # XLA names a Pallas custom call after the innermost scope
            # around it: the value-and-gradient kernels ``glm.objective.<n>``,
            # a Hessian-vector kernel ``glm.hvp.<n>``
            assert len(kernels) == 3
            named = r"glm\.objective\.\d+" + (
                r"|glm\.hvp\.\d+" if program == "tron_fit" else ""
            )
            assert all(re.fullmatch(named, k) for k in kernels)
            assert all(a[1] and b[1] for a, b in renamed)  # kernels only
            assert len(renamed) == len(kernels)
        elif program == "sparse_descent_kernels":
            # the subspace lanes' value-and-gradient kernel sits inside
            # ``jit(_subspace_value_grad)``, under the scope that names it:
            # one a site of ``optim/lbfgs``, each under the solve's stage
            assert len(kernels) == 3
            assert all(re.fullmatch(r"re\.sparse_pass\.\d+", k) for k in kernels)
            assert all(a[1] and b[1] for a, b in renamed)
            assert len(renamed) == len(kernels)
            assert _kernel_paths(scoped) and all(
                "/re.solve/" in path and path.count("/re.sparse_pass/") == 1
                for path in _kernel_paths(scoped)
            )
        else:
            assert renamed == []
            if program in ("descent", "sparse_descent"):
                # dense lanes, and subspace lanes shorter than a tile,
                # stay on XLA's sweeps
                assert kernels == []
        if program == "tile_fit":
            assert kernels and all(
                re.fullmatch(r"_tiled_apply_jit\.\d+", k) for k in kernels
            )


# the (capacity, width, entities) classes of ``glmix_sparse_re``'s per-user
# effect at its 1/8 cut, as ``prepare_buckets`` builds them from the
# configuration's data (counted on the CPU, PR 27): 17,312 users,
# 16,238,476 support columns
SPARSE_RE_CLASSES = (
    (64, 256, 98), (64, 512, 4847), (64, 1024, 1523), (128, 1024, 5147),
    (128, 2048, 20), (256, 1024, 281), (256, 2048, 3163), (512, 2048, 1048),
    (512, 4096, 529), (1024, 4096, 515), (2048, 4096, 71), (2048, 8192, 48),
    (4096, 8192, 20), (8192, 8192, 2),
)


def test_sparse_random_effect_visit_fits_the_chip_at_the_benchmarks_cut(
    topo, monkeypatch, request
):
    """The fused visit of the benchmark's wide sparse per-user effect
    (2,500,033 rows, 17,312 users, 16,384 columns, 16 nonzeros a row, the
    bucket classes above) compiles for a v5e, and what it needs leaves room
    for the rest of the descent: the whole fused outer iteration asked for
    13.4 GB of the chip's 16.9 GB when this test was written (7.9 GB of it
    scratch), the visit alone for less. A change that densifies more lanes
    at a time, or brings an (n, 16) or (k, d) temporary back, shows here
    without a chip.

    As a TPU backend decides it (PR 37): the eleven classes of 128 rows or
    more evaluate value and gradient through ``ops/fused``'s row-major
    float32 kernel, one a class and site of ``optim/lbfgs``, each named
    after ``re.sparse_pass`` under ``re.solve`` (the stages
    ``sparse_re.pass_s_per_iter`` and ``sparse_re.pass_roofline`` read);
    the three 64-row classes keep the multiply-reduces, in the same
    program."""
    import stage_programs as programs
    from photon_ml_tpu.ops import glm

    monkeypatch.setattr(glm, "_interpret_fused", lambda: False)
    programs.lanes_take_the_kernel(monkeypatch)
    from photon_ml_tpu.config import (
        OptimizationConfig,
        OptimizerConfig,
        RegularizationContext,
    )
    from photon_ml_tpu.game import RandomEffectCoordinate, SparseFeatures
    from photon_ml_tpu.game.data import (
        EntityBuckets,
        EntityGrouping,
        GameBatch,
        NonzeroMajorSparseFeatures,
    )
    from photon_ml_tpu.game.random_effect import (
        PreparedBucket,
        subspace_chunk_lanes,
    )
    from photon_ml_tpu.ops.batch import LocalSparseBatch
    from photon_ml_tpu.types import RegularizationType

    n, entities, d, nnz = 2_500_033, 17_312, 16_384, 16
    spec = _spec(topo)
    f32, i32 = jnp.float32, jnp.int32
    prepared = []
    for capacity, width, k in SPARSE_RE_CLASSES:
        chunk = subspace_chunk_lanes(capacity, width, k)
        lanes = -(-k // chunk) * chunk  # padded to whole chunks
        prepared.append(PreparedBucket(
            entity_ids=np.zeros(k, np.int64), ids=spec((k,), i32),
            static=LocalSparseBatch(
                indices=spec((lanes, capacity * nnz), i32),
                values=spec((lanes, capacity * nnz), f32),
                labels=spec((lanes, capacity), f32),
                offsets=spec((lanes, capacity), f32),
                weights=spec((lanes, capacity), f32), num_features=width,
            ),
            # the cell's users are blocks of the file: run starts, no order
            row_idx=spec((lanes,), i32), mask=spec((lanes, capacity), f32),
            num_real=k, columns=spec((lanes, width), i32),
        ))
    shard = SparseFeatures(
        indices=spec((n, nnz), i32), values=spec((n, nnz), f32), num_features=d
    )
    coordinate = RandomEffectCoordinate(
        coordinate_id="per_userId",
        batch=GameBatch(
            labels=spec((n,), f32), offsets=spec((n,), f32),
            weights=spec((n,), f32), features={"per_userId": shard},
            id_tags={"userId": spec((n,), i32)},
        ),
        feature_shard_id="per_userId", random_effect_type="userId",
        config=OptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=50, tolerance=3e-3),
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        ),
        grouping=EntityGrouping(entities, np.zeros(0), np.zeros(0), []),
        buckets=EntityBuckets((), [], []), task_type=TaskType.LOGISTIC_REGRESSION,
        num_entities=entities,
    )
    object.__setattr__(coordinate, "_prepared_cache", prepared)
    visit = coordinate._build_visit_fn()
    jax.clear_caches()  # the rule is read when ``_solve_bucket`` is traced
    request.addfinalizer(jax.clear_caches)
    compiled = visit.lower(
        spec((n,), f32), spec((n,), f32), spec((entities, d), f32),
        (None, tuple(
            (pb.static, pb.row_idx, pb.mask, pb.ids, pb.columns) for pb in prepared
        )),
        NonzeroMajorSparseFeatures(
            indices=spec((nnz, n), i32), values=spec((nnz, n), f32), num_features=d
        ),
        spec((n,), i32),
    ).compile()
    need = compiled.memory_analysis()
    total = (need.argument_size_in_bytes + need.output_size_in_bytes
             + need.temp_size_in_bytes)
    assert total < 9.0e9, (
        need.argument_size_in_bytes, need.output_size_in_bytes,
        need.temp_size_in_bytes,
    )
    text = compiled.as_text()
    taken = [c for c in SPARSE_RE_CLASSES if c[0] >= 128]
    assert len(taken) == 11
    kernels = [name for name, is_kernel in _instructions(text) if is_kernel]
    assert len(kernels) == 3 * len(taken)
    assert all(re.fullmatch(r"re\.sparse_pass\.\d+", k) for k in kernels)
    assert all(
        "/re.solve/" in path and "/re.sparse_pass/" in path
        for path in _kernel_paths(text)
    )


# The capacity classes (capacity, entities) of the per-item effect of
# ``glmix_ml20m_full`` (20,000,263 rows, 26,744 items), as ``bucket_entities``
# builds them from ``benchmark/datagen_glmix_mesh.id_columns`` (counted on the
# CPU, PR 38): the effect whose rows lie all over the file
ML20M_FULL_ITEM_CLASSES = (
    (256, 12452), (512, 5219), (1024, 4274), (2048, 2719), (4096, 1351),
    (8192, 524), (32768, 197), (131072, 8),
)


def test_mesh_visit_of_the_whole_item_effect_fits_a_chip_of_four(
    topo, monkeypatch, request
):
    """The per-item visit of ``ml20m_full_descent4`` under ``shard_map`` for
    the 2x2 mesh, at the whole size: a chip holds a quarter of every class's
    lanes and 5,000,066 rows, the residual is made whole (one all-gather to
    f32[20000264], 80 MB), a chip gathers its own segment of the effect's
    order out of it once (PR 39: about 5.03M indices, the fullest chip's rows
    and the leading 0) and the solved lanes are gathered; no per-row matrix is
    ever the whole batch's and no class is read by one index a slot. The
    visit asked 5.81 GB a chip when this was written (5.14 of it scratch); one
    replicated ``(rows, 8)`` operand is 10.2 GB more."""
    from photon_ml_tpu.config import (
        OptimizationConfig,
        OptimizerConfig,
        RegularizationContext,
    )
    from photon_ml_tpu.game import DenseFeatures, RandomEffectCoordinate
    from photon_ml_tpu.game import coordinate as coordinate_module
    from photon_ml_tpu.game.data import EntityBuckets, EntityGrouping, GameBatch
    from photon_ml_tpu.game.random_effect import PreparedBucket
    from photon_ml_tpu.ops.batch import DenseBatch
    from photon_ml_tpu.types import OptimizerType, RegularizationType

    monkeypatch.setattr(coordinate_module, "fuses_under_mesh", lambda *a: True)
    mesh = Mesh(np.array(topo.devices), ("data",))
    rows, entities, width = 20_000_264, 26_744, 8
    over, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    f32, i32 = jnp.float32, jnp.int32
    row = lambda shape, dt=f32: jax.ShapeDtypeStruct(shape, dt, sharding=over)
    segment = 5_027_600  # mesh.row_imbalance 1.0055 of 5,000,066, and the leading 0
    order = row((4 * segment,), i32)
    prepared, bucket_args = [], []
    for capacity, k in ML20M_FULL_ITEM_CLASSES:
        lanes = -(-k // 4) * 4
        static = DenseBatch(
            X=row((lanes, capacity, width)), labels=row((lanes, capacity)),
            offsets=row((lanes, capacity)), weights=row((lanes, capacity)),
        )
        starts = row((lanes,), i32)
        prepared.append(PreparedBucket(
            entity_ids=np.zeros(k, np.int64), ids=None, static=static,
            row_idx=starts, mask=row((lanes, capacity)), num_real=k, order=order,
        ))
        bucket_args.append(
            (static, starts, row((lanes, capacity)), row((lanes,), i32), None)
        )
    shard = DenseFeatures(X=row((rows, width)))
    coordinate = RandomEffectCoordinate(
        coordinate_id="per_itemId",
        batch=GameBatch(
            labels=row((rows,)), offsets=row((rows,)), weights=row((rows,)),
            features={"per_itemId": shard}, id_tags={"itemId": row((rows,), i32)},
        ),
        feature_shard_id="per_itemId", random_effect_type="itemId",
        config=OptimizationConfig(
            optimizer=OptimizerConfig(
                optimizer_type=OptimizerType.NEWTON_CHOLESKY, max_iterations=20,
                tolerance=1e-7,
            ),
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        ),
        grouping=EntityGrouping(entities, np.zeros(0), np.zeros(0), []),
        buckets=EntityBuckets((), [], []), task_type=TaskType.LOGISTIC_REGRESSION,
        num_entities=entities, mesh=mesh,
    )
    object.__setattr__(coordinate, "_prepared_cache", prepared)
    visit = coordinate._build_visit_fn()
    jax.clear_caches()
    request.addfinalizer(jax.clear_caches)
    compiled = visit.lower(
        row((rows,)), row((rows,)),
        jax.ShapeDtypeStruct((entities, width), f32, sharding=whole),
        (order, tuple(bucket_args)), shard, row((rows,), i32),
    ).compile()
    need = compiled.memory_analysis()
    total = (need.argument_size_in_bytes + need.output_size_in_bytes
             + need.temp_size_in_bytes)
    assert total < 7.0e9, (
        need.argument_size_in_bytes, need.output_size_in_bytes,
        need.temp_size_in_bytes,
    )
    text = compiled.as_text()
    # a device's program: the residual whole, every matrix a quarter
    assert f"f32[{rows}]" in text and f"f32[{rows // 4},{width}]" in text
    assert not re.search(rf"\[{rows},\d+\]", text)
    assert re.search(r"all-gather(-start)?\(", text)
    # the residual in the effect's order, gathered by a chip's segment
    assert f"f32[{segment}]" in text and f"s32[{segment}]" in text
    assert not re.search(r"s32\[\d+,(256|512|1024|2048|4096|8192|32768|131072)\]", text)
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    assert any("/mesh.exchange/" in p for p in paths)
