"""Test harness setup.

Parity with the reference's test strategy (SURVEY.md §4): the reference runs
distributed code in local-mode Spark; we run collective code on a virtual
8-device CPU mesh via ``xla_force_host_platform_device_count``, so every
``shard_map``/psum code path executes in CI without TPU hardware. Must run
before the first ``import jax`` anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Analytic device-cost capture (obs/devcost) AOT-compiles every fresh
# executable a second time while a telemetry sink is active. The tier-1
# suite sits NEAR its wall-clock budget (1260 s — see ROADMAP's tier-1
# line), so the suite pins capture OFF and
# tests that exercise it (tests/test_devcost.py) opt back in by clearing
# or overriding this variable.
os.environ.setdefault("PHOTON_DEVCOST", "0")
# Double precision in tests: finite-difference derivative checks need it.
os.environ["JAX_ENABLE_X64"] = "1"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache (tier-1 runtime): the suite's dominant
# idiom is "reference arm vs knob arm, asserted bitwise", which compiles
# the SAME HLO two or more times per test — and the suite is
# compile-dominated, not execution-dominated (a warm cache cuts
# representative modules ~57%; intra-run dedupe alone cuts them ~18% cold).
# The cache key is content-addressed over the HLO and the jax/XLA versions,
# so a code change is a clean miss, never a stale hit, and a cache hit
# returns byte-identical executables — bitwise parity assertions are
# unaffected. min-compile-time 0 matters: the duplicate mass is many SMALL
# programs, which the 1 s default would skip. The directory is fixed — the
# checkout's own ``.jax_cache``, the one ``utils/compile_cache`` uses — and
# set through the environment only, before the first ``import jax``:
# ``setdefault`` so an outer environment still wins, and gloo loopback
# worker subprocesses inherit it and dedupe their identical per-process
# programs against it too.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    ),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import numpy as np
import pytest


def pytest_collection_modifyitems(config, items):
    """Run ``kernel``-marked tests (the Pallas interpret-mode parity
    block — the suite's biggest time cost) LAST, preserving relative
    order on both sides of the split. On a box where the tier-1
    wall-clock budget truncates the run, the cut then lands on kernel
    parity coverage (selectable separately via ``-m kernel``) instead of
    on unrelated tests mid-suite; on a fast box every test still runs."""
    items.sort(key=lambda it: it.get_closest_marker("kernel") is not None)


# Tier-1 runtime guard (the suite sits NEAR its wall-clock budget —
# 1260 s, see ROADMAP's tier-1 line): every
# kernel-marked test must trace its Pallas kernels at retuned-DOWN
# constants — interpret-mode cost scales with the DMA-step carve, and one
# test silently instantiating default-size tiles (GROUPS_PER_STEP=32 x
# SEGMENTS_PER_DMA=4 = 16K-nnz steps) costs ~an order of magnitude more
# than the 8x2 test discipline. Collection cannot see what a test will
# build, so the fixture below (a) RETUNES kernel-marked tests down to the
# 8x2 carve by default (tests may monkeypatch further; the layout builder
# and kernel read the constants at call time, so both sides track), and
# (b) wraps the layout builder to fail AT THE BUILD, with an actionable
# message, if a test restores a default-size carve. Run the tier-1
# command with ``--durations=15`` (see ROADMAP) to spot runtime creep.
_KERNEL_TEST_MAX_STEP_NNZ = 8 * 2 * 128  # the retuned-down 8x2 carve


@pytest.fixture(autouse=True)
def _kernel_test_constants_guard(request):
    if request.node.get_closest_marker("kernel") is None:
        yield
        return
    import photon_ml_tpu.ops.sparse_tiled as st

    orig_build = st.build_write_major_layout
    orig_constants = (st.GROUPS_PER_STEP, st.SEGMENTS_PER_DMA)
    st.GROUPS_PER_STEP, st.SEGMENTS_PER_DMA = 8, 2

    def guarded(*args, **kwargs):
        # groups_per_step is parameter #6 of build_write_major_layout —
        # resolve positional and keyword spellings alike, or a positional
        # call would silently bypass the guard
        gps = kwargs.get("groups_per_step")
        if gps is None and len(args) > 5:
            gps = args[5]
        if gps is None:
            gps = st.GROUPS_PER_STEP
        step_nnz = gps * st.SEGMENTS_PER_DMA * st.GROUP
        if step_nnz > _KERNEL_TEST_MAX_STEP_NNZ:
            pytest.fail(
                f"kernel-marked test built a tile layout at default-size "
                f"constants (GROUPS_PER_STEP={gps} x SEGMENTS_PER_DMA="
                f"{st.SEGMENTS_PER_DMA} = {step_nnz}-nnz DMA steps > "
                f"{_KERNEL_TEST_MAX_STEP_NNZ}). Interpret-mode kernel cost "
                f"scales with the step carve and the tier-1 suite sits "
                f"near its wall-clock budget: keep the retuned-down constants "
                f"this fixture installs (or monkeypatch smaller), or drop "
                f"the kernel marker if no kernel is traced."
            )
        return orig_build(*args, **kwargs)

    st.build_write_major_layout = guarded
    try:
        yield
    finally:
        st.build_write_major_layout = orig_build
        st.GROUPS_PER_STEP, st.SEGMENTS_PER_DMA = orig_constants


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs_per_module():
    """Every CPU executable keeps memory mappings alive, and the suite
    compiles thousands: around 88% of the way through, the process hit
    ``vm.max_map_count`` (65530) and died with SIGSEGV inside an unrelated
    compile. Dropping the in-memory caches after each module bounds the
    mappings; the persistent cache above makes a later re-compile of the
    same program a file read."""
    yield
    import jax

    jax.clear_caches()


@pytest.fixture
def rng():
    return np.random.default_rng(42)
