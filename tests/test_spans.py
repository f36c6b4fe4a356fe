"""Host spans always on (``obs/spans.py``): the registry timers a span moves
with no sink, the sites this names, the names' one home, the compile
pipeline's seconds counted once, and the baseline a profiler session takes.
"""

from __future__ import annotations

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stage_programs as programs
from photon_ml_tpu import obs
from photon_ml_tpu.obs import sink, spans
from photon_ml_tpu.obs.metrics import REGISTRY

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the modules with a span site that ISSUE 36 touched
SPANNED_MODULES = (
    "game/descent.py", "game/data.py", "game/coordinate.py", "ops/batch.py",
    "ops/sparse_tiled.py", "ops/tile_cache.py", "supervised/training.py",
    "parallel/distributed.py",
)


@pytest.fixture
def timers():
    """The span and compile-step timers from zero, with no sink."""
    obs.shutdown()
    for prefix in ("span", "jax."):
        REGISTRY.reset_timers(prefix)

    def read(prefix=spans.TIMER):
        return {
            k[len(prefix):]: v for k, v in REGISTRY.timer_snapshot(prefix).items()
        }

    return read


def _calls(timers, prefix=spans.TIMER) -> dict:
    return {k: v["calls"] for k, v in timers(prefix).items()}


def test_top_level_and_self_seconds(timers):
    with obs.span("a/outer"):
        with obs.span("a/inner"):
            pass
        with obs.span("a/inner"):
            pass
    with obs.span("a/alone"):
        pass
    assert _calls(timers) == {"a/outer": 1, "a/inner": 2, "a/alone": 1}
    # only the outermost open span of the thread is top-level
    assert _calls(timers, spans.TOP_TIMER) == {"a/outer": 1, "a/alone": 1}
    # a span with children keeps what it spent under none of them; a leaf
    # (a/alone, a/inner) has no such timer
    own = timers(spans.SELF_TIMER)
    assert set(own) == {"a/outer"}
    all_ = timers()
    assert own["a/outer"]["seconds"] == pytest.approx(
        all_["a/outer"]["seconds"] - all_["a/inner"]["seconds"], abs=1e-9
    )
    assert 0 <= own["a/outer"]["seconds"] <= all_["a/outer"]["seconds"]
    assert spans.open_spans() == 0


def test_spanned_decorates_a_function(timers):
    @spans.spanned("a/fn")
    def double(x, scale=2):
        """doc"""
        assert spans.open_spans() == 1
        return x * scale

    assert double(3) == 6 and double(3, scale=3) == 9
    assert double.__name__ == "double" and double.__doc__ == "doc"
    assert _calls(timers) == {"a/fn": 2}


def test_a_tiny_descent_moves_each_named_timer_once_a_site(timers):
    from photon_ml_tpu.game import CoordinateDescent

    coordinates, batch, task = programs.descent_coordinates()
    seq = list(programs.DESCENT_COORDINATES)
    # building the data ran the set-up entry points, once a call
    assert _calls(timers) == {
        spans.GAME_BATCH: 1, spans.GAME_GROUP: 1, spans.GAME_BUCKET: 1,
    }
    descent = CoordinateDescent(coordinates, batch, task)
    descent.run(seq, 2)  # 2 iterations: one launch of r = 2
    first = _calls(timers)
    assert first == {
        spans.GAME_BATCH: 1, spans.GAME_GROUP: 1, spans.GAME_BUCKET: 1,
        spans.DESCENT_RUN: 1,
        # the fused program's parts, then the launch's owns and statics
        spans.DESCENT_PREPARE: 2, spans.DESCENT_LAUNCH: 1,
        spans.DESCENT_COLLECT: 1,
        # each coordinate stages its tensors at its first use, inside the
        # first prepare; a dense fixed shard keeps its layout
        spans.COORD_FIXED: 1, spans.COORD_RE: 1, spans.LAYOUT_OPTIMIZE: 1,
    }
    descent.run(seq, 3)  # 3 iterations: launches of r = 2 and r = 1
    second = _calls(timers)
    grown = {k: second[k] - first[k] for k in second if second[k] != first[k]}
    assert grown == {
        spans.DESCENT_RUN: 1, spans.DESCENT_PREPARE: 2,
        spans.DESCENT_LAUNCH: 2, spans.DESCENT_COLLECT: 2,
    }
    # nothing nests in an entry point but what belongs to it
    assert set(_calls(timers, spans.TOP_TIMER)) == {
        spans.GAME_BATCH, spans.GAME_GROUP, spans.GAME_BUCKET, spans.DESCENT_RUN,
    }
    # the parts do not exceed their parent, and the parent's own seconds
    # are what is left of it
    t, own = timers(), timers(spans.SELF_TIMER)
    steps = sum(
        t[k]["seconds"]
        for k in (spans.DESCENT_PREPARE, spans.DESCENT_LAUNCH, spans.DESCENT_COLLECT)
    )
    run = t[spans.DESCENT_RUN]["seconds"]
    assert steps <= run
    assert own[spans.DESCENT_RUN]["seconds"] == pytest.approx(run - steps, abs=1e-6)
    staged = t[spans.COORD_FIXED]["seconds"] + t[spans.COORD_RE]["seconds"]
    assert staged <= t[spans.DESCENT_PREPARE]["seconds"]
    # the compile pipeline's seconds fell inside the launches
    steps_s = sum(
        timers("jax.").get(k[len("jax."):], {"seconds": 0.0})["seconds"]
        for k in (sink.TRACE_TIMER, sink.LOWER_TIMER)
    )
    assert 0 < steps_s <= run


def test_the_unfused_descent_nests_under_one_entry_point(timers, monkeypatch):
    """The eager visit loop (a compaction cadence turns the fused visit
    off) runs under ``descent/run`` like the fused one: the visits' spans
    are its children, and nothing else is top-level."""
    from photon_ml_tpu.game import CoordinateDescent

    monkeypatch.setenv("PHOTON_RE_COMPACT_EVERY", "2")
    coordinates, batch, task = programs.descent_coordinates()
    seq = list(programs.DESCENT_COORDINATES)
    REGISTRY.reset_timers("span")
    CoordinateDescent(coordinates, batch, task).run(seq, 2)
    calls = _calls(timers)
    assert calls[spans.DESCENT_RUN] == 1 and calls[spans.DESCENT_ITER] == 2
    assert calls[spans.DESCENT_VISIT] == 2 * len(seq)
    assert spans.DESCENT_LAUNCH not in calls
    assert set(_calls(timers, spans.TOP_TIMER)) == {spans.DESCENT_RUN}


def test_a_tiny_tile_coo_build_moves_each_phase(timers, monkeypatch):
    """``optimize_batch_layout`` -> ``tile_cache`` -> ``tile_sparse_batch``:
    one entry point, the build's phases inside it, and their sum within
    the entry point's own seconds."""
    import photon_ml_tpu.ops.sparse_tiled as st
    from photon_ml_tpu.ops import tile_cache
    from photon_ml_tpu.ops.batch import SparseBatch, optimize_batch_layout

    monkeypatch.setattr(st, "GROUPS_PER_STEP", 8)
    monkeypatch.setattr(st, "SEGMENTS_PER_DMA", 2)
    tile_cache.clear()
    rng = np.random.default_rng(0)
    n, d, k = 2048, 4096, 8
    batch = SparseBatch(
        indices=jnp.asarray(rng.integers(0, d, (n, k)), jnp.int32),
        values=jnp.asarray(rng.normal(size=(n, k)), jnp.float32),
        labels=jnp.zeros((n,), jnp.float32), offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32), num_features=d,
    )
    tiled = optimize_batch_layout(batch, hbm_budget_bytes=1e6)
    assert isinstance(tiled, st.TiledSparseBatch) and len(tiled.chunks) == 1
    assert _calls(timers) == {
        spans.LAYOUT_OPTIMIZE: 1,
        # the gate's look at the values, the fingerprint's arrays, the build's
        spans.LAYOUT_TO_HOST: 3,
        spans.LAYOUT_FINGERPRINT: 1, spans.LAYOUT_HEAD: 1, spans.LAYOUT_MERGE: 1,
        # the nonzeros' extraction, the chunk's own nonzeros, its two layouts
        spans.LAYOUT_PACK: 3,
        spans.LAYOUT_STAGE: 1,
    }
    assert _calls(timers, spans.TOP_TIMER) == {spans.LAYOUT_OPTIMIZE: 1}
    t = timers()
    phases = sum(v["seconds"] for name, v in t.items() if name != spans.LAYOUT_OPTIMIZE)
    assert 0 < phases <= t[spans.LAYOUT_OPTIMIZE]["seconds"]
    # a second request hits the layout cache: no build, no phase but the
    # gate and the fingerprint
    optimize_batch_layout(batch, hbm_budget_bytes=1e6)
    again = _calls(timers)
    assert again[spans.LAYOUT_PACK] == 3 and again[spans.LAYOUT_FINGERPRINT] == 2
    assert again[spans.LAYOUT_TO_HOST] == 5


def _span_name_arguments(path: str):
    """The first argument of every ``span(...)`` / ``spanned(...)`` call in
    the module at ``path``."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        fn = node.func
        name = fn.id if isinstance(fn, ast.Name) else getattr(fn, "attr", "")
        if name in ("span", "spanned"):
            yield node.args[0]


@pytest.mark.parametrize("module", SPANNED_MODULES)
def test_every_span_name_is_a_constant_of_obs_spans(module):
    """As ``test_stages`` holds for the stage names: a span of these
    modules is opened by a constant of ``obs/spans.py``, never by a string
    written at the site."""
    constants = {
        name for name, value in vars(spans).items()
        if name.isupper() and isinstance(value, str) and "/" in value
    }
    found = list(_span_name_arguments(os.path.join(ROOT, "photon_ml_tpu", module)))
    assert found, f"{module} opens no span"
    for arg in found:
        assert isinstance(arg, ast.Name) and arg.id in constants, ast.dump(arg)


def test_the_names_keep_their_form():
    names = [
        value for name, value in vars(spans).items()
        if name.isupper() and isinstance(value, str) and "/" in value
    ]
    assert len(names) == len(set(names))
    for value in names:
        layer, _, step = value.partition("/")
        assert layer and step and "/" not in step and " " not in value
    assert set(spans.TOP_LEVEL) <= set(names)


def test_tracing_seconds_are_counted_once_under_a_nested_jit(timers):
    """JAX reports a tracing duration for every ``jit`` it traces, the
    inner ones inside the outer one's trace and inside its seconds. The
    listener books the outermost alone."""
    reported = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: reported.append((name, secs))
    )

    @jax.jit
    def inner(x):
        return jnp.tanh(x) * 2.0 + 1.0

    @jax.jit
    def middle(x):
        return inner(inner(x) + 1.0).sum()

    @jax.jit
    def outer(x):
        return middle(x) + middle(x * 2.0)

    x = jnp.ones((16,), jnp.float32)  # made before the span: its own traces
    jax.block_until_ready(x)
    reported.clear()
    with obs.span("a/launch"):
        jax.block_until_ready(outer(x))
    traces = [s for name, s in reported if name == sink._JAX_TRACE]
    assert len(traces) >= 3  # outer, middle, inner: each reported
    booked = timers("jax.")[sink.TRACE_TIMER[len("jax."):]]
    assert booked["calls"] == 1
    assert booked["seconds"] == pytest.approx(max(traces))
    assert booked["seconds"] < sum(traces)
    lowered = timers("jax.")[sink.LOWER_TIMER[len("jax."):]]
    assert lowered["calls"] == 1  # one module: the inner functions ride in it


def test_compile_steps_are_booked_under_a_span_only(timers):
    @jax.jit
    def f(x):
        return x * 3.0 - 1.0

    jax.block_until_ready(f(jnp.ones((4,), jnp.float32)))  # no span open
    assert not timers("jax.").get(sink.LOWER_TIMER[len("jax."):])
    assert REGISTRY.timer_snapshot("jax.compile_s")  # the old timer: always


def test_a_profiler_session_takes_the_registry_as_its_baseline(timers, tmp_path):
    """What the benchmark reads set-up from: the timers as they stood when
    an entry point first ran under the profiler, whatever runs later."""
    with obs.span("a/setup"):
        pass
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("a/window"):
            with obs.span("a/step"):
                pass
        with obs.span("a/window"):
            pass
    with obs.span("a/setup"):  # after the session: a check that runs again
        pass
    base = spans.session_baseline()
    assert {k: v["calls"] for k, v in base.items() if k.startswith("span")} == {
        "span.a/setup": 1, "span_top.a/setup": 1,
    }
    assert _calls(timers)["a/setup"] == 2
    # the next session takes its own
    with jax.profiler.trace(str(tmp_path / "second")):
        with obs.span("a/window"):
            pass
    assert spans.session_baseline()["span.a/setup"]["calls"] == 2
