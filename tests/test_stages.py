"""Stage names inside the compiled programs (``obs/stages.py``).

(a) every stage of the vocabulary reaches the lowered programs' op names;
(d) the scopes change no result; the compile cache is keyed by the names'
version. (b), the deviceless v5e compile with and without scopes, is in
``tests/test_kernels_compile_tpu.py``: one file loads libtpu. (c) of ISSUE
24, the set-up timers, went with its part 2.
"""

from __future__ import annotations

import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import stage_programs as programs
from photon_ml_tpu.obs import stages


def _lowered_text(name: str) -> str:
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    fn, args, kwargs = programs.build(name, mesh)
    if name != "sparse_descent_kernels":
        return fn.lower(*args, **kwargs).as_text(debug_info=True)
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as patch:
        programs.lanes_take_the_kernel(patch)
        try:
            return fn.lower(*args, **kwargs).as_text(debug_info=True)
        finally:
            jax.clear_caches()


@pytest.fixture(scope="module")
def lowered():
    texts: dict[str, str] = {}

    def get(name: str) -> str:
        if name not in texts:
            texts[name] = _lowered_text(name)
        return texts[name]

    return get


def _has_segment(text: str, stage: str) -> bool:
    """``stage`` as one whole ``/``-separated segment of a location."""
    return re.search(r'[/"]' + re.escape(stage) + r'[/"]', text) is not None


@pytest.fixture
def no_scopes(monkeypatch):
    """``stage`` as a null context: what the programs were before this
    module existed. Programs traced under either arm must not leak into
    the other, so the trace caches are dropped on both sides."""
    jax.clear_caches()
    programs.without_scopes(monkeypatch)
    yield
    jax.clear_caches()


@pytest.mark.parametrize(
    "program,stage",
    [(p, s) for p, names in programs.PROGRAM_STAGES.items() for s in names],
)
def test_stage_reaches_the_lowered_program(lowered, program, stage):
    assert _has_segment(lowered(program), stage)


def test_the_programs_cover_the_vocabulary():
    """Every name of ``obs/stages.py`` is held by some program above, so a
    stage added there without a site (or a site that stopped tracing) shows
    here."""
    vocabulary = {
        value for name, value in vars(stages).items()
        if name.isupper() and isinstance(value, str) and name != "COORD_PREFIX"
    }
    held = {s for names in programs.PROGRAM_STAGES.values() for s in names}
    assert vocabulary <= held
    assert {stages.coord(c) for c in programs.DESCENT_COORDINATES} <= held


@pytest.mark.parametrize("cid,segment", [
    ("per_user", "coord.per_user"),
    ("per-item.v2", "coord.per-item.v2"),
    ("shard/a b:c", "coord.shard_a_b_c"),
])
def test_a_coordinate_id_becomes_one_segment(cid, segment):
    assert stages.coord(cid) == segment
    assert "/" not in segment


def test_without_scopes_no_stage_is_lowered(no_scopes):
    """The arm the other tests compare with really has no scope."""
    text = _lowered_text("descent")
    assert not any(_has_segment(text, s) for s in programs.DESCENT_STAGES)


def _run_descent():
    from photon_ml_tpu.game import CoordinateDescent

    coordinates, batch, task = programs.descent_coordinates()
    seq = list(programs.DESCENT_COORDINATES)
    result = CoordinateDescent(coordinates, batch, task).run(seq, 2)
    return [np.asarray(result.model[c].coefficient_means) for c in seq] + [
        np.asarray(result.training_scores[c]) for c in seq
    ]


def test_scopes_change_no_result(request):
    with_scopes = _run_descent()
    request.getfixturevalue("no_scopes")
    without = _run_descent()
    for a, b in zip(with_scopes, without):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_the_compile_cache_is_keyed_by_the_stage_names_version(monkeypatch):
    """JAX leaves metadata out of the persistent cache's key, so a scope
    that moves with no instruction changing would be served its old names:
    ``configure_compile_cache`` hashes ``stages.VERSION`` into every key."""
    from jax._src import cache_key

    from photon_ml_tpu.utils import compile_cache

    monkeypatch.setattr(cache_key, "custom_hook", cache_key.custom_hook)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere")
    assert compile_cache.configure_compile_cache() == "/somewhere"
    assert cache_key.custom_hook() == f"photon_ml_tpu.obs.stages/{stages.VERSION}"
