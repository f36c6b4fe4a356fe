"""Profiling hook tests: the trace context writes a loadable trace, a span
lands in it by name (the span bridge) whether or not a sink is on, and the
no-op path stays a no-op."""

import glob
import os
import warnings

import jax
import jax.numpy as jnp

from photon_ml_tpu import obs
from photon_ml_tpu.utils import profile_trace


def _host_event_names(profile_dir) -> set[str]:
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        profile = ProfileData.from_file(path)
        return {
            ev.name
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
        }


def test_span_under_a_profiler_trace_is_on_the_host_plane(tmp_path):
    """Under a profiler trace the program's span is an event of the
    profiler's host plane, by name: it shares a clock with the device
    operations, with a telemetry sink and, since PR 36, without one."""
    obs.configure(str(tmp_path / "telemetry"))
    try:
        with profile_trace(str(tmp_path), "unit"):
            with obs.span("descent/iter", iteration=0):
                x = jnp.ones((64, 64)) @ jnp.ones((64, 64))
                jax.block_until_ready(x)
    finally:
        obs.shutdown()
    with profile_trace(str(tmp_path), "off"):
        with obs.span("glm/lambda"):
            jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    assert "descent/iter" in _host_event_names(tmp_path / "unit")
    assert "glm/lambda" in _host_event_names(tmp_path / "off")


def test_profile_trace_none_is_noop(tmp_path):
    with profile_trace(None, "unit"):
        pass
    assert list(tmp_path.iterdir()) == []
