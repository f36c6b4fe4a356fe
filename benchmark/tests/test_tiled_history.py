"""``optim.tiled_history_share``: the share of the L-BFGS history rings'
bytes that a solve stores as whole (8, 128) tiles, read from the
program's trace-time counters ``lbfgs_history.*``."""

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness

NAME = "optim.tiled_history_share"


def _trace_a_solve(d):
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.optim import lbfgs

    class Linear:
        def value_and_grad(self, w):
            return jnp.sum(w), jnp.ones_like(w)

    init, _, _ = lbfgs._lbfgs_funcs(Linear(), OptimizerConfig(), None)
    jax.eval_shape(init, jax.ShapeDtypeStruct((d,), jnp.float32))


@pytest.mark.parametrize(
    "widths,share",
    [((), None), ((47_236,), 0.0), ((1 << 20,), 100.0), ((65, 1 << 20), None)],
    ids=["no_solve", "rcv1_wide", "criteo_wide", "mixed"],
)
def test_the_reader_reads_the_tiled_share(widths, share):
    from photon_ml_tpu.obs.metrics import REGISTRY

    REGISTRY.reset(prefix="lbfgs_history.")
    for d in widths:
        _trace_a_solve(d)
    got = harness.layer_reader(NAME)(None)
    if widths == (65, 1 << 20):
        # bytes, not solves: the 65-wide ring is 5,200 of 83,891,280
        assert got == pytest.approx(100.0 * 83_886_080 / 83_891_280)
    else:
        assert got == share
    REGISTRY.reset(prefix="lbfgs_history.")


def test_the_manifest_lists_it_for_the_lbfgs_fit_cells():
    manifest = harness.load_manifest()
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert entry["layer"] == "optimizers" and entry["moves"] == "fit_s"
    assert entry["source"] == "program_counter" and entry["unit"] == "%"
    assert entry["workloads"] == ["kdda_fit", "criteo_fit", "rcv1_fit"]
    fits = {w["name"] for w in manifest["workloads"]
            if w["traffic"].startswith("fit_") and "tron" not in w["traffic"]}
    assert set(entry["workloads"]) <= fits
