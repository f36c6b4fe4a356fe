"""How ``data/small_tpu_stages.xplane.pb`` was recorded (a TPU v5e, one
chip; my chip run, PR 24):

    chiprun -- python3 benchmark/tests/record_stage_trace.py \
        --out chiprun_out/small_tpu_stages.xplane.pb

Three small programs inside the harness's slice span, twice each. One has
the stages of a descent (``visit.fixed`` under ``coord.fixed``, then
``coord.per_user`` around ``visit.re`` around ``re.offsets``, a loop under
``re.solve``, ``re.score``, and a total outside every stage); one those of a
fit (a loop whose body is ``glm.objective`` inside ``lbfgs.line_search``
and a reduction under ``lbfgs.two_loop``, after a scaling under
``lbfgs.update``); one has no stage at all and the same shapes, so that it
shares instruction names with the others. The names are written here as
strings: the benchmark's reader is tested against the trace format, not
against the program.
"""

import argparse
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import harness

    p = argparse.ArgumentParser()
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data",
        "small_tpu_stages.xplane.pb"))
    args = p.parse_args()

    scope = jax.named_scope

    @jax.jit
    def descent_program(x, rows):
        with scope("coord.fixed"), scope("visit.fixed"):
            x = x + jnp.tanh(x @ x.T).sum(axis=0) * 1e-3
        with scope("coord.per_user"), scope("visit.re"):
            with scope("re.offsets"):
                y = x[rows] * 0.5

            def body(i, y):
                with scope("re.solve"):
                    return jnp.tanh(y @ y) * 0.5

            y = jax.lax.fori_loop(0, 4, body, y)
            with scope("re.score"):
                score = (y * x).sum(axis=1)
            total = score + 1.0
        return total.sum()

    @jax.jit
    def fit_program(x):
        def body(i, x):
            with scope("lbfgs.line_search"):
                with scope("glm.objective"):
                    m = jnp.tanh(x @ x)
                x = m * 0.5 + x
            with scope("lbfgs.two_loop"):  # a reduction: its own operation
                return x / (1.0 + jnp.sqrt((x * x).sum()))

        with scope("lbfgs.update"):
            x = x * 0.25
        return jax.lax.fori_loop(0, 3, body, x).sum()

    @jax.jit
    def plain_program(x):
        def body(i, x):
            x = jnp.tanh(x @ x) * 0.5 + x
            return x / (1.0 + jnp.sqrt((x * x).sum()))

        return jax.lax.fori_loop(0, 3, body, x * 0.25).sum()

    x = jnp.ones((256, 256), jnp.float32) * 0.01
    rows = (jnp.arange(256, dtype=jnp.int32) * 7) % 256
    programs = (
        lambda: descent_program(x, rows), lambda: fit_program(x),
        lambda: plain_program(x),
    )
    for run in programs:
        run().block_until_ready()
    harness._start_trace()
    with harness._annotate(harness.SLICE_SPAN):
        # the device's clock runs a millisecond or two off the host's
        # (PR 22's recording): keep every launch well inside the span
        time.sleep(0.01)
        for _ in range(2):
            for run in programs:
                with harness._annotate("fit"):
                    out = run()
                with harness._annotate("fence"):
                    out.block_until_ready()
        time.sleep(0.01)
    path = harness._stop_trace()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    shutil.copy(path, args.out)
    print(args.out, os.path.getsize(args.out), "bytes")


if __name__ == "__main__":
    main()
