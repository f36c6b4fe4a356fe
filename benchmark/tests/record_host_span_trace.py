"""How ``data/small_cpu_host_spans.xplane.pb`` was recorded (CPU backend,
one device):

    JAX_PLATFORMS=cpu python benchmark/tests/record_host_span_trace.py

Three units inside the harness's slice span. A unit is what a descent cell's
is: under the harness's ``descent.run`` span a program span ``descent/run``
with ``descent/prepare`` (a 4 ms pause: the device idles under it),
``descent/launch`` (a small jitted program is dispatched) and
``descent/collect`` (its result is awaited, then a 3 ms pause) inside it;
then the harness's ``fence``, and a 5 ms pause under no span at all. The
program's span names are written here as strings: the benchmark's reader is
tested against the trace format, not against the program.
"""

import argparse
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

PROGRAM_SPANS = ("descent/run", "descent/prepare", "descent/launch", "descent/collect")


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from benchmark import harness

    p = argparse.ArgumentParser()
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data",
        "small_cpu_host_spans.xplane.pb"))
    args = p.parse_args()

    @jax.jit
    def small_program(x):
        def body(i, x):
            return jnp.tanh(x @ x) * 0.5
        return jax.lax.fori_loop(0, 4, body, x).sum()

    x = jnp.ones((256, 256), jnp.float32)
    small_program(x).block_until_ready()
    harness._start_trace()
    with harness._annotate(harness.SLICE_SPAN):
        for _ in range(3):
            with harness._annotate("descent.run"), TraceAnnotation("descent/run"):
                with TraceAnnotation("descent/prepare"):
                    time.sleep(0.004)
                with TraceAnnotation("descent/launch"):
                    out = small_program(x)
                with TraceAnnotation("descent/collect"):
                    out.block_until_ready()
                    time.sleep(0.003)
            with harness._annotate("fence"):
                out.block_until_ready()
            time.sleep(0.005)
    path = harness._stop_trace()
    shutil.copy(path, args.out)
    print(args.out, os.path.getsize(args.out), "bytes")


if __name__ == "__main__":
    main()
