"""How ``data/small_cpu.xplane.pb`` was recorded (CPU backend, one device):

    JAX_PLATFORMS=cpu python benchmark/tests/record_small_trace.py

Three launches of one small jitted program inside the harness's slice span,
each under a ``fit`` span and followed by a ``fence`` span, with a pause
between the second and the third during which nothing runs. With ``--out``
on a machine with a TPU it records the same thing there.
"""

import argparse
import glob
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
        os.path.dirname(os.path.abspath(__file__)), "data", "small_cpu.xplane.pb"))
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
        for i in range(3):
            with harness._annotate("fit"):
                out = small_program(x)
            with harness._annotate("fence"):
                out.block_until_ready()
            if i == 1:
                time.sleep(0.05)
    path = harness._stop_trace()
    shutil.copy(path, args.out)
    print(args.out, os.path.getsize(args.out), "bytes")


if __name__ == "__main__":
    main()
