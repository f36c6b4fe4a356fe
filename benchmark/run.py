#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on, which
must hold a TPU with at least the chips the cell asks for: there is no CPU
fallback, and without a chip (or without the program, in a directory that
holds only the benchmark) it prints no result and exits non-zero. It prints
the device first, progress on standard error, and LAST on standard output
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, and ``breakdown`` when traced. See ``harness.py``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # before anything heavy is imported

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _log(msg: str) -> None:
    print(f"[benchmark +{time.perf_counter() - T_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = p.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "photon_ml_tpu")):
        print("benchmark: the program (photon_ml_tpu/) is not in this "
              "directory; nothing to measure", file=sys.stderr)
        return 2

    from benchmark import harness

    resolved = harness.resolve(harness.load_manifest(), args.workload)

    import jax

    platform = jax.default_backend()
    devices = jax.devices()
    if platform != "tpu" or len(devices) < resolved.chips:
        print(f"benchmark: {args.workload} needs {resolved.chips} TPU chip(s); "
              f"JAX reports platform={platform!r} with {len(devices)} "
              "device(s). There is no CPU fallback (benchmark/tests run the "
              "runners tiny on the CPU backend)", file=sys.stderr)
        return 2
    print(f"benchmark: platform: {platform}  device_kind: "
          f"{devices[0].device_kind}  devices: {len(devices)}  "
          f"jax: {jax.__version__}", flush=True)

    # the program's own helper: <checkout>/.jax_cache, or the directory
    # JAX_COMPILATION_CACHE_DIR names; small programs are kept either way
    from photon_ml_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _log(f"workload {args.workload} seed {args.seed} seconds {args.seconds} "
         f"trace {args.trace}; compile cache at {cache_dir}")

    result = harness.run_cell(
        resolved, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices[: resolved.chips], t_start=T_START, log=_log,
    )
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
