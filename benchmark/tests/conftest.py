"""The benchmark's own tests: everything but the chip.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They run the runners tiny on the CPU backend (Pallas kernels in interpret
mode) and on four virtual devices, the references against closed forms,
the trace reduction on a small recorded trace, and the manifest against
the files it names. Must be set before the first ``import jax``.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4"
    ).strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
