"""Shared utilities: logging, stage timing, device profiling, atomic IO."""

from photon_ml_tpu.utils.atomic_io import (  # noqa: F401
    atomic_replace,
    atomic_replace_bytes,
    atomic_savez,
)
from photon_ml_tpu.utils.logging import PhotonLogger, timed  # noqa: F401
from photon_ml_tpu.utils.profiling import profile_trace  # noqa: F401
