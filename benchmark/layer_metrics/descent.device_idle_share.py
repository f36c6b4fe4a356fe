"""Percent of the traced slice in which the chip ran nothing (profiler
trace; layer: device)."""

from benchmark.readers import idle_share as read  # noqa: F401
