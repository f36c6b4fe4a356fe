"""Seconds the chip ran an operation per outer iteration (profiler trace,
union over all programs; layer: game_descent)."""

from benchmark.readers import busy_seconds_per_work as read  # noqa: F401
