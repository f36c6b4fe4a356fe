"""Seconds of set-up in which Python traced the program's functions to
jaxprs (outermost traces only, so no second is counted twice) and lowered
them to MLIR modules, Pallas kernels included; from ``jax.monitoring``,
booked under the program's spans only (``jax.trace_s`` + ``jax.lower_s``;
layer: compile). They fall inside the leaf spans that launch programs."""

from benchmark import host_spans


def read(obs):
    return host_spans.setup_compile_steps("TRACE_TIMER", "LOWER_TIMER")
