"""``setup_s`` less ``setup.program_s``: the interpreter, imports, the
backend's start, the benchmark's own data and reference copies, and what the
warm-up's fence waits for (layer: drivers)."""

from benchmark import host_spans
from benchmark.readers import counter


def read(obs):
    inside, whole = host_spans.setup_program(), counter(obs, "setup_s")
    return None if inside is None or whole is None else whole - inside
