"""Idle seconds of the first device per outer iteration while no program span
is open (the harness's fence, ``account`` and loop). The label is the
innermost program span open on the host at the gap's midpoint (layer:
game_descent; ``benchmark/host_spans.py``); the three ``descent.idle_*``
parts add up to the device's idle seconds."""

from benchmark import host_spans


def read(obs):
    return host_spans.idle_per_work(obs, host_spans.is_outside)
