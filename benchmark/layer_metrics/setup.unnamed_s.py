"""Seconds of set-up inside a program span that has spans inside it and
under none of them (``span_self.*``): ``setup.program_s`` less the leaf
spans' seconds, which is what is still to name (layer: drivers)."""

from benchmark import host_spans


def read(obs):
    return host_spans.setup_unnamed()
