"""Seconds of set-up in which the random-effect coordinates staged their
tensors: ``prepare_buckets``, a sparse shard's index maps and its scoring
layout, at the coordinate's first use (program span
``coordinate/random-effect``; layer: random_effects)."""

from benchmark import host_spans


def read(obs):
    return host_spans.setup_span("COORD_RE")
