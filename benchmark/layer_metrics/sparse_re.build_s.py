"""Seconds of the host build of the sparse random effects' per-entity index
maps in set-up (``game/projector.sparse_index_map`` and the re-classing of
the buckets by width), from the program's timer ``re_subspace.build``
(layer: random_effects)."""

from benchmark.readers import counter


def read(obs):
    return counter(obs, "re_subspace.build.seconds")
