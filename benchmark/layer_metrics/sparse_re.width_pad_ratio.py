"""Columns the subspace lanes are solved at over the columns their
entities' rows touch (layer: random_effects): the sum over entities of the
width rung (a power of two from 128) over the sum of the supports, from the
program's prepare-time counters ``re_subspace.padded_columns`` and
``re_subspace.support_columns``. 1.0 is no padding."""

from benchmark.readers import ratio


def read(obs):
    return ratio(obs, "re_subspace.padded_columns", "re_subspace.support_columns")
