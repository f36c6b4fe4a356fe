"""Seconds of the host-side grouping and bucketing of the random effects
in set-up (``game/data.group_by_entity`` and ``bucket_entities``), on the
benchmark's host clock (layer: game_descent)."""

from benchmark.readers import counter


def read(obs):
    return counter(obs, "descent.group_bucket_s")
