"""Seconds of ``ops/batch.optimize_batch_layout`` in set-up, on the
benchmark's host clock (layer: layout)."""

from benchmark.readers import counter


def read(obs):
    return counter(obs, "layout.build_s")
