"""Seconds of set-up in ``game/data.make_game_batch`` (program span
``game/batch``; layer: game_descent)."""

from benchmark import host_spans


def read(obs):
    return host_spans.setup_span("GAME_BATCH")
