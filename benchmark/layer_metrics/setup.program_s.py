"""Seconds of set-up spent inside the program: the sum of its outermost
spans before the slice began (``make_game_batch``, ``group_by_entity``,
``bucket_entities``, ``optimize_batch_layout``, and the warm-up's
``CoordinateDescent.run`` / ``train_glm`` / ``DistributedTrainer.train``),
each second once, from the registry timers ``span_top.*`` (layer: drivers;
``benchmark/host_spans.py``)."""

from benchmark import host_spans


def read(obs):
    return host_spans.setup_program()
