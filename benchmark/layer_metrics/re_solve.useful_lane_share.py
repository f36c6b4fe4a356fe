"""Percent of the executed entity-iterations of the bucket solves that
were useful (layer: random_effects). Every lane of a bucket runs until its
slowest lane stops, so executed = lanes x slowest, useful = sum over lanes;
from the per-lane iteration counts of each random effect's last visit."""

from benchmark.readers import ratio


def read(obs):
    return ratio(
        obs, "re_solve.useful_entity_iterations",
        "re_solve.executed_entity_iterations", 100.0,
    )
