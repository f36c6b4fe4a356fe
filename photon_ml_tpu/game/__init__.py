"""GAME (Generalized Additive Mixed Effects / GLMix) layer.

Reference parity: ``photon-api::ml.{data,algorithm,model}`` GAME machinery —
``GameDatum``, ``FixedEffectDataset``/``RandomEffectDataset``,
``Coordinate`` hierarchy, ``CoordinateDescent``, ``CoordinateDataScores``
(SURVEY.md §2.2, §3.1) — rebuilt TPU-first:

- Data is one columnar, device-resident ``GameBatch`` (not an RDD of row
  objects): per-shard feature matrices + global labels/offsets/weights +
  integer entity-id columns.
- The group-by-entity shuffle happens ONCE on the host at ingest (sort by
  entity → contiguous segments → padded buckets); there is no runtime
  shuffle at all.
- Random-effect training is a vmap-batched solver over entity buckets —
  millions of tiny solves become a few big batched kernels, sharded over
  the mesh's entity axis.
"""

from photon_ml_tpu.game.data import (  # noqa: F401
    DenseFeatures,
    EntityBuckets,
    EntityGrouping,
    GameBatch,
    SparseFeatures,
    bucket_entities,
    capacity_classes,
    group_by_entity,
    make_game_batch,
    place_game_batch,
    placeable_over,
)
from photon_ml_tpu.game.random_effect import (  # noqa: F401
    RandomEffectTrainingResult,
    random_effect_scores,
    train_random_effects,
)
from photon_ml_tpu.game.models import (  # noqa: F401
    FixedEffectModel,
    GameModel,
    GameSubModel,
    RandomEffectModel,
)
from photon_ml_tpu.game.coordinate import (  # noqa: F401
    Coordinate,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.descent import CoordinateDescent, CoordinateDescentResult  # noqa: F401
from photon_ml_tpu.game.streaming import (  # noqa: F401
    StreamedGameData,
    StreamedGameTrainer,
)
