"""Online serving subsystem: ``photon-ml-tpu serve``.

Photon-ML's deployment story is train-offline/score-offline; this package
opens the request path. Four pieces, each composing a part the training
side already proved out:

- ``store`` — a :class:`HotModelStore`: the published GAME model's fixed
  effects stay device-resident whole, while the per-entity random-effect
  coefficient shards flow through a byte-budgeted LRU **hot working set**
  (``ops/bytelru``, the chunk cache's accounting generalized from data
  chunks to model shards; knob ``PHOTON_SERVE_HOT_BYTES``).
- ``router`` — micro-window request batching (flush on
  ``PHOTON_SERVE_MAX_BATCH`` or ``PHOTON_SERVE_MAX_WAIT_MS``) answered on
  the shared ``_score_matvec`` scoring program at a FIXED padded window
  shape, so request batching never recompiles; cross-owner requests ride
  the existing framed P2P via the atom placement map.
- ``refresh`` — incremental per-entity refresh: new events for one entity
  warm-start only that entity's solve through the chunked solver entry
  points and publish atomically; the refreshed coefficients are BITWISE
  the offline warm-start solve of the same bucket
  (knob ``PHOTON_SERVE_REFRESH_EVERY``).
- ``loadgen`` — a Zipf open-loop load generator recording p50/p99
  latency, hot-set hit rate and micro-window occupancy into telemetry
  (``cli/serve``; rendered by ``report summarize``/``report fleet``).
"""

from photon_ml_tpu.serve.loadgen import (  # noqa: F401
    open_loop_arrivals,
    run_serve_trace,
    zipf_entity_trace,
)
from photon_ml_tpu.serve.refresh import refresh_entity  # noqa: F401
from photon_ml_tpu.serve.router import (  # noqa: F401
    MicroWindowServer,
    ScoreRequest,
)
from photon_ml_tpu.serve.store import HotModelStore  # noqa: F401
