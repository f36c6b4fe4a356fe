"""Static analysis for the repo's load-bearing disciplines.

``photon-ml-tpu lint`` runs five AST passes over the package (plus the
README knob table) and fails on violations of the
invariants fourteen PRs of review kept re-finding by hand:

1. **knobs** — every ``PHOTON_*`` knob registered
   (``analysis/registry.py``) with strict parse idiom and all mirror
   surfaces wired (sink knob snapshot, devcost fingerprint, README
   table), drift failing in both directions.
2. **jit-keys** — no knob accessor / knob global / env read inside a
   jitted body (the stale-executable class).
3. **concurrency** — no unlocked mutation of module-level containers in
   worker-pool / process-wide-cache modules.
4. **exceptions** — no silent ``except`` swallow in ``parallel/``,
   ``game/streaming.py``, ``game/descent.py``.
5. **telemetry** — emitted event/metric names and the names
   ``obs/report.py``/``obs/export.py`` consume agree, both directions.

Pure stdlib ``ast`` — importing this package never initializes a jax
backend, so the lint leg is cheap enough for every CI run.
"""

from photon_ml_tpu.analysis.core import (  # noqa: F401
    Finding, Project,
)
from photon_ml_tpu.analysis.registry import KNOBS, Knob  # noqa: F401
from photon_ml_tpu.analysis.runner import (  # noqa: F401
    PASSES, discover_root, lint,
)
