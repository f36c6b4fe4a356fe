"""Host seconds inside the program, by the program's own spans.

The program names its host steps (``photon_ml_tpu/obs/spans.py``:
``span("descent/launch")``): each span is an annotation on the profiler's
clock, so a traced slice shows it on the host plane beside the device
operations, and a wall-clock timer ``span.<name>`` in the program's
always-on registry, with ``span_top.<name>`` for the calls that were the
outermost open span of their thread and ``span_self.<name>`` for what a
span with spans inside it spent under none of them. The compile pipeline's
steps land beside them (``jax.trace_s``, ``jax.lower_s``,
``jax.cache_load_s``; ``obs/sink.py``), booked only under an open span.

Two things are read here, for the per-layer readers:

- SET-UP seconds: the registry as it stood when the traced slice began
  (``spans.session_baseline()``: the program copies its timers when an
  entry point first runs under a profiler session). Everything before the
  slice is set-up; nothing after it (the window, the check, which may run
  the program again) is in it. ``setup_seconds`` gives one timer,
  ``setup_sum`` a family.
- the WINDOW's idle device seconds by the program span open on the host:
  the trace the harness left in ``harness.TRACE_DIR``, re-read with
  ``trace_reduce``'s own functions, and ``reduce_trace``'s arithmetic with
  the program's names in place of the harness's: every gap between two
  operations of the first device inside ``bench.slice`` is labelled with the
  innermost program span open at its midpoint, or ``OUTSIDE`` where none is.

The span names are the program's, taken from its module and its registry:
nothing is listed here. A reader returns None, and the harness leaves the
metric out, where the program has no such module (a parent commit under
this benchmark) or saw no profiler session.

Once per process the whole table goes to standard error: set-up by timer
(calls, seconds), then per program span of the slice its calls, host
seconds and the idle device seconds under it.
"""

from __future__ import annotations

import functools
import importlib
import sys

from benchmark import harness, stages
from benchmark.trace_reduce import _clip, _collect, _union

OUTSIDE = "(no program span)"


def program_spans():
    """``photon_ml_tpu.obs.spans`` where the program has always-on spans
    with names, else None."""
    try:
        module = importlib.import_module("photon_ml_tpu.obs.spans")
    except ImportError:
        return None
    wanted = ("TOP_LEVEL", "TIMER", "TOP_TIMER", "SELF_TIMER", "session_baseline")
    return module if all(hasattr(module, a) for a in wanted) else None


def _log(line: str) -> None:
    print(f"[benchmark host spans] {line}", file=sys.stderr, flush=True)


# -- set-up --------------------------------------------------------------------

def _baseline() -> dict | None:
    spans = program_spans()
    base = spans.session_baseline() if spans is not None else None
    if base is not None:
        _log_setup(spans, id(base))
    return base


def setup_seconds(timer: str) -> float | None:
    """Seconds the registry timer ``timer`` held when the slice began (0.0
    for a timer that had not moved); None without a baseline."""
    base = _baseline()
    if base is None:
        return None
    return float(base.get(timer, {}).get("seconds", 0.0))


def setup_sum(prefix: str) -> float | None:
    """The same, summed over every timer whose name starts with ``prefix``."""
    base = _baseline()
    if base is None:
        return None
    return float(sum(t["seconds"] for k, t in base.items() if k.startswith(prefix)))


def setup_span(name: str) -> float | None:
    """Set-up seconds of every call of the program span ``name`` (a
    constant of ``obs/spans.py``, by its attribute name)."""
    spans = program_spans()
    if spans is None or not hasattr(spans, name):
        return None
    return setup_seconds(spans.TIMER + getattr(spans, name))


def setup_program() -> float | None:
    """Set-up seconds inside the program: its outermost spans, each second
    once."""
    spans = program_spans()
    return None if spans is None else setup_sum(spans.TOP_TIMER)


def setup_unnamed() -> float | None:
    """Of those, the seconds under a span with children and under none of
    them: ``setup_program()`` less this is the leaves' seconds."""
    spans = program_spans()
    return None if spans is None else setup_sum(spans.SELF_TIMER)


def setup_compile_steps(*names: str) -> float | None:
    """Set-up seconds of the compile pipeline's steps ``names`` (timer
    constants of ``obs/sink.py``, by attribute name), booked under the
    program's spans only."""
    if program_spans() is None:
        return None
    sink = importlib.import_module("photon_ml_tpu.obs.sink")
    if not all(hasattr(sink, n) for n in names):
        return None
    parts = [setup_seconds(getattr(sink, n)) for n in names]
    return None if None in parts else sum(parts)


@functools.lru_cache(maxsize=1)
def _log_setup(spans, _baseline_id: int) -> None:
    base = spans.session_baseline()
    top = sum(t["seconds"] for k, t in base.items() if k.startswith(spans.TOP_TIMER))
    own = sum(t["seconds"] for k, t in base.items() if k.startswith(spans.SELF_TIMER))
    _log(f"set-up, the registry when the slice began: {top:.6f} s inside the "
         f"program's entry points, {own:.6f} s of it under a span with "
         "children and under none of them; timer | calls | seconds")
    for prefix in (spans.TOP_TIMER, spans.TIMER, spans.SELF_TIMER, "jax."):
        rows = sorted(
            ((k, t) for k, t in base.items() if k.startswith(prefix)),
            key=lambda kt: -kt[1]["seconds"],
        )
        for k, t in rows:
            _log(f"{k} | {t['calls']} | {t['seconds']:.6f}")


# -- the window ----------------------------------------------------------------

@functools.lru_cache(maxsize=2)
def _window(path: str, names: frozenset) -> dict:
    """Per program span of the slice ``[calls, host seconds, idle device
    seconds under it, idle seconds it overlaps]``, and under ``OUTSIDE`` the
    idle seconds with no program span open; ``"window"`` and ``"idle"`` hold
    the slice's and the idle total. "Under it" is ``reduce_trace``'s
    arithmetic, a whole gap to the span open at its midpoint, and what the
    metrics read; "overlaps" cuts every gap at the spans' edges and gives
    each piece to the innermost span open over it, which says more where
    one gap runs from a launch's collection to the next launch's dispatch.
    Both columns add up to the idle total."""
    import warnings

    from jax.profiler import ProfileData

    with warnings.catch_warnings():  # the stats iterator's own deprecation
        warnings.simplefilter("ignore", DeprecationWarning)
        ops, _, host = _collect(ProfileData.from_file(path))
    bounds = [(s, e) for s, e, name in host if name == harness.SLICE_SPAN]
    if bounds:
        lo, hi = bounds[0]
    else:  # as reduce_trace: the device events' own extent
        lo = min(s for evs in ops.values() for s, *_ in evs)
        hi = max(e for evs in ops.values() for _, e, *_ in evs)
    mine = [(s, e, name) for s, e, name in host if name in names and e > lo and s < hi]
    table: dict = {OUTSIDE: [0, 0.0, 0.0, 0.0]}
    for s, e, name in mine:
        row = table.setdefault(name, [0, 0.0, 0.0, 0.0])
        row[0] += 1
        row[1] += min(e, hi) - max(s, lo)

    def innermost(at: float, among) -> str:
        open_ = [(e - s, name) for s, e, name in among if s <= at < e]
        return min(open_)[1] if open_ else OUTSIDE

    first = ops[min(ops)]
    merged = _clip(_union([(s, e) for s, e, *_ in first]), lo, hi)
    edges = [lo] + [t for ab in merged for t in ab] + [hi]
    idle = 0.0
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        over = [(s, e, name) for s, e, name in mine if e > a and s < b]
        table[innermost(0.5 * (a + b), over)][2] += b - a
        cuts = sorted({a, b} | {t for s, e, _ in over for t in (s, e) if a < t < b})
        for c0, c1 in zip(cuts, cuts[1:]):
            table[innermost(0.5 * (c0 + c1), over)][3] += c1 - c0
        idle += b - a
    _log(f"slice {hi - lo:.6f} s, first device idle {idle:.6f} s; program span | "
         "calls | host seconds | idle device seconds under it (a gap to its "
         "midpoint's span) | idle seconds it overlaps")
    for name, (calls, secs, gap, cut) in sorted(
        table.items(), key=lambda kv: -kv[1][3]
    ):
        _log(f"{name} | {calls} | {secs:.6f} | {gap:.6f} | {cut:.6f}")
    return {"window": hi - lo, "idle": idle, "spans": table}


def window() -> dict | None:
    """The slice of the trace the harness left, by program span (see
    ``_window``); None where the program has no spans or there is no trace."""
    spans = program_spans()
    path = stages.trace_path()
    if spans is None or path is None:
        return None
    from photon_ml_tpu.obs.metrics import REGISTRY

    names = frozenset(
        k[len(spans.TIMER):] for k in REGISTRY.timer_snapshot(spans.TIMER)
    )
    return _window(path, names)


def idle_per_work(obs, choose) -> float | None:
    """Idle seconds of the first device per unit of work, summed over the
    labels that ``choose(label, spans)`` picks: a program span's name, or
    ``OUTSIDE``."""
    work = obs.counters.get("work")
    table = window()
    if not work or table is None:
        return None
    spans = program_spans()
    return sum(
        row[2] for label, row in table["spans"].items() if choose(label, spans)
    ) / work


def is_collect(label: str, spans) -> bool:
    return label == spans.DESCENT_COLLECT


def is_outside(label: str, spans) -> bool:
    return label == OUTSIDE


def is_launch(label: str, spans) -> bool:
    """Every other program span: ``descent/prepare``, ``descent/launch``,
    and ``descent/run`` itself before the first and after the last of its
    steps, so that the three parts add up to the device's idle seconds."""
    return not is_collect(label, spans) and not is_outside(label, spans)
