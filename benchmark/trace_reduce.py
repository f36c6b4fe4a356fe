"""From a profiler trace to numbers: the one reduction every
trace-sourced metric uses.

``reduce_trace`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
and returns a ``Reduced``: the traced window (the harness's own
``bench.slice`` span), per device the seconds in which an operation ran
(the union of the device-op intervals inside the window), per operation on
the first device its count and time, the programs launched there, and the
idle gaps of the first device, each labelled with the harness span that
was open on the host at the time.

Where the device operations are found:

- on a TPU, in the planes ``/device:TPU:<n>``: line ``XLA Ops`` holds one
  event per executed HLO operation, named by its whole HLO text
  (``%fusion.8 = f32[256,256]{...} fusion(%p0, ...)``). An operation is
  known here by the part before `` = ``, and patterns search that name and
  the text without its ``%operand`` references, so ``all-reduce`` finds the
  collectives and not the fusions that consume one. A ``while`` spans its
  body's events, so self time is what remains of an event outside its
  children; line ``XLA Modules`` holds one event per
  program launch (``jit_f(<fingerprint>)``). The device's clock runs a
  millisecond or two off the host's in the traces seen so far: nothing
  against a window of seconds;
- on the CPU backend (the rehearsal path and the recorded test trace), in
  the host plane's events that carry an ``hlo_op`` stat; a program launch
  is a distinct ``(hlo_module, run_id)`` there.

An operation matches a pattern if the regular expression is found in its
name, its HLO text or any of its text stats.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = (
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
)
_OPERAND = re.compile(r"%[\w.\-]+")


@dataclass
class Op:
    name: str
    text: str  # name and text stats, what patterns search
    count: int = 0
    total_s: float = 0.0  # summed durations, children included
    self_s: float = 0.0  # summed durations outside nested operations


@dataclass
class Reduced:
    window_s: float
    busy_by_device: list[float]
    ops: dict[str, Op] = field(default_factory=dict)  # first device
    programs: dict[str, list] = field(default_factory=dict)  # name -> [count, s]
    idle_by_span: dict[str, float] = field(default_factory=dict)
    longest_gap_s: float = 0.0

    @property
    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the devices used."""
        return sum(self.busy_by_device) / max(len(self.busy_by_device), 1)

    def op_seconds(self, pattern: str) -> float:
        """Summed time on the first device of the operations matching
        ``pattern``: self time, so that a matching ``while`` and its
        matching body are not counted twice."""
        rx = re.compile(pattern)
        return sum(op.self_s for op in self.ops.values() if rx.search(op.text))

    def op_count(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(op.count for op in self.ops.values() if rx.search(op.text))

    def program_launches(self) -> int:
        return sum(count for count, _ in self.programs.values())

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.values(), key=lambda o: -o.self_s)[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[op.name, op.self_s] for op in ops],
            "idle_gaps": [[name, s] for name, s in gaps],
        }

    def summary(self) -> dict:
        return {
            "window_s": self.window_s, "busy_by_device": self.busy_by_device,
            "ops": len(self.ops), "program_launches": self.program_launches(),
            "programs": {
                k: v for k, v in sorted(
                    self.programs.items(), key=lambda kv: -kv[1][1]
                )[:8]
            },
            "longest_gap_s": self.longest_gap_s, **self.breakdown(),
        }


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            if hi > merged[-1][1]:
                merged[-1] = (merged[-1][0], hi)
        else:
            merged.append((lo, hi))
    return merged


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def _short(name: str) -> str:
    """``%fusion.8 = f32[...] fusion(...)`` -> ``fusion.8``;
    ``jit_f(123)`` -> ``jit_f``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", head)


def _event_text(event) -> tuple[str, dict]:
    stats = {k: v for k, v in event.stats}
    text = " ".join(
        [event.name] + [v for v in stats.values() if isinstance(v, str)]
    )
    return text, stats


def _collect(profile):
    """Per device the op events ``(start, end, name, text)``, the program
    events of the first device, and every host event."""
    ops: dict[int, list] = {}
    programs: list[tuple[str, float, float]] = []  # name, start, end
    host: list[tuple[float, float, str]] = []
    cpu_runs: dict[tuple, list[float]] = {}
    planes = list(profile.planes)
    on_tpu = any(_TPU_PLANE.match(plane.name) for plane in planes)
    for plane in planes:
        tpu = _TPU_PLANE.match(plane.name)
        for line in plane.lines:
            for ev in line.events:
                start, end = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
                if tpu:
                    dev = int(tpu.group(1))
                    if line.name == "XLA Ops":
                        name = _short(ev.name)
                        text = name + " " + _OPERAND.sub("", ev.name)
                        ops.setdefault(dev, []).append((start, end, name, text))
                    elif line.name == "XLA Modules" and dev == 0:
                        programs.append((_short(ev.name), start, end))
                    continue
                text, stats = _event_text(ev)
                if "hlo_op" in stats and not on_tpu:  # the CPU backend's ops
                    dev = int(stats.get("device_ordinal", 0))
                    ops.setdefault(dev, []).append((start, end, ev.name, text))
                    if dev == 0:
                        run = (stats.get("hlo_module", "?"), stats.get("run_id"))
                        span = cpu_runs.setdefault(run, [start, end])
                        span[0], span[1] = min(span[0], start), max(span[1], end)
                elif ev.duration_ns > 0:
                    host.append((start, end, ev.name))
    for (module, _), (lo, hi) in cpu_runs.items():
        programs.append((module, lo, hi))
    return ops, programs, host


def _self_times(events: list) -> list[float]:
    """Each event's duration outside the events nested in it (events of one
    device line nest properly: a loop spans its body)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    self_s = [events[i][1] - events[i][0] for i in range(len(events))]
    stack: list[int] = []
    for i in order:
        start, end = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        if stack and end <= events[stack[-1]][1]:
            self_s[stack[-1]] -= end - start
        stack.append(i)
    return self_s


def reduce_trace(path: str, *, slice_span: str, devices: int,
                 spans: tuple[str, ...] = ()) -> Reduced:
    """Reduce the trace at ``path``. ``slice_span`` names the host span
    that bounds the window; ``devices`` is how many devices the cell used;
    ``spans`` are the harness's own host spans, by which idle gaps are
    labelled (innermost first)."""
    import warnings

    from jax.profiler import ProfileData

    with warnings.catch_warnings():  # the stats iterator's own deprecation
        warnings.simplefilter("ignore", DeprecationWarning)
        ops, programs, host = _collect(ProfileData.from_file(path))
    if not ops:
        raise RuntimeError(f"no device operation in the trace at {path}")
    bounds = [(s, e) for s, e, name in host if name == slice_span]
    if bounds:
        lo, hi = bounds[0]
    else:  # no span on the host plane: the device events' own extent
        lo = min(s for evs in ops.values() for s, *_ in evs)
        hi = max(e for evs in ops.values() for _, e, *_ in evs)
    busy = []
    for dev in sorted(ops)[:devices]:
        merged = _clip(_union([(s, e) for s, e, *_ in ops[dev]]), lo, hi)
        busy.append(sum(b - a for a, b in merged))
    out = Reduced(window_s=hi - lo, busy_by_device=busy)

    first = [ev for ev in ops[min(ops)] if ev[1] > lo and ev[0] < hi]
    for (start, end, name, text), own in zip(first, _self_times(first)):
        op = out.ops.setdefault(name, Op(name=name, text=text))
        op.count += 1
        op.total_s += end - start
        op.self_s += own
    for name, start, end in programs:
        if lo <= start < hi:  # launched inside the window
            entry = out.programs.setdefault(name, [0, 0.0])
            entry[0] += 1
            entry[1] += end - start

    # idle gaps of the first device, by the harness span open at the time
    merged = _clip(_union([(s, e) for s, e, *_ in first]), lo, hi)
    edges = [lo] + [t for ab in merged for t in ab] + [hi]
    mine = [(s, e, name) for s, e, name in host if name in spans]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        open_ = [(e - s, name) for s, e, name in mine if s <= mid < e]
        label = min(open_)[1] if open_ else "(no span)"
        out.idle_by_span[label] = out.idle_by_span.get(label, 0.0) + (b - a)
        out.longest_gap_s = max(out.longest_gap_s, b - a)
    return out
