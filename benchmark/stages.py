"""Device seconds by stage of the program, from the trace the harness left.

The program names its stages inside its compiled programs
(``photon_ml_tpu/obs/stages.py``, ``jax.named_scope``): every operation
staged inside ``with stage("re.solve")`` carries the name as one
``/``-separated segment of its ``op_name``, and a TPU trace shows that path
as the ``tf_op`` stat of the operation's EVENT METADATA
(``jit(fused)/coord.per_user/jit(run)/visit.re/jit(_bucket_step)/re.solve/
while/body/dot_general:``). ``jax.profiler.ProfileData`` shows neither the
metadata's stats nor the ``metadata_id`` that joins an event to them, so
``trace_reduce`` cannot see a stage. This module reads the few messages it
needs from the ``.xplane.pb`` itself (protobuf wire format, nothing
imported) and does the arithmetic with ``trace_reduce``'s own functions:
the first ``/device:TPU:<n>`` plane's ``XLA Ops`` line inside the host
plane's ``bench.slice`` span, each event clipped to the span, self time
(a ``while`` less its body). Operations are keyed by PROGRAM and name:
``fusion.3`` of ``jit_fused`` is not ``fusion.3`` of ``jit_slice_all``.

A stage name has a dot and no bracket (``re.solve``, ``coord.per_user``),
which no segment JAX itself writes has (``jit(run)``, ``while``, ``body``,
``statics[1][2].X``), so the table below needs no list of names. An
operation belongs to a stage if the stage is a whole segment of its path;
stages nest, and a metric family picks segments that do not overlap, plus
UNSTAGED: the self time of operations in no segment of the family (no
``tf_op`` at all, or one outside every stage). A family's parts add up to
the device's busy seconds; ``part`` checks that and logs it.

A reader returns None, and the harness leaves the metric out, where the
program has no stages: no ``photon_ml_tpu/obs/stages.py`` (a parent
commit under this benchmark), or a TPU trace in which no operation carries
a stage (executables from a compile cache that is older than the names).
The CPU backend's trace (``benchmark/tests``' rehearsal) names operations
but not their paths: there everything is unstaged and is reported so, to
drive the readers end to end; ``run.py`` never runs there.

Once per process the slice's table goes to standard error: stage path by
coordinate, seconds, share of busy, executions, distinct operations and,
from the metadata's ``bytes_accessed`` of the operations that nest nothing,
GB/s; then the longest unstaged operations by name.
"""

from __future__ import annotations

import functools
import glob
import importlib.util
import os
import re
import struct
import sys
from dataclasses import dataclass

from benchmark import harness
from benchmark.trace_reduce import _TPU_PLANE, _clip, _self_times, _short, _union

# the families the per-layer metrics read (names of obs/stages.py)
DESCENT = ("visit.fixed", "re.offsets", "re.solve", "re.score")
FIT = ("glm.objective", "lbfgs.")  # a trailing dot: every stage so prefixed
UNSTAGED = "(unstaged)"

_STAGE = re.compile(r"^[A-Za-z0-9_\-]+(\.[A-Za-z0-9_\-]+)+$")
_COORD = "coord."


# -- the wire format -----------------------------------------------------------

def _fields(buf: bytes, pos: int, end: int):
    """(field number, value) of one message: an int for a varint or a
    fixed-width field, ``(start, end)`` into ``buf`` for a length-delimited
    one."""
    while pos < end:
        key = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        kind = key & 7
        if kind == 0:
            value = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                value |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
        elif kind == 2:
            size = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                size |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            value = (pos, pos + size)
            pos += size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value = int.from_bytes(buf[pos:pos + width], "little")
            pos += width
        else:
            raise ValueError(f"wire type {kind} at byte {pos}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf: bytes, span, stat_names: dict) -> tuple[str, object]:
    """XStat: ``metadata_id`` = 1, then one of ``double`` = 2, ``uint64`` =
    3, ``int64`` = 4, ``str`` = 5, ``bytes`` = 6, ``ref`` = 7 (a stat
    metadata's name stands for the string)."""
    name, value = "", None
    for no, v in _fields(buf, *span):
        if no == 1:
            name = stat_names.get(v, str(v))
        elif no == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif no == 3:
            value = v
        elif no == 4:
            value = _signed(v)
        elif no == 5:
            value = _text(buf, v)
        elif no == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf: bytes, span):
    key, value = 0, None
    for no, v in _fields(buf, *span):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


@dataclass
class Plane:
    name: str
    # line name -> [(metadata_id, start_ns, duration_ns)], as ProfileData
    # gives them: line timestamp plus offset, whole nanoseconds
    lines: dict[str, list[tuple[int, float, float]]]
    event_names: dict[int, str]  # XEventMetadata.name by id
    event_stats: dict[int, dict]  # its stats by name


def _plane_name(buf: bytes, span) -> str:
    return next((_text(buf, v) for no, v in _fields(buf, *span) if no == 2), "")


def _plane(buf: bytes, span, name: str) -> Plane:
    """XPlane: ``name`` = 2, ``lines`` = 3, ``event_metadata`` = 4 and
    ``stat_metadata`` = 5 (maps: key 1, value 2)."""
    parts = list(_fields(buf, *span))
    stat_names = {}
    for no, v in parts:
        if no == 5:
            key, value = _map_entry(buf, v)
            stat_names[key] = next(
                (_text(buf, x) for n, x in _fields(buf, *value) if n == 2), ""
            )
    plane = Plane(name, {}, {}, {})
    for no, v in parts:
        if no == 4:  # XEventMetadata: id 1, name 2, stats 5
            key, value = _map_entry(buf, v)
            stats = {}
            for n, x in _fields(buf, *value):
                if n == 2:
                    plane.event_names[key] = _text(buf, x)
                elif n == 5:
                    stat_name, stat_value = _stat(buf, x, stat_names)
                    stats[stat_name] = stat_value
            plane.event_stats[key] = stats
        elif no == 3:  # XLine: name 2, timestamp_ns 3, events 4
            line_name, timestamp_ns, events = "", 0, []
            for n, x in _fields(buf, *v):
                if n == 2:
                    line_name = _text(buf, x)
                elif n == 3:
                    timestamp_ns = _signed(x)
                elif n == 4:
                    events.append(x)
            rows = plane.lines.setdefault(line_name, [])
            for ev in events:  # XEvent: metadata_id 1, offset_ps 2, duration_ps 3
                metadata_id = offset_ps = duration_ps = 0
                for n, x in _fields(buf, *ev):
                    if n == 1:
                        metadata_id = x
                    elif n == 2:
                        offset_ps = x
                    elif n == 3:
                        duration_ps = x
                rows.append((
                    metadata_id, float(offset_ps // 1000 + timestamp_ns),
                    float(duration_ps // 1000),
                ))
    return plane


def read_planes(path: str, choose=lambda names: names) -> list[Plane]:
    """The planes of the XSpace at ``path`` (``planes`` = 1) whose names
    ``choose`` picks from the list of all of them; the others stay
    unparsed."""
    with open(path, "rb") as f:
        buf = f.read()
    spans = [v for no, v in _fields(buf, 0, len(buf)) if no == 1]
    names = [_plane_name(buf, v) for v in spans]
    chosen = set(choose(names))
    return [_plane(buf, v, n) for v, n in zip(spans, names) if n in chosen]


# -- the slice, by operation ---------------------------------------------------

@dataclass
class OpTime:
    """One instruction of one program on the first device, inside the slice."""
    program: str
    name: str
    path: tuple[str, ...]  # the segments of its tf_op, () where it has none
    count: int = 0
    self_s: float = 0.0
    leaf_bytes: float = 0.0  # bytes_accessed of its executions that nest nothing

    def within(self, stages) -> bool:
        return any(
            seg == s or (s.endswith(".") and seg.startswith(s))
            for seg in self.path for s in stages
        )


@dataclass
class Slice:
    window_s: float
    busy_s: float  # union of the first device's operations inside the window
    ops: list[OpTime]

    def seconds(self, within=(), outside=()) -> float:
        """Self seconds of the operations in a stage of ``within`` (any
        operation where it is empty) and in none of ``outside``."""
        return sum(
            op.self_s for op in self.ops
            if (not within or op.within(within)) and not op.within(outside)
        )


def trace_path() -> str | None:
    found = glob.glob(os.path.join(
        harness.TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"
    ))
    return found[0] if found else None


@functools.lru_cache(maxsize=4)
def read_slice(path: str, slice_span: str = harness.SLICE_SPAN) -> Slice | None:
    """The first TPU device's operations inside the slice span; None where
    the trace has no TPU plane."""
    def first_device_and_host(names):
        tpu = sorted(
            (int(m.group(1)), m.group(0)) for m in map(_TPU_PLANE.match, names) if m
        )
        return [name for _, name in tpu[:1]] + [
            name for name in names if name.startswith("/host:")
        ]

    planes = read_planes(path, first_device_and_host)
    device = next((p for p in planes if _TPU_PLANE.match(p.name)), None)
    if device is None:
        return None
    events = [
        (start * 1e-9, (start + duration) * 1e-9, metadata_id)
        for metadata_id, start, duration in device.lines.get("XLA Ops", [])
    ]
    bounds = [
        (start * 1e-9, (start + duration) * 1e-9)
        for p in planes if not _TPU_PLANE.match(p.name)
        for rows in p.lines.values() for metadata_id, start, duration in rows
        if duration > 0 and p.event_names.get(metadata_id) == slice_span
    ]
    if bounds:
        lo, hi = bounds[0]
    else:  # as reduce_trace: the device events' own extent
        lo = min(s for s, _, _ in events)
        hi = max(e for _, e, _ in events)
    inside = [
        (max(s, lo), min(e, hi), metadata_id)
        for s, e, metadata_id in events if e > lo and s < hi
    ]
    busy = sum(b - a for a, b in _clip(_union([ev[:2] for ev in inside]), lo, hi))

    programs = {}  # program_id -> name, from the launches' names
    for metadata_id, _, _ in device.lines.get("XLA Modules", []):
        full = device.event_names.get(metadata_id, "")
        m = re.search(r"\((\d+)\)$", full)
        if m:
            programs[int(m.group(1))] = _short(full)
    ops: dict[tuple, OpTime] = {}
    for (start, end, metadata_id), own in zip(inside, _self_times(inside)):
        stats = device.event_stats.get(metadata_id, {})
        program = programs.get(stats.get("program_id"), str(stats.get("program_id", "?")))
        name = _short(device.event_names.get(metadata_id, "?"))
        op = ops.get((program, name))
        if op is None:
            tf_op = str(stats.get("tf_op") or "")
            path = tuple(s for s in tf_op.rsplit(":", 1)[0].split("/") if s)
            op = ops[(program, name)] = OpTime(program, name, path)
        op.count += 1
        op.self_s += own
        if own >= (end - start) * (1.0 - 1e-12):
            op.leaf_bytes += float(stats.get("bytes_accessed") or 0)
    return Slice(window_s=hi - lo, busy_s=busy, ops=list(ops.values()))


# -- the table -----------------------------------------------------------------

def _log(line: str) -> None:
    print(f"[benchmark stages] {line}", file=sys.stderr, flush=True)


def log_table(sl: Slice, work: float | None, top: int = 12) -> None:
    rows: dict[tuple, list] = {}
    for op in sl.ops:
        named = list(dict.fromkeys(s for s in op.path if _STAGE.match(s)))
        coord = next((s for s in named if s.startswith(_COORD)), "-")
        stages = ">".join(s for s in named if not s.startswith(_COORD))
        row = rows.setdefault((coord, stages or UNSTAGED), [0.0, 0, 0, 0.0, 0.0])
        row[0] += op.self_s
        row[1] += op.count
        row[2] += 1
        if op.leaf_bytes:
            row[3] += op.leaf_bytes
            row[4] += op.self_s
    per = f", {work:g} units of work" if work else ""
    _log(f"slice {sl.window_s:.6f} s, device busy {sl.busy_s:.6f} s{per}; "
         "coordinate | stages | seconds | % of busy | executions | operations | GB/s")
    for (coord, stages), (s, n, k, b, bs) in sorted(
        rows.items(), key=lambda kv: -kv[1][0]
    ):
        gbs = f"{b / bs / 1e9:.1f}" if bs > 0 else "-"
        _log(f"{coord} | {stages} | {s:.6f} | {100 * s / sl.busy_s:.2f} | "
             f"{n} | {k} | {gbs}")
    by_program: dict[str, float] = {}
    for op in sl.ops:
        by_program[op.program] = by_program.get(op.program, 0.0) + op.self_s
    _log("by program: " + ", ".join(
        f"{name} {s:.6f}" for name, s in sorted(by_program.items(), key=lambda kv: -kv[1])
    ))
    loose = sorted(
        (op for op in sl.ops if not any(_STAGE.match(s) for s in op.path)),
        key=lambda op: -op.self_s,
    )[:top]
    for op in loose:
        _log(f"unstaged: {op.program} {op.name} {op.self_s:.6f} s x{op.count} "
             f"tf_op={'/'.join(op.path) or '-'}")


# -- what the readers call -----------------------------------------------------

def _program_has_stages() -> bool:
    try:
        return importlib.util.find_spec("photon_ml_tpu.obs.stages") is not None
    except ImportError:
        return False


@functools.lru_cache(maxsize=1)
def _observed(path: str | None, cpu_ops: tuple, work: float) -> Slice | None:
    """The slice the readers of one traced run share; its table is logged
    once."""
    sl = read_slice(path) if path else None
    if sl is None:  # the CPU backend: operations without paths
        sl = Slice(
            window_s=0.0, busy_s=sum(s for _, s in cpu_ops),
            ops=[OpTime("?", name, (), 1, s) for name, s in cpu_ops],
        )
    elif not any(_STAGE.match(s) for op in sl.ops for s in op.path):
        _log("no operation of this trace carries a stage: executables from "
             "a compile cache older than obs/stages.py? nothing reported")
        return None
    log_table(sl, work)
    return sl


@functools.lru_cache(maxsize=8)
def _family(path, cpu_ops: tuple, work: float, stages: tuple) -> dict | None:
    sl = _observed(path, cpu_ops, work)
    if sl is None:
        return None
    parts = {
        s: sl.seconds(within=(s,), outside=stages[:i]) / work
        for i, s in enumerate(stages)
    }
    parts[UNSTAGED] = sl.seconds(outside=stages) / work
    total, busy = sum(parts.values()), sl.busy_s / work
    _log(f"family {stages}: {parts} add up to {total:.9f} s per unit of work; "
         f"device busy {busy:.9f} s per unit (off by "
         f"{100 * abs(total - busy) / busy if busy else 0.0:.4f}%)")
    return parts


def part(obs, stages: tuple[str, ...], name: str) -> float | None:
    """Seconds per unit of work of one part of a family: a stage of
    ``stages`` less the stages before it, or ``UNSTAGED``. None where the
    program has no stages or the slice did no work."""
    work = obs.counters.get("work")
    if not work or not _program_has_stages():
        return None
    cpu_ops = tuple(sorted((name_, op.self_s) for name_, op in obs.trace.ops.items()))
    parts = _family(trace_path(), cpu_ops, float(work), tuple(stages))
    return None if parts is None else parts[name]

