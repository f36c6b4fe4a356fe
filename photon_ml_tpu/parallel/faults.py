"""Deterministic fault injection for the framed-P2P exchange mesh.

The reference inherits fault tolerance from Spark — task retry and
lineage recovery are exercised daily by real cluster flakiness. Our
JAX/TPU fleet has no such substrate, and real network faults are neither
reproducible nor CI-friendly, so this module makes them DETERMINISTIC: a
seedless fault plan (knob ``PHOTON_FAULT_PLAN``) names exactly which
frame-set of which exchange on which link gets dropped, corrupted,
delayed, or torn down — and the link layer's retry/backoff, the CRC
corruption detection, the heartbeat-to-timeout path and the peer-loss
recovery machinery can each be driven through their full state machines
by host-side tests and the chaos harness (``scripts/chaos_quick.sh``)
with zero real flakiness.

Plan grammar (JSON — a list of fault specs, or ``@/path/to/plan.json``):

    [
      {"op": "drop",    "link": [0, 1], "seq": 2, "tag": "offsets"},
      {"op": "corrupt", "link": [1, 0], "seq": 1},
      {"op": "delay",   "link": [0, 1], "seq": 3, "delay_s": 0.2},
      {"op": "close",   "link": [0, 1], "seq": 4},
      {"op": "kill",    "link": [1, 0], "seq": 2, "exit_code": 137}
    ]

- ``op``: ``drop`` (the frame set is never sent), ``corrupt`` (payload
  bytes are flipped before send — detected by the CRC trailer when
  ``PHOTON_P2P_CRC`` negotiated, by size/row validation otherwise),
  ``delay`` (``delay_s`` sleep before send), ``close`` (the link socket
  is closed instead of sending — the peer sees EOF), ``kill`` (the
  process exits hard at the send boundary — the peer-loss drill),
  ``rejoin`` (the process exits hard AND re-execs itself ``delay_s``
  seconds later as a rejoin boot — the elastic-rejoin drill; needs
  ``PHOTON_REJOIN_CMD``, a JSON argv list naming the command to
  relaunch, because a ``python -c`` worker's own command string is not
  recoverable from ``sys.argv``. The child gets ``PHOTON_REJOIN_BOOT``
  = the dying process's index and an EMPTY fault plan — a rejoined
  process must not re-run the plan that killed it).
- ``link``: ``[src, dst]`` ORIGINAL process indices. Send-side faults
  fire on the ``src`` process; every spec is matched on the side that
  performs the send (the injection boundary is the framed send path,
  where one process can deterministically perturb the wire).
- ``seq``: the per-link frame-set ordinal (the SAME submission-order
  counter the PR-9 telemetry correlation ids use — the k-th frame set
  ever sent on that link), so a plan entry names one exact frame set.
- ``tag`` (optional): additionally require the exchange tag to match
  (e.g. ``offsets``, ``scores``, ``ingest/<cid>``). Omitted = any tag.

Every spec fires AT MOST ONCE (consumed on match), so a retried
exchange's resend goes through clean — exactly the transient-fault
contract the retry layer is tested against. The plan is parsed once per
distinct env value and the no-plan fast path is one ``is None`` check,
so production exchanges pay nothing.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field

VALID_OPS = ("drop", "corrupt", "delay", "close", "kill", "rejoin")


@dataclass
class FaultSpec:
    op: str
    src: int
    dst: int
    seq: int
    tag: str | None = None
    delay_s: float = 0.0
    exit_code: int = 137
    fired: bool = False

    def matches(self, src: int, dst: int, seq: int, tag: str) -> bool:
        if self.fired:
            return False
        if (self.src, self.dst, self.seq) != (src, dst, seq):
            return False
        return self.tag is None or self.tag == tag


@dataclass
class FaultPlan:
    specs: list[FaultSpec] = field(default_factory=list)

    def pop_send_fault(
        self, src: int, dst: int, seq: int, tag: str
    ) -> FaultSpec | None:
        """The (at most one) unfired spec for this frame set, consumed.
        First match in plan order wins — a plan listing two faults for
        one frame set fires them on successive attempts, which is how a
        plan expresses 'fail twice, then succeed'."""
        for s in self.specs:
            if s.matches(src, dst, seq, tag):
                s.fired = True
                return s
        return None

    @property
    def remaining(self) -> int:
        return sum(1 for s in self.specs if not s.fired)


def parse_plan(text: str) -> FaultPlan:
    """Strict parse — a typo'd plan must fail the run loudly, not
    silently chaos-test nothing."""
    if text.startswith("@"):
        with open(text[1:]) as f:
            text = f.read()
    doc = json.loads(text)
    if not isinstance(doc, list):
        raise ValueError(
            f"PHOTON_FAULT_PLAN must be a JSON list of fault specs, got "
            f"{type(doc).__name__}"
        )
    specs: list[FaultSpec] = []
    for i, d in enumerate(doc):
        if not isinstance(d, dict):
            raise ValueError(f"fault spec {i} is not an object: {d!r}")
        unknown = set(d) - {"op", "link", "seq", "tag", "delay_s", "exit_code"}
        if unknown:
            raise ValueError(
                f"fault spec {i}: unknown keys {sorted(unknown)}"
            )
        op = d.get("op")
        if op not in VALID_OPS:
            raise ValueError(
                f"fault spec {i}: op {op!r} not in {VALID_OPS}"
            )
        link = d.get("link")
        if (
            not isinstance(link, (list, tuple)) or len(link) != 2
            or not all(isinstance(x, int) and x >= 0 for x in link)
        ):
            raise ValueError(
                f"fault spec {i}: link must be [src, dst] process "
                f"indices, got {link!r}"
            )
        seq = d.get("seq")
        if not isinstance(seq, int) or seq < 1:
            raise ValueError(
                f"fault spec {i}: seq must be a 1-based frame-set "
                f"ordinal, got {seq!r}"
            )
        if op in ("delay", "rejoin") and not d.get("delay_s"):
            raise ValueError(f"fault spec {i}: {op} requires delay_s > 0")
        specs.append(
            FaultSpec(
                op=op, src=int(link[0]), dst=int(link[1]), seq=seq,
                tag=d.get("tag"), delay_s=float(d.get("delay_s", 0.0)),
                exit_code=int(d.get("exit_code", 137)),
            )
        )
    return FaultPlan(specs=specs)


# parsed-plan cache keyed on the raw env value: call-time knob reads (the
# knob idiom) without re-parsing per frame; fired-state lives in
# the cached object, so one process's plan is consumed monotonically
_PLAN_CACHE: dict[str, FaultPlan] = {}


def active_plan() -> FaultPlan | None:
    """The process's fault plan, or None (the production fast path)."""
    env = os.environ.get("PHOTON_FAULT_PLAN")
    if not env:
        return None
    plan = _PLAN_CACHE.get(env)
    if plan is None:
        plan = _PLAN_CACHE[env] = parse_plan(env)
    return plan


def reset() -> None:
    """Forget parsed plans (tests re-arm consumed specs this way)."""
    _PLAN_CACHE.clear()


def _corrupt(buf: bytes) -> bytes:
    """Flip one byte mid-payload — undetectable by length framing,
    guaranteed caught by the CRC trailer."""
    if not buf:
        return buf
    i = len(buf) // 2
    return buf[:i] + bytes([buf[i] ^ 0xFF]) + buf[i + 1:]


def apply_send_fault(
    spec: FaultSpec, frames: list[bytes], sock
) -> tuple[list[bytes] | None, bool]:
    """Apply ``spec`` at the framed send boundary. Returns ``(frames,
    corrupt_wire)``: the frame payloads to send (None = the whole frame
    set is dropped) and whether the link layer should corrupt the FIRST
    frame's bytes on the wire — after any CRC trailer is computed, so
    the corruption models a wire/buffer fault the trailer detects (a
    pre-CRC flip would be faithfully checksummed and sail through,
    which tests nothing). ``close``/``kill`` act on the socket/process
    directly."""
    _emit(spec)
    if spec.op == "drop":
        return None, False
    if spec.op == "delay":
        time.sleep(spec.delay_s)
        return frames, False
    if spec.op == "corrupt":
        return frames, True
    if spec.op == "close":
        try:
            sock.close()
        # lint: waive(except-swallow) the close IS the injected fault; a double-close error is the drill succeeding
        except OSError:
            pass
        # the next sendall on the closed socket raises
        return frames, False
    if spec.op == "kill":
        # flush telemetry? no — a killed process is a killed process;
        # the drill is precisely that its shard ends mid-run and its
        # peers must cope. os._exit skips atexit/finally by design.
        os._exit(spec.exit_code)
    if spec.op == "rejoin":
        _spawn_rejoin_child(spec)
        os._exit(spec.exit_code)
    raise AssertionError(f"unhandled fault op {spec.op!r}")


def _spawn_rejoin_child(spec: FaultSpec) -> None:
    """Launch the delayed re-exec for a ``rejoin`` spec, then let the
    caller hard-exit. The child is a detached ``sh`` that sleeps
    ``delay_s`` and execs the command from ``PHOTON_REJOIN_CMD`` (JSON
    argv) with ``PHOTON_REJOIN_BOOT`` = this process's original index
    and the fault plan CLEARED. stdout/stderr are inherited, so a
    harness reading the dying worker's pipe also captures the
    rejoiner's output — no extra plumbing."""
    import subprocess

    raw = os.environ.get("PHOTON_REJOIN_CMD")
    if not raw:
        raise RuntimeError(
            "fault op 'rejoin' needs PHOTON_REJOIN_CMD (JSON argv list "
            "of the command to relaunch)"
        )
    cmd = json.loads(raw)
    if not isinstance(cmd, list) or not all(isinstance(c, str) for c in cmd):
        raise RuntimeError(
            f"PHOTON_REJOIN_CMD must be a JSON list of strings, got {raw!r}"
        )
    env = dict(os.environ)
    env["PHOTON_REJOIN_BOOT"] = str(spec.src)
    env.pop("PHOTON_FAULT_PLAN", None)
    # sh -c 'sleep N; exec "$0" "$@"' <argv...>: $0/$@ carry the command
    # verbatim (no quoting pitfalls), and the exec replaces the shell so
    # the rejoiner is a direct child of init once this process dies
    subprocess.Popen(
        [
            "/bin/sh", "-c",
            f'sleep {float(spec.delay_s)}; exec "$0" "$@"', *cmd,
        ],
        env=env, start_new_session=True,
    )


def _emit(spec: FaultSpec) -> None:
    """A ``fault_injected`` record in the run's telemetry shard — the
    chaos harness asserts the fault actually fired (except ``kill``,
    whose shard necessarily truncates)."""
    try:
        from photon_ml_tpu.obs.spans import emit_event

        emit_event(
            "fault_injected", op=spec.op, src=spec.src, dst=spec.dst,
            seq=spec.seq, tag=spec.tag,
        )
    # lint: waive(except-swallow) telemetry guard: the fault record must never take down the drill it observes
    except Exception:
        pass


# -- synthetic straggler (the re-planner's fault drill) ----------------------


def straggler_spec() -> tuple[int, float] | None:
    """``PHOTON_RE_STRAGGLER`` = ``"<process>:<delay_s>"`` — the
    deterministic straggler injection for the telemetry-driven
    re-planner (``PHOTON_RE_REPLAN_IMBALANCE``): the named process
    sleeps ``delay_s`` at the start of every streamed random-effect
    bucket-solve visit, so its MEASURED solve wall genuinely inflates
    (the re-plan trigger reads real telemetry, not a faked gauge) while
    the math — and therefore the model, bitwise — is untouched. Strict
    parse, like every fault knob."""
    env = os.environ.get("PHOTON_RE_STRAGGLER")
    if not env:
        return None
    proc, sep, delay = env.partition(":")
    if not sep:
        raise ValueError(
            f"PHOTON_RE_STRAGGLER must be '<process>:<delay_s>', "
            f"got {env!r}"
        )
    return int(proc), float(delay)


def maybe_straggle() -> float:
    """Apply the straggler injection on the named process; returns the
    seconds slept (0.0 on every other process / with the knob unset —
    the production fast path is one env read)."""
    spec = straggler_spec()
    if spec is None:
        return 0.0
    proc, delay = spec
    if delay <= 0.0:
        return 0.0
    import jax

    if jax.process_index() != proc:
        return 0.0
    time.sleep(delay)
    try:
        from photon_ml_tpu.obs.spans import emit_event

        emit_event(
            "fault_injected", op="straggler", src=proc, dst=proc,
            seq=0, tag="re_solve", delay_s=delay,
        )
    # lint: waive(except-swallow) telemetry guard: the straggler record must never take down the visit it delays
    except Exception:
        pass
    return delay
