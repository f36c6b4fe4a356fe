"""Multi-host runtime scaffolding.

Reference parity: the reference scales out on a Spark cluster — a driver
plus executors on many hosts, with the cluster manager handling membership
and the shuffle service moving data (SURVEY.md §2.6 Spark-replacement
table). The TPU-native replacement is ``jax.distributed``: every host runs
the SAME program, ``jax.distributed.initialize`` wires the processes into
one runtime, ``jax.devices()`` becomes the GLOBAL device list, and a mesh
built over it spans the whole slice — XLA then routes collectives over
ICI within a host/pod and DCN across pods. No driver, no shuffle: each
host reads its own slice of the input (``host_shard_of_paths``) and
assembles its rows into a globally-sharded array
(``global_batch_from_host_shards``).

Usage (same command on every host, e.g. under GKE/xmanager):

    python -m photon_ml_tpu.cli.train ... --multihost

with the coordinator address/process count/process id taken from the
standard env vars (``JAX_COORDINATOR_ADDRESS``, ``JAX_NUM_PROCESSES``,
``JAX_PROCESS_ID``) or auto-detected on TPU pods (GCE metadata).
"""

from __future__ import annotations

import os
import time
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def initialize_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> dict:
    """Join this process into the multi-host runtime.

    Arguments default to the standard env vars / TPU-pod auto-detection
    (``jax.distributed.initialize`` semantics). Returns a summary dict
    (process index/count, local/global device counts) for logging. Safe to
    call on a single host only when explicit arguments or env vars are set;
    plain single-host runs should simply not call this.
    """
    # resolve the standard env vars ourselves — jax.distributed auto-detects
    # only inside known cluster environments (TPU pods, SLURM, …)
    if coordinator_address is None:
        coordinator_address = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])
    # PHOTON_COORD_MAX_MISSING_HEARTBEATS (strict int parse, default =
    # jax's own 100 s timeout): how many 10 s heartbeats the coordination
    # service / client tolerate missing before declaring a task dead and
    # FATALing every member — passed on as ``heartbeat_timeout_seconds``
    # (10 s x the value). An elastic fleet (PHOTON_DESCENT_DEGRADE /
    # PHOTON_REJOIN) raises it so the repo's own roll-call tier — not
    # the jax coordination service, which cannot degrade in place — is
    # what decides who is dead.
    hb = os.environ.get("PHOTON_COORD_MAX_MISSING_HEARTBEATS")
    # strict parse OUTSIDE the init-error rewrap: a typo'd knob must name
    # itself, not masquerade as a cluster configuration problem
    heartbeat = (
        {"heartbeat_timeout_seconds": 10 * int(hb)}
        if hb is not None and hb != "" else {}
    )
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            **heartbeat,
        )
    except (ValueError, RuntimeError) as e:
        raise RuntimeError(
            "multihost initialization failed — on non-auto-detected "
            "clusters set JAX_COORDINATOR_ADDRESS, JAX_NUM_PROCESSES and "
            "JAX_PROCESS_ID (or pass them explicitly); on a single host, "
            f"drop --multihost. Underlying error: {e}"
        ) from e
    return runtime_summary()


def runtime_summary() -> dict:
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }


def host_shard_of_paths(paths: Sequence[str]) -> list[str]:
    """The input files THIS host reads: a round-robin slice of the sorted
    path list by process index (the reference's executor partition
    assignment, without a shuffle service). Every path must be visible to
    every host (shared filesystem / object store), but each is read once
    globally."""
    ordered = sorted(paths)
    return ordered[jax.process_index() :: jax.process_count()]


def global_batch_from_host_shards(local_arrays, mesh: Mesh, axis_name: str = "data"):
    """Assemble per-host row blocks into ONE globally row-sharded pytree.

    Each process passes its own ``local_arrays`` (a pytree of host numpy
    arrays with identical structure and per-host row counts that sum to the
    global batch); ``jax.make_array_from_process_local_data`` builds global
    arrays whose addressable shards hold this host's rows — no host ever
    materializes the global batch (SURVEY.md §7: the 1B-row path).
    """
    sharding = NamedSharding(mesh, P(axis_name))

    def to_global(a):
        a = np.asarray(a)
        return jax.make_array_from_process_local_data(sharding, a)

    return jax.tree.map(to_global, local_arrays)


def shard_batch_multihost(local_batch, mesh: Mesh, axis_name: str = "data"):
    """Multi-host twin of ``parallel.distributed.shard_batch``: every host
    contributes ITS OWN rows (from its slice of the input files) and the
    result is one globally row-sharded ``Batch`` — no host ever holds the
    global data.

    Hosts may have unequal row counts; each pads with zero-weight rows
    (inert in the objective) to the global per-host maximum, rounded up so
    the global row count divides the mesh's data axis.
    """
    from jax.experimental import multihost_utils

    from photon_ml_tpu.ops.batch import pad_batch

    n_local = local_batch.num_rows
    counts = multihost_utils.process_allgather(np.asarray([n_local]))
    per_host = int(np.max(counts))
    devs_per_host = max(len(jax.local_devices()), 1)
    per_host = -(-per_host // devs_per_host) * devs_per_host
    local = pad_batch(local_batch, per_host)
    return global_batch_from_host_shards(
        jax.tree.map(np.asarray, local), mesh, axis_name
    )


def is_output_process() -> bool:
    """True on the single process that writes shared outputs (models,
    metrics, checkpoints). All hosts COMPUTE; exactly one host WRITES —
    concurrent writers to shared storage interleave and corrupt files.
    In a degraded group the lowest-ranked SURVIVOR writes (the original
    writer may be the lost peer)."""
    return effective_process_index() == 0


# per-call monotonic barrier suffix: every process calls sync_processes
# at the same program points in the same order, so the counters agree —
# and two overlapping barriers carrying the SAME caller tag (possible
# once the pipelined exchange schedule defers work past a barrier site)
# can no longer alias each other inside the runtime's key-matched
# barrier bookkeeping.
_BARRIER_SEQ = [0]


def sync_processes(tag: str = "photon-ml-barrier") -> None:
    """Barrier across all processes (e.g. before reading files another
    process wrote). No-op on a single process. The wire tag is
    ``{tag}#{n}`` with ``n`` a per-process monotonic call counter
    (identical across processes by the matched-call-order requirement
    every collective already has), so repeated barriers under one caller
    tag are distinct barrier keys. In a degraded group the barrier
    rides the framed-P2P survivor mesh (same tag discipline) — the jax
    barrier would wait on the dead peer forever."""
    if effective_process_count() <= 1:
        return
    _BARRIER_SEQ[0] += 1
    if _DEGRADED is not None:
        _p2p_allgather_obj(f"{tag}#{_BARRIER_SEQ[0]}", tag="barrier")
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(f"{tag}#{_BARRIER_SEQ[0]}")


def broadcast_from_host0(pytree):
    """Every process receives process 0's value of ``pytree`` (host numpy
    leaves; identity on a single process). The pytree STRUCTURE must be
    identical on every process — only leaf values may differ. Used to make
    checkpoint-resume decisions (and restored state) consistent when hosts
    do not share an output filesystem. In a degraded group "host 0" is
    the lowest-ranked SURVIVOR and the broadcast rides the framed-P2P
    survivor mesh."""
    if effective_process_count() <= 1:
        return pytree
    if _DEGRADED is not None:
        rank = effective_process_index()
        views = _p2p_allgather_obj(
            pytree if rank == 0 else None, tag="broadcast0"
        )
        return jax.tree.map(np.asarray, views[0])
    from jax.experimental import multihost_utils

    out = multihost_utils.broadcast_one_to_all(pytree)
    return jax.tree.map(np.asarray, out)


def allgather_row_chunks(arrays, chunk_rows: int, pad_values=None):
    """Chunk-wise all-to-all of per-host row blocks (the TPU-native stand-in
    for the reference's Spark shuffle, done on HOSTS over DCN).

    ``arrays`` is a dict of same-leading-dim host numpy arrays (this host's
    rows). Yields one round at a time: a dict of ``(P, chunk_rows, ...)``
    stacked arrays holding EVERY process's chunk — the receiver filters the
    rows it owns and frees the round before the next, so peak memory is
    O(P · chunk_rows), never O(global rows). Hosts with fewer rows pad
    trailing rounds (``pad_values[k]``, default 0 — pick a sentinel the
    receiver can filter, e.g. -1 entity ids). Every process yields the SAME
    number of rounds (a collective requirement).
    """
    pad_values = dict(pad_values or {})
    keys = list(arrays)
    n_loc = len(arrays[keys[0]]) if keys else 0
    counts = allgather_host(np.asarray([n_loc])).reshape(-1)
    rounds = int(-(-int(counts.max()) // chunk_rows)) if counts.max() else 0
    for r in range(rounds):
        lo = r * chunk_rows
        hi = min(lo + chunk_rows, n_loc)
        chunk = {}
        for k in keys:
            a = np.asarray(arrays[k])
            part = a[lo:hi] if lo < n_loc else a[:0]
            pad = chunk_rows - len(part)
            if pad:
                fill = np.full(
                    (pad,) + a.shape[1:], pad_values.get(k, 0), a.dtype
                )
                part = np.concatenate([part, fill])
            chunk[k] = part
        if _DEGRADED is not None:
            views = _p2p_allgather_obj(chunk, tag="row_chunks")
            yield {
                k: np.stack([v[k] for v in views]) for k in keys
            }
            continue
        from jax.experimental import multihost_utils

        gathered = multihost_utils.process_allgather(chunk)
        yield {k: np.asarray(v) for k, v in gathered.items()}


# host-collective payload wire formats: a 1-byte kind prefix selects
# how the rest decodes. PICKLE is the original format (arbitrary host
# objects); NDARRAY is the fast path for array-bearing payloads — the
# container skeleton (dicts/lists/tuples with array leaves replaced by
# position markers) plus per-array (dtype, shape) specs pickle small,
# and the array bytes ride RAW after them, so the send side never
# pickles (or copies) a row payload and the recv side reconstructs with
# one ``np.frombuffer`` per array. Values round-trip byte-identically
# (asserted in tests/test_re_combine.py); only the wire encoding
# differs, and both ends of a mesh always run the same build.
_PAYLOAD_PICKLE = 0
_PAYLOAD_NDARRAY = 1


class _NdRef:
    """Skeleton placeholder for the i-th raw array of an NDARRAY-format
    payload (module-level so the pickled skeleton resolves it)."""

    __slots__ = ("i",)

    def __init__(self, i: int):
        self.i = i

    def __reduce__(self):
        return (_NdRef, (self.i,))


def _encode_host_payload(obj) -> tuple[list, int]:
    """``(wire_parts, total_bytes)`` for one host-collective payload.
    ``wire_parts`` is a list of buffers (bytes / byte-cast memoryviews)
    the sender streams in order — array payloads are zero-copy views of
    the (contiguous) source arrays. Only plain ndarrays of simple
    dtypes take the raw path; object/structured dtypes and ndarray
    subclasses stay pickled (in the skeleton, or — when no raw-able
    array exists at all — as a wholesale PICKLE-format payload)."""
    import pickle
    import struct

    arrays: list[np.ndarray] = []
    shapes: list[tuple] = []

    def strip(x):
        # raw fast path ONLY for plain ndarrays of simple dtypes:
        # subclasses (MaskedArray carries a mask) and structured dtypes
        # (dtype.str is lossy — '|V12' drops the fields) must keep the
        # pickle round-trip the skeleton gives them
        if (
            type(x) is np.ndarray
            and not x.dtype.hasobject
            and x.dtype.names is None
        ):
            # record the ORIGINAL shape: ascontiguousarray promotes 0-d
            # to 1-d, and the decode reshape must undo that
            arrays.append(np.ascontiguousarray(x))
            shapes.append(x.shape)
            return _NdRef(len(arrays) - 1)
        if isinstance(x, dict):
            return {k: strip(v) for k, v in x.items()}
        if isinstance(x, tuple):
            vals = [strip(v) for v in x]
            # preserve tuple subclasses (namedtuples) — the pickle
            # format round-trips them, so this format must too
            return type(x)(*vals) if hasattr(x, "_fields") else tuple(vals)
        if isinstance(x, list):
            return [strip(v) for v in x]
        return x

    skeleton = strip(obj)
    if not arrays:
        raw = bytes([_PAYLOAD_PICKLE]) + pickle.dumps(obj, protocol=4)
        return [raw], len(raw)
    specs = [
        (a.dtype.str, shape) for a, shape in zip(arrays, shapes)
    ]
    head = pickle.dumps((skeleton, specs), protocol=4)
    parts: list = [
        bytes([_PAYLOAD_NDARRAY]) + struct.pack("!q", len(head)) + head
    ]
    total = len(parts[0])
    for a in arrays:
        if a.size == 0:
            continue  # zero-size arrays have no bytes (and memoryview
            # cannot cast shapes with zeros); the spec alone rebuilds them
        m = memoryview(a).cast("B")
        parts.append(m)
        total += len(m)
    return parts, total


def _decode_host_payload(raw: bytes):
    """Inverse of ``_encode_host_payload`` over the received frame
    bytes. Arrays come back as fresh WRITABLE copies — the contract the
    pickle format always gave callers (several mutate results in
    place), and the one copy here replaces the decode copy pickle paid
    anyway."""
    import pickle
    import struct

    kind = raw[0]
    body = memoryview(raw)[1:]
    if kind == _PAYLOAD_PICKLE:
        return pickle.loads(body)
    if kind != _PAYLOAD_NDARRAY:
        raise RuntimeError(
            f"host collective payload: unknown wire format {kind}"
        )
    head_len = struct.unpack("!q", body[:8])[0]
    skeleton, specs = pickle.loads(body[8:8 + head_len])
    offset = 1 + 8 + head_len
    arrays = []
    for dt, shape in specs:
        dtype = np.dtype(dt)
        count = int(np.prod(shape, dtype=np.int64))
        a = np.frombuffer(
            raw, dtype, count=count, offset=offset
        ).reshape(shape).copy()
        offset += count * dtype.itemsize
        arrays.append(a)

    def restore(x):
        if isinstance(x, _NdRef):
            return arrays[x.i]
        if isinstance(x, dict):
            return {k: restore(v) for k, v in x.items()}
        if isinstance(x, tuple):
            vals = [restore(v) for v in x]
            return type(x)(*vals) if hasattr(x, "_fields") else tuple(vals)
        if isinstance(x, list):
            return [restore(v) for v in x]
        return x

    return restore(skeleton)


def _send_frame_parts(sock, parts: list, total: int, crc: bool,
                      peer: int | None = None, tag: str | None = None,
                      heartbeat: float | None = None,
                      corrupt_wire: bool = False) -> None:
    """``_send_frame`` for a multi-buffer payload: one length prefix
    covering the whole frame, each part streamed without concatenation
    (the array fast path's zero-copy send), and — frame protocol v1 —
    one CRC32 trailer computed incrementally over the parts (identical
    to the single-buffer trailer over their concatenation).

    ``corrupt_wire`` (fault injection only) flips a byte of the FIRST
    part on the wire AFTER the trailer is computed — the same
    post-CRC discipline as ``_send_frame``'s."""
    import struct

    wire = parts
    if corrupt_wire and parts:
        from photon_ml_tpu.parallel import faults

        wire = [faults._corrupt(bytes(parts[0])), *parts[1:]]
    _sendall_hb(sock, struct.pack("!q", total), peer, tag, heartbeat)
    for p in wire:
        _sendall_hb(sock, p, peer, tag, heartbeat)
    if crc:
        import zlib

        c = 0
        for p in parts:
            c = zlib.crc32(p, c)
        _sendall_hb(
            sock, struct.pack("!I", c & 0xFFFFFFFF), peer, tag, heartbeat
        )


def _ring_allgather(
    links: dict, ordered_pids: list[int], rank: int, obj,
    tag: str, heartbeat: float | None, stats: dict | None = None,
) -> list:
    """One framed allgather of a host object over an explicit ring:
    ``ordered_pids[rank]`` is this process, links are keyed by ORIGINAL
    pid. The single implementation behind the degraded-group
    collectives, the roll-call agreement round AND the owner-segment
    combine (hand-rolled copies of threaded socket code WILL drift).
    Array-bearing payloads ride the raw-ndarray wire format (no pickle
    copy/overhead per array). Bumps the per-link frame-set counters
    like every framed user, so submission-order correlation stays
    matched. ``stats`` (optional) receives the byte accounting:
    ``payload_bytes`` (this rank's encoded payload), ``bytes_sent``
    (= payload × (P−1), the rotation schedule's send traffic) and
    ``bytes_recv``. Returns the per-rank list."""
    import struct
    import threading

    from photon_ml_tpu.parallel import faults

    protos = links.get("proto", {})
    parts, total = _encode_host_payload(obj)
    P_ = len(ordered_pids)
    own_pid = ordered_pids[rank]
    plan = faults.active_plan()
    out: dict[int, object] = {rank: obj}
    err: list[BaseException] = []

    def send_all():
        try:
            for r in range(1, P_):
                peer_pid = ordered_pids[(rank + r) % P_]
                seq = _next_link_seq("send", peer_pid)
                wire_parts, corrupt_wire = parts, False
                if plan is not None:
                    # the ring collectives are framed users like the
                    # row exchange — the deterministic fault plan can
                    # name their frame sets too (the in-memory combine
                    # is exactly where the descent-degrade drill kills)
                    spec = plan.pop_send_fault(own_pid, peer_pid, seq, tag)
                    if spec is not None:
                        wire_parts, corrupt_wire = faults.apply_send_fault(
                            spec, parts, links["send"][peer_pid]
                        )
                if wire_parts is None:
                    continue  # the frame set was dropped
                _send_frame_parts(
                    links["send"][peer_pid], wire_parts, total,
                    protos.get(peer_pid, 0) >= _FRAME_PROTO_CRC,
                    peer_pid, tag, heartbeat,
                    corrupt_wire=corrupt_wire,
                )
        except BaseException as e:
            e.peer = getattr(e, "peer", peer_pid)
            err.append(e)

    t = threading.Thread(target=send_all)
    t.start()
    bytes_recv = 0
    for r in range(1, P_):
        src_rank = (rank - r) % P_
        src_pid = ordered_pids[src_rank]
        sock = links["recv"][src_pid]
        _next_link_seq("recv", src_pid)
        try:
            n = struct.unpack(
                "!q", _recv_exact(sock, 8, src_pid, tag, heartbeat)
            )[0]
            raw = _recv_frame_payload(
                sock, n, protos.get(src_pid, 0) >= _FRAME_PROTO_CRC,
                src_pid, tag, heartbeat,
            )
        except BaseException as e:
            # name the silent link: the suspected-loss hardening (and
            # the roll call it triggers) wants a peer to start from
            e.peer = getattr(e, "peer", src_pid)
            raise
        bytes_recv += n
        out[src_rank] = _decode_host_payload(raw)
    t.join()
    if err:
        raise err[0]
    if stats is not None:
        stats.update(
            payload_bytes=total,
            bytes_sent=total * (P_ - 1),
            bytes_recv=bytes_recv,
        )
    return [out[r] for r in range(P_)]


def _p2p_allgather_obj(obj, tag: str = "host_collective",
                       drain: bool = True, stats: dict | None = None) -> list:
    """Allgather one host object over the framed-P2P links of the
    CURRENT group — the degraded world's replacement for
    ``multihost_utils.process_allgather`` (which would hang on the dead
    peer), and the transport behind the owner-segment collectives on a
    HEALTHY mesh too. Returns the per-rank list in ascending effective
    rank; a sync collective drains the async queue first, like every
    other synchronous socket user (``drain=False`` is for the exchange
    WORKER itself, which is the queue — draining there would wait on
    its own future).

    A transient link fault here hardens straight into ``PeerLost``
    (peer ``-1`` when the failing link is unknown) — in a DEGRADED
    group always, and on a healthy mesh whenever the reliable mode is
    armed (``PHOTON_P2P_RETRIES`` > 0): these collectives have no
    completion ACK, so a mid-collective retry could desync peers — but
    the failure is symmetric (the teardown kills every peer's links,
    so every peer's collective fails too), and the right recovery is a
    roll call from the caller's handler (the streamed fit, the
    in-place-degrading descent), not an abort. With retries unset the
    healthy-mesh error propagates raw — the pre-elastic behavior
    byte-for-byte."""
    P_ = effective_process_count()
    pid = effective_process_index()
    if P_ <= 1:
        if stats is not None:
            stats.update(payload_bytes=0, bytes_sent=0, bytes_recv=0)
        return [obj]
    if drain:
        drain_async_exchanges()
    try:
        links = _host_links()
        heartbeat = _p2p_heartbeat_s() if _sink_active() else None
        return _ring_allgather(
            links, [_orig_pid(r) for r in range(P_)], pid, obj,
            tag, heartbeat, stats=stats,
        )
    except BaseException as e:
        _reset_host_links()
        if isinstance(e, OSError):
            if _DEGRADED is not None:
                raise PeerLost(
                    getattr(e, "peer", -1),
                    f"degraded-group host collective {tag!r} failed: {e}",
                ) from e
            if _p2p_retries() > 0:
                # suspected loss, not a verdict: the roll call in the
                # caller's recovery path decides whether the peer is
                # really gone (nobody lost -> the handler retries or
                # aborts with the flapped-links message)
                raise PeerLost(
                    getattr(e, "peer", -1),
                    f"host collective {tag!r} failed on the full "
                    f"mesh: {e}",
                ) from e
        raise


def allgather_obj_p2p(obj, tag: str = "host_collective",
                      stats: dict | None = None) -> list:
    """Public synchronous framed-P2P allgather of one host object over
    the current group (healthy or degraded mesh): the owner-segment
    collective the random-effect combine and the diagnostics gather
    ride. Identity on a single process. Must be called collectively (at
    the same program point on every process of the group)."""
    return _p2p_allgather_obj(obj, tag=tag, stats=stats)


def allgather_host(array: np.ndarray) -> np.ndarray:
    """Stack one same-shape host array from every process of the
    CURRENT group: a ``(P_eff, ...)`` array. The jax collective
    normally; the framed-P2P survivor mesh when degraded (the jax
    runtime still counts the dead peer and would hang). Every
    group-shaped reduction in the trainer routes through here so a
    degraded group keeps training."""
    array = np.asarray(array)
    if effective_process_count() <= 1:
        return array[None]
    if _DEGRADED is None:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(array))
    return np.stack(_p2p_allgather_obj(array, tag="allgather_host"))


def _fragment_may_proceed(survivors, group) -> bool:
    """The roll call's split-brain quorum, as a pure predicate (the
    drills in tests/test_faults.py enumerate partitions against it): a
    fragment survives iff it holds a STRICT majority of the group's
    MEMBERS, or exactly half of them including the group's writer (its
    lowest member). Membership counts the CURRENT group only — an
    invited rejoiner in the agreed set is not yet a member, and letting
    it pad a fragment's count would let two fragments (one holding the
    rejoiner, one holding a member majority) both pass. At most one
    fragment of any partition satisfies the predicate."""
    group = sorted(group)
    writer = group[0]
    members = [s for s in survivors if s in group]
    if 2 * len(members) > len(group):
        return True
    return 2 * len(members) == len(group) and writer in members


def roll_call(
    window_s: float | None = None,
    candidates: Sequence[int] | None = None,
    guard_group: Sequence[int] | None = None,
) -> list[int]:
    """Survivor census after a suspected peer loss (the barrier-tagged
    roll call of the recovery tier). Every process that hit
    ``PeerLost`` on the same exchange calls this at the same program
    point (the reliable mode's completion ACK guarantees the failure —
    and therefore the retry exhaustion — is observed by EVERY
    survivor): each rebuilds a mesh over the current group from the
    cached addresses, dropping peers that stay unreachable past the
    window (knob ``PHOTON_ROLLCALL_WINDOW_S``, default 10 s), then
    survivors exchange their reachable sets over the candidate mesh
    and agree on the INTERSECTION — a peer any survivor cannot reach
    is lost for everyone (a half-connected peer cannot participate in
    a full exchange mesh anyway). Returns the sorted surviving
    ORIGINAL process indices (always including this process).

    ``candidates`` widens the census beyond the current group — the
    elastic-rejoin roll call names the current survivors PLUS the
    invited rejoiners, so one roll call can admit a returning process
    and drop a freshly-dead one in the same round. ``guard_group`` is
    the membership set the split-brain quorum is judged against (the
    CURRENT group — a rejoiner is not a member until admitted); it
    defaults to the current group."""
    if window_s is None:
        env = os.environ.get("PHOTON_ROLLCALL_WINDOW_S")
        window_s = float(env) if env else 10.0
    global _HOST_LINKS
    with _LINKS_BUILD_LOCK:
        _reset_host_links()
        pid = _self_pid()
        if guard_group is not None:
            group = sorted(int(p) for p in guard_group)
        elif _DEGRADED is not None:
            group = list(_DEGRADED["survivors"])
        else:
            group = list(range(_world_size()))
        candidates = (
            list(group) if candidates is None
            else sorted(int(p) for p in candidates)
        )
        deadline = time.monotonic() + window_s
        # survivors enter a roll call at times spread across their
        # peers' retry budgets, and each unreachable-candidate removal
        # needs one more agreement pass over the reduced set — so the
        # loop keeps probing past the per-candidate patience window, up
        # to a give-up that extends with every removal, before this
        # process declares itself isolated
        give_up = deadline + window_s
        probe_timeout = max(min(2.0, window_s / 4.0), 0.2)
        survivors = None
        while len(candidates) > 1:
            try:
                links = _build_host_links(candidates, probe_timeout)
            except PeerUnreachable as e:
                if time.monotonic() >= deadline:
                    candidates.remove(e.peer)
                    # the reduced set gets a fresh patience window: its
                    # members may still be probing the removed peer in
                    # their own (later-entered) roll calls
                    deadline = time.monotonic() + window_s
                    give_up = max(give_up, deadline + window_s)
                else:
                    time.sleep(probe_timeout / 2.0)
                continue
            except (OSError, RuntimeError):
                # a build race (two peers mid-rebuild) — retry until
                # the give-up, then give up on the stragglers
                if time.monotonic() >= give_up:
                    break
                time.sleep(probe_timeout / 2.0)
                continue
            _HOST_LINKS = links
            # barrier-tagged agreement round: intersect everyone's view
            try:
                rank = candidates.index(pid)
                views = _ring_allgather(
                    links, candidates, rank, list(candidates),
                    "rollcall", None,
                )
            except OSError:
                # the agreement raced a peer whose OWN build attempt
                # failed after ours succeeded (it tears down the
                # freshly-accepted sockets): rebuild and re-agree
                _reset_host_links()
                if time.monotonic() >= give_up:
                    break
                time.sleep(probe_timeout / 2.0)
                continue
            agreed = set(candidates)
            for v in views:
                agreed &= set(v)
            if pid not in agreed:
                _reset_host_links()
                raise RuntimeError(
                    f"roll call excluded this process ({pid}): survivors "
                    f"agreed on {sorted(agreed)}"
                )
            if agreed != set(candidates):
                # some survivor could not reach a candidate this process
                # could: drop to the intersection and rebuild over it
                # (the excluded peer's own roll call ends with it alone)
                _reset_host_links()
                candidates = sorted(agreed)
                if len(candidates) > 1:
                    _HOST_LINKS = _build_host_links(
                        candidates, _p2p_timeout_s()
                    )
            survivors = sorted(candidates)
            break
        if survivors is None:
            _reset_host_links()
            survivors = [pid]
        # split-brain guard: a roll call has no external arbiter, so a
        # network PARTITION (not a death) would let both halves "agree"
        # on themselves — and both halves' rank-0 would pass
        # is_output_process() and write checkpoints concurrently, the
        # corruption the single-writer rule exists to prevent. A
        # fragment may proceed iff it holds a STRICT majority of the
        # group, or exactly half of it INCLUDING the group's current
        # writer (its lowest member). At most one fragment can satisfy
        # either condition: a strict majority is unique, the writer
        # lives in one fragment, and a strict majority plus an exact
        # half cannot coexist. (The earlier rule let ANY fragment
        # holding the writer proceed — a 1-of-4 writer fragment and the
        # 3-of-4 majority fragment would then BOTH survive a partition,
        # exactly the double-writer scenario the guard exists for; the
        # split-brain drill in tests/test_faults.py pins the fix.)
        if not _fragment_may_proceed(survivors, group):
            _reset_host_links()
            _emit_event(
                "roll_call_abort", survivors=survivors,
                group=list(group),
            )
            raise RuntimeError(
                f"roll call reached only {survivors} of {sorted(group)}: "
                f"a fragment without a strict member majority (or exactly "
                f"half the group including the writer, process "
                f"{min(group)}) must abort rather than risk a split-brain "
                "second writer — restart this process and rejoin"
            )
        _emit_event(
            "roll_call", survivors=survivors,
            lost=[p for p in group if p not in survivors],
        )
        return survivors


def allreduce_sum_host(*arrays: np.ndarray):
    """Sum numpy arrays across ALL processes of the current group
    (returns them unchanged on a single process). Used by the streaming
    objective to combine per-host partial (value, gradient) sums — the
    treeAggregate analog for the out-of-core path."""
    if effective_process_count() <= 1:
        return arrays if len(arrays) > 1 else arrays[0]
    if _DEGRADED is not None:
        gathered = _p2p_allgather_obj(
            tuple(np.asarray(a) for a in arrays), tag="allreduce_sum"
        )
        summed = tuple(
            np.sum(np.stack([g[i] for g in gathered]), axis=0)
            for i in range(len(arrays))
        )
        return summed if len(summed) > 1 else summed[0]
    from jax.experimental import multihost_utils

    stacked = multihost_utils.process_allgather(arrays)  # each: (P, ...)
    summed = tuple(np.sum(np.asarray(a), axis=0) for a in stacked)
    return summed if len(summed) > 1 else summed[0]


# running counters for the LAST exchange_rows call (tests assert the
# per-visit traffic is O(owned rows), not O(P * rows) — VERDICT r3 weak #5)
LAST_EXCHANGE_STATS: dict = {}

_PROC_MESH = None


def _process_mesh():
    """A 1-D mesh with ONE device per process (each process's first local
    device) — the lane for host-to-host all_to_all exchanges."""
    global _PROC_MESH
    if _PROC_MESH is None:
        from jax.sharding import Mesh

        P_ = jax.process_count()
        by_proc: dict[int, object] = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, d)
        _PROC_MESH = Mesh(
            np.array([by_proc[p] for p in range(P_)]), ("proc",)
        )
    return _PROC_MESH


_A2A_JIT = None


def _all_to_all_jit():
    """One cached jitted all_to_all program (jit handles shape/dtype
    polymorphism through its own cache; rebuilding the shard_map per call
    would recompile every exchange). Audited for per-call re-trace:
    the mesh object, the shard_map closure and the jit wrapper are all
    process-lifetime singletons, so repeated exchanges with identical
    (shape, dtype) reuse ONE executable — asserted by the cache-growth
    test in tests/test_multihost.py (``_a2a_cache_size``)."""
    global _A2A_JIT
    if _A2A_JIT is None:
        from jax.sharding import PartitionSpec as P

        _A2A_JIT = jax.jit(
            jax.shard_map(
                lambda x: jax.lax.all_to_all(
                    x, "proc", split_axis=0, concat_axis=0, tiled=True
                ),
                mesh=_process_mesh(),
                in_specs=P("proc"),
                out_specs=P("proc"),
            )
        )
    return _A2A_JIT


def _a2a_cache_size() -> int:
    """Number of compiled variants behind the cached all_to_all jit —
    the executable-reuse tripwire: coordinate descent re-enters the
    exchange with identical shapes every visit, so this must stay FLAT
    across repeated same-shape calls (growth = a re-trace regression
    that would recompile the exchange every visit)."""
    if _A2A_JIT is None:
        return 0
    try:
        return int(_A2A_JIT._cache_size())
    except AttributeError:  # very old jax: no public cache introspection
        return 0


def exchange_rows(arrays, dest: np.ndarray, tag: str = ""):
    """Deliver row ``i`` of every array to process ``dest[i]`` — the
    point-to-point shuffle the reference does with a Spark exchange.

    Unlike ``allgather_row_chunks`` (every row to EVERY host: O(P·n)
    traffic), this routes each row only to its destination. Two transports,
    chosen per call from the globally-consistent (P, P) bucket-count
    matrix:

    - **Balanced** (padded allocation ≤ 2× payload): one
      ``lax.all_to_all`` over the process mesh — rides ICI on pods, one
      compiled program re-entered when per-visit counts are stable.
      SPMD collectives require UNIFORM (source, dest) block sizes, so
      every bucket pads to the global max — fine when destinations are
      balanced, structurally O(P×payload) under entity skew (one hot
      entity ⇒ one hot owner ⇒ one huge bucket sets every bucket's pad).
    - **Skewed** (padding would exceed 2× payload): a host-side TCP
      point-to-point exchange (``_host_p2p_exchange``) sending each
      bucket EXACTLY — zero padding under any skew, the direct analog of
      the reference's Netty shuffle riding DCN (SURVEY §2.7). Per-host
      traffic is O(rows sent + rows owned) always.

    Returns a dict of received rows (grouped by source process, sources in
    ascending order — every process receives with the same layout rule, so
    the result is deterministic and transport-independent). Single
    process: identity. All processes must call this collectively with the
    same key set. ``tag`` labels the exchange in telemetry (the per-link
    ``p2p_send``/``p2p_recv`` events of the framed transport carry it);
    it never affects routing or results.
    """
    arrays = {k: np.asarray(v) for k, v in arrays.items()}
    P_ = effective_process_count()
    if P_ <= 1:
        LAST_EXCHANGE_STATS.update(
            bytes_sent=0, rows_sent=len(dest), padded_rows=len(dest),
            transport="local",
        )
        return arrays
    from jax.experimental import multihost_utils as mhu
    from jax.sharding import PartitionSpec as P

    dest = np.asarray(dest, np.int64)
    order = np.argsort(dest, kind="stable")
    counts = np.bincount(dest, minlength=P_).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)])
    # every process learns every (source, destination) bucket size — a
    # (P, P) int matrix, negligible next to the row payload
    counts_matrix = allgather_host(counts).reshape(P_, P_)
    maxc = max(int(counts_matrix.max()), 1)

    # transport decision — identical on every process (counts_matrix is):
    # all_to_all allocates P·maxc slots per process against its
    # counts.sum() real rows; beyond 2× padding, go point-to-point.
    # A degraded group ALWAYS goes point-to-point: the all_to_all
    # program spans the full device mesh, dead peer included.
    total_payload = max(int(counts_matrix.sum()), 1)
    if _DEGRADED is not None or P_ * P_ * maxc > 2 * total_payload:
        # one global socket-use order: never interleave with an in-flight
        # worker-thread exchange mid-frame (no-op when none are pending)
        drain_async_exchanges()
        return _host_p2p_exchange(
            arrays, order, starts, counts_matrix, tag=tag
        )

    from photon_ml_tpu.obs import devcost

    mesh = _process_mesh()
    pid = jax.process_index()
    out: dict[str, np.ndarray] = {}
    bytes_sent = 0
    for key in sorted(arrays):
        a = arrays[key]
        feat = a.shape[1:]
        local = np.zeros((P_, maxc) + feat, a.dtype)
        for p in range(P_):
            rows = order[starts[p]:starts[p + 1]]
            local[p, : len(rows)] = a[rows]
        bytes_sent += local.nbytes
        g = mhu.host_local_array_to_global_array(local, mesh, P("proc"))
        swapped = _all_to_all_jit()(g)
        # analytic cost of the exchange-adjacent executable, captured
        # AFTER the collective ran: the capture's AOT compile happens on
        # the sink-holding process only, and doing it before the call
        # would park every peer mid-collective behind that compile. One
        # capture per fresh (shape, dtype) — the devcost layer dedups.
        devcost.capture("multihost.all_to_all", _all_to_all_jit(), (g,))
        recv = np.asarray(
            mhu.global_array_to_host_local_array(swapped, mesh, P("proc"))
        )  # (P, maxc, *feat): slice s = rows from source s
        out[key] = np.concatenate(
            [recv[s, : counts_matrix[s, pid]] for s in range(P_)]
        )
    LAST_EXCHANGE_STATS.update(
        bytes_sent=bytes_sent,
        rows_sent=int(counts.sum()),
        padded_rows=P_ * maxc * len(arrays),
        transport="all_to_all",
    )
    return out


# lazily-built full TCP mesh between processes for the skewed-exchange
# transport: {"send": {peer: socket}, "recv": {peer: socket}}
_HOST_LINKS: dict | None = None

# per-link frame-set sequence counters for TELEMETRY CORRELATION: the
# framed exchange's submission-order invariant (every process issues the
# same exchange sequence at the same program points) means the k-th
# frame-set SENT on link i→j is exactly the k-th frame-set RECEIVED on
# that link at j — so both ends derive the same correlation id
# ``p2p:<src>><dst>#<k>`` with zero extra bytes on the wire, and
# ``report fleet`` joins each link's send/recv events across shard
# files by that id (one-sided wait = recv-start − send-start).
# Incremented UNCONDITIONALLY (not sink-gated): a process whose sink
# activates mid-sequence must still agree with its peers on k.
_LINK_SEQ: dict = {"send": {}, "recv": {}}


def _next_link_seq(direction: str, peer: int) -> int:
    seqs = _LINK_SEQ[direction]
    seqs[peer] = seqs.get(peer, 0) + 1
    return seqs[peer]


def _sink_active() -> bool:
    """Whether telemetry is on (cheap; the exchange hot path must stay
    byte-identical when it is not)."""
    try:
        from photon_ml_tpu.obs import sink as _sink

        return _sink.is_active()
    except Exception:
        return False


def _emit_event(event: str, **payload) -> None:
    try:
        from photon_ml_tpu.obs.spans import emit_event

        emit_event(event, **payload)
    except Exception:
        pass  # telemetry must never take down the exchange it observes


def _reset_host_links() -> None:
    """Close every cached exchange socket and drop THIS process's mesh so
    its next exchange rebuilds from scratch. Called on ANY
    ``_host_p2p_exchange`` error: after a partial send/receive the
    length-prefix framing on the surviving streams is undefined (a retry
    would read payload bytes as a prefix and silently mis-frame
    everything after), so the only safe local state is no mesh at all.
    The reset is per-process by construction (an error such as a size
    mismatch may be raised on one host only); peers discover it FAIL-FAST
    on their next exchange — their sends/receives against the closed
    sockets error instead of mis-framing — which resets them too, so a
    caller-level collective retry converges to a full mesh rebuild."""
    global _HOST_LINKS
    links, _HOST_LINKS = _HOST_LINKS, None
    # correlation counters restart with the mesh: after a teardown both
    # ends rebuild and resynchronize at frame-set 1 (frames lost to the
    # error surface as UNMATCHED send/recv events in ``report fleet`` —
    # the telemetry-health signal, by design)
    _LINK_SEQ["send"] = {}
    _LINK_SEQ["recv"] = {}
    if not links:
        return
    for side in ("send", "recv"):
        for sock in links.get(side, {}).values():
            try:
                sock.close()
            except OSError:
                pass


def _coordinator_address() -> str:
    """The ``jax.distributed`` coordinator address: the standard env var
    when set, else JAX's own distributed global state (the runtime knows
    its coordinator even when it was wired up by pod auto-detection or
    explicit ``initialize`` arguments — the env var is absent on exactly
    those paths)."""
    target = os.environ.get("JAX_COORDINATOR_ADDRESS", "")
    if target:
        return target
    try:
        from jax._src import distributed as _distributed

        return getattr(_distributed.global_state, "coordinator_address", None) or ""
    except Exception:
        return ""


def _is_loopback(ip: str) -> bool:
    return ip.startswith("127.") or ip in ("0.0.0.0", "localhost", "::1")


def _coordinator_is_loopback(host: str) -> bool:
    """True when the coordinator host is loopback — literally, or through
    DNS/hosts resolution (the single-machine harness may pass the
    machine's own hostname, which stock Debian/Ubuntu maps to
    127.0.1.1)."""
    if not host:
        return False
    if _is_loopback(host):
        return True
    import socket

    try:
        return _is_loopback(socket.gethostbyname(host))
    except OSError:
        return False


def _local_ip() -> str:
    """This host's address as peers should dial it. Override with
    ``PHOTON_EXCHANGE_HOST`` to pin a specific NIC. Otherwise discover the
    OUTBOUND interface by UDP-connecting toward the ``jax.distributed``
    coordinator (env var or the runtime's own global state; no packet is
    sent — the kernel just picks the route) —
    ``gethostbyname(gethostname())`` is NOT used because stock
    Debian/Ubuntu ``/etc/hosts`` maps the hostname to 127.0.1.1, which
    would advertise an undialable loopback to remote peers.

    A discovered LOOPBACK address with ``process_count > 1`` under a
    non-loopback (or unknown) coordinator fails FAST: advertising it would
    make every remote peer dial itself and hang the mesh build until the
    300 s socket timeout. A loopback COORDINATOR means every process lives
    on this machine (a remote process could not have reached it), so
    loopback peers are dialable and the single-machine multi-process test
    harness keeps working."""
    explicit = os.environ.get("PHOTON_EXCHANGE_HOST")
    if explicit:
        return explicit
    import socket

    target = _coordinator_address()
    host = target.rsplit(":", 1)[0] if target else ""

    # any non-loopback discovery returns immediately; one loopback result
    # only means THAT probe routed locally (e.g. the coordinator hostname
    # mapped to 127.0.1.1 via /etc/hosts — the later 8.8.8.8 probe still
    # finds the real NIC), so keep probing and fail fast only once EVERY
    # source has come up loopback
    last = "127.0.0.1"
    for probe in filter(None, [host, "8.8.8.8"]):
        try:
            with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
                s.connect((probe, 53))
                ip = s.getsockname()[0]
        except OSError:
            continue
        if not _is_loopback(ip):
            return ip
        last = ip
    try:
        ip = socket.gethostbyname(socket.gethostname())
        if not _is_loopback(ip):
            return ip
        last = ip
    except OSError:
        pass
    if jax.process_count() > 1 and not _coordinator_is_loopback(host):
        raise RuntimeError(
            f"host exchange address discovery found only loopback {last!r} "
            f"with process_count={jax.process_count()}: remote peers "
            "cannot dial it (the mesh build would hang until the "
            "300 s timeout). Set PHOTON_EXCHANGE_HOST to this host's "
            "reachable address."
        )
    return last


def _p2p_timeout_s() -> float | None:
    """Socket timeout for the host P2P exchange mesh, knob
    ``PHOTON_P2P_TIMEOUT_S`` (seconds; generous default — exchanges move
    real payload over slow DCN links, and a false-positive timeout tears
    the mesh down; ``0`` or negative disables the timeout entirely, the
    usual knob convention, restoring blocking sockets). Applied to EVERY
    socket operation of the mesh — accept, connect, send, recv — so a
    dead or silent peer raises ``socket.timeout`` instead of hanging the
    exchange forever; the error then reaches the existing
    ``_reset_host_links`` teardown and the caller's retry rebuilds the
    mesh."""
    env = os.environ.get("PHOTON_P2P_TIMEOUT_S")
    if env is not None and env != "":
        v = float(env)
        return v if v > 0 else None
    return 300.0


def _configure_link_socket(sock) -> None:
    """Apply the exchange-mesh socket policy: the knob timeout (no socket
    in the mesh may block forever) and TCP_NODELAY (length-prefixed small
    frames must not wait on Nagle)."""
    import socket

    sock.settimeout(_p2p_timeout_s())
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)


def _p2p_retries() -> int:
    """Transient-fault retry budget for the framed host P2P exchange,
    knob ``PHOTON_P2P_RETRIES`` (default 0 = the pre-retry behavior:
    any link error tears the mesh down and raises, bit-for-bit). N > 0
    enables the RELIABLE exchange mode: every framed exchange ends with
    a per-link completion ACK (so one process's failure fails every
    process's exchange — the cross-process precondition for a
    consistent collective retry), and a failed exchange is retried up
    to N times through the ``_reset_host_links`` teardown + cached-
    address rebuild path, with exponential backoff
    (``PHOTON_P2P_BACKOFF_S``) between attempts."""
    env = os.environ.get("PHOTON_P2P_RETRIES")
    if env is not None and env != "":
        return max(int(env), 0)
    return 0


def _p2p_backoff_s() -> float:
    """Base backoff between exchange retry attempts, knob
    ``PHOTON_P2P_BACKOFF_S`` (seconds; attempt k sleeps
    ``base * 2**k``, plus a deterministic per-process jitter fraction
    derived from (process index, attempt) — decorrelated across the
    fleet with no RNG state, the seedless discipline the fault plan
    uses)."""
    env = os.environ.get("PHOTON_P2P_BACKOFF_S")
    if env is not None and env != "":
        return max(float(env), 0.0)
    return 0.5


def _retry_backoff_sleep(attempt: int) -> float:
    base = _p2p_backoff_s()
    if base <= 0.0:
        return 0.0
    # deterministic jitter in [0, 0.5): hash of (pid, attempt) — every
    # process backs off a slightly different amount without any RNG
    pid = _self_pid()
    jitter = ((pid * 2654435761 + attempt * 40503) % 512) / 1024.0
    return base * (2.0 ** attempt) * (1.0 + jitter)


def _p2p_crc_enabled() -> bool:
    """``PHOTON_P2P_CRC`` (default 0): advertise frame-protocol v1 at
    mesh build. A link uses the CRC32 integrity trailer only when BOTH
    ends advertised v1 (the hello's spare high bytes carry the version,
    so a v0 peer still reads its pid unchanged) — corruption then
    surfaces as a detected ``LinkCorruption`` instead of a mis-framed
    length prefix downstream. Off = the PR-10 wire format byte-for-
    byte."""
    env = os.environ.get("PHOTON_P2P_CRC")
    if env is not None and env != "":
        return int(env) != 0
    return False


# frame-protocol versions a process can advertise in the mesh hello:
# 0 = length-prefixed frames (the original wire format), 1 = length
# prefix + payload + CRC32 trailer. The hello int packs
# ``pid | (version << 16)`` — version 0 leaves the hello bytes exactly
# the PR-10 wire bytes.
_FRAME_PROTO_CRC = 1


class LinkCorruption(ConnectionError):
    """A framed-P2P payload failed its CRC32 integrity check — the
    frame ARRIVED (framing intact) but its bytes are wrong. A transient
    fault for the retry layer: the mesh tears down and the exchange
    re-runs."""


class PeerUnreachable(ConnectionError):
    """A mesh (re)build could not reach one specific peer (connect
    refused / timed out / accept never arrived). Transient until the
    retry budget exhausts — then it hardens into ``PeerLost``."""

    def __init__(self, peer: int, message: str):
        super().__init__(message)
        self.peer = peer


class PeerLost(ConnectionError):
    """Retries exhausted against a specific peer: the exchange layer
    has given up on reaching it. Callers with a recovery path (the
    streamed GAME trainer) catch this, confirm the loss with a roll
    call, re-plan placement around the dead peer and resume from the
    last checkpoint; callers without one get a clean abort that names
    the peer instead of a 300 s timeout stack."""

    def __init__(self, peer: int, message: str):
        super().__init__(message)
        self.peer = peer


# -- degraded process group (peer-loss recovery) -----------------------------
#
# After a confirmed peer loss the jax collective runtime is unusable
# (every collective would include — and hang on — the dead process), so
# recovery shrinks the world HOST-SIDE: a degraded group names the
# surviving ORIGINAL process indices, every multihost helper in this
# module routes through the framed-P2P survivor mesh (addresses are
# cached from the first build — no collective needed), and
# ``effective_process_index/count`` replace ``jax.process_index/count``
# for group-shaped decisions. The jax runtime itself stays up (device
# compute is process-local); it is simply never asked to cross
# processes again.

_DEGRADED: dict | None = None

# rejoin identity: a process RE-EXEC'D after a loss (the elastic-rejoin
# half, knob PHOTON_REJOIN) cannot re-enter the original
# ``jax.distributed`` cohort — its fresh runtime reports
# ``process_index() == 0`` / ``process_count() == 1``. ``bootstrap_
# rejoin`` records the process's ORIGINAL identity (pid + world size,
# from the persisted mesh-address cache) here, and every identity read
# in this module goes through ``_self_pid``/``_world_size`` so the
# rejoined process keeps speaking the framed-P2P protocol under its
# original name. None on every normally-initialized process — the
# helpers then read the jax runtime exactly as before.
_REJOIN_IDENTITY: dict | None = None


def rejoin_identity() -> dict | None:
    return _REJOIN_IDENTITY


def _self_pid() -> int:
    """This process's ORIGINAL process index (survives a rejoin
    re-exec, where ``jax.process_index()`` resets to 0)."""
    if _REJOIN_IDENTITY is not None:
        return int(_REJOIN_IDENTITY["pid"])
    return jax.process_index()


def _world_size() -> int:
    """The ORIGINAL fleet size (survives a rejoin re-exec, where
    ``jax.process_count()`` resets to 1)."""
    if _REJOIN_IDENTITY is not None:
        return int(_REJOIN_IDENTITY["world"])
    return jax.process_count()


def original_process_index() -> int:
    """Public twin of ``_self_pid`` for consumers outside this module
    (the telemetry sink's shard index, the rejoin drills)."""
    return _self_pid()


def original_process_count() -> int:
    return _world_size()


def degraded_group() -> dict | None:
    return _DEGRADED


def effective_process_count() -> int:
    if _DEGRADED is not None:
        return len(_DEGRADED["survivors"])
    if _REJOIN_IDENTITY is not None:
        # a rejoiner BEFORE admission: group-shaped code must not
        # mistake it for a healthy single-process world (it must not
        # run collectives at all until the rejoin roll call seats it)
        return _world_size()
    return jax.process_count()


def effective_process_index() -> int:
    if _DEGRADED is not None:
        return _DEGRADED["rank"]
    if _REJOIN_IDENTITY is not None:
        return _self_pid()
    return jax.process_index()


def effective_topology() -> tuple:
    """The EFFECTIVE device topology this process computes under, as a
    hashable cache-key component: ``(backend, local device count,
    effective process count)``. The executable caches (``_tiled_apply``'s
    jit statics, the tile-layout cache's tuned-constants key) carry this
    so a degrade-in-place — which changes the effective group without
    restarting the process — can never re-enter an executable compiled
    for the pre-loss topology by shape coincidence, while a SAME-topology
    re-entry (the cheap-abort restart at survivor count, or plain
    repeated visits) hits every cache it already filled: zero growth,
    zero recompiles. Read at CALL time, the same discipline as every
    tuned constant."""
    return (
        jax.default_backend(),
        len(jax.local_devices()),
        effective_process_count(),
    )


def set_degraded_group(survivors) -> None:
    """Shrink this process's world to ``survivors`` (sorted original
    process indices; must include this process). Tears the socket mesh
    down — the next exchange rebuilds it over the survivor set from the
    cached addresses. An EXPANDED group (elastic rejoin) goes through
    here too: even at full original size the group keeps routing over
    the framed-P2P mesh, because a rejoined process's fresh jax runtime
    is not part of the original collective cohort."""
    global _DEGRADED
    survivors = tuple(sorted(int(s) for s in survivors))
    pid = _self_pid()
    if pid not in survivors:
        raise ValueError(
            f"process {pid} cannot join a degraded group {survivors} "
            "that excludes it"
        )
    _reset_host_links()
    if (
        len(survivors) == _world_size()
        and _DEGRADED is None
        and _REJOIN_IDENTITY is None
    ):
        return  # full group = not degraded
    _DEGRADED = {
        "survivors": survivors,
        "rank": survivors.index(pid),
    }
    from photon_ml_tpu.obs.metrics import REGISTRY

    REGISTRY.gauge_set("fleet.survivors", float(len(survivors)))
    _emit_event(
        "degraded_group", survivors=list(survivors),
        rank=_DEGRADED["rank"],
    )


def _orig_pid(rank: int) -> int:
    """Effective rank -> original process index (identity when the
    group is whole)."""
    if _DEGRADED is not None:
        return _DEGRADED["survivors"][rank]
    return rank


def _p2p_heartbeat_s() -> float | None:
    """Blocked-recv heartbeat cadence, knob ``PHOTON_P2P_HEARTBEAT_S``
    (seconds; ``0`` or negative disables). While a framed-P2P recv is
    blocked on a silent peer, the exchange emits one rate-limited
    ``p2p_heartbeat`` telemetry event per interval — so a stuck link is
    visible (with its peer, tag and blocked seconds) in the run's shard
    file long before the ``PHOTON_P2P_TIMEOUT_S`` abort (default 300 s)
    tears the mesh down."""
    env = os.environ.get("PHOTON_P2P_HEARTBEAT_S")
    if env is not None and env != "":
        v = float(env)
        return v if v > 0 else None
    return 5.0


def _recv_exact(sock, n: int, peer: int | None = None,
                tag: str | None = None,
                heartbeat: float | None = None) -> bytes:
    """``heartbeat=None`` (the default, and always when no sink is
    active — callers snapshot that ONCE per exchange) is the plain
    pre-heartbeat recv, byte-identical to the original hot path."""
    if heartbeat is None:
        chunks = []
        while n:
            part = sock.recv(min(n, 1 << 20))
            if not part:
                raise ConnectionError("exchange peer closed the connection")
            chunks.append(part)
            n -= len(part)
        return b"".join(chunks)
    # heartbeat path: poll readiness so a silent peer surfaces in
    # telemetry every ``heartbeat`` seconds; the knob timeout keeps its
    # exact semantics (max SILENCE, the same contract settimeout gives
    # the plain path — the clock resets whenever bytes arrive).
    # selectors (epoll/poll on Linux), NOT select.select: the exchange
    # mesh plus chunk cache plus JAX can push socket fds past
    # FD_SETSIZE (1024), where select() raises — the instrument must
    # never crash an exchange the plain path would have completed.
    import selectors

    timeout_s = _p2p_timeout_s()
    chunks = []
    silent = 0.0
    with selectors.DefaultSelector() as sel:
        sel.register(sock, selectors.EVENT_READ)
        while n:
            t0 = time.perf_counter()
            ready = sel.select(timeout=heartbeat)
            if not ready:
                silent += time.perf_counter() - t0
                _emit_event(
                    "p2p_heartbeat", peer=peer, tag=tag,
                    blocked_s=silent, bytes_remaining=n,
                    direction="recv",
                )
                if timeout_s is not None and silent >= timeout_s:
                    import socket as _socket

                    raise _socket.timeout(
                        f"exchange recv from process {peer} silent for "
                        f"{silent:.1f}s (PHOTON_P2P_TIMEOUT_S)"
                    )
                continue
            part = sock.recv(min(n, 1 << 20))
            if not part:
                raise ConnectionError(
                    "exchange peer closed the connection"
                )
            silent = 0.0
            chunks.append(part)
            n -= len(part)
    return b"".join(chunks)


def _sendall_hb(sock, data: bytes, peer: int | None = None,
                tag: str | None = None,
                heartbeat: float | None = None) -> None:
    """``sendall`` twin of ``_recv_exact``'s heartbeat mode.
    ``heartbeat=None`` (always, when no sink is active) is
    ``sock.sendall`` verbatim — the original hot path. With a
    heartbeat, a send stalled on a full kernel buffer toward a wedged
    peer emits rate-limited ``p2p_heartbeat`` events with ``direction:
    send`` — previously a blocked SEND was invisible until the timeout
    abort (only blocked recvs heartbeated). Timeout semantics mirror
    the plain path's ``settimeout``: max SILENCE, the clock resets
    whenever bytes move."""
    if heartbeat is None:
        sock.sendall(data)
        return
    import selectors

    timeout_s = _p2p_timeout_s()
    view = memoryview(data)
    silent = 0.0
    with selectors.DefaultSelector() as sel:
        sel.register(sock, selectors.EVENT_WRITE)
        while view:
            t0 = time.perf_counter()
            ready = sel.select(timeout=heartbeat)
            if not ready:
                silent += time.perf_counter() - t0
                _emit_event(
                    "p2p_heartbeat", peer=peer, tag=tag,
                    blocked_s=silent, bytes_remaining=len(view),
                    direction="send",
                )
                if timeout_s is not None and silent >= timeout_s:
                    import socket as _socket

                    raise _socket.timeout(
                        f"exchange send to process {peer} blocked for "
                        f"{silent:.1f}s (PHOTON_P2P_TIMEOUT_S)"
                    )
                continue
            sent = sock.send(view)
            if sent == 0:
                raise ConnectionError(
                    "exchange peer closed the connection"
                )
            silent = 0.0
            view = view[sent:]


def _send_frame(sock, payload: bytes, crc: bool,
                peer: int | None = None, tag: str | None = None,
                heartbeat: float | None = None,
                corrupt_wire: bool = False) -> None:
    """One framed payload: 8-byte length prefix + payload, plus (frame
    protocol v1, negotiated per link at mesh build) a CRC32 trailer of
    the payload. The length prefix never counts the trailer, so every
    row-count validation downstream is protocol-independent.

    ``corrupt_wire`` (fault injection only) flips a payload byte AFTER
    the trailer is computed — modelling a wire/buffer fault, which is
    exactly what the trailer exists to catch. A pre-CRC flip would be
    faithfully checksummed and arrive "valid"."""
    import struct

    wire = payload
    if corrupt_wire:
        from photon_ml_tpu.parallel import faults

        wire = faults._corrupt(payload)
    _sendall_hb(sock, struct.pack("!q", len(payload)), peer, tag, heartbeat)
    _sendall_hb(sock, wire, peer, tag, heartbeat)
    if crc:
        import zlib

        _sendall_hb(
            sock, struct.pack("!I", zlib.crc32(payload)),
            peer, tag, heartbeat,
        )


def _recv_frame_payload(sock, n: int, crc: bool,
                        peer: int | None = None, tag: str | None = None,
                        heartbeat: float | None = None) -> bytes:
    """The payload bytes of a frame whose length prefix was already
    read, verifying the v1 CRC trailer when the link negotiated it. A
    mismatch raises ``LinkCorruption`` — a DETECTED transient for the
    retry layer, where the unchecked protocol would have handed
    corrupt rows to the solver (or mis-framed every later exchange)."""
    raw = _recv_exact(sock, n, peer, tag, heartbeat)
    if crc:
        import struct
        import zlib

        want = struct.unpack(
            "!I", _recv_exact(sock, 4, peer, tag, heartbeat)
        )[0]
        got = zlib.crc32(raw)
        if got != want:
            raise LinkCorruption(
                f"exchange frame from process {peer} tag {tag!r}: "
                f"CRC32 mismatch (got {got:#010x}, trailer {want:#010x})"
            )
    return raw


# completion-ACK magic for the reliable exchange mode: one byte per
# link per exchange, confirming the peer finished its WHOLE exchange —
# without it, one process's failure could leave peers believing the
# exchange succeeded, and a later retry would resend frames into
# streams whose counters no longer agree (silent mis-framing)
_ACK_BYTE = b"\xa5"


# addresses from the FIRST mesh build, cached process-wide: {orig_pid:
# (ip_str, port)}. A REBUILD (retry after teardown, survivor mesh after
# a peer loss) reuses them and re-binds this process's own recorded
# port — no collective, so a rebuild is legal from the exchange worker
# thread and from a degraded group the jax runtime can no longer span.
_HOST_ADDRS: dict[int, tuple[str, int]] | None = None

# serializes mesh builds across threads (the exchange worker may rebuild
# mid-retry while the main thread bootstraps an async exchange)
import threading as _threading

_LINKS_BUILD_LOCK = _threading.RLock()


def _hello_int(pid: int) -> int:
    """The mesh hello: the sender's pid, with the advertised frame-
    protocol version in the spare high bytes. Version 0 (CRC knob off)
    leaves the int — and the wire bytes — exactly the original pid."""
    proto = _FRAME_PROTO_CRC if _p2p_crc_enabled() else 0
    return pid | (proto << 16)


def _decode_hello(raw: int) -> tuple[int, int]:
    return raw & 0xFFFF, raw >> 16


def _close_quietly(sock) -> None:
    try:
        sock.close()
    except OSError:
        pass


def _gather_link_addrs() -> dict[int, tuple[str, int]]:
    """First-build address bootstrap over the jax runtime (collective —
    each process allgathers its (IPv4, port) as five small ints; the
    only collective the mesh ever uses) with this process's listener
    already bound. Cached for every later rebuild."""
    import socket

    from jax.experimental import multihost_utils as mhu

    P_ = jax.process_count()
    assert _HOST_ADDRS is not None  # own entry recorded by caller
    ip = np.frombuffer(
        socket.inet_aton(_HOST_ADDRS[jax.process_index()][0]), np.uint8
    ).astype(np.int64)
    port = _HOST_ADDRS[jax.process_index()][1]
    addrs = np.asarray(
        mhu.process_allgather(np.concatenate([ip, [port]]))
    ).reshape(P_, 5)
    return {
        p: (
            socket.inet_ntoa(addrs[p, :4].astype(np.uint8).tobytes()),
            int(addrs[p, 4]),
        )
        for p in range(P_)
    }


def _build_host_links(peers: list[int], timeout_s, srv=None) -> dict:
    """One full-mesh build over ``peers`` (original pids, this process
    included): every ordered pair gets a dedicated unidirectional TCP
    connection, so concurrent sends and receives never share a stream.
    On ANY partial failure the already-established sockets are closed,
    the listener is closed and the acceptor thread is JOINED before the
    error propagates — a half-built mesh must never leak connected
    sockets or a live acceptor into the next rebuild attempt (they
    would accept/deliver stale hellos there and mis-key the mesh).

    Returns ``{"send": {pid: sock}, "recv": {pid: sock},
    "proto": {pid: negotiated version}}``."""
    import socket
    import struct
    import threading

    global _HOST_ADDRS
    pid = _self_pid()
    others = [p for p in peers if p != pid]
    first_build = _HOST_ADDRS is None
    if srv is None:
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.settimeout(timeout_s)  # accept() must not hang on a dead peer
        # rebuilds bind the RECORDED port (peers dial the cached
        # address); the first build lets the OS pick
        own_port = 0 if first_build else _HOST_ADDRS[pid][1]
        try:
            srv.bind(("0.0.0.0", own_port))
        except OSError:
            srv.close()
            raise
        srv.listen(max(len(peers), 1))
    if first_build:
        _HOST_ADDRS = {pid: (_local_ip(), srv.getsockname()[1])}
        try:
            _HOST_ADDRS = _gather_link_addrs()
        except BaseException:
            _HOST_ADDRS = None
            srv.close()
            raise
        _maybe_persist_mesh_addrs()

    recv_socks: dict[int, socket.socket] = {}
    recv_protos: dict[int, int] = {}
    accept_err: list[BaseException] = []

    def accept_all():
        try:
            for _ in range(len(others)):
                conn, _ = srv.accept()
                _configure_link_socket(conn)
                raw = struct.unpack("!i", _recv_exact(conn, 4))[0]
                src, proto = _decode_hello(raw)
                if src in recv_socks:
                    # a peer re-dialed (its previous build attempt
                    # aborted): the stale socket is dead — replace it
                    _close_quietly(recv_socks[src])
                recv_socks[src] = conn
                recv_protos[src] = proto
        except BaseException as e:
            accept_err.append(e)

    acceptor = threading.Thread(target=accept_all, daemon=True)
    acceptor.start()
    send_socks: dict[int, socket.socket] = {}
    send_protos: dict[int, int] = {}
    try:
        order = sorted(others, key=lambda p: (p - pid) % max(len(peers), 1))
        for peer in order:
            peer_ip, peer_port = _HOST_ADDRS[peer]
            # dial with PATIENCE while our own listener stays up: on a
            # concurrent rebuild both peers race listen-then-dial, and a
            # refused connect only means the peer has not re-listened
            # YET. Abandoning the whole build on first refusal would
            # close our listener too — two rebuilding peers would then
            # livelock, each dialing the other's closed port during the
            # other's backoff sleep. So refusals retry in place until
            # the per-build timeout budget; only then is the peer
            # declared unreachable for this attempt.
            deadline = time.monotonic() + (
                timeout_s if timeout_s is not None else 30.0
            )
            while True:
                try:
                    s = socket.create_connection(
                        (peer_ip, peer_port), timeout=timeout_s
                    )
                    break
                except OSError as e:
                    if time.monotonic() >= deadline:
                        raise PeerUnreachable(
                            peer,
                            f"exchange mesh build: cannot connect to "
                            f"process {peer} at {peer_ip}:{peer_port}: "
                            f"{e}",
                        ) from e
                    time.sleep(0.05)
            _configure_link_socket(s)
            s.sendall(struct.pack("!i", _hello_int(pid)))
            send_socks[peer] = s
        acceptor.join(timeout=timeout_s)
        if acceptor.is_alive() or len(recv_socks) != len(others):
            missing = sorted(set(others) - set(recv_socks))
            err = RuntimeError(
                f"host exchange mesh incomplete: accepted "
                f"{len(recv_socks)} of {len(others)} peers"
                + (f" (missing {missing})" if missing else "")
            )
            if missing:
                # name the lowest missing peer even when several are
                # missing: the retry/roll-call tier only needs ONE
                # suspect to treat the failure as transient-then-
                # PeerLost — a raw RuntimeError here would propagate
                # past the retry loop and crash a survivor that merely
                # raced its peers' own rebuild attempts
                err = PeerUnreachable(missing[0], str(err))
            raise err
    except BaseException:
        # partial-failure cleanup: closing the listener unblocks a
        # still-alive acceptor (accept() raises), so the join below
        # cannot hang; every established socket closes so nothing
        # leaks into the next attempt
        srv.close()
        for s in send_socks.values():
            _close_quietly(s)
        for s in recv_socks.values():
            _close_quietly(s)
        acceptor.join(timeout=timeout_s)
        raise
    srv.close()
    my_proto = _FRAME_PROTO_CRC if _p2p_crc_enabled() else 0
    # per-link negotiation: the CRC trailer rides a link only when BOTH
    # ends advertised it (the send side knows the peer's version from
    # the recv-side hello — the mesh is symmetric, every pair has both
    # links, and each process advertises ONE version to everyone)
    proto = {
        p: min(my_proto, recv_protos.get(p, 0)) for p in others
    }
    return {"send": send_socks, "recv": recv_socks, "proto": proto}


def _host_links() -> dict:
    """The (lazily built) socket mesh for this process's CURRENT group
    — all processes normally, the survivors after a degraded-group
    switch. First build must be called collectively (address
    bootstrap); rebuilds are collective-free (cached addresses)."""
    global _HOST_LINKS
    with _LINKS_BUILD_LOCK:
        if _HOST_LINKS is not None:
            return _HOST_LINKS
        if _DEGRADED is not None:
            peers = list(_DEGRADED["survivors"])
        else:
            peers = list(range(_world_size()))
        _HOST_LINKS = _build_host_links(peers, _p2p_timeout_s())
        return _HOST_LINKS


def _host_p2p_exchange(arrays, order, starts, counts_matrix=None,
                       transport="p2p_host", tag=""):
    """Skew-robust transport for ``exchange_rows``: each (source, dest)
    bucket travels EXACTLY, length-prefixed, over its pair's dedicated TCP
    link — no padding under any skew (an SPMD collective must pad every
    bucket to a uniform size, which costs O(P × payload) when one entity
    dominates). Sends run on a helper thread in rotation order (round r:
    send to pid+r, receive from pid−r) so every process's receiver drains
    concurrently — no cyclic wait. Layout of the result matches the
    all_to_all transport exactly (ascending source, stable within source).

    ANY error tears THIS process's socket mesh down
    (``_reset_host_links``): a partially-drained stream's next bytes are
    payload, not a length prefix, so reusing a survivor would silently
    mis-frame every later exchange. Peers fail fast against the closed
    sockets on their next use and reset themselves, so retries rebuild
    the mesh instead of corrupting data.

    ``PHOTON_P2P_RETRIES`` > 0 makes that retry AUTOMATIC: transient
    link faults (connect refused, recv timeout, peer EOF, CRC
    corruption) are retried here with bounded exponential backoff +
    jitter through the cached-address mesh rebuild — collective-free,
    so the retry is legal from the exchange worker thread too. The
    reliable mode's per-exchange completion ACK guarantees every
    process observes the same exchange outcome, so all peers retry the
    SAME exchange and the rebuilt streams stay frame-matched. When the
    budget exhausts against one unreachable peer, the error hardens
    into ``PeerLost`` — the recovery layer's signal.
    """
    retries = _p2p_retries()
    attempt = 0
    while True:
        try:
            return _host_p2p_exchange_impl(
                arrays, order, starts, counts_matrix, transport, tag
            )
        except BaseException as e:
            # closing the sockets also unblocks a sender thread stuck
            # in sendall against a stalled peer — it errors out + exits
            _reset_host_links()
            transient = isinstance(e, OSError)
            if transient and attempt < retries:
                attempt += 1
                backoff = _retry_backoff_sleep(attempt - 1)
                from photon_ml_tpu.obs.metrics import REGISTRY

                REGISTRY.counter_inc("p2p.retries")
                _emit_event(
                    "p2p_retry", attempt=attempt, max_attempts=retries,
                    tag=tag, error=type(e).__name__,
                    peer=getattr(e, "peer", None), backoff_s=backoff,
                )
                if backoff > 0.0:
                    time.sleep(backoff)
                continue
            if retries and transient:
                from photon_ml_tpu.obs.metrics import REGISTRY

                REGISTRY.counter_inc("p2p.giveups")
                _emit_event(
                    "p2p_giveup", attempts=attempt, tag=tag,
                    error=type(e).__name__,
                    peer=getattr(e, "peer", None),
                )
                if isinstance(e, PeerUnreachable):
                    raise PeerLost(
                        e.peer,
                        f"exchange retries exhausted ({retries}) against "
                        f"unreachable process {e.peer}: {e}",
                    ) from e
            raise


def _host_p2p_exchange_impl(arrays, order, starts, counts_matrix,
                            transport="p2p_host", tag=""):
    """``counts_matrix=None`` is the COLLECTIVE-FREE framing mode (the
    overlapped exchange schedule): each bucket's row count is derived
    from its length prefix instead of a pre-exchanged (P, P) count
    matrix, so the whole exchange is pure sockets — safe to run on the
    exchange worker thread concurrently with main-thread jax
    collectives, whose global ordering a worker-side allgather would
    violate. Frame sizes are validated per key (row-multiple + all keys
    from one source agreeing on the row count)."""
    import struct
    import threading

    from photon_ml_tpu.parallel import faults

    P_ = effective_process_count()
    pid = effective_process_index()
    links = _host_links()
    protos = links.get("proto", {})
    reliable = _p2p_retries() > 0
    plan = faults.active_plan()
    keys = sorted(arrays)
    parts: dict[str, dict[int, np.ndarray]] = {
        k: {pid: np.ascontiguousarray(
            arrays[k][order[starts[pid]:starts[pid + 1]]]
        )}
        for k in keys
    }
    bytes_sent = 0
    send_err: list[BaseException] = []
    # snapshot ONCE per exchange: the env knob and the sink check stay
    # off the per-frame hot path, and a concurrent sink reconfigure
    # cannot flip the recv framing mid-exchange
    telemetry = _sink_active()
    heartbeat = _p2p_heartbeat_s() if telemetry else None

    def send_all():
        nonlocal bytes_sent
        try:
            for r in range(1, P_):
                peer = (pid + r) % P_
                o_pid, o_peer = _orig_pid(pid), _orig_pid(peer)
                sock = links["send"][o_peer]
                crc = protos.get(o_peer, 0) >= _FRAME_PROTO_CRC
                seq = _next_link_seq("send", o_peer)
                t_start = time.time()
                t0 = time.perf_counter()
                rows = order[starts[peer]:starts[peer + 1]]
                bufs = [
                    np.ascontiguousarray(arrays[k][rows]).tobytes()
                    for k in keys
                ]
                corrupt_wire = False
                if plan is not None:
                    spec = plan.pop_send_fault(o_pid, o_peer, seq, tag)
                    if spec is not None:
                        bufs, corrupt_wire = faults.apply_send_fault(
                            spec, bufs, sock
                        )
                peer_bytes = 0
                if bufs is not None:  # None = the frame set was dropped
                    for j, buf in enumerate(bufs):
                        _send_frame(
                            sock, buf, crc, o_peer, tag, heartbeat,
                            corrupt_wire=corrupt_wire and j == 0,
                        )
                        peer_bytes += len(buf)
                bytes_sent += peer_bytes
                if telemetry:
                    # one event per (link, exchange): the frame-set, not
                    # per key — report fleet joins it with the peer's
                    # p2p_recv through the shared correlation id
                    _emit_event(
                        "p2p_send", peer=o_peer,
                        bytes=peer_bytes,
                        rows=int(starts[peer + 1] - starts[peer]),
                        dur_s=time.perf_counter() - t0,
                        t_start=t_start,
                        corr=f"p2p:{o_pid}>{o_peer}#{seq}",
                        tag=tag, transport=transport,
                    )
        except BaseException as e:  # surfaced after join
            send_err.append(e)

    sender = threading.Thread(target=send_all)
    sender.start()
    for r in range(1, P_):
        src = (pid - r) % P_
        o_pid, o_src = _orig_pid(pid), _orig_pid(src)
        sock = links["recv"][o_src]
        crc = protos.get(o_src, 0) >= _FRAME_PROTO_CRC
        seq = _next_link_seq("recv", o_src)
        t_start = time.time()
        t0 = time.perf_counter()
        src_bytes = 0
        src_rows = 0
        n_src: int | None = None  # framed mode: all keys must agree
        for k in keys:
            a = arrays[k]
            row_bytes = a.itemsize * int(
                np.prod(a.shape[1:], dtype=np.int64)
            )
            got = struct.unpack(
                "!q", _recv_exact(sock, 8, o_src, tag, heartbeat)
            )[0]
            if counts_matrix is not None:
                n = int(counts_matrix[src, pid])
                want = n * row_bytes
                if got != want:
                    raise RuntimeError(
                        f"exchange size mismatch from process {o_src} key "
                        f"{k!r}: expected {want} bytes ({n} rows), got {got}"
                    )
            else:
                if row_bytes <= 0 or got % row_bytes:
                    raise RuntimeError(
                        f"exchange frame from process {o_src} key {k!r}: "
                        f"{got} bytes is not a multiple of the "
                        f"{row_bytes}-byte row"
                    )
                n = got // row_bytes
                if n_src is None:
                    n_src = n
                elif n != n_src:
                    raise RuntimeError(
                        f"exchange frames from process {o_src} disagree on "
                        f"row count: key {k!r} carries {n} rows, earlier "
                        f"keys carried {n_src}"
                    )
            raw = _recv_frame_payload(sock, got, crc, o_src, tag, heartbeat)
            src_bytes += got
            src_rows = n
            parts[k][src] = np.frombuffer(raw, a.dtype).reshape(
                (n,) + a.shape[1:]
            ).copy()
        if telemetry:
            _emit_event(
                "p2p_recv", peer=o_src,
                bytes=src_bytes, rows=int(src_rows),
                dur_s=time.perf_counter() - t0,
                t_start=t_start,
                corr=f"p2p:{o_src}>{o_pid}#{seq}",
                tag=tag, transport=transport,
            )
    sender.join()
    if send_err:
        raise send_err[0]
    if reliable:
        # completion-ACK round (reliable mode only — one extra byte per
        # link per exchange, absent from the knob-off wire format): a
        # link's ACK arrives only after its peer finished its WHOLE
        # exchange, so any single failure fails every process's
        # exchange and the collective retry stays frame-matched
        for r in range(1, P_):
            peer = (pid + r) % P_
            _sendall_hb(
                links["send"][_orig_pid(peer)], _ACK_BYTE,
                _orig_pid(peer), tag, heartbeat,
            )
        for r in range(1, P_):
            src = (pid - r) % P_
            o_src = _orig_pid(src)
            got = _recv_exact(
                links["recv"][o_src], 1, o_src, tag, heartbeat
            )
            if got != _ACK_BYTE:
                raise RuntimeError(
                    f"exchange completion ACK from process {o_src} "
                    f"carries {got!r} (stream desync)"
                )
    # this process's send counts: identical to counts_matrix[pid] when a
    # matrix was exchanged, and derivable locally when not (framed mode)
    counts_send = np.diff(starts)
    LAST_EXCHANGE_STATS.update(
        bytes_sent=bytes_sent,
        rows_sent=int(counts_send.sum()),
        # same accounting as the all_to_all branch (allocated row-slots,
        # summed over keys) — here exactly the payload: zero padded slots
        padded_rows=int(counts_send.sum()) * len(arrays),
        transport=transport,
    )
    return {
        k: np.concatenate([parts[k][s] for s in range(P_)]) for k in keys
    }


# -- overlapped (asynchronous) point-to-point exchange ----------------------
#
# The pipelined exchange schedule (PHOTON_RE_SHARD=1): an exchange is
# ISSUED at one program point and JOINED at a later one, with device
# solves / host bookkeeping / jax collectives in between — instead of a
# barrier per coordinate. The exchange body runs on ONE dedicated worker
# thread per process in strict submission order (every process submits
# the same exchange sequence at the same program points, so the socket
# streams stay frame-matched), and it is COLLECTIVE-FREE (framed p2p:
# row counts ride the length prefixes) so a worker-side exchange can
# never interleave a collective against the main thread's.

_EXCHANGE_POOL = None
_EXCHANGE_LOCK = None  # guards the pending list + overlap accounting
_PENDING_EXCHANGES: list = []
_EXCHANGE_TOTALS = {"exchange_s": 0.0, "wait_s": 0.0}


def _exchange_state():
    global _EXCHANGE_POOL, _EXCHANGE_LOCK
    if _EXCHANGE_LOCK is None:
        import threading

        _EXCHANGE_LOCK = threading.Lock()
    if _EXCHANGE_POOL is None:
        from concurrent.futures import ThreadPoolExecutor

        _EXCHANGE_POOL = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="photon-exchange"
        )
    return _EXCHANGE_POOL, _EXCHANGE_LOCK


def _record_overlap(kind: str, seconds: float) -> None:
    """Cumulative exchange/wait seconds + the derived overlap-ratio
    gauge: the fraction of exchange wall the consumer did NOT block on
    (1.0 = fully hidden behind other work, 0.0 = a barrier schedule).
    Mirrored into the PR-4 registry so the ratio rides every telemetry
    snapshot and ``photon-ml-tpu report``."""
    from photon_ml_tpu.obs.metrics import REGISTRY

    _, lock = _exchange_state()
    with lock:
        _EXCHANGE_TOTALS[kind] += seconds
        wall = _EXCHANGE_TOTALS["exchange_s"]
        wait = _EXCHANGE_TOTALS["wait_s"]
    REGISTRY.timer_add(f"re_exchange.{kind}", seconds)
    # zero wall (the single-process identity path) reads as fully
    # overlapped: there was nothing to wait for — and the gauge must
    # exist on every topology the schedule runs on
    ratio = 1.0 if wall <= 0.0 else max(0.0, min(1.0, 1.0 - wait / wall))
    REGISTRY.gauge_set("re_shard.exchange_overlap_ratio", ratio)


class ExchangeHandle:
    """A pending ``exchange_rows_async``. ``result()`` blocks until the
    exchange lands and returns the received-rows dict (the same layout
    contract as ``exchange_rows``); the blocked seconds are recorded as
    ``re_exchange.wait_s`` against the worker's ``re_exchange.exchange_s``
    for the overlap-ratio gauge (and, with a sink active, emitted as an
    ``exchange_wait`` event so the per-process timeline shows where the
    consumer actually blocked)."""

    def __init__(self, future=None, value=None, tag: str = ""):
        self._future = future
        self._value = value
        self._tag = tag

    @property
    def done(self) -> bool:
        return self._future is None or self._future.done()

    def result(self) -> dict:
        if self._future is None:
            return self._value
        import time as _time

        t0 = _time.perf_counter()
        try:
            out = self._future.result()
        finally:
            waited = _time.perf_counter() - t0
            _record_overlap("wait_s", waited)
            if _sink_active():
                _emit_event(
                    "exchange_wait", tag=self._tag, wait_s=waited
                )
            _, lock = _exchange_state()
            with lock:
                _PENDING_EXCHANGES[:] = [
                    e for e in _PENDING_EXCHANGES
                    if e[0] is not self._future
                ]
        self._future = None
        self._value = out
        return out


def drain_async_exchanges() -> None:
    """Wait for every in-flight async exchange (results stay claimable
    through their handles). A SYNCHRONOUS p2p exchange must not touch
    the sockets while the worker is mid-frame, and submission order is
    the cross-process consistency invariant — so the sync path drains
    first, preserving one global socket-use order.

    A worker exception observed here is RECORDED (``exchange_drain_
    error`` event + ``p2p.exchange_drain_errors`` counter) before being
    left for the owner handle to re-raise — previously it was swallowed
    bare, so a failed background exchange whose handle was never polled
    was invisible in ``report fleet``. A failed entry is dropped from
    the pending list on first observation (the handle keeps its own
    future reference, so ``result()`` still re-raises) — otherwise
    every later drain would re-wait and re-report the same failure."""
    _, lock = _exchange_state()
    with lock:
        pending = list(_PENDING_EXCHANGES)
    for entry in pending:
        f, tag = entry
        try:
            exc = f.exception()  # waits; the owner handle re-raises on
            # result() — this is observation, not consumption
        except Exception as e:
            exc = e
        if exc is not None:
            with lock:
                if entry in _PENDING_EXCHANGES:
                    _PENDING_EXCHANGES.remove(entry)
            from photon_ml_tpu.obs.metrics import REGISTRY

            REGISTRY.counter_inc("p2p.exchange_drain_errors")
            _emit_event(
                "exchange_drain_error", tag=tag,
                error=type(exc).__name__,
                peer=getattr(exc, "peer", None),
            )


def reset_async_exchanges() -> None:
    """Forget every pending async-exchange record without waiting.
    Recovery calls this after a peer loss: the failed attempt's handles
    are abandoned wholesale, and leaving their futures in the pending
    list would make every later drain re-wait and re-report them."""
    _, lock = _exchange_state()
    with lock:
        _PENDING_EXCHANGES.clear()


def confirm_peer_loss(err) -> tuple[list[int], list[int], list[int]]:
    """The loss-confirmation preamble every ``PeerLost`` recovery tier
    shares (the streamed fit's checkpoint re-entry, the in-memory
    descent's in-place degrade): count + emit the suspected loss, drop
    the failed attempt's abandoned async exchanges, roll-call the
    CURRENT group and return ``(group, survivors, lost)`` — an empty
    ``lost`` means every peer answered (a link flap, not a death) and
    the caller should retry rather than degrade. One shared helper so
    the tiers cannot drift on what "confirming a loss" means."""
    from photon_ml_tpu.obs.metrics import REGISTRY

    REGISTRY.counter_inc("fleet.peer_lost")
    _emit_event(
        "peer_lost", peer=int(getattr(err, "peer", -1)), error=str(err)
    )
    reset_async_exchanges()
    dg = degraded_group()
    group = (
        list(dg["survivors"]) if dg is not None
        else list(range(original_process_count()))
    )
    survivors = roll_call()
    lost = sorted(set(group) - set(survivors))
    return group, survivors, lost


def exchange_rows_async(
    arrays, dest: np.ndarray, tag: str = ""
) -> ExchangeHandle:
    """Issue ``exchange_rows`` without blocking: returns a handle whose
    ``result()`` yields the identical received-rows layout. Transport is
    ALWAYS the framed host P2P path (collective-free — the worker thread
    must never run a jax collective; padding-free — the schedule exists
    for the skewed configs where all_to_all padding is pathological).
    The socket mesh is built (collectively) on the CALLING thread at
    first use, so the collective stays in program order. Single process:
    completes inline (identity)."""
    arrays = {k: np.asarray(v) for k, v in arrays.items()}
    P_ = effective_process_count()
    if P_ <= 1:
        LAST_EXCHANGE_STATS.update(
            bytes_sent=0, rows_sent=len(dest), padded_rows=len(dest),
            transport="local",
        )
        # inline identity still contributes (zero-wait) overlap samples,
        # so the gauge exists on every topology the schedule runs on
        _record_overlap("exchange_s", 0.0)
        _record_overlap("wait_s", 0.0)
        return ExchangeHandle(value=arrays)
    dest = np.asarray(dest, np.int64)
    order = np.argsort(dest, kind="stable")
    counts = np.bincount(dest, minlength=P_).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)])
    _host_links()  # collective bootstrap happens HERE, in program order
    pool, lock = _exchange_state()

    def run():
        import time as _time

        t0 = _time.perf_counter()
        try:
            return _host_p2p_exchange(
                arrays, order, starts, counts_matrix=None,
                transport="p2p_host_async", tag=tag,
            )
        finally:
            dur = _time.perf_counter() - t0
            _record_overlap("exchange_s", dur)
            if _sink_active():
                _emit_event("exchange", tag=tag, dur_s=dur)

    fut = pool.submit(run)
    with lock:
        _PENDING_EXCHANGES.append((fut, tag))
    return ExchangeHandle(future=fut, tag=tag)


class ObjCollectiveHandle:
    """A pending ``allgather_obj_p2p_async``. ``result()`` blocks until
    the allgather lands and returns the per-rank list. Unlike
    ``ExchangeHandle`` it records nothing into the ``re_exchange.*``
    overlap accounting — owner-segment callers keep their own
    ``re_combine.*`` books (mixing the two would skew the exchange
    overlap gauge the sharded-solve sweeps gate on)."""

    def __init__(self, future=None, value=None, tag: str = ""):
        self._future = future
        self._value = value
        self._tag = tag

    def result(self) -> list:
        if self._future is None:
            return self._value
        try:
            out = self._future.result()
        finally:
            _, lock = _exchange_state()
            with lock:
                _PENDING_EXCHANGES[:] = [
                    e for e in _PENDING_EXCHANGES
                    if e[0] is not self._future
                ]
        self._future = None
        self._value = out
        return out


def allgather_obj_p2p_async(
    obj, tag: str = "host_collective", stats: dict | None = None
) -> ObjCollectiveHandle:
    """Issue ``allgather_obj_p2p`` on the dedicated exchange worker:
    the frames go on the wire while the caller keeps working (the
    owner-segment combine overlaps its diagnostics readback under the
    coefficient-segment send). Same discipline as
    ``exchange_rows_async``: the mesh bootstrap (collective on first
    use) happens on the CALLING thread in program order, the body runs
    on the single worker in strict submission order, and the pending
    entry keeps every synchronous socket user draining behind it.
    ``stats`` is filled by the worker (byte accounting plus
    ``exchange_s``, the worker-side wall) before the handle resolves.
    Single process: completes inline (identity)."""
    P_ = effective_process_count()
    if P_ <= 1:
        if stats is not None:
            stats.update(
                payload_bytes=0, bytes_sent=0, bytes_recv=0,
                exchange_s=0.0,
            )
        return ObjCollectiveHandle(value=[obj], tag=tag)
    _host_links()  # collective bootstrap HERE, in program order
    pool, lock = _exchange_state()

    def run():
        t0 = time.perf_counter()
        try:
            return _p2p_allgather_obj(
                obj, tag=tag, drain=False, stats=stats
            )
        finally:
            if stats is not None:
                stats["exchange_s"] = time.perf_counter() - t0

    fut = pool.submit(run)
    with lock:
        _PENDING_EXCHANGES.append((fut, tag))
    return ObjCollectiveHandle(future=fut, tag=tag)


# -- elastic rejoin (knob PHOTON_REJOIN) -------------------------------------
#
# The degrade half shrinks the world in place; this half grows it back.
# A process lost to the fleet re-execs (the ``rejoin`` fault spec, or an
# operator restart), reloads its ORIGINAL identity and the cached mesh
# addresses from the persisted mesh cache (knob ``PHOTON_MESH_CACHE``),
# binds its recorded port and WAITS to be invited. The surviving group,
# at a visit boundary, probes the lost peers' cached addresses; a
# listening rejoiner gets an INVITE naming the candidate set, then both
# sides run one barrier-tagged rejoin roll call (``roll_call`` with
# ``candidates`` = survivors + rejoiners, quorum guarded by the CURRENT
# group) and the agreed, expanded group continues over the framed-P2P
# mesh — the jax collective cohort is never re-entered (a fresh runtime
# cannot rejoin it), which is exactly why every group-shaped helper in
# this module routes host-side once degraded.


def rejoin_enabled() -> bool:
    """``PHOTON_REJOIN`` (strict int parse; default 0 = lost peers stay
    lost, today's behavior byte-for-byte)."""
    env = os.environ.get("PHOTON_REJOIN")
    if env is not None and env != "":
        return int(env) != 0
    return False


def rejoin_window_s() -> float:
    """``PHOTON_REJOIN_WINDOW_S`` (seconds, strict float parse; default
    10): how long the fleet lingers for returning peers at the FIRST
    visit boundary after a degrade (and how long a booting rejoiner
    waits for its invite). Later boundaries use instant probes, so a
    peer that never returns costs one connect-refused per boundary."""
    env = os.environ.get("PHOTON_REJOIN_WINDOW_S")
    if env is not None and env != "":
        return max(float(env), 0.0)
    return 10.0


def _mesh_cache_path() -> str | None:
    """``PHOTON_MESH_CACHE``: file path the first mesh build persists
    its ``{pid: (ip, port)}`` table to (atomically), and a rejoin boot
    reloads it from. Unset (default) = nothing is written — the
    pre-rejoin behavior byte-for-byte."""
    return os.environ.get("PHOTON_MESH_CACHE") or None


def _maybe_persist_mesh_addrs() -> None:
    """Persist the freshly-bootstrapped address table for future
    rejoiners. Every process writes (atomic replace — on a shared
    filesystem the copies are identical; on split filesystems each
    host keeps its own). Never fatal: the cache is an enabler for
    rejoin, not a correctness dependency of the healthy path."""
    path = _mesh_cache_path()
    if path is None or _HOST_ADDRS is None:
        return
    try:
        import json

        from photon_ml_tpu.utils.atomic_io import atomic_replace_bytes

        doc = {
            "world": _world_size(),
            "addrs": {
                str(p): [ip, int(port)]
                for p, (ip, port) in sorted(_HOST_ADDRS.items())
            },
        }
        atomic_replace_bytes(
            os.path.dirname(path) or ".", path, json.dumps(doc).encode()
        )
    except Exception:
        _emit_event("mesh_cache_write_failed", path=path)


def bootstrap_rejoin(pid: int | None = None, path: str | None = None) -> dict:
    """Adopt a lost process's ORIGINAL identity in a fresh interpreter:
    load the persisted mesh-address cache, record ``(pid, world)`` as
    this process's identity (``jax.process_index/count`` are 0/1 here —
    the fresh runtime never joined the original cohort), and leave the
    process ready for ``rejoin_wait``. ``pid`` defaults to the
    ``PHOTON_REJOIN_BOOT`` env var the ``rejoin`` fault spec plants in
    the re-exec'd child."""
    global _HOST_ADDRS, _REJOIN_IDENTITY
    import json

    if pid is None:
        env = os.environ.get("PHOTON_REJOIN_BOOT")
        if not env:
            raise RuntimeError(
                "bootstrap_rejoin needs the original process index: pass "
                "pid= or set PHOTON_REJOIN_BOOT"
            )
        pid = int(env)
    path = path or _mesh_cache_path()
    if path is None:
        raise RuntimeError(
            "bootstrap_rejoin needs the persisted mesh cache: set "
            "PHOTON_MESH_CACHE (the same path the original fleet ran "
            "with) or pass path="
        )
    with open(path) as f:
        doc = json.load(f)
    addrs = {
        int(p): (str(ip), int(port))
        for p, (ip, port) in doc["addrs"].items()
    }
    if pid not in addrs:
        raise RuntimeError(
            f"mesh cache {path!r} has no address for process {pid} "
            f"(recorded: {sorted(addrs)})"
        )
    _reset_host_links()
    _HOST_ADDRS = addrs
    _REJOIN_IDENTITY = {"pid": int(pid), "world": int(doc["world"])}
    _emit_event("rejoin_boot", pid=int(pid), world=int(doc["world"]))
    return dict(_REJOIN_IDENTITY)


# hello-int version values reserved for the rejoin rendezvous (the mesh
# frame protocol uses 0/1, so these can never be mistaken for a build
# hello's version — and a rejoiner can tell a roll-call dial from an
# invite and stay out of a build it was not named in)
_HELLO_PROBE = 0x7D
_HELLO_INVITE = 0x7E


def probe_rejoiners(
    lost: Sequence[int], window_s: float = 0.0, poll_s: float = 0.25
) -> list[int]:
    """Which of the ``lost`` original pids are back and listening on
    their recorded mesh address (rank-0 survivor side; the result must
    be broadcast over the group before acting on it — probing is
    per-process I/O, not a collective). A probe is one cheap connect +
    2-word handshake; refused/timed out = not back yet. ``window_s``
    lingers, re-polling every ``poll_s``, until at least one rejoiner
    answers or the window closes."""
    import socket
    import struct

    if _HOST_ADDRS is None:
        return []
    deadline = time.monotonic() + max(window_s, 0.0)
    present: list[int] = []
    while True:
        for p in lost:
            if p in present or p not in _HOST_ADDRS:
                continue
            try:
                with socket.create_connection(
                    _HOST_ADDRS[p], timeout=0.5
                ) as s:
                    s.settimeout(2.0)
                    s.sendall(struct.pack(
                        "!i", _self_pid() | (_HELLO_PROBE << 16)
                    ))
                    if _recv_exact(s, 1) == _ACK_BYTE:
                        present.append(p)
            except OSError:
                continue
        if present or time.monotonic() >= deadline:
            return sorted(present)
        time.sleep(poll_s)


def send_rejoin_invites(
    present: Sequence[int], candidates: Sequence[int],
    survivors: Sequence[int],
) -> list[int]:
    """Deliver the rejoin invitation (candidate set + current survivor
    set — everything a rejoiner needs to enter the SAME roll call the
    survivors are about to run) to each probed-present rejoiner.
    Returns the pids that ACKed; a rejoiner that died between probe and
    invite simply drops out of the roll call like any unreachable
    candidate."""
    import pickle
    import socket
    import struct

    invited: list[int] = []
    payload = pickle.dumps(
        {
            "candidates": [int(c) for c in sorted(candidates)],
            "survivors": [int(s) for s in sorted(survivors)],
        },
        protocol=4,
    )
    for p in present:
        try:
            with socket.create_connection(
                _HOST_ADDRS[p], timeout=2.0
            ) as s:
                s.settimeout(5.0)
                s.sendall(struct.pack(
                    "!i", _self_pid() | (_HELLO_INVITE << 16)
                ))
                s.sendall(struct.pack("!q", len(payload)))
                s.sendall(payload)
                if _recv_exact(s, 1) == _ACK_BYTE:
                    invited.append(int(p))
        except OSError:
            continue
    return invited


def rejoin_wait(window_s: float | None = None) -> dict | None:
    """Rejoiner side of the rendezvous: bind this process's RECORDED
    mesh port, answer probes, and wait up to ``window_s`` for an
    invite. Returns the invite payload (``candidates`` + ``survivors``)
    or None when the window closes uninvited.

    A dial that is NOT a probe/invite — a degrade roll call racing this
    boot reaches the same recorded port — is closed unanswered: the
    rejoiner must not wedge a mesh build it was not named in (the
    build's accept count then falls short and the roll call drops this
    pid for that round; a later boundary re-invites it). The listener
    is closed before returning, so the rejoin roll call can re-bind the
    port."""
    import pickle
    import socket
    import struct

    if _REJOIN_IDENTITY is None or _HOST_ADDRS is None:
        raise RuntimeError(
            "rejoin_wait outside a rejoin boot: call bootstrap_rejoin "
            "first"
        )
    if window_s is None:
        window_s = rejoin_window_s()
    own_port = _HOST_ADDRS[_self_pid()][1]
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    try:
        srv.bind(("0.0.0.0", own_port))
        srv.listen(8)
        deadline = time.monotonic() + max(window_s, 0.0)
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            srv.settimeout(min(remaining, 1.0))
            try:
                conn, _ = srv.accept()
            except socket.timeout:
                continue
            try:
                conn.settimeout(5.0)
                raw = struct.unpack("!i", _recv_exact(conn, 4))[0]
                src, kind = _decode_hello(raw)
                if kind == _HELLO_PROBE:
                    conn.sendall(_ACK_BYTE)
                    continue
                if kind != _HELLO_INVITE:
                    # a mesh/roll-call build dialing our recorded port:
                    # close unanswered (see docstring)
                    continue
                n = struct.unpack("!q", _recv_exact(conn, 8))[0]
                payload = pickle.loads(_recv_exact(conn, n))
                conn.sendall(_ACK_BYTE)
                _emit_event(
                    "rejoin_invited", inviter=int(src),
                    candidates=payload.get("candidates"),
                    survivors=payload.get("survivors"),
                )
                return payload
            except OSError:
                continue
            finally:
                _close_quietly(conn)
    finally:
        _close_quietly(srv)


def allreduce_max_host(*arrays: np.ndarray):
    """Elementwise max across ALL processes of the current group
    (identity on one process). Used by the streamed feature summary for
    min/max statistics (min rides as max of the negation)."""
    if effective_process_count() <= 1:
        return arrays if len(arrays) > 1 else arrays[0]
    if _DEGRADED is not None:
        gathered = _p2p_allgather_obj(
            tuple(np.asarray(a) for a in arrays), tag="allreduce_max"
        )
        maxed = tuple(
            np.max(np.stack([g[i] for g in gathered]), axis=0)
            for i in range(len(arrays))
        )
        return maxed if len(maxed) > 1 else maxed[0]
    from jax.experimental import multihost_utils

    stacked = multihost_utils.process_allgather(arrays)  # each: (P, ...)
    maxed = tuple(np.max(np.asarray(a), axis=0) for a in stacked)
    return maxed if len(maxed) > 1 else maxed[0]
