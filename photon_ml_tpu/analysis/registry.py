"""The knob registry: ONE source of truth per ``PHOTON_*`` environment knob.

Fourteen PRs of bitwise-parity-gated knobs left every knob hand-wired
through up to five mirror surfaces — the bench ``RETUNE_ENV`` tables, the
telemetry ``run_start`` knob snapshot (``obs/sink._knob_snapshot``), the
device-cost capture-key fingerprint (``obs/devcost._knob_raw_state``), and
the README knob table — with nothing but reviewer memory keeping them in
sync (``obs/devcost.py`` literally documents "the failure mode of
forgetting"). This module makes the wiring mechanical: each knob declares
its type, parse idiom, default, owning module, call-time accessors, retune
module global, and which mirror surfaces must carry it (with explicit,
reasoned exemptions where a surface legitimately does not apply). The
``photon-ml-tpu lint`` knob pass cross-checks every surface against this
table BY PARSING THE ACTUAL SOURCES, so drift in either direction — a knob
added to a surface but not here, or registered here but missing from a
required surface — fails the lint run.

Surface semantics:

- ``retune_table`` — the bench.py RETUNE dict that must carry the knob
  (``RETUNE_ENV`` / ``RETUNE_ENV_PREFETCH`` / ``RETUNE_ENV_RE`` /
  ``RETUNE_ENV_SHARD``), or None with an ``exempt`` reason.
- ``sink_key`` — the key under which ``sink._knob_snapshot`` must report
  the knob (the run_start configuration record), or None with a reason.
- devcost fingerprint — REQUIRED exactly when ``sink_key`` is set: the
  snapshot is memoized on ``devcost._knob_raw_state``, so every snapshot
  input must be fingerprinted there (env name or retune global), or a
  mid-process knob flip reuses a stale snapshot in capture keys.
- README — every registered knob appears in the generated README knob
  table (``photon-ml-tpu lint --write-docs`` renders it from this
  registry; the knob pass fails when the committed table drifts).

Parse idioms (``parse``):

- ``strict_int`` / ``strict_float`` — ``int(env)`` / ``float(env)`` with
  no fallback: a typo fails the run loudly (the repo discipline for every
  knob that changes math or schedule).
- ``enum`` — strict membership in a named value set
  (``validate_kernel_dtype``, ``_RE_COMBINE_MODES``).
- ``spec`` — structured string with its own strict parser
  (``"<process>:<delay_s>"``).
- ``raw`` — free string/path/JSON consumed verbatim; truthiness on these
  is fine and the parse check does not apply.
- ``lenient_warn`` — documented exception: ``PHOTON_DEVCOST`` degrades to
  capture-off with one warning because observability misconfiguration
  must never take down the run it observes.
"""

from __future__ import annotations

from dataclasses import dataclass, field

SURFACES = ("retune", "sink", "devcost", "readme")

#: retune tables the bench defines; the lint pass parses these names out
#: of bench.py and cross-checks membership in both directions.
RETUNE_TABLES = (
    "RETUNE_ENV",
    "RETUNE_ENV_PREFETCH",
    "RETUNE_ENV_RE",
    "RETUNE_ENV_SHARD",
    "RETUNE_ENV_SERVE",
    "RETUNE_ENV_STREAM",
)


@dataclass(frozen=True)
class Knob:
    name: str  # the PHOTON_* environment variable
    kind: str  # int | flag | float | enum | str | path | json | spec
    parse: str  # strict_int | strict_float | enum | spec | raw | lenient_warn
    default: str  # human-readable default
    owner: str  # repo-relative path of the owning module
    doc: str  # one-line description (README table row)
    accessors: tuple = ()  # call-time accessor function names
    retune_global: str | None = None  # module global the bench retunes
    retune_table: str | None = None
    sink_key: str | None = None
    exempt: tuple = ()  # ((surface, reason), ...) for absent surfaces

    def exempt_reason(self, surface: str) -> str | None:
        for s, reason in self.exempt:
            if s == surface:
                return reason
        return None

    @property
    def needs_devcost(self) -> bool:
        # the devcost fingerprint exists to invalidate the memoized sink
        # snapshot, so it must cover exactly the snapshot's inputs
        return self.sink_key is not None and self.exempt_reason(
            "devcost") is None


_EXEMPT_FAULT = (
    ("retune", "fault-injection / recovery drill knob, not a perf lever "
               "the bench sweeps"),
    ("sink", "does not change executables or solve math; drills log their "
             "own fault/recovery telemetry events"),
)
_EXEMPT_TRANSPORT = (
    ("retune", "transport reliability knob; bitwise-neutral to results "
               "and not swept by bench configs"),
    ("sink", "does not change executables or solve math; retries/CRC "
             "emit their own p2p_* telemetry events"),
)
_EXEMPT_DEPLOY = (
    ("retune", "deployment plumbing (addresses/paths), not a perf lever"),
    ("sink", "no effect on executables or math"),
)

KNOBS: tuple[Knob, ...] = (
    # -- sparse-tiled kernel constants (RETUNE_ENV) -------------------------
    Knob(
        name="PHOTON_GROUPS_PER_STEP", kind="int", parse="strict_int",
        default="32", owner="photon_ml_tpu/ops/sparse_tiled.py",
        doc="groups per DMA step of the sparse-tiled kernels",
        retune_global="GROUPS_PER_STEP", retune_table="RETUNE_ENV",
        sink_key="groups_per_step",
    ),
    Knob(
        name="PHOTON_SEGMENTS_PER_DMA", kind="int", parse="strict_int",
        default="4", owner="photon_ml_tpu/ops/sparse_tiled.py",
        doc="segments per double-buffered DMA step",
        retune_global="SEGMENTS_PER_DMA", retune_table="RETUNE_ENV",
        sink_key="segments_per_dma",
    ),
    Knob(
        name="PHOTON_GROUPS_PER_RUN", kind="int", parse="strict_int",
        default="2", owner="photon_ml_tpu/ops/sparse_tiled.py",
        doc="groups per shared-source slab run",
        retune_global="GROUPS_PER_RUN", retune_table="RETUNE_ENV",
        sink_key="groups_per_run",
    ),
    Knob(
        name="PHOTON_KERNEL_DTYPE", kind="enum", parse="enum",
        default="f32", owner="photon_ml_tpu/ops/sparse_tiled.py",
        doc="storage precision rung: f32 (bitwise anchor) | int8",
        accessors=("kernel_dtype",),
        retune_global="KERNEL_DTYPE", retune_table="RETUNE_ENV",
        sink_key="kernel_dtype",
    ),
    # -- host-ingest pipeline (RETUNE_ENV_PREFETCH) -------------------------
    Knob(
        name="PHOTON_PREFETCH_DEPTH", kind="int", parse="strict_int",
        default="2", owner="photon_ml_tpu/ops/prefetch.py",
        doc="chunks prepared ahead of the consumer; 0 = synchronous",
        accessors=("prefetch_depth",),
        retune_global="PREFETCH_DEPTH", retune_table="RETUNE_ENV_PREFETCH",
        sink_key="prefetch_depth",
    ),
    Knob(
        name="PHOTON_CHUNK_CACHE_BUDGET", kind="int", parse="strict_int",
        default="25% of device HBM", owner="photon_ml_tpu/ops/prefetch.py",
        doc="device-resident chunk-cache byte budget",
        accessors=("chunk_cache_budget_bytes",),
        retune_global="CHUNK_CACHE_BUDGET",
        retune_table="RETUNE_ENV_PREFETCH",
        sink_key="chunk_cache_budget_bytes",
    ),
    # -- random-effect bucket solves (RETUNE_ENV_RE) ------------------------
    Knob(
        name="PHOTON_RE_COMPACT_EVERY", kind="int", parse="strict_int",
        default="0", owner="photon_ml_tpu/game/random_effect.py",
        doc="outer iterations per compaction chunk; 0 = single launch",
        accessors=("compact_every",),
        retune_global="COMPACT_EVERY", retune_table="RETUNE_ENV_RE",
        sink_key="re_compact_every",
    ),
    Knob(
        name="PHOTON_RE_FUSE_BUCKETS", kind="flag", parse="strict_int",
        default="0", owner="photon_ml_tpu/game/random_effect.py",
        doc="1 = fuse same-geometry buckets into one launch",
        accessors=("fuse_buckets",),
        retune_global="FUSE_BUCKETS", retune_table="RETUNE_ENV_RE",
        sink_key="re_fuse_buckets",
    ),
    Knob(
        name="PHOTON_RE_COMBINE", kind="enum", parse="enum",
        default="allreduce", owner="photon_ml_tpu/game/random_effect.py",
        doc="cross-process combine transport: allreduce | segments",
        accessors=("re_combine_mode",),
        retune_global="RE_COMBINE", retune_table="RETUNE_ENV_RE",
        sink_key="re_combine",
    ),
    Knob(
        name="PHOTON_RE_PROJECT", kind="enum", parse="enum",
        default="0", owner="photon_ml_tpu/game/projector.py",
        doc="per-entity feature projection: 0 | support | hash",
        accessors=("re_project_mode",),
        retune_global="RE_PROJECT", retune_table="RETUNE_ENV_RE",
        sink_key="re_project",
    ),
    Knob(
        name="PHOTON_RE_PROJECT_DIM", kind="int", parse="strict_int",
        default="32", owner="photon_ml_tpu/game/projector.py",
        doc="signed-hash fold width (pow2) for classes whose support "
            "exceeds it (hash mode only)",
        accessors=("re_project_dim",),
        retune_global="RE_PROJECT_DIM", retune_table="RETUNE_ENV_RE",
        sink_key="re_project_dim",
    ),
    # -- entity-shard placement (RETUNE_ENV_SHARD) --------------------------
    Knob(
        name="PHOTON_RE_SHARD", kind="flag", parse="strict_int",
        default="0", owner="photon_ml_tpu/parallel/placement.py",
        doc="1 = skew-aware entity sharding + overlapped P2P exchange",
        accessors=("re_shard_enabled",),
        retune_global="RE_SHARD", retune_table="RETUNE_ENV_SHARD",
        sink_key="re_shard",
    ),
    Knob(
        name="PHOTON_RE_SPLIT", kind="int", parse="strict_int",
        default="0", owner="photon_ml_tpu/parallel/placement.py",
        doc="sub-bucket atom target count; 0 = bucket-atomic placement",
        accessors=("re_split_factor",),
        retune_global="RE_SPLIT", retune_table="RETUNE_ENV_SHARD",
        sink_key="re_split",
    ),
    Knob(
        name="PHOTON_RE_REPLAN_IMBALANCE", kind="float",
        parse="strict_float", default="0 (off)",
        owner="photon_ml_tpu/parallel/placement.py",
        doc="measured max/mean solve-wall ratio that triggers a re-plan",
        accessors=("replan_imbalance_threshold",),
        retune_global="REPLAN_IMBALANCE", retune_table="RETUNE_ENV_SHARD",
        sink_key="re_replan_imbalance",
    ),
    Knob(
        name="PHOTON_RE_DEVICE_SPLIT", kind="flag", parse="strict_int",
        default="0", owner="photon_ml_tpu/parallel/placement.py",
        doc="1 = second-level LPT: owned atoms placed per LOCAL device",
        accessors=("re_device_split_enabled",),
        retune_global="RE_DEVICE_SPLIT", retune_table="RETUNE_ENV_SHARD",
        sink_key="re_device_split",
    ),
    Knob(
        name="PHOTON_RE_SPLIT_WEIGHT", kind="enum", parse="enum",
        default="rows", owner="photon_ml_tpu/parallel/placement.py",
        doc="atom split/placement weight axis: rows | bytes",
        accessors=("re_split_weight",),
        retune_global="RE_SPLIT_WEIGHT", retune_table="RETUNE_ENV_SHARD",
        sink_key="re_split_weight",
    ),
    # -- feature-range-sharded fixed effect (RETUNE_ENV_SHARD) --------------
    Knob(
        name="PHOTON_FE_SHARD", kind="flag", parse="strict_int",
        default="0", owner="photon_ml_tpu/data/index_map.py",
        doc="1 = range-shard the fixed-effect feature space across processes",
        accessors=("fe_shard_enabled",),
        retune_global="FE_SHARD", retune_table="RETUNE_ENV_SHARD",
        sink_key="fe_shard",
    ),
    Knob(
        name="PHOTON_FE_SPLIT_WEIGHT", kind="enum", parse="enum",
        default="nnz", owner="photon_ml_tpu/data/index_map.py",
        doc="feature-range boundary weight axis: nnz | width",
        accessors=("fe_split_weight",),
        retune_global="FE_SPLIT_WEIGHT", retune_table="RETUNE_ENV_SHARD",
        sink_key="fe_split_weight",
    ),
    # -- online serving (RETUNE_ENV_SERVE) ----------------------------------
    Knob(
        name="PHOTON_SERVE_HOT_BYTES", kind="int", parse="strict_int",
        default="25% of RE model bytes", owner="photon_ml_tpu/serve/store.py",
        doc="hot-set byte budget for device-resident model shards",
        accessors=("serve_hot_budget_bytes",),
        retune_global="SERVE_HOT_BYTES", retune_table="RETUNE_ENV_SERVE",
        sink_key="serve_hot_bytes",
    ),
    Knob(
        name="PHOTON_SERVE_MAX_BATCH", kind="int", parse="strict_int",
        default="32", owner="photon_ml_tpu/serve/router.py",
        doc="micro-window flush size (also the padded scoring shape)",
        accessors=("serve_max_batch",),
        retune_global="SERVE_MAX_BATCH", retune_table="RETUNE_ENV_SERVE",
        sink_key="serve_max_batch",
    ),
    Knob(
        name="PHOTON_SERVE_MAX_WAIT_MS", kind="float", parse="strict_float",
        default="2.0", owner="photon_ml_tpu/serve/router.py",
        doc="oldest-request wait (ms) that forces a partial-window flush",
        accessors=("serve_max_wait_ms",),
        retune_global="SERVE_MAX_WAIT_MS", retune_table="RETUNE_ENV_SERVE",
        sink_key="serve_max_wait_ms",
    ),
    Knob(
        name="PHOTON_SERVE_REFRESH_EVERY", kind="int", parse="strict_int",
        default="0 (off)", owner="photon_ml_tpu/serve/refresh.py",
        doc="buffered events per entity that trigger an incremental refresh",
        accessors=("serve_refresh_every",),
        retune_global="SERVE_REFRESH_EVERY", retune_table="RETUNE_ENV_SERVE",
        sink_key="serve_refresh_every",
    ),
    # -- streaming executor (RETUNE_ENV_STREAM) -----------------------------
    Knob(
        name="PHOTON_STREAM_EXECUTOR", kind="flag", parse="strict_int",
        default="0", owner="photon_ml_tpu/ops/stream_executor.py",
        doc="1 = route streamed consumers through the shared executor "
            "(multi-tenant chunk-cache arbiter + cross-stream scheduling)",
        accessors=("stream_executor_enabled",),
        retune_global="STREAM_EXECUTOR", retune_table="RETUNE_ENV_STREAM",
        sink_key="stream_executor",
    ),
    Knob(
        name="PHOTON_STREAM_PRIORITY", kind="spec", parse="spec",
        default="'' (built-in table: serve=100, refresh=10, rest=50)",
        owner="photon_ml_tpu/ops/stream_executor.py",
        doc="per-consumer scheduling priority overrides, "
            "'name=int,...' — higher preempts lower streams' prefetch depth",
        accessors=("stream_priority_spec", "priority_of"),
        retune_global="STREAM_PRIORITY", retune_table="RETUNE_ENV_STREAM",
        sink_key="stream_priority",
    ),
    Knob(
        name="PHOTON_STREAM_SHARE", kind="spec", parse="spec",
        default="'' (no per-consumer cap)",
        owner="photon_ml_tpu/ops/stream_executor.py",
        doc="per-consumer chunk-cache budget shares, 'name=frac,...' — "
            "caps a stream's charged bytes at frac x the cache budget",
        accessors=("stream_share_spec", "share_fraction"),
        retune_global="STREAM_SHARE", retune_table="RETUNE_ENV_STREAM",
        sink_key="stream_share",
    ),
    # -- observability / selection toggles ---------------------------------
    Knob(
        name="PHOTON_RE_ITER_ACCOUNTING", kind="flag", parse="strict_int",
        default="follows telemetry sink",
        owner="photon_ml_tpu/game/random_effect.py",
        doc="force per-lane iteration readback for re_solve.* counters",
        accessors=("_iter_accounting_enabled",),
        exempt=(
            ("retune", "diagnostics readback toggle, not a perf lever; "
                       "bench R_re_skew sets it explicitly"),
            ("sink", "changes only whether counters are read back, never "
                     "executables or math"),
        ),
    ),
    Knob(
        name="PHOTON_TELEMETRY_FLEET", kind="flag", parse="strict_int",
        default="follows PHOTON_RE_SHARD", owner="photon_ml_tpu/obs/sink.py",
        doc="per-process telemetry shards on processes 1..N-1",
        accessors=("fleet_telemetry_enabled",),
        exempt=(
            ("retune", "telemetry file layout, not a perf lever"),
            ("sink", "configures the sink itself; recorded implicitly by "
                     "which shard files exist"),
        ),
    ),
    Knob(
        name="PHOTON_DEVCOST", kind="flag", parse="lenient_warn",
        default="follows telemetry sink", owner="photon_ml_tpu/obs/devcost.py",
        doc="force analytic device-cost capture on (1, sink-less) or off (0)",
        accessors=("capture_enabled",),
        exempt=(
            ("retune", "observability gate, not a perf lever; bench --quick "
                       "sets it explicitly"),
            ("sink", "gates capture only; documented-lenient parse because "
                     "observability must never take down the run"),
        ),
    ),
    Knob(
        name="PHOTON_DISABLE_FUSED", kind="flag", parse="strict_int",
        default="0", owner="photon_ml_tpu/ops/glm.py",
        doc="1 vetoes auto-enabling the fused one-pass Pallas kernels",
        accessors=("fused_disabled",),
        exempt=(
            ("retune", "an auto-selection veto for TPU dense batches, not "
                       "a swept lever; CPU bench configs never auto-fuse"),
            ("sink", "the chosen path is visible as the objective's fused "
                     "flag and in executable labels"),
        ),
    ),
    # -- fault tolerance / elastic fleet ------------------------------------
    Knob(
        name="PHOTON_DESCENT_DEGRADE", kind="flag", parse="strict_int",
        default="0", owner="photon_ml_tpu/game/descent.py",
        doc="1 = in-place degraded-group recovery for the in-memory descent",
        accessors=("descent_degrade_enabled",), exempt=_EXEMPT_FAULT,
    ),
    Knob(
        name="PHOTON_REJOIN", kind="flag", parse="strict_int", default="0",
        owner="photon_ml_tpu/parallel/multihost.py",
        doc="1 = elastic rejoin for the streamed trainer",
        accessors=("rejoin_enabled",), exempt=_EXEMPT_FAULT,
    ),
    Knob(
        name="PHOTON_REJOIN_WINDOW_S", kind="float", parse="strict_float",
        default="10", owner="photon_ml_tpu/parallel/multihost.py",
        doc="rejoin probe/invite window seconds",
        exempt=_EXEMPT_FAULT,
    ),
    Knob(
        name="PHOTON_REJOIN_CMD", kind="json", parse="raw", default="unset",
        owner="photon_ml_tpu/parallel/faults.py",
        doc="argv (JSON list) used to re-exec a killed process",
        exempt=_EXEMPT_FAULT,
    ),
    Knob(
        name="PHOTON_REJOIN_BOOT", kind="spec", parse="raw", default="unset",
        owner="photon_ml_tpu/parallel/faults.py",
        doc="internal handshake: dying process's index for the rebooted "
            "child (set by the relauncher, not by operators)",
        exempt=_EXEMPT_FAULT,
    ),
    Knob(
        name="PHOTON_MESH_CACHE", kind="path", parse="raw", default="unset",
        owner="photon_ml_tpu/parallel/multihost.py",
        doc="persisted mesh-address cache enabling rejoin identity",
        exempt=_EXEMPT_FAULT,
    ),
    Knob(
        name="PHOTON_ROLLCALL_WINDOW_S", kind="float", parse="strict_float",
        default="10", owner="photon_ml_tpu/parallel/multihost.py",
        doc="roll-call census window seconds",
        exempt=_EXEMPT_FAULT,
    ),
    Knob(
        name="PHOTON_COORD_MAX_MISSING_HEARTBEATS", kind="int",
        parse="strict_int", default="jax default",
        owner="photon_ml_tpu/parallel/multihost.py",
        doc="heartbeats the jax coordination service tolerates missing",
        exempt=_EXEMPT_FAULT,
    ),
    Knob(
        name="PHOTON_FAULT_PLAN", kind="json", parse="raw", default="unset",
        owner="photon_ml_tpu/parallel/faults.py",
        doc="deterministic fault-injection plan (JSON list or @file)",
        exempt=_EXEMPT_FAULT,
    ),
    Knob(
        name="PHOTON_RE_STRAGGLER", kind="spec", parse="spec",
        default="unset", owner="photon_ml_tpu/parallel/faults.py",
        doc="straggler drill: '<process>:<delay_s>' per-visit sleep",
        exempt=_EXEMPT_FAULT,
    ),
    # -- framed-P2P transport ----------------------------------------------
    Knob(
        name="PHOTON_P2P_RETRIES", kind="int", parse="strict_int",
        default="0", owner="photon_ml_tpu/parallel/multihost.py",
        doc="reliable-exchange retry budget; 0 = raise on first link error",
        exempt=_EXEMPT_TRANSPORT,
    ),
    Knob(
        name="PHOTON_P2P_BACKOFF_S", kind="float", parse="strict_float",
        default="0.5", owner="photon_ml_tpu/parallel/multihost.py",
        doc="base exponential backoff between exchange retries",
        exempt=_EXEMPT_TRANSPORT,
    ),
    Knob(
        name="PHOTON_P2P_CRC", kind="flag", parse="strict_int", default="0",
        owner="photon_ml_tpu/parallel/multihost.py",
        doc="advertise CRC32-trailed frame protocol v1 at mesh build",
        exempt=_EXEMPT_TRANSPORT,
    ),
    Knob(
        name="PHOTON_P2P_TIMEOUT_S", kind="float", parse="strict_float",
        default="300", owner="photon_ml_tpu/parallel/multihost.py",
        doc="per-socket-operation timeout for the exchange mesh",
        exempt=_EXEMPT_TRANSPORT,
    ),
    Knob(
        name="PHOTON_P2P_HEARTBEAT_S", kind="float", parse="strict_float",
        default="5", owner="photon_ml_tpu/parallel/multihost.py",
        doc="blocked-recv heartbeat cadence for fleet telemetry",
        exempt=_EXEMPT_TRANSPORT,
    ),
    # -- deployment plumbing -----------------------------------------------
    Knob(
        name="PHOTON_EXCHANGE_HOST", kind="str", parse="raw",
        default="derived from coordinator",
        owner="photon_ml_tpu/parallel/multihost.py",
        doc="explicit exchange-mesh bind/advertise host override",
        exempt=_EXEMPT_DEPLOY,
    ),
    Knob(
        name="PHOTON_ML_TPU_CACHE", kind="path", parse="raw",
        default="<tmpdir>/photon_ml_tpu_native",
        owner="photon_ml_tpu/native/build.py",
        doc="build cache directory for the native ingest extension",
        exempt=_EXEMPT_DEPLOY,
    ),
)


def by_name() -> dict[str, Knob]:
    return {k.name: k for k in KNOBS}


def accessor_names() -> frozenset[str]:
    """Call-time knob accessor function names — calling one of these
    inside a jitted body bakes the value into the traced executable
    silently (the stale-executable bug class the jit pass hunts)."""
    out = set()
    for k in KNOBS:
        out.update(k.accessors)
    return frozenset(out)


def retune_global_names() -> frozenset[str]:
    """Retune-mutable module globals (bench child processes overwrite
    these from the environment); reading one inside a jitted body without
    carrying it as a static key is the same stale-executable class."""
    return frozenset(
        k.retune_global for k in KNOBS if k.retune_global is not None
    )


def expected_retune_tables() -> dict[str, set[str]]:
    out: dict[str, set[str]] = {t: set() for t in RETUNE_TABLES}
    for k in KNOBS:
        if k.retune_table is not None:
            out[k.retune_table].add(k.name)
    return out


def check_retune_tables(actual: dict[str, dict]) -> None:
    """Runtime twin of the lint cross-check, called by ``bench.py`` at
    retune-application time: raise on any drift between the bench's
    RETUNE dicts and this registry, so a bench process cannot even START
    a sweep over an unregistered (or un-wired) knob."""
    expected = expected_retune_tables()
    problems = []
    for table, env_map in actual.items():
        names = set(env_map)
        want = expected.get(table, set())
        for extra in sorted(names - want):
            problems.append(
                f"{table} carries {extra} but the knob registry "
                f"(photon_ml_tpu/analysis/registry.py) does not place it "
                f"there — register it (and wire its mirror surfaces)"
            )
        for missing in sorted(want - names):
            problems.append(
                f"{table} is missing {missing}, which the knob registry "
                f"requires there"
            )
    if problems:
        raise ValueError(
            "bench RETUNE tables drifted from the knob registry:\n  "
            + "\n  ".join(problems)
        )


# -- README knob table (generated; photon-ml-tpu lint --write-docs) ---------

KNOB_TABLE_BEGIN = "<!-- knob-table:begin (generated from photon_ml_tpu/analysis/registry.py — edit there, then `photon-ml-tpu lint --write-docs`) -->"
KNOB_TABLE_END = "<!-- knob-table:end -->"


def render_knob_table() -> str:
    """The README knob table, one row per registered knob. Regenerate
    with ``photon-ml-tpu lint --write-docs``; the lint knob pass fails
    when the committed table and the registry disagree."""
    lines = [
        KNOB_TABLE_BEGIN,
        "| Knob | Kind | Default | Retune table | Snapshot key | What it does |",
        "|---|---|---|---|---|---|",
    ]
    for k in KNOBS:
        retune = f"`{k.retune_table}`" if k.retune_table else "—"
        sink = f"`{k.sink_key}`" if k.sink_key else "—"
        lines.append(
            f"| `{k.name}` | {k.kind} ({k.parse}) | {k.default} | "
            f"{retune} | {sink} | {k.doc} |"
        )
    lines.append(KNOB_TABLE_END)
    return "\n".join(lines)


def _validate_registry() -> None:
    """Import-time self-check: every knob either requires each surface or
    carries an explicit exemption reason — an entry can never be silently
    ambiguous about a surface."""
    seen = set()
    for k in KNOBS:
        if k.name in seen:
            raise AssertionError(f"duplicate knob registration: {k.name}")
        seen.add(k.name)
        if k.retune_table is None and k.exempt_reason("retune") is None:
            raise AssertionError(
                f"{k.name}: no retune_table and no 'retune' exemption"
            )
        if k.retune_table is not None and k.retune_table not in RETUNE_TABLES:
            raise AssertionError(
                f"{k.name}: unknown retune table {k.retune_table}"
            )
        if k.sink_key is None and k.exempt_reason("sink") is None:
            raise AssertionError(
                f"{k.name}: no sink_key and no 'sink' exemption"
            )


_validate_registry()
