"""Benchmark harness: honest, quality-checked throughput on configs A-F
(named in BASELINE.json).

Protocol ("speed is never reported without a parity check"):
- Every timed window ends with FULL host materialization of the result
  (``float()`` on the loss + ``np.asarray`` on the weights). On this
  platform ``jax.block_until_ready`` alone under-reports by ~1000x (the
  round-1 artifact); scalar materialization is the reliable fence.
- Median of ``REPEATS`` timed solves, compile excluded by a warm-up solve.
- A roofline sanity check rejects physically impossible numbers: the
  implied HBM traffic of a measurement (lower-bounded by one feature-matrix
  read per optimizer iteration) must stay below any TPU's HBM bandwidth.
- Every config reports a model-quality metric (AUC / RMSE / loss ratio
  against the data's generating model) next to its throughput.

Throughput metric = optimizer-iteration sample throughput: samples x
optimizer iterations / wall-clock. Line-search passes do extra FLOPs that
this metric does NOT credit, so it understates device utilization —
comparable across rounds and to the reference's per-iteration accounting
(SURVEY.md §6).

``vs_baseline``: the reference (Photon-ML on Spark) publishes no numbers
(BASELINE.json, ``published``), so configs A-C compare against a one-core Spark/Breeze-style
numpy proxy of the same iteration math measured on this host — i.e. "how
many Spark executor cores one TPU chip replaces". GAME configs (D/E) have
no meaningful single-core proxy and report ``vs_baseline: null``.

Output contract: stdout carries EXACTLY ONE JSON line — the headline metric
{"metric", "value", "unit", "vs_baseline", ...} with per-config results
embedded under "configs". Per-config progress lines go to stderr, and the
full detail is also written to BENCH_DETAIL.json next to this file.
``--quick`` keeps the same contract over the A/A2/F smoke subset at toy
shapes (seconds, one timed rep, no artifact writes) — the cheap regression
gate; kernel constants retune from the environment via RETUNE_ENV.
"""

from __future__ import annotations

import json
import os
import sys
import time

# The CPU proxies must measure ONE core (they model one Spark executor
# core). BLAS pools size themselves at first numpy import, so pin first.
for _v in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_v, "1")

import numpy as np

REPEATS = 3
# --quick: a smoke-sized subset (configs A/A2/F at toy shapes, one timed
# rep, no marginal differencing) that finishes in seconds and keeps the
# stdout single-JSON-line contract — the cheap regression gate for perf
# changes. Quick runs never touch BENCH_DETAIL.json (toy numbers must not
# overwrite the real artifact).
QUICK = False
QUICK_CONFIGS = (
    "A_sparse_logistic", "A2_sparse_highdim", "F_streaming", "R_re_skew",
    "S_serve_zipf",
)
# Kernel retune knobs: the sparse-tiled constants are module globals read
# at call time (layout builder AND kernel), so a child process can retune
# them from the environment — the bench-side lever for the
# GROUPS_PER_STEP/SEGMENTS_PER_DMA/GROUPS_PER_RUN sweep.
RETUNE_ENV = {
    "PHOTON_GROUPS_PER_STEP": "GROUPS_PER_STEP",
    "PHOTON_SEGMENTS_PER_DMA": "SEGMENTS_PER_DMA",
    "PHOTON_GROUPS_PER_RUN": "GROUPS_PER_RUN",
    # storage precision rung for the packed slabs + gathered operands
    # (f32 = bitwise anchor | int8 with per-tile scales); the ONE
    # string-valued knob — parsed strictly by validate_kernel_dtype, so a
    # typo fails the run instead of silently benching f32
    "PHOTON_KERNEL_DTYPE": "KERNEL_DTYPE",
}
# Host-ingest pipeline knobs: same call-time-read discipline, applied to
# ops/prefetch (depth 0 = the synchronous pre-prefetch schedule
# bit-for-bit; the cache budget bounds the device-resident chunk tier).
RETUNE_ENV_PREFETCH = {
    "PHOTON_PREFETCH_DEPTH": "PREFETCH_DEPTH",
    "PHOTON_CHUNK_CACHE_BUDGET": "CHUNK_CACHE_BUDGET",
}
# Random-effect bucket-solve knobs (game/random_effect): compact_every 0 =
# today's single-launch schedule bit-for-bit; fuse_buckets 0 = one launch
# per bucket. The R_re_skew config is the sweep surface for both.
RETUNE_ENV_RE = {
    "PHOTON_RE_COMPACT_EVERY": "COMPACT_EVERY",
    "PHOTON_RE_FUSE_BUCKETS": "FUSE_BUCKETS",
    # cross-process combine transport for owned-bucket sharded solves:
    # "allreduce" (default, dense O(P·E·d)) | "segments" (owner-segment
    # framed P2P, O(E·d)) — string knob, strict-parsed like KERNEL_DTYPE
    "PHOTON_RE_COMBINE": "RE_COMBINE",
    # per-entity feature projection for the bucket solves: "0" (default,
    # full-width solves bit-for-bit) | "support" (each capacity class
    # solves over its globally-active columns only — exact under
    # L2-at-zero) | "hash" (signed-hash fold to RE_PROJECT_DIM for
    # classes whose support exceeds it; lossy, quality-parity gated)
    "PHOTON_RE_PROJECT": "RE_PROJECT",
    "PHOTON_RE_PROJECT_DIM": "RE_PROJECT_DIM",
}
# Entity-sharded placement + overlapped exchange (parallel/placement):
# 0 = the pre-sharding schedule bit-for-bit (modular owners, blocking
# exchanges), 1 = skew-aware placement + overlapped P2P exchange.
# RE_SPLIT > 0 refines placement below bucket granularity (sub-bucket
# atoms: the value is the split rule's target atom count; 0 = today's
# bucket-atomic placement bit-for-bit). REPLAN_IMBALANCE > 0 turns on
# the telemetry-driven between-iterations re-planner (float knob: the
# measured solve-wall max/mean ratio that triggers an entity
# migration; 0 = off).
RETUNE_ENV_SHARD = {
    "PHOTON_RE_SHARD": "RE_SHARD",
    "PHOTON_RE_SPLIT": "RE_SPLIT",
    "PHOTON_RE_REPLAN_IMBALANCE": "REPLAN_IMBALANCE",
    # RE_DEVICE_SPLIT = 1 adds the second LPT level: each process's
    # owned atoms are placed over its LOCAL devices (0 = the
    # single-unit-per-process schedule bit-for-bit). RE_SPLIT_WEIGHT
    # picks the split/placement weight axis: "rows" (default) or
    # "bytes" (combine-segment lane bytes — closes the r09 max-owner-
    # bytes gap to the row-balance ratio).
    "PHOTON_RE_DEVICE_SPLIT": "RE_DEVICE_SPLIT",
    "PHOTON_RE_SPLIT_WEIGHT": "RE_SPLIT_WEIGHT",
    # FE_SHARD = 1 range-shards the FIXED-effect feature space across
    # processes (0 = replicated coefficients bit-for-bit); the knobs
    # live in data/index_map (module_overrides below redirects them).
    # FE_SPLIT_WEIGHT picks the boundary weight axis: "nnz" (default,
    # Zipf-aware prefix cut) or "width" (uniform index split, the
    # naive rule kept for A/B).
    "PHOTON_FE_SHARD": "FE_SHARD",
    "PHOTON_FE_SPLIT_WEIGHT": "FE_SPLIT_WEIGHT",
}
# Online-serving knobs (serve/store, serve/router, serve/refresh — the
# module_overrides below redirect the non-store vars): the hot-set byte
# budget (0 = 25% of RE model bytes), the micro-window latency/throughput
# pair (max-batch is also the ONE padded scoring shape; max-wait is the
# float knob, strict-parsed like REPLAN_IMBALANCE), and the
# events-per-entity incremental-refresh trigger (0 = off). S_serve_zipf
# is the sweep surface.
RETUNE_ENV_SERVE = {
    "PHOTON_SERVE_HOT_BYTES": "SERVE_HOT_BYTES",
    "PHOTON_SERVE_MAX_BATCH": "SERVE_MAX_BATCH",
    "PHOTON_SERVE_MAX_WAIT_MS": "SERVE_MAX_WAIT_MS",
    "PHOTON_SERVE_REFRESH_EVERY": "SERVE_REFRESH_EVERY",
}
# Streaming-executor knobs (ops/stream_executor): the executor toggle
# (0 = every consumer keeps its pre-executor wiring bit-for-bit), the
# per-consumer priority-override spec ("name=int,..." — higher preempts
# lower streams' prefetch depth), and the per-consumer chunk-cache
# budget-share spec ("name=frac,..."). X_stream is the sweep surface.
RETUNE_ENV_STREAM = {
    "PHOTON_STREAM_EXECUTOR": "STREAM_EXECUTOR",
    "PHOTON_STREAM_PRIORITY": "STREAM_PRIORITY",
    "PHOTON_STREAM_SHARE": "STREAM_SHARE",
}
# No TPU generation exceeds this HBM bandwidth (v5p ~2.8 TB/s); a
# measurement implying more is a timing artifact, not a fast solve.
HBM_ROOFLINE_BYTES_PER_S = 4.0e12
# Utilization denominator: a v5e-class chip's HBM bandwidth (~819 GB/s).
# `implied_hbm_fraction` = achieved bytes/s over THIS constant, so "how
# close to memory-bound" is auditable per config (VERDICT r2 weak #7); on
# a different chip generation the fraction rescales by its bandwidth.
CHIP_HBM_BYTES_PER_S = 8.19e11


def _hbm_utilization(bytes_per_pass: float, sec_per_pass: float) -> dict:
    gbps = bytes_per_pass / sec_per_pass / 1e9
    return {
        "implied_hbm_gbps": round(gbps, 1),
        "implied_hbm_fraction": round(gbps * 1e9 / CHIP_HBM_BYTES_PER_S, 4),
    }


def _marginal_reps(
    solve,
    w0,
    cfg_long,
    short_T: int,
    bytes_per_pass: float,
    main: tuple | None,
    reps: int = 3,
) -> dict:
    """Median-of-``reps`` differenced marginals, shared by every config
    that differences a short solve out of a long one (a single pair let
    one draw of the documented session noise decide borderline bars —
    round-4 review). Later pairs perturb w0 so a runtime that caches
    identical (program, argument) executions cannot replay either solve; ``main`` reuses the already-timed primary
    solve as rep 0's long run. Returns the kept reps for BOTH
    denominations plus the count of candidates lost to dispatch jitter
    (negative difference) or the roofline guard — silently thinned reps
    were indistinguishable from clean agreement in the artifact."""
    from photon_ml_tpu.config import OptimizerConfig

    cfg_s = OptimizerConfig(max_iterations=short_T, tolerance=0.0)
    iter_reps: list[float] = []
    pass_reps: list[float] = []
    rejected = 0
    for rep in range(reps):
        w0_r = w0 if rep == 0 else w0 + (1e-4 * rep)
        if rep == 0 and main is not None:
            dt_l, its_l, passes_l = main
        else:
            dt_l, _, res_l = _timed_solves(
                lambda w=w0_r: solve(w, cfg_long),
                bytes_lower_bound_per_run=bytes_per_pass,
            )
            its_l = max(int(res_l.iterations), 1)
            passes_l = max(int(res_l.objective_passes), its_l)
        dt_s, _, res_s = _timed_solves(
            lambda w=w0_r: solve(w, cfg_s),
            bytes_lower_bound_per_run=bytes_per_pass,
        )
        its_s = max(int(res_s.iterations), 1)
        passes_s = max(int(res_s.objective_passes), its_s)
        for denom, out in (
            (its_l - its_s, iter_reps),
            (passes_l - passes_s, pass_reps),
        ):
            if denom > 0 and dt_l > dt_s:
                m = _guard_marginal(bytes_per_pass, (dt_l - dt_s) / denom)
                if m is None:
                    rejected += 1
                else:
                    out.append(m)
            else:
                rejected += 1
    return {
        "marginal": float(np.median(iter_reps)) if iter_reps else None,
        "marginal_pass": float(np.median(pass_reps)) if pass_reps else None,
        "iter_reps": [round(m, 6) for m in sorted(iter_reps)],
        "pass_reps": [round(m, 6) for m in sorted(pass_reps)],
        "rejected": rejected,
    }


def _guard_marginal(bytes_per_pass: float, marginal: float | None):
    """A differenced marginal implying more than the HBM roofline is a
    timing artifact (wall noise or a replayed execution between the two
    solves), not a
    result — reject it so it reaches neither the utilization figures nor
    the reported marginal fields (the same never-report-impossible rule
    ``_timed_solves`` enforces on end-to-end times)."""
    if (
        marginal is not None
        and bytes_per_pass / marginal > HBM_ROOFLINE_BYTES_PER_S
    ):
        return None
    return marginal


def _materialize(result) -> float:
    """Force completion: pull the loss scalar AND the weights to host."""
    np.asarray(result.w)
    return float(result.value)


def _timed_solves(solve, bytes_lower_bound_per_run: float):
    """Median wall-clock of REPEATS fully-materialized solves.

    Returns (median seconds, final loss, last result) — callers reuse the
    result for quality metrics instead of running an extra untimed solve.

    ``bytes_lower_bound_per_run`` must be a TRUE lower bound on the HBM
    traffic of one solve — use ONE objective pass, not passes x configured
    iterations, because optimizers may legitimately stop early. Raises
    RuntimeError if the implied bandwidth breaches the roofline: an
    impossible number must never be reported as a result.
    """
    result = solve()  # compile + warm-up, excluded
    _materialize(result)
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        result = solve()
        value = _materialize(result)
        times.append(time.perf_counter() - t0)
    dt = float(np.median(times))
    implied = bytes_lower_bound_per_run / dt
    if implied > HBM_ROOFLINE_BYTES_PER_S:
        raise RuntimeError(
            f"timing artifact: measured {dt * 1e3:.3f} ms implies "
            f"{implied / 1e12:.1f} TB/s of HBM traffic (> roofline "
            f"{HBM_ROOFLINE_BYTES_PER_S / 1e12:.1f} TB/s); refusing to report"
        )
    return dt, value, result


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _trace_device_execs(fn, prefix: str) -> tuple[int, float] | None:
    """Run ``fn`` under a profiler trace; return (count, device_seconds)
    over DEVICE executions of compiled programs whose name starts with
    ``prefix``.

    This is how launch-count and device-time fields are produced: counted
    from the hardware trace of an actual run, never derived from the code
    shape (an asserted count can silently contradict what executes — r4's
    artifact claimed one launch per coordinate while the fused-outer path
    launched one per ITERATION). Device duration comes from the chip's own
    counters, so it is immune to host-side wall-clock noise (the
    documented ~3× session swings live in dispatch latency, not on the
    device). Returns None when the trace has no device-side process (e.g.
    CPU-only runs, where neither number would describe the accelerator).
    """
    import glob as _glob
    import gzip as _gzip
    import json as _json
    import tempfile

    import jax

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        with jax.profiler.trace(tdir):
            fn()
        count = 0
        device_ps = 0
        saw_device = False
        for path in _glob.glob(f"{tdir}/**/*.trace.json.gz", recursive=True):
            with _gzip.open(path) as f:
                trace = _json.load(f)
            events = trace.get("traceEvents", [])
            device_pids = {
                e.get("pid")
                for e in events
                if e.get("ph") == "M"
                and e.get("name") == "process_name"
                and "/device:" in e.get("args", {}).get("name", "")
            }
            if device_pids:
                saw_device = True
            for e in events:
                if (
                    e.get("ph") == "X"
                    and e.get("pid") in device_pids
                    and e.get("name", "").startswith(prefix)
                ):
                    count += 1
                    device_ps += int(
                        e.get("args", {}).get("device_duration_ps", "0")
                    )
    return (count, device_ps / 1e12) if saw_device else None


# ----------------------------------------------------------------- proxies


def _median_of_runs(fn, runs: int = 3) -> float:
    """Median of repeated one-core proxy measurements: the shared host's
    load spikes swing a single measurement ~1.7x (seen in round 2),
    which swings the vs-proxy ratio with it; the median of
    three runs is the honest middle in both directions."""
    return float(np.median([fn() for _ in range(runs)]))


def _proxy_logistic_dense(n: int, d: int, iters: int = 5) -> float:
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, d))
    y = (rng.uniform(size=n) < 0.5).astype(np.float64)
    w = np.zeros(d)
    for _ in range(1):
        p = 1.0 / (1.0 + np.exp(-(X @ w)))
        g = X.T @ (p - y)
    t0 = time.perf_counter()
    for _ in range(iters):
        p = 1.0 / (1.0 + np.exp(-(X @ w)))
        g = X.T @ (p - y)
        w = w - 1e-6 * g
    return n * iters / (time.perf_counter() - t0)


def _proxy_logistic_sparse(n: int, d: int, k: int, iters: int = 5) -> float:
    """One-core gather/scatter logistic pass on padded sparse rows."""
    rng = np.random.default_rng(0)
    idx = rng.integers(0, d, size=(n, k))
    val = rng.normal(size=(n, k))
    y = (rng.uniform(size=n) < 0.5).astype(np.float64)
    w = np.zeros(d)

    def passes():
        m = np.sum(val * w[idx], axis=1)
        p = 1.0 / (1.0 + np.exp(-m))
        g = np.zeros(d)
        np.add.at(g, idx.ravel(), (val * (p - y)[:, None]).ravel())
        return g

    passes()
    t0 = time.perf_counter()
    for _ in range(iters):
        w = w - 1e-6 * passes()
    return n * iters / (time.perf_counter() - t0)


def _proxy_linear_tron(n: int, d: int, iters: int = 5) -> float:
    """One-core linear value+grad+one-Hv pass per iteration (TRON shape)."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, d))
    y = rng.normal(size=n)
    w = np.zeros(d)
    v = rng.normal(size=d)
    for _ in range(1):  # warm: first-touch pages + BLAS buffers
        g = X.T @ (X @ w - y)
        hv = X.T @ (X @ v)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = X @ w - y
        g = X.T @ r
        hv = X.T @ (X @ v)  # one CG step's Hessian-vector product
        w = w - 1e-6 * (g + 1e-9 * hv)
    return n * iters / (time.perf_counter() - t0)


def _proxy_poisson_dense(n: int, d: int, iters: int = 5) -> float:
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, d))
    y = rng.poisson(1.0, size=n).astype(np.float64)
    w = np.zeros(d)
    for _ in range(1):  # warm: first-touch pages + BLAS buffers
        g = X.T @ (np.exp(np.clip(X @ w, -30, 30)) - y)
    t0 = time.perf_counter()
    for _ in range(iters):
        mu = np.exp(np.clip(X @ w, -30, 30))
        g = X.T @ (mu - y)
        w = w - 1e-8 * g
    return n * iters / (time.perf_counter() - t0)


# ----------------------------------------------------------------- configs


def bench_dense_logistic(jax, jnp, dtype=None):
    """Headline: dense logistic L-BFGS.

    The default stores X bfloat16 with float32 accumulation — HBM
    bandwidth is the bottleneck and halving it is ~2.2x on this chip with
    AUC unchanged (the quality gate enforces that); the f32 variant is kept
    as a separate config for round-over-round comparability."""
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.evaluation.evaluators import auc_roc
    from photon_ml_tpu.ops.batch import DenseBatch
    from photon_ml_tpu.ops.glm import make_objective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.optim import lbfgs_minimize
    from photon_ml_tpu.types import TaskType

    dtype = dtype or jnp.bfloat16
    n, d, iters = 1 << 20, 512, 30

    @jax.jit
    def make_data(key):
        k1, k2, k3 = jax.random.split(key, 3)
        X = jax.random.normal(k1, (n, d), jnp.float32)
        X = X.at[:, d - 1].set(1.0)
        w_true = jax.random.normal(k2, (d,), jnp.float32) * 0.5
        p = jax.nn.sigmoid(X @ w_true)
        y = (jax.random.uniform(k3, (n,)) < p).astype(jnp.float32)
        return X, y, w_true

    X, y, w_true = make_data(jax.random.PRNGKey(0))
    batch = DenseBatch(
        X=X.astype(dtype), labels=y, offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
    )
    obj = make_objective(
        batch, loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=1.0,
        intercept_index=d - 1, data_hints=(True, True),
    )
    cfg = OptimizerConfig(max_iterations=iters, tolerance=0.0)  # fixed trip
    w0 = jnp.zeros((d,), jnp.float32)

    itemsize = jnp.dtype(dtype).itemsize
    dt, value, res = _timed_solves(
        lambda: lbfgs_minimize(obj, w0, cfg),
        bytes_lower_bound_per_run=float(n) * d * itemsize,  # one objective pass
    )
    auc_model = float(auc_roc(batch.matvec(res.w), y))
    auc_true = float(auc_roc(X @ w_true, y))
    # the solver may stop before the configured trip count (converged
    # within arithmetic precision) — count the iterations it actually ran
    iters = max(int(res.iterations), 1)
    passes = max(int(res.objective_passes), iters)
    # marginal ms/iteration: difference a short solve out of the long one —
    # cancels the fixed per-solve dispatch+readback latency (0.1-0.25 s per
    # solve on the round-5 remote-attached chip; small on an attached one).
    # ALSO denominate by objective PASSES (full X reads incl. line-search
    # trials): the iteration-denominated marginal swings run-to-run with
    # the trial count (the round-2 table-vs-BENCH_DETAIL 5.1 ms vs
    # 2.0 ms "discrepancy" was exactly this); sec-per-PASS is the physical
    # unit, directly comparable to one HBM read of X.
    bytes_per_pass = float(n) * d * itemsize
    marginal = marginal_pass = None
    mreps = {"iter_reps": [], "pass_reps": [], "rejected": 0}
    short_T = 9
    if iters > short_T:
        mreps = _marginal_reps(
            lambda w, c: lbfgs_minimize(obj, w, c),
            w0, cfg, short_T, bytes_per_pass,
            main=(dt, iters, passes),
        )
        marginal = mreps["marginal"]
        marginal_pass = mreps["marginal_pass"]
    util = (
        _hbm_utilization(bytes_per_pass, marginal_pass)
        if marginal_pass is not None
        else _hbm_utilization(bytes_per_pass, dt / passes)
    )
    sps = n * iters / dt
    proxy = _median_of_runs(lambda: _proxy_logistic_dense(1 << 16, d))
    return {
        "samples_per_sec": round(sps, 1),
        "sec_per_solve": round(dt, 6),
        "sec_per_iteration": round(dt / iters, 6),
        "sec_per_iteration_marginal": (
            None if marginal is None else round(marginal, 6)
        ),
        "samples_per_sec_marginal": (
            None if marginal is None else round(n / marginal, 1)
        ),
        "sec_per_pass_marginal": (
            None if marginal_pass is None else round(marginal_pass, 6)
        ),
        "sec_per_pass_marginal_all": mreps["pass_reps"],
        "sec_per_iteration_marginal_all": mreps["iter_reps"],
        "marginal_reps_rejected": mreps["rejected"],
        **util,
        # full-data objective passes incl. line-search trials — the honest
        # work unit; sec/pass is the fused-kernel wall-clock per X read
        "objective_passes": passes,
        "samples_x_passes_per_sec": round(n * passes / dt, 1),
        "sec_per_pass": round(dt / passes, 6),
        "final_loss": round(value, 6),
        "auc": round(auc_model, 6),
        "auc_generating_model": round(auc_true, 6),
        "quality_ok": bool(auc_model >= 0.98 * auc_true),
        "vs_one_core_proxy": round(sps / proxy, 2),
        "dtype": str(jnp.dtype(dtype).name),
        "shape": {"n": n, "d": d, "iters": iters},
    }


def _make_sparse_problem(jax, jnp, n, d, k, seed):
    from photon_ml_tpu.ops.batch import SparseBatch

    @jax.jit
    def make_data(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        idx = jax.random.randint(k1, (n, k), 0, d, jnp.int32)
        val = jax.random.normal(k2, (n, k), jnp.float32)
        w_true = jax.random.normal(k3, (d,), jnp.float32) * 0.3
        m = jnp.sum(val * w_true[idx], axis=-1)
        y = (jax.random.uniform(k4, (n,)) < jax.nn.sigmoid(m)).astype(jnp.float32)
        return idx, val, y, w_true

    idx, val, y, w_true = make_data(jax.random.PRNGKey(seed))
    batch = SparseBatch(
        indices=idx, values=val, labels=y,
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32), num_features=d,
    )
    return batch, w_true


def _dtype_quality_parity(jnp, sparse_batch, iters, *,
                          auc_model, final_loss, w_model):
    """The precision ladder's model-quality gate: re-run the identical
    train-to-convergence fit on the f32 anchor rung and report AUC/loss
    deltas (plus RMSE of the margins against the anchor's — the
    regression-flavored delta the protocol names). Forces the env knob
    (env wins over the module global, so a sweep's child env is the only
    thing to override) and restores it afterwards; the tile caches key on
    the rung, so the rebuild can never reuse the reduced-precision
    layouts. The same dict is emitted as a ``quality_parity`` telemetry
    event so ``photon-ml-tpu report``/``--diff`` renders the gate next to
    the wall numbers."""
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.evaluation.evaluators import auc_roc
    from photon_ml_tpu.ops.glm import make_objective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.sparse_tiled import kernel_dtype, tile_sparse_batch
    from photon_ml_tpu.optim import lbfgs_minimize
    from photon_ml_tpu.types import TaskType

    rung = kernel_dtype()
    prev = os.environ.get("PHOTON_KERNEL_DTYPE")
    os.environ["PHOTON_KERNEL_DTYPE"] = "f32"
    try:
        batch32 = tile_sparse_batch(sparse_batch)
        obj32 = make_objective(
            batch32, loss_for_task(TaskType.LOGISTIC_REGRESSION),
            l2_weight=1.0, data_hints=(True, True),
        )
        d = sparse_batch.num_features
        res32 = lbfgs_minimize(
            obj32, jnp.zeros((d,), jnp.float32),
            OptimizerConfig(max_iterations=iters, tolerance=0.0),
        )
        auc32 = float(auc_roc(
            sparse_batch.matvec(res32.w), sparse_batch.labels
        ))
        loss32 = float(res32.value)
        m32 = np.asarray(sparse_batch.matvec(res32.w))
    finally:
        if prev is None:
            os.environ.pop("PHOTON_KERNEL_DTYPE", None)
        else:
            os.environ["PHOTON_KERNEL_DTYPE"] = prev
    # margins RMSE at the reduced rung's solution vs the anchor's —
    # computed on the XLA reference matvec so kernel error and model
    # drift are not conflated
    m_rung = np.asarray(sparse_batch.matvec(w_model))
    qp = {
        "kernel_dtype": rung,
        "auc": round(auc_model, 6),
        "auc_f32": round(auc32, 6),
        "auc_delta": round(auc_model - auc32, 6),
        "final_loss": round(final_loss, 6),
        "final_loss_f32": round(loss32, 6),
        "loss_rel_delta": round(
            (final_loss - loss32) / max(abs(loss32), 1e-12), 6
        ),
        "margins_rmse_vs_f32": round(
            float(np.sqrt(np.mean((m_rung - m32) ** 2))), 6
        ),
    }
    from photon_ml_tpu.obs.spans import emit_event

    emit_event("quality_parity", **qp)
    return qp


def _sparse_logistic_bench(jax, jnp, n, d, k, iters, densify_dtype,
                           tiled=False):
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.evaluation.evaluators import auc_roc
    from photon_ml_tpu.ops.batch import maybe_densify
    from photon_ml_tpu.ops.glm import make_objective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.optim.common import select_minimize_fn
    from photon_ml_tpu.types import TaskType

    sparse_batch, w_true = _make_sparse_problem(jax, jnp, n, d, k, seed=1)
    # The framework's ingest decision: one scatter at ingest buys MXU
    # matmuls every iteration when the dense matrix fits the HBM budget;
    # over-budget problems re-block into the tile-COO Pallas layout
    # (``tiled=True`` — SURVEY §7 "Sparse features on TPU").
    if tiled:
        from photon_ml_tpu.ops.sparse_tiled import tile_sparse_batch

        batch = tile_sparse_batch(sparse_batch)
    elif densify_dtype is not None:
        batch = maybe_densify(sparse_batch, dtype=densify_dtype)
    else:
        batch = sparse_batch
    densified = densify_dtype is not None and batch is not sparse_batch
    obj = make_objective(
        batch, loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=1.0,
        data_hints=(True, True),
    )
    cfg = OptimizerConfig(max_iterations=iters, tolerance=0.0)
    w0 = jnp.zeros((d,), jnp.float32)
    # the library's own selection boundary (optim/common): the returned
    # solver carries the obs/devcost capture twin, so the warm-up solve's
    # fresh executable lands its analytic flops/bytes in telemetry —
    # keyed by the active knob tuple (dtype rung, segments, groups/run)
    lbfgs_minimize, _ = select_minimize_fn(cfg)

    itemsize = 2 if densified and densify_dtype == jnp.bfloat16 else 4
    if tiled:
        # one value+grad pass streams BOTH write-major layouts (margins +
        # gradient): the packed streams are the traffic, at their ACTUAL
        # storage width (nbytes) — the precision ladder's bytes-moved win
        # is auditable straight from this number (f32: 12 B/nnz, int8: 4)
        bytes_per_pass = float(
            sum(
                int(c.m_arrays[0].nbytes + c.g_arrays[0].nbytes)
                for c in batch.chunks
            )
        )
    elif densified:
        bytes_per_pass = float(n) * d * itemsize
    else:
        bytes_per_pass = float(n) * k * 8
    dt, value, res = _timed_solves(
        lambda: lbfgs_minimize(obj, w0, cfg),
        bytes_lower_bound_per_run=float(bytes_per_pass),  # one objective pass
    )
    auc_model = float(auc_roc(sparse_batch.matvec(res.w), sparse_batch.labels))
    auc_true = float(auc_roc(sparse_batch.matvec(w_true), sparse_batch.labels))
    iters = max(int(res.iterations), 1)
    passes = max(int(res.objective_passes), iters)
    # marginal differencing: cancels the fixed per-solve dispatch
    # latency, exactly like the dense configs (VERDICT r3 weak #7) —
    # median of 3 independent pairs via the shared helper (r4 next-9)
    marginal = marginal_pass = None
    mreps = {"iter_reps": [], "pass_reps": [], "rejected": 0}
    short_T = max(iters // 3, 2)
    if iters > short_T and not QUICK:  # quick: one solve, no differencing
        mreps = _marginal_reps(
            lambda w, c: lbfgs_minimize(obj, w, c),
            w0, cfg, short_T, float(bytes_per_pass),
            main=(dt, iters, passes),
        )
        marginal = mreps["marginal"]
        marginal_pass = mreps["marginal_pass"]
    util = (
        _hbm_utilization(bytes_per_pass, marginal_pass)
        if marginal_pass is not None
        else _hbm_utilization(bytes_per_pass, dt / passes)
    )
    sps = n * iters / dt
    proxy = _median_of_runs(lambda: _proxy_logistic_sparse(1 << 15, d, k))
    constants = {}
    if tiled:
        import photon_ml_tpu.ops.sparse_tiled as st

        # the tuned constants this run's layouts+kernel were built with —
        # retune sweeps (RETUNE_ENV) are auditable from the artifact
        constants["kernel_constants"] = {
            "groups_per_step": st.GROUPS_PER_STEP,
            "segments_per_dma": st.SEGMENTS_PER_DMA,
            "groups_per_run": st.GROUPS_PER_RUN,
            "kernel_dtype": st.kernel_dtype(),
        }
        # the streamed bytes at the active rung: what a dtype sweep diffs
        constants["packed_stream_bytes_per_pass"] = int(bytes_per_pass)
        # run-padding overhead of the slab-run lever: padded stream nnz
        # over the raw nonzero count (GROUPS_PER_RUN=1 reproduces the
        # pre-run-batching padding exactly)
        raw_nnz = int(np.count_nonzero(np.asarray(sparse_batch.values)))
        packed_nnz = sum(
            int(c.m_arrays[0].shape[0] + c.g_arrays[0].shape[0]) * 128
            for c in batch.chunks
        ) // 2
        constants["stream_padding_ratio"] = round(packed_nnz / raw_nnz, 4)
        if st.kernel_dtype() != "f32":
            # quality-parity gate (BASELINE: never report speed without a
            # parity check): reduced rungs cannot be bitwise, so the SAME
            # train-to-convergence fit re-runs on the f32 anchor and the
            # AUC/loss deltas ride the result + telemetry block
            # cfg.max_iterations, NOT the local ``iters`` (rebound above
            # to the REALIZED count): an early-terminating reduced-rung
            # solve must not shrink the anchor's iteration budget, or the
            # anchor underfits and the gate reads falsely favorable
            constants["quality_parity"] = _dtype_quality_parity(
                jnp, sparse_batch, cfg.max_iterations,
                auc_model=auc_model, final_loss=float(value), w_model=res.w,
            )
    return {
        "samples_per_sec": round(sps, 1),
        "sec_per_solve": round(dt, 6),
        "sec_per_iteration": round(dt / iters, 6),
        "sec_per_iteration_marginal": (
            None if marginal is None else round(marginal, 6)
        ),
        "samples_per_sec_marginal": (
            None if marginal is None else round(n / marginal, 1)
        ),
        "sec_per_pass_marginal": (
            None if marginal_pass is None else round(marginal_pass, 6)
        ),
        # every KEPT differencing rep, sorted, plus the count lost to
        # jitter/roofline rejection — min/median and rep attrition both
        # visible for borderline-bar audits (VERDICT r4 next-9)
        "sec_per_pass_marginal_all": mreps["pass_reps"],
        "sec_per_iteration_marginal_all": mreps["iter_reps"],
        "marginal_reps_rejected": mreps["rejected"],
        "objective_passes": passes,
        "final_loss": round(value, 6),
        "auc": round(auc_model, 6),
        "auc_generating_model": round(auc_true, 6),
        "quality_ok": bool(auc_model >= 0.98 * auc_true),
        "vs_one_core_proxy": round(sps / proxy, 2),
        **util,
        "densified": densified,
        "tiled_coo_kernels": tiled,
        **constants,
        "shape": {"n": n, "d": d, "nnz_per_row": k, "iters": iters},
    }


def bench_a_sparse_logistic(jax, jnp):
    """Config A: a9a-shaped sparse binary logistic (scaled up ~16x in rows
    and ~33x in features), ingested sparse, auto-densified to bf16 for the
    solve (the framework's standard ingest decision at this size)."""
    if QUICK:
        return _sparse_logistic_bench(
            jax, jnp, n=1 << 13, d=2048, k=16, iters=8,
            densify_dtype=jnp.bfloat16,
        )
    return _sparse_logistic_bench(
        jax, jnp, n=1 << 19, d=4096, k=64, iters=20, densify_dtype=jnp.bfloat16
    )


def bench_a2_sparse_highdim(jax, jnp):
    """Config A2: high-dimensional sparse logistic (dense would need
    ~270 GB) on the tile-COO Pallas kernels (``ops/sparse_tiled.py``) —
    nonzeros re-blocked by (row-slab, col-slab) so margins/gradient run at
    VMEM vector rates instead of XLA's ~6e7 elem/s latency-bound
    gather/scatter (round 2 ran 0.37x ONE CPU core on that path).
    n=2^20 failed in round 5 (its scalar-prefetch streams overflowed
    SMEM); it has run since PR 21 (PERF.md), and the cell stays at 2^19
    until S0 resizes it. Quick mode keeps the kernel path (layout
    build + both directions end-to-end) at smoke shapes."""
    if QUICK:
        return _sparse_logistic_bench(
            jax, jnp, n=1 << 11, d=4096, k=4, iters=6, densify_dtype=None,
            tiled=True,
        )
    return _sparse_logistic_bench(
        jax, jnp, n=1 << 19, d=1 << 17, k=32, iters=30, densify_dtype=None,
        tiled=True,
    )


def bench_b_linear_tron(jax, jnp):
    """Config B: L2 linear regression under the TRON trust-region solver."""
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops.batch import DenseBatch
    from photon_ml_tpu.ops.glm import make_objective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.optim.tron import tron_minimize
    from photon_ml_tpu.types import TaskType

    n, d, iters, noise = 1 << 20, 256, 15, 0.1

    @jax.jit
    def make_data(key):
        k1, k2, k3 = jax.random.split(key, 3)
        X = jax.random.normal(k1, (n, d), jnp.float32)
        w_true = jax.random.normal(k2, (d,), jnp.float32) * 0.5
        y = X @ w_true + noise * jax.random.normal(k3, (n,), jnp.float32)
        return X, y, w_true

    X, y, w_true = make_data(jax.random.PRNGKey(2))
    batch = DenseBatch(
        X=X, labels=y, offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
    )
    obj = make_objective(batch, loss_for_task(TaskType.LINEAR_REGRESSION), l2_weight=1.0,
                         data_hints=(True, True))
    cfg = OptimizerConfig(max_iterations=iters, tolerance=0.0)
    w0 = jnp.zeros((d,), jnp.float32)

    dt, value, res = _timed_solves(
        lambda: tron_minimize(obj, w0, cfg),
        bytes_lower_bound_per_run=float(n) * d * 4,  # one objective pass
    )
    rmse = float(jnp.sqrt(jnp.mean((batch.matvec(res.w) - y) ** 2)))
    its = max(int(res.iterations), 1)
    passes = max(int(res.objective_passes), its)
    # marginal per PASS (one full X read: the fused value_and_grad and the
    # fused Hv each stream X once) — TRON's CG makes passes, not outer
    # iterations, the physical work unit; the solver counts them inside
    # the CG loop and the short-solve differencing cancels the
    # fixed dispatch latency (VERDICT r4 weak #4: B's roofline was derived
    # from END-TO-END time, which says nothing about kernel quality)
    marginal = marginal_pass = None
    mreps = {"iter_reps": [], "pass_reps": [], "rejected": 0}
    short_T = max(its // 3, 2)
    if its > short_T:
        mreps = _marginal_reps(
            lambda w, c: tron_minimize(obj, w, c),
            w0, cfg, short_T, float(n) * d * 4,
            main=(dt, its, passes),
        )
        marginal = mreps["marginal"]
        marginal_pass = mreps["marginal_pass"]
    sps = n * its / dt
    util = (
        _hbm_utilization(float(n) * d * 4, marginal_pass)
        if marginal_pass is not None
        else _hbm_utilization(float(n) * d * 4, dt / passes)
    )
    proxy = _median_of_runs(lambda: _proxy_linear_tron(1 << 16, d))
    return {
        "samples_per_sec": round(sps, 1),
        "sec_per_solve": round(dt, 6),
        "sec_per_iteration": round(dt / its, 6),
        "sec_per_iteration_marginal": (
            None if marginal is None else round(marginal, 6)
        ),
        "samples_per_sec_marginal": (
            None if marginal is None else round(n / marginal, 1)
        ),
        "objective_passes": passes,
        "sec_per_pass": round(dt / passes, 6),
        "sec_per_pass_marginal": (
            None if marginal_pass is None else round(marginal_pass, 6)
        ),
        "sec_per_pass_marginal_all": mreps["pass_reps"],
        "sec_per_iteration_marginal_all": mreps["iter_reps"],
        "marginal_reps_rejected": mreps["rejected"],
        "final_loss": round(value, 6),
        "rmse": round(rmse, 6),
        "noise_floor": noise,
        "quality_ok": bool(rmse <= 2.0 * noise),
        "vs_one_core_proxy": round(sps / proxy, 2),
        **util,
        "hbm_note": "bytes = one X read per PASS (value_and_grad or CG Hv, each fused to a single X stream); roofline from sec_per_pass_marginal",
        "shape": {"n": n, "d": d, "iters": its, "passes": passes},
    }


def bench_c_poisson(jax, jnp):
    """Config C: Poisson regression (count data), L-BFGS."""
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops.batch import DenseBatch
    from photon_ml_tpu.ops.glm import make_objective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.optim import lbfgs_minimize
    from photon_ml_tpu.types import TaskType

    n, d, iters = 1 << 20, 256, 20

    # Poisson sampling isn't in jax.random's stable API across versions at
    # fixed shapes; counts are generated on host at this modest size.
    # small weight scale keeps margins within the sampling clip, so w_true
    # is (near-)optimal for the unclipped objective and the loss comparison
    # below is a meaningful parity check
    rng = np.random.default_rng(3)
    X_h = rng.normal(size=(n, d)).astype(np.float32)
    w_true_h = (rng.normal(size=d) * 0.05).astype(np.float32)
    lam = np.exp(np.clip(X_h @ w_true_h, -10, 3))
    y_h = rng.poisson(lam).astype(np.float32)

    X, y = jnp.asarray(X_h), jnp.asarray(y_h)
    w_true = jnp.asarray(w_true_h)
    batch = DenseBatch(
        X=X, labels=y, offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
    )
    loss = loss_for_task(TaskType.POISSON_REGRESSION)
    obj = make_objective(batch, loss, l2_weight=1.0, data_hints=(True, True))
    cfg = OptimizerConfig(max_iterations=iters, tolerance=0.0)
    w0 = jnp.zeros((d,), jnp.float32)

    dt, value, res = _timed_solves(
        lambda: lbfgs_minimize(obj, w0, cfg),
        bytes_lower_bound_per_run=float(n) * d * 4,  # one objective pass
    )
    loss_true = float(obj.value(w_true))
    iters = max(int(res.iterations), 1)
    passes = max(int(res.objective_passes), iters)
    # marginal differencing, pass-denominated (VERDICT r3 weak #7) —
    # median of 3 pairs via the shared helper (r4 next-9)
    marginal = marginal_pass = None
    mreps = {"iter_reps": [], "pass_reps": [], "rejected": 0}
    short_T = max(iters // 3, 2)
    if iters > short_T:
        mreps = _marginal_reps(
            lambda w, c: lbfgs_minimize(obj, w, c),
            w0, cfg, short_T, float(n) * d * 4,
            main=(dt, iters, passes),
        )
        marginal = mreps["marginal"]
        marginal_pass = mreps["marginal_pass"]
    sps = n * iters / dt
    util = (
        _hbm_utilization(float(n) * d * 4, marginal_pass)
        if marginal_pass is not None
        else _hbm_utilization(float(n) * d * 4, dt / passes)
    )
    proxy = _median_of_runs(lambda: _proxy_poisson_dense(1 << 16, d))
    return {
        "samples_per_sec": round(sps, 1),
        "sec_per_solve": round(dt, 6),
        "sec_per_iteration": round(dt / iters, 6),
        "sec_per_iteration_marginal": (
            None if marginal is None else round(marginal, 6)
        ),
        "samples_per_sec_marginal": (
            None if marginal is None else round(n / marginal, 1)
        ),
        "sec_per_pass_marginal": (
            None if marginal_pass is None else round(marginal_pass, 6)
        ),
        "sec_per_pass_marginal_all": mreps["pass_reps"],
        "sec_per_iteration_marginal_all": mreps["iter_reps"],
        "marginal_reps_rejected": mreps["rejected"],
        "objective_passes": passes,
        "final_loss": round(value, 6),
        "loss_of_generating_model": round(loss_true, 6),
        "quality_ok": bool(value <= loss_true + 0.02 * abs(loss_true)),
        "vs_one_core_proxy": round(sps / proxy, 2),
        **util,
        "shape": {"n": n, "d": d, "iters": iters},
    }


def _game_setup(jax, jnp, n, effects):
    from photon_ml_tpu.config import (
        OptimizationConfig,
        OptimizerConfig,
        RegularizationContext,
    )
    from photon_ml_tpu.data.synthetic import synthetic_game_data
    from photon_ml_tpu.game import (
        CoordinateDescent,
        FixedEffectCoordinate,
        RandomEffectCoordinate,
        bucket_entities,
        group_by_entity,
        make_game_batch,
    )
    from photon_ml_tpu.types import RegularizationType, TaskType

    rng = np.random.default_rng(4)
    d_fixed = 64
    data = synthetic_game_data(rng, n, d_fixed=d_fixed, effects=effects)
    features = {"global": data.X}
    id_tags = {}
    for name in effects:
        features[f"per_{name}"] = data.entity_X[name]
        id_tags[name] = data.entity_ids[name]
    batch = make_game_batch(data.y, features, id_tags=id_tags)

    opt = OptimizerConfig(max_iterations=20, tolerance=1e-7)
    # per-entity solves use the framework's small-d solver: batched damped
    # Newton with exact (d, d) Cholesky steps — a handful of large fused
    # kernels per iteration instead of L-BFGS's many small sequential ones
    # (the quality gates below verify the same optimum is reached)
    from photon_ml_tpu.types import OptimizerType

    opt_re = OptimizerConfig(
        optimizer_type=OptimizerType.NEWTON_CHOLESKY,
        max_iterations=20, tolerance=1e-7,
    )
    coords = {
        "fixed": FixedEffectCoordinate(
            coordinate_id="fixed", batch=batch, feature_shard_id="global",
            config=OptimizationConfig(optimizer=opt),
            task_type=TaskType.LOGISTIC_REGRESSION,
            intercept_index=d_fixed,
        )
    }
    for name in effects:
        grouping = group_by_entity(np.asarray(batch.id_tags[name]))
        coords[f"per_{name}"] = RandomEffectCoordinate(
            coordinate_id=f"per_{name}", batch=batch,
            feature_shard_id=f"per_{name}", random_effect_type=name,
            config=OptimizationConfig(
                optimizer=opt_re,
                regularization=RegularizationContext(RegularizationType.L2),
                regularization_weight=1.0,
            ),
            grouping=grouping, buckets=bucket_entities(grouping),
            task_type=TaskType.LOGISTIC_REGRESSION,
            num_entities=grouping.num_entities,
        )
    cd = CoordinateDescent(coords, batch, TaskType.LOGISTIC_REGRESSION)
    return cd, batch, data


def _game_bench(jax, jnp, n, effects, outer_iters, long_factor=3):
    import dataclasses

    from photon_ml_tpu.evaluation.evaluators import auc_roc
    from photon_ml_tpu.game.models import FixedEffectModel
    from photon_ml_tpu.models.glm import Coefficients

    cd, batch, data = _game_setup(jax, jnp, n, effects)
    seq = ("fixed",) + tuple(f"per_{name}" for name in effects)

    def perturbed(model, seed: int):
        """A run-unique warm start: a runtime that DEDUPES executions with
        identical (program, argument) pairs lets repeated/differenced runs
        on identical state read back cached results and under-report.
        A coefficient-scale (sigma=1) perturbation makes every visit's
        values run-unique AND leaves real optimization work to do — a
        near-optimum warm start would let the solves converge instantly
        and time only launch overhead."""
        prng = np.random.default_rng(seed)
        models = {}
        for cid, sub in model.models.items():
            if isinstance(sub, FixedEffectModel):
                w = sub.model.coefficients.means
                w = w + jnp.asarray(
                    prng.normal(size=w.shape).astype(np.float32)
                )
                models[cid] = dataclasses.replace(
                    sub,
                    model=dataclasses.replace(
                        sub.model, coefficients=Coefficients(w, None)
                    ),
                )
            else:
                W = sub.coefficients
                W = W + jnp.asarray(
                    prng.normal(size=W.shape).astype(np.float32) * 0.3
                )
                models[cid] = dataclasses.replace(
                    sub, coefficients=W, variances=None
                )
        return dataclasses.replace(model, models=models)

    def timed_run(iters: int, seed: int, warm) -> tuple[float, object]:
        model0 = perturbed(warm, seed)
        t0 = time.perf_counter()
        result = cd.run(seq, iters, initial_model=model0)
        # fence: materialize every trained coefficient before stopping the clock
        for sub in result.model.models.values():
            np.asarray(sub.coefficient_means)
        return time.perf_counter() - t0, result

    warm = cd.run(seq, 2).model  # compile warm-up (cold + warm-start paths)
    timed_run(1, 999, warm)  # compile the warm-scores-init branch too
    long_iters = outer_iters * long_factor
    # compile every power-of-two chunk variant the timed lengths will use
    # (descent runs fused iterations in pow2 chunks; a variant compiling
    # inside a timed window would swamp the differencing)
    timed_run(outer_iters, 998, warm)
    timed_run(long_iters, 997, warm)
    dt, result = timed_run(outer_iters, 0, warm)

    # marginal sec/outer-iteration: difference a longer run out of a short
    # one — cancels the fixed per-run dispatch+readback latency
    # (0.1-0.25 s per sync in round 5), the same accounting the dense GLM
    # configs report. THREE independent estimates (fresh perturbed starts
    # each — an execution-dedup cache forbids reuse) so borderline pass/fail
    # is judged on min/median, not one draw of the documented session
    # noise (VERDICT r4 weak #8 / next-9).
    marginals = []
    for rep in range(3):
        dt_s, _ = timed_run(outer_iters, 100 + 2 * rep, warm)
        dt_l, _ = timed_run(long_iters, 101 + 2 * rep, warm)
        if dt_l > dt_s:
            marginals.append((dt_l - dt_s) / (long_iters - outer_iters))
    marginal = float(np.median(marginals)) if marginals else None

    # MEASURED launch count + device time: execute one run under the
    # profiler, count the descent-loop program's device executions and sum
    # their chip-counter durations — the previous artifact asserted
    # len(seq) for the launch count, which contradicted the whole-outer
    # fusion actually running (VERDICT r4 weak #3). Device time is the
    # noise-immune per-iteration cost: with iteration chunking the launch
    # latency amortizes toward zero, which pushes the wall marginal BELOW
    # the wall differencing noise floor — the chip counters stay exact.
    traced = _trace_device_execs(
        lambda: timed_run(long_iters, 200, warm), prefix="jit_fused"
    )
    launches_per_outer = None
    sec_per_outer_device = None
    if traced is not None:
        launch_count, device_sec = traced
        launches_per_outer = round(launch_count / long_iters, 3)
        if device_sec > 0.0:
            # duration-less traces (count still valid) keep device fields
            # absent rather than dividing by zero
            sec_per_outer_device = device_sec / long_iters

    # quality (outside the timed window — AUC compiles its own program)
    scores = result.model.score(batch)
    auc_model = float(auc_roc(scores, batch.labels))

    # generating model's AUC on the same rows: the quality ceiling
    margin = data.X @ data.w_fixed
    for name in effects:
        margin = margin + np.sum(
            data.w_entity[name][data.entity_ids[name]] * data.entity_X[name], axis=1
        )
    auc_true = float(auc_roc(jnp.asarray(margin), batch.labels))
    sec_per_outer = dt / outer_iters

    # primary marginal estimator: chip counters when available (immune to
    # host wall noise — with chunked launches the per-iteration
    # wall difference is SMALLER than the documented session jitter, so
    # the differencing reps spread ~20× around the device truth), else
    # the wall differencing median. marginal_method says which one this
    # artifact used; the raw wall reps stay visible either way.
    if sec_per_outer_device is not None:
        marginal_primary = sec_per_outer_device
        marginal_method = "device_counters"
    else:
        marginal_primary = marginal
        marginal_method = (
            "wall_differencing" if marginal is not None else None
        )
    # wall-rep note only describes the WALL estimator (the device-counter
    # primary, when present, stands on its own regardless)
    marginal_note = None if marginals else "wall_differencing_below_noise_floor"
    return {
        "sec_per_outer_iteration": round(sec_per_outer, 4),
        "sec_per_outer_iteration_marginal": (
            None if marginal_primary is None else round(marginal_primary, 4)
        ),
        "marginal_method": marginal_method,
        "sec_per_outer_iteration_marginal_wall_all": [
            round(m, 4) for m in sorted(marginals)
        ],
        "marginal_note": marginal_note,
        "samples_per_sec": round(n * outer_iters / dt, 1),
        "samples_per_sec_marginal": (
            None if marginal_primary is None
            else round(n / marginal_primary, 1)
        ),
        # chip-counter accounting (profiler trace of a fresh perturbed
        # run): immune to dispatch/wall noise; the honest
        # per-iteration number now that chunked launches push the wall
        # marginal below the differencing noise floor
        "sec_per_outer_iteration_device": (
            None if sec_per_outer_device is None
            else round(sec_per_outer_device, 4)
        ),
        "samples_per_sec_device": (
            None if sec_per_outer_device is None
            else round(n / sec_per_outer_device, 1)
        ),
        "auc": round(auc_model, 6),
        "auc_generating_model": round(auc_true, 6),
        "quality_ok": bool(auc_model >= 0.95 * auc_true),
        "vs_one_core_proxy": None,
        # MEASURED count of descent-program device executions per outer
        # iteration (profiler trace), NOT an assertion from the code shape
        "fused_launches_per_outer_iteration": launches_per_outer,
        "shape": {"n": n, "effects": {k: list(v) for k, v in effects.items()},
                   "outer_iters": outer_iters},
    }


def bench_d_game_fixed(jax, jnp):
    """Config D: GAME fixed-effect-only logistic (single-coordinate CD).

    3 vs 9 iterations chunk as [2,1] vs [8,1] — equal launch counts, so
    the differencing cancels dispatch latency (same reasoning as E)."""
    return _game_bench(jax, jnp, n=1 << 18, effects={}, outer_iters=3)


def bench_e_game_glmm(jax, jnp):
    """Config E: GAME GLMM — fixed + per-user + per-item random effects.

    outer_iters=4 with long=2× so BOTH differenced runs are exactly ONE
    pow2-chunked launch (r=4 vs r=8): equal launch counts make the wall
    differencing cancel dispatch latency instead of embedding it."""
    return _game_bench(
        jax, jnp, n=1 << 18,
        effects={"userId": (20000, 8), "itemId": (4000, 8)},
        outer_iters=4, long_factor=2,
    )


def bench_f_streaming(jax, jnp):
    """Config F: out-of-core pipeline smoke — host-chunked data streamed
    through the device per L-BFGS iteration (double-buffered device_put).
    When the host→device link is slow (0.02 GB/s in round 5, measured
    below), the reported samples/s measures the LINK, not the design;
    ingest_gbps is reported so the number is interpretable. On an
    attached chip (PCIe/DMA, tens of GB/s) the same path
    is compute-bound. Kept small: it validates the pipeline end-to-end on
    the bench chip every round."""
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.streaming import StreamingGLMObjective, dense_chunks
    from photon_ml_tpu.optim.host_lbfgs import host_lbfgs_minimize
    from photon_ml_tpu.types import TaskType

    n, d, iters, chunk_rows = 1 << 16, 256, 3, 1 << 14
    if QUICK:
        n, d, iters, chunk_rows = 1 << 13, 128, 2, 1 << 11

    rng = np.random.default_rng(5)
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = (rng.normal(size=d) * 0.3).astype(np.float32)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w_true)))).astype(np.float32)
    chunks = dense_chunks(X, y, chunk_rows=chunk_rows)

    # measured ingest bandwidth (one chunk); warm BOTH the transfer and the
    # sum kernel first so the timed window holds neither compile nor trace
    probe = jax.device_put(chunks[0])
    float(jnp.sum(probe["X"]))
    t0 = time.perf_counter()
    probe = jax.device_put(chunks[0])
    float(jnp.sum(probe["X"]))
    ingest_gbps = chunks[0]["X"].nbytes / (time.perf_counter() - t0) / 1e9

    sobj = StreamingGLMObjective(chunks, loss_for_task(TaskType.LOGISTIC_REGRESSION),
                                 num_features=d, l2_weight=1.0)
    cfg = OptimizerConfig(max_iterations=iters, tolerance=0.0)
    host_lbfgs_minimize(sobj, np.zeros(d, np.float32), cfg)  # warm-up/compile
    t0 = time.perf_counter()
    res = host_lbfgs_minimize(sobj, np.zeros(d, np.float32), cfg)
    dt = time.perf_counter() - t0
    its = max(int(res.iterations), 1)
    from photon_ml_tpu.ops import prefetch as _prefetch

    _cache_snapshot = _prefetch.cache_stats()  # one coherent snapshot
    return {
        "samples_per_sec": round(n * its / dt, 1),
        "sec_per_iteration": round(dt / its, 4),
        "final_loss": round(float(res.value), 6),
        "ingest_gbps_measured": round(ingest_gbps, 4),
        "transfer_limited": bool(ingest_gbps < 1.0),
        **_overlap_microbench(jax, jnp),
        **_hostpack_overlap_microbench(jax, jnp),
        # the host-ingest pipeline knobs this run used — the retune
        # surface (RETUNE_ENV_PREFETCH) round-trips through the JSON
        # contract exactly like the kernel constants, so a prefetch sweep
        # is auditable from stdout alone
        "prefetch": {
            "prefetch_depth": _prefetch.prefetch_depth(),
            "chunk_cache_budget_bytes": int(
                _prefetch.chunk_cache_budget_bytes()
            ),
            "chunk_cache": {
                k: _cache_snapshot[k]
                for k in ("device_hits", "host_hits", "misses", "evictions")
            },
        },
        "quality_ok": bool(np.isfinite(float(res.value))),
        "vs_one_core_proxy": None,
        "shape": {"n": n, "d": d, "iters": its, "chunk_rows": chunk_rows},
    }


def _overlap_microbench(jax, jnp):
    """Measures the double-buffering claim with a number (VERDICT r2 weak
    #5: the overlap was asserted, never measured). Small chunks + an
    artificially heavy per-chunk kernel sized near the transfer time, so
    overlap is resolvable even on a slow host link:

    - pipelined: issue chunk i+1's ``device_put`` before consuming chunk
      i's compute (exactly ``StreamingGLMObjective._stream``'s schedule) →
      wall ≈ max(transfer, compute) per chunk;
    - serialized: block on each chunk's compute before the next transfer →
      wall ≈ transfer + compute per chunk.

    ``overlap_ratio`` = serialized/pipelined — 1.0 means no overlap, ~2.0
    is the theoretical best when transfer ≈ compute. The per-chunk compute
    is sized ADAPTIVELY to the measured transfer time (a fixed size would
    be unresolvable across links whose speeds differ by 100x)."""
    import functools

    n_c, d_c, n_chunks = 1 << 11, 512, 6
    rng = np.random.default_rng(9)
    host_chunks = [
        rng.normal(size=(n_c, d_c)).astype(np.float32) for _ in range(n_chunks)
    ]
    w_mat = jnp.asarray(rng.normal(size=(d_c, d_c)).astype(np.float32) * 0.01)

    @functools.partial(jax.jit, static_argnames=("length",))
    def heavy_n(x, length):
        def body(c, _):
            return jnp.tanh(c @ w_mat), None
        c, _ = jax.lax.scan(body, x, None, length=length)
        return jnp.sum(c)

    # measure the transfer (median of 3, warm)
    dev = jax.device_put(host_chunks[0])
    float(jnp.sum(dev))
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        dev = jax.device_put(host_chunks[1])
        float(jnp.sum(dev))
        ts.append(time.perf_counter() - t0)
    t_transfer = float(np.median(ts))

    # marginal compute cost per scan step (difference cancels dispatch)
    x_dev = jax.device_put(host_chunks[0])
    float(heavy_n(x_dev, 32)); float(heavy_n(x_dev, 256))
    t0 = time.perf_counter(); float(heavy_n(x_dev, 32)); t32 = time.perf_counter() - t0
    t0 = time.perf_counter(); float(heavy_n(x_dev, 256)); t256 = time.perf_counter() - t0
    per_step = max((t256 - t32) / 224, 1e-7)
    repeat = int(np.clip(t_transfer / per_step, 32, 1 << 18))
    heavy = lambda x: heavy_n(x, repeat)

    def pipelined():
        acc = 0.0
        nxt = jax.device_put(host_chunks[0])
        outs = []
        for i in range(n_chunks):
            cur = nxt
            if i + 1 < n_chunks:
                nxt = jax.device_put(host_chunks[i + 1])
            outs.append(heavy(cur))
        for o in outs:
            acc += float(o)
        return acc

    def serialized():
        acc = 0.0
        for i in range(n_chunks):
            cur = jax.device_put(host_chunks[i])
            acc += float(heavy(cur))  # block before the next transfer
        return acc

    pipelined(); serialized()  # compile + warm both paths
    # alternate the schedules and take medians: the host link speed
    # drifts with host load, and a single back-to-back pair aliases that
    # drift into the ratio
    ts_pipe, ts_serial = [], []
    for _ in range(3):
        t0 = time.perf_counter(); pipelined()
        ts_pipe.append(time.perf_counter() - t0)
        t0 = time.perf_counter(); serialized()
        ts_serial.append(time.perf_counter() - t0)
    t_pipe = float(np.median(ts_pipe))
    t_serial = float(np.median(ts_serial))
    return {
        "overlap_sec_pipelined": round(t_pipe, 4),
        "overlap_sec_serialized": round(t_serial, 4),
        "overlap_ratio": round(t_serial / t_pipe, 3),
        "overlap_chunk_transfer_sec": round(t_transfer, 4),
        "overlap_compute_steps_per_chunk": repeat,
    }


def _hostpack_overlap_microbench(jax, jnp):
    """Measures the HOST-PACK overlap claim of the prefetch pipeline
    (``ops/prefetch``) with a number, the same way ``_overlap_microbench``
    measures transfer overlap: per chunk, a genuinely heavy host
    preparation (sort over the chunk — the shape of the tile-COO pack;
    GIL-releasing numpy) feeds a device kernel sized ADAPTIVELY near the
    measured pack time, so overlap is resolvable on any backend:

    - prefetch on (depth 2): chunk ``i+k``'s pack+``device_put`` runs on
      the worker pool while chunk ``i``'s compute is consumed — exactly
      the schedule every streamed consumer now runs;
    - prefetch off (depth 0): the synchronous pack→compute loop.

    ``hostpack_overlap_ratio`` = serialized/pipelined — 1.0 means no
    overlap, ~2.0 is the ceiling when pack ≈ compute. The per-stage wall
    counters (``utils/profiling`` — host-pack / device-put seconds on the
    workers, consumer-wait seconds on the main thread) are reported from
    the SAME pipelined run, so where the critical path went is observable,
    not asserted."""
    import functools

    from photon_ml_tpu.ops import prefetch
    from photon_ml_tpu.utils import profiling

    n_c, d_c, n_chunks = 1 << 11, 256, 6
    rng = np.random.default_rng(11)
    raw = [
        rng.normal(size=(n_c, d_c)).astype(np.float32)
        for _ in range(n_chunks)
    ]
    w_mat = jnp.asarray(rng.normal(size=(d_c, d_c)).astype(np.float32) * 0.01)

    def pack(i):
        # argsort+gather over every element: the tile-COO pack's shape
        # (host sort over the nonzero stream), releases the GIL
        x = raw[i]
        order = np.argsort(x, axis=0, kind="stable")
        return np.take_along_axis(x, order, axis=0)

    @functools.partial(jax.jit, static_argnames=("length",))
    def heavy_n(x, length):
        def body(c, _):
            return jnp.tanh(c @ w_mat), None
        c, _ = jax.lax.scan(body, x, None, length=length)
        return jnp.sum(c)

    # size the device compute near the measured pack time (fixed sizes
    # would be unresolvable across the 100x backend speed range)
    pack(0)
    t0 = time.perf_counter()
    for i in range(n_chunks):
        pack(i)
    t_pack = (time.perf_counter() - t0) / n_chunks
    x_dev = jax.device_put(raw[0])
    float(heavy_n(x_dev, 8)); float(heavy_n(x_dev, 64))
    t0 = time.perf_counter(); float(heavy_n(x_dev, 8)); t8 = time.perf_counter() - t0
    t0 = time.perf_counter(); float(heavy_n(x_dev, 64)); t64 = time.perf_counter() - t0
    per_step = max((t64 - t8) / 56, 1e-7)
    repeat = int(np.clip(t_pack / per_step, 8, 1 << 16))
    heavy = lambda x: heavy_n(x, repeat)

    def prepare(i):
        # timed_device_put keeps the pack/put stage split disjoint (the
        # put would otherwise double-count inside the worker's pack timer)
        return prefetch.timed_device_put(pack(i))

    def run(depth):
        acc = 0.0
        for x in prefetch.prefetch_iter(n_chunks, prepare, depth):
            acc += float(heavy(x))
        return acc

    run(2); run(0)  # compile + warm both schedules
    ts_on, ts_off = [], []
    for _ in range(3):  # alternate: link/load drift must not alias in
        t0 = time.perf_counter(); run(2)
        ts_on.append(time.perf_counter() - t0)
        t0 = time.perf_counter(); run(0)
        ts_off.append(time.perf_counter() - t0)
    # stage split from ONE dedicated pipelined run (the timing loop above
    # would mix the serialized runs' device_put seconds into the counters
    # and misattribute where the critical path went)
    profiling.reset_counters("prefetch.")
    run(2)
    stages = {
        k.split(".", 1)[1]: round(v["seconds"], 4)
        for k, v in profiling.counter_snapshot("prefetch.").items()
    }
    t_on = float(np.median(ts_on))
    t_off = float(np.median(ts_off))
    return {
        "hostpack_sec_pipelined": round(t_on, 4),
        "hostpack_sec_serialized": round(t_off, 4),
        "hostpack_overlap_ratio": round(t_off / t_on, 3),
        "hostpack_chunk_pack_sec": round(t_pack, 4),
        "hostpack_compute_steps_per_chunk": repeat,
        "hostpack_stage_seconds": stages,
    }


def bench_g_eval_auc(jax, jnp):
    """Config G: evaluator scale — exact sort-based AUC vs O(n) histogram
    (BUCKETED_AUC) on a 1e8-row synthetic score vector, with the
    exact-vs-bucketed delta reported (SURVEY §7 "Distributed AUC at 1B
    rows": the histogram path is the billion-row design; this entry pins
    its cost and its accuracy against the exact evaluator at the largest
    single-chip size)."""
    from photon_ml_tpu.evaluation.evaluators import auc_roc
    from photon_ml_tpu.evaluation.scalable import bucketed_auc

    n = 100_000_000

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(key)
        s = jax.random.normal(k1, (n,), jnp.float32)
        y = (jax.random.uniform(k2, (n,)) < jax.nn.sigmoid(1.5 * s)).astype(
            jnp.float32
        )
        return s, y

    s, y = make(jax.random.PRNGKey(7))

    def timed(f, a, b):
        v = float(f(a, b))  # compile + warm
        t0 = time.perf_counter()
        v = float(f(a, b))
        return time.perf_counter() - t0, v

    bucketed_f = jax.jit(lambda s, y: bucketed_auc(s, y))
    t_bucket, v_bucket = timed(bucketed_f, s, y)

    # exact-vs-bucketed accuracy at the largest size the exact sort
    # tolerates: the 1e8-row argsort kernel-faults this platform's TPU
    # worker (same class of fault as A2 at n=2^20 — reproduced twice), so
    # the delta is pinned at 2^24 rows where both paths run
    n_small = 1 << 24
    s_s, y_s = s[:n_small], y[:n_small]
    exact_f = jax.jit(lambda s, y: auc_roc(s, y))
    t_exact, v_exact = timed(exact_f, s_s, y_s)
    _, v_bucket_small = timed(bucketed_f, s_s, y_s)
    delta = abs(v_exact - v_bucket_small)
    return {
        "rows": n,
        "sec_bucketed_auc": round(t_bucket, 4),
        "rows_per_sec_bucketed": round(n / t_bucket, 1),
        "auc_bucketed": round(v_bucket, 8),
        "delta_rows": n_small,
        "sec_exact_sort_auc_at_delta_rows": round(t_exact, 4),
        "auc_exact_at_delta_rows": round(v_exact, 8),
        "exact_vs_bucketed_delta": round(delta, 8),
        "exact_sort_at_full_rows": "skipped: 1e8-row argsort kernel-faults "
                                   "this platform's TPU worker",
        "quality_ok": bool(delta < 1e-4),
        "vs_one_core_proxy": None,
    }


def bench_dense_logistic_f32(jax, jnp):
    """The headline shape with float32 feature storage (round-over-round
    comparability with earlier, pre-bf16 rounds)."""
    return bench_dense_logistic(jax, jnp, dtype=jnp.float32)


def bench_r_re_skew(jax, jnp):
    """Config R_re_skew: iteration-skewed random-effect bucket solves —
    the lane-compaction/launch-fusion testbed. A synthetic bucket set
    where a minority of entities (ill-conditioned features) need ~10× the
    L-BFGS iterations of the rest, so the single-launch vmapped solve
    burns most of its lane-iterations on already-converged entities.
    Reports the ``re_solve.*`` registry accounting (executed vs useful
    entity-iterations, launches, wasted-lane fraction) next to the wall —
    sweep ``PHOTON_RE_COMPACT_EVERY`` ∈ {0, 1, 4, 16} ×
    ``PHOTON_RE_FUSE_BUCKETS`` ∈ {0, 1}: results are BITWISE knob-
    invariant (tests assert it), only the schedule and counters move."""
    # the off-knob path counts executed/useful only when accounting is on
    # (it costs one tiny per-bucket readback the deferred-diagnostics
    # design otherwise skips)
    prev_accounting = os.environ.get("PHOTON_RE_ITER_ACCOUNTING")
    os.environ["PHOTON_RE_ITER_ACCOUNTING"] = "1"
    try:
        from photon_ml_tpu.config import OptimizerConfig
        from photon_ml_tpu.game import (
            DenseFeatures,
            bucket_entities,
            group_by_entity,
            train_random_effects,
        )
        from photon_ml_tpu.game import random_effect as re_mod
        from photon_ml_tpu.obs.metrics import REGISTRY
        from photon_ml_tpu.ops.losses import loss_for_task
        from photon_ml_tpu.types import TaskType

        E, C, d = (48, 16, 6) if QUICK else (1024, 32, 8)
        rng = np.random.default_rng(7)
        ids = np.repeat(np.arange(E), C).astype(np.int32)
        n = E * C
        X = rng.normal(size=(n, d)).astype(np.float32)
        # every 16th entity is SLOW: anisotropically scaled features make its
        # L-BFGS grind ~10× the iterations of the easy lanes
        slow = np.arange(0, E, 16)
        X[np.isin(ids, slow)] *= np.geomspace(1.0, 60.0, d).astype(np.float32)
        W_true = (rng.normal(size=(E, d)) * 0.5).astype(np.float32)
        margin = np.sum(W_true[ids] * X, axis=1)
        y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        cfg = OptimizerConfig(max_iterations=200, tolerance=1e-7)
        grouping = group_by_entity(ids, num_entities=E)
        buckets = bucket_entities(grouping)
        feats = DenseFeatures(X=jnp.asarray(X))
        offsets = np.zeros(n, np.float32)
        weights = np.ones(n, np.float32)

        def solve(seed):
            # run-unique warm start (coefficient-scale noise would skip real
            # work; 1e-3 noise keeps the full solve while defeating the
            # identical-(program, args) execution dedup)
            prng = np.random.default_rng(seed)
            w0 = jnp.asarray(
                prng.normal(size=(E, d)).astype(np.float32) * 1e-3
            )
            res = train_random_effects(
                feats, y, offsets, weights, buckets, E, loss, cfg,
                l2_weight=1.0, initial_coefficients=w0,
            )
            W = np.asarray(res.coefficients)  # fence: materialize the result
            return W, res

        solve(1)  # compile warm-up (off- and on-knob paths alike)
        REGISTRY.reset("re_solve.")
        t0 = time.perf_counter()
        _, res = solve(2)
        dt = time.perf_counter() - t0
        snap = REGISTRY.snapshot("re_solve.")

        def counter(name):
            return float(snap["counters"].get(name, {}).get("value", 0.0))

        executed = counter("re_solve.executed_entity_iterations")
        useful = counter("re_solve.useful_entity_iterations")
        iters = res.iterations
        conv_frac = float(np.mean(res.converged))

        # Entity-shard placement readout (deterministic host arithmetic —
        # gate-stable): the skew-aware plan vs naive round-robin over 4
        # virtual shards of the bench's Zipf entity distribution (this
        # config's own rows are uniform — its skew is in ITERATIONS —
        # so the Zipf ladder from the MULTICHIP_r06 capture is the
        # meaningful placement surface). The multi-process wall/overlap
        # numbers live in MULTICHIP_r06.json; here the planner's balance
        # advantage and the exchange-overlap instrument ride the --quick
        # JSON contract so `report gate` tripwires them from a smoke run
        # alone.
        from photon_ml_tpu.parallel.multihost import exchange_rows_async
        from photon_ml_tpu.parallel.placement import (
            plan_entity_placement,
            re_device_split_enabled,
            re_shard_enabled,
            re_split_factor,
            re_split_weight,
            record_placement_metrics,
        )

        entity_rows = _multichip_r06_sizes()
        shard_plan = plan_entity_placement(entity_rows, 4)
        rr_plan = plan_entity_placement(entity_rows, 4, skew_aware=False)
        record_placement_metrics(shard_plan)
        REGISTRY.gauge_set(
            "re_shard.round_robin_balance", rr_plan.balance
        )
        # exercise the issue→join path of the overlapped exchange once
        # (identity on one process) so the overlap-ratio gauge is present
        # in every capture — a missing instrument must trip the gate
        exchange_rows_async(
            {"probe": np.zeros(4, np.float32)},
            np.zeros(4, np.int64),
        ).result()

        return {
            "re_shard_balance": round(shard_plan.balance, 6),
            "re_shard_round_robin_balance": round(rr_plan.balance, 6),
            "re_shard_rows_max": float(shard_plan.loads.max()),
            "re_shard_rows_mean": float(shard_plan.loads.mean()),
            "sec_solve": round(dt, 4),
            "entity_iterations_per_sec": (
                None if dt <= 0 else round(float(iters.sum()) / dt, 1)
            ),
            "iterations_max": int(iters.max()),
            "iterations_median": float(np.median(iters)),
            "re_executed_entity_iterations": executed,
            "re_useful_entity_iterations": useful,
            "re_wasted_lane_fraction": (
                round(1.0 - useful / executed, 4) if executed > 0 else None
            ),
            "re_launches": counter("re_solve.launches"),
            "re_knobs": {
                "compact_every": int(re_mod.compact_every()),
                "fuse_buckets": int(bool(re_mod.fuse_buckets())),
                "re_shard": int(bool(re_shard_enabled())),
                "re_split": int(re_split_factor()),
                "re_device_split": int(bool(re_device_split_enabled())),
                "re_split_weight": str(re_split_weight()),
            },
            "converged_fraction": conv_frac,
            "quality_ok": bool(conv_frac == 1.0),
            "vs_one_core_proxy": None,
            "shape": {"entities": E, "capacity": C, "d": d},
        }
    finally:
        # restore: the flag must not leak into later in-process
        # configs or tests (it flips a host-sync readback globally)
        if prev_accounting is None:
            os.environ.pop("PHOTON_RE_ITER_ACCOUNTING", None)
        else:
            os.environ["PHOTON_RE_ITER_ACCOUNTING"] = prev_accounting


def bench_s_serve_zipf(jax, jnp):
    """Config S_serve_zipf: the online-serving operating point — a GAME
    model in the canonical photon-ml shape (fixed effect + per-member +
    per-item random effects) served from a ``HotModelStore`` whose
    hot-set budget is the default 25% of the random-effect coefficient
    bytes, under a Zipf(1) open-loop trace. Three phases, the first two
    bitwise:

    1. **score parity** — micro-window serve-path scores vs the batch
       ``score`` driver (``GameTransformer.transform``) over the SAME
       rows, including out-of-range entity ids and window padding;
       counted as u32-view mismatches (must be 0).
    2. **refresh parity** — ``refresh_entity`` (the chunked warm-start
       solve) vs ``solve_entity_offline`` (the one-shot minimize) on the
       same event bucket, both the L-BFGS and OWL-QN arms, PLUS every
       untouched entity's coefficient bytes across the refresh (must be
       0 mismatches).
    3. **the wall-clock trace** — open-loop Poisson arrivals at a fixed
       offered rate, Zipf(1) entity popularity on both effects; records
       p50/p99 latency, hot-set hit rate and micro-window occupancy (the
       numbers ``SERVE_r13.json`` commits and ``gate_quick.sh`` gates).
       The per-item effect is small enough to stay resident, which is
       what lifts the blended hit rate over the 0.8 acceptance line —
       the realistic serving property the bench is shaped around.

    Phase 1 doubles as program warm-up: it runs the same padded (B, d)
    window geometry the trace uses, so the trace measures serving, not
    first-compile."""
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.game.data import make_game_batch
    from photon_ml_tpu.game.models import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_ml_tpu.serve import (
        HotModelStore,
        open_loop_arrivals,
        run_serve_trace,
        zipf_entity_trace,
    )
    from photon_ml_tpu.serve.refresh import (
        entity_event_batch,
        refresh_entity,
        solve_entity_offline,
    )
    from photon_ml_tpu.serve.router import MicroWindowServer, ScoreRequest
    from photon_ml_tpu.transformers import GameTransformer

    E_m, E_i, d_fe, d_re, N, rate = (
        (128, 16, 8, 4, 2400, 3000.0) if QUICK
        else (1024, 64, 16, 8, 9000, 2000.0)
    )
    rng = np.random.default_rng(13)
    model = GameModel(models={
        "fixed": FixedEffectModel(
            model=GeneralizedLinearModel(Coefficients(
                jnp.asarray((rng.normal(size=d_fe) * 0.5).astype(np.float32))
            )),
            feature_shard_id="global",
        ),
        "per_member": RandomEffectModel(
            coefficients=jnp.asarray(
                (rng.normal(size=(E_m, d_re)) * 0.5).astype(np.float32)
            ),
            variances=None, random_effect_type="member",
            feature_shard_id="member_f",
        ),
        "per_item": RandomEffectModel(
            coefficients=jnp.asarray(
                (rng.normal(size=(E_i, d_re)) * 0.5).astype(np.float32)
            ),
            variances=None, random_effect_type="item",
            feature_shard_id="item_f",
        ),
    })

    member_ids = zipf_entity_trace(E_m, N, rng=np.random.default_rng(5))
    item_ids = zipf_entity_trace(E_i, N, rng=np.random.default_rng(6))
    Xg = rng.normal(size=(N, d_fe)).astype(np.float32)
    Xm = rng.normal(size=(N, d_re)).astype(np.float32)
    Xi = rng.normal(size=(N, d_re)).astype(np.float32)
    offs = (rng.normal(size=N) * 0.1).astype(np.float32)

    def request(i, member, item):
        return ScoreRequest(
            rid=int(i),
            features={"global": Xg[i], "member_f": Xm[i], "item_f": Xi[i]},
            id_tags={"member": int(member), "item": int(item)},
            offset=float(offs[i]),
        )

    # -- phase 1: serve-path score parity vs the batch driver (bitwise) ----
    par_n = min(N, 384)
    par_m = np.array(member_ids[:par_n])
    par_i = np.array(item_ids[:par_n])
    # out-of-range ids must score 0 for that effect in BOTH paths
    par_m[3] = -1
    par_m[17] = E_m + 5
    par_i[29] = E_i + 2
    par_store = HotModelStore(model)
    got: dict[int, float] = {}
    server = MicroWindowServer(
        par_store,
        on_scores=lambda w, s: got.update(
            {r.rid: float(v) for r, v in zip(w, s)}
        ),
    )
    for i in range(par_n):
        server.submit(request(i, par_m[i], par_i[i]))
    server.drain()  # the last partial window exercises the padding path
    serve_scores = np.asarray([got[i] for i in range(par_n)], np.float32)
    ref = GameTransformer(model).transform(make_game_batch(
        labels=np.zeros(par_n, np.float32),
        features={"global": Xg[:par_n], "member_f": Xm[:par_n],
                  "item_f": Xi[:par_n]},
        id_tags={"member": par_m, "item": par_i},
        offsets=offs[:par_n],
    ))
    ref = np.asarray(jax.block_until_ready(ref), np.float32)
    score_mismatches = int(np.sum(
        serve_scores.view(np.uint32) != ref.view(np.uint32)
    ))

    # -- phase 2: incremental refresh parity (bitwise, both solver arms) ---
    cfg = OptimizerConfig(max_iterations=50, tolerance=1e-8)
    refresh_mismatches = 0
    W0 = np.asarray(model["per_member"].coefficients)
    for entity, l1 in ((int(member_ids[0]), 0.0), (int(member_ids[1]), 0.05)):
        k = 24
        Xe = rng.normal(size=(k, d_re)).astype(np.float32)
        margin = Xe @ W0[entity]
        ye = (
            rng.uniform(size=k) < 1.0 / (1.0 + np.exp(-margin))
        ).astype(np.float32)
        batch = entity_event_batch(Xe, ye)
        updated, res = refresh_entity(
            model, "per_member", entity, batch, cfg,
            l2_weight=1.0, l1_weight=l1,
        )
        off = solve_entity_offline(
            model["per_member"], entity, batch, cfg,
            l2_weight=1.0, l1_weight=l1,
        )
        a = np.asarray(res.w, np.float32)
        b = np.asarray(off.w, np.float32)
        refresh_mismatches += int(np.sum(
            a.view(np.uint32) != b.view(np.uint32)
        ))
        # untouched entities: every OTHER row's bytes survive the refresh
        W1 = np.asarray(updated["per_member"].coefficients)
        mask = np.ones(E_m, bool)
        mask[entity] = False
        refresh_mismatches += int(np.sum(
            W0[mask].view(np.uint32) != W1[mask].view(np.uint32)
        ))

    # -- phase 3: the wall-clock open-loop Zipf trace ----------------------
    # fresh store: clean lifetime hit-rate accounting (phase 1 already
    # compiled the window programs — same padded geometry)
    trace_store = HotModelStore(model)
    arrivals = open_loop_arrivals(N, rate, rng=np.random.default_rng(7))
    reqs = []
    for i in range(N):
        r = request(i, member_ids[i], item_ids[i])
        r.arrival_s = float(arrivals[i])
        reqs.append(r)
    trace = run_serve_trace(trace_store, reqs)

    return {
        "sec_trace": round(trace["elapsed_s"], 4),
        "offered_rate_hz": rate,
        "achieved_rate_hz": (
            None if trace["elapsed_s"] <= 0
            else round(N / trace["elapsed_s"], 1)
        ),
        "serve_requests": trace["requests"],
        "serve_windows": trace["windows"],
        "serve_latency_p50_ms": round(trace["latency_p50_ms"], 4),
        "serve_latency_p99_ms": round(trace["latency_p99_ms"], 4),
        "serve_latency_mean_ms": round(trace["latency_mean_ms"], 4),
        "serve_hot_hit_rate": round(trace["hot_hit_rate"], 4),
        "serve_window_occupancy_mean": round(
            trace["window_occupancy_mean"], 4
        ),
        "serve_hot_budget_bytes": trace_store.budget_bytes(),
        "serve_total_re_bytes": trace_store.total_re_bytes,
        "score_parity_mismatches": score_mismatches,
        "refresh_parity_mismatches": refresh_mismatches,
        "quality_ok": bool(
            score_mismatches == 0 and refresh_mismatches == 0
        ),
        "vs_one_core_proxy": None,
        "shape": {"members": E_m, "items": E_i, "d_fe": d_fe,
                  "d_re": d_re, "requests": N, "rate_hz": rate},
    }


def bench_x_stream(jax, jnp):
    """Config X_stream: fit-with-per-visit-validation through the unified
    streaming executor (``ops/stream_executor``), A/B inside ONE process:

    - **off arm** (``PHOTON_STREAM_EXECUTOR=0``): the pre-executor wiring
      — the training objective streams through the PR-3 storage-keyed
      chunk cache, and the per-iteration validation objective replays the
      SAME chunk content through its own fresh host arrays (a different
      loader's copy of the shard), which the storage-keyed cache cannot
      dedup: the validation working set transfers its full bytes on top
      of the training set's.
    - **on arm** (``PHOTON_STREAM_EXECUTOR=1``): both consumers ride the
      executor's multi-tenant arbiter, keyed by chunk CONTENT fingerprint
      × pack dtype — the validation stream re-uses the training stream's
      resident device buffers (shared hits), so cross-stream transfer
      bytes drop by the shared-chunk fraction (~half here: two
      content-identical working sets, one transfer).

    Both arms run the identical L-BFGS fit (per-iteration validation =
    the held-out streamed objective value over the copied chunks) and
    must agree BITWISE on the final weights and on every per-visit
    validation value — the executor reorders PREPARATION only. Transfer
    traffic is counted from the byte counters each arm's cache actually
    charges (``prefetch.cache.miss_bytes`` off,
    ``stream.cache.miss_bytes`` on — BOTH streams route through the
    counted path in both arms); consumer-wait seconds come from the
    shared ``prefetch.consumer_wait_s`` stage timer."""
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.ops import prefetch, stream_executor
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.streaming import (
        StreamingGLMObjective,
        dense_chunks,
    )
    from photon_ml_tpu.optim.host_lbfgs import host_lbfgs_minimize
    from photon_ml_tpu.types import TaskType

    n, d, chunk_rows, iters = (
        (6000, 24, 512, 4) if QUICK else (40000, 48, 2048, 6)
    )
    rng = np.random.default_rng(14)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, d - 1] = 1.0
    w_true = (rng.normal(size=d) * 0.5).astype(np.float32)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-(X @ w_true)))).astype(
        np.float32
    )
    chunks = dense_chunks(X, y, chunk_rows=chunk_rows)
    # the validation loader's OWN copies: content-equal, storage-distinct
    # (exactly what a second reader of the same shard produces)
    val_chunks = [{k: np.array(v) for k, v in c.items()} for c in chunks]
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    cfg = OptimizerConfig(max_iterations=iters, tolerance=0.0)

    def counter(name: str) -> float:
        c = REGISTRY.snapshot()["counters"].get(name)
        return float(c["value"]) if c else 0.0

    def timer_s(name: str) -> float:
        t = REGISTRY.snapshot()["timers"].get(name)
        return float(t["seconds"]) if t else 0.0

    def arm(executor_on: bool) -> dict:
        os.environ["PHOTON_STREAM_EXECUTOR"] = "1" if executor_on else "0"
        prefetch.clear_cache()
        stream_executor.clear()
        xfer_key = (
            "stream.cache.miss_bytes" if executor_on
            else "prefetch.cache.miss_bytes"
        )
        x0 = counter(xfer_key)
        wait0 = timer_s("prefetch.consumer_wait_s")
        # the validation loader's objective over ITS copies of the chunks
        val_obj = StreamingGLMObjective(
            val_chunks, loss, num_features=d, l2_weight=1.0,
            intercept_index=d - 1,
        )
        visits: list[float] = []

        def validate(it, w, value):
            visits.append(float(val_obj.value(jnp.asarray(w))))

        t0 = time.perf_counter()
        sobj = StreamingGLMObjective(
            chunks, loss, num_features=d, l2_weight=1.0,
            intercept_index=d - 1,
        )
        res = host_lbfgs_minimize(
            sobj, np.zeros(d, np.float32), cfg,
            iteration_callback=validate,
        )
        elapsed = time.perf_counter() - t0
        return {
            "w": np.asarray(res.w, np.float32),
            "visits": visits,
            "transfer_bytes": counter(xfer_key) - x0,
            "consumer_wait_s": timer_s("prefetch.consumer_wait_s") - wait0,
            "sec": elapsed,
            "cache": (
                stream_executor.cache_stats() if executor_on
                else prefetch.cache_stats()
            ),
        }

    prev = os.environ.get("PHOTON_STREAM_EXECUTOR")
    try:
        off = arm(False)
        on = arm(True)
    finally:
        if prev is None:
            os.environ.pop("PHOTON_STREAM_EXECUTOR", None)
        else:
            os.environ["PHOTON_STREAM_EXECUTOR"] = prev
        prefetch.clear_cache()
        stream_executor.clear()

    mismatches = int(
        np.sum(off["w"].view(np.uint32) != on["w"].view(np.uint32))
    )
    off_v = np.asarray(off["visits"], np.float32)
    on_v = np.asarray(on["visits"], np.float32)
    if off_v.shape == on_v.shape:
        mismatches += int(
            np.sum(off_v.view(np.uint32) != on_v.view(np.uint32))
        )
    else:
        mismatches += 1
    dedup_bytes = off["transfer_bytes"] - on["transfer_bytes"]
    on_cache = on["cache"]
    return {
        "sec_off": round(off["sec"], 4),
        "sec_on": round(on["sec"], 4),
        "transfer_bytes_off": int(off["transfer_bytes"]),
        "transfer_bytes_on": int(on["transfer_bytes"]),
        "dedup_bytes": int(dedup_bytes),
        "dedup_fraction": (
            round(dedup_bytes / off["transfer_bytes"], 4)
            if off["transfer_bytes"] else 0.0
        ),
        "consumer_wait_s_off": round(off["consumer_wait_s"], 4),
        "consumer_wait_s_on": round(on["consumer_wait_s"], 4),
        "stream_cache_hits": int(on_cache["hits"]),
        "stream_cache_shared_hits": int(on_cache["shared_hits"]),
        "stream_cache_misses": int(on_cache["misses"]),
        "stream_cache_evictions": int(on_cache["evictions"]),
        "parity_mismatches": mismatches,
        "quality_ok": bool(mismatches == 0 and dedup_bytes > 0),
        "vs_one_core_proxy": None,
        "shape": {"rows": n, "features": d, "chunk_rows": chunk_rows,
                  "chunks": len(chunks), "iterations": iters},
    }


CONFIGS = {
    "headline_dense_logistic": bench_dense_logistic,
    "dense_logistic_f32": bench_dense_logistic_f32,
    "A_sparse_logistic": bench_a_sparse_logistic,
    "A2_sparse_highdim": bench_a2_sparse_highdim,
    "B_linear_tron": bench_b_linear_tron,
    "C_poisson": bench_c_poisson,
    "D_game_fixed_only": bench_d_game_fixed,
    "E_game_glmm": bench_e_game_glmm,
    "F_streaming": bench_f_streaming,
    "G_eval_auc_scale": bench_g_eval_auc,
    "R_re_skew": bench_r_re_skew,
    "S_serve_zipf": bench_s_serve_zipf,
    "X_stream": bench_x_stream,
}


def _apply_retune_env() -> None:
    """Apply the env-var retune surfaces to their module globals
    (call-time-read, so layout builder, kernel, prefetch pipeline and
    random-effect solve loop all track): RETUNE_ENV → sparse-tiled kernel
    constants, RETUNE_ENV_PREFETCH → host-ingest pipeline knobs,
    RETUNE_ENV_RE → random-effect solve knobs."""
    import importlib

    surfaces = (
        (RETUNE_ENV, "photon_ml_tpu.ops.sparse_tiled", "kernel constants"),
        (RETUNE_ENV_PREFETCH, "photon_ml_tpu.ops.prefetch", "prefetch knobs"),
        (RETUNE_ENV_RE, "photon_ml_tpu.game.random_effect",
         "random-effect knobs"),
        (RETUNE_ENV_SHARD, "photon_ml_tpu.parallel.placement",
         "entity-shard knobs"),
        (RETUNE_ENV_SERVE, "photon_ml_tpu.serve.store", "serving knobs"),
        (RETUNE_ENV_STREAM, "photon_ml_tpu.ops.stream_executor",
         "stream-executor knobs"),
    )
    # runtime twin of the `photon-ml-tpu lint` knob pass: a sweep over a
    # knob that is not registered (or not fully wired through its mirror
    # surfaces) must fail BEFORE any config runs, not after a blind sweep
    from photon_ml_tpu.analysis.registry import check_retune_tables

    check_retune_tables({
        "RETUNE_ENV": RETUNE_ENV,
        "RETUNE_ENV_PREFETCH": RETUNE_ENV_PREFETCH,
        "RETUNE_ENV_RE": RETUNE_ENV_RE,
        "RETUNE_ENV_SHARD": RETUNE_ENV_SHARD,
        "RETUNE_ENV_SERVE": RETUNE_ENV_SERVE,
        "RETUNE_ENV_STREAM": RETUNE_ENV_STREAM,
    })
    def _parse(var: str, raw: str):
        if var == "PHOTON_KERNEL_DTYPE":
            # the one string knob: strict-parse (reject unknown rungs
            # loudly) exactly like the strict-int parse of its siblings
            from photon_ml_tpu.ops.sparse_tiled import validate_kernel_dtype

            return validate_kernel_dtype(raw)
        if var == "PHOTON_RE_COMBINE":
            from photon_ml_tpu.game.random_effect import _RE_COMBINE_MODES

            if raw not in _RE_COMBINE_MODES:
                raise ValueError(
                    f"PHOTON_RE_COMBINE must be one of "
                    f"{_RE_COMBINE_MODES}, got {raw!r}"
                )
            return raw
        if var == "PHOTON_RE_REPLAN_IMBALANCE":
            return float(raw)
        if var == "PHOTON_SERVE_MAX_WAIT_MS":
            return float(raw)
        if var in ("PHOTON_STREAM_PRIORITY", "PHOTON_STREAM_SHARE"):
            # spec strings ("name=value,..."): strict-validate through the
            # executor's own parsers, then keep the raw spec (the
            # accessors re-parse at call time)
            from photon_ml_tpu.ops.stream_executor import _parse_spec

            _parse_spec(
                raw, var,
                int if var == "PHOTON_STREAM_PRIORITY" else float,
            )
            return raw
        if var == "PHOTON_RE_PROJECT":
            from photon_ml_tpu.game.projector import _RE_PROJECT_MODES

            if raw not in _RE_PROJECT_MODES:
                raise ValueError(
                    f"PHOTON_RE_PROJECT must be one of "
                    f"{_RE_PROJECT_MODES}, got {raw!r}"
                )
            return raw
        if var == "PHOTON_RE_SPLIT_WEIGHT":
            from photon_ml_tpu.parallel.placement import _SPLIT_WEIGHT_MODES

            if raw not in _SPLIT_WEIGHT_MODES:
                raise ValueError(
                    f"PHOTON_RE_SPLIT_WEIGHT must be one of "
                    f"{_SPLIT_WEIGHT_MODES}, got {raw!r}"
                )
            return raw
        if var == "PHOTON_FE_SPLIT_WEIGHT":
            from photon_ml_tpu.data.index_map import _FE_SPLIT_WEIGHT_MODES

            if raw not in _FE_SPLIT_WEIGHT_MODES:
                raise ValueError(
                    f"PHOTON_FE_SPLIT_WEIGHT must be one of "
                    f"{_FE_SPLIT_WEIGHT_MODES}, got {raw!r}"
                )
            return raw
        return int(raw)

    # the projection knobs ride RETUNE_ENV_RE (they retune the RE solve)
    # but their module globals live with the ladder derivation
    module_overrides = {
        "PHOTON_RE_PROJECT": "photon_ml_tpu.game.projector",
        "PHOTON_RE_PROJECT_DIM": "photon_ml_tpu.game.projector",
        # the fixed-effect range-shard knobs ride RETUNE_ENV_SHARD (they
        # retune cross-process placement) but live with the partitioner
        "PHOTON_FE_SHARD": "photon_ml_tpu.data.index_map",
        "PHOTON_FE_SPLIT_WEIGHT": "photon_ml_tpu.data.index_map",
        # the serving knobs ride RETUNE_ENV_SERVE; the micro-window pair
        # lives with the router and the refresh trigger with the refresher
        "PHOTON_SERVE_MAX_BATCH": "photon_ml_tpu.serve.router",
        "PHOTON_SERVE_MAX_WAIT_MS": "photon_ml_tpu.serve.router",
        "PHOTON_SERVE_REFRESH_EVERY": "photon_ml_tpu.serve.refresh",
    }
    for env_map, module_name, label in surfaces:
        pending = {
            attr: (var, _parse(var, os.environ[var]))
            for var, attr in env_map.items()
            if os.environ.get(var)
        }
        if pending:
            for attr, (var, value) in pending.items():
                mod = importlib.import_module(
                    module_overrides.get(var, module_name)
                )
                setattr(mod, attr, value)
            _log(
                f"[bench] retuned {label} from env: "
                f"{ {a: v for a, (_, v) in pending.items()} }"
            )


def _telemetry_block() -> dict:
    """The run's telemetry snapshot for the JSON contract: the full
    metrics-registry snapshot (counters / gauges / histograms / timers —
    the same dict a ``--telemetry-dir`` run embeds in its ``run_end``
    record; the legacy stage counters ARE ``metrics["timers"]``, one
    source of truth) and the knob values the process executed under. One
    coherent block per config subprocess, so a sweep can diff cache
    traffic, compile wall and stage seconds from stdout alone."""
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.obs.sink import SCHEMA_VERSION, _knob_snapshot

    return {
        "schema_version": SCHEMA_VERSION,
        "metrics": REGISTRY.snapshot(),
        "knobs": _knob_snapshot(),
    }


def _run_one(name: str, quick: bool = False,
             telemetry_dir: str | None = None) -> None:
    """Child mode: run one config, print its result JSON on stdout.

    ``telemetry_dir`` archives this config's full telemetry JSONL next to
    the bench artifact (one run file per config, run_id = config name —
    the ROADMAP sweep-backlog format); quick and telemetry runs also
    enable analytic device-cost capture (``PHOTON_DEVCOST``, overridable
    from the environment) so ``devcost.*`` gauges ride the JSON contract
    and ``photon-ml-tpu report gate`` can tripwire byte/flop regressions
    from a ``--quick`` capture alone."""
    global QUICK, REPEATS
    from photon_ml_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()  # before the config's first compile
    if quick:
        QUICK = True
        REPEATS = 1
    if quick or telemetry_dir:
        os.environ.setdefault("PHOTON_DEVCOST", "1")
    _apply_retune_env()
    # installs the jax.monitoring compile listener BEFORE the config's
    # first compile — configs that never touch an obs-importing module
    # (pure-ops configs like A) would otherwise report no jax.compile_s
    import photon_ml_tpu.obs as obs

    run_path = None
    if telemetry_dir:
        run_path = obs.configure(telemetry_dir, run_id=name)

    import jax
    import jax.numpy as jnp

    try:
        result = CONFIGS[name](jax, jnp)
        result["telemetry"] = _telemetry_block()
        if "quality_parity" in result:
            # the quality gate rides the telemetry block too (the protocol's
            # "never report speed without a parity check" — a dtype sweep
            # diffs quality from the same block it diffs cache traffic from)
            result["telemetry"]["quality_parity"] = result["quality_parity"]
        if telemetry_dir:
            # round-trip the archive location through the JSON contract
            result["telemetry"]["telemetry_dir"] = telemetry_dir
            result["telemetry"]["run_path"] = run_path
    finally:
        obs.shutdown()  # emit run_end + flush durably (no-op when disabled)
    print(json.dumps(result))


def _run_config_subprocess(name: str, quick: bool = False,
                           telemetry_dir: str | None = None) -> dict:
    """Run one config in a fresh subprocess; return its result dict (or an
    {"error": ...} dict — an impossible number or a crash is reported,
    never faked). Factored out so the contract test can stub the child."""
    import subprocess

    here = os.path.abspath(__file__)
    argv = [sys.executable, here, "--config", name] + (
        ["--quick"] if quick else []
    ) + (["--telemetry-dir", telemetry_dir] if telemetry_dir else [])
    try:
        proc = subprocess.run(
            argv, capture_output=True, text=True, timeout=900,
        )
        sys.stderr.write(proc.stderr)
        if proc.returncode == 0:
            return json.loads(proc.stdout.strip().splitlines()[-1])
        tail = (proc.stderr or "").strip().splitlines()[-3:]
        return {"error": f"rc={proc.returncode}: {' | '.join(tail)}"}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def main(quick: bool = False, telemetry_dir: str | None = None) -> None:
    # Each config runs in its OWN subprocess, one at a time. A chip belongs
    # to one process: this parent never imports jax (module level imports
    # numpy only, and nothing on main()'s path imports photon_ml_tpu), so
    # it holds no device and each child gets the chip alone; two children
    # at once would fail or hang on it. Device memory is fully released
    # between configs — closure-captured batches baked into cached
    # executables otherwise accumulate until the process runs out of HBM —
    # and one config crashing cannot poison the rest.
    results: dict[str, dict] = {}
    names = QUICK_CONFIGS if quick else tuple(CONFIGS)
    for name in names:
        _log(f"[bench] {name} ...")
        if telemetry_dir:
            results[name] = _run_config_subprocess(
                name, quick=quick, telemetry_dir=telemetry_dir
            )
        else:
            # keyword shape kept stable: the contract test stubs this
            # callable with a (name, quick=...) lambda
            results[name] = _run_config_subprocess(name, quick=quick)
        _log(f"[bench] {name}: {json.dumps(results[name])[:300]}")

    head = results.get("headline_dense_logistic", {})
    if not quick:
        # quick mode writes NO artifacts: toy-shape numbers must never
        # overwrite the measured table or BENCH_DETAIL.json
        detail_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_DETAIL.json"
        )
        with open(detail_path, "w") as f:
            json.dump(results, f, indent=2)

    print(
        json.dumps(
            {
                "metric": "glm_logistic_lbfgs_samples_per_sec_per_chip",
                "value": head.get("samples_per_sec"),
                "unit": "samples/s",
                "vs_baseline": head.get("vs_one_core_proxy"),
                "quick": quick,
                "telemetry_dir": telemetry_dir,
                "quality": {
                    "auc": head.get("auc"),
                    "auc_generating_model": head.get("auc_generating_model"),
                    "quality_ok": head.get("quality_ok"),
                },
                "configs": results,
            }
        )
    )
    bad = [k for k, v in results.items() if "error" in v or v.get("quality_ok") is False]
    if bad:
        _log(f"[bench] configs with errors/quality failures: {bad}")
        sys.exit(1)


# -- MULTICHIP_r06: entity-sharded multi-process random-effect capture ------
#
# `python bench.py --multichip-r06` spawns a loopback multi-process CPU
# harness (gloo collectives, one process per virtual chip — the same
# recipe as tests/test_multihost.py) running the streamed GAME
# random-effect coordinate on a Zipf-skewed entity distribution, once
# per arm: PHOTON_RE_SHARD=0 (today's modular owners, blocking
# exchanges) and PHOTON_RE_SHARD=1 (skew-aware placement + overlapped
# P2P exchange). Writes MULTICHIP_r06.json and archives each arm's
# telemetry JSONL (process 0's sink) under --telemetry-dir. Also records
# the pure-planner balance table (skew-aware vs round-robin over
# P ∈ {2, 4, 8} shards of the same distribution) — the ≤1.15×-vs-≥1.5×
# acceptance numbers, deterministic on any host.

MULTICHIP_R06_ENTITIES = 64
MULTICHIP_R06_D = 3


def _multichip_r06_sizes() -> "np.ndarray":
    """Zipf-ish per-entity row counts (head entity ~300 rows, tail 2):
    skewed enough that round-robin loses a full shard to the head
    (balance ≥ 1.5× at 4 shards) while LPT stays ≤ 1.15×."""
    E = MULTICHIP_R06_ENTITIES
    return np.maximum(
        (300.0 / (1 + np.arange(E)) ** 1.1).astype(np.int64), 2
    )


def _multichip_r06_dataset():
    rng = np.random.default_rng(606)
    sizes = _multichip_r06_sizes()
    ids = np.repeat(np.arange(len(sizes)), sizes).astype(np.int64)
    ids = ids[rng.permutation(len(ids))]
    n = len(ids)
    X = rng.normal(size=(n, MULTICHIP_R06_D)).astype(np.float32)
    W_true = (rng.normal(size=(len(sizes), MULTICHIP_R06_D)) * 0.5).astype(
        np.float32
    )
    margin = np.sum(W_true[ids] * X, axis=1)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(
        np.float32
    )
    return ids, X, y


def _multichip_r06_worker(
    coordinator: str, pid: int, nproc: int, arm: str,
    telemetry_dir: str | None,
) -> None:
    """One harness process of the MULTICHIP_r06 capture (child mode)."""
    import hashlib

    _multichip_worker_setup(
        coordinator, pid, nproc,
        knobs={"PHOTON_RE_SHARD": "1" if arm == "skew_aware" else "0"},
    )
    import photon_ml_tpu.obs as obs

    run_path = None
    if telemetry_dir:
        run_path = obs.configure(
            telemetry_dir, run_id=f"MULTICHIP_r06_{arm}_P{nproc}"
        )
    try:
        from photon_ml_tpu.config import (
            GameTrainingConfig,
            OptimizationConfig,
            OptimizerConfig,
            RandomEffectCoordinateConfig,
            RegularizationContext,
        )
        from photon_ml_tpu.game.streaming import (
            StreamedGameData,
            StreamedGameTrainer,
        )
        from photon_ml_tpu.types import RegularizationType, TaskType

        ids, X, y = _multichip_r06_dataset()
        n = len(ids)
        bounds = np.linspace(0, n, nproc + 1).astype(int)
        lo, hi = bounds[pid], bounds[pid + 1]
        opt = OptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=25, tolerance=1e-8),
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        )
        cfg = GameTrainingConfig(
            task_type=TaskType.LOGISTIC_REGRESSION,
            coordinate_update_sequence=("per_entity",),
            coordinate_descent_iterations=2,
            fixed_effect_coordinates={},
            random_effect_coordinates={
                "per_entity": RandomEffectCoordinateConfig(
                    random_effect_type="eid", feature_shard_id="r",
                    optimization=opt,
                )
            },
        )
        data = StreamedGameData(
            labels=y[lo:hi],
            features={"r": X[lo:hi]},
            id_tags={"eid": ids[lo:hi]},
        )
        trainer = StreamedGameTrainer(
            cfg, chunk_rows=1 << 16, multihost=nproc > 1
        )
        t0 = time.perf_counter()
        model, info = trainer.fit(data)
        wall = time.perf_counter() - t0
        W = np.asarray(model.models["per_entity"].coefficients, np.float32)

        from photon_ml_tpu.obs.metrics import REGISTRY
        from photon_ml_tpu.parallel.multihost import LAST_EXCHANGE_STATS

        snap = REGISTRY.snapshot()
        gauges = {
            k: v for k, v in snap.get("gauges", {}).items()
            if k.startswith("re_shard.")
        }
        timers = {
            k: v.get("seconds")
            for k, v in snap.get("timers", {}).items()
            if k.startswith("re_exchange.")
        }
        print("RESULT " + json.dumps({
            "pid": pid,
            "arm": arm,
            "wall_s": round(wall, 4),
            "W_sha256": hashlib.sha256(
                np.ascontiguousarray(W).tobytes()
            ).hexdigest(),
            "loss": info["per_entity"].final_loss,
            "converged": bool(info["per_entity"].converged),
            "gauges": gauges,
            "exchange_timers": timers,
            "last_exchange_transport": LAST_EXCHANGE_STATS.get("transport"),
            "run_path": run_path,
        }))
    finally:
        if telemetry_dir:
            obs.shutdown()


def _multichip_worker_setup(
    coordinator: str, pid: int, nproc: int, knobs: dict | None = None,
):
    """Shared child-process prelude for every ``--multichip-rNN-worker``
    (r06..r12 hand-rolled identical copies of this before it was
    extracted): pin the CPU platform BEFORE the first jax import, apply
    the leg's knob environment (a None value UNSETS the variable —
    "knob absent" is a distinct arm from "knob 0"), select the gloo
    host-collective transport, and join the coordinator. Returns the
    configured ``jax`` module."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    for k, v in (knobs or {}).items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = str(v)
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    from photon_ml_tpu.parallel.multihost import initialize_multihost

    initialize_multihost(coordinator, num_processes=nproc, process_id=pid)
    return jax


def _worker_probes():
    """The per-worker telemetry/bitwise probes every multichip leg
    re-declared inline: ``counter`` (registry counter value, 0.0 when
    absent), ``gauge`` (raw registry gauge, ``default`` when absent —
    callers that want a float pass ``default=0.0``) and ``sha`` (the
    canonical contiguous-bytes digest the bitwise contracts compare)."""
    import hashlib

    from photon_ml_tpu.obs.metrics import REGISTRY

    def counter(name: str) -> float:
        return float(
            REGISTRY.snapshot().get("counters", {})
            .get(name, {}).get("value", 0.0)
        )

    def gauge(name: str, default=None):
        return REGISTRY.snapshot().get("gauges", {}).get(name, default)

    def sha(a) -> str:
        return hashlib.sha256(
            np.ascontiguousarray(np.asarray(a)).tobytes()
        ).hexdigest()

    return counter, gauge, sha


def _collect_worker_results(
    worker_flag: str, nproc: int, label: str, timeout_s: int = 900,
    nproc_arg: int | None = None,
) -> dict[int, dict]:
    """Parent-side results collection every ``run_multichip_rNN`` leg
    hand-rolled: spawn the loopback workers for ``worker_flag`` with the
    standard ``coordinator pid nproc`` argv tail, unwrap each RESULT
    line's ``results`` payload, and fail loudly on a missing process
    (a worker that died after its peers completed their collectives).
    ``nproc_arg`` overrides the argv nproc (the r09-style single-process
    reference leg)."""
    raw = _spawn_loopback_workers(
        lambda coordinator, pid: (
            [worker_flag, coordinator, str(pid),
             str(nproc if nproc_arg is None else nproc_arg)]
        ),
        nproc, label, timeout_s=timeout_s,
    )
    per_pid = {pid: r["results"] for pid, r in raw.items()}
    if set(per_pid) != set(range(nproc)):
        raise RuntimeError(f"missing worker results: have {sorted(per_pid)}")
    return per_pid


def _spawn_loopback_workers(
    worker_args, nproc: int, label: str, timeout_s: int = 900,
) -> dict[int, dict]:
    """Shared multi-process loopback harness scaffolding (r06/r07/r08):
    spawn ``nproc`` ``bench.py`` workers against one fresh loopback
    coordinator, each with FILE-backed stdout/stderr (a worker that
    fills an unread 64 KB pipe — chatty XLA/gloo logging — would stall
    inside a collective and deadlock the whole arm), wait sequentially,
    and on ANY failure kill the stragglers (one dead worker must not
    orphan its peers, who block forever on the missing process's
    collectives). ``worker_args(coordinator, pid)`` yields each
    worker's argv tail. Returns the merged ``{pid: RESULT-line JSON}``
    map."""
    import socket
    import subprocess
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    tmpdir = tempfile.mkdtemp(prefix=f"{label}_")
    logs = []
    procs = []
    outs = []
    # the try opens BEFORE the spawn loop: a Popen that raises mid-loop
    # (fork/exec failure) must still kill the already-spawned workers —
    # they would otherwise block forever inside initialize_multihost
    # waiting for a coordinator quorum that can never complete
    try:
        for pid in range(nproc):
            out_f = open(os.path.join(tmpdir, f"{label}-{pid}.out"), "w+")
            err_f = open(os.path.join(tmpdir, f"{label}-{pid}.err"), "w+")
            logs.append((out_f, err_f))
            procs.append(subprocess.Popen(
                [sys.executable, os.path.join(here, "bench.py")]
                + list(worker_args(coordinator, pid)),
                stdout=out_f, stderr=err_f, text=True, env=env, cwd=here,
            ))
        for p, (out_f, err_f) in zip(procs, logs):
            p.wait(timeout=timeout_s)
            out_f.seek(0)
            err_f.seek(0)
            out = out_f.read()
            if p.returncode != 0:
                raise RuntimeError(
                    f"{label} worker failed (rc={p.returncode}):\n"
                    f"{out[-2000:]}\n{err_f.read()[-4000:]}"
                )
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for out_f, err_f in logs:
            out_f.close()
            err_f.close()
    per_pid: dict[int, dict] = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT "):
                r = json.loads(line[len("RESULT "):])
                per_pid[r["pid"]] = r
    return per_pid


def run_multichip_r06(
    out_path: str = "MULTICHIP_r07.json",
    telemetry_dir: str | None = "telemetry_r06",
    nproc: int = 2,
) -> dict:
    """Drive the multi-process capture (parent mode) and write the
    capture artifact (MULTICHIP_r07.json — the r06 recipe's successor:
    same two arms, plus the FLEET telemetry readout. The skew-aware arm
    runs with PHOTON_RE_SHARD=1, so fleet telemetry archives every
    process's ``.p<k>`` shard next to the process-0 JSONLs in
    ``telemetry_r06/`` and the doc records the merged straggler/P2P
    summary from ``report fleet``)."""
    here = os.path.dirname(os.path.abspath(__file__))

    arms: dict[str, dict] = {}
    for arm in ("baseline_modulo", "skew_aware"):
        # run ids are fixed strings: clear any previous capture's
        # canonical file AND .p<k> shards first, so a re-capture under
        # different knobs (or after a crash) can never join a fresh
        # canonical run with a stale shard of the same name
        if telemetry_dir:
            import glob as _glob

            for stale in _glob.glob(os.path.join(
                here, telemetry_dir,
                f"run-MULTICHIP_r06_{arm}_P{nproc}*.jsonl",
            )):
                os.remove(stale)
        per_pid = _spawn_loopback_workers(
            lambda coordinator, pid: (
                ["--multichip-r06-worker", coordinator, str(pid),
                 str(nproc), arm]
                + (["--telemetry-dir", telemetry_dir]
                   if telemetry_dir else [])
            ),
            nproc, f"multichip_r06_{arm}",
        )
        arms[arm] = {
            "per_process": per_pid,
            "bitwise_identical_across_processes": (
                len({r["W_sha256"] for r in per_pid.values()}) == 1
            ),
        }
        # merged fleet readout (skew-aware arm only: RE_SHARD=1 turns
        # fleet telemetry on, so processes 1..N-1 wrote .p<k> shards):
        # per-process phase walls, straggler summary, correlated P2P
        # link table, unmatched-event health — the numbers the on-chip
        # sweep gates across the whole fleet
        if telemetry_dir:
            try:
                from photon_ml_tpu.obs.report import (
                    fleet_run_paths,
                    gate_metrics_from_fleet,
                    summarize_fleet,
                )

                paths = fleet_run_paths(
                    os.path.join(here, telemetry_dir),
                    run_id=f"MULTICHIP_r06_{arm}_P{nproc}",
                )
                fs = summarize_fleet(paths)
                arms[arm]["fleet"] = {
                    "shards": [os.path.basename(p) for p in paths],
                    "process_count": fs["process_count"],
                    "straggler": fs["straggler"],
                    "phases": {
                        ph: {
                            k: agg[k]
                            for k in ("per_process", "max_s", "imbalance",
                                      "slowest")
                        }
                        for ph, agg in fs["phases"].items()
                    },
                    "p2p": {
                        k: v for k, v in fs["p2p"].items()
                        if k != "links"
                    },
                    "p2p_links": fs["p2p"]["links"],
                    "overlap": fs["overlap"],
                    "exchange": fs["exchange"],
                    "gate_metrics": gate_metrics_from_fleet(fs),
                }
            except Exception as e:  # the capture must still land
                arms[arm]["fleet"] = {"error": str(e)}

    # pure-planner balance table on the same distribution: the
    # ≤1.15×-vs-≥1.5× acceptance readout, deterministic on any host
    from photon_ml_tpu.parallel.placement import plan_entity_placement

    sizes = _multichip_r06_sizes()
    table = {}
    for P_ in (2, 4, 8):
        sk = plan_entity_placement(sizes, P_)
        rr = plan_entity_placement(sizes, P_, skew_aware=False)
        table[str(P_)] = {
            "skew_aware_balance": round(sk.balance, 4),
            "round_robin_balance": round(rr.balance, 4),
            "skew_aware_rows_max": float(sk.loads.max()),
            "round_robin_rows_max": float(rr.loads.max()),
        }
    doc = {
        "round": 7,
        "what": (
            "entity-sharded multi-process random-effect solves with "
            "FLEET telemetry: skew-aware bucket placement + overlapped "
            "P2P exchange, per-process sink shards, correlated P2P "
            "link events and the merged straggler readout "
            f"(streamed GAME, Zipf E config, {nproc}-process loopback "
            "CPU harness, gloo collectives)"
        ),
        "entities": MULTICHIP_R06_ENTITIES,
        "rows_total": int(_multichip_r06_sizes().sum()),
        "nproc": nproc,
        "arms": arms,
        "planner_balance_by_shards": table,
        "acceptance": {
            "skew_balance_4_shards": table["4"]["skew_aware_balance"],
            "round_robin_balance_4_shards": table["4"]["round_robin_balance"],
            "skew_le_1.15": table["4"]["skew_aware_balance"] <= 1.15,
            "round_robin_ge_1.5": table["4"]["round_robin_balance"] >= 1.5,
        },
        "telemetry_dir": telemetry_dir,
        "note": (
            "CPU wall at toy scale is dispatch/exchange-latency bound — "
            "recorded per the BASELINE protocol either way; the on-chip "
            "sweep decides defaults (ROADMAP backlog)"
        ),
    }
    with open(os.path.join(here, out_path), "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    _log(f"[bench] MULTICHIP_r06 capture written to {out_path}")
    return doc


# -- MULTICHIP_r08: owner-segment combine A/B (PHOTON_RE_COMBINE) -----------
#
# `python bench.py --multichip-r08` spawns the gloo loopback harness (4
# processes by default — the acceptance config) and runs the IN-MEMORY
# owned-bucket random-effect solve (train_random_effects under the
# global mesh, PHOTON_RE_SHARD=1) twice per ladder rung: once with the
# dense fixed-layout combine (PHOTON_RE_COMBINE=allreduce) and once
# with the owner-segment framed-P2P combine (=segments). The ladder is
# million-entity-SHAPED: real entity counts (every entity a live lane),
# Zipf-shaped row counts scaled down so a CPU harness finishes; the doc
# extrapolates the measured per-process combine bytes to E = 1e6 from
# the top rung's slope (the combine payload is exactly linear in E).
# Writes MULTICHIP_r08.json with per-rung per-arm wall/bytes, bitwise
# cross-arm + cross-process checks, and a flat gate_metrics section
# `scripts/gate_quick.sh` gates against BASELINE_combine_cpu.json.

MULTICHIP_R08_D = 4
MULTICHIP_R08_LADDER = (1024, 8192)
MULTICHIP_R08_NPROC = 4


def _multichip_r08_sizes(E: int) -> "np.ndarray":
    """Zipf(~1) per-entity row counts spanning the WHOLE entity range
    (head entity ≈ E^0.9 rows, rank-i entity ≈ (E/i)^0.9, no clamp
    plateau): the property that matters for the combine A/B is the real
    Zipf one — row mass per capacity OCTAVE is roughly constant while
    entity population doubles toward the tail — so the bucket ladder's
    ~8 merged classes (the placement atoms; same-capacity buckets
    co-own by the fusion-group constraint) carry comparable row loads
    and LPT spreads them across shards, exactly the million-entity
    placement shape with rows scaled down (~10 rows/entity mean)."""
    return np.maximum(
        ((E / (1.0 + np.arange(E))) ** 0.9).astype(np.int64), 1
    )


def _multichip_r08_dataset(E: int):
    rng = np.random.default_rng(808)
    sizes = _multichip_r08_sizes(E)
    ids = np.repeat(np.arange(E), sizes).astype(np.int64)
    ids = ids[rng.permutation(len(ids))]
    n = len(ids)
    X = rng.normal(size=(n, MULTICHIP_R08_D)).astype(np.float32)
    W_true = (rng.normal(size=(E, MULTICHIP_R08_D)) * 0.5).astype(
        np.float32
    )
    margin = np.sum(W_true[ids] * X, axis=1)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(
        np.float32
    )
    return ids, X, y


def _multichip_r08_worker(coordinator: str, pid: int, nproc: int) -> None:
    """One harness process of the combine A/B (child mode): every
    process holds the full (replicated) in-memory dataset — exactly the
    in-memory trainer's contract — and dispatches only its owned
    buckets; the combine is the code under test."""
    jax = _multichip_worker_setup(
        coordinator, pid, nproc, knobs={"PHOTON_RE_SHARD": "1"},
    )
    import hashlib

    import jax.numpy as jnp

    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.game import bucket_entities, group_by_entity
    from photon_ml_tpu.game.data import DenseFeatures
    from photon_ml_tpu.game.random_effect import train_random_effects
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.parallel import data_mesh
    from photon_ml_tpu.types import TaskType, VarianceComputationType

    mesh = data_mesh()
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    counter, _, _ = _worker_probes()

    results: dict[str, dict] = {}
    for E in MULTICHIP_R08_LADDER:
        ids, X, y = _multichip_r08_dataset(E)
        n = len(ids)
        buckets = bucket_entities(group_by_entity(ids, num_entities=E))
        for arm in ("allreduce", "segments"):
            os.environ["PHOTON_RE_COMBINE"] = arm
            b0 = counter("re_combine.bytes_sent")
            t0 = time.perf_counter()
            res = train_random_effects(
                features=DenseFeatures(X=jnp.asarray(X)),
                labels=y,
                offsets=np.zeros(n, np.float32),
                weights=np.ones(n, np.float32),
                buckets=buckets,
                num_entities=E,
                loss=loss,
                config=OptimizerConfig(max_iterations=4, tolerance=1e-8),
                l2_weight=1.0,
                variance_computation=VarianceComputationType.SIMPLE,
                mesh=mesh,
            )
            W = np.asarray(jax.device_get(res.coefficients), np.float32)
            V = np.asarray(jax.device_get(res.variances), np.float32)
            it = np.asarray(res.iterations, np.int64)
            wall = time.perf_counter() - t0
            results[f"E{E}/{arm}"] = {
                "wall_s": round(wall, 4),
                "combine_bytes_sent": counter("re_combine.bytes_sent") - b0,
                "W_sha256": hashlib.sha256(
                    np.ascontiguousarray(W).tobytes()
                ).hexdigest(),
                "V_sha256": hashlib.sha256(
                    np.ascontiguousarray(V).tobytes()
                ).hexdigest(),
                "it_sha256": hashlib.sha256(
                    np.ascontiguousarray(it).tobytes()
                ).hexdigest(),
            }
    print("RESULT " + json.dumps({"pid": pid, "results": results}))


def run_multichip_r08(
    out_path: str = "MULTICHIP_r08.json", nproc: int = MULTICHIP_R08_NPROC
) -> dict:
    """Drive the combine-A/B capture (parent mode) and write
    MULTICHIP_r08.json. Asserts the bitwise contract in-harness (same
    model hashes across processes AND across combine arms) and records
    the per-process combine-byte reduction the acceptance bound
    (≥ (P−1)/P · 50%) is written against."""
    here = os.path.dirname(os.path.abspath(__file__))

    per_pid = _collect_worker_results(
        "--multichip-r08-worker", nproc, "multichip_r08"
    )

    rungs: dict[str, dict] = {}
    gate_metrics: dict[str, float] = {}
    all_bitwise = True
    for E in MULTICHIP_R08_LADDER:
        rung: dict = {"entities": E,
                      "rows_total": int(_multichip_r08_sizes(E).sum())}
        for arm in ("allreduce", "segments"):
            key = f"E{E}/{arm}"
            walls = [per_pid[p][key]["wall_s"] for p in range(nproc)]
            bts = [per_pid[p][key]["combine_bytes_sent"]
                   for p in range(nproc)]
            shas = {
                field: {per_pid[p][key][field] for p in range(nproc)}
                for field in ("W_sha256", "V_sha256", "it_sha256")
            }
            consistent = all(len(s) == 1 for s in shas.values())
            all_bitwise &= consistent
            rung[arm] = {
                "wall_s_max": max(walls),
                # mean = fleet combine traffic / P (the O(P·E·d) vs
                # O(E·d) axis); max = the busiest owner — bounded below
                # by bucket-atomic placement (the Zipf tail class is one
                # placement atom), the ROADMAP "placement below process
                # granularity" item, NOT a transport property
                "combine_bytes_per_process_mean": sum(bts) / nproc,
                "combine_bytes_per_process_max": max(bts),
                "combine_bytes_per_process": {
                    str(p): bts[p] for p in range(nproc)
                },
                "bitwise_identical_across_processes": consistent,
            }
        same_model = all(
            per_pid[0][f"E{E}/allreduce"][f] ==
            per_pid[0][f"E{E}/segments"][f]
            for f in ("W_sha256", "V_sha256", "it_sha256")
        )
        all_bitwise &= same_model
        rung["bitwise_identical_across_arms"] = same_model
        for stat in ("mean", "max"):
            b_all = rung["allreduce"][f"combine_bytes_per_process_{stat}"]
            b_seg = rung["segments"][f"combine_bytes_per_process_{stat}"]
            rung[f"bytes_reduction_fraction_{stat}"] = (
                1.0 - b_seg / b_all if b_all else 0.0
            )
            gate_metrics[f"E{E}/re_combine/bytes_sent_{stat}/allreduce"] = (
                float(b_all)
            )
            gate_metrics[f"E{E}/re_combine/bytes_sent_{stat}/segments"] = (
                float(b_seg)
            )
        rungs[str(E)] = rung
    top = rungs[str(MULTICHIP_R08_LADDER[-1])]
    reduction = top["bytes_reduction_fraction_mean"]
    bound = (nproc - 1) / nproc * 0.5
    # the combine payload is exactly linear in E (every entity is one
    # lane of one bucket), so the top rung's measured bytes/entity slope
    # extrapolates to the million-entity point the ladder is shaped for
    E_top = MULTICHIP_R08_LADDER[-1]
    extrapolation: dict = {"entities": 1_000_000}
    for arm in ("allreduce", "segments"):
        extrapolation[arm] = round(
            top[arm]["combine_bytes_per_process_mean"] / E_top * 1_000_000
        )
    doc = {
        "round": 8,
        "what": (
            "owner-segment sparse combine A/B for entity-sharded "
            "in-memory random-effect solves: PHOTON_RE_COMBINE="
            "allreduce (dense fixed-layout, O(P·E·d)/visit) vs "
            "=segments (owner-segment framed P2P, O(E·d)/visit) on a "
            f"Zipf million-entity-shaped ladder, {nproc}-process "
            "loopback CPU harness (gloo collectives)"
        ),
        "nproc": nproc,
        "d": MULTICHIP_R08_D,
        "ladder": rungs,
        "extrapolation_1M_entities_bytes_per_process": extrapolation,
        "acceptance": {
            "bitwise_identical": all_bitwise,
            "bytes_reduction_at_top_rung": round(reduction, 4),
            "bytes_reduction_at_top_rung_max_owner": round(
                top["bytes_reduction_fraction_max"], 4
            ),
            "required_reduction": round(bound, 4),
            "reduction_ge_required": reduction >= bound,
        },
        "gate_metrics": gate_metrics,
        "note": (
            "CPU wall at toy scale is dispatch/exchange-latency bound "
            "(recorded per the BASELINE protocol); the byte counts are "
            "the load-bearing measurement — exact on the segments arm "
            "(framed payload bytes), analytic-lower-bound on the "
            "allreduce arm (dense buffer × (P−1)). The per-process MEAN "
            "(= fleet combine traffic / P) is the acceptance metric; "
            "the MAX owner's reduction is bounded by bucket-ATOMIC "
            "placement (a Zipf tail capacity class is one placement "
            "atom owning most entities) — splitting placement below "
            "bucket granularity is the recorded ROADMAP follow-up"
        ),
    }
    if not all_bitwise:
        raise RuntimeError(
            f"MULTICHIP_r08: bitwise contract violated: {rungs}"
        )
    with open(os.path.join(here, out_path), "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    _log(
        f"[bench] MULTICHIP_r08 capture written to {out_path} "
        f"(reduction {reduction:.1%} vs required {bound:.1%})"
    )
    return doc


# -- MULTICHIP_r09: sub-bucket placement atoms A/B (PHOTON_RE_SPLIT) --------
#
# `python bench.py --multichip-r09` spawns the gloo loopback harness (4
# processes — the acceptance config) and runs the r08 in-memory
# owned-bucket solve on the SAME Zipf ladder twice per rung, both arms
# on the owner-segment combine (PHOTON_RE_COMBINE=segments): once
# bucket-ATOMIC (PHOTON_RE_SPLIT=0 — exactly the PR-12 schedule, whose
# per-process wire bytes are asserted bit-for-bit against the committed
# MULTICHIP_r08.json and whose per-process launch counts are asserted
# against the legacy one-launch-per-owned-bucket schedule) and once
# with sub-bucket atoms (PHOTON_RE_SPLIT=MULTICHIP_R09_SPLIT). Each arm
# runs a COLD solve (the r08 recipe verbatim) plus a WARM+PRIOR solve
# (warm start + per-entity Gaussian prior from the cold pass — the
# prior lanes must remap through the sub-bucket permutation too), and
# every arm's coefficients/variances/iterations/prior-pass results are
# asserted bitwise identical across processes AND against a
# single-process unsplit reference run. The acceptance axis is the MAX
# owner's combine bytes: bucket-atomic placement pins the Zipf tail
# class on one owner (r08 measured the max-owner reduction at only
# ~9%), sub-bucket atoms spread it, target >= 40% with atom-granularity
# balance <= 1.15. Writes MULTICHIP_r09.json with a flat gate_metrics
# section `scripts/gate_quick.sh` gates against BASELINE_split_cpu.json.

MULTICHIP_R09_SPLIT = 16
MULTICHIP_R09_NPROC = MULTICHIP_R08_NPROC


def _multichip_r09_worker(coordinator: str, pid: int, nproc: int) -> None:
    """One harness process of the split A/B (child mode): the r08
    worker's contract (full replicated dataset, owned-bucket dispatch,
    segments combine) with the PHOTON_RE_SPLIT arm toggle, per-arm
    launch/byte accounting and the warm+prior second pass."""
    jax = _multichip_worker_setup(
        coordinator, pid, nproc,
        knobs={"PHOTON_RE_SHARD": "1", "PHOTON_RE_COMBINE": "segments"},
    )
    import jax.numpy as jnp

    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.game import bucket_entities, group_by_entity
    from photon_ml_tpu.game.data import DenseFeatures, split_entity_buckets
    from photon_ml_tpu.game.random_effect import (
        _plan_bucket_owners,
        train_random_effects,
    )
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.parallel import data_mesh
    from photon_ml_tpu.types import TaskType, VarianceComputationType

    mesh = data_mesh()
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    counter, _gauge, sha = _worker_probes()

    def gauge(name: str) -> float:
        return float(_gauge(name, 0.0))

    results: dict[str, dict] = {}
    for E in MULTICHIP_R08_LADDER:
        ids, X, y = _multichip_r08_dataset(E)
        n = len(ids)
        buckets = bucket_entities(group_by_entity(ids, num_entities=E))
        arms = (("unsplit", 0), ("split", MULTICHIP_R09_SPLIT))
        if nproc == 1:
            # the single-process run is the bitwise REFERENCE leg: only
            # its unsplit results are ever read, so skip the split arm
            arms = (("unsplit", 0),)
        for arm, split in arms:
            os.environ["PHOTON_RE_SPLIT"] = str(split)
            # the deterministic owner map this arm will place by (pure
            # host arithmetic — same inputs on every process), plus the
            # legacy launch expectation for the knob-off assertion:
            # one launch per owned bucket, the PR-12 schedule
            b2, parents, n_split = split_entity_buckets(buckets, split)
            owners = _plan_bucket_owners(b2, parents, n_split)
            owned_buckets = int((np.asarray(owners) == pid).sum())
            common = dict(
                features=DenseFeatures(X=jnp.asarray(X)),
                labels=y,
                offsets=np.zeros(n, np.float32),
                weights=np.ones(n, np.float32),
                buckets=buckets,
                num_entities=E,
                loss=loss,
                config=OptimizerConfig(max_iterations=4, tolerance=1e-8),
                l2_weight=1.0,
                variance_computation=VarianceComputationType.SIMPLE,
                mesh=mesh,
            )
            b0 = counter("re_combine.bytes_sent")
            l0 = counter("re_solve.launches")
            t0 = time.perf_counter()
            res = train_random_effects(**common)  # the r08 recipe verbatim
            W = np.asarray(jax.device_get(res.coefficients), np.float32)
            V = np.asarray(jax.device_get(res.variances), np.float32)
            it = np.asarray(res.iterations, np.int64)
            cold_bytes = counter("re_combine.bytes_sent") - b0
            cold_launches = counter("re_solve.launches") - l0
            # warm + prior pass: the sub-bucket permutation must carry
            # the warm-start AND per-entity prior lanes identically
            b1 = counter("re_combine.bytes_sent")
            res2 = train_random_effects(
                initial_coefficients=jnp.asarray(W),
                prior_coefficients=jnp.asarray(W),
                prior_variances=jnp.asarray(V),
                **common,
            )
            W2 = np.asarray(jax.device_get(res2.coefficients), np.float32)
            V2 = np.asarray(jax.device_get(res2.variances), np.float32)
            wall = time.perf_counter() - t0
            results[f"E{E}/{arm}"] = {
                "wall_s": round(wall, 4),
                "combine_bytes_sent": cold_bytes,
                "combine_bytes_sent_prior": (
                    counter("re_combine.bytes_sent") - b1
                ),
                "launches": cold_launches,
                "owned_buckets_expected": owned_buckets,
                "owner_sha256": sha(np.asarray(owners, np.int64)),
                "balance": gauge("re_shard.balance"),
                "atoms": gauge("re_shard.atoms"),
                "split_classes": gauge("re_shard.split_classes"),
                "W_sha256": sha(W),
                "V_sha256": sha(V),
                "it_sha256": sha(it),
                "W_prior_sha256": sha(W2),
                "V_prior_sha256": sha(V2),
            }
    print("RESULT " + json.dumps({"pid": pid, "results": results}))


def run_multichip_r09(
    out_path: str = "MULTICHIP_r09.json", nproc: int = MULTICHIP_R09_NPROC
) -> dict:
    """Drive the split-placement A/B (parent mode) and write
    MULTICHIP_r09.json. Asserts, in-harness: bitwise-identical model
    hashes across processes, across arms, and against a single-process
    unsplit reference; the unsplit arm reproducing the committed
    MULTICHIP_r08.json segments wire bytes AND the legacy
    one-launch-per-owned-bucket schedule bit-for-bit; and the
    acceptance bounds (max-owner combine-byte reduction >= 40%,
    atom-granularity balance <= 1.15)."""
    here = os.path.dirname(os.path.abspath(__file__))

    per_pid = _collect_worker_results(
        "--multichip-r09-worker", nproc, "multichip_r09"
    )
    # single-process unsplit reference: the bitwise anchor every arm
    # must reproduce (owned mode at P=1 dispatches every bucket locally
    # and skips the combine — the plain in-memory solve)
    ref = _collect_worker_results(
        "--multichip-r09-worker", 1, "multichip_r09_ref", nproc_arg=1
    )[0]

    try:
        with open(os.path.join(here, "MULTICHIP_r08.json")) as f:
            r08 = json.load(f)
    except FileNotFoundError:
        r08 = None

    hash_fields = (
        "W_sha256", "V_sha256", "it_sha256",
        "W_prior_sha256", "V_prior_sha256",
    )
    rungs: dict[str, dict] = {}
    gate_metrics: dict[str, float] = {}
    problems: list[str] = []
    for E in MULTICHIP_R08_LADDER:
        rung: dict = {"entities": E,
                      "rows_total": int(_multichip_r08_sizes(E).sum())}
        for arm in ("unsplit", "split"):
            key = f"E{E}/{arm}"
            bts = [per_pid[p][key]["combine_bytes_sent"]
                   for p in range(nproc)]
            bts_prior = [per_pid[p][key]["combine_bytes_sent_prior"]
                         for p in range(nproc)]
            for field in hash_fields:
                vals = {per_pid[p][key][field] for p in range(nproc)}
                if len(vals) != 1:
                    problems.append(f"{key}: {field} differs across processes")
                elif vals != {ref[f"E{E}/unsplit"][field]}:
                    problems.append(
                        f"{key}: {field} != single-process unsplit reference"
                    )
            if len({per_pid[p][key]["owner_sha256"]
                    for p in range(nproc)}) != 1:
                problems.append(f"{key}: owner maps differ across processes")
            # knob-off bit-for-bit: the legacy one-launch-per-owned-
            # bucket schedule, per process (2 solves per arm: cold counts
            # owned buckets exactly; the warm pass repeats it)
            if arm == "unsplit":
                for p in range(nproc):
                    got = per_pid[p][key]["launches"]
                    want = per_pid[p][key]["owned_buckets_expected"]
                    if got != want:
                        problems.append(
                            f"{key} p{p}: launches {got} != legacy "
                            f"schedule {want}"
                        )
            rung[arm] = {
                "wall_s_max": max(
                    per_pid[p][key]["wall_s"] for p in range(nproc)
                ),
                "combine_bytes_per_process_mean": sum(bts) / nproc,
                "combine_bytes_per_process_max": max(bts),
                "combine_bytes_per_process": {
                    str(p): bts[p] for p in range(nproc)
                },
                "combine_bytes_prior_per_process_max": max(bts_prior),
                "balance": per_pid[0][key]["balance"],
                "atoms": per_pid[0][key]["atoms"],
                "split_classes": per_pid[0][key]["split_classes"],
            }
            gate_metrics[f"E{E}/re_combine/bytes_sent_max/{arm}"] = float(
                max(bts)
            )
            gate_metrics[f"E{E}/re_combine/bytes_sent_mean/{arm}"] = float(
                sum(bts) / nproc
            )
            gate_metrics[f"E{E}/re_shard/balance/{arm}"] = float(
                per_pid[0][key]["balance"]
            )
        rungs[str(E)] = rung
        gate_metrics[f"E{E}/re_shard/atoms/split"] = float(
            rung["split"]["atoms"]
        )
        # PR-12 reproduction: the unsplit arm's cold-pass segments wire
        # bytes must be BIT-FOR-BIT the committed r08 capture's
        if r08 is not None:
            want = r08["ladder"][str(E)]["segments"][
                "combine_bytes_per_process"
            ]
            got = rung["unsplit"]["combine_bytes_per_process"]
            if {k: float(v) for k, v in got.items()} != {
                k: float(v) for k, v in want.items()
            }:
                problems.append(
                    f"E{E}: unsplit segments bytes {got} != committed "
                    f"MULTICHIP_r08.json {want}"
                )
        b_un = rung["unsplit"]["combine_bytes_per_process_max"]
        b_sp = rung["split"]["combine_bytes_per_process_max"]
        rung["max_owner_bytes_reduction_fraction"] = (
            1.0 - b_sp / b_un if b_un else 0.0
        )
        m_un = rung["unsplit"]["combine_bytes_per_process_mean"]
        m_sp = rung["split"]["combine_bytes_per_process_mean"]
        rung["mean_bytes_delta_fraction"] = (
            m_sp / m_un - 1.0 if m_un else 0.0
        )
    top = rungs[str(MULTICHIP_R08_LADDER[-1])]
    reduction = top["max_owner_bytes_reduction_fraction"]
    balance_split = top["split"]["balance"]
    acceptance = {
        "bitwise_identical": not problems,
        "max_owner_bytes_reduction_at_top_rung": round(reduction, 4),
        "required_reduction": 0.40,
        "reduction_ge_required": reduction >= 0.40,
        "balance_split_at_top_rung": round(balance_split, 4),
        "balance_le_1_15": balance_split <= 1.15,
        "unsplit_reproduces_r08_wire_bytes": r08 is not None and not any(
            "MULTICHIP_r08" in p for p in problems
        ),
        "unsplit_reproduces_legacy_launches": not any(
            "legacy schedule" in p for p in problems
        ),
    }
    doc = {
        "round": 9,
        "what": (
            "sub-bucket placement atoms A/B for entity-sharded "
            "in-memory random-effect solves: PHOTON_RE_SPLIT=0 "
            "(bucket-atomic placement — the PR-12 schedule) vs "
            f"={MULTICHIP_R09_SPLIT} (heavy capacity classes split "
            "into >= 2-entity sub-bucket atoms by pure global-bincount "
            "arithmetic), both on the owner-segment combine "
            f"(PHOTON_RE_COMBINE=segments), {nproc}-process loopback "
            "CPU harness (gloo collectives) + a single-process unsplit "
            "bitwise reference"
        ),
        "nproc": nproc,
        "d": MULTICHIP_R08_D,
        "split": MULTICHIP_R09_SPLIT,
        "ladder": rungs,
        "acceptance": acceptance,
        "gate_metrics": gate_metrics,
        "problems": problems,
        "note": (
            "CPU wall at toy scale is dispatch/exchange-latency bound "
            "(recorded per the BASELINE protocol); the load-bearing "
            "measurement is the MAX owner's combine bytes — the r08 "
            "capture's known limit (max-owner reduction ~9%: the Zipf "
            "tail capacity class was ONE placement atom). Sub-bucket "
            "atoms bound the busiest owner at O(total/P + max-atom) "
            "instead of O(heaviest class); the mean per-process bytes "
            "stay within the segment-header overhead of the unsplit "
            "arm (finer atoms add one tiny per-bucket frame header "
            "each, no payload)"
        ),
    }
    if problems:
        raise RuntimeError(
            f"MULTICHIP_r09: bitwise/reproduction contract violated: "
            f"{problems}"
        )
    with open(os.path.join(here, out_path), "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    _log(
        f"[bench] MULTICHIP_r09 capture written to {out_path} "
        f"(max-owner reduction {reduction:.1%} vs required 40.0%, "
        f"split balance {balance_split:.3f}x)"
    )
    return doc


# -- MULTICHIP_r10: device-granularity placement A/B (PHOTON_RE_DEVICE_SPLIT)
#
# `python bench.py --multichip-r10` spawns the gloo loopback harness (4
# processes) with each worker FORCING 4 host-platform CPU devices
# (XLA_FLAGS=--xla_force_host_platform_device_count=4 — the parent
# harness strips XLA_FLAGS from the child env, so the worker sets it
# before its first jax use) and runs the r09 in-memory owned-bucket
# recipe on the same Zipf ladder across four arms, all on the
# owner-segment combine:
#
#   off      PHOTON_RE_SPLIT=16, DEVICE_SPLIT=0 — exactly the PR-13
#            split schedule; its per-process segments wire bytes are
#            asserted bit-for-bit against the committed
#            MULTICHIP_r09.json split arm
#   device   same split, DEVICE_SPLIT=1 — owned atoms placed per LOCAL
#            device; coefficients/variances/iterations AND per-process
#            wire bytes must be bit-for-bit the off arm's (the device
#            level changes WHERE owned solves run, never what crosses
#            the process transport)
#   device64 PHOTON_RE_SPLIT=64, DEVICE_SPLIT=1 — the balance arm:
#            finer atoms give the per-device LPT enough units to bound
#            re_shard.device_balance <= 1.15 across 4 local devices
#   bytes    PHOTON_RE_SPLIT=16, SPLIT_WEIGHT=bytes — the weight-axis
#            arm: lane-count (combine-byte) weighted split+placement;
#            its MAX owner's combine bytes must improve on the off
#            arm's (the r09 capture's known limit: row balance 1.044
#            but max/mean combine bytes ~2.0x)
#
# Every arm runs the cold solve plus the warm+prior pass, and every
# arm's model hashes are asserted bitwise identical across processes
# AND across arms (split factor, weight axis and device placement are
# all schedule-only). Writes MULTICHIP_r10.json with a flat
# gate_metrics section `scripts/gate_quick.sh` gates against
# BASELINE_device_cpu.json.

MULTICHIP_R10_NDEV = 4
MULTICHIP_R10_SPLIT = 64
MULTICHIP_R10_NPROC = MULTICHIP_R08_NPROC


def _multichip_r10_worker(coordinator: str, pid: int, nproc: int) -> None:
    """One harness process of the device-placement A/B (child mode):
    the r09 worker's contract under a FORCED 4-local-device CPU
    topology, with the PHOTON_RE_DEVICE_SPLIT / PHOTON_RE_SPLIT_WEIGHT
    arm toggles and the per-device placement gauges
    (re_shard.device_balance / re_shard.devices /
    re_shard.device_rows.<d>) read into the capture."""
    # before any jax import: the parent strips XLA_FLAGS from the child
    # env, and the backend reads it once at first use
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        f"{MULTICHIP_R10_NDEV}"
    )
    jax = _multichip_worker_setup(
        coordinator, pid, nproc,
        knobs={"PHOTON_RE_SHARD": "1", "PHOTON_RE_COMBINE": "segments"},
    )
    if jax.local_device_count() != MULTICHIP_R10_NDEV:
        raise RuntimeError(
            f"forced host device count did not take: "
            f"{jax.local_device_count()} != {MULTICHIP_R10_NDEV}"
        )
    import jax.numpy as jnp

    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.game import bucket_entities, group_by_entity
    from photon_ml_tpu.game.data import DenseFeatures, split_entity_buckets
    from photon_ml_tpu.game.random_effect import (
        _plan_bucket_owners,
        train_random_effects,
    )
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.parallel import data_mesh
    from photon_ml_tpu.parallel.placement import re_split_weight
    from photon_ml_tpu.types import TaskType, VarianceComputationType

    mesh = data_mesh()
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    counter, _gauge, sha = _worker_probes()

    def gauge(name: str) -> float:
        return float(_gauge(name, 0.0))

    # (arm, PHOTON_RE_SPLIT, PHOTON_RE_DEVICE_SPLIT, PHOTON_RE_SPLIT_WEIGHT)
    arms = (
        ("off", MULTICHIP_R09_SPLIT, 0, "rows"),
        ("device", MULTICHIP_R09_SPLIT, 1, "rows"),
        ("device64", MULTICHIP_R10_SPLIT, 1, "rows"),
        ("bytes", MULTICHIP_R09_SPLIT, 0, "bytes"),
    )
    results: dict[str, dict] = {}
    for E in MULTICHIP_R08_LADDER:
        ids, X, y = _multichip_r08_dataset(E)
        n = len(ids)
        buckets = bucket_entities(group_by_entity(ids, num_entities=E))
        for arm, split, dev_split, weight in arms:
            os.environ["PHOTON_RE_SPLIT"] = str(split)
            os.environ["PHOTON_RE_DEVICE_SPLIT"] = str(dev_split)
            os.environ["PHOTON_RE_SPLIT_WEIGHT"] = weight
            # the deterministic owner map this arm will place by (pure
            # host arithmetic — same inputs on every process)
            b2, parents, n_split = split_entity_buckets(
                buckets, split, weight=re_split_weight()
            )
            owners = _plan_bucket_owners(b2, parents, n_split)
            common = dict(
                features=DenseFeatures(X=jnp.asarray(X)),
                labels=y,
                offsets=np.zeros(n, np.float32),
                weights=np.ones(n, np.float32),
                buckets=buckets,
                num_entities=E,
                loss=loss,
                config=OptimizerConfig(max_iterations=4, tolerance=1e-8),
                l2_weight=1.0,
                variance_computation=VarianceComputationType.SIMPLE,
                mesh=mesh,
            )
            b0 = counter("re_combine.bytes_sent")
            l0 = counter("re_solve.launches")
            t0 = time.perf_counter()
            res = train_random_effects(**common)
            W = np.asarray(jax.device_get(res.coefficients), np.float32)
            V = np.asarray(jax.device_get(res.variances), np.float32)
            it = np.asarray(res.iterations, np.int64)
            cold_bytes = counter("re_combine.bytes_sent") - b0
            cold_launches = counter("re_solve.launches") - l0
            # warm + prior pass: device placement must carry warm-start
            # and per-entity prior lanes through the same permutation
            b1 = counter("re_combine.bytes_sent")
            res2 = train_random_effects(
                initial_coefficients=jnp.asarray(W),
                prior_coefficients=jnp.asarray(W),
                prior_variances=jnp.asarray(V),
                **common,
            )
            W2 = np.asarray(jax.device_get(res2.coefficients), np.float32)
            V2 = np.asarray(jax.device_get(res2.variances), np.float32)
            wall = time.perf_counter() - t0
            rec = {
                "wall_s": round(wall, 4),
                "combine_bytes_sent": cold_bytes,
                "combine_bytes_sent_prior": (
                    counter("re_combine.bytes_sent") - b1
                ),
                "launches": cold_launches,
                "owner_sha256": sha(np.asarray(owners, np.int64)),
                "balance": gauge("re_shard.balance"),
                "atoms": gauge("re_shard.atoms"),
                "W_sha256": sha(W),
                "V_sha256": sha(V),
                "it_sha256": sha(it),
                "W_prior_sha256": sha(W2),
                "V_prior_sha256": sha(V2),
            }
            if dev_split:
                # the second-level placement gauges, set by THIS
                # process's own device plan during prepare
                rec["device_balance"] = gauge("re_shard.device_balance")
                rec["devices"] = gauge("re_shard.devices")
                rec["device_rows"] = [
                    gauge(f"re_shard.device_rows.{d}")
                    for d in range(MULTICHIP_R10_NDEV)
                ]
            results[f"E{E}/{arm}"] = rec
    print("RESULT " + json.dumps({"pid": pid, "results": results}))


def run_multichip_r10(
    out_path: str = "MULTICHIP_r10.json", nproc: int = MULTICHIP_R10_NPROC
) -> dict:
    """Drive the device-placement A/B (parent mode) and write
    MULTICHIP_r10.json. Asserts, in-harness: bitwise-identical model
    hashes across processes AND across all four arms; the device arm
    reproducing the off arm's per-process wire bytes exactly (the
    device level never changes what crosses the process transport);
    the off arm reproducing the committed MULTICHIP_r09.json split-arm
    segments wire bytes bit-for-bit; and the acceptance bounds
    (device balance <= 1.15 at the top rung, bytes-weighted split
    improving the MAX owner's combine bytes over the rows-weighted
    off arm)."""
    here = os.path.dirname(os.path.abspath(__file__))

    per_pid = _collect_worker_results(
        "--multichip-r10-worker", nproc, "multichip_r10", timeout_s=1800
    )

    try:
        with open(os.path.join(here, "MULTICHIP_r09.json")) as f:
            r09 = json.load(f)
    except FileNotFoundError:
        r09 = None

    arm_names = ("off", "device", "device64", "bytes")
    hash_fields = (
        "W_sha256", "V_sha256", "it_sha256",
        "W_prior_sha256", "V_prior_sha256",
    )
    rungs: dict[str, dict] = {}
    gate_metrics: dict[str, float] = {}
    problems: list[str] = []
    for E in MULTICHIP_R08_LADDER:
        rung: dict = {"entities": E,
                      "rows_total": int(_multichip_r08_sizes(E).sum())}
        anchor = per_pid[0][f"E{E}/off"]
        for arm in arm_names:
            key = f"E{E}/{arm}"
            bts = [per_pid[p][key]["combine_bytes_sent"]
                   for p in range(nproc)]
            bts_prior = [per_pid[p][key]["combine_bytes_sent_prior"]
                         for p in range(nproc)]
            for field in hash_fields:
                vals = {per_pid[p][key][field] for p in range(nproc)}
                if len(vals) != 1:
                    problems.append(f"{key}: {field} differs across processes")
                elif vals != {anchor[field]}:
                    # split factor, weight axis and device placement are
                    # schedule-only: every arm must match the off arm
                    problems.append(f"{key}: {field} != off arm")
            if len({per_pid[p][key]["owner_sha256"]
                    for p in range(nproc)}) != 1:
                problems.append(f"{key}: owner maps differ across processes")
            arm_rec = {
                "wall_s_max": max(
                    per_pid[p][key]["wall_s"] for p in range(nproc)
                ),
                "combine_bytes_per_process_mean": sum(bts) / nproc,
                "combine_bytes_per_process_max": max(bts),
                "combine_bytes_per_process": {
                    str(p): bts[p] for p in range(nproc)
                },
                "combine_bytes_prior_per_process_max": max(bts_prior),
                "launches_per_process": {
                    str(p): per_pid[p][key]["launches"]
                    for p in range(nproc)
                },
                "balance": per_pid[0][key]["balance"],
                "atoms": per_pid[0][key]["atoms"],
            }
            if "device_balance" in per_pid[0][key]:
                # fleet MAX: each process plans its own owned atoms over
                # its local devices, the worst host bounds the win
                arm_rec["device_balance_max"] = max(
                    per_pid[p][key]["device_balance"] for p in range(nproc)
                )
                arm_rec["devices"] = per_pid[0][key]["devices"]
                arm_rec["device_rows_per_process"] = {
                    str(p): per_pid[p][key]["device_rows"]
                    for p in range(nproc)
                }
                gate_metrics[f"E{E}/re_shard/device_balance/{arm}"] = float(
                    arm_rec["device_balance_max"]
                )
            rung[arm] = arm_rec
            gate_metrics[f"E{E}/re_combine/bytes_sent_max/{arm}"] = float(
                max(bts)
            )
            gate_metrics[f"E{E}/re_combine/bytes_sent_mean/{arm}"] = float(
                sum(bts) / nproc
            )
            gate_metrics[f"E{E}/re_shard/balance/{arm}"] = float(
                per_pid[0][key]["balance"]
            )
            gate_metrics[f"E{E}/re_shard/atoms/{arm}"] = float(
                per_pid[0][key]["atoms"]
            )
            gate_metrics[f"E{E}/re_solve/launches/{arm}"] = float(
                max(per_pid[p][key]["launches"] for p in range(nproc))
            )
        # the device level never changes what crosses the process
        # transport: per-process wire bytes must be EXACTLY the off
        # arm's (same split factor, same owner map, same owned rows)
        off_b = rung["off"]["combine_bytes_per_process"]
        dev_b = rung["device"]["combine_bytes_per_process"]
        if off_b != dev_b:
            problems.append(
                f"E{E}: device arm wire bytes {dev_b} != off arm {off_b}"
            )
        # PR-13 reproduction: the off arm's cold-pass segments wire
        # bytes must be BIT-FOR-BIT the committed r09 split capture's
        if r09 is not None:
            want = r09["ladder"][str(E)]["split"][
                "combine_bytes_per_process"
            ]
            if {k: float(v) for k, v in off_b.items()} != {
                k: float(v) for k, v in want.items()
            }:
                problems.append(
                    f"E{E}: off arm segments bytes {off_b} != committed "
                    f"MULTICHIP_r09.json split arm {want}"
                )
        b_off = rung["off"]["combine_bytes_per_process_max"]
        b_byt = rung["bytes"]["combine_bytes_per_process_max"]
        rung["bytes_weight_max_owner_reduction_fraction"] = (
            1.0 - b_byt / b_off if b_off else 0.0
        )
        rungs[str(E)] = rung
    top = rungs[str(MULTICHIP_R08_LADDER[-1])]
    dev_balance = top["device64"]["device_balance_max"]
    byte_gain = top["bytes_weight_max_owner_reduction_fraction"]
    acceptance = {
        "bitwise_identical": not problems,
        "device_balance_at_top_rung": round(dev_balance, 4),
        "device_balance_le_1_15": dev_balance <= 1.15,
        "bytes_weight_max_owner_reduction_at_top_rung": round(byte_gain, 4),
        "required_bytes_weight_reduction": 0.25,
        "bytes_weight_reduction_ge_required": byte_gain >= 0.25,
        "device_arm_reproduces_off_wire_bytes": not any(
            "device arm wire bytes" in p for p in problems
        ),
        "off_reproduces_r09_wire_bytes": r09 is not None and not any(
            "MULTICHIP_r09" in p for p in problems
        ),
    }
    doc = {
        "round": 10,
        "what": (
            "device-granularity placement A/B for entity-sharded "
            "in-memory random-effect solves under a forced "
            f"{MULTICHIP_R10_NDEV}-local-device CPU topology: "
            "PHOTON_RE_DEVICE_SPLIT=0 (the PR-13 single-unit-per-"
            "process schedule) vs =1 (owned atoms LPT-placed per LOCAL "
            f"device), at PHOTON_RE_SPLIT={MULTICHIP_R09_SPLIT} and "
            f"={MULTICHIP_R10_SPLIT}, plus a PHOTON_RE_SPLIT_WEIGHT="
            "bytes arm (lane-count weighted split+placement), all on "
            f"the owner-segment combine, {nproc}-process loopback CPU "
            "harness (gloo collectives)"
        ),
        "nproc": nproc,
        "ndev": MULTICHIP_R10_NDEV,
        "d": MULTICHIP_R08_D,
        "split": MULTICHIP_R09_SPLIT,
        "split_device_arm": MULTICHIP_R10_SPLIT,
        "ladder": rungs,
        "acceptance": acceptance,
        "gate_metrics": gate_metrics,
        "problems": problems,
        "note": (
            "CPU wall at toy scale is dispatch/exchange-latency bound "
            "(recorded per the BASELINE protocol); the load-bearing "
            "measurements are (1) re_shard.device_balance — the "
            "second-level LPT bound over each process's local devices, "
            "needing the finer split to have enough atoms per process "
            "— and (2) the bytes-weighted split's MAX-owner combine "
            "bytes: the r09 capture's known limit (row balance 1.044 "
            "but max/mean combine bytes ~2.0x — lane-heavy capacity "
            "classes carry few rows), which the lane-count weight axis "
            "closes without touching the solve schedule"
        ),
    }
    if problems:
        raise RuntimeError(
            f"MULTICHIP_r10: bitwise/reproduction contract violated: "
            f"{problems}"
        )
    with open(os.path.join(here, out_path), "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    _log(
        f"[bench] MULTICHIP_r10 capture written to {out_path} "
        f"(device balance {dev_balance:.3f}x vs required 1.15x, "
        f"bytes-weight max-owner reduction {byte_gain:.1%})"
    )
    return doc


# `python bench.py --multichip-r11` spawns the gloo loopback harness (4
# processes) and runs the in-memory owned-bucket recipe on a Zipf
# ladder with CLASS-CORRELATED column sparsity (entity e activates only
# its first ncols(e) columns, ncols tied to the entity's row count —
# head entities touch most of d=32, tail entities a handful) across
# four arms, all on the owner-segment combine:
#
#   off      PHOTON_RE_PROJECT unset — the full-width schedule verbatim;
#            its cold launches are asserted == this process's owned
#            bucket count (one launch per owned bucket)
#   off0     PHOTON_RE_PROJECT=0 — must be BIT-FOR-BIT the off arm
#            (models, launches, wire bytes): the knob default is the
#            prior code path, not an approximation of it
#   support  PHOTON_RE_PROJECT=support — each capacity class solves over
#            its globally-active columns only; exact under L2-at-zero,
#            so its cold AUC is gated at parity with the off arm, and
#            its mean per-process combine bytes must come in >= 30%
#            under the off arm's (the d_e/d ratio shrinks every
#            downstream byte)
#   hash     PHOTON_RE_PROJECT=hash, PHOTON_RE_PROJECT_DIM=16 — classes
#            whose support exceeds 16 fold by signed hashing; lossy, so
#            it is gated on |ΔAUC| <= 0.005 vs the off arm
#
# Every arm runs the cold solve plus the warm+prior pass (the fold must
# carry warm starts and MAP priors), and every arm's model hashes are
# asserted bitwise identical across processes. Writes MULTICHIP_r11.json
# with a flat gate_metrics section `scripts/gate_quick.sh` gates against
# BASELINE_project_cpu.json.

MULTICHIP_R11_D = 32
MULTICHIP_R11_DIM = 16
MULTICHIP_R11_NPROC = MULTICHIP_R08_NPROC


def _multichip_r11_signal_columns():
    """The columns allowed to carry true signal: one per distinct hash
    slot of the committed fold (d=32 -> dim=16), computed from the SAME
    deterministic `_hash_fold` the ladder uses. Feature hashing is only
    quality-safe when the dominant features don't collide (the colliding
    mass must sit on weak/rare features) — the r11 dataset encodes that
    operating regime explicitly, and the quality-parity gate certifies
    the fold machinery preserves it end-to-end (the same way the int8
    rung certifies quantization-friendly scales, not arbitrary ones)."""
    from photon_ml_tpu.game.projector import _hash_fold

    slots, _ = _hash_fold(
        np.arange(MULTICHIP_R11_D, dtype=np.int64), MULTICHIP_R11_DIM, None
    )
    sig, seen = [], set()
    for j in range(MULTICHIP_R11_D):
        if int(slots[j]) not in seen:
            seen.add(int(slots[j]))
            sig.append(j)
    return np.asarray(sig, np.int64)


def _multichip_r11_dataset(E: int):
    """The projection A/B dataset: r08's Zipf row-count ladder (floored
    at 6 rows/entity so per-entity estimates are meaningful) at d=32,
    with each row activating 3 SIGNAL columns plus 2 weak noise columns
    inside its entity's FIRST ncols(e) columns — ncols grows with the
    entity's row count, so capacity class (a row-count bucket)
    correlates with support width, which is exactly the structure the
    per-class projection ladder exploits. Signal lives on
    collision-free columns of the committed fold; noise columns (the
    hash collisions) carry 0.2-scaled values and zero true weight.
    Returns a held-out twin draw alongside the training rows: the
    quality-parity AUC is measured OUT-OF-SAMPLE, because in-sample AUC
    rewards the wider dense solve for memorizing few-row entities — an
    overfitting gap, not a fold-quality signal."""
    rng = np.random.default_rng(1111)
    sizes = np.maximum(_multichip_r08_sizes(E), 6)
    d = MULTICHIP_R11_D
    ncols = np.minimum(
        d, 3 + (np.ceil(np.log2(sizes + 1.0)) * 3).astype(np.int64)
    )
    ids = np.repeat(np.arange(E), sizes).astype(np.int64)
    ids = ids[rng.permutation(len(ids))]
    n = len(ids)
    sig_cols = _multichip_r11_signal_columns()
    noise_cols = np.setdiff1d(np.arange(d), sig_cols)
    n_sig = np.searchsorted(sig_cols, ncols)  # sig cols < ncols[e]
    n_noi = np.searchsorted(noise_cols, ncols)
    W_true = np.zeros((E, d), np.float32)
    W_true[:, sig_cols] = (
        rng.normal(size=(E, len(sig_cols)))
        / np.sqrt(1.0 + np.arange(len(sig_cols)))[None, :]
    ).astype(np.float32)

    def draw():
        X = np.zeros((n, d), np.float32)
        for _ in range(3):
            c = sig_cols[rng.integers(0, 1 << 30, size=n) % n_sig[ids]]
            X[np.arange(n), c] = rng.normal(size=n).astype(np.float32)
        for _ in range(2):
            c = noise_cols[rng.integers(0, 1 << 30, size=n) % n_noi[ids]]
            X[np.arange(n), c] = (
                0.2 * rng.normal(size=n)
            ).astype(np.float32)
        margin = 2.0 * np.sum(W_true[ids] * X, axis=1)
        y = (
            rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))
        ).astype(np.float32)
        return X, y

    X, y = draw()
    X_eval, y_eval = draw()
    return ids, X, y, X_eval, y_eval


def _multichip_r11_worker(coordinator: str, pid: int, nproc: int) -> None:
    """One harness process of the projection A/B (child mode): the r09
    worker's contract (full replicated dataset, owned-bucket dispatch,
    segments combine) with the PHOTON_RE_PROJECT arm toggle, per-arm
    launch/byte accounting, the projection gauges and the cold-pass
    training AUC (the quality-parity anchor)."""
    jax = _multichip_worker_setup(
        coordinator, pid, nproc,
        knobs={
            "PHOTON_RE_SHARD": "1",
            "PHOTON_RE_COMBINE": "segments",
            "PHOTON_RE_SPLIT": "0",
            "PHOTON_RE_SPLIT_WEIGHT": None,
        },
    )
    import jax.numpy as jnp

    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.evaluation.evaluators import auc_roc
    from photon_ml_tpu.game import bucket_entities, group_by_entity
    from photon_ml_tpu.game.data import DenseFeatures
    from photon_ml_tpu.game.random_effect import (
        _plan_bucket_owners,
        train_random_effects,
    )
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.parallel import data_mesh
    from photon_ml_tpu.types import TaskType, VarianceComputationType

    mesh = data_mesh()
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    counter, gauge, sha = _worker_probes()

    # (arm, PHOTON_RE_PROJECT value; None = env unset)
    arms = (
        ("off", None),
        ("off0", "0"),
        ("support", "support"),
        ("hash", "hash"),
    )
    os.environ["PHOTON_RE_PROJECT_DIM"] = str(MULTICHIP_R11_DIM)
    results: dict[str, dict] = {}
    for E in MULTICHIP_R08_LADDER:
        ids, X, y, X_eval, y_eval = _multichip_r11_dataset(E)
        n = len(ids)
        buckets = bucket_entities(group_by_entity(ids, num_entities=E))
        # the deterministic owner map every arm places by (projection
        # never moves ownership at split=0), plus the launch
        # expectation for the knob-off assertion: one launch per owned
        # bucket, the owned-bucket schedule verbatim
        owners = _plan_bucket_owners(buckets)
        owned_buckets = int((np.asarray(owners) == pid).sum())
        for arm, knob in arms:
            if knob is None:
                os.environ.pop("PHOTON_RE_PROJECT", None)
            else:
                os.environ["PHOTON_RE_PROJECT"] = knob
            common = dict(
                features=DenseFeatures(X=jnp.asarray(X)),
                labels=y,
                offsets=np.zeros(n, np.float32),
                weights=np.ones(n, np.float32),
                buckets=buckets,
                num_entities=E,
                loss=loss,
                config=OptimizerConfig(max_iterations=4, tolerance=1e-8),
                l2_weight=1.0,
                variance_computation=VarianceComputationType.SIMPLE,
                mesh=mesh,
            )
            b0 = counter("re_combine.bytes_sent")
            l0 = counter("re_solve.launches")
            t0 = time.perf_counter()
            res = train_random_effects(**common)
            W = np.asarray(jax.device_get(res.coefficients), np.float32)
            V = np.asarray(jax.device_get(res.variances), np.float32)
            it = np.asarray(res.iterations, np.int64)
            cold_bytes = counter("re_combine.bytes_sent") - b0
            cold_launches = counter("re_solve.launches") - l0
            # cold-pass HELD-OUT AUC: the quality-parity anchor (every
            # process computes the same number from the replicated W);
            # out-of-sample, so the dense arm's few-row memorization
            # doesn't masquerade as fold-quality loss
            auc = float(auc_roc(np.sum(W[ids] * X_eval, axis=1), y_eval))
            # warm + prior pass: the fold must carry warm starts AND
            # per-entity MAP priors through the same projection
            b1 = counter("re_combine.bytes_sent")
            res2 = train_random_effects(
                initial_coefficients=jnp.asarray(W),
                prior_coefficients=jnp.asarray(W),
                prior_variances=jnp.asarray(V),
                **common,
            )
            W2 = np.asarray(jax.device_get(res2.coefficients), np.float32)
            V2 = np.asarray(jax.device_get(res2.variances), np.float32)
            wall = time.perf_counter() - t0
            rec = {
                "wall_s": round(wall, 4),
                "combine_bytes_sent": cold_bytes,
                "combine_bytes_sent_prior": (
                    counter("re_combine.bytes_sent") - b1
                ),
                "launches": cold_launches,
                "owned_buckets": owned_buckets,
                "auc": auc,
                "W_sha256": sha(W),
                "V_sha256": sha(V),
                "it_sha256": sha(it),
                "W_prior_sha256": sha(W2),
                "V_prior_sha256": sha(V2),
            }
            if knob not in (None, "0"):
                rec["mean_ratio"] = gauge("re_project.mean_ratio")
                rec["dims_saved_bytes"] = gauge(
                    "re_project.dims_saved_bytes"
                )
            results[f"E{E}/{arm}"] = rec
    print("RESULT " + json.dumps({"pid": pid, "results": results}))


def run_multichip_r11(
    out_path: str = "MULTICHIP_r11.json", nproc: int = MULTICHIP_R11_NPROC
) -> dict:
    """Drive the projection A/B (parent mode) and write
    MULTICHIP_r11.json. Asserts, in-harness: bitwise-identical model
    hashes across processes per arm; the off0 arm reproducing the off
    arm bit-for-bit (models, launch counters, wire bytes — knob 0 IS
    the prior code); off-arm cold launches == each process's owned
    bucket count; and the acceptance bounds (support arm cutting the
    mean per-process combine bytes >= 30% with AUC at parity, hash arm
    within |dAUC| <= 0.005)."""
    here = os.path.dirname(os.path.abspath(__file__))

    per_pid = _collect_worker_results(
        "--multichip-r11-worker", nproc, "multichip_r11", timeout_s=2400
    )

    arm_names = ("off", "off0", "support", "hash")
    hash_fields = (
        "W_sha256", "V_sha256", "it_sha256",
        "W_prior_sha256", "V_prior_sha256",
    )
    rungs: dict[str, dict] = {}
    gate_metrics: dict[str, float] = {}
    problems: list[str] = []
    for E in MULTICHIP_R08_LADDER:
        rung: dict = {"entities": E,
                      "rows_total": int(
                          np.maximum(_multichip_r08_sizes(E), 6).sum()
                      )}
        anchor = per_pid[0][f"E{E}/off"]
        for arm in arm_names:
            key = f"E{E}/{arm}"
            bts = [per_pid[p][key]["combine_bytes_sent"]
                   for p in range(nproc)]
            for field in hash_fields:
                vals = {per_pid[p][key][field] for p in range(nproc)}
                if len(vals) != 1:
                    problems.append(f"{key}: {field} differs across processes")
            arm_rec = {
                "wall_s_max": max(
                    per_pid[p][key]["wall_s"] for p in range(nproc)
                ),
                "combine_bytes_per_process_mean": sum(bts) / nproc,
                "combine_bytes_per_process_max": max(bts),
                "combine_bytes_per_process": {
                    str(p): bts[p] for p in range(nproc)
                },
                "combine_bytes_prior_per_process_max": max(
                    per_pid[p][key]["combine_bytes_sent_prior"]
                    for p in range(nproc)
                ),
                "launches_per_process": {
                    str(p): per_pid[p][key]["launches"]
                    for p in range(nproc)
                },
                "auc": per_pid[0][key]["auc"],
            }
            if "mean_ratio" in per_pid[0][key]:
                arm_rec["mean_ratio"] = per_pid[0][key]["mean_ratio"]
                arm_rec["dims_saved_bytes"] = per_pid[0][key][
                    "dims_saved_bytes"
                ]
                # the ladder is deterministic arithmetic on the global
                # activity bincount: every process must read the same
                # ratio from its own gauges
                ratios = {per_pid[p][key]["mean_ratio"]
                          for p in range(nproc)}
                if len(ratios) != 1:
                    problems.append(
                        f"{key}: re_project.mean_ratio differs across "
                        f"processes: {sorted(ratios)}"
                    )
                gate_metrics[f"E{E}/re_project/mean_ratio/{arm}"] = float(
                    per_pid[0][key]["mean_ratio"]
                )
            rung[arm] = arm_rec
            gate_metrics[f"E{E}/re_combine/bytes_sent_max/{arm}"] = float(
                max(bts)
            )
            gate_metrics[f"E{E}/re_combine/bytes_sent_mean/{arm}"] = float(
                sum(bts) / nproc
            )
            gate_metrics[f"E{E}/re_solve/launches/{arm}"] = float(
                max(per_pid[p][key]["launches"] for p in range(nproc))
            )
            if arm != "off":
                gate_metrics[f"E{E}/quality/auc_delta_abs/{arm}"] = abs(
                    float(per_pid[0][key]["auc"]) - float(anchor["auc"])
                )
        # knob 0 IS the prior code: models, launches and wire bytes all
        # bit-for-bit the unset run's
        for field in hash_fields:
            if per_pid[0][f"E{E}/off0"][field] != anchor[field]:
                problems.append(f"E{E}: off0 {field} != off arm")
        for p in range(nproc):
            o, z = per_pid[p][f"E{E}/off"], per_pid[p][f"E{E}/off0"]
            if o["combine_bytes_sent"] != z["combine_bytes_sent"]:
                problems.append(f"E{E}/p{p}: off0 wire bytes != off arm")
            if o["launches"] != z["launches"]:
                problems.append(f"E{E}/p{p}: off0 launches != off arm")
            # launch-counter contract: one launch per owned bucket
            if o["launches"] != o["owned_buckets"]:
                problems.append(
                    f"E{E}/p{p}: off launches {o['launches']} != owned "
                    f"buckets {o['owned_buckets']}"
                )
        b_off = rung["off"]["combine_bytes_per_process_mean"]
        b_sup = rung["support"]["combine_bytes_per_process_mean"]
        rung["support_bytes_reduction_fraction_mean"] = (
            1.0 - b_sup / b_off if b_off else 0.0
        )
        rungs[str(E)] = rung
    top = rungs[str(MULTICHIP_R08_LADDER[-1])]
    reduction = top["support_bytes_reduction_fraction_mean"]
    d_sup = abs(top["support"]["auc"] - top["off"]["auc"])
    d_hsh = abs(top["hash"]["auc"] - top["off"]["auc"])
    acceptance = {
        "bitwise_identical": not problems,
        "support_bytes_reduction_at_top_rung": round(reduction, 4),
        "required_support_bytes_reduction": 0.30,
        "support_reduction_ge_required": reduction >= 0.30,
        "support_auc_delta_abs": round(d_sup, 6),
        "hash_auc_delta_abs": round(d_hsh, 6),
        "quality_parity_abs_bound": 0.005,
        "quality_parity_ok": d_sup <= 0.005 and d_hsh <= 0.005,
    }
    doc = {
        "round": 11,
        "what": (
            "per-entity feature projection A/B for entity-sharded "
            "in-memory random-effect solves: PHOTON_RE_PROJECT unset/0 "
            "(full-width, bit-for-bit twins) vs support (per-class "
            "active-column subspace, exact under L2-at-zero) vs hash "
            f"(signed fold to {MULTICHIP_R11_DIM} for over-wide "
            f"classes), d={MULTICHIP_R11_D} with class-correlated "
            f"column sparsity, all on the owner-segment combine, "
            f"{nproc}-process loopback CPU harness (gloo collectives)"
        ),
        "nproc": nproc,
        "d": MULTICHIP_R11_D,
        "project_dim": MULTICHIP_R11_DIM,
        "ladder": rungs,
        "acceptance": acceptance,
        "gate_metrics": gate_metrics,
        "problems": problems,
        "note": (
            "CPU wall at toy scale is dispatch/exchange-latency bound "
            "(recorded per the BASELINE protocol); the load-bearing "
            "measurements are (1) the support arm's mean per-process "
            "combine bytes — the segments payload ships d_e-width "
            "lanes, so the cut IS the mean width ratio — and (2) the "
            "quality-parity deltas on the HELD-OUT draw: support is "
            "exact modulo reduction order (FP-level AUC agreement), "
            "hash is lossy and rides the documented |dAUC| <= 0.005 "
            "gate in its collision-free-signal operating regime"
        ),
    }
    if problems:
        raise RuntimeError(
            f"MULTICHIP_r11: bitwise/reproduction contract violated: "
            f"{problems}"
        )
    if not acceptance["support_reduction_ge_required"]:
        raise RuntimeError(
            f"MULTICHIP_r11: support arm cut only {reduction:.1%} of "
            f"mean per-process combine bytes (need >= 30%)"
        )
    if not acceptance["quality_parity_ok"]:
        raise RuntimeError(
            f"MULTICHIP_r11: quality parity breached: support dAUC "
            f"{d_sup:.6f}, hash dAUC {d_hsh:.6f} (bound 0.005)"
        )
    with open(os.path.join(here, out_path), "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    _log(
        f"[bench] MULTICHIP_r11 capture written to {out_path} "
        f"(support bytes cut {reduction:.1%} vs required 30%, "
        f"support dAUC {d_sup:.2g}, hash dAUC {d_hsh:.2g})"
    )
    return doc


# -- MULTICHIP_r12: feature-range-sharded fixed-effect A/B (PHOTON_FE_SHARD)
#
# `python bench.py --multichip-r12` runs the gloo loopback harness at
# P in {1, 2, 4} over ONE wide synthetic sparse GLM (d = 100k, Zipf
# column popularity — the skew the nnz-weighted partitioner exists
# for). Three arms per group: knob UNSET (off), knob "0" (off0 — must
# reproduce off bit-for-bit: knob 0 IS the prior code) and knob "1"
# (shard — each process holds only its contiguous feature range:
# range-local optimizer state, column-restricted chunks, per-range
# packed tile-COO streams). The solve runs the UNTILED streamed path
# (Pallas interpret mode at d=100k would dominate the capture with
# simulator time, not bytes); the packed-stream claim is measured
# where the bytes actually live — the tile-COO layout pack under the
# retuned 8x2 carve, read from the process-wide tile_cache byte
# accounting. The load-bearing numbers: per-process packed bytes
# shrinking ~ (P-1)/P on the shard arm, nnz balance <= 1.15x, and the
# sharded solve matching the single-process reference (gradient
# probe at a fixed iterate; model + held scores after 3 L-BFGS
# iterations under range-global line-search scalars).

MULTICHIP_R12_D = 100_000
MULTICHIP_R12_N = 4096
MULTICHIP_R12_K = 16
MULTICHIP_R12_CHUNK = 512
MULTICHIP_R12_PROCS = (1, 2, 4)
MULTICHIP_R12_ITERS = 3


def _multichip_r12_chunks():
    """Deterministic wide sparse chunks: Zipf(1.3) column draws (a few
    very hot features, a long cold tail) with standard-normal values and
    a planted linear signal — every process rebuilds the identical
    dataset from the fixed seed (the replicated-rows contract)."""
    rng = np.random.default_rng(1217)
    d, n, k = MULTICHIP_R12_D, MULTICHIP_R12_N, MULTICHIP_R12_K
    idx = ((rng.zipf(1.3, size=(n, k)).astype(np.int64) - 1) % d).astype(
        np.int32
    )
    vals = rng.standard_normal((n, k)).astype(np.float32)
    w_true = (rng.standard_normal(d) * 0.5).astype(np.float32)
    margins = (vals * w_true[idx]).sum(axis=1)
    y = (margins + 0.5 * rng.standard_normal(n) > 0).astype(np.float32)
    chunks = []
    for lo in range(0, n, MULTICHIP_R12_CHUNK):
        hi = lo + MULTICHIP_R12_CHUNK
        chunks.append({
            "labels": y[lo:hi],
            "indices": idx[lo:hi],
            "values": vals[lo:hi],
            "offsets": np.zeros(hi - lo, np.float32),
            "weights": np.ones(hi - lo, np.float32),
        })
    return chunks


def _multichip_r12_worker(coordinator: str, pid: int, nproc: int) -> None:
    """One harness process of the fe-shard A/B (child mode): per arm,
    pack the tile-COO layouts (the packed-byte measurement), run the
    untiled streamed solve (3 host-L-BFGS iterations), score through
    the module ``stream_scores`` consumer, and probe value_and_grad at
    a fixed iterate. Process 0 ships the full vectors (base64 f32
    bytes) so the parent can compare the sharded arm NUMERICALLY
    against the single-process reference; every process ships shas so
    cross-process lockstep is asserted bitwise."""
    import base64

    _multichip_worker_setup(
        coordinator, pid, nproc,
        knobs={
            # the retuned 8x2 carve (the kernel-shaping constants every
            # on-chip capture since the carve retune runs under)
            "PHOTON_GROUPS_PER_STEP": "8",
            "PHOTON_SEGMENTS_PER_DMA": "2",
            "PHOTON_FE_SHARD": None,
            "PHOTON_FE_SPLIT_WEIGHT": None,
        },
    )
    import jax.numpy as jnp

    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops import tile_cache
    from photon_ml_tpu.ops.losses import logistic_loss
    from photon_ml_tpu.ops.streaming import (
        StreamingGLMObjective,
        stream_scores,
    )
    from photon_ml_tpu.optim.host_lbfgs import host_lbfgs_minimize

    counter, gauge, sha = _worker_probes()
    chunks = _multichip_r12_chunks()
    d, n = MULTICHIP_R12_D, MULTICHIP_R12_N
    rng = np.random.default_rng(7)
    w_probe = (rng.standard_normal(d) * 0.01).astype(np.float32)

    def b64(a) -> str:
        return base64.b64encode(
            np.ascontiguousarray(np.asarray(a, np.float32)).tobytes()
        ).decode()

    arms = (("off", None), ("off0", "0"), ("shard", "1"))
    results: dict[str, dict] = {}
    for arm, knob in arms:
        if knob is None:
            os.environ.pop("PHOTON_FE_SHARD", None)
        else:
            os.environ["PHOTON_FE_SHARD"] = knob
        # packed-stream measurement: a TILED objective packs every
        # chunk's layout at construction (host pack only; no kernel
        # runs) — the cache's resident-byte total IS this process's
        # packed tile-COO stream footprint for one full data pass
        tile_cache.clear()
        tobj = StreamingGLMObjective(
            chunks, logistic_loss, num_features=d, l2_weight=1e-3,
            tile_sparse=True,
        )
        packed_bytes = int(tile_cache.stats()["bytes"])
        del tobj
        # the solve: untiled streamed path, same objective contract
        sobj = StreamingGLMObjective(
            chunks, logistic_loss, num_features=d, l2_weight=1e-3,
            tile_sparse=False,
        )
        # fixed-iterate probe: one value_and_grad — the parent checks
        # the concatenated range segments against the reference grad
        wp = sobj.fe_slice(w_probe) if sobj.fe_active else w_probe
        pv, pg = sobj.value_and_grad(jnp.asarray(wp, jnp.float32))
        pg = np.asarray(pg, np.float32)
        pg_full = sobj.fe_gather(pg) if sobj.fe_active else pg
        w0 = np.zeros(d, np.float32)
        w0 = sobj.fe_slice(w0) if sobj.fe_active else w0
        t0 = time.perf_counter()
        res = host_lbfgs_minimize(
            sobj, w0,
            OptimizerConfig(
                max_iterations=MULTICHIP_R12_ITERS, tolerance=1e-12
            ),
        )
        wall = time.perf_counter() - t0
        w_fit = np.asarray(res.w, np.float32)
        w_full = sobj.fe_gather(w_fit) if sobj.fe_active else w_fit
        # module scorer: the fourth streamed consumer under test (the
        # shard arm takes its collective fixed-order-reduction path)
        scores = np.asarray(
            stream_scores(
                chunks, w_full, num_rows=n, num_features=d,
                tile_sparse=False,
            ),
            np.float32,
        )
        rec = {
            "wall_s": round(wall, 4),
            "packed_stream_bytes": packed_bytes,
            "probe_value": float(pv),
            "value": float(res.value),
            "iterations": int(res.iterations),
            "w_sha256": sha(w_full),
            "scores_sha256": sha(scores),
            "grad_sha256": sha(pg_full),
        }
        if arm == "shard":
            rec["fe"] = {
                "ranges": gauge("fe_shard.ranges"),
                "width": gauge("fe_shard.width"),
                "nnz_local": gauge("fe_shard.nnz_local"),
                "nnz_balance": gauge("fe_shard.nnz_balance"),
            }
        if pid == 0:
            rec["w_b64"] = b64(w_full)
            rec["scores_b64"] = b64(scores)
            rec["grad_b64"] = b64(pg_full)
        results[arm] = rec
    print("RESULT " + json.dumps({"pid": pid, "results": results}))


def run_multichip_r12(
    out_path: str = "MULTICHIP_r12.json",
    procs: tuple = MULTICHIP_R12_PROCS,
) -> dict:
    """Drive the fe-shard A/B (parent mode) and write MULTICHIP_r12.json.
    Asserts, in-harness: off0 reproducing off bit-for-bit per process
    (model, scores, gradient probe, packed bytes — knob 0 IS the prior
    code); every arm bitwise-lockstep across its group's processes; the
    multi-process off arms reproducing the P=1 off reference bitwise
    (replicated rows, no sharding → the identical computation); the
    sharded model/scores/gradient numerically matching the reference;
    and the acceptance bounds (packed-byte reduction >= 40% at P=4,
    nnz balance <= 1.15)."""
    import base64

    here = os.path.dirname(os.path.abspath(__file__))
    # the P=1 off arm is the bitwise/numeric reference every group is
    # compared against — it is always captured, even for a custom list
    procs = tuple(sorted(set(int(P) for P in procs) | {1}))

    def de64(s: str) -> "np.ndarray":
        return np.frombuffer(base64.b64decode(s), np.float32)

    groups = {
        P: _collect_worker_results(
            "--multichip-r12-worker", P, f"multichip_r12_P{P}",
            timeout_s=1800,
        )
        for P in procs
    }
    ref = groups[1][0]
    ref_w = de64(ref["off"]["w_b64"])
    ref_scores = de64(ref["off"]["scores_b64"])
    ref_grad = de64(ref["off"]["grad_b64"])

    problems: list[str] = []
    gate_metrics: dict[str, float] = {}
    rungs: dict[str, dict] = {}
    sha_fields = ("w_sha256", "scores_sha256", "grad_sha256")
    for P, per_pid in groups.items():
        rung: dict = {"nproc": P}
        for arm in ("off", "off0", "shard"):
            for field in sha_fields:
                vals = {per_pid[p][arm][field] for p in range(P)}
                if len(vals) != 1:
                    problems.append(
                        f"P{P}/{arm}: {field} differs across processes"
                    )
            # knob-off bit-for-bit: "0" and unset are the same code
            # path, down to the packed layout bytes
            if arm == "off0":
                for p in range(P):
                    a, b = per_pid[p]["off"], per_pid[p]["off0"]
                    same = all(
                        a[f] == b[f] for f in sha_fields
                    ) and a["packed_stream_bytes"] == b["packed_stream_bytes"]
                    if not same:
                        problems.append(
                            f"P{P} p{p}: off0 != off (knob 0 must be "
                            f"bit-for-bit the unset path)"
                        )
            # replicated rows: the unsharded arms compute the identical
            # full-space solve regardless of P
            if arm in ("off", "off0"):
                for field in sha_fields:
                    if per_pid[0][arm][field] != ref["off"][field]:
                        problems.append(
                            f"P{P}/{arm}: {field} != P=1 off reference"
                        )
        off_bytes = per_pid[0]["off"]["packed_stream_bytes"]
        if len({per_pid[p]["off"]["packed_stream_bytes"]
                for p in range(P)}) != 1:
            problems.append(f"P{P}: off packed bytes differ across processes")
        shard_bytes = [
            per_pid[p]["shard"]["packed_stream_bytes"] for p in range(P)
        ]
        mean_bytes = sum(shard_bytes) / P
        reduction = 1.0 - mean_bytes / off_bytes if off_bytes else 0.0
        expected = (P - 1) / P
        fe0 = per_pid[0]["shard"].get("fe") or {}
        # numeric parity vs the reference (the sharded arms reassociate
        # float32 sums per range, so bitwise equality is not the
        # contract off-P1; the gradient probe is a SINGLE evaluation —
        # segments are disjoint contractions — while model/scores carry
        # 3 iterations of line-search amplification)
        w_s = de64(groups[P][0]["shard"]["w_b64"])
        sc_s = de64(groups[P][0]["shard"]["scores_b64"])
        g_s = de64(groups[P][0]["shard"]["grad_b64"])
        grad_diff = float(np.max(np.abs(g_s - ref_grad)))
        w_diff = float(np.max(np.abs(w_s - ref_w)))
        scores_diff = float(np.max(np.abs(sc_s - ref_scores)))
        if grad_diff > 1e-4:
            problems.append(
                f"P{P}: gradient probe max|delta| {grad_diff:.3g} > 1e-4"
            )
        if w_diff > 2e-3:
            problems.append(f"P{P}: model max|delta| {w_diff:.3g} > 2e-3")
        if scores_diff > 2e-3:
            problems.append(
                f"P{P}: scores max|delta| {scores_diff:.3g} > 2e-3"
            )
        rung.update({
            "packed_stream_bytes_off": off_bytes,
            "packed_stream_bytes_shard_per_process": {
                str(p): shard_bytes[p] for p in range(P)
            },
            "packed_stream_bytes_shard_mean": mean_bytes,
            "packed_bytes_reduction_fraction": round(reduction, 4),
            "ideal_reduction_fraction": round(expected, 4),
            "within_5pct_of_ideal": abs(reduction - expected) <= 0.05,
            "nnz_balance": fe0.get("nnz_balance"),
            "ranges": fe0.get("ranges"),
            "grad_probe_max_abs_delta": grad_diff,
            "model_max_abs_delta": w_diff,
            "scores_max_abs_delta": scores_diff,
            "wall_s_max_shard": max(
                per_pid[p]["shard"]["wall_s"] for p in range(P)
            ),
        })
        rungs[str(P)] = rung
        gate_metrics[f"P{P}/packed_stream_bytes/off"] = float(off_bytes)
        gate_metrics[f"P{P}/packed_stream_bytes/shard_mean"] = float(
            mean_bytes
        )
        if fe0.get("nnz_balance") is not None:
            gate_metrics[f"P{P}/fe_shard/nnz_balance"] = float(
                fe0["nnz_balance"]
            )
        if fe0.get("ranges") is not None:
            gate_metrics[f"P{P}/fe_shard/ranges"] = float(fe0["ranges"])

    top = rungs[str(max(procs))]
    reduction = top["packed_bytes_reduction_fraction"]
    balance = float(top["nnz_balance"] or 0.0)
    acceptance = {
        "bitwise_and_parity_ok": not problems,
        "packed_bytes_reduction_at_top_P": reduction,
        "required_reduction": 0.40,
        "reduction_ge_required": reduction >= 0.40,
        "within_5pct_of_ideal_at_top_P": bool(top["within_5pct_of_ideal"]),
        "nnz_balance_at_top_P": round(balance, 4),
        "balance_le_1_15": bool(balance and balance <= 1.15),
    }
    doc = {
        "round": 12,
        "what": (
            "feature-range-sharded fixed-effect A/B (PHOTON_FE_SHARD): "
            "knob unset vs 0 vs 1 on a wide synthetic sparse logistic "
            f"GLM (d={MULTICHIP_R12_D}, n={MULTICHIP_R12_N}, "
            f"k={MULTICHIP_R12_K} Zipf columns), gloo loopback CPU "
            f"groups at P in {list(procs)}; packed tile-COO stream "
            "bytes from the process-wide layout cache under the 8x2 "
            "carve, solves on the untiled streamed path (3 host-L-BFGS "
            "iterations, range-global line-search scalars)"
        ),
        "d": MULTICHIP_R12_D,
        "n": MULTICHIP_R12_N,
        "ladder": rungs,
        "acceptance": acceptance,
        "gate_metrics": gate_metrics,
        "problems": problems,
        "note": (
            "CPU wall at this scale is host-pack/dispatch bound and "
            "recorded per the BASELINE protocol; the load-bearing "
            "numbers are the per-process packed-stream bytes (the "
            "range slice genuinely shrinks what each process packs, "
            "ships and pins — raw index/value streams shrink the same "
            "way via the per-row compaction) and the parity columns. "
            "The shard arms reassociate float32 reductions per range, "
            "so parity is numeric (tight bounds above), not bitwise; "
            "off/off0 ARE bitwise, per process and across P."
        ),
    }
    if problems:
        raise RuntimeError(
            f"MULTICHIP_r12: bitwise/parity contract violated: {problems}"
        )
    with open(os.path.join(here, out_path), "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    _log(
        f"[bench] MULTICHIP_r12 capture written to {out_path} "
        f"(packed-byte reduction {reduction:.1%} at P={max(procs)} vs "
        f"required 40.0%, nnz balance {balance:.3f}x)"
    )
    return doc


# -- SERVE_r13: the online-serving latency/parity capture -------------------
#
# `python bench.py --serve` drives the S_serve_zipf config (full shape)
# in a fresh subprocess and writes SERVE_r13.json: the committed record
# of the serving subsystem's operating point — open-loop Zipf(1) p50/p99
# latency, hot-set hit rate at the default 25%-of-RE-bytes budget,
# micro-window occupancy — plus the two BITWISE parity counts (serve
# scores vs the batch driver, incremental refresh vs the offline
# warm-start solve), which must be zero. gate_quick.sh asserts the
# acceptance flags and gates gate_metrics against BASELINE_serve_cpu.json
# (UPDATE_BASELINE=1 re-blesses). `--serve --quick` runs the toy shape
# and writes NO artifacts — it exists for the stdout contract test; the
# hit-rate floor is only asserted on the full capture (toy shapes sit
# below it by construction).

SERVE_R13_HIT_RATE_FLOOR = 0.80


def run_serve_r13(
    out_path: str = "SERVE_r13.json",
    telemetry_dir: str | None = None,
    quick: bool = False,
) -> dict:
    """Drive the serving capture (parent mode), print the one-line JSON
    doc on stdout (the ``--quick`` contract), and — full mode only —
    write ``SERVE_r13.json``. Raises on any parity mismatch or a
    full-shape hit rate below the acceptance floor."""
    here = os.path.dirname(os.path.abspath(__file__))
    res = _run_config_subprocess(
        "S_serve_zipf", quick=quick, telemetry_dir=telemetry_dir
    )
    if "error" in res:
        raise RuntimeError(f"SERVE_r13: S_serve_zipf failed: {res['error']}")

    problems: list[str] = []
    score_mm = int(res["score_parity_mismatches"])
    refresh_mm = int(res["refresh_parity_mismatches"])
    if score_mm:
        problems.append(
            f"serve-path scores != batch driver: {score_mm} u32 mismatches"
        )
    if refresh_mm:
        problems.append(
            f"refresh != offline warm-start solve: {refresh_mm} u32 "
            f"mismatches (refreshed row + untouched rows)"
        )
    hit = float(res["serve_hot_hit_rate"])
    if not quick and hit < SERVE_R13_HIT_RATE_FLOOR:
        problems.append(
            f"hot-set hit rate {hit:.4f} < {SERVE_R13_HIT_RATE_FLOOR} "
            f"under Zipf(1) at the 25% budget"
        )
    budget_frac = (
        res["serve_hot_budget_bytes"] / res["serve_total_re_bytes"]
        if res.get("serve_total_re_bytes") else 0.0
    )
    acceptance = {
        "score_parity_bitwise": score_mm == 0,
        "refresh_parity_bitwise": refresh_mm == 0,
        "hot_hit_rate": round(hit, 4),
        "required_hit_rate": SERVE_R13_HIT_RATE_FLOOR,
        "hit_rate_ge_required": hit >= SERVE_R13_HIT_RATE_FLOOR,
        "hot_budget_fraction_of_re_bytes": round(budget_frac, 4),
    }
    gate_metrics = {
        "serve/latency_p50_ms": float(res["serve_latency_p50_ms"]),
        "serve/latency_p99_ms": float(res["serve_latency_p99_ms"]),
        "serve/hot_hit_rate": hit,
        "serve/window_occupancy": float(res["serve_window_occupancy_mean"]),
        # parity counts gate EXACT (tier {"rel": 0, "abs": 0}): any
        # nonzero current vs the committed-zero baseline fails
        "serve/refresh_parity": float(refresh_mm),
        "serve/score_parity": float(score_mm),
    }
    doc = {
        "round": 13,
        "what": (
            "online-serving capture (S_serve_zipf): a fixed + per-member "
            "+ per-item GAME model served through the HotModelStore "
            "(hot-set budget = default 25% of RE coefficient bytes) "
            "under an open-loop Zipf(1) trace at a fixed offered rate; "
            "micro-window batched scoring (padded to max-batch, one "
            "program geometry for the server's lifetime); BITWISE "
            "score parity vs the batch driver and BITWISE incremental-"
            "refresh parity vs the offline warm-start solve"
        ),
        "quick": quick,
        "shape": res["shape"],
        "trace": {
            "offered_rate_hz": res["offered_rate_hz"],
            "achieved_rate_hz": res["achieved_rate_hz"],
            "elapsed_s": res["sec_trace"],
            "requests": res["serve_requests"],
            "windows": res["serve_windows"],
            "latency_p50_ms": res["serve_latency_p50_ms"],
            "latency_p99_ms": res["serve_latency_p99_ms"],
            "latency_mean_ms": res["serve_latency_mean_ms"],
            "hot_hit_rate": res["serve_hot_hit_rate"],
            "window_occupancy_mean": res["serve_window_occupancy_mean"],
            "hot_budget_bytes": res["serve_hot_budget_bytes"],
            "total_re_bytes": res["serve_total_re_bytes"],
        },
        "acceptance": acceptance,
        "gate_metrics": gate_metrics,
        "problems": problems,
        "note": (
            "CPU capture per the BASELINE protocol: absolute latency is "
            "host-dispatch bound (the window scorer pays per-op dispatch "
            "on this backend), so the latency tiers gate LOOSELY and the "
            "load-bearing numbers are the parity counts (exact) and the "
            "hit rate (floor). The per-item effect stays hot-resident "
            "under the shared budget — that blended locality, not the "
            "member effect alone, is what clears the 0.8 floor; on-chip "
            "latency numbers remain a ROADMAP item."
        ),
    }
    # the single-JSON-line stdout contract (same discipline as --quick);
    # diagnostics go to stderr via _log
    print(json.dumps(doc))
    if problems:
        raise RuntimeError(f"SERVE_r13: acceptance violated: {problems}")
    if not quick:
        with open(os.path.join(here, out_path), "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        _log(
            f"[bench] SERVE_r13 capture written to {out_path} "
            f"(p50 {doc['trace']['latency_p50_ms']:.2f} ms, p99 "
            f"{doc['trace']['latency_p99_ms']:.2f} ms, hit rate "
            f"{hit:.3f} >= {SERVE_R13_HIT_RATE_FLOOR})"
        )
    return doc


def run_stream_r14(
    out_path: str = "BENCH_r14_stream_cpu.json",
    telemetry_dir: str | None = None,
    quick: bool = False,
) -> dict:
    """Drive the streaming-executor capture (X_stream, parent mode),
    print the one-line JSON doc on stdout, and — full mode only — write
    ``BENCH_r14_stream_cpu.json``. Raises on a parity mismatch or when
    the executor's content-keyed arbiter fails to dedup ANY cross-stream
    transfer bytes (the perf claim the PR ships)."""
    here = os.path.dirname(os.path.abspath(__file__))
    res = _run_config_subprocess(
        "X_stream", quick=quick, telemetry_dir=telemetry_dir
    )
    if "error" in res:
        raise RuntimeError(f"STREAM_r14: X_stream failed: {res['error']}")

    problems: list[str] = []
    mm = int(res["parity_mismatches"])
    if mm:
        problems.append(
            f"executor-on != executor-off: {mm} u32 mismatches across "
            f"final weights + per-visit validation scores"
        )
    dedup = int(res["dedup_bytes"])
    if dedup <= 0:
        problems.append(
            f"no cross-stream transfer dedup: off "
            f"{res['transfer_bytes_off']} B vs on "
            f"{res['transfer_bytes_on']} B"
        )
    acceptance = {
        "bitwise_identical": mm == 0,
        "transfer_bytes_off": int(res["transfer_bytes_off"]),
        "transfer_bytes_on": int(res["transfer_bytes_on"]),
        "dedup_fraction": float(res["dedup_fraction"]),
        "transfer_bytes_reduced": dedup > 0,
    }
    gate_metrics = {
        # lower-is-better tiers only ("stream/" rel 0.5; evictions get
        # their own absolute slack; parity gates EXACT)
        "stream/transfer_bytes": float(res["transfer_bytes_on"]),
        "stream/cache_evictions": float(res["stream_cache_evictions"]),
        "stream/parity": float(mm),
    }
    doc = {
        "round": 14,
        "what": (
            "streaming-executor capture (X_stream): an L-BFGS fit with "
            "per-iteration validation, where the validation objective "
            "replays the training chunks through FRESH host arrays (a "
            "second loader's copy of the shard); executor-off transfers "
            "BOTH working sets (the storage-keyed cache cannot see they "
            "are the same bytes), executor-on dedups the validation set "
            "against the training stream's resident entries "
            "(content-keyed multi-tenant arbiter); both arms BITWISE "
            "identical"
        ),
        "quick": quick,
        "shape": res["shape"],
        "measure": {
            "sec_off": res["sec_off"],
            "sec_on": res["sec_on"],
            "transfer_bytes_off": res["transfer_bytes_off"],
            "transfer_bytes_on": res["transfer_bytes_on"],
            "dedup_bytes": res["dedup_bytes"],
            "dedup_fraction": res["dedup_fraction"],
            "consumer_wait_s_off": res["consumer_wait_s_off"],
            "consumer_wait_s_on": res["consumer_wait_s_on"],
            "stream_cache_hits": res["stream_cache_hits"],
            "stream_cache_shared_hits": res["stream_cache_shared_hits"],
            "stream_cache_misses": res["stream_cache_misses"],
            "stream_cache_evictions": res["stream_cache_evictions"],
        },
        "acceptance": acceptance,
        "gate_metrics": gate_metrics,
        "problems": problems,
        "note": (
            "CPU capture per the BASELINE protocol: transfer bytes are "
            "counted from the cache byte counters each arm actually "
            "charges (prefetch.cache.miss_bytes off, "
            "stream.cache.miss_bytes on) — deterministic for a fixed "
            "shape, which is why they gate at a tight tier while the "
            "wait-second deltas ride the doc ungated. The dedup "
            "fraction is the shared working-set fraction (~half: two "
            "content-identical chunk sets, one transfer), plus the "
            "content-keyed bonus of constant columns (all-zero offsets "
            "/ all-one weights collapse to one entry across chunks, "
            "which the storage-keyed cache transfers per chunk)."
        ),
    }
    print(json.dumps(doc))
    if problems:
        raise RuntimeError(f"STREAM_r14: acceptance violated: {problems}")
    if not quick:
        with open(os.path.join(here, out_path), "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        _log(
            f"[bench] STREAM_r14 capture written to {out_path} "
            f"(dedup {res['dedup_fraction']:.1%} of off-arm transfer "
            f"bytes, {res['stream_cache_hits']} resident hits, "
            f"parity bitwise)"
        )
    return doc


if __name__ == "__main__":
    args = sys.argv[1:]
    telemetry_dir = None
    if "--telemetry-dir" in args:
        i = args.index("--telemetry-dir")
        if i + 1 >= len(args):
            _log("usage: --telemetry-dir requires a directory argument")
            sys.exit(2)
        telemetry_dir = args[i + 1]
        del args[i:i + 2]
    if len(args) >= 2 and args[0] == "--config":
        _run_one(args[1], quick="--quick" in args[2:],
                 telemetry_dir=telemetry_dir)
    elif args == ["--quick"]:
        main(quick=True, telemetry_dir=telemetry_dir)
    elif args and args[0] == "--multichip-r06-worker":
        _multichip_r06_worker(
            args[1], int(args[2]), int(args[3]), args[4],
            telemetry_dir,
        )
    elif args and args[0] in ("--multichip-r06", "--multichip-r07"):
        # one recipe, two names: --multichip-r07 is the r06 capture plus
        # the fleet-telemetry readout (shards + straggler summary); the
        # old flag keeps working and produces the same successor doc
        run_multichip_r06(
            telemetry_dir=telemetry_dir or "telemetry_r06",
            nproc=int(args[1]) if len(args) > 1 else 2,
        )
    elif args and args[0] == "--multichip-r08-worker":
        _multichip_r08_worker(args[1], int(args[2]), int(args[3]))
    elif args and args[0] == "--multichip-r08":
        run_multichip_r08(
            nproc=int(args[1]) if len(args) > 1 else MULTICHIP_R08_NPROC,
        )
    elif args and args[0] == "--multichip-r09-worker":
        _multichip_r09_worker(args[1], int(args[2]), int(args[3]))
    elif args and args[0] == "--multichip-r09":
        run_multichip_r09(
            nproc=int(args[1]) if len(args) > 1 else MULTICHIP_R09_NPROC,
        )
    elif args and args[0] == "--multichip-r10-worker":
        _multichip_r10_worker(args[1], int(args[2]), int(args[3]))
    elif args and args[0] == "--multichip-r10":
        run_multichip_r10(
            nproc=int(args[1]) if len(args) > 1 else MULTICHIP_R10_NPROC,
        )
    elif args and args[0] == "--multichip-r11-worker":
        _multichip_r11_worker(args[1], int(args[2]), int(args[3]))
    elif args and args[0] == "--multichip-r11":
        run_multichip_r11(
            nproc=int(args[1]) if len(args) > 1 else MULTICHIP_R11_NPROC,
        )
    elif args and args[0] == "--multichip-r12-worker":
        _multichip_r12_worker(args[1], int(args[2]), int(args[3]))
    elif args and args[0] == "--multichip-r12":
        run_multichip_r12(
            procs=(
                tuple(int(a) for a in args[1:])
                if len(args) > 1 else MULTICHIP_R12_PROCS
            ),
        )
    elif args and args[0] == "--serve":
        run_serve_r13(
            telemetry_dir=telemetry_dir,
            quick="--quick" in args[1:],
        )
    elif args and args[0] == "--stream":
        run_stream_r14(
            telemetry_dir=telemetry_dir,
            quick="--quick" in args[1:],
        )
    elif not args:
        main(telemetry_dir=telemetry_dir)
    else:
        _log(f"usage: bench.py [--quick | "
             f"--config NAME [--quick] | --serve [--quick] | "
             f"--stream [--quick] | "
             f"--multichip-r07 [NPROC] | "
             f"--multichip-r08 [NPROC] | --multichip-r09 [NPROC] | "
             f"--multichip-r10 [NPROC] | --multichip-r11 [NPROC] | "
             f"--multichip-r12 [P...]] "
             f"[--telemetry-dir DIR]; got {args}")
        sys.exit(2)
