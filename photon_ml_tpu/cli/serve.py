"""Online serving driver (``photon-ml-tpu serve``).

Loads a PUBLISHED GAME model — a manifest root written by
``io/model_io.publish_game_model`` (``MANIFEST.json`` pointer, atomic) or
a bare ``save_game_model`` directory — into a :class:`HotModelStore`
(fixed effects device-resident whole, random effects behind the
byte-budgeted hot working set) and drives the micro-window scoring loop
against an open-loop Zipf trace at a fixed offered rate: the serving
subsystem end to end, on one process, with the full latency/hit-rate
telemetry a ``--telemetry-dir`` run archives for ``photon-ml-tpu
report``.

Hot swap: with ``--poll-every N`` the trace runs in N-request slices and
the manifest fingerprint is re-peeked between slices
(``peek_published_fingerprint`` — no directory scraping, no model
load); a changed fingerprint swaps a freshly-loaded snapshot in before
the next slice. Publication is atomic, so the poll either sees the old
complete snapshot or the new one.

The stdout contract is one JSON summary line (requests, windows,
latency p50/p99, hot-set hit rate, occupancy, swaps) — the same
discipline as ``bench.py --quick``.

Usage:
    photon-ml-tpu serve --model-root published/ \\
        [--requests 10000] [--rate-hz 2000] [--zipf-s 1.0] [--seed 0] \\
        [--hot-bytes N] [--max-batch B] [--max-wait-ms W] \\
        [--poll-every N] [--telemetry-dir DIR]
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from photon_ml_tpu.utils import PhotonLogger
from photon_ml_tpu.utils.compile_cache import configure_compile_cache


def _synthetic_requests(
    model, n: int, zipf_s: float, seed: int
) -> list:
    """An open-loop request list shaped by the loaded model: one Zipf
    entity stream per random-effect tag, N(0, 1) features per shard at
    the model's dims (arrival times are stamped by the caller)."""
    from photon_ml_tpu.game.models import FixedEffectModel, RandomEffectModel
    from photon_ml_tpu.serve.loadgen import zipf_entity_trace
    from photon_ml_tpu.serve.router import ScoreRequest

    rng = np.random.default_rng(seed)
    shard_dims: dict[str, int] = {}
    id_streams: dict[str, np.ndarray] = {}
    for i, (cid, sub) in enumerate(sorted(model.models.items())):
        if isinstance(sub, FixedEffectModel):
            shard_dims[sub.feature_shard_id] = int(
                sub.model.coefficients.dim
            )
        elif isinstance(sub, RandomEffectModel):
            shard_dims[sub.feature_shard_id] = int(sub.coefficients.shape[1])
            id_streams[sub.random_effect_type] = zipf_entity_trace(
                sub.num_entities, n, s=zipf_s,
                rng=np.random.default_rng(seed + 1 + i),
            )
    features = {
        sid: rng.normal(size=(n, d)).astype(np.float32)
        for sid, d in shard_dims.items()
    }
    return [
        ScoreRequest(
            rid=i,
            features={sid: features[sid][i] for sid in shard_dims},
            id_tags={tag: int(ids[i]) for tag, ids in id_streams.items()},
        )
        for i in range(n)
    ]


def _load(model_root: str):
    """(model, fingerprint-or-None): manifest root when published,
    bare ``save_game_model`` directory otherwise."""
    from photon_ml_tpu.io.model_io import (
        MODEL_MANIFEST,
        load_game_model,
        load_published_model,
    )

    if os.path.exists(os.path.join(model_root, MODEL_MANIFEST)):
        model, manifest = load_published_model(model_root)
        return model, manifest.get("fingerprint")
    return load_game_model(model_root), None


def run(
    model_root: str,
    requests: int = 10_000,
    rate_hz: float = 2000.0,
    zipf_s: float = 1.0,
    seed: int = 0,
    hot_bytes: int | None = None,
    max_batch: int | None = None,
    max_wait_ms: float | None = None,
    poll_every: int = 0,
    logger: PhotonLogger | None = None,
) -> dict:
    from photon_ml_tpu.io.model_io import peek_published_fingerprint
    from photon_ml_tpu.serve.loadgen import (
        open_loop_arrivals,
        run_serve_trace,
    )
    from photon_ml_tpu.serve.store import HotModelStore

    logger = logger or PhotonLogger(None)
    model, fingerprint = _load(model_root)
    store = HotModelStore(model, budget_bytes=hot_bytes)
    logger.info(
        f"serving model from {model_root} "
        f"(fingerprint {fingerprint or 'unpublished'}): hot budget "
        f"{store.budget_bytes()}B of {store.total_re_bytes}B RE bytes"
    )

    reqs = _synthetic_requests(model, requests, zipf_s, seed)
    arrivals = open_loop_arrivals(
        requests, rate_hz, rng=np.random.default_rng(seed + 97)
    )
    for r, t in zip(reqs, arrivals):
        r.arrival_s = float(t)

    swaps = 0
    slices = (
        [reqs]
        if poll_every <= 0
        else [reqs[i:i + poll_every] for i in range(0, len(reqs), poll_every)]
    )
    lat_p50 = lat_p99 = occupancy = 0.0
    windows = 0
    scores: dict[int, float] = {}
    base_s = 0.0
    for sl in slices:
        # each slice re-anchors its arrivals so a long manifest poll (or
        # a slow slice) doesn't bill queueing delay to the next slice
        for r in sl:
            r.arrival_s -= base_s
        base_s += float(sl[-1].arrival_s)
        summary = run_serve_trace(
            store, sl, max_batch=max_batch, max_wait_ms=max_wait_ms,
        )
        windows += summary["windows"]
        scores.update(summary["scores"])
        lat_p50, lat_p99 = summary["latency_p50_ms"], summary["latency_p99_ms"]
        occupancy = summary["window_occupancy_mean"]
        if poll_every > 0 and fingerprint is not None:
            fresh = peek_published_fingerprint(model_root)
            if fresh is not None and fresh != fingerprint:
                model, fingerprint = _load(model_root)
                store = HotModelStore(model, budget_bytes=hot_bytes)
                swaps += 1
                logger.info(f"hot-swapped snapshot (fingerprint {fresh})")

    out = {
        "requests": requests,
        "windows": windows,
        "latency_p50_ms": round(lat_p50, 4),
        "latency_p99_ms": round(lat_p99, 4),
        "hot_hit_rate": round(store.hit_rate(), 4),
        "window_occupancy_mean": round(occupancy, 4),
        "hot_budget_bytes": store.budget_bytes(),
        "snapshot_swaps": swaps,
        "fingerprint": fingerprint,
    }
    print(json.dumps(out))
    # the per-request scores ride the return value only: the stdout
    # contract stays one summary line
    return {**out, "scores": scores}


def main(argv: list[str] | None = None) -> None:
    configure_compile_cache()
    p = argparse.ArgumentParser(description="online GAME serving driver")
    p.add_argument(
        "--model-root", required=True,
        help="published-model root (MANIFEST.json) or a bare model dir",
    )
    p.add_argument("--requests", type=int, default=10_000)
    p.add_argument("--rate-hz", type=float, default=2000.0)
    p.add_argument("--zipf-s", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--hot-bytes", type=int, default=None,
        help="hot-set byte budget (default: PHOTON_SERVE_HOT_BYTES, "
             "else 25%% of the model's random-effect bytes)",
    )
    p.add_argument("--max-batch", type=int, default=None)
    p.add_argument("--max-wait-ms", type=float, default=None)
    p.add_argument(
        "--poll-every", type=int, default=0,
        help="re-peek the manifest fingerprint every N requests and "
             "hot-swap a newly published snapshot in (0 = never)",
    )
    p.add_argument(
        "--telemetry-dir", default=None,
        help="write the run's telemetry JSONL into this directory; "
             "render/diff with `photon-ml-tpu report`",
    )
    args = p.parse_args(argv)
    from photon_ml_tpu import obs

    obs.configure(args.telemetry_dir, run_id="serve")
    try:
        run(
            args.model_root,
            requests=args.requests,
            rate_hz=args.rate_hz,
            zipf_s=args.zipf_s,
            seed=args.seed,
            hot_bytes=args.hot_bytes,
            max_batch=args.max_batch,
            max_wait_ms=args.max_wait_ms,
            poll_every=args.poll_every,
        )
    finally:
        obs.shutdown()


if __name__ == "__main__":
    main()
