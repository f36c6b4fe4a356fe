"""Block coordinate descent over GAME coordinates.

Reference parity: ``photon-api::ml.algorithm.CoordinateDescent`` (SURVEY.md
§2.2, §3.1): iterate the configured coordinate sequence for N outer
iterations; for each coordinate, the training offsets are
``base_offsets + total_score − this coordinate's score`` (residual
exchange); retrain, update that coordinate's scores; track per-iteration
validation metrics.

Coordinates present in the initial (warm-start) model but absent from the
update sequence are "locked": they keep contributing scores but are never
retrained — matching the reference's treatment of pre-trained coordinates.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Mapping, Sequence

import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.evaluation import EvaluationResults, evaluate_all
from photon_ml_tpu.obs import emit_event, span
from photon_ml_tpu.obs.spans import (
    DESCENT_CHECKPOINT,
    DESCENT_COLLECT,
    DESCENT_ITER,
    DESCENT_LAUNCH,
    DESCENT_PREPARE,
    DESCENT_RUN,
    DESCENT_VALIDATION,
    DESCENT_VISIT,
)
from photon_ml_tpu.obs.stages import coord, stage
from photon_ml_tpu.game.coordinate import Coordinate
from photon_ml_tpu.game.data import GameBatch
from photon_ml_tpu.game.models import GameModel
from photon_ml_tpu.types import TaskType

Array = jnp.ndarray


def _build_fused_outer(coordinates: Mapping[str, Any], seq: Sequence[str]):
    """One jitted program per CHUNK of outer iterations: every coordinate's
    fused visit (offsets → solve → score → total) chained in sequence, and
    the whole sequence chained over R iterations by ``lax.scan`` (the
    coordinates' pure ``advance`` hooks wire one visit's result into the
    next visit's warm start, exactly as the host loop does through the
    model objects). Returns a host callable ``run_outer(model, total,
    scores, r, record) -> (model, total, scores, trackers_by_cid_per_iter)``, or
    None when any coordinate needs host-side staging per visit (per-visit
    down-sampling; under a mesh, one that spans processes, a batch not
    placed over it, owned-bucket placement: ``game/coordinate``'s gates).

    Under a mesh the program is the same chain: each coordinate's ``apply``
    is a ``shard_map`` over the mesh with its partitioning written down, the
    total and every coordinate's score stay row-sharded through the scan,
    and the coefficients are whole on every device.

    Why: each program launch costs fixed latency on remote-attached
    accelerators; per-visit fusion pays K launches per outer iteration,
    per-outer fusion pays one — and the scan amortizes even that one over
    R iterations, so the launch cost vanishes from the per-iteration
    marginal entirely."""
    import jax
    from jax import lax

    parts = []
    for cid in seq:
        get = getattr(coordinates[cid], "_fused_visit_parts", None)
        p = get() if get is not None else None
        if p is None:
            return None
        parts.append(p)
    applies = tuple(p[1] for p in parts)
    advances = tuple(p[3] for p in parts)

    @partial(jax.jit, static_argnames=("r",))
    def fused(total, owns, statics, r):
        def step(carry, _):
            total, owns, statics = carry
            outs = []
            owns = list(owns)
            statics = list(statics)
            for i in range(len(applies)):
                with stage(coord(seq[i])):
                    aux, s_new, total = applies[i](statics[i], total, owns[i])
                    owns[i] = s_new
                    statics[i] = advances[i](aux, statics[i])
                outs.append(aux)  # scores come from the carry, not the ys
            return (total, tuple(owns), tuple(statics)), tuple(outs)

        (total, owns, _), stacked = lax.scan(
            step, (total, owns, statics), None, length=r
        )
        return total, owns, stacked

    @partial(jax.jit, static_argnames=("r",))
    def slice_all(stacked, r):
        # unstack the per-iteration aux in ONE dispatch: slicing leaf-by-
        # leaf on the host side costs one tiny device program PER LEAF per
        # iteration per coordinate (~100 dispatches per chunk — measured
        # 10× the whole chunk's solve time in round 5, where a dispatch
        # cost 0.1 s or more)
        return tuple(
            jax.tree.map(lambda a: a[i], stacked) for i in range(r)
        )

    def run_outer(model, total, scores, r=1, record=None):
        # three host steps a launch, each a span (obs/spans.py): what the
        # device waits for between two launches is one of them.
        # ``record(model, total, scores, trackers_per_iter)`` is the
        # caller's own bookkeeping of the launch, run inside the last span
        with span(DESCENT_PREPARE):
            owns = tuple(
                scores[cid] if cid in scores else jnp.zeros_like(total)
                for cid in seq
            )
            statics = tuple(
                p[0](model.models.get(cid)) for p, cid in zip(parts, seq)
            )
        # one span per fused LAUNCH: the per-iteration boundaries do not
        # exist on the host inside a scanned chunk
        with span(DESCENT_LAUNCH, iterations=r):
            total, owns, stacked = fused(total, owns, statics, r)
        with span(DESCENT_COLLECT):
            scores = dict(scores)
            # per-iteration trackers come back STACKED (leading R axis);
            # postprocess each iteration's slice — one dispatch, no host syncs
            sliced = slice_all(stacked, r)
            trackers_per_iter: list[dict[str, Any]] = []
            for it in range(r):
                iter_trackers: dict[str, Any] = {}
                for i, (cid, p) in enumerate(zip(seq, parts)):
                    aux_it = sliced[it][i]
                    # only the chunk's LAST iteration needs the sub-model (a
                    # projected coordinate's model build dispatches a device
                    # matmul — r−1 of those per chunk would claw back the
                    # dispatch savings the chunking exists for)
                    last = it == r - 1
                    sub_model, tracker = p[2](aux_it, build_model=last)
                    iter_trackers[cid] = tracker
                    if last:
                        model = model.updated(cid, sub_model)
                trackers_per_iter.append(iter_trackers)
            for i, cid in enumerate(seq):
                scores[cid] = owns[i]
            if record is not None:
                record(model, total, scores, trackers_per_iter)
        return model, total, scores, trackers_per_iter

    return run_outer


# chunk cap: bounds the stacked per-iteration tracker/diagnostic buffers a
# single launch returns (R × the per-iteration aux, e.g. R·(E·d) coefficient
# snapshots) while still amortizing dispatch latency R-fold
_MAX_FUSED_CHUNK = 16

# In-place degrade for the in-memory descent (PHOTON_DESCENT_DEGRADE;
# the knob idiom: env > module global, strict int parse, call-time
# read). 0 (default) keeps today's behavior byte-for-byte: a PeerLost
# aborts with the actionable restart-from-checkpoint message. 1 catches
# the loss at the OUTER-ITERATION boundary instead: roll call, shrink
# to the degraded process group, re-plan random-effect ownership over
# the survivors (prepare_buckets re-runs under the degraded
# effective_process_* shape), drop the compiled programs keyed on the
# old topology, and re-run the interrupted iteration from its start-of-
# iteration state — run() returns normally, no process restarts.
DESCENT_DEGRADE = 0

# iteration-retry budget for roll calls that find every peer alive (a
# link flap, not a loss): the ring collectives the in-memory combine
# rides have no per-exchange retry, so the iteration re-run IS the
# transient absorption — bounded, so a persistently flapping link still
# surfaces as an error instead of an infinite loop
_MAX_FLAP_RETRIES = 3


def descent_degrade_enabled() -> bool:
    """Strict parse like every sibling knob — a typo must fail the run
    loudly, not silently keep the abort-on-loss behavior."""
    env = os.environ.get("PHOTON_DESCENT_DEGRADE")
    if env is not None and env != "":
        return int(env) != 0
    return int(DESCENT_DEGRADE) != 0


def _pow2_floor(x: int) -> int:
    return 1 << (max(x, 1).bit_length() - 1)


def _is_output_process() -> bool:
    """Multi-host: every process loads checkpoints (read-only); exactly one
    writes them — concurrent writers to shared storage corrupt files. In a
    degraded group the lowest-ranked SURVIVOR writes (the multihost helper
    already resolves that; identical to ``jax.process_index() == 0`` on a
    healthy fleet)."""
    from photon_ml_tpu.parallel.multihost import is_output_process

    return is_output_process()


@dataclass(frozen=True)
class CoordinateDescentResult:
    model: GameModel
    # validation_history[i][cid] — metrics after training cid in outer iter i
    validation_history: list[dict[str, EvaluationResults]]
    trackers: dict[str, list[Any]]  # cid → per-iteration optimizer trackers
    training_scores: dict[str, Array]  # final per-coordinate scores

    @property
    def final_validation(self) -> EvaluationResults | None:
        if not self.validation_history:
            return None
        last = self.validation_history[-1]
        if not last:
            return None
        return last[list(last)[-1]]


class CoordinateDescent:
    """Drives coordinates through residual-offset retraining.

    ``coordinates`` must share one training ``GameBatch`` (they hold views
    of it); ``validation_batch`` is scored with the evolving full model
    after each coordinate update, mirroring the reference's per-iteration
    validation tracking.
    """

    def __init__(
        self,
        coordinates: Mapping[str, Coordinate],
        batch: GameBatch,
        task_type: TaskType,
        validation_batch: GameBatch | None = None,
        evaluators: Sequence[str] = (),
        logger: Callable[[str], None] | None = None,
        mesh=None,
    ):
        self.coordinates = dict(coordinates)
        self.batch = batch
        self.task_type = task_type
        self.validation_batch = validation_batch
        self.evaluators = list(evaluators)
        self._log = logger or (lambda msg: None)
        # evaluators with sharded implementations (BUCKETED_AUC) compute
        # over the mesh without gathering the score vector to one device
        self.mesh = mesh
        # fused outer-iteration programs, keyed by update sequence (the
        # jitted chain compiles once and re-enters across run() calls)
        self._fused_outer_cache: dict[tuple, Any] = {}

    def run(
        self,
        update_sequence: Sequence[str],
        num_iterations: int,
        initial_model: GameModel | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_fingerprint: str | None = None,
        resume_fingerprints: Sequence[str] = (),
    ) -> CoordinateDescentResult:
        """``checkpoint_dir`` enables resumable descent: the model is
        checkpointed after every outer iteration, and an existing checkpoint
        in the directory restarts from where it left off (exceeds the
        reference, which only supports whole-model warm start —
        SURVEY.md §5.4). ``checkpoint_fingerprint`` identifies the training
        setup; a stored checkpoint with a different fingerprint is ignored
        rather than resumed. ``resume_fingerprints`` extends the accepted
        set (the ``load_checkpoint`` collection support the streamed
        trainer already uses): a pre-loss layout's checkpoint — whose
        fingerprint legitimately differs from the survivor layout's — can
        resume a degraded restart instead of retraining from scratch."""
        for cid in update_sequence:
            if cid not in self.coordinates:
                raise KeyError(f"update sequence names unknown coordinate {cid!r}")
        try:
            with span(DESCENT_RUN, iterations=num_iterations):
                return self._run_inner(
                    update_sequence, num_iterations, initial_model,
                    checkpoint_dir, checkpoint_fingerprint,
                    resume_fingerprints,
                )
        except BaseException as e:
            self._raise_if_peer_lost(e, checkpoint_dir)
            raise

    @staticmethod
    def _raise_if_peer_lost(e: BaseException, checkpoint_dir) -> None:
        """The in-memory descent cannot shrink its world mid-run — every
        compiled program spans the FULL device mesh (the host loop's
        per-visit programs over a mesh that spans processes; the fused
        outer iteration only ever over one process's own devices, where no
        peer can be lost), so a lost process invalidates the executables
        themselves (unlike the streamed trainer, whose host-side exchanges
        re-plan around the survivor set). What it CAN do is turn the 300
        s-timeout stack into an actionable, telemetry-visible instruction:
        restart the job on the surviving hosts and resume from the
        per-iteration checkpoint this class already writes."""
        from photon_ml_tpu.parallel.multihost import PeerLost

        if not isinstance(e, PeerLost):
            return
        emit_event("peer_lost", peer=int(e.peer), error=str(e))
        hint = (
            f"resume from the last per-iteration checkpoint in "
            f"{checkpoint_dir!r} by restarting on the surviving hosts"
            if checkpoint_dir is not None else
            "re-run with checkpoint_dir set to make the restart resume "
            "instead of retrain"
        )
        raise RuntimeError(
            f"in-memory coordinate descent lost process {e.peer}: the "
            f"mesh-spanning executables cannot degrade in place — {hint} "
            "(the streamed trainer recovers in place; see README "
            "'Fault tolerance & recovery')"
        ) from e

    def _run_inner(
        self,
        update_sequence: Sequence[str],
        num_iterations: int,
        initial_model: GameModel | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_fingerprint: str | None = None,
        resume_fingerprints: Sequence[str] = (),
    ) -> CoordinateDescentResult:

        start_iteration = 0
        model = initial_model or GameModel(models={}, task_type=self.task_type)
        ckpt = None
        digest = None
        if checkpoint_dir is not None:
            from photon_ml_tpu.checkpoint import batch_digest, load_checkpoint

            # ties restored residual scores to THIS batch: a checkpoint from
            # different data resumes the model but recomputes the scores
            digest = batch_digest(self.batch.labels, self.batch.weights)
            # a None primary fingerprint keeps its accept-anything
            # semantics; otherwise the allow-list is the primary plus the
            # caller's resume collection (the degraded-restart path)
            accepted: Any = checkpoint_fingerprint
            if checkpoint_fingerprint is not None and resume_fingerprints:
                accepted = (checkpoint_fingerprint, *resume_fingerprints)
            ckpt = load_checkpoint(
                checkpoint_dir,
                fingerprint=accepted,
                data_digest=digest,
            )
            if ckpt is not None:
                model = ckpt.model
                start_iteration = ckpt.next_iteration
                self._log(
                    f"resuming coordinate descent from checkpoint at outer "
                    f"iteration {start_iteration}"
                )

        trackers: dict[str, list[Any]] = {cid: [] for cid in update_sequence}
        validation_history: list[dict[str, EvaluationResults]] = []

        scores: dict[str, Array]
        if ckpt is not None and ckpt.scores is not None and ckpt.total is not None:
            # bit-exact resume: restore the residual-exchange state rather
            # than recomputing it (recomputation differs by float
            # re-association, which the per-entity solvers amplify)
            scores = {cid: jnp.asarray(s) for cid, s in ckpt.scores.items()}
            total = jnp.asarray(ckpt.total)
        else:
            # warm-start scores for every coordinate already in the model
            # (including locked ones not in the update sequence)
            scores = {}
            for cid, sub in model.models.items():
                coord = self.coordinates.get(cid)
                scores[cid] = (
                    coord.score(sub) if coord is not None else sub.score(self.batch)
                )
            # running total of base offsets + every coordinate's score, so the
            # per-coordinate residual is one subtraction (total − own score),
            # not an O(K²) re-sum over the other coordinates
            total = self.batch.offsets
            for s in scores.values():
                total = total + s

        # whole-outer-iteration fusion: when no per-visit validation is
        # configured and every coordinate runs the fused-visit fast path,
        # ALL coordinate visits of an outer iteration trace into ONE
        # program — on launch-latency-dominated platforms the per-launch
        # cost is the wall-clock floor, so K coordinates at one launch
        # beat K launches regardless of the math inside
        fused_outer = None
        if not (self.validation_batch is not None and self.evaluators):
            key = tuple(update_sequence)
            if key not in self._fused_outer_cache:
                # a coordinate's first use stages its tensors here
                with span(DESCENT_PREPARE):
                    self._fused_outer_cache[key] = _build_fused_outer(
                        self.coordinates, update_sequence
                    )
            fused_outer = self._fused_outer_cache[key]

        def append_tracker(cid: str, tracker) -> None:
            # bound HBM retention of lazy per-entity diagnostics: the
            # previous visit's device buffers are released UNMATERIALIZED
            # — earlier-visit per-entity histories are dropped by design
            # (only the final visit's diagnostics are readable); reading
            # a released tracker raises RuntimeError
            if trackers[cid]:
                release = getattr(
                    trackers[cid][-1], "release_device_diagnostics", None
                )
                if release is not None:
                    release()
            trackers[cid].append(tracker)

        def end_of_iteration(
            it: int, iter_validation, model, scores, total
        ) -> None:
            # the advanced state arrives as ARGUMENTS, not via closure:
            # the eager iteration body runs in _run_one_iteration's own
            # scope, where rebinding model/total would leave a closure
            # over this scope reading the previous iteration's values
            validation_history.append(iter_validation)
            emit_event("descent_iteration", iteration=it)
            if checkpoint_dir is not None and _is_output_process():
                from photon_ml_tpu.checkpoint import save_checkpoint

                with span(DESCENT_CHECKPOINT, iteration=it):
                    save_checkpoint(
                        checkpoint_dir,
                        model,
                        next_iteration=it + 1,
                        fingerprint=checkpoint_fingerprint,
                        scores={cid: np.asarray(s) for cid, s in scores.items()},
                        total=np.asarray(total),
                        data_digest=digest,
                    )

        def record_launch(first, model, total, scores, trackers_per_iter):
            # a fused launch's logical iterations, as events
            for j, iter_trackers in enumerate(trackers_per_iter):
                for cid in update_sequence:
                    append_tracker(cid, iter_trackers[cid])
                    self._log(f"iter {first + j} coordinate {cid}: trained")
                end_of_iteration(first + j, {}, model, scores, total)

        if fused_outer is not None:
            # iteration chunking: run outer iterations in power-of-two
            # chunks (largest first), each chunk ONE device launch — the
            # per-launch dispatch latency of remote-attached chips then
            # amortizes over the chunk instead of bounding every
            # iteration's wall-clock. Checkpoint cadence is per-iteration
            # by contract, so an enabled checkpoint_dir pins r=1. Chunks
            # are powers of two so at most log₂(cap) program variants
            # compile (the scan body itself compiles once per variant).
            cap = 1 if checkpoint_dir is not None else _MAX_FUSED_CHUNK
            it = start_iteration
            while it < num_iterations:
                r = min(_pow2_floor(num_iterations - it), cap)
                model, total, scores, _ = fused_outer(
                    model, total, scores, r, partial(record_launch, it)
                )
                it += r
            return CoordinateDescentResult(
                model=model,
                validation_history=validation_history,
                trackers=trackers,
                training_scores=scores,
            )

        from photon_ml_tpu.parallel.multihost import PeerLost

        it = start_iteration
        flap_retries = 0
        while it < num_iterations:
            iter_validation: dict[str, EvaluationResults] = {}
            # start-of-iteration rollback state for the in-place degrade
            # (PHOTON_DESCENT_DEGRADE): device arrays are immutable, so
            # holding the references IS the snapshot — every survivor
            # re-runs the interrupted iteration from the identical state
            snap_model, snap_scores, snap_total = model, dict(scores), total
            snap_trackers = {cid: len(trackers[cid]) for cid in update_sequence}
            snap_history = len(validation_history)
            try:
                self._run_one_iteration(
                    it, update_sequence, iter_validation,
                    # mutable iteration state rides a cell the body
                    # writes back through
                    state := {"model": model, "scores": scores,
                              "total": total},
                    append_tracker, end_of_iteration,
                )
            except PeerLost as e:
                if not descent_degrade_enabled():
                    raise
                shrunk = self._degrade_in_place(e, it)
                if not shrunk:
                    flap_retries += 1
                    if flap_retries > _MAX_FLAP_RETRIES:
                        raise RuntimeError(
                            f"in-memory descent iteration {it}: links "
                            f"flapped {flap_retries} times with every "
                            "peer alive — raise PHOTON_P2P_RETRIES/"
                            "BACKOFF_S rather than retrying the "
                            "iteration forever"
                        ) from e
                # roll back to the start-of-iteration state and re-run
                # this iteration over the (possibly shrunk) group
                model, scores, total = (
                    snap_model, dict(snap_scores), snap_total
                )
                for cid in update_sequence:
                    del trackers[cid][snap_trackers[cid]:]
                del validation_history[snap_history:]
                continue
            model = state["model"]
            scores = state["scores"]
            total = state["total"]
            flap_retries = 0
            it += 1

        return CoordinateDescentResult(
            model=model,
            validation_history=validation_history,
            trackers=trackers,
            training_scores=scores,
        )

    def _run_one_iteration(
        self, it, update_sequence, iter_validation, state,
        append_tracker, end_of_iteration,
    ) -> None:
        """One outer iteration of the eager (unfused) visit loop — the
        body the degrade-in-place handler treats as a transaction:
        either it completes (``state`` carries the advanced model/
        scores/total) or the caller rolls back to its start-of-
        iteration snapshot."""
        model = state["model"]
        scores = state["scores"]
        total = state["total"]
        with span(DESCENT_ITER, iteration=it):
                for cid in update_sequence:
                    coord = self.coordinates[cid]
                    with span(DESCENT_VISIT, iteration=it, coordinate=cid):
                        visit = getattr(coord, "visit", None)
                        if visit is not None:
                            # fused path: offsets → solve → score → total
                            # in ONE program launch (the coordinate falls
                            # back internally when its config needs
                            # host-side staging per visit)
                            sub_model, tracker, new_score, total = visit(
                                total, scores.get(cid), model.models.get(cid)
                            )
                        else:
                            offsets = (
                                total - scores[cid] if cid in scores else total
                            )
                            sub_model, tracker = coord.train(
                                offsets, model.models.get(cid)
                            )
                            new_score = coord.score(sub_model)
                            total = offsets + new_score
                        scores[cid] = new_score
                        model = model.updated(cid, sub_model)
                        append_tracker(cid, tracker)

                    if self.validation_batch is not None and self.evaluators:
                        with span(
                            DESCENT_VALIDATION, iteration=it, coordinate=cid
                        ):
                            vscores = model.score(self.validation_batch)
                            res = evaluate_all(
                                self.evaluators,
                                vscores,
                                self.validation_batch.labels,
                                self.validation_batch.weights,
                                group_ids=self.validation_batch.host_id_tags(),
                                mesh=self.mesh,
                            )
                        iter_validation[cid] = res
                        self._log(f"iter {it} coordinate {cid}: {res}")
                    else:
                        self._log(f"iter {it} coordinate {cid}: trained")
                end_of_iteration(it, iter_validation, model, scores, total)
        state["model"] = model
        state["scores"] = scores
        state["total"] = total

    def _degrade_in_place(self, err, iteration: int) -> bool:
        """The PHOTON_DESCENT_DEGRADE handler: confirm the loss with a
        barrier-tagged roll call, shrink the process group to the
        survivors, re-plan random-effect ownership over them and drop
        every compiled program keyed on the old topology — WITHOUT
        leaving ``run()``. Returns True when the group shrank, False
        when the roll call found every peer alive (a link flap: the
        mesh was rebuilt by the roll call, the caller just re-runs the
        iteration). Coordinates that cannot degrade (executables
        genuinely spanning the device mesh) re-raise into the
        existing actionable abort."""
        from photon_ml_tpu.obs.metrics import REGISTRY
        from photon_ml_tpu.parallel import multihost as mh

        self._log(
            f"iteration {iteration}: peer loss "
            f"(process {getattr(err, 'peer', -1)}) — starting roll call"
        )
        group, survivors, lost = mh.confirm_peer_loss(err)
        if not lost:
            emit_event(
                "descent_retry", iteration=iteration, group=list(group),
            )
            self._log(
                f"iteration {iteration}: roll call found every process "
                "alive (links flapped) — re-running the iteration over "
                "the rebuilt mesh"
            )
            return False
        # degradability gate only once the roll call CONFIRMED a loss —
        # a link flap needs no degradation, so a mesh-spanning
        # coordinate must not turn a retryable flap into the abort. A
        # mesh-spanning fixed effect (or a lane-sharded random effect)
        # cannot shrink in-process: keep the restart-from-checkpoint
        # abort for a real loss there.
        blockers = [
            getattr(coord, "_degrade_blocker", lambda: None)()
            for coord in self.coordinates.values()
        ]
        if (
            self.mesh is not None
            and self.validation_batch is not None
            and self.evaluators
        ):
            # validation scores/evaluates over the descent-level device
            # mesh every visit — the dead process's devices cannot leave
            # that mesh in-process any more than a coordinate's can
            blockers.append(
                "validation evaluates over the full device mesh"
            )
        for blocker in blockers:
            if blocker is not None:
                self._log(
                    f"iteration {iteration}: lost processes {lost} with "
                    f"PHOTON_DESCENT_DEGRADE=1, but {blocker} — falling "
                    "back to the abort path"
                )
                raise err
        mh.set_degraded_group(survivors)
        # drop the dead topology's executables/staged tensors: the next
        # visit re-prepares owned buckets over the survivor group (the
        # re-plan itself runs inside prepare_buckets, on the degraded
        # effective_process_* shape — deterministic pure-host
        # arithmetic, identical on every survivor)
        self._fused_outer_cache.clear()
        for coord in self.coordinates.values():
            reset = getattr(coord, "_reset_compiled_state", None)
            if reset is not None:
                reset()
        REGISTRY.counter_inc("fleet.degraded_descents")
        emit_event(
            "degraded_descent", iteration=iteration,
            survivors=[int(s) for s in survivors],
            lost=[int(p) for p in lost],
        )
        self._log(
            f"iteration {iteration}: lost processes {lost}, surviving "
            f"group {survivors} — degraded in place, re-running the "
            "iteration over the survivor set"
        )
        return True
