"""GLM objective: fused value / gradient / Hessian-vector kernels.

Reference parity: this module replaces the reference's entire objective
stack — ``photon-lib::ml.function.{ObjectiveFunction,DiffFunction,
TwiceDiffFunction}``, ``photon-api::ml.function.glm.DistributedGLMLossFunction``
and ``SingleNodeGLMLossFunction``, and the aggregators
(``ValueAndGradientAggregator``, ``HessianVectorAggregator``,
``HessianMatrixAggregator``, ``HessianDiagonalAggregator``) — SURVEY.md §2.2.

TPU-first design (vs the reference's broadcast + per-partition fold +
treeAggregate):

- One fused pass per evaluation: margins (MXU matmul) → pointwise loss
  derivatives (VPU, fused by XLA) → gradient contraction (MXU matmul).
- **The distributed and single-node objectives are the same code.** The
  ``axis_name`` field selects the twin (SURVEY.md §4 "twin structure"): when
  set, the objective is being traced inside ``shard_map`` over a mesh axis
  and partial sums are reduced with ``lax.psum`` over ICI — the reference's
  driver→executor broadcast *and* executor→driver treeAggregate both
  collapse into that one collective, and the optimizer loop stays on device.
- Loss semantics match the reference: objective = Σ_i weight_i·l(margin_i, y_i)
  (+ 0.5·λ₂·‖w‖² over regularized coordinates). Sums, not means, so
  regularization weights mean the same thing as in the reference.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.normalization import NormalizationContext, no_normalization
from photon_ml_tpu.obs.stages import GLM_HVP, GLM_OBJECTIVE, stage
from photon_ml_tpu.ops.batch import Batch, DenseBatch
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.types import VarianceComputationType

Array = jnp.ndarray


def reg_delta(w: Array, prior_mean, prior_precision) -> Array:
    """prec·(w − μ) — the (L2 or Gaussian-MAP) regularizer's gradient
    direction; w itself for plain L2. The ONE home for this math: both the
    device objective and the streamed twin delegate here, so the MAP policy
    cannot diverge between the paths."""
    if prior_mean is None:
        return w
    prec = jnp.ones_like(w) if prior_precision is None else prior_precision
    return prec * (w - prior_mean)


def reg_curvature(like: Array, prior_mean, prior_precision) -> Array:
    """The regularizer's diagonal curvature scale (prec, or ones)."""
    if prior_mean is None or prior_precision is None:
        return jnp.ones_like(like)
    return prior_precision


def reg_term(w: Array, l2_weight, reg_mask, prior_mean, prior_precision) -> Array:
    """0.5·λ₂·Σ maskⱼ·precⱼ·(wⱼ−μⱼ)² (μ=0, prec=1 for plain L2)."""
    delta = w if prior_mean is None else w - prior_mean
    prec = reg_curvature(w, prior_mean, prior_precision)
    return 0.5 * l2_weight * jnp.sum(reg_mask * prec * delta * delta)


def _interpret_fused() -> bool:
    """Pallas kernels run compiled on an accelerator and in interpreter
    mode on the CPU backend only (the CPU test suite exercises the
    identical program); no other backend falls back to the interpreter."""
    return jax.default_backend() == "cpu"


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["batch", "norm", "l2_weight", "reg_mask", "prior_mean",
                 "prior_precision"],
    meta_fields=["loss", "axis_name", "fused", "offsets_zero", "weights_one"],
)
@dataclass(frozen=True)
class GLMObjective:
    """Value/gradient/Hv contracts consumed by the optimizers.

    Fields:
      batch     — the (local shard of the) training data.
      norm      — normalization applied inside evaluation (never to data).
      l2_weight — scalar λ₂ (array so regularization grids don't recompile).
      reg_mask  — (d,) 0/1 mask of regularized coordinates (intercept → 0).
      loss      — pointwise loss namespace (static).
      axis_name — mesh axis to psum over, or None for single-node (static).
      fused     — use the one-pass Pallas kernels (``ops/fused.py``) for
                  value_and_grad/hvp on dense batches (static; ``X`` streams
                  from HBM once per evaluation instead of 2-3 times).
      offsets_zero / weights_one — static data hints (detected once at
                  construction): constant-0 offsets / constant-1 weights
                  let the fused kernels skip those per-row streams (4 B
                  a row each since they are lane-dense, ``ops/fused.py``).
      prior_mean / prior_precision — optional (d,) Gaussian prior for
                  incremental training: the regularizer becomes
                  0.5·λ₂·Σ maskⱼ·precⱼ·(wⱼ−μⱼ)², i.e. a MAP update toward
                  the previous model (reference: Photon-ML's incremental
                  learning uses the prior model's means/variances the same
                  way; plain L2 is the μ=0, prec=1 special case).
    """

    batch: Batch
    norm: NormalizationContext
    l2_weight: Array
    reg_mask: Array
    loss: PointwiseLoss
    axis_name: str | None = None
    fused: bool = False
    offsets_zero: bool = False
    weights_one: bool = False
    prior_mean: Array | None = None
    prior_precision: Array | None = None

    # -- collective hook (identity when single-node) --------------------------
    def _reduce(self, x):
        if self.axis_name is None:
            return x
        return lax.psum(x, self.axis_name)

    def _weighted(self, x: Array) -> Array:
        """weights * x, with zero-weight rows forced to exactly 0 so padding
        can never poison the sums (0 * inf would be NaN — e.g. an overflowed
        poisson loss on a padded row)."""
        w = self.batch.weights
        return jnp.where(w != 0.0, w * x, 0.0)

    # -- margins --------------------------------------------------------------
    def margins(self, w: Array) -> Array:
        with stage(GLM_OBJECTIVE):
            u, c = self.norm.to_effective(w)
            return self.batch.matvec(u) - c + self.batch.offsets

    # -- regularizer (plain L2 or Gaussian prior) ------------------------------
    def _reg_delta(self, w: Array) -> Array:
        return reg_delta(w, self.prior_mean, self.prior_precision)

    def _reg_curvature(self, like: Array) -> Array:
        return reg_curvature(like, self.prior_mean, self.prior_precision)

    # -- objective contracts ---------------------------------------------------
    def _l2_term(self, w: Array) -> Array:
        return reg_term(
            w, self.l2_weight, self.reg_mask, self.prior_mean,
            self.prior_precision,
        )

    @property
    def one_pass_value_grad(self) -> bool:
        """Line-search policy hint for the optimizers: evaluate
        value_and_grad at every TRIAL point (instead of value-only trials
        plus a separate gradient pass at acceptance). True when (a) the
        fused dense kernel makes value_and_grad cost one X read anyway, or
        (b) the tile-COO sparse kernels make the typical one-trial
        iteration cheaper that way (margins+grad = 2 kernel passes beats
        margins-trial + margins+grad = 3), or (c) the batch says the same
        of itself (``SubspaceDenseBatch``: 1 read of X against 2 through
        the kernel, 2 against 3 on XLA's sweeps)."""
        from photon_ml_tpu.ops.sparse_tiled import TiledSparseBatch

        return (
            self.fused
            or isinstance(self.batch, TiledSparseBatch)
            or getattr(self.batch, "one_pass_value_grad", False)
        )

    def value(self, w: Array) -> Array:
        with stage(GLM_OBJECTIVE):
            m = self.margins(w)
            local = jnp.sum(self._weighted(self.loss.value(m, self.batch.labels)))
            return self._reduce(local) + self._l2_term(w)

    # -- margin-state API (Newton's hot loop) ----------------------------------
    # Margins are affine in w, so a solver can carry m = margins(w) in its
    # loop state (updating it as m + t·dm after a line search) and derive
    # value/grad/Hessian from the STORED margins — one matvec per iteration
    # (the direction's) instead of re-deriving margins inside every
    # contract. ``optim.newton`` uses these when present; the generic
    # value/grad contracts above stay the interface for everything else.

    def direction_margins(self, p: Array) -> Array:
        """d margins / d t along direction p (no offset term)."""
        with stage(GLM_OBJECTIVE):
            u_p, c_p = self.norm.to_effective(p)
            return self.batch.matvec(u_p) - c_p

    def value_and_grad_from_margins(self, m: Array, w: Array) -> tuple[Array, Array]:
        """``value_and_grad(w)`` given m = margins(w) — saves the forward
        matvec; the gradient contraction still reads the data once."""
        with stage(GLM_OBJECTIVE):
            lv = self.loss.value(m, self.batch.labels)
            r = self._weighted(self.loss.d1(m, self.batch.labels))
            local = (jnp.sum(self._weighted(lv)), self.batch.rmatvec(r), jnp.sum(r))
            val, g_raw, r_sum = self._reduce(local)
            g = (self.norm.grad_to_model_space(g_raw, r_sum)
                 + self.l2_weight * self.reg_mask * self._reg_delta(w))
            return val + self._l2_term(w), g

    def hessian_from_margins(self, m: Array, w: Array) -> Array:
        """``hessian(w)`` given m = margins(w) (dense batches only)."""
        with stage(GLM_OBJECTIVE):
            if not isinstance(self.batch, DenseBatch):
                raise NotImplementedError(
                    "full Hessian requires a DenseBatch; use hessian_diag or hvp"
                )
            d2 = self._weighted(self.loss.d2(m, self.batch.labels))
            Z = (self.batch.X - self.norm.shifts) * self.norm.factors
            h = self._reduce(Z.T @ (d2[:, None] * Z))
            return h + jnp.diag(self.l2_weight * self.reg_mask * self._reg_curvature(self.reg_mask))

    def ray_values_from_margins(
        self, m: Array, dm: Array, w: Array, p: Array, ts: Array
    ) -> Array:
        """``ray_values`` given m = margins(w) and dm = direction_margins(p)
        — the whole Armijo ladder with NO matvec at all."""
        with stage(GLM_OBJECTIVE):
            y = self.batch.labels

            def at(t):
                return jnp.sum(self._weighted(self.loss.value(m + t * dm, y)))

            data = self._reduce(jax.vmap(at)(ts))
            return data + self._reg_ray(w, p, ts)

    def _reg_ray(self, w: Array, p: Array, ts: Array) -> Array:
        """0.5·λ·Σ mask·prec·(δ + t·p)² for every t (δ = w − μ, or w)."""
        delta = w if self.prior_mean is None else w - self.prior_mean
        prec = self._reg_curvature(w)
        q0 = jnp.sum(self.reg_mask * prec * delta * delta)
        q1 = jnp.sum(self.reg_mask * prec * delta * p)
        q2 = jnp.sum(self.reg_mask * prec * p * p)
        return 0.5 * self.l2_weight * (q0 + 2.0 * ts * q1 + ts * ts * q2)

    def ray_values(self, w: Array, p: Array, ts: Array) -> Array:
        """Objective at ``w + t·p`` for every t in ``ts`` — data is read
        ONCE regardless of len(ts).

        Margins are affine in w (``to_effective`` is linear), so
        m(t) = m(w) + t·dm with one extra matvec for dm; each trial is then
        an elementwise loss reduction over precomputed margins, and the
        quadratic regularizer expands analytically in t. Newton's Armijo
        ladder uses this: the naive ``vmap`` over trial points paid K full
        X-reads per iteration (profiled: the dominant cost of bench config
        E's per-entity solves after the solver itself went custom-call-free).
        """
        return self.ray_values_from_margins(
            self.margins(w), self.direction_margins(p), w, p, ts
        )

    def value_and_grad(self, w: Array) -> tuple[Array, Array]:
        with stage(GLM_OBJECTIVE):
            if self.fused and isinstance(self.batch, DenseBatch):
                u, c = self.norm.to_effective(w)
                local = self.batch.value_grad_pass(
                    u, c, self.loss,
                    offsets=None if self.offsets_zero else self.batch.offsets,
                    weights=None if self.weights_one else self.batch.weights,
                    interpret=_interpret_fused(),
                )
            else:
                return self.value_and_grad_from_margins(self.margins(w), w)
            val, g_raw, r_sum = self._reduce(local)
            g = (self.norm.grad_to_model_space(g_raw, r_sum)
                 + self.l2_weight * self.reg_mask * self._reg_delta(w))
            return val + self._l2_term(w), g

    def grad(self, w: Array) -> Array:
        return self.value_and_grad(w)[1]

    def hvp(self, w: Array, v: Array) -> Array:
        """Gauss-Newton/Hessian-vector product H·v = AᵀDA·v + λ₂·v (A = the
        normalized design matrix, D = diag(weight·d2)). One forward matmul +
        one reverse matmul; for TRON's CG loop this is the hot kernel."""
        with stage(GLM_OBJECTIVE), stage(GLM_HVP):
            v_eff = self.norm.factors * v
            if self.fused and isinstance(self.batch, DenseBatch):
                from photon_ml_tpu.ops.fused import fused_hvp

                u, c = self.norm.to_effective(w)
                local = fused_hvp(
                    self.batch.X, self.batch.labels,
                    None if self.offsets_zero else self.batch.offsets,
                    None if self.weights_one else self.batch.weights,
                    u, v_eff, c,
                    jnp.dot(self.norm.shifts, v_eff), loss=self.loss,
                    interpret=_interpret_fused(),
                )
            else:
                m = self.margins(w)
                d2 = self._weighted(self.loss.d2(m, self.batch.labels))
                mv = self.batch.matvec(v_eff) - jnp.dot(self.norm.shifts, v_eff)
                q = d2 * mv
                local = (self.batch.rmatvec(q), jnp.sum(q))
            hv_raw, q_sum = self._reduce(local)
            hv = self.norm.grad_to_model_space(hv_raw, q_sum)
            return hv + self.l2_weight * self.reg_mask * self._reg_curvature(v) * v

    def hessian_diag(self, w: Array) -> Array:
        """diag(H) — for VarianceComputationType.SIMPLE.

        diag_j = f_j² [ Σ d2ᵢxᵢⱼ² − 2 s_j Σ d2ᵢxᵢⱼ + s_j² Σ d2ᵢ ] + λ₂·mask.
        """
        with stage(GLM_OBJECTIVE):
            m = self.margins(w)
            d2 = self._weighted(self.loss.d2(m, self.batch.labels))
            local = (self.batch.rmatvec_sq(d2), self.batch.rmatvec(d2), jnp.sum(d2))
            sq, lin, tot = self._reduce(local)
            f, s = self.norm.factors, self.norm.shifts
            diag = f * f * (sq - 2.0 * s * lin + s * s * tot)
            return diag + self.l2_weight * self.reg_mask * self._reg_curvature(diag)

    def hessian(self, w: Array) -> Array:
        """Full (d, d) Hessian — for VarianceComputationType.FULL. Dense
        batches only (FULL variance is a small-d feature in the reference
        too: it inverts a d×d matrix on the driver)."""
        return self.hessian_from_margins(self.margins(w), w)




@partial(
    jax.tree_util.register_dataclass,
    data_fields=["means", "variances"],
    meta_fields=["min_variance"],
)
@dataclass(frozen=True)
class GaussianPrior:
    """Informative Gaussian prior for incremental training (MAP update).

    Built from a previously-trained model's coefficient means and
    variances: the new fit is pulled toward ``means`` with per-coordinate
    strength 1/variance (relative to the L2 weight λ₂). Reference:
    Photon-ML's incremental learning consumes the prior model's
    ``BayesianLinearModelAvro`` means/variances the same way (SURVEY.md §2.3
    Model IO; warm start + prior = incremental retraining).

    Registered as a pytree so it can cross ``jit``/``shard_map`` boundaries
    (the sharded fixed-effect solve passes it as a replicated argument).
    """

    means: Array
    variances: Array | None = None
    min_variance: float = 1e-6

    @property
    def precisions(self) -> Array | None:
        """1/variance, with NON-POSITIVE variances treated as UNINFORMATIVE
        (precision 1, i.e. plain-L2 strength). Model loaders zero-fill
        variances for features absent from the saved record and for padded
        new entities — clamping those zeros to min_variance would give them
        near-infinite precision and freeze them at the prior mean forever;
        the reference gives missing prior features a default variance of 1
        for exactly this reason."""
        if self.variances is None:
            return None
        v = jnp.asarray(self.variances, jnp.float32)
        return jnp.where(v > 0.0, 1.0 / jnp.maximum(v, self.min_variance), 1.0)

    @classmethod
    def from_coefficients(cls, means, variances, norm=None) -> "GaussianPrior":
        """Build the prior IN THE SOLVER'S SPACE from original-feature-space
        model coefficients: means map through the normalization, variances
        through the inverse of the output map var_out = f²·var_norm. The
        single home for this transform (GLM sweep, GAME fixed effect, and
        the per-entity lanes all route through it); handles (d,) vectors
        and (E, d) per-entity matrices alike."""
        mu = jnp.asarray(means, jnp.float32)
        if norm is not None:
            f = norm.model_from_original_space
            mu = jax.vmap(f)(mu) if mu.ndim == 2 else f(mu)
        var = None
        if variances is not None:
            var = jnp.asarray(variances, jnp.float32)
            if norm is not None:
                var = var / (norm.factors**2)
        return cls(means=mu, variances=var)


def compute_variances(
    obj: GLMObjective, w: Array, variance_type: VarianceComputationType
) -> Array | None:
    """Coefficient variances from the Hessian at the optimum.

    Parity: ``photon-api::ml.optimization.VarianceComputationType`` — SIMPLE
    inverts the Hessian diagonal; FULL takes the diagonal of the full
    Hessian inverse. Shared by the GLM sweep and the GAME fixed-effect
    coordinate (one implementation, one set of numerical guards).
    """
    if variance_type is VarianceComputationType.NONE:
        return None
    if variance_type is VarianceComputationType.SIMPLE:
        return 1.0 / jnp.maximum(obj.hessian_diag(w), 1e-12)
    H = obj.hessian(w)
    d = H.shape[0]
    Hinv = jnp.linalg.inv(H + 1e-9 * jnp.eye(d, dtype=H.dtype))
    return jnp.diag(Hinv)


def make_objective(
    batch: Batch,
    loss: PointwiseLoss,
    l2_weight: float | Array = 0.0,
    norm: NormalizationContext | None = None,
    intercept_index: int | None = None,
    axis_name: str | None = None,
    fused: bool | None = None,
    data_hints: tuple[bool, bool] | None = None,
    prior: "GaussianPrior | None" = None,
) -> GLMObjective:
    """Convenience constructor. ``intercept_index`` is excluded from L2
    regularization (and from normalization if ``norm`` is built with it).

    ``fused=None`` auto-enables the one-pass Pallas kernels on TPU for
    dense batches they take (``auto_fused``; by ``ops/fused.supports_fused``
    a bfloat16 or float32 width that is a multiple of 128 takes the
    row-major kernels, a float32 matrix of any other width of 8 or more
    that the chip keeps feature-major takes the feature-major ones, and
    the rest stays on XLA's sweeps); pass ``False``/``True`` to force
    (``True`` off-TPU runs the kernels in interpreter mode — correct but
    slow, for tests). Set the environment variable
    ``PHOTON_DISABLE_FUSED=1`` to veto auto-enabling.

    ``data_hints`` = (offsets all zero, weights all one), for callers that
    know their device-resident data (host numpy arrays are auto-detected
    for free). The hints let the fused kernels drop those aux streams.

    ``prior`` switches the regularizer from plain L2 to a Gaussian MAP
    prior (incremental training): 0.5·λ₂·Σ maskⱼ·precⱼ·(wⱼ−μⱼ)²."""
    d = batch.num_features
    if norm is None:
        norm = no_normalization(d, intercept_index)
    mask = jnp.ones((d,), jnp.float32)
    if intercept_index is not None:
        mask = mask.at[intercept_index].set(0.0)
    if fused is None:
        fused = auto_fused(batch)
    offsets_zero = weights_one = False
    if fused:
        offsets_zero, weights_one = (
            data_hints if data_hints is not None else _constant_hints(batch)
        )
    if isinstance(batch, DenseBatch):
        _count_dense_layout(d, batch.X.dtype, bool(fused))
    return GLMObjective(
        batch=batch,
        norm=norm,
        l2_weight=jnp.asarray(l2_weight, jnp.float32),
        reg_mask=mask,
        loss=loss,
        axis_name=axis_name,
        fused=bool(fused),
        offsets_zero=offsets_zero,
        weights_one=weights_one,
        prior_mean=None if prior is None else jnp.asarray(prior.means, jnp.float32),
        prior_precision=None if prior is None else prior.precisions,
    )


def _count_dense_layout(d: int, dtype, fused: bool) -> None:
    """``dense_layout.columns`` (the real width of every dense objective
    built) and ``dense_layout.padded_columns`` (the columns its kernels'
    blocks add to it: the feature-major kernels round the features up to
    whole sublane groups, the row-major kernels and the XLA path add
    none), in the always-on registry."""
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.ops import fused as kernels

    padded = 0
    if fused and kernels.reads_feature_major(d, dtype):
        padded = kernels.sublane_width(d) - d
    REGISTRY.counter_inc("dense_layout.columns", float(d))
    REGISTRY.counter_inc("dense_layout.padded_columns", float(padded))


def fused_disabled() -> bool:
    """``PHOTON_DISABLE_FUSED`` veto for :func:`auto_fused`, strict int
    parse like every sibling knob. The previous truthiness read made
    ``PHOTON_DISABLE_FUSED=0`` DISABLE fusion — ``"0"`` is a truthy
    string — which is exactly the inversion the lint knob pass now
    rejects repo-wide (``knob-truthy-parse``)."""
    import os

    env = os.environ.get("PHOTON_DISABLE_FUSED")
    if env is not None and env != "":
        return int(env) != 0
    return False


def fused_for_shape(n: int, d: int, dtype) -> bool:
    """What ``auto_fused`` asks of the shapes alone: a TPU backend, no
    ``PHOTON_DISABLE_FUSED`` veto, and ``ops/fused.supports_fused``. For
    callers that hold no concrete array where they decide (a lane of a
    ``vmap``: ``game/random_effect.subspace_one_read``)."""
    from photon_ml_tpu.ops import fused

    return (
        jax.default_backend() == "tpu"
        and not fused_disabled()
        and fused.supports_fused(n, d, dtype)
    )


def auto_fused(batch: Batch) -> bool:
    """Should this (concrete) batch use the one-pass Pallas kernels?
    True on TPU for dense shapes ``ops/fused.supports_fused`` takes, stored
    as the kernels they take read them. Callers that
    construct objectives inside a transform (``jit``, ``shard_map``,
    ``vmap``) must decide BEFORE entering it: under a transform X is a
    tracer, whose storage cannot be seen, and this returns False. They pass
    the pre-computed answer in as ``fused=``: through a static arg under
    ``jit`` or ``shard_map``, as ``game/coordinate``'s fixed visit does from
    its base batch and ``parallel/distributed.py`` with per-device row
    counts; from the lanes' shapes under ``vmap``, where the kernels batch
    (the mapped axis becomes their outer grid axis), as
    ``game/random_effect``'s subspace lanes do."""
    from photon_ml_tpu.ops import fused

    if not (
        isinstance(batch, DenseBatch)
        and not isinstance(batch.X, jax.core.Tracer)
        and fused_for_shape(batch.num_rows, batch.num_features, batch.X.dtype)
    ):
        return False
    # the feature-major kernels read X as its transpose: free where the
    # chip keeps the array that way (it does, for a float32 width that is
    # no multiple of 128), a relayout of the whole matrix where it does not
    if fused.reads_feature_major(batch.num_features, batch.X.dtype):
        return fused.stored_feature_major(batch.X)
    return True


def _constant_hints(batch: Batch) -> tuple[bool, bool]:
    """(offsets all 0, weights all 1) — static data hints for the fused
    kernels. Only HOST numpy arrays are inspected (a free scan): checking a
    device array would force a blocking device→host sync per objective
    construction, which call sites like the coordinate-descent loop pay
    every iteration. Callers holding device arrays that know their data
    pass ``data_hints`` to ``make_objective`` instead."""
    import numpy as np

    def _is_const(x, value) -> bool:
        return isinstance(x, np.ndarray) and bool(np.all(x == value))

    return _is_const(batch.offsets, 0.0), _is_const(batch.weights, 1.0)
