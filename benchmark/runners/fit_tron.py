"""Traffic kind ``fit_tron``: complete TRON fits of one resident dense data
set at LIBSVM epsilon's shape and preprocessing.

As ``runners/fit.py`` on one chip (same unit, same fence, same counters and
facts under the same names, so its per-layer readers work here unchanged):
a float32 ``DenseBatch`` -> ``supervised/training.train_glm`` with
``OptimizerType.TRON``. Its own: the rows (``benchmark/datagen_epsilon``),
an ``OptimizerConfig`` that carries the configuration's
``max_cg_iterations``, the count of CG steps, and a check against
``benchmark/reference/tron.py`` that also holds the program's
Hessian-vector product to the reference's.

It calls nothing that a program without this cell's kernels lacks: such a
program fits the same matrix on XLA's sweeps, and is measured so.

The configuration file gives the sizes, so the tests run this tiny on the
CPU backend by handing in a small configuration.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from benchmark import datagen_epsilon
from benchmark.reference import tron as reference
from benchmark.runners import fit as fit_runner

unit = fit_runner.unit
facts = fit_runner.facts


def setup(cell) -> SimpleNamespace:
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops.batch import DenseBatch
    from photon_ml_tpu.types import OptimizerType, TaskType

    if len(cell.devices) != 1:
        raise ValueError("the resident TRON fit is a one-chip path")
    cfg = cell.config
    feats = cfg["features"]
    opt = cfg["optimizer"]
    if feats["kind"] != "dense" or feats["dtype"] != "float32" or cfg["intercept"]:
        raise ValueError("fit_tron fits a dense float32 matrix without a bias term")
    n = int(feats["rows"]) * int(feats.get("row_multiple", 1))
    d = int(feats["columns"])
    st = SimpleNamespace(
        cfg=cfg, cell=cell, facts={}, last=None, n=n, d=d, mesh=None,
        intercept=None, host_rows=None,
        l2=float(cfg["l2"]),
        task=TaskType(cfg["task"]),
        opt=OptimizerConfig(
            optimizer_type=OptimizerType(opt["type"]),
            max_iterations=int(opt["max_iterations"]),
            tolerance=float(opt["tolerance"]),
            max_cg_iterations=int(opt["max_cg_iterations"]),
        ),
    )
    X, y = datagen_epsilon.epsilon_rows(
        n, d, int(feats["generate_block_rows"]), int(feats["data_seed"]),
        int(feats["factors"]), float(feats["factor_strength"]),
        float(feats["factor_decay"]), float(feats["margin_std"]),
    )
    st.batch = DenseBatch(
        X=X, labels=y, offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
    )
    jax.block_until_ready(st.batch)
    st.fit = fit_runner._fitter(st)
    return st


def account(st, out) -> dict:
    """``fit.py``'s counters and the CG steps: ``optim/tron.py`` counts one
    pass at ``w = 0``, one a CG step and one an outer iteration."""
    counted = fit_runner.account(st, out)
    counted["optim.cg_steps"] = (
        counted["optim.objective_passes"] - counted["optim.iterations"] - 1.0
    )
    return counted


def shape(st) -> dict:
    """The algorithm's sizes (the real columns, not a padded width)."""
    return {
        "rows": st.n, "columns": st.d, "nonzeros": None, "itemsize": 4,
        "devices": 1,
    }


def _program_hvp(st, w, v):
    """``GLMObjective.hvp`` of the objective ``train_glm`` builds."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.glm import make_objective
    from photon_ml_tpu.ops.losses import loss_for_task

    objective = make_objective(st.batch, loss_for_task(st.task), l2_weight=st.l2)
    hv = jax.jit(lambda obj, w, v: obj.hvp(w, v))(
        objective, jnp.asarray(w, jnp.float32), jnp.asarray(v, jnp.float32)
    )
    return np.asarray(hv, np.float64)


def _rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check(st) -> dict:
    """Three readings against ``reference/tron.py`` at the returned ``w``:
    the reported loss, the gradient over that at zero (a fit stopped an
    outer iteration early fails), and the program's Hessian-vector product
    on a unit vector drawn from ``--seed``. The notes also hold what the
    reference itself reads one precision down (every matmul operand
    rounded to bfloat16, one bf16 MXU pass): the limits lie between the
    two."""
    res, w = st.last
    g = st.cfg["guarantees"]
    X, y = st.batch.X, st.batch.labels
    rows = int(st.cfg["features"]["generate_block_rows"])
    f_ref, g_ref = reference.value_grad(X, y, w, st.l2, rows)
    _, g_zero = reference.value_grad(X, y, np.zeros_like(w), st.l2, rows)
    v = np.random.default_rng(st.cell.seed).standard_normal(st.d)
    v /= np.linalg.norm(v)
    hv_ref = reference.hvp(X, y, w, v, st.l2, rows)
    loss_rel = abs(float(res.value) - f_ref) / abs(f_ref)
    grad_ratio = float(np.linalg.norm(g_ref) / np.linalg.norm(g_zero))
    hvp_rel = _rel(_program_hvp(st, w, v), hv_ref)
    f_low, _ = reference.value_grad(X, y, w, st.l2, rows, operands="bfloat16")
    hv_low = reference.hvp(X, y, w, v, st.l2, rows, operands="bfloat16")
    ok = (
        loss_rel <= float(g["loss_rel_tol"])
        and grad_ratio <= float(g["grad_ratio_max"])
        and hvp_rel <= float(g["hvp_rel_tol"])
    )
    return {
        "correct": bool(ok),
        "notes": {
            "loss_reported": float(res.value), "loss_reference": f_ref,
            "loss_rel_diff": loss_rel, "grad_ratio": grad_ratio,
            "hvp_rel_diff": hvp_rel,
            "one_bf16_pass.loss_rel_diff": abs(f_low - f_ref) / abs(f_ref),
            "one_bf16_pass.hvp_rel_diff": _rel(hv_low, hv_ref),
            "iterations": int(res.iterations), "reason": int(res.reason),
            "objective_passes": int(res.objective_passes),
            "grad_norm_history_over_first": [
                float(x) for x in np.asarray(res.grad_norm_history)[
                    : int(res.iterations) + 1
                ] / float(res.grad_norm_history[0])
            ],
        },
    }
