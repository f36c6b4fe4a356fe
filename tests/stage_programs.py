"""The compiled programs whose stage names the tests hold (not a test
module): the fused outer iteration of a tiny GAME descent (fixed effect +
one random effect), ``lbfgs_minimize`` over a tile-COO batch,
``DistributedTrainer``'s ``_sharded_solve``, and ``tron_minimize`` over a
dense float32 batch through the feature-major kernels. Each builder returns
``(jitted function, positional arguments, keyword arguments)``; the
arguments are concrete arrays (or, for the sharded solve, shapes on the
mesh handed in), so a caller can run, lower, or compile deviceless
(``as_specs`` turns arrays into shapes on a described device).

``tests/test_stages.py`` lowers them on the CPU backend;
``tests/test_kernels_compile_tpu.py`` compiles them for a described v5e
(the deviceless compiles live in that one file: one process loads libtpu).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# which stage must appear in which program's op names
DESCENT_COORDINATES = ("fixed", "per-user")
DESCENT_STAGES = (
    "coord.fixed", "coord.per-user", "visit.fixed", "visit.re", "re.offsets",
    "re.solve", "re.score", "glm.objective", "lbfgs.two_loop",
    "lbfgs.line_search", "lbfgs.update", "newton.solve",
)
FIT_STAGES = (
    "glm.objective", "lbfgs.two_loop", "lbfgs.line_search", "lbfgs.update",
)
# a descent whose random effect is over a SPARSE shard: L-BFGS lanes in
# per-entity subspaces
SPARSE_DESCENT_STAGES = (
    "coord.fixed", "coord.per-user", "visit.fixed", "visit.re", "re.offsets",
    "re.solve", "re.subspace", "re.sparse_pass", "re.score", "glm.objective",
    "lbfgs.two_loop", "lbfgs.line_search", "lbfgs.update",
)
# a fit over a tile-COO layout with a dense head: the two parts of a pass
TILE_FIT_STAGES = FIT_STAGES + ("glm.head", "glm.tail")
# a TRON fit: Hessian-vector passes inside the objective, CG and the
# trust-region update outside it
TRON_FIT_STAGES = ("glm.objective", "glm.hvp", "tron.cg", "tron.update")
# ``sparse_descent_kernels``: the same with lanes long enough (512 rows of
# 1,024 columns) for ``ops/fused``'s row-major float32 kernel, traced under
# ``lanes_take_the_kernel``
# ``mesh_descent``: the same descent with its rows placed over the mesh handed
# in: both visits under ``shard_map``, the random effect's exchanges named
MESH_DESCENT_STAGES = DESCENT_STAGES + ("mesh.exchange",)
PROGRAM_STAGES = {
    "descent": DESCENT_STAGES, "mesh_descent": MESH_DESCENT_STAGES,
    "tile_fit": TILE_FIT_STAGES,
    "sharded": FIT_STAGES,
    "sparse_descent": SPARSE_DESCENT_STAGES,
    "sparse_descent_kernels": SPARSE_DESCENT_STAGES,
    "tron_fit": TRON_FIT_STAGES,
}


def descent_coordinates(n=256, d=5, entities=12, seed=0, sparse=False,
                        columns=300, mesh=None):
    """``sparse``: the random effect's shard is ``columns`` wide with 4
    nonzeros a row, and its entities are trained by L-BFGS. ``mesh``: the
    batch's rows are placed over it and the coordinates are handed it."""
    from photon_ml_tpu.config import (
        OptimizationConfig,
        OptimizerConfig,
        RegularizationContext,
    )
    from photon_ml_tpu.game import (
        DenseFeatures,
        FixedEffectCoordinate,
        RandomEffectCoordinate,
        SparseFeatures,
        bucket_entities,
        group_by_entity,
        make_game_batch,
    )
    from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

    rng = np.random.default_rng(seed)
    ids = rng.integers(0, entities, n).astype(np.int32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    if sparse:
        per_user = SparseFeatures(
            indices=jnp.asarray(rng.integers(0, columns, (n, 4)), jnp.int32),
            values=jnp.asarray(rng.uniform(0.2, 1.0, (n, 4)), jnp.float32),
            num_features=columns,
        )
    else:
        per_user = DenseFeatures(X=rng.normal(size=(n, 3)).astype(np.float32))
    batch = make_game_batch(
        y,
        {"global": DenseFeatures(X=rng.normal(size=(n, d + 1)).astype(np.float32)),
         "per_user": per_user},
        id_tags={"user": ids}, mesh=mesh,
    )
    re_optimizer = OptimizerType.LBFGS if sparse else OptimizerType.NEWTON_CHOLESKY

    def opt(kind):
        return OptimizationConfig(
            optimizer=OptimizerConfig(
                optimizer_type=kind, max_iterations=4, tolerance=1e-6
            ),
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        )

    task = TaskType.LOGISTIC_REGRESSION
    grouping = group_by_entity(ids, num_entities=entities)
    fixed, per_user = DESCENT_COORDINATES
    coordinates = {
        fixed: FixedEffectCoordinate(
            coordinate_id=fixed, batch=batch, feature_shard_id="global",
            config=opt(OptimizerType.LBFGS), task_type=task, intercept_index=d,
            mesh=mesh,
        ),
        per_user: RandomEffectCoordinate(
            coordinate_id=per_user, batch=batch, feature_shard_id="per_user",
            random_effect_type="user",
            config=opt(re_optimizer), grouping=grouping,
            buckets=bucket_entities(grouping), task_type=task,
            num_entities=entities, mesh=mesh,
        ),
    }
    return coordinates, batch, task


def slot_index_reading(prepared):
    """The same prepared buckets reading their residual offsets the plain
    way, one index a slot (``offsets[row_idx] * mask`` with (k_pad, C)
    ``row_idx``, which ``_bucket_offsets`` still reads and nothing stages):
    the reference the run-start forms are held against bit for bit. The slot
    indices are what the staged starts (and the effect's order, where it has
    one) say, placed where the bucket's mask is."""
    import dataclasses

    out = []
    for pb in prepared:
        if pb.static is None:  # owned elsewhere: nothing staged
            out.append(pb)
            continue
        mask = np.asarray(pb.mask) != 0
        at = np.asarray(pb.row_idx)[:, None] + np.arange(mask.shape[1])
        if pb.order is not None:
            order = np.asarray(pb.order)
            at = order[np.minimum(at, len(order) - 1)]
        slots = np.where(mask, at, 0).astype(np.int32)
        out.append(dataclasses.replace(
            pb, row_idx=jax.device_put(slots, pb.mask.sharding), order=None,
        ))
    return out


def descent_program(sparse=False, **size):
    """The jitted ``fused`` of ``game/descent._build_fused_outer`` with the
    arguments ``run_outer`` gives it from the zero model."""
    from photon_ml_tpu.game.descent import _build_fused_outer

    coordinates, batch, _ = descent_coordinates(sparse=sparse, **size)
    seq = list(DESCENT_COORDINATES)
    run_outer = _build_fused_outer(coordinates, seq)
    fused = next(
        cell.cell_contents for cell in run_outer.__closure__
        if getattr(cell.cell_contents, "__name__", "") == "fused"
    )
    total = jnp.zeros_like(batch.offsets)  # over the mesh where the batch is
    owns = tuple(jnp.zeros_like(total) for _ in seq)
    statics = tuple(coordinates[c]._fused_visit_parts()[0](None) for c in seq)
    return fused, (total, owns, statics), {"r": 1}


def tile_fit_program():
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops.batch import SparseBatch
    from photon_ml_tpu.ops.glm import make_objective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.sparse_tiled import tile_sparse_batch
    from photon_ml_tpu.optim import lbfgs_minimize
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(0)
    n, d, k = 1 << 12, 1 << 13, 8
    # Zipf(1.0) columns, so that the resident build finds a dense head
    rank = np.floor(np.exp(rng.random((n, k)) * np.log(d + 1.0)) - 1.0)
    batch = SparseBatch(
        indices=jnp.asarray(
            (np.clip(rank, 0, d - 1).astype(np.int64) * 3571 + 17) % d, jnp.int32
        ),
        values=jnp.asarray(rng.normal(size=(n, k)), jnp.float32),
        labels=jnp.asarray(rng.random(n) < 0.5, jnp.float32),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32), num_features=d,
    )
    objective = make_objective(
        tile_sparse_batch(batch, hbm_budget_bytes=1e9),
        loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=1.0,
    )
    config = OptimizerConfig(max_iterations=3, tolerance=0.0)
    return lbfgs_minimize, (objective, jnp.zeros((d,), jnp.float32)), {
        "config": config
    }


def tron_fit_program():
    """``tron_minimize`` over a dense float32 batch whose width is no
    multiple of 128 (the feature-major kernels), offsets all zero."""
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops.batch import DenseBatch
    from photon_ml_tpu.ops.glm import make_objective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.optim.tron import tron_minimize
    from photon_ml_tpu.types import OptimizerType, TaskType

    rng = np.random.default_rng(0)
    n, d = 1 << 12, 200
    batch = DenseBatch(
        X=jnp.asarray(rng.normal(size=(n, d)), jnp.float32),
        labels=jnp.asarray(rng.random(n) < 0.5, jnp.float32),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
    )
    objective = make_objective(
        batch, loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=1.0,
        fused=True, data_hints=(True, False),
    )
    config = OptimizerConfig(
        optimizer_type=OptimizerType.TRON, max_iterations=3, tolerance=0.0,
        max_cg_iterations=4,
    )
    return tron_minimize, (objective, jnp.zeros((d,), jnp.float32)), {
        "config": config
    }


def sharded_program(mesh):
    """``_sharded_solve`` over a bf16 dense batch row-sharded on ``mesh``
    (axis ``data``), fused kernel on, as shapes."""
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops.batch import DenseBatch
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.optim import lbfgs_minimize
    from photon_ml_tpu.parallel.distributed import _sharded_solve
    from photon_ml_tpu.types import TaskType

    rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    n, d = 1 << 14, 512

    def row(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rows)

    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    batch = DenseBatch(
        X=row((n, d), jnp.bfloat16), labels=row((n,)), offsets=row((n,)),
        weights=row((n,)),
    )
    w0 = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=rep)
    return _sharded_solve, (batch, w0, scalar, scalar, None, None), dict(
        minimize_fn=lbfgs_minimize,
        loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
        config=OptimizerConfig(max_iterations=3, tolerance=0.0),
        intercept_index=None, axis_name="data", mesh=mesh, use_l1=False,
        fused=True, data_hints=(True, False),
    )


def build(name: str, mesh):
    """The program ``name`` of ``PROGRAM_STAGES``; ``mesh`` is the sharded
    solve's."""
    if name == "sharded":
        return sharded_program(mesh)
    if name == "mesh_descent":
        return descent_program(mesh=mesh)
    if name == "sparse_descent":
        return descent_program(sparse=True)
    if name == "sparse_descent_kernels":
        return descent_program(sparse=True, n=2048, entities=6, columns=1024)
    return {
        "descent": descent_program, "tile_fit": tile_fit_program,
        "tron_fit": tron_fit_program,
    }[name]()


def lanes_take_the_kernel(monkeypatch) -> None:
    """``game/random_effect.subspace_one_read`` as a TPU backend answers
    it (the kernels' own shape gate), for programs traced from here on. The
    caller drops JAX's trace caches on both sides."""
    from photon_ml_tpu.game import random_effect
    from photon_ml_tpu.ops import fused

    monkeypatch.setattr(random_effect, "fused_for_shape", fused.supports_fused)


def without_scopes(monkeypatch) -> None:
    """``stage`` as a null context, for programs traced from here on: what
    they were before ``obs/stages.py`` existed. Callers drop JAX's trace
    caches on both sides (``jax.clear_caches()``), or a program traced
    under one arm is served to the other."""
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
    )


def as_specs(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )
