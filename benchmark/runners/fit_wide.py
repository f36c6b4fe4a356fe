"""Traffic kind ``fit_wide``: complete GLM fits of one resident sparse data
set of hashed fields, at any width.

As ``runners/fit.py`` on one chip (same unit, same fence, same counters and
facts under the same names, the same check against
``benchmark/reference/glm.py`` on the host's copy of the rows, so its
per-layer readers work here unchanged): ``SparseBatch`` ->
``ops/batch.optimize_batch_layout`` -> ``supervised/training.train_glm``.
Only the rows differ: ``benchmark/datagen_criteo`` hashes (field, value)
pairs in 32 bits, where ``datagen.sparse_glm_rows`` stops at 65,536 columns.

Refused up front: a program whose tile-COO build pads every non-empty cell
to a run of 256 slots whatever the cell holds (a commit before the
sparse-cell form). At 10^6 columns a cell holds 20 nonzeros, the streams
would take 27 GB, and such a program finds that out only after a minute of
host sorting, by running out of device memory.

The configuration file gives the sizes, so the tests run this tiny on the
CPU backend by handing in a small configuration.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from benchmark import datagen_criteo
from benchmark.runners import fit as fit_runner

unit = fit_runner.unit
account = fit_runner.account
facts = fit_runner.facts
shape = fit_runner.shape
check = fit_runner.check


def setup(cell) -> SimpleNamespace:
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops import sparse_tiled
    from photon_ml_tpu.ops.batch import SparseBatch, optimize_batch_layout
    from photon_ml_tpu.ops.streaming import device_hbm_budget_bytes
    from photon_ml_tpu.types import OptimizerType, TaskType

    if not hasattr(sparse_tiled, "SUB_SLABS"):
        raise RuntimeError(
            "this program's tile-COO layout pads every cell to a whole run "
            "(no sparse-cell form): a matrix this wide does not fit it"
        )
    if len(cell.devices) != 1:
        raise ValueError("the sparse fit is a one-chip path")
    cfg = cell.config
    feats = cfg["features"]
    opt = cfg["optimizer"]
    st = SimpleNamespace(
        cfg=cfg, cell=cell, facts={}, last=None,
        l2=float(cfg["l2"]),
        task=TaskType(cfg["task"]),
        opt=OptimizerConfig(
            optimizer_type=OptimizerType(opt["type"]),
            max_iterations=int(opt["max_iterations"]),
            tolerance=float(opt["tolerance"]),
        ),
        n=int(feats["rows"]), d=int(feats["columns"]), mesh=None,
        intercept=None,
    )
    cardinalities = (
        [int(feats["integer_bins"])] * int(feats["integer_fields"])
        + [int(c) for c in feats["categorical_cardinalities"]]
    )
    idx, val, y = datagen_criteo.hashed_field_rows(
        cell.seed, st.n, st.d, cardinalities, int(feats["data_seed"]),
        float(feats["label_scale"]), float(feats["label_shift"]),
    )
    batch = SparseBatch(
        indices=idx, values=val, labels=y,
        offsets=jnp.zeros((st.n,), jnp.float32),
        weights=jnp.ones((st.n,), jnp.float32), num_features=st.d,
    )
    jax.block_until_ready(batch)
    # the reference's own copy of the rows, on the host
    st.host_rows = (np.asarray(idx), np.asarray(val), np.asarray(y))
    t0 = time.perf_counter()
    st.batch = optimize_batch_layout(
        batch, hbm_budget_bytes=device_hbm_budget_bytes()
    )
    jax.block_until_ready(st.batch)
    st.facts["layout.build_s"] = time.perf_counter() - t0
    st.facts["layout.nonzeros"] = float(np.count_nonzero(st.host_rows[1]))
    chunks = getattr(st.batch, "chunks", None)
    if chunks is not None:
        # packed (groups, streams, 128) arrays, one a direction a chunk
        st.facts["layout.slots"] = float(sum(
            int(arrays[0].shape[0]) * int(arrays[0].shape[-1])
            for c in chunks for arrays in (c.m_arrays, c.g_arrays)
        ))
    st.w0 = jnp.zeros((st.d,), jnp.float32)
    jax.block_until_ready(st.w0)
    st.fit = fit_runner._fitter(st)
    return st
