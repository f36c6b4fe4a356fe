"""Stage names inside the compiled programs: the one place they live.

``with stage(RE_SOLVE):`` puts the name on JAX's name stack, so every
operation staged inside carries it as one ``/``-separated segment of its
``op_name`` (``jit(fused)/coord.per_user/jit(run)/visit.re/jit(_bucket_step)/
re.solve/...``). That is metadata only: the compiled program has the same
instructions under the same names with the scopes as without, so a scope
costs nothing at run time and cannot move a measurement (one name does
change: XLA calls a Pallas custom call after the innermost scope around it,
so the fused dense kernel is ``glm.objective.<n>``, not ``body.<n>``). A
profiler trace shows the path as the ``tf_op`` stat of each device
operation, which is where ``benchmark/stages.py`` reads device seconds per
stage.

A name is one path segment: letters, digits, ``_``, ``.`` and ``-``, no
``/``, and with a dot in it, which no segment JAX writes itself has
(``jit(run)``, ``while``, ``body``): a reader can tell a stage from the
rest of a path without a list of names. Stages nest (a loop body inherits the scope around the loop, and
``glm.objective`` sits inside ``visit.fixed`` and ``lbfgs.line_search``); a
reader asks for a segment and chooses segments that do not overlap. XLA
gives a fusion one ``op_name``, so a fusion across a stage boundary is
booked whole to one side.
"""

from __future__ import annotations

import re

import jax

VISIT_FIXED = "visit.fixed"  # the whole fixed-effect visit (game/coordinate)
VISIT_RE = "visit.re"  # the whole random-effect visit (game/coordinate)
RE_OFFSETS = "re.offsets"  # residual offsets gathered into bucket slots
RE_SOLVE = "re.solve"  # everything bucket-shaped: lanes, solve, scatter
RE_SUBSPACE = "re.subspace"  # lanes through a sparse shard's column maps
RE_SPARSE_PASS = "re.sparse_pass"  # a subspace lane's passes over its rows
RE_SCORE = "re.score"  # the W[ids] gathers and (n, d_e) work of scoring
GLM_OBJECTIVE = "glm.objective"  # every pass over the data (ops/glm)
GLM_HEAD = "glm.head"  # inside it: the dense head's multiply-reduces
GLM_TAIL = "glm.tail"  # inside it: the tile-COO kernels (ops/sparse_tiled)
GLM_HVP = "glm.hvp"  # inside it: a Hessian-vector pass, fused kernel or XLA
LBFGS_TWO_LOOP = "lbfgs.two_loop"  # the search direction (optim/lbfgs)
LBFGS_LINE_SEARCH = "lbfgs.line_search"  # trial points and their loops
LBFGS_UPDATE = "lbfgs.update"  # acceptance, ring buffers, next state
TRON_CG = "tron.cg"  # truncated CG's dots, axpys and boundary step (optim/tron)
TRON_UPDATE = "tron.update"  # acceptance, trust radius, histories, next state
NEWTON_SOLVE = "newton.solve"  # factorisation and step (optim/newton)
# under a mesh: the residual's and the coefficients' all-gathers, and the
# copies that exist only to feed them (game/coordinate's mesh visit)
MESH_EXCHANGE = "mesh.exchange"
COORD_PREFIX = "coord."  # + the coordinate id: which coordinate's visit

# Hashed into the persistent compile cache's key by ``utils/compile_cache``.
# JAX leaves metadata out of that key, and an executable comes back from the
# cache with the names it was compiled with: after a scope moves with no
# instruction changing, a warm cache would keep serving the old names to
# every profile. Raise this when a site or a name of this module changes.
VERSION = 6

_NOT_SEGMENT = re.compile(r"[^A-Za-z0-9_.\-]")


def coord(coordinate_id: str) -> str:
    """``coord.<cid>``, the id's other characters replaced by ``_``."""
    return COORD_PREFIX + _NOT_SEGMENT.sub("_", str(coordinate_id))


def stage(name: str):
    return jax.named_scope(name)
