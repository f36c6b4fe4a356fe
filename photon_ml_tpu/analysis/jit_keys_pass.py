"""Pass 2 — jit-cache-key analysis (the stale-executable bug class).

The repo's discipline since PR 1: retunable knobs are read at CALL time
and passed into jitted programs as STATIC arguments, so a retune
recompiles instead of silently reusing a stale executable. The violation
this pass hunts is the inverse: a function that enters ``jax.jit`` whose
BODY calls a knob accessor (``kernel_dtype()``, ``prefetch_depth()``, …)
or reads a retune-mutable module global (``GROUPS_PER_RUN``,
``SEGMENTS_PER_DMA``, …) or the environment directly. Values read inside
a traced body are baked into the executable at first trace — the jit
cache keys only on argument shapes/statics, so a later knob flip REUSES
the stale program (PR 2's missing-static bug, found by hand then;
mechanical now).

Jitted functions are recognized syntactically:

- decorated with ``jax.jit`` / ``jit`` / ``partial(jax.jit, ...)`` /
  ``functools.partial(jax.jit, ...)``;
- passed to a ``jax.jit(...)`` call anywhere in the module by name
  (covers ``self._chunk_vg = jax.jit(chunk_value_grad)`` and module-level
  ``_A2A_JIT = jax.jit(fn)``).

Nested helper functions inside a jitted body are traced with it, so the
whole body subtree is checked.

Codes: ``jit-knob-accessor``, ``jit-retune-global``, ``jit-env-read``.
"""

from __future__ import annotations

import ast

from photon_ml_tpu.analysis import registry as reg_mod
from photon_ml_tpu.analysis.core import (
    Finding, ModuleInfo, Project, call_name, const_str, dotted_name,
)

_JIT_NAMES = {"jax.jit", "jit"}
_PARTIAL_NAMES = {"partial", "functools.partial"}


def _is_jit_expr(node: ast.AST) -> bool:
    """``jax.jit`` / ``jit`` / ``partial(jax.jit, ...)``."""
    if dotted_name(node) in _JIT_NAMES:
        return True
    if (
        isinstance(node, ast.Call)
        and dotted_name(node.func) in _PARTIAL_NAMES
        and node.args
        and dotted_name(node.args[0]) in _JIT_NAMES
    ):
        return True
    return False


def jitted_functions(mi: ModuleInfo) -> list[ast.FunctionDef]:
    """Every FunctionDef that syntactically enters ``jax.jit``."""
    by_name: dict[str, list[ast.FunctionDef]] = {}
    out: list[ast.FunctionDef] = []
    seen: set[ast.FunctionDef] = set()
    for node in ast.walk(mi.tree):
        if isinstance(node, ast.FunctionDef):
            by_name.setdefault(node.name, []).append(node)
            if any(_is_jit_expr(d) for d in node.decorator_list):
                if node not in seen:
                    seen.add(node)
                    out.append(node)
    # functions wrapped by name: jax.jit(fn, ...) anywhere in the module
    for node in ast.walk(mi.tree):
        if (
            isinstance(node, ast.Call)
            and dotted_name(node.func) in _JIT_NAMES
            and node.args
            and isinstance(node.args[0], ast.Name)
        ):
            for fn in by_name.get(node.args[0].id, ()):
                if fn not in seen:
                    seen.add(fn)
                    out.append(fn)
    return out


def run(project: Project, registry=None) -> list[Finding]:
    knobs = list(registry or reg_mod.KNOBS)
    accessors = set()
    globals_ = set()
    accessor_owner: dict[str, str] = {}
    global_owner: dict[str, str] = {}
    for k in knobs:
        for a in k.accessors:
            accessors.add(a)
            accessor_owner[a] = k.name
        if k.retune_global:
            globals_.add(k.retune_global)
            global_owner[k.retune_global] = k.name
    findings: list[Finding] = []
    for mi in project.iter_modules():
        for fn in jitted_functions(mi):
            scope = f"{mi.relpath}::{fn.name}"
            # parameter names shadow retune globals: a static arg named
            # like the global IS the discipline working as intended
            params = {
                a.arg
                for a in (
                    fn.args.args + fn.args.kwonlyargs + fn.args.posonlyargs
                )
            }
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    cn = call_name(node)
                    if cn in accessors:
                        findings.append(Finding(
                            "jit-knob-accessor", mi.relpath, node.lineno,
                            f"{fn.name}:{cn}",
                            f"jitted function '{fn.name}' calls knob "
                            f"accessor {cn}() "
                            f"({accessor_owner[cn]}) inside its traced "
                            f"body — the value is baked in at first "
                            f"trace and a retune reuses the stale "
                            f"executable; read it at the call site and "
                            f"pass it as a static argument",
                        ))
                elif isinstance(node, ast.Attribute) and isinstance(
                    node.ctx, ast.Load
                ):
                    if node.attr in globals_:
                        findings.append(Finding(
                            "jit-retune-global", mi.relpath, node.lineno,
                            f"{fn.name}:{node.attr}",
                            f"jitted function '{fn.name}' reads "
                            f"retune-mutable global "
                            f"{dotted_name(node) or node.attr} "
                            f"({global_owner[node.attr]}) inside its "
                            f"traced body — pass it as a static "
                            f"argument instead",
                        ))
                elif isinstance(node, ast.Name) and isinstance(
                    node.ctx, ast.Load
                ):
                    if node.id in globals_ and node.id not in params:
                        findings.append(Finding(
                            "jit-retune-global", mi.relpath, node.lineno,
                            f"{fn.name}:{node.id}",
                            f"jitted function '{fn.name}' reads "
                            f"retune-mutable global {node.id} "
                            f"({global_owner[node.id]}) inside its "
                            f"traced body — pass it as a static "
                            f"argument instead",
                        ))
            for name, read in env_reads_in(fn):
                findings.append(Finding(
                    "jit-env-read", mi.relpath, read.lineno,
                    f"{fn.name}:{name}",
                    f"jitted function '{fn.name}' reads {name} from "
                    f"the environment inside its traced body — the "
                    f"read happens once at trace time; hoist it to "
                    f"the call site and pass a static argument",
                ))
    return findings


def env_reads_in(fn: ast.FunctionDef):
    """PHOTON_* env reads inside one function subtree (same matcher as
    the knob pass, scoped)."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "get"
                and node.args
            ):
                s = const_str(node.args[0])
                if s and s.startswith("PHOTON_"):
                    yield s, node
        elif isinstance(node, ast.Subscript) and isinstance(
            node.ctx, ast.Load
        ):
            s = const_str(node.slice)
            if s and s.startswith("PHOTON_"):
                yield s, node
