"""Pass 1 — knob discipline.

Finds every ``PHOTON_*`` environment read in the package, then
cross-checks the knob registry against every mirror surface BY PARSING
THE SOURCES: the ``sink._knob_snapshot`` keys, the
``devcost._knob_raw_state`` fingerprint, and the generated README knob
table. Drift in any direction fails.

Codes:

- ``knob-unregistered`` — an env read of a PHOTON_* name absent from the
  registry (new knobs must be registered before they ship).
- ``knob-truthy-parse`` — an int/flag/float knob's env read used directly
  in a boolean context (``if os.environ.get(...)`` /
  ``not os.environ.get(...)``): the string ``"0"`` is truthy, so ``=0``
  INVERTS the operator's intent (the PHOTON_DISABLE_FUSED bug class).
  Strict-parse (``int(env) != 0``) instead.
- ``knob-sink-missing`` / ``knob-sink-unregistered`` — registry vs. the
  ``_knob_snapshot`` keys, both directions.
- ``knob-devcost-missing`` — a snapshot-carried knob not fingerprinted in
  ``_knob_raw_state`` (the memoized snapshot would go stale on a
  mid-process flip of only that knob).
- ``knob-readme-missing`` / ``knob-readme-stale`` — registry vs. the
  committed README knob table (regenerate with ``--write-docs``).
"""

from __future__ import annotations

import ast

from photon_ml_tpu.analysis import registry as reg_mod
from photon_ml_tpu.analysis.core import (
    Finding, ModuleInfo, Project, const_str,
)

_SINK_RELPATH = "photon_ml_tpu/obs/sink.py"
_DEVCOST_RELPATH = "photon_ml_tpu/obs/devcost.py"

#: parse kinds the boolean-context check applies to — a raw string / path
#: / JSON knob used truthily ("set or not") is fine by design
_NUMERIC_KINDS = ("int", "flag", "float")


def env_reads(mi: ModuleInfo):
    """Yield ``(name, node)`` for every PHOTON_* environment read: a
    ``.get("PHOTON_X")`` call, a Load-context ``[...]`` subscript, or an
    ``in``-membership test against an environ-shaped mapping. The base
    is matched loosely on purpose (``os.environ`` or a local alias like
    devcost's ``env = os.environ``): in this codebase string-keyed
    ``PHOTON_*`` lookups ARE environment reads."""
    for node in ast.walk(mi.tree):
        if isinstance(node, ast.Call):
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "get"
                and node.args
            ):
                name = const_str(node.args[0])
                if name and name.startswith("PHOTON_"):
                    yield name, node
        elif isinstance(node, ast.Subscript):
            if isinstance(node.ctx, ast.Load):
                name = const_str(node.slice)
                if name and name.startswith("PHOTON_"):
                    yield name, node
        elif isinstance(node, ast.Compare):
            if len(node.ops) == 1 and isinstance(
                node.ops[0], (ast.In, ast.NotIn)
            ):
                name = const_str(node.left)
                if name and name.startswith("PHOTON_"):
                    yield name, node


def _in_boolean_context(mi: ModuleInfo, node: ast.AST) -> bool:
    """Is this expression consumed directly as a truth value? Covers the
    swallow idioms ``if os.environ.get(X)``, ``not os.environ.get(X)``,
    ``... and os.environ.get(X)``, and conditional-expression tests."""
    parent = mi.parents.get(node)
    if isinstance(parent, (ast.UnaryOp,)) and isinstance(
        parent.op, ast.Not
    ):
        return True
    if isinstance(parent, ast.BoolOp):
        return True
    if isinstance(parent, (ast.If, ast.While)) and parent.test is node:
        return True
    if isinstance(parent, ast.IfExp) and parent.test is node:
        return True
    return False


def scan_env_reads(project: Project, registry=None) -> list[Finding]:
    knobs = {k.name: k for k in (registry or reg_mod.KNOBS)}
    findings: list[Finding] = []
    for mi in project.iter_modules():
        for name, node in env_reads(mi):
            knob = knobs.get(name)
            if knob is None:
                findings.append(Finding(
                    "knob-unregistered", mi.relpath, node.lineno, name,
                    f"environment read of unregistered knob {name}; add it "
                    f"to photon_ml_tpu/analysis/registry.py (with surface "
                    f"exemptions where they apply)",
                ))
                continue
            if knob.kind in _NUMERIC_KINDS and _in_boolean_context(
                mi, node
            ):
                findings.append(Finding(
                    "knob-truthy-parse", mi.relpath, node.lineno, name,
                    f"{name} is a {knob.kind} knob but this read is used "
                    f"as a bare truth value — '0' is a truthy string, so "
                    f"'=0' inverts the intent; use the strict parse idiom "
                    f"(int(env) != 0) like the sibling knobs",
                ))
    return findings


# -- mirror-surface extraction ---------------------------------------------


def _function(mi: ModuleInfo, name: str) -> ast.FunctionDef | None:
    for node in ast.walk(mi.tree):
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


def sink_snapshot_keys(sink_mi: ModuleInfo) -> set[str] | None:
    """Keys assigned as ``knobs["..."] = ...`` inside ``_knob_snapshot``."""
    fn = _function(sink_mi, "_knob_snapshot")
    if fn is None:
        return None
    keys: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Subscript) and isinstance(
            node.ctx, ast.Store
        ):
            s = const_str(node.slice)
            if s:
                keys.add(s)
    return keys


def devcost_fingerprint(devcost_mi: ModuleInfo) -> set[str] | None:
    """The env names ``_knob_raw_state`` reads."""
    fn = _function(devcost_mi, "_knob_raw_state")
    if fn is None:
        return None
    envs: set[str] = set()
    for node in ast.walk(fn):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "get"
            and node.args
        ):
            s = const_str(node.args[0])
            if s:
                envs.add(s)
    return envs


def readme_table_block(readme_path: str) -> str | None:
    """The committed README knob-table block, markers included (None =
    markers not found)."""
    with open(readme_path, encoding="utf-8") as f:
        text = f.read()
    begin = text.find("<!-- knob-table:begin")
    end = text.find(reg_mod.KNOB_TABLE_END)
    if begin < 0 or end < 0 or end < begin:
        return None
    return text[begin:end + len(reg_mod.KNOB_TABLE_END)]


def readme_table_names(readme_path: str) -> set[str] | None:
    """Knob names in the generated README table (None = no markers)."""
    block = readme_table_block(readme_path)
    if block is None:
        return None
    names: set[str] = set()
    for line in block.splitlines():
        line = line.strip()
        if line.startswith("| `PHOTON_"):
            names.add(line.split("`")[1])
    return names


def check_surfaces(project: Project, registry=None) -> list[Finding]:
    knobs = list(registry or reg_mod.KNOBS)
    by_name = {k.name: k for k in knobs}
    findings: list[Finding] = []

    # -- sink snapshot (both directions) -----------------------------------
    sink_mi = project.module(_SINK_RELPATH)
    if sink_mi is not None:
        keys = sink_snapshot_keys(sink_mi)
        if keys is not None:
            claimed = {k.sink_key for k in knobs if k.sink_key}
            for k in knobs:
                if k.sink_key and k.sink_key not in keys:
                    findings.append(Finding(
                        "knob-sink-missing", sink_mi.relpath, 1,
                        k.name,
                        f"{k.name} requires snapshot key "
                        f"'{k.sink_key}' in sink._knob_snapshot but the "
                        f"snapshot does not report it",
                    ))
            for key in sorted(keys - claimed):
                findings.append(Finding(
                    "knob-sink-unregistered", sink_mi.relpath, 1, key,
                    f"sink._knob_snapshot reports '{key}' but no "
                    f"registered knob claims that key",
                ))

    # -- devcost fingerprint ------------------------------------------------
    devcost_mi = project.module(_DEVCOST_RELPATH)
    if devcost_mi is not None:
        envs = devcost_fingerprint(devcost_mi)
        if envs is not None:
            for k in knobs:
                if not k.needs_devcost:
                    continue
                # the snapshot reads env > global at SNAPSHOT time, so
                # the env var MUST be fingerprinted — the global alone
                # goes stale on a mid-process env flip
                if k.name in envs:
                    continue
                findings.append(Finding(
                    "knob-devcost-missing", devcost_mi.relpath, 1,
                    k.name,
                    f"{k.name} feeds sink._knob_snapshot (key "
                    f"'{k.sink_key}') but devcost._knob_raw_state does "
                    f"not fingerprint its env var — a mid-process flip of "
                    f"only this knob would reuse a stale memoized snapshot "
                    f"in capture keys",
                ))

    # -- README knob table ---------------------------------------------------
    if project.readme_path is not None:
        names = readme_table_names(project.readme_path)
        relpath = "README.md"
        if names is None:
            findings.append(Finding(
                "knob-readme-missing", relpath, 1, "knob-table",
                "README has no generated knob table (markers not found); "
                "run `photon-ml-tpu lint --write-docs`",
            ))
        else:
            registered = {k.name for k in knobs}
            for name in sorted(registered - names):
                findings.append(Finding(
                    "knob-readme-missing", relpath, 1, name,
                    f"{name} is registered but absent from the README "
                    f"knob table; run `photon-ml-tpu lint --write-docs`",
                ))
            for name in sorted(names - registered):
                findings.append(Finding(
                    "knob-readme-stale", relpath, 1, name,
                    f"README knob table lists {name}, which is not in "
                    f"the registry; run `photon-ml-tpu lint --write-docs`",
                ))
            if names == registered and registry is None:
                # same name set but drifted CONTENT (a default, doc or
                # surface column changed in the registry): the committed
                # block must match the rendered table verbatim (modulo
                # whitespace). Only meaningful against the real
                # registry — fixture registries never rendered the
                # committed README.
                committed = _normalize_block(
                    readme_table_block(project.readme_path) or ""
                )
                rendered = _normalize_block(reg_mod.render_knob_table())
                if committed != rendered:
                    findings.append(Finding(
                        "knob-readme-stale", relpath, 1, "knob-table",
                        "README knob table content drifted from the "
                        "registry (a default/doc/surface column "
                        "changed); run `photon-ml-tpu lint "
                        "--write-docs`",
                    ))
    return findings


def _normalize_block(block: str) -> list[str]:
    return [ln.strip() for ln in block.splitlines() if ln.strip()]


def run(project: Project, registry=None) -> list[Finding]:
    return scan_env_reads(project, registry) + check_surfaces(
        project, registry
    )
