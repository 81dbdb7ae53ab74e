"""Spec-key completeness audit: no SearchSpec field may silently miss
the plan-cache key.

The ROADMAP's multi-tenant serving plane wants one plan cache shared
across tenants; a ``SearchSpec`` field that changes compiled behavior
but not the cache key is then a cross-tenant correctness bug (two
specs collide on one compiled plan).  This module audits the keying
contract two ways:

**Static** (:func:`static_audit`, jax-free): parses ``core/spec.py``
and ``core/engine.py`` sources and cross-references the ``SearchSpec``
dataclass fields against the engine's declared partition —
``PLAN_KEY_FIELDS`` (reach the key via the ``_plan_key`` prefix, the
per-kind key element, or the mesh shape), ``KIND_DISPATCH_FIELDS``
(select *which* plan kind runs, so the kind string keys them), and
``TRACE_INVARIANT_FIELDS`` (host-side only, never closed over by a
plan body).  It also checks how keys are built: ``PLAN_KINDS`` is a
dict literal of unique string kinds, ``DiscordEngine.plan`` is the
only ``self._get_plan(...)`` call site and gives sharded kinds the
mesh shape (``ndev``, through ``_resolve_mesh``), and ``_plan_key``
really references ``backend``/``znorm``/``block``/``precision``.

**Runtime** (:func:`runtime_audit`, property-based): builds tiny
engines, perturbs each field in turn, and asserts the populated plan
keys change — or stay identical for the declared trace-invariant
fields.  This is the half a static pass cannot prove: that the key
elements actually *vary* with the field.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .lint import package_root
from .report import Finding

__all__ = ["static_audit", "runtime_audit", "coverage"]

_DECLS = ("PLAN_KEY_FIELDS", "KIND_DISPATCH_FIELDS",
          "TRACE_INVARIANT_FIELDS")


def _read(name: str, override: Optional[str]) -> str:
    if override is not None:
        return override
    return (package_root() / "core" / name).read_text()


def _spec_fields(spec_tree: ast.AST) -> List[str]:
    """SearchSpec dataclass field names, in declaration order."""
    for node in ast.walk(spec_tree):
        if isinstance(node, ast.ClassDef) and node.name == "SearchSpec":
            return [stmt.target.id for stmt in node.body
                    if isinstance(stmt, ast.AnnAssign)
                    and isinstance(stmt.target, ast.Name)]
    return []


def _module_tuples(engine_tree: ast.AST) -> Dict[str, Tuple[str, ...]]:
    """The engine's declared field partition (module-level tuple
    assignments named in ``_DECLS``)."""
    out: Dict[str, Tuple[str, ...]] = {}
    for node in engine_tree.body:  # type: ignore[attr-defined]
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id in _DECLS \
                and isinstance(node.value, ast.Tuple):
            out[node.targets[0].id] = tuple(
                e.value for e in node.value.elts
                if isinstance(e, ast.Constant))
    return out


def _engine_methods(engine_tree: ast.AST) -> List[ast.FunctionDef]:
    for node in ast.walk(engine_tree):
        if isinstance(node, ast.ClassDef) and \
                node.name == "DiscordEngine":
            return [m for m in node.body
                    if isinstance(m, ast.FunctionDef)]
    return []


def _get_plan_sites(method: ast.FunctionDef) -> List[int]:
    """Line of each self._get_plan call in ``method``."""
    return [node.lineno for node in ast.walk(method)
            if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "_get_plan"]


def _plan_kinds(engine_tree: ast.AST) -> Optional[ast.Dict]:
    """The engine's module-level ``PLAN_KINDS = {...}`` literal."""
    for node in engine_tree.body:  # type: ignore[attr-defined]
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "PLAN_KINDS" \
                and isinstance(node.value, ast.Dict):
            return node.value
    return None


def _calls(method: ast.FunctionDef, attr: str) -> bool:
    return any(isinstance(n, ast.Call)
               and isinstance(n.func, ast.Attribute)
               and n.func.attr == attr
               for n in ast.walk(method))


def coverage(engine_source: Optional[str] = None,
             spec_source: Optional[str] = None) -> Dict[str, str]:
    """How each SearchSpec field reaches the plan-cache key — the
    per-field map the report's ``meta`` carries (100% coverage is the
    acceptance bar; :func:`static_audit` flags any gap)."""
    engine_tree = ast.parse(_read("engine.py", engine_source))
    decls = _module_tuples(engine_tree)
    how = {
        "PLAN_KEY_FIELDS": "plan key (via _plan_key prefix, the "
                           "per-kind key element, or the mesh shape)",
        "KIND_DISPATCH_FIELDS": "selects the plan kind string",
        "TRACE_INVARIANT_FIELDS": "trace-invariant (host-side only; "
                                  "runtime audit asserts keys are "
                                  "unchanged)",
    }
    fields = _spec_fields(ast.parse(_read("spec.py", spec_source)))
    out: Dict[str, str] = {}
    for f in fields:
        for decl, desc in how.items():
            if f in decls.get(decl, ()):
                out[f] = desc
                break
        else:
            out[f] = "UNCOVERED"
    return out


def static_audit(engine_source: Optional[str] = None,
                 spec_source: Optional[str] = None) -> List[Finding]:
    """Cross-reference SearchSpec fields with the engine's declared
    key partition and every plan-key construction site."""
    findings: List[Finding] = []

    def bad(rule: str, line: int, msg: str) -> None:
        findings.append(Finding("speckey", rule, "core/engine.py",
                                line, msg))

    spec_tree = ast.parse(_read("spec.py", spec_source))
    engine_tree = ast.parse(_read("engine.py", engine_source))
    fields = set(_spec_fields(spec_tree))
    if not fields:
        findings.append(Finding(
            "speckey", "spec-fields", "core/spec.py", 0,
            "could not locate the SearchSpec dataclass fields"))
        return findings

    decls = _module_tuples(engine_tree)
    for name in _DECLS:
        if name not in decls:
            bad("field-partition", 0,
                f"missing module-level declaration {name} — the "
                "audit needs the engine's own statement of how each "
                "spec field is keyed")
    declared: Set[str] = set()
    for name, vals in decls.items():
        dupes = declared & set(vals)
        if dupes:
            bad("field-partition", 0,
                f"{sorted(dupes)} appear in more than one of "
                f"{_DECLS} — the partition must be disjoint")
        declared |= set(vals)
    for f in sorted(fields - declared):
        bad("field-partition", 0,
            f"SearchSpec field {f!r} is not declared in any of "
            f"{_DECLS} — a new field must be keyed (or explicitly "
            "declared trace-invariant) before it ships")
    for f in sorted(declared - fields):
        bad("field-partition", 0,
            f"declared field {f!r} does not exist on SearchSpec "
            "(stale declaration)")

    methods = _engine_methods(engine_tree)
    if not methods:
        bad("plan-key-sites", 0, "could not locate DiscordEngine")
        return findings

    # _plan_key must prefix the session-invariant spec fields
    plan_key = next((m for m in methods if m.name == "_plan_key"),
                    None)
    if plan_key is None:
        bad("plan-key-prefix", 0,
            "DiscordEngine._plan_key is missing — backend/znorm/"
            "block have no route into the plan keys")
    else:
        attrs = {n.attr for n in ast.walk(plan_key)
                 if isinstance(n, ast.Attribute)}
        for needed in ("backend", "znorm", "block", "precision"):
            if needed not in attrs:
                bad("plan-key-prefix", plan_key.lineno,
                    f"_plan_key does not reference {needed!r}; the "
                    "field cannot reach the plan keys")

    # plan keys are built in one place, DiscordEngine.plan, from the
    # PLAN_KINDS table: string kinds, each named once, and the mesh
    # shape on the sharded ones
    table = _plan_kinds(engine_tree)
    if table is None:
        bad("plan-key-sites", 0,
            "no module-level PLAN_KINDS dict literal — the audit (and "
            "readers) can no longer see which plan kinds exist")
    else:
        kinds: Dict[str, int] = {}
        for k in table.keys:
            line = getattr(k, "lineno", table.lineno)
            if not (isinstance(k, ast.Constant)
                    and isinstance(k.value, str)):
                bad("plan-key-sites", line,
                    "PLAN_KINDS keys must be string kinds")
                continue
            if k.value in kinds:
                bad("plan-key-sites", line,
                    f"duplicate plan kind {k.value!r} (also at line "
                    f"{kinds[k.value]}) — the later entry silently "
                    "replaces the earlier")
            kinds[k.value] = line
    for m in methods:
        if m.name in ("plan", "_get_plan"):
            continue
        for line in _get_plan_sites(m):
            bad("plan-key-sites", line,
                f"{m.name}: builds a plan key outside "
                "DiscordEngine.plan — every key must come from the "
                "PLAN_KINDS table")
    plan = next((m for m in methods if m.name == "plan"), None)
    if plan is None or not _get_plan_sites(plan):
        bad("plan-key-sites", 0,
            "DiscordEngine.plan, the one place plan keys are built, is "
            "missing or never calls _get_plan")
    else:
        # names that flow into the key: every assignment to ``key``
        names = set()
        for node in ast.walk(plan):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target]
                       if isinstance(node, ast.AugAssign) else [])
            if any(isinstance(x, ast.Name) and x.id == "key"
                   for x in targets):
                names |= {n.id for n in ast.walk(node.value)
                          if isinstance(n, ast.Name)}
        if not ("ndev" in names and _calls(plan, "_resolve_mesh")):
            bad("plan-key-sites", plan.lineno,
                "plan: sharded kinds' keys omit the mesh shape (ndev) "
                "— plans for different mesh shapes would collide")
    return findings


#: perturbation fixtures of the runtime audit: field -> (override,
#: operation).  Trace-invariant fields assert *unchanged* keys.
_BASE = dict(s=24, k=1, method="matrix_profile", znorm=True,
             P=4, alpha=4, seed=0, r=None, block=32, ndev=None)
_PERTURB_KEYED = {
    "s": ({"s": 40}, "search"),
    "znorm": ({"znorm": False}, "search"),
    # "backend" is added per-run (it must differ from the base)
    "backend": (None, "search"),
    "block": ({"block": 64}, "search"),
    "ndev": ({"ndev": 1}, "batched"),
    "method": ({"method": "ring"}, "search"),
    "precision": ({"precision": "bf16"}, "search"),
}
_PERTURB_INVARIANT = {
    "k": {"k": 3},
    "P": {"P": 6},
    "alpha": {"alpha": 5},
    "seed": {"seed": 7},
    "r": {"r": 0.5},
}


def runtime_audit(*, backend: str = "xla") -> List[Finding]:
    """Perturb every SearchSpec field on tiny engines and assert the
    populated plan keys change (keyed fields) or stay identical
    (trace-invariant fields).  Imports jax — run it where the tile
    backends run, not on the lint-only path."""
    import dataclasses

    import numpy as np

    from repro.core import engine as engine_mod
    from repro.core.engine import DiscordEngine
    from repro.core.spec import SearchSpec

    findings: List[Finding] = []

    def bad(rule: str, msg: str) -> None:
        findings.append(Finding("speckey", rule, "core/engine.py", 0,
                                msg))

    spec_fields = {f.name for f in dataclasses.fields(SearchSpec)}
    declared = (set(engine_mod.PLAN_KEY_FIELDS)
                | set(engine_mod.KIND_DISPATCH_FIELDS)
                | set(engine_mod.TRACE_INVARIANT_FIELDS))
    for f in sorted(spec_fields - declared):
        bad("field-partition",
            f"SearchSpec field {f!r} missing from the engine's "
            "declared key partition")
    exercised = set(_PERTURB_KEYED) | set(_PERTURB_INVARIANT)
    for f in sorted(spec_fields - exercised):
        bad("runtime-coverage",
            f"SearchSpec field {f!r} has no perturbation fixture — "
            "extend repro.analysis.speckey._PERTURB_* so the audit "
            "keeps covering 100% of the spec")

    x = np.sin(0.37 * np.arange(96.0)) + 0.05 * np.cos(np.arange(96.0))
    base = dict(_BASE, backend=backend)
    perturb = dict(_PERTURB_KEYED)
    perturb["backend"] = (
        {"backend": "xla" if backend == "numpy" else "numpy"},
        "search")

    def plan_keys(overrides: dict, op: str) -> frozenset:
        eng = DiscordEngine(SearchSpec(**{**base, **overrides}))
        if op == "batched":
            eng.search_batched(np.stack([x, x + 0.25]))
        else:
            eng.search(x)
        return frozenset(eng._plans)

    ref = {"search": plan_keys({}, "search"),
           "batched": plan_keys({}, "batched")}
    for fname, (ov, op) in perturb.items():
        if fname not in spec_fields:
            continue
        if plan_keys(ov, op) == ref[op]:
            bad("key-collision",
                f"perturbing SearchSpec.{fname} ({ov}) left the plan "
                "keys unchanged — two specs differing in "
                f"{fname!r} would collide on one compiled plan")
    for fname, ov in _PERTURB_INVARIANT.items():
        if fname not in spec_fields:
            continue
        if plan_keys(ov, "search") != ref["search"]:
            bad("spurious-key",
                f"perturbing the declared trace-invariant field "
                f"SearchSpec.{fname} ({ov}) changed the plan keys — "
                "either it belongs in PLAN_KEY_FIELDS or the key "
                "leaks host-only state (needless recompiles)")
    return findings
