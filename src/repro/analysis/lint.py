"""AST lint rules for the engine + kernel layers (jax-free).

The repo's performance story rests on invariants nothing in Python
enforces: all Eq. (3) tile math must live behind the backend registry,
compiled plan bodies must never sync to the host, kernels must stay
f32, and every ``jax.jit`` in ``core/`` must be reachable only through
the engine's plan cache.  This module is a small rule engine over
``ast`` that checks those invariants statically — it imports neither
jax nor the linted modules, so it runs on a CPU-only CI box in
seconds.

Rules (docs/analysis.md has the full catalogue):

``tile-math``
    No ``dot_general`` / ``jnp.dot``-family calls / ``@`` matmuls /
    manual ``sum((a - b) ** 2)`` distance math outside ``kernels/``
    and the tile layer (``core/tiles.py``; ``core/distance.py`` and
    ``core/serial/`` are the paper-faithful counted scalar plane and
    allowlisted by design).

``host-sync``
    No host synchronization (``.item()``, ``np.*`` calls,
    ``block_until_ready``, ``jax.device_get``, ``float(...)``) inside
    the plan bodies of ``core/engine.py`` (the ``_op_*``, ``_rows_*``
    and ``_layout_*`` methods and ``build()`` closures) or the
    jit-safe ``PanEngine`` methods of
    ``core/pan.py`` — a sync there either breaks tracing or silently
    serializes every plan invocation.  The serve/telemetry dispatch
    paths (``DiscordServer._exec_group``,
    ``TelemetryMonitor._prepare_metric``) carry a weaker
    *deferred-sync* contract: they run host-side (so host NumPy
    staging like ``np.stack`` is fine) but must never force results
    back (``.item()``, ``np.asarray``/``to_np`` on outputs,
    ``block_until_ready``, ``device_get``, or a nested
    ``flush()``/``discords()``) — groups must overlap on device, with
    all blocking folds in the response path.

``f64-kernel``
    No float64 literals/dtypes and no ``dot_general`` without
    ``preferred_element_type`` inside ``kernels/`` (MXU dtype drift).

``untracked-jit``
    No ``jax.jit`` in ``core/`` outside ``DiscordEngine._get_plan`` —
    every compiled plan must be reachable through (and accounted by)
    the plan cache.

Escape hatch: append ``# analysis: ignore[rule-name]`` (with a
justifying comment) on the flagged line or the line directly above.
"""
from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .report import Finding

__all__ = ["RULES", "lint_source", "run_lint", "package_root"]

IGNORE_RE = re.compile(r"#\s*analysis:\s*ignore\[([a-zA-Z0-9_\-, ]+)\]")


def package_root() -> Path:
    """Directory of the ``repro`` package — lint paths are relative
    to it (e.g. ``core/engine.py``)."""
    return Path(__file__).resolve().parent.parent


def _attr_chain(node: ast.AST) -> str:
    """Dotted name of a Name/Attribute chain (``""`` otherwise)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_sq_diff(node: ast.AST) -> bool:
    """Any descendant ``(a - b) ** 2`` — the manual-d² signature."""
    for sub in ast.walk(node):
        if (isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Pow)
                and isinstance(sub.left, ast.BinOp)
                and isinstance(sub.left.op, ast.Sub)
                and isinstance(sub.right, ast.Constant)
                and sub.right.value == 2):
            return True
    return False


class Rule:
    """One lint rule: a path scope plus an AST check."""
    name = "rule"
    description = ""

    def applies_to(self, relpath: str) -> bool:
        raise NotImplementedError

    def check(self, tree: ast.AST, relpath: str
              ) -> Iterator[Tuple[int, str]]:
        raise NotImplementedError


class TileMathRule(Rule):
    name = "tile-math"
    description = ("Eq. (3)/distance tile math must live behind "
                   "kernels/ or core/tiles.py")
    #: discord-plane layers in scope (the LM scaffolding — models/,
    #: optim/, train/, parallel/, checkpoint/ — legitimately matmuls)
    SCOPE = ("core/", "launch/", "data/", "telemetry/", "serve/")
    #: the tile layer itself plus the paper-faithful counted scalar
    #: plane (core/distance.py, core/serial/) — allowlisted by design
    ALLOW = ("core/tiles.py", "core/distance.py")
    ALLOW_PREFIX = ("core/serial/",)
    _DOT_FUNCS = {"dot", "matmul", "einsum", "tensordot", "vdot"}
    _ARRAY_MODS = {"jnp", "np", "numpy", "jax.numpy", "lax", "jax.lax"}

    def applies_to(self, relpath: str) -> bool:
        if relpath in self.ALLOW or \
                relpath.startswith(self.ALLOW_PREFIX):
            return False
        return relpath.startswith(self.SCOPE)

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if isinstance(node, ast.BinOp) and \
                    isinstance(node.op, ast.MatMult):
                yield (node.lineno,
                       "matrix multiply (@) outside the kernel "
                       "registry — route tile math through "
                       "kernels.registry / core/tiles.py")
            elif isinstance(node, ast.Call):
                chain = _attr_chain(node.func)
                # fall back to the bare attribute name when the
                # receiver is an expression (((a-b)**2).sum(), chained
                # calls) and the dotted chain can't be resolved
                if chain:
                    last = chain.rsplit(".", 1)[-1]
                elif isinstance(node.func, ast.Attribute):
                    last = node.func.attr
                else:
                    last = ""
                if last == "dot_general":
                    yield (node.lineno,
                           "dot_general outside kernels/ — tile "
                           "contractions belong to the backend "
                           "registry")
                elif ("." in chain
                        and chain.rsplit(".", 1)[0] in self._ARRAY_MODS
                        and last in self._DOT_FUNCS):
                    yield (node.lineno,
                           f"{chain}() outside kernels/ — tile "
                           "contractions belong to the backend "
                           "registry")
                elif last == "sum":
                    hay: List[ast.AST] = list(node.args)
                    if isinstance(node.func, ast.Attribute):
                        hay.append(node.func.value)
                    if any(_is_sq_diff(h) for h in hay):
                        yield (node.lineno,
                               "manual sum((a - b) ** 2) distance — "
                               "use the tile layer "
                               "(core/tiles.exact_pair_d2 or a "
                               "registry backend)")


class HostSyncRule(Rule):
    name = "host-sync"
    description = ("no host sync (.item(), np.*, block_until_ready, "
                   "float()) inside plan bodies; no output sync / "
                   "nested flush in serve/telemetry dispatch paths")
    SCOPE = ("core/engine.py", "core/pan.py")
    #: host-side dispatch paths with a *deferred-sync* contract:
    #: file -> method names whose bodies stage work but must never
    #: force results back to the host (the blocking folds belong to
    #: the response path, so plan groups overlap on device)
    DEFERRED = {
        "serve/discord.py": ("_exec_group",),
        "telemetry/monitor.py": ("_prepare_metric",),
    }

    def applies_to(self, relpath: str) -> bool:
        return relpath in self.SCOPE or relpath in self.DEFERRED

    def _traced_scopes(self, tree, relpath) -> Iterator[ast.AST]:
        """The subtrees whose code runs under jit tracing: in
        engine.py every op body, row body and layout
        (``_op_*``/``_rows_*``/``_layout_*``) and ``build()`` closure,
        in pan.py every ``PanEngine`` method (PanEngine is constructed
        *inside* plan bodies)."""
        if relpath.endswith("engine.py"):
            for node in ast.walk(tree):
                if isinstance(node, ast.FunctionDef) and (
                        node.name == "build" or node.name.startswith(
                            ("_op_", "_rows_", "_layout_"))):
                    yield node
        elif relpath.endswith("pan.py"):
            for node in ast.walk(tree):
                if isinstance(node, ast.ClassDef) and \
                        node.name == "PanEngine":
                    for sub in node.body:
                        if isinstance(sub, ast.FunctionDef):
                            yield sub

    def _deferred_scopes(self, tree, relpath) -> Iterator[ast.AST]:
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef) and \
                    node.name in self.DEFERRED.get(relpath, ()):
                yield node

    def check(self, tree, relpath):
        for scope in self._traced_scopes(tree, relpath):
            for node in ast.walk(scope):
                if isinstance(node, ast.Call):
                    chain = _attr_chain(node.func)
                    if (isinstance(node.func, ast.Attribute)
                            and node.func.attr == "item"):
                        yield (node.lineno,
                               ".item() forces a device->host sync "
                               "inside a plan body")
                    elif chain.startswith(("np.", "numpy.")):
                        yield (node.lineno,
                               f"{chain}() is host NumPy inside a "
                               "plan body — it breaks tracing or "
                               "silently syncs every invocation")
                    elif chain == "jax.device_get":
                        yield (node.lineno,
                               "jax.device_get inside a plan body")
                    elif chain == "float":
                        yield (node.lineno,
                               "float(...) on a traced value forces "
                               "a host sync inside a plan body")
                elif isinstance(node, ast.Attribute) and \
                        node.attr == "block_until_ready":
                    yield (node.lineno,
                           "block_until_ready inside a plan body")
        for scope in self._deferred_scopes(tree, relpath):
            for node in ast.walk(scope):
                if isinstance(node, ast.Call):
                    chain = _attr_chain(node.func)
                    last = node.func.attr \
                        if isinstance(node.func, ast.Attribute) \
                        else chain
                    if last == "item":
                        yield (node.lineno,
                               ".item() blocks the dispatch path on "
                               "device results — fold in the "
                               "response path instead")
                    elif chain in ("np.asarray", "numpy.asarray") \
                            or last == "to_np":
                        yield (node.lineno,
                               f"{last}() on the dispatch path syncs "
                               "device output — host staging uses "
                               "np.stack/np.array on inputs; result "
                               "folds belong to the response path")
                    elif chain == "jax.device_get":
                        yield (node.lineno,
                               "jax.device_get on the dispatch path "
                               "blocks the group overlap")
                    elif last in ("flush", "discords"):
                        yield (node.lineno,
                               f"{last}() inside the dispatch path "
                               "forces the deferred work it is "
                               "supposed to be deferring")
                elif isinstance(node, ast.Attribute) and \
                        node.attr == "block_until_ready":
                    yield (node.lineno,
                           "block_until_ready on the dispatch path "
                           "serializes plan groups")


class F64KernelRule(Rule):
    name = "f64-kernel"
    description = ("no float64 literals / dtype drift in kernel "
                   "files; dot_general must pin "
                   "preferred_element_type")
    SCOPE = ("kernels/",)
    _F64_STRS = {"float64", "f64", "double"}

    def applies_to(self, relpath: str) -> bool:
        return relpath.startswith(self.SCOPE)

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and \
                    node.attr == "float64":
                yield (node.lineno,
                       "float64 in a kernel file — tiles are f32 "
                       "end to end (MXU dtype drift)")
            elif isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and \
                    node.value in self._F64_STRS:
                yield (node.lineno,
                       f"dtype string {node.value!r} in a kernel "
                       "file — tiles are f32 end to end")
            elif isinstance(node, ast.Call):
                chain = _attr_chain(node.func)
                if chain.rsplit(".", 1)[-1] == "dot_general" and \
                        not any(k.arg == "preferred_element_type"
                                for k in node.keywords):
                    yield (node.lineno,
                           "dot_general without preferred_element_"
                           "type — the accumulator dtype drifts "
                           "with the platform")


class UntrackedJitRule(Rule):
    name = "untracked-jit"
    description = ("every jax.jit in core/ must go through the "
                   "engine plan cache (_get_plan)")
    SCOPE = ("core/",)

    def applies_to(self, relpath: str) -> bool:
        return relpath.startswith(self.SCOPE)

    def check(self, tree, relpath):
        hits: List[int] = []

        def visit(node: ast.AST, in_get_plan: bool) -> None:
            if isinstance(node, ast.FunctionDef):
                in_get_plan = in_get_plan or node.name == "_get_plan"
            if isinstance(node, ast.Attribute) and \
                    node.attr == "jit" and \
                    _attr_chain(node) == "jax.jit" and \
                    not in_get_plan:
                hits.append(node.lineno)
            for child in ast.iter_child_nodes(node):
                visit(child, in_get_plan)

        visit(tree, False)
        for line in hits:
            yield (line,
                   "jax.jit outside DiscordEngine._get_plan — "
                   "untracked compilations bypass the plan cache "
                   "(stats.plans/traces) and retrace per call site")


RULES: Tuple[Rule, ...] = (TileMathRule(), HostSyncRule(),
                           F64KernelRule(), UntrackedJitRule())


def _ignored_lines(source: str) -> Dict[int, Set[str]]:
    """line -> rule names suppressed on that line (pragma scan)."""
    out: Dict[int, Set[str]] = {}
    for i, text in enumerate(source.splitlines(), start=1):
        m = IGNORE_RE.search(text)
        if m:
            out[i] = {r.strip() for r in m.group(1).split(",")}
    return out


def lint_source(source: str, relpath: str,
                rules: Sequence[Rule] = RULES) -> List[Finding]:
    """Lint one module's source as if it lived at ``relpath``
    (posix path relative to the ``repro`` package root)."""
    relpath = relpath.replace("\\", "/")
    tree = ast.parse(source)
    ignored = _ignored_lines(source)
    findings: List[Finding] = []
    for rule in rules:
        if not rule.applies_to(relpath):
            continue
        for line, message in rule.check(tree, relpath):
            if any(rule.name in ignored.get(ln, ())
                   for ln in (line, line - 1)):
                continue
            findings.append(Finding("lint", rule.name, relpath, line,
                                    message))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def run_lint(root: Optional[Path] = None,
             rules: Sequence[Rule] = RULES,
             counts: Optional[dict] = None) -> List[Finding]:
    """Lint every ``*.py`` under the ``repro`` package.  When a dict
    is passed as ``counts`` it is filled with coverage numbers
    (files/rules/per-rule files-in-scope) for the report artifact."""
    root = Path(root) if root is not None else package_root()
    findings: List[Finding] = []
    n_files = 0
    in_scope = {rule.name: 0 for rule in rules}
    for path in sorted(root.rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        n_files += 1
        for rule in rules:
            if rule.applies_to(rel):
                in_scope[rule.name] += 1
        findings.extend(lint_source(path.read_text(), rel, rules))
    if counts is not None:
        counts["files"] = n_files
        counts["rules"] = len(rules)
        counts["files_in_scope"] = in_scope
    return findings
