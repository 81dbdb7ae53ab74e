"""Jaxpr audit: IR-level invariants of every registered plan kind.

The AST lint (``analysis/lint.py``) sees source text; closures, vmaps
and ``lax.map`` micro-batch plans hide what actually gets compiled.
This pass abstractly traces every plan kind of
``repro.core.engine.plan_kind_registry()`` via ``jax.make_jaxpr`` —
no execution, no compile — and walks the closed jaxpr for invariants
only the IR can prove:

``ir-f64``
    No float64 dtype anywhere in the closed jaxpr (avals, literals,
    baked constants).  With jax's x64 mode off this cannot trigger —
    the rule is defense-in-depth against an ``enable_x64`` context
    leaking into a plan trace.

``ir-dot-pet``
    Every ``dot_general`` carries ``preferred_element_type=float32``
    (unpinned accumulators drift with the platform — the f64-kernel
    AST rule checked only ``kernels/`` sources; this checks what the
    trace actually staged, wherever it came from).

``ir-callback``
    ``pure_callback`` / ``io_callback`` appear only in plans whose
    backend declares ``host_callback`` traits
    (``kernels.registry.backend_traits``) — i.e. the ``numpy``
    reference backend — and never inside sharded or ``mb`` plans (the
    audit matrix simply has no numpy cells for those layouts:
    coalesced and mesh plans are device-backend planes by contract).

``ir-const``
    No oversized baked-in constant (default threshold
    ``DEFAULT_CONST_BYTES``): a large closed-over array is a
    closure-capture retrace hazard — it silently re-bakes per plan
    instead of flowing through the plan's arguments.

``ir-flop-model`` / ``ir-lane-model``
    The static FLOP/lane cross-audit: the ordered ``dot_general``
    decomposition of the traced body (contraction cells x widths,
    scan/``lax.map``/mesh multiplicities folded in) must equal the
    registry entry's expected ``pattern``, and its width-normalized
    lane count (``PlanKindAudit.model_lanes``) must equal the
    ``tile_lanes`` the runtime accounting books for the same geometry
    (docs/cps.md) — a static proof that the numbers every BENCH gate
    trusts decompose correctly.  Applies where the backend's
    ``dot_model`` trait is ``"exact"`` (``xla``); pallas dots are
    MXU-padded inside ``pallas_call`` kernels and numpy contractions
    never reach the IR (both still get the dtype/callback/const
    rules).

Importing this module imports the engine (and jax) — keep it off
the lint-only path.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from repro.core.engine import PLAN_KINDS

from .report import Finding

__all__ = ["DEFAULT_CONST_BYTES", "ZNORM_ONLY_KINDS", "audit_matrix",
           "run_irlint", "summarize_jaxpr"]

#: closure-captured constants above this many bytes are flagged as
#: retrace hazards (a (256, 256) f32 slab = 256 KiB trips it; the
#: id/iota vectors the plans legitimately bake are ~1 KiB)
DEFAULT_CONST_BYTES = 128 * 1024

#: kinds audited (and sanitized) in z-normalized mode only
#: (``PLAN_KINDS[kind].znorm_only``)
ZNORM_ONLY_KINDS = frozenset(k for k, pk in PLAN_KINDS.items()
                             if pk.znorm_only)

_CALLBACK_PRIMS = ("pure_callback", "io_callback")


# ---------------------------------------------------------------------
# jaxpr walker
# ---------------------------------------------------------------------
@dataclass
class DotSite:
    """One ``dot_general`` in the traced body, multiplicity folded."""
    mult: int            # enclosing scan/map lengths x mesh devices
    out_cells: int       # product of the output aval's shape
    width: int           # product of the contraction dim sizes
    pet: Optional[str]   # preferred_element_type (None if unpinned)

    @property
    def cells(self) -> int:
        return self.mult * self.out_cells


@dataclass
class IRSummary:
    """Everything the rules need from one closed jaxpr."""
    dots: List[DotSite] = field(default_factory=list)
    callbacks: List[str] = field(default_factory=list)
    f64: List[str] = field(default_factory=list)
    consts: List[Tuple[tuple, str, int]] = field(default_factory=list)


def _jaxprs_in(v):
    """Yield (open-jaxpr, consts) for any jaxpr-like object inside a
    params value (Jaxpr, ClosedJaxpr, or containers of them)."""
    if hasattr(v, "eqns") and hasattr(v, "invars"):       # open Jaxpr
        yield v, ()
    elif hasattr(v, "jaxpr") and hasattr(v, "consts"):    # ClosedJaxpr
        yield v.jaxpr, tuple(v.consts)
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _jaxprs_in(item)


def _eqn_mult(eqn) -> int:
    """Static execution multiplicity of an eqn's sub-jaxprs: scan
    length, mesh device count for shard_map, 1 otherwise (cond
    branches are alternatives, not repetitions — the audited plans
    carry no data-dependent dots)."""
    import numpy as np
    name = eqn.primitive.name
    if name == "scan":
        return int(eqn.params.get("length", 1))
    if name == "shard_map":
        mesh = eqn.params.get("mesh")
        if mesh is not None:
            try:
                return int(np.prod([int(n) for n in
                                    dict(mesh.shape).values()]))
            except (TypeError, AttributeError):
                return int(getattr(mesh, "size", 1))
    return 1


def _note_f64(dtype, where: str, summ: IRSummary) -> None:
    import numpy as np
    if dtype is not None and np.dtype(dtype) == np.float64:
        summ.f64.append(where)


def _collect_consts(consts, summ: IRSummary) -> None:
    import numpy as np
    for c in consts:
        arr = getattr(c, "dtype", None)
        if arr is None:
            continue
        nbytes = int(getattr(c, "nbytes", 0) or
                     np.dtype(c.dtype).itemsize
                     * int(np.prod(getattr(c, "shape", ()) or (1,))))
        summ.consts.append((tuple(getattr(c, "shape", ())),
                            str(np.dtype(c.dtype)), nbytes))
        _note_f64(c.dtype, f"baked constant {tuple(c.shape)}", summ)


def _walk(jaxpr, mult: int, summ: IRSummary) -> None:
    import numpy as np
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _CALLBACK_PRIMS:
            summ.callbacks.append(name)
        for v in eqn.invars:
            # literals carry concrete values; vars carry avals
            aval = getattr(v, "aval", None)
            _note_f64(getattr(aval, "dtype", None),
                      f"{name} input", summ)
        for v in eqn.outvars:
            _note_f64(getattr(getattr(v, "aval", None), "dtype", None),
                      f"{name} output", summ)
        if name == "dot_general":
            (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
            lhs_shape = eqn.invars[0].aval.shape
            width = int(np.prod([lhs_shape[d] for d in lhs_c])) \
                if lhs_c else 1
            out_cells = int(np.prod(eqn.outvars[0].aval.shape)) \
                if eqn.outvars[0].aval.shape else 1
            pet = eqn.params.get("preferred_element_type")
            summ.dots.append(DotSite(
                mult=mult, out_cells=out_cells, width=width,
                pet=None if pet is None else str(np.dtype(pet))))
        m = _eqn_mult(eqn)
        for sub, consts in _jaxprs_in(list(eqn.params.values())):
            _collect_consts(consts, summ)
            _walk(sub, mult * m, summ)


def summarize_jaxpr(closed) -> IRSummary:
    """Walk a ClosedJaxpr (recursively through pjit/scan/shard_map/
    cond/pallas_call sub-jaxprs) into an :class:`IRSummary`."""
    summ = IRSummary()
    _collect_consts(closed.consts, summ)
    for v in closed.jaxpr.invars:
        _note_f64(getattr(getattr(v, "aval", None), "dtype", None),
                  "plan input", summ)
    _walk(closed.jaxpr, 1, summ)
    return summ


# ---------------------------------------------------------------------
# audit matrix + per-cell rules
# ---------------------------------------------------------------------
def audit_matrix(registry, backends: Sequence[str]
                 ) -> List[Tuple[str, str, bool]]:
    """The (kind, backend, znorm) cells to audit.  Every kind on every
    device backend; the host-callback (numpy) backend only audits
    local non-coalesced kinds — ``mb`` and sharded plans are
    device-backend planes by contract, which is exactly what lets the
    ``ir-callback`` rule be absolute for them.  Raw mode re-audits on
    ``xla`` only (same dot decomposition; :data:`ZNORM_ONLY_KINDS`
    are skipped)."""
    from ..kernels.registry import backend_traits
    cells: List[Tuple[str, str, bool]] = []
    for be in backends:
        host_cb = bool(backend_traits(be)["host_callback"])
        for e in registry.values():
            pk = PLAN_KINDS[e.kind]
            if host_cb and (pk.sharded or pk.layout == "mb"):
                continue
            cells.append((e.kind, be, True))
    if "xla" in backends:
        for e in registry.values():
            if e.kind not in ZNORM_ONLY_KINDS:
                cells.append((e.kind, "xla", False))
    return cells


def _template(kind: str) -> str:
    """The :class:`_Engines` spec template a plan kind audits on."""
    pk = PLAN_KINDS[kind]
    t = ("qsweep" if pk.quant else
         "pan" if pk.op.startswith("pan") else "mp")
    return t + "_ndev" if pk.sharded else t


class _Engines:
    """Engine per (spec template, backend, znorm) — mirrors the
    sanitizer's spec templates so the audited geometry is the same
    family the poison pass exercises."""

    def __init__(self, *, s: int, ladder, block: int, ndev: int):
        self.s, self.ladder = int(s), tuple(ladder)
        self.block, self.ndev = int(block), int(ndev)
        self._cache: Dict[tuple, object] = {}

    def get(self, template: str, backend: str, znorm: bool):
        key = (template, backend, znorm)
        if key in self._cache:
            return self._cache[key]
        from repro.core.engine import DiscordEngine
        from repro.core.spec import SearchSpec
        base = dict(k=2, znorm=znorm, backend=backend,
                    block=self.block)
        specs = {
            "mp": dict(s=self.s, method="matrix_profile"),
            "mp_ndev": dict(s=self.s, method="matrix_profile",
                            ndev=self.ndev),
            "pan": dict(s=self.ladder, method="matrix_profile"),
            "pan_ndev": dict(s=self.ladder, method="matrix_profile",
                             ndev=self.ndev),
            # the quantized kinds audit at bf16 — int8 pins an int32
            # dot accumulator by construction (never a pet="float32"
            # site), so bf16 is the precision whose dot the
            # ir-dot-pet rule must see pinned
            "qsweep": dict(s=self.s, method="matrix_profile",
                           precision="bf16"),
            "qsweep_ndev": dict(s=self.s, method="ring",
                                ndev=self.ndev, precision="bf16"),
        }
        eng = DiscordEngine(SearchSpec(**{**base, **specs[template]}))
        self._cache[key] = eng
        return eng


def _audit_cell(entry, eng, backend: str, znorm: bool, *,
                const_bytes: int) -> Tuple[List[Finding], dict]:
    """Trace one (kind, backend, znorm) cell and run every IR rule."""
    import jax
    import numpy as np

    from ..kernels.registry import backend_traits
    locus = f"{entry.kind}[{backend},znorm={znorm}]"
    findings: List[Finding] = []
    try:
        fn = eng.plan(entry.kind, *entry.geom)
        avals = [jax.ShapeDtypeStruct(tuple(shape), np.dtype(dt))
                 for shape, dt in entry.avals]
        closed = jax.make_jaxpr(fn)(*avals)
    except Exception as e:      # noqa: BLE001 - findings, not crashes
        return [Finding("irlint", "ir-trace-error", locus, 0,
                        f"abstract trace failed: "
                        f"{type(e).__name__}: {e}")], {}
    summ = summarize_jaxpr(closed)
    traits = backend_traits(backend)

    for where in sorted(set(summ.f64)):
        findings.append(Finding(
            "irlint", "ir-f64", locus, 0,
            f"float64 staged into the plan jaxpr ({where}) — plans "
            "are f32 end to end"))
    for d in summ.dots:
        if d.pet != "float32":
            findings.append(Finding(
                "irlint", "ir-dot-pet", locus, 0,
                f"dot_general (cells={d.cells}, width={d.width}) "
                f"with preferred_element_type={d.pet!r} — every tile "
                "contraction must pin a float32 accumulator"))
    if summ.callbacks and not traits["host_callback"]:
        findings.append(Finding(
            "irlint", "ir-callback", locus, 0,
            f"{len(summ.callbacks)} host callback(s) "
            f"({sorted(set(summ.callbacks))}) staged into a "
            f"{backend}-backend plan — callbacks are the numpy "
            "reference backend's privilege, and never legal in "
            "*_ring/*_mb plans"))
    for shape, dt, nbytes in summ.consts:
        if nbytes > const_bytes:
            findings.append(Finding(
                "irlint", "ir-const", locus, 0,
                f"baked-in constant {shape} {dt} ({nbytes} B > "
                f"{const_bytes} B) — closure-captured slabs re-bake "
                "per plan (retrace hazard); route them through the "
                "plan's arguments"))

    meta = {"backend": backend, "znorm": znorm,
            "dot_sites": len(summ.dots),
            "callbacks": len(summ.callbacks)}
    if traits["dot_model"] == "exact":
        traced = tuple((d.cells, d.width) for d in summ.dots)
        meta["dots"] = [list(t) for t in traced]
        meta["macs"] = int(sum(c * w for c, w in traced))
        meta["tile_lanes"] = int(entry.lanes)
        if traced != tuple(entry.pattern):
            findings.append(Finding(
                "irlint", "ir-flop-model", locus, 0,
                f"traced dot decomposition {list(traced)} != expected "
                f"{list(entry.pattern)} (cells x width per site, "
                "multiplicities folded) — the cps.md accounting no "
                "longer describes what this plan compiles"))
        else:
            model = entry.model_lanes(traced)
            meta["model_lanes"] = int(model)
            if model != entry.lanes:
                findings.append(Finding(
                    "irlint", "ir-lane-model", locus, 0,
                    f"width-normalized lanes of the traced IR "
                    f"({model}) != runtime tile_lanes accounting "
                    f"({entry.lanes}) at the pinned geometry"))
    return findings, meta


def run_irlint(backends: Iterable[str] = ("numpy", "xla", "pallas"),
               kinds: Optional[Sequence[str]] = None,
               ndev: Optional[int] = None,
               const_bytes: int = DEFAULT_CONST_BYTES,
               ) -> Tuple[List[Finding], dict]:
    """Audit every registered plan kind's traced jaxpr.

    Returns ``(findings, meta)``; ``meta["lane_model"]`` records the
    per-kind static-vs-runtime lane cross-check (xla, znorm=True
    cells) for the report artifact.  ``ndev`` defaults to the local
    device count (CI forces 4 so the ``*_ring`` kinds audit a real
    multi-device mesh).
    """
    import jax

    from repro.core.engine import plan_kind_registry
    if ndev is None:
        ndev = jax.local_device_count()
    registry = plan_kind_registry(ndev=ndev)
    if kinds is not None:
        unknown = sorted(set(kinds) - set(registry))
        if unknown:
            raise ValueError(f"unknown plan kinds {unknown} "
                             f"(known: {tuple(registry)})")
        registry = type(registry)(
            (k, v) for k, v in registry.items() if k in set(kinds))
    engines = _Engines(s=24, ladder=(16, 24, 32), block=32, ndev=ndev)

    findings: List[Finding] = []
    if kinds is None:
        findings.extend(coverage_audit())
    checked: List[str] = []
    lane_model: Dict[str, dict] = {}
    for kind, backend, znorm in audit_matrix(registry,
                                             tuple(backends)):
        entry = registry[kind]
        eng = engines.get(_template(kind), backend, znorm)
        f, meta = _audit_cell(entry, eng, backend, znorm,
                              const_bytes=const_bytes)
        findings.extend(f)
        checked.append(f"{kind}[{backend},znorm={znorm}]")
        if backend == "xla" and znorm and "model_lanes" in meta:
            lane_model[kind] = {k: meta[k] for k in
                                ("macs", "model_lanes", "tile_lanes")}
    meta = {"ndev": int(ndev), "kinds": list(registry),
            "checked": checked, "lane_model": lane_model}
    return findings, meta


def coverage_audit() -> List[Finding]:
    """Registry completeness against the table: every
    :data:`PLAN_KINDS` kind has a ``plan_kind_registry`` entry (the
    "discover, don't hard-code" contract), every entry is a table
    kind, and every kind's op and layout are ``DiscordEngine``
    methods ``plan`` can call."""
    from repro.core.engine import DiscordEngine, plan_kind_registry
    registry = plan_kind_registry()
    findings: List[Finding] = []

    def bad(kind: str, msg: str) -> None:
        findings.append(Finding("irlint", "ir-kind-coverage",
                                f"core/engine.py::{kind}", 0, msg))

    for kind in sorted(set(PLAN_KINDS) - set(registry)):
        bad(kind, f"plan kind {kind} has no plan_kind_registry entry "
                  "— the IR auditor cannot see it")
    for kind in sorted(set(registry) - set(PLAN_KINDS)):
        bad(kind, f"plan_kind_registry names {kind}, which is not in "
                  "PLAN_KINDS")
    for kind, pk in PLAN_KINDS.items():
        for name in ("_op_" + pk.op, "_layout_" + pk.layout):
            if not callable(getattr(DiscordEngine, name, None)):
                bad(kind, f"plan kind {kind} needs DiscordEngine.{name}, "
                          "which does not exist")
    return findings
