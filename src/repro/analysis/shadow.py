"""Shadow-numerics sanitizer: f64 replay on a conditioning-hostile
series.

The plan planes run f32 end to end (the IR audit proves it).  f32 is
*enough* for exact discord ranking only while the top-k margins
dominate the accumulated rounding — and the classic killer is a large
mean offset: the z-norm statistics difference ``E[x²] − μ²`` and the
distance form ``‖q‖² + ‖c‖² − 2⟨q,c⟩`` both cancel catastrophically
when the series rides far from zero (telemetry gauges, absolute
temperatures, prices).  This pass replays every plan kind on a series
built to be hostile — mean offset ≫ amplitude, a near-constant shelf
(tiny true variance, so the f32 σ error is a visible fraction of it),
planted discords with known margins — and checks each result against
an independent float64 reference path:

* reference matrix profiles are computed directly in f64 (explicit
  z-normalized windows, stable two-pass moments — *not* the engine's
  csum algebra, so a shared bug can't cancel out);
* top-k selection and the pan global ranking reuse the engine's own
  host-side selectors (``topk_nonoverlapping``,
  ``global_normalized_topk``) so only numerics differ, never
  tie-breaking;
* ``topk-drift``: the f32 plan's discord **positions** must equal the
  f64 reference exactly — a flipped rank on this series means the
  margins users rely on are already gone;
* ``nnd-divergence``: each neighbor distance must stay within
  ``tol`` (relative) of the f64 value;
* per-cell worst-case relative error, f32 ULP distance, and the
  reference's own top-k margin go to the report — the baseline the
  quantized (bf16/int8) tile-sweep pass is gated against.

The quantized sweep kinds (``qsweep`` / ``qsweep_tail`` /
``qsweep_ring``, docs/cps.md) replay under **every requested
precision** and face two gates.  The hostile series gets the same 5%
regret rule as the exact kinds — the bound pass + f32 refinement
contract promises bit-identical results, so any extra drift here is a
soundness bug, not a quantization artifact.  But the hostile series
is also a degenerate prune case: its huge mean offset inflates the
window norms and with them the rounding-error radius, so every block
legitimately survives the bound pass (prune ratio 0, still exact).
A second replay on the sanitizer's *benign* series therefore asserts
the bf16 bound pass actually prunes (``qsweep-no-prune``) — without
it, a silently vacuous bound (radius overflow, wrong norm term) would
keep passing every exactness gate while the quantized plane quietly
degenerates into a 2x-cost exact sweep.

Micro-batch (``*_mb``) plans are not separately shadowed: they are
property-tested bit-identical to their single-stream counterparts
(tests/test_serve_property.py), so the single-stream cells cover
them.

Importing this module imports the engine (and jax) — keep it off the
lint-only path.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from repro.core.engine import PLAN_KINDS

from .irlint import ZNORM_ONLY_KINDS
from .report import Finding
from .sanitize import ALL_KINDS, _Context

__all__ = ["DEFAULT_TOL", "QUANT_KINDS", "hostile_series",
           "ref_profile", "ref_topk", "run_shadow"]

#: plan kinds that run the quantized bound pass + exact refinement —
#: replayed per precision, and prune-gated on the benign series
QUANT_KINDS = tuple(k for k in ALL_KINDS
                    if k in PLAN_KINDS and PLAN_KINDS[k].quant)

#: max relative nnd error vs the f64 reference before a finding; the
#: hostile series is built to sit well inside this on a healthy tree
#: (observed worst ~1e-3 offset-dominated) while a broken σ clamp or
#: dropped correction term overshoots it by orders of magnitude
DEFAULT_TOL = 0.05

_OFFSET = 100.0      # mean offset ≫ amplitude: cancellation hostile
_SHELF_AMP = 0.35    # near-constant shelf amplitude


def hostile_series(length: int = 90, *, offset: float = _OFFSET,
                   shelf_amp: float = _SHELF_AMP):
    """Two conditioning-hostile series (primary + batch mate): mean
    offset ``offset``, a near-constant shelf over [0.25L, 0.45L), and
    two planted discords each (one in the stream-tail region) with
    margins large enough that f64 and healthy-f32 agree on ranks."""
    import numpy as np
    t = np.arange(float(length))
    lo, hi = int(0.25 * length), int(0.45 * length)
    x = offset + np.sin(0.31 * t) + 0.23 * np.cos(0.11 * t)
    x[lo:hi] = offset + shelf_amp * np.sin(0.31 * t[lo:hi])
    x[int(0.60 * length)] += 3.0
    x[int(0.85 * length)] -= 2.6     # lands in the appended tail
    y = offset + np.cos(0.27 * t) - 0.17 * np.sin(0.13 * t)
    y[lo:hi] = offset + shelf_amp * np.cos(0.27 * t[lo:hi])
    y[int(0.55 * length)] += 2.9
    y[int(0.88 * length)] -= 2.5
    return x, y


class _ShadowContext(_Context):
    """The sanitizer's per-(backend, znorm) plan drivers, re-pointed
    at the hostile series."""

    def __init__(self, backend: str, znorm: bool, **kw):
        super().__init__(backend, znorm, **kw)
        self.x, self.y = hostile_series(len(self.x))


# ---------------------------------------------------------------------
# f64 reference path
# ---------------------------------------------------------------------
def ref_profile(x, s: int, znorm: bool):
    """Float64 matrix profile of ``x`` at window ``s`` — explicit
    windows and two-pass moments, deliberately not the engine's
    cumulative-sum algebra."""
    import numpy as np
    x = np.asarray(x, dtype=np.float64)
    W = np.lib.stride_tricks.sliding_window_view(x, s)
    n = W.shape[0]
    if znorm:
        mu = W.mean(axis=1, keepdims=True)
        sig = np.maximum(W.std(axis=1, keepdims=True), 1e-10)
        Z = (W - mu) / sig
    else:
        Z = W
    g = Z @ Z.T
    sq = np.einsum("id,id->i", Z, Z)
    d2 = np.maximum(sq[:, None] + sq[None, :] - 2.0 * g, 0.0)
    idx = np.arange(n)
    d2[np.abs(idx[:, None] - idx[None, :]) < s] = np.inf
    return np.sqrt(d2.min(axis=1))


def ref_topk(prof, k: int, s: int
             ) -> Tuple[List[int], List[float], float]:
    """Reference top-k through the engine's own selector, plus the
    margin from the k-th pick down to the next candidate (how much
    rounding the ranking can absorb before a rank flips)."""
    import numpy as np

    from repro.core.tiles import topk_nonoverlapping
    scored = np.where(np.isfinite(prof), prof, -np.inf)
    pos, vals = topk_nonoverlapping(scored, k + 1, s)
    margin = (float(vals[k - 1] - vals[k])
              if len(vals) > k else math.inf)
    return ([int(p) for p in pos[:k]],
            [float(v) for v in vals[:k]], margin)


# ---------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------
def _observe(cell: dict, got: float, ref: float) -> float:
    """Fold one (f32 result, f64 reference) pair into the cell's
    worst-case stats; returns the relative error."""
    import numpy as np
    diff = abs(got - ref)
    rel = diff / max(abs(ref), 1e-12)
    ulp_unit = float(np.spacing(np.float32(abs(ref)))) or 1e-45
    cell["worst_rel"] = max(cell["worst_rel"], rel)
    cell["worst_ulp"] = max(cell["worst_ulp"], diff / ulp_unit)
    return rel


def _compare_discord(locus: str, res, x, s: int, znorm: bool, k: int,
                     tol: float, findings: List[Finding],
                     cell: dict) -> None:
    """Top-k stability is judged by *regret*, not exact positions:
    overlapping windows make neighboring starts genuine near-ties, so
    two independent float paths may legally swap them.  Each reported
    position is scored by the f64 reference profile at that position —
    drift means the plan picked a window whose *true* discord value
    falls short of the reference's pick at the same rank by more than
    ``tol``.  Reported nnds are then checked against the f64 truth of
    the window actually picked."""
    import numpy as np
    prof = ref_profile(x, s, znorm)
    pos, vals, margin = ref_topk(prof, k, s)
    cell["min_margin"] = min(cell["min_margin"], margin)
    got_pos = [int(p) for p in res.positions]
    got_nnd = [float(v) for v in res.nnds]
    for rank, (gp, gv) in enumerate(zip(got_pos, got_nnd)):
        ref_v = vals[rank] if rank < len(vals) else None
        ok = 0 <= gp < prof.shape[0] and np.isfinite(prof[gp])
        if ref_v is None or not ok:
            findings.append(Finding(
                "shadow", "topk-drift", locus, 0,
                f"rank-{rank} position {gp} has no finite f64 "
                f"reference value (s={s}, ref top-k {pos})"))
            continue
        true_v = float(prof[gp])
        if gp != pos[rank] and \
                abs(true_v - ref_v) / max(abs(ref_v), 1e-12) > tol:
            findings.append(Finding(
                "shadow", "topk-drift", locus, 0,
                f"rank-{rank} position {gp} (true nnd {true_v:.6g}) "
                f"!= f64 reference {pos[rank]} (nnd {ref_v:.6g}, "
                f"margin {margin:.3g}) at s={s} — not a near-tie; "
                "ranking lost to rounding on a conditioning-hostile "
                "series"))
            continue
        rel = _observe(cell, gv, true_v)
        if rel > tol:
            findings.append(Finding(
                "shadow", "nnd-divergence", locus, 0,
                f"nnd {gv!r} vs f64 truth {true_v!r} at position "
                f"{gp} (rel err {rel:.3g} > tol {tol}, s={s})"))


def _compare_pan(locus: str, res, x, ladder: Sequence[int],
                 znorm: bool, k: int, tol: float,
                 findings: List[Finding], cell: dict) -> None:
    import numpy as np

    from repro.core.pan import global_normalized_topk
    profs = {s: ref_profile(x, s, znorm) for s in ladder}
    for s, rung in zip(ladder, res.per_rung):
        _compare_discord(f"{locus}@s={s}", rung, x, s, znorm, k,
                         tol, findings, cell)
    ref_g = global_normalized_topk([profs[s] for s in ladder],
                                   list(ladder), k)
    # same regret gate as per-rung, on the length-normalized score
    # d/sqrt(s) the global ranking actually sorts by
    for rank, got_e in enumerate(res.global_topk):
        gs, gp = int(got_e["s"]), int(got_e["position"])
        gn = float(got_e["nnd"])
        prof = profs.get(gs)
        ok = prof is not None and 0 <= gp < prof.shape[0] \
            and np.isfinite(prof[gp])
        if rank >= len(ref_g) or not ok:
            findings.append(Finding(
                "shadow", "topk-drift", locus, 0,
                f"pan global rank-{rank} entry (s={gs}, pos={gp}) "
                "has no finite f64 reference value"))
            continue
        true_v = float(prof[gp])
        ref_e = ref_g[rank]
        ref_score = float(ref_e["nnd"]) / math.sqrt(int(ref_e["s"]))
        got_score = true_v / math.sqrt(gs)
        if (gs, gp) != (int(ref_e["s"]), int(ref_e["position"])) and \
                abs(got_score - ref_score) \
                / max(abs(ref_score), 1e-12) > tol:
            findings.append(Finding(
                "shadow", "topk-drift", locus, 0,
                f"pan global rank-{rank} (s={gs}, pos={gp}, true "
                f"score {got_score:.6g}) != f64 reference "
                f"(s={int(ref_e['s'])}, pos={int(ref_e['position'])}, "
                f"score {ref_score:.6g}) — not a near-tie"))
            continue
        rel = _observe(cell, gn, true_v)
        if rel > tol:
            findings.append(Finding(
                "shadow", "nnd-divergence", locus, 0,
                f"pan global nnd {gn!r} vs f64 truth {true_v!r} "
                f"(s={gs}, pos={gp}, rel err {rel:.3g} > tol {tol})"))


def _compare_kind(ctx: _ShadowContext, kind: str, res, tol: float,
                  findings: List[Finding], cell: dict,
                  locus: str) -> None:
    k, s, lad, zn = 2, ctx.s, ctx.ladder, ctx.znorm
    if kind in ("profile", "ring", "tail", "tail_ring",
                "qsweep", "qsweep_tail", "qsweep_ring"):
        _compare_discord(locus, res, ctx.x, s, zn, k, tol,
                         findings, cell)
    elif kind in ("batched", "batched_ring"):
        for series, r in zip((ctx.x, ctx.y), res):
            _compare_discord(locus, r, series, s, zn, k, tol,
                             findings, cell)
    elif kind in ("pan", "pan_lb", "pan_ring", "pan_tail",
                  "pan_tail_ring"):
        _compare_pan(locus, res, ctx.x, lad, zn, k, tol,
                     findings, cell)
    elif kind in ("pan_batched", "pan_batched_ring"):
        for series, r in zip((ctx.x, ctx.y), res):
            _compare_pan(locus, r, series, lad, zn, k, tol,
                         findings, cell)
    else:
        raise ValueError(f"unknown plan kind {kind!r} "
                         f"(known: {ALL_KINDS})")


# ---------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------
def _benign_prune(backend: str, kind: str, precision: str
                  ) -> Optional[float]:
    """Prune ratio of one quant kind on the sanitizer's *benign*
    series (well-conditioned: the bound radius is tight and the bound
    pass must actually retire blocks there)."""
    ctx = _Context(backend, True, precision=precision)
    res = ctx._run_raw(kind)
    pr = getattr(res, "extra", {}).get("prune_ratio")
    return None if pr is None else float(pr)


def run_shadow(backends: Iterable[str] = ("numpy", "xla", "pallas"),
               znorms: Iterable[bool] = (True, False),
               kinds: Sequence[str] = ALL_KINDS,
               tol: float = DEFAULT_TOL,
               raw_backends: Iterable[str] = ("xla",),
               precisions: Sequence[str] = ("bf16", "int8"),
               quant_backends: Iterable[str] = ("xla",),
               ) -> Tuple[List[Finding], dict]:
    """Replay every (backend, znorm, kind) cell on the hostile series
    against the f64 reference; returns ``(findings, meta)`` with
    per-cell worst relative error / ULP distance / reference margin
    under ``meta["cells"]`` and a per-kind rollup under
    ``meta["worst_by_kind"]``.

    znorm=True (the serving default, and the numerically hostile
    mode) runs on every requested backend; raw mode re-replays only
    on ``raw_backends`` — its ``‖q‖² + ‖c‖² − 2⟨q,c⟩`` cancellation
    algebra is shared tile code, and the trimmed cells keep the
    whole analyzer inside its CI wall-clock budget.

    The quantized kinds (:data:`QUANT_KINDS`) fan out over
    ``precisions`` (cell locus ``kind:precision[...]``) but replay
    only on ``quant_backends`` — the same budget trim as raw mode;
    per-backend bound soundness is property-tested exhaustively by
    tests/test_quantized.py, so the shadow pass only needs one
    backend to watch the end-to-end regret/prune contract.  They face
    the same 5% regret rule — their refinement contract is bit-exactness, so
    quantization buys them no slack — and additionally replay on the
    benign series, where a zero prune ratio raises ``qsweep-no-prune``
    (a vacuous bound passes every exactness gate while silently
    doubling the sweep cost; the cell records both ratios)."""
    unknown = sorted(set(kinds) - set(ALL_KINDS))
    if unknown:
        raise ValueError(f"unknown plan kinds {unknown} "
                         f"(known: {ALL_KINDS})")
    findings: List[Finding] = []
    checked: List[str] = []
    cells: Dict[str, dict] = {}
    by_kind: Dict[str, dict] = {}
    raw_backends = tuple(raw_backends)
    quant_backends = tuple(quant_backends)
    for backend in backends:
        for znorm in znorms:
            if not znorm and backend not in raw_backends:
                continue
            ctx = _ShadowContext(backend, bool(znorm))
            qctx: Dict[str, _ShadowContext] = {}
            for kind in kinds:
                if not znorm and kind in ZNORM_ONLY_KINDS:
                    continue
                if (kind in QUANT_KINDS
                        and backend not in quant_backends):
                    continue
                if kind in QUANT_KINDS:
                    for p in precisions:
                        if p not in qctx:
                            qctx[p] = _ShadowContext(
                                backend, bool(znorm), precision=p)
                    variants = [(f"{kind}:{p}", qctx[p], p)
                                for p in precisions]
                else:
                    variants = [(kind, ctx, None)]
                for label, c, prec in variants:
                    locus = f"{label}[{backend},znorm={znorm}]"
                    cell = {"worst_rel": 0.0, "worst_ulp": 0.0,
                            "min_margin": math.inf}
                    try:
                        res = c._run_raw(kind)
                        _compare_kind(c, kind, res, tol, findings,
                                      cell, locus)
                    except Exception as e:  # noqa: BLE001
                        findings.append(Finding(
                            "shadow", "kind-error", locus, 0,
                            f"shadow replay failed: "
                            f"{type(e).__name__}: {e}"))
                        continue
                    checked.append(locus)
                    cells[locus] = {
                        "worst_rel": float(cell["worst_rel"]),
                        "worst_ulp": float(cell["worst_ulp"]),
                        "min_margin": (
                            float(cell["min_margin"])
                            if math.isfinite(cell["min_margin"])
                            else None)}
                    if prec is not None:
                        pr = getattr(res, "extra", {}).get(
                            "prune_ratio")
                        cells[locus]["hostile_prune_ratio"] = (
                            None if pr is None else float(pr))
                        if znorm:
                            try:
                                bpr = _benign_prune(backend, kind,
                                                    prec)
                            except Exception as e:  # noqa: BLE001
                                findings.append(Finding(
                                    "shadow", "kind-error", locus, 0,
                                    "benign-series quant replay "
                                    f"failed: {type(e).__name__}: "
                                    f"{e}"))
                                continue
                            cells[locus]["benign_prune_ratio"] = bpr
                            if bpr is None or bpr <= 0.0:
                                findings.append(Finding(
                                    "shadow", "qsweep-no-prune",
                                    locus, 0,
                                    f"{prec} bound pass pruned "
                                    f"nothing on the benign series "
                                    f"(prune_ratio={bpr!r}) — the "
                                    "bound is vacuous; results stay "
                                    "exact but the quantized sweep "
                                    "degenerates into a 2x-cost "
                                    "exact sweep"))
                    agg = by_kind.setdefault(
                        kind, {"worst_rel": 0.0, "worst_ulp": 0.0,
                               "min_margin": None})
                    agg["worst_rel"] = max(agg["worst_rel"],
                                           cells[locus]["worst_rel"])
                    agg["worst_ulp"] = max(agg["worst_ulp"],
                                           cells[locus]["worst_ulp"])
                    m = cells[locus]["min_margin"]
                    if m is not None:
                        agg["min_margin"] = (
                            m if agg["min_margin"] is None
                            else min(agg["min_margin"], m))
    meta = {"tol": float(tol), "checked": checked, "cells": cells,
            "worst_by_kind": by_kind}
    return findings, meta
