"""Padding-poison sanitizer: pad lanes must never influence results.

Every plan-cached path pads its series to a power-of-two length bucket
and relies on id masking (``TileEngine._mask_ids`` -> id −1 ->
exclusion mask -> +inf) to keep the pad lanes out of the result.  The
PR 4 tiny-series geometry bug lived exactly there: a pad lane that
leaked into a min.  This pass makes the contract falsifiable — it
reruns every plan kind with the pad region filled with NaN / ±inf
canaries (via :data:`repro.core.engine.PAD_FILL`) and asserts the
results are **bit-identical** to the benign zero fill.  NaN is the
sharpest canary: one unmasked pad lane turns a min/argmin NaN, so any
reliance on "pad zeros are harmless" fails loudly instead of silently
biasing a top-k.

Plan-kind coverage (``ALL_KINDS``) spans the whole session surface:
every kind of the engine's ``PLAN_KINDS`` table is reached through one
search entry point, local (``LOCAL_KINDS``) or mesh-sharded
(``SHARDED_KINDS``) as the table says — profile / batched /
stream-tail / pan ladder / pan LB-abandon / pan-stream / pan-batched /
quantized sweep (bound pass + exact refinement, docs/cps.md).
Raw (``znorm=False``) skips the table's ``znorm_only`` kinds (spec
validation rejects raw ``ring``, hence also ``qsweep_ring``; a raw
sharded stream falls back to the local tail plan, already covered by
``tail``).  The quantized kinds are the
sharpest cells here: a pad lane that leaks into the *bound* pass
doesn't just bias a min — it can wrongly prune a block, so the
bit-identical bar doubles as a prune-soundness probe.

Importing this module imports the engine (and jax) — keep it off
the lint-only path.
"""
from __future__ import annotations

import math
import warnings
from contextlib import contextmanager
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from repro.core.engine import PLAN_KINDS

from .irlint import ZNORM_ONLY_KINDS
from .report import Finding

__all__ = ["ALL_KINDS", "LOCAL_KINDS", "SHARDED_KINDS", "CANARIES",
           "pad_fill", "run_sanitizer", "selfcheck"]

#: plan kinds reached through another kind's entry point: the f32
#: refinements run inside their bound pass's search, and the LB
#: schedule ``pan_lb`` sweeps ``pan_base``/``pan_step``.  The serve
#: plane's ``mb`` kinds have no entry here: their lanes are
#: bit-identical to the local kinds (tests/test_serve_property.py).
_ENTRY = {"qsweep_refine": "qsweep", "qsweep_tail_refine": "qsweep_tail",
          "pan_base": "pan_lb", "pan_step": "pan_lb"}


def _entries(sharded: bool) -> Tuple[str, ...]:
    """Entry points of the table's local or sharded kinds."""
    out: List[str] = []
    for kind, pk in PLAN_KINDS.items():
        entry = _ENTRY.get(kind, kind)
        if (pk.sharded == sharded and pk.layout != "mb"
                and entry not in out):
            out.append(entry)
    return tuple(out)


LOCAL_KINDS = _entries(False)
SHARDED_KINDS = _entries(True)
ALL_KINDS = LOCAL_KINDS + SHARDED_KINDS

CANARIES = (("nan", float("nan")), ("+inf", math.inf),
            ("-inf", -math.inf))

_S = 24
_LADDER = (16, 24, 32)
_BLOCK = 32
_LEN = 90          # buckets to 256: most of every tile row is padding
_TAIL_AT = 70


@contextmanager
def pad_fill(value: float):
    """Temporarily poison the engine's host-side bucket padding.

    Canary NaNs legitimately flow through the dot tiles before the id
    mask retires them, so numpy's invalid-value warnings are muted for
    the duration — a real leak shows up as a changed result, not as a
    warning."""
    import numpy as np

    from repro.core import engine as engine_mod
    prev = engine_mod.PAD_FILL
    engine_mod.PAD_FILL = float(value)
    try:
        with np.errstate(all="ignore"), warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            yield
    finally:
        engine_mod.PAD_FILL = prev


def _norm(v):
    """Python-native scalar (dict values in global_topk sigs)."""
    if hasattr(v, "item"):
        return v.item()
    return v


def _result_sig(res) -> tuple:
    """Comparable signature of a Discord/Pan result (or list of them):
    positions, neighbor distances and the pan global top-k, compared
    exactly — the sanitizer's bar is *bit-identical*, not allclose."""
    if isinstance(res, (list, tuple)):
        return tuple(_result_sig(r) for r in res)
    if hasattr(res, "per_rung"):          # PanResult
        return ("pan",
                tuple(_result_sig(r) for r in res.per_rung),
                tuple(tuple(sorted((k, _norm(v)) for k, v in g.items()
                                   if k in ("s", "position", "nnd",
                                            "normalized")))
                      for g in res.global_topk))
    return ("discord", tuple(int(p) for p in res.positions),
            tuple(float(v) for v in res.nnds))


class _Context:
    """Lazily-built engines for one (backend, znorm) cell, reused
    across pad fills so each kind compiles once and replays poisoned."""

    def __init__(self, backend: str, znorm: bool, *,
                 s: int = _S, ladder: Sequence[int] = _LADDER,
                 block: int = _BLOCK, ndev: Optional[int] = None,
                 length: int = _LEN, tail_at: int = _TAIL_AT,
                 precision: str = "bf16"):
        import numpy as np
        self.backend, self.znorm = backend, znorm
        self.s, self.ladder = int(s), tuple(int(v) for v in ladder)
        self.block, self._ndev = int(block), ndev
        self.precision = precision
        t = np.arange(float(length))
        self.x = np.sin(0.31 * t) + 0.23 * np.cos(0.11 * t)
        self.x[int(0.6 * length)] += 2.5        # a planted discord
        self.y = np.cos(0.27 * t) - 0.17 * np.sin(0.13 * t)
        self.tail_at = int(tail_at)
        self._engines: Dict[str, object] = {}

    @property
    def ndev(self) -> int:
        if self._ndev is None:
            import jax
            self._ndev = jax.local_device_count()
        return self._ndev

    def _engine(self, key: str):
        if key in self._engines:
            return self._engines[key]
        from repro.core.engine import DiscordEngine
        from repro.core.spec import SearchSpec
        base = dict(k=2, znorm=self.znorm, backend=self.backend,
                    block=self.block)
        specs = {
            "mp": dict(s=self.s, method="matrix_profile"),
            "mp_ndev": dict(s=self.s, method="matrix_profile",
                            ndev=self.ndev),
            "ring": dict(s=self.s, method="ring", ndev=self.ndev),
            "pan": dict(s=self.ladder, method="matrix_profile"),
            "pan_ndev": dict(s=self.ladder, method="matrix_profile",
                             ndev=self.ndev),
            "qsweep": dict(s=self.s, method="matrix_profile",
                           precision=self.precision),
            "qsweep_ndev": dict(s=self.s, method="ring",
                                ndev=self.ndev,
                                precision=self.precision),
        }
        eng = DiscordEngine(SearchSpec(**{**base, **specs[key]}))
        self._engines[key] = eng
        return eng

    # one driver per plan kind; each returns the raw result object(s)
    def _run_raw(self, kind: str):
        import numpy as np
        x, y, at = self.x, self.y, self.tail_at
        stack = np.stack([x, y])
        if kind == "profile":
            return self._engine("mp").search(x)
        if kind == "batched":
            return self._engine("mp").search_batched(stack)
        if kind == "tail":
            st = self._engine("mp").open_stream(s=self.s,
                                                history=x[:at])
            return st.append(x[at:]).discords()
        if kind == "pan":
            return self._engine("pan").search_pan(x)
        if kind == "pan_lb":
            return self._engine("pan").search_pan(x, schedule="lb")
        if kind == "pan_tail":
            st = self._engine("pan").open_stream(history=x[:at])
            return st.append(x[at:]).discords()
        if kind == "pan_batched":
            return self._engine("pan").search_batched(stack)
        if kind == "ring":
            return self._engine("ring").search(x)
        if kind == "batched_ring":
            return self._engine("mp_ndev").search_batched(stack)
        if kind == "tail_ring":
            st = self._engine("mp_ndev").open_stream(s=self.s,
                                                     history=x[:at])
            return st.append(x[at:]).discords()
        if kind == "pan_ring":
            return self._engine("pan_ndev").search_pan(x)
        if kind == "pan_tail_ring":
            st = self._engine("pan_ndev").open_stream(history=x[:at])
            return st.append(x[at:]).discords()
        if kind == "pan_batched_ring":
            return self._engine("pan_ndev").search_batched(stack)
        if kind == "qsweep":
            return self._engine("qsweep").search(x)
        if kind == "qsweep_tail":
            st = self._engine("qsweep").open_stream(s=self.s,
                                                    history=x[:at])
            return st.append(x[at:]).discords()
        if kind == "qsweep_ring":
            return self._engine("qsweep_ndev").search(x)
        raise ValueError(f"unknown plan kind {kind!r} "
                         f"(known: {ALL_KINDS})")

    def run(self, kind: str) -> tuple:
        return _result_sig(self._run_raw(kind))


def _sanitize_ctx(ctx: _Context, kinds: Sequence[str],
                  canaries=CANARIES
                  ) -> Tuple[List[Finding], List[str]]:
    """Benign baseline vs each canary fill, per kind, one context."""
    findings: List[Finding] = []
    checked: List[str] = []
    where = f"[{ctx.backend},znorm={ctx.znorm}]"
    for kind in kinds:
        if not ctx.znorm and kind in ZNORM_ONLY_KINDS:
            continue
        locus = f"{kind}{where}"
        try:
            with pad_fill(0.0):
                base = ctx.run(kind)
        except Exception as e:      # noqa: BLE001 - findings, not crashes
            findings.append(Finding(
                "sanitize", "kind-error", locus, 0,
                f"benign-padding run failed: {type(e).__name__}: {e}"))
            continue
        for label, value in canaries:
            try:
                with pad_fill(value):
                    poisoned = ctx.run(kind)
            except Exception as e:  # noqa: BLE001
                findings.append(Finding(
                    "sanitize", "poison-crash", locus, 0,
                    f"{label} pad canary crashed the plan: "
                    f"{type(e).__name__}: {e}"))
                continue
            if poisoned != base:
                findings.append(Finding(
                    "sanitize", "poison-leak", locus, 0,
                    f"{label} pad canary changed the result — a pad "
                    "lane (masked id -1) is reaching the min/top-k "
                    f"(benign={base!r} poisoned={poisoned!r})"))
        checked.append(locus)
    return findings, checked


def run_sanitizer(backends: Iterable[str] = ("numpy", "xla", "pallas"),
                  znorms: Iterable[bool] = (True, False),
                  kinds: Sequence[str] = ALL_KINDS,
                  ) -> Tuple[List[Finding], List[str]]:
    """Poison every requested (backend, znorm, kind) cell; returns
    (findings, checked-cell loci).  ``pallas`` auto-interprets off-TPU
    (kernels.pallas_backend.default_interpret)."""
    unknown = sorted(set(kinds) - set(ALL_KINDS))
    if unknown:
        raise ValueError(f"unknown plan kinds {unknown} "
                         f"(known: {ALL_KINDS})")
    findings: List[Finding] = []
    checked: List[str] = []
    for backend in backends:
        for znorm in znorms:
            ctx = _Context(backend, bool(znorm))
            f, c = _sanitize_ctx(ctx, kinds)
            findings.extend(f)
            checked.extend(c)
    return findings, checked


def _kinds_for_spec(spec) -> Tuple[str, ...]:
    """The plan-kind family a user's spec actually exercises."""
    sharded = spec.ndev is not None
    if spec.multi_window:
        if sharded:
            return ("pan_ring", "pan_tail_ring", "pan_batched_ring")
        return ("pan", "pan_lb", "pan_tail", "pan_batched")
    quant = spec.precision != "f32"
    if spec.method == "ring":
        return ("qsweep_ring",) if quant else ("ring",)
    if spec.method == "matrix_profile":
        if quant:
            # the quant stream tail is a local plan even when sharded
            return (("qsweep_ring", "qsweep_tail") if sharded
                    else ("qsweep", "qsweep_tail"))
        if sharded:
            return ("batched_ring", "tail_ring")
        return ("profile", "batched", "tail")
    return ()      # serial / hst_jax / drag: no bucketed plan padding


def selfcheck(spec) -> Tuple[List[Finding], List[str]]:
    """Sanitize the plan kinds *this* spec will run, at its own
    window geometry/backend/znorm, on a small synthetic series —
    ``launch/discord.py --selfcheck`` runs this before a long search."""
    kinds = _kinds_for_spec(spec)
    if not kinds:
        return [], []
    smax = max(spec.windows)
    ladder = spec.windows if spec.multi_window else (spec.s,)
    length = max(_LEN, smax + 48)
    ctx = _Context(spec.backend or "xla", spec.znorm,
                   s=spec.windows[0], ladder=ladder,
                   block=min(spec.block, 64), ndev=spec.ndev,
                   length=length, tail_at=length - 16,
                   precision=spec.precision)
    return _sanitize_ctx(ctx, kinds)
