"""Pluggable distance-tile backends — the single home of Eq. (3).

Every search strategy in the repo (HST-JAX verification sweeps, the
distributed ring, the matrix-profile baseline, the batched multi-series
front door) reduces to the same hot spot: a (Bq x Bc) tile of squared
z-normalized distances in the scalar-product form

    d2(k, l) = 2 s (1 - (k.l - s mu_k mu_l) / (s sigma_k sigma_l))

with the self-match band and padding lanes masked to +inf.  This module
is the registry of interchangeable implementations of that tile:

  * ``xla``    — jnp dot_general + rank-1 correction; the portable
                 default (CPU/GPU, and perfectly respectable on TPU).
  * ``pallas`` — MXU tile kernel (this file) for gathered window
                 blocks; the series-chunk window-tile variants live in
                 ``kernels/mpblock`` and are dispatched by the engine
                 (``core/tiles.TileEngine``) for contiguous sweeps.
  * ``numpy``  — pure-NumPy host reference, routed through
                 ``jax.pure_callback`` so it stays usable inside jitted
                 search loops.  Ground truth for parity tests.

Backend selection order (``resolve_backend``):
  explicit argument > ``REPRO_TILE_BACKEND`` env var > auto-detect
  (``pallas`` on TPU, ``xla`` elsewhere — the ``default_interpret``
  convention).

A backend is a callable

    fn(qwin, qmu, qsig, qid, cwin, cmu, csig, cid, *, s, n_valid) -> d2

taking f32 window blocks (Bq, s)/(Bc, s), their per-window stats, and
their *global* window ids (i32; negative or >= n_valid means padding),
returning the masked (Bq, Bc) f32 d2 tile.  Register new hardware with
``@register_backend("name")``.

The registry also carries a second, smaller primitive per backend: the
**raw dot tile**

    fn(q, c) -> dots            # (Bq, w) x (Bc, w) -> (Bq, Bc) f32

with no stats, masking or Eq. (3) arithmetic.  It exists for the
pan-length plan family (``core/pan.py``), whose VALMOD-style
incremental sweep carries the QT inner products across window lengths
and therefore needs bare scalar products at arbitrary widths (the full
base width once, then each ladder step's small extension).  Every pan
sweep shape rides it: the full ladder plans, the ``PanStream`` tail
plans (one tail row block against candidate slabs — no masked variant
needed, the exclusion/validity mask is applied downstream on the
carried-QT distances), the LB-abandoning schedule's base/step plans,
and the batched (B, ladder) plans.  Register with
``@register_dot_backend("name")``; a backend without a registered dot
tile falls back to the ``xla`` implementation (exact — it is the same
contraction, just not hand-placed).

The third primitive is the **bound dot tile** of the quantized-sweep
plane (``SearchSpec(precision="bf16"|"int8")``, docs/cps.md):

    fn(q, c, *, precision, sq=None, sc=None) -> dots_low

a *reduced-precision* approximation of the f32 dot tile — bf16-rounded
inputs contracted with ``preferred_element_type=f32`` (xla / pallas
MXU), a per-row-scaled int8 variant accumulated in exact int32, or a
host NumPy emulation of the same roundings.  It is always paired with
:func:`bound_dot_radius`, the rigorously derived error radius ``rad``
such that ``|dots_low - dots_f32| <= rad`` for the f32 tile the exact
plans would compute on the same inputs (derivation in
docs/ARCHITECTURE.md §"Quantized bound pass").  The engine turns
``dots_low ± rad`` into d² bounds through the same monotone Eq. (3)
pipeline the exact tiles use, prunes lanes whose upper bound cannot
enter the top-k, and refines survivors in f32 — bit-identical results,
fewer full-precision lanes.  Register with
``@register_bound_backend("name")``; unregistered backends fall back
to the ``xla`` bound tile.
"""
from __future__ import annotations

import functools
import os
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from .common import (F32_DOT, default_interpret, exclusion_mask,
                     exclusion_mask_cols, pad_block_operands, pad_to,
                     znorm_d2_cols, znorm_d2_formula)

TileBackendFn = Callable[..., jnp.ndarray]

_REGISTRY: Dict[str, TileBackendFn] = {}
_DOT_REGISTRY: Dict[str, TileBackendFn] = {}
_BOUND_REGISTRY: Dict[str, TileBackendFn] = {}
_ALIASES = {"jnp": "xla", "ref": "numpy", "np": "numpy"}

ENV_VAR = "REPRO_TILE_BACKEND"

# On an effectively single-threaded host the XLA CPU client's async
# dispatch pool has one thread, and a host callback (the ``numpy``
# reference backend routes every tile through ``jax.pure_callback``)
# can deadlock against the program that is waiting on it: the callback
# blocks re-entering Python while the dispatch thread holds the slot
# its result is needed to release.  Synchronous dispatch runs the
# program on the caller's thread and sidesteps the cycle; on a one-CPU
# box there is no dispatch latency to hide anyway.  Set
# ``REPRO_KEEP_ASYNC_DISPATCH=1`` to opt out of the guard.
if ((os.cpu_count() or 1) <= 1
        and not os.environ.get("REPRO_KEEP_ASYNC_DISPATCH")):
    jax.config.update("jax_cpu_enable_async_dispatch", False)


#: IR-level traits per backend, consumed by the jaxpr auditor
#: (``repro.analysis.irlint``).  ``host_callback`` marks backends
#: whose tiles legitimately stage a ``jax.pure_callback`` into the
#: plan body (so the auditor's callback-containment rule knows where
#: callbacks are allowed); ``dot_model`` says how the backend's dot
#: sites relate to the static FLOP/lane model of docs/cps.md:
#: ``"exact"`` — every ``dot_general`` in the jaxpr maps 1:1 onto
#: accounted tile lanes; ``"mxu-padded"`` — dots live inside
#: ``pallas_call`` kernels padded to MXU tile geometry (128-lane
#: widths), so IR-level FLOPs over-count the accounted lanes by the
#: padding and the lane cross-audit does not apply; ``"host"`` — the
#: contraction happens in host NumPy behind the callback and never
#: appears in the IR at all.
BACKEND_TRAITS: Dict[str, Dict[str, object]] = {
    "xla": {"host_callback": False, "dot_model": "exact"},
    "pallas": {"host_callback": False, "dot_model": "mxu-padded"},
    "numpy": {"host_callback": True, "dot_model": "host"},
}


def backend_traits(name: str) -> Dict[str, object]:
    """IR traits of backend ``name`` (aliases resolved).  Unregistered
    custom backends default to conservative traits (no callbacks
    expected, no exact dot model claimed)."""
    name = _ALIASES.get(name, name)
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown tile backend {name!r}; available: "
            f"{available_backends()}")
    return dict(BACKEND_TRAITS.get(
        name, {"host_callback": False, "dot_model": "unknown"}))


def register_backend(name: str):
    """Decorator: add a tile backend under ``name``."""
    def deco(fn: TileBackendFn) -> TileBackendFn:
        _REGISTRY[name] = fn
        return fn
    return deco


def register_dot_backend(name: str):
    """Decorator: add a raw dot-tile backend under ``name``."""
    def deco(fn: TileBackendFn) -> TileBackendFn:
        _DOT_REGISTRY[name] = fn
        return fn
    return deco


def get_dot_backend(name: str) -> TileBackendFn:
    """Raw dot-tile implementation for ``name`` (xla fallback)."""
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown tile backend {name!r}; available: "
            f"{available_backends()}")
    return _DOT_REGISTRY.get(name, _DOT_REGISTRY["xla"])


def register_bound_backend(name: str):
    """Decorator: add a reduced-precision bound dot tile under
    ``name``."""
    def deco(fn: TileBackendFn) -> TileBackendFn:
        _BOUND_REGISTRY[name] = fn
        return fn
    return deco


def get_bound_backend(name: str) -> TileBackendFn:
    """Bound dot-tile implementation for ``name`` (xla fallback)."""
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown tile backend {name!r}; available: "
            f"{available_backends()}")
    return _BOUND_REGISTRY.get(name, _BOUND_REGISTRY["xla"])


def available_backends() -> tuple:
    return tuple(sorted(_REGISTRY))


def get_backend(name: str) -> TileBackendFn:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown tile backend {name!r}; available: "
            f"{available_backends()}") from None


def resolve_backend(name: str | None = None) -> str:
    """explicit arg > REPRO_TILE_BACKEND env > hardware auto-detect."""
    if name is None:
        name = os.environ.get(ENV_VAR) or None
    if name is None:
        name = "pallas" if jax.default_backend() == "tpu" else "xla"
    name = _ALIASES.get(name, name)
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown tile backend {name!r}; available: "
            f"{available_backends()}")
    return name


# ----------------------------------------------------------------------
# xla backend
# ----------------------------------------------------------------------
@register_backend("xla")
def tile_d2_xla(qwin, qmu, qsig, qid, cwin, cmu, csig, cid, *,
                s: int, n_valid: int):
    dots = lax.dot_general(qwin, cwin, (((1,), (1,)), ((), ())),
                           precision=F32_DOT,
                           preferred_element_type=jnp.float32)
    d2 = znorm_d2_formula(dots, s, qmu, qsig, cmu, csig)
    return jnp.where(exclusion_mask(qid, cid, s, n_valid), jnp.inf, d2)


# ----------------------------------------------------------------------
# numpy backend (host reference behind pure_callback)
# ----------------------------------------------------------------------
def _tile_d2_np(qwin, qmu, qsig, qid, cwin, cmu, csig, cid,
                s: int, n_valid: int) -> np.ndarray:
    """The reference implementation — deliberately an *independent*
    NumPy transcription of Eq. (3) (not a call into znorm_d2_formula),
    so backend-parity tests validate the shared formula against it."""
    dots = np.asarray(qwin, np.float32) @ np.asarray(cwin, np.float32).T
    corr = (dots - s * np.outer(qmu, cmu)) / (s * np.outer(qsig, csig))
    d2 = np.maximum(2.0 * s * (1.0 - corr), 0.0)
    qi = np.asarray(qid)[:, None]
    cj = np.asarray(cid)[None, :]
    bad = ((np.abs(qi - cj) < s) | (qi < 0) | (qi >= n_valid)
           | (cj < 0) | (cj >= n_valid))
    return np.where(bad, np.inf, d2).astype(np.float32)


@register_backend("numpy")
def tile_d2_numpy(qwin, qmu, qsig, qid, cwin, cmu, csig, cid, *,
                  s: int, n_valid: int):
    out = jax.ShapeDtypeStruct((qwin.shape[0], cwin.shape[0]),
                               jnp.float32)
    fn = functools.partial(_tile_d2_np, s=s, n_valid=n_valid)
    return jax.pure_callback(fn, out, qwin, qmu, qsig, qid,
                             cwin, cmu, csig, cid)


# ----------------------------------------------------------------------
# pallas backend (gathered window blocks; one resident MXU tile)
# ----------------------------------------------------------------------
def _tile_d2_kernel(q_ref, qmu_ref, qsig_ref, qid_ref,
                    c_ref, cmu_ref, csig_ref, cid_ref,
                    d2_ref, *, s: int, n_valid: int):
    dots = lax.dot_general(q_ref[...], c_ref[...],
                           (((1,), (1,)), ((), ())), precision=F32_DOT,
                           preferred_element_type=jnp.float32)
    d2 = znorm_d2_cols(dots, s, qmu_ref[...], qsig_ref[...],
                       cmu_ref[...], csig_ref[...])
    bad = exclusion_mask_cols(qid_ref[...], cid_ref[...], s, n_valid)
    d2_ref[...] = jnp.where(bad, float("inf"), d2)


BLOCK_Q = 128    # VMEM-resident query rows per grid step
BLOCK_C = 128    # candidate columns streamed per grid step


@register_backend("pallas")
def tile_d2_pallas(qwin, qmu, qsig, qid, cwin, cmu, csig, cid, *,
                   s: int, n_valid: int, interpret: bool | None = None):
    """Gridded MXU tile kernel: arbitrary (Bq, Bc) inputs stream
    through VMEM in (BLOCK_Q x BLOCK_C) steps, so per-step residency
    is bounded no matter how large the caller's blocks are (the
    distributed ring hands over whole per-shard slabs).  Query stats
    and ids enter as (Bq, 1) columns and candidate ones as (1, Bc)
    rows: Mosaic rejects 1-D per-window blocks."""
    if interpret is None:
        interpret = default_interpret()
    bq, bc = qwin.shape[0], cwin.shape[0]
    rows_q = BLOCK_Q if bq > BLOCK_Q else 8
    qwin, qmu, qsig, qid = pad_block_operands(qwin, qmu, qsig, qid,
                                              rows=rows_q, lanes=128)
    cwin, cmu, csig, cid = pad_block_operands(cwin, cmu, csig, cid,
                                              rows=BLOCK_C, lanes=128)
    bq_p, s_p = qwin.shape
    bc_p = cwin.shape[0]
    blk_q = min(bq_p, BLOCK_Q)
    grid = (bq_p // blk_q, bc_p // BLOCK_C)
    kernel = functools.partial(_tile_d2_kernel, s=s, n_valid=n_valid)
    q_col = pl.BlockSpec((blk_q, 1), lambda i, j: (i, 0))
    c_row = pl.BlockSpec((1, BLOCK_C), lambda i, j: (0, j))
    d2 = pl.pallas_call(
        kernel,
        name="tile_d2",
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk_q, s_p), lambda i, j: (i, 0)),
            q_col, q_col, q_col,
            pl.BlockSpec((BLOCK_C, s_p), lambda i, j: (j, 0)),
            c_row, c_row, c_row,
        ],
        out_specs=pl.BlockSpec((blk_q, BLOCK_C), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bq_p, bc_p), jnp.float32),
        interpret=interpret,
    )(qwin, qmu[:, None], qsig[:, None], qid[:, None],
      cwin, cmu[None, :], csig[None, :], cid[None, :])
    return d2[:bq, :bc]


# ----------------------------------------------------------------------
# raw dot-tile backends (pan-length incremental QT)
# ----------------------------------------------------------------------
@register_dot_backend("xla")
def dot_tile_xla(q, c):
    return lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                           precision=F32_DOT,
                           preferred_element_type=jnp.float32)


def _dot_tile_np(q, c) -> np.ndarray:
    return (np.asarray(q, np.float32)
            @ np.asarray(c, np.float32).T).astype(np.float32)


@register_dot_backend("numpy")
def dot_tile_numpy(q, c):
    out = jax.ShapeDtypeStruct((q.shape[0], c.shape[0]), jnp.float32)
    return jax.pure_callback(_dot_tile_np, out, q, c)


def _dot_tile_kernel(q_ref, c_ref, o_ref):
    o_ref[...] = lax.dot_general(q_ref[...], c_ref[...],
                                 (((1,), (1,)), ((), ())),
                                 precision=F32_DOT,
                                 preferred_element_type=jnp.float32)


@register_dot_backend("pallas")
def dot_tile_pallas(q, c, *, interpret: bool | None = None):
    """Gridded MXU dot tile.  Widths pad to the 128-lane tile with
    zeros (dot products unchanged), rows to MXU sublanes; padded rows
    are sliced off, so the tile is exact at any (Bq, Bc, w)."""
    if interpret is None:
        interpret = default_interpret()
    bq, bc = q.shape[0], c.shape[0]
    rows_q = BLOCK_Q if bq > BLOCK_Q else 8
    q = pad_to(pad_to(q, 128, axis=1), rows_q, axis=0)
    c = pad_to(pad_to(c, 128, axis=1), BLOCK_C, axis=0)
    bq_p, w_p = q.shape
    bc_p = c.shape[0]
    blk_q = min(bq_p, BLOCK_Q)
    dots = pl.pallas_call(
        _dot_tile_kernel,
        name="dot_tile",
        grid=(bq_p // blk_q, bc_p // BLOCK_C),
        in_specs=[
            pl.BlockSpec((blk_q, w_p), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_C, w_p), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((blk_q, BLOCK_C), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bq_p, bc_p), jnp.float32),
        interpret=interpret,
    )(q, c)
    return dots[:bq, :bc]


# ----------------------------------------------------------------------
# bound dot-tile backends (quantized sweep: bf16/int8 bound pass)
# ----------------------------------------------------------------------
#: per-row int8 scale floor — keeps all-zero (and denormal-flushed)
#: windows from dividing by zero; a floored row quantizes to all-zero
#: int8, and the radius formula (which uses the same floored scale)
#: stays sound
I8_SCALE_FLOOR = 1e-30


def quant_scales(win) -> jnp.ndarray:
    """Per-row symmetric int8 scale for a window block: ``max|row| /
    127`` (floored), so ``round(row / scale)`` never clips a live
    value."""
    mx = jnp.max(jnp.abs(win), axis=1)
    return jnp.maximum(mx, I8_SCALE_FLOOR) / 127.0


def bound_dot_radius(precision: str, nq, nc, w: int, sq=None, sc=None):
    """Error radius ``rad`` with ``|dots_low - dots_f32| <= rad``.

    ``nq``/``nc`` are the f32 L2 norms of the query/candidate window
    rows, ``w`` the (static) contraction width, ``sq``/``sc`` the int8
    scales from :func:`quant_scales`.  Derivation and the slack-factor
    accounting (input rounding + both sides' f32 accumulation +
    norm/formula evaluation rounding + an absolute denormal term) live
    in docs/ARCHITECTURE.md §"Quantized bound pass"; the soundness
    property ``d2_lo <= d2_f32 <= d2_hi`` is enforced per backend x
    znorm mode by tests/test_quantized.py.
    """
    w = int(w)
    outer = nq[:, None] * nc[None, :]
    absterm = (w * 2.0 ** -120) * (1.0 + nq[:, None] + nc[None, :])
    if precision == "bf16":
        # 2e + e^2 input rounding (e = 2^-8), ~3 gamma_w for the two
        # f32 accumulations + cross-backend formula ordering, inflated
        # for the f32 evaluation of the norms and of this very formula
        coef = ((2.0 ** -7 + 2.0 ** -16 + 3.0 * w * 2.0 ** -24)
                * (1.0 + w * 2.0 ** -20))
        return coef * outer + absterm
    if precision != "int8":
        raise ValueError(f"no bound radius for precision={precision!r}")
    rw = float(np.sqrt(w))
    nq_hat = nq + 0.5 * rw * sq          # ||dequantized row|| bound
    core = 0.5 * rw * (nq_hat[:, None] * sc[None, :]
                       + sq[:, None] * nc[None, :])
    acc = (4.0 * w * 2.0 ** -24) * outer
    return core * (1.0 + 2.0 ** -12 + w * 2.0 ** -20) + acc + absterm


def _quantize_i8(x, scale):
    return jnp.clip(jnp.round(x / scale[:, None]),
                    -127.0, 127.0).astype(jnp.int8)


@register_bound_backend("xla")
def bound_dot_xla(q, c, *, precision: str, sq=None, sc=None):
    if precision == "bf16":
        return lax.dot_general(q.astype(jnp.bfloat16),
                               c.astype(jnp.bfloat16),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    # int8: exact int32 accumulation (127^2 * w < 2^31 for any sane w),
    # error enters only through quantization + the f32 dequant scaling
    acc = lax.dot_general(_quantize_i8(q, sq), _quantize_i8(c, sc),
                          (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sq[:, None] * sc[None, :]


def _round_bf16_np(a: np.ndarray) -> np.ndarray:
    """Round-to-nearest-even to bf16, returned as f32 (bit-level
    emulation of XLA's convert_element_type)."""
    bits = np.ascontiguousarray(np.asarray(a, np.float32)).view(
        np.uint32)
    rounded = (bits + np.uint32(0x7FFF)
               + ((bits >> np.uint32(16)) & np.uint32(1))
               ) & np.uint32(0xFFFF0000)
    return rounded.view(np.float32)


def _bound_bf16_np(q, c) -> np.ndarray:
    return (_round_bf16_np(q)
            @ _round_bf16_np(c).T).astype(np.float32)


def _bound_i8_np(q, c, sq, sc) -> np.ndarray:
    q32 = np.asarray(q, np.float32)
    c32 = np.asarray(c, np.float32)
    sq = np.asarray(sq, np.float32)
    sc = np.asarray(sc, np.float32)
    # nan_to_num keeps poisoned padding lanes (sanitizer canaries) out
    # of the float->int cast, which would warn; live lanes are finite
    # and unchanged
    qi = np.nan_to_num(np.clip(np.rint(q32 / sq[:, None]), -127, 127),
                       nan=0.0).astype(np.int32)
    ci = np.nan_to_num(np.clip(np.rint(c32 / sc[:, None]), -127, 127),
                       nan=0.0).astype(np.int32)
    dots = (qi @ ci.T).astype(np.float32)
    return dots * sq[:, None] * sc[None, :]


@register_bound_backend("numpy")
def bound_dot_numpy(q, c, *, precision: str, sq=None, sc=None):
    out = jax.ShapeDtypeStruct((q.shape[0], c.shape[0]), jnp.float32)
    if precision == "bf16":
        return jax.pure_callback(_bound_bf16_np, out, q, c)
    return jax.pure_callback(_bound_i8_np, out, q, c, sq, sc)


def _bound_dot_kernel_bf16(q_ref, c_ref, o_ref):
    o_ref[...] = lax.dot_general(q_ref[...].astype(jnp.bfloat16),
                                 c_ref[...].astype(jnp.bfloat16),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)


@register_bound_backend("pallas")
def bound_dot_pallas(q, c, *, precision: str, sq=None, sc=None,
                     interpret: bool | None = None):
    """bf16 MXU bound tile — inputs round to bf16 *inside* the kernel
    so VMEM traffic stays f32-aligned with the exact tiles.  The int8
    variant rides the xla lowering (int8 MXU tiling is a separate
    project; the bound contract only cares about the rounding model,
    which is identical)."""
    if precision != "bf16":
        return bound_dot_xla(q, c, precision=precision, sq=sq, sc=sc)
    if interpret is None:
        interpret = default_interpret()
    bq, bc = q.shape[0], c.shape[0]
    rows_q = BLOCK_Q if bq > BLOCK_Q else 8
    q = pad_to(pad_to(q, 128, axis=1), rows_q, axis=0)
    c = pad_to(pad_to(c, 128, axis=1), BLOCK_C, axis=0)
    bq_p, w_p = q.shape
    bc_p = c.shape[0]
    blk_q = min(bq_p, BLOCK_Q)
    dots = pl.pallas_call(
        _bound_dot_kernel_bf16,
        name="bound_dot",
        grid=(bq_p // blk_q, bc_p // BLOCK_C),
        in_specs=[
            pl.BlockSpec((blk_q, w_p), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_C, w_p), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((blk_q, BLOCK_C), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bq_p, bc_p), jnp.float32),
        interpret=interpret,
    )(q, c)
    return dots[:bq, :bc]
