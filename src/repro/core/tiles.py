"""Unified distance-tile engine — one tile plane for every search.

The paper's whole cost model collapses onto Eq. (3) z-normalized
distance evaluations; this module is the single implementation of that
hot spot that all search strategies share:

  * ``hst_jax``            — batched verification sweeps (``sweep``)
  * ``distributed``        — ring matrix profile / DRAG (``tile_d2``)
  * ``matrix_profile``     — SCAMP-class baseline (``profile``)
  * ``find_discords_batched`` — multi-series serving plane
                              (``batched_profile``)

The actual tile math lives behind the pluggable backend registry in
``repro.kernels.registry`` (``numpy`` | ``xla`` | ``pallas``); this
module owns the *data plane*: window gathering, contiguous Hankel
blocks, padding, stats, min/argmin reductions, and top-k extraction.

Data model: a ``TileBlock`` is a block of windows with per-window stats
and *global* window ids (ids outside [0, n_valid) are padding and come
back masked to +inf).  A ``TileEngine`` wraps one series and hands out
blocks whose padding invariants match what the backends expect.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels.common import ceil_div, default_interpret, sliding_stats_jnp
from ..kernels.registry import (available_backends, get_backend,
                                register_backend, resolve_backend)

__all__ = [
    "TileBlock", "TileMins", "TileEngine", "tile_d2", "tile_mins",
    "set_row_mins", "pair_d2", "exact_pair_d2", "topk_nonoverlapping",
    "batched_profile", "self_join_grid", "self_join_lanes",
    "resolve_backend", "available_backends", "register_backend",
]


class TileBlock(NamedTuple):
    """A block of windows + stats + global ids (padding ids < 0)."""
    win: jnp.ndarray    # (B, s) f32
    mu: jnp.ndarray     # (B,)   f32
    sig: jnp.ndarray    # (B,)   f32
    ids: jnp.ndarray    # (B,)   i32; <0 or >= n_valid -> masked


class TileMins(NamedTuple):
    row_min: jnp.ndarray   # (Bq,) min d2 per query row
    row_arg: jnp.ndarray   # (Bq,) candidate id realizing it
    col_min: jnp.ndarray   # (Bc,) min d2 per candidate column
    col_arg: jnp.ndarray   # (Bc,) query id realizing it


def tile_d2(q: TileBlock, c: TileBlock, *, s: int, n_valid: int,
            backend: Optional[str] = None) -> jnp.ndarray:
    """Masked (Bq, Bc) squared-distance tile via the selected backend."""
    fn = get_backend(resolve_backend(backend))
    return fn(q.win, q.mu, q.sig, q.ids, c.win, c.mu, c.sig, c.ids,
              s=s, n_valid=n_valid)


def tile_mins(d2: jnp.ndarray, qids, cids) -> TileMins:
    """Row/col (min, argmin) of a d2 tile, in global-id space."""
    return TileMins(
        row_min=jnp.min(d2, axis=1),
        row_arg=cids[jnp.argmin(d2, axis=1)],
        col_min=jnp.min(d2, axis=0),
        col_arg=qids[jnp.argmin(d2, axis=0)],
    )


def set_row_mins(q: tuple, c: tuple, *, s: int, n_valid, block: int,
                 backend: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row (min d2, neighbor id) of window set ``q`` against window set
    ``c``, both slices of :meth:`TileEngine.window_set` built on the
    (resolved) ``backend``.  Eq. (3) distances only."""
    if backend == "pallas":
        from ..kernels.mpblock.kernel import mp_block_pallas
        return mp_block_pallas(*q, *c, s=s, n_valid=n_valid, block=block,
                               interpret=default_interpret())
    m = tile_mins(tile_d2(TileBlock(*q), TileBlock(*c), s=s,
                          n_valid=n_valid, backend=backend), q[3], c[3])
    return m.row_min, m.row_arg


def self_join_grid(nb: int, block: int, backend: str,
                   znorm: bool = True) -> str:
    """Tile grid :meth:`TileEngine.block_rows` sweeps for the self-join
    of ``nb`` window blocks on the (resolved) ``backend``:
    ``"triangle"`` (each window pair once) on ``pallas`` with Eq. (3)
    when its step table and column accumulator fit the core
    (``kernels.mpblock.kernel.tri_fits``: up to 571 blocks), else
    ``"square"``."""
    from ..kernels.mpblock import kernel as mpk
    if backend == "pallas" and znorm and mpk.tri_fits(nb, block):
        return "triangle"
    return "square"


def self_join_lanes(nb: int, block: int, backend: str,
                    znorm: bool = True, live=None) -> int:
    """Tile lanes of one whole self-join of ``nb`` window blocks, of
    which ``live`` (default all) hold a window, as
    :meth:`TileEngine.block_rows` sweeps it: on ``pallas`` with Eq. (3)
    the mpblock kernels stop at the live blocks and take the upper
    triangle of their tiles, ``live (live + 1) / 2`` of them, where
    :func:`self_join_grid` picks it, else their square; every other
    path sweeps the square of all ``nb``."""
    if live is None or not (backend == "pallas" and znorm):
        live = nb
    if self_join_grid(nb, block, backend, znorm) == "triangle":
        return live * (live + 1) // 2 * block * block
    return (live * block) ** 2


def pair_d2(wa, wb, mu_a, sig_a, mu_b, sig_b, s: int, valid=None):
    """Row-wise Eq. (3): d2 between paired windows (B, s) x (B, s).

    The 1-D sibling of the tile — used by HST's chained warm-up and
    topology passes where pairs are scattered, not blocked.
    """
    dots = jnp.sum(wa * wb, axis=1)
    corr = (dots - s * mu_a * mu_b) / (s * sig_a * sig_b)
    d2 = jnp.maximum(2.0 * s * (1.0 - corr), 0.0)
    if valid is not None:
        d2 = jnp.where(valid, d2, jnp.inf)
    return d2


def exact_pair_d2(wa, wb) -> np.ndarray:
    """Row-wise exact (f64, host) squared distance of paired window
    stacks — the tile plane's scalar-refinement sibling (used by the
    LB-abandoning pan schedule).  Lives here so no caller has to spell
    ``sum((a - b) ** 2)`` outside the tile layer (the ``tile-math``
    lint rule, docs/analysis.md)."""
    wa = np.asarray(wa, np.float64)
    wb = np.asarray(wb, np.float64)
    return np.sum((wa - wb) ** 2, axis=1)


def topk_nonoverlapping(profile: np.ndarray, k: int, s: int
                        ) -> Tuple[list, list]:
    """Host-side top-k maxima of a profile under the non-overlap rule."""
    p = np.asarray(profile, np.float64).copy()
    n = p.shape[0]
    pos, vals = [], []
    for _ in range(k):
        i = int(np.argmax(p))
        if not np.isfinite(p[i]):
            break
        pos.append(i)
        vals.append(float(p[i]))
        p[max(0, i - s + 1):min(n, i + s)] = -np.inf
    return pos, vals


# ----------------------------------------------------------------------
# engine
# ----------------------------------------------------------------------
class TileEngine:
    """Tile data plane for one series (jit/vmap-safe: jnp ops only).

    Owns the padded series / per-window stats and hands out
    ``TileBlock``s; every distance evaluation dispatches through the
    backend registry.  ``block`` is the candidate tile side; the
    series is padded so that every contiguous block's Hankel build
    stays in bounds (nb * block + s - 1 samples).
    """

    def __init__(self, series, s: int, *, block: int = 256,
                 backend: Optional[str] = None, n_valid=None,
                 znorm: bool = True):
        """``n_valid`` (optional, may be a *traced* scalar) marks how
        many leading windows hold real data; the rest are plan-cache
        padding whose ids are remapped to -1 so every backend masks
        them to +inf.  Left as None, the series' own length decides
        (the original static behavior, trace-identical).

        ``znorm=False`` switches the engine to raw Euclidean
        distances (DADD's convention).  The pluggable backends only
        speak Eq. (3); raw tiles are recovered from them exactly by a
        rank-1 norm correction — see ``_raw_d2``.
        """
        self.s = int(s)
        self.block = int(block)
        self.backend = resolve_backend(backend)
        self.znorm = bool(znorm)
        x = jnp.asarray(series, jnp.float32)
        self.n = x.shape[0] - self.s + 1
        self.nb = ceil_div(self.n, self.block)
        n_pad = self.nb * self.block
        L_need = n_pad + self.s - 1
        self.series_pad = jnp.pad(x, (0, max(0, L_need - x.shape[0])))
        self._dyn = n_valid is not None
        self.n_valid = self.n if n_valid is None else n_valid
        if self.znorm:
            mu, sig = sliding_stats_jnp(x, self.s)
            self.mu_pad = jnp.pad(mu, (0, n_pad - self.n))
            self.sig_pad = jnp.pad(sig, (0, n_pad - self.n),
                                   constant_values=1.0)
        else:
            # Raw mode: neutral stats (mu=0, sig=1) turn the backends'
            # Eq. (3) tile into 2s - 2<q,c>; the true raw d2 is then
            # ||q||^2 + ||c||^2 - 2<q,c>, recovered in _raw_d2 from the
            # per-window squared norms.  The series is pre-scaled so
            # every window norm is <= sqrt(s): by Cauchy-Schwarz no dot
            # product can exceed s, keeping the backends' max(., 0)
            # clamp inactive (the 1e-3 headroom absorbs f32 rounding).
            csum2 = jnp.concatenate(
                [jnp.zeros(1, jnp.float32),
                 jnp.cumsum(self.series_pad * self.series_pad)])
            self.nrm_pad = csum2[self.s:self.s + n_pad] - csum2[:n_pad]
            # the scale must only see live windows: pad windows overlap
            # the bucket's pad samples (the sanitizer poisons those
            # with NaN/±inf canaries), and one poisoned norm here
            # would NaN the whole scaled series.  Value-identical
            # under benign zero fill — every pad-window norm is a
            # suffix sum of the last live window's.
            live = jnp.arange(n_pad) < self.n_valid
            mx = jnp.max(jnp.where(live, self.nrm_pad, 0.0))
            g = jnp.sqrt(jnp.float32(self.s)) / (
                jnp.sqrt(jnp.maximum(mx, 1e-30)) * 1.001)
            self._g = jnp.where(mx > 0, g, 1.0)
            self.series_pad = self.series_pad * self._g
            self.mu_pad = jnp.zeros(n_pad, jnp.float32)
            self.sig_pad = jnp.ones(n_pad, jnp.float32)

    def live_blocks(self):
        """Window blocks holding a window of the record,
        ``ceil(n_valid / block)`` as an int32 scalar (traced with
        ``n_valid``); None when the engine was built without a dynamic
        n_valid, whose every block is live."""
        if not self._dyn:
            return None
        nv = jnp.asarray(self.n_valid, jnp.int32)
        return jnp.clip(ceil_div(nv, self.block), 1, self.nb)

    def _mask_ids(self, ids):
        """Remap plan-cache padding windows (id >= n_valid) to -1 so
        the backends' id mask retires them; identity when the engine
        was built without a dynamic n_valid."""
        if not self._dyn:
            return ids
        return jnp.where(ids < self.n_valid, ids, jnp.int32(-1))

    def _raw_d2(self, t, qids, cids):
        """Invert the neutral-stats Eq. (3) tile to raw Euclidean d2.

        t = 2s - 2*g^2*<q,c> (masked lanes +inf) ->
        d2 = ||q||^2 + ||c||^2 - (2s - t)/g^2, clamped at 0.

        Norm gathers stay inside the live range: masked lanes carry
        id -1 (-> index 0, real data) and t=+inf already forces them
        to +inf, so clipping to n_valid-1 never changes a value — it
        just guarantees no pad-poisoned norm is ever even loaded.
        """
        top = jnp.maximum(self.n_valid - 1, 0)
        nq = self.nrm_pad[jnp.clip(qids, 0, top)]
        nc = self.nrm_pad[jnp.clip(cids, 0, top)]
        dots2 = (2.0 * self.s - t) / (self._g * self._g)
        return jnp.maximum(nq[:, None] + nc[None, :] - dots2, 0.0)

    # -- block constructors -------------------------------------------
    def query_block(self, ids) -> TileBlock:
        """Gathered windows at arbitrary ids (clipped for the gather;
        the *raw* ids are kept so out-of-range lanes mask to +inf)."""
        ids = self._mask_ids(jnp.asarray(ids, jnp.int32))
        safe = jnp.clip(ids, 0, self.n - 1)
        win = self.series_pad[safe[:, None] + jnp.arange(self.s)[None, :]]
        return TileBlock(win, self.mu_pad[safe], self.sig_pad[safe], ids)

    def contiguous_block(self, c0) -> TileBlock:
        """One (block,) contiguous window block at (traced) offset c0."""
        chunk = lax.dynamic_slice(self.series_pad, (c0,),
                                  (self.block + self.s - 1,))
        win = chunk[jnp.arange(self.block)[:, None]
                    + jnp.arange(self.s)[None, :]]
        return TileBlock(
            win,
            lax.dynamic_slice(self.mu_pad, (c0,), (self.block,)),
            lax.dynamic_slice(self.sig_pad, (c0,), (self.block,)),
            self._mask_ids(c0 + jnp.arange(self.block, dtype=jnp.int32)))

    def block_chunks(self) -> jnp.ndarray:
        """(nb, W) reversed series chunks, one per window block — the
        mpblock kernel's compact stand-in for :meth:`all_windows`
        (``kernels/mpblock/kernel.py``)."""
        from ..kernels.mpblock.kernel import block_chunks
        return block_chunks(self.series_pad, self.nb, self.block, self.s)

    def all_windows(self) -> TileBlock:
        """Every (padded) window, materialized — candidate side of the
        blocked full-profile sweep."""
        n_pad = self.mu_pad.shape[0]
        win = self.series_pad[jnp.arange(n_pad)[:, None]
                              + jnp.arange(self.s)[None, :]]
        return TileBlock(win, self.mu_pad, self.sig_pad,
                         self._mask_ids(jnp.arange(n_pad,
                                                   dtype=jnp.int32)))

    def window_set(self, pad_to: int) -> tuple:
        """Every window as a ``(body, mu, sig, ids)`` set for
        :func:`set_row_mins`, padded with masked lanes (ids -1) to
        ``pad_to`` windows, a multiple of ``block`` (ring shards split
        it evenly).  ``body`` is the per-block series chunks of
        :meth:`block_chunks` on ``pallas`` and the materialized window
        rows elsewhere; callers only pass the set on."""
        n_pad = self.mu_pad.shape[0]
        pad = pad_to - n_pad
        if self.backend == "pallas":
            body = jnp.pad(self.block_chunks(),
                           ((0, pad // self.block), (0, 0)))
        else:
            body = jnp.pad(self.all_windows().win, ((0, pad), (0, 0)))
        ids = self._mask_ids(jnp.arange(n_pad, dtype=jnp.int32))
        return (body,
                jnp.pad(self.mu_pad, (0, pad)),
                jnp.pad(self.sig_pad, (0, pad), constant_values=1.0),
                jnp.pad(ids, (0, pad), constant_values=-1))

    # -- tile ops ------------------------------------------------------
    def d2(self, q: TileBlock, c: TileBlock,
           backend: Optional[str] = None) -> jnp.ndarray:
        t = tile_d2(q, c, s=self.s, n_valid=self.n,
                    backend=backend or self.backend)
        if self.znorm:
            return t
        return self._raw_d2(t, q.ids, c.ids)

    def sweep(self, q: TileBlock, c0, *, backend: Optional[str] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """d2 tile of gathered queries vs the contiguous block at c0.

        This is HST's inner-loop shape.  On the ``pallas`` backend the
        candidate Hankel tile is built in-kernel from the raw chunk
        (the mpblock VMEM trick); elsewhere the block is materialized
        and handed to the window-block backend.  Returns (d2, cid).
        """
        backend = resolve_backend(backend or self.backend)
        cid = self._mask_ids(c0 + jnp.arange(self.block, dtype=jnp.int32))
        if backend == "pallas":
            from ..kernels.mpblock.kernel import qvc_block_pallas
            chunk = lax.dynamic_slice(self.series_pad, (c0,),
                                      (self.block + self.s - 1,))
            cmu = lax.dynamic_slice(self.mu_pad, (c0,), (self.block,))
            csig = lax.dynamic_slice(self.sig_pad, (c0,), (self.block,))
            d2 = qvc_block_pallas(
                q.win, q.mu, q.sig, q.ids, chunk, cmu, csig, cid,
                s=self.s, n_valid=self.n,
                interpret=default_interpret())
            if not self.znorm:
                d2 = self._raw_d2(d2, q.ids, cid)
            return d2, cid
        return self.d2(q, self.contiguous_block(c0), backend), cid

    # -- full self-join profile ---------------------------------------
    def profile(self, *, backend: Optional[str] = None,
                interpret: Optional[bool] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Exact matrix profile (d2, neighbor) of the whole series: the
        :meth:`block_rows` of every query block."""
        d2b, argb = self.block_rows(backend=backend, interpret=interpret)
        return d2b.reshape(-1)[:self.n], argb.reshape(-1)[:self.n]

    def block_rows(self, starts=None, *, backend: Optional[str] = None,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Row (min d2, neighbor) of the query blocks at the (traced,
        block-aligned) ``starts`` (None: every block, in order)
        against every window, each ``(len(starts), block)``.  A row is
        computed the same way whichever blocks are listed with it, so
        re-sweeping a few blocks reproduces the full profile's rows bit
        for bit.

        ``pallas`` dispatches to the mpblock kernels (window tiles built
        in VMEM from per-block series chunks), whose grids stop at the
        :meth:`live_blocks`.  Every block listed, the self-join is swept
        as its upper triangle (``mp_tri_pallas``), each pair once with
        the lower block on the query side; listed blocks take the same
        tiles in the same roles (``mp_rows_pallas``: pairs with a lower
        block as the columns of its tile, the rest as rows), so their
        rows are the triangle's bit for bit.  Buckets past
        :func:`self_join_grid`'s bound sweep the square
        (``mp_block_pallas``) either way.  Other backends run a blocked
        row sweep through the registry.  ``interpret`` overrides the
        pallas interpret-mode auto-detect (debug hook; ignored by the
        other backends).  The kernels only speak Eq. (3), so
        ``znorm=False`` engines take the blocked sweep on every backend.
        """
        backend = resolve_backend(backend or self.backend)
        nb, blk = self.nb, self.block
        every = starts is None
        if every:
            starts = jnp.arange(nb, dtype=jnp.int32) * blk
        if backend == "pallas" and self.znorm:
            from ..kernels.mpblock import kernel as mpk
            if interpret is None:
                interpret = default_interpret()
            chunks = self.block_chunks()
            ids = self._mask_ids(jnp.arange(nb * blk, dtype=jnp.int32))
            live = self.live_blocks()
            rows = starts // blk
            ops = (chunks, self.mu_pad, self.sig_pad, ids)
            kw = dict(s=self.s, n_valid=self.n, block=blk,
                      interpret=interpret)
            if self_join_grid(nb, blk, backend) == "square":
                def q(v):
                    return v.reshape(nb, blk)[rows].ravel()
                d2, arg = mpk.mp_block_pallas(
                    chunks[rows], q(self.mu_pad), q(self.sig_pad), q(ids),
                    *ops, nq=live if every else None, nc=live, **kw)
            elif every:
                d2, arg = mpk.mp_tri_pallas(*ops, nb_live=live, **kw)
            else:
                d2, arg = mpk.mp_rows_pallas(*ops, rows, nb_live=live,
                                             **kw)
            return d2.reshape(-1, blk), arg.reshape(-1, blk)

        cand = self.all_windows()

        def one_block(b0):
            q = self.contiguous_block(b0)
            d2 = self.d2(q, cand, backend)
            return (jnp.min(d2, axis=1),
                    jnp.argmin(d2, axis=1).astype(jnp.int32))

        return lax.map(one_block, starts)


# ----------------------------------------------------------------------
# batched multi-series plane
# ----------------------------------------------------------------------
# session-free serving front door: jax's own cache keys this jit per
# (s, block, backend) tuple, there is no engine whose plan cache could
# account for it.  # analysis: ignore[untracked-jit]
@functools.partial(jax.jit, static_argnames=("s", "block", "backend"))
def _batched_profile_jit(series_batch, *, s, block, backend):
    def one(x):
        return TileEngine(x, s, block=block, backend=backend).profile()

    if backend == "xla":
        return jax.vmap(one)(series_batch)       # one compiled MXU sweep
    # pallas_call / pure_callback don't batch — scan the batch instead
    return lax.map(one, series_batch)


def batched_profile(series_batch, s: int, *, block: int = 256,
                    backend: Optional[str] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Matrix profile of a (B, L) stack of equal-length series.

    The serving-plane workhorse: on ``xla`` the whole batch is one
    vmapped tile sweep (B series amortize one compilation and fill the
    MXU together); ``pallas``/``numpy`` scan the batch series-by-series
    through the same engine.  Returns (d2 (B, n), neighbor (B, n)).
    """
    xb = jnp.atleast_2d(jnp.asarray(series_batch, jnp.float32))
    return _batched_profile_jit(xb, s=s, block=block,
                                backend=resolve_backend(backend))
