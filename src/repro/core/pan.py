"""Pan-length discord search: one shared sweep for a ladder of windows.

The discord *length* is the one search parameter the paper cannot tell
you (cost depends on it non-trivially, Sec 4), so practitioners sweep a
range of ``s`` values.  Run naively that costs a full Eq. (3) tile
sweep per length.  VALMOD (Linardi et al., "Matrix Profile Goes MAD")
observed that almost all of that work is shared: the scalar products
``QT(i, j) = <x[i:i+s], x[j:j+s]>`` at length ``s + d`` differ from the
length-``s`` ones only by ``d`` extra multiply-adds per pair.  This
module is that observation as a plan family:

``PanEngine``
    jit-safe sweep over a *ladder* ``(s_0 < s_1 < ... < s_{R-1})``:

      * the per-rung ``mu``/``sigma`` (and raw window norms) come from
        the block-centred window sums of
        ``kernels.common.sliding_stats_jnp``, rung by rung, so in-range
        stats are bit-identical to the single-length engine's;
      * per query block, the **base rung** pays one full-width dot tile
        (``dot_tile`` backend primitive, ``kernels.registry``) and each
        later rung only the ``(s_r - s_{r-1})``-wide *extension* tile,
        accumulated into the carried QT — Eq. (3) (or the raw-Euclidean
        norm identity) then turns the same QT into every rung's
        distances with that rung's stats, exclusion band, and validity
        count.

    Exactness: the carried QT is the exact scalar product at every rung
    (the extension tiles add precisely the missing terms), and the
    per-rung stats/masks are the single-length engine's own — so each
    rung's profile is the same quantity the independent sweep computes,
    differing only in floating-point summation order.

``cross_length_lb``
    The cross-length lower bound (ARCHITECTURE.md has the proof):

        d2_{s'}(i, j) >= s * (a_i - b_j)^2 + a_i * b_j * d2_s(i, j)

    with ``a_i = sigma_s(i) / sigma_s'(i)`` (and ``b_j`` likewise), for
    any pair valid at both lengths and ``s' > s``.  Minimizing over the
    neighbor gives a per-window bound on the next rung's nnd profile
    from the previous rung's — ``search_pan`` uses it as a runtime
    cross-check of the incremental sweep (a violated bound means a
    broken QT carry, not a data property), and it is the hook for
    rung-abandoning schedules (ROADMAP).

Beyond the full-ladder sweep, ``PanEngine`` exposes the three sweep
shapes the session layer's pan planes are built from:

  * ``profile()`` — the full-ladder profile sweep (``("pan", ...)``
    plans), each window pair once where the single-length self-join
    sweeps its upper triangle (``tiles.self_join_grid``); ``rows(starts)``
    — the same sweep of listed query blocks against every candidate
    (query blocks shardable across a mesh);
  * ``tail(qids, c0, n_cand)`` — a *streaming append* sweep: the new
    tail windows against a candidate id range, QT carried across rungs
    exactly like the full sweep, returning row **and** column minima
    per rung so the host can fold new-neighbor improvements into every
    rung's old profile (``("pan_tail", ...)`` plans);
  * ``carry_rows(qt_in)`` — a full-grid sweep that *returns* the
    carried QT and evaluates Eq. (3) only at the engine's last rung —
    the building block of the sequential LB-abandoning rung schedule
    (``("pan_base", ...)`` / ``("pan_step", ...)`` plans), where the
    QT crosses between plan invocations so a skipped rung pays no
    evaluation at all.

``cross_length_lb`` / ``cross_length_ub``
    The cross-length *bracket* (ARCHITECTURE.md §3b has both proofs):
    the lower bound certifies the QT carry at runtime
    (``lb_margin`` / ``lb_ok``), and the upper bound — per-window, from
    the previous rung's profile, neighbors and stats only — is what
    lets the LB-abandoning schedule *skip* a rung: if no window's
    bounded ``d/sqrt(s)`` score can beat the current k-th global pick,
    the rung's evaluation is provably irrelevant to the global top-k.

Work accounting (docs/cps.md): pan lanes are **width-normalized** — an
extension tile sweeps the same (rows x cols) cells but computes only
``d`` of the ``s_r`` scalar products a from-scratch lane needs, so it
counts ``d / s_r`` of a lane per cell (``pan_lanes``).  That is what
makes the ladder's total comparable with (and far below) ``R``
independent sweeps.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels.common import (ceil_div, exclusion_mask,
                              raw_d2_from_dots, series_csums,
                              stats_from_csums, znorm_d2_formula)
from ..kernels.registry import get_dot_backend, resolve_backend
from .tiles import self_join_grid
from .windows import sliding_stats

__all__ = ["PanEngine", "canonical_ladder", "pan_lanes",
           "pan_rung_shares", "pan_tail_sweep", "cross_length_lb",
           "cross_length_ub", "ladder_lb_margin",
           "global_normalized_topk"]


def canonical_ladder(windows) -> Tuple[int, ...]:
    """Sorted, deduplicated tuple of window lengths — the *ladder
    bucket* every pan plan is keyed on (two specs whose windows agree
    up to order/duplicates share one compiled sweep)."""
    if isinstance(windows, (int, np.integer)):
        windows = (windows,)
    lad = tuple(sorted({int(v) for v in windows}))
    if not lad:
        raise ValueError("empty window ladder")
    if lad[0] < 2:
        raise ValueError(f"window length must be >= 2, got {lad[0]}")
    return lad


def pan_rung_shares(ladder: Sequence[int], n_rows: int,
                    n_cols: int) -> List[int]:
    """Per-rung width-normalized lane shares of one pan sweep over an
    (n_rows x n_cols) tile grid: the base rung sweeps full lanes, each
    later rung ``(s_r - s_{r-1}) / s_r`` of a lane per cell
    (docs/cps.md).  The shares are THE decomposition — ``pan_lanes``
    is their sum, and every per-rung ``calls`` report uses them, so
    per-rung calls always sum to the sweep total (even accumulated
    across a stream's appends, where a ceil-of-sums would drift)."""
    cells = n_rows * n_cols
    shares = [cells]                    # base rung: full-width lanes
    for prev, cur in zip(ladder[:-1], ladder[1:]):
        shares.append(ceil_div(cells * (cur - prev), cur))
    return shares


def pan_lanes(ladder: Sequence[int], n_rows: int, n_cols: int) -> int:
    """Width-normalized lanes of one pan sweep — the sum of
    :func:`pan_rung_shares`."""
    return int(sum(pan_rung_shares(ladder, n_rows, n_cols)))


class PanEngine:
    """Ladder-shared tile sweep for one series (jit/shard_map-safe).

    Construct inside a jitted plan body, like ``TileEngine`` — all ops
    are jnp.  ``series`` is the (bucketed) series; the engine pads it
    so every grid window id can be sliced at the *longest* rung.
    ``n_valid`` (traced scalar) is the true window count at the **base
    rung**; rung ``r``'s own count is derived as
    ``n_valid - (s_r - s_0)``.
    """

    def __init__(self, series, ladder: Tuple[int, ...], *,
                 block: int = 256, backend: Optional[str] = None,
                 znorm: bool = True, n_valid=None,
                 n_pad: Optional[int] = None):
        self.ladder = canonical_ladder(ladder)
        self.block = int(block)
        self.backend = resolve_backend(backend)
        self.znorm = bool(znorm)
        s0, smax = self.ladder[0], self.ladder[-1]
        x = jnp.asarray(series, jnp.float32)
        self.n = x.shape[0] - s0 + 1            # base-rung window count
        if n_pad is None:
            self.nb = ceil_div(self.n, self.block)
            self.n_pad = self.nb * self.block
        else:
            # forced grid size (candidate-sharded tail plans pad the
            # grid to a device multiple; sequential-schedule step plans
            # must match the base plan's carried-QT geometry)
            if n_pad % self.block:
                raise ValueError(f"n_pad={n_pad} is not a multiple of "
                                 f"block={self.block}")
            self.n_pad = int(n_pad)
            self.nb = self.n_pad // self.block
        # the single-length self-join's tile grid: where it sweeps the
        # upper triangle, so does :meth:`profile`
        self.grid = self_join_grid(self.nb, self.block, self.backend,
                                   self.znorm)
        need = self.n_pad + smax - 1
        self.series_pad = jnp.pad(x, (0, max(0, need - x.shape[0])))
        self.n_valid = self.n if n_valid is None else n_valid
        # every rung's stats through the same series_csums /
        # stats_from_csums pass as sliding_stats_jnp — so in-range
        # values are bit-identical to the single-length TileEngine's by
        # construction.
        self.mu: List[jnp.ndarray] = []
        self.sig: List[jnp.ndarray] = []
        self.nrm: List[jnp.ndarray] = []        # raw ||window||^2
        for s in self.ladder:
            mu, sig, nrm = stats_from_csums(
                series_csums(self.series_pad, s), s, self.n_pad)
            self.mu.append(mu)
            self.sig.append(sig)
            self.nrm.append(nrm)

    # ------------------------------------------------------------------
    def _cand_slab(self, c0=0, count: Optional[int] = None):
        """Candidate-side materialization for the id range
        ``[c0, c0 + count)`` (default: the whole grid): the base
        windows plus each rung's extension slab (total count x s_max
        floats — the pan analogue of ``TileEngine.all_windows``).
        ``c0`` may be traced (the candidate-sharded tail plan passes
        each device's own shard offset); ``count`` is static."""
        count = self.n_pad if count is None else int(count)
        ids = c0 + jnp.arange(count)
        base = self.series_pad[ids[:, None]
                               + jnp.arange(self.ladder[0])[None, :]]
        exts = []
        for prev, cur in zip(self.ladder[:-1], self.ladder[1:]):
            off = prev + jnp.arange(cur - prev)
            exts.append(self.series_pad[ids[:, None] + off[None, :]])
        return base, exts, ids.astype(jnp.int32)

    def _q_slab(self, qs, lo: int, hi: int):
        """Query-side window gather for series offsets [lo, hi)."""
        off = lo + jnp.arange(hi - lo)
        return self.series_pad[qs[:, None] + off[None, :]]

    def _rung_d2(self, qt, r: int, q_idx, c_idx, qid, cid):
        """Rung ``r``'s masked squared distances from the carried QT
        tile: Eq. (3) with rung stats (znorm) or the raw-Euclidean
        norm identity, exclusion band and validity at the rung's own
        window count.  ``q_idx``/``c_idx`` index the stats arrays (in
        [0, n_pad)); ``qid``/``cid`` are the global ids the mask sees
        (ids outside [0, rung n_valid) are padding)."""
        s_r = self.ladder[r]
        nv = self.n_valid - (s_r - self.ladder[0])
        if self.znorm:
            d2 = znorm_d2_formula(qt, s_r,
                                  self.mu[r][q_idx], self.sig[r][q_idx],
                                  self.mu[r][c_idx], self.sig[r][c_idx])
        else:
            d2 = raw_d2_from_dots(qt, self.nrm[r][q_idx],
                                  self.nrm[r][c_idx])
        return jnp.where(exclusion_mask(qid, cid, s_r, nv), jnp.inf, d2)

    def rows(self, starts) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Pan sweep of the query blocks at ``starts`` (m,) against
        every candidate: returns ``(d2, ngh)`` of shape
        ``(m, R, block)`` — per rung, each query window's min squared
        distance and the global candidate id realizing it.
        """
        dot = get_dot_backend(self.backend)
        cand_base, cand_exts, cids = self._cand_slab()
        cc = jnp.clip(cids, 0, self.n_pad - 1)
        s0 = self.ladder[0]

        def one(q0):
            qi = q0 + jnp.arange(self.block, dtype=jnp.int32)
            qs = jnp.clip(qi, 0, self.n_pad - 1)
            qt = dot(self._q_slab(qs, 0, s0), cand_base)
            d2s, args = [], []
            for r, s_r in enumerate(self.ladder):
                if r:
                    qt = qt + dot(self._q_slab(qs, self.ladder[r - 1],
                                               s_r), cand_exts[r - 1])
                d2 = self._rung_d2(qt, r, qs, cc, qi, cids)
                d2s.append(jnp.min(d2, axis=1))
                args.append(jnp.argmin(d2, axis=1).astype(jnp.int32))
            return jnp.stack(d2s), jnp.stack(args)

        return lax.map(one, jnp.asarray(starts, jnp.int32))

    def tail(self, qids, c0=0, n_cand: Optional[int] = None):
        """Streaming-append sweep: the (bucketed, masked) query windows
        ``qids`` — the appended tail, global base-rung ids, possibly
        traced — against the candidate id range ``[c0, c0 + n_cand)``
        at **every** rung, QT carried across rungs exactly like the
        full sweep.

        Returns ``(row_d2, row_ngh, col_d2, col_ngh)`` of shapes
        ``(R, Qb) / (R, Qb) / (R, n_cand) / (R, n_cand)``: per rung,
        the row minima are the tail windows' exact nnds and the column
        minima are each candidate's best distance *to the tail*, which
        the host min-folds into the rung's old profile (append-only:
        an old window's nnd can only be superseded, never worsen).
        """
        dot = get_dot_backend(self.backend)
        n_cand = self.n_pad if n_cand is None else int(n_cand)
        cand_base, cand_exts, cids = self._cand_slab(c0, n_cand)
        cc = jnp.clip(cids, 0, self.n_pad - 1)
        qids = jnp.asarray(qids, jnp.int32)
        qs = jnp.clip(qids, 0, self.n_pad - 1)
        qt = dot(self._q_slab(qs, 0, self.ladder[0]), cand_base)
        rd2, rng, cd2, cng = [], [], [], []
        for r, s_r in enumerate(self.ladder):
            if r:
                qt = qt + dot(self._q_slab(qs, self.ladder[r - 1], s_r),
                              cand_exts[r - 1])
            d2 = self._rung_d2(qt, r, qs, cc, qids, cids)
            rd2.append(jnp.min(d2, axis=1))
            rng.append(cids[jnp.argmin(d2, axis=1)])
            cd2.append(jnp.min(d2, axis=0))
            cng.append(qids[jnp.argmin(d2, axis=0)])
        return (jnp.stack(rd2), jnp.stack(rng),
                jnp.stack(cd2), jnp.stack(cng))

    def carry_rows(self, qt_in=None):
        """Full-grid sweep that *returns* the carried QT and evaluates
        Eq. (3) only at the engine's **last** rung — the building block
        of the sequential LB-abandoning schedule.

        With ``qt_in=None`` (the base plan, single-rung ladder) the
        base dot tiles are paid in full; otherwise ``qt_in`` is the
        (n_pad, n_pad) QT carried at ``ladder[0]``'s width from the
        previous evaluated rung, and this engine's ladder spells the
        *intermediate* widths so the extension dots accumulate in
        exactly the full ladder sweep's order (same floats, whether or
        not the rungs in between were evaluated).

        Returns ``(qt_out (n_pad, n_pad), d2 (n_pad,), ngh)`` at the
        last rung.
        """
        dot = get_dot_backend(self.backend)
        cand_base, cand_exts, cids = self._cand_slab()
        cc = jnp.clip(cids, 0, self.n_pad - 1)
        last = len(self.ladder) - 1

        def one(q0):
            qi = q0 + jnp.arange(self.block, dtype=jnp.int32)
            qs = jnp.clip(qi, 0, self.n_pad - 1)
            if qt_in is None:
                qt = dot(self._q_slab(qs, 0, self.ladder[0]), cand_base)
            else:
                qt = lax.dynamic_slice_in_dim(qt_in, q0, self.block)
            for r in range(1, len(self.ladder)):
                qt = qt + dot(self._q_slab(qs, self.ladder[r - 1],
                                           self.ladder[r]),
                              cand_exts[r - 1])
            d2 = self._rung_d2(qt, last, qs, cc, qi, cids)
            return (qt, jnp.min(d2, axis=1),
                    jnp.argmin(d2, axis=1).astype(jnp.int32))

        starts = jnp.arange(self.nb, dtype=jnp.int32) * self.block
        qt, d2, arg = lax.map(one, starts)
        return (qt.reshape(self.n_pad, self.n_pad),
                d2.reshape(-1), arg.reshape(-1))

    def profile(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """All rungs' full profiles: ``(d2, ngh)`` of shape
        ``(R, n_pad)`` (entries past rung r's own window count are
        masked +inf).  Each window pair once where ``self.grid`` is
        the triangle (:meth:`_half_profile`), else every query block
        against every candidate (:meth:`rows`)."""
        if self.grid == "triangle":
            return self._half_profile()
        starts = jnp.arange(self.nb, dtype=jnp.int32) * self.block
        d2, arg = self.rows(starts)             # (nb, R, block)
        R = len(self.ladder)
        return (d2.transpose(1, 0, 2).reshape(R, -1),
                arg.transpose(1, 0, 2).reshape(R, -1))

    def _half_profile(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """:meth:`profile` with each window pair computed once: the
        ``nb (nb + 1) / 2`` tiles of the self-join's triangle, in
        circulant order.  Query block ``i`` sweeps the candidate blocks
        ``i, i + 1, ...`` (mod ``nb``), ``nb // 2 + 1`` of them, and one
        fewer in the last ``nb / 2`` rows of an even ``nb``, so any two
        blocks meet in exactly one tile.  A tile's row minima are its
        query windows'; off the diagonal tile, its column minima fold
        into an accumulator carried across the rows, and the two meet
        at the end.  Every fold keeps the lowest minimizing id on a
        tie, :meth:`rows`' ``argmin`` rule, and a window with no finite
        distance reads neighbour 0, as there."""
        dot = get_dot_backend(self.backend)
        nb, blk, lad = self.nb, self.block, self.ladder
        R, n_pad = len(lad), self.n_pad
        base, exts, ids = self._cand_slab()
        # doubled, so a row's wrapped run of candidate blocks is a slice
        base, ids = (jnp.concatenate([v, v]) for v in (base, ids))
        exts = [jnp.concatenate([e, e]) for e in exts]
        none = jnp.iinfo(jnp.int32).max

        def first_min(d2, who, axis):
            m = jnp.min(d2, axis=axis)
            return m, jnp.min(jnp.where(d2 == jnp.expand_dims(m, axis),
                                        who, none), axis=axis)

        def fold(a, b):
            take = (b[0] < a[0]) | ((b[0] == a[0]) & (b[1] < a[1]))
            return (jnp.where(take, b[0], a[0]),
                    jnp.where(take, b[1], a[1]))

        def sweep(width):
            def row(col, i):
                q0 = i * blk
                qi = q0 + jnp.arange(blk, dtype=jnp.int32)
                qs = jnp.clip(qi, 0, n_pad - 1)

                def cand(v):
                    return lax.dynamic_slice_in_dim(v, q0, width * blk)

                cid = cand(ids)
                cc = jnp.clip(cid, 0, n_pad - 1)
                qt = dot(self._q_slab(qs, 0, lad[0]), cand(base))
                rmin, cmin = [], []
                for r in range(R):
                    if r:
                        qt = qt + dot(self._q_slab(qs, lad[r - 1], lad[r]),
                                      cand(exts[r - 1]))
                    d2 = self._rung_d2(qt, r, qs, cc, qi, cid)
                    rmin.append(first_min(d2, cid[None, :], 1))
                    if width > 1:   # the diagonal tile's columns are rows
                        cmin.append(first_min(d2[:, blk:], qi[:, None], 0))
                if width > 1:
                    def at(v):
                        return lax.dynamic_slice_in_dim(
                            v, q0 + blk, (width - 1) * blk, axis=1)
                    new = fold(tuple(at(v) for v in col),
                               tuple(jnp.stack(v) for v in zip(*cmin)))
                    col = tuple(lax.dynamic_update_slice_in_dim(
                        v, n, q0 + blk, axis=1) for v, n in zip(col, new))
                return col, tuple(jnp.stack(v) for v in zip(*rmin))
            return row

        h = nb // 2
        groups = (((0, nb, h + 1),) if nb % 2 else
                  ((0, h, h + 1), (h, nb, h)))
        col = (jnp.full((R, 2 * n_pad), jnp.inf, jnp.float32),
               jnp.full((R, 2 * n_pad), none, jnp.int32))
        rows = []
        for lo, hi, width in groups:
            col, out = lax.scan(sweep(width), col,
                                jnp.arange(lo, hi, dtype=jnp.int32))
            rows.append(out)                    # (rows, R, block) each
        row = tuple(jnp.concatenate(v).transpose(1, 0, 2).reshape(R, -1)
                    for v in zip(*rows))
        col = fold(tuple(v[:, :n_pad] for v in col),
                   tuple(v[:, n_pad:] for v in col))
        d2, ngh = fold(row, col)
        return d2, jnp.where(d2 == jnp.inf, 0, ngh)


def pan_tail_sweep(series_pad, ladder: Tuple[int, ...], q0, Qb: int, *,
                   block: int = 256, backend: Optional[str] = None,
                   znorm: bool = True, n_valid=None):
    """One carried-QT tail sweep — the batched tail entry point.

    The ``Qb`` (bucketed, masked) base-rung query windows starting at
    ``q0`` against every candidate at every rung of ``ladder``:
    exactly :meth:`PanEngine.tail` over a fresh engine, packaged as a
    function so the single-tenant ``("pan_tail", ...)`` plan and the
    serve plane's per-lane ``("pan_tail_mb", ...)`` bodies share one
    definition (bit-identical coalescing).  ``q0`` and ``n_valid`` may
    be traced; ``Qb`` is static.  Returns
    ``(row_d2 (R, Qb), row_ngh, col_d2 (R, n_pad), col_ngh)``.
    """
    peng = PanEngine(series_pad, ladder, block=block, backend=backend,
                     znorm=znorm, n_valid=n_valid)
    qids = q0 + jnp.arange(Qb, dtype=jnp.int32)
    return peng.tail(qids)


# ----------------------------------------------------------------------
# cross-length lower bound (host side)
# ----------------------------------------------------------------------
def cross_length_lb(d2_prev: np.ndarray, sig_prev: np.ndarray,
                    sig_next: np.ndarray) -> np.ndarray:
    """Lower bound on the squared nnd profile at the *next* (longer)
    rung from the previous rung's exact profile.

    With ``a_i = sig_prev[i] / sig_next[i]`` the pairwise bound
    ``d2_next(i, j) >= a_i * a_j * d2_prev(i, j)`` (ARCHITECTURE.md,
    dropped ``(a_i - a_j)^2`` term) minimized over the neighbor gives

        nnd2_next(i) >= a_i * min_j(a_j) * nnd2_prev(i).

    Arguments are per-window arrays; ``sig_next`` has the next rung's
    (shorter) window count and trims the others.  Degenerate windows
    (sigma at the clamp floor) get the trivial bound 0.
    """
    n_next = sig_next.shape[0]
    a = np.asarray(sig_prev[:n_next], np.float64) / \
        np.asarray(sig_next, np.float64)
    a = np.where(np.asarray(sig_next) <= 1e-9, 0.0, a)
    if a.size == 0:
        return np.zeros(0, np.float64)
    return a * float(a.min()) * np.asarray(d2_prev[:n_next], np.float64)


# ----------------------------------------------------------------------
# cross-length upper bound (host side) — the other half of the bracket
# ----------------------------------------------------------------------
def cross_length_ub(d2_prev: np.ndarray, ngh_prev: np.ndarray,
                    s_prev: int, s_next: int, n_next: int, *,
                    stats_prev=None, stats_next=None,
                    nrm_prev=None, nrm_next=None,
                    max_hops: int = 8):
    """Per-window upper bound on the squared nnd profile at the *next*
    (longer) rung, from the previous rung's exact profile, neighbor
    ids and window stats only — no next-rung distance is evaluated.
    Returns ``(ub, partner)``: the bound and the prev-rung partner id
    it was derived from (-1 where unbounded) — the partner is what the
    LB-abandoning schedule's exact pair *refinement* re-measures when
    the stats-only bound alone is too loose to skip.

    This is what lets the LB-abandoning schedule *skip* a rung: if no
    window's ``sqrt(ub[i]) / sqrt(s_next)`` can beat its per-window
    threshold (the k-th global normalized pick, or an overlapping
    pick's own score), no window of the rung can alter the global
    top-k (docs/ARCHITECTURE.md §3b has the derivation).

    The bound per window ``i`` uses the pair ``(i, j)`` with
    ``j = ngh_prev[i]`` — any known pair distance upper-bounds the nnd.
    Splitting the length-``s_next`` z-normalized distance at ``s_prev``
    gives *exactly*

        d2_next(i,j) = s_prev (a_i - a_j)^2 + a_i a_j d2_prev(i,j)
                       + s_prev (m_i - m_j)^2 + ext(i,j)

    with ``a_i = sigma_prev(i)/sigma_next(i)``,
    ``m_i = (mu_prev(i) - mu_next(i))/sigma_next(i)``, and the
    extension term bounded by ``ext <= 2 (E_i + E_j)`` where
    ``E_i = s_next - s_prev (sigma_prev(i)^2 +
    (mu_prev(i) - mu_next(i))^2) / sigma_next(i)^2`` is the extension's
    exact z-normalized energy (from stats alone).  In raw mode
    (``nrm_*`` given instead of ``stats_*``) the extension terms are
    plain squares: ``ub = d2_prev + 2 (dE_i + dE_j)`` with
    ``dE_i = ||w_i||^2_next - ||w_i||^2_prev``.

    A previous-rung neighbor can be *unusable* at the next rung (its
    window no longer exists, or falls inside the next rung's wider
    exclusion band).  Distances are Euclidean metrics in both modes, so
    the neighbor chain ``i -> ngh(i) -> ngh(ngh(i)) ...`` is followed
    (triangle inequality, summed nnds) up to ``max_hops`` until a
    usable partner appears; windows left unbounded get ``+inf`` —
    conservative: they can only *prevent* a skip, never cause a wrong
    one.  Degenerate windows (sigma at the clamp floor, where the
    z-norm algebra is undefined) are ``+inf`` too.
    """
    d2_prev = np.asarray(d2_prev, np.float64)
    ngh = np.asarray(ngh_prev, np.int64)
    n_prev = d2_prev.shape[0]
    idx = np.arange(n_next)
    j = ngh[:n_next].copy()
    dist = np.sqrt(np.maximum(d2_prev[:n_next], 0.0))
    hops = np.zeros(n_next, np.int64)

    def usable(jj):
        return (jj >= 0) & (jj < n_next) & (np.abs(idx - jj) >= s_next)

    ok = usable(j)
    active = ~ok & (j >= 0) & (j < n_prev)
    for _ in range(max_hops):
        if not active.any():
            break
        dist[active] += np.sqrt(np.maximum(d2_prev[j[active]], 0.0))
        j[active] = ngh[j[active]]
        hops[active] += 1
        ok |= active & usable(j)
        active = ~ok & (j >= 0) & (j < n_prev)
    # the direct (0-hop) pair keeps the exact d2; chained pairs square
    # the triangle-summed distance
    d2p = np.where(hops == 0, d2_prev[:n_next], dist * dist)

    ub = np.full(n_next, np.inf)
    partner = np.where(ok, j, -1)
    v = np.flatnonzero(ok)
    if v.size == 0:
        return ub, partner
    ii, jj = idx[v], j[v]
    if stats_prev is not None:
        mu_p, sig_p = (np.asarray(a, np.float64) for a in stats_prev)
        mu_n, sig_n = (np.asarray(a, np.float64) for a in stats_next)
        mu_p, sig_p = mu_p[:n_next], sig_p[:n_next]
        a = sig_p / sig_n
        m = (mu_p - mu_n) / sig_n
        e = np.maximum(
            s_next - s_prev * (sig_p ** 2 + (mu_p - mu_n) ** 2)
            / sig_n ** 2, 0.0)
        ub_v = (s_prev * (a[ii] - a[jj]) ** 2 + a[ii] * a[jj] * d2p[v]
                + s_prev * (m[ii] - m[jj]) ** 2
                + 2.0 * (e[ii] + e[jj]))
        degen = (sig_p <= 2e-10) | (sig_n <= 2e-10)
        ub_v[degen[ii] | degen[jj]] = np.inf
    else:
        de = np.maximum(np.asarray(nrm_next, np.float64)[:n_next]
                        - np.asarray(nrm_prev, np.float64)[:n_next], 0.0)
        ub_v = d2p[v] + 2.0 * (de[ii] + de[jj])
    ub[v] = ub_v
    # degenerate windows keep their partner: the stats-only algebra is
    # void (+inf) but the exact pair refinement — which uses the same
    # clamped z-norm convention as the sweep — still applies
    return ub, partner


def ladder_lb_margin(x: np.ndarray, ladder: Sequence[int],
                     d2s: Sequence[np.ndarray],
                     znorm: bool = True) -> float:
    """Worst slack of the runtime cross-length lower-bound self-check
    over consecutive rung transitions: ``min (d2_r - lb) / s_r`` over
    finite cells (a violated bound means a broken QT carry, not a data
    property).  ``d2s`` holds each rung's squared nnd profile (trimmed
    to its own window count).  Single-rung ladders return 0.0; ladders
    with no finite transition cells return +inf (vacuously passing).
    """
    if len(ladder) <= 1:
        return 0.0
    x = np.asarray(x, np.float64).ravel()
    margin = np.inf
    prev_d2 = prev_sig = None
    for r, s_r in enumerate(ladder):
        d2_r = np.asarray(d2s[r], np.float64)
        # the sigma-ratio LB is the only consumer of host sigmas: skip
        # the O(L) passes in raw mode (monotonicity bound applies)
        sig_r = sliding_stats(x, s_r)[1] if znorm else None
        if r:
            lb = (cross_length_lb(prev_d2, prev_sig, sig_r)
                  if znorm else prev_d2[:d2_r.shape[0]])
            # inf-profile windows (no valid non-self match at a rung)
            # would yield inf - inf = NaN and poison the min: check
            # finite cells only
            fin = np.isfinite(d2_r) & np.isfinite(lb)
            if fin.any():
                margin = min(margin, float(np.min(
                    (d2_r[fin] - lb[fin]) / s_r)))
        prev_d2, prev_sig = d2_r, sig_r
    return float(margin)


# ----------------------------------------------------------------------
# global length-normalized ranking (host side)
# ----------------------------------------------------------------------
def global_normalized_topk(profiles: Sequence[np.ndarray],
                           ladder: Sequence[int], k: int) -> List[dict]:
    """Greedy top-k discords *across* rungs ranked by the
    length-normalized distance ``d / sqrt(s)``, with interval-overlap
    exclusion: a pick at ``(s, i)`` retires every candidate (at any
    rung) whose window ``[j, j + s_r)`` overlaps ``[i, i + s)``.
    Exact by construction — it scans the full exact profiles.
    """
    scores = []
    for prof, s in zip(profiles, ladder):
        p = np.asarray(prof, np.float64)
        scores.append(np.where(np.isfinite(p), p / math.sqrt(s),
                               -np.inf))
    out: List[dict] = []
    for _ in range(int(k)):
        best_r, best_i, best_v = -1, -1, -np.inf
        for r, sc in enumerate(scores):
            if sc.size == 0:
                continue
            i = int(np.argmax(sc))
            if sc[i] > best_v:
                best_r, best_i, best_v = r, i, float(sc[i])
        if best_r < 0 or not np.isfinite(best_v):
            break
        s_pick = int(ladder[best_r])
        out.append({"s": s_pick, "position": best_i,
                    "nnd": best_v * math.sqrt(s_pick),
                    "score": best_v})
        for r, sc in enumerate(scores):
            s_r = int(ladder[r])
            lo = max(0, best_i - s_r + 1)
            hi = min(sc.size, best_i + s_pick)
            sc[lo:hi] = -np.inf
    return out
