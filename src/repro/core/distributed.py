"""Multi-device discord search with shard_map — parallel HST/DRAG.

Parallelizing HST is the paper's own stated future work (Sec. 5); this
module is the framework's beyond-paper contribution on Plane A.  Two
sweeps, both exact:

1. The ring matrix profile — the SCAMP-class full profile, distributed.
   Every device owns one *query* shard of windows and one *candidate*
   shard, the same window set: its window blocks are dealt to the
   devices block-cyclically (device ``d`` holds blocks ``d, d + ndev,
   ...``; ``TileEngine.window_set``), so a record's live windows spread
   evenly over the ring, whatever its length in the bucket.  The
   candidate shards travel around the ring with ``lax.ppermute`` while
   each device folds the visiting shard into its queries' running (min,
   argmin).  After ``ndev`` hops every pair has been examined exactly
   once.  On ``pallas`` each hop sweeps only the shards' leading live
   blocks, ``ceil(live blocks / ndev)`` a side on every device, so the
   hops stay in step and the shard padding is never swept.  This is
   DADD's disk-page model mapped to a TPU pod: the "disk" is the other
   devices' HBM (DESIGN.md §7.5), and the permute traffic overlaps
   with the local MXU tile work.

   Since the session fold-in (docs/ARCHITECTURE.md) the ring sweep is
   a first-class *plan kind* of :class:`repro.core.engine.DiscordEngine`
   — length-bucketed, plan-cached under ``(kind, s, bucket,
   mesh-shape)``, serving batched and streaming traffic.  This module
   keeps the shard-local hop body (:func:`_ring_mp_shard`, reused by
   the engine's plans) and thin wrappers (``ring_matrix_profile``,
   ``distributed_discords``) that route through a session.

2. ``drag_discords`` — the DRAG/DADD two-phase search, distributed:
   phase 1 sweeps the ring once with *early block abandonment* at a
   threshold ``r`` (each device kills its local candidates whose running
   nnd drops below ``r``), phase 2 ranks the survivors' exact nnds.
   With a well-chosen ``r`` (the paper's sampling recipe) phase 1 kills
   ~everything and total work approaches O(N²/ndev) *scanned* but with
   the block-abandon short-circuit most tiles are skipped.  The retry
   loop is data-dependent (r halves until k survivors), so DRAG stays a
   standalone sweep dispatched by the engine rather than a cached plan.

Exactness argument: both sweeps only ever *lower* upper bounds by real
distance evaluations over the complete candidate set, so the returned
maxima coincide with the serial algorithms' (tested in
tests/test_distributed.py against brute force).
"""
from __future__ import annotations

import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.sharding import SERIES_AXIS as AXIS, series_mesh
from .result import DiscordResult
from .tiles import (TileBlock, resolve_backend, set_row_mins, tile_d2,
                    tile_mins, topk_nonoverlapping)

#: legacy name of :func:`repro.parallel.sharding.series_mesh`
data_mesh = series_mesh


# ----------------------------------------------------------------------
# shared tile math (Eq. 3 on a q-block x c-block tile) — routed through
# the pluggable distance-tile engine; the ring only moves the blocks
# ----------------------------------------------------------------------
def _tile_d2(qwin, qmu, qsig, qid, cwin, cmu, csig, cid, s, n,
             backend: str):
    return tile_d2(TileBlock(qwin, qmu, qsig, qid),
                   TileBlock(cwin, cmu, csig, cid),
                   s=s, n_valid=n, backend=backend)


def _pack_blocks(series: np.ndarray, s: int, ndev: int):
    """Host-side prep: per-device window blocks + stats, padded."""
    x = np.asarray(series, dtype=np.float32)
    n = x.shape[0] - s + 1
    per = -(-n // ndev)
    n_pad = per * ndev
    ids = np.arange(n_pad, dtype=np.int32)
    x_pad = np.pad(x, (0, max(0, n_pad + s - 1 - x.shape[0])))
    win = np.lib.stride_tricks.sliding_window_view(x_pad, s)[:n_pad]
    csum = np.concatenate([[0.0], np.cumsum(x_pad, dtype=np.float64)])
    csum2 = np.concatenate([[0.0], np.cumsum(x_pad.astype(np.float64) ** 2)])
    mu = ((csum[s:s + n_pad] - csum[:n_pad]) / s).astype(np.float32)
    var = (csum2[s:s + n_pad] - csum2[:n_pad]) / s - mu.astype(np.float64) ** 2
    sig = np.sqrt(np.maximum(var, 0.0)).astype(np.float32)
    sig = np.maximum(sig, 1e-10)
    return win, mu, sig, ids, n, per


# ----------------------------------------------------------------------
# 1) ring matrix profile
# ----------------------------------------------------------------------
def _ring_mp_shard(qbody, qmu, qsig, qid, s: int, n: int, ndev: int,
                   backend: str, block: int, hop_blocks):
    """Per-shard body: local queries fixed; candidates orbit the ring.
    The arguments are the shard of a ``TileEngine.window_set``;
    ``hop_blocks`` (int32, may be traced) bounds each hop's grid to
    the leading blocks of both shards on ``pallas``, and must cover
    every live block of every shard."""
    q = (qbody, qmu, qsig, qid)
    perm = [(i, (i + 1) % ndev) for i in range(ndev)]

    def hop(carry, _):
        c, best, barg = carry
        tmin, targ = set_row_mins(q, c, s=s, n_valid=n, block=block,
                                  backend=backend, live=hop_blocks)
        take = tmin < best
        best = jnp.where(take, tmin, best)
        barg = jnp.where(take, targ, barg)
        c = tuple(lax.ppermute(a, AXIS, perm) for a in c)
        return (c, best, barg), None

    init = (q, jnp.full(qid.shape[0], jnp.inf, jnp.float32),
            jnp.full(qid.shape[0], -1, jnp.int32))
    (_c, best, barg), _ = lax.scan(hop, init, None, length=ndev)
    return best, barg


def ring_matrix_profile(series, s: int, *, mesh: Optional[Mesh] = None,
                        backend: Optional[str] = None
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact distributed matrix profile: (nnd, neighbor) per window.

    Thin wrapper: builds a one-shot ring session and runs its
    plan-cached mesh sweep (hold a ``DiscordEngine`` yourself to reuse
    the compiled plan across calls)."""
    from .engine import DiscordEngine
    from .spec import SearchSpec
    eng = DiscordEngine(SearchSpec(s=s, method="ring", backend=backend),
                        mesh=mesh)
    prof, ngh, *_ = eng._ring_profile(series, s)
    return prof, ngh


# ----------------------------------------------------------------------
# 2) DRAG two-phase distributed discord search
# ----------------------------------------------------------------------
def _drag_shard(qwin, qmu, qsig, qid, r: float, s: int, n: int,
                ndev: int, backend: str):
    """Phase-1 body: ring sweep with block-level abandonment at ``r``.

    A query whose running nnd drops below ``r`` is dead; once every
    query in the local block is dead the remaining hops only forward the
    ring traffic (the tile compute is ``lax.cond``-ed away — this is the
    paper's early-abandon mapped to block granularity).
    """
    perm = [(i, (i + 1) % ndev) for i in range(ndev)]

    def hop(carry, _):
        cwin, cmu, csig, cid, best, barg, alive = carry

        def live_tile(args):
            best, barg = args
            d2 = _tile_d2(qwin, qmu, qsig, qid, cwin, cmu, csig, cid,
                          s, n, backend)
            m = tile_mins(d2, qid, cid)
            tmin, targ = m.row_min, m.row_arg
            take = tmin < best
            return jnp.where(take, tmin, best), \
                jnp.where(take, targ, barg)

        best, barg = lax.cond(jnp.any(alive), live_tile,
                              lambda a: a, (best, barg))
        alive = best >= r * r          # d2-space threshold
        cwin = lax.ppermute(cwin, AXIS, perm)
        cmu = lax.ppermute(cmu, AXIS, perm)
        csig = lax.ppermute(csig, AXIS, perm)
        cid = lax.ppermute(cid, AXIS, perm)
        return (cwin, cmu, csig, cid, best, barg, alive), None

    init = (qwin, qmu, qsig, qid,
            jnp.full(qwin.shape[0], jnp.inf, jnp.float32),
            jnp.full(qwin.shape[0], -1, jnp.int32),
            jnp.ones(qwin.shape[0], bool))
    carry, _ = lax.scan(hop, init, None, length=ndev)
    _, _, _, _, best, barg, alive = carry
    return best, barg, alive


def drag_discords(series, s: int, k: int = 1, *, r: Optional[float] = None,
                  mesh: Optional[Mesh] = None, seed: int = 0,
                  backend: Optional[str] = None) -> DiscordResult:
    """Distributed DRAG: threshold sweep then exact ranking.

    ``r`` defaults to the paper's sampling recipe (Sec 4.4): exact
    k-discord nnd on a ~1% sample, scaled by 0.99.  If ``r`` proves too
    large (fewer than k survivors) the search re-runs with r/2 — the
    exact failure mode the paper describes, made self-healing.
    """
    t0 = time.perf_counter()
    mesh = mesh or data_mesh()
    ndev = mesh.devices.size
    backend = resolve_backend(backend)
    if r is None:
        from .serial.dadd import pick_r_by_sampling
        r = 0.99 * pick_r_by_sampling(np.asarray(series, np.float64), s,
                                      k, seed=seed)
    win, mu, sig, ids, n, per = _pack_blocks(series, s, ndev)
    sh = NamedSharding(mesh, P(AXIS))
    sh2 = NamedSharding(mesh, P(AXIS, None))
    args = (jax.device_put(win, sh2), jax.device_put(mu, sh),
            jax.device_put(sig, sh), jax.device_put(ids, sh))

    retries = 0
    while True:
        body = functools.partial(_drag_shard, r=float(r), s=s, n=n,
                                 ndev=ndev, backend=backend)
        # DRAG's data-dependent retry regeometries (r shrinks until
        # the alive set fits) — the shard body is a new closure each
        # round, so no engine plan cache can hold it.
        # analysis: ignore[untracked-jit]
        f = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(AXIS, None), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(P(AXIS), P(AXIS), P(AXIS)), check_vma=False))
        d2, arg, alive = f(*args)
        d = np.sqrt(np.asarray(d2)[:n])
        alive = np.asarray(alive)[:n]
        prof = np.where(alive, d, -np.inf)
        pos, vals = topk_nonoverlapping(prof, k, s)
        if len(pos) >= k or r <= 1e-6 or retries >= 6:
            break
        r = r / 2.0           # self-healing re-run (paper Sec 4.4)
        retries += 1

    lanes = int(n) * int(per) * ndev         # scanned-lane upper bound
    return DiscordResult(
        positions=pos, nnds=vals, calls=lanes,
        n=n, s=s, method=f"drag[{ndev}dev]",
        runtime_s=time.perf_counter() - t0, tile_lanes=lanes,
        extra={"r": float(r), "retries": retries, "tile_lanes": lanes,
               "survivors": int(alive.sum()), "ndev": ndev})


def distributed_discords(series, s: int, k: int = 1, *,
                         mesh: Optional[Mesh] = None,
                         backend: Optional[str] = None) -> DiscordResult:
    """Exact k discords from the ring matrix profile (SCAMP-class).

    Thin wrapper over the session layer: one-shot
    ``DiscordEngine(SearchSpec(method="ring"), mesh=...).search`` —
    hold the engine yourself to amortize the compiled ring plan."""
    from .engine import DiscordEngine
    from .spec import SearchSpec
    eng = DiscordEngine(SearchSpec(s=s, k=k, method="ring",
                                   backend=backend), mesh=mesh)
    return eng.search(series)
