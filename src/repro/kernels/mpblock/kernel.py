"""Pallas TPU kernels: matrix-profile tiles with in-kernel window build.

The distance hot spot without the (N, s) window matrix in HBM.  A
``block`` of consecutive windows is carried as one short *chunk* of the
raw series, ``W = chunk_width(block, s_pad)`` samples long (the block's
``block + s - 1`` samples, rounded up to whole 128-lane tiles) and
stored reversed.  The kernel rebuilds the block's (block, s_pad) window
tile in VMEM with a single strided lane rotation (``pltpu.roll`` with
``stride=1``: row ``b`` is the chunk rotated by ``b``), zeroes the lanes
past ``s``, and contracts two such tiles on the MXU.  Window lanes come
out in reversed sample order; both sides of every contraction use the
same order, so the dot products are unchanged.

``mp_block_pallas`` runs the (query block i, candidate block j) grid
with ``j`` innermost.  Each grid step folds its tile's row (min,
argmin) into output block ``i``, which only consecutive steps visit, so
the accumulator never depends on an output block being read back from
HBM.  The grid's bounds may be traced live block counts ``(nq, nc)``:
a length-bucketed series passes the blocks that hold a window of the
record, so the bucket's padding square is never swept.  Every tile
past them is wholly padding (ids -1 or >= n_valid) and could only
fold +inf into a row, never take, so the live rows are the same bit
for bit; the query rows past ``nq`` come back (+inf, 0), as a padding
row of the full grid does.  The ring's hops, which join two different
window sets, run it.

The self-join uses d(a, b) = d(b, a): ``mp_tri_pallas`` sweeps the
upper triangle of tiles in ascending rows, each pair once, and keeps a
column accumulator of every window in VMEM, which persists across the
whole grid, so no output block is ever revisited: by the time row
``i`` starts, its column minima are final and seed its row
accumulator.  ``mp_rows_pallas`` gives listed rows of the same sweep
bit for bit.  Buckets whose step table or accumulator would not fit
(:func:`tri_fits`) keep the square.

Residency: per grid step, two chunks (1, W) and the per-window stats
of both blocks are double-buffered in VMEM, the query tile is cached in
a (block, s_pad) scratch for the whole row of the grid, and the (block, W)
rotation staging plus the (block, block) distance tile are temporaries
— about 3 MB at block=256, s_pad=384, independent of the series length.
The triangle adds its column accumulator, 8 bytes a window (1 MB for
the 512 blocks of a 2^17 bucket).
HBM holds the chunks (W / block times the series, 2.5x at those sizes)
and the per-window stats.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import (F32_DOT, ceil_div, exclusion_mask_cols,
                      pad_block_operands, pad_to, znorm_d2_cols)

BIG = float("inf")
LANES = 128
INT_MAX = jnp.iinfo(jnp.int32).max


def lane_pad(s: int) -> int:
    """Window width ``s`` rounded up to whole 128-lane tiles."""
    return ceil_div(s, LANES) * LANES


def chunk_width(block: int, s_pad: int) -> int:
    """Samples per reversed chunk: ``block + s_pad - 1`` rounded up to
    whole lane tiles, enough for every rotation of the window tile."""
    return ceil_div(block + s_pad - 1, LANES) * LANES


def reversed_chunk(seg, width: int):
    """One reversed chunk from a natural-order series segment (zero
    filled past its end)."""
    return pad_to(seg, width)[:width][::-1]


def block_chunks(series, nb: int, block: int, s: int):
    """(nb, W) reversed chunks of ``nb`` consecutive window blocks:
    row ``b`` is ``series[b*block : b*block + W]`` reversed, with zeros
    past the end of ``series``."""
    width = chunk_width(block, lane_pad(s))
    x = jnp.pad(series, (0, max(0, nb * block + width - series.shape[0])))
    idx = (jnp.arange(nb)[:, None] * block
           + jnp.arange(width - 1, -1, -1)[None, :])
    return x[idx]


def _window_tile(chunk, block: int, s: int, s_pad: int):
    """(block, s_pad) window tile of one reversed chunk ``(1, W)``.

    Rolling row ``b`` right by ``b`` puts sample ``b + t`` of the
    natural series at lane ``W - 1 - t``; the last ``s_pad`` lanes are
    the tile, and lanes holding ``t >= s`` are zeroed (with ``where``,
    so a non-finite pad sample cannot leak through ``0 * x``).
    """
    width = chunk.shape[1]
    rows = pltpu.roll(jnp.broadcast_to(chunk, (block, width)), 0, 1,
                      stride=1, stride_axis=0)
    tile = rows[:, width - s_pad:]
    lane = lax.broadcasted_iota(jnp.int32, (block, s_pad), 1)
    return jnp.where(lane >= s_pad - s, tile, 0.0)


def _tile_d2(qtile_ref, cc_ref, qmu_ref, qsig_ref, qid_ref, cmu_ref,
             csig_ref, cid_ref, *, s: int, s_pad: int, block: int,
             n_valid: int):
    """(masked (block, block) d2 tile, candidate ids (1, block)) of the
    query window tile in ``qtile_ref`` (rows) against the candidate
    block built from its chunk (lanes).  Every kernel here computes a
    pair through this one function, with the lower block on the query
    side wherever a pair is computed once, so a pair reads the same
    bits whichever of those kernels computes it."""
    ctile = _window_tile(cc_ref[...], block, s, s_pad)
    dots = lax.dot_general(qtile_ref[...], ctile,
                           (((1,), (1,)), ((), ())), precision=F32_DOT,
                           preferred_element_type=jnp.float32)
    d2 = znorm_d2_cols(dots, s, qmu_ref[...], qsig_ref[...],
                       cmu_ref[...], csig_ref[...])
    cid = cid_ref[...]
    return (jnp.where(exclusion_mask_cols(qid_ref[...], cid, s, n_valid),
                      BIG, d2), cid)


def _fold_rows(d2, cid, dmin_ref, darg_ref):
    """Fold a tile's row (min, first minimizing candidate) into the
    (block, 1) row accumulator; strict ``<`` keeps the earlier fold on
    a tie, so folding candidates in ascending id order keeps the
    lowest minimizing id (argmin's tie rule, without argmin)."""
    tmin = jnp.min(d2, axis=1, keepdims=True)
    targ = jnp.min(jnp.where(d2 == tmin, cid, INT_MAX), axis=1,
                   keepdims=True)
    cur = dmin_ref[...]
    take = tmin < cur
    dmin_ref[...] = jnp.where(take, tmin, cur)
    darg_ref[...] = jnp.where(take, targ, darg_ref[...])


def _fold_cols(d2, qid, amin_ref, aarg_ref, row):
    """Fold a tile's column (min, first minimizing query) into row
    ``row`` of a lane-dense (rows, block) accumulator, with
    :func:`_fold_rows`' tie rule."""
    tmin = jnp.min(d2, axis=0, keepdims=True)
    targ = jnp.min(jnp.where(d2 == tmin, qid, INT_MAX), axis=0,
                   keepdims=True)
    cur = amin_ref[pl.ds(row, 1), :]
    take = tmin < cur
    amin_ref[pl.ds(row, 1), :] = jnp.where(take, tmin, cur)
    aarg_ref[pl.ds(row, 1), :] = jnp.where(take, targ,
                                           aarg_ref[pl.ds(row, 1), :])


def _lanes_to_rows(v, fill):
    """(1, n) -> (n, 1), exactly: the diagonal of ``v`` broadcast over
    rows (a select and a lane reduction, no transpose)."""
    n = v.shape[1]
    eye = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.min(jnp.where(eye, v, fill), axis=1, keepdims=True)


def _start_row(amin_ref, aarg_ref, row, dmin_ref, darg_ref):
    """Start a row accumulator from row ``row`` of a column
    accumulator: the minima over every lower-indexed candidate block."""
    dmin_ref[...] = _lanes_to_rows(amin_ref[pl.ds(row, 1), :], BIG)
    darg_ref[...] = _lanes_to_rows(aarg_ref[pl.ds(row, 1), :], INT_MAX)


def _mp_rows_kernel(qc_ref, qmu_ref, qsig_ref, qid_ref,
                    cc_ref, cmu_ref, csig_ref, cid_ref,
                    dmin_ref, darg_ref, qtile_ref, *,
                    s: int, s_pad: int, block: int, n_valid: int):
    @pl.when(pl.program_id(1) == 0)     # first step of query row i
    def _init():
        qtile_ref[...] = _window_tile(qc_ref[...], block, s, s_pad)
        dmin_ref[...] = jnp.full((block, 1), BIG, jnp.float32)
        darg_ref[...] = jnp.zeros((block, 1), jnp.int32)

    d2, cid = _tile_d2(qtile_ref, cc_ref, qmu_ref, qsig_ref, qid_ref,
                       cmu_ref, csig_ref, cid_ref, s=s, s_pad=s_pad,
                       block=block, n_valid=n_valid)
    _fold_rows(d2, cid, dmin_ref, darg_ref)


def mp_block_pallas(q_chunks, qmu, qsig, qid, c_chunks, cmu, csig, cid,
                    *, s: int, n_valid: int, block: int, nq=None,
                    nc=None, interpret: bool = True):
    """Row (min d2, argmin id) of every query window over every
    candidate window, with the windows built in-kernel from chunks.

    q_chunks (nbq, W) / c_chunks (nbc, W): reversed chunks from
    :func:`block_chunks`.  qmu/qsig/qid (nbq*block,) and
    cmu/csig/cid (nbc*block,): per-window stats and *global* ids (ids
    outside [0, n_valid) are padding and never win).  The self-join
    passes the same operands on both sides.  ``nq`` / ``nc`` (int32
    scalars, may be traced; None = all ``nbq`` / ``nbc``) bound the
    grid to the leading query / candidate blocks: every block past
    them must hold padding ids only.  Returns (d2 (nbq*block,) f32,
    neighbour id (nbq*block,) i32); a row with no unmasked candidate,
    and every row past ``nq``, keeps (+inf, 0).
    """
    nbq, width = q_chunks.shape
    nbc = c_chunks.shape[0]
    s_pad = lane_pad(s)
    assert width == chunk_width(block, s_pad), (width, block, s_pad)

    def rows(v):
        return v.reshape(nbq, block, 1)

    def cols(v):
        return v.reshape(nbc, 1, block)

    row_spec = pl.BlockSpec((None, block, 1), lambda i, j: (i, 0, 0))
    col_spec = pl.BlockSpec((None, 1, block), lambda i, j: (j, 0, 0))
    kernel = functools.partial(_mp_rows_kernel, s=s, s_pad=s_pad,
                               block=block, n_valid=n_valid)
    dmin, darg = pl.pallas_call(
        kernel,
        name="mp_block",
        grid=(nbq if nq is None else nq, nbc if nc is None else nc),
        in_specs=[
            pl.BlockSpec((None, 1, width), lambda i, j: (i, 0, 0)),
            row_spec, row_spec, row_spec,
            pl.BlockSpec((None, 1, width), lambda i, j: (j, 0, 0)),
            col_spec, col_spec, col_spec,
        ],
        out_specs=(row_spec, row_spec),
        out_shape=(jax.ShapeDtypeStruct((nbq, block, 1), jnp.float32),
                   jax.ShapeDtypeStruct((nbq, block, 1), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((block, s_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q_chunks.reshape(nbq, 1, width), rows(qmu), rows(qsig), rows(qid),
      c_chunks.reshape(nbc, 1, width), cols(cmu), cols(csig), cols(cid))
    if nq is not None:                  # rows past nq were never written
        dmin, darg = _dead_rows(jnp.arange(nbq) < nq, dmin, darg)
    return dmin.reshape(-1), darg.reshape(-1)


#: VMEM the triangle's column accumulator may take: one f32 min and one
#: i32 argmin per window of the bucket, lane-dense
TRI_ACC_BYTES = 8 << 20

#: SMEM the triangle's packed step table may take (a v5e core has 1 MiB
#: of SMEM, which the kernel's other scalars share)
STEP_TABLE_BYTES = 640 << 10


def tri_fits(nb: int, block: int) -> bool:
    """Whether a bucket of ``nb`` window blocks sweeps its self-join
    as the upper triangle (:func:`mp_tri_pallas`, :func:`mp_rows_pallas`):
    its step table, 4 bytes a step, fits :data:`STEP_TABLE_BYTES` and its
    column accumulator, ``2 * nb * lane_pad(block) * 4`` bytes, fits
    :data:`TRI_ACC_BYTES` -- up to 571 blocks, a 2^17 bucket at block
    256.  Larger buckets keep :func:`mp_block_pallas` over the square."""
    return (2 * nb * (nb + 1) <= STEP_TABLE_BYTES
            and 2 * nb * lane_pad(block) * 4 <= TRI_ACC_BYTES)


def _tri_step(t, live):
    """(i, j) of step ``t`` of the upper-triangle grid over ``live``
    blocks: rows ``i`` ascending, ``j = i .. live - 1`` ascending in
    each.  Counted from the end, rows hold 1, 2, 3, ... steps, so the
    ``u``-th step from the end lies in the row ``r`` from the end with
    ``r (r + 1) / 2 <= u``: a square root, then one exact step each way
    for its rounding."""
    u = ((live * (live + 1)) >> 1) - 1 - t

    def tri(v):
        return (v * (v + 1)) >> 1

    r = ((jnp.sqrt((8 * u + 1).astype(jnp.float32)) - 1.0)
         * 0.5).astype(jnp.int32)
    r = jnp.where(tri(r + 1) <= u, r + 1, r)
    r = jnp.where(tri(r) > u, r - 1, r)
    return live - 1 - r, live - 1 - (u - tri(r))


def _tri_table(nb: int, live):
    """Step table of the triangle grid over ``live`` (int32, may be
    traced) of ``nb`` blocks: every step's ``i << 16 | j``, made by
    :func:`_tri_step` outside the kernel and prefetched to SMEM, so a
    step's index maps read one word (:func:`_tri_ij`)."""
    t = jnp.arange(nb * (nb + 1) // 2, dtype=jnp.int32)
    i, j = _tri_step(jnp.minimum(t, ((live * (live + 1)) >> 1) - 1), live)
    return (i << 16) | j


def _tri_ij(t, ref):
    """(i, j) of triangle step ``t`` from the step table ``ref``."""
    v = ref[t]
    return v >> 16, v & 0xFFFF


def _load_query(qc_ref, qmu_ref, qsig_ref, qid_ref, qtile_ref, qmu_s,
                qsig_s, qid_s, *, s: int, s_pad: int, block: int):
    """Query side of a self-join kernel: the window tile of the chunk,
    and the block's lane-dense stats as the (block, 1) rows that
    :func:`_tile_d2` reads."""
    qtile_ref[...] = _window_tile(qc_ref[...], block, s, s_pad)
    qmu_s[...] = _lanes_to_rows(qmu_ref[...], BIG)
    qsig_s[...] = _lanes_to_rows(qsig_ref[...], BIG)
    qid_s[...] = _lanes_to_rows(qid_ref[...], INT_MAX)


def _mp_tri_kernel(step_ref, qc_ref, qmu_ref, qsig_ref, qid_ref,
                   cc_ref, cmu_ref, csig_ref, cid_ref,
                   dmin_ref, darg_ref, qtile_ref, qmu_s, qsig_s, qid_s,
                   amin_ref, aarg_ref, *, s: int, s_pad: int,
                   block: int, n_valid: int):
    t = pl.program_id(0)
    i, j = _tri_ij(t, step_ref)

    @pl.when(t == 0)
    def _clear():
        amin_ref[...] = jnp.full(amin_ref.shape, BIG, jnp.float32)
        aarg_ref[...] = jnp.zeros(aarg_ref.shape, jnp.int32)

    @pl.when(j == i)        # first step of row i: its columns are final
    def _init():
        _load_query(qc_ref, qmu_ref, qsig_ref, qid_ref, qtile_ref, qmu_s,
                    qsig_s, qid_s, s=s, s_pad=s_pad, block=block)
        _start_row(amin_ref, aarg_ref, i, dmin_ref, darg_ref)

    d2, cid = _tile_d2(qtile_ref, cc_ref, qmu_s, qsig_s, qid_s,
                       cmu_ref, csig_ref, cid_ref, s=s, s_pad=s_pad,
                       block=block, n_valid=n_valid)
    _fold_rows(d2, cid, dmin_ref, darg_ref)

    @pl.when(j > i)         # the diagonal tile's rows cover its columns
    def _cols():
        _fold_cols(d2, qid_s[...], amin_ref, aarg_ref, j)


def _self_join_call(kernel, name, index, grid, out_spec, n_out,
                    acc_rows, semantics, chunks, mu, sig, ids, prefetch, *,
                    block: int, s_pad: int, interpret: bool):
    """``pallas_call`` of a self-join kernel whose ``index(*grid_ids,
    ref)`` gives the (query, candidate) blocks of a step: the chunk and
    lane-dense stats of both blocks, from one copy of the operands;
    ``n_out`` row output blocks; scratch for the query tile and its
    (block, 1) stats, and a lane-dense column accumulator of
    ``acc_rows`` blocks."""
    nb, width = chunks.shape

    def q(*a):
        return (index(*a)[0], 0, 0)

    def c(*a):
        return (index(*a)[1], 0, 0)

    blocks = [v.reshape(nb, 1, -1) for v in (chunks, mu, sig, ids)]
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((None, 1, width), q),
                      *[pl.BlockSpec((None, 1, block), q)] * 3,
                      pl.BlockSpec((None, 1, width), c),
                      *[pl.BlockSpec((None, 1, block), c)] * 3],
            out_specs=(out_spec, out_spec),
            scratch_shapes=[pltpu.VMEM((block, s_pad), jnp.float32),
                            pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, 1), jnp.int32),
                            pltpu.VMEM((acc_rows, block), jnp.float32),
                            pltpu.VMEM((acc_rows, block), jnp.int32)]),
        out_shape=(jax.ShapeDtypeStruct((n_out, block, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n_out, block, 1), jnp.int32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        interpret=interpret,
    )(prefetch, *blocks, *blocks)


def mp_tri_pallas(chunks, mu, sig, ids, *, s: int, n_valid: int,
                  block: int, nb_live=None, interpret: bool = True):
    """The self-join's row (min d2, argmin id), each window pair
    computed once: the upper triangle of tiles ``(i, j)``, ``j >= i``.

    Operands as :func:`mp_block_pallas`' self-join passes them, once:
    ``chunks`` (nb, W), ``mu``/``sig``/``ids`` (nb*block,).  The grid
    is 1-D and dense, ``L (L + 1) / 2`` steps for ``L = nb_live``
    (int32, may be traced; None = ``nb``) live blocks, in ascending
    rows ``i`` with ``j`` ascending in each (a step table in SMEM,
    :func:`_tri_table`); block ``i`` is always the query side.  A step
    folds its tile's row minima into output block ``i``, which only
    consecutive steps visit, and, off the diagonal, its column (min,
    first minimizing row id) into a VMEM accumulator of every window,
    lane-dense, that persists across the grid.  Every row ``i' < i``
    has passed column block ``i`` before row ``i`` starts, so row ``i``
    starts from those column minima and not from +inf.  Both folds
    keep the lowest minimizing id, the square's tie rule: the column
    side holds the lower ids and is folded first.  A pair's distance
    can differ from the square's in its last bits, since the square
    also computes it the other way round.  Rows past ``nb_live`` come
    back (+inf, 0).  Buckets past :func:`tri_fits` keep
    :func:`mp_block_pallas`.
    """
    nb, width = chunks.shape
    s_pad = lane_pad(s)
    assert width == chunk_width(block, s_pad), (width, block, s_pad)
    assert tri_fits(nb, block), (nb, block)
    live = jnp.asarray(nb if nb_live is None else nb_live, jnp.int32)
    kernel = functools.partial(_mp_tri_kernel, s=s, s_pad=s_pad,
                               block=block, n_valid=n_valid)
    dmin, darg = _self_join_call(
        kernel, "mp_tri", _tri_ij,
        (nb * (nb + 1) // 2 if nb_live is None
         else (live * (live + 1)) // 2,),
        pl.BlockSpec((None, block, 1),
                     lambda t, ref: (_tri_ij(t, ref)[0], 0, 0)),
        nb, nb, ("arbitrary",), chunks, mu, sig, ids,
        _tri_table(nb, live),
        block=block, s_pad=s_pad, interpret=interpret)
    if nb_live is not None:             # rows past it were never written
        dmin, darg = _dead_rows(jnp.arange(nb) < live, dmin, darg)
    return dmin.reshape(-1), darg.reshape(-1)


def _mp_listed_kernel(rows_ref, qc_ref, qmu_ref, qsig_ref, qid_ref,
                      cc_ref, cmu_ref, csig_ref, cid_ref,
                      dmin_ref, darg_ref, qtile_ref, qmu_s, qsig_s, qid_s,
                      amin_ref, aarg_ref, *, s: int, s_pad: int,
                      block: int, n_valid: int):
    r = rows_ref[pl.program_id(0)]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _clear():
        amin_ref[...] = jnp.full(amin_ref.shape, BIG, jnp.float32)
        aarg_ref[...] = jnp.zeros(aarg_ref.shape, jnp.int32)

    @pl.when(j <= r)        # the query block changes: j, then r
    def _query():
        _load_query(qc_ref, qmu_ref, qsig_ref, qid_ref, qtile_ref, qmu_s,
                    qsig_s, qid_s, s=s, s_pad=s_pad, block=block)

    d2, cid = _tile_d2(qtile_ref, cc_ref, qmu_s, qsig_s, qid_s,
                       cmu_ref, csig_ref, cid_ref, s=s, s_pad=s_pad,
                       block=block, n_valid=n_valid)

    @pl.when(j < r)         # tile (j, r): row r's pairs as its columns
    def _cols():
        _fold_cols(d2, qid_s[...], amin_ref, aarg_ref, 0)

    @pl.when(j == r)
    def _init():
        _start_row(amin_ref, aarg_ref, 0, dmin_ref, darg_ref)

    @pl.when(j >= r)        # tile (r, j): row r's pairs as its rows
    def _rows():
        _fold_rows(d2, cid, dmin_ref, darg_ref)


def mp_rows_pallas(chunks, mu, sig, ids, rows, *, s: int, n_valid: int,
                   block: int, nb_live=None, interpret: bool = True):
    """The rows of :func:`mp_tri_pallas` at the listed query blocks
    ``rows`` (P,) (int32, may be traced), bit for bit: row ``r`` takes
    its pairs with blocks ``j < r`` as the columns of tile ``(j, r)``
    and those with ``j >= r`` as the rows of tile ``(r, j)``, the
    operand roles of the triangle, folded ``j`` ascending with its tie
    rule.  Grid ``(P, nb_live)``; returns ((P*block,) d2, ids), and
    (+inf, 0) for a listed block at or past ``nb_live``."""
    nb, width = chunks.shape
    s_pad = lane_pad(s)
    assert width == chunk_width(block, s_pad), (width, block, s_pad)
    rows = jnp.asarray(rows, jnp.int32)

    def index(p, j, rows_ref):
        r = rows_ref[p]
        return jnp.minimum(j, r), jnp.maximum(j, r)

    kernel = functools.partial(_mp_listed_kernel, s=s, s_pad=s_pad,
                               block=block, n_valid=n_valid)
    dmin, darg = _self_join_call(
        kernel, "mp_rows", index,
        (rows.shape[0], nb if nb_live is None else nb_live),
        pl.BlockSpec((None, block, 1), lambda p, j, rr: (p, 0, 0)),
        rows.shape[0], 1, ("parallel", "arbitrary"), chunks, mu, sig,
        ids, rows, block=block, s_pad=s_pad, interpret=interpret)
    if nb_live is not None:             # such rows were never written
        dmin, darg = _dead_rows(rows < nb_live, dmin, darg)
    return dmin.reshape(-1), darg.reshape(-1)


def _dead_rows(live, dmin, darg):
    """(+inf, 0) in the output blocks where ``live`` (per block) is
    false."""
    live = live[:, None, None]
    return jnp.where(live, dmin, BIG), jnp.where(live, darg, 0)


def _qvc_tile_kernel(q_ref, qmu_ref, qsig_ref, qid_ref,
                     chunk_ref, cmu_ref, csig_ref, cid_ref,
                     d2_ref, ctile_ref, *, s: int, s_pad: int,
                     block: int, n_valid: int):
    """Gathered query rows vs one contiguous candidate block.

    The candidate (block, s_pad) window tile is built *in-kernel* from
    the block's reversed chunk (the same VMEM-resident build as the
    full-profile kernel) on the first grid step and cached for the
    rest, so the HBM side of the tile never materializes block*s
    floats.  The queries arrive lane-reversed and zero-padded to match.
    """
    @pl.when(pl.program_id(0) == 0)
    def _build():
        ctile_ref[...] = _window_tile(chunk_ref[...], block, s, s_pad)

    dots = lax.dot_general(q_ref[...], ctile_ref[...],
                           (((1,), (1,)), ((), ())), precision=F32_DOT,
                           preferred_element_type=jnp.float32)
    d2 = znorm_d2_cols(dots, s, qmu_ref[...], qsig_ref[...],
                       cmu_ref[...], csig_ref[...])
    bad = exclusion_mask_cols(qid_ref[...], cid_ref[...], s, n_valid)
    d2_ref[...] = jnp.where(bad, BIG, d2)


#: query rows per grid step of the gathered-query kernel
QVC_ROWS = 128


def qvc_block_pallas(qwin, qmu, qsig, qid, chunk, cmu, csig, cid, *,
                     s: int, n_valid: int, interpret: bool = True):
    """Masked d2 tile of gathered queries vs a contiguous window block.

    qwin (Bq, s) + stats/ids; chunk (block + s - 1,) raw series slice
    whose windows are built in-kernel; cmu/csig/cid (block,).
    Returns (Bq, block) f32 with +inf at masked lanes.

    Query rows stream through VMEM ``QVC_ROWS`` at a time (8-row
    aligned below that), so any Bq fits.  Rows pad to the step and the
    block to 128 lanes before the kernel; padded ids are -1 so their
    lanes come back +inf and are sliced off.
    """
    bq = qwin.shape[0]
    block = cmu.shape[0]
    s_pad = lane_pad(s)
    rows = QVC_ROWS if bq > QVC_ROWS else 8
    qwin, qmu, qsig, qid = pad_block_operands(qwin, qmu, qsig, qid,
                                              rows=rows, lanes=LANES)
    bq_p = qwin.shape[0]
    blk_q = min(bq_p, QVC_ROWS)
    blk_p = ceil_div(block, LANES) * LANES
    width = chunk_width(blk_p, s_pad)
    kernel = functools.partial(_qvc_tile_kernel, s=s, s_pad=s_pad,
                               block=blk_p, n_valid=n_valid)
    q_col = pl.BlockSpec((blk_q, 1), lambda i: (i, 0))
    c_row = pl.BlockSpec((1, blk_p), lambda i: (0, 0))
    d2 = pl.pallas_call(
        kernel,
        name="qvc_block",
        grid=(bq_p // blk_q,),
        in_specs=[
            pl.BlockSpec((blk_q, s_pad), lambda i: (i, 0)),
            q_col, q_col, q_col,
            pl.BlockSpec((1, width), lambda i: (0, 0)),
            c_row, c_row, c_row,
        ],
        out_specs=pl.BlockSpec((blk_q, blk_p), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bq_p, blk_p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((blk_p, s_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(qwin[:, ::-1], qmu[:, None], qsig[:, None], qid[:, None],
      reversed_chunk(chunk, width)[None, :],
      pad_to(cmu, blk_p)[None, :], pad_to(csig, blk_p, value=1.0)[None, :],
      pad_to(cid, blk_p, value=-1)[None, :])
    return d2[:bq, :block]
