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
row of the full grid does.  Inside the live grid every tile is
computed: the d(a, b) = d(b, a) triangle would need a column
accumulator revisited across the whole grid.

Residency: per grid step, two chunks (1, W) and the per-window stats
of both blocks are double-buffered in VMEM, the query tile is cached in
a (block, s_pad) scratch for the whole row of the grid, and the (block, W)
rotation staging plus the (block, block) distance tile are temporaries
— about 3 MB at block=256, s_pad=384, independent of the series length.
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


def _mp_rows_kernel(qc_ref, qmu_ref, qsig_ref, qid_ref,
                    cc_ref, cmu_ref, csig_ref, cid_ref,
                    dmin_ref, darg_ref, qtile_ref, *,
                    s: int, s_pad: int, block: int, n_valid: int):
    @pl.when(pl.program_id(1) == 0)     # first step of query row i
    def _init():
        qtile_ref[...] = _window_tile(qc_ref[...], block, s, s_pad)
        dmin_ref[...] = jnp.full((block, 1), BIG, jnp.float32)
        darg_ref[...] = jnp.zeros((block, 1), jnp.int32)

    ctile = _window_tile(cc_ref[...], block, s, s_pad)
    dots = lax.dot_general(qtile_ref[...], ctile,
                           (((1,), (1,)), ((), ())), precision=F32_DOT,
                           preferred_element_type=jnp.float32)
    d2 = znorm_d2_cols(dots, s, qmu_ref[...], qsig_ref[...],
                       cmu_ref[...], csig_ref[...])
    cid = cid_ref[...]
    d2 = jnp.where(exclusion_mask_cols(qid_ref[...], cid, s, n_valid),
                   BIG, d2)
    tmin = jnp.min(d2, axis=1, keepdims=True)
    # first minimizing candidate (argmin's tie rule, without argmin)
    targ = jnp.min(jnp.where(d2 == tmin, cid, INT_MAX), axis=1,
                   keepdims=True)
    cur = dmin_ref[...]
    take = tmin < cur
    dmin_ref[...] = jnp.where(take, tmin, cur)
    darg_ref[...] = jnp.where(take, targ, darg_ref[...])


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
        live = (jnp.arange(nbq) < nq)[:, None, None]
        dmin = jnp.where(live, dmin, BIG)
        darg = jnp.where(live, darg, 0)
    return dmin.reshape(-1), darg.reshape(-1)


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
