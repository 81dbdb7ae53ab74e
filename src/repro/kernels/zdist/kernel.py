"""Pallas TPU kernel: blocked z-normalized min-distance (the HST hot loop).

One grid step computes a (block_q x block_c) tile of squared
z-normalized distances via the Eq. (3) scalar-product form — a single
MXU matmul plus a rank-1 correction — masks the self-match band, and
folds the tile into per-query running (min, argmin) accumulators.

Blocking: queries stay resident in VMEM across the inner (candidate)
grid dimension; candidate windows stream block by block.  Tile sides
default to 128 = MXU systolic width; ``s`` should be a multiple of 128
on real hardware for full MXU occupancy (ops.py pads).

Layout per grid step (i, j):
  q_ref    (block_q, s)   query windows            VMEM resident over j
  qid_ref  (block_q,)     global query ids (gathered queries -> arbitrary)
  qmu/qsig (block_q,)     query stats
  c_ref    (block_c, s)   candidate windows        streamed
  cmu/csig (block_c,)     candidate stats
  min_ref  (block_q,)     running min d^2          accumulator (out)
  arg_ref  (block_q,)     running argmin           accumulator (out)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import znorm_d2_formula

BIG = float("inf")   # python scalar: must not be a traced constant


def _zdist_tile_kernel(qid_ref, q_ref, qmu_ref, qsig_ref,
                       c_ref, cmu_ref, csig_ref,
                       min_ref, arg_ref, *,
                       s: int, block_c: int, n_valid: int):
    j = pl.program_id(1)
    q = q_ref[...]
    c = c_ref[...]
    dots = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (bq, bc) on the MXU
    d2 = znorm_d2_formula(dots, s, qmu_ref[...], qsig_ref[...],
                          cmu_ref[...], csig_ref[...])

    bq, bc = d2.shape
    qi = qid_ref[...][:, None]                          # (bq, 1) global ids
    cj = j * block_c + jax.lax.broadcasted_iota(jnp.int32, (bq, bc), 1)
    bad = (jnp.abs(qi - cj) < s) | (cj >= n_valid)      # self-match + padding
    d2 = jnp.where(bad, BIG, d2)

    tile_min = jnp.min(d2, axis=1)
    tile_arg = (j * block_c + jnp.argmin(d2, axis=1)).astype(jnp.int32)

    @pl.when(j == 0)
    def _init():
        min_ref[...] = tile_min
        arg_ref[...] = tile_arg

    @pl.when(j > 0)
    def _update():
        cur = min_ref[...]
        take = tile_min < cur
        min_ref[...] = jnp.where(take, tile_min, cur)
        arg_ref[...] = jnp.where(take, tile_arg, arg_ref[...])


def zdist_min_pallas(qids, qwin, qmu, qsig, cwin, cmu, csig, *,
                     s: int, n_valid: int, block_q: int = 128,
                     block_c: int = 128, interpret: bool = True):
    """Min z-norm distance (squared) + argmin from each query window to
    every candidate window.  All inputs pre-padded to block multiples.
    """
    nq, s_pad = qwin.shape
    nc = cwin.shape[0]
    assert nq % block_q == 0 and nc % block_c == 0
    grid = (nq // block_q, nc // block_c)
    kernel = functools.partial(
        _zdist_tile_kernel, s=s, block_c=block_c, n_valid=n_valid)
    out_shape = (
        jax.ShapeDtypeStruct((nq,), jnp.float32),
        jax.ShapeDtypeStruct((nq,), jnp.int32),
    )
    return pl.pallas_call(
        kernel,
        name="zdist_min",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q,), lambda i, j: (i,)),         # qid
            pl.BlockSpec((block_q, s_pad), lambda i, j: (i, 0)),  # q
            pl.BlockSpec((block_q,), lambda i, j: (i,)),         # qmu
            pl.BlockSpec((block_q,), lambda i, j: (i,)),         # qsig
            pl.BlockSpec((block_c, s_pad), lambda i, j: (j, 0)),  # c
            pl.BlockSpec((block_c,), lambda i, j: (j,)),         # cmu
            pl.BlockSpec((block_c,), lambda i, j: (j,)),         # csig
        ],
        out_specs=(
            pl.BlockSpec((block_q,), lambda i, j: (i,)),
            pl.BlockSpec((block_q,), lambda i, j: (i,)),
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(qids, qwin, qmu, qsig, cwin, cmu, csig)
