"""Pallas TPU kernel: fused PAA + SAX digitization (bandwidth-bound).

Computes the packed SAX word of every window in one pass:
  word(i) = sum_j digit(i,j) * alpha^(P-1-j),
  digit(i,j) = #{breakpoints < (boxsum[i + j*w]/w - mu_i) / sigma_i}.

Input is the *box-sum* array (sliding sum of width w = s/P), so the
kernel reads O(N) values instead of touching every point P times; the
digitization is a small unrolled comparison ladder (alpha-1 <= 63
compares) on the VPU.  Grid blocks over windows; boxsum/stats are
loaded with dynamic-offset static-size slices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _paa_sax_kernel(boxsum_ref, mu_ref, sig_ref, words_ref, *,
                    P: int, w: int, alpha: int, block: int,
                    breakpoints: tuple):
    i = pl.program_id(0)
    n0 = i * block
    mu = mu_ref[pl.ds(n0, block)]
    sig = sig_ref[pl.ds(n0, block)]
    inv_sig = 1.0 / sig
    words = jnp.zeros((block,), jnp.int32)
    for j in range(P):                                    # static unroll
        seg = boxsum_ref[pl.ds(n0 + j * w, block)] / w
        val = (seg - mu) * inv_sig
        digit = jnp.zeros((block,), jnp.int32)
        for bp in breakpoints:                            # alpha-1 compares
            digit += (val > bp).astype(jnp.int32)
        words = words * alpha + digit
    words_ref[...] = words


def paa_sax_pallas(boxsum_pad, mu_pad, sig_pad, *, P: int, w: int,
                   alpha: int, breakpoints: tuple, block: int = 256,
                   interpret: bool = True):
    n_pad = mu_pad.shape[0]
    assert n_pad % block == 0
    grid = (n_pad // block,)
    kernel = functools.partial(
        _paa_sax_kernel, P=P, w=w, alpha=alpha, block=block,
        breakpoints=tuple(float(b) for b in breakpoints))
    L = boxsum_pad.shape[0]
    return pl.pallas_call(
        kernel,
        name="paa_sax",
        grid=grid,
        in_specs=[
            pl.BlockSpec((L,), lambda i: (0,)),          # boxsum resident
            pl.BlockSpec((n_pad,), lambda i: (0,)),
            pl.BlockSpec((n_pad,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        interpret=interpret,
    )(boxsum_pad, mu_pad, sig_pad)
