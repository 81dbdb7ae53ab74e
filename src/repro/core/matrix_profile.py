"""Exact matrix profile in JAX — the SCAMP-class baseline (Fig. 6).

All tile math routes through the shared distance-tile engine
(``core/tiles.TileEngine``), so the backend is pluggable:
  * ``xla``    — blocked lax.map sweep (fast on CPU, used by benches)
  * ``pallas`` — kernels/mpblock (window tiles rebuilt in VMEM from
                 per-block series chunks; the TPU target, validated in
                 interpret mode)
  * ``numpy``  — host reference (parity tests)
``backend="jnp"`` is kept as a legacy alias of ``xla``.

Also exposes ``discords_via_matrix_profile`` so SCAMP can answer the
same k-discord question as the other algorithms (profile -> top-k
non-overlapping maxima).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from .result import DiscordResult
from .tiles import (TileEngine, resolve_backend, self_join_lanes,
                    topk_nonoverlapping)


# standalone one-shot baseline kept session-free on purpose (the
# engine's bucketed ("profile", ...) plan is the cached path); jax's
# own cache keys this per static tuple.  # analysis: ignore[untracked-jit]
@functools.partial(jax.jit,
                   static_argnames=("s", "block", "backend", "interpret"))
def _mp_jit(series, *, s, block, backend, interpret):
    eng = TileEngine(series, s, block=block, backend=backend)
    return eng.profile(interpret=interpret)


def matrix_profile_jax(series, s: int, *, block: int = 256,
                       backend: str | None = None,
                       interpret: bool | None = None):
    """(nnd, neighbor) arrays for every window.

    ``interpret`` is a pallas-only debug override (see
    ``TileEngine.profile``).
    """
    backend = resolve_backend(backend)
    d2, arg = _mp_jit(jnp.asarray(np.asarray(series), jnp.float32),
                      s=s, block=block, backend=backend,
                      interpret=interpret)
    return jnp.sqrt(d2), arg


def discords_via_matrix_profile(series, s: int, k: int = 1, *,
                                block: int = 256,
                                backend: str | None = None
                                ) -> DiscordResult:
    t0 = time.perf_counter()
    backend = resolve_backend(backend)
    d, arg = matrix_profile_jax(series, s, block=block, backend=backend)
    prof = np.asarray(d, np.float64)
    n = prof.shape[0]
    pos, vals = topk_nonoverlapping(prof, k, s)
    # swept tile lanes (docs/cps.md): the block-aligned grid's square,
    # or its upper triangle where the pallas mpblock kernel sweeps that
    lanes = self_join_lanes(-(-n // block), block, backend)
    return DiscordResult(positions=pos, nnds=vals,
                         calls=lanes,
                         n=n, s=s, method=f"scamp[{backend}]",
                         runtime_s=time.perf_counter() - t0,
                         tile_lanes=lanes,
                         extra={"backend": backend,
                                "tile_lanes": lanes})
