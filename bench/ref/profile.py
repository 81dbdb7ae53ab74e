"""Plain reference: the exact z-normalized nearest-neighbour profile.

For a series of ``L`` points and window ``s`` there are ``n = L - s + 1``
windows.  Each window is z-normalized on its own (two-pass mean and
deviation in f32, deviation floored at 1e-10 as the paper's code does),
and the squared distance of windows ``i`` and ``j`` is
``|z_i|^2 + |z_j|^2 - 2 z_i . z_j``.  Pairs closer than ``s`` (trivial
matches) do not count.  The nearest-neighbour distance of ``i`` is the
square root of its row minimum; the top-k discords are the k largest of
these that do not overlap (the paper's definition).

It imports nothing of the program and takes nothing the program made:
only the series the benchmark generated.  Row minima are taken in
blocks of ``rows`` query windows, spread over the given devices, so the
largest temporary is ``rows x n_pad`` floats.

``precision`` names how the contraction is computed:

* ``"f32"``  f32 operands at ``Precision.HIGHEST``: the reference;
* ``"high"`` f32 operands at ``Precision.HIGH`` (three bf16 passes);
* ``"bf16"`` operands rounded to bf16, one pass, f32 accumulation.

The last two are the controls: the reference in a lower precision,
which a correct run must be told apart from.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PRECISIONS = {
    "f32": (jnp.float32, lax.Precision.HIGHEST),
    "high": (jnp.float32, lax.Precision.HIGH),
    "bf16": (jnp.bfloat16, lax.Precision.DEFAULT),
}
SIGMA_FLOOR = 1e-10
AXIS = "ref"


def _znorm(xp, *, s: int, n_pad: int):
    w = xp[jnp.arange(n_pad)[:, None] + jnp.arange(s)[None, :]]
    c = w - jnp.mean(w, axis=1, keepdims=True)
    sd = jnp.sqrt(jnp.mean(c * c, axis=1, keepdims=True))
    return c / jnp.maximum(sd, SIGMA_FLOOR)


def _row_mins(xp, starts, n, *, s: int, n_pad: int, rows: int,
              precision: str):
    dtype, prec = PRECISIONS[precision]
    z = _znorm(xp, s=s, n_pad=n_pad).astype(dtype)
    zf = z.astype(jnp.float32)
    nrm = jnp.sum(zf * zf, axis=1)
    cols = jnp.arange(n_pad)

    def one(r0):
        q = lax.dynamic_slice_in_dim(z, r0, rows)
        dots = lax.dot_general(q, z, (((1,), (1,)), ((), ())),
                               precision=prec,
                               preferred_element_type=jnp.float32)
        i = r0 + jnp.arange(rows)
        d2 = (lax.dynamic_slice_in_dim(nrm, r0, rows)[:, None]
              + nrm[None, :] - 2.0 * dots)
        bad = ((jnp.abs(i[:, None] - cols[None, :]) < s)
               | (cols[None, :] >= n))
        return jnp.min(jnp.where(bad, jnp.inf, jnp.maximum(d2, 0.0)),
                       axis=1)

    return lax.map(one, starts).reshape(-1)


@functools.lru_cache(maxsize=None)
def _compiled(devices: Tuple, s: int, n_pad: int, rows: int,
              precision: str):
    body = functools.partial(_row_mins, s=s, n_pad=n_pad, rows=rows,
                             precision=precision)
    if len(devices) == 1:
        return jax.jit(body)      # runs where its arguments were put
    mesh = Mesh(np.array(devices), (AXIS,))
    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(), P(AXIS), P()),
                                 out_specs=P(AXIS), check_vma=False))


def nnd_profile(x, s: int, *, precision: str = "f32",
                devices: Optional[Sequence] = None,
                rows: int = 256) -> np.ndarray:
    """Exact nearest-neighbour distance of every window of ``x`` (f64
    on the host; ``inf`` where a window has no non-trivial match)."""
    x = np.asarray(x, np.float64).ravel()
    n = x.shape[0] - s + 1
    if n < 2:
        raise ValueError(f"{x.shape[0]} points hold no pair of "
                         f"{s}-point windows")
    devices = tuple(devices or jax.devices()[:1])
    step = rows * len(devices)
    n_pad = max(1 << (n - 1).bit_length(), step)
    n_pad = -(-n_pad // step) * step
    xp = np.zeros(n_pad + s - 1, np.float32)
    xp[:x.shape[0]] = x
    starts = np.arange(0, n_pad, rows, dtype=np.int32)
    fn = _compiled(devices, int(s), n_pad, int(rows), precision)
    if len(devices) == 1:
        args = [jax.device_put(a, devices[0])
                for a in (xp, starts, np.int32(n))]
    else:
        mesh = Mesh(np.array(devices), (AXIS,))
        rep, sh = NamedSharding(mesh, P()), NamedSharding(mesh, P(AXIS))
        args = [jax.device_put(xp, rep), jax.device_put(starts, sh),
                jax.device_put(np.int32(n), rep)]
    d2 = np.asarray(fn(*args), np.float64)[:n]
    return np.sqrt(d2)


def topk_nonoverlapping(profile: np.ndarray, k: int, s: int
                        ) -> Tuple[List[int], List[float]]:
    """The ``k`` largest finite profile values whose windows do not
    overlap, largest first: positions and values."""
    p = np.asarray(profile, np.float64).copy()
    p[~np.isfinite(p)] = -np.inf
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
