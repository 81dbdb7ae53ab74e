"""Shared kernel utilities: padding, grid math, backend detection."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: precision of every f32 tile contraction: the operands stay f32 on the
#: TPU's MXU, whose default would round them to bf16 (no effect on CPU)
F32_DOT = jax.lax.Precision.HIGHEST


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def pad_to(x, multiple: int, axis: int = 0, value=0.0):
    """Pad `x` along `axis` to the next multiple of `multiple`."""
    n = x.shape[axis]
    target = ceil_div(n, multiple) * multiple
    if target == n:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - n)
    return jnp.pad(x, widths, constant_values=value)


def pad_block_operands(win, mu, sig, ids, *, rows: int,
                       lanes: int | None = None):
    """MXU-align one window block (win, mu, sig, ids).

    Rows go to a multiple of ``rows`` and window lanes to a multiple
    of ``lanes`` (zero lanes don't change dot products).  Padded stats
    are (mu=0, sig=1) and padded ids are -1, so every extra lane comes
    back masked to +inf and can be sliced off.  This is THE alignment
    invariant for window-block pallas kernels — keep all of them on it.
    """
    if lanes is not None:
        win = pad_to(win, lanes, axis=1)
    win = pad_to(win, rows, axis=0)
    rows_p = win.shape[0]
    return (win, pad_to(mu, rows_p), pad_to(sig, rows_p, value=1.0),
            pad_to(ids, rows_p, value=-1))


def raw_d2_from_dots(dots, nrm_q, nrm_c):
    """Raw-Euclidean squared-distance tile from a dot-product tile via
    the norm identity ``||q||² + ||c||² - 2<q,c>`` (clamped at 0) —
    the one place the raw-mode inversion is spelled (the engine's
    masking runs *after* this, so poisoned pad lanes still retire)."""
    return jnp.maximum(nrm_q[:, None] + nrm_c[None, :] - 2.0 * dots,
                       0.0)


def default_interpret() -> bool:
    """Pallas kernels execute for real only on TPU; elsewhere interpret."""
    return jax.default_backend() != "tpu"


def sum_block(s_max: int) -> int:
    """Points per block of :func:`series_csums`: a power of two, at
    least 1024 and at least the longest window, so that a window spans
    at most two blocks."""
    return max(1024, 1 << (int(s_max) - 1).bit_length())


def series_csums(series, s: int):
    """Block-centred prefix sums of a series for windows of ``s``
    points, the one pass every sliding-stats consumer derives from:
    ``(c, e1, e2, f1, f2)``.

    The series (f32) is cut into blocks of ``B`` = :func:`sum_block`
    points.  ``c`` (nb,) is the mean of each block's first window;
    ``e1``/``e2`` (nb, B + 1) are the exclusive prefix sums of the
    block's points less ``c`` and of their squares, and ``f1``/``f2``
    (nb, s) those of the first ``s - 1`` points of the next block, less
    the same ``c``.  So every sum has the size of one block's
    deviations, whatever the series' length or level: prefix sums over
    the whole series grow with both, and cost an f32 window statistic a
    digit for every tenfold.  A window's sums read only its own points
    and its block's first window, which every window of the block that
    holds real data covers or follows, so padding after the data never
    reaches them."""
    x = jnp.asarray(series, dtype=jnp.float32)
    s = int(s)
    B = sum_block(s)
    nb = ceil_div(x.shape[0], B)
    blocks = jnp.pad(x, (0, nb * B - x.shape[0])).reshape(nb, B)
    nxt = jnp.pad(blocks[1:, :s - 1], ((0, 1), (0, 0)))
    c = jnp.mean(blocks[:, :s], axis=1)[:, None]

    def psum(v):
        return jnp.pad(jnp.cumsum(v, axis=1), ((0, 0), (1, 0)))

    y, z = blocks - c, nxt - c
    return c[:, 0], psum(y), psum(y * y), psum(z), psum(z * z)


def stats_from_csums(sums, s: int, n: int):
    """(mu, clamped sigma, raw ||window||²) of the first ``n`` windows
    of length ``s`` from :func:`series_csums` made for ``s``.  THE
    sliding-stats formula — ``sliding_stats_jnp`` and the pan-length
    ladder both delegate here, so per-rung stats are bit-identical to
    the single-length engine's by construction.

    Window ``i = b B + j`` is summed about ``c[b]``: its points in
    block ``b`` from ``e``, then the ``m = j + s - B`` points it reaches
    into block ``b + 1`` from ``f``."""
    c, e1, e2, f1, f2 = sums
    nb, B = e1.shape[0], e1.shape[1] - 1
    s = int(s)

    def window(e, f):
        last = jnp.broadcast_to(e[:, B:], (nb, s - 1))
        head = jnp.concatenate([e[:, s:], last], axis=1) - e[:, :B]
        return (head + jnp.pad(f[:, 1:], ((0, 0), (B - s + 1, 0)))
                ).reshape(-1)[:n]

    s1, s2 = window(e1, f1), window(e2, f2)
    cb = jnp.repeat(c, B)[:n]
    dev = s1 / s
    var = jnp.maximum(s2 / s - dev * dev, 0.0)
    return (cb + dev, jnp.maximum(jnp.sqrt(var), 1e-10),
            s2 + cb * (2.0 * s1 + s * cb))


def sliding_stats_jnp(series, s: int):
    """jnp twin of windows.sliding_stats (float32 path, clamped sigma)."""
    x = jnp.asarray(series, dtype=jnp.float32)
    n = x.shape[0] - s + 1
    mu, sigma, _ = stats_from_csums(series_csums(x, s), s, n)
    return mu, sigma


def windows_jnp(series, s: int):
    """(N, s) materialized windows (oracle-side only)."""
    x = jnp.asarray(series)
    n = x.shape[0] - s + 1
    idx = jnp.arange(n)[:, None] + jnp.arange(s)[None, :]
    return x[idx]


def znorm_d2_formula(dots, s, mu_q, sig_q, mu_c, sig_c):
    """Eq. (3) squared distance from raw dot products (broadcasting)."""
    return znorm_d2_cols(dots, s, mu_q[:, None], sig_q[:, None],
                         mu_c[None, :], sig_c[None, :])


def znorm_d2_cols(dots, s, mu_q, sig_q, mu_c, sig_c):
    """:func:`znorm_d2_formula` on stats already shaped to broadcast
    against the (Bq, Bc) tile: query stats (Bq, 1), candidate stats
    (1, Bc).  Pallas TPU kernels carry their stats in these 2-D
    layouts, since Mosaic does not accept 1-D per-window blocks."""
    corr = (dots - s * mu_q * mu_c) / (s * sig_q * sig_c)
    return jnp.maximum(2.0 * s * (1.0 - corr), 0.0)


def exclusion_mask(qid, cid, s: int, n_valid: int):
    """Self-match band + padding lanes (ids outside [0, n_valid)) of
    1-D id vectors."""
    return exclusion_mask_cols(qid[:, None], cid[None, :], s, n_valid)


def exclusion_mask_cols(qi, cj, s: int, n_valid: int):
    """:func:`exclusion_mask` on ids shaped (Bq, 1) and (1, Bc)."""
    return ((jnp.abs(qi - cj) < s) | (qi < 0) | (qi >= n_valid)
            | (cj < 0) | (cj >= n_valid))


def to_np(x) -> np.ndarray:
    return np.asarray(jax.device_get(x))
