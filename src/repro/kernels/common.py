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


def series_csums(series):
    """Zero-prefixed cumulative sums of x and x² (f32) — the one pass
    every sliding-stats consumer derives from."""
    x = jnp.asarray(series, dtype=jnp.float32)
    return (jnp.concatenate([jnp.zeros(1, x.dtype), jnp.cumsum(x)]),
            jnp.concatenate([jnp.zeros(1, x.dtype),
                             jnp.cumsum(x * x)]))


def stats_from_csums(csum, csum2, s: int, n: int):
    """(mu, clamped sigma, raw ||window||²) of the ``n`` windows of
    length ``s`` from precomputed cumulative sums.  THE sliding-stats
    formula — ``sliding_stats_jnp`` and the pan-length ladder both
    delegate here, so per-rung stats are bit-identical to the
    single-length engine's by construction."""
    winsum = csum[s:s + n] - csum[:n]
    winsum2 = csum2[s:s + n] - csum2[:n]
    mu = winsum / s
    var = jnp.maximum(winsum2 / s - mu * mu, 0.0)
    return mu, jnp.maximum(jnp.sqrt(var), 1e-10), winsum2


def sliding_stats_jnp(series, s: int):
    """jnp twin of windows.sliding_stats (float32 path, clamped sigma)."""
    x = jnp.asarray(series, dtype=jnp.float32)
    n = x.shape[0] - s + 1
    mu, sigma, _ = stats_from_csums(*series_csums(x), s, n)
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
