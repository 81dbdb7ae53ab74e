"""The f32 window statistics (``kernels/common.py`` ``series_csums``,
``stats_from_csums``) against a plain f64 reference: each window's
mean and deviation taken over its own points, two passes.

The sums restart at every block, about the mean of the block's first
window, so their f32 rounding stays that of one block whatever the
series' length or level; prefix sums over the whole series lost about
a digit of the deviation for every tenfold in length (2.5e-4 at 520k
points) and more on a large level.  Windows inside a block, across a
block boundary and as long as a block are all covered.
"""
import numpy as np
import pytest

from repro.kernels.common import (series_csums, sliding_stats_jnp,
                                  stats_from_csums, sum_block)


def _reference(x, s):
    w = np.lib.stride_tricks.sliding_window_view(x, s)
    return w.mean(axis=1), w.std(axis=1), (w * w).sum(axis=1)


def _series(n, level, seed):
    rng = np.random.default_rng(seed)
    t = np.arange(n)
    x = level + 0.3 * np.sin(2 * np.pi * t / 180) + 0.05 * rng.normal(size=n)
    # the program's input is f32: compare on the same numbers
    return x.astype(np.float32).astype(np.float64)


@pytest.mark.parametrize("n,s,level", [
    (3_000, 300, 0.0),           # windows inside and across blocks
    (5_000, 1024, 1.0),          # a window as long as a block
    (5_000, 1025, 1.0),          # blocks of 2048
    (200_000, 300, 0.1),         # long: no growth with the length
    (200_000, 300, 50.0),        # a level 100x the spread
])
def test_window_stats_match_f64(n, s, level):
    x = _series(n, level, seed=n + s)
    mu, sig = (np.asarray(a, np.float64) for a in sliding_stats_jnp(x, s))
    nrm = np.asarray(stats_from_csums(series_csums(x, s), s, n - s + 1)[2])
    ref_mu, ref_sig, ref_nrm = _reference(x, s)
    # a few f32 roundings of one block's sums (about 6e-8 each)
    assert np.max(np.abs(mu - ref_mu)) < 1e-7 * max(abs(level), 1.0)
    assert np.max(np.abs(sig - ref_sig) / ref_sig) < 2e-6
    assert np.max(np.abs(nrm - ref_nrm) / ref_nrm) < 2e-6


def test_padding_after_the_data_never_reaches_a_window():
    """Each window reads its own points and its block's first window
    only: NaN after the data leaves every window of the data as with
    zeros there, bit for bit."""
    s, live = 300, 3_700
    x = _series(5_000, 0.5, seed=1)
    zeros, poison = x.copy(), x.copy()
    zeros[live:], poison[live:] = 0.0, np.nan
    n = live - s + 1
    for a, b in zip(sliding_stats_jnp(zeros, s), sliding_stats_jnp(poison, s)):
        assert np.array_equal(np.asarray(a)[:n], np.asarray(b)[:n])


def test_block_holds_the_longest_window():
    assert sum_block(300) == 1024
    assert sum_block(1024) == 1024
    assert sum_block(1025) == 2048
