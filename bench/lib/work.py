"""Useful work of a search, counted from the problem and not from the
grid a kernel happens to sweep.

A series of ``n`` windows of length ``s`` holds ``n (n - 1) / 2``
unordered window pairs, and each pair's dot product is ``s`` multiplies
and ``s`` adds.  A kernel that sweeps the full square, the bucket
padding or padded lanes does more than this; one that exploits the
symmetry does no less.  So a roofline share computed from these counts
cannot pass 100% unless the time leaves out part of the work.
"""
from __future__ import annotations


def useful_pairs(n_windows: int) -> int:
    """Unordered pairs of distinct windows."""
    n = int(n_windows)
    return n * (n - 1) // 2


def useful_flop(n_windows: int, s: int) -> int:
    """Multiply-adds of one dot product per unordered pair, as FLOP."""
    return 2 * int(s) * useful_pairs(n_windows)


def useful_bytes(n_windows: int, s: int) -> int:
    """Least HBM traffic of one profile: the f32 series read once, and
    one f32 distance and one i32 neighbour written per window."""
    n = int(n_windows)
    return 4 * (n + int(s) - 1) + 8 * n


def roofline_s(flop: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of compute at the bf16
    peak and traffic at the HBM peak."""
    return max(flop / peaks["bf16_flop_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
