"""ECG-like recordings with implanted anomalies.

``ecg_like`` and ``with_implanted_anomalies`` are copies of
``repro.data.timeseries`` (kept here so that a change to the program
cannot move the benchmark's inputs).
"""
from __future__ import annotations

import numpy as np


def ecg_like(n: int, *, period: int = 180, noise: float = 0.03,
             seed: int = 0) -> np.ndarray:
    """Periodic spike train resembling an ECG lead (P-QRS-T-ish)."""
    rng = np.random.default_rng(seed)
    t = np.arange(n, dtype=np.float64)
    phase = (t % period) / period
    beat = (1.2 * np.exp(-((phase - 0.30) / 0.012) ** 2)      # R
            - 0.3 * np.exp(-((phase - 0.26) / 0.02) ** 2)     # Q
            - 0.25 * np.exp(-((phase - 0.34) / 0.02) ** 2)    # S
            + 0.25 * np.exp(-((phase - 0.55) / 0.06) ** 2)    # T
            + 0.12 * np.exp(-((phase - 0.12) / 0.05) ** 2))   # P
    return beat + noise * rng.normal(size=n)


def with_implanted_anomalies(x: np.ndarray, *, n_anomalies: int = 1,
                             length: int = 64, amp: float = 1.0,
                             seed: int = 0):
    """Inject localized bumps; returns (series, positions)."""
    rng = np.random.default_rng(seed + 1)
    x = x.copy()
    n = x.shape[0]
    pos = []
    for _ in range(n_anomalies):
        for _try in range(100):
            p = int(rng.integers(length, n - 2 * length))
            if all(abs(p - q) > 4 * length for q in pos):
                break
        bump = amp * np.sin(np.linspace(0, np.pi, length)) \
            * rng.choice([-1.0, 1.0])
        x[p:p + length] += bump
        pos.append(p)
    return x, sorted(pos)


def generate(n: int, seed: int, *, period: int, noise: float,
             anomalies: int, anomaly_length: int,
             anomaly_amp: float) -> np.ndarray:
    """One ECG-like recording of ``n`` points with implanted anomalies."""
    x, _ = with_implanted_anomalies(
        ecg_like(n, period=period, noise=noise, seed=seed),
        n_anomalies=anomalies, length=anomaly_length, amp=anomaly_amp,
        seed=seed)
    return x
