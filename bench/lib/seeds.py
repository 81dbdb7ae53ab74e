"""Seeds derived from a run's ``--seed``."""
from __future__ import annotations

import numpy as np


def sub_seed(seed: int, *path: int) -> int:
    """A 32-bit seed derived from ``seed`` and an index path, so every
    recording or tenant of a run gets its own stream of numbers."""
    entropy = [int(seed) & (2 ** 64 - 1)] + [int(p) for p in path]
    return int(np.random.SeedSequence(entropy).generate_state(1)[0])
