"""What a traffic runner hands back to the harness.

A runner is the class ``Runner`` of ``bench/runners/<name>.py``, built as
``Runner(cell, seed, devices, log)``.  ``setup()`` makes the data from
the seed and warms up every shape its traffic uses; ``window(seconds,
span)`` runs the measured window and returns a :class:`Window`;
``release()`` drops the program's state; ``check(control=None)``
compares what the window produced with the plain reference (or, with
``control``, the reference in that lower precision in the program's
place) and returns ``{name: {"value", "limit"}}``.  ``requests`` names
what ``attempted`` counts.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict


@dataclass
class Window:
    """What one measured window produced."""
    e2e: Dict[str, float]
    attempted: int
    failed: int
    counters: Dict[str, float] = field(default_factory=dict)
    seconds: float = 0.0
