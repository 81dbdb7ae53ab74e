"""The program's own host spans in a traced window (``engine.*``, listed
in the docstring of ``repro.core.engine``), on the profiler's clock.

A search is an outermost ``engine.search`` span wholly inside the
window: the window's edge may cut one, and an entry that calls another
nests a second one inside.  Like ``bench/lib/trace.py``, this works on
plain ``(name, start_ns, end_ns)`` tuples.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

from bench.lib.trace import Interval, TraceSummary, union

SEARCH = "engine.search"
WAIT = "engine.wait"


def searches(tr: TraceSummary
             ) -> List[Tuple[Interval, List[Tuple[int, int]]]]:
    """Each search of the window, with the union of the ``engine.wait``
    spans inside it."""
    spans = [e for e in tr.host if e[0] in (SEARCH, WAIT)]
    found = [e for e in spans
             if e[0] == SEARCH and tr.lo < e[1] and e[2] < tr.hi]
    outer = [e for e in found
             if not any(o is not e and o[1] <= e[1] and e[2] <= o[2]
                        for o in found)]
    return [(e, union([w for w in spans if w[0] == WAIT
                       and e[1] <= w[1] and w[2] <= e[2]]))
            for e in outer]


def overlap_ns(a: Sequence[Tuple[int, int]],
               b: Sequence[Tuple[int, int]]) -> int:
    """Time covered by both of two lists of disjoint intervals."""
    return sum(max(0, min(a1, b1) - max(a0, b0))
               for a0, a1 in a for b0, b1 in b)
