"""Device idle time inside the engine's waits, in ms per search: the
holes in the union of a chip's operations (its ``XLA Ops`` line) that
fall inside the ``engine.wait`` spans of the window's searches
(``bench/lib/spans.py``), averaged over the chips and divided by the
number of searches.  The host blocks there while the chip runs
nothing: runtime overhead, or a stall.
"""
from bench.lib.spans import overlap_ns, searches
from bench.lib.trace import gaps

LAYER = "engine"
UNIT = "ms"
MOVES = "search_s"


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    found = searches(tr)
    if not found:
        return None
    blocked = [w for _, waits in found for w in waits]
    idle = sum(overlap_ns(gaps(evs, tr.lo, tr.hi), blocked)
               for evs in tr.devices.values()) / len(tr.devices)
    return 1e-6 * idle / len(found)
