"""The engine's host self-time per search, in ms: each search's
duration (``bench/lib/spans.py``: an outermost ``engine.search`` span
wholly inside the window) less the union of its ``engine.wait`` spans,
averaged over the searches.  This is the host work of the program's
search path (the series' conversion and padding, dispatch, the
profile's copy back, the top-k), which a closed loop's chip waits
through.
"""
from bench.lib.spans import searches

LAYER = "engine"
UNIT = "ms"
MOVES = "search_s"


def read(ctx):
    if ctx.trace is None:
        return None
    found = searches(ctx.trace)
    if not found:
        return None
    self_ns = sum((b - a) - sum(w1 - w0 for w0, w1 in waits)
                  for (_, a, b), waits in found)
    return 1e-6 * self_ns / len(found)
