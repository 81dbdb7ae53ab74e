"""Distance lanes the engine swept per unique window pair of the series
sent: ``EngineStats.tile_lanes`` over the window divided by the sum of
``n (n - 1) / 2`` over its searches (``bench/lib/work.py``).

The profile plan sweeps the full padded square, so each pair counts
twice, and the length bucket pads ``n`` windows up to ``n_pad``: today
this reads about ``2 (n_pad / n)^2``.  A plan that exploits symmetry or
a finer bucket lowers it.
"""

LAYER = "engine"
UNIT = "lanes/pair"
MOVES = "search_s"


def read(ctx):
    pairs = ctx.counters.get("useful_pairs")
    if not pairs:
        return None
    return ctx.counters["tile_lanes"] / pairs
