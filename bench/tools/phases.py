#!/usr/bin/env python3
"""Each search's phases in a kept trace, on the profiler's clock.

    python3 bench/tools/phases.py DIR/ecg-long.search.xplane.pb ...

A trace is kept by ``bench/tools/readings.py --keep-trace DIR``.  For
every search of the window (``bench/lib/spans.py``) it takes the time of
each ``engine.*`` phase inside it, the chip's busy time inside each
phase, and the host time since the previous search ended (the runner's
loop); then the median search, each search slower than ``--slow``
seconds with its excess over the median by phase, and the number of
the mpblock kernel's device operations that lie outside every
``engine.search`` span (0 when the program's spans and the device
share a clock).  One JSON line per trace.
"""
import argparse
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.lib import trace as tr  # noqa: E402
from bench.lib.cells import metric_reader  # noqa: E402
from bench.lib.spans import SEARCH, overlap_ns, searches  # noqa: E402

PHASES = ("engine.prepare", "engine.dispatch", "engine.wait",
          "engine.fetch", "engine.select")


def phases(path: str, slow: float) -> dict:
    s = tr.read(path)
    busy = tr.union([e for evs in s.devices.values() for e in evs])
    rows, last = [], None
    for (_, a, b), _waits in searches(s):
        row = {"at_s": (a - s.lo) * 1e-9, "search": (b - a) * 1e-6,
               "loop": (a - last) * 1e-6 if last is not None else None}
        for p in PHASES:
            spans = [(x, y) for n, x, y in s.host
                     if n == p and a <= x and y <= b]
            row[p] = sum(y - x for x, y in spans) * 1e-6
            row[p + ".busy"] = overlap_ns(spans, busy) * 1e-6
        rows.append(row)
        last = b
    keys = [k for k in rows[0] if k != "at_s"] if rows else []
    median = {k: statistics.median(r[k] for r in rows
                                   if r[k] is not None) for k in keys}
    slower = [{"at_s": r["at_s"], "search": r["search"],
               "excess": {k: r[k] - median[k] for k in keys
                          if r[k] is not None}}
              for r in rows if r["search"] > slow * 1e3]
    kernel = metric_reader("mpblock_roofline").KERNEL
    outer = [(a, b) for n, a, b in s.host if n == SEARCH]
    stray = sum(1 for evs in s.devices.values()
                for n, a, b in evs if kernel.search(n)
                and not any(x <= a and b <= y for x, y in outer))
    return {"trace": path, "searches": len(rows), "median_ms": median,
            "slower": slower, "kernels_outside_searches": stray,
            "idle_gaps": s.idle_gaps(10)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("traces", nargs="+")
    ap.add_argument("--slow", type=float, default=0.60,
                    help="a search slower than this many seconds is "
                         "listed with its excess by phase")
    args = ap.parse_args(argv)
    for path in args.traces:
        print(json.dumps(phases(path, args.slow)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
