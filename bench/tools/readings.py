#!/usr/bin/env python3
"""Read the numbers that a cell's ``correct`` compares, over many seeds,
for the program and for the controls, in one process.

    python3 bench/tools/readings.py --workload ecg-long.search \
        --seeds 11,12,13 --seconds 3 --controls bf16,high \
        --control-seeds 11,12,13

Each seed is one run of the cell (set-up, a window of ``--seconds``,
the check), as ``bench/run.py`` makes it; for the control seeds the
check is also made with the reference in each named lower precision in
the program's place.  A limit is set above the largest program reading
and below the smallest control reading (PERF.md).  ``--keep-trace DIR``
traces the first seed's window and keeps the trace there.  The last line
is a JSON summary.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.run import log, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    controls = [c for c in args.controls.split(",") if c]
    cseeds = {int(s) for s in args.control_seeds.split(",") if s}
    out = {"program": {}, "controls": {c: {} for c in controls}}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        r = run_cell(args.workload, seed=seed, seconds=args.seconds,
                     trace=bool(args.keep_trace) and i == 0,
                     keep_trace=args.keep_trace,
                     controls=controls if seed in cseeds else ())
        out["program"][seed] = {k: c["value"] for k, c in
                                r["checks"].items()}
        out["program"][seed]["correct"] = r["correct"]
        for c, chk in r.get("controls", {}).items():
            out["controls"][c][seed] = {k: v["value"] for k, v in
                                        chk.items()}
        log(f"readings seed {seed}: {out['program'][seed]} controls "
            f"{ {c: out['controls'][c].get(seed) for c in controls} }")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
