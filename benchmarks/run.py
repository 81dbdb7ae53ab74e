"""Benchmark runner: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only table4]

Each module's ``run()`` returns {"tables": [BenchTable...],
"claims": {...}} — the claims are the paper's assertions checked on
the synthetic analogue panel; any False claim fails the run (exit 1).
Results land in experiments/benchmarks/.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

MODULES = [
    "table1_2_discords",
    "table3_cps",
    "table4_noise",
    "table5_seqlen",
    "table6_rra",
    "table7_dadd",
    "fig6_scamp",
    "fig7_scaling",
    "kernels",
    "roofline",
]

# claims that are informational (not pass/fail)
SOFT_CLAIMS = {"median_speedup_k1", "median_speedup_k10",
               "low_noise_speedup", "mid_noise_speedup", "speedups",
               "hst_cps_range", "hs_cps_range", "scamp_slope",
               "hst_slope", "median_speedup", "n_cells", "skipped"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale dataset sizes (slow)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="experiments/benchmarks")
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    failures = []
    all_results = {}
    for name in MODULES:
        if args.only and args.only not in name:
            continue
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.perf_counter()
        res = mod.run(small=not args.full)
        dt = time.perf_counter() - t0
        print(f"\n===== {name}  ({dt:.1f}s) =====")
        for tb in res["tables"]:
            print(tb.markdown())
            print()
        print("claims:", json.dumps(res["claims"], default=str))
        for k, v in res["claims"].items():
            if k not in SOFT_CLAIMS and v is False:
                failures.append(f"{name}.{k}")
        all_results[name] = {
            "claims": res["claims"],
            "tables": {tb.title: tb.csv() for tb in res["tables"]},
            "seconds": dt,
        }
    (out / "results.json").write_text(
        json.dumps(all_results, indent=1, default=str))
    if failures:
        print("\nFAILED CLAIMS:", failures)
        return 1
    print("\nall claims hold")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
