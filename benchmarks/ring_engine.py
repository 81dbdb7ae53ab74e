"""Ring-plan benchmark: the mesh-sharded engine across device counts.

Forces a 4-device host platform (set before jax init), then runs the
same plan-cached ring search on meshes of 1, 2 and 4 devices plus the
single-device local profile plan as the baseline, and emits
``BENCH_ring.json``:

  * per-device-count cold (trace+compile) and warm wall clock;
  * swept ``tile_lanes`` per search (the shared work unit of
    docs/cps.md — mesh padding makes ring lanes grow slightly with
    device count, which is the honest cost of alignment);
  * the compile-once contract (``traces`` after two same-bucket
    searches) per mesh shape.

On a CPU host the forced devices share the same cores, so warm
*speedups* are not the point here — lane accounting, trace counts and
the cold/warm split are.  On a real TPU mesh the same code path is the
scaling benchmark.

Usage:  PYTHONPATH=src python -m benchmarks.ring_engine [--out PATH]
"""
from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=4"

import argparse            # noqa: E402
import json                # noqa: E402
import time                # noqa: E402

import jax                 # noqa: E402
import numpy as np         # noqa: E402

from repro.core import DiscordEngine, SearchSpec      # noqa: E402
from repro.data import sine_noise                     # noqa: E402

from .util import BenchTable                          # noqa: E402

N, S, K = 16384, 128, 3
REPS = 3
NDEVS = (1, 2, 4)


def _warm(fn):
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def run(out_path: str = "BENCH_ring.json") -> dict:
    x = sine_noise(N, E=0.3, seed=0)
    y = sine_noise(N - 200, E=0.3, seed=1)     # same bucket, new length
    avail = len(jax.devices())

    rows = []
    # single-device local profile plan: the non-ring baseline
    eng = DiscordEngine(SearchSpec(s=S, k=K, method="matrix_profile"))
    t0 = time.perf_counter()
    r = eng.search(x)
    cold = time.perf_counter() - t0
    rows.append({"plan": "local", "ndev": 1, "cold_s": cold,
                 "warm_s": _warm(lambda: eng.search(x)),
                 "tile_lanes": int(r.tile_lanes), "cps": r.cps,
                 "traces_after_2nd_bucket_search": eng.stats.traces})

    for ndev in NDEVS:
        if ndev > avail:
            continue
        eng = DiscordEngine(SearchSpec(s=S, k=K, method="ring",
                                       ndev=ndev))
        t0 = time.perf_counter()
        r = eng.search(x)
        cold = time.perf_counter() - t0
        warm = _warm(lambda: eng.search(x))
        eng.search(y)                          # same-bucket re-search
        rows.append({"plan": "ring", "ndev": ndev, "cold_s": cold,
                     "warm_s": warm, "tile_lanes": int(r.tile_lanes),
                     "cps": r.cps,
                     "traces_after_2nd_bucket_search": eng.stats.traces})

    dev = jax.devices()[0]
    result = {
        "shape": {"n": N, "s": S, "k": K},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": avail},
        "devices_available": avail,
        "backend": eng.backend,
        "runs": rows,
    }

    tab = BenchTable(f"ring engine (n={N}, s={S}, k={K}) on "
                     f"{avail} {dev.platform} device(s) "
                     f"({dev.device_kind})",
                     ["plan", "ndev", "cold_s", "warm_s",
                      "tile_lanes", "traces"])
    for row in rows:
        tab.row(row["plan"], row["ndev"], f"{row['cold_s']:.3f}",
                f"{row['warm_s']:.3f}", row["tile_lanes"],
                row["traces_after_2nd_bucket_search"])
    print(tab)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"\nwrote {out_path}")
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_ring.json")
    run(ap.parse_args().out)
