"""Session-API benchmark: compile-once and streaming wins.

Measures what the DiscordEngine redesign buys over the stateless
entrypoints and emits ``BENCH_engine.json``:

  * first-call vs warm-call ``search`` latency in one length bucket
    (the warm call reuses the compiled plan — zero traces), plus a
    cross-length warm call in the same bucket;
  * ``DiscordStream.append`` throughput vs recomputing the full
    profile from scratch after every chunk.

On CPU the absolute numbers are modest; the *ratios* (compile
amortization, tail-sweep vs full-sweep lanes) are the contract.

Usage:  PYTHONPATH=src python -m benchmarks.engine_sessions [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import DiscordEngine, SearchSpec
from repro.data import sine_noise

from .util import BenchTable

N, S, K = 4096, 128, 3
CHUNK = 256
N_APPENDS = 8
REPS = 3


def _t(fn):
    fn()                                   # warm anything one-off
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def run(out_path: str = "BENCH_engine.json") -> dict:
    spec = SearchSpec(s=S, k=K, method="matrix_profile")
    x = sine_noise(N, E=0.3, seed=0)
    y = sine_noise(N - 100, E=0.3, seed=1)     # same bucket, new length

    # -- compile-once: cold vs warm ------------------------------------
    eng = DiscordEngine(spec)
    t0 = time.perf_counter()
    eng.search(x)
    first_call_s = time.perf_counter() - t0    # traces + compiles
    warm_call_s = _t(lambda: eng.search(x))
    warm_other_len_s = _t(lambda: eng.search(y))
    assert eng.stats.traces == 1, eng.stats    # the whole point

    # -- streaming append vs full recompute ----------------------------
    base = x[: N - CHUNK * N_APPENDS]
    chunks = [x[N - CHUNK * (N_APPENDS - i): N - CHUNK * (N_APPENDS - i)
               + CHUNK] for i in range(N_APPENDS)]
    stream = eng.open_stream(history=base)
    lanes0 = stream.tile_lanes
    t0 = time.perf_counter()
    for c in chunks:
        stream.append(c)
    append_total_s = time.perf_counter() - t0
    append_mean_s = append_total_s / N_APPENDS
    append_lanes = (stream.tile_lanes - lanes0) // N_APPENDS
    # the stateless alternative: full profile after every chunk
    full_recompute_s = _t(lambda: eng.search(x))
    eng.stats.tile_lanes = 0
    eng.search(x)
    full_lanes = eng.stats.tile_lanes

    result = {
        "shape": {"n": N, "s": S, "k": K, "chunk": CHUNK,
                  "appends": N_APPENDS},
        "backend": eng.backend,
        "first_call_s": first_call_s,
        "warm_call_s": warm_call_s,
        "warm_other_length_s": warm_other_len_s,
        "compile_amortization_x": first_call_s / max(warm_call_s, 1e-9),
        "append_mean_s": append_mean_s,
        "append_points_per_s": CHUNK / max(append_mean_s, 1e-9),
        "full_recompute_s": full_recompute_s,
        "append_speedup_x": full_recompute_s / max(append_mean_s, 1e-9),
        "append_tile_lanes": int(append_lanes),
        "full_tile_lanes": int(full_lanes),
        "lane_ratio": full_lanes / max(append_lanes, 1),
        "traces": eng.stats.traces,
        "plans": eng.stats.plans,
    }

    tab = BenchTable("engine sessions (n=%d, s=%d)" % (N, S),
                     ["metric", "value"])
    for key in ("first_call_s", "warm_call_s", "warm_other_length_s",
                "append_mean_s", "full_recompute_s",
                "append_speedup_x", "lane_ratio", "traces"):
        v = result[key]
        tab.row(key, f"{v:.4f}" if isinstance(v, float) else v)
    print(tab)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"\nwrote {out_path}")
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_engine.json")
    run(ap.parse_args().out)
