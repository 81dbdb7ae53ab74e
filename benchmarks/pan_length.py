"""Pan-length ladder benchmark: one shared sweep vs independent ones.

Measures what the pan-length plan family buys over L independent
per-length searches and emits ``BENCH_pan.json``:

  * width-normalized ``tile_lanes`` of one ladder sweep vs the sum of
    the independent per-length sweeps (``lane_ratio`` — the
    acceptance bar is < 0.6 for an 8-rung ladder);
  * cold vs warm ``search_pan`` wall clock (compile-once: the warm
    call reuses the one compiled ladder plan, zero new traces);
  * the independent sweeps' wall clock through the same engine cache
    (their best case) for an honest runtime comparison;
  * **streaming appends** (PanStream): lanes of appending the last
    points vs a from-scratch ladder resweep
    (``stream_append_lane_ratio`` — gated < 0.5, with per-rung result
    parity);
  * **LB-abandoning schedule** (``schedule="lb_abandon"``, k=1 global
    top-k-only regime): evaluated lanes vs the all-rung sweep
    (``lb_abandon_lane_ratio`` — gated <= 1.0 with skipped rungs
    reported, and the global top-k bit-equal to the all-rung sweep's).

On CPU the wall-clock numbers are modest; the *lane ratios* and the
trace counts are the contract (docs/cps.md).

Usage:  PYTHONPATH=src python -m benchmarks.pan_length [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import DiscordEngine, SearchSpec
from repro.data import sine_noise, with_implanted_anomalies

from .util import BenchTable

N, K = 8192, 3
LADDER = tuple(range(64, 121, 8))          # 8 rungs: 64..120
REPS = 3


def _t(fn):
    fn()                                   # warm once
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def run(out_path: str = "BENCH_pan.json") -> dict:
    x = sine_noise(N, E=0.3, seed=0)
    x, _pos = with_implanted_anomalies(x, n_anomalies=2,
                                       length=max(LADDER), amp=0.8,
                                       seed=0)

    # -- pan: one ladder sweep -----------------------------------------
    eng = DiscordEngine(SearchSpec(s=LADDER, k=K,
                                   method="matrix_profile"))
    t0 = time.perf_counter()
    pan = eng.search_pan(x)
    pan_cold_s = time.perf_counter() - t0
    pan_warm_s = _t(lambda: eng.search_pan(x))
    assert eng.stats.traces == 1, eng.stats    # compile-once, mesh of 1

    # -- independent per-length sweeps (one engine each, warm) ---------
    indep_lanes = 0
    engines = [DiscordEngine(SearchSpec(s=s, k=K,
                                        method="matrix_profile"))
               for s in LADDER]

    def indep_all():
        for e in engines:
            e.search(x)

    indep_cold_t0 = time.perf_counter()
    indep_all()
    indep_cold_s = time.perf_counter() - indep_cold_t0
    indep_warm_s = _t(indep_all)
    indep_results = []
    for e in engines:
        e.stats.tile_lanes = 0
        indep_results.append(e.search(x))
        indep_lanes += e.stats.tile_lanes

    parity = all(p.positions == r.positions
                 for p, r in zip(pan.per_rung, indep_results))

    # -- streaming appends (PanStream) ---------------------------------
    # fill on the same final length bucket, then append the held-out
    # tail: the pan tail plan pays base-rung tail tiles + Δ-wide
    # extensions only
    held = 512
    st = eng.open_stream(history=x[:N - held])
    fill_lanes = st.tile_lanes
    t0 = time.perf_counter()
    st.append(x[N - held:N - held // 2])
    st.append(x[N - held // 2:])
    stream_append_s = time.perf_counter() - t0
    append_lanes = st.tile_lanes - fill_lanes
    sd = st.discords()
    stream_parity = all(
        a.positions == b.positions
        and np.allclose(a.nnds, b.nnds, rtol=1e-3, atol=1e-2)
        for a, b in zip(sd.per_rung, pan.per_rung))

    # -- LB-abandoning rung schedule (k=1: global top-k only) ----------
    # a dominant base-rung discord in an otherwise self-similar series
    # lets the cross-length bracket retire trailing rungs; smaller N
    # keeps the sequential plans' carried QT modest
    n_lb = 4096
    rng = np.random.default_rng(0)
    x_lb = (np.sin(0.05 * np.arange(n_lb))
            + 0.15 * rng.normal(size=n_lb))
    x_lb[1500:1500 + LADDER[0]] += 1.4 * np.sin(
        np.linspace(0, np.pi, LADDER[0]))
    eng_lb = DiscordEngine(SearchSpec(s=LADDER, k=1,
                                      method="matrix_profile"))
    ref_lb = eng_lb.search_pan(x_lb)
    t0 = time.perf_counter()
    lb = eng_lb.search_pan(x_lb, schedule="lb_abandon")
    lb_s = time.perf_counter() - t0
    lb_parity = ([(g["s"], g["position"]) for g in lb.global_topk]
                 == [(g["s"], g["position"]) for g in ref_lb.global_topk])

    result = {
        "shape": {"n": N, "k": K, "ladder": list(LADDER),
                  "rungs": len(LADDER)},
        "backend": eng.backend,
        "pan_tile_lanes": int(pan.tile_lanes),
        "independent_tile_lanes": int(indep_lanes),
        "lane_ratio": pan.tile_lanes / max(indep_lanes, 1),
        "pan_cold_s": pan_cold_s,
        "pan_warm_s": pan_warm_s,
        "independent_cold_s": indep_cold_s,
        "independent_warm_s": indep_warm_s,
        "warm_speedup_x": indep_warm_s / max(pan_warm_s, 1e-9),
        "traces": eng.stats.traces,
        "plans": eng.stats.plans,
        "lb_ok": bool(pan.extra["lb_ok"]),
        "lb_margin": pan.lb_margin,
        "parity_with_independent": bool(parity),
        "global_topk": pan.global_topk,
        # streaming appends (PanStream over the same ladder)
        "stream_held_points": held,
        "stream_append_lanes": int(append_lanes),
        "stream_append_lane_ratio": append_lanes / pan.tile_lanes,
        "stream_append_s": stream_append_s,
        "stream_parity": bool(stream_parity),
        # LB-abandoning rung schedule (k=1 global-top-k-only regime)
        "lb_abandon_n": n_lb,
        "lb_abandon_lanes": int(lb.tile_lanes),
        "lb_abandon_ladder_lanes": int(lb.extra["ladder_lanes"]),
        "lb_abandon_lane_ratio": (lb.tile_lanes
                                  / lb.extra["ladder_lanes"]),
        "lb_abandon_skipped_rungs": list(lb.extra["skipped_rungs"]),
        "lb_abandon_refine_calls": int(lb.extra["refine_calls"]),
        "lb_abandon_resweeps": int(lb.extra["resweeps"]),
        "lb_abandon_s": lb_s,
        "lb_abandon_parity": bool(lb_parity),
    }

    tab = BenchTable("pan-length ladder (n=%d, %d rungs %d..%d)"
                     % (N, len(LADDER), LADDER[0], LADDER[-1]),
                     ["metric", "value"])
    for key in ("pan_tile_lanes", "independent_tile_lanes",
                "lane_ratio", "pan_cold_s", "pan_warm_s",
                "independent_warm_s", "warm_speedup_x", "traces",
                "lb_ok", "parity_with_independent",
                "stream_append_lanes", "stream_append_lane_ratio",
                "stream_parity", "lb_abandon_lane_ratio",
                "lb_abandon_skipped_rungs", "lb_abandon_parity"):
        v = result[key]
        tab.row(key, f"{v:.4f}" if isinstance(v, float) else v)
    print(tab)
    assert result["lane_ratio"] < 0.6, result["lane_ratio"]
    assert parity, "pan results diverged from independent sweeps"
    # CI gates (ISSUE 5): streaming appends stay under half a
    # from-scratch ladder resweep; the LB-abandoning schedule never
    # evaluates more than the all-rung sweep and returns its top-k
    assert result["stream_append_lane_ratio"] < 0.5, \
        result["stream_append_lane_ratio"]
    assert stream_parity, "pan stream diverged from the ladder sweep"
    # the <= 1 lane bound holds for confirmed skips; a fixpoint
    # resweep (skip invalidated by the final picks) may exceed it, so
    # pin the seeded showcase to zero resweeps to keep the gate honest
    assert result["lb_abandon_resweeps"] == 0, \
        result["lb_abandon_resweeps"]
    assert result["lb_abandon_lane_ratio"] <= 1.0, \
        result["lb_abandon_lane_ratio"]
    assert result["lb_abandon_skipped_rungs"], \
        "LB-abandon schedule skipped nothing on the showcase workload"
    assert lb_parity, "LB-abandon diverged from the all-rung sweep"
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"\nwrote {out_path}")
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_pan.json")
    run(ap.parse_args().out)
