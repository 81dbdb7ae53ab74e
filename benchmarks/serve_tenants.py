"""Multi-tenant serve-plane benchmark: coalesced vs sequential.

Measures what the fleet layer (``repro.serve.DiscordServer``) buys
over serving each tenant's appends one at a time, and emits
``BENCH_serve.json``:

  * **micro-batched vs sequential dispatch** — the same tenant fleet
    and append schedule served through the coalescing flush path vs
    per-tenant sequential streams over one warm shared engine (the
    sequential path's best case).  ``dispatch_ratio`` (device
    round-trips issued / sequential equivalent) is the contract and
    is CI-gated < 0.5; wall clocks are reported for context (on CPU
    the lax.map lanes still run serially, so the wall-clock win is
    python/dispatch overhead only — the ratio is the device-queue
    story);
  * **bit-identical parity** — every tenant's profile and neighbor
    ids after the coalesced run equal the sequential run's exactly
    (asserted, not just reported);
  * **1k-tenant cache locality** — a 1000-tenant fleet over
    bucket-identical specs: shared plan-cache hit rate (gated > 0.9),
    fleet-wide compile-once (traces == plans), and the dispatch ratio
    at scale.

Usage:  PYTHONPATH=src python -m benchmarks.serve_tenants [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import DiscordEngine, SearchSpec
from repro.serve import DiscordServer

from .util import BenchTable

S, K = 64, 3
N_TENANTS, HISTORY, ROUNDS, APPEND = 64, 512, 4, 64
N_FLEET, FLEET_HISTORY, FLEET_ROUNDS, FLEET_APPEND = 1000, 128, 4, 16


def _fleet(rng, n, hist_len):
    return [np.sin(0.07 * np.arange(hist_len))
            + 0.2 * rng.normal(size=hist_len) for _ in range(n)]


def run(out_path: str = "BENCH_serve.json") -> dict:
    spec = SearchSpec(s=S, k=K, method="matrix_profile")
    rng = np.random.default_rng(0)
    hist = _fleet(rng, N_TENANTS, HISTORY)
    apps = rng.normal(size=(ROUNDS, N_TENANTS, APPEND))

    # -- coalesced: one server, micro-batched flushes ------------------
    srv = DiscordServer()
    t0 = time.perf_counter()
    for t in range(N_TENANTS):
        srv.open(t, spec, history=hist[t])
    srv.flush()
    for i in range(ROUNDS):
        for t in range(N_TENANTS):
            srv.append(t, apps[i, t])
        srv.flush()
    coalesced_s = time.perf_counter() - t0
    st = srv.stats()

    # -- sequential: same appends, one tenant at a time over one warm
    # shared engine (its best case: plans still compile once) ----------
    eng = DiscordEngine(spec)
    t0 = time.perf_counter()
    refs = [eng.open_stream(history=hist[t]) for t in range(N_TENANTS)]
    for i in range(ROUNDS):
        for t in range(N_TENANTS):
            refs[t].append(apps[i, t])
    sequential_s = time.perf_counter() - t0

    # bit-identical parity, every tenant
    for t in range(N_TENANTS):
        got = srv.stream(t)
        assert np.array_equal(got.profile(), refs[t].profile()), t
        assert np.array_equal(got.neighbors(), refs[t].neighbors()), t

    # -- 1k tenants: shared-cache locality at fleet scale --------------
    rng2 = np.random.default_rng(1)
    fleet_hist = _fleet(rng2, N_FLEET, FLEET_HISTORY)
    fleet_apps = rng2.normal(size=(FLEET_ROUNDS, N_FLEET,
                                   FLEET_APPEND))
    big = DiscordServer()
    t0 = time.perf_counter()
    for t in range(N_FLEET):
        big.open(t, spec, history=fleet_hist[t])
    big.flush()
    for i in range(FLEET_ROUNDS):
        for t in range(N_FLEET):
            big.append(t, fleet_apps[i, t])
        big.flush()
    fleet_s = time.perf_counter() - t0
    bst = big.stats()

    result = {
        "shape": {"s": S, "k": K, "tenants": N_TENANTS,
                  "history": HISTORY, "rounds": ROUNDS,
                  "append": APPEND},
        "backend": eng.backend,
        "coalesced_s": coalesced_s,
        "sequential_s": sequential_s,
        "speedup_x": sequential_s / max(coalesced_s, 1e-9),
        "dispatches": st.dispatches,
        "sequential_dispatches": st.sequential_dispatches,
        "dispatch_ratio": st.dispatch_ratio,
        "coalesced_lanes": st.coalesced,
        "padded_lanes": st.padded_lanes,
        "cache": st.cache,
        "parity_bit_identical": True,         # asserted above
        "fleet": {"tenants": N_FLEET, "history": FLEET_HISTORY,
                  "rounds": FLEET_ROUNDS, "append": FLEET_APPEND,
                  "wall_s": fleet_s,
                  "dispatches": bst.dispatches,
                  "sequential_dispatches": bst.sequential_dispatches,
                  "dispatch_ratio": bst.dispatch_ratio,
                  "cache_hit_rate": bst.cache_hit_rate,
                  "plans": bst.plans, "traces": bst.traces},
    }

    tab = BenchTable("multi-tenant serve plane (s=%d, %d tenants + "
                     "%d-tenant fleet)" % (S, N_TENANTS, N_FLEET),
                     ["metric", "value"])
    for key in ("coalesced_s", "sequential_s", "speedup_x",
                "dispatches", "sequential_dispatches",
                "dispatch_ratio", "coalesced_lanes", "padded_lanes",
                "parity_bit_identical"):
        v = result[key]
        tab.row(key, f"{v:.4f}" if isinstance(v, float) else v)
    for key in ("wall_s", "dispatch_ratio", "cache_hit_rate",
                "plans", "traces"):
        v = result["fleet"][key]
        tab.row(f"fleet_{key}", f"{v:.4f}" if isinstance(v, float)
                else v)
    print(tab)

    # CI gates (ISSUE 8): micro-batching must beat sequential dispatch
    # by 2x and the 1k-tenant fleet must hit the shared cache > 90%
    assert result["dispatch_ratio"] < 0.5, result["dispatch_ratio"]
    assert result["fleet"]["dispatch_ratio"] < 0.5, \
        result["fleet"]["dispatch_ratio"]
    assert result["fleet"]["cache_hit_rate"] > 0.9, \
        result["fleet"]["cache_hit_rate"]
    assert result["fleet"]["traces"] == result["fleet"]["plans"], \
        "fleet-wide compile-once broke"
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"\nwrote {out_path}")
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serve.json")
    run(ap.parse_args().out)
