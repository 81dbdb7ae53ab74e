"""Quantized pruned-sweep benchmark: bound pass + exact refinement.

Measures what the reduced-precision plane (docs/cps.md "qsweep")
buys over the exact f32 profile sweep and emits ``BENCH_quant.json``:

  * **prune ratio** — fraction of query blocks the bf16/int8 bound
    pass retires without f32 refinement, per precision x backend
    (numpy, xla).  The bf16 xla ratio is the contract and is
    CI-gated > 0.5 on the planted-discord series;
  * **refine fraction** — refinement lanes / total lanes, the other
    face of the same coin (how much of the hybrid's work is still
    exact);
  * **lanes/s** — swept pair-lanes per second for the quantized
    hybrid vs the exact sweep, plus the lane ratio (quantized total
    lanes / exact lanes; < 1 means the prune beat its own bound-pass
    overhead);
  * **bit-identical parity** — every precision's positions and nnds
    equal the exact f32 search's (asserted, not just reported).

Usage:  PYTHONPATH=src python -m benchmarks.quantized_sweep [--out P]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import DiscordEngine, SearchSpec

from .util import BenchTable

N, S, K, BLOCK = 2048, 64, 1, 64
BACKENDS = ("numpy", "xla")
PRECISIONS = ("bf16", "int8")


def _series() -> np.ndarray:
    """Planted-discord series with healthy top-k margins (globally
    z-normed so the bound radius stays tight)."""
    rng = np.random.default_rng(0)
    x = (np.sin(np.linspace(0.0, 64.0 * np.pi, N))
         + 0.05 * rng.standard_normal(N))
    x[1000:1000 + S] += np.hanning(S) * 4.0
    return (x - x.mean()) / x.std()


def _spec(backend: str, precision: str) -> SearchSpec:
    return SearchSpec(s=S, k=K, method="matrix_profile", block=BLOCK,
                      backend=backend, precision=precision)


def _timed_search(spec: SearchSpec, x: np.ndarray):
    eng = DiscordEngine(spec)
    eng.search(x)                          # warm: compile out of band
    t0 = time.perf_counter()
    res = eng.search(x)
    return res, time.perf_counter() - t0


def run(out_path: str = "BENCH_quant.json") -> dict:
    x = _series()
    result = {"shape": {"n": N, "s": S, "k": K, "block": BLOCK},
              "cells": {}}
    for backend in BACKENDS:
        exact, exact_s = _timed_search(_spec(backend, "f32"), x)
        result["cells"][f"f32|{backend}"] = {
            "lanes": exact.calls,
            "lanes_per_s": exact.calls / max(exact_s, 1e-9),
            "wall_s": exact_s}
        for prec in PRECISIONS:
            res, wall = _timed_search(_spec(backend, prec), x)
            assert list(res.positions) == list(exact.positions), \
                (backend, prec, res.positions, exact.positions)
            assert np.array_equal(np.asarray(res.nnds),
                                  np.asarray(exact.nnds)), \
                (backend, prec)
            refine = res.extra["refine_calls"]
            result["cells"][f"{prec}|{backend}"] = {
                "prune_ratio": res.extra["prune_ratio"],
                "refine_fraction": refine / res.calls,
                "lanes": res.calls,
                "lane_ratio_vs_exact": res.calls / exact.calls,
                "lanes_per_s": res.calls / max(wall, 1e-9),
                "wall_s": wall,
                "parity_bit_identical": True}      # asserted above

    tab = BenchTable(
        "quantized pruned sweep (n=%d, s=%d, block=%d)"
        % (N, S, BLOCK),
        ["cell", "prune_ratio", "refine_frac", "lane_ratio",
         "lanes/s"])
    for cell, d in result["cells"].items():
        tab.row(cell,
                "%.3f" % d.get("prune_ratio", 0.0),
                "%.3f" % d.get("refine_fraction", 1.0),
                "%.3f" % d.get("lane_ratio_vs_exact", 1.0),
                "%.3g" % d["lanes_per_s"])
    print(tab)

    # CI gates (ISSUE 10): the bf16 bound pass must retire most query
    # blocks on the planted-discord series (parity asserted above)
    gate = result["cells"]["bf16|xla"]["prune_ratio"]
    assert gate > 0.5, f"bf16 xla prune_ratio {gate} <= 0.5"
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"\nwrote {out_path}")
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_quant.json")
    run(ap.parse_args().out)
