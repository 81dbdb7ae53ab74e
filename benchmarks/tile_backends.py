"""Tile-engine micro-benchmark: xla vs pallas-interpret vs numpy.

Times the unified distance-tile sweep (the Eq. (3) hot spot every
search strategy now shares) across backends and tile geometries, and
emits ``BENCH_tiles.json``.

On CPU the pallas numbers are interpret-mode (correctness and tile
geometry, not speed); on a real TPU re-run this to compare the MXU
kernel against the XLA fallback.

Usage:  PYTHONPATH=src python -m benchmarks.tile_backends [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.core.tiles import TileEngine, available_backends

from .util import BenchTable

# (n, s, block): small enough for interpret mode, big enough to fill
# an MXU tile on hardware
SHAPES = [(4_096, 128, 128), (8_192, 128, 256), (8_192, 256, 256)]
N_QUERIES = 64
REPS = 3


def _bench_sweep(eng: TileEngine, qblk, backend: str) -> dict:
    """Median wall time of one full candidate sweep (all blocks),
    as one compiled program (dispatch overhead excluded)."""
    import jax.numpy as jnp
    from jax import lax

    starts = jnp.arange(eng.nb, dtype=jnp.int32) * eng.block
    sweep_jit = jax.jit(lambda q: lax.map(
        lambda c0: eng.sweep(q, c0, backend=backend)[0], starts))

    def sweep_all():
        return jax.block_until_ready(sweep_jit(qblk))

    sweep_all()                              # warm-up / compile
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        sweep_all()
        times.append(time.perf_counter() - t0)
    t = float(np.median(times))
    lanes = N_QUERIES * eng.nb * eng.block   # distance lanes computed
    return {"seconds": t, "lanes": lanes,
            "mlanes_per_s": lanes / t / 1e6}


def run(small: bool = True, out_path: str = "BENCH_tiles.json") -> dict:
    rng = np.random.default_rng(0)
    shapes = SHAPES[:1] if small else SHAPES
    backends = [b for b in ("xla", "pallas", "numpy")
                if b in available_backends()]
    table = BenchTable(
        "distance-tile backends (sweep throughput)",
        ["backend", "N", "s", "block", "sweep ms", "Mlanes/s"])
    results = {"device": jax.default_backend(),
               "interpret_pallas": jax.default_backend() != "tpu",
               "n_queries": N_QUERIES, "entries": []}
    for n, s, block in shapes:
        x = np.sin(0.01 * np.arange(n)) + 0.1 * rng.normal(size=n)
        eng = TileEngine(x.astype(np.float32), s, block=block)
        qids = rng.choice(eng.n, size=N_QUERIES, replace=False)
        qblk = eng.query_block(qids.astype(np.int32))
        for be in backends:
            r = _bench_sweep(eng, qblk, be)
            entry = {"backend": be, "n": n, "s": s, "block": block, **r}
            results["entries"].append(entry)
            table.row(be, n, s, block, f"{r['seconds'] * 1e3:.1f}",
                      f"{r['mlanes_per_s']:.1f}")
    print(table.markdown())
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"\nwrote {out_path}")
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="sweep all shapes (slower)")
    ap.add_argument("--out", default="BENCH_tiles.json")
    args = ap.parse_args()
    run(small=not args.full, out_path=args.out)
