"""Multi-device discord search (the paper's stated future work).

The ring matrix profile is a first-class *plan family* of the
``DiscordEngine`` session layer: mesh-sharded, length-bucketed, and
plan-cached under ``(kind, s, bucket, mesh-shape)`` — so the second
sharded search in a bucket retraces nothing, streams sweep only the
owning shard's tail tiles, and batched searches pick a two-level
layout automatically.  This example runs on forced host-platform
devices (8 by default; any pre-set ``--xla_force_host_platform_
device_count`` is respected, e.g. CI's 4) and checks ring and DRAG
against the serial exact result.

    PYTHONPATH=src python examples/distributed_discord.py
"""
import os

if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import time                                                  # noqa: E402

import numpy as np                                           # noqa: E402
import jax                                                   # noqa: E402

from repro.core import DiscordEngine, SearchSpec             # noqa: E402
from repro.data import ecg_like, with_implanted_anomalies    # noqa: E402

ndev = len(jax.devices())
print(f"devices: {ndev} x {jax.devices()[0].platform} "
      f"({jax.devices()[0].device_kind})")
x, planted = with_implanted_anomalies(
    ecg_like(20_000, period=160, noise=0.03, seed=3),
    n_anomalies=3, length=128, amp=0.6, seed=3)
s = 128
print(f"series {x.shape[0]} pts, planted anomalies at {planted}\n")

base = SearchSpec(s=s, k=3, method="hst")
assert base.replace(method="distributed").method == "ring"  # one name

t0 = time.perf_counter()
serial = DiscordEngine(base).search(x)
print(f"serial HST        : {serial.positions} "
      f"({time.perf_counter() - t0:.2f}s, {serial.calls} calls, "
      f"cps={serial.cps:.1f})")

ring_eng = DiscordEngine(base.replace(method="ring"))
t0 = time.perf_counter()
ring = ring_eng.search(x)
print(f"ring MP ({ndev} dev)  : {ring.positions} "
      f"({time.perf_counter() - t0:.2f}s, {ring.tile_lanes} tile "
      f"lanes, cps={ring.cps:.1f})")

# compile-once, mesh-wide: a second same-bucket sharded search reuses
# the compiled ring plan — zero new traces
t0 = time.perf_counter()
ring_eng.search(x[:19_000])
print(f"warm same-bucket  : {time.perf_counter() - t0:.2f}s "
      f"({ring_eng.stats.traces} trace(s) total)")
assert ring_eng.stats.traces == 1

t0 = time.perf_counter()
drag = DiscordEngine(base.replace(method="drag")).search(x)
print(f"DRAG    ({ndev} dev)  : {drag.positions} "
      f"({time.perf_counter() - t0:.2f}s, "
      f"{drag.extra['survivors']} phase-1 survivors)")

assert serial.positions == ring.positions == drag.positions
print("\nall three engines agree (exact).")

# sharded streaming: each append sweeps only the owning shard's tail
# tiles, then min-folds the per-shard results globally
stream = ring_eng.open_stream(history=x[:16_000])
fill = stream.tile_lanes
for lo in range(16_000, 20_000, 1000):
    stream.append(x[lo:lo + 1000])
print(f"\nsharded stream: fill swept {fill} lanes, {stream.appends - 1} "
      f"appends swept {stream.tile_lanes - fill} more")
assert stream.discords().positions == ring.positions

# two-level batched layout: short series go series-parallel across the
# mesh, long ones ring-shard each series
batch = np.stack([x[:4000], x[4000:8000], x[8000:12000]])
rs = ring_eng.search_batched(batch)
print(f"batched ({len(rs)} series): layout={rs[0].extra['layout']}, "
      f"method={rs[0].method}")
