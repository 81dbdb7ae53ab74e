"""Ring plan family: mesh-sharded searches through the session layer.

Runs on 4 forced host-platform devices in a subprocess (device count
must be set before jax init) and pins the PR-3 contract:

  1. PARITY — ring-plan results (nnd profile, neighbors, top-k) match
     the single-device engine exactly, for block-aligned and unaligned
     shard geometries.
  2. COMPILE-ONCE, MESH-WIDE — the second same-bucket sharded search
     adds zero new jit traces (``stats.traces``).
  3. STREAMING — a sharded stream (ring fill + per-shard tail sweeps
     with a global min-fold) matches the exact profile, sweeping fewer
     lanes per append than a full resweep.
  4. TWO-LEVEL BATCHED — series-parallel layout below the
     per-device threshold, ring-per-series above it, both matching
     per-series single-device searches.
  5. CPS — all four planes (serial, hst_jax, engine, ring) report the
     shared work definition of docs/cps.md:
     ``cps == calls / (n * k)``, with ``calls == tile_lanes`` on the
     tiled planes and ``tile_lanes == 0`` on the serial counted plane.
"""
import json
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.pop("REPRO_RING_SERIES_THRESHOLD", None)
import json
import numpy as np
import jax
from repro.core import DiscordEngine, SearchSpec
from repro.core.serial.brute import exact_nnd_profile

rng = np.random.default_rng(0)
x = np.sin(0.08 * np.arange(2500)) + 0.15 * rng.normal(size=2500)
x[1200:1260] += 1.2 * np.sin(np.linspace(0, np.pi, 60))
s = 80
out = {"ndev": len(jax.devices())}

# -- parity, aligned and unaligned shard geometry ----------------------
# block=256: bucket 4096 -> 16 blocks over 4 devices (aligned shards);
# block=64:  31 blocks over 4 devices (needs device-count padding).
for tag, block in (("aligned", 256), ("unaligned", 64)):
    ring = DiscordEngine(SearchSpec(s=s, k=3, method="ring",
                                    block=block, backend="xla"))
    local = DiscordEngine(SearchSpec(s=s, k=3, method="matrix_profile",
                                     block=block, backend="xla"))
    prof_r, ngh_r, *_ = ring._ring_profile(x, s)
    xp = np.zeros(4096, np.float32)
    xp[:x.size] = x
    n = x.size - s + 1
    d2_l, ngh_l = local.plan("profile", s, 4096)(xp, np.int32(n))
    prof_l = np.sqrt(np.asarray(d2_l, np.float64)[:n])
    out[f"prof_close_{tag}"] = bool(np.allclose(prof_r, prof_l,
                                                rtol=1e-4, atol=1e-4))
    out[f"ngh_equal_{tag}"] = bool(
        np.array_equal(ngh_r, np.asarray(ngh_l, np.int64)[:n]))
    rr, rl = ring.search(x), local.search(x)
    out[f"pos_equal_{tag}"] = rr.positions == rl.positions
    out[f"nnd_close_{tag}"] = bool(np.allclose(rr.nnds, rl.nnds,
                                               rtol=1e-5))

# -- zero retrace on the second same-bucket sharded search -------------
eng = DiscordEngine(SearchSpec(s=s, k=3, method="ring", backend="xla"))
eng.search(x)
t1 = eng.stats.traces
eng.search(x[:2400])                      # same 4096 bucket, new length
out["traces_first"] = t1
out["traces_second"] = eng.stats.traces
out["plans"] = eng.stats.plans

# -- sharded stream: ring fill + per-shard tail sweep + global fold ----
st = eng.open_stream(history=x[:2000])
fill_lanes = st.tile_lanes
for lo in range(2000, 2500, 137):
    st.append(x[lo:lo + 137])
ref = exact_nnd_profile(np.asarray(x, np.float64), s)
out["stream_close"] = bool(np.allclose(st.profile(), ref, atol=3e-3))
out["stream_appends"] = st.appends
out["append_lanes_lt_fill"] = bool(st.tile_lanes - fill_lanes
                                   < fill_lanes)
full = eng.search(x)
got = st.discords()
out["stream_pos_equal"] = got.positions == full.positions

# -- two-level batched layout ------------------------------------------
stack = np.stack([x[:1000], x[1000:2000], x[500:1500]])
local1 = DiscordEngine(SearchSpec(s=s, k=3, method="matrix_profile",
                                  backend="xla"))
refs = [local1.search(row) for row in stack]
rs = eng.search_batched(stack)            # short series: series-parallel
out["batched_layout_short"] = rs[0].extra["layout"]
out["batched_pos_equal_short"] = all(
    r.positions == f.positions for r, f in zip(rs, refs))
os.environ["REPRO_RING_SERIES_THRESHOLD"] = "100"
rs2 = eng.search_batched(stack)           # now "long": ring per series
out["batched_layout_long"] = rs2[0].extra["layout"]
out["batched_pos_equal_long"] = all(
    r.positions == f.positions for r, f in zip(rs2, refs))

# -- shared cps definition across the four planes ----------------------
planes = {
    "serial": DiscordEngine(SearchSpec(s=s, k=3,
                                       method="hst")).search(x),
    "hst_jax": DiscordEngine(SearchSpec(s=s, k=3, method="hst_jax",
                                        backend="xla")).search(x),
    "engine": local1.search(x),
    "ring": eng.search(x),
}
cps = {}
for name, r in planes.items():
    cps[name] = {
        "cps_matches": abs(r.cps - r.calls / (r.n * r.k)) < 1e-9,
        "tile_lanes": int(r.tile_lanes),
        "calls": int(r.calls),
        "k": r.k,
    }
out["cps"] = cps
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    p = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_runs_on_four_devices(result):
    assert result["ndev"] == 4


@pytest.mark.parametrize("tag", ["aligned", "unaligned"])
def test_ring_profile_matches_single_device(result, tag):
    assert result[f"prof_close_{tag}"]
    assert result[f"ngh_equal_{tag}"]


@pytest.mark.parametrize("tag", ["aligned", "unaligned"])
def test_ring_topk_matches_single_device(result, tag):
    assert result[f"pos_equal_{tag}"]
    assert result[f"nnd_close_{tag}"]


def test_second_sharded_search_adds_zero_traces(result):
    assert result["traces_first"] == result["traces_second"] == 1
    assert result["plans"] == 1


def test_sharded_stream_parity_and_tail_only_lanes(result):
    assert result["stream_close"]
    assert result["stream_pos_equal"]
    assert result["stream_appends"] == 5
    assert result["append_lanes_lt_fill"]


def test_batched_two_level_layout(result):
    assert result["batched_layout_short"] == "series-parallel"
    assert result["batched_layout_long"] == "ring-per-series"
    assert result["batched_pos_equal_short"]
    assert result["batched_pos_equal_long"]


def test_cps_shared_definition_across_planes(result):
    cps = result["cps"]
    for name, row in cps.items():
        assert row["cps_matches"], name
        assert row["k"] == 3, name
    # tiled planes: calls IS the swept lane count
    for name in ("hst_jax", "engine", "ring"):
        assert cps[name]["tile_lanes"] == cps[name]["calls"] > 0, name
    # serial counted plane has no tile plane
    assert cps["serial"]["tile_lanes"] == 0
    assert cps["serial"]["calls"] > 0
