"""The ring's balanced shards and live-bounded hops.

The ring plan deals the window blocks to its devices block-cyclically
(``TileEngine.window_set``) and, on ``pallas``, stops each hop's grid
at ``ceil(live blocks / ndev)`` blocks a side.  On 4 forced host
devices (a subprocess: the device count must be set before JAX
starts), on ``xla`` and ``pallas`` (interpret), the ring profile must
match the one-device profile plan at lengths whose live block counts
are 0, 1, 2 and 3 mod 4, and at one with fewer live blocks than
devices, where whole shards are padding: nnds within the ring's
parity tolerance, each neighbour realizing its distance and at least
``s`` away, the same top-k.  ``tile_lanes`` books the grid swept:
``ndev^2 m^2 block^2`` on ``pallas``, the whole shard square on
``xla``; a traced search's ``engine.search`` span carries
``hop_blocks``, the ``m`` it swept.
"""
import json

import pytest

from conftest import run_sharded_subprocess

S, BLOCK, NDEV = 24, 32, 4
#: lengths in the 512 bucket (16 blocks of 32, 4 a shard) with 12, 9,
#: 10 and 11 live blocks, and one in the 256 bucket (8 blocks, 2 a
#: shard) with 3, fewer than the devices
LENGTHS = (400, 300, 320, 350, 113)
BACKENDS = ("xla", "pallas")
TRACED = 350                     # the length searched under a trace

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import glob, json, tempfile
import numpy as np
import jax
from jax.profiler import ProfileData
from repro.core import DiscordEngine, SearchSpec
from repro.core.engine import _bucket_pad
from repro.core.spec import length_bucket

S, BLOCK, LENGTHS, BACKENDS, TRACED = (%(S)d, %(BLOCK)d, %(LENGTHS)r,
                                       %(BACKENDS)r, %(TRACED)d)


def series(L):
    rng = np.random.default_rng(L)
    x = np.sin(0.07 * np.arange(L)) + 0.1 * rng.normal(size=L)
    x[L // 3:L // 3 + S] += 0.8 * np.sin(np.linspace(0, np.pi, S))
    return x


def engine(method, backend):
    return DiscordEngine(SearchSpec(s=S, k=2, method=method, block=BLOCK,
                                    backend=backend))


out = {"ndev": len(jax.devices()), "cases": {}, "traces": {}}
for backend in BACKENDS:
    ring, local = engine("ring", backend), engine("matrix_profile", backend)
    for L in LENGTHS:
        x = series(L)
        n, Lb = L - S + 1, length_bucket(L)
        before = ring.stats.tile_lanes
        prof, ngh, *_ = ring._ring_profile(x, S)
        booked = ring.stats.tile_lanes - before
        d2, _ = local.plan("profile", S, Lb)(_bucket_pad(x, Lb),
                                             np.int32(n))
        ref = np.sqrt(np.asarray(d2, np.float64)[:n])
        w = np.lib.stride_tricks.sliding_window_view(x, S)
        z = (w - w.mean(1, keepdims=True)) / w.std(1, keepdims=True)
        real = np.linalg.norm(z - z[ngh], axis=1)
        rr, rl = ring.search(x), local.search(x)
        out["cases"][f"{backend}-{L}"] = {
            "n": n, "bucket": Lb, "booked": int(booked),
            "calls": int(rr.calls),
            "prof_close": bool(np.allclose(prof, ref, rtol=1e-4,
                                           atol=1e-4)),
            "prof_gap": float(np.max(np.abs(prof - ref))),
            "real_gap": float(np.max(np.abs(real - prof))),
            "min_sep": int(np.min(np.abs(ngh - np.arange(n)))),
            "pos": [list(rr.positions), list(rl.positions)],
            "nnds": [list(rr.nnds), list(rl.nnds)]}
    out["traces"][backend] = ring.stats.traces

# one traced pallas ring search: the span's stats
ring = engine("ring", "pallas")
ring.search(series(TRACED))              # compile outside the trace
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
try:
    ring.search(series(TRACED))
finally:
    jax.profiler.stop_trace()
path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
spans = []
for plane in ProfileData.from_file(path).planes:
    for line in plane.lines:
        for e in line.events:
            if e.name == "engine.search":
                spans.append({k: (v if isinstance(v, (int, float, str))
                                  else str(v)) for k, v in e.stats})
out["spans"] = spans
print(json.dumps(out))
""" % {"S": S, "BLOCK": BLOCK, "LENGTHS": LENGTHS, "BACKENDS": BACKENDS,
       "TRACED": TRACED}

CASES = [f"{b}-{L}" for b in BACKENDS for L in LENGTHS]


def _ceil(a, b):
    return -(-a // b)


def hop_blocks(n):
    """``m``: the live blocks of ``n`` windows over the devices."""
    return _ceil(_ceil(n, BLOCK), NDEV)


@pytest.fixture(scope="module")
def result():
    p = run_sharded_subprocess(SCRIPT, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_runs_on_four_devices(result):
    assert result["ndev"] == NDEV


def test_lengths_cover_every_residue_and_a_short_record():
    live = [_ceil(L - S + 1, BLOCK) for L in LENGTHS]
    assert sorted(v % NDEV for v in live[:4]) == [0, 1, 2, 3]
    assert live[4] < NDEV


@pytest.mark.parametrize("case", CASES)
def test_ring_profile_matches_one_device(result, case):
    c = result["cases"][case]
    assert c["prof_close"], c["prof_gap"]


@pytest.mark.parametrize("case", CASES)
def test_neighbours_realize_their_distance(result, case):
    c = result["cases"][case]
    assert c["real_gap"] < 2e-3
    assert c["min_sep"] >= S


@pytest.mark.parametrize("case", CASES)
def test_ring_topk_matches_one_device(result, case):
    (pos_r, pos_l), (nnd_r, nnd_l) = (result["cases"][case][k]
                                      for k in ("pos", "nnds"))
    assert pos_r == pos_l
    assert all(abs(a - b) <= 1e-5 * b for a, b in zip(nnd_r, nnd_l))


@pytest.mark.parametrize("case", CASES)
def test_lanes_book_the_swept_grid(result, case):
    c = result["cases"][case]
    if case.startswith("pallas"):
        side = hop_blocks(c["n"]) * BLOCK
    else:                        # the whole shard, padding included
        nb = _ceil(c["bucket"] - S + 1, BLOCK)
        side = _ceil(nb, NDEV) * BLOCK
    assert c["booked"] == c["calls"] == NDEV * NDEV * side * side


def test_bound_engages_below_the_bucket(result):
    """300 points fill 9 of the 512 bucket's 16 blocks: 3 of each
    shard's 4 are swept a hop, 9/16 of the square."""
    pallas, xla = (result["cases"][f"{b}-300"]["booked"]
                   for b in ("pallas", "xla"))
    assert 16 * pallas == 9 * xla


def test_one_plan_per_bucket(result):
    assert result["traces"] == {b: 2 for b in BACKENDS}


def test_search_span_carries_hop_blocks(result):
    (span,) = [sp for sp in result["spans"] if sp.get("kind") == "ring"]
    assert span["ndev"] == NDEV
    assert span["n"] == TRACED - S + 1
    assert span["hop_blocks"] == hop_blocks(TRACED - S + 1) == 3
