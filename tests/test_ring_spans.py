"""The ring search's host spans (``repro.core.engine`` docstring).

Runs on 4 forced host-platform devices in a subprocess (the device
count must be set before JAX starts), as ``test_ring_engine.py`` does.
Two ring searches of a fresh session and one batched search in the
ring-per-series layout are profiled, and the ``.xplane.pb`` is read
back with ``jax.profiler.ProfileData``.  Each ring search must show one
``engine.search`` (``kind`` ring) with the five phases of the profile
path nested inside it in order and without overlap, and the stats
``search``, ``bucket``, ``n`` and ``ndev``; the batched entry nests
its ring searches inside its own span.  Outside a trace the same
searches answer exactly as the ring plan called directly does, with
the same ``EngineStats``.
"""
import json

import pytest

from conftest import run_sharded_subprocess

S = 64
LENGTHS = (2000, 1900)          # one bucket: one plan, one trace
BATCH = 2
PHASES = ["engine.prepare", "engine.dispatch", "engine.wait",
          "engine.fetch", "engine.select"]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["REPRO_RING_SERIES_THRESHOLD"] = "100"
import glob, json, tempfile
import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import ProfileData
from repro.core import DiscordEngine, SearchSpec
from repro.core.engine import _bucket_pad
from repro.core.spec import length_bucket
from repro.core.tiles import topk_nonoverlapping

S, LENGTHS, BATCH = %(S)d, %(LENGTHS)r, %(BATCH)d


def series(n, seed):
    rng = np.random.default_rng(seed)
    x = np.sin(0.07 * np.arange(n)) + 0.1 * rng.normal(size=n)
    x[n // 2:n // 2 + S] += 0.8 * rng.normal(size=S)
    return x


def engine():
    return DiscordEngine(SearchSpec(s=S, k=2, method="ring",
                                    backend="xla"))


def answer(r):
    return [list(map(int, r.positions)), list(map(float, r.nnds))]


def run(eng):
    rs = [eng.search(series(n, i)) for i, n in enumerate(LENGTHS)]
    stack = np.stack([series(LENGTHS[0], 10 + b) for b in range(BATCH)])
    rb = eng.search_batched(stack)
    return [answer(r) for r in rs + rb], [r.extra.get("layout")
                                          for r in rb]


def plain(v):
    return v if isinstance(v, (int, float, str)) else str(v)


out = {"ndev": len(jax.devices())}
d = tempfile.mkdtemp()
eng = engine()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
jax.profiler.start_trace(d, profiler_options=opts)
try:
    out["traced"], out["layouts"] = run(eng)
finally:
    jax.profiler.stop_trace()
out["traced_stats"] = eng.stats.as_dict()
path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
lines = []
for plane in ProfileData.from_file(path).planes:
    for line in plane.lines:
        evs = [[e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                {k: plain(v) for k, v in e.stats}]
               for e in line.events if e.name.startswith("engine.")]
        if evs:
            lines.append([plane.name, sorted(evs, key=lambda e: e[1])])
out["lines"] = lines

# untraced, and the ring plan called as the search path called it
# before it had spans
eng2 = engine()
out["untraced"], _ = run(eng2)
out["untraced_stats"] = eng2.stats.as_dict()
eng3 = engine()
direct, lanes = [], 0
xs = [series(n, i) for i, n in enumerate(LENGTHS)]
xs += [series(LENGTHS[0], 10 + b) for b in range(BATCH)]
for x in xs:
    n, Lb = len(x) - S + 1, length_bucket(len(x))
    d2, arg, ln, _ = eng3._ring_exec(S, Lb, jnp.asarray(_bucket_pad(x, Lb)),
                                     np.int32(n))
    prof = np.sqrt(np.asarray(d2, np.float64)[:n])
    pos, vals = topk_nonoverlapping(
        np.where(np.isfinite(prof), prof, -np.inf), 2, S)
    direct.append([list(map(int, pos)), list(map(float, vals))])
    lanes += ln
out["direct"] = direct
out["direct_stats"] = {"traces": eng3.stats.traces,
                       "plans": eng3.stats.plans,
                       "searches": len(LENGTHS) + 1, "appends": 0,
                       "tile_lanes": lanes}
print(json.dumps(out))
""" % {"S": S, "LENGTHS": LENGTHS, "BATCH": BATCH}


@pytest.fixture(scope="module")
def result():
    p = run_sharded_subprocess(SCRIPT, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _events(result):
    (plane, evs), = result["lines"]
    assert plane.startswith("/host:")
    return evs


def _ring_searches(evs):
    return [e for e in evs if e[0] == "engine.search"
            and e[3].get("kind") == "ring"]


def _inside(evs, outer):
    _, lo, hi, _ = outer
    return [e for e in evs if lo <= e[1] and e[2] <= hi and e is not outer]


def test_runs_on_four_devices(result):
    assert result["ndev"] == 4
    assert result["layouts"] == ["ring-per-series"] * BATCH


def test_spans_on_one_host_line(result):
    evs = _events(result)
    n_ring = len(LENGTHS) + BATCH
    assert len(_ring_searches(evs)) == n_ring
    # the ring searches with their phases, and the batched entry's span
    assert len(evs) == n_ring * (1 + len(PHASES)) + 1


@pytest.mark.parametrize("i", range(len(LENGTHS) + BATCH))
def test_phases_nest_in_order_without_overlap(result, i):
    evs = _events(result)
    inside = _inside(evs, _ring_searches(evs)[i])
    assert [e[0] for e in inside] == PHASES
    for a, b in zip(inside, inside[1:]):
        assert a[2] <= b[1]


@pytest.mark.parametrize("i", range(len(LENGTHS)))
def test_search_span_stats(result, i):
    stats = _ring_searches(_events(result))[i][3]
    assert stats["kind"] == "ring"
    assert stats["search"] == i
    assert stats["n"] == LENGTHS[i] - S + 1
    assert stats["bucket"] == 2048
    assert stats["ndev"] == 4


def test_batched_ring_per_series_nests(result):
    evs = _events(result)
    outer, = [e for e in evs if e[0] == "engine.search"
              and e[3].get("kind") == "batched"]
    inner = [e for e in _inside(evs, outer) if e[0] == "engine.search"]
    assert len(inner) == BATCH
    assert all(e[3]["kind"] == "ring" for e in inner)
    # the batched call takes one index, after the two single searches
    assert [e[3]["search"] for e in inner] == [len(LENGTHS)] * BATCH
    assert all(e[3]["n"] == LENGTHS[0] - S + 1 for e in inner)


def test_untraced_answers_as_before(result):
    assert result["untraced"] == result["traced"]
    assert result["untraced"] == result["direct"]


def test_untraced_engine_stats_match(result):
    assert result["untraced_stats"] == result["traced_stats"]
    assert result["untraced_stats"] == result["direct_stats"]
    assert result["untraced_stats"]["traces"] == 1
    assert result["untraced_stats"]["plans"] == 1
