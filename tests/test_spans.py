"""The profile search's host spans (``repro.core.engine`` docstring).

Two searches of a fresh session are profiled; the ``.xplane.pb`` is
read back with ``jax.profiler.ProfileData``.  Each search must show
``engine.search`` once, with its six phases nested inside it in order
and without overlap, all on one host line, with ``search`` stats 0 and
1.  Outside a trace the same searches answer exactly as the profile
path did before it had spans.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DiscordEngine, SearchSpec
from repro.core.engine import _bucket_pad
from repro.core.spec import length_bucket
from repro.core.tiles import topk_nonoverlapping

S = 64
LENGTHS = (2000, 1900)          # one bucket: one plan, one trace
PHASES = ("engine.prepare", "engine.dispatch", "engine.wait",
          "engine.fetch", "engine.select")


def _series(n, seed):
    rng = np.random.default_rng(seed)
    x = np.sin(0.07 * np.arange(n)) + 0.1 * rng.normal(size=n)
    x[n // 2:n // 2 + S] += 0.8 * rng.normal(size=S)
    return x


def _engine():
    return DiscordEngine(SearchSpec(s=S, k=2, method="matrix_profile",
                                    backend="xla"))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Spans and results of two searches made under the profiler."""
    from jax.profiler import ProfileData
    d = str(tmp_path_factory.mktemp("spans"))
    eng = _engine()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        results = [eng.search(_series(n, i))
                   for i, n in enumerate(LENGTHS)]
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                      recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns),
                    int(e.start_ns + e.duration_ns), dict(e.stats))
                   for e in line.events if e.name.startswith("engine.")]
            if evs:
                lines[(plane.name, line.name)] = sorted(
                    evs, key=lambda e: e[1])
    return {"lines": lines, "results": results, "stats": eng.stats}


def _searches(traced):
    (evs,) = traced["lines"].values()
    return [e for e in evs if e[0] == "engine.search"], evs


def test_spans_on_one_host_line(traced):
    assert len(traced["lines"]) == 1
    (plane, _line), = traced["lines"]
    assert plane.startswith("/host:")
    searches, evs = _searches(traced)
    assert len(searches) == len(LENGTHS)
    assert len(evs) == len(LENGTHS) * (1 + len(PHASES))


@pytest.mark.parametrize("i", range(len(LENGTHS)))
def test_phases_nest_in_order_without_overlap(traced, i):
    searches, evs = _searches(traced)
    _, lo, hi, _ = searches[i]
    inside = [e for e in evs if lo <= e[1] and e[2] <= hi
              and e[0] != "engine.search"]
    assert tuple(e[0] for e in inside) == PHASES
    for (_, _, end, _), (_, start, _, _) in zip(inside, inside[1:]):
        assert end <= start


@pytest.mark.parametrize("i", range(len(LENGTHS)))
def test_search_span_stats(traced, i):
    searches, _ = _searches(traced)
    stats = searches[i][3]
    assert stats["search"] == i
    assert stats["kind"] == "profile"
    assert stats["n"] == LENGTHS[i] - S + 1
    assert stats["bucket"] == length_bucket(LENGTHS[i])
    assert stats["grid"] == "square"            # xla sweeps the square


@pytest.mark.parametrize("i", range(len(LENGTHS)))
def test_untraced_search_answers_as_before(traced, i):
    """Outside a trace: the traced answer, and that of the plan called
    as the profile path called it before it had spans."""
    eng = _engine()
    for j in range(i):
        eng.search(_series(LENGTHS[j], j))
    x = _series(LENGTHS[i], i)
    r = eng.search(x)
    t = traced["results"][i]
    assert list(r.positions) == list(t.positions)
    assert list(r.nnds) == list(t.nnds)
    n, Lb = len(x) - S + 1, length_bucket(len(x))
    d2, _ = eng.plan("profile", S, Lb)(jnp.asarray(_bucket_pad(x, Lb)),
                                       np.int32(n))
    prof = np.sqrt(np.asarray(d2, np.float64)[:n])
    pos, vals = topk_nonoverlapping(
        np.where(np.isfinite(prof), prof, -np.inf), 2, S)
    assert list(r.positions) == list(pos)
    assert list(r.nnds) == list(vals)


def test_untraced_engine_stats_match(traced):
    eng = _engine()
    for i, n in enumerate(LENGTHS):
        eng.search(_series(n, i))
    assert eng.stats.as_dict() == traced["stats"].as_dict()
    lanes = eng._n_pad(S, length_bucket(LENGTHS[0])) ** 2
    assert eng.stats.as_dict() == {"traces": 1, "plans": 1,
                                   "searches": 2, "appends": 0,
                                   "tile_lanes": 2 * lanes}
