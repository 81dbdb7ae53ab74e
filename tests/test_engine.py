"""Session API contract: SearchSpec + DiscordEngine + DiscordStream.

  1. SPEC — frozen, validated, hashable; aliases canonicalize
     (``distributed`` == ``ring``, ``jnp`` == ``xla``); multi-window
     tuples only with the profile method.
  2. COMPILE-ONCE — a second search in the same length bucket triggers
     zero new jit traces (the engine's plan bodies count their own
     traces); a new bucket traces exactly once more; streams share the
     session's plan cache.
  3. STREAMING — ``DiscordStream.append``-built profiles match a
     from-scratch search of the concatenated series on every backend
     (numpy / xla / pallas-interpret), in both z-normalized and raw
     Euclidean mode, while sweeping only the appended tail tile rows
     (tile-lane counter strictly below the full-sweep count).
  4. REPORTING — batched results carry the true per-batch wall clock
     and total tile-op counts; the deprecated wrappers warn and agree
     with the session API.
  5. PLAN TABLE — every ``PLAN_KINDS`` kind builds through
     ``DiscordEngine.plan`` under its pinned cache key and traces once;
     each lane of a stacked kind equals its op's local plan bit for
     bit.
"""
import numpy as np
import pytest

from repro.core import (DiscordEngine, DiscordStream, SearchSpec,
                        find_discords, find_discords_batched)
from repro.core.serial.brute import exact_nnd_profile
from repro.core.spec import canonical_method, length_bucket
from repro.core.tiles import topk_nonoverlapping

BACKENDS = ("numpy", "xla", "pallas")


def _series(seed, n=420):
    rng = np.random.default_rng(seed)
    t = np.arange(n)
    x = np.sin(0.07 * t) + 0.1 * rng.normal(size=n)
    if n > 200:               # short chunks (stream appends) stay plain
        p = int(rng.integers(80, n - 80))
        x[p:p + 30] += rng.uniform(0.7, 1.3) * np.sin(
            np.linspace(0, np.pi, 30))
    return x


# ----------------------------------------------------------------------
# SearchSpec
# ----------------------------------------------------------------------
def test_spec_canonicalization_and_aliases():
    assert canonical_method("distributed") == "ring"
    assert canonical_method("ring") == "ring"
    assert canonical_method("scamp") == "matrix_profile"
    assert SearchSpec(s=32, method="distributed").method == "ring"
    assert SearchSpec(s=32, backend="jnp").backend == "xla"
    assert SearchSpec(s=[48]).s == 48              # singleton -> scalar
    assert SearchSpec(s=[48, 64], method="mp").s == (48, 64)


@pytest.mark.parametrize("bad", [
    dict(s=32, method="nope"),
    dict(s=1),
    dict(s=32, k=0),
    dict(s=32, r=-1.0),
    dict(s=32, backend="cuda-typo"),
    dict(s=(32, 48), method="hst"),        # multi-window needs profile
    dict(s=(32, 32), method="matrix_profile"),     # duplicate lengths
    dict(s=32, method="hst_jax", znorm=False),     # Eq.(3)-only method
    dict(s=32, method="dadd", znorm=False),
    dict(s=32, method="hst", ndev=2),      # ndev is sharded-plane only
    dict(s=32, method="ring", ndev=0),
])
def test_spec_validation_rejects(bad):
    with pytest.raises(ValueError):
        SearchSpec(**bad)


def test_spec_hashable_and_replace():
    a = SearchSpec(s=64, k=2, method="matrix_profile")
    b = SearchSpec(s=64, k=2, method="scamp")      # alias -> equal spec
    assert a == b and hash(a) == hash(b)
    cache = {a: "plan"}
    assert cache[b] == "plan"
    c = a.replace(k=3)
    assert c.k == 3 and c != a and a.k == 2        # frozen original


def test_length_bucket_powers_of_two():
    assert length_bucket(1) == 256
    assert length_bucket(256) == 256
    assert length_bucket(257) == 512
    assert length_bucket(40, lo=32) == 64


# ----------------------------------------------------------------------
# compile-once plan cache
# ----------------------------------------------------------------------
def test_second_same_bucket_search_traces_nothing():
    eng = DiscordEngine(SearchSpec(s=32, k=2, method="matrix_profile",
                                   backend="xla"))
    r1 = eng.search(_series(0, 500))
    assert eng.stats.traces == 1 and eng.stats.plans == 1
    r2 = eng.search(_series(1, 460))       # different length, same 512
    assert eng.stats.traces == 1, "same-bucket search must not retrace"
    assert eng.stats.searches == 2
    assert r1.extra["bucket"] == r2.extra["bucket"] == 512
    eng.search(_series(2, 600))            # new 1024 bucket
    assert eng.stats.traces == 2 and eng.stats.plans == 2


def test_stream_shares_session_plan_cache():
    eng = DiscordEngine(SearchSpec(s=32, k=1, method="matrix_profile",
                                   backend="xla"))
    eng.search(_series(3, 500))
    t = eng.stats.traces
    st = eng.open_stream(history=_series(4, 430))  # same bucket: reuse
    assert eng.stats.traces == t
    st.append(_series(5, 30))              # first tail plan traces once
    assert eng.stats.traces == t + 1
    st.append(_series(6, 25))              # same (Lb, Qb): no retrace
    assert eng.stats.traces == t + 1


def test_bucketed_search_matches_exact_profile():
    x = _series(7, 500)
    for s in (24, 33):                     # tail straddles the bucket
        r = DiscordEngine(SearchSpec(s=s, k=2,
                                     method="matrix_profile",
                                     backend="xla")).search(x)
        prof = exact_nnd_profile(np.asarray(x, np.float64), s)
        pos, vals = topk_nonoverlapping(prof, 2, s)
        assert r.positions == pos
        assert np.allclose(r.nnds, vals, atol=3e-3)


# ----------------------------------------------------------------------
# streaming: parity + tail-only sweeps
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
def test_stream_append_parity_every_backend(backend):
    """append-built profile == from-scratch profile of the
    concatenation, and the discords agree with a full search."""
    x = _series(10, 400)
    s = 24
    eng = DiscordEngine(SearchSpec(s=s, k=2, method="matrix_profile",
                                   backend=backend))
    st = eng.open_stream(history=x[:300])
    for lo, hi in ((300, 340), (340, 371), (371, 400)):
        st.append(x[lo:hi])
    assert st.n_points == 400 and st.n_windows == 400 - s + 1
    ref = exact_nnd_profile(np.asarray(x, np.float64), s)
    assert np.allclose(st.profile(), ref, atol=3e-3), backend
    full = eng.search(x)
    got = st.discords()
    assert got.positions == full.positions, backend
    assert np.allclose(got.nnds, full.nnds, rtol=1e-4), backend
    # neighbors respect the exclusion zone
    ngh = st.neighbors()
    assert np.all(np.abs(ngh - np.arange(st.n_windows)) >= s)


@pytest.mark.parametrize("backend", ("xla", "pallas"))
def test_profile_tile_lanes_count_swept_blocks(backend):
    """``tile_lanes`` books what the profile paths sweep: the upper
    triangle of the mpblock kernel's live blocks on ``pallas``, the
    bucket's padded square on ``xla`` -- per search, per batched series
    and for a stream's first fill."""
    s, block, L = 32, 128, 700
    eng = DiscordEngine(SearchSpec(s=s, k=1, method="matrix_profile",
                                   backend=backend, block=block))
    n_pad = eng._n_pad(s, length_bucket(L))
    nb = -(-(L - s + 1) // block)
    swept = (nb * (nb + 1) // 2 * block ** 2 if backend == "pallas"
             else n_pad ** 2)
    assert nb * block < n_pad
    x = _series(3, L)
    r = eng.search(x)
    assert r.calls == r.tile_lanes == eng.stats.tile_lanes == swept
    rs = eng.search_batched(np.stack([x, x[::-1]]))
    assert [b.calls for b in rs] == [swept, swept]
    assert eng.stats.tile_lanes == 3 * swept
    st = eng.open_stream(history=x)
    assert st.tile_lanes == swept
    assert eng.stats.tile_lanes == 4 * swept


@pytest.mark.parametrize("backend", ("xla", "pallas"))
def test_profile_calls_count_its_grid(backend):
    """A profile search's ``calls`` is the tiles its grid sweeps: the
    upper triangle of the live blocks on ``pallas`` (each window pair
    once), the padded square on ``xla`` (each pair twice), and
    ``extra["grid"]`` says which ran."""
    s, block, L = 24, 64, 600
    eng = DiscordEngine(SearchSpec(s=s, k=1, method="matrix_profile",
                                   backend=backend, block=block))
    r = eng.search(_series(4, L))
    n = L - s + 1
    nb = -(-n // block)
    if backend == "pallas":
        assert r.extra["grid"] == "triangle"
        assert r.calls == nb * (nb + 1) // 2 * block ** 2
        assert r.calls < n * n          # under the pairs counted twice
    else:
        assert r.extra["grid"] == "square"
        assert r.calls == eng._n_pad(s, length_bucket(L)) ** 2


def test_stream_sweeps_only_tail_rows():
    eng = DiscordEngine(SearchSpec(s=24, k=1, method="matrix_profile",
                                   backend="xla"))
    st = eng.open_stream(history=_series(11, 400))
    full_lanes = st.tile_lanes             # init == one full sweep
    before = eng.stats.tile_lanes
    st.append(_series(12, 40))
    append_lanes = eng.stats.tile_lanes - before
    assert 0 < append_lanes < full_lanes, \
        (append_lanes, full_lanes)         # tail rows only, not O(N^2)
    # a fresh from-scratch search re-sweeps the full tile grid
    eng2 = DiscordEngine(SearchSpec(s=24, k=1, method="matrix_profile",
                                    backend="xla"))
    eng2.search(np.concatenate([_series(11, 400), _series(12, 40)]))
    assert append_lanes < eng2.stats.tile_lanes


@pytest.mark.parametrize("backend", BACKENDS)
def test_stream_raw_euclidean_parity(backend):
    """znorm=False (DADD/telemetry convention): the rank-1 norm
    correction recovers exact raw distances through the Eq. (3)
    backends."""
    x = _series(13, 380)
    s = 20
    eng = DiscordEngine(SearchSpec(s=s, k=2, method="matrix_profile",
                                   backend=backend, znorm=False))
    st = eng.open_stream(history=x[:300])
    st.append(x[300:])
    ref = exact_nnd_profile(np.asarray(x, np.float64), s, znorm=False)
    assert np.allclose(st.profile(), ref, atol=1e-2), backend


def test_stream_buffers_until_one_window():
    eng = DiscordEngine(SearchSpec(s=32, k=1, method="matrix_profile",
                                   backend="xla"))
    st = eng.open_stream()
    st.append(np.zeros(10))                # < s: no windows yet
    assert st.n_windows == 0 and st.discords().positions == []
    x = _series(14, 300)
    st2 = eng.open_stream(history=x[:20])
    st2.append(x[20:])                     # first real fill
    ref = exact_nnd_profile(np.asarray(x, np.float64), 32)
    assert np.allclose(st2.profile(), ref, atol=3e-3)


# ----------------------------------------------------------------------
# multi-window
# ----------------------------------------------------------------------
def test_multi_window_matches_single_window_searches():
    x = _series(15, 450)
    eng = DiscordEngine(SearchSpec(s=(24, 32), k=2,
                                   method="matrix_profile",
                                   backend="xla"))
    r24, r32 = eng.search(x)
    assert (r24.s, r32.s) == (24, 32)
    for r in (r24, r32):
        one = DiscordEngine(SearchSpec(s=r.s, k=2,
                                       method="matrix_profile",
                                       backend="xla")).search(x)
        assert r.positions == one.positions
        assert np.allclose(r.nnds, one.nnds, rtol=1e-4)
    # both lengths ride ONE pan-length ladder sweep (PR 4): one plan,
    # and fewer swept lanes than two independent per-length sweeps
    assert eng.stats.plans == 1
    assert eng.stats.tile_lanes < 2 * 512 ** 2


# ----------------------------------------------------------------------
# batched reporting
# ----------------------------------------------------------------------
def test_batched_true_wall_clock_and_tile_ops():
    xb = np.stack([_series(20), _series(21), _series(22)])
    eng = DiscordEngine(SearchSpec(s=32, k=2, method="matrix_profile",
                                   backend="xla"))
    rs = eng.search_batched(xb)
    assert len(rs) == 3
    # every member reports the SAME true batch wall clock, not /B
    assert len({r.runtime_s for r in rs}) == 1
    for r in rs:
        assert r.extra["batch_size"] == 3
        assert r.extra["per_series_s"] == pytest.approx(
            r.runtime_s / 3)
        assert r.extra["tile_lanes"] == 3 * 512 ** 2
    # parity with per-series searches
    for i, r in enumerate(rs):
        one = eng.search(xb[i])
        assert r.positions == one.positions
        assert np.allclose(r.nnds, one.nnds, rtol=1e-4)


# ----------------------------------------------------------------------
# deprecated wrappers
# ----------------------------------------------------------------------
def test_wrappers_warn_and_agree_with_session_api():
    x = _series(23, 400)
    with pytest.warns(DeprecationWarning):
        r = find_discords(x, 32, 2, method="matrix_profile",
                          backend="xla")
    eng = DiscordEngine(SearchSpec(s=32, k=2, method="matrix_profile",
                                   backend="xla"))
    assert r.positions == eng.search(x).positions
    with pytest.warns(DeprecationWarning):
        rb = find_discords_batched(x[None, :], 32, 2, backend="xla")
    assert rb[0].positions == r.positions
    assert "per_series_s" in rb[0].extra


def test_wrapper_accepts_both_ring_spellings():
    from repro.core.api import engine_for
    a = engine_for(SearchSpec(s=64, method="ring"))
    b = engine_for(SearchSpec(s=64, method="distributed"))
    assert a is b                          # one canonical engine


def test_wrapper_cache_respects_env_backend_flip(monkeypatch):
    """A backend=None spec re-resolves per call: flipping
    REPRO_TILE_BACKEND mid-process must not hit a stale engine."""
    from repro.core.api import engine_for
    spec = SearchSpec(s=48, method="matrix_profile")
    monkeypatch.delenv("REPRO_TILE_BACKEND", raising=False)
    default = engine_for(spec).backend
    monkeypatch.setenv("REPRO_TILE_BACKEND", "numpy")
    assert engine_for(spec).backend == "numpy"
    monkeypatch.delenv("REPRO_TILE_BACKEND")
    assert engine_for(spec).backend == default


def test_spec_coerces_numeric_fields():
    spec = SearchSpec(s=np.int64(32), k=2.0, seed=np.int32(5),
                      r=np.float32(1.5), method="dadd")
    assert spec == SearchSpec(s=32, k=2, seed=5, r=1.5, method="dadd")
    assert type(spec.k) is int and type(spec.r) is float


def test_profile_search_rejects_stray_kwargs():
    eng = DiscordEngine(SearchSpec(s=32, method="matrix_profile",
                                   backend="xla"))
    with pytest.raises(TypeError):
        eng.search(_series(30, 300), interpret=True)


def test_batched_and_stream_reject_non_profile_methods():
    """search_batched/open_stream run the exact-profile plan family;
    any other method must raise instead of silently ignoring its
    semantics (e.g. drag's threshold, hst's counted plane)."""
    for method in ("hst", "hst_jax", "drag"):
        eng = DiscordEngine(SearchSpec(s=32, method=method,
                                       backend="xla"))
        with pytest.raises(ValueError, match="profile plan"):
            eng.search_batched(np.zeros((2, 300)))
        with pytest.raises(ValueError, match="profile plan"):
            eng.open_stream()


# ----------------------------------------------------------------------
# telemetry monitor rides the stream
# ----------------------------------------------------------------------
def test_monitor_appends_instead_of_recomputing():
    from repro.telemetry import DiscordMonitor, MetricBuffer
    rng = np.random.default_rng(0)
    buf = MetricBuffer()
    mon = DiscordMonitor(buf, window=16, k=2)
    for i in range(400):
        buf.log(i, {"loss": 2.0 + 0.01 * rng.normal()})
    rep1 = mon.scan_metric("loss")
    assert rep1 is not None and not rep1.any_flagged
    assert mon.engine.stats.appends == 1   # first scan = one full fill
    for i in range(400, 500):
        v = 2.0 + 0.01 * rng.normal() + (1.5 if 450 <= i < 466 else 0.0)
        buf.log(i, {"loss": v})
    before = mon.engine.stats.tile_lanes
    rep2 = mon.scan_metric("loss")
    delta = mon.engine.stats.tile_lanes - before
    assert mon.engine.stats.appends == 2   # incremental, not recompute
    assert delta < before                  # tail sweep only
    assert rep2.any_flagged
    assert any(440 <= p <= 470 for p in rep2.flagged), rep2.flagged


def test_monitor_handles_drifting_metric():
    """The frozen-at-seed standardization keeps the f32 raw-distance
    math conditioned when the metric drifts (diffs with a large common
    offset would otherwise cancel catastrophically)."""
    from repro.telemetry import DiscordMonitor, MetricBuffer
    rng = np.random.default_rng(3)
    quiet = MetricBuffer()
    spiky = MetricBuffer()
    for i in range(600):
        base = 100.0 - 0.05 * i + 1e-4 * rng.normal()   # steep drift
        quiet.log(i, {"loss": base})
        spiky.log(i, {"loss": base + (0.5 if 400 <= i < 416 else 0.0)})
    rq = DiscordMonitor(quiet, window=16, k=2, z=6.0) \
        .scan_metric("loss")
    assert rq is not None and not rq.any_flagged, rq.flagged
    rs = DiscordMonitor(spiky, window=16, k=2).scan_metric("loss")
    assert rs.any_flagged
    assert any(380 <= p <= 430 for p in rs.flagged), rs.flagged


def test_monitor_wrapped_buffer_rebuild_is_capped():
    """Post-wrap the series is no longer append-only: the monitor
    rebuilds per scan over a bounded window, positions reported in
    visible-series index space."""
    from repro.telemetry import DiscordMonitor, MetricBuffer
    rng = np.random.default_rng(4)
    buf = MetricBuffer(capacity=512)
    mon = DiscordMonitor(buf, window=16, k=2, min_points=64,
                         max_scan_points=256)
    for i in range(700):                   # wraps at 512
        v = 2.0 + 0.01 * rng.normal() + (1.5 if 660 <= i < 676 else 0.0)
        buf.log(i, {"loss": v})
    rep = mon.scan_metric("loss")
    # no stream persisted, rebuild capped at max_scan_points
    assert "loss" not in mon._streams
    assert mon.engine.stats.tile_lanes <= 256 ** 2
    # visible series = last 512 points; spike at visible 472..487
    assert rep.any_flagged
    assert any(450 <= p <= 500 for p in rep.flagged), rep.flagged
    lanes = mon.engine.stats.tile_lanes
    rep2 = mon.scan_metric("loss")         # no new points: memo hit,
    assert rep2.flagged == rep.flagged     # no O(n^2) re-sweep
    assert mon.engine.stats.tile_lanes == lanes
    buf.log(700, {"loss": 2.0})            # new point invalidates memo
    rep3 = mon.scan_metric("loss")
    assert mon.engine.stats.tile_lanes > lanes
    assert rep3.any_flagged


# ---------------------------------------------------------------------
# 5. PLAN TABLE
# ---------------------------------------------------------------------
#: every plan kind's cache key at the audit registry's geometry (s 24,
#: ladder (16, 24, 32), block 32, length 90 -> bucket 256, Qb 32,
#: B 2, one device), as its builder keyed it before the kinds came
#: from one table
PINNED_KEYS = {
    "profile": ("profile", 24, 256),
    "batched": ("batched", 24, 2, 256),
    "tail": ("tail", 24, 256, 32),
    "qsweep": ("qsweep", 24, 256),
    "qsweep_refine": ("qsweep_refine", 24, 256),
    "qsweep_tail": ("qsweep_tail", 24, 256, 32),
    "qsweep_tail_refine": ("qsweep_tail_refine", 24, 256, 32),
    "pan": ("pan", (16, 24, 32), 256),
    "pan_tail": ("pan_tail", (16, 24, 32), 256, 32),
    "pan_base": ("pan_base", 16, 256),
    "pan_step": ("pan_step", (16, 24, 32), 256, 256),
    "pan_batched": ("pan_batched", (16, 24, 32), 2, 256),
    "profile_mb": ("profile_mb", 24, 256, 2),
    "tail_mb": ("tail_mb", 24, 256, 32, 2),
    "pan_mb": ("pan_mb", (16, 24, 32), 256, 2),
    "pan_tail_mb": ("pan_tail_mb", (16, 24, 32), 256, 32, 2),
    "ring": ("ring", 24, 256, (1,)),
    "batched_ring": ("batched_ring", 24, 2, 256, (1,)),
    "tail_ring": ("tail_ring", 24, 256, 32, (1,)),
    "qsweep_ring": ("qsweep_ring", 24, 256, (1,)),
    "pan_ring": ("pan_ring", (16, 24, 32), 256, (1,)),
    "pan_tail_ring": ("pan_tail_ring", (16, 24, 32), 256, 32, (1,)),
    "pan_batched_ring": ("pan_batched_ring", (16, 24, 32), 2, 256, (1,)),
}


def _kind_engine(kind, backend="xla"):
    """A one-device engine of the spec family the IR audit gives
    ``kind``."""
    from repro.analysis.irlint import _Engines, _template
    return _Engines(s=24, ladder=(16, 24, 32), block=32,
                    ndev=1).get(_template(kind), backend, True)


@pytest.mark.parametrize("kind", list(PINNED_KEYS))
def test_plan_kind_builds_once_under_its_key(kind):
    import jax

    from repro.core.engine import PLAN_KINDS, plan_kind_registry
    assert list(PINNED_KEYS) == list(PLAN_KINDS)
    key = PINNED_KEYS[kind]
    geom = key[1:-1] if PLAN_KINDS[kind].sharded else key[1:]
    entry = plan_kind_registry(ndev=1)[kind]
    assert entry.geom == geom
    eng = _kind_engine(kind)
    fn = eng.plan(kind, *geom)
    assert list(eng._plans) == [eng._plan_key(key)]
    avals = [jax.ShapeDtypeStruct(shape, np.dtype(dt))
             for shape, dt in entry.avals]
    jax.eval_shape(fn, *avals)
    assert (eng.stats.plans, eng.stats.traces) == (1, 1)
    assert eng.plan(kind, *geom) is fn          # same geometry: a hit
    jax.eval_shape(fn, *avals)
    assert (eng.stats.plans, eng.stats.traces) == (1, 1)
    assert len(eng._plans) == 1


#: stacked kinds -> (op geometry, base window, tail op)
_LANE_OPS = {
    "profile": ((24, 256), 24, False),
    "tail": ((24, 256, 32), 24, True),
    "pan": (((16, 24, 32), 256), 16, False),
    "pan_tail": (((16, 24, 32), 256, 32), 16, True),
}


@pytest.mark.parametrize("kind,backend", [
    ("profile_mb", "xla"), ("profile_mb", "pallas"), ("tail_mb", "xla"),
    ("pan_mb", "xla"), ("pan_tail_mb", "xla"), ("batched", "xla"),
    ("pan_batched", "xla"), ("batched_ring", "xla"),
    ("pan_batched_ring", "xla")])
def test_plan_kind_lanes_match_local_plan(kind, backend):
    """Each lane of a stacked kind equals its op's local plan on that
    lane's series: the ``mb`` lanes (``lax.map``) with their own valid
    counts (and tail starts) bit for bit, the batched ones with the
    shared count.  On ``xla`` the batched layouts vmap: the profile
    lanes still match bit for bit, while the vmapped ladder sweep
    batches its extension dots and may round its profile values a few
    ulps apart (its neighbours stay equal)."""
    import jax.numpy as jnp

    from repro.core.engine import PLAN_KINDS, _bucket_pad
    pk = PLAN_KINDS[kind]
    geom, w0, tail = _LANE_OPS[pk.op]
    mb = pk.layout == "mb"
    rng = np.random.default_rng(11)
    lengths = (90, 77, 128) if mb else (90, 90, 90)
    xs = np.stack([_bucket_pad(np.sin(0.29 * np.arange(n))
                               + 0.2 * rng.normal(size=n), 256)
                   for n in lengths])
    nv = [n - w0 + 1 for n in lengths]
    q0 = [v - 20 for v in nv]
    lanes = [(q, v) if tail else (v,) for q, v in zip(q0, nv)]
    eng = _kind_engine(kind, backend)
    if mb:
        out = eng.plan(kind, *geom, 3)(
            jnp.asarray(xs), *(jnp.asarray(c, jnp.int32)
                               for c in zip(*lanes)))
    else:
        n_valid = (np.int32(nv[0]) if pk.layout == "batched"
                   else jnp.full((1,), nv[0], jnp.int32))
        out = eng.plan(kind, geom[0], 3, geom[1])(jnp.asarray(xs),
                                                  n_valid)
    local = eng.plan(pk.op, *geom)
    vmapped_ladder = not mb and pk.op == "pan"
    for b, args in enumerate(lanes):
        want = local(jnp.asarray(xs[b]), *(np.int32(a) for a in args))
        for i, (got_i, want_i) in enumerate(zip(out, want)):
            got_i, want_i = np.asarray(got_i)[b], np.asarray(want_i)
            if vmapped_ladder and i == 0:
                np.testing.assert_allclose(got_i, want_i, rtol=1e-5)
            else:
                assert np.array_equal(got_i, want_i), (kind, b, i)
