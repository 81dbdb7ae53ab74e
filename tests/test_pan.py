"""Pan-length plan family (PR 4 + PR 5) + the edge-case bugfix sweep.

  1. PARITY — ``search_pan`` results match L independent per-length
     ``matrix_profile`` searches (positions exactly, nnds numerically)
     on every backend, in both znorm modes; the swept ``tile_lanes``
     are strictly below the independent-sweep total.
  2. COMPILE-ONCE — a second same-ladder, same-bucket ``search_pan``
     adds zero new jit traces; the ladder canonicalizes (sorted,
     deduped) into the plan key.
  3. LANES — an 8-rung ladder sweeps < 0.6x the independent lanes
     (the acceptance bar of the width-normalized accounting in
     docs/cps.md), and per-rung ``calls`` sum to the pan total.
  4. BOUNDS — the cross-length lower bound is a true lower bound and
     the upper bound a true upper bound of brute-force profiles, and
     the runtime ``lb_ok`` self-check holds.
  5. GLOBAL RANKING — ``d / sqrt(s)`` greedy merge respects interval-
     overlap exclusion across rungs.
  6. SHARDED — a 4-device (forced host platform, subprocess) pan
     search matches the local one with zero retraces on repeat; the
     pan-tail stream and the two batched layouts match too.
  7. STREAMING (PR 5) — ``PanStream`` appends equal a from-scratch
     ladder search on every backend in both znorm modes while paying
     strictly fewer lanes than a full resweep.
  8. LB-ABANDON (PR 5) — the sequential schedule returns the all-rung
     sweep's exact global top-k on adversarial ladders (including a
     last-rung winner) and never evaluates more lanes than the
     all-rung sweep.
  9. BATCHED (PR 5) — multi-window ``search_batched`` equals
     per-series ``search_pan``.
 10. SATELLITES — serial hst/hotsax truncate when k exceeds the
     non-overlapping discords (no -1 sentinel poisoning later
     rounds); Eq. (6) smoothing width is the documented convention
     with serial-vs-jax parity; hst_jax tiny-series geometry stays
     exact across backends; engine rejections name the spec field.
"""
import json
import os

import numpy as np
import pytest

from conftest import run_sharded_subprocess

from repro.core import (DiscordEngine, PanResult, PanStream, SearchSpec,
                        find_discords)
from repro.core.pan import (canonical_ladder, cross_length_lb,
                            cross_length_ub, global_normalized_topk,
                            pan_lanes)
from repro.core.serial.brute import exact_nnd_profile
from repro.core.windows import sliding_stats, smoothing_width

BACKENDS = ("numpy", "xla", "pallas")
LADDER = (24, 32, 40)


def _series(seed, n=600):
    rng = np.random.default_rng(seed)
    x = np.sin(0.07 * np.arange(n)) + 0.1 * rng.normal(size=n)
    p = int(rng.integers(120, n - 120))
    x[p:p + 40] += rng.uniform(0.7, 1.2) * np.sin(
        np.linspace(0, np.pi, 40))
    return x


# ----------------------------------------------------------------------
# ladder canonicalization
# ----------------------------------------------------------------------
def test_canonical_ladder():
    assert canonical_ladder((64, 48, 64, 56)) == (48, 56, 64)
    assert canonical_ladder(32) == (32,)
    assert canonical_ladder([40]) == (40,)
    with pytest.raises(ValueError):
        canonical_ladder(())
    with pytest.raises(ValueError):
        canonical_ladder((1, 32))


def test_pan_lanes_formula():
    # base rung full lanes + Delta/s share per later rung
    assert pan_lanes((32,), 100, 100) == 10_000
    assert pan_lanes((32, 40), 100, 100) == 10_000 + 2_000
    assert pan_lanes((32, 40, 48), 10, 10) == 100 + 20 + 17  # ceil


# ----------------------------------------------------------------------
# parity with independent per-length searches
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("znorm", (True, False))
def test_pan_matches_independent_searches(backend, znorm):
    x = _series(1)
    eng = DiscordEngine(SearchSpec(s=LADDER, k=2,
                                   method="matrix_profile",
                                   backend=backend, znorm=znorm))
    pan = eng.search_pan(x)
    assert isinstance(pan, PanResult)
    assert pan.ladder == LADDER
    indep_lanes = 0
    for r in pan.per_rung:
        one_eng = DiscordEngine(SearchSpec(s=r.s, k=2,
                                           method="matrix_profile",
                                           backend=backend,
                                           znorm=znorm))
        one = one_eng.search(x)
        assert r.positions == one.positions, (backend, znorm, r.s)
        assert np.allclose(r.nnds, one.nnds, rtol=1e-3, atol=1e-2), \
            (backend, znorm, r.s)
        indep_lanes += one_eng.stats.tile_lanes
    # the whole point: one ladder sweep beats L independent sweeps
    assert pan.tile_lanes < indep_lanes
    assert eng.stats.tile_lanes == pan.tile_lanes
    assert pan.extra["lb_ok"], pan.lb_margin


def test_pan_rung_profiles_match_brute():
    x = _series(2, n=420)
    eng = DiscordEngine(SearchSpec(s=(16, 24, 32), k=2,
                                   method="matrix_profile",
                                   backend="xla"))
    pan = eng.search_pan(x)
    from repro.core.tiles import topk_nonoverlapping
    for r in pan.per_rung:
        prof = exact_nnd_profile(np.asarray(x, np.float64), r.s)
        pos, vals = topk_nonoverlapping(prof, 2, r.s)
        assert r.positions == pos, r.s
        assert np.allclose(r.nnds, vals, atol=3e-3), r.s


# ----------------------------------------------------------------------
# compile-once
# ----------------------------------------------------------------------
def test_pan_zero_retrace_second_same_ladder_search():
    eng = DiscordEngine(SearchSpec(s=LADDER, k=1,
                                   method="matrix_profile",
                                   backend="xla"))
    eng.search_pan(_series(3, 500))
    assert eng.stats.traces == 1 and eng.stats.plans == 1
    eng.search_pan(_series(4, 460))       # same 512 bucket: no retrace
    assert eng.stats.traces == 1, \
        "same (ladder, bucket) pan search must not retrace"
    assert eng.stats.searches == 2
    # an explicit ladder in a different order/duplication canonicalizes
    # into the SAME plan key
    eng.search_pan(_series(5, 480), ladder=(40, 24, 32, 40))
    assert eng.stats.traces == 1
    eng.search_pan(_series(6, 700))       # new 1024 bucket: one trace
    assert eng.stats.traces == 2 and eng.stats.plans == 2


def test_multi_window_search_routes_through_pan_in_spec_order():
    x = _series(7, 450)
    eng = DiscordEngine(SearchSpec(s=(40, 24), k=1,
                                   method="matrix_profile",
                                   backend="xla"))
    r40, r24 = eng.search(x)              # spec order, not ladder order
    assert (r40.s, r24.s) == (40, 24)
    assert eng.stats.plans == 1           # one pan plan for both rungs
    assert r24.extra["ladder"] == (24, 40)


# ----------------------------------------------------------------------
# lane accounting (the acceptance bar)
# ----------------------------------------------------------------------
def test_eight_rung_ladder_sweeps_under_0p6x_independent():
    ladder = tuple(range(48, 105, 8))     # 8 rungs
    assert len(ladder) == 8
    x = _series(8, 900)
    eng = DiscordEngine(SearchSpec(s=ladder, k=1,
                                   method="matrix_profile",
                                   backend="xla"))
    pan = eng.search_pan(x)
    assert pan.tile_lanes < 0.6 * pan.extra["independent_lanes"], \
        (pan.tile_lanes, pan.extra["independent_lanes"])
    # per-rung calls decompose the pan total exactly
    assert sum(r.calls for r in pan.per_rung) == pan.tile_lanes
    # and the independent baseline is what L single-length engines
    # would actually sweep over the same bucket
    indep = 0
    for s in ladder:
        one = DiscordEngine(SearchSpec(s=s, k=1,
                                       method="matrix_profile",
                                       backend="xla"))
        one.search(x)
        indep += one.stats.tile_lanes
    assert pan.extra["independent_lanes"] == indep


@pytest.mark.parametrize("backend", ("xla", "pallas"))
def test_pan_lanes_count_its_grid(backend):
    """The ladder books the cells its sweep covers: each window pair
    once on ``pallas``, where the single-length self-join sweeps its
    triangle (``nb (nb + 1) / 2`` tiles of the bucket), the padded
    square on ``xla``; the independent baseline is what the
    single-length searches book."""
    block = 64
    x = _series(9, 500)
    eng = DiscordEngine(SearchSpec(s=LADDER, k=1, method="matrix_profile",
                                   backend=backend, block=block))
    pan = eng.search_pan(x)
    nb = eng._n_pad(LADDER[0], 512) // block
    cells = (nb * (nb + 1) // 2 * block ** 2 if backend == "pallas"
             else (nb * block) ** 2)
    assert pan.tile_lanes == pan_lanes(LADDER, 1, cells)
    indep = 0
    for s in LADDER:
        one = DiscordEngine(SearchSpec(s=s, k=1, method="matrix_profile",
                                       backend=backend, block=block))
        one.search(x)
        indep += one.stats.tile_lanes
    assert pan.extra["independent_lanes"] == indep
    assert pan.tile_lanes < indep


def _pan_engine(x, ladder, block):
    import jax.numpy as jnp
    from repro.core.pan import PanEngine
    peng = PanEngine(jnp.asarray(x, jnp.float32), ladder, block=block,
                     backend="pallas", znorm=True)
    assert peng.grid == "triangle"
    return peng


def _square_profile(peng):
    """Every query block against every candidate (``PanEngine.rows``),
    as (R, n_pad) d2 and neighbours."""
    import jax.numpy as jnp
    d2, arg = peng.rows(jnp.arange(peng.nb, dtype=jnp.int32) * peng.block)
    R = len(peng.ladder)
    return (np.asarray(d2).transpose(1, 0, 2).reshape(R, -1),
            np.asarray(arg).transpose(1, 0, 2).reshape(R, -1))


@pytest.mark.parametrize("nb", [1, 2, 3, 4, 5])
def test_pan_half_profile_matches_square(nb):
    """The half sweep (each window pair once, in circulant rows) at odd
    and even block counts against the square: distances within f32
    rounding and the same neighbours on a series without near-ties,
    the padding windows (+inf, 0)."""
    block, ladder = 32, (8, 12, 16)
    x = np.random.default_rng(nb).normal(size=nb * block - 5)
    peng = _pan_engine(x, ladder, block)
    assert peng.nb == nb
    d2, ngh = (np.asarray(v) for v in peng.profile())
    sq_d2, sq_ngh = _square_profile(peng)
    assert np.array_equal(np.isfinite(d2), np.isfinite(sq_d2))
    live = np.isfinite(sq_d2)
    assert live[0].any()
    assert np.allclose(d2[live], sq_d2[live], rtol=1e-5, atol=1e-5)
    assert np.array_equal(ngh, sq_ngh)
    assert np.all(ngh[~live] == 0)


def test_pan_half_profile_exact_ties_take_the_lowest_id():
    """Planted exact ties: a periodic series of small multiples of 1/2
    with neutral stats (mu 0, sigma 1) makes every dot product exact,
    so a window's best candidate recurs once a period with the same
    d2.  The half sweep keeps the lowest minimizing id on both of its
    sides, as the square's argmin does, bit for bit at every rung."""
    import jax.numpy as jnp
    block, ladder, nb = 32, (8, 12, 16), 4
    rng = np.random.default_rng(5)
    period = block + 11
    x = np.resize(0.5 * rng.integers(-2, 3, size=period),
                  nb * block + ladder[0] - 1)
    peng = _pan_engine(x, ladder, block)
    peng.mu = [jnp.zeros_like(v) for v in peng.mu]
    peng.sig = [jnp.ones_like(v) for v in peng.sig]
    d2, ngh = (np.asarray(v) for v in peng.profile())
    sq_d2, sq_ngh = _square_profile(peng)
    assert np.array_equal(d2, sq_d2)
    assert np.array_equal(ngh, sq_ngh)
    # exact arithmetic at the base rung: the lowest id among the
    # largest dots outside the exclusion band, tied on most windows
    n0 = x.size - ladder[0] + 1
    win = np.lib.stride_tricks.sliding_window_view(x, ladder[0])
    dots = win @ win.T
    ids = np.arange(n0)
    dots[np.abs(ids[:, None] - ids[None, :]) < ladder[0]] = -np.inf
    best = dots == dots.max(axis=1)[:, None]
    assert np.mean(best.sum(axis=1) > 1) > 0.5
    assert np.array_equal(ngh[0, :n0], np.argmax(best, axis=1))


# ----------------------------------------------------------------------
# cross-length lower bound
# ----------------------------------------------------------------------
def test_cross_length_lb_is_a_true_lower_bound():
    for seed, (s, s_next) in ((0, (16, 24)), (1, (20, 21)),
                              (2, (16, 48))):
        x = _series(seed, n=300)
        d2_prev = exact_nnd_profile(x, s) ** 2
        d2_next = exact_nnd_profile(x, s_next) ** 2
        sig_prev = sliding_stats(x, s)[1]
        sig_next = sliding_stats(x, s_next)[1]
        lb = cross_length_lb(d2_prev, sig_prev, sig_next)
        n_next = d2_next.shape[0]
        assert np.all(d2_next >= lb[:n_next] - 1e-6), (seed, s, s_next)


def test_raw_mode_monotone_bound():
    # raw Euclidean d2 can only grow when the window extends
    x = _series(9, 300)
    d16 = exact_nnd_profile(x, 16, znorm=False) ** 2
    d24 = exact_nnd_profile(x, 24, znorm=False) ** 2
    assert np.all(d24 >= d16[:d24.shape[0]] - 1e-9)


# ----------------------------------------------------------------------
# global length-normalized ranking
# ----------------------------------------------------------------------
def test_global_topk_overlap_exclusion():
    # two rungs; rung-1 peak inside rung-0 pick's interval is excluded
    p0 = np.zeros(100)
    p0[50] = 8.0                           # score 8/sqrt(16) = 2.0
    p1 = np.zeros(90)
    p1[55] = 9.0                           # overlaps pick; 9/sqrt(26)
    p1[10] = 7.0                           # clear second pick
    got = global_normalized_topk([p0, p1], (16, 26), 2)
    assert got[0] == {"s": 16, "position": 50, "nnd": 8.0,
                      "score": pytest.approx(2.0)}
    assert got[1]["s"] == 26 and got[1]["position"] == 10
    # scores come out non-increasing
    assert got[0]["score"] >= got[1]["score"]


def test_pan_result_global_topk_does_not_overlap():
    x = _series(10, 700)
    pan = DiscordEngine(SearchSpec(s=(24, 32, 48), k=3,
                                   method="matrix_profile",
                                   backend="xla")).search_pan(x)
    picks = pan.global_topk
    assert picks and len(picks) <= 3
    for i, a in enumerate(picks):
        for b in picks[i + 1:]:
            lo = max(a["position"], b["position"])
            hi = min(a["position"] + a["s"], b["position"] + b["s"])
            assert lo >= hi, (a, b)        # intervals disjoint


def test_search_pan_rejects_non_profile_methods():
    eng = DiscordEngine(SearchSpec(s=32, method="hst"))
    with pytest.raises(ValueError, match="profile plan"):
        eng.search_pan(_series(11, 300), ladder=(24, 32))


# ----------------------------------------------------------------------
# streaming pan appends (PanStream)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("znorm", (True, False))
def test_pan_stream_append_matches_from_scratch(backend, znorm):
    x = _series(12, 640)
    eng = DiscordEngine(SearchSpec(s=LADDER, k=2,
                                   method="matrix_profile",
                                   backend=backend, znorm=znorm))
    ref = eng.search_pan(x)
    st = eng.open_stream(history=x[:520])
    assert isinstance(st, PanStream)
    fill_lanes = st.tile_lanes
    st.append(x[520:600])
    st.append(x[600:])
    append_lanes = st.tile_lanes - fill_lanes
    sd = st.discords()
    assert isinstance(sd, PanResult) and sd.ladder == LADDER
    for a, b in zip(sd.per_rung, ref.per_rung):
        assert a.positions == b.positions, (backend, znorm, a.s)
        assert np.allclose(a.nnds, b.nnds, rtol=1e-3, atol=1e-2), \
            (backend, znorm, a.s)
    # the point of the tail plan: appends pay base-rung tail tiles
    # plus Δ-wide extensions only — strictly below a full resweep
    assert 0 < append_lanes < ref.tile_lanes, \
        (backend, znorm, append_lanes, ref.tile_lanes)
    # per-rung calls decompose the stream total exactly, even
    # accumulated across fill + appends (docs/cps.md)
    assert sum(r.calls for r in sd.per_rung) == sd.tile_lanes
    assert sd.extra["lb_ok"], sd.lb_margin


def test_pan_stream_profiles_match_brute_every_rung():
    x = _series(13, 560)
    eng = DiscordEngine(SearchSpec(s=(16, 24, 32), k=1,
                                   method="matrix_profile",
                                   backend="xla"))
    st = eng.open_stream(history=x[:420])
    for lo, hi in ((420, 480), (480, 530), (530, 560)):
        st.append(x[lo:hi])
    for r, s_r in enumerate(st.ladder):
        ref = exact_nnd_profile(np.asarray(x, np.float64), s_r)
        assert st.n_windows(r) == ref.shape[0]
        assert np.allclose(st.profile(r), ref, atol=3e-3), s_r


def test_pan_stream_zero_retrace_same_bucket_appends():
    x = _series(14, 900)
    eng = DiscordEngine(SearchSpec(s=LADDER, k=1,
                                   method="matrix_profile",
                                   backend="xla"))
    st = eng.open_stream(history=x[:700])
    t_fill = eng.stats.traces
    st.append(x[700:760])                 # tail plan: one trace
    t_tail = eng.stats.traces
    assert t_tail == t_fill + 1
    st.append(x[760:820])                 # same (Lb, Qb): no retrace
    assert eng.stats.traces == t_tail, \
        "same-bucket pan tail append must not retrace"
    assert eng.stats.appends == 3 and st.appends == 3


def test_pan_stream_waits_for_longest_rung():
    """Points accumulate silently until the longest rung fits; the
    first fill then covers every rung, and appends take over."""
    x = _series(15, 300)
    eng = DiscordEngine(SearchSpec(s=(16, 64), k=1,
                                   method="matrix_profile",
                                   backend="xla"))
    st = eng.open_stream(history=x[:40])   # < s_max + 1: no sweep yet
    assert st.tile_lanes == 0
    assert st.discords().per_rung == []
    st.append(x[40:200])                   # first fill
    st.append(x[200:])
    ref = eng.search_pan(x)
    for a, b in zip(st.discords().per_rung, ref.per_rung):
        assert a.positions == b.positions
        assert np.allclose(a.nnds, b.nnds, rtol=1e-3, atol=1e-2)


# ----------------------------------------------------------------------
# cross-length upper bound + the LB-abandoning rung schedule
# ----------------------------------------------------------------------
def _brute_profile_ngh(x, s, znorm=True):
    """Exact (nnd², neighbor) per window by full-matrix brute force."""
    from repro.core.windows import windows_view, znorm_windows
    w = (znorm_windows(x, s) if znorm
         else np.asarray(windows_view(x, s), np.float64))
    n = w.shape[0]
    d2 = np.sum((w[:, None, :] - w[None, :, :]) ** 2, axis=-1)
    i, j = np.indices((n, n))
    d2[np.abs(i - j) < s] = np.inf
    return d2.min(axis=1), d2.argmin(axis=1)


@pytest.mark.parametrize("znorm", (True, False))
def test_cross_length_ub_is_a_true_upper_bound(znorm):
    for seed, (s, s_next) in ((3, (16, 24)), (4, (20, 21)),
                              (5, (16, 48))):
        x = _series(seed, n=260)
        d2_prev, ngh_prev = _brute_profile_ngh(x, s, znorm)
        d2_next, _ = _brute_profile_ngh(x, s_next, znorm)
        n_next = d2_next.shape[0]
        if znorm:
            ub, partner = cross_length_ub(
                d2_prev, ngh_prev, s, s_next, n_next,
                stats_prev=sliding_stats(x, s),
                stats_next=sliding_stats(x, s_next))
        else:
            csum2 = np.concatenate([[0.0], np.cumsum(x * x)])
            nrm = lambda w: csum2[w:w + x.shape[0] - w + 1] \
                - csum2[:x.shape[0] - w + 1]
            ub, partner = cross_length_ub(
                d2_prev, ngh_prev, s, s_next, n_next,
                nrm_prev=nrm(s), nrm_next=nrm(s_next))
        assert np.all(d2_next <= ub + 1e-6), (seed, s, s_next, znorm)
        # bounded windows carry a usable partner for the refinement:
        # valid at the next rung and outside its exclusion band
        fin = np.isfinite(ub)
        assert np.all(partner[fin] >= 0)
        assert np.all(np.abs(np.flatnonzero(fin) - partner[fin])
                      >= s_next)


def _global_picks(pan):
    return [(g["s"], g["position"]) for g in pan.global_topk]


@pytest.mark.parametrize("znorm", (True, False))
def test_lb_abandon_matches_all_rung_sweep(znorm):
    """Adversarial ladders: the LB-abandoning schedule must return the
    all-rung sweep's global top-k exactly — whichever rung wins."""
    rng = np.random.default_rng(5)
    n = 1500
    t = np.arange(n)
    base = np.sin(0.2 * t) + 0.05 * rng.normal(size=n)
    # chirp: short windows still look like ordinary sine stretches,
    # only the longest rung captures the modulation -> the winner
    # lives at the LAST rung, so nothing may be wrongly skipped
    chirp = base.copy()
    seg = np.arange(96)
    chirp[700:796] = np.sin(0.2 * (700 + seg)
                            + 0.5 * np.sin(2 * np.pi * seg / 96)) \
        + 0.05 * rng.normal(size=96)
    short = _series(16, 1200)              # winner at a short rung
    for x, lad, k in ((chirp, (16, 48, 96), 1),
                      (short, (24, 32, 40), 2),
                      (short, (24, 48), 3)):
        eng = DiscordEngine(SearchSpec(s=lad, k=k,
                                       method="matrix_profile",
                                       backend="xla", znorm=znorm))
        ref = eng.search_pan(x)
        lb = eng.search_pan(x, schedule="lb_abandon")
        assert _global_picks(lb) == _global_picks(ref), (lad, k, znorm)
        assert np.allclose([g["score"] for g in lb.global_topk],
                           [g["score"] for g in ref.global_topk],
                           rtol=1e-4)
        # confirmed skips never exceed the all-rung sweep; only a
        # fixpoint resweep (reported) may
        if lb.extra["resweeps"] == 0:
            assert lb.tile_lanes <= lb.extra["ladder_lanes"]
        assert lb.extra["lb_ok"]
    # and the chirp's winner really is the last rung (the adversarial
    # setup the schedule must survive)
    eng = DiscordEngine(SearchSpec(s=(16, 48, 96), k=1,
                                   method="matrix_profile",
                                   backend="xla"))
    assert eng.search_pan(chirp).global_topk[0]["s"] == 96


def test_lb_abandon_skips_rungs_and_saves_lanes():
    """A dominant base-rung discord lets the bracket retire trailing
    rungs: lanes stay strictly below the all-rung sweep while the
    global top-k is bit-equal."""
    rng = np.random.default_rng(0)
    n = 4096
    x = np.sin(0.05 * np.arange(n)) + 0.15 * rng.normal(size=n)
    x[1500:1564] += 1.4 * np.sin(np.linspace(0, np.pi, 64))
    lad = tuple(range(48, 105, 8))
    eng = DiscordEngine(SearchSpec(s=lad, k=1, method="matrix_profile",
                                   backend="xla"))
    ref = eng.search_pan(x)
    lb = eng.search_pan(x, schedule="lb")
    assert _global_picks(lb) == _global_picks(ref)
    assert lb.extra["skipped_rungs"], "bracket should retire rungs here"
    assert lb.tile_lanes < lb.extra["ladder_lanes"]
    # evaluated + skipped = the whole ladder; accounting decomposes
    assert (sorted(lb.extra["evaluated_rungs"]
                   + lb.extra["skipped_rungs"]) == sorted(lad))
    assert sum(r.calls for r in lb.per_rung) == lb.tile_lanes
    # refinement pairs are scalar calls, never tile lanes (docs/cps.md)
    assert lb.calls == lb.tile_lanes + lb.extra["refine_calls"]
    # global-top-k-only result: per_rung holds evaluated rungs only
    assert tuple(r.s for r in lb.per_rung) == lb.extra["evaluated_rungs"]


def test_lb_abandon_validation():
    eng = DiscordEngine(SearchSpec(s=(24, 32), method="matrix_profile",
                                   backend="xla"))
    with pytest.raises(ValueError, match="schedule"):
        eng.search_pan(_series(17, 300), schedule="bogus")
    sh = DiscordEngine(SearchSpec(s=(24, 32), method="matrix_profile",
                                  backend="xla", ndev=1))
    with pytest.raises(ValueError, match="lb_abandon"):
        sh.search_pan(_series(17, 300), schedule="lb")
    # the alias and the result-side alias both exist
    pan = eng.search_pan(_series(17, 300), schedule="lb")
    assert pan.global_normalized_topk == pan.global_topk
    assert pan.extra["schedule"] == "lb_abandon"


# ----------------------------------------------------------------------
# batched pan (the (B, ladder) plan)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
def test_pan_batched_matches_per_series(backend):
    xb = np.stack([_series(18, 600), _series(19, 600),
                   np.roll(_series(18, 600), 150)])
    eng = DiscordEngine(SearchSpec(s=LADDER, k=2,
                                   method="matrix_profile",
                                   backend=backend))
    rs = eng.search_batched(xb)
    assert len(rs) == 3 and all(isinstance(r, PanResult) for r in rs)
    assert eng.stats.searches == 1        # one batch = one search
    for b, r in enumerate(rs):
        one = eng.search_pan(xb[b])
        for a, o in zip(r.per_rung, one.per_rung):
            assert a.positions == o.positions, (backend, b, a.s)
            assert np.allclose(a.nnds, o.nnds, rtol=1e-3, atol=1e-2)
        assert _global_picks(r) == _global_picks(one)
        assert r.extra["batch_size"] == 3
        assert r.extra["batch_index"] == b
        assert r.extra["layout"] == "local"
        assert r.extra["per_series_s"] == pytest.approx(
            r.runtime_s / 3)


def test_pan_batched_raw_mode_and_second_batch_zero_retrace():
    xb = np.stack([_series(20, 500), _series(21, 500)])
    eng = DiscordEngine(SearchSpec(s=(24, 40), k=1,
                                   method="matrix_profile",
                                   backend="xla", znorm=False))
    eng.search_batched(xb)
    t1 = eng.stats.traces
    rs = eng.search_batched(xb[:, :480])   # same (B, Lb): no retrace
    assert eng.stats.traces == t1
    one = eng.search_pan(xb[0][:480])
    assert rs[0].per_rung[0].positions == one.per_rung[0].positions


# ----------------------------------------------------------------------
# sharded pan (forced 4-device host platform, subprocess)
# ----------------------------------------------------------------------
SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
from repro.core import DiscordEngine, SearchSpec

rng = np.random.default_rng(0)
x = np.sin(0.06 * np.arange(1800)) + 0.12 * rng.normal(size=1800)
x[800:870] += 1.2 * np.sin(np.linspace(0, np.pi, 70))
ladder = (48, 64, 80)

sh = DiscordEngine(SearchSpec(s=ladder, k=2, method="matrix_profile",
                              backend="xla", ndev=4))
pan = sh.search_pan(x)
t1 = sh.stats.traces
sh.search_pan(x[:1700])                 # same bucket: zero new traces
loc = DiscordEngine(SearchSpec(s=ladder, k=2,
                               method="matrix_profile",
                               backend="xla")).search_pan(x)
print(json.dumps({
    "ndev": sh.ndev,
    "traces_first": t1,
    "traces_second": sh.stats.traces,
    "positions": [r.positions for r in pan.per_rung],
    "local_positions": [r.positions for r in loc.per_rung],
    "nnds": [r.nnds for r in pan.per_rung],
    "local_nnds": [r.nnds for r in loc.per_rung],
    "lb_ok": pan.extra["lb_ok"],
}))
"""


def test_pan_sharded_matches_local_and_compiles_once(run_sharded):
    out = run_sharded(SHARDED_SCRIPT, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["ndev"] == 4
    assert rep["traces_first"] == 1
    assert rep["traces_second"] == 1, "sharded pan must not retrace"
    assert rep["positions"] == rep["local_positions"]
    assert np.allclose(np.concatenate(rep["nnds"]),
                       np.concatenate(rep["local_nnds"]), rtol=1e-4)
    assert rep["lb_ok"]


PAN_TAIL_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
from repro.core import DiscordEngine, SearchSpec

rng = np.random.default_rng(0)
x = np.sin(0.06 * np.arange(1800)) + 0.12 * rng.normal(size=1800)
x[800:870] += 1.2 * np.sin(np.linspace(0, np.pi, 70))
ladder = (48, 64, 80)

loc = DiscordEngine(SearchSpec(s=ladder, k=2, method="matrix_profile",
                               backend="xla"))
ref = loc.search_pan(x)

# sharded pan stream: fill shards query blocks, appends shard the
# candidates through the ("pan_tail_ring", ...) plan
sh = DiscordEngine(SearchSpec(s=ladder, k=2, method="matrix_profile",
                              backend="xla", ndev=4))
st = sh.open_stream(history=x[:1500])
fill_lanes = st.tile_lanes
st.append(x[1500:1650])
t_tail = sh.stats.traces
st.append(x[1650:])                     # same (Lb, Qb): no retrace
tail_retraces = sh.stats.traces - t_tail
sd = st.discords()

# sharded batched pan, both two-level layouts
xb = np.stack([x, np.roll(x, 100)])
rs_par = sh.search_batched(xb)
os.environ["REPRO_RING_SERIES_THRESHOLD"] = "1000"
rs_ring = sh.search_batched(xb)

full_lanes = ref.tile_lanes
print(json.dumps({
    "ndev": sh.ndev,
    "stream_positions": [r.positions for r in sd.per_rung],
    "stream_nnds": [r.nnds for r in sd.per_rung],
    "ref_positions": [r.positions for r in ref.per_rung],
    "ref_nnds": [r.nnds for r in ref.per_rung],
    "append_lanes": st.tile_lanes - fill_lanes,
    "full_lanes": full_lanes,
    "traces_second_append": tail_retraces,
    "lb_ok": sd.extra["lb_ok"],
    "layouts": [rs_par[0].extra["layout"], rs_ring[0].extra["layout"]],
    "batched_positions": [[r.positions for r in p.per_rung]
                          for p in rs_par + rs_ring],
    "per_series_positions": [[r.positions for r in
                              loc.search_pan(xb[b]).per_rung]
                             for b in (0, 1)] * 2,
}))
"""


def test_pan_tail_sharded_matches_local_and_compiles_once(run_sharded):
    """4-device sharded pan stream + batched pan: parity with the
    local from-scratch ladder search, strictly-below-resweep append
    lanes, zero retrace on the second same-bucket append, and both
    two-level batched layouts."""
    out = run_sharded(PAN_TAIL_SHARDED_SCRIPT, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["ndev"] == 4
    assert rep["stream_positions"] == rep["ref_positions"]
    assert np.allclose(np.concatenate(rep["stream_nnds"]),
                       np.concatenate(rep["ref_nnds"]), rtol=1e-3,
                       atol=1e-2)
    assert 0 < rep["append_lanes"] < rep["full_lanes"]
    assert rep["traces_second_append"] == 0, \
        "sharded pan tail append must not retrace"
    assert rep["lb_ok"]
    assert rep["layouts"] == ["series-parallel", "pan-ring-per-series"]
    assert rep["batched_positions"] == rep["per_series_positions"]


# ----------------------------------------------------------------------
# the sharded-subprocess guard itself (PR 6 noted these tests deadlock
# on single-CPU boxes: the forced-host-device collectives never
# rendezvous; the conftest helper must bound or skip the mesh wait)
# ----------------------------------------------------------------------
def test_sharded_helper_skips_on_single_cpu(monkeypatch):
    monkeypatch.setattr(os, "cpu_count", lambda: 1)
    with pytest.raises(pytest.skip.Exception, match="single-CPU"):
        run_sharded_subprocess("print('never runs')")


def test_sharded_helper_bounds_the_mesh_wait(monkeypatch):
    """A child that hangs past the timeout becomes a skip, not a hung
    tier-1 run."""
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    with pytest.raises(pytest.skip.Exception, match="mesh"):
        run_sharded_subprocess("import time; time.sleep(60)",
                               timeout=2)


def test_sharded_helper_returns_completed_process(monkeypatch):
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    out = run_sharded_subprocess("print(6 * 7)")
    assert out.returncode == 0
    assert out.stdout.strip() == "42"


# ----------------------------------------------------------------------
# satellite: rejection messages name the spec field + alternatives
# ----------------------------------------------------------------------
def test_rejections_name_spec_field_and_alternatives():
    """The engine's entry-point rejections must say *which spec field*
    is wrong and what the supported alternatives are — and must not
    claim pan/batched/stream combinations are unsupported now that
    they are."""
    hst = DiscordEngine(SearchSpec(s=32, method="hst"))
    for op in (lambda: hst.search_batched(np.zeros((2, 300))),
               lambda: hst.open_stream(),
               lambda: hst.search_pan(np.zeros(300), ladder=(16, 24))):
        with pytest.raises(ValueError) as ei:
            op()
        msg = str(ei.value)
        assert "spec.method" in msg and "'hst'" in msg
        assert "matrix_profile" in msg and "ring" in msg
    # the sharded single-length plans' znorm guard names spec.znorm
    # and points at the plans that do run raw
    ring = DiscordEngine(SearchSpec(s=32, method="ring"))
    object.__setattr__(ring.spec, "znorm", False)   # unreachable via
    with pytest.raises(ValueError) as ei:           # spec validation:
        ring._require_znorm("the ring plan")        # defense-in-depth
    assert "spec.znorm" in str(ei.value) and "pan" in str(ei.value)
    # too-short series name the spec window
    eng = DiscordEngine(SearchSpec(s=64, method="matrix_profile",
                                   backend="xla"))
    with pytest.raises(ValueError, match="spec.s"):
        eng.search_batched(np.zeros((2, 40)))
    multi = DiscordEngine(SearchSpec(s=(24, 64),
                                     method="matrix_profile",
                                     backend="xla"))
    with pytest.raises(ValueError, match="spec.s"):
        multi.search_batched(np.zeros((2, 40)))


# ----------------------------------------------------------------------
# satellite: serial k > available truncation
# ----------------------------------------------------------------------
@pytest.mark.parametrize("method", ("hst", "hotsax"))
def test_serial_truncates_when_k_exceeds_available(method):
    rng = np.random.default_rng(0)
    x = np.sin(0.1 * np.arange(300)) + 0.1 * rng.normal(size=300)
    s = 100                                # at most 3 non-overlapping
    with pytest.warns(DeprecationWarning):
        ref = find_discords(x, s, 6, method="brute")
        r = find_discords(x, s, 6, method=method)
    assert r.k == ref.k < 6
    assert all(p >= 0 for p in r.positions)
    # the old -1 sentinel excluded every i < s-1 from later rounds;
    # position 0 IS one of the non-overlapping discords here
    assert sorted(r.positions) == sorted(ref.positions)
    assert np.allclose(sorted(r.nnds), sorted(ref.nnds), rtol=1e-3)


# ----------------------------------------------------------------------
# satellite: Eq. (6) smoothing convention
# ----------------------------------------------------------------------
def test_smoothing_width_convention():
    assert smoothing_width(8) == 9         # even s: exactly s + 1
    assert smoothing_width(7) == 9         # odd s: rounds UP to s + 2
    assert smoothing_width(2) == 3


@pytest.mark.parametrize("s", (7, 8, 15, 16))
def test_smoothing_serial_vs_jax_parity(s):
    import jax.numpy as jnp
    from repro.core.hst_jax import _smooth
    from repro.core.windows import moving_average_centered
    x = np.random.default_rng(s).normal(size=200)
    serial = moving_average_centered(x, s)
    jaxed = np.asarray(_smooth(jnp.asarray(x, jnp.float32), s))
    assert np.allclose(serial, jaxed, atol=1e-5)
    # borders keep the raw value on both
    half = smoothing_width(s) // 2
    assert np.allclose(serial[:half], x[:half])
    assert np.allclose(jaxed[-half:], np.asarray(x[-half:], np.float32))


# ----------------------------------------------------------------------
# satellite: hst_jax tiny-series geometry
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
def test_hst_jax_tiny_series_exact(backend):
    rng = np.random.default_rng(3)
    for n, s in ((40, 8), (60, 8), (20, 4)):
        x = np.sin(0.3 * np.arange(n)) + 0.2 * rng.normal(size=n)
        with pytest.warns(DeprecationWarning):
            ref = find_discords(x, s, 1, method="brute")
            r = find_discords(x, s, 1, method="hst_jax",
                              backend=backend)
        assert r.positions == ref.positions, (n, s, backend)
        assert r.nnds[0] == pytest.approx(ref.nnds[0], abs=1e-3)
        assert r.extra["block"] <= max(8, -(-(n - s + 1) // 8) * 8)


@pytest.mark.parametrize("znorm", (True, False))
def test_engine_tiny_series_exact_every_backend(znorm):
    rng = np.random.default_rng(4)
    n, s = 50, 8                           # n_seq = 43 < one block
    x = np.sin(0.25 * np.arange(n)) + 0.2 * rng.normal(size=n)
    ref = exact_nnd_profile(np.asarray(x, np.float64), s, znorm=znorm)
    from repro.core.tiles import topk_nonoverlapping
    pos, vals = topk_nonoverlapping(ref, 1, s)
    for backend in BACKENDS:
        r = DiscordEngine(SearchSpec(s=s, k=1, method="matrix_profile",
                                     backend=backend,
                                     znorm=znorm)).search(x)
        assert r.positions == pos, (backend, znorm)
        assert np.allclose(r.nnds, vals, atol=1e-2), (backend, znorm)
