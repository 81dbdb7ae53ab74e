"""Quantized sweep (bf16/int8 bound pass + exact f32 refinement):
bound-soundness + bit-exactness harness (docs/cps.md).

  1. BOUND SOUNDNESS — per backend, the reduced-precision dot tile
     stays inside the derived error radius of the exact dot on
     adversarial window blocks (huge mean offsets, near-constant
     rows, denormal scales); at the plan level the ``(lo, hi)`` d²
     bracket contains the engine's own f32 refinement values, per
     backend x znorm x precision.
  2. EXACTNESS — ``precision="bf16"/"int8"`` search / batched /
     stream results are bit-identical to ``precision="f32"`` on
     every backend and znorm mode (the prune is certified, never
     lossy); the mesh-sharded ``qsweep_ring`` matches the ring
     plan's positions and the local profile's values bitwise.
  3. PLAN CACHE — repeat quantized searches in the same bucket add
     zero new traces (the data-dependent refinement count rides a
     fixed trip-count-2 plan, so no shape ever changes).
  4. ACCOUNTING — ``calls == tile_lanes + refine_calls`` decomposes
     exactly; ``prune_ratio`` stays in [0, 1]; sub-two-block buckets
     fall back to the exact plan outright.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DiscordEngine, SearchSpec
from repro.core.engine import _bucket_pad
from repro.core.spec import length_bucket
from repro.kernels.registry import (bound_dot_radius, get_bound_backend,
                                    quant_scales)

BACKENDS = ("numpy", "xla", "pallas")
PRECISIONS = ("bf16", "int8")

#: conditioning-adversarial transforms of the base series/windows:
#: a mean offset >> amplitude (catastrophic cancellation in both the
#: znorm stats and the distance form), a near-constant regime (tiny
#: true variance), and a denormal-scale regime (products underflow)
ADVERSARIAL = {
    "offset": dict(offset=1.0e6),
    "near_constant": dict(offset=5.0, scale=1e-6),
    "denormal": dict(scale=1e-38),
}


def _series(seed, n=500, offset=0.0, scale=1.0):
    rng = np.random.default_rng(seed)
    t = np.arange(float(n))
    x = np.sin(0.21 * t) + 0.1 * rng.standard_normal(n)
    p = n // 2
    w = min(24, n - p)
    x[p:p + w] += 1.1 * np.sin(np.linspace(0, np.pi, w))
    return offset + scale * x


def _spec(backend, precision, znorm=True, **kw):
    base = dict(s=24, k=2, method="matrix_profile", block=32,
                backend=backend, znorm=znorm, precision=precision)
    base.update(kw)
    return SearchSpec(**base)


# ---------------------------------------------------------------------
# 1. BOUND SOUNDNESS
# ---------------------------------------------------------------------
@pytest.mark.parametrize("family", sorted(ADVERSARIAL))
@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_bound_dot_within_radius_of_exact(backend, precision, family):
    """|dots_low - dots_exact| <= rad elementwise, on adversarial
    window blocks — the registry-level half of the soundness story
    (the engine turns this into the d² bracket)."""
    rng = np.random.default_rng(abs(hash((backend, precision,
                                          family))) % (2 ** 31))
    kw = ADVERSARIAL[family]
    off, sc = kw.get("offset", 0.0), kw.get("scale", 1.0)
    w = 24
    q = (off + sc * rng.standard_normal((16, w))).astype(np.float32)
    c = (off + sc * rng.standard_normal((24, w))).astype(np.float32)
    qj, cj = jnp.asarray(q), jnp.asarray(c)
    sq, scl = quant_scales(qj), quant_scales(cj)
    dots = np.asarray(get_bound_backend(backend)(
        qj, cj, precision=precision, sq=sq, sc=scl), np.float64)
    nq = jnp.sqrt(jnp.sum(qj * qj, axis=1))      # f32, as the engine
    nc = jnp.sqrt(jnp.sum(cj * cj, axis=1))
    rad = np.asarray(bound_dot_radius(precision, nq, nc, w,
                                      sq=sq, sc=scl), np.float64)
    exact = q.astype(np.float64) @ c.astype(np.float64).T
    err = np.abs(dots - exact)
    assert np.all(err <= rad), \
        f"worst excess {np.max(err - rad):.3g} (rad max {rad.max():.3g})"
    assert np.all(np.isfinite(rad)) and np.all(rad >= 0)


@pytest.mark.parametrize("family", sorted(ADVERSARIAL))
@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("znorm", (True, False))
@pytest.mark.parametrize("backend", BACKENDS)
def test_bound_pass_brackets_f32_refinement(backend, znorm, precision,
                                            family):
    """lo <= d2_f32 <= hi per query row: the bound plan's bracket must
    contain the refinement plan's own f32 block minima — exactly the
    inequality the certified prune rests on."""
    s, block = 24, 32
    x = _series(3, n=180, **ADVERSARIAL[family])
    eng = DiscordEngine(_spec(backend, precision, znorm=znorm))
    Lb = length_bucket(len(x))
    n_true = len(x) - s + 1
    n_pad = eng._n_pad(s, Lb)
    xp = jnp.asarray(_bucket_pad(np.asarray(x, np.float64), Lb))
    nv = np.int32(n_true)
    lo, hi = eng.plan("qsweep", s, Lb)(xp, nv)
    lo = np.asarray(lo, np.float64)
    hi = np.asarray(hi, np.float64)
    rplan = eng.plan("qsweep_refine", s, Lb)
    nb = n_pad // block
    d2 = np.empty(n_pad)
    for i in range(0, nb, 2):
        pair = (i, i + 1) if i + 1 < nb else (i, i)
        b2 = jnp.asarray(np.array(pair, np.int32) * block)
        d2p = np.asarray(rplan(xp, b2, nv)[0], np.float64)
        for lane, b in enumerate(pair):
            d2[b * block:(b + 1) * block] = d2p[lane]
    v = np.isfinite(d2[:n_true])
    assert v.any()
    assert np.all(lo[:n_true][v] <= d2[:n_true][v])
    assert np.all(d2[:n_true][v] <= hi[:n_true][v])


# ---------------------------------------------------------------------
# 2. EXACTNESS (search / batched / stream), 4. ACCOUNTING
# ---------------------------------------------------------------------
@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("znorm", (True, False))
@pytest.mark.parametrize("backend", BACKENDS)
def test_search_bit_identical_to_f32(backend, znorm, precision):
    x = _series(1)
    rq = DiscordEngine(_spec(backend, precision, znorm=znorm)).search(x)
    rf = DiscordEngine(_spec(backend, "f32", znorm=znorm)).search(x)
    assert list(rq.positions) == list(rf.positions)
    assert np.array_equal(np.asarray(rq.nnds), np.asarray(rf.nnds))
    assert rq.method.startswith("qsweep[")
    assert rq.extra["precision"] == precision
    # hybrid accounting: the reported calls decompose exactly
    assert rq.calls == rq.tile_lanes + rq.extra["refine_calls"]
    assert rq.calls == (rq.extra["bound_lanes"]
                        + rq.extra["refine_calls"])
    assert 0.0 <= rq.extra["prune_ratio"] <= 1.0


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("family", sorted(ADVERSARIAL))
def test_search_bit_identical_on_adversarial_series(family, precision):
    x = _series(2, n=300, **ADVERSARIAL[family])
    rq = DiscordEngine(_spec("xla", precision)).search(x)
    rf = DiscordEngine(_spec("xla", "f32")).search(x)
    assert list(rq.positions) == list(rf.positions)
    assert np.array_equal(np.asarray(rq.nnds), np.asarray(rf.nnds))


@pytest.mark.parametrize("precision", PRECISIONS)
def test_batched_matches_per_series_f32(precision):
    xb = np.stack([_series(6), _series(7)])
    q = DiscordEngine(_spec("xla", precision))
    f = DiscordEngine(_spec("xla", "f32"))
    rqs = q.search_batched(xb)
    assert len(rqs) == 2
    for b, (xi, rq) in enumerate(zip(xb, rqs)):
        rf = f.search(xi)
        assert list(rq.positions) == list(rf.positions)
        assert np.array_equal(np.asarray(rq.nnds), np.asarray(rf.nnds))
        assert rq.extra["layout"] == "qsweep-per-series"
        assert rq.extra["batch_index"] == b
        assert rq.calls == rq.tile_lanes + rq.extra["refine_calls"]
    assert q.stats.searches == 1        # one API call, one search


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_stream_bit_identical_and_accounted(backend, precision):
    x = _series(8, 520)
    sq = DiscordEngine(_spec(backend, precision)).open_stream(
        s=24, history=x[:300])
    sf = DiscordEngine(_spec(backend, "f32")).open_stream(
        s=24, history=x[:300])
    for lo in (300, 410):
        sq.append(x[lo:lo + 110])
        sf.append(x[lo:lo + 110])
    assert np.array_equal(sq.profile(), sf.profile())
    assert np.array_equal(sq.neighbors(), sf.neighbors())
    dq, df = sq.discords(), sf.discords()
    assert list(dq.positions) == list(df.positions)
    assert np.array_equal(np.asarray(dq.nnds), np.asarray(df.nnds))
    # the tail accounting decomposes the same way as the search plane
    assert dq.calls == sq.tile_lanes + sq.refine_calls
    assert dq.extra["precision"] == precision
    assert 0.0 <= dq.extra["prune_ratio"] <= 1.0


def test_small_bucket_falls_back_to_exact():
    # default block=256: a 256-bucket holds a single query block, so
    # pruning is vacuous and the engine runs the exact plan outright
    x = _series(9, 120)
    q = DiscordEngine(SearchSpec(s=24, k=2, method="matrix_profile",
                                 precision="bf16", backend="xla"))
    f = DiscordEngine(SearchSpec(s=24, k=2, method="matrix_profile",
                                 backend="xla"))
    rq, rf = q.search(x), f.search(x)
    assert rq.method == rf.method          # exact path, not qsweep
    assert list(rq.positions) == list(rf.positions)
    assert np.array_equal(np.asarray(rq.nnds), np.asarray(rf.nnds))
    # same for the stream: the tail op stages the exact plan
    stq = DiscordEngine(SearchSpec(
        s=24, method="matrix_profile", precision="bf16",
        backend="xla")).open_stream(s=24, history=x[:90])
    stf = DiscordEngine(SearchSpec(
        s=24, method="matrix_profile",
        backend="xla")).open_stream(s=24, history=x[:90])
    stq.append(x[90:])
    stf.append(x[90:])
    assert np.array_equal(stq.profile(), stf.profile())
    assert stq.refine_calls == 0


# ---------------------------------------------------------------------
# 3. PLAN CACHE: zero retrace on repeat searches
# ---------------------------------------------------------------------
def test_repeat_search_traces_nothing():
    eng = DiscordEngine(_spec("xla", "bf16"))
    eng.search(_series(4, 500))
    t = eng.stats.traces
    eng.search(_series(5, 460))            # same 512 bucket
    assert eng.stats.traces == t, \
        "same-bucket quantized search must not retrace"
    assert eng.stats.searches == 2


def test_repeat_stream_appends_trace_once():
    eng = DiscordEngine(_spec("xla", "bf16"))
    x = _series(10, 480)                   # stays inside the 512 bucket
    st = eng.open_stream(s=24, history=x[:260])
    st.append(x[260:370])
    t = eng.stats.traces
    st.append(x[370:480])                  # same (Lb, Qb): no retrace
    assert eng.stats.traces == t


# ---------------------------------------------------------------------
# mesh-sharded qsweep_ring (forced 4-device subprocess)
# ---------------------------------------------------------------------
QSWEEP_RING_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
from repro.core import DiscordEngine, SearchSpec

rng = np.random.default_rng(0)
t = np.arange(1024.0)
x = np.sin(0.08 * t) + 0.1 * rng.standard_normal(1024)
x[600:640] += 1.1 * np.sin(np.linspace(0, np.pi, 40))
base = dict(s=64, k=2, block=32, backend="xla")
out = {}
for prec in ("bf16", "int8"):
    ring_q = DiscordEngine(SearchSpec(method="ring", precision=prec,
                                      **base))
    ring_f = DiscordEngine(SearchSpec(method="ring", **base))
    local_f = DiscordEngine(SearchSpec(method="matrix_profile", **base))
    rq, rr, rl = ring_q.search(x), ring_f.search(x), local_f.search(x)
    tr = ring_q.stats.traces
    ring_q.search(x[:1000])               # same 1024 bucket
    # sharded batched layout dispatches per-series qsweep_ring
    eb = DiscordEngine(SearchSpec(method="matrix_profile",
                                  precision=prec, ndev=4, **base))
    rbs = eb.search_batched(np.stack([x, x[::-1].copy()]))
    out[prec] = {
        "pos_vs_ring": list(rq.positions) == list(rr.positions),
        "bitwise_vs_local": bool(np.array_equal(
            np.asarray(rq.nnds), np.asarray(rl.nnds))),
        "method": rq.method,
        "decomposes": rq.calls
            == rq.tile_lanes + rq.extra["refine_calls"],
        "ndev": rq.extra["ndev"],
        "retrace": ring_q.stats.traces - tr,
        "batched_bitwise": bool(np.array_equal(
            np.asarray(rbs[0].nnds), np.asarray(rl.nnds))),
        "batched_layout": rbs[0].extra["layout"],
    }
print(json.dumps(out))
"""


def test_qsweep_ring_parity_and_accounting(run_sharded):
    out = run_sharded(QSWEEP_RING_SCRIPT, timeout=420)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    for prec, d in doc.items():
        assert d["pos_vs_ring"], (prec, d)
        assert d["bitwise_vs_local"], (prec, d)
        assert d["method"].startswith("qsweep_ring["), d["method"]
        assert d["decomposes"] and d["ndev"] == 4
        assert d["retrace"] == 0
        assert d["batched_bitwise"]
        assert d["batched_layout"] == "qsweep-per-series"
