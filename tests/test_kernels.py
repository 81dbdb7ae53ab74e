"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes per the deliverable contract."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.sax import sax_words
from repro.core.serial.brute import exact_nnd_profile
from repro.kernels.mpblock.ops import matrix_profile
from repro.kernels.paa.ops import sax_words_op
from repro.kernels.zdist.ops import zdist_min
from repro.kernels.zdist.ref import zdist_min_ref


@pytest.mark.parametrize("n,s", [(700, 33), (1500, 96), (2100, 128),
                                 (900, 200)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_zdist_vs_ref(n, s, dtype):
    rng = np.random.default_rng(n + s)
    x = (np.sin(0.05 * np.arange(n)) +
         0.2 * rng.normal(size=n)).astype(dtype)
    q = rng.choice(n - s + 1, size=64, replace=False)
    d, ngh = zdist_min(x, s, q)
    d2r, nghr = zdist_min_ref(np.asarray(x, np.float32), s, q)
    assert np.allclose(np.asarray(d), np.sqrt(np.asarray(d2r)),
                       atol=2e-3)
    # argmin ties can differ; distances at claimed neighbors must match
    assert np.allclose(np.asarray(d), np.sqrt(np.asarray(d2r)), atol=2e-3)


@pytest.mark.parametrize("n,s", [(500, 25), (900, 64), (1300, 100)])
def test_mpblock_matches_brute_profile(n, s):
    rng = np.random.default_rng(n)
    x = (np.sin(0.03 * np.arange(n)) + 0.1 * rng.normal(size=n)
         ).astype(np.float32)
    d, arg = matrix_profile(x, s)
    prof = exact_nnd_profile(np.asarray(x, np.float64), s)
    assert np.allclose(np.asarray(d), prof, atol=2e-3)
    # neighbor indices must be valid non-self-matches
    arg = np.asarray(arg)
    idx = np.arange(prof.shape[0])
    assert np.all(np.abs(arg - idx) >= s)


def test_mpblock_earlier_block_neighbours_match_xla():
    """Windows whose nearest neighbours sit in *earlier* blocks: the
    second half of the series repeats the first, so every late window's
    neighbour lies blocks before its own.  An accumulator that relied
    on revisiting an output block would lose exactly these minima on
    the chip (interpret mode keeps the whole output and hides it); the
    kernel must match the xla profile, neighbours included."""
    import jax.numpy as jnp
    from repro.core.tiles import TileEngine
    rng = np.random.default_rng(5)
    half = rng.normal(size=700)
    x = np.concatenate([half, half]) + 0.01 * rng.normal(size=1400)
    s, block = 40, 64
    out = {be: TileEngine(jnp.asarray(x, jnp.float32), s, block=block,
                          backend=be).profile()
           for be in ("pallas", "xla")}
    d_pl, a_pl = (np.asarray(v) for v in out["pallas"])
    d_xl, a_xl = (np.asarray(v) for v in out["xla"])
    late = np.arange(d_pl.shape[0]) >= 700
    assert np.mean(a_xl[late] // block < np.arange(
        d_pl.shape[0])[late] // block) > 0.9
    assert np.allclose(d_pl, d_xl, rtol=1e-3, atol=1e-3)
    assert np.array_equal(a_pl, a_xl)


MP_S, MP_BLOCK, MP_NB = 40, 64, 8      # one bucket of 8 window blocks


def _mp_self_join(grid, ops, *, n_valid, live=None):
    """Self-join rows of ``ops`` (chunks, mu, sig, ids) through the
    square (``mp_block_pallas``) or triangle (``mp_tri_pallas``)
    kernel, bounded by ``live`` blocks when given."""
    from repro.kernels.mpblock.kernel import mp_block_pallas, mp_tri_pallas
    if grid == "square":
        return mp_block_pallas(*ops, *ops, s=MP_S, n_valid=n_valid,
                               block=MP_BLOCK, nq=live, nc=live)
    return mp_tri_pallas(*ops, s=MP_S, n_valid=n_valid, block=MP_BLOCK,
                         nb_live=live)


def _mp_operands(x, n_valid):
    import jax.numpy as jnp
    from repro.core.tiles import TileEngine
    eng = TileEngine(jnp.asarray(x, jnp.float32), MP_S, block=MP_BLOCK,
                     backend="pallas", n_valid=n_valid)
    return eng, (eng.block_chunks(), eng.mu_pad, eng.sig_pad,
                 eng._mask_ids(jnp.arange(MP_NB * MP_BLOCK,
                                          dtype=jnp.int32)))


@pytest.mark.parametrize("grid", ["square", "triangle"])
@pytest.mark.parametrize("n_valid", [
    3 * MP_BLOCK, 3 * MP_BLOCK + 1, 3 * MP_BLOCK - 1,
    MP_NB * MP_BLOCK, "profile_mb"])
def test_mpblock_live_grid_matches_full_grid(n_valid, grid, monkeypatch):
    """Bounding the grid by the live blocks changes no row, bit for
    bit, and leaves every dead row (+inf, 0): at the block edges, at
    the bucket's full count (counts == nb), and per lane of the
    micro-batched profile plan, whose lanes hold different counts --
    for the square grid and the upper-triangle one."""
    import jax
    import jax.numpy as jnp
    from repro.core import DiscordEngine, SearchSpec
    from repro.core.tiles import TileEngine
    from repro.kernels.common import ceil_div
    from repro.kernels.mpblock import kernel as mpk
    rng = np.random.default_rng(7)
    L = MP_NB * MP_BLOCK + MP_S - 1
    x = np.sin(0.05 * np.arange(L)) + 0.3 * rng.normal(size=L)
    if n_valid == "profile_mb":
        if grid == "square":            # as for a bucket past tri_fits
            monkeypatch.setattr(mpk, "tri_fits", lambda nb, block: False)
        nvs = [3 * MP_BLOCK + 1, MP_BLOCK - 1, MP_NB * MP_BLOCK,
               5 * MP_BLOCK]

        def sweep():
            eng = DiscordEngine(SearchSpec(
                s=MP_S, k=1, method="matrix_profile", backend="pallas",
                block=MP_BLOCK))
            assert eng._profile_grid(MP_S, L) == grid
            return eng.plan("profile_mb", MP_S, L, len(nvs))(
                jnp.asarray(np.stack([x] * len(nvs)), jnp.float32),
                jnp.asarray(nvs, jnp.int32))

        got = sweep()
        # the same plan over the bucket's full grid
        monkeypatch.setattr(TileEngine, "live_blocks", lambda self: None)
        ref = sweep()
    else:
        nvs = [n_valid]
        eng, ops = _mp_operands(x, n_valid)
        rows = jax.jit(lambda n, *o: _mp_self_join(grid, o, n_valid=eng.n,
                                                   live=n))
        got = [v[None] for v in rows(
            jnp.int32(ceil_div(n_valid, MP_BLOCK)), *ops)]
        ref = [v[None] for v in _mp_self_join(grid, ops, n_valid=eng.n)]
    for b, nv in enumerate(nvs):
        d2, arg = np.asarray(got[0][b]), np.asarray(got[1][b])
        assert np.array_equal(d2, np.asarray(ref[0][b])), nv
        assert np.array_equal(arg, np.asarray(ref[1][b])), nv
        dead = ceil_div(nv, MP_BLOCK) * MP_BLOCK
        assert np.all(np.isposinf(d2[dead:])) and np.all(arg[dead:] == 0)
        assert np.isfinite(d2[:nv]).any(), nv


@pytest.mark.parametrize("live", [1, 2, 3, 7, 64, 255, 256, 257, 570,
                                  571])
def test_tri_step_enumerates_the_upper_triangle(live):
    """Step ``t`` of the triangle grid maps to the ``t``-th tile
    ``(i, j)``, ``j >= i``, in ascending rows and columns: the square
    root that fills the step table and its two exact steps hold at
    every size the table admits (``tri_fits``)."""
    import jax.numpy as jnp
    from repro.kernels.mpblock.kernel import _tri_step
    t = jnp.arange(live * (live + 1) // 2, dtype=jnp.int32)
    i, j = (np.asarray(v) for v in _tri_step(t, jnp.int32(live)))
    ri, rj = np.triu_indices(live)
    assert np.array_equal(i, ri) and np.array_equal(j, rj)


def test_mptri_matches_square_profile():
    """Each pair computed once matches the square, which computes it
    both ways: distances within f32 rounding, the same neighbours on a
    series without near-ties."""
    rng = np.random.default_rng(11)
    L = MP_NB * MP_BLOCK + MP_S - 1
    x = np.cumsum(rng.normal(size=L))
    nv = MP_NB * MP_BLOCK - MP_BLOCK // 2
    eng, ops = _mp_operands(x, nv)
    sq = [np.asarray(v) for v in _mp_self_join("square", ops,
                                               n_valid=eng.n)]
    tri = [np.asarray(v) for v in _mp_self_join("triangle", ops,
                                                n_valid=eng.n)]
    live = np.arange(sq[0].shape[0]) < nv
    assert np.array_equal(np.isposinf(sq[0]), np.isposinf(tri[0]))
    assert np.all(np.isfinite(tri[0][live]))
    assert np.allclose(tri[0][live], sq[0][live], rtol=1e-4, atol=1e-3)
    assert np.array_equal(tri[1], sq[1])


def test_mptri_exact_ties_take_the_lowest_id():
    """Planted exact ties: a periodic series of small multiples of 1/2
    with neutral stats (mu 0, sigma 1) makes every dot product exact,
    so each window's best candidate recurs once a period with the very
    same d2.  Both sides of the triangle keep the lowest minimizing id,
    as the square does, bit for bit."""
    import jax.numpy as jnp
    from repro.kernels.mpblock.kernel import block_chunks
    rng = np.random.default_rng(3)
    period = MP_BLOCK + MP_BLOCK // 2 + 5
    n = MP_NB * MP_BLOCK
    x = np.resize(0.5 * rng.integers(-2, 3, size=period),
                  n + MP_S - 1).astype(np.float32)
    ops = (block_chunks(jnp.asarray(x), MP_NB, MP_BLOCK, MP_S),
           jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32),
           jnp.arange(n, dtype=jnp.int32))
    sq = [np.asarray(v) for v in _mp_self_join("square", ops, n_valid=n)]
    tri = [np.asarray(v) for v in _mp_self_join("triangle", ops,
                                                n_valid=n)]
    assert np.array_equal(tri[0], sq[0])
    assert np.array_equal(tri[1], sq[1])
    # exact arithmetic: the lowest id among the largest dots, outside
    # the exclusion zone
    win = np.lib.stride_tricks.sliding_window_view(
        x.astype(np.float64), MP_S)[:n]
    dots = win @ win.T
    ids = np.arange(n)
    dots[np.abs(ids[:, None] - ids[None, :]) < MP_S] = -np.inf
    best = dots.max(axis=1)
    ties = (dots == best[:, None]).sum(axis=1)
    assert np.mean(ties > 1) > 0.5          # ties on most rows
    assert np.array_equal(tri[1], np.argmax(dots == best[:, None], axis=1))


@pytest.mark.parametrize("which", ["first", "middle", "last"])
def test_block_rows_listed_match_profile_rows(which):
    """``TileEngine.block_rows`` of listed blocks (the refine plan's
    path) is the triangle profile's rows bit for bit: the first block
    (no column side), a middle one and the last live one (no row side
    past the diagonal)."""
    import jax
    import jax.numpy as jnp
    from repro.core.tiles import TileEngine
    rng = np.random.default_rng(19)
    L = MP_NB * MP_BLOCK + MP_S - 1
    x = np.sin(0.04 * np.arange(L)) + 0.2 * rng.normal(size=L)
    nv = 6 * MP_BLOCK - 9                   # 6 live blocks of 8
    r = {"first": 0, "middle": 3, "last": 5}[which]

    def rows(xs, nvs, starts):
        eng = TileEngine(xs, MP_S, block=MP_BLOCK, backend="pallas",
                         n_valid=nvs)
        return eng.block_rows(), eng.block_rows(starts)

    full, listed = jax.jit(rows)(jnp.asarray(x, jnp.float32),
                                 jnp.int32(nv),
                                 jnp.asarray([r, 1], jnp.int32) * MP_BLOCK)
    for k, blk in enumerate((r, 1)):
        assert np.array_equal(np.asarray(listed[0][k]),
                              np.asarray(full[0][blk]))
        assert np.array_equal(np.asarray(listed[1][k]),
                              np.asarray(full[1][blk]))
    assert np.isfinite(np.asarray(listed[0][0])).any()


@pytest.mark.parametrize("s,P,alpha", [(96, 4, 4), (120, 4, 3),
                                       (64, 8, 6), (150, 5, 4)])
def test_paa_sax_words_match(s, P, alpha):
    rng = np.random.default_rng(s * P)
    x = (np.sin(0.02 * np.arange(2000)) +
         0.3 * rng.normal(size=2000)).astype(np.float32)
    w = np.asarray(sax_words_op(x, s, P, alpha))
    wr = sax_words(np.asarray(x, np.float64), s, P, alpha)
    assert np.mean(w == wr) > 0.995       # f32-vs-f64 breakpoint ties


_READ_DISPATCH = (
    "import repro.kernels.registry, jax; "
    "print(jax.config._value_holders"
    "['jax_cpu_enable_async_dispatch'].value)")


def _child_dispatch_value(env_extra):
    env = dict(os.environ, **env_extra)
    out = subprocess.run([sys.executable, "-c", _READ_DISPATCH],
                         capture_output=True, text=True, timeout=120,
                         env=env)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip()


def test_single_cpu_async_dispatch_guard():
    """Importing the registry on a one-CPU host must flip the XLA CPU
    client to synchronous dispatch — with async dispatch, the single
    dispatch-pool thread deadlocks against ``pure_callback`` tiles (the
    numpy reference backend) once a second compiled plan is dispatched.
    Regression test for the tier-1 hang in
    ``test_pan_matches_independent_searches[*-numpy]``."""
    expect = "False" if (os.cpu_count() or 1) <= 1 else "True"
    assert _child_dispatch_value({}) == expect


def test_async_dispatch_guard_env_escape():
    """``REPRO_KEEP_ASYNC_DISPATCH=1`` opts out of the guard."""
    val = _child_dispatch_value({"REPRO_KEEP_ASYNC_DISPATCH": "1"})
    assert val == "True"


_QSWEEP_SYNC = r"""
import jax
jax.config.update("jax_cpu_enable_async_dispatch", False)
import numpy as np
from repro.core import DiscordEngine, SearchSpec

rng = np.random.default_rng(0)
x = np.sin(0.2 * np.arange(420.0)) + 0.1 * rng.standard_normal(420)
spec = SearchSpec(s=24, k=2, method="matrix_profile",
                  precision="bf16", block=32, backend="numpy")
eng = DiscordEngine(spec)
r = eng.search(x)
st = eng.open_stream(s=24, history=x[:300])
st.append(x[300:])
d = st.discords()
assert r.calls == r.tile_lanes + r.extra["refine_calls"]
assert d.calls == st.tile_lanes + st.refine_calls
print("qsweep-sync-ok")
"""


def test_qsweep_two_phase_dispatch_under_sync_guard():
    """The quantized plane interleaves dispatch and host work twice
    per search (bound-pass fetch, then a data-dependent number of
    refinement calls) with ``pure_callback`` tiles on the numpy
    backend — the exact shape that deadlocked under the one-CPU
    async-dispatch pool.  Force the guard's synchronous-dispatch
    state and run both phases (search + stream tail) end to end."""
    out = subprocess.run([sys.executable, "-c", _QSWEEP_SYNC],
                         capture_output=True, text=True, timeout=300,
                         env=dict(os.environ))
    assert out.returncode == 0, out.stderr
    assert "qsweep-sync-ok" in out.stdout


def test_zdist_excludes_self_matches():
    rng = np.random.default_rng(0)
    x = rng.normal(size=800).astype(np.float32)
    s = 50
    q = np.arange(100, 120)
    d, ngh = zdist_min(x, s, q)
    ngh = np.asarray(ngh)
    assert np.all(np.abs(ngh - q) >= s)
