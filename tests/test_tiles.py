"""Unified distance-tile engine: backend parity + integration.

Contract under test:
  1. PARITY — numpy / xla / pallas(interpret) backends agree to 1e-3 on
     random series, including the exclusion zone (identical +inf mask)
     and tail-padding lanes (n not a multiple of block);
  2. the engine's contiguous sweep (HST's inner-loop shape, with the
     in-kernel Hankel build on pallas) agrees across backends;
  3. REGRESSION — `hst_jax` discords are identical to brute force on
     the synthetic suite for every backend (pre/post-refactor
     behavior), and `find_discords_batched` matches `find_discords`
     run serially on each member;
  4. `_scatter_min` keeps (nnd, ngh) paired and breaks ties
     deterministically (order-independent).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import find_discords, find_discords_batched
from repro.core.hst_jax import NND_INIT, _scatter_min
from repro.core.tiles import (TileEngine, available_backends, pair_d2,
                              resolve_backend, set_row_mins, tile_d2,
                              tile_mins, topk_nonoverlapping)

BACKENDS = ("numpy", "xla", "pallas")


def _series(seed, n=700):
    rng = np.random.default_rng(seed)
    t = np.arange(n)
    x = np.sin(0.07 * t) + 0.1 * rng.normal(size=n)
    p = int(rng.integers(100, n - 100))
    x[p:p + 40] += rng.uniform(0.6, 1.4) * np.sin(
        np.linspace(0, np.pi, 40))
    return x


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------
def test_registry_contents_and_resolution(monkeypatch):
    assert set(BACKENDS) <= set(available_backends())
    assert resolve_backend("xla") == "xla"
    assert resolve_backend("jnp") == "xla"          # legacy alias
    monkeypatch.setenv("REPRO_TILE_BACKEND", "numpy")
    assert resolve_backend() == "numpy"
    assert resolve_backend("pallas") == "pallas"    # arg beats env
    monkeypatch.delenv("REPRO_TILE_BACKEND")
    with pytest.raises(ValueError):
        resolve_backend("cuda-typo")


# ----------------------------------------------------------------------
# backend parity: gathered-query tiles
# ----------------------------------------------------------------------
@pytest.mark.parametrize("seed,n,s,block", [(0, 700, 33, 128),
                                            (1, 509, 24, 128),
                                            (2, 900, 64, 256)])
def test_tile_d2_backend_parity(seed, n, s, block):
    """All backends produce the same masked d2 tile (tail-padded n)."""
    x = _series(seed, n)
    eng = TileEngine(x, s, block=block)
    rng = np.random.default_rng(seed)
    qids = jnp.asarray(rng.choice(eng.n, size=16, replace=False),
                       jnp.int32)
    q = eng.query_block(qids)
    # last block straddles the valid/padding boundary on purpose
    c = eng.contiguous_block((eng.nb - 1) * block)
    tiles = {be: np.asarray(eng.d2(q, c, be)) for be in BACKENDS}
    ref = tiles["numpy"]
    finite = np.isfinite(ref)
    assert finite.any() and (~finite).any()   # exclusion/padding present
    for be in ("xla", "pallas"):
        got = tiles[be]
        assert np.array_equal(np.isfinite(got), finite), be
        assert np.allclose(got[finite], ref[finite], atol=1e-3), be


@pytest.mark.parametrize("backend", BACKENDS)
def test_exclusion_zone_masked(backend):
    x = _series(3, 400)
    s = 20
    eng = TileEngine(x, s, block=128)
    q = eng.query_block(jnp.arange(10, 26, dtype=jnp.int32))
    c = eng.contiguous_block(0)
    d2 = np.asarray(eng.d2(q, c, backend))
    qi = np.arange(10, 26)[:, None]
    cj = np.arange(128)[None, :]
    band = (np.abs(qi - cj) < s) | (cj >= eng.n)
    assert np.all(np.isinf(d2[band]))
    assert np.all(np.isfinite(d2[~band]))


def test_sweep_backend_parity():
    """The contiguous sweep (in-kernel Hankel build on pallas)."""
    x = _series(4, 600)
    eng = TileEngine(x, 32, block=128)
    q = eng.query_block(jnp.asarray([5, 99, 300, 511], jnp.int32))
    for c0 in (0, 128, (eng.nb - 1) * 128):
        ref, cid_ref = eng.sweep(q, c0, backend="numpy")
        ref = np.asarray(ref)
        for be in ("xla", "pallas"):
            got, cid = eng.sweep(q, c0, backend=be)
            got = np.asarray(got)
            assert np.array_equal(np.asarray(cid), np.asarray(cid_ref))
            fin = np.isfinite(ref)
            assert np.array_equal(np.isfinite(got), fin), (be, c0)
            assert np.allclose(got[fin], ref[fin], atol=1e-3), (be, c0)


def test_sweep_parity_unaligned_geometry():
    """block/s that are NOT multiples of the MXU tile sides — the
    alignment padding inside the pallas paths must be invisible."""
    x = _series(8, 700)
    eng = TileEngine(x, 33, block=200)       # 200 % 128 != 0, 33 % 128 != 0
    q = eng.query_block(jnp.asarray([0, 7, 123, 400, 600], jnp.int32))
    for c0 in (0, 200, (eng.nb - 1) * 200):
        ref, _ = eng.sweep(q, c0, backend="numpy")
        ref = np.asarray(ref)
        for be in ("xla", "pallas"):
            got, _ = eng.sweep(q, c0, backend=be)
            got = np.asarray(got)
            assert got.shape == ref.shape, (be, c0)
            fin = np.isfinite(ref)
            assert np.array_equal(np.isfinite(got), fin), (be, c0)
            assert np.allclose(got[fin], ref[fin], atol=1e-3), (be, c0)


def test_tile_mins_in_global_id_space():
    x = _series(5, 500)
    eng = TileEngine(x, 25, block=128)
    qids = jnp.asarray([0, 50, 200, 310], jnp.int32)
    q = eng.query_block(qids)
    c = eng.contiguous_block(128)
    d2 = eng.d2(q, c, "xla")
    m = tile_mins(d2, q.ids, c.ids)
    ref = np.asarray(d2)
    assert np.allclose(np.asarray(m.row_min), ref.min(axis=1))
    rows = np.arange(ref.shape[0])
    assert np.allclose(
        ref[rows, np.asarray(m.row_arg) - 128], ref.min(axis=1))
    assert np.allclose(np.asarray(m.col_min), ref.min(axis=0))


def test_pair_d2_matches_tile_diagonal():
    x = _series(6, 400)
    s = 16
    eng = TileEngine(x, s, block=128)
    a = jnp.asarray([0, 10, 50, 200], jnp.int32)
    b = jnp.asarray([100, 210, 300, 20], jnp.int32)
    qa, qb = eng.query_block(a), eng.query_block(b)
    d2_pair = np.asarray(pair_d2(qa.win, qb.win, qa.mu, qa.sig,
                                 qb.mu, qb.sig, s))
    d2_tile = np.asarray(eng.d2(qa, qb, "xla"))
    assert np.allclose(d2_pair, np.diag(d2_tile), atol=1e-4)


# ----------------------------------------------------------------------
# full profile + batched front door
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
def test_profile_backend_matches_brute(backend):
    from repro.core.serial.brute import exact_nnd_profile
    x = _series(7, 450)
    s = 24
    eng = TileEngine(x, s, block=128, backend=backend)
    d2, arg = eng.profile()
    prof = exact_nnd_profile(np.asarray(x, np.float64), s)
    assert np.allclose(np.sqrt(np.asarray(d2)), prof, atol=2e-3)
    arg = np.asarray(arg)
    assert np.all(np.abs(arg - np.arange(eng.n)) >= s)


@pytest.mark.parametrize("backend", BACKENDS)
def test_window_set_shards_fold_to_profile(backend):
    """The ring's shape: halves of a padded ``window_set`` (chunks on
    pallas, window rows elsewhere) swept against each other with
    ``set_row_mins`` and min-folded give the exact profile, and each
    neighbor realizes its distance."""
    from repro.core.serial.brute import exact_nnd_profile
    x = _series(5, 450)
    s, block = 24, 128
    n = len(x) - s + 1
    eng = TileEngine(x, s, block=block, backend=backend, n_valid=n)
    n_sh = 6 * block                   # 4 tile blocks padded to 2 x 3
    ws = eng.window_set(n_sh)
    shards = [tuple(a[:a.shape[0] // 2] for a in ws),
              tuple(a[a.shape[0] // 2:] for a in ws)]
    d2, arg = [], []
    for q in shards:
        (da, aa), (db, ab) = (
            set_row_mins(q, c, s=s, n_valid=n_sh, block=block,
                         backend=eng.backend) for c in shards)
        take = db < da
        d2.append(np.where(take, db, da))
        arg.append(np.where(take, ab, aa))
    d = np.sqrt(np.concatenate(d2)[:n])
    arg = np.concatenate(arg)[:n]
    xf = np.asarray(x, np.float64)
    prof = exact_nnd_profile(xf, s)
    assert np.allclose(d, prof, atol=2e-3)
    assert np.all(np.abs(arg - np.arange(n)) >= s)
    w = np.lib.stride_tricks.sliding_window_view(xf, s)
    z = (w - w.mean(1, keepdims=True)) / w.std(1, keepdims=True)
    assert np.allclose(np.linalg.norm(z - z[arg], axis=1), prof,
                       atol=2e-3)


@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_matches_serial(backend):
    """Covers both _batched_profile_jit branches: vmap (xla) and the
    lax.map scan (pallas interpret / numpy pure_callback)."""
    s, k = 32, 2
    xb = np.stack([_series(10), _series(11), _series(12)])
    batched = find_discords_batched(xb, s, k, backend=backend)
    assert len(batched) == 3
    for i, rb in enumerate(batched):
        ser = find_discords(xb[i], s, k, method="matrix_profile")
        assert rb.positions == ser.positions, (backend, i)
        assert np.allclose(rb.nnds, ser.nnds, rtol=1e-4), (backend, i)


def test_batched_single_series_and_backend_kw():
    x = _series(13, 500)
    rb = find_discords_batched(x[None, :], 24, 1, backend="xla")[0]
    ser = find_discords(x, 24, 1, method="matrix_profile")
    assert rb.positions == ser.positions


# ----------------------------------------------------------------------
# hst_jax regression: identical discords pre/post refactor
# ----------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_hst_jax_regression_vs_brute(seed):
    x = _series(seed, 600)
    s = 32
    ref = find_discords(x, s, 1, method="brute")
    r = find_discords(x, s, 1, method="hst_jax", seed=seed)
    assert r.positions == ref.positions
    assert r.nnds[0] == pytest.approx(ref.nnds[0], rel=1e-3)


def test_hst_jax_numpy_backend_exact():
    x = _series(20, 500)
    s = 24
    ref = find_discords(x, s, 2, method="brute")
    r = find_discords(x, s, 2, method="hst_jax", backend="numpy")
    assert r.positions == ref.positions
    assert r.extra["backend"] == "numpy"


def test_hst_jax_deterministic_across_runs():
    x = _series(21, 600)
    a = find_discords(x, 32, 3, method="hst_jax", seed=5)
    b = find_discords(x, 32, 3, method="hst_jax", seed=5)
    assert a.positions == b.positions
    assert a.nnds == b.nnds


# ----------------------------------------------------------------------
# _scatter_min: deterministic ties, (nnd, ngh) stay paired
# ----------------------------------------------------------------------
def test_scatter_min_tie_is_deterministic():
    nnd = jnp.full(4, NND_INIT)
    ngh = jnp.full(4, -1, jnp.int32)
    # two updates to row 1 with EQUAL distance from different sources
    idx = jnp.asarray([1, 1], jnp.int32)
    d = jnp.asarray([2.0, 2.0], jnp.float32)
    fwd = _scatter_min(nnd, ngh, idx, d, jnp.asarray([7, 3], jnp.int32))
    rev = _scatter_min(nnd, ngh, idx, d, jnp.asarray([3, 7], jnp.int32))
    for nnd2, ngh2 in (fwd, rev):
        assert float(nnd2[1]) == 2.0
        assert int(ngh2[1]) == 3          # smallest source wins, always
    assert np.array_equal(np.asarray(fwd[1]), np.asarray(rev[1]))


def test_scatter_min_keeps_pair_on_equal_nonimproving_update():
    nnd = jnp.asarray([5.0, 1.0], jnp.float32)
    ngh = jnp.asarray([9, 8], jnp.int32)
    # d == current nnd: no improvement -> neighbor must NOT churn
    nnd2, ngh2 = _scatter_min(nnd, ngh, jnp.asarray([1], jnp.int32),
                              jnp.asarray([1.0], jnp.float32),
                              jnp.asarray([4], jnp.int32))
    assert float(nnd2[1]) == 1.0 and int(ngh2[1]) == 8
    # strictly better distance -> both move together
    nnd3, ngh3 = _scatter_min(nnd, ngh, jnp.asarray([1], jnp.int32),
                              jnp.asarray([0.5], jnp.float32),
                              jnp.asarray([4], jnp.int32))
    assert float(nnd3[1]) == 0.5 and int(ngh3[1]) == 4


def test_scatter_min_ignores_dead_lanes():
    nnd = jnp.asarray([5.0, 5.0], jnp.float32)
    ngh = jnp.asarray([-1, -1], jnp.int32)
    nnd2, ngh2 = _scatter_min(
        nnd, ngh, jnp.asarray([-1, 5, 0], jnp.int32),
        jnp.asarray([1.0, 1.0, jnp.inf], jnp.float32),
        jnp.asarray([2, 2, 2], jnp.int32))
    assert np.allclose(np.asarray(nnd2), [5.0, 5.0])
    assert np.array_equal(np.asarray(ngh2), [-1, -1])


# ----------------------------------------------------------------------
# misc
# ----------------------------------------------------------------------
def test_topk_nonoverlapping():
    prof = np.zeros(100)
    prof[10] = 5.0
    prof[12] = 4.9      # overlaps the first peak at s=10
    prof[50] = 3.0
    pos, vals = topk_nonoverlapping(prof, 3, 10)
    assert pos[:2] == [10, 50] and vals[0] == 5.0
