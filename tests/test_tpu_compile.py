"""Compile every Pallas kernel the engine can select on a TPU, for a
described (not attached) TPU v5e, at real widths.

Interpret mode, which every other test runs, accepts kernels that the
chip's compiler (Mosaic) rejects: unaligned slices, 1-D block layouts,
too much VMEM.  These tests compile each main-path kernel with
``interpret=False`` against a ``v5e:2x2`` topology description — the
TPU compiler ships with jaxlib, no chip is needed — at the length
bucket of a 120k-point recording (n_pad = 2^17), window s = 300
(384 MXU lanes) and block 256; and the ring plan whole over the
topology's four chips, at the same sizes.

The topology is described inside a fixture, never at import time, so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.  The persistent compilation
cache is off around the compiles: entries written for a described chip
cannot be read back without one.
"""
import os

import pytest

N_PAD = 2 ** 17
S = 300
BLOCK = 256


@pytest.fixture(scope="module")
def v5e_2x2():
    """The described topology's four devices."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2[0])


def _kernel_case(name):
    """(fn, argument shapes) of one kernel at the real widths."""
    import jax.numpy as jnp
    from repro.kernels.mpblock.kernel import (chunk_width, lane_pad,
                                              mp_block_pallas,
                                              mp_rows_pallas,
                                              mp_tri_pallas,
                                              qvc_block_pallas)
    from repro.kernels.registry import (bound_dot_pallas,
                                        dot_tile_pallas, tile_d2_pallas)

    f32, i32 = jnp.float32, jnp.int32
    nb = N_PAD // BLOCK
    width = chunk_width(BLOCK, lane_pad(S))

    def window_set(rows, first):
        return [(first, f32), ((rows,), f32), ((rows,), f32),
                ((rows,), i32)]

    if name == "mp_block_pallas":
        return (lambda *a: mp_block_pallas(*a, s=S, n_valid=N_PAD,
                                           block=BLOCK, interpret=False),
                window_set(N_PAD, (nb, width)) * 2)
    if name == "mp_block_pallas[live]":
        # traced live block counts bound the grid (bucket padding)
        return (lambda *a: mp_block_pallas(*a[:8], s=S, n_valid=N_PAD,
                                           block=BLOCK, nq=a[8],
                                           nc=a[9], interpret=False),
                window_set(N_PAD, (nb, width)) * 2
                + [((), i32), ((), i32)])
    if name == "mp_tri_pallas[live]":
        # the self-join's upper triangle, the profile plan's kernel
        return (lambda *a: mp_tri_pallas(*a[:4], s=S, n_valid=N_PAD,
                                         block=BLOCK, nb_live=a[4],
                                         interpret=False),
                window_set(N_PAD, (nb, width)) + [((), i32)])
    if name == "mp_rows_pallas[live]":
        # listed rows of the triangle, the refine plan's kernel
        return (lambda *a: mp_rows_pallas(*a[:5], s=S, n_valid=N_PAD,
                                          block=BLOCK, nb_live=a[5],
                                          interpret=False),
                window_set(N_PAD, (nb, width)) + [((2,), i32), ((), i32)])
    if name == "qvc_block_pallas":
        return (lambda *a: qvc_block_pallas(*a, s=S, n_valid=N_PAD,
                                            interpret=False),
                window_set(BLOCK, (BLOCK, S))
                + window_set(BLOCK, (BLOCK + S - 1,)))
    if name == "tile_d2_pallas":
        return (lambda *a: tile_d2_pallas(*a, s=S, n_valid=N_PAD,
                                          interpret=False),
                window_set(BLOCK, (BLOCK, S))
                + window_set(N_PAD, (N_PAD, S)))
    if name == "dot_tile_pallas":
        return (lambda q, c: dot_tile_pallas(q, c, interpret=False),
                [((BLOCK, S), f32), ((N_PAD, S), f32)])
    assert name == "bound_dot_pallas[bf16]"
    return (lambda q, c: bound_dot_pallas(q, c, precision="bf16",
                                          interpret=False),
            [((BLOCK, S), f32), ((N_PAD, S), f32)])


#: the self-join kernels, whose custom call ``mpblock_roofline`` finds
#: by its (row min f32, argmin s32) pair of (blocks, block, 1) results
ROW_PAIR_KERNELS = ("mp_block_pallas", "mp_block_pallas[live]",
                    "mp_tri_pallas[live]", "mp_rows_pallas[live]")


@pytest.mark.parametrize("name", [
    *ROW_PAIR_KERNELS, "qvc_block_pallas", "tile_d2_pallas",
    "dot_tile_pallas", "bound_dot_pallas[bf16]"])
def test_kernel_compiles_for_v5e(one_chip, name):
    import jax
    from bench.metrics.mpblock_roofline import KERNEL
    fn, shapes = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    found = [ln for ln in text.splitlines()
             if KERNEL.search(ln.strip().removeprefix("ROOT "))]
    assert len(found) == (name in ROW_PAIR_KERNELS), found


def test_ring_plan_hop_kernel_compiles_for_v5e(v5e_2x2, monkeypatch):
    """The ring plan over the four chips, as a search runs it: each
    chip builds its dealt shard, and ``mp_block`` runs once a hop with
    the traced live bound ``(nq, nc)`` inside ``shard_map`` and
    ``lax.scan``.  Its custom call keeps the result pair that
    ``mpblock_roofline`` finds, beside the hop's collective-permute."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench.metrics.mpblock_roofline import KERNEL
    from repro.core import DiscordEngine, SearchSpec, tiles
    from repro.parallel.sharding import SERIES_AXIS

    monkeypatch.setattr(tiles, "default_interpret", lambda: False)
    mesh = Mesh(np.array(v5e_2x2), (SERIES_AXIS,))
    eng = DiscordEngine(SearchSpec(s=S, method="ring", backend="pallas",
                                   block=BLOCK), mesh=mesh)
    rep = NamedSharding(mesh, P())
    plan = eng.plan("ring", S, N_PAD)
    text = plan.lower(
        jax.ShapeDtypeStruct((N_PAD,), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    ).compile().as_text()
    found = [ln for ln in text.splitlines()
             if KERNEL.search(ln.strip().removeprefix("ROOT "))]
    assert len(found) == 1, found
    assert "collective-permute" in text
