"""The trace reduction and the work counts, without a chip."""
import json
import os

import pytest

from bench.lib import trace as tr
from bench.lib.work import roofline_s, useful_flop, useful_pairs

HERE = os.path.dirname(__file__)
FIXTURE = os.path.join(HERE, "fixtures", "ecg-long.search.xplane.pb")


def test_union_busy_and_gaps():
    evs = [("a", 10, 20), ("b", 15, 30), ("c", 40, 50), ("d", 45, 46)]
    assert tr.union(evs) == [(10, 30), (40, 50)]
    assert tr.busy_ns(evs) == 30
    assert tr.gaps(evs, 0, 60) == [(0, 10), (30, 40), (50, 60)]
    assert tr.clip(evs, 18, 42) == [("a", 18, 20), ("b", 18, 30),
                                    ("c", 40, 42)]


def test_summary_ops_and_gap_labels():
    s = tr.TraceSummary(
        lo=0, hi=100,
        devices={0: [("fusion.1", 0, 10), ("mp_rows", 10, 60),
                     ("collective-permute-done", 60, 65)],
                 1: [("mp_rows", 0, 40)]},
        host=[("bench.window", 0, 100), ("bench.search", 0, 70),
              ("bench.search", 75, 100)])
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s(0) == pytest.approx(65e-9)
    assert s.mean_busy_s() == pytest.approx(52.5e-9)
    assert s.total_op_s(lambda n: "mp_rows" in n) == pytest.approx(90e-9)
    assert s.top_ops(1) == [["mp_rows", pytest.approx(45e-9)]]
    gaps = s.idle_gaps(3)
    # device 1 idles 40-100 (midpoint 70: the window only), device 0
    # 65-100 (midpoint 82: the second search)
    assert gaps[0] == ["TPU:1 bench.window", pytest.approx(60e-9)]
    assert gaps[1] == ["TPU:0 bench.search", pytest.approx(35e-9)]


def test_work_counts():
    assert useful_pairs(4) == 6
    assert useful_flop(4, 300) == 6 * 600
    peaks = {"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert roofline_s(2e12, 1e6, peaks) == pytest.approx(2.0)
    assert roofline_s(1.0, 3e9, peaks) == pytest.approx(3.0)


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded chip trace")
def test_recorded_chip_trace():
    """A short window of ecg-long.search recorded on one TPU v5e: the
    device plane, the kernel and the window span are found, and the
    kernel holds nearly all of the busy time."""
    s = tr.read(FIXTURE)
    with open(os.path.join(HERE, "fixtures", "expected.json")) as f:
        want = json.load(f)
    assert sorted(s.devices) == [0]
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert s.busy_s(0) == pytest.approx(want["busy_s"], rel=1e-9)
    kernel = s.total_op_s(lambda n: want["kernel_pattern"] in n)
    assert kernel == pytest.approx(want["kernel_s"], rel=1e-9)
    assert 0.5 * s.busy_s(0) < kernel <= s.busy_s(0)
    # the mpblock reader's own matcher finds the same kernel
    from bench.lib.cells import metric_reader
    pattern = metric_reader("mpblock_roofline").KERNEL
    assert s.total_op_s(lambda n: bool(pattern.search(n))) == kernel
    assert s.top_ops(1)[0][0].endswith("custom-call tpu_custom_call")
    assert s.idle_gaps(1)[0][0] != "no span"


def test_capture_and_read_on_cpu():
    """A CPU trace has no TPU plane, but the window span and the host
    spans inside it are found."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tr.capture(True) as cap:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.search"):
                f(x).block_until_ready()
    try:
        s = tr.read(cap["path"])
    finally:
        tr.cleanup(cap)
    assert s.devices == {}
    assert s.window_s > 0
    assert any(name == "bench.search" for name, _, _ in s.host)
    assert not os.path.exists(cap["dir"])
