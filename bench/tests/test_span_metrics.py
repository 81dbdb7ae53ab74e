"""The readers of the program's spans, on synthetic traces."""
import pytest

from bench.lib import trace as tr
from bench.lib.cells import metric_reader
from bench.run import Context

HOST = "engine.host_ms_per_search"
IDLE = "engine.wait_idle_ms_per_search"
MS = 1_000_000                  # ns


def _read(name, summary):
    return metric_reader(name).read(
        Context(peaks=None, counters={}, trace=summary))


def _search(t, wait=(4, 9), idle=()):
    """One search's spans from ``t`` ms: prepare, dispatch, the wait
    ``wait`` ms after ``t``, fetch, select; 10 ms in all."""
    w0, w1 = (t + wait[0]) * MS, (t + wait[1]) * MS
    return [("engine.search", t * MS, (t + 10) * MS),
            ("engine.prepare", t * MS, (t + 2) * MS),
            ("engine.dispatch", (t + 2) * MS, w0),
            ("engine.wait", w0, w1),
            ("engine.fetch", w1, (t + 9.5) * MS),
            ("engine.select", (t + 9.5) * MS, (t + 10) * MS)]


def _summary(host, device, lo=0, hi=100):
    return tr.TraceSummary(
        lo=lo * MS, hi=hi * MS, devices={0: device},
        host=tr.clip([("bench.window", lo * MS, hi * MS)] + host,
                     lo * MS, hi * MS))


def _ops(*ms):
    return [("%fn.1 = custom-call", a * MS, b * MS) for a, b in ms]


def test_host_time_excludes_the_wait():
    s = _summary(_search(10) + _search(30, wait=(2, 9)),
                 _ops((12, 19), (32, 39)))
    # 10 ms less a wait of 5 ms and of 7 ms
    assert _read(HOST, s) == pytest.approx(4.0)
    assert _read(IDLE, s) == pytest.approx(0.0)


def test_nested_search_counts_once():
    """A batched entry that calls the profile path: the outer span is
    the search, and the inner one's wait is subtracted from it."""
    inner = _search(12)
    outer = [("engine.search", 10 * MS, 30 * MS)]
    s = _summary(outer + inner, _ops((16, 21)))
    assert _read(HOST, s) == pytest.approx(15.0)
    assert _read(IDLE, s) == pytest.approx(0.0)
    # the chip idles the last 1 ms of the 5 ms wait (20-21 ms)
    s = _summary(outer + inner, _ops((16, 20)))
    assert _read(IDLE, s) == pytest.approx(1.0)


@pytest.mark.parametrize("edge", ["start", "end"])
def test_search_cut_by_the_window_is_left_out(edge):
    cut = _search(-5) if edge == "start" else _search(95)
    s = _summary(_search(40) + cut, _ops((44, 49)))
    assert _read(HOST, s) == pytest.approx(5.0)
    # the chip idles in the cut search's wait too; it is not counted
    assert _read(IDLE, s) == pytest.approx(0.0)


def test_idle_hole_half_inside_the_wait():
    """The chip idles 46-52 ms; the wait is 44-49 ms: 3 ms of the hole
    lie inside it, the rest in fetch and select and the next search."""
    s = _summary(_search(40) + _search(50),
                 _ops((40, 46), (52, 59), (60, 100)))
    assert _read(IDLE, s) == pytest.approx(3.0 / 2)


@pytest.mark.parametrize("name", [HOST, IDLE])
def test_no_search_span_reads_nothing(name):
    s = _summary([("bench.search", 10 * MS, 20 * MS)], _ops((10, 20)))
    assert _read(name, s) is None
    assert metric_reader(name).read(
        Context(peaks=None, counters={}, trace=None)) is None


def test_idle_needs_a_device_plane():
    s = _summary(_search(10), [])
    s.devices = {}
    assert _read(IDLE, s) is None
    assert _read(HOST, s) == pytest.approx(5.0)
