"""The four-chip ring cell, ``ecg-ring4.search``, on 4 virtual CPU
devices at a tiny size: each backend traced and untraced reads
``correct``; a ring whose ``lax.ppermute`` leaves the candidate set
where it is, and the bf16 control, read ``correct`` false.  The
collective's reader is checked on synthetic traces.

The device count has to be set before JAX starts, so the runs are made
in one child process (``XLA_FLAGS``), which prints one JSON line.
"""
import json
import os
import subprocess
import sys

import pytest

from bench.lib import trace as tr
from bench.lib.cells import metric_reader
from bench.run import Context
from bench.tests.conftest import ROOT

CELL = "ecg-ring4.search"
#: the cell cut to a size the CPU runs in seconds: same runner, same
#: check, the configuration's limit
TINY = {"config": {
    "spec": {"s": 64},
    "data": {"recordings": 3, "length_lo": 1100, "length_hi": 1400,
             "params": {"anomaly_length": 64}},
    "check": {"sample": 2}}}
SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [%(root)r, %(root)r + "/src"]
import copy, json
import jax
from bench.run import run_cell

CELL, TINY = %(cell)r, %(tiny)r


def over(backend):
    o = copy.deepcopy(TINY)
    o["config"]["spec"]["backend"] = backend
    return o


def run(backend, trace=False, controls=()):
    r = run_cell(CELL, seed=2 ** 31 + 11, seconds=0.5, trace=trace,
                 require_tpu=False, overrides=over(backend),
                 controls=controls)
    return {"correct": r["correct"], "attempted": r["attempted"],
            "failed": r["failed"], "metrics": sorted(r["metrics"]),
            "devices": r["device"]["count"],
            "checks": {k: [c["value"], c["limit"]]
                       for k, c in r["checks"].items()},
            "controls": {p: {k: [c["value"], c["limit"]]
                             for k, c in chk.items()}
                         for p, chk in r.get("controls", {}).items()}}


out = {}
for backend in ("xla", "pallas"):
    for trace in (False, True):
        out[f"{backend}-{int(trace)}"] = run(backend, trace)
out["control"] = run("xla", controls=("bf16",))
jax.lax.ppermute = lambda x, axis_name, perm: x
out["no-permute"] = run("xla")
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    if (os.cpu_count() or 1) < 2:
        pytest.skip("forced multi-device host collectives deadlock on "
                    "single-CPU boxes")
    script = SCRIPT % {"root": str(ROOT), "cell": CELL, "tiny": TINY}
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, cwd=str(ROOT))
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("trace", [0, 1])
def test_ring_cell_runs_and_is_correct(runs, backend, trace):
    r = runs[f"{backend}-{trace}"]
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["devices"] == 4
    if trace:
        assert "setup_s" not in r["metrics"]
        # no TPU plane on the CPU: the program's spans and counters
        # are read, the device-trace readers find nothing
        assert "engine.swept_per_useful" in r["metrics"]
        assert "engine.host_ms_per_search" in r["metrics"]
        assert "ring.collective_share" not in r["metrics"]
    else:
        assert r["metrics"] == ["search_s", "setup_s"]


def test_program_passes_and_bf16_control_fails(runs):
    """With the configuration's own limit, which at this size lies
    between the program's reading and the control's as on the chip
    (PERF.md)."""
    r = runs["control"]
    assert r["correct"], r["checks"]
    value, limit = r["controls"]["bf16"]["nnd_rel_gap"]
    assert value > limit


def test_ring_without_its_permute_is_not_correct(runs):
    """Each chip sweeps its own candidate shard ``ndev`` times."""
    r = runs["no-permute"]
    assert not r["correct"]
    value, limit = r["checks"]["nnd_rel_gap"]
    assert value > limit
    assert r["failed"] == 0


# -- the collective's reader on synthetic traces -----------------------
MS = 1_000_000                  # ns
PERMUTE = ("%collective-permute-start.1 = (f32[64]{0}, f32[64]{0}) "
           "collective-permute-start(f32[64]{0} %p), "
           "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}")
DONE = ("%collective-permute-done.1 = f32[64]{0} "
        "collective-permute-done((f32[64]{0}, f32[64]{0}) "
        "%collective-permute-start.1)")
USER = ("%fusion.2 = f32[64]{0} fusion(f32[64]{0} "
        "%collective-permute-done.1), kind=kLoop")
KERNEL = "%mp_block.1 = (f32[2,256,1]{2,1,0}, s32[2,256,1]{2,1,0}) custom-call()"


def _read(summary):
    return metric_reader("ring.collective_share").read(
        Context(peaks=None, counters={}, trace=summary))


def _summary(devices, async_ops=None):
    return tr.TraceSummary(lo=0, hi=100 * MS, devices=devices,
                           host=[("bench.window", 0, 100 * MS)],
                           async_ops=async_ops or {})


def _ev(name, a, b):
    return (name, a * MS, b * MS)


def test_collective_share_is_the_mean_over_chips():
    """Permutes on two of four chips: chip 0 holds 10-14 and 12-16 ms
    (6 ms in union), chip 1 2 ms; the fusion that reads a permute's
    result does not count, nor the send in flight on chip 0's async
    line, which spans the kernel."""
    devices = {d: [_ev(KERNEL, 20, 90)] for d in range(4)}
    devices[0] += [_ev(PERMUTE, 10, 14), _ev(DONE, 12, 16),
                   _ev(USER, 16, 18)]
    devices[1] += [_ev(DONE, 30, 32)]
    async_ops = {0: [_ev(PERMUTE, 10, 92)]}
    assert _read(_summary(devices, async_ops)) == pytest.approx(
        (6 + 2) / 4 / 100 * 100)


def test_collective_share_without_permutes_reads_nothing():
    devices = {d: [_ev(KERNEL, 20, 90), _ev(USER, 90, 92)]
               for d in range(4)}
    assert _read(_summary(devices)) is None
    assert _read(_summary(devices, {0: [_ev(PERMUTE, 10, 92)]})) is None
    assert _read(_summary({})) is None


def test_reader_found_by_name():
    mod = metric_reader("ring.collective_share")
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == ("ring", "%", "search_s")
