"""The control: the plain reference in bf16 put in the program's place
must come out as not correct, while the program itself comes out
correct in the same run (CPU, tiny sizes).

The limits are set for this size as PERF.md sets them for the cell's:
between the program's readings (1e-5 and 7e-5 here) and the bf16
control's (5e-4 and more).  At the cells' own sizes on the chip the
control reads 20-200x higher than the program (PERF.md)."""
import pytest

from bench.run import run_cell
from bench.tests.conftest import tiny

TINY_LIMITS = {"ecg-long.search": {"nnd_rel_gap": 1e-4}}


@pytest.mark.parametrize("cell", ["ecg-long.search"])
def test_bf16_control_fails_and_program_passes(cell):
    over = tiny(cell)
    over["config"]["check"] = {**over["config"].get("check", {}),
                               "limits": TINY_LIMITS[cell]}
    r = run_cell(cell, seed=987654321987, seconds=0.5, trace=False,
                 require_tpu=False, overrides=over, controls=("bf16",))
    assert r["correct"], r["checks"]
    ctl = r["controls"]["bf16"]
    assert any(c["value"] > c["limit"] for c in ctl.values()), ctl
