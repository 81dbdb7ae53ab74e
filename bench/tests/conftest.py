"""Tests of the benchmark itself, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They call the harness and the runners directly, since the measurement
command refuses a machine without a TPU.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: each cell cut to a size the CPU runs in seconds: same runners, same
#: checks, the configuration's limits unchanged
TINY = {
    "ecg-long.search": {"config": {
        "spec": {"s": 64},
        "data": {"recordings": 3, "length_lo": 1100, "length_hi": 1400,
                 "params": {"anomaly_length": 64}},
        "check": {"sample": 2}}}
}


def tiny(cell: str, backend: str = "xla") -> dict:
    import copy
    over = copy.deepcopy(TINY[cell])
    over["config"]["spec"]["backend"] = backend
    return over

