"""Each traffic runner run end to end on the CPU at a tiny size, with
the Pallas kernels in interpret mode: set-up, window, metrics, check."""
import pytest

from bench.run import run_cell
from bench.tests.conftest import tiny


@pytest.mark.parametrize("cell", ["ecg-long.search"])
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_is_correct(cell, trace):
    r = run_cell(cell, seed=2 ** 31 + 7, seconds=0.5, trace=trace,
                 require_tpu=False, overrides=tiny(cell, "pallas"))
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    if trace:
        # no TPU plane on the CPU: only the host-counted metrics appear
        assert "setup_s" not in r["metrics"]
        assert r["device"]["window_s"] > 0
    else:
        assert r["metrics"]["setup_s"]["value"] > 0
        assert len(r["metrics"]) >= 2


def test_cpu_is_refused():
    with pytest.raises(SystemExit):
        run_cell("ecg-long.search", seed=1, seconds=0.1, trace=False)


def test_files_found_by_name():
    """Runners, generators and readers come from their own files."""
    from bench.lib.cells import generator, metric_reader, runner_class
    assert runner_class({"runner": "closed_search"}).requests == "searches"
    x = generator({"generator": "ecg", "params": {
        "period": 48, "noise": 0.03, "anomalies": 1, "anomaly_length": 32,
        "anomaly_amp": 0.5}})(500, 3)
    assert x.shape == (500,)
    assert metric_reader("mpblock_roofline").LAYER == "kernels"
    with pytest.raises(SystemExit):
        runner_class({"runner": "no_such_runner"})
