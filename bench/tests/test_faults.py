"""A run with the timed path broken underneath must come out as not
correct: the harness's chip check is skipped, everything else runs as
on the chip (CPU, tiny sizes, the configuration's limits)."""
import numpy as np
from bench.run import run_cell
from bench.tests.conftest import tiny
from repro.core import engine as engine_mod


def run(cell):
    r = run_cell(cell, seed=424242, seconds=0.5, trace=False,
                 require_tpu=False, overrides=tiny(cell))
    return r


def fails(r):
    return not r["correct"] and any(
        not (isinstance(c["value"], (int, float))
             and c["value"] <= c["limit"]) for c in r["checks"].values())


# -- ecg-long.search: one search answers one recording ------------------
def test_search_answer_altered(monkeypatch):
    real = engine_mod.topk_nonoverlapping

    def altered(profile, k, s):
        pos, vals = real(profile, k, s)
        return pos, [v * (1 + 1e-2) for v in vals]
    monkeypatch.setattr(engine_mod, "topk_nonoverlapping", altered)
    assert fails(run("ecg-long.search"))


def test_search_half_the_windows_left_out(monkeypatch):
    """The top-k taken over the first half of the profile only."""
    real = engine_mod.topk_nonoverlapping

    def half(profile, k, s):
        p = np.array(profile, np.float64)
        p[p.shape[0] // 2:] = -np.inf
        return real(p, k, s)
    monkeypatch.setattr(engine_mod, "topk_nonoverlapping", half)
    assert fails(run("ecg-long.search"))


def test_search_returns_a_stale_answer(monkeypatch):
    real = engine_mod.DiscordEngine.search
    first = {}

    def stale(self, series, **kw):
        if "r" not in first:
            first["r"] = real(self, series, **kw)
        return first["r"]
    monkeypatch.setattr(engine_mod.DiscordEngine, "search", stale)
    assert fails(run("ecg-long.search"))

