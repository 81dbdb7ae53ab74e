"""Closed-loop searches: one client searches recordings back to back.

The configuration's ``data`` gives how many recordings there are and
the range of their lengths: the lengths are spread evenly over the
range, the same for every seed, and the seed picks the data and the
order in which the recordings are sent.  A traffic mix may override the
spec (``spec``) or the data (``data``).  ``search_s`` is the window over
the searches it completed.
"""
from __future__ import annotations

import time
from typing import Callable, List, Optional

import numpy as np

from bench.lib.cells import generator
from bench.lib.seeds import sub_seed
from bench.lib.window import Window
from bench.lib.work import useful_bytes, useful_flop, useful_pairs
from bench.ref.profile import nnd_profile, topk_nonoverlapping


class Runner:
    requests = "searches"

    def __init__(self, cell, seed: int, devices, log: Callable):
        from repro.core import SearchSpec
        cfg, tr = cell.config, cell.traffic
        self.cell, self.seed, self.devices, self.log = cell, seed, devices, log
        self.spec = SearchSpec(**{**cfg["spec"], **tr.get("spec", {})})
        self.data = {**cfg["data"], **tr.get("data", {})}
        self.s, self.k = int(self.spec.s), int(self.spec.k)
        self.engine = None
        self.done: List[tuple] = []
        self.failed = 0

    def setup(self) -> None:
        from repro.core import DiscordEngine
        d = self.data
        gen = generator(d)
        lengths = np.linspace(d["length_lo"], d["length_hi"],
                              d["recordings"]).round().astype(int)
        rng = np.random.default_rng(sub_seed(self.seed, 0))
        self.order = [int(r) for r in rng.permutation(d["recordings"])]
        self.series = [gen(int(n), sub_seed(self.seed, 1, r))
                       for r, n in enumerate(lengths)]
        mesh = None
        if len(self.devices) > 1:
            from jax.sharding import Mesh
            mesh = Mesh(np.array(self.devices), ("series",))
        self.engine = DiscordEngine(self.spec, mesh=mesh)
        self.log(f"recordings: {d['recordings']} of "
                 f"{int(lengths.min())}-{int(lengths.max())} points, "
                 f"s={self.s}, k={self.k}, method={self.spec.method}, "
                 f"backend={self.engine.backend}, "
                 f"devices={len(self.devices)}")
        r = self.order[0]
        t = time.perf_counter()
        self.engine.search(self.series[r])      # compiles the one plan
        self.log(f"warm-up search of recording {r}: "
                 f"{time.perf_counter() - t:.3f} s, "
                 f"traces {self.engine.stats.traces}")

    def window(self, seconds: float, span) -> Window:
        eng, s = self.engine, self.s
        lanes0 = eng.stats.tile_lanes
        pairs = flop = nbytes = 0
        i = 0
        t0 = time.perf_counter()
        while True:
            r = self.order[i % len(self.order)]
            x = self.series[r]
            ts, cs = time.perf_counter(), time.thread_time()
            try:
                with span("bench.search"):
                    res = eng.search(x)
            except Exception as e:      # a search that never answers
                self.failed += 1
                self.log(f"search {i} of recording {r} failed: {e!r}")
            else:
                dt = time.perf_counter() - ts
                self.done.append((r, list(res.positions),
                                  [float(v) for v in res.nnds], dt))
                n = len(x) - s + 1
                pairs += useful_pairs(n)
                flop += useful_flop(n, s)
                nbytes += useful_bytes(n, s)
                self.log(f"search {i}: recording {r}, {len(x)} points, "
                         f"{dt:.4f} s (host thread cpu "
                         f"{time.thread_time() - cs:.4f} s, at "
                         f"{ts - t0:.3f} s) -> positions {res.positions} "
                         f"nnds {[round(v, 6) for v in res.nnds]}")
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        span_s = time.perf_counter() - t0
        return Window(e2e={"search_s": span_s / i}, attempted=i,
                      failed=self.failed, seconds=span_s,
                      counters={"tile_lanes": eng.stats.tile_lanes - lanes0,
                                "useful_pairs": pairs, "useful_flop": flop,
                                "useful_bytes": nbytes})

    def release(self) -> None:
        self.engine = None

    def check(self, control: Optional[str] = None) -> dict:
        """Each search of a sample of the searched recordings (the
        longest among them) against the reference's top-k: the
        relative gap of every returned nnd to the reference profile at
        its position and to the reference's discord of the same rank.
        With ``control`` the reference in that precision stands in for
        the program."""
        chk = self.cell.config["check"]
        searched = sorted({r for r, *_ in self.done})
        sample: List[int] = []
        if searched:
            longest = max(searched, key=lambda r: len(self.series[r]))
            rest = [r for r in searched if r != longest]
            rng = np.random.default_rng(sub_seed(self.seed, 2))
            take = min(int(chk["sample"]) - 1, len(rest))
            sample = [longest] + [int(r) for r in
                                  rng.choice(rest, take, replace=False)]
        gap, compared = 0.0, 0
        for r in sample:
            x = self.series[r]
            ref = nnd_profile(x, self.s, devices=self.devices)
            _, ref_vals = topk_nonoverlapping(ref, self.k, self.s)
            if control:
                cp = nnd_profile(x, self.s, precision=control,
                                 devices=self.devices)
                answers = [topk_nonoverlapping(cp, self.k, self.s)]
            else:
                answers = [(p, v) for rr, p, v, _ in self.done if rr == r]
            for pos, vals in answers:
                gap = max(gap, nnd_rel_gap(pos, vals, ref, ref_vals))
                compared += 1
            self.log(f"reference of recording {r}: discords "
                     f"{[round(v, 6) for v in ref_vals]}, "
                     f"{len(answers)} answer(s) compared")
        lim = chk["limits"]
        return {"nnd_rel_gap": {"value": float(gap) if compared
                                else float("inf"),
                                "limit": lim["nnd_rel_gap"]},
                "searches_failed": {"value": self.failed, "limit": 0}}


def nnd_rel_gap(pos, vals, ref_profile, ref_vals) -> float:
    """Largest relative gap of a top-k answer: each returned nnd against
    the reference profile at the returned position, and against the
    reference's discord of the same rank."""
    if len(pos) != len(ref_vals) or len(vals) != len(pos):
        return float("inf")
    worst = 0.0
    for p, v, rv in zip(pos, vals, ref_vals):
        if not 0 <= p < len(ref_profile):
            return float("inf")
        worst = max(worst, abs(v - ref_profile[p]) / rv, abs(v - rv) / rv)
    return worst
