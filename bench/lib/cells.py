"""Find a cell's files by the names that ``BENCHMARK.json`` gives.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix; the configuration's file is the one its ``configs`` entry lists,
the mix is ``bench/traffic/<traffic>.json``, whose ``runner`` names the
runner ``bench/runners/<runner>.py``, each per-layer metric is
``bench/metrics/<name>.py``, and a configuration's ``data.generator``
is ``bench/data/<generator>.py``.  Nothing here names a cell, and a new
one of any of these is a new file.
"""
from __future__ import annotations

import copy
import functools
import importlib.util
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _merge(base: dict, over: Optional[dict]) -> dict:
    out = copy.deepcopy(base)
    for k, v in (over or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration's file, as run
    traffic: dict         # the traffic mix's file
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, overrides: Optional[Dict[str, dict]] = None
              ) -> Cell:
    """The cell ``name`` with its configuration, traffic mix and
    metrics.  ``overrides`` ({"config": {...}, "traffic": {...}}) are
    merged into the files' contents: rehearsals at tiny sizes use them,
    the measurement command never does."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(ROOT / conf["file"]) as f:
        config = json.load(f)
    with open(BENCH / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    overrides = overrides or {}
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in reported]
    return Cell(name=name, chips=int(w["chips"]),
                config=_merge(config, overrides.get("config")),
                traffic=_merge(traffic, overrides.get("traffic")),
                end_to_end=e2e, per_layer=per_layer)


def load_module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded from its file (a
    name may hold ``.`` and ``-``); each is loaded once."""
    key = f"bench_{kind}_" + re.sub(r"\W", "_", name)
    if key not in sys.modules:
        path = BENCH / kind / f"{name}.py"
        if not path.is_file():
            raise SystemExit(f"no {path.relative_to(ROOT)} for {name!r}")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def metric_reader(name: str):
    """The module ``bench/metrics/<name>.py`` (its ``read(ctx)``)."""
    return load_module("metrics", name)


def runner_class(traffic: dict):
    """The class ``Runner`` of ``bench/runners/<runner>.py`` that the
    traffic mix names."""
    return load_module("runners", traffic["runner"]).Runner


def generator(data: dict):
    """``generate`` of ``bench/data/<generator>.py`` with the
    configuration's parameters bound."""
    gen = load_module("data", data["generator"]).generate
    return functools.partial(gen, **data.get("params", {}))


def load_peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (has "
                         f"{sorted(table['devices'])})")
    return table["devices"][device_kind]
