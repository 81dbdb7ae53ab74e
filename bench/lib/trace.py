"""Profiler trace capture and its reduction to device metrics.

A traced run wraps its measured window in ``jax.profiler`` and the
benchmark's own ``TraceAnnotation`` spans (``bench.window`` around the
whole window, ``bench.search`` and ``bench.reference`` around each call
into the program).  The reduction
reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and keeps,
for each device, the operations on its ``XLA Ops`` line clipped to the
``bench.window`` span, and the host spans of the thread that ran the
window.  From those:

* busy time: the union of a device's operation intervals;
* operation time: the summed durations of the operations whose name
  matches (a kernel, a collective);
* idle gaps: the holes in the union, each named after the innermost
  host span that was open at its midpoint.

The functions below ``TraceSummary`` work on plain ``(name, start_ns,
end_ns)`` tuples, so the arithmetic is tested without a chip.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"             # the TensorCore's operations
ASYNC_LINE = "Async XLA Ops"     # copies and collectives beside them
WINDOW_SPAN = "bench.window"
#: "%fusion.3 = f32[...] fusion(...), kind=..." -> "%fusion.3 fusion"
_HLO = re.compile(r"^(%[\w.\-]+) = .*?[})] ([a-z][\w\-]*)\(")


# -- interval arithmetic ------------------------------------------------
def clip(events: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """Events cut to [lo, hi]; those outside are dropped."""
    out = []
    for name, a, b in events:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union(events: Sequence[Interval]) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals covered by any event."""
    merged: List[List[int]] = []
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events: Sequence[Interval]) -> int:
    return sum(b - a for a, b in union(events))


def gaps(events: Sequence[Interval], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """The idle holes of [lo, hi] between the union's intervals."""
    out, t = [], lo
    for a, b in union(events):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(host: Sequence[Interval], t: int) -> Optional[str]:
    """Name of the shortest host span open at ``t``."""
    best = None
    for name, a, b in host:
        if a <= t < b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else None


def op_ns(events: Sequence[Interval], match: Callable[[str], bool]) -> int:
    return sum(b - a for name, a, b in events if match(name))


def short_name(name: str) -> str:
    """An operation's HLO instruction name and opcode, from the full
    HLO text the device trace gives as its name."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    label = f"{m.group(1)} {m.group(2)}"
    if "tpu_custom_call" in name:
        label += " tpu_custom_call"
    return label


# -- summary of one traced window -------------------------------------
@dataclass
class TraceSummary:
    lo: int
    hi: int
    devices: Dict[int, List[Interval]]           # XLA Ops, per chip
    host: List[Interval] = field(default_factory=list)
    async_ops: Dict[int, List[Interval]] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self, dev: int) -> float:
        return busy_ns(self.devices[dev]) * 1e-9

    def mean_busy_s(self) -> float:
        return (sum(self.busy_s(d) for d in self.devices)
                / max(len(self.devices), 1))

    def op_s(self, dev: int, match: Callable[[str], bool]) -> float:
        """Summed durations of the matching operations on both of the
        chip's operation lines."""
        return (op_ns(self.devices[dev], match)
                + op_ns(self.async_ops.get(dev, []), match)) * 1e-9

    def total_op_s(self, match: Callable[[str], bool]) -> float:
        return sum(self.op_s(d, match) for d in self.devices)

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` operation names with the most device time, in
        seconds averaged over the devices."""
        tot: Dict[str, int] = {}
        for evs in self.devices.values():
            for name, a, b in evs:
                name = short_name(name)
                tot[name] = tot.get(name, 0) + (b - a)
        nd = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / nd] for name, ns in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The ``n`` longest idle gaps, each named after the host span
        open at its midpoint (prefixed with the device on several)."""
        every = [(b - a, dev, a, b) for dev, evs in self.devices.items()
                 for a, b in gaps(evs, self.lo, self.hi)]
        every.sort(key=lambda g: -g[0])
        out = []
        for ns, dev, a, b in every[:n]:
            label = innermost(self.host, (a + b) // 2) or "no span"
            if len(self.devices) > 1:
                label = f"TPU:{dev} {label}"
            out.append([label, ns * 1e-9])
        return out


# -- capture and parsing -----------------------------------------------
@contextlib.contextmanager
def capture(enabled: bool):
    """Profile the body when ``enabled``; yields a dict that holds the
    ``.xplane.pb`` path once the body is done.  The trace directory is
    made under ``$TMPDIR``; :func:`cleanup` removes it."""
    out: dict = {}
    if not enabled:
        yield out
        return
    import jax
    d = tempfile.mkdtemp(prefix="bench-trace-")
    out["dir"] = d
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # host spans without a Python tracer
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        out["path"] = found[0] if found else None


def cleanup(out: dict) -> None:
    if out.get("dir"):
        shutil.rmtree(out["dir"], ignore_errors=True)


def keep(path: str, dest: str, name: str) -> None:
    """Copy a trace and its :func:`describe` into ``dest``."""
    import json
    os.makedirs(dest, exist_ok=True)
    if os.path.getsize(path) < 16 << 20:
        shutil.copy(path, os.path.join(dest, f"{name}.xplane.pb"))
    with open(os.path.join(dest, f"{name}.trace.json"), "w") as f:
        json.dump(describe(path), f, indent=1)


def _events(line) -> List[Interval]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def read(path: str) -> TraceSummary:
    """Reduce one ``.xplane.pb`` to the window's device operations and
    the window thread's host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, List[Interval]] = {}
    async_ops: Dict[int, List[Interval]] = {}
    host_lines: List[List[Interval]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = _events(line)
                elif line.name == ASYNC_LINE:
                    async_ops[int(m.group(1))] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                if any(name == WINDOW_SPAN for name, _, _ in evs):
                    host_lines.append(evs)
    if not host_lines:
        raise RuntimeError(f"trace {path} holds no {WINDOW_SPAN} span")
    host = host_lines[0]
    lo, hi = next((a, b) for name, a, b in host if name == WINDOW_SPAN)
    return TraceSummary(
        lo=lo, hi=hi,
        devices={d: clip(evs, lo, hi) for d, evs in sorted(devices.items())},
        host=clip(host, lo, hi),
        async_ops={d: clip(evs, lo, hi) for d, evs in async_ops.items()})


def describe(path: str, top: int = 40) -> dict:
    """What a trace holds, for reading one by hand: every plane and
    line with its event count, and per device line the ``top`` names
    with the most time and the stats of one event of each."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            tot: Dict[str, list] = {}
            for e in line.events:
                rec = tot.setdefault(e.name, [0, 0, None])
                rec[0] += 1
                rec[1] += int(e.duration_ns)
                if rec[2] is None:
                    rec[2] = {k: str(v)[:160] for k, v in e.stats}
            names = sorted(tot.items(), key=lambda kv: -kv[1][1])[:top]
            lines.append({"line": line.name,
                          "events": sum(r[0] for r in tot.values()),
                          "top": [[k, r[0], r[1], r[2]]
                                  for k, r in names]})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}
