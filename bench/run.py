#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  One process: it finds the chips, sets up
the cell (data from the seed, the program, a warm-up of every shape the
cell's traffic uses), measures one window of ``--seconds``, checks what
the window produced against the plain reference, and prints one JSON
object as the last line of standard output:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown"], "checks"}

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
profiles the window and reports its per-layer metrics, the device's
busy and window seconds and the longest device operations and idle
gaps.  ``checks`` holds every number compared with its limit; the same
lines end standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional, Sequence  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.lib import trace as tracing  # noqa: E402
from bench.lib.cells import (Cell, load_cell, load_peaks,  # noqa: E402
                              metric_reader, runner_class)


@dataclass
class Context:
    """What a per-layer reader may read (``bench/metrics/<name>.py``)."""
    peaks: Optional[dict]
    counters: dict
    trace: Optional[tracing.TraceSummary]


class CompileCounter:
    """Counts jaxpr traces and backend compiles (or cache loads) while
    ``on``: the window should see none."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.counts = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event in self.counts:
            self.counts[event] += 1

    def close(self):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._hear)


class GcPauses:
    """Python garbage collections while ``on``: (generation, start,
    seconds) of each."""

    def __init__(self):
        self.on = False
        self.events = []
        self._start = None
        gc.callbacks.append(self._hear)

    def _hear(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self.on and self._start is not None:
            self.events.append((info["generation"], self._start,
                                time.perf_counter() - self._start))

    def close(self):
        gc.callbacks.remove(self._hear)

    def summary(self, t0: float) -> str:
        per = {g: [d for gg, _, d in self.events if gg == g]
               for g in (0, 1, 2)}
        long = [f"gen{g} {d * 1e3:.1f} ms at {a - t0:.3f} s"
                for g, a, d in self.events if d > 0.02]
        return ("; ".join(f"gen{g} {len(v)} x, {sum(v) * 1e3:.1f} ms in all"
                          for g, v in per.items())
                + f"; over 20 ms: {long or 'none'}")


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def find_devices(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < chips):
        raise SystemExit(
            f"bench: this cell needs {chips} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform} device(s). No result.")
    return devices


def run_cell(workload, *, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, overrides: Optional[dict] = None,
             t_start: Optional[float] = None, controls: Sequence[str] = (),
             keep_trace: Optional[str] = None) -> dict:
    """One run of one cell (its name, or a :class:`Cell` a test built);
    returns the result object (the caller prints it).  The other
    arguments serve the tools and tests, never the measurement command:
    ``require_tpu=False`` and ``overrides`` rehearse on the CPU at tiny
    sizes; ``controls`` also checks the reference in each named lower
    precision in the program's place (``result["controls"]``);
    ``keep_trace`` copies the trace and a description of it there."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = (workload if isinstance(workload, Cell)
            else load_cell(workload, overrides))
    import jax
    devices = find_devices(cell.chips, require_tpu)
    dev0 = devices[0]
    peaks = load_peaks(dev0.device_kind) if require_tpu else None
    used = devices[:cell.chips]
    log(f"cell {cell.name}: device {dev0.device_kind} x{len(devices)} "
        f"({dev0.platform}), using {len(used)}; seed {seed}, "
        f"window {seconds} s, trace {int(trace)}")

    runner = runner_class(cell.traffic)(cell, seed, used, log)
    runner.setup()
    # the benchmark's own spans, which name the host's work in a trace
    span = jax.profiler.TraceAnnotation
    counter, pauses = CompileCounter(), GcPauses()
    setup_s = time.perf_counter() - t_start
    log(f"setup {setup_s:.4f} s")
    with tracing.capture(trace) as cap:
        counter.on = pauses.on = True
        t0 = time.perf_counter()
        with span("bench.window"):
            win = runner.window(seconds, span)
        counter.on = pauses.on = False
    counter.close()
    pauses.close()
    log(f"window {win.seconds:.4f} s: {win.attempted} "
        f"{runner.requests} attempted, {win.failed} failed; traces and "
        f"compiles inside it: {sum(counter.counts.values())} "
        f"{counter.counts}")
    log(f"garbage collections inside it: {pauses.summary(t0)}")
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in used)}
    result = {"correct": False, "attempted": win.attempted,
              "failed": win.failed, "metrics": {}, "device": device}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        summary = tracing.read(cap["path"])
        if keep_trace:
            tracing.keep(cap["path"], keep_trace, cell.name)
        tracing.cleanup(cap)
        device["busy_s"] = summary.mean_busy_s()
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(10)}
        ctx = Context(peaks=peaks, counters=win.counters, trace=summary)
        values = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"]).read(ctx)
            if v is not None:
                values[m["name"]] = float(v)
    else:
        values = {"setup_s": setup_s, **win.e2e}
    for name, v in values.items():
        result["metrics"][name] = {"value": v, "unit": units[name]}
        log(f"metric {name} = {v!r} {units[name]}")

    runner.release()
    with span("bench.reference"):
        checks = runner.check()
    result["correct"] = win.failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    for prec in controls:
        result.setdefault("controls", {})[prec] = runner.check(control=prec)
    result["checks"] = checks
    return result


def report(result: dict) -> None:
    """The checks as the last lines of standard error, then the result
    as the last line of standard output."""
    sys.stdout.flush()
    print(f"correct = {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(_plain(result)), flush=True)


def _plain(obj):
    """The result with every non-finite number as a string, so that
    the line stays plain JSON."""
    if isinstance(obj, dict):
        return {k: _plain(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_plain(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return str(obj)
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    # every program, however quick to compile, goes to the cache, so a
    # second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {cache}")
    report(run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), t_start=T_START))
    return 0


if __name__ == "__main__":
    sys.exit(main())
