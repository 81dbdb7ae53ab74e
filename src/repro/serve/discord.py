"""Multi-tenant streaming discord serve plane.

The session layer (``core/engine.py``) makes repeated searches cheap
for *one* user: plans compile once, appends sweep only the tail.
This module is the fleet layer that keeps those wins across thousands
of concurrent tenants (the ROADMAP's "millions of users" shape):

``DiscordServer``
    Owns a fleet of ``DiscordStream`` / ``PanStream`` tenant sessions
    behind

    * a **shared cross-tenant plan cache** — every tenant engine is
      constructed over one :class:`repro.core.engine.PlanCache`, so
      bucket-identical specs (same backend/znorm/block + geometry)
      reuse each other's compilations.  The cache is budgeted (max
      live compiled plans, LRU-evicted) and its hit/miss/eviction
      counters surface in :class:`ServeStats`;
    * **cross-stream micro-batching** — pending appends whose specs
      map to the same plan key are coalesced into one
      ``("tail_mb"/"pan_tail_mb"/"profile_mb"/"pan_mb", ..., B)``
      dispatch instead of ``B`` device round-trips.  Each lane runs
      the exact single-tenant plan body under ``lax.map``, so results
      are **bit-identical** to per-tenant sequential appends — the
      parity property the hypothesis suite asserts;
    * **deferred synchronization** — a flush round first *dispatches*
      every coalesced group (async device work), then walks the
      response path where the host folds block, so device queues stay
      full instead of round-tripping per group;
    * **admission control** — the pending-append queue is bounded
      (``max_pending``); an over-budget append raises
      :class:`AdmissionError` loudly instead of buffering unboundedly;
    * **straggler detection** — optional, through the existing
      ``telemetry/straggler.py``: per-flush wall times of each plan
      group feed a :class:`StragglerDetector` slot, so a plan family
      whose dispatches drift slow (e.g. a backend falling off its fast
      path) is reported like a slow host in a training fleet.

Semantics contract: ``flush()`` drains the queue in rounds of one
pending append per tenant, so each tenant's appends apply in their
original order and every coalesced fold equals the sequential one —
``server.append(t, p1); server.append(t, p2)`` is bit-identical to
``stream.append(p1).append(p2)``.

User guide: docs/serving.md.
"""
from __future__ import annotations

import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Union

import jax.numpy as jnp
import numpy as np

from ..core.engine import (DiscordEngine, DiscordStream, PanStream,
                           PlanCache)
from ..core.result import DiscordResult, PanResult
from ..core.spec import SearchSpec, length_bucket

__all__ = ["DiscordServer", "ServeStats", "AdmissionError"]


class AdmissionError(RuntimeError):
    """The server's bounded pending-append queue is full.  Raised
    loudly — appends are never silently dropped or reordered — so the
    caller can flush, shed load, or raise ``max_pending``."""


@dataclass
class ServeStats:
    """One flush-consistent snapshot of the serve plane's telemetry
    (``DiscordServer.stats()``).

    ``dispatches`` counts device round-trips actually issued;
    ``sequential_dispatches`` what the same appends would have cost
    one-tenant-at-a-time — their ratio (``dispatch_ratio``, lower is
    better) is the micro-batching win the serve benchmark CI-gates.
    ``cache`` carries the shared plan cache's hit/miss/eviction
    counters; ``plans``/``traces``/``tile_lanes`` aggregate the
    engine fleet's session counters (``traces == plans`` is the
    fleet-wide compile-once contract).
    """
    tenants: int = 0
    engines: int = 0
    appends_queued: int = 0
    appends_applied: int = 0
    points: int = 0
    rejected: int = 0
    flushes: int = 0
    rounds: int = 0
    dispatches: int = 0
    sequential_dispatches: int = 0
    coalesced: int = 0
    padded_lanes: int = 0
    pending: int = 0
    plans: int = 0
    traces: int = 0
    tile_lanes: int = 0
    cache: dict = field(default_factory=dict)
    straggler: Optional[dict] = None

    @property
    def dispatch_ratio(self) -> float:
        """Issued device dispatches per sequential-equivalent dispatch
        (1.0 = no coalescing; the serve benchmark gates < 0.5)."""
        return self.dispatches / max(self.sequential_dispatches, 1)

    @property
    def cache_hit_rate(self) -> float:
        return float(self.cache.get("hit_rate", 0.0))

    def as_dict(self) -> dict:
        return {"tenants": self.tenants, "engines": self.engines,
                "appends_queued": self.appends_queued,
                "appends_applied": self.appends_applied,
                "points": self.points, "rejected": self.rejected,
                "flushes": self.flushes, "rounds": self.rounds,
                "dispatches": self.dispatches,
                "sequential_dispatches": self.sequential_dispatches,
                "dispatch_ratio": self.dispatch_ratio,
                "coalesced": self.coalesced,
                "padded_lanes": self.padded_lanes,
                "pending": self.pending, "plans": self.plans,
                "traces": self.traces, "tile_lanes": self.tile_lanes,
                "cache": dict(self.cache),
                "straggler": self.straggler}


class _Tenant:
    """One tenant session: its stream plus the bounded FIFO of
    appends not yet applied."""

    __slots__ = ("tid", "spec", "stream", "pending")

    def __init__(self, tid, spec: SearchSpec,
                 stream: Union[DiscordStream, PanStream]):
        self.tid = tid
        self.spec = spec
        self.stream = stream
        self.pending: deque = deque()


class DiscordServer:
    """Fleet front door for streaming discord search (docs/serving.md).

        srv = DiscordServer(cache_budget=64, max_group=32)
        srv.open("sensor-1", s=128, k=3, history=warmup)
        srv.append("sensor-1", new_points)     # queued, bounded
        srv.flush()                            # coalesced dispatches
        print(srv.discords("sensor-1"))
        print(srv.stats().as_dict())

    Tenants whose specs bucket identically share compiled plans
    through one :class:`PlanCache`; same-plan-key appends coalesce
    into micro-batched dispatches whose per-lane results are
    bit-identical to sequential per-tenant appends.

    ``cache_budget``
        Max live compiled plans in the shared cache (``None`` =
        unbounded).  Each plan pins one XLA executable — this is the
        serve plane's compile-memory knob.
    ``max_pending``
        Bound on queued-but-unapplied appends across all tenants;
        ``append`` past it raises :class:`AdmissionError`.
    ``max_group``
        Largest micro-batch lane count per dispatch (batch sizes
        bucket to powers of two up to this, so lane-count plan keys
        stay few).
    ``straggler_slots``
        When set, plan groups are hashed onto this many detector
        slots and per-flush group wall times feed a
        ``telemetry.straggler.StragglerDetector`` (``decide()``
        snapshot in ``stats().straggler``).

    Scope: local (non-sharded) tenant specs only — a mesh-sharded
    session already owns the whole device fleet, so serving it behind
    a tenant multiplexer would deadlock devices against each other;
    ``open`` rejects ``ndev``/``ring`` specs with a pointer to
    per-session usage.
    """

    def __init__(self, *, cache_budget: Optional[int] = None,
                 max_pending: int = 65536, max_group: int = 64,
                 straggler_slots: Optional[int] = None,
                 straggler_kwargs: Optional[dict] = None):
        if max_group < 1:
            raise ValueError(f"max_group must be >= 1, got {max_group}")
        if max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, "
                             f"got {max_pending}")
        self.plan_cache = PlanCache(budget=cache_budget)
        self.max_pending = int(max_pending)
        self.max_group = int(max_group)
        self._engines: "OrderedDict[SearchSpec, DiscordEngine]" = \
            OrderedDict()
        self._tenants: "OrderedDict" = OrderedDict()
        self._pending_total = 0
        self._counters = ServeStats()
        self._straggler = None
        self._straggler_last: Optional[dict] = None
        self._slots: Dict[tuple, int] = {}
        if straggler_slots is not None:
            from ..telemetry.straggler import StragglerDetector
            self._straggler = StragglerDetector(
                int(straggler_slots), **(straggler_kwargs or {}))

    # -- tenancy -------------------------------------------------------
    def __len__(self) -> int:
        return len(self._tenants)

    def __contains__(self, tid) -> bool:
        return tid in self._tenants

    @property
    def tenant_ids(self) -> List:
        return list(self._tenants)

    def engine_for(self, spec: SearchSpec) -> DiscordEngine:
        """The fleet engine serving ``spec`` (deduped per spec; every
        engine shares this server's plan cache)."""
        if spec.ndev is not None or spec.method in ("ring", "drag"):
            raise ValueError(
                "DiscordServer serves local (non-sharded) specs only: "
                "a mesh-sharded session owns the whole device fleet "
                "already, so multiplexing tenants over it would make "
                "dispatches contend for the same collective.  Run "
                f"spec={spec} through its own DiscordEngine session "
                "instead.")
        eng = self._engines.get(spec)
        if eng is None:
            eng = DiscordEngine(spec, plan_cache=self.plan_cache)
            self._engines[spec] = eng
        return eng

    def open(self, tid, spec: Optional[SearchSpec] = None, *,
             history=None, **spec_kwargs):
        """Admit a tenant: a new stream session under ``spec`` (or
        spec kwargs).  ``history`` is queued like a first append, so
        fleet warm-ups coalesce their fills too.  Returns ``tid``."""
        if tid in self._tenants:
            raise ValueError(f"tenant {tid!r} is already open")
        if spec is None:
            spec = SearchSpec(**spec_kwargs)
        elif spec_kwargs:
            raise TypeError("pass either a SearchSpec or spec kwargs, "
                            "not both")
        eng = self.engine_for(spec)
        ten = _Tenant(tid, spec, eng.open_stream())
        self._tenants[tid] = ten
        if history is not None and np.asarray(history).size:
            self.append(tid, history)
        return tid

    def close(self, tid) -> Union[DiscordStream, PanStream]:
        """Apply the tenant's pending appends, release it, and hand
        its stream back to the caller."""
        ten = self._tenant(tid)
        if ten.pending:
            self.flush()
        del self._tenants[tid]
        return ten.stream

    def _tenant(self, tid) -> _Tenant:
        ten = self._tenants.get(tid)
        if ten is None:
            raise KeyError(f"unknown tenant {tid!r} (open tenants: "
                           f"{len(self._tenants)})")
        return ten

    # -- ingest --------------------------------------------------------
    def append(self, tid, points) -> "DiscordServer":
        """Queue points for ``tid`` (bounded; applied at the next
        ``flush`` in arrival order, coalesced across tenants)."""
        ten = self._tenant(tid)
        pts = np.asarray(points, np.float64).ravel()
        if pts.size == 0:
            return self
        if self._pending_total >= self.max_pending:
            self._counters.rejected += 1
            raise AdmissionError(
                f"append for tenant {tid!r} rejected: "
                f"{self._pending_total} pending appends >= "
                f"max_pending={self.max_pending}.  The queue is "
                "bounded by design (appends are never silently "
                "dropped) — call flush() to drain it, or raise "
                "max_pending.")
        ten.pending.append(pts)
        self._pending_total += 1
        self._counters.appends_queued += 1
        self._counters.points += int(pts.size)
        return self

    # -- the coalesced flush path --------------------------------------
    def _group_geom(self, op: dict) -> tuple:
        """The micro-batch plan-key geometry (minus the lane count) an
        op coalesces under — ops with equal full keys share one
        dispatch."""
        kind = op["kind"]
        if kind == "fill":
            return ("profile_mb", op["s"], op["Lb"])
        if kind == "tail":
            return ("tail_mb", op["s"], op["Lb"], op["Qb"])
        if kind == "qtail":
            # the quantized stream tail has no micro-batch plan (its
            # refinement pass is data-dependent per stream), so each
            # op keys uniquely and takes the len==1 generic dispatch
            return ("qtail", op["s"], op["Lb"], op["Qb"], id(op))
        if kind == "pan_fill":
            return ("pan_mb", op["ladder"], op["Lb"])
        return ("pan_tail_mb", op["ladder"], op["Lb"], op["Qb"])

    def _exec_group(self, chunk) -> tuple:
        """Dispatch one plan group (async — no host sync here: the
        response path's folds block later, so groups overlap on
        device)."""
        self._counters.dispatches += 1
        self._counters.sequential_dispatches += len(chunk)
        if len(chunk) == 1:
            ten, op = chunk[0]
            return ten.stream._append_exec(op)
        ten0, op0 = chunk[0]
        eng = ten0.stream.engine
        kind = op0["kind"]
        n = len(chunk)
        # lane counts bucket to powers of two so the cache holds a
        # ladder of B values, not one plan per fleet size; padding
        # lanes replicate lane 0 and are discarded host-side
        B = min(length_bucket(n, lo=1), self.max_group)
        pad = B - n
        stack = jnp.asarray(np.stack(
            [op["xp"] for _, op in chunk] + [op0["xp"]] * pad))
        nv = jnp.asarray(np.array(
            [op["n_new"] for _, op in chunk] + [op0["n_new"]] * pad,
            np.int32))
        self._counters.coalesced += n
        self._counters.padded_lanes += pad
        lanes = (nv,)
        if kind in ("tail", "pan_tail"):
            lanes = (jnp.asarray(np.array(
                [op["q0"] for _, op in chunk] + [op0["q0"]] * pad,
                np.int32)), nv)
        return eng.plan(*self._group_geom(op0), B)(stack, *lanes)

    def _finish_group(self, chunk, out) -> None:
        """Response path: fold each lane's outputs into its tenant's
        profile (the host-side ``np.asarray`` blocks live here)."""
        if len(chunk) == 1:
            ten, op = chunk[0]
            ten.stream._append_finish(op, out)
        else:
            for b, (ten, op) in enumerate(chunk):
                ten.stream._append_finish(
                    op, tuple(o[b] for o in out))
        self._counters.appends_applied += len(chunk)

    def _observe(self, entries) -> None:
        """Feed per-group wall times into the straggler detector (one
        fleet 'host' per plan-group slot; slots not dispatched this
        flush read as the observed median, i.e. unremarkable)."""
        det = self._straggler
        if det is None or not entries:
            return
        n = det.n_hosts
        times: Dict[int, float] = {}
        for key, _chunk, _out, dt in entries:
            slot = self._slots.setdefault(key, len(self._slots) % n)
            times[slot] = max(times.get(slot, 0.0), dt)
        med = float(np.median(list(times.values())))
        det.log_step(self._counters.flushes,
                     np.array([times.get(h, med) for h in range(n)]))
        self._straggler_last = det.decide()

    def flush(self) -> int:
        """Apply every pending append and return the number of rounds.

        Drains in rounds of **one pending append per tenant** (so each
        tenant's appends apply in order — the sequential semantics the
        bit-identical parity contract needs), grouping each round's
        staged ops by plan key and dispatching every group before any
        group's host folds block (deferred sync).
        """
        rounds = 0
        while self._pending_total:
            rounds += 1
            staged = []
            for ten in self._tenants.values():
                if ten.pending:
                    pts = ten.pending.popleft()
                    self._pending_total -= 1
                    op = ten.stream._append_begin(pts)
                    if op is None:        # absorbed, nothing to sweep
                        self._counters.appends_applied += 1
                    else:
                        staged.append((ten, op))
            groups: "OrderedDict[tuple, list]" = OrderedDict()
            for ten, op in staged:
                key = ten.stream.engine._plan_key(self._group_geom(op))
                groups.setdefault(key, []).append((ten, op))
            entries = []
            for key, members in groups.items():
                for i in range(0, len(members), self.max_group):
                    chunk = members[i:i + self.max_group]
                    t0 = time.perf_counter()
                    out = self._exec_group(chunk)
                    entries.append([key, chunk, out,
                                    time.perf_counter() - t0])
            for e in entries:             # response path: folds block
                t0 = time.perf_counter()
                self._finish_group(e[1], e[2])
                e[3] += time.perf_counter() - t0
            self._observe(entries)
        self._counters.flushes += 1
        self._counters.rounds += rounds
        return rounds

    # -- queries (flush-then-read) -------------------------------------
    def stream(self, tid) -> Union[DiscordStream, PanStream]:
        """The tenant's stream with every queued append applied."""
        ten = self._tenant(tid)
        if self._pending_total:
            self.flush()
        return ten.stream

    def discords(self, tid, k: Optional[int] = None
                 ) -> Union[DiscordResult, PanResult]:
        """Current top-k discords of the tenant (flushes first)."""
        return self.stream(tid).discords(k)

    def profile(self, tid, rung: int = 0) -> np.ndarray:
        """Current exact nnd profile of the tenant (flushes first;
        ``rung`` selects the ladder rung on pan tenants)."""
        st = self.stream(tid)
        if isinstance(st, PanStream):
            return st.profile(rung)
        if rung:
            raise ValueError(f"tenant {tid!r} is single-length; "
                             f"rung={rung} is only meaningful on "
                             "multi-window (pan) tenants")
        return st.profile()

    # -- telemetry -----------------------------------------------------
    def stats(self) -> ServeStats:
        """A flush-consistent snapshot of the serve-plane counters,
        the shared cache telemetry and the engine fleet's aggregated
        session stats."""
        c = self._counters
        agg = {"plans": 0, "traces": 0, "tile_lanes": 0}
        for eng in self._engines.values():
            st = eng.stats
            agg["plans"] += st.plans
            agg["traces"] += st.traces
            agg["tile_lanes"] += st.tile_lanes
        return ServeStats(
            tenants=len(self._tenants), engines=len(self._engines),
            appends_queued=c.appends_queued,
            appends_applied=c.appends_applied, points=c.points,
            rejected=c.rejected, flushes=c.flushes, rounds=c.rounds,
            dispatches=c.dispatches,
            sequential_dispatches=c.sequential_dispatches,
            coalesced=c.coalesced, padded_lanes=c.padded_lanes,
            pending=self._pending_total, plans=agg["plans"],
            traces=agg["traces"], tile_lanes=agg["tile_lanes"],
            cache=self.plan_cache.as_dict(),
            straggler=self._straggler_last)

    def report(self) -> dict:
        return self.stats().as_dict()

    def __repr__(self) -> str:
        c = self._counters
        return (f"DiscordServer(tenants={len(self._tenants)}, "
                f"engines={len(self._engines)}, "
                f"pending={self._pending_total}, "
                f"cache={self.plan_cache!r}, "
                f"dispatches={c.dispatches}/"
                f"{c.sequential_dispatches})")
