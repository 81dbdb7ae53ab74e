"""Compile-once discord-search sessions: DiscordEngine + DiscordStream.

HST's two core ideas — the warm-up process and the similarity of
sequences close in time (paper Sec. 3) — are properties of a *sequence
of related searches*, but a stateless entrypoint retraces, recompiles
and forgets between calls.  This module is the session layer that
carries that state:

``DiscordEngine``
    Owns a plan cache keyed on ``(kind, s, length_bucket, ...)``.  Series
    lengths are rounded up to power-of-two buckets (the ServeEngine
    prompt-bucket rule) and the padding windows are *masked* inside the
    tile backends (their ids remap to -1), so a second search over any
    series in the same bucket reuses the compiled tile sweep with zero
    new traces.  ``search`` / ``search_batched`` are the one-shot and
    serving front doors; non-profile methods (serial counted
    implementations, hst_jax, ring, drag) dispatch through the same
    object so one spec describes any search.

``DiscordStream``
    The paper's neighbor-similarity idea expressed at the API layer:
    an append-only series whose exact nnd profile is maintained
    incrementally.  Appending points can only *lower* an existing
    window's nnd (new neighbors appear, none retire), so old windows
    warm-start from their previous value and each ``append`` sweeps
    only the new tail tile rows (new windows vs everything, column
    minima folded back into the old profile) instead of the full
    O(N^2) sweep.

Mesh-sharded plan family (the ring fold-in, docs/ARCHITECTURE.md):
    ``method="ring"`` — or an explicit ``mesh=`` / ``SearchSpec(ndev=)``
    placement — makes the multi-device ring sweep of
    ``core/distributed`` a first-class plan *kind* of this cache, keyed
    ``(kind, s, length-bucket, mesh-shape)``.  The plan builds
    length-bucketed ``TileEngine`` window blocks, pads the window count
    so every per-device shard stays a multiple of ``spec.block``
    (MXU-aligned), and runs the same ``ppermute`` hop body as the
    standalone module under ``shard_map`` — so repeated sharded
    searches hit zero new traces exactly like local ones.  Sharded
    engines also route ``search_batched`` through a two-level layout
    (series-parallel across devices; ring per series past
    ``REPRO_RING_SERIES_THRESHOLD`` windows) and ``DiscordStream``
    appends through a sharded tail plan in which each device sweeps
    only its own candidate shard against the new tail windows and the
    per-shard minima are min-folded globally.

Pan-length plan family (``core/pan.py``, docs/ARCHITECTURE.md §3b,
docs/pan.md for the user guide):
    ``search_pan`` runs a whole *ladder* of window lengths from one
    QT-carrying tile sweep — the base rung pays full-width dot tiles,
    each later rung only its extension width — plan-cached per
    ``(canonical ladder, length-bucket)`` (``("pan", ...)`` locally,
    ``("pan_ring", ...)`` with the query blocks sharded across the
    mesh).  Multi-window specs route ``search`` through it, and the
    ladder is a full citizen of every session plane:

      * **streaming** — ``open_stream`` on a multi-window spec returns
        a :class:`PanStream` whose appends sweep only the tail rows at
        every rung from one carried QT (``("pan_tail", ...)`` plans;
        candidate-sharded ``("pan_tail_ring", ...)`` on meshed
        sessions);
      * **batched** — ``search_batched`` on a multi-window spec runs
        the (B, ladder) plan (``("pan_batched", ...)``, vmapped on
        ``xla``, scanned elsewhere; two-level sharded layout);
      * **global-top-k-only** — ``search_pan(schedule="lb_abandon")``
        sweeps rungs sequentially through carried-QT
        ``("pan_base", ...)`` / ``("pan_step", ...)`` plans and skips
        any rung whose ``pan.cross_length_ub`` bracket provably cannot
        beat the current k-th global ``d/sqrt(s)`` pick — skips are
        re-verified against the final top-k, so the result equals the
        all-rung sweep's.

Plan kinds (``PLAN_KINDS``): every compiled plan is one kind of that
table, built by ``DiscordEngine.plan(kind, *geom)`` from a per-series
op body (``_op_<op>``: profile, tail, pan, pan_tail, pan_base,
pan_step, qsweep, qsweep_refine, qsweep_tail, qsweep_tail_refine)
under a layout (``_layout_<layout>``): ``local``; ``mb``, the serve
plane's ``lax.map`` micro-batch with per-lane scalars; ``batched``,
vmapped on ``xla`` and scanned elsewhere; ``series_sharded``, the
batched layout under ``shard_map``; ``row_sharded``, query blocks
across the mesh; and the shard bodies of ``ring``, ``tail_ring`` and
``pan_tail_ring``.  The key is ``(kind, *geom)``, plus the mesh shape
``(ndev,)`` for sharded kinds, behind the spec prefix of
``_plan_key``.  Every plan bumps ``stats.traces`` when (and only when)
it is traced, so tests can assert the compile-once contract directly.
The IR audit's registry (``plan_kind_registry``) and the analysers'
kind lists are derived from the same table.

Fleet plane (``repro.serve.DiscordServer``, docs/serving.md): the
plan cache is a first-class :class:`PlanCache` object — private and
unbounded per engine by default, shareable (budgeted, LRU-evicting)
across a multi-tenant engine fleet — and every stream append is split
into ``_append_begin`` / ``_append_exec`` / ``_append_finish`` phases
so the server can coalesce same-plan-key appends from many tenants
into one ``(*_mb, ...)`` micro-batched dispatch whose ``lax.map``
lanes run the exact single-tenant bodies (bit-identical results).

Work accounting is unified across planes (docs/cps.md): every result
reports ``calls`` (= swept ``tile_lanes`` on this plane) and the
derived ``cps``.

Spans (``jax.profiler.TraceAnnotation``): each entry that answers from
a plan opens an ``engine.search`` span on the calling thread, which a
profile puts on the device planes' clock.  Names are fixed strings and
identifiers are stats: ``kind`` (``profile``, ``ring``, ``qsweep``,
``batched``, ``pan``) on every one; on the profile and ring paths
``search`` (the ``stats.searches`` index the search takes), ``bucket``
and ``n``; on the profile path ``grid`` too (``triangle`` or
``square``, ``DiscordEngine._profile_grid``), on the ring path
``ndev``.  An entry that calls another nests a second
``engine.search`` inside its own; readers take the outermost.

On the profile and ring paths the span holds, in order and without
overlap:
``engine.prepare``
    f64 conversion, length check, bucket, padding (and on the profile
    path the plan lookup);
``engine.dispatch``
    host-to-device copy and the plan's call, which returns before the
    device finishes (and traces and compiles on a cache miss);
``engine.wait``
    the host blocked until the device's profile is ready (on the ring,
    both sharded outputs: squared nnds and neighbours);
``engine.fetch``
    the device-to-host copy of the profile (on the ring, of both);
``engine.select``
    square root, the non-overlapping top-k, the result.

Each plan's traced function runs under ``jax.named_scope(<kind>)``,
and every ``pallas_call`` carries a ``name=``, so device operations
are named by plan kind and kernel.
"""
from __future__ import annotations

import functools
import inspect
import math
import os
import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from ..kernels.common import ceil_div, exclusion_mask, znorm_d2_formula
from ..kernels.registry import (bound_dot_radius, get_bound_backend,
                                quant_scales, resolve_backend)
from .pan import (PanEngine, canonical_ladder, cross_length_ub,
                  global_normalized_topk, ladder_lb_margin, pan_lanes,
                  pan_rung_shares, pan_tail_sweep)
from .result import DiscordResult, PanResult
from .spec import SearchSpec, length_bucket
from .tiles import (TileEngine, exact_pair_d2, self_join_grid,
                    self_join_lanes, topk_nonoverlapping)
from .windows import sliding_stats

__all__ = ["DiscordEngine", "DiscordStream", "PanStream", "EngineStats",
           "PlanCache", "PLAN_KINDS", "PlanKindAudit", "plan_kind_registry",
           "plan_pad_geom", "plan_shard_geom", "plan_pan_row_geom",
           "ring_series_threshold", "PLAN_KEY_FIELDS",
           "KIND_DISPATCH_FIELDS", "TRACE_INVARIANT_FIELDS"]

# -- SearchSpec keying contract (audited by repro.analysis.speckey) ----
#: spec fields that reach every plan-cache key: ``backend``/``znorm``/
#: ``block``/``precision`` through the ``_plan_key`` prefix, ``s``
#: through each kind's own key element, ``ndev`` through the
#: mesh-shape element of the sharded kinds
PLAN_KEY_FIELDS = ("s", "backend", "znorm", "block", "ndev",
                   "precision")
#: spec fields that select *which* plan kind runs — the kind string
#: leading every key carries them
KIND_DISPATCH_FIELDS = ("method",)
#: host-side fields no plan body ever closes over; perturbing them
#: must mint zero new plans (speckey.runtime_audit asserts this)
TRACE_INVARIANT_FIELDS = ("k", "P", "alpha", "seed", "r")

#: host-side fill of the length-bucket padding.  Results never depend
#: on it — every padded lane's id is masked to -1 downstream — and
#: repro.analysis.sanitize proves that by swapping in NaN/±inf
#: canaries and asserting bit-identical top-k.
PAD_FILL = 0.0


@dataclass(frozen=True)
class _PlanKind:
    """How :meth:`DiscordEngine.plan` builds one kind: the per-series
    ``op`` body (``DiscordEngine._op_<op>``) under a ``layout``
    (``DiscordEngine._layout_<layout>``), and what the analysers read
    of it.  ``znorm_only``: audited in z-normalized mode only (the
    engine refuses raw ``ring``/``tail_ring``; ``qsweep_ring`` rides a
    ring spec).  ``quant``: a quantized bound pass or its f32
    refinement (docs/cps.md)."""
    op: str
    layout: str
    znorm_only: bool = False
    quant: bool = False

    @property
    def sharded(self) -> bool:
        """Runs under ``shard_map`` on the session mesh, and keys on
        its shape."""
        return self.layout not in ("local", "mb", "batched")


#: every plan-cache kind, by the string that leads its key, names its
#: device operations (``jax.named_scope``) and keys the serve plane's
#: coalesced groups
PLAN_KINDS = {
    "profile": _PlanKind("profile", "local"),
    "batched": _PlanKind("profile", "batched"),
    "tail": _PlanKind("tail", "local"),
    "qsweep": _PlanKind("qsweep", "local", quant=True),
    "qsweep_refine": _PlanKind("qsweep_refine", "local", quant=True),
    "qsweep_tail": _PlanKind("qsweep_tail", "local", quant=True),
    "qsweep_tail_refine": _PlanKind("qsweep_tail_refine", "local",
                                    quant=True),
    "pan": _PlanKind("pan", "local"),
    "pan_tail": _PlanKind("pan_tail", "local"),
    "pan_base": _PlanKind("pan_base", "local"),
    "pan_step": _PlanKind("pan_step", "local"),
    "pan_batched": _PlanKind("pan", "batched"),
    "profile_mb": _PlanKind("profile", "mb"),
    "tail_mb": _PlanKind("tail", "mb"),
    "pan_mb": _PlanKind("pan", "mb"),
    "pan_tail_mb": _PlanKind("pan_tail", "mb"),
    "ring": _PlanKind("profile", "ring", znorm_only=True),
    "batched_ring": _PlanKind("profile", "series_sharded"),
    "tail_ring": _PlanKind("tail", "tail_ring", znorm_only=True),
    "qsweep_ring": _PlanKind("qsweep", "row_sharded", znorm_only=True,
                             quant=True),
    "pan_ring": _PlanKind("pan", "row_sharded"),
    "pan_tail_ring": _PlanKind("pan_tail", "pan_tail_ring"),
    "pan_batched_ring": _PlanKind("pan", "series_sharded"),
}


def plan_pad_geom(s: int, Lb: int, block: int) -> int:
    """Padded window count of a bucket-``Lb`` sweep at window ``s`` —
    the tile-grid geometry the local plans key on.  Module level
    (not a method) so the IR auditor's static lane model
    (``repro.analysis.irlint``) derives its expectations from the
    same arithmetic the plans use."""
    return ceil_div(Lb - s + 1, block) * block


def plan_shard_geom(s: int, Lb: int, block: int,
                    ndev: int) -> Tuple[int, int, int]:
    """Window-count geometry of a sharded bucket-``Lb`` sweep:
    ``(n_pad, per, n_sh)`` where ``n_pad`` is the tile grid's own
    padded window count, ``per`` the per-device shard (rounded up to a
    multiple of ``block`` so shards stay MXU-aligned), and
    ``n_sh = per * ndev`` the mesh-wide padded count."""
    n_pad = plan_pad_geom(s, Lb, block)
    per = ceil_div(n_pad // block, ndev) * block
    return n_pad, per, per * ndev


def plan_pan_row_geom(ladder, Lb: int, block: int,
                      ndev: int) -> Tuple[int, int]:
    """Query-row geometry of a pan sweep: ``(n_pad, nb_p)`` where
    ``n_pad`` is the base-rung padded window count and ``nb_p`` the
    query block count padded to a device multiple (1 device = no
    padding)."""
    n_pad = plan_pad_geom(ladder[0], Lb, block)
    nb = n_pad // block
    return n_pad, ceil_div(nb, ndev) * ndev


def _bucket_pad(x, Lb: int, rows: Optional[int] = None) -> np.ndarray:
    """Bucket-pad a series (or a (B, L) stack, optionally to ``rows``
    rows) to ``Lb`` columns of f32, filling the pad with PAD_FILL."""
    x = np.asarray(x)
    if x.ndim == 1:
        xp = np.full(Lb, PAD_FILL, np.float32)
        xp[:x.shape[0]] = x
        return xp
    xp = np.full((x.shape[0] if rows is None else rows, Lb),
                 PAD_FILL, np.float32)
    xp[:x.shape[0], :x.shape[1]] = x
    return xp


def _win_norms(win):
    """f32 L2 norm of each window row, computed fresh from the rows —
    the quantized bound pass must not reuse the cumsum-derived norm
    pads (their cancellation error would poison the certified error
    radius; docs/ARCHITECTURE.md)."""
    return jnp.sqrt(jnp.sum(win * win, axis=1))


def _tail_block(eng: TileEngine, q, c0):
    """One candidate block of a tail sweep: the query rows' minima over
    the block at ``c0`` with their candidate ids, then the block's
    column minima over the rows with their query ids."""
    d2, cid = eng.sweep(q, c0)
    return (jnp.min(d2, axis=1), cid[jnp.argmin(d2, axis=1)],
            jnp.min(d2, axis=0), q.ids[jnp.argmin(d2, axis=0)])


def _min_fold(rm, ra):
    """Fold stacked row minima ``rm`` (one row per candidate block or
    device) and their neighbours ``ra`` into the global minimum."""
    sel = jnp.argmin(rm, axis=0)[None]
    return (jnp.take_along_axis(rm, sel, axis=0)[0],
            jnp.take_along_axis(ra, sel, axis=0)[0])


def ring_series_threshold() -> int:
    """Per-device series-length threshold (in windows) above which a
    sharded ``search_batched`` switches from series-parallel layout to
    a ring sweep per series.  Env-overridable so scaling tests can
    exercise both layouts on small inputs."""
    return int(os.environ.get("REPRO_RING_SERIES_THRESHOLD", 4096))


class PlanCache:
    """A shareable cache of compiled plans (the extracted session
    plan-cache, now a first-class object so the serve plane can hand
    every tenant engine the *same* instance).

    Each :class:`DiscordEngine` owns a private unbounded ``PlanCache``
    by default; ``repro.serve.DiscordServer`` shares one across its
    whole engine fleet so bucket-identical tenant specs reuse each
    other's compilations.  Keys are full ``_plan_key`` tuples — the
    ``(backend, znorm, block)`` prefix keeps cross-engine entries
    collision-free (that prefix was designed for exactly this merge;
    see ``DiscordEngine._plan_key``).

    ``budget`` is the memory knob: the maximum number of live compiled
    plans (each entry pins one XLA executable, the dominant per-plan
    host allocation).  Over-budget inserts evict the least recently
    used entry — a hit refreshes recency — and call ``on_evict(key)``
    so owners can drop side state.  ``hits`` / ``misses`` /
    ``evictions`` feed the serve plane's ``ServeStats`` telemetry.
    """

    def __init__(self, budget: Optional[int] = None,
                 on_evict: Optional[Callable] = None):
        if budget is not None and budget < 1:
            raise ValueError(f"budget must be a positive plan count "
                             f"or None (unbounded), got {budget}")
        self._plans: "OrderedDict" = OrderedDict()
        self.budget = budget
        self.on_evict = on_evict
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._plans)

    def __contains__(self, key) -> bool:
        return key in self._plans

    def get(self, key, thunk) -> Tuple[Callable, bool]:
        """The cached plan under ``key``, building via ``thunk()`` on
        a miss.  Returns ``(fn, fresh)`` — ``fresh`` tells the calling
        engine to count a new plan."""
        fn = self._plans.get(key)
        if fn is not None:
            self.hits += 1
            self._plans.move_to_end(key)
            return fn, False
        self.misses += 1
        fn = thunk()
        self._plans[key] = fn
        if self.budget is not None:
            while len(self._plans) > self.budget:
                old, _ = self._plans.popitem(last=False)
                self.evictions += 1
                if self.on_evict is not None:
                    self.on_evict(old)
        return fn, True

    def as_dict(self) -> dict:
        total = self.hits + self.misses
        return {"plans": len(self._plans), "budget": self.budget,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "hit_rate": self.hits / total if total else 0.0}

    def __repr__(self) -> str:
        return (f"PlanCache(plans={len(self._plans)}, "
                f"budget={self.budget}, hits={self.hits}, "
                f"misses={self.misses}, evictions={self.evictions})")


@dataclass
class EngineStats:
    """Session counters (host-side accounting).

    ``traces`` counts jit traces of the engine's compiled plans — the
    compile-once contract is ``traces == plans`` for the session.
    ``tile_lanes`` counts distance lanes swept through the tile
    engine, the blocked analogue of the paper's distance calls: on
    ``pallas`` the profile kinds sweep only the live window blocks,
    as the upper triangle of their tiles where the bucket allows it
    (``_profile_lanes``), elsewhere the bucket's padded square.
    """
    traces: int = 0
    plans: int = 0
    searches: int = 0
    appends: int = 0
    tile_lanes: int = 0

    def as_dict(self) -> dict:
        return {"traces": self.traces, "plans": self.plans,
                "searches": self.searches, "appends": self.appends,
                "tile_lanes": self.tile_lanes}


class DiscordEngine:
    """A discord-search session for one :class:`SearchSpec`.

    Construct from a spec (or spec kwargs), then call ``search`` /
    ``search_batched`` any number of times over series of varying
    length — same-bucket calls reuse compiled plans — or
    ``open_stream`` to maintain a profile incrementally.

        eng = DiscordEngine(SearchSpec(s=128, k=3,
                                       method="matrix_profile"))
        r1 = eng.search(x)            # traces + compiles
        r2 = eng.search(y)            # same bucket: zero new traces
        st = eng.open_stream(history=x)
        st.append(new_points)         # sweeps only the tail tile rows
        print(st.discords())

    Mesh placement: pass an explicit 1-D ``jax.sharding.Mesh`` as
    ``mesh=`` (normalized onto the series axis), or set
    ``SearchSpec(ndev=...)`` for an auto data-mesh over the first
    ``ndev`` local devices (``None`` = all of them).  A ``ring`` spec,
    an explicit mesh, or ``ndev`` makes the session *sharded*: ring
    searches, batched sweeps and stream appends then run mesh-wide,
    plan-cached under ``(kind, s, length-bucket, mesh-shape)``.
    """

    def __init__(self, spec: Optional[SearchSpec] = None, *,
                 mesh=None, plan_cache: Optional[PlanCache] = None,
                 **spec_kwargs):
        if spec is None:
            spec = SearchSpec(**spec_kwargs)
        elif spec_kwargs:
            raise TypeError("pass either a SearchSpec or spec kwargs, "
                            "not both")
        if not isinstance(spec, SearchSpec):
            raise TypeError(f"spec must be a SearchSpec, got "
                            f"{type(spec).__name__}")
        self.spec = spec
        # resolve once at session start so env-var flips mid-session
        # can't split the plan cache across backends
        self.backend = resolve_backend(spec.backend)
        self.stats = EngineStats()
        # private unbounded cache by default; the serve plane passes a
        # shared (budgeted, LRU) instance so tenants co-own plans
        self.plan_cache = (plan_cache if plan_cache is not None
                           else PlanCache())
        self._explicit_mesh = mesh is not None
        self._mesh = None
        if mesh is not None:
            from ..parallel.sharding import as_series_mesh
            self._mesh = as_series_mesh(mesh)
            if (spec.ndev is not None
                    and int(self._mesh.devices.size) != spec.ndev):
                raise ValueError(
                    f"mesh has {int(self._mesh.devices.size)} device(s) "
                    f"but spec.ndev={spec.ndev}")

    def __repr__(self) -> str:
        mesh = (f", ndev={int(self._mesh.devices.size)}"
                if self._mesh is not None else "")
        return (f"DiscordEngine({self.spec}, backend={self.backend}"
                f"{mesh}, plans={self.stats.plans}, "
                f"traces={self.stats.traces})")

    # -- mesh placement ------------------------------------------------
    @property
    def sharded(self) -> bool:
        """True when this session runs the mesh-sharded plan family
        (ring/drag method, explicit mesh, or spec-pinned device
        count)."""
        return (self._explicit_mesh or self.spec.ndev is not None
                or self.spec.method in ("ring", "drag"))

    def _resolve_mesh(self):
        """The session's series mesh (auto data-mesh on first use)."""
        if self._mesh is None:
            from ..parallel.sharding import series_mesh
            self._mesh = series_mesh(self.spec.ndev)
        return self._mesh

    @property
    def ndev(self) -> int:
        """Device count of the sharded plan family (1 when local)."""
        return (int(self._resolve_mesh().devices.size) if self.sharded
                else 1)

    # -- plan cache ----------------------------------------------------
    def _n_pad(self, s: int, Lb: int) -> int:
        """Padded window count of bucket ``Lb`` (tile geometry)."""
        return plan_pad_geom(s, Lb, self.spec.block)

    def _swept_cols(self, s: int, Lb: int, n_true: int) -> int:
        """Candidate windows a profile-family plan sweeps per query row
        for a record of ``n_true`` windows: the mpblock kernel's grid
        stops at the live blocks (``pallas``, Eq. (3)); every other
        path sweeps the bucket's padded width.  A full profile sweeps
        the square of it."""
        if self.backend == "pallas" and self.spec.znorm:
            return ceil_div(n_true, self.spec.block) * self.spec.block
        return self._n_pad(s, Lb)

    def _profile_grid(self, s: int, Lb: int) -> str:
        """Tile grid of a profile-family plan's self-join sweep in
        bucket ``Lb`` (``tiles.self_join_grid``): ``"triangle"``, each
        window pair once, or ``"square"``."""
        block = self.spec.block
        return self_join_grid(self._n_pad(s, Lb) // block, block,
                              self.backend, self.spec.znorm)

    def _profile_lanes(self, s: int, Lb: int, n_true: int) -> int:
        """Tile lanes one full profile of ``n_true`` windows sweeps in
        bucket ``Lb`` (``tiles.self_join_lanes``): the upper triangle
        of the live blocks on the triangle grid; the square of
        :meth:`_swept_cols` elsewhere."""
        block = self.spec.block
        return self_join_lanes(self._n_pad(s, Lb) // block, block,
                               self.backend, self.spec.znorm,
                               ceil_div(n_true, block))

    def _pan_cells(self, s0: int, Lb: int) -> int:
        """Tile cells one local ladder sweep (``PanEngine.profile``)
        covers in bucket ``Lb`` at base window ``s0``: each window
        pair once where the single-length self-join sweeps its
        triangle, else the bucket's padded square
        (``tiles.self_join_lanes`` over every block)."""
        block = self.spec.block
        return self_join_lanes(self._n_pad(s0, Lb) // block, block,
                               self.backend, self.spec.znorm)

    def _plan_key(self, key):
        """Full cache key of a plan: the session-invariant spec prefix
        (``backend``/``znorm``/``block``/``precision`` — everything a
        compiled tile sweep closes over besides the per-kind geometry)
        + the kind's own key.  The prefix is what lets the shared
        cross-tenant cache (``repro.serve.DiscordServer``'s
        ``PlanCache``) merge engine caches without collisions; the
        speckey audit (docs/analysis.md) checks it stays complete."""
        return (self.backend, self.spec.znorm, self.spec.block,
                self.spec.precision) + tuple(key)

    @property
    def _plans(self):
        """This session's view of its (possibly shared) plan cache —
        the mapping the speckey runtime audit inspects."""
        return self.plan_cache._plans

    def _get_plan(self, key, build):
        kind = key[0]
        key = self._plan_key(key)
        fn, fresh = self.plan_cache.get(
            key, lambda: jax.jit(jax.named_scope(kind)(build())))
        if fresh:
            self.stats.plans += 1
        return fn

    def plan(self, kind: str, *geom):
        """The compiled plan of ``kind`` (a :data:`PLAN_KINDS` key) at
        geometry ``geom``, from the session's plan cache.

        The key is ``(kind, *geom)``, with the mesh shape ``(ndev,)``
        appended for sharded kinds.  On a miss the kind's layout
        (``_layout_<layout>``) composes its op body (``_op_<op>``) and
        the traced function bumps ``stats.traces`` once per trace."""
        pk = PLAN_KINDS[kind]
        key = (kind,) + geom
        ndev = 1
        if pk.sharded:
            ndev = int(self._resolve_mesh().devices.size)
            key += ((ndev,),)

        def build():
            body = getattr(self, "_layout_" + pk.layout)(pk.op, geom,
                                                         ndev)

            def fn(*args):
                self.stats.traces += 1        # trace-time side effect
                return body(*args)
            # the compiled program names its parameters as the body does
            fn.__signature__ = inspect.signature(body)
            return fn
        return self._get_plan(key, build)

    # -- per-series op bodies (one per PLAN_KINDS op) ------------------
    def _tiles(self, series_pad, s: int, n_valid) -> TileEngine:
        spec = self.spec
        return TileEngine(series_pad, s, block=spec.block,
                          backend=self.backend, znorm=spec.znorm,
                          n_valid=n_valid)

    def _ladder(self, series_pad, ladder: tuple, n_valid,
                n_pad: Optional[int] = None) -> PanEngine:
        spec = self.spec
        return PanEngine(series_pad, ladder, block=spec.block,
                         backend=self.backend, znorm=spec.znorm,
                         n_valid=n_valid, n_pad=n_pad)

    def _op(self, op: str, geom: tuple):
        """Op ``op``'s per-series body at geometry ``geom``: the
        method ``_op_<op>(*geom, *args)`` with ``geom`` bound."""
        return functools.partial(getattr(self, "_op_" + op), *geom)

    def _op_profile(self, s: int, Lb: int, series_pad, n_valid):
        """(series_pad (Lb,), n_valid) -> (d2 (n_pad,), neighbor)."""
        return self._tiles(series_pad, s, n_valid).profile()

    def _op_tail(self, s: int, Lb: int, Qb: int, series_pad, q0,
                 n_valid):
        """Streaming-append sweep: only the new tail tile rows.

        (series_pad (Lb,), q0, n_valid) ->
            (row_d2 (Qb,), row_ngh, col_d2 (n_pad,), col_ngh)

        Rows are the ``Qb`` (bucketed, masked) windows starting at
        ``q0`` — the appended tail — swept against every candidate
        block.  Row minima are the new windows' exact nnds; column
        minima are each existing window's best distance *to the new
        windows*, which the host folds into the old profile (append-
        only: old nnds can only be superseded, never worsen).
        """
        eng = self._tiles(series_pad, s, n_valid)
        q = eng.query_block(q0 + jnp.arange(Qb, dtype=jnp.int32))
        starts = jnp.arange(eng.nb, dtype=jnp.int32) * eng.block
        rm, ra, cm, ca = lax.map(functools.partial(_tail_block, eng, q),
                                 starts)
        return (*_min_fold(rm, ra), cm.reshape(-1), ca.reshape(-1))

    def _op_pan(self, ladder: tuple, Lb: int, series_pad, n_valid0):
        """(series_pad (Lb,), n_valid0) -> (d2 (R, n_pad), ngh).

        The pan-length ladder sweep (``core/pan.py``): every rung's
        exact profile from one QT-carrying pass — the base rung pays
        full-width dot tiles, each later rung only its extension
        width.  ``n_valid0`` is the true window count at the *base*
        rung; the plan derives every other rung's count from it, so
        one compiled sweep serves the whole bucket (keyed on the
        canonical ladder — the *ladder bucket* — and ``Lb``).
        """
        return self._ladder(series_pad, ladder, n_valid0).profile()

    def _op_pan_tail(self, ladder: tuple, Lb: int, Qb: int, series_pad,
                     q0, n_valid0):
        """Streaming pan append: only the tail rows, at every rung.

        (series_pad (Lb,), q0, n_valid0) ->
            (row_d2 (R, Qb), row_ngh, col_d2 (R, n_pad), col_ngh)

        The tail's base-rung window ids from ``q0`` swept against
        every candidate with the QT carried across rungs exactly like
        the full sweep (``pan.pan_tail_sweep``): an append pays
        base-rung tail tiles plus Δ-wide extensions only.
        """
        spec = self.spec
        return pan_tail_sweep(series_pad, ladder, q0, Qb,
                              block=spec.block, backend=self.backend,
                              znorm=spec.znorm, n_valid=n_valid0)

    def _op_pan_base(self, s0: int, Lb: int, series_pad, n_valid0):
        """(series_pad (Lb,), n_valid0) -> (qt (n_pad, n_pad), d2, ngh).

        Rung 0 of the sequential LB-abandoning schedule: pays the
        full-width base dot tiles once and *returns* the carried QT so
        the ``("pan_step", ...)`` plans can extend it across plan
        invocations (the host decides between steps whether the next
        rung is worth evaluating at all).
        """
        return self._ladder(series_pad, (s0,), n_valid0,
                            self._n_pad(s0, Lb)).carry_rows()

    def _op_pan_step(self, sub_ladder: tuple, Lb: int, n_pad: int,
                     series_pad, qt, n_valid_from):
        """(series_pad, qt (n_pad, n_pad), n_valid_from) ->
        (qt', d2, ngh).

        One evaluated step of the sequential schedule: extends the
        carried QT from ``sub_ladder[0]`` (the last evaluated rung)
        through every intermediate — possibly skipped — width to
        ``sub_ladder[-1]``, accumulating the extension dots in exactly
        the full ladder sweep's order (so evaluated profiles match it
        whether or not the rungs in between were evaluated), and
        applies Eq. (3) only at the final rung.  ``n_valid_from`` is
        the window count at ``sub_ladder[0]``; ``n_pad`` is the *base*
        rung's grid (the carried QT's geometry), not this sub-ladder's.
        """
        return self._ladder(series_pad, sub_ladder, n_valid_from,
                            n_pad).carry_rows(qt)

    def _rows_pan(self, ladder: tuple, Lb: int):
        """The ladder sweep over listed query blocks
        (``PanEngine.rows``), assembled into ``(R, n_pad)`` profiles.
        No raw-mode guard: the pan body computes raw distances
        natively from the carried QT."""
        n_pad = self._n_pad(ladder[0], Lb)
        R = len(ladder)

        def rows(series_pad, n_valid0, starts):
            return self._ladder(series_pad, ladder, n_valid0).rows(starts)

        def assemble(d2, arg):
            return (d2.transpose(1, 0, 2).reshape(R, -1)[:, :n_pad],
                    arg.transpose(1, 0, 2).reshape(R, -1)[:, :n_pad])
        return rows, assemble, n_pad

    # -- quantized-sweep ops (bf16/int8 bound + f32 refine) ------------
    def _qstats(self, win):
        """Fresh f32 norms (and int8 scales) of a block of windows,
        as :meth:`_qsweep_bracket` takes them."""
        return (_win_norms(win), quant_scales(win)
                if self.spec.precision == "int8" else None)

    def _qsweep_bracket(self, s: int, eng: TileEngine, q, c, qstats,
                        cstats):
        """Certified f32 bracket ``(d2_lo, d2_hi)`` of the exact-f32
        tile d² for one query block vs one candidate block.

        The bound backend returns reduced-precision dots with
        ``|dots_low - dots_f32| <= rad``
        (``kernels.registry.bound_dot_radius``); d² is monotone
        *decreasing* in the dots through Eq. (3) (σ > 0) and through
        the raw-mode inversion (its clamps are monotone), and f32
        evaluation of the monotone formula pipeline is itself weakly
        monotone — so evaluating the exact pipeline at ``dots ± rad``
        brackets the f32 tile value, not just the real-valued
        distance (full derivation: docs/ARCHITECTURE.md)."""
        spec, prec = self.spec, self.spec.precision
        (nq, sq), (nc, sc) = qstats, cstats      # scales None off int8
        dots = get_bound_backend(self.backend)(q.win, c.win,
                                               precision=prec,
                                               sq=sq, sc=sc)
        rad = bound_dot_radius(prec, nq, nc, s, sq, sc)
        bad = exclusion_mask(q.ids, c.ids, s, eng.n)

        def d2_of(dd):
            d2 = znorm_d2_formula(dd, s, q.mu, q.sig, c.mu, c.sig)
            d2 = jnp.where(bad, jnp.inf, d2)
            if not spec.znorm:
                d2 = eng._raw_d2(d2, q.ids, c.ids)
            return d2
        return d2_of(dots + rad), d2_of(dots - rad)

    def _rows_qsweep(self, s: int, Lb: int):
        """Reduced-precision bound pass over listed query blocks:
        ``(rows, assemble, n_pad)`` where ``rows(series_pad, n_valid,
        starts) -> (lo, hi)`` brackets, per listed block, each row's
        profile value (``lo <= exact-f32-profile d² <= hi``, shape
        ``(len(starts), block)``) and ``assemble`` flattens them."""
        def rows(series_pad, n_valid, starts):
            eng = self._tiles(series_pad, s, n_valid)
            cand = eng.all_windows()
            cstats = self._qstats(cand.win)

            def one_block(b0):
                q = eng.contiguous_block(b0)
                lo, hi = self._qsweep_bracket(s, eng, q, cand,
                                              self._qstats(q.win), cstats)
                return jnp.min(lo, axis=1), jnp.min(hi, axis=1)
            return lax.map(one_block, starts)

        def assemble(lo, hi):
            return lo.reshape(-1), hi.reshape(-1)
        return rows, assemble, self._n_pad(s, Lb)

    def _op_qsweep(self, s: int, Lb: int, series_pad, n_valid):
        """(series_pad (Lb,), n_valid) -> (lo_d2 (n_pad,), hi_d2).

        The quantized bound pass of the two-phase search
        (docs/cps.md): per window a certified bracket of the exact
        f32 profile value.  The host prunes whole query blocks whose
        upper bounds cannot reach the top-k and refines the rest
        through ``("qsweep_refine", ...)``.
        """
        block = self.spec.block
        rows, assemble, n_pad = self._rows_qsweep(s, Lb)
        starts = jnp.arange(n_pad // block, dtype=jnp.int32) * block
        return assemble(*rows(series_pad, n_valid, starts))

    def _op_qsweep_refine(self, s: int, Lb: int, series_pad, b2,
                          n_valid):
        """(series_pad (Lb,), b2 (2,), n_valid) ->
            (d2 (2, block), ngh).

        Exact f32 re-sweep of a *pair* of query blocks against every
        candidate — ``TileEngine.block_rows`` of listed blocks, which
        computes every pair with the tile and operand roles that the
        ``("profile", ...)`` plan's sweep of all blocks uses (on
        ``pallas`` the triangle's: a pair with a lower block as a
        column of that block's tile) and folds them in the same order,
        so refined rows are bit-identical to a full profile sweep's.
        The block starts are traced operands, so one compiled plan
        refines any pair: zero retraces across the escalation loop.
        The fixed trip count
        of 2 is load-bearing: XLA unrolls trip-count-1 loops into the
        enclosing computation and re-fuses the math into ulp-different
        results (observed in raw mode),
        while any preserved loop compiles the shared scan body
        identically — callers duplicate a start to pad odd refinement
        sets, and buckets with fewer than two blocks take the exact
        plans outright.
        """
        return self._tiles(series_pad, s, n_valid).block_rows(b2)

    def _op_qsweep_tail(self, s: int, Lb: int, Qb: int, series_pad, q0,
                        n_valid):
        """Quantized streaming-append bound pass.

        (series_pad (Lb,), q0, n_valid) ->
            (row_lo (nb, Qb), row_hi (nb, Qb), col_lo (n_pad,))

        Per candidate block ``b``: ``row_lo[b]`` / ``row_hi[b]``
        bracket each tail row's min over that block's candidates, and
        ``col_lo`` lower-bounds each existing window's best distance
        to the new tail windows.  The host
        (``DiscordStream._qtail_fold``) refines only the candidate
        blocks that can matter, through
        ``("qsweep_tail_refine", ...)``.
        """
        eng = self._tiles(series_pad, s, n_valid)
        q = eng.query_block(q0 + jnp.arange(Qb, dtype=jnp.int32))
        qstats = self._qstats(q.win)
        starts = jnp.arange(self._n_pad(s, Lb) // eng.block,
                            dtype=jnp.int32) * eng.block

        def one(c0):
            c = eng.contiguous_block(c0)
            lo, hi = self._qsweep_bracket(s, eng, q, c, qstats,
                                          self._qstats(c.win))
            return (jnp.min(lo, axis=1), jnp.min(hi, axis=1),
                    jnp.min(lo, axis=0))

        rlo, rhi, clo = lax.map(one, starts)
        return rlo, rhi, clo.reshape(-1)

    def _op_qsweep_tail_refine(self, s: int, Lb: int, Qb: int,
                               series_pad, q0, n_valid, c2):
        """(series_pad (Lb,), q0, n_valid, c2 (2,)) ->
            (rm (2, Qb), ra, cm (2, block), ca).

        Exact f32 tail sweep of the ``Qb`` tail queries against a
        *pair* of candidate blocks — the ``("tail", ...)`` plan's
        per-block ``lax.map`` body (``_tail_block``) re-run verbatim
        (same shapes, same reduction order), so refined tail rows and
        columns are bit-identical to the full exact tail sweep's.  The
        traced pair of starts keeps one compiled plan serving every
        refinement; the fixed trip count of 2 preserves the scan (see
        ``_op_qsweep_refine`` — XLA unrolls trip-count-1 loops and
        drifts by ulps).
        """
        eng = self._tiles(series_pad, s, n_valid)
        q = eng.query_block(q0 + jnp.arange(Qb, dtype=jnp.int32))
        return lax.map(functools.partial(_tail_block, eng, q), c2)

    # -- layouts (one per PLAN_KINDS layout) ---------------------------
    def _shard(self, body, in_dims, out_dims):
        """``jax.shard_map`` of ``body`` over the session's series
        mesh.  Each operand and output is named by the dimension the
        mesh axis shards (``0`` leading, ``1`` second) or ``None``
        (replicated)."""
        from jax.sharding import PartitionSpec as P

        from .distributed import AXIS

        def spec(d):
            return P() if d is None else P(*([None] * d), AXIS)
        return jax.shard_map(
            body, mesh=self._resolve_mesh(),
            in_specs=tuple(spec(d) for d in in_dims),
            out_specs=tuple(spec(d) for d in out_dims),
            check_vma=False)

    def _layout_local(self, op: str, geom: tuple, ndev: int):
        return self._op(op, geom)

    def _layout_mb(self, op: str, geom: tuple, ndev: int):
        """Cross-tenant micro-batch, geometry ``(*op_geom, B)``:
        ``(stack (B, Lb), *scalars (B,))``, each lane the local op
        body with that tenant's own scalars under ``lax.map`` — never
        vmap — so every lane is bit-identical to the tenant's own
        local plan (the serve plane's coalesced dispatch)."""
        body = self._op(op, geom[:-1])

        def run(stack, *lanes):
            return lax.map(lambda t: body(*t), (stack, *lanes))
        return run

    def _layout_batched(self, op: str, geom: tuple, ndev: int):
        """Batched sweep, geometry ``(s_or_ladder, B, Lb)``:
        ``(stack (B, Lb), n_valid)`` with one valid count for the
        whole batch, vmapped into one MXU sweep on ``xla`` and
        scanned elsewhere (pallas_call / pure_callback don't batch)."""
        body = self._op(op, (geom[0], geom[2]))
        be = self.backend

        def run(stack, n_valid):
            def one(x):
                return body(x, n_valid)
            if be == "xla":
                return jax.vmap(one)(stack)
            return lax.map(one, stack)
        return run

    def _layout_series_sharded(self, op: str, geom: tuple, ndev: int):
        """Series-parallel level of the two-level batched layout,
        geometry ``(s_or_ladder, Bp, Lb)``: ``(stack (Bp, Lb),
        n_valid (1,))``, the batch sharded across the mesh and each
        device running :meth:`_layout_batched` over its sub-batch."""
        batched = self._layout_batched(op, geom, ndev)
        return self._shard(lambda sub, n_valid: batched(sub, n_valid[0]),
                           (0, None), (0, 0))

    def _layout_row_sharded(self, op: str, geom: tuple, ndev: int):
        """Query blocks sharded across the mesh, candidates replicated
        (``_rows_<op>``): ``(series_pad (Lb,), n_valid)``.  The block
        count is padded to a device multiple; ``assemble`` turns the
        per-block rows back into the op's outputs."""
        rows, assemble, n_pad = getattr(self, "_rows_" + op)(*geom)
        block = self.spec.block
        nb_p = ceil_div(n_pad // block, ndev) * ndev
        sweep = self._shard(lambda st, sp, nv: rows(sp, nv[0], st),
                            (0, None, None), (0, 0))

        def run(series_pad, n_valid):
            starts = jnp.arange(nb_p, dtype=jnp.int32) * block
            return assemble(*sweep(starts, series_pad,
                                   jnp.full((1,), n_valid, jnp.int32)))
        return run

    def _layout_ring(self, op: str, geom: tuple, ndev: int):
        """(series_pad (Lb,), n_valid) -> (d2 (n_sh,), neighbor).

        The ring matrix profile: every device owns one block-aligned
        shard of query windows; candidate shards orbit the ring via
        ``ppermute`` (the hop body shared with ``core/distributed``)
        while each device min-folds the visiting shard into its
        queries.  Masking is carried entirely by the window ids, so
        one compiled plan serves every series in the bucket.
        """
        from .distributed import _ring_mp_shard
        self._require_znorm("the ring plan")
        s, Lb = geom
        _, per, n_sh = self._shard_geom(s, Lb, ndev)
        sweep = self._shard(
            functools.partial(_ring_mp_shard, s=s, n=n_sh, ndev=ndev,
                              backend=self.backend,
                              block=self.spec.block),
            (0, 0, 0, 0), (0, 0))

        def run(series_pad, n_valid):
            return sweep(*self._tiles(series_pad, s,
                                      n_valid).window_set(n_sh))
        return run

    def _layout_tail_ring(self, op: str, geom: tuple, ndev: int):
        """The tail op with the *candidates* sharded: each device
        sweeps the tail queries against its own candidate shard and
        the per-shard row minima are min-folded globally (every
        candidate has one owning shard, so columns need no fold)."""
        from .distributed import _tile_d2
        self._require_znorm("the sharded tail plan")
        s, Lb, Qb = geom
        n_pad, per, n_sh = self._shard_geom(s, Lb, ndev)
        be = self.backend

        def shard_body(qwin, qmu, qsig, qid, cwin, cmu, csig, cid):
            d2 = _tile_d2(qwin, qmu, qsig, qid,
                          cwin, cmu, csig, cid, s, n_sh, be)
            return (jnp.min(d2, axis=1)[None],
                    cid[jnp.argmin(d2, axis=1)][None],
                    jnp.min(d2, axis=0),
                    qid[jnp.argmin(d2, axis=0)])

        sweep = self._shard(shard_body, (None,) * 4 + (0,) * 4,
                            (0, 0, 0, 0))

        def run(series_pad, q0, n_valid):
            eng = self._tiles(series_pad, s, n_valid)
            q = eng.query_block(q0 + jnp.arange(Qb, dtype=jnp.int32))
            rm, ra, cm, ca = sweep(
                q.win, q.mu, q.sig, q.ids,
                *self._sharded_blocks(eng, n_pad, n_sh))
            return (*_min_fold(rm, ra), cm, ca)
        return run

    def _layout_pan_tail_ring(self, op: str, geom: tuple, ndev: int):
        """The pan tail op with the *candidates* sharded: each device
        carries the QT for the tail queries against only the candidate
        id range it owns; row minima are min-folded globally and the
        column slices concatenate back to the full grid.  No znorm
        guard: the pan body computes raw distances natively."""
        from .distributed import AXIS
        ladder, Lb, Qb = geom
        n_pad, per, n_sh = self._shard_geom(ladder[0], Lb, ndev)

        def shard_body(series_pad, q0, n_valid0):
            dev = lax.axis_index(AXIS)
            peng = self._ladder(series_pad, ladder, n_valid0[0], n_sh)
            qids = q0[0] + jnp.arange(Qb, dtype=jnp.int32)
            rd2, rng, cd2, cng = peng.tail(qids, dev * per, per)
            return rd2[None], rng[None], cd2, cng

        sweep = self._shard(shard_body, (None, None, None),
                            (0, 0, 1, 1))

        def run(series_pad, q0, n_valid0):
            rm, ra, cm, ca = sweep(
                series_pad, jnp.full((1,), q0, jnp.int32),
                jnp.full((1,), n_valid0, jnp.int32))
            return (*_min_fold(rm, ra), cm[:, :n_pad], ca[:, :n_pad])
        return run

    # -- mesh geometry -------------------------------------------------
    def _shard_geom(self, s: int, Lb: int, ndev: int):
        """Window-count geometry of a sharded bucket-``Lb`` sweep
        (:func:`plan_shard_geom`): ``(n_pad, per, n_sh)``."""
        return plan_shard_geom(s, Lb, self.spec.block, ndev)

    def _sharded_blocks(self, eng: TileEngine, n_pad: int, n_sh: int):
        """All (bucket-padded) windows of ``eng``, further padded to
        the mesh-wide count ``n_sh`` with masked lanes (ids -1) so the
        per-device shards split evenly and stay block-aligned."""
        blk = eng.all_windows()          # padding ids already masked
        pad = n_sh - n_pad
        return (jnp.pad(blk.win, ((0, pad), (0, 0))),
                jnp.pad(blk.mu, (0, pad)),
                jnp.pad(blk.sig, (0, pad), constant_values=1.0),
                jnp.pad(blk.ids, (0, pad), constant_values=-1))

    def _pan_row_geom(self, ladder: tuple, Lb: int, ndev: int):
        """Query-row geometry of a pan sweep
        (:func:`plan_pan_row_geom`): ``(n_pad, nb_p)``."""
        return plan_pan_row_geom(ladder, Lb, self.spec.block, ndev)

    # -- searches ------------------------------------------------------
    def search(self, series, **kw
               ) -> Union[DiscordResult, List[DiscordResult]]:
        """Top-k discords of a 1-D series under this engine's spec.

        Multi-window specs return one ``DiscordResult`` per window
        length (all lengths reuse this session's plan cache).  Extra
        kwargs are forwarded to the non-plan methods (e.g. hst_jax's
        ``batch=``); the plan-cached profile path takes none.
        """
        spec = self.spec
        if spec.multi_window:
            if kw:
                raise TypeError("multi-window search takes no extra "
                                f"kwargs, got {sorted(kw)}")
            # all lengths share one pan-length ladder sweep; results
            # come back in the spec's own window order
            pan = self.search_pan(series)
            by_s = {r.s: r for r in pan.per_rung}
            return [by_s[s] for s in spec.windows]
        if spec.method == "matrix_profile":
            if kw:
                raise TypeError("matrix_profile search is fully "
                                "described by the spec and takes no "
                                f"extra kwargs, got {sorted(kw)}")
            if spec.precision != "f32":
                return self._search_qsweep(series, spec.s)
            return self._search_profile(series, spec.s)
        if spec.method == "ring":
            if kw:
                raise TypeError("ring search is fully described by "
                                "the spec and mesh placement and takes "
                                f"no extra kwargs, got {sorted(kw)}")
            if spec.precision != "f32":
                return self._search_qsweep_ring(series)
            res = self._search_ring(series)
            self.stats.searches += 1
            return res
        return self._dispatch(series, **kw)

    def _search_profile(self, series, s: int) -> DiscordResult:
        """Bucketed, plan-cached exact-profile search, in the spans of
        the module docstring."""
        t0 = time.perf_counter()
        with TraceAnnotation("engine.search", kind="profile",
                             search=self.stats.searches) as span:
            with TraceAnnotation("engine.prepare"):
                x = np.asarray(series, np.float64).ravel()
                L = x.shape[0]
                if L < s + 1:
                    raise ValueError(
                        f"series of {L} points is too short for window "
                        f"spec.s={s} (need at least s + 1 points)")
                n_true = L - s + 1
                Lb = length_bucket(L)
                grid = self._profile_grid(s, Lb)
                span.set_metadata(bucket=Lb, n=n_true, grid=grid)
                xp = _bucket_pad(x, Lb)
                plan = self.plan("profile", s, Lb)
            with TraceAnnotation("engine.dispatch"):
                d2, _arg = plan(jnp.asarray(xp), np.int32(n_true))
            with TraceAnnotation("engine.wait"):
                d2.block_until_ready()
            with TraceAnnotation("engine.fetch"):
                d2 = np.asarray(d2, np.float64)[:n_true]
            with TraceAnnotation("engine.select"):
                prof = np.sqrt(d2)
                pos, vals = topk_nonoverlapping(
                    np.where(np.isfinite(prof), prof, -np.inf),
                    self.spec.k, s)
                lanes = self._profile_lanes(s, Lb, n_true)
                self.stats.searches += 1
                self.stats.tile_lanes += lanes
                return DiscordResult(
                    positions=pos, nnds=vals,
                    calls=lanes,          # swept tile lanes (docs/cps.md)
                    n=n_true, s=s, method=f"scamp[{self.backend}]",
                    runtime_s=time.perf_counter() - t0, tile_lanes=lanes,
                    extra={"backend": self.backend, "bucket": Lb,
                           "tile_lanes": lanes, "grid": grid,
                           "znorm": self.spec.znorm})

    def _qsweep_select(self, lo_d2, hi_d2, n_true: int, s: int,
                       refine):
        """Host-side escalation select of the two-phase quantized
        search: certified per-window brackets in, *exact* top-k out.

        ``refine_many(bs)`` runs the f32 refinement plan over the
        listed query blocks (the caller pairs them up for the fixed-
        trip-count plan) and yields ``(b, d2_row)`` pairs whose rows
        are bit-identical to the full ``("profile", ...)`` sweep's.

        Soundness/exactness: unrefined rows score at their certified
        upper bound (``+inf`` when the bound overflowed — forced
        refinement), refined rows at their exact value, so the greedy
        composed profile is pointwise >= the exact one and equal on
        refined rows; once every greedy pick is refined, first-index
        ``np.argmax`` induction makes the pick sequence identical to
        running ``topk_nonoverlapping`` on the fully exact profile
        (derivation: docs/ARCHITECTURE.md).  Returns
        ``(pos, vals, n_refined_blocks, nb_live)``.
        """
        k, block = self.spec.k, self.spec.block
        refine_many = refine
        lo = np.asarray(lo_d2, np.float64)[:n_true]
        hi = np.asarray(hi_d2, np.float64)[:n_true]
        # lower-bound profile: nonfinite rows can never seed the
        # threshold; upper-bound profile: nonfinite rows must refine
        lb = np.where(np.isfinite(lo),
                      np.sqrt(np.maximum(lo, 0.0)), -np.inf)
        ub = np.where(np.isfinite(hi),
                      np.sqrt(np.maximum(hi, 0.0)), np.inf)
        nb_live = ceil_div(n_true, block)
        refined = np.zeros(nb_live, bool)
        score = ub.copy()

        def do_refine(bs):
            bs = [b for b in bs if not refined[b]]
            for b, d2b in refine_many(bs):
                j0 = b * block
                n_rows = min(block, n_true - j0)
                prof = np.sqrt(np.asarray(d2b, np.float64)[:n_rows])
                score[j0:j0 + n_rows] = np.where(
                    np.isfinite(prof), prof, -np.inf)
                refined[b] = True

        # seed round: the k-th greedy pick on the lower-bound profile
        # is a certified threshold — every block whose upper bounds
        # all fall below it can never reach the top-k
        _, svals = topk_nonoverlapping(lb, k, s)
        thr = svals[k - 1] if len(svals) >= k else -np.inf
        do_refine([b for b in range(nb_live)
                   if np.any(ub[b * block:
                                min(b * block + block, n_true)]
                             >= thr)])

        # escalation loop: refine any block holding an unrefined
        # greedy pick until the whole pick sequence is exact
        while True:
            pos, vals = topk_nonoverlapping(score, k, s)
            need = sorted({int(p) // block for p in pos
                           if not refined[int(p) // block]})
            if not need:
                return pos, vals, int(refined.sum()), nb_live
            do_refine(need)

    def _qsweep_exec(self, series, s: int, bound_plan_lanes):
        """Shared driver of the local and ring quantized searches:
        bucket/pad, bound pass via ``bound_plan_lanes(s, Lb) ->
        (plan, bound_lanes)``, escalation select, hybrid accounting.
        Returns everything the result constructors need — or ``None``
        when the bucket holds fewer than two query blocks, where
        pruning is vacuous and the trip-count-2 refinement plan could
        not match the (unrolled) exact sweep; callers fall back to
        the exact f32 search (trivially bit-identical)."""
        spec = self.spec
        x = np.asarray(series, np.float64).ravel()
        L = x.shape[0]
        if L < s + 1:
            raise ValueError(f"series of {L} points is too short for "
                             f"window spec.s={s} (need at least "
                             f"s + 1 points)")
        n_true = L - s + 1
        Lb = length_bucket(L)
        n_pad = self._n_pad(s, Lb)
        if n_pad // spec.block < 2:
            return None
        xp = jnp.asarray(_bucket_pad(x, Lb))
        nv = np.int32(n_true)
        plan, bound_lanes = bound_plan_lanes(s, Lb)
        lo, hi = plan(xp, nv)
        rplan = self.plan("qsweep_refine", s, Lb)
        ncalls = 0

        def refine_many(bs):
            nonlocal ncalls
            for i in range(0, len(bs), 2):
                pair = bs[i:i + 2]
                padded = (pair if len(pair) == 2
                          else (pair[0], pair[0]))
                b2 = jnp.asarray(np.array(padded, np.int32)
                                 * spec.block)
                d2p, _ngh = rplan(xp, b2, nv)
                ncalls += 1
                d2p = np.asarray(d2p, np.float64)
                for lane, b in enumerate(pair):
                    yield b, d2p[lane]

        pos, vals, n_ref, nb_live = self._qsweep_select(
            lo, hi, n_true, s, refine_many)
        # honest lanes: every executed refinement call sweeps a pair
        # of (block x n_pad) tiles, duplicate padding included
        refine_lanes = (ncalls * 2 * spec.block
                        * self._swept_cols(s, Lb, n_true))
        self.stats.tile_lanes += bound_lanes + refine_lanes
        prune = 1.0 - (n_ref / nb_live if nb_live else 0.0)
        extra = {"backend": self.backend, "bucket": Lb,
                 "precision": spec.precision,
                 "tile_lanes": bound_lanes,
                 "bound_lanes": bound_lanes,
                 "refine_calls": refine_lanes,
                 "refined_blocks": n_ref, "blocks": nb_live,
                 "prune_ratio": prune, "znorm": spec.znorm}
        return pos, vals, bound_lanes, refine_lanes, n_true, extra

    def _search_qsweep(self, series, s: int) -> DiscordResult:
        """Quantized two-phase search (docs/cps.md): reduced-precision
        bound pass over every pair, host-side certified prune, f32
        refinement of the surviving query blocks only.  Positions and
        nnds are bit-identical to ``_search_profile``'s; only the
        lane accounting moves (``calls = tile_lanes +
        refine_calls``)."""
        t0 = time.perf_counter()

        def bound_plan_lanes(s_, Lb):
            return (self.plan("qsweep", s_, Lb),
                    self._n_pad(s_, Lb) ** 2)

        with TraceAnnotation("engine.search", kind="qsweep"):
            out = self._qsweep_exec(series, s, bound_plan_lanes)
            if out is None:      # single-block bucket: exact outright
                return self._search_profile(series, s)
            pos, vals, bl, rl, n_true, extra = out
            self.stats.searches += 1
            return DiscordResult(
                positions=pos, nnds=vals, calls=bl + rl, n=n_true, s=s,
                method=f"qsweep[{self.spec.precision}|{self.backend}]",
                runtime_s=time.perf_counter() - t0, tile_lanes=bl,
                extra=extra)

    def _ring_exec(self, s: int, Lb: int, series_pad, n_valid):
        """One ring-plan invocation — the single source of the mesh
        lane formula (``per^2`` per device per hop, ``ndev`` hops,
        ``ndev`` devices).  Returns ``(d2, arg, lanes, ndev)``; the
        caller owns the stats fold."""
        ndev = int(self._resolve_mesh().devices.size)
        d2, arg = self.plan("ring", s, Lb)(series_pad, n_valid)
        _, per, n_sh = self._shard_geom(s, Lb, ndev)
        return d2, arg, n_sh * per * ndev, ndev

    def _ring_d2(self, series, s: int, span=None):
        """Mesh-sharded exact squared nnd and neighbour of every true
        window, through the plan cache, in the ``engine.prepare`` to
        ``engine.fetch`` spans of the module docstring; ``span``, the
        caller's ``engine.search``, is given ``bucket``, ``n`` and
        ``ndev``.  Returns ``(d2, ngh, lanes, Lb, ndev, n_true)``, the
        arrays on the host (f64 and i64)."""
        with TraceAnnotation("engine.prepare"):
            x = np.asarray(series, np.float64).ravel()
            L = x.shape[0]
            if L < s + 1:
                raise ValueError(f"series of {L} points is too short for "
                                 f"window spec.s={s} (need at least "
                                 f"s + 1 points)")
            n_true = L - s + 1
            Lb = length_bucket(L)
            xp = _bucket_pad(x, Lb)
        with TraceAnnotation("engine.dispatch"):
            d2, arg, lanes, ndev = self._ring_exec(s, Lb, jnp.asarray(xp),
                                                   np.int32(n_true))
        if span is not None:
            span.set_metadata(bucket=Lb, n=n_true, ndev=ndev)
        with TraceAnnotation("engine.wait"):
            jax.block_until_ready((d2, arg))
        with TraceAnnotation("engine.fetch"):
            d2 = np.asarray(d2, np.float64)[:n_true]
            ngh = np.asarray(arg, np.int64)[:n_true]
        self.stats.tile_lanes += lanes
        return d2, ngh, lanes, Lb, ndev, n_true

    def _ring_profile(self, series, s: int):
        """:meth:`_ring_d2` with the nnd itself: ``(prof, ngh, lanes,
        Lb, ndev, n_true)``."""
        d2, *rest = self._ring_d2(series, s)
        return (np.sqrt(d2), *rest)

    def _search_ring(self, series) -> DiscordResult:
        """Top-k discords via the mesh-sharded ring plan, in the spans
        of the module docstring.  Callers own the ``stats.searches``
        bump (one per API call, so a batched ring-per-series layout
        still counts as one search)."""
        t0 = time.perf_counter()
        s = self.spec.s
        with TraceAnnotation("engine.search", kind="ring",
                             search=self.stats.searches) as span:
            d2, _ngh, lanes, Lb, ndev, n_true = self._ring_d2(
                series, s, span)
            with TraceAnnotation("engine.select"):
                prof = np.sqrt(d2)
                pos, vals = topk_nonoverlapping(
                    np.where(np.isfinite(prof), prof, -np.inf),
                    self.spec.k, s)
                return DiscordResult(
                    positions=pos, nnds=vals, calls=lanes, n=n_true, s=s,
                    method=f"ring_mp[{ndev}dev|{self.backend}]",
                    runtime_s=time.perf_counter() - t0, tile_lanes=lanes,
                    extra={"backend": self.backend, "bucket": Lb,
                           "ndev": ndev, "tile_lanes": lanes,
                           "znorm": self.spec.znorm})

    def _search_qsweep_ring(self, series) -> DiscordResult:
        """Quantized ring search: mesh-sharded bound pass
        (``("qsweep_ring", ...)``) + local f32 refinement.  Bit-
        identical positions/nnds to the refinement plan's local
        profile on every mesh shape (refined values never cross the
        mesh); bumps ``stats.searches`` itself."""
        t0 = time.perf_counter()
        spec = self.spec
        s = spec.s
        ndev = int(self._resolve_mesh().devices.size)

        def bound_plan_lanes(s_, Lb):
            n_pad = self._n_pad(s_, Lb)
            q_sh = (ceil_div(n_pad // spec.block, ndev) * ndev
                    * spec.block)
            return self.plan("qsweep_ring", s_, Lb), q_sh * n_pad

        out = self._qsweep_exec(series, s, bound_plan_lanes)
        if out is None:      # single-block bucket: exact outright
            res = self._search_ring(series)
            self.stats.searches += 1
            return res
        pos, vals, bl, rl, n_true, extra = out
        extra["ndev"] = ndev
        self.stats.searches += 1
        return DiscordResult(
            positions=pos, nnds=vals, calls=bl + rl, n=n_true, s=s,
            method=(f"qsweep_ring[{spec.precision}|{ndev}dev|"
                    f"{self.backend}]"),
            runtime_s=time.perf_counter() - t0, tile_lanes=bl,
            extra=extra)

    # -- pan-length (window-ladder) searches ---------------------------
    def _pan_finish(self, x, lad, d2s, *, lanes, cells, Lb, ndev,
                    method, extra, k=None, rung_calls=None,
                    rung_indices=None, ladder=None,
                    calls=None) -> PanResult:
        """Shared host-side pan post-processing: per-rung top-k, the
        cross-length LB self-check (``pan.ladder_lb_margin``) and the
        global ``d/sqrt(s)``-normalized ranking.  ``d2s`` is the
        (R, >= n_r) squared profile stack for the rungs in ``lad``
        (the evaluated sub-ladder on the LB schedule); ``cells`` the
        swept (rows x cols) grid whose ``pan_rung_shares`` the
        per-rung ``calls`` default to.  Overrides: ``rung_calls``
        (per-rung lanes that are not the one-sweep shares — the LB
        schedule's step lanes, the stream's accumulated shares),
        ``rung_indices`` (each rung's position in the *full* ladder),
        ``ladder`` (the full ladder for the result when ``lad`` is a
        sub-ladder), ``calls`` (result total when it exceeds
        ``lanes``, e.g. + refine calls).  Runtime fields are stamped
        by the caller (``_stamp_pan_runtime``)."""
        spec = self.spec
        k = spec.k if k is None else int(k)
        full_lad = lad if ladder is None else ladder
        if rung_calls is None:
            rung_calls = pan_rung_shares(lad, 1, cells)
        L = x.shape[0]
        per_rung, profiles, d2_list = [], [], []
        for r, s_r in enumerate(lad):
            n_r = L - s_r + 1
            d2_r = d2s[r, :n_r]
            prof = np.sqrt(np.maximum(d2_r, 0.0))
            pos, vals = topk_nonoverlapping(
                np.where(np.isfinite(prof), prof, -np.inf), k, s_r)
            per_rung.append(DiscordResult(
                positions=pos, nnds=vals, calls=rung_calls[r], n=n_r,
                s=s_r, method=method, tile_lanes=rung_calls[r],
                extra={"backend": self.backend, "bucket": Lb,
                       "ladder": full_lad,
                       "rung": r if rung_indices is None
                       else rung_indices[r],
                       "pan_tile_lanes": lanes,
                       "znorm": spec.znorm}))
            profiles.append(prof)
            d2_list.append(d2_r)
        lb_margin = ladder_lb_margin(x, lad, d2_list, spec.znorm)
        lb_ok = bool(lb_margin >= -3e-3)
        for rr in per_rung:
            rr.extra["lb_ok"] = lb_ok
        return PanResult(
            per_rung=per_rung,
            global_topk=global_normalized_topk(profiles, lad, k),
            ladder=full_lad, n=L - full_lad[0] + 1,
            calls=lanes if calls is None else calls,
            tile_lanes=lanes, method=method,
            lb_margin=float(lb_margin),
            extra={"backend": self.backend, "bucket": Lb, "ndev": ndev,
                   "znorm": spec.znorm, "lb_ok": lb_ok, **extra})

    @staticmethod
    def _stamp_pan_runtime(pan: PanResult, elapsed: float) -> PanResult:
        """Honest per-ladder wall clock on the result and every rung."""
        pan.runtime_s = elapsed
        for rr in pan.per_rung:
            rr.runtime_s = elapsed
            rr.extra["per_rung_s"] = elapsed / max(len(pan.per_rung), 1)
        return pan

    def search_pan(self, series, *, ladder=None,
                   schedule: str = "ladder") -> PanResult:
        """Exact discords at every rung of a window-length ladder from
        **one** shared tile sweep, plus the global length-normalized
        (``d / sqrt(s)``) top-k across rungs (docs/pan.md).

        ``ladder`` defaults to the spec's window tuple; any iterable
        of lengths is accepted and canonicalized (sorted, deduped) —
        the canonical ladder is the plan-cache key, so a second search
        over the same ladder and length bucket adds zero new traces.
        Runs on local sessions and (query-block-sharded) on meshed
        ones, in both znorm modes, on every tile backend.

        ``schedule`` picks between the two plan families:

        * ``"ladder"`` (default) — one all-rung sweep; every
          ``per_rung`` entry matches an independent single-length
          ``matrix_profile`` search at that rung (same positions, same
          nnds up to summation order).
        * ``"lb_abandon"`` (alias ``"lb"``) — sequential rungs with
          cross-length-bracket skipping, for when only
          ``global_normalized_topk`` matters: ``per_rung`` then holds
          the *evaluated* rungs only, and skipped rungs' lane savings
          are reported in ``extra`` (local sessions only).

        Either way the incremental QT carry is cross-checked at
        runtime against the cross-length lower bound (``lb_margin`` /
        ``extra["lb_ok"]``, see ``pan.cross_length_lb``).
        """
        t0 = time.perf_counter()
        spec = self.spec
        if spec.method not in ("matrix_profile", "ring"):
            raise ValueError(
                "search_pan runs the exact-profile plan family and "
                "supports spec.method='matrix_profile' (local) or "
                "'ring' (mesh-sharded); got "
                f"spec.method={spec.method!r}.  Serial counted "
                "methods, hst_jax and drag search one length at a "
                "time through search().")
        if schedule not in ("ladder", "lb", "lb_abandon"):
            raise ValueError(
                "schedule must be 'ladder' (one all-rung sweep, "
                "per-rung results) or 'lb_abandon'/'lb' (sequential "
                "rungs, LB-skipped when only the global top-k "
                f"matters); got {schedule!r}")
        with TraceAnnotation("engine.search", kind="pan"):
            lad = canonical_ladder(spec.windows if ladder is None
                                   else ladder)
            x = np.asarray(series, np.float64).ravel()
            L = x.shape[0]
            if L < lad[-1] + 1:
                raise ValueError(f"series of {L} points is too short for "
                                 f"the ladder's longest window {lad[-1]} "
                                 f"(spec.s={spec.s} / ladder={lad})")
            if schedule != "ladder":
                return self._search_pan_lb(x, lad, t0)
            s0 = lad[0]
            n0 = L - s0 + 1
            Lb = length_bucket(L)
            xp = _bucket_pad(x, Lb)
            ndev = self.ndev if self.sharded else 1
            if self.sharded:
                plan = self.plan("pan_ring", lad, Lb)
                n_pad, nb_p = self._pan_row_geom(lad, Lb, ndev)
                cells = nb_p * spec.block * n_pad
            else:
                plan = self.plan("pan", lad, Lb)
                cells = self._pan_cells(s0, Lb)
            # neighbor ids stay on device: PanResult carries no neighbor
            # info, so only the d2 profiles cross to the host
            d2s, _args = plan(jnp.asarray(xp), np.int32(n0))
            d2s = np.asarray(d2s, np.float64)
            lanes = pan_lanes(lad, 1, cells)
            pan = self._pan_finish(
                x, lad, d2s, lanes=lanes, cells=cells, Lb=Lb,
                ndev=ndev,
                method=(f"pan[{self.backend}]" if ndev == 1 else
                        f"pan[{ndev}dev|{self.backend}]"),
                extra={"independent_lanes":
                           self._independent_lanes(lad, Lb, L),
                       "schedule": "ladder"})
            self.stats.searches += 1
            self.stats.tile_lanes += lanes
            return self._stamp_pan_runtime(pan, time.perf_counter() - t0)

    # -- the sequential LB-abandoning rung schedule --------------------
    def _rung_stats(self, x, cache: dict, s_r: int):
        """Host stats of one rung for the cross-length bracket:
        ``(mu, sigma)`` in znorm mode, raw window squared norms
        otherwise (cached per rung within one schedule)."""
        if s_r not in cache:
            if self.spec.znorm:
                cache[s_r] = sliding_stats(x, s_r)
            else:
                csum2 = np.concatenate(
                    [[0.0], np.cumsum(np.asarray(x, np.float64) ** 2)])
                n_r = x.shape[0] - s_r + 1
                cache[s_r] = csum2[s_r:s_r + n_r] - csum2[:n_r]
        return cache[s_r]

    def _pan_picks(self, x, lad, evaluated: dict, k: int) -> List[dict]:
        """The running global normalized top-k over the evaluated
        rungs' profiles — the greedy picks the skip test is measured
        against."""
        idx = sorted(evaluated)
        profiles = [np.sqrt(np.maximum(
            evaluated[r][0][:x.shape[0] - lad[r] + 1], 0.0))
            for r in idx]
        return global_normalized_topk(profiles,
                                      [lad[r] for r in idx], k)

    def _exact_pairs(self, x, s_n: int, ii, jj, stats_cache: dict):
        """Exact (f64, host) rung-``s_n`` distances of the window
        pairs ``(ii, jj)`` — the LB-abandoning schedule's *refinement*
        step: when the stats-only ``cross_length_ub`` is too loose, a
        window's one known pair is re-measured at the next length
        (VALMOD-style).  These are scalar Eq. (3)/raw evaluations —
        counted in ``calls``, never in ``tile_lanes``."""
        from .windows import windows_view
        w = windows_view(np.asarray(x, np.float64), s_n)
        a, b = w[ii], w[jj]
        if self.spec.znorm:
            mu, sig = self._rung_stats(x, stats_cache, s_n)
            a = (a - mu[ii][:, None]) / sig[ii][:, None]
            b = (b - mu[jj][:, None]) / sig[jj][:, None]
        return exact_pair_d2(a, b)

    def _rung_skippable(self, x, lad, r: int, le: int, evaluated: dict,
                        stats_cache: dict, picks: List[dict], k: int):
        """Can rung ``r`` be skipped given the current global picks?

        Per window the threshold is the k-th pick's score — or, for a
        window whose interval overlaps a pick, that pick's own (higher)
        score: a candidate provably below an overlapping pick is
        excluded the moment the pick is made, so it can never alter
        the greedy outcome (docs/ARCHITECTURE.md §3b).  Windows whose
        stats-only ``cross_length_ub`` fails the threshold get their
        one known pair re-measured exactly (``_exact_pairs``).
        Returns ``(skippable, refine_calls)``.
        """
        s_p, s_n = lad[le], lad[r]
        n_n = x.shape[0] - s_n + 1
        d2_p, ngh_p = evaluated[le]
        if self.spec.znorm:
            ub, partner = cross_length_ub(
                d2_p, ngh_p, s_p, s_n, n_n,
                stats_prev=self._rung_stats(x, stats_cache, s_p),
                stats_next=self._rung_stats(x, stats_cache, s_n))
        else:
            ub, partner = cross_length_ub(
                d2_p, ngh_p, s_p, s_n, n_n,
                nrm_prev=self._rung_stats(x, stats_cache, s_p),
                nrm_next=self._rung_stats(x, stats_cache, s_n))
        if n_n <= 0:
            return True, 0
        kth = picks[k - 1]["score"] if len(picks) == k else -np.inf
        thr = np.full(n_n, kth)
        for p in picks:
            lo = max(0, p["position"] - s_n + 1)
            hi = min(n_n, p["position"] + p["s"])
            thr[lo:hi] = np.maximum(thr[lo:hi], p["score"])
        # strict, with float-slack headroom: the bracket is exact in
        # real arithmetic but compares f32-swept profiles
        need = thr - 1e-3 * np.maximum(1.0, np.abs(thr))
        sc = np.sqrt(np.maximum(ub, 0.0)) / math.sqrt(s_n)
        fail = np.flatnonzero(~(sc < need))
        refines = 0
        if fail.size:
            fi = fail[partner[fail] >= 0]
            if fi.size:
                d2r = self._exact_pairs(x, s_n, fi, partner[fi],
                                        stats_cache)
                sc[fi] = np.sqrt(np.maximum(d2r, 0.0)) / math.sqrt(s_n)
                refines = int(fi.size)
            fail = np.flatnonzero(~(sc < need))
        return fail.size == 0, refines

    def _search_pan_lb(self, x, lad, t0) -> PanResult:
        """Sequential LB-abandoning rung schedule: rungs sweep
        lowest-first through carried-QT ``("pan_base", ...)`` /
        ``("pan_step", ...)`` plans, and a rung is *skipped* when the
        cross-length bracket proves no window in it can beat the
        current k-th global normalized pick.  Because a later pick can
        exclude earlier ones (the greedy k-th is not monotone in the
        candidate set), every skip is re-verified against the *final*
        top-k and violated skips are re-swept — so the returned
        ``global_normalized_topk`` always equals the all-rung sweep's.
        """
        if self.sharded:
            raise ValueError(
                "schedule='lb_abandon' runs the local sequential plan "
                "family only; on a mesh-sharded session (spec.ndev / "
                "mesh= / spec.method='ring') use schedule='ladder', "
                "which shards the ladder's query blocks across the "
                "mesh")
        spec = self.spec
        L = x.shape[0]
        Lb = length_bucket(L)
        xp = _bucket_pad(x, Lb)
        xp = jnp.asarray(xp)
        n0 = L - lad[0] + 1
        n_pad = self._n_pad(lad[0], Lb)
        cells = n_pad * n_pad
        stats_cache: dict = {}

        qt, d2_0, ngh_0 = self.plan("pan_base", lad[0], Lb)(
            xp, np.int32(n0))
        evaluated = {0: (np.asarray(d2_0, np.float64),
                         np.asarray(ngh_0, np.int64))}
        rung_lanes = {0: cells}
        lanes = cells
        refine_calls = 0
        skipped: List[int] = []
        last = 0
        for r in range(1, len(lad)):
            picks = self._pan_picks(x, lad, evaluated, spec.k)
            ok, refines = self._rung_skippable(
                x, lad, r, last, evaluated, stats_cache, picks, spec.k)
            refine_calls += refines
            if ok:
                skipped.append(r)
                continue
            step = self.plan("pan_step", tuple(lad[last:r + 1]), Lb,
                                       n_pad)
            qt, d2_r, ngh_r = step(xp, qt,
                                   np.int32(L - lad[last] + 1))
            evaluated[r] = (np.asarray(d2_r, np.float64),
                            np.asarray(ngh_r, np.int64))
            rung_lanes[r] = ceil_div(cells * (lad[r] - lad[last]),
                                     lad[r])
            lanes += rung_lanes[r]
            last = r
        # fixpoint re-verification: skips were tested against the
        # *running* picks, and the greedy k-th is not monotone in the
        # candidate set — a later pick can exclude earlier ones
        resweeps = 0
        while True:
            picks = self._pan_picks(x, lad, evaluated, spec.k)
            bad = None
            for r in skipped:
                le = max(e for e in evaluated if e < r)
                ok, refines = self._rung_skippable(
                    x, lad, r, le, evaluated, stats_cache, picks,
                    spec.k)
                refine_calls += refines
                if not ok:
                    bad = r
                    break
            if bad is None:
                break
            # the carried QT has moved past this rung: re-sweep it
            # from scratch through the cached single-length plan
            skipped.remove(bad)
            s_b = lad[bad]
            d2_b, ngh_b = self.plan("profile", s_b, Lb)(
                xp, np.int32(L - s_b + 1))
            evaluated[bad] = (np.asarray(d2_b, np.float64),
                              np.asarray(ngh_b, np.int64))
            rung_lanes[bad] = self._profile_lanes(s_b, Lb, L - s_b + 1)
            lanes += rung_lanes[bad]
            resweeps += 1

        eval_idx = sorted(evaluated)
        eval_lad = tuple(lad[r] for r in eval_idx)
        d2s = np.full((len(eval_idx), n0), np.inf)
        for row, r in enumerate(eval_idx):
            n_r = L - lad[r] + 1
            d2s[row, :n_r] = evaluated[r][0][:n_r]
        pan = self._pan_finish(
            x, eval_lad, d2s, lanes=lanes, cells=cells, Lb=Lb, ndev=1,
            method=f"pan_lb[{self.backend}]",
            rung_calls=[rung_lanes[r] for r in eval_idx],
            rung_indices=eval_idx, ladder=lad,
            calls=lanes + refine_calls,
            extra={"schedule": "lb_abandon",
                   "evaluated_rungs": eval_lad,
                   "skipped_rungs": tuple(lad[r] for r in skipped),
                   "resweeps": resweeps,
                   "refine_calls": refine_calls,
                   "ladder_lanes": pan_lanes(lad, 1,
                                             self._pan_cells(lad[0], Lb)),
                   "independent_lanes":
                       self._independent_lanes(lad, Lb, L)})
        self.stats.searches += 1
        self.stats.tile_lanes += lanes
        return self._stamp_pan_runtime(pan, time.perf_counter() - t0)

    def _independent_lanes(self, ladder: tuple, Lb: int, L: int) -> int:
        """What ``len(ladder)`` independent local per-length profile
        searches of a series of ``L`` points would sweep
        (``_profile_lanes``) — the pan sweep's baseline."""
        return sum(self._profile_lanes(s, Lb, L - s + 1) for s in ladder)

    def search_batched(self, series_batch
                       ) -> Union[List[DiscordResult], List[PanResult]]:
        """Top-k discords of every series in a (B, L) stack — one
        plan-cached sweep (vmapped on ``xla``, scanned elsewhere).

        Multi-window specs run the (B, ladder) pan plan instead and
        return one :class:`PanResult` per series (docs/pan.md).

        Sharded sessions route through a two-level layout: the batch
        is series-parallel across the mesh devices (each device sweeps
        its own sub-batch locally), except when the series are longer
        than :func:`ring_series_threshold` windows — then each series
        is itself ring-sharded mesh-wide, one after another.

        Timing is honest: every result carries the true per-batch wall
        clock in ``runtime_s`` (first call includes the one-time
        trace/compile; warm calls don't) plus the amortized
        ``per_series_s`` and the total swept ``tile_lanes`` in
        ``extra`` — so cps/runtime comparisons against serial methods
        see the real cost.
        """
        spec = self.spec
        self._require_profile_plan("search_batched")
        t0 = time.perf_counter()
        with TraceAnnotation("engine.search", kind="batched"):
            xb = np.atleast_2d(np.asarray(series_batch, np.float64))
            B, L = xb.shape
            if spec.multi_window:
                return self._search_pan_batched(xb, t0)
            s = spec.s
            if L < s + 1:
                raise ValueError(f"series of {L} points is too short for "
                                 f"window spec.s={s}")
            if spec.precision != "f32":
                return self._search_batched_qsweep(xb, t0)
            if self.sharded:
                return self._search_batched_sharded(xb, t0)
            n_true = L - s + 1
            Lb = length_bucket(L)
            xbp = _bucket_pad(xb, Lb)
            d2b, _argb = self.plan("batched", s, B, Lb)(jnp.asarray(xbp),
                                                      np.int32(n_true))
            profs = np.sqrt(np.asarray(d2b, np.float64)[:, :n_true])
            elapsed = time.perf_counter() - t0
            per_lanes = self._profile_lanes(s, Lb, n_true)
            lanes = B * per_lanes
            self.stats.searches += 1
            self.stats.tile_lanes += lanes
            out: List[DiscordResult] = []
            for b in range(B):
                prof = np.where(np.isfinite(profs[b]), profs[b], -np.inf)
                pos, vals = topk_nonoverlapping(prof, spec.k, s)
                out.append(DiscordResult(
                    positions=pos, nnds=vals, calls=per_lanes,
                    n=n_true, s=s, method=f"batched_mp[{self.backend}]",
                    runtime_s=elapsed, tile_lanes=per_lanes,
                    extra={"batch_size": B, "batch_index": b,
                           "backend": self.backend, "bucket": Lb,
                           "per_series_s": elapsed / B,
                           "tile_lanes": lanes}))
            return out

    def _search_batched_qsweep(self, xb: np.ndarray, t0: float
                               ) -> List[DiscordResult]:
        """Batched quantized layout: the prune/refine escalation is
        per-series host control flow, so the quantized batch runs
        series-after-series through the single-series two-phase
        drivers (ring-sharded bound pass on meshed sessions, local
        otherwise) — every series reuses the same two cached plans.
        One API call counts as one search, like the other batched
        layouts, and timing is honest (true per-batch wall clock on
        every result)."""
        s = self.spec.s
        B = xb.shape[0]
        one = (self._search_qsweep_ring if self.sharded
               else lambda x: self._search_qsweep(x, s))
        out = [one(xb[b]) for b in range(B)]
        elapsed = time.perf_counter() - t0
        total = sum(r.calls for r in out)
        self.stats.searches -= B - 1
        for b, r in enumerate(out):
            r.runtime_s = elapsed
            r.extra.update(batch_size=B, batch_index=b,
                           layout="qsweep-per-series",
                           per_series_s=elapsed / B,
                           batch_tile_lanes=total)
        return out

    def _search_batched_sharded(self, xb: np.ndarray, t0: float
                                ) -> List[DiscordResult]:
        """Two-level mesh layout of a batched search (see
        ``search_batched``)."""
        spec, s = self.spec, self.spec.s
        B, L = xb.shape
        n_true = L - s + 1
        mesh = self._resolve_mesh()
        ndev = int(mesh.devices.size)
        # the ring plans speak Eq. (3) only (no raw-mode inversion), so
        # a raw sharded batch always takes the series-parallel layout,
        # whose per-device profile sweep handles znorm=False exactly
        if n_true > ring_series_threshold() and spec.znorm:
            # level 2: each series is ring-sharded across the mesh
            out = []
            for b in range(B):
                r = self._search_ring(xb[b])
                r.extra["layout"] = "ring-per-series"
                out.append(r)
            # honest batch timing, same contract as the other layouts:
            # runtime_s = the true per-batch wall clock on every result
            elapsed = time.perf_counter() - t0
            total_lanes = sum(r.tile_lanes for r in out)
            for b, r in enumerate(out):
                r.runtime_s = elapsed
                r.extra.update(batch_size=B, batch_index=b,
                               per_series_s=elapsed / B,
                               tile_lanes=total_lanes)
            self.stats.searches += 1
            return out
        # level 1: series-parallel — pad the batch to a device multiple
        Lb = length_bucket(L)
        Bp = ceil_div(B, ndev) * ndev
        xbp = _bucket_pad(xb, Lb, rows=Bp)
        d2b, _argb = self.plan("batched_ring", s, Bp, Lb)(
            jnp.asarray(xbp), jnp.full((1,), n_true, jnp.int32))
        profs = np.sqrt(np.asarray(d2b, np.float64)[:B, :n_true])
        elapsed = time.perf_counter() - t0
        per_lanes = self._profile_lanes(s, Lb, n_true)
        lanes = Bp * per_lanes
        self.stats.searches += 1
        self.stats.tile_lanes += lanes
        out = []
        for b in range(B):
            prof = np.where(np.isfinite(profs[b]), profs[b], -np.inf)
            pos, vals = topk_nonoverlapping(prof, spec.k, s)
            out.append(DiscordResult(
                positions=pos, nnds=vals, calls=per_lanes,
                n=n_true, s=s,
                method=f"batched_mp[{ndev}dev|{self.backend}]",
                runtime_s=elapsed, tile_lanes=per_lanes,
                extra={"batch_size": B, "batch_index": b,
                       "backend": self.backend, "bucket": Lb,
                       "ndev": ndev, "layout": "series-parallel",
                       "per_series_s": elapsed / B,
                       "tile_lanes": lanes}))
        return out

    def _search_pan_batched(self, xb: np.ndarray, t0: float
                            ) -> List[PanResult]:
        """Batched pan (the (B, ladder) plan): every series of the
        stack through one ladder sweep — ``("pan_batched", ...)``
        locally, the two-level layout on a mesh (series-parallel
        below :func:`ring_series_threshold` base-rung windows,
        query-block-sharded pan per series above; no znorm guard —
        the pan body computes raw distances natively)."""
        spec = self.spec
        lad = canonical_ladder(spec.windows)
        B, L = xb.shape
        if L < lad[-1] + 1:
            raise ValueError(f"series of {L} points is too short for "
                             f"the ladder's longest window {lad[-1]} "
                             f"(spec.s={spec.s})")
        n0 = L - lad[0] + 1
        Lb = length_bucket(L)
        s0 = lad[0]
        if self.sharded and n0 > ring_series_threshold():
            # level 2: each series is itself a query-block-sharded pan
            out = [self.search_pan(xb[b]) for b in range(B)]
            elapsed = time.perf_counter() - t0
            total = sum(p.tile_lanes for p in out)
            # one API call = one search, like the other batched layouts
            self.stats.searches -= B - 1
            for b, p in enumerate(out):
                self._stamp_pan_runtime(p, elapsed)
                p.extra.update(batch_size=B, batch_index=b,
                               layout="pan-ring-per-series",
                               per_series_s=elapsed / B,
                               batch_tile_lanes=total)
            return out
        ndev = self.ndev if self.sharded else 1
        if self.sharded:
            Bp = ceil_div(B, ndev) * ndev
            xbp = _bucket_pad(xb, Lb, rows=Bp)
            d2b, _argb = self.plan("pan_batched_ring", lad, Bp, Lb)(
                jnp.asarray(xbp), jnp.full((1,), n0, jnp.int32))
            layout = "series-parallel"
            n_swept = Bp
        else:
            xbp = _bucket_pad(xb, Lb)
            d2b, _argb = self.plan("pan_batched", lad, B, Lb)(
                jnp.asarray(xbp), np.int32(n0))
            layout = "local"
            n_swept = B
        d2b = np.asarray(d2b, np.float64)
        cells = self._pan_cells(s0, Lb)
        per_lanes = pan_lanes(lad, 1, cells)
        total = n_swept * per_lanes
        self.stats.searches += 1
        self.stats.tile_lanes += total
        elapsed = time.perf_counter() - t0
        method = (f"pan_batched[{self.backend}]" if ndev == 1 else
                  f"pan_batched[{ndev}dev|{self.backend}]")
        out: List[PanResult] = []
        for b in range(B):
            pan = self._pan_finish(
                xb[b], lad, d2b[b], lanes=per_lanes,
                cells=cells, Lb=Lb, ndev=ndev, method=method,
                extra={"batch_size": B, "batch_index": b,
                       "layout": layout, "per_series_s": elapsed / B,
                       "batch_tile_lanes": total,
                       "independent_lanes":
                           self._independent_lanes(lad, Lb, L),
                       "schedule": "ladder"})
            out.append(self._stamp_pan_runtime(pan, elapsed))
        return out

    # -- streaming -----------------------------------------------------
    def _require_profile_plan(self, op: str) -> None:
        """Batched/stream entry points run the exact-profile plan
        family only — anything else would silently ignore the spec's
        method semantics (e.g. drag's threshold, hst's counted
        plane)."""
        if self.spec.method not in ("matrix_profile", "ring"):
            raise ValueError(
                f"{op} runs the exact-profile plan family and "
                "supports spec.method='matrix_profile' (local "
                "sessions) or 'ring' (mesh-sharded) — scalar and "
                "multi-window (pan ladder) specs alike; got "
                f"spec.method={self.spec.method!r}.  The serial "
                "counted methods, hst_jax and drag run one-shot "
                "single-series searches through search() only.")

    def _require_znorm(self, what: str) -> None:
        """The sharded single-length plans feed Eq. (3) tiles straight
        through the ring/min-fold bodies with no raw-mode
        (``znorm=False``) inversion — the uninverted tile is not a
        monotone transform of raw distance, so allowing it would
        silently return wrong neighbors.  Raw sharded work must route
        through the series-parallel/local profile plans (they apply
        ``TileEngine._raw_d2``) or the pan plans (which compute raw
        distances natively from the carried QT and need no guard)."""
        if not self.spec.znorm:
            raise ValueError(
                f"{what} speaks Eq. (3) z-normalized distance only "
                "and rejects spec.znorm=False; raw (Euclidean) "
                "searches run on the local or series-parallel profile "
                "plans, and raw ladder searches on the pan plans")

    def open_stream(self, s: Optional[int] = None, *,
                    history=None
                    ) -> Union["DiscordStream", "PanStream"]:
        """Open an append-only profile stream, optionally seeded with
        ``history`` points.

        On a scalar-``s`` spec (or with an explicit ``s=``) this is a
        single-length :class:`DiscordStream`.  On a multi-window spec
        with ``s=None`` it is a :class:`PanStream` that maintains
        *every* ladder rung's exact profile incrementally — appends
        sweep only the tail rows, QT carried across rungs
        (docs/pan.md).
        """
        self._require_profile_plan("open_stream")
        if s is None:
            if self.spec.multi_window:
                return PanStream(self, self.spec.windows,
                                 history=history)
            s = self.spec.s
        return DiscordStream(self, int(s), history=history)

    # -- non-plan methods (serial counted plane, hst_jax, drag) --------
    def _dispatch(self, series, **kw) -> DiscordResult:
        spec = self.spec
        s, k = spec.s, spec.k
        series = np.asarray(series, dtype=np.float64)
        self.stats.searches += 1
        m = spec.method
        if m == "brute":
            from .serial import brute_force
            return brute_force(series, s, k, znorm=spec.znorm)
        if m == "hotsax":
            from .serial import hotsax
            return hotsax(series, s, k, P=spec.P, alpha=spec.alpha,
                          seed=spec.seed)
        if m == "hst":
            from .serial import hst
            return hst(series, s, k, P=spec.P, alpha=spec.alpha,
                       seed=spec.seed, znorm=spec.znorm)
        if m == "dadd":
            from .serial import dadd
            from .serial.dadd import pick_r_by_sampling
            rr = spec.r if spec.r is not None else \
                0.99 * pick_r_by_sampling(series, s, k, seed=spec.seed)
            return dadd(series, s, k, r=rr, seed=spec.seed)
        if m == "rra":
            from .serial import rra
            return rra(series, s, k, P=spec.P, alpha=spec.alpha,
                       seed=spec.seed)
        if m == "hst_jax":
            from .hst_jax import hst_jax
            return hst_jax(series, s, k, P=spec.P, alpha=spec.alpha,
                           seed=spec.seed, backend=self.backend, **kw)
        if m == "drag":
            if "mesh" in kw:
                raise TypeError(
                    "mesh placement moved to the session: pass "
                    "DiscordEngine(spec, mesh=...) (or "
                    "SearchSpec(ndev=...)) instead of "
                    "search(..., mesh=...)")
            from .distributed import drag_discords
            return drag_discords(series, s, k, r=spec.r, seed=spec.seed,
                                 mesh=self._resolve_mesh(),
                                 backend=self.backend, **kw)
        raise AssertionError(f"unreachable method {m!r}")


class DiscordStream:
    """Append-only series with an incrementally maintained exact nnd
    profile (opened via :meth:`DiscordEngine.open_stream`).

    The first fill runs one bucketed full-profile plan; every later
    ``append`` sweeps only the new tail tile rows through the session's
    plan cache and min-folds the column results into the old profile —
    in the append-only case an old window's nnd can only be superseded
    by a closer new neighbor, never worsen, so no old row is ever
    re-swept.

    On a sharded engine the fill runs the ring plan and every append
    runs the sharded tail plan: each device sweeps the tail queries
    against only the candidate shard it owns, and the per-shard row
    minima are min-folded globally — same exact results, mesh-wide
    work split.
    """

    def __init__(self, engine: DiscordEngine, s: int, history=None):
        self.engine = engine
        self.s = int(s)
        # the sharded fill/tail plans are Eq. (3)-only (no raw-mode
        # inversion): raw streams on a sharded session fall back to
        # the local plans, which handle znorm=False exactly
        self._sharded = engine.sharded and engine.spec.znorm
        # quantized streams (spec.precision != "f32") run the exact
        # fill, then every tail through the ("qsweep_tail", ...)
        # bound pass + per-block f32 refinement (docs/cps.md)
        self._quant = engine.spec.precision != "f32"
        self._x = np.zeros(0, np.float64)
        self._d2 = np.zeros(0, np.float64)
        self._ngh = np.zeros(0, np.int64)
        self.appends = 0
        self.tile_lanes = 0
        self.refine_calls = 0
        self._qtail_blocks = 0
        self._qtail_refined = 0
        if history is not None and np.asarray(history).size:
            self.append(history)

    # -- state ---------------------------------------------------------
    @property
    def n_points(self) -> int:
        return int(self._x.shape[0])

    @property
    def n_windows(self) -> int:
        return int(self._d2.shape[0])

    @property
    def series(self) -> np.ndarray:
        return self._x.copy()

    def profile(self) -> np.ndarray:
        """Exact nnd per window (+inf where no non-self match exists)."""
        return np.sqrt(self._d2)

    def neighbors(self) -> np.ndarray:
        return self._ngh.copy()

    # -- updates -------------------------------------------------------
    #
    # ``append`` is split into three phases so the serve plane
    # (``repro.serve.DiscordServer``) can interleave them across
    # tenants: ``_append_begin`` mutates the series and stages the op
    # the device must run, ``_append_exec`` runs it through this
    # session's own plans, ``_append_finish`` folds the outputs into
    # the profile.  A micro-batched dispatch replaces only the middle
    # phase (same per-lane body, ``lax.map``-ed), so coalesced appends
    # stay bit-identical to ``append``'s.

    def _append_begin(self, pts: np.ndarray):
        """Absorb ``pts`` into the series and stage the device op this
        append needs — ``None`` while the series is still shorter than
        one window (nothing to sweep)."""
        eng, s = self.engine, self.s
        n_old = max(0, self._x.shape[0] - s + 1)
        self._x = np.concatenate([self._x, pts])
        L = self._x.shape[0]
        n_new = max(0, L - s + 1)
        if n_new == n_old:            # still shorter than one window
            return None
        Lb = length_bucket(L)
        xp = _bucket_pad(self._x, Lb)
        ndev = eng.ndev if self._sharded else 1
        if n_old == 0:                # first fill: one full-profile plan
            if self._sharded:
                _, per, n_sh = eng._shard_geom(s, Lb, ndev)
                lanes = n_sh * per * ndev
            else:
                lanes = eng._profile_lanes(s, Lb, n_new)
            return {"kind": "fill", "s": s, "Lb": Lb, "xp": xp,
                    "n_new": n_new, "lanes": lanes}
        n_tail = n_new - n_old
        Qb = length_bucket(n_tail, lo=32)
        if self._quant and eng._n_pad(s, Lb) // eng.spec.block >= 2:
            # quantized tail: local bound pass + per-block f32
            # refinement — the host escalation needs per-block
            # control flow, so the quant tail never shards (the
            # sharded fill above still does).  Single-block buckets
            # fall through to the exact tail (pruning is vacuous and
            # the trip-count-2 refine plan needs a preserved loop).
            return {"kind": "qtail", "s": s, "Lb": Lb, "Qb": Qb,
                    "xp": xp, "q0": n_old, "n_new": n_new,
                    "n_tail": n_tail,
                    "lanes": Qb * eng._n_pad(s, Lb)}
        lanes = Qb * (eng._shard_geom(s, Lb, ndev)[2] if self._sharded
                      else eng._n_pad(s, Lb))
        return {"kind": "tail", "s": s, "Lb": Lb, "Qb": Qb, "xp": xp,
                "q0": n_old, "n_new": n_new, "n_tail": n_tail,
                "lanes": lanes}

    def _append_exec(self, op: dict):
        """Run a staged op through the single-tenant plans (device
        outputs returned un-synced — the caller's host folds block)."""
        eng = self.engine
        if op["kind"] == "fill":
            if self._sharded:
                d2, arg, _, _ = eng._ring_exec(
                    op["s"], op["Lb"], jnp.asarray(op["xp"]),
                    np.int32(op["n_new"]))
                return d2, arg
            return eng.plan("profile", op["s"], op["Lb"])(
                jnp.asarray(op["xp"]), np.int32(op["n_new"]))
        if op["kind"] == "qtail":
            return eng.plan("qsweep_tail", op["s"], op["Lb"],
                                         op["Qb"])(
                jnp.asarray(op["xp"]), np.int32(op["q0"]),
                np.int32(op["n_new"]))
        plan = (eng.plan("tail_ring", op["s"], op["Lb"], op["Qb"])
                if self._sharded
                else eng.plan("tail", op["s"], op["Lb"], op["Qb"]))
        return plan(jnp.asarray(op["xp"]), np.int32(op["q0"]),
                    np.int32(op["n_new"]))

    def _append_finish(self, op: dict, out) -> "DiscordStream":
        """Fold one op's device outputs into the profile (host side)."""
        eng = self.engine
        n_new = op["n_new"]
        if op["kind"] == "fill":
            d2, arg = out
            self._d2 = np.asarray(d2, np.float64)[:n_new]
            self._ngh = np.asarray(arg, np.int64)[:n_new]
        elif op["kind"] == "qtail":   # quantized tail: bound + refine
            self._qtail_fold(op, out)
        else:                         # tail sweep only
            rd2, rngh, cd2, cngh = out
            n_tail = op["n_tail"]
            d2 = np.concatenate([self._d2,
                                 np.asarray(rd2, np.float64)[:n_tail]])
            ngh = np.concatenate([self._ngh,
                                  np.asarray(rngh, np.int64)[:n_tail]])
            cm = np.asarray(cd2, np.float64)[:n_new]
            ca = np.asarray(cngh, np.int64)[:n_new]
            better = cm < d2
            d2 = np.where(better, cm, d2)
            ngh = np.where(better, ca, ngh)
            self._d2, self._ngh = d2, ngh
        lanes = op["lanes"]
        self.appends += 1
        self.tile_lanes += lanes
        eng.stats.appends += 1
        eng.stats.tile_lanes += lanes
        return self

    def _qtail_fold(self, op: dict, out) -> None:
        """Host fold of one quantized tail op: certified brackets in,
        the *exact* tail fold out.

        Row side: candidate block ``b`` can hold a live tail row's
        minimum only if ``rlo[b, i] <= row_ub[i] = min_b' rhi[b', i]``
        for some live row ``i`` (pad rows are +inf everywhere and
        must not widen the criterion) — excluded blocks sit strictly
        above every live row minimum, so the first-min fold over the
        refined subset (ascending block order) equals the full
        ``argmin(rm, axis=0)`` fold of ``_op_tail``, neighbor
        tie-breaks included.  Column side: candidate ``j`` can only
        improve an old nnd when its certified lower bound undercuts
        the current profile (``clo[j] < d2[j]``); a skipped block's
        exact ``cm >= clo >= d2`` makes the strict min-fold a no-op.
        Derivation: docs/ARCHITECTURE.md.
        """
        eng = self.engine
        s, Lb, Qb = op["s"], op["Lb"], op["Qb"]
        n_new, n_tail = op["n_new"], op["n_tail"]
        block = eng.spec.block
        rlo, rhi, clo = (np.asarray(a, np.float64) for a in out)
        nb = rlo.shape[0]
        xp = jnp.asarray(op["xp"])
        q0, nv = np.int32(op["q0"]), np.int32(n_new)
        rplan = eng.plan("qsweep_tail_refine", s, Lb, Qb)
        refined: dict = {}
        ncalls = 0

        def refine_many(bs):
            nonlocal ncalls
            bs = [int(b) for b in bs if int(b) not in refined]
            for i in range(0, len(bs), 2):
                pair = bs[i:i + 2]
                padded = (pair if len(pair) == 2
                          else (pair[0], pair[0]))
                c2 = jnp.asarray(np.array(padded, np.int32) * block)
                arrs = [np.asarray(a, np.float64)
                        for a in rplan(xp, q0, nv, c2)]
                ncalls += 1
                for lane, b in enumerate(pair):
                    refined[b] = [a[lane] for a in arrs]

        row_ub = np.min(rhi[:, :n_tail], axis=0)
        need = np.any(rlo[:, :n_tail] <= row_ub[None, :], axis=1)
        refine_many(np.flatnonzero(need))
        rbs = sorted(refined)
        rm = np.stack([refined[b][0] for b in rbs])
        ra = np.stack([refined[b][1] for b in rbs])
        sel = np.argmin(rm, axis=0)
        cols = np.arange(Qb)
        row_d2 = rm[sel, cols][:n_tail]
        row_ngh = ra[sel, cols][:n_tail]
        d2 = np.concatenate([self._d2, row_d2])
        ngh = np.concatenate([self._ngh, row_ngh.astype(np.int64)])
        refine_many([b for b in range(nb)
                     if (b * block < n_new
                         and np.any(clo[b * block:
                                        min(b * block + block,
                                            n_new)]
                                    < d2[b * block:
                                         min(b * block + block,
                                             n_new)]))])
        for b in sorted(refined):
            j0, j1 = b * block, min(b * block + block, n_new)
            if j1 <= j0:
                continue
            cm = refined[b][2][:j1 - j0]
            ca = refined[b][3][:j1 - j0].astype(np.int64)
            better = cm < d2[j0:j1]
            d2[j0:j1] = np.where(better, cm, d2[j0:j1])
            ngh[j0:j1] = np.where(better, ca, ngh[j0:j1])
        self._d2, self._ngh = d2, ngh
        # hybrid accounting (docs/cps.md): the op's ``lanes`` are the
        # bound pass; each refinement call pays a pair of exact
        # (Qb x block) tiles, duplicate padding included
        r_lanes = ncalls * 2 * Qb * block
        self.refine_calls += r_lanes
        self._qtail_blocks += nb
        self._qtail_refined += len(refined)
        eng.stats.tile_lanes += r_lanes

    def append(self, points) -> "DiscordStream":
        """Fold new points into the profile, sweeping only the tail."""
        pts = np.asarray(points, np.float64).ravel()
        if pts.size == 0:
            return self
        op = self._append_begin(pts)
        if op is None:
            return self
        return self._append_finish(op, self._append_exec(op))

    # -- queries -------------------------------------------------------
    def discords(self, k: Optional[int] = None) -> DiscordResult:
        """Top-k non-overlapping discords of the current profile."""
        k = self.engine.spec.k if k is None else int(k)
        if self._d2.size == 0:
            return DiscordResult(positions=[], nnds=[], calls=0, n=0,
                                 s=self.s,
                                 method=f"stream[{self.engine.backend}]")
        prof = self.profile()
        pos, vals = topk_nonoverlapping(
            np.where(np.isfinite(prof), prof, -np.inf), k, self.s)
        extra = {"appends": self.appends,
                 "tile_lanes": self.tile_lanes,
                 "backend": self.engine.backend}
        if self._quant:
            extra.update(
                precision=self.engine.spec.precision,
                refine_calls=self.refine_calls,
                prune_ratio=(1.0 - self._qtail_refined
                             / self._qtail_blocks
                             if self._qtail_blocks else 0.0))
        return DiscordResult(
            positions=pos, nnds=vals,
            calls=self.tile_lanes + self.refine_calls,
            n=self.n_windows, s=self.s,
            method=f"stream[{self.engine.backend}]",
            tile_lanes=self.tile_lanes,
            extra=extra)


class PanStream:
    """Append-only series with **every ladder rung's** exact nnd
    profile maintained incrementally (opened via
    :meth:`DiscordEngine.open_stream` on a multi-window spec; user
    guide in docs/pan.md).

    The first fill (once the series covers the longest rung) runs the
    session's full pan ladder plan.  Every later ``append`` runs a
    ``("pan_tail", ...)`` plan: the tail's base-rung query rows span
    every rung's new windows (rung ``r``'s new windows start
    ``s_r - s_0`` ids *before* the base rung's), the QT is carried
    across rungs exactly like the full sweep — so an append pays
    base-rung tail tiles plus Δ-wide extensions only — and per rung
    the row minima become the new windows' exact nnds while the column
    minima min-fold new-neighbor improvements into the old profile
    (append-only: an old window's nnd can only be superseded, never
    worsen).

    On a sharded engine the fill shards the ladder's query blocks and
    each append shards the *candidates* (``("pan_tail_ring", ...)``).
    Both znorm modes run sharded — the pan bodies compute raw
    distances natively from the carried QT, so no raw-mode guard is
    needed (unlike the single-length sharded tail plan).
    """

    def __init__(self, engine: DiscordEngine, ladder, history=None):
        self.engine = engine
        self.ladder = canonical_ladder(ladder)
        self._sharded = engine.sharded
        self._x = np.zeros(0, np.float64)
        self._d2 = [np.zeros(0, np.float64) for _ in self.ladder]
        self._ngh = [np.zeros(0, np.int64) for _ in self.ladder]
        self._filled = False
        self.appends = 0
        self.tile_lanes = 0
        self._cells = 0            # swept (rows x cols) grid cells
        # per-rung width-normalized shares, accumulated per sweep so
        # they always sum to tile_lanes exactly (pan.pan_rung_shares;
        # re-deriving shares from the cell total would ceil-drift)
        self._rung_lanes = [0] * len(self.ladder)
        if history is not None and np.asarray(history).size:
            self.append(history)

    # -- state ---------------------------------------------------------
    @property
    def n_points(self) -> int:
        return int(self._x.shape[0])

    def n_windows(self, rung: int = 0) -> int:
        return int(self._d2[rung].shape[0])

    @property
    def series(self) -> np.ndarray:
        return self._x.copy()

    def profile(self, rung: int = 0) -> np.ndarray:
        """Exact nnd per window at one rung (+inf where no non-self
        match exists)."""
        return np.sqrt(np.maximum(self._d2[rung], 0.0))

    def profiles(self) -> List[np.ndarray]:
        """Every rung's exact nnd profile, ascending ``s``."""
        return [self.profile(r) for r in range(len(self.ladder))]

    def neighbors(self, rung: int = 0) -> np.ndarray:
        return self._ngh[rung].copy()

    # -- updates -------------------------------------------------------
    #
    # Same three-phase split as ``DiscordStream`` (see the comment
    # there): the serve plane coalesces the middle phase across
    # tenants while begin/finish stay per-tenant, so micro-batched
    # pan appends are bit-identical to sequential ones.

    def _append_begin(self, pts: np.ndarray):
        """Absorb ``pts`` and stage the device op — ``None`` while the
        longest rung doesn't fit yet."""
        eng, lad = self.engine, self.ladder
        s0, smax = lad[0], lad[-1]
        n_old = max(0, self._x.shape[0] - s0 + 1)   # base rung
        self._x = np.concatenate([self._x, pts])
        L = self._x.shape[0]
        n_new = L - s0 + 1
        if L < smax + 1:              # longest rung doesn't fit yet
            return None
        Lb = length_bucket(L)
        xp = _bucket_pad(self._x, Lb)
        ndev = eng.ndev if self._sharded else 1
        if not self._filled:          # first fill: one full ladder plan
            if self._sharded:
                n_pad, nb_p = eng._pan_row_geom(lad, Lb, ndev)
                cells = nb_p * eng.spec.block * n_pad
            else:
                cells = eng._pan_cells(s0, Lb)
            return {"kind": "pan_fill", "ladder": lad, "Lb": Lb,
                    "xp": xp, "n_new": n_new,
                    "shares": pan_rung_shares(lad, 1, cells),
                    "cells": cells}
        # the tail's base-rung query ids span every rung's new
        # windows: rung r's start n_old - (s_r - s0) is smallest
        # at the longest rung
        q0 = max(0, n_old - (smax - s0))
        Qb = length_bucket(n_new - q0, lo=32)
        n_cols = (eng._shard_geom(s0, Lb, ndev)[2]
                  if self._sharded else eng._n_pad(s0, Lb))
        return {"kind": "pan_tail", "ladder": lad, "Lb": Lb, "Qb": Qb,
                "xp": xp, "q0": q0, "n_new": n_new,
                "shares": pan_rung_shares(lad, Qb, n_cols),
                "cells": Qb * n_cols}

    def _append_exec(self, op: dict):
        """Run a staged op through the single-tenant plans."""
        eng, lad = self.engine, self.ladder
        if op["kind"] == "pan_fill":
            plan = (eng.plan("pan_ring", lad, op["Lb"])
                    if self._sharded else eng.plan("pan", lad, op["Lb"]))
            return plan(jnp.asarray(op["xp"]), np.int32(op["n_new"]))
        plan = (eng.plan("pan_tail_ring", lad, op["Lb"], op["Qb"])
                if self._sharded
                else eng.plan("pan_tail", lad, op["Lb"], op["Qb"]))
        return plan(jnp.asarray(op["xp"]), np.int32(op["q0"]),
                    np.int32(op["n_new"]))

    def _append_finish(self, op: dict, out) -> "PanStream":
        """Fold one op's device outputs into every rung's profile."""
        eng, lad = self.engine, self.ladder
        if op["kind"] == "pan_fill":
            d2s, args = out
            d2s = np.asarray(d2s, np.float64)
            args = np.asarray(args, np.int64)
            L = op["n_new"] + lad[0] - 1
            for r, s_r in enumerate(lad):
                n_r = L - s_r + 1
                self._d2[r] = d2s[r, :n_r].copy()
                self._ngh[r] = args[r, :n_r].copy()
            self._filled = True
        else:                         # pan tail sweep only
            rd2, rng, cd2, cng = out
            rd2 = np.asarray(rd2, np.float64)
            rng = np.asarray(rng, np.int64)
            cd2 = np.asarray(cd2, np.float64)
            cng = np.asarray(cng, np.int64)
            q0 = op["q0"]
            L = op["n_new"] + lad[0] - 1
            for r, s_r in enumerate(lad):
                n_r_old = self._d2[r].shape[0]
                n_r = L - s_r + 1
                # rows [n_r_old - q0, n_r - q0): this rung's new
                # windows — their row minima are exact nnds
                d2 = np.concatenate(
                    [self._d2[r], rd2[r, n_r_old - q0:n_r - q0]])
                ngh = np.concatenate(
                    [self._ngh[r], rng[r, n_r_old - q0:n_r - q0]])
                # columns: every old window's best distance *to the
                # tail* min-folds in (append-only fold)
                cm, ca = cd2[r, :n_r], cng[r, :n_r]
                better = cm < d2
                self._d2[r] = np.where(better, cm, d2)
                self._ngh[r] = np.where(better, ca, ngh)
        shares = op["shares"]
        lanes = sum(shares)
        for r, share in enumerate(shares):
            self._rung_lanes[r] += share
        self.appends += 1
        self.tile_lanes += lanes
        self._cells += op["cells"]
        eng.stats.appends += 1
        eng.stats.tile_lanes += lanes
        return self

    def append(self, points) -> "PanStream":
        """Fold new points into every rung's profile, sweeping only
        the tail (one carried-QT pass for the whole ladder)."""
        pts = np.asarray(points, np.float64).ravel()
        if pts.size == 0:
            return self
        op = self._append_begin(pts)
        if op is None:
            return self
        return self._append_finish(op, self._append_exec(op))

    # -- queries -------------------------------------------------------
    def discords(self, k: Optional[int] = None) -> PanResult:
        """Per-rung top-k plus the global ``d/sqrt(s)``-normalized
        top-k of the current profiles (the same post-processing as
        ``search_pan``, including the cross-length LB self-check)."""
        eng, lad = self.engine, self.ladder
        k = eng.spec.k if k is None else int(k)
        method = f"pan_stream[{eng.backend}]"
        if not self._filled:
            return PanResult(per_rung=[], global_topk=[], ladder=lad,
                             n=0, calls=0, tile_lanes=0, method=method)
        t0 = time.perf_counter()
        L = self._x.shape[0]
        n0 = L - lad[0] + 1
        d2s = np.full((len(lad), n0), np.inf)
        for r in range(len(lad)):
            d2s[r, :self._d2[r].shape[0]] = self._d2[r]
        pan = eng._pan_finish(
            self._x, lad, d2s, lanes=self.tile_lanes,
            cells=self._cells, Lb=length_bucket(L),
            ndev=eng.ndev if self._sharded else 1, method=method, k=k,
            rung_calls=list(self._rung_lanes),
            extra={"appends": self.appends, "schedule": "stream"})
        return eng._stamp_pan_runtime(pan,
                                      time.perf_counter() - t0)


# ----------------------------------------------------------------------
# Plan-kind registry (the IR auditor's discovery surface)
# ----------------------------------------------------------------------
@dataclass(frozen=True)
class PlanKindAudit:
    """One plan-cache kind at a pinned, representative audit geometry.

    ``geom`` is the kind's geometry, so ``DiscordEngine.plan(kind,
    *geom)`` builds it, and ``avals`` its abstract inputs.
    ``pattern`` is the expected ordered ``dot_general`` decomposition
    of the traced plan body on the ``xla`` backend: one ``(cells,
    width)`` entry per dot site in program order, where ``cells`` is
    the total swept (query x candidate) cell count with every scan /
    ``lax.map`` / mesh multiplicity folded in, and ``width`` the
    contraction length.  ``groups`` assigns dot sites to the
    width-normalized lane groups of docs/cps.md — ``((site_idx, ...),
    s_norm)`` — so the modelled lane count is

        sum over groups of  units * ceil(macs_g / units / s_norm)

    with ``macs_g = sum(cells_i * width_i)`` over the group's sites.
    ``units`` is the number of independent per-series accounting units
    (the batch width of the ``mb``/batched layouts — their runtime
    accounting applies the ceil per series, then multiplies).
    ``lanes`` is the ``tile_lanes`` the runtime call site books for
    the same geometry on ``xla`` (``pallas`` books the live blocks of
    the profile kinds, ``_profile_lanes``); ``repro.analysis.irlint``
    asserts the traced IR reproduces ``pattern`` exactly and that
    ``model_lanes()`` of the traced dots equals ``lanes``.
    """
    kind: str
    geom: tuple          # DiscordEngine.plan geometry
    avals: tuple         # ((shape, dtype-name), ...) abstract inputs
    pattern: tuple       # ((cells, width), ...) expected dot sites
    groups: tuple        # (((site_idx, ...), s_norm), ...)
    units: int           # independent per-series accounting units
    lanes: int           # runtime tile_lanes at this geometry

    def model_lanes(self, dots=None) -> int:
        """Width-normalized lane count of a traced ``(cells, width)``
        dot decomposition (defaults to the expected ``pattern``)."""
        dots = tuple(self.pattern if dots is None else dots)
        total = 0
        for sites, s_norm in self.groups:
            macs = sum(dots[i][0] * dots[i][1] for i in sites)
            total += self.units * ceil_div(macs // self.units, s_norm)
        return int(total)


def plan_kind_registry(*, s: int = 24, ladder=(16, 24, 32),
                       block: int = 32, length: int = 90,
                       Qb: int = 32, batch: int = 2, ndev: int = 1
                       ) -> "OrderedDict[str, PlanKindAudit]":
    """Every :data:`PLAN_KINDS` kind at one pinned geometry.

    The IR auditor (``repro.analysis.irlint``) *discovers* plan kinds
    here, and each entry carries the expected dot decomposition and
    runtime lane formula so the static FLOP/lane cross-audit stays
    honest.  Entries are derived from the table: each op's facts for
    one series, times its layout's multiplicity (the ``mb`` and
    batched layouts stack ``B`` series, the series-parallel layout
    ``Bp``).  The sharded kinds whose geometry is their own are
    written out.  The geometry knobs mirror the sanitizer's defaults
    (length 90 buckets to 256 so most of every tile row is padding);
    ``ndev`` shapes the sharded entries and must match the mesh the
    auditor builds.
    """
    lad = canonical_ladder(ladder)
    if len(lad) < 2:
        raise ValueError("the audit ladder needs >= 2 rungs (the "
                         "pan_step kind extends across widths), got "
                         f"{lad}")
    R = len(lad)
    Lb = length_bucket(int(length))
    s, Qb, B, ndev = int(s), int(Qb), int(batch), int(ndev)
    n_pad = plan_pad_geom(s, Lb, block)
    _, per, n_sh = plan_shard_geom(s, Lb, block, ndev)
    p_pad = plan_pad_geom(lad[0], Lb, block)
    _, _, p_sh = plan_shard_geom(lad[0], Lb, block, ndev)
    _, nb_p = plan_pan_row_geom(lad, Lb, block, ndev)
    # quantized-sweep row geometry: the sharded bound pass pads the
    # query blocks to a device multiple (q_sh rows total)
    q_sh = ceil_div(n_pad // block, ndev) * ndev * block
    Bp = ceil_div(B, ndev) * ndev
    #: per-site contraction widths of one pan sweep: full base width,
    #: then each rung's extension
    widths = (lad[0],) + tuple(lad[r] - lad[r - 1] for r in range(1, R))
    f32, i32 = "float32", "int32"
    x, n, pair = ((Lb,), f32), ((), i32), ((2,), i32)
    one = (((0,), s),)
    per_rung = tuple(((r,), lad[r]) for r in range(R))

    def pan_pattern(rows, cols):
        return tuple((rows * cols, w) for w in widths)

    # (geom, avals, pattern, groups, lanes): each op for one series,
    # and the sharded kinds with a geometry of their own
    facts = {
        "profile": ((s, Lb), (x, n), ((n_pad * n_pad, s),), one,
                    n_pad ** 2),
        "tail": ((s, Lb, Qb), (x, n, n), ((Qb * n_pad, s),), one,
                 Qb * n_pad),
        "qsweep": ((s, Lb), (x, n), ((n_pad * n_pad, s),), one,
                   n_pad ** 2),
        "qsweep_refine": ((s, Lb), (x, pair, n),
                          ((2 * block * n_pad, s),), one,
                          2 * block * n_pad),
        "qsweep_tail": ((s, Lb, Qb), (x, n, n), ((Qb * n_pad, s),),
                        one, Qb * n_pad),
        "qsweep_tail_refine": ((s, Lb, Qb), (x, n, n, pair),
                               ((2 * Qb * block, s),), one,
                               2 * Qb * block),
        "pan": ((lad, Lb), (x, n), pan_pattern(p_pad, p_pad), per_rung,
                pan_lanes(lad, p_pad, p_pad)),
        "pan_tail": ((lad, Lb, Qb), (x, n, n), pan_pattern(Qb, p_pad),
                     per_rung, int(sum(pan_rung_shares(lad, Qb, p_pad)))),
        "pan_base": ((lad[0], Lb), (x, n), ((p_pad * p_pad, lad[0]),),
                     (((0,), lad[0]),), p_pad ** 2),
        "pan_step": ((lad, Lb, p_pad), (x, ((p_pad, p_pad), f32), n),
                     tuple((p_pad * p_pad, w) for w in widths[1:]),
                     # the LB schedule accounts one evaluated step as a
                     # single extension at the step's final width
                     # (docs/cps.md)
                     ((tuple(range(R - 1)), lad[-1]),),
                     ceil_div(p_pad * p_pad * (lad[-1] - lad[0]),
                              lad[-1])),
        "ring": ((s, Lb), (x, n), ((n_sh * per * ndev, s),), one,
                 n_sh * per * ndev),
        "tail_ring": ((s, Lb, Qb), (x, n, n), ((Qb * n_sh, s),), one,
                      Qb * n_sh),
        "qsweep_ring": ((s, Lb), (x, n), ((q_sh * n_pad, s),), one,
                        q_sh * n_pad),
        "pan_ring": ((lad, Lb), (x, n), pan_pattern(nb_p * block, p_pad),
                     per_rung, pan_lanes(lad, nb_p * block, p_pad)),
        "pan_tail_ring": ((lad, Lb, Qb), (x, n, n),
                          pan_pattern(Qb, p_sh), per_rung,
                          int(sum(pan_rung_shares(lad, Qb, p_sh)))),
    }
    out: "OrderedDict[str, PlanKindAudit]" = OrderedDict()
    for kind, pk in PLAN_KINDS.items():
        geom, avals, pattern, groups, lanes = facts.get(kind,
                                                        facts[pk.op])
        m = 1
        if pk.layout == "mb":
            m = B
            geom = geom + (B,)
            avals = tuple(((B,) + shape, dt) for shape, dt in avals)
        elif pk.layout in ("batched", "series_sharded"):
            m = B if pk.layout == "batched" else Bp
            geom = (geom[0], m, geom[1])
            avals = (((m,) + avals[0][0], f32),
                     avals[1] if pk.layout == "batched" else ((1,), i32))
        out[kind] = PlanKindAudit(
            kind, geom, avals, tuple((m * c, w) for c, w in pattern),
            groups, m, m * lanes)
    return out
