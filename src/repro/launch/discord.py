"""Discord-search launcher (Plane A CLI).

Builds a typed ``SearchSpec`` from argv and runs it through one
``DiscordEngine`` session — the same code path as the library API, for
every method.  Every accepted spelling funnels through
``repro.core.spec`` canonicalization, so the CLI surface cannot drift
from the library: ``--method distributed`` *is* ``ring`` (the
mesh-sharded plan family), ``--method scamp``/``mp`` are
``matrix_profile``, and ``--backend jnp``/``ref``/``np`` resolve to
their canonical tile backends (``xla``/``numpy``).

Backend auto-resolution when ``--backend`` is omitted follows the
registry order: ``REPRO_TILE_BACKEND`` env var if set, else ``pallas``
on TPU and ``xla`` everywhere else (resolved once per session).

Entry-point flags compose with the window spelling: ``--stream P``
drives the session's stream plane (appends sweep only the tail) and
``--batch B`` the batched plane — with a ladder ``--s`` both run the
pan plans (PanStream / the (B, ladder) plan, docs/pan.md), and
``--schedule lb`` runs the LB-abandoning rung schedule when only the
global top-k matters.

    python -m repro.launch.discord --method hst --n 20000 --s 120 -k 3
    python -m repro.launch.discord --method ring --ndev 4 --backend xla
    python -m repro.launch.discord --method matrix_profile --s 96,128
    python -m repro.launch.discord --method mp --s 64:128:16 --stream 4096
    python -m repro.launch.discord --method mp --s 64:128:16 --batch 8
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.core import DiscordEngine, PanResult, SearchSpec
from repro.core.spec import (JAX_METHODS, METHOD_ALIASES,
                             SERIAL_METHODS, canonical_method)
from repro.data import sine_noise, with_implanted_anomalies
from repro.kernels.registry import ENV_VAR as BACKEND_ENV_VAR
from repro.kernels.registry import _ALIASES as _BACKEND_ALIASES
from repro.kernels.registry import available_backends

METHOD_CHOICES = sorted(set(SERIAL_METHODS) | set(JAX_METHODS)
                        | set(METHOD_ALIASES))
#: canonical tile backends plus the registry's accepted alias
#: spellings — derived, so a new backend/alias is advertised here
#: automatically
BACKEND_CHOICES = tuple(sorted(set(available_backends())
                               | set(_BACKEND_ALIASES)))


def _parse_s(text: str):
    """``"120"`` -> 120, ``"96,128"`` -> (96, 128) (multi-window),
    ``"64:128:16"`` -> (64, 80, 96, 112, 128) (pan-length ladder;
    ``hi`` inclusive, step defaults to 1)."""
    if ":" in text:
        parts = [int(p) for p in text.split(":") if p]
        if len(parts) not in (2, 3):
            raise argparse.ArgumentTypeError(
                f"ladder must be lo:hi[:step], got {text!r}")
        lo, hi = parts[0], parts[1]
        step = parts[2] if len(parts) == 3 else 1
        if step < 1 or hi < lo:
            raise argparse.ArgumentTypeError(
                f"ladder must have hi >= lo and step >= 1, got {text!r}")
        rungs = tuple(range(lo, hi + 1, step))
        return rungs[0] if len(rungs) == 1 else rungs
    parts = [int(p) for p in text.split(",") if p]
    return parts[0] if len(parts) == 1 else tuple(parts)


def build_parser() -> argparse.ArgumentParser:
    alias_help = ", ".join(f"{a} == {c}"
                           for a, c in sorted(METHOD_ALIASES.items()))
    ap = argparse.ArgumentParser(
        prog="repro.launch.discord",
        description="k-discord search through one DiscordEngine "
                    "session (library-identical code path).")
    ap.add_argument("--method", default="hst", choices=METHOD_CHOICES,
                    help=f"serial counted: {', '.join(SERIAL_METHODS)}; "
                         f"blocked jax: {', '.join(JAX_METHODS)}; "
                         f"aliases: {alias_help}")
    ap.add_argument("--file", help="1-column text file of points")
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--E", type=float, default=0.5)
    ap.add_argument("--anomalies", type=int, default=2)
    ap.add_argument("--s", type=_parse_s, default=120,
                    help="window length; a comma list (96,128) or a "
                         "lo:hi:step ladder (64:128:16, hi inclusive) "
                         "runs the pan-length matrix_profile search — "
                         "every rung from one shared sweep, plus the "
                         "global d/sqrt(s)-normalized top-k.  "
                         "Composes with --stream (PanStream: appends "
                         "sweep only the tail at every rung), --batch "
                         "(the (B, ladder) plan) and --schedule")
    ap.add_argument("-k", type=int, default=1)
    ap.add_argument("--P", type=int, default=4)
    ap.add_argument("--alpha", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--r", type=float, default=None,
                    help="DADD/DRAG abandon threshold (default: paper "
                         "sampling recipe)")
    ap.add_argument("--backend", default=None, choices=BACKEND_CHOICES,
                    help="distance-tile backend for the jax methods "
                         "(canonical: numpy | xla | pallas; aliases "
                         "jnp == xla, ref/np == numpy).  Omitted: "
                         f"${BACKEND_ENV_VAR} if set, else pallas on "
                         "TPU and xla elsewhere")
    ap.add_argument("--ndev", type=int, default=None,
                    help="device count of the auto data-mesh for the "
                         "sharded methods (ring/drag and batched/"
                         "stream layouts); default: all local devices")
    ap.add_argument("--raw", action="store_true",
                    help="raw Euclidean windows instead of Eq. (3) "
                         "z-normalized (DADD's convention; only "
                         "brute | hst | matrix_profile)")
    ap.add_argument("--stream", type=int, default=None, metavar="P",
                    help="drive the stream plane: hold out the last P "
                         "points, open_stream on the rest, append "
                         "them, print the stream's discords.  Scalar "
                         "--s streams one profile; a ladder --s "
                         "streams every rung through the pan tail "
                         "plan (profile-plan methods only)")
    ap.add_argument("--batch", type=int, default=None, metavar="B",
                    help="drive the batched plane: search B synthetic "
                         "series (seeds seed..seed+B-1) in one "
                         "search_batched call.  Scalar --s runs the "
                         "batched profile plan; a ladder --s the "
                         "(B, ladder) pan plan (profile-plan methods "
                         "only; not with --file/--stream)")
    ap.add_argument("--selfcheck", action="store_true",
                    help="before searching, run the padding-poison "
                         "sanitizer (repro.analysis.sanitize) against "
                         "this spec's own plan kinds on a small "
                         "synthetic series — NaN/±inf pad canaries "
                         "must leave results bit-identical; aborts "
                         "(exit 2) on any finding.  Off by default; "
                         "adds a few seconds of tiny compiles")
    ap.add_argument("--schedule", default="ladder",
                    choices=("ladder", "lb", "lb_abandon"),
                    help="ladder --s only: 'ladder' sweeps every rung "
                         "in one plan (per-rung results); 'lb' / "
                         "'lb_abandon' sweeps rungs sequentially and "
                         "skips rungs the cross-length bracket rules "
                         "out — same global top-k, fewer lanes (one-"
                         "shot local search only)")
    return ap


def validate_args(ap: argparse.ArgumentParser,
                  args: argparse.Namespace) -> argparse.Namespace:
    """Cross-flag rules the type system can't express — fail loudly at
    the parser, naming the flags, before any jax work starts."""
    profile_plan = canonical_method(args.method) in ("matrix_profile",
                                                     "ring")
    if args.stream is not None and args.batch is not None:
        ap.error("--stream and --batch are different session planes; "
                 "pick one")
    if (args.stream is not None or args.batch is not None) \
            and not profile_plan:
        ap.error(f"--stream/--batch run the exact-profile plan family "
                 f"(--method matrix_profile|scamp|mp or ring|"
                 f"distributed); --method {args.method} searches "
                 "one-shot only")
    if args.batch is not None and args.batch < 1:
        ap.error("--batch must be >= 1")
    if args.stream is not None and args.stream < 1:
        ap.error("--stream must hold out >= 1 points")
    if args.batch is not None and args.file:
        ap.error("--batch generates synthetic series; it does not "
                 "compose with --file")
    if args.schedule != "ladder":
        if isinstance(args.s, int):
            ap.error("--schedule lb needs a window ladder "
                     "(--s lo:hi:step or a comma list)")
        if args.stream is not None or args.batch is not None:
            ap.error("--schedule lb is a one-shot search_pan "
                     "schedule; it does not compose with "
                     "--stream/--batch")
    return args


def spec_from_args(args: argparse.Namespace) -> SearchSpec:
    """argv -> canonicalized SearchSpec (aliases resolve here)."""
    return SearchSpec(s=args.s, k=args.k, method=args.method,
                      P=args.P, alpha=args.alpha, seed=args.seed,
                      r=args.r, znorm=not args.raw,
                      backend=args.backend, ndev=args.ndev)


def _print_pan(pan: PanResult) -> None:
    for r in pan.per_rung:
        print(r)
    skips = (f", skipped rungs {pan.extra['skipped_rungs']} "
             f"(all-rung sweep: {pan.extra['ladder_lanes']} lanes)"
             if pan.extra.get("schedule") == "lb_abandon" else "")
    indep = pan.extra.get("independent_lanes")
    baseline = (f" (independent sweeps would cost {indep})"
                if indep else "")
    print(f"pan ladder {pan.ladder}: tile_lanes={pan.tile_lanes}"
          f"{baseline}, lb_ok={pan.extra['lb_ok']}{skips}")
    for g in pan.global_topk:
        print(f"  global s={g['s']} pos={g['position']} "
              f"nnd={g['nnd']:.4f} nnd/sqrt(s)={g['score']:.4f}")


def main(argv=None):
    ap = build_parser()
    args = validate_args(ap, ap.parse_args(argv))

    anchor = args.s if isinstance(args.s, int) else max(args.s)
    if args.file:
        x = np.loadtxt(args.file)
    else:
        x = sine_noise(args.n, E=args.E, seed=args.seed)
        x, pos = with_implanted_anomalies(
            x, n_anomalies=args.anomalies, length=anchor,
            amp=0.8, seed=args.seed)
        print(f"synthetic Eq.7 series, implanted at {pos}")

    spec = spec_from_args(args)
    engine = DiscordEngine(spec)
    mesh = f", ndev={engine.ndev}" if engine.sharded else ""
    print(f"{spec} -> backend={engine.backend}{mesh}")
    if args.selfcheck:
        from repro.analysis.sanitize import selfcheck
        findings, checked = selfcheck(spec)
        if findings:
            for f in findings:
                print(f"selfcheck: {f}")
            print(f"selfcheck: {len(findings)} padding-poison "
                  "finding(s) for this spec — aborting the search")
            raise SystemExit(2)
        if checked:
            print(f"selfcheck: pad canaries clean across "
                  f"{len(checked)} plan-kind run(s) "
                  f"({', '.join(checked)})")
        else:
            print(f"selfcheck: method {spec.method!r} runs no "
                  "bucketed plans; nothing to poison")
    if args.batch is not None:
        xb = np.stack([x] + [
            with_implanted_anomalies(
                sine_noise(x.shape[0], E=args.E, seed=args.seed + b),
                n_anomalies=args.anomalies, length=anchor, amp=0.8,
                seed=args.seed + b)[0]
            for b in range(1, args.batch)])
        results = engine.search_batched(xb)
        for b, r in enumerate(results):
            print(f"series {b}:")
            if isinstance(r, PanResult):
                _print_pan(r)
            else:
                print(r)
        return
    if args.stream is not None:
        if args.stream >= x.shape[0]:
            ap.error(f"--stream {args.stream} holds out the whole "
                     f"{x.shape[0]}-point series; nothing to seed "
                     "the stream with")
        st = engine.open_stream(history=x[:-args.stream])
        held = st.tile_lanes
        st.append(x[-args.stream:])
        print(f"stream: fill {held} lanes, append "
              f"{st.tile_lanes - held} lanes ({st.appends} appends)")
        res = st.discords()
        if isinstance(res, PanResult):
            _print_pan(res)
        else:
            print(res)
        return
    if spec.multi_window:
        _print_pan(engine.search_pan(x, schedule=args.schedule))
    else:
        res = engine.search(x)
        for r in res if isinstance(res, list) else [res]:
            print(r)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
