#!/usr/bin/env python3
"""Chip smoke test: drive the discord-search main path once on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py               # one chip, every phase below
    python chip_smoke.py --four-chips  # only the 4-device ring phase

One process does everything; nothing here starts a child that needs the
chip.  Every phase checks its results and any failure exits non-zero
with the traceback.  Without a TPU (or outside a checkout) the script
exits non-zero before printing any result.

One-chip phases, through the public ``DiscordEngine``/``DiscordServer``
entry points, on a seeded ECG-like series of ``N`` points (the scale of
the paper's long ECG records, filling the 2^17 length bucket) with
implanted anomalies:

  device        JAX's first device is a TPU and the tile backend
                resolves to ``pallas`` with compiled (not interpreted)
                kernels
  search        matrix_profile on pallas: same positions as the ``xla``
                backend on the same chip (nnds within rel. 1e-3) and as
                ``hst_jax``; on a 4096-point prefix, the same discords
                as the f64 brute-force reference computed on the host
  kernels       the compiled profile plan holds Mosaic kernels
                (``tpu_custom_call``)
  compile-once  a second series in the same bucket adds no traces
  stream        open_stream(history) + append equals the one-shot result
  serve         64 tenants with appends in one DiscordServer are
                bit-identical to sequential per-tenant streams

``--four-chips`` runs a ``method="ring"`` search over a 4-device mesh on
2^19 points and compares it with the one-chip matrix_profile result on
device 0, computed in the same process.

The last line of standard output is the JSON verdict
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
earlier lines carry timings and sizes for information only.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

S = 300                 # window length of the paper's ECG experiments
K = 3
N = 120_000             # one long ECG-like record (2^17 bucket)
N_RING = 2 ** 19        # four-chip ring series
PREFIX = 4096           # host f64 brute-force reference prefix
STREAM_TAIL = 8192
TENANTS = 64
TENANT_HISTORY = 4096
TENANT_APPENDS = (64, 200, 512)
RTOL = 1e-3


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def ecg_series(n: int, seed: int):
    from repro.data import ecg_like, with_implanted_anomalies
    x, pos = with_implanted_anomalies(ecg_like(n, seed=seed),
                                      n_anomalies=K, length=S,
                                      amp=0.6, seed=seed)
    return x, pos


def timed(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    return out, time.perf_counter() - t0


def same_discords(a, b, what: str, rtol: float = RTOL) -> None:
    import numpy as np
    if list(a.positions) != list(b.positions):
        raise AssertionError(f"{what}: positions {a.positions} != "
                             f"{b.positions}")
    if not np.allclose(a.nnds, b.nnds, rtol=rtol, atol=0.0):
        raise AssertionError(f"{what}: nnds {a.nnds} vs {b.nnds} "
                             f"(rtol {rtol})")


def phase_device():
    import jax
    from repro.kernels.common import default_interpret
    from repro.kernels.registry import resolve_backend
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX's first device is "
                         f"{dev.platform!r}, not a TPU; refusing to run")
    backend = resolve_backend()
    if backend != "pallas" or default_interpret():
        raise SystemExit(f"chip_smoke: tile backend {backend!r} "
                         f"(interpret={default_interpret()}) on a TPU; "
                         "expected compiled pallas kernels")
    log(f"device {dev.device_kind} x{len(jax.devices())}, "
        f"backend {backend}")
    return dev


def phase_search(x):
    import numpy as np
    from repro.core import DiscordEngine, SearchSpec
    from repro.core.serial.brute import brute_force

    eng = DiscordEngine(SearchSpec(s=S, k=K, method="matrix_profile"))
    r, t_cold = timed(eng.search, x)
    r2, t_warm = timed(eng.search, x)
    log(f"matrix_profile[pallas] n={len(x)} bucket={r.extra['bucket']} "
        f"cold {t_cold:.2f}s warm {t_warm:.2f}s -> {r.positions} "
        f"{np.round(r.nnds, 4).tolist()}")
    same_discords(r, r2, "pallas repeat", rtol=0.0)

    rx, t_x = timed(DiscordEngine(SearchSpec(
        s=S, k=K, method="matrix_profile", backend="xla")).search, x)
    log(f"matrix_profile[xla] {t_x:.2f}s (cold) -> {rx.positions}")
    same_discords(r, rx, "pallas vs xla")

    rh, t_h = timed(DiscordEngine(SearchSpec(s=S, k=K,
                                             method="hst_jax")).search, x)
    log(f"hst_jax[pallas] {t_h:.2f}s (cold) -> {rh.positions}")
    if list(rh.positions) != list(r.positions):
        raise AssertionError(f"hst_jax positions {rh.positions} != "
                             f"{r.positions}")

    xp = x[:PREFIX]
    rp = eng.search(xp)
    rb, t_b = timed(brute_force, np.asarray(xp, np.float64), S, K)
    log(f"prefix {PREFIX}: pallas {rp.positions} vs f64 brute "
        f"{rb.positions} ({t_b:.1f}s on the host)")
    same_discords(rp, rb, "pallas vs f64 brute prefix")
    return eng, r


def phase_kernels(eng, x):
    import numpy as np
    from repro.core.spec import length_bucket
    Lb = length_bucket(len(x))
    xp = np.zeros(Lb, np.float32)
    xp[:len(x)] = x
    plan = eng.plan("profile", S, Lb)
    text = plan.lower(xp, np.int32(len(x) - S + 1)).compile().as_text()
    calls = text.count("tpu_custom_call")
    if calls == 0:
        raise AssertionError("compiled profile plan holds no Mosaic "
                             "kernel (tpu_custom_call)")
    log(f"profile plan: {calls} tpu_custom_call site(s)")


def phase_compile_once(eng):
    traces = eng.stats.traces
    y, _ = ecg_series(N - 2000, seed=7)
    r, t = timed(eng.search, y)
    if eng.stats.traces != traces:
        raise AssertionError(f"same-bucket search retraced: "
                             f"{traces} -> {eng.stats.traces}")
    log(f"compile-once: second series {len(y)} pts, {t:.2f}s, "
        f"traces still {traces}")


def phase_stream(eng, x, one_shot):
    st = eng.open_stream(history=x[:-STREAM_TAIL])
    _, t = timed(st.append, x[-STREAM_TAIL:])
    d = st.discords()
    log(f"stream: append {STREAM_TAIL} pts {t:.2f}s -> {d.positions}")
    same_discords(d, one_shot, "stream vs one-shot", rtol=1e-5)


def phase_serve():
    import numpy as np
    from repro.core import DiscordEngine, SearchSpec
    from repro.serve import DiscordServer

    spec = SearchSpec(s=S, k=K, method="matrix_profile")
    rng = np.random.default_rng(11)
    hist = {t: ecg_series(TENANT_HISTORY, seed=100 + t)[0]
            for t in range(TENANTS)}
    adds = {t: [rng.normal(0.2, 0.3, size=m) for m in TENANT_APPENDS]
            for t in range(TENANTS)}
    srv = DiscordServer(max_group=TENANTS)
    t0 = time.perf_counter()
    for t in range(TENANTS):
        srv.open(t, spec, history=hist[t])
    srv.flush()
    for step in range(len(TENANT_APPENDS)):
        for t in range(TENANTS):
            srv.append(t, adds[t][step])
        srv.flush()
    t_srv = time.perf_counter() - t0
    ref = DiscordEngine(spec)
    for t in range(TENANTS):
        st = ref.open_stream(history=hist[t])
        for a in adds[t]:
            st.append(a)
        got = srv.stream(t)
        if not (np.array_equal(got.profile(), st.profile())
                and np.array_equal(got.neighbors(), st.neighbors())):
            raise AssertionError(f"tenant {t}: served profile differs "
                                 "from its sequential stream")
    stats = srv.stats().as_dict()
    log(f"serve: {TENANTS} tenants x {TENANT_HISTORY} pts + "
        f"{len(TENANT_APPENDS)} appends in {t_srv:.2f}s, "
        f"{stats['dispatches']} dispatches; bit-identical to "
        f"sequential streams")


def run_one_chip():
    x, implanted = ecg_series(N, seed=0)
    log(f"series: {len(x)} pts, s={S}, k={K}, implanted at {implanted}")
    eng, r = phase_search(x)
    phase_compile_once(eng)
    phase_kernels(eng, x)
    phase_stream(eng, x, r)
    phase_serve()


def run_four_chips():
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import DiscordEngine, SearchSpec

    devs = jax.devices()
    if len(devs) != 4:
        raise SystemExit(f"chip_smoke --four-chips: {len(devs)} "
                         "device(s), need 4")
    x, implanted = ecg_series(N_RING, seed=3)
    log(f"series: {len(x)} pts, s={S}, k={K}, implanted at {implanted}")
    mesh = Mesh(np.array(devs), ("series",))
    ring = DiscordEngine(SearchSpec(s=S, k=K, method="ring"), mesh=mesh)
    rr, t_cold = timed(ring.search, x)
    rr2, t_warm = timed(ring.search, x)
    log(f"ring[{len(devs)} dev] cold {t_cold:.2f}s warm {t_warm:.2f}s "
        f"-> {rr.positions} {np.round(rr.nnds, 4).tolist()}")
    same_discords(rr, rr2, "ring repeat", rtol=0.0)
    with jax.default_device(devs[0]):
        one = DiscordEngine(SearchSpec(s=S, k=K, method="matrix_profile"))
        r1, t1 = timed(one.search, x)
    log(f"matrix_profile[1 chip] {t1:.2f}s (cold) -> {r1.positions}")
    same_discords(rr, r1, "ring vs one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device ring phase")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    dev = phase_device()
    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips()
    else:
        run_one_chip()
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
