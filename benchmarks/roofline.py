"""Roofline analysis from the dry-run's compiled artifacts (§Roofline).

For every (arch × shape × mesh) cell this derives three per-step time
lower bounds from the dry-run JSON (TPU v5e constants):

    compute    = FLOPs_per_chip    / 197e12   [bf16 MXU peak]
    memory     = bytes_per_chip    / 819e9    [HBM bandwidth]
    collective = coll_bytes_per_chip / 50e9   [per-link ICI]

Correction: XLA's cost analysis counts a while-loop body once, so the
scanned L-layer stack under-reports; the dry-run records a calibrated
``layer_terms`` delta (L=2 scanned vs unrolled — see
launch/dryrun.py:calibrate_layer_terms) and we add (L-1)x of it here.
The compiled module is the per-chip program, so its numbers are
per-chip already (no further division).

MODEL_FLOPS uses the standard accounting: 6·N_active·tokens for train
(fwd+bwd), 2·N_active·tokens for prefill/decode, plus the attention
term 12·L·H·hd·S²·B(·0.5 causal) for quadratic-attention archs.

Output: markdown table + JSON at experiments/roofline/.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import numpy as np

PEAK_FLOPS = 197e12       # bf16 / chip (TPU v5e-class)
HBM_BW = 819e9            # bytes/s / chip
ICI_BW = 50e9             # bytes/s / link

KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")


def corrected(rec: dict, field: str, variant: str) -> float:
    """total(L) = scan2 + (L-1) * layer, from the measurement pair.

    variant "tile" for flops (loop-free, exact counts) and "prod" for
    bytes (streaming-traffic model) — see dryrun.calibrate_layer_terms.
    """
    L = rec.get("n_layers", 1)
    meas = rec.get("measured", {}).get(variant, {})
    base = meas.get("scan2", {}).get(field, rec.get(field, 0.0))
    layer = meas.get("layer", {}).get(field, 0.0)
    return float(base + max(layer, 0.0) * (L - 1))


def corrected_collectives(rec: dict) -> float:
    L = rec.get("n_layers", 1)
    meas = rec.get("measured", {}).get("prod", {})
    base = meas.get("scan2", {}).get("collectives",
                                     rec.get("collectives", {}))
    layer = meas.get("layer", {}).get("collectives", {})
    tot = 0.0
    for k in KINDS:
        tot += base.get(k, 0) + max(layer.get(k, 0), 0) * (L - 1)
    return tot


def model_flops(rec: dict, cfg) -> float:
    """Analytic MODEL_FLOPS for the whole step (all chips)."""
    B, S = rec["global_batch"], rec["seq_len"]
    n_act = rec["params_active"]
    kind = rec["kind"]
    if kind == "train":
        tokens = B * S
        mult = 6.0
    elif kind == "prefill":
        tokens = B * S
        mult = 2.0
    else:                      # decode: one token per lane
        tokens = B * 1
        mult = 2.0
    flops = mult * n_act * tokens
    # attention score/value matmuls (quadratic archs only)
    if cfg is not None and cfg.n_heads and cfg.mixer != "rwkv6":
        ctx = min(S, cfg.window) if cfg.window else S
        hd_tot = cfg.n_heads * cfg.hd
        per_tok = 2 * 2 * ctx * hd_tot * (0.5 if kind != "decode" else 1.0)
        bwd = 3.0 if kind == "train" else 1.0
        flops += cfg.n_layers * tokens * per_tok * bwd
    return flops


def model_bytes_per_chip(rec: dict, cfg) -> float:
    """Analytic streaming-traffic model (TPU-fusion-optimistic):

      weights+optimizer: train reads P (bf16) fwd + bwd + remat-fwd,
      reads/writes f32 grads + m/v + params  ->  ~30 B/param;
      serve reads params once  ->  2 B/param;
      activations: ~16 streamed (B,T,d) arrays per layer for train
      (fwd+bwd+recompute), ~6 for prefill; decode streams the KV cache
      once plus per-token state.

    This is the fusion-aware lower bound the HLO bytes column is
    checked against (CPU HLO counts every unfused elementwise op, so
    the measured column is a strict upper bound).
    """
    if cfg is None:
        return 0.0
    chips = rec["n_chips"]
    B, S = rec["global_batch"], rec["seq_len"]
    P = rec["params_active"]
    d, L = cfg.d_model, cfg.n_layers
    kind = rec["kind"]
    if kind == "train":
        w = 30.0 * P
        act = 16.0 * B * S * d * L * 2.0
    elif kind == "prefill":
        w = 2.0 * P
        act = 6.0 * B * S * d * L * 2.0
    else:
        w = 2.0 * P
        kv = (2 * B * min(S, cfg.window or S) * cfg.n_kv_heads
              * cfg.hd * L * 2.0) if cfg.n_heads else \
            (B * (cfg.d_model // max(cfg.ssm_state, 64))
             * cfg.ssm_state ** 2 * L * 4.0)
        act = 2.0 * kv + 8.0 * B * d * L * 2.0
    return (w + act) / chips


def analyse(rec: dict) -> dict:
    from repro.configs import get_config
    try:
        cfg = get_config(rec["arch"])
    except Exception:          # noqa: BLE001
        cfg = None
    chips = rec["n_chips"]
    f = corrected(rec, "flops", "tile")
    b = corrected(rec, "bytes_accessed", "prod")
    c = corrected_collectives(rec)
    t_comp = f / PEAK_FLOPS
    t_mem_hlo = b / HBM_BW
    t_mem_model = model_bytes_per_chip(rec, cfg) / HBM_BW
    # HLO bytes (CPU, unfused) upper-bound the traffic; the analytic
    # streaming model lower-bounds it.  Use the geometric mean as the
    # memory term; both endpoints are reported.
    t_mem = float(np.sqrt(max(t_mem_hlo, 1e-12)
                          * max(t_mem_model, 1e-12)))
    t_coll = c / ICI_BW
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dom = max(terms, key=terms.get)
    mf = model_flops(rec, cfg)
    hlo_global = f * chips
    bound = max(terms.values())
    return {
        **{k: rec[k] for k in ("arch", "shape", "mesh", "kind")},
        "t_compute_s": t_comp,
        "t_memory_s": t_mem,
        "t_memory_hlo_s": t_mem_hlo,
        "t_memory_model_s": t_mem_model,
        "t_collective_s": t_coll,
        "dominant": dom,
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "step_lower_bound_s": bound,
        # achievable fraction of compute roofline given the bottleneck
        "roofline_fraction": (mf / chips / PEAK_FLOPS) / bound
        if bound > 0 else 0.0,
        "mem_fit_gib": (rec["memory"]["temp_bytes"]
                        + rec["memory"]["argument_bytes"]) / 2 ** 30,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-dir", default="experiments/dryrun")
    ap.add_argument("--out", default="experiments/roofline")
    ap.add_argument("--mesh", default="pod16x16",
                    help="mesh to tabulate (roofline is single-pod)")
    args = ap.parse_args(argv)
    recs = []
    for f in sorted(Path(args.dryrun_dir).glob("*.json")):
        r = json.loads(f.read_text())
        if r.get("status") == "ok":
            recs.append(analyse(r))
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "roofline.json").write_text(json.dumps(recs, indent=1))

    lines = ["| cell | compute s | memory s | collective s | dominant |"
             " useful | roofline frac | mem GiB |",
             "|---|---|---|---|---|---|---|---|"]
    for r in sorted(recs, key=lambda r: (r["arch"], r["shape"])):
        if r["mesh"] != args.mesh:
            continue
        lines.append(
            f"| {r['arch']} × {r['shape']} "
            f"| {r['t_compute_s']:.4f} | {r['t_memory_s']:.4f} "
            f"| {r['t_collective_s']:.4f} | {r['dominant']} "
            f"| {r['useful_ratio']:.2f} | {r['roofline_fraction']:.2f} "
            f"| {r['mem_fit_gib']:.1f} |")
    md = "\n".join(lines)
    (out / "roofline.md").write_text(md)
    print(md)
    return recs


def run(small: bool = True) -> dict:
    """Bench-runner entry: summarize if dry-run artifacts exist."""
    d = Path("experiments/dryrun")
    if not d.exists() or not list(d.glob("*.json")):
        return {"tables": [], "claims": {"skipped": "no dry-run output"}}
    recs = main(["--dryrun-dir", str(d)])
    ok = [r for r in recs if r["mesh"] == "pod16x16"]
    from .util import BenchTable
    t = BenchTable("roofline summary (single-pod)",
                   ["dominant term", "#cells", "median roofline frac"])
    for dom in ("compute", "memory", "collective"):
        sub = [r for r in ok if r["dominant"] == dom]
        if sub:
            t.row(dom, len(sub), f"{np.median([r['roofline_fraction'] for r in sub]):.2f}")
    return {"tables": [t],
            "claims": {"n_cells": len(ok),
                       "all_fit_16gib": bool(all(r["mem_fit_gib"] < 16
                                                 for r in ok))}}


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
