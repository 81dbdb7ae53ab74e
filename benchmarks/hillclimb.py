"""Per-cell hillclimb driver: lower a cell with config overrides and
print the three roofline terms + memory fit (§Perf methodology).

    PYTHONPATH=src python -m benchmarks.hillclimb \
        --arch internlm2-1.8b --shape train_4k \
        --override microbatch=4 --tag mb4
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse                                              # noqa: E402
import json                                                  # noqa: E402
from pathlib import Path                                     # noqa: E402

from repro.launch.dryrun import run_cell                     # noqa: E402

from .roofline import analyse                                # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--override", default="")
    ap.add_argument("--tag", default="hc")
    ap.add_argument("--out", default="experiments/hillclimb")
    args = ap.parse_args(argv)

    overrides = {}
    for kv in filter(None, args.override.split(",")):
        k, v = kv.split("=")
        if v in ("true", "false"):
            overrides[k] = v == "true"
        elif v.lstrip("-").isdigit():
            overrides[k] = int(v)
        else:
            try:
                overrides[k] = float(v)
            except ValueError:
                overrides[k] = v

    out = Path(args.out) / args.tag
    rec = run_cell(args.arch, args.shape, multi_pod=False, out_dir=out,
                   overrides=overrides)
    if rec["status"] != "ok":
        print(json.dumps(rec, indent=1))
        return 1
    a = analyse(rec)
    mem = (rec["memory"]["temp_bytes"]
           + rec["memory"]["argument_bytes"]) / 2 ** 30
    print(f"\n[{args.tag}] {args.arch} x {args.shape} {overrides}")
    print(f"  compute    {a['t_compute_s']:8.4f} s")
    print(f"  memory     {a['t_memory_s']:8.4f} s  "
          f"(hlo {a['t_memory_hlo_s']:.4f} / model "
          f"{a['t_memory_model_s']:.4f})")
    print(f"  collective {a['t_collective_s']:8.4f} s")
    print(f"  dominant   {a['dominant']}   roofline frac "
          f"{a['roofline_fraction']:.3f}   useful {a['useful_ratio']:.2f}")
    print(f"  fit        {mem:.2f} GiB/chip "
          f"{'OK' if mem < 16 else 'OVER'}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import sys
    sys.exit(main())
