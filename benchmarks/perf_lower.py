"""Perf-hillclimb driver: lower one cell with ModelConfig overrides and log
the roofline delta vs a named baseline record.

    PYTHONPATH=src python -m benchmarks.perf_lower \
        --arch jamba-1.5-large-398b --shape train_4k \
        --set mamba_scan=assoc --tag jamba_assoc

``--wave-step`` instead audits the fused wave-peel kernel lowering: it
lowers the unfused XLA peel chain, censuses its [W, E] HBM
materializations, and ASSERTS the fused lowering eliminates them (its
only HBM operands are the 1-D index tables and the [V_loc, W] lane slab;
per-iteration HBM bytes are zero by construction).

    PYTHONPATH=src python -m benchmarks.perf_lower --wave-step
"""

import argparse
import ast
import json
import os
import sys


def wave_step_mode(args) -> None:
    from benchmarks.bench_wave import analyze_fused_step

    info = analyze_fused_step(args.graph, wave=args.wave)
    print(f"[wave-step] graph={info['graph']} W={info['wave']} "
          f"E={info['num_edges']} iters={info['iters']} "
          f"backend={info['backend']}"
          f"{' (interpret)' if info['interpret'] else ''}")
    print(f"  unfused: {info['unfused_bytes_step']:.3e} B/step "
          f"({info['unfused_bytes_per_iter']:.3e} B/iter), "
          f"[W,E] HBM materializations per iter: "
          f"{info['unfused_we_materializations']}")
    print(f"  fused:   {info['fused_bytes_step']:.3e} B/step "
          f"({info['fused_bytes_per_iter_hbm']:.0f} B/iter HBM), "
          f"[W,E] HBM materializations: "
          f"{info['fused_we_materializations']}")
    print(f"  bytes ratio fused/unfused: {info['bytes_ratio']:.2e}")
    if info["unfused_we_materializations"] <= 0:
        sys.exit("[wave-step] FAIL: no [W, E] materializations found in "
                 "the unfused lowering — baseline census is broken")
    if info["fused_we_materializations"] != 0:
        sys.exit("[wave-step] FAIL: fused lowering still round-trips "
                 "[W, E] arrays through HBM")
    if not info["fused_bytes_step"] < info["unfused_bytes_step"]:
        sys.exit("[wave-step] FAIL: fused lowering does not reduce HBM "
                 "bytes per step")
    print("[wave-step] OK: fused lowering eliminates the [W, E] HBM "
          "round-trips")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--set", action="append", default=[],
                    help="field=value ModelConfig override (repeatable)")
    ap.add_argument("--tag")
    ap.add_argument("--baseline", default="",
                    help="path of a baseline record to diff against")
    ap.add_argument("--wave-step", action="store_true",
                    help="audit the fused wave-peel kernel lowering "
                         "instead of lowering a dry-run cell")
    ap.add_argument("--graph", default="collegemsg",
                    help="benchmark graph for --wave-step")
    ap.add_argument("--wave", type=int, default=16,
                    help="lane count for --wave-step")
    args = ap.parse_args()

    if args.wave_step:
        wave_step_mode(args)
        return
    if not (args.arch and args.shape and args.tag):
        ap.error("--arch, --shape and --tag are required "
                 "(unless --wave-step)")

    from repro.launch.dryrun import lower_cell

    overrides = {}
    for kv in getattr(args, "set"):
        k, v = kv.split("=", 1)
        try:
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v
    rec, _ = lower_cell(args.arch, args.shape, args.mesh == "multi",
                        overrides=overrides)
    rec["overrides"] = overrides
    out = os.path.join(os.path.dirname(__file__), "results", "perf",
                       args.tag + ".json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    rl = rec["roofline"]
    print(f"[perf] {args.tag}: t_comp={rl['t_compute_s']:.3f} "
          f"t_mem={rl['t_memory_s']:.3f} t_coll={rl['t_collective_s']:.3f} "
          f"dom={rl['dominant']} frac={rl.get('roofline_fraction', 0):.5f}")
    if args.baseline and os.path.exists(args.baseline):
        base = json.load(open(args.baseline))["roofline"]
        for k in ("t_compute_s", "t_memory_s", "t_collective_s",
                  "roofline_fraction"):
            if base.get(k):
                print(f"  {k:18s} {base[k]:10.4f} -> {rl[k]:10.4f} "
                      f"({rl[k] / base[k]:.3f}x)")


if __name__ == "__main__":
    main()
