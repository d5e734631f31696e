"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness contract) and writes
full JSON records under benchmarks/results/.  The wave-engine rows
(bench_wave + its fused-kernel gate run_kernel + bench_pipeline +
bench_service + bench_streaming + bench_cache + bench_chaos incl. its
kill-anywhere durability drill + bench_distributed) are
additionally folded into the
repo-root ``BENCH_wave.json`` so the wave-mode perf trajectory is
tracked across PRs; bench_wave.run_kernel raises on fused-vs-composite
bit divergence or a fused-cost regression, and bench_pipeline,
bench_service and bench_streaming verify cross-engine result
equivalence (including the streaming snapshot-consistency gate) and
raise (non-zero exit) on divergence, so the harness doubles as a
regression gate.  With ``REPRO_BENCH_SMOKE=1`` only the gate benches run,
on shrunken graphs, and the trajectory file is left untouched — that is
the per-PR CI mode.  The dry-run tables are produced by
``python -m repro.launch.dryrun`` (it needs the 512-device env and is
kept out of this CPU-timing harness); the on-chip benchmark, roofline
shares included, is ``python3 -m tcqbench``.
"""

from __future__ import annotations

import json
import os
import sys
import traceback


def main() -> None:
    from benchmarks import (bench_cache, bench_chaos, bench_distributed,
                            bench_distribution, bench_k, bench_memory,
                            bench_pipeline, bench_pruning, bench_queries,
                            bench_service, bench_span, bench_streaming,
                            bench_wave)
    from benchmarks.common import SMOKE

    print("name,us_per_call,derived")
    failures = 0
    trajectory = {}

    def row(name, seconds, derived=""):
        print(f"{name},{seconds * 1e6:.1f},{derived}")

    try:
        for r in ([] if SMOKE else bench_queries.run()):
            tag = f"queries/{r['graph']}/q{r['id']}"
            row(tag + "/otcd", r["t_otcd_s"],
                f"results={r['n_results']}")
            row(tag + "/otcd_wave", r["t_otcd_wave_s"],
                f"steps<=cells={r['cells_evaluated_otcd']}")
            row(tag + "/tcd", r["t_tcd_s"],
                f"speedup_otcd={r['speedup_otcd_vs_tcd']:.1f}x")
            row(tag + "/iphc_online", r["t_iphc_online_s"],
                f"speedup_otcd={r['speedup_otcd_vs_iphc']:.1f}x")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        for r in ([] if SMOKE else bench_pruning.run()):
            row(f"pruning/{r['graph']}", 0.0,
                f"pruned%={r['pct_total_pruned']:.1f} "
                f"(por={r['pct_por']:.1f} pou={r['pct_pou']:.1f} "
                f"pol={r['pct_pol']:.1f} empty={r['pct_empty']:.1f})")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        for r in ([] if SMOKE else bench_k.run()):
            row(f"impact_k/{r['graph']}/k{r['k']}", r["t_otcd_s"],
                f"cores={r['n_cores']} cc={r['n_components']} "
                f"tcd_s={r['t_tcd_s']:.3f}")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        for r in ([] if SMOKE else bench_span.run()):
            row(f"impact_span/{r['graph']}/x{r['span_uts']}",
                r["t_otcd_s"],
                f"cells={r['cells_total']} cores={r['n_cores']} "
                f"tcd_s={r['t_tcd_s']:.3f}")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        for r in ([] if SMOKE else bench_memory.run()):
            row(f"memory/{r['graph']}", 0.0,
                f"tel_bytes={r['tel_bytes']} "
                f"bytes_per_edge={r['tel_bytes_per_edge']:.1f}")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        for r in ([] if SMOKE else bench_distribution.run()):
            row(f"distribution/{r['graph']}", r["wall_s"],
                f"cores={r['n_cores']}")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        wrows = bench_wave.run()
        trajectory["wave"] = wrows
        for r in wrows:
            if r["bench"] == "wave_width":
                row(f"wave/width{r['wave']}", r["t_s"],
                    f"device_steps={r['device_steps']}")
            else:
                row(f"wave/degree_{r['path']}", r["t_s"],
                    f"iters={r['iters']}")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        # the fused wave-peel kernel gate: run_kernel() raises on any
        # fused-vs-composite bit divergence and on a cost-model
        # regression (fused bytes/step must stay strictly below the
        # unfused chain), so a broken kernel fails the harness like a
        # cross-engine result divergence would
        krows = bench_wave.run_kernel()
        trajectory["kernel"] = krows
        for r in krows:
            if r["bench"] == "fused_step":
                row(f"kernel/{r['path']}", r["t_s"],
                    f"iters={r['iters']} wave={r['wave']}")
            else:
                row("kernel/cost", 0.0,
                    f"bytes_ratio={r['bytes_ratio']:.2e} "
                    f"fused_B/step={r['fused_bytes_step']:.3e} "
                    f"unfused_B/step={r['unfused_bytes_step']:.3e} "
                    f"gate_ok={r['gate_ok']}")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        prows = bench_pipeline.run()
        trajectory["pipeline"] = prows
        for r in prows:
            if r["bench"] == "pipeline":
                row(f"pipeline/{r['mode']}", r["t_s"],
                    f"steps={r['device_steps']} syncs={r['host_syncs']} "
                    f"bytes/step={r['bytes_per_step']:.0f}")
            else:
                row("pipeline/speedup", 0.0,
                    f"wave_vs_serial="
                    f"{r['speedup_wave_vs_serial']:.2f}x")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        srows = bench_service.run()
        trajectory["service"] = srows
        for r in srows:
            if r["bench"] == "service":
                extra = (f" occ={r['occupancy']:.2f}"
                         if "occupancy" in r else "")
                row(f"service/{r['mode']}", r["t_s"],
                    f"qps={r['qps']:.2f}{extra}")
            else:
                row("service/speedup", 0.0,
                    f"batch_vs_serial_loop="
                    f"{r['speedup_batch_vs_serial_loop']:.2f}x "
                    f"batch_vs_wave_loop="
                    f"{r['speedup_batch_vs_wave_loop']:.2f}x")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        strows = bench_streaming.run()
        trajectory["streaming"] = strows
        for r in strows:
            if r["bench"] == "streaming":
                row(f"streaming/{r['mode']}", r["t_s"],
                    f"qps={r['qps']:.2f} occ={r['occupancy']:.2f}")
            elif r["bench"] == "streaming_ingest":
                row("streaming/ingest", r["t_s"],
                    f"qps={r['qps']:.2f} epochs={r['epochs_ingested']} "
                    f"p95={r['p95_ms']:.0f}ms "
                    f"midflight={r['admitted_midflight']}")
            else:
                row("streaming/speedup", 0.0,
                    f"clustered_vs_union="
                    f"{r['speedup_clustered_vs_union']:.2f}x "
                    f"(union_E={r['union_window_edges']} "
                    f"cluster_E<={r['max_cluster_window_edges']})")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        # cache gate: warm-vs-cold equivalence (bit-identity, including
        # across interleaved ingest epochs), a warm-speedup floor and a
        # hit-rate floor — the module raises on any violation, so a
        # stale or dead cache fails the harness like a wrong core would
        carows = bench_cache.run()
        trajectory["cache"] = carows
        for r in carows:
            if r["bench"] == "cache":
                extra = (f" hit_rate={r['hit_rate']:.2f}"
                         if "hit_rate" in r else "")
                row(f"cache/{r['mode']}", r["t_s"],
                    f"qps={r['qps']:.2f}{extra}")
            elif r["bench"] == "cache_ingest":
                row("cache/ingest", r["t_s"],
                    f"epochs={r['epochs']} verified={r['verified']} "
                    f"invalidated={r['invalidated']} "
                    f"rekeyed={r['rekeyed']} "
                    f"equivalent={r['equivalent']}")
            else:
                row("cache/speedup", 0.0,
                    f"warm_vs_cold={r['speedup_warm_vs_cold']:.2f}x "
                    f"hit_rate={r['hit_rate']:.2%} "
                    f"gate_ok={r['gate_ok']}")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        # chaos gate: every fault scenario must stay bit-identical to
        # the fault-free run (the module raises otherwise), so injected
        # kernel failures / corruption / crashes fail the harness just
        # like a wrong core would
        crows = bench_chaos.run()
        trajectory["chaos"] = crows
        for r in crows:
            if r["bench"] == "chaos":
                row(f"chaos/{r['scenario']}/s{r['seed']}",
                    r.get("wall_s", 0.0),
                    f"equivalent={r['equivalent']} "
                    f"demotions={r.get('demotions', 0)}")
            else:
                row("chaos/overload", r["wall_s"],
                    f"shed_rate={r['shed_rate']:.2f} "
                    f"p99={r['p99_ms']:.0f}ms "
                    f"timeouts={r['timeouts']}")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        # durability gate: the kill-anywhere drill (crash after every
        # journal record + torn/corrupt post-mortems) must recover to a
        # bit-identical drain over each surviving prefix — the module
        # raises on any divergence, lost admission, or lineage mismatch
        wrows = bench_chaos.run_durability()
        trajectory["durability"] = wrows
        for r in wrows:
            if r["scenario"] == "kill":
                row(f"durability/kill@{r['crash_after_record']}",
                    r["recover_s"],
                    f"tail={r['tail_records']} "
                    f"requeued={r['requeued']} "
                    f"equivalent={r['equivalent']}")
            elif r["scenario"] == "summary":
                row("durability/summary", r["max_recover_s"],
                    f"records={r['journal_records']} "
                    f"kill_points={r['kill_points']}")
            else:
                row(f"durability/{r['scenario']}", r["recover_s"],
                    f"tail={r['tail_records']} "
                    f"skipped_snaps={r.get('snapshots_skipped', 0)} "
                    f"equivalent={r['equivalent']}")
    except Exception:
        failures += 1
        traceback.print_exc()

    try:
        # distributed gate: every mesh shape must stay bit-identical to
        # the single-device drain, and the best shape must clear the
        # aggregate-qps floor (the module raises on either violation)
        drows = bench_distributed.run()
        trajectory["distributed"] = drows
        for r in drows:
            if r["bench"] == "distributed":
                row(f"distributed/{r['mesh']}", r["t_s"],
                    f"qps={r['qps']:.2f} speedup={r['speedup']:.2f}x "
                    f"eff={r['efficiency']:.2f} "
                    f"combine={r['combine']} "
                    f"equivalent={r['equivalent']}")
            else:
                row("distributed/speedup", 0.0,
                    f"best={r['best_mesh']} "
                    f"{r['speedup']:.2f}x floor={r['floor']}x "
                    f"gate_ok={r['gate_ok']}")
    except Exception:
        failures += 1
        traceback.print_exc()

    # only a complete trajectory may replace the tracked file — a partial
    # write would clobber the last good cross-PR history (and smoke-sized
    # runs never overwrite the measured numbers)
    if not SMOKE and \
            {"wave", "kernel", "pipeline", "service", "streaming",
             "cache", "chaos", "durability",
             "distributed"} <= trajectory.keys():
        out = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_wave.json")
        with open(out, "w") as f:
            json.dump(trajectory, f, indent=1, default=str)

    if failures:
        print(f"# {failures} bench module(s) failed", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
