"""Beyond-paper engine benches: wave width scaling, Pallas kernel vs XLA
segment-sum degree path, peel-iteration counts, and the fused wave-peel
step (``run_kernel``: bit-identity gate + structured HLO cost-model
deltas fused vs unfused — feeds the roofline's per-iteration cost model
and BENCH_wave.json's ``kernel`` section)."""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core.wave import make_segsum_fns, tcd_wave

from benchmarks.common import GRAPH_K, emit, engine, graph, pick_queries, \
    timeit


def run(name: str = "collegemsg"):
    g = graph(name)
    eng = engine(name)
    k = GRAPH_K[name]
    q = pick_queries(name, 1, span_uts=120, seed=3)[0]
    rows = []
    for wave in (1, 4, 16, 64):
        mode = "serial" if wave == 1 else "wave"
        kw = {} if wave == 1 else {"mode": "wave", "wave": wave}
        t = timeit(lambda: eng.query(k, q["ts"], q["te"], **kw), repeat=2)
        res = eng.query(k, q["ts"], q["te"], **kw)
        rows.append({"bench": "wave_width", "graph": name, "wave": wave,
                     "t_s": t, "device_steps": res.stats.device_steps,
                     "cells": res.stats.cells_evaluated,
                     "n_cores": len(res)})

    # kernel-vs-XLA degree path on a standalone wave
    tel = g.device_tel()
    uts = g.unique_ts
    qn = 16
    rng = np.random.default_rng(0)
    idx = rng.integers(0, uts.size - 10, qn)
    ts = jnp.asarray(uts[idx], jnp.int32)
    te = jnp.asarray(uts[np.minimum(idx + 80, uts.size - 1)], jnp.int32)
    alive = jnp.ones((qn, g.num_vertices), bool)
    for use_kernel, label in ((False, "xla_segsum"), (True, "pallas")):
        sp, sv = make_segsum_fns(g, use_kernel=use_kernel)

        def go():
            r = tcd_wave(tel, alive, ts, te, k, 1,
                         num_vertices=g.num_vertices,
                         seg_pair=sp, seg_vert=sv)
            r.alive.block_until_ready()
            return r

        t = timeit(go, repeat=2)
        r = go()
        rows.append({"bench": "degree_path", "graph": name, "path": label,
                     "t_s": t, "iters": int(r.iters),
                     "note": "pallas runs interpret-mode on CPU; the TPU "
                             "comparison is structural (see EXPERIMENTS)"})
    emit("bench_wave", rows)
    return rows


def analyze_fused_step(name: str = "collegemsg", wave: int = 16,
                       seed: int = 0) -> dict:
    """Fused-Pallas vs XLA-composite wave step on one seeded mixed wave.

    Runs both lowerings (the fused kernel in interpret mode on CPU — the
    same kernel body the TPU compiles) and RAISES on any bit divergence;
    then builds the structural cost comparison: the unfused chain's
    per-iteration HBM bytes/FLOPs from the compiled HLO (launch/hlo_cost
    while-body accounting) vs the fused kernel's analytic model, whose
    HBM bytes are iteration-independent.  Cost numbers are valid on CPU —
    they describe the lowerings, not the host — which is why they (and
    not interpret-mode wall-clock) are the regression gate.
    """
    from repro.core.wave import make_wave_step_fn
    from repro.kernels.segdeg.ops import on_tpu
    from repro.kernels.wave_peel.ops import fused_step_cost
    from repro.launch.hlo_cost import HLOCost

    g = graph(name)
    tel = g.device_tel()
    v = g.num_vertices
    e = int(tel.t.shape[0])
    p = int(tel.pair_u.shape[0])
    fused = make_wave_step_fn(tel, v, use_kernel=True)
    comp = make_wave_step_fn(tel, v, use_kernel=False)

    rng = np.random.default_rng(seed)
    uts = g.unique_ts
    idx = rng.integers(0, max(1, uts.size - 90), wave)
    ts = jnp.asarray(uts[idx], jnp.int32)
    te = jnp.asarray(uts[np.minimum(idx + 80, uts.size - 1)], jnp.int32)
    k = jnp.asarray(rng.integers(2, 5, wave), jnp.int32)
    h = jnp.asarray(rng.integers(1, 3, wave), jnp.int32)
    alive = jnp.ones((wave, v), dtype=bool)

    def go_fused():
        r = fused(alive, ts, te, k, h)
        r.alive.block_until_ready()
        return r

    def go_comp():
        r = comp(alive, ts, te, k, h)
        r.alive.block_until_ready()
        return r

    t_fused = timeit(go_fused, repeat=2)
    t_comp = timeit(go_comp, repeat=2)
    rf, rc = go_fused(), go_comp()
    for field in ("alive", "packed", "tti_lo", "tti_hi", "n_edges", "iters"):
        a = np.asarray(getattr(rf, field))
        b = np.asarray(getattr(rc, field))
        if not np.array_equal(a, b):
            raise RuntimeError(
                f"fused wave-peel kernel diverges from the XLA composite "
                f"on {field} (graph={name}, seed={seed})")
    iters = int(rf.iters)

    # unfused chain (the served XLA composite, its shared class program
    # over this window's tables): compiled HLO, while-body per-iteration
    # accounting (the dynamic fixpoint cond has no static trip count, so
    # the module total counts the body once; N iterations add (N-1) x body)
    hlo = comp.jitted.lower(*comp.window_args, alive, ts, te, k,
                            h).compile().as_text()
    hc = HLOCost(hlo)
    # only dynamic-condition loops (the fixpoint) scale with iters; their
    # bodies already fold in any nested counted loops (scatter lowerings)
    bodies = [v for v in hc.while_bodies().values() if v["dynamic"]]
    flops_it = sum(b["flops"] for b in bodies)
    bytes_it = sum(b["bytes"] for b in bodies)
    unfused_bytes = hc.bytes + (iters - 1) * bytes_it
    unfused_flops = hc.flops + (iters - 1) * flops_it
    # [W, E] / [E, W] HBM materializations in the unfused lowering (the
    # lanes' window and edge-activity masks over the class's edge rows)
    e_rows = comp.rows[0]
    we_census = (hc.shape_census((wave, e_rows))
                 + hc.shape_census((e_rows, wave)))

    fc = fused_step_cost(*fused.local_counts, wave=wave, iters=iters)
    # structural [W, E] check on the fused side: the kernel's only HBM
    # operands are the 1-D index tables and the [V_loc, W_pad] lane slab
    fused_we = sum(1 for s in getattr(fused, "operand_shapes", [])
                   if len(s) == 2 and set(s) == {wave, e} and e != wave)
    if fc["bytes_per_iter_hbm"] > 0:
        fused_we += 1

    return {
        "graph": name, "wave": wave, "iters": iters, "seed": seed,
        "num_edges": e, "num_pairs": p, "num_vertices": v,
        "backend": fused.backend, "interpret": bool(fused.interpret),
        "compiled_tpu": bool(on_tpu()),
        "t_fused_s": t_fused, "t_composite_s": t_comp,
        "unfused_bytes_step": unfused_bytes,
        "unfused_bytes_per_iter": bytes_it,
        "unfused_flops_step": unfused_flops,
        "unfused_we_materializations": we_census,
        "fused_bytes_step": fc["bytes_per_step"],
        "fused_bytes_per_iter_hbm": fc["bytes_per_iter_hbm"],
        "fused_flops_step": fc["flops_per_step"],
        "fused_vmem_bytes": fc["vmem_bytes"],
        "fused_we_materializations": fused_we,
        "bytes_ratio": fc["bytes_per_step"] / max(unfused_bytes, 1.0),
    }


def run_kernel(name: str = "collegemsg") -> list:
    """The fused_step bench + gates.  Raises RuntimeError on fused-vs-
    composite divergence or if the fused lowering's modeled bytes/step is
    not strictly below the unfused chain's.  Interpret-mode wall-clock is
    recorded for context but is explicitly NOT the gate (on CPU the
    kernel runs under the Pallas interpreter; the TPU compiles it)."""
    info = analyze_fused_step(name)
    if not info["fused_bytes_step"] < info["unfused_bytes_step"]:
        raise RuntimeError(
            "fused wave-peel kernel does not win on modeled HBM bytes/step: "
            f"fused={info['fused_bytes_step']:.0f} vs "
            f"unfused={info['unfused_bytes_step']:.0f}")
    if info["unfused_we_materializations"] <= 0:
        raise RuntimeError(
            "unfused-lowering census found no [W, E] HBM materializations — "
            "the cost baseline is not measuring the chain it claims to")
    if info["fused_we_materializations"] != 0:
        raise RuntimeError(
            "fused lowering still materializes [W, E] arrays in HBM")
    note = ("compiled TPU wall-clock" if info["compiled_tpu"] else
            "interpret-mode wall-clock on CPU — context only, NOT the gate")
    rows = [
        {"bench": "fused_step", "graph": name, "path": "fused_pallas",
         "t_s": info["t_fused_s"], "iters": info["iters"],
         "wave": info["wave"], "backend": info["backend"],
         "interpret": info["interpret"], "note": note},
        {"bench": "fused_step", "graph": name, "path": "xla_composite",
         "t_s": info["t_composite_s"], "iters": info["iters"],
         "wave": info["wave"], "backend": "xla", "interpret": False,
         "note": "XLA wall-clock on the current host"},
        dict(info, bench="fused_step_cost",
             gate="bit-identity + fused_bytes_step < unfused_bytes_step",
             gate_ok=True),
    ]
    emit("bench_kernel", rows)
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
    for r in run_kernel():
        print(r)
