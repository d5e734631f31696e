#!/usr/bin/env python3
"""Chip smoke test: serve real TCQ requests on a TPU and check the answers.

    python chip_smoke.py          # one chip: TCQService -> WavePipeline ->
                                  # fused wave-peel kernel, end to end
    python chip_smoke.py --mesh   # four chips: the sharded service on a
                                  # 4x1 and a 2x2 mesh vs the one-chip one

The one-chip phase builds a MathOverflow-shaped temporal graph (paper
Table 2, SNAP sx-mathoverflow: 24,576 vertices, 507,904 generated edges,
span 65,536) from a seed, starts a ``TCQService`` with its defaults
(auto-dispatched kernel, no degradation ladder, core cache on), and serves
six ``(k, h, [Ts, Te])`` requests with one ingest batch between them, so
an epoch swap happens on the chip.  Every answer must equal a second
service on the same chip that runs the XLA composite step, and the
narrow-window request must equal the brute-force oracle on the host.
Every pool must have run the compiled Pallas kernel.

The script refuses to run without a TPU, starts no other process, and
prints one JSON line last: ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0

# MathOverflow shape class (configs/tcq.py "tcq-mathoverflow")
GRAPH = {"num_vertices": 24_576, "num_edges": 507_904, "time_span": 65_536}

# (k, h, ts, te); the third is the narrow window the host oracle checks.
# Requests before the ingest batch pin epoch 0, the rest epoch 1.
BEFORE_INGEST = [(2, 1, 1000, 1149), (3, 1, 1000, 1149), (2, 1, 30000, 30019)]
AFTER_INGEST = [(2, 1, 40000, 40127), (3, 1, 40000, 40127),
                (2, 2, 10000, 10127)]
ORACLE_REQUEST = 2
INGEST = {"edges": 1_000, "ts": 40000, "te": 40127}


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def make_graph(spec=GRAPH, seed=SEED):
    from repro.graphs import powerlaw_temporal

    return powerlaw_temporal(spec["num_vertices"], spec["num_edges"],
                             spec["time_span"], seed=seed)


def make_ingest(num_vertices, spec=INGEST, seed=SEED):
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    w = np.arange(1, num_vertices + 1, dtype=np.float64) ** -1.5
    w /= w.sum()
    u = rng.choice(num_vertices, spec["edges"], p=w)
    v = rng.choice(num_vertices, spec["edges"], p=w)
    t = rng.integers(spec["ts"], spec["te"] + 1, spec["edges"])
    return u, v, t


def serve(svc, ingest, before=BEFORE_INGEST, after=AFTER_INGEST):
    """The smoke's request sequence on one service: the first request
    alone (it compiles), the rest of the first epoch, one ingest batch,
    then the second epoch's requests.  Returns (tickets, first_s)."""
    def submit(reqs):
        return [svc.submit({"k": k, "h": h, "ts": ts, "te": te})
                for k, h, ts, te in reqs]

    t0 = time.perf_counter()
    tickets = submit(before[:1])
    svc.run_until_idle()
    first_s = time.perf_counter() - t0
    tickets += submit(before[1:])
    svc.push_edges(*ingest)
    tickets += submit(after)
    svc.run_until_idle()
    for tk in tickets:
        check(tk.status == "done", f"request {tk.id} ended {tk.status}")
    return tickets, first_s


def digest(ticket):
    return {tti: (tuple(c.vertices.tolist()), int(c.n_edges))
            for tti, c in ticket.result.by_tti().items()}


def check_same(got, want, what):
    for a, b in zip(got, want):
        check(digest(a) == digest(b),
              f"request {a.id} ({a.k}, {a.h}, [{a.ts}, {a.te}]) differs "
              f"from {what}")


def check_oracle(ticket):
    from repro.core.oracle import brute_force_query

    want = brute_force_query(ticket.graph, ticket.k, ticket.ts, ticket.te,
                             ticket.h)
    got = digest(ticket)
    check(got.keys() == want.keys(),
          f"request {ticket.id}: TTIs differ from the host oracle")
    for tti, (verts, n_edges) in got.items():
        check(set(verts) == set(want[tti]["vertices"])
              and n_edges == want[tti]["n_edges"],
              f"request {ticket.id}: core {tti} differs from the oracle")
    return len(want)


def report(name, svc, tickets):
    for tk in tickets:
        print(f"[smoke] {name} request {tk.id}: k={tk.k} h={tk.h} "
              f"window=[{tk.ts}, {tk.te}] epoch={tk.epoch} "
              f"cores={len(tk.result.cores)} latency_s={tk.latency_s:.3f}")
    for rec in svc.pool_log:
        print(f"[smoke] {name} pool window={list(rec['window'])} "
              f"epoch={rec['epoch']} members={rec['members']} "
              f"wave={rec['wave']} steps={rec['device_steps']} "
              f"backend={rec['backend']} interpret={rec['interpret']} "
              f"wall_s={rec['wall_s']:.3f}")


def one_chip_phase(graph, ingest):
    from repro.core import TCQService

    svc = TCQService(graph)
    tickets, first_s = serve(svc, ingest)
    print(f"[smoke] first (compiling) request: {first_s:.3f} s")
    report("kernel", svc, tickets)
    for rec in svc.pool_log:
        if rec["backend"] != "pallas":
            print(f"[smoke] pool {rec['window']} fell back to "
                  f"backend={rec['backend']} (kernel SMEM/VMEM budget)")
        check(rec["backend"] == "pallas" and rec["interpret"] is False,
              f"pool {rec['window']} ran backend={rec['backend']} "
              f"interpret={rec['interpret']}, not the compiled kernel")
    check(svc.epoch == 1, "the ingest batch did not swap the epoch")

    ref = TCQService(graph, use_kernel=False)
    ref_tickets, _ = serve(ref, ingest)
    check(all(r["backend"] == "xla" for r in ref.pool_log),
          "reference service did not run the XLA composite")
    check_same(tickets, ref_tickets, "the on-chip XLA composite")
    print(f"[smoke] all {len(tickets)} answers equal the on-chip "
          "XLA composite")
    n = check_oracle(tickets[ORACLE_REQUEST])
    print(f"[smoke] request {tickets[ORACLE_REQUEST].id} equals the host "
          f"oracle ({n} cores)")


def mesh_phase(graph, ingest):
    import jax

    from repro.core import TCQService
    from repro.launch.mesh import make_mesh

    check(len(jax.devices()) >= 4, "--mesh needs four chips")
    base = TCQService(graph)
    want, _ = serve(base, ingest)
    report("one-chip", base, want)
    # 4x1: lanes sharded, the fused kernel per shard; 2x2: TEL sharded
    # over "model", degrees combined across shards by the XLA step
    for shape, backend in (((4, 1), "pallas"), ((2, 2), "xla_sharded")):
        name = f"{shape[0]}x{shape[1]}"
        mesh = make_mesh(shape, ("data", "model"), devices=jax.devices()[:4])
        svc = TCQService(graph, mesh=mesh)
        t0 = time.perf_counter()
        got, first_s = serve(svc, ingest)
        print(f"[smoke] mesh {name}: served in "
              f"{time.perf_counter() - t0:.3f} s (first request "
              f"{first_s:.3f} s)")
        report(name, svc, got)
        check_same(got, want, "the one-chip service")
        eng = svc.engine
        edge_devs = {len(a.sharding.device_set) for a in eng._plan_arrays}
        pipe, _, _ = eng.make_pool(1000, 1149)
        lane_devs = len(pipe._new_slot().buf.sharding.device_set)
        print(f"[smoke] mesh {name}: lane buffer on {lane_devs} devices, "
              f"edge shards on {sorted(edge_devs)} devices, "
              f"backends {sorted({r['backend'] for r in svc.pool_log})}")
        check(lane_devs == 4 and edge_devs == {4},
              f"mesh {name}: arrays are not spread over four devices")
        check(all(r["backend"] == backend for r in svc.pool_log),
              f"mesh {name}: a pool did not run backend={backend}")
        print(f"[smoke] mesh {name}: all {len(got)} answers bit-identical "
              "to the one-chip service")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="run only the four-chip sharded-service phase")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke: the repro package is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); refusing to run", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = devices[0]
    print(f"[smoke] device {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    graph = make_graph()
    ingest = make_ingest(graph.num_vertices)
    print(f"[smoke] graph: {graph.num_vertices} vertices, "
          f"{GRAPH['num_edges']} generated edges, {graph.num_edges} "
          f"after self-loop removal, {graph.num_pairs} pairs, span "
          f"{graph.span}, built in {time.perf_counter() - t0:.3f} s")
    try:
        if args.mesh:
            mesh_phase(graph, ingest)
        else:
            one_chip_phase(graph, ingest)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    if "repro.launch.dryrun" in sys.modules:
        print("chip_smoke: repro.launch.dryrun was imported (it forces the "
              "CPU platform)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
