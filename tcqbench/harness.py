"""One run of one cell: build the deployment, warm it up, drive open-loop
traffic for the window, check the answers against the plain reference, and
print the result line.

Set-up builds the graph from the seed (or loads it from the checkout's graph
cache; see ``base_seed`` for what the seed draws), starts a ``TCQService`` with the configuration's settings, and serves
the mix's warm-up rounds: lane-pool widths, capacity classes and the refill,
warm-start and pack programs compile here.  JAX's persistent compile cache is
pointed inside the checkout (``tcqbench/.cache/jax``), where the programs of
the graph upload land, and switched off before the warm-up serves its first
request: the program compiles a step for every window it peels, with the
window's tables inside, so no such program serves another seed's graph, and
a run whose warm-up read its programs from the cache served its window 15%
slower than one that compiled them.  So a window the program compiles for is
compiled in every run, as a deployment compiles for every window its users
ask for first; the timed windows are never among the warm-up windows.

The window offers the mix's requests at their due times through the
service's ``poll`` hook, whatever the service is doing, and times each from
its due time to its ticket's completion.  After the close the run waits
up to ``GRACE_S`` for requests still in flight; one that never completes is
failed.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from tcqbench import graphgen, reference
from tcqbench import trace as tracemod
from tcqbench import traffic, workmodel
from tcqbench.registry import HERE, Registry

REPO = HERE.parent
JAX_CACHE = HERE / ".cache" / "jax"
TRACE_DIR = HERE / ".cache" / "trace"
GRACE_S = 60.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def configure_jax():
    import jax

    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def check_chip(jax, chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def disable_persistent_cache(jax) -> None:
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


class CompileLog:
    """Backend compiles, from JAX's monitoring events (wall clock)."""

    def __init__(self):
        import jax.monitoring

        self.spans: List[tuple] = []

        def listen(event, start, end, **kw):
            if event == COMPILE_EVENT:
                self.spans.append((start, end, kw.get("fun_name", "?")))

        self._listen = listen
        jax.monitoring.register_event_time_span_listener(listen)

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_time_span_listener(self._listen)

    def within(self, lo: float, hi: float) -> List[tuple]:
        return [s for s in self.spans if lo <= s[0] <= hi]


def base_seed(mix: dict, seed: int) -> int:
    """The seed that builds the graph and draws the schedule.  A mix whose
    ``seed_draws`` is ``order`` takes the run's seed for both: each seed
    has its own graph and its own order of the requests.  One whose
    ``seed_draws`` is ``labels`` takes 0, so every seed meets the same graph
    and the same requests in the same order, and the run's seed relabels
    the graph's vertices and reorders its edges (``graphgen.relabel``)."""
    draws = mix["seed_draws"]
    if draws not in ("order", "labels"):
        raise ValueError(f"unknown seed_draws {draws!r}")
    return seed if draws == "order" else 0


def inputs(reg: Registry, cfg: dict, mix: dict, seed: int, seconds: float):
    """A run's inputs: ``((u, v, t, cached), warm-up rounds, timed
    requests)``, the same for every run of the seed."""
    g = cfg["graph"]
    base = base_seed(mix, seed)
    u, v, t, cached = graphgen.load_or_generate(cfg["name"], g, base,
                                                reg.root / ".cache" / "graphs")
    if base != seed:
        u, v, t = graphgen.relabel(u, v, t, int(g["num_vertices"]), seed)
    times = np.unique(t)
    rounds = traffic.warmup(mix, times, base)
    reqs = traffic.schedule(mix, times, seconds, base,
                            exclude=traffic.warmup_windows(rounds))
    return (u, v, t, cached), rounds, reqs


def _import_program():
    src = str(REPO / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.core import TCQService, TemporalGraph

    return TCQService, TemporalGraph


def _request(r: dict) -> dict:
    return {"k": r["k"], "h": r["h"], "ts": r["ts"], "te": r["te"]}


def _digest(tk) -> Dict[tuple, tuple]:
    return reference.digest({c.tti: (c.vertices, c.n_edges)
                             for c in tk.result.cores})


def drive(svc, reqs: List[dict], seconds: float,
          span: Callable[[str], contextlib.AbstractContextManager]):
    """Offer ``reqs`` open loop; returns (open, close, end, due, tickets),
    times on ``time.perf_counter``'s clock."""
    n = len(reqs)
    t_open = time.perf_counter()
    close = t_open + seconds
    deadline = close + GRACE_S
    due = [t_open + r["arrive_s"] for r in reqs]
    tickets: List = [None] * n
    nxt = 0

    def poll(s) -> None:
        nonlocal nxt
        now = time.perf_counter()
        while nxt < n and due[nxt] <= now:
            with span("submit"):
                tickets[nxt] = s.submit(_request(reqs[nxt]))
            nxt += 1

    while True:
        poll(svc)
        now = time.perf_counter()
        if svc.pending and now < deadline:
            with span("pump"):
                svc.pump(poll)
        elif nxt < n:
            with span("idle"):
                time.sleep(max(0.0, due[nxt] - now))
        else:
            break
    end = time.perf_counter()
    if end < close:
        with span("idle"):
            time.sleep(close - end)
        end = time.perf_counter()
    return t_open, close, end, due, tickets


def compare(answers: List[Optional[Dict[tuple, tuple]]], reqs: List[dict],
            u, v, t) -> Dict[str, dict]:
    """Every request's answer (a digest, or None where none came) against
    the plain reference's over the same graph: the numbers compared, each
    with its limit.  A run is correct where none exceeds its limit."""
    missing = sum(a is None for a in answers)
    wrong = sum(a is not None and a != reference.digest(reference.tcq(
        u, v, t, r["k"], r["h"], r["ts"], r["te"]))
        for a, r in zip(answers, reqs))
    return {"wrong_answers": {"value": wrong, "limit": 0},
            "missing_answers": {"value": missing, "limit": 0}}


def is_correct(compared: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def _ticket_record(tk, due: float) -> dict:
    done = tk is not None and tk.status == "done"
    return {
        "due": due,
        "submit_s": None if tk is None else tk.submit_s,
        "admit_s": None if tk is None else tk.admit_s,
        "done_s": tk.done_s if done else None,
        "stats": dataclasses.asdict(tk.result.stats) if done else None,
    }


def _percentile(values, q):
    return float(np.quantile(np.asarray(values, dtype=np.float64), q))


def run(workload: str, seed: int, seconds: float, traced: bool, *,
        registry: Optional[Registry] = None, require_chip: bool = True,
        t_start: Optional[float] = None, log=None) -> dict:
    """One run; returns the result line's object.  Raises ``NoChip`` before
    any work when ``require_chip`` and JAX finds no TPU."""
    t_start = time.time() if t_start is None else t_start
    reg = registry or Registry()
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = reg.workload(workload)
    cfg = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"], cell["config"])
    if require_chip:
        jax = configure_jax()
        devices = check_chip(jax, int(cell["chips"]))
        peaks = reg.peaks(devices[0].device_kind)
    else:
        import jax

        devices, peaks = jax.devices(), None
    compiles = CompileLog()
    TCQService, TemporalGraph = _import_program()

    # ---- set-up
    phases = {}
    t0 = time.perf_counter()
    g = cfg["graph"]
    (u, v, t, cached), rounds, reqs = inputs(reg, cfg, mix, seed, seconds)
    phases["graph"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    graph = TemporalGraph.from_edges(u, v, t, int(g["num_vertices"]))
    svc = TCQService(graph, **cfg["service"])
    phases["service"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    disable_persistent_cache(jax)
    for rnd in rounds:
        for r in rnd:
            svc.submit(_request(r))
        svc.run_until_idle()
    warm_pools = list(svc.pool_log)
    svc.pool_log.clear()
    svc.completed.clear()
    phases["warmup"] = time.perf_counter() - t0
    engine0 = svc.stats

    # ---- window
    span = (lambda name: jax.profiler.TraceAnnotation(
        tracemod.SPAN_PREFIX + name)) if traced else \
        (lambda name: contextlib.nullcontext())
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    setup_s = time.time() - t_start
    wall_open = time.time()
    with span("window"):
        t_open, close, end, due, tickets = drive(svc, reqs, seconds, span)
    wall_end = time.time()
    events = None
    if traced:
        jax.profiler.stop_trace()
        path = tracemod.find_xplane(str(TRACE_DIR))
        events = tracemod.flatten(path) if path else []
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    compiles.close()
    stats = devices[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    # ---- what the window produced, then free the program's state
    recs = [_ticket_record(tk, d) for tk, d in zip(tickets, due)]
    answers = [_digest(tk) if r["done_s"] is not None else None
               for tk, r in zip(tickets, recs)]
    pools = [dict(p) for p in svc.pool_log]
    engine1 = svc.stats
    del svc, tickets, graph
    gc.collect()

    # ---- the comparison with the reference
    t0 = time.perf_counter()
    n = len(reqs)
    compared = compare(answers, reqs, u, v, t)
    missing = compared["missing_answers"]["value"]
    wrong = compared["wrong_answers"]["value"]
    correct = is_correct(compared)
    ref_s = time.perf_counter() - t0

    # ---- end-to-end metrics
    lat = [(r["done_s"] - r["due"]) * 1e3 for r in recs
           if r["done_s"] is not None]
    on_time = sum(r["done_s"] is not None and r["done_s"] <= close
                  for r in recs)
    e2e = {
        "latency_p50_ms": _percentile(lat, 0.5) if lat else None,
        "latency_mean_ms": float(np.mean(lat)) if lat else None,
        "setup_s": setup_s,
    }
    units = {m["name"]: m["unit"] for kind in ("end_to_end", "per_layer")
             for m in reg.benchmark()[kind]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}

    # ---- per-layer metrics (traced run)
    run_rec = {"tickets": recs, "pools": pools, "peaks": peaks,
               "compiles": compiles.within(wall_open, wall_end),
               "trace": None}
    breakdown = None
    if traced:
        for p in pools:
            e, pr, vx = workmodel.window_counts(u, v, t, *p["window"])
            p["least_bytes_per_step"] = workmodel.step_bytes(
                e, pr, vx, p["wave"], int(g["num_vertices"]))
        win = tracemod.window(events)
        if win is not None:
            run_rec["trace"] = {"events": events, "window": win}
            busy = tracemod.busy_seconds(events, win)
            device["busy_s"] = busy if busy is not None else 0.0
            device["window_s"] = (win[1] - win[0]) / 1e9
            breakdown = {
                "device_ops": [list(x) for x in tracemod.top_ops(events, win)],
                "idle_gaps": [list(x) for x in
                              tracemod.idle_gaps(events, win)[:10]],
            }
        names = [m["name"] for m in reg.metrics(workload, "per_layer")]
        wanted = {name: reg.reader(name)(run_rec) for name in names}
    else:
        names = [m["name"] for m in reg.metrics(workload, "end_to_end")]
        wanted = {name: e2e[name] for name in names}
    metrics = {k: {"value": float(x), "unit": units[k]}
               for k, x in wanted.items() if x is not None}

    # ---- lines for the reader of the run
    late = [(r["submit_s"] - r["due"]) * 1e3 for r in recs
            if r["submit_s"] is not None]
    log(f"[tcqbench] {workload} seed={seed} graph "
        f"{'loaded' if cached else 'generated'} in {phases['graph']:.3f} s, "
        f"service {phases['service']:.3f} s, warm-up {phases['warmup']:.3f} s "
        f"({len(warm_pools)} pools), setup_s={setup_s:.3f}")
    log(f"[tcqbench] requests due={n} done={n - missing} on_time={on_time} "
        f"latency samples={len(lat)} pools={len(pools)} backends="
        f"{sorted({p['backend'] for p in pools})} compiles_in_window="
        f"{len(run_rec['compiles'])} window_s={end - t_open:.3f}")
    if run_rec["compiles"]:
        names = collections.Counter(c[2] for c in run_rec["compiles"])
        log(f"[tcqbench] compiled in window: {dict(names)}")
    if lat:
        log(f"[tcqbench] latency ms: p50={_percentile(lat, .5):.3f} "
            f"mean={np.mean(lat):.3f} p95={_percentile(lat, .95):.3f} "
            f"max={max(lat):.3f}")
    if late:
        log(f"[tcqbench] generator lateness ms: p50={_percentile(late, .5):.3f}"
            f" p95={_percentile(late, .95):.3f} max={max(late):.3f}")
    if pools:
        walls = [p["wall_s"] for p in pools]
        steps = [p["device_steps"] for p in pools]
        log(f"[tcqbench] pools: wall_s mean={np.mean(walls):.3f} "
            f"max={max(walls):.3f}, steps mean={np.mean(steps):.1f} "
            f"max={max(steps)}, lanes={sorted({p['wave'] for p in pools})}, "
            f"members max={max(p['members'] for p in pools)}")
        slow = sorted(pools, key=lambda p: -p["wall_s"])[:3]
        log("[tcqbench] slowest pools (wall_s, steps, window, edges): "
            + "; ".join(f"{p['wall_s']:.3f}, {p['device_steps']}, "
                        f"{tuple(p['window'])}, {p['window_edges']}"
                        for p in slow))
    log(f"[tcqbench] engine window-TEL before={engine0['window_tel']} "
        f"after={engine1['window_tel']}")
    log(f"[tcqbench] reference checked {n} of {n} requests in "
        f"{ref_s:.3f} s")
    for name, c in compared.items():
        log(f"[tcqbench] compared {name}={c['value']} limit={c['limit']}")
    out = {"correct": correct, "attempted": n, "failed": missing + wrong,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="python3 -m tcqbench",
                                 description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # libtpu logs to /tmp unless told otherwise; a run writes only inside
    # its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start=t_start)
    except NoChip as e:
        print(f"tcqbench: {e}; refusing to run", file=sys.stderr)
        return 3
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0
