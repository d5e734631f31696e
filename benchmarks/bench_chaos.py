"""Chaos harness: seeded fault injection over the full serving stack,
gated on bit-identical results vs the fault-free run.

Every scenario replays the *same deterministic workload* (the
anti-union request set of ``bench_streaming``) through a ``TCQService``
whose engine runs the graceful-degradation ladder
(``ResilienceConfig``), with one fault class injected per scenario via
``core/faultinject.py``:

1. ``slow_lane`` — straggler steps (injected sleeps); results must not
   move, only latency.
2. ``kernel_vmem`` — the fused Pallas rung is built under a 1-byte VMEM
   budget (``interpret=False``) and is unavailable from the start: the
   ladder opens on the XLA rung and logs the demotion.
3. ``kernel_failure`` — the XLA rung raises an injected
   :class:`KernelFault` mid-pool; the ladder demotes to the oracle and
   replays the failed call bit-identically.
4. ``divergence`` — the XLA rung silently corrupts one vertex's alive
   bit; the sampled oracle tripwire catches it, quarantines the rung for
   the epoch, and replays on the oracle.
5. ``malformed_ingest`` — a stream of invalid edge batches (negative /
   overflowing / NaN / mismatched / sentinel-colliding) lands mid-run;
   each must raise :class:`GraphIngestError` and leave the graph (and
   every result) untouched.
6. ``midpool_cancel`` — one ticket is cancelled mid-pool and another
   expires via a past deadline; their lanes are reclaimed, both resolve
   with terminal statuses, and every *surviving* ticket stays
   bit-identical.
7. ``crash_restore`` — the service is snapshotted mid-queue, serialized
   through an in-memory ``.npz``, restored, and drained; the union of
   pre-crash and post-restore results must equal the uninterrupted run.

8. ``sharded_rung_fault`` — on an 8-virtual-device lane-sharded mesh
   with the fused kernel rung live, one window pool's Pallas rung takes
   an injected :class:`KernelFault` on its first call: *that* pool's
   :class:`~repro.core.distributed.ShardedDegradationLadder` demotes to
   the sharded XLA rung and replays, every other pool's ladder stays on
   the kernel, and the whole drain is bit-identical to the fault-free
   sharded run (subprocess, like ``bench_distributed`` — jax locks the
   device count at first init).

The **kill-anywhere durability drill** (:func:`run_durability`) extends
the crash scenario to the write-ahead journal (``core/wal.py``): a
deterministic op tape (admissions, ingest batches, a cancellation, a
checkpoint) is applied one entry per poll tick while pools drain, then
the drill kills the service *after every single journal record* (plus:
mid-checkpoint between segment rotation and snapshot write, a torn
tail, a flipped tail byte, a corrupted newest snapshot) and requires
``TCQService.recover`` + drain to be bit-identical to the uninterrupted
run over the surviving journal prefix — graph fingerprint included.
Recovery wall-clock vs journal-tail length forms the
``BENCH_wave.json["durability"]`` curve.

Any divergence raises (``assert_cores_equal``), so ``python -m
benchmarks.run`` — and the CI ``chaos_gate`` / ``wal_gate`` jobs
(``REPRO_CHAOS=1`` / ``REPRO_WAL_GATE=1`` widen the sweeps) — fail on a
broken recovery path exactly like a wrong core.  A final closed-loop
run at ~2x overload records the shed rate and p99 under backpressure
for the BENCH_wave.json ``chaos`` trajectory.
"""

from __future__ import annotations

import io
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmarks.bench_streaming import disjoint_requests
from benchmarks.common import SMOKE, assert_cores_equal, emit, graph

CHAOS = os.environ.get("REPRO_CHAOS", "") not in ("", "0")
SEEDS = (0, 1, 2) if CHAOS else (0,)


def _sig(reqs):
    return [(r["k"], r.get("h", 1), r["ts"], r["te"]) for r in reqs]


def _serve(svc, reqs, poll=None):
    tickets = [svc.submit(dict(r)) for r in reqs]
    svc.run_until_idle(poll)
    return tickets


def _gate(tickets, ref, *, skip=(), ctx=""):
    """Every non-skipped ticket bit-identical to the fault-free run."""
    for i, (tk, want) in enumerate(zip(tickets, ref)):
        if i in skip:
            continue
        assert_cores_equal(tk.result, want.result,
                           ctx=f"chaos[{ctx}] req#{i}")


def _events(svc):
    return svc.engine.resilience_events()


def run_scenarios(name: str, seed: int):
    from repro.core import ResilienceConfig, TCQService
    from repro.core.faultinject import (FaultPlan, KernelFault,
                                        malformed_batches, rung_faults)
    from repro.core.graph import GraphIngestError

    g = graph(name)
    reqs = disjoint_requests(name)
    rows = []

    def scenario(tag, fn):
        t0 = time.perf_counter()
        extra = fn()
        rows.append({"bench": "chaos", "scenario": tag, "graph": name,
                     "seed": seed, "n_queries": len(reqs),
                     "equivalent": True,      # the gates above raised
                     "wall_s": time.perf_counter() - t0, **(extra or {})})

    # fault-free reference (ladder on, no injection — the ladder itself
    # must be invisible when nothing fails)
    svc0 = TCQService(g, use_kernel=False,
                      resilience=ResilienceConfig(seed=seed))
    ref = _serve(svc0, reqs)
    assert not _events(svc0), _events(svc0)

    def slow_lane():
        cfg = ResilienceConfig(seed=seed, rung_wrapper=rung_faults(
            {"xla": FaultPlan(slow_at=(0, 2, 5), delay_s=0.02)}))
        svc = TCQService(g, use_kernel=False, resilience=cfg)
        _gate(_serve(svc, reqs), ref, ctx="slow_lane")
        assert not _events(svc), _events(svc)   # stragglers never demote
        return {"demotions": 0}
    scenario("slow_lane", slow_lane)

    def kernel_vmem():
        # fused rung built under an impossible VMEM budget (and
        # interpret=False so the budget check actually runs off-TPU):
        # unavailable from call zero, ladder opens on XLA
        cfg = ResilienceConfig(seed=seed, interpret=False,
                               vmem_budget_bytes=1)
        svc = TCQService(g, use_kernel=True, resilience=cfg)
        _gate(_serve(svc, reqs), ref, ctx="kernel_vmem")
        evs = _events(svc)
        assert evs and all(e["reason"] == "vmem_budget" for e in evs), evs
        return {"demotions": len(evs), "reason": "vmem_budget"}
    scenario("kernel_vmem", kernel_vmem)

    def kernel_failure():
        cfg = ResilienceConfig(seed=seed, rung_wrapper=rung_faults(
            {"xla": FaultPlan(fail_at=(1,))}))
        svc = TCQService(g, use_kernel=False, resilience=cfg)
        _gate(_serve(svc, reqs), ref, ctx="kernel_failure")
        evs = _events(svc)
        assert any(e["reason"] == "error" for e in evs), evs
        return {"demotions": len(evs), "reason": "error"}
    scenario("kernel_failure", kernel_failure)

    def divergence():
        cfg = ResilienceConfig(seed=seed, tripwire_every=1,
                               rung_wrapper=rung_faults(
                                   {"xla": FaultPlan(corrupt_at=(0,))}))
        svc = TCQService(g, use_kernel=False, resilience=cfg)
        _gate(_serve(svc, reqs), ref, ctx="divergence")
        evs = _events(svc)
        assert any(e["reason"] == "divergence" for e in evs), evs
        return {"demotions": len(evs), "reason": "divergence"}
    scenario("divergence", divergence)

    def malformed_ingest():
        svc = TCQService(g, use_kernel=False,
                         resilience=ResilienceConfig(seed=seed))
        bad = malformed_batches(seed)
        state = {"i": 0, "rejected": 0}

        def poll(s):
            if state["i"] < len(bad):
                u, v, t = bad[state["i"]]
                state["i"] += 1
                epoch0 = s.epoch
                try:
                    s.push_edges(u, v, t)
                except GraphIngestError:
                    state["rejected"] += 1
                assert s.epoch == epoch0     # rejected batch: no epoch

        tickets = _serve(svc, reqs, poll)
        # drain any batches the poll never reached (short pools)
        while state["i"] < len(bad):
            poll(svc)
        assert state["rejected"] == len(bad), (state, len(bad))
        _gate(tickets, ref, ctx="malformed_ingest")
        return {"batches_rejected": state["rejected"]}
    scenario("malformed_ingest", malformed_ingest)

    def midpool_cancel():
        svc = TCQService(g, use_kernel=False,
                         resilience=ResilienceConfig(seed=seed))
        tickets = [svc.submit(dict(r)) for r in reqs]
        # one already-expired deadline (times out at the first sweep) ...
        doomed = svc.submit({**reqs[0], "deadline_s": -1.0})
        state = {"polls": 0}

        def poll(s):
            state["polls"] += 1
            if state["polls"] == 2:          # mid-pool: lanes are live
                s.cancel(tickets[0])         # the widest (longest) member
        svc.run_until_idle(poll)
        assert doomed.status == "timeout" and doomed.done
        assert tickets[0].status == "cancelled" and tickets[0].done
        assert tickets[0].result is not None      # partial, not missing
        _gate(tickets, ref, skip={0}, ctx="midpool_cancel")
        return {"cancelled": 1, "timeouts": 1}
    scenario("midpool_cancel", midpool_cancel)

    def crash_restore():
        svc = TCQService(g, use_kernel=False,
                         resilience=ResilienceConfig(seed=seed))
        for r in reqs:
            svc.submit(dict(r))
        early = svc.pump()                   # some resolve pre-crash
        buf = io.BytesIO()
        svc.save_snapshot(buf)               # ... crash ...
        buf.seek(0)
        from repro.core import TCQService as Svc
        svc2 = Svc.load_snapshot(buf, use_kernel=False,
                                 resilience=ResilienceConfig(seed=seed))
        late = svc2.run_until_idle()
        by_id = {tk.id: tk for tk in early + late}
        assert len(by_id) == len(reqs), (sorted(by_id), len(reqs))
        for i in range(len(reqs)):
            assert_cores_equal(by_id[i].result, ref[i].result,
                               ctx=f"chaos[crash_restore] req#{i}")
        return {"resolved_precrash": len(early),
                "resolved_postrestore": len(late)}
    scenario("crash_restore", crash_restore)

    return rows


def run_overload(name: str):
    """Closed loop at ~2x overload: concurrency far above what the
    bounded queue admits, tight deadlines — records shed rate and p99
    under backpressure (the BENCH_wave.json ``chaos`` headline)."""
    from repro.launch.serve import serve_closed_loop

    g = graph(name)
    base = disjoint_requests(name)
    n = 12 if SMOKE else 24
    reqs = [dict(base[i % len(base)]) for i in range(n)]
    svc, tickets, rep = serve_closed_loop(
        g, reqs, concurrency=16, queue_cap=8, deadline_s=30.0)
    assert rep["completed"] + rep["shed"] + rep["timeouts"] == n, rep
    # bounded p99: the deadline is the latency ceiling — a completed
    # request can never have waited past it
    assert rep["p99_ms"] <= 30_000.0, rep
    return [{"bench": "chaos_overload", "graph": name, "n_queries": n,
             "overload_x": 2.0, **rep}]


# ------------------------------------------------- sharded per-shard fault
# Small/dense like bench_distributed's CFG: the point is ladder routing,
# not peel throughput.  Two far-apart window groups guarantee two pools,
# hence two independently built ShardedDegradationLadders.
_SHARDED_CFG = {"V": 64, "E": 192, "span": 128, "per_group": 4, "k": 2}

_SHARDED_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys; sys.path.insert(0, "src")
import json
import numpy as np, jax
from repro.core import ResilienceConfig, TCQService
from repro.core.faultinject import FaultPlan, FaultyStep
from repro.graphs import powerlaw_temporal
from repro.launch.mesh import make_mesh

cfg = json.loads(sys.argv[1])
g = powerlaw_temporal(cfg["V"], cfg["E"], cfg["span"], seed=9)
lo, hi = g.span
third = max(2, (hi - lo) // 3)
reqs = []                       # two disjoint groups -> two pools/ladders
for base in (lo, lo + 2 * third):
    for i in range(cfg["per_group"]):
        reqs.append(dict(k=cfg["k"], ts=int(base + i),
                         te=int(min(base + third - i, hi))))


def digest(tickets):
    return [sorted((k, tuple(c.vertices.tolist()), c.n_edges)
                   for k, c in t.result.by_tti().items())
            for t in sorted(tickets, key=lambda t: t.id)]


mesh = make_mesh((8, 1), ("data", "model"))   # lane-only: kernel rung up


def drain(wrapper):
    svc = TCQService(g, mesh=mesh, use_kernel=True, cache=False,
                     retain_snapshots=False,
                     resilience=ResilienceConfig(seed=0,
                                                 rung_wrapper=wrapper))
    for r in reqs:
        svc.submit(dict(r))
    out = svc.run_until_idle()
    return svc, digest(out)


_, want = drain(None)                              # fault-free sharded ref

state = {"armed": True}


def one_shot(name, fn):
    # ladders are built per window pool, so arming exactly one pallas
    # rung faults exactly one pool's shards — the per-shard fault
    if name == "pallas" and state["armed"]:
        state["armed"] = False
        return FaultyStep(fn, FaultPlan(fail_at=(0,)))
    return fn


svc, got = drain(one_shot)
evs = svc.engine.resilience_events()
demo = [e for e in evs if e.get("reason") == "error"]
assert not state["armed"], "fault never armed: no pallas rung was built"
assert len(demo) == 1, f"expected exactly one demotion, got {evs}"
assert got == want, "sharded drain diverged after per-shard rung fault"
backends = [p.get("backend") for p in svc.pool_log]
print("ROWS::" + json.dumps([{
    "bench": "chaos", "scenario": "sharded_rung_fault",
    "graph": "powerlaw64", "seed": 0, "devices": 8, "mesh": "8x1",
    "n_queries": len(reqs), "pools": len(svc.pool_log),
    "pool_backends": backends, "demotions": len(demo),
    "reason": "error", "equivalent": True}]))
"""


def run_sharded_fault() -> list:
    """Scenario 8 (subprocess: jax pins the device count at first init):
    one pool's Pallas rung faults on an 8-device lane-sharded mesh; only
    that pool's ladder demotes, the drain stays bit-identical."""
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_WORKER, json.dumps(_SHARDED_CFG)],
        capture_output=True, text=True, timeout=1800,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if out.returncode != 0:
        raise RuntimeError("sharded_rung_fault worker failed:\n"
                           + out.stderr[-3000:])
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("ROWS::")][-1]
    return json.loads(line[len("ROWS::"):])


# --------------------------------------------------- kill-anywhere drill
def _durability_ops(name: str):
    """The drill's deterministic op tape: admissions, a same-tick
    submit+cancel twin of the first request (pinned to epoch 0, before
    any ingest — if a crash lands *between* the submit and the cancel
    records, recovery legitimately runs the twin to completion and its
    result must equal the first request's), two ingest batches, and a
    mid-tape checkpoint."""
    g = graph(name)
    reqs = [dict(r) for r in disjoint_requests(name)]
    rng = np.random.default_rng(1234)
    V = int(g.num_vertices)
    uts = g.unique_ts
    lo, hi = int(uts[0]), int(uts[-1])

    def batch(n):
        u = rng.integers(0, V, size=n)
        v = (u + 1 + rng.integers(0, V - 1, size=n)) % V   # never self-loop
        t = rng.integers(lo, hi + 1, size=n)
        return (u.astype(np.int64), v.astype(np.int64), t.astype(np.int64))

    ops = [("submit", dict(reqs[0])),
           ("submit_cancel", dict(reqs[0]))]       # epoch-0 twin of reqs[0]
    ops += [("submit", dict(r)) for r in reqs[1:4]]
    ops += [("edges", batch(24)), ("checkpoint",)]
    ops += [("submit", dict(r)) for r in reqs[4:8]]
    ops += [("edges", batch(12))]
    return ops


def _drive_ops(svc, ops, tickets=None):
    """Apply one tape entry per poll tick while pools drain.  ``tickets``
    (id -> ticket) is filled *as submits land*, so a caller catching
    :class:`InjectedCrash` still sees everything admitted pre-crash."""
    tickets = {} if tickets is None else tickets
    state = {"i": 0}

    def poll(s):
        if state["i"] >= len(ops):
            return
        op = ops[state["i"]]
        state["i"] += 1
        if op[0] == "submit":
            tk = s.submit(dict(op[1]))
            tickets[tk.id] = tk
        elif op[0] == "submit_cancel":
            tk = s.submit(dict(op[1]))
            tickets[tk.id] = tk
            s.cancel(tk)
        elif op[0] == "edges":
            s.push_edges(*op[1])
        elif op[0] == "checkpoint" and s.wal is not None:
            s.checkpoint()

    while state["i"] < len(ops) or svc.pending:
        svc.run_until_idle(poll)
    return tickets


def _journal_roster(wal_dir):
    """Every record on disk, in replay order (asserts no torn tail)."""
    from repro.core import wal as walmod

    roster = []
    for seq, path in walmod.list_segments(wal_dir):
        recs, tail, _ = walmod.read_segment(path)
        assert tail is None, (path, tail)
        roster.extend(recs)
    return roster


def _fingerprints(g0, roster):
    """Expected graph fingerprint after each journal-record prefix."""
    fps, g = [], g0
    for rec in roster:
        if rec.kind == "edges":
            g = g.add_edges(rec.arrays["u"], rec.arrays["v"],
                            rec.arrays["t"])
        fps.append(g.fingerprint())
    return fps


def _gate_recovery(rec_svc, prefix, precrash, ref_by_id, ref_twin,
                   want_fp, ctx):
    """The drill's contract for one surviving journal prefix: recovery +
    drain must account for *every* admission in the prefix (resolved
    pre-crash or re-queued — never lost), every result bit-identical to
    the fault-free reference, and the recovered graph fingerprint equal
    to the prefix's expected lineage."""
    got = {tk.id: tk for tk in rec_svc.run_until_idle()}
    fp = rec_svc.graph.fingerprint()
    assert fp == want_fp, (ctx, fp, want_fp)
    cancelled = {int(r.meta["id"]) for r in prefix if r.kind == "cancel"}
    checked = 0
    for r in prefix:
        if r.kind != "submit":
            continue
        rid = int(r.meta["id"])
        tk = got.get(rid)
        if tk is None:                       # resolved before the crash
            tk = precrash.get(rid)
            assert tk is not None and tk.done, \
                f"durability[{ctx}]: journaled admission #{rid} was lost"
        if rid in cancelled:
            assert tk.status == "cancelled", (ctx, rid, tk.status)
            continue
        want = ref_by_id[rid]
        if want.status == "cancelled":
            # the cancel record fell off the surviving tail: the
            # recovered ticket runs to completion — its result must
            # match the reference twin with the same request + epoch pin
            want = ref_twin[(tk.k, tk.h, tk.ts, tk.te, tk.epoch)]
        assert_cores_equal(tk.result, want.result,
                           ctx=f"durability[{ctx}] id#{rid}")
        checked += 1
    return checked


def run_durability(name: str = "collegemsg"):
    """Kill-anywhere durability drill: crash the service after *every*
    journal record (every prefix when ``REPRO_CHAOS``/full bench;
    representative points in SMOKE), plus mid-checkpoint
    (rotation-before-snapshot), torn-tail, flipped-byte and
    corrupt-newest-snapshot post-mortems — recovery + drain must be
    bit-identical to the uninterrupted run over each surviving prefix.
    Emits the recovery-time vs journal-tail-length curve."""
    from repro.core import TCQService
    from repro.core import wal as walmod
    from repro.core.faultinject import (CrashingWAL, InjectedCrash,
                                        corrupt_snapshot, flip_tail_byte,
                                        torn_tail)

    g = graph(name)
    ops = _durability_ops(name)
    rows = []

    # fault-free reference: same tape, no journal
    ref_by_id = _drive_ops(TCQService(g), ops)
    ref_twin = {(tk.k, tk.h, tk.ts, tk.te, tk.epoch): tk
                for tk in ref_by_id.values() if tk.status == "done"}

    # the uninterrupted journaled run: its directory is the post-mortem
    # mutilation target, its journal the kill-point roster
    tmp = tempfile.mkdtemp(prefix="tcq-durability-")
    try:
        full_dir = os.path.join(tmp, "full")
        svc = TCQService(g, wal_dir=full_dir, fsync="always")
        full = _drive_ops(svc, ops)
        for rid, tk in full.items():
            if tk.status == "done":
                assert_cores_equal(tk.result, ref_by_id[rid].result,
                                   ctx=f"durability[journaled] id#{rid}")
        svc.wal.close()
        roster = _journal_roster(full_dir)
        fps = _fingerprints(g, roster)
        R = len(roster)
        sig = [(r.kind, (r.meta or {}).get("id")) for r in roster]

        def kill_at(n):
            """Fresh run killed right after record ``n`` lands, then
            recover + gate the n+1-record prefix."""
            d = os.path.join(tmp, f"kill-{n}")
            killer = CrashingWAL(walmod.WriteAheadLog(d, fsync="always"),
                                 crash_after_records=n)
            crash_svc = TCQService(g, wal=killer)
            seen = {}
            try:
                _drive_ops(crash_svc, ops, seen)
                raise AssertionError(f"crash point {n} never fired")
            except InjectedCrash:
                pass
            prefix = _journal_roster(d)
            got_sig = [(r.kind, (r.meta or {}).get("id")) for r in prefix]
            assert got_sig == sig[:n + 1], (n, got_sig, sig[:n + 1])
            rec = TCQService.recover(d)
            rep = rec.recovery_report
            checked = _gate_recovery(rec, prefix, seen, ref_by_id,
                                     ref_twin, fps[n], f"kill@{n}")
            rec.wal.close()
            return {"bench": "durability", "scenario": "kill",
                    "graph": name, "crash_after_record": n,
                    "tail_records": rep["wal_records"],
                    "snapshot_seq": rep["snapshot_seq"],
                    "requeued": rep["pending_after"],
                    "results_checked": checked,
                    "recover_s": rep["recover_s"], "equivalent": True}

        # every prefix on the full sweep; SMOKE samples the boundary
        # cases (first record, around the first ingest + the checkpoint,
        # the final record)
        points = list(range(R))
        if SMOKE and not CHAOS:
            e0 = next(i for i, r in enumerate(roster) if r.kind == "edges")
            points = sorted({0, 1, e0, min(e0 + 1, R - 1), R - 1})
        for n in points:
            rows.append(kill_at(n))

        def post_mortem(scenario, mutilate, prefix_len, *, tail_reason=None,
                        snapshots_skipped=0):
            """Mutilate a copy of the completed run's journal dir, then
            recover + gate the surviving prefix."""
            d = os.path.join(tmp, scenario)
            shutil.copytree(full_dir, d)
            mutilate(d)
            rec = TCQService.recover(d)
            rep = rec.recovery_report
            if tail_reason is not None:
                reasons = [e["reason"] for e in rep["tail_events"]]
                assert reasons == [tail_reason], (scenario, rep)
            assert len(rep["snapshots_skipped"]) == snapshots_skipped, rep
            checked = _gate_recovery(rec, roster[:prefix_len], full,
                                     ref_by_id, ref_twin,
                                     fps[prefix_len - 1], scenario)
            rec.wal.close()
            rows.append({"bench": "durability", "scenario": scenario,
                         "graph": name,
                         "tail_records": rep["wal_records"],
                         "tail_events": rep["tail_events"],
                         "snapshots_skipped":
                             len(rep["snapshots_skipped"]),
                         "results_checked": checked,
                         "recover_s": rep["recover_s"],
                         "equivalent": True})

        # torn tail: the last record is half-written at power loss — it
        # was never acknowledged, so the prefix simply ends one earlier
        post_mortem("torn_tail", torn_tail, R - 1, tail_reason="torn")
        # bit rot inside the last record: CRC catches it, same cut
        post_mortem("flipped_byte", flip_tail_byte, R - 1,
                    tail_reason="corrupt")
        # corrupt newest snapshot: fall back to the previous retained
        # checkpoint and replay its (longer) tail — nothing is lost
        post_mortem("corrupt_snapshot", corrupt_snapshot, R,
                    snapshots_skipped=1)

        # mid-checkpoint crash: dies after the rotation seals the old
        # segment, before the snapshot lands; a junk .tmp (a snapshot
        # save that died mid-write) is strewn in for good measure
        d = os.path.join(tmp, "mid-checkpoint")
        killer = CrashingWAL(walmod.WriteAheadLog(d, fsync="always"),
                             crash_on_rotate=True)
        crash_svc = TCQService(g, wal=killer)
        seen = {}
        try:
            _drive_ops(crash_svc, ops, seen)
            raise AssertionError("rotate crash never fired")
        except InjectedCrash:
            pass
        with open(os.path.join(d, "snapshot-99999999.npz.tmp"), "wb") as f:
            f.write(b"half a snapshot")
        prefix = _journal_roster(d)
        n = len(prefix)
        assert [(r.kind, (r.meta or {}).get("id")) for r in prefix] \
            == sig[:n], "pre-rotation journal diverged"
        rec = TCQService.recover(d)
        rep = rec.recovery_report
        checked = _gate_recovery(rec, prefix, seen, ref_by_id, ref_twin,
                                 fps[n - 1], "mid_checkpoint")
        ck = rec.checkpoint()            # GC sweeps the junk .tmp
        assert not os.path.exists(os.path.join(
            d, "snapshot-99999999.npz.tmp")), "stray .tmp survived GC"
        rec.wal.close()
        rows.append({"bench": "durability", "scenario": "mid_checkpoint",
                     "graph": name, "tail_records": rep["wal_records"],
                     "results_checked": checked,
                     "recover_s": rep["recover_s"],
                     "gc_removed": ck["gc_removed"], "equivalent": True})
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    rows.append({"bench": "durability", "scenario": "summary",
                 "graph": name, "journal_records": R,
                 "kill_points": len(points),
                 "max_recover_s": max(r["recover_s"] for r in rows
                                      if "recover_s" in r),
                 "equivalent": True})
    return rows


def run(name: str = "collegemsg"):
    rows = []
    for seed in SEEDS:
        rows += run_scenarios(name, seed)
    rows += run_sharded_fault()
    rows += run_overload(name)
    emit("bench_chaos", rows)
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
    for r in run_durability():
        print(r)
