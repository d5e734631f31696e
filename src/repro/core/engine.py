"""Device-resident multi-tenant wave pipeline — the engine behind
``mode="wave"`` and ``TCQEngine.query_batch``.

The pipeline is split in two layers:

* **Per-query schedule bookkeeping** lives in ``core/scheduler.py``: a
  :class:`~repro.core.scheduler.QueryState` owns one query's row cursors,
  IntervalSet pruning (Rules 1–3), empty-cell staircase, warm-start rows
  (Theorem 1) and TTI dedup (Property 2).

* **The lane pool** (this module) owns the device side: one persistent
  [W, V] bool buffer whose rows ("lanes") each peel one schedule cell per
  fused :func:`wave_step`.  The pool draws ready cells round-robin from
  *any number* of QueryStates, so lanes freed by one query's draining tail
  are immediately refilled with another query's cells — the fused step
  stays full under concurrent traffic instead of decaying with a single
  query's schedule.  ``k``/``h`` ride along as per-lane [W] vectors, so
  one step carries cells from queries with different thresholds.  The
  pool is a *live queue*: ``run_pool``'s optional ``admit`` hook is
  polled whenever lanes free, so a streaming service
  (``core/service.py``) can admit newly arrived queries mid-flight with
  no drain barrier between request batches.

Device mechanics (carried over from the single-query pipeline, measured
3.7x over the seed stepwise engine, which was retired after PR 2):

* **Persistent lane state** — the [W, V] buffer is donated through every
  ``wave_step``; exhausted lanes are refilled *in place* with
  ``lax.dynamic_update_index_in_dim`` (cold rows from all-ones, warm rows
  from the owning query's best completed row-initial core), so lane masks
  never round-trip through the host.

* **Fused step + packed result transfer** — truncate + frontier peel
  (edge activity carried in the fixpoint loop), the TTI reduction,
  per-lane stats, and a ``uint32`` bitmask pack [W, ceil(V/32)] are one
  jitted program; each step syncs one packed array plus four small [W]
  vectors, and core vertex sets are decoded host-side in one deferred
  bulk ``np.unpackbits`` per query.

* **Depth-D slot ring** — D lane buffers (default 2) cycle through
  dispatch: while slots B..D execute on device, the host retires slot A
  (pruning, packed collection), reassembles and re-dispatches it, then
  blocks on the next slot's scalars.  Pruning observed by an in-flight
  slot is thus up to D-1 steps stale — safe, because a stale lane at
  worst re-induces a core its query already found, and such duplicates
  are removed by TTI identity (Property 2) and counted per query.

* **Kernel degree path** — the Pallas ``banded_segsum`` closures (and
  their k_max band analysis) are built once per ``TCQEngine`` by the
  dispatching wrapper: compiled Pallas on TPU, XLA segment-sum elsewhere.

The pipeline peels against a *windowed* TEL (``TCQEngine._window_tel``,
epoch-keyed so graph updates can never serve stale truncations): for a
pool, one TEL truncated to the union window serves every lane — per-lane
``ts``/``te`` keep each query's exact windowed semantics, so cross-query
packing is bit-identical to running each query alone.  The streaming
service clusters co-admitted requests by window overlap and runs one
pool per cluster, so each pool's TEL stays tight.
"""

from __future__ import annotations

import functools
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.core.graph import DeviceTEL
from repro.core.results import CoreResult, QueryStats
from repro.core.scheduler import QueryState, RowCursor
# The device step itself (StepResult, the XLA-composite wave_step, the
# fused-Pallas dispatcher and the bitmask pack helpers) lives in
# core/wave.py next to the peel loop; re-exported here because the
# engine is their primary consumer and external callers import them
# from this module.
from repro.core.wave import (StepResult, make_wave_step_fn,  # noqa: F401
                             pack_alive_u32, packed_width,
                             unpack_alive_u32, wave_step)


# ---------------------------------------------------------- lane refills
@functools.partial(jax.jit, donate_argnums=(0,))
def _set_lane(buf: jnp.ndarray, li, row: jnp.ndarray) -> jnp.ndarray:
    """In-place (donated) overwrite of lane ``li`` with a device row."""
    return lax.dynamic_update_index_in_dim(buf, row, li, 0)


@functools.partial(jax.jit, static_argnames=("value",), donate_argnums=(0,))
def _fill_lane(buf: jnp.ndarray, li, value: bool) -> jnp.ndarray:
    """In-place (donated) fill of lane ``li`` with a constant mask."""
    row = jnp.full((buf.shape[1],), value, dtype=bool)
    return lax.dynamic_update_index_in_dim(buf, row, li, 0)


# -------------------------------------------------------------- lane pool
class _Slot:
    """One ring stage: a device lane buffer + its in-flight step.

    ``lanes[li]`` holds the (QueryState, RowCursor) the lane is serving,
    or None when free; ``dirty`` marks lanes holding a stale (dead) mask;
    ``step`` is the pool step number of the in-flight step.
    """

    __slots__ = ("buf", "lanes", "dirty", "inflight", "step",
                 "_params_np", "_params_dev")

    def __init__(self, wave: int, num_vertices: int, buf=None):
        # callers may hand in a pre-placed buffer (the sharded pipeline
        # allocates its slabs with an explicit mesh sharding)
        self.buf = (jnp.zeros((wave, num_vertices), dtype=bool)
                    if buf is None else buf)
        self.lanes: List[Optional[Tuple[QueryState, RowCursor]]] = \
            [None] * wave
        self.dirty: set = set()
        self.inflight: Optional[StepResult] = None
        self.step = -1
        # committed (ts, te, k, h) cache for sharded pipelines: the host
        # vectors + their device placements from the last dispatch
        self._params_np = None
        self._params_dev = None


class WavePipeline:
    """Depth-D software-pipelined lane pool over :func:`wave_step`.

    :meth:`run_pool` serves any number of QueryStates through one shared
    lane buffer; :meth:`run` is the single-query wrapper used by
    ``TCQEngine.query(mode="wave")``.
    """

    def __init__(self, tel: DeviceTEL, num_vertices: int,
                 seg_pair, seg_vert, wave: int, depth: int = 2,
                 step_fn=None):
        self.tel = tel
        self.num_vertices = num_vertices
        self.seg_pair = seg_pair
        self.seg_vert = seg_vert
        self.wave = wave
        self.depth = max(1, int(depth))
        # the device step: a prebuilt ``make_wave_step_fn`` closure (the
        # engine pins one per windowed TEL so the fused kernel's host-side
        # band analysis is never rebuilt per pipeline), else the default
        # dispatch — fused Pallas on TPU, XLA composite elsewhere.  The
        # lane buffer is donated through every step either way.
        if step_fn is None:
            step_fn = make_wave_step_fn(tel, num_vertices,
                                        seg_pair=seg_pair, seg_vert=seg_vert,
                                        donate=True)
        self._step = step_fn

    # ------------------------------------------------- subclass seams
    # The sharded pipeline (core/distributed.py) overrides these four
    # hooks to place slot buffers on a mesh, batch lane refills into two
    # device calls, and account per-shard occupancy + collective bytes.
    # The base implementations reproduce the historical single-device
    # behavior exactly (same jitted calls in the same order).
    def _new_slot(self) -> "_Slot":
        return _Slot(self.wave, self.num_vertices)

    def _refill_lanes(self, buf, sets, fills):
        """Apply lane refills to ``buf``: ``sets`` is [(lane, device
        row)] warm starts, ``fills`` is [(lane, bool)] constant masks.
        Lanes are disjoint across the two lists, so application order
        between them is irrelevant."""
        for li, value in fills:
            buf = _fill_lane(buf, li, value)
        for li, row in sets:
            buf = _set_lane(buf, li, row)
        return buf

    def _record_occupied(self, occupied: List[int]) -> None:
        pass

    def _warm_row(self, res: StepResult, packed: np.ndarray, li: int):
        """Thunk producing lane ``li``'s [V] alive row for warm-start
        reuse (only materialized when the cell becomes the row's best
        warm start).  Sharded pipelines override this: slicing a
        mesh-sharded buffer is an eager cross-device gather, so they
        unpack the already-fetched host bitmask instead."""
        return lambda: res.alive[li]

    def _commit_params(self, slot: "_Slot", params):
        """Place the per-lane (ts, te, k, h) host vectors for the step.
        Sharded pipelines override this to commit to the lane axis once
        per refill instead of once per step."""
        return tuple(jnp.asarray(p) for p in params)

    def _finish_pool(self, pool_stats: QueryStats) -> None:
        pass

    def run(self, uts: np.ndarray, k: int, h: int, prune: bool,
            stats: QueryStats, cache=None
            ) -> Dict[Tuple[int, int], CoreResult]:
        """Single-query entry: one QueryState, same stats object for both
        the query's and the pool's counters.  ``cache`` is an optional
        corecache.CacheView — hits skip lanes, peels are inserted."""
        qs = QueryState(uts, k, h, prune, stats, cache=cache)
        self.run_pool([qs], stats)
        return qs.decode_results(self.num_vertices)

    def run_pool(self, states: List[QueryState], pool_stats: QueryStats,
                 admit: Optional[Callable[[], List[QueryState]]] = None
                 ) -> None:
        """Drain a live pool of queries through the shared lane buffer.

        Cells are claimed round-robin across queries, so one device step
        mixes lanes from many (k, h, window) queries; each query's results
        accumulate in its own QueryState (bit-identical to running it
        alone — packing changes lane placement, never pruning soundness,
        because every QueryState keeps private pruning/dedup state).

        ``admit`` turns the fixed state list into a *live queue*: it is
        polled every time a slot reassembles (i.e. whenever lanes free
        up) and may hand back freshly admitted QueryStates, which join
        the claimable rotation immediately — mid-flight admission with
        no drain barrier.  The pool only ends once every in-flight lane
        has retired *and* ``admit`` comes back empty, so a streaming
        service can keep the fused step full across request arrivals.

        Admission is earliest-deadline-first: cells are claimed from the
        live state with the smallest ``(deadline, priority)`` key, with
        the original round-robin rotation breaking ties — so best-effort
        pools (every deadline inf) schedule exactly as before, while a
        deadline-carrying pool drains urgent queries first.  A state
        whose ``cancelled`` flag is set (deadline timeout, client
        cancellation — see ``TCQService``) stops claiming immediately
        and its in-flight lanes are *reclaimed mid-pool*: freed at the
        next assemble/retire without result feedback, ready for other
        queries' cells.

        Profiler spans (``jax.profiler.TraceAnnotation``, args ``pool`` =
        ``pool_stats.pool`` and ``step``): ``tcq.pipeline.run_pool`` over
        the ring; ``tcq.pipeline.assemble`` / ``.dispatch`` / ``.retire``
        per phase of each step; ``tcq.pipeline.sync`` around retire's
        blocking ``device_get``; ``tcq.engine.step_compile`` around the
        first call of a freshly built step function, where JAX traces,
        lowers and compiles it (counted in ``pool_stats.step_compiles``)
        — unless the step's ``program_warm`` says its size class's
        program already ran (counted in ``step_program_reuses``).
        """
        with TraceAnnotation("tcq.pipeline.run_pool", pool=pool_stats.pool):
            self._run_ring(states, pool_stats, admit)

    def _run_ring(self, states: List[QueryState], pool_stats: QueryStats,
                  admit: Optional[Callable[[], List[QueryState]]]) -> None:
        W = self.wave
        pool = pool_stats.pool
        claimable = deque(s for s in states if s.n > 0 and not s.cancelled)
        occupied_total = 0

        def refill() -> None:
            if admit is None:
                return
            for s in admit():
                if s.n > 0 and not s.cancelled:
                    claimable.append(s)
                    pool_stats.admissions += 1

        def claim() -> Optional[Tuple[QueryState, RowCursor]]:
            while claimable:
                bi, best = 0, claimable[0]._edf
                for i, s2 in enumerate(claimable):
                    k2 = s2._edf
                    if k2 < best:
                        bi, best = i, k2
                claimable.rotate(-bi)       # EDF: walk to an urgent state
                s = claimable[0]
                if s.cancelled:
                    claimable.popleft()
                    continue
                row = s.claim()
                if row is not None:
                    claimable.rotate(-1)    # round-robin among EDF ties
                    return s, row
                claimable.popleft()         # drained: nothing pending
            return None

        def release_cancelled(slot: _Slot) -> None:
            """Reclaim lanes whose query was cancelled since dispatch:
            the lane frees (dirty — its mask is garbage to everyone
            else) and the state's live-lane count drops so ``done``
            can resolve without result feedback."""
            for li in range(W):
                lane = slot.lanes[li]
                if lane is not None and lane[0].cancelled:
                    lane[0].live_rows -= 1
                    slot.lanes[li] = None
                    slot.dirty.add(li)

        def assemble(slot: _Slot) -> None:
            """Claim ready cells into free lanes and refill their masks."""
            refill()
            release_cancelled(slot)
            sets: List[Tuple[int, jnp.ndarray]] = []
            fills: List[Tuple[int, bool]] = []
            for li in range(W):
                if slot.lanes[li] is not None:
                    continue
                got = claim()
                if got is None:
                    break
                s, row = got
                slot.lanes[li] = (s, row)
                warm = s.warm_start(row)
                if warm is not None:
                    sets.append((li, warm))
                else:
                    fills.append((li, True))
                slot.dirty.discard(li)
                pool_stats.lane_refills += 1
            # lanes that died and were not re-claimed: zero once so the
            # shared fixpoint loop never spends iterations peeling them
            for li in sorted(slot.dirty):
                fills.append((li, False))
            slot.dirty.clear()
            if sets or fills:
                slot.buf = self._refill_lanes(slot.buf, sets, fills)

        def dispatch(slot: _Slot) -> None:
            occupied = [li for li in range(W)
                        if slot.lanes[li] is not None]
            if not occupied:
                slot.inflight = None
                return
            # stage per-lane params in python lists: element stores into
            # numpy arrays cost ~100ns each and this runs per step
            ts_l, te_l = [0] * W, [-1] * W      # empty window for padding
            k_l, h_l = [1] * W, [1] * W
            for li in occupied:
                s, row = slot.lanes[li]
                ts_l[li], te_l[li] = s.window(row)
                k_l[li], h_l[li] = s.k, s.h
                s.stats.cells_evaluated += 1
            ts_arr = np.array(ts_l, np.int32)
            te_arr = np.array(te_l, np.int32)
            k_arr = np.array(k_l, np.int32)
            h_arr = np.array(h_l, np.int32)
            args = (slot.buf, *self._commit_params(
                slot, (ts_arr, te_arr, k_arr, h_arr)))
            step = self._step
            slot.step = pool_stats.device_steps
            if getattr(step, "called", False):
                slot.inflight = step(*args)
            else:
                # the flag lives on the step object, which the engine pins
                # per window-TEL entry: a cache hit's step is already built.
                # A fresh fused step may share its size class's program.
                warm = getattr(step, "program_warm", None)
                if warm is not None and warm(*args):
                    slot.inflight = step(*args)
                    pool_stats.step_program_reuses += 1
                else:
                    with TraceAnnotation("tcq.engine.step_compile",
                                         pool=pool, step=slot.step):
                        slot.inflight = step(*args)
                    pool_stats.step_compiles += 1
                step.called = True
            slot.buf = slot.inflight.alive   # donated through; new handle
            pool_stats.device_steps += 1
            nonlocal occupied_total
            occupied_total += len(occupied)
            self._record_occupied(occupied)

        def retire(slot: _Slot) -> None:
            res = slot.inflight
            slot.inflight = None
            with TraceAnnotation("tcq.pipeline.sync", pool=pool,
                                 step=slot.step):
                packed, lo, hi, ne, it = jax.device_get(
                    (res.packed, res.tti_lo, res.tti_hi, res.n_edges,
                     res.iters))
            pool_stats.host_syncs += 1
            pool_stats.bytes_synced += (packed.nbytes + lo.nbytes + hi.nbytes
                                        + ne.nbytes + it.nbytes)
            pool_stats.peel_iters += int(it)
            # python scalars up front: numpy scalar indexing costs ~100ns
            # per element and this loop touches four per occupied lane
            lo_l, hi_l, ne_l = lo.tolist(), hi.tolist(), ne.tolist()
            for li in range(W):
                lane = slot.lanes[li]
                if lane is None:
                    continue
                s, row = lane
                if s.cancelled:
                    # cancelled mid-step: reclaim the lane, discard the
                    # result (no feedback — the query is already resolved
                    # as timed out / cancelled by the service)
                    s.live_rows -= 1
                    slot.lanes[li] = None
                    slot.dirty.add(li)
                    continue
                keep = s.retire(row, lo_l[li], hi_l[li], ne_l[li],
                                packed[li],
                                self._warm_row(res, packed, li))
                if not keep:
                    slot.lanes[li] = None
                    slot.dirty.add(li)

        # prime every slot, then cycle the ring: retire+reassemble+
        # redispatch one slot while the other D-1 slots' steps execute on
        # device — host pruning bookkeeping overlaps device compute, and
        # D-1 steps are always in flight before we block on scalars.
        # Idle slots reassemble too (a live queue may have admitted new
        # queries since their last dispatch), and the ring only stops
        # once nothing is in flight and the final admit poll is empty.
        def redispatch(slot: _Slot) -> None:
            step = pool_stats.device_steps
            with TraceAnnotation("tcq.pipeline.assemble", pool=pool,
                                 step=step):
                assemble(slot)
            with TraceAnnotation("tcq.pipeline.dispatch", pool=pool,
                                 step=step):
                dispatch(slot)

        slots = [self._new_slot() for _ in range(self.depth)]
        for slot in slots:
            redispatch(slot)
        cur = 0
        while True:
            if all(s.inflight is None for s in slots):
                refill()
                if not claimable:
                    break
            slot = slots[cur]
            if slot.inflight is not None:
                with TraceAnnotation("tcq.pipeline.retire", pool=pool,
                                     step=slot.step):
                    retire(slot)
            redispatch(slot)
            cur = (cur + 1) % self.depth

        if pool_stats.device_steps:
            pool_stats.occupancy = occupied_total / pool_stats.device_steps
        self._finish_pool(pool_stats)
