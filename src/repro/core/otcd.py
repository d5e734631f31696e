"""TCD / OTCD query scheduling (paper §3–§4) over the device engines.

The schedule bookkeeping (which (ts, te) cells remain, per the three pruning
rules) is inherently sequential, tiny, and lives on host — it is factored
into ``core/scheduler.py`` (:class:`~repro.core.scheduler.QueryState`:
row cursors, IntervalSet pruning, empty-cell staircase, warm starts, TTI
dedup).  Every TCD operation (truncate + peel + TTI) is a single compiled
device program with dynamic window/threshold scalars — one compilation
serves the whole query.  All modes peel against a *windowed* TEL
(:meth:`TCQEngine._window_tel`, an LRU-cached power-of-two-bucketed
truncation) so per-cell peel work scales with the query window, not |E|.

Enumeration is over *unique* timestamps inside [Ts, Te] (column index space);
cells between adjacent real timestamps are exact duplicates of their
right-snap and are never scheduled (a strict, exact strengthening of PoR).

Two execution modes share that schedule:

* ``serial`` — paper-faithful: one cell per device program (`tcd.tcd`),
  decremental warm starts along each row (Theorem 1).
* ``wave`` — the device-resident lane pool (`engine.WavePipeline`): a
  persistent donated [W, V] lane buffer, one fused ``wave_step`` (peel +
  TTI + stats + uint32 bitmask pack) per batch of schedule cells with
  per-lane (ts, te, k, h), packed O(W·V/32) result transfer with deferred
  bulk decode, and a depth-D slot ring so host pruning bookkeeping
  overlaps device compute.

(The seed stepwise engine — one blocking host round-trip per step — served
as the pipeline's benchmark baseline through PR 2 and was retired once the
BENCH_wave.json trajectory had cross-PR history; ``bench_pipeline`` now
gates wave mode against the serial engine.)

**Streaming.**  The engine is *epoch-versioned*: ``update_graph`` installs
a new immutable snapshot (produced by ``TemporalGraph.add_edges``'s
incremental merge-append), bumps ``engine.epoch``, and refreshes the
device TEL inside power-of-two *capacity classes* — edge/pair/vertex
buffers are sentinel-padded to capacities that only grow by doubling, so
a streaming append almost never changes a compiled program's shapes.
``_window_tel`` is keyed by ``(epoch, Ts, Te)`` and each cache entry pins
the TEL *and* the step it was built with, so a graph update
can never serve a stale truncation to a new query nor a fresh truncation
to a query pinned to an older epoch (snapshot consistency — the contract
``core/service.py``'s mid-flight admission is built on).

:meth:`TCQEngine.query_batch` serves *many* queries through one shared
lane pool off a single union-window TEL; the streaming
:class:`~repro.core.service.TCQService` goes further — window-clustered
pools with mid-flight admission — and uses this engine underneath.
"""

from __future__ import annotations

import time
from collections import OrderedDict, defaultdict
from typing import Dict, List, Mapping, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import tcd as tcd_mod
from repro.core.engine import WavePipeline
from repro.core.graph import (DeviceTEL, TemporalGraph, pow2_capacity,
                              window_tables)
from repro.core.intervals import IntervalSet
from repro.core.results import CoreResult, QueryStats, TCQResult
from repro.core.scheduler import QueryState, autotune_wave

_I32_MAX = np.iinfo(np.int32).max
_I32_MIN = np.iinfo(np.int32).min
_WINDOW_CACHE_MAX = 64
_EPOCH_AUX_MAX = 8          # snapshot pair-table LRU (epochs still in flight)


class WindowTEL(NamedTuple):
    """One window-truncated TEL plus everything needed to peel it.

    The step and the device vertex width are *pinned per entry*: they
    were built against this entry's capacity classes, so a later
    capacity growth (or epoch bump) can never mix a cached TEL with an
    incompatible step.
    """

    tel: DeviceTEL
    num_vertices: int        # device vertex width (capacity, >= live V)
    window_edges: int        # live (non-sentinel) edges inside the window
    step_fn: object = None   # pinned wave step (make_wave_step_fn closure)


class _EpochAux(NamedTuple):
    """Per-epoch pair-table device arrays (capacity padded)."""

    pair_u: object
    pair_v: object
    hp_src: object
    hp_pair: object
    pair_cap: int
    v_cap: int


class TCQEngine:
    """Holds the device TEL + compiled TCD programs for one temporal graph.

    ``use_kernel`` selects the device step for wave mode: True forces
    the fused peel-to-fixpoint wave kernel (``kernels/wave_peel``;
    interpret mode off-TPU), False the XLA composite, None (default)
    auto-dispatches.  The step — with its host-side window tables — is
    built once per cached window truncation and reused by every wave
    query on this engine.

    The engine is streaming-capable: :meth:`update_graph` installs a new
    graph snapshot under a fresh epoch.  ``num_vertices`` is the *device*
    vertex width (a capacity ≥ the live vertex count once the graph has
    grown past its initial size); padded vertices have no incident edges,
    peel out on the first fixpoint iteration for any k >= 1, and never
    appear in results.
    """

    def __init__(self, graph: TemporalGraph, degree_fn=None, *,
                 use_kernel: Optional[bool] = None,
                 resilience=None, cache=None,
                 mesh=None, combine: str = "auto"):
        from repro.kernels.segdeg.ops import on_tpu
        from repro.core.wave import ResilienceConfig
        from repro.core.corecache import CoreCache

        self._degree_fn = degree_fn
        # mesh=(jax Mesh) shards the wave path: edges over the mesh's
        # "model" axis, query lanes over pod x data (core/distributed.py).
        # The serial path, the TCD primitives and every cache stay
        # single-device — the mesh only changes who executes the peel.
        # combine: "psum" | "rs_ag" | "auto" (pick from V and lane count,
        # scheduler.choose_combine) — the degree-combine collective.
        self.mesh = mesh
        if mesh is not None:
            from repro.core.distributed import mesh_shard_counts

            self._lane_shards, self._model_shards = mesh_shard_counts(mesh)
            self._dist = {"pool_runs": 0, "device_steps": 0,
                          "collective_bytes": 0}
        else:
            self._lane_shards = self._model_shards = 1
            self._dist = None
        self._combine_req = combine
        self._combine = None
        self._shard_plan = None
        self._plan_arrays = None
        # cache=True builds a default TTI-keyed core-result cache
        # (corecache.CoreCache); an instance is used as-is; None/False
        # disables result caching (the default for bare engines — the
        # streaming service enables it for engines it owns).  Cached
        # results are only sound for the standard distinct-neighbour
        # degree, so a custom degree_fn forces the cache off.
        if cache is True:
            cache = CoreCache()
        self.core_cache = (cache or None) if degree_fn is None else None
        self._use_kernel = on_tpu() if use_kernel is None else use_kernel
        # resilience=True (or a ResilienceConfig) pins a degradation
        # ladder (Pallas -> XLA -> numpy oracle; demotion on VMEM/compile
        # failure or a sampled divergence tripwire) as every window's
        # step_fn instead of the single-lowering dispatch.  Ladder rungs
        # never donate the lane buffer (failed calls replay one rung
        # down bit-identically), so resilient mode trades the donated
        # in-place lane update for fault containment.
        if resilience is True:
            resilience = ResilienceConfig()
        self._resilience: Optional[ResilienceConfig] = resilience or None
        self.epoch = 0
        # (epoch, Ts, Te) -> WindowTEL, LRU
        self._win_cache: "OrderedDict[Tuple[int, int, int], WindowTEL]" = \
            OrderedDict()
        self._win_hits = 0
        self._win_misses = 0
        self._win_evictions = 0
        # epoch -> _EpochAux, LRU (snapshots with queries still in flight)
        self._epoch_aux: "OrderedDict[int, _EpochAux]" = OrderedDict()
        self._install(graph, initial=True)

    # ------------------------------------------------------------- streaming
    def _install(self, graph: TemporalGraph, initial: bool) -> None:
        """(Re)build the device TEL inside the engine's capacity classes.

        Initial capacities are exact (a static graph pays zero padding);
        once streaming appends outgrow a capacity it jumps to the next
        power of two, so recompiles are amortized O(1) over a stream and
        shapes are shared across epochs in the same capacity class.
        """
        if initial:
            self._edge_cap = graph.num_edges
            self._pair_cap = graph.num_pairs
            self._v_cap = graph.num_vertices
            grew_pairs = grew_verts = True
        else:
            grew_pairs = graph.num_pairs > self._pair_cap
            grew_verts = graph.num_vertices > self._v_cap
            if graph.num_edges > self._edge_cap:
                self._edge_cap = pow2_capacity(graph.num_edges)
            if grew_pairs:
                self._pair_cap = pow2_capacity(graph.num_pairs)
            if grew_verts:
                self._v_cap = pow2_capacity(graph.num_vertices)
        if self.mesh is not None:
            # one vertex width everywhere: the sharded step needs V to be
            # a multiple of 8*model_shards (byte-aligned alive slices per
            # shard), and the single-device TEL must agree — its hp_src
            # sentinel is v_cap, which must sit at the shared width's
            # dropped segment, not inside a wider sharded degree slice
            from repro.core.distributed import ShardPlan

            self._v_cap = ShardPlan._round_vertices(self._v_cap,
                                                    self._model_shards)
        self.graph = graph
        arrs = graph.tel_arrays(edge_capacity=self._edge_cap,
                                pair_capacity=self._pair_cap,
                                vertex_capacity=self._v_cap)
        self.tel = DeviceTEL(**{k: jnp.asarray(v) for k, v in arrs.items()})
        if initial or grew_verts:
            self.num_vertices = self._v_cap
            self._ones = jnp.ones((self._v_cap,), dtype=bool)
        aux = _EpochAux(self.tel.pair_u, self.tel.pair_v, self.tel.hp_src,
                        self.tel.hp_pair, self._pair_cap, self._v_cap)
        self._remember_aux(self.epoch, aux)
        if self.mesh is not None:
            self._install_shards(graph, initial)

    def _install_shards(self, graph: TemporalGraph, initial: bool) -> None:
        """Build or in-place refresh the frozen-ownership shard plan and
        re-place the full-graph edge shards on the mesh.  In the
        streaming steady state (no capacity growth) ``refresh`` keeps
        every buffer shape, so the compiled sharded step — keyed on
        (mesh, v_cap, p_cap, combine) plus the edge-cap bucket — carries
        across epochs with zero recompiles."""
        from repro.core.distributed import ShardPlan, wave_shardings
        from repro.core.scheduler import choose_combine

        if initial or self._shard_plan is None:
            self._shard_plan = ShardPlan.build(graph, self._model_shards,
                                               vertex_capacity=self._v_cap)
        else:
            self._shard_plan.refresh(graph, vertex_capacity=self._v_cap)
        plan = self._shard_plan
        assert plan.num_vertices == self._v_cap
        sh = wave_shardings(self.mesh, plan.num_vertices, plan.num_shards)
        self._edges_sharding = sh["edges"]
        self._plan_arrays = tuple(
            jax.device_put(a, sh["edges"])
            for a in (plan.src, plan.dst, plan.t, plan.pair_local,
                      plan.hp_src, plan.hp_pair))
        if self._combine_req == "auto":
            # nominal wave of 32 lanes: the choice only flips on V, and
            # pinning it here keeps one compiled program per capacity
            # class instead of one per autotuned W
            self._combine = choose_combine(self._v_cap, 32,
                                           self._model_shards)
        else:
            self._combine = self._combine_req

    def _sharded_step(self, arrays, tel, Ts: int, Te: int, *, full: bool):
        """The sharded device step (or ladder) for one window entry.
        ``arrays`` are the mesh-placed edge shards, ``tel`` the matching
        single-device window TEL (serial mode, the ladder's oracle rung,
        and the kernel-within-shard build all read it)."""
        from repro.core.distributed import (ShardedDegradationLadder,
                                            make_sharded_kernel_step,
                                            make_sharded_step_fn)

        plan = self._shard_plan
        if self._resilience is not None:
            return ShardedDegradationLadder(
                self.mesh, arrays, tel, self._v_cap, p_cap=plan.p_cap,
                combine=self._combine, use_kernel=self._use_kernel,
                config=self._resilience)
        refused = []
        if self._use_kernel and self._model_shards == 1:
            step = make_sharded_kernel_step(self.mesh, tel, self._v_cap,
                                            on_refuse=refused.append)
            if step is not None:
                return step
        step = make_sharded_step_fn(
            self.mesh, arrays, num_vertices=self._v_cap, p_cap=plan.p_cap,
            combine=self._combine, donate=True)
        if refused:
            step.fallback = refused[0]
        return step

    def update_graph(self, graph: TemporalGraph) -> int:
        """Install a new graph snapshot (streaming append) under a fresh
        epoch; returns the new epoch.  In-flight queries pinned to older
        epochs are untouched — their window TELs (and the snapshots they
        were truncated from) stay valid and epoch-keyed.  Host cost is
        O(E) array padding; device programs recompile only when a
        capacity class grows (amortized O(1) by doubling).

        When the new snapshot is the direct child of the current one
        (``graph.parent_uid`` matches and the appended batch's time span
        is known), the core-result cache is *advanced*, not flushed:
        entries the batch cannot affect are re-keyed to the new epoch,
        entries it can are invalidated (see CoreCache.advance_epoch).  An
        unrelated snapshot simply starts the new epoch cold — entries at
        older epochs stay valid for queries still pinned to them."""
        old_epoch, old_uid = self.epoch, self.graph.uid
        self.epoch += 1
        self._install(graph, initial=False)
        if self.core_cache is not None:
            span = getattr(graph, "appended_span", None)
            if span is not None and \
                    getattr(graph, "parent_uid", None) == old_uid:
                self.core_cache.advance_epoch(old_epoch, self.epoch,
                                              int(span[0]), int(span[1]))
        return self.epoch

    def _remember_aux(self, epoch: int, aux: _EpochAux) -> None:
        self._epoch_aux[epoch] = aux
        self._epoch_aux.move_to_end(epoch)
        while len(self._epoch_aux) > _EPOCH_AUX_MAX:
            self._epoch_aux.popitem(last=False)

    def _aux_for(self, epoch: int, g: TemporalGraph) -> _EpochAux:
        """Pair-table device arrays for one epoch's snapshot, padded to
        the engine's *current* capacity classes (snapshots are ancestors
        of the current graph, so they always fit)."""
        hit = self._epoch_aux.get(epoch)
        if hit is not None:
            self._epoch_aux.move_to_end(epoch)
            return hit
        if g.num_pairs > self._pair_cap or g.num_vertices > self._v_cap:
            raise ValueError(
                "snapshot exceeds engine capacities — not an ancestor of "
                "the engine's current graph")
        arrs = g.tel_arrays(pair_capacity=self._pair_cap,
                            vertex_capacity=self._v_cap)
        aux = _EpochAux(
            jnp.asarray(arrs["pair_u"]), jnp.asarray(arrs["pair_v"]),
            jnp.asarray(arrs["hp_src"]), jnp.asarray(arrs["hp_pair"]),
            self._pair_cap, self._v_cap)
        self._remember_aux(epoch, aux)
        return aux

    def retire_epochs(self, live_epochs) -> int:
        """Evict window-TEL and pair-table cache entries for epochs no
        longer pinned by any in-flight or pending ticket.

        The window LRU is size-bounded but not epoch-aware: a retired
        epoch's WindowTELs (device edge buffers + pinned closures) used
        to sit in the cache pinning device memory until capacity eviction
        pushed them out.  The streaming service calls this after every
        pool with the set of epochs still pinned; the engine's current
        epoch is always kept.  Returns the number of evicted entries.
        """
        live = {int(e) for e in live_epochs}
        live.add(self.epoch)
        dead_w = [k for k in self._win_cache if k[0] not in live]
        for k in dead_w:
            del self._win_cache[k]
        dead_a = [e for e in self._epoch_aux if e not in live]
        for e in dead_a:
            del self._epoch_aux[e]
        if self.core_cache is not None:
            self.core_cache.retire_epochs(live)
        return len(dead_w) + len(dead_a)

    def rebase_epoch(self, epoch: int) -> None:
        """Re-key the engine's current snapshot under an externally
        dictated epoch number (crash recovery: a restored service resumes
        its pre-crash epoch numbering, so re-admitted tickets' pinned
        epochs stay meaningful and later pushes continue the sequence)."""
        epoch = int(epoch)
        if epoch == self.epoch:
            return
        aux = self._epoch_aux.pop(self.epoch)
        moved = [(k, v) for k, v in self._win_cache.items()
                 if k[0] == self.epoch]
        for k, _ in moved:
            del self._win_cache[k]
        if self.core_cache is not None:
            self.core_cache.rebase_epoch(self.epoch, epoch)
        self.epoch = epoch
        self._epoch_aux[epoch] = aux
        for (_, ts, te), v in moved:
            self._win_cache[(epoch, ts, te)] = v

    def resilience_events(self) -> List[Dict]:
        """Degradation events (demotions, unavailable rungs) across every
        live window ladder, most recent windows last.  Empty when the
        engine was built without ``resilience``."""
        out: List[Dict] = []
        for (ep, ts, te), wt in self._win_cache.items():
            for ev in getattr(wt.step_fn, "events", ()):
                out.append({"epoch": ep, "window": (ts, te), **ev})
        return out

    # -------------------------------------------------------- window slicing
    def _window_tel(self, Ts: int, Te: int, *,
                    graph: Optional[TemporalGraph] = None,
                    epoch: Optional[int] = None,
                    pool: int = -1) -> WindowTEL:
        """Device TEL truncated to [Ts, Te] for one epoch's snapshot.

        Every cell of a query's schedule lies inside [Ts, Te], so both the
        serial engine and the wave pipeline peel against only the window's
        edges — per-iteration work scales with the window, not |E|.  The
        serial engine reads the truncated TEL, whose edge arrays are
        padded to a power-of-two bucket with sentinel edges (t=int32 min,
        pair_id=pair capacity).  The wave step reads the window's own
        tables (``window_tables``: local edges, pairs, half-pairs and
        vertices), built once per entry; both of its lowerings, the fused
        kernel and the XLA composite, pad them to the window's size class
        and pass them as arguments, so every window of a class shares one
        compiled program.  The cache is LRU and keyed by
        ``(epoch, Ts, Te)``: a graph
        update can never serve a stale truncation (new epoch, new key),
        while queries pinned to an older epoch — pass ``graph``/``epoch``
        explicitly — keep hitting their snapshot's entries.  Each entry
        pins the closures and device vertex width it was built with.  A
        miss builds the entry inside a ``tcq.engine.window_tel`` profiler
        span (arg ``pool``: the service pool asking, -1 for none).
        """
        g = self.graph if graph is None else graph
        ep = self.epoch if epoch is None else int(epoch)
        key = (ep, int(Ts), int(Te))
        hit = self._win_cache.get(key)
        if hit is not None:
            self._win_hits += 1
            self._win_cache.move_to_end(key)
            return hit
        self._win_misses += 1
        with TraceAnnotation("tcq.engine.window_tel", pool=pool):
            out = self._build_window_tel(g, ep, int(Ts), int(Te), pool)
        if len(self._win_cache) >= _WINDOW_CACHE_MAX:
            self._win_cache.popitem(last=False)     # evict least-recent
            self._win_evictions += 1
        self._win_cache[key] = out
        return out

    def _build_window_tel(self, g: TemporalGraph, ep: int, Ts: int,
                          Te: int, pool: int = -1) -> WindowTEL:
        """The cache-miss path of :meth:`_window_tel`: truncation, sort,
        uploads, the window tables (inside a ``tcq.engine.window_tables``
        span, args ``pool`` and ``edges``) and the pinned step's build."""
        from repro.core.wave import make_wave_step_fn

        aux = self._aux_for(ep, g)
        idx = np.flatnonzero((g.t >= Ts) & (g.t <= Te))
        e = int(idx.size)
        donate = self._resilience is None
        if ep == self.epoch and e >= g.num_edges:
            tel = host = self.tel
        else:
            bucket = pow2_capacity(e)
            pad = bucket - e
            # sentinel timestamp must be below every representable window
            # (t = -1 would collide with graphs using negative timestamps);
            # sentinel pair id = pair capacity (dropped by the scatter)
            t_w = np.concatenate(
                [g.t[idx], np.full(pad, _I32_MIN, np.int32)])
            host = DeviceTEL(
                src=np.concatenate([g.src[idx], np.zeros(pad, np.int32)]),
                dst=np.concatenate([g.dst[idx], np.zeros(pad, np.int32)]),
                t=t_w,
                pair_id=np.concatenate(
                    [g.pair_id[idx], np.full(pad, aux.pair_cap, np.int32)]),
                pair_u=aux.pair_u,
                pair_v=aux.pair_v,
                hp_src=aux.hp_src,
                hp_pair=aux.hp_pair,
                time_perm=np.argsort(t_w, kind="stable").astype(np.int32),
            )
            tel = DeviceTEL(*(jnp.asarray(a) for a in host))
        if self.mesh is not None:
            if tel is self.tel:
                arrays = self._plan_arrays
            else:
                plan = self._shard_plan
                sharr = plan.window_arrays(g, int(Ts), int(Te))
                hp = plan.hp_arrays(g)
                arrays = tuple(jax.device_put(a, self._edges_sharding)
                               for a in sharr + hp)
            step = self._sharded_step(arrays, tel, Ts, Te,
                                      full=tel is self.tel)
        else:
            # pin the wave step per cache entry: both lowerings peel over
            # this truncation's local tables, built once per (epoch, Ts,
            # Te) and shared by every pipeline that peels this window (the
            # program they feed is shared by the window's size class)
            with TraceAnnotation("tcq.engine.window_tables", pool=pool,
                                 edges=e):
                tables = window_tables(host)
            step = make_wave_step_fn(tel, aux.v_cap, tables=tables,
                                     use_kernel=self._use_kernel,
                                     donate=donate,
                                     resilience=self._resilience)
        return WindowTEL(tel, aux.v_cap, e, step)

    # ------------------------------------------------------------ pool seam
    def make_pool(self, lo: int, hi: int, *,
                  graph: Optional[TemporalGraph] = None,
                  epoch: Optional[int] = None, num_queries: int = 1,
                  wave: Union[int, str] = "auto", depth: int = 2,
                  pool: int = -1):
        """Window TEL + lane pipeline for one pool run — the single seam
        ``query``/``query_batch``/``TCQService.pump`` build pools
        through, so the mesh routing decision lives in one place.

        Returns ``(pipe, wt, wave)``: on a plain engine a
        :class:`~repro.core.engine.WavePipeline` over the window's
        single-device step; on a mesh engine a
        :class:`~repro.core.distributed.ShardedWavePipeline` over the
        shard_map step, with W autotuned (or rounded up) to a multiple
        of the lane-axis size.
        """
        wt = self._window_tel(int(lo), int(hi), graph=graph, epoch=epoch,
                              pool=pool)
        if self.mesh is None:
            if wave == "auto":
                wave = autotune_wave(wt.num_vertices, wt.window_edges,
                                     num_queries=num_queries, depth=depth)
            pipe = WavePipeline(wt.tel, wt.num_vertices, wave, depth,
                                step_fn=wt.step_fn)
            return pipe, wt, wave
        from repro.core.distributed import ShardedWavePipeline

        L = self._lane_shards
        if wave == "auto":
            wave = autotune_wave(wt.num_vertices, wt.window_edges,
                                 num_queries=num_queries, depth=depth,
                                 lane_shards=L)
        else:
            wave = -(-int(wave) // L) * L   # even lane split per shard
        pipe = ShardedWavePipeline(wt.step_fn, mesh=self.mesh,
                                   num_vertices=wt.num_vertices,
                                   wave=wave, depth=depth,
                                   dist_counters=self._dist)
        return pipe, wt, wave

    # --------------------------------------------------------- observability
    def stats(self) -> Dict:
        """Engine-level cache observability: the window-TEL LRU's
        hit/miss/eviction counters and, when result caching is on, the
        TTI core cache's counters (see CoreCache.stats)."""
        out = {
            "epoch": self.epoch,
            "window_tel": {
                "hits": self._win_hits,
                "misses": self._win_misses,
                "evictions": self._win_evictions,
                "size": len(self._win_cache),
            },
        }
        if self.core_cache is not None:
            out["core_cache"] = self.core_cache.stats()
        if self.mesh is not None:
            out["distributed"] = {
                "mesh": dict(zip(self.mesh.axis_names,
                                 self.mesh.devices.shape)),
                "devices": int(self.mesh.devices.size),
                "lane_shards": self._lane_shards,
                "model_shards": self._model_shards,
                "combine": self._combine,
                **self._dist,
            }
        return out

    def _cache_view(self, k: int, h: int, epoch: Optional[int] = None):
        """CacheView bound to (epoch, k, h), or None when caching is off."""
        from repro.core.corecache import CacheView

        if self.core_cache is None:
            return None
        return CacheView(self.core_cache,
                         self.epoch if epoch is None else int(epoch),
                         k, h)

    # ------------------------------------------------------------- primitives
    def _tcd(self, alive, ts, te, k, h, wt: Optional[WindowTEL] = None):
        tel = self.tel if wt is None else wt.tel
        nv = self.num_vertices if wt is None else wt.num_vertices
        return tcd_mod.tcd(tel, alive, ts, te, k, h, num_vertices=nv,
                           degree_fn=self._degree_fn)

    # ------------------------------------------------------------------ query
    def query(self, k: int, Ts: int, Te: int, *, h: int = 1,
              algorithm: str = "otcd", mode: str = "serial",
              wave: Union[int, str] = 8, depth: int = 2,
              min_span: Optional[int] = None,
              max_span: Optional[int] = None) -> TCQResult:
        """All distinct temporal k-cores over subintervals of [Ts, Te].

        algorithm: "otcd" (TTI pruning, §4) or "tcd" (full enumeration, §3).
        mode: "serial" (paper-faithful) or "wave" (device-resident lane
        pool — up to ``wave`` schedule cells per fused device step,
        ``depth`` steps in flight).
        wave: lane count for wave mode, or "auto" to pick it from the
        vertex count, the windowed edge count and the ring depth
        (scheduler.autotune_wave).
        depth: slot-ring depth D for wave mode (pipelining; pruning seen
        by in-flight steps is up to D-1 steps stale, still exact).
        h: link-strength lower bound (paper §6.2); 1 = plain TCQ.
        min_span/max_span: time-span constraint (paper §6.2), applied on the
        fly; pruning stays exact because it is TTI-based.
        """
        if mode not in ("serial", "wave"):
            raise ValueError(
                f"unknown mode {mode!r}: expected 'serial' or 'wave' (the "
                "seed 'wave_stepwise' baseline was retired after PR 2)")
        t0 = time.perf_counter()
        uts = self.graph.unique_ts
        uts = uts[(uts >= Ts) & (uts <= Te)].astype(np.int64)
        n = int(uts.size)
        stats = QueryStats(n_timestamps=n, cells_total=n * (n + 1) // 2)
        if n == 0:
            return TCQResult([], stats)
        prune = algorithm == "otcd"
        if mode == "wave" and self._degree_fn is not None:
            # custom degree semantics are only plumbed through the scalar
            # TCD path; run serial (which honors degree_fn) rather than
            # silently ignoring the override
            mode = "serial"
        if mode == "wave":
            pipe, wt, wave = self.make_pool(int(uts[0]), int(uts[-1]),
                                            wave=wave, depth=depth)
            stats.window_edges = wt.window_edges
            cores = pipe.run(uts, k, h, prune, stats,
                             cache=self._cache_view(k, h))
        elif self._degree_fn is not None:
            # custom degree fns are written against the graph's real TEL
            # layout — never hand them the bucket-padded window truncation
            stats.window_edges = self.graph.num_edges
            cores = self._run_serial(uts, k, h, prune, stats)
        else:
            # serial peels against the same windowed TEL as wave mode:
            # per-cell work scales with the window's edges, not |E|
            wt = self._window_tel(int(uts[0]), int(uts[-1]))
            stats.window_edges = wt.window_edges
            cores = self._run_serial(uts, k, h, prune, stats, wt)
        out = list(cores.values())
        stats.wall_time_s = time.perf_counter() - t0
        res = TCQResult(out, stats)
        if min_span is not None or max_span is not None:
            res = res.filter_span(min_span, max_span)
        return res

    # ------------------------------------------------------------ query batch
    def query_batch(self, requests: Sequence[Mapping], *,
                    algorithm: str = "otcd", wave: Union[int, str] = "auto",
                    depth: int = 2) -> List[TCQResult]:
        """Serve many concurrent TCQ queries through one shared lane pool.

        ``requests`` is a sequence of mappings with keys ``k``, ``ts``,
        ``te`` and optionally ``h`` (default 1) — the format produced by
        ``repro.data.TCQRequestStream``.  Each request gets its own
        :class:`~repro.core.scheduler.QueryState` (private pruning, warm
        starts, TTI dedup), while the lane pool packs ready cells from
        every in-flight query into shared fused steps with per-lane
        (ts, te, k, h).  One TEL truncated to the *union* window serves
        the whole batch; per-lane windows keep each query's exact
        semantics, so every returned ``TCQResult`` is bit-identical to
        running that query alone.  Throughput improves because lanes
        freed by one query's draining tail are refilled with another's
        cells instead of idling — best when the batch's windows overlap
        (a serving hot set): per-iteration peel cost scales with the
        *union* window's edges, so batching a few narrow windows from
        opposite ends of a long timeline can cost more than looping
        ``query()``.  The streaming :class:`~repro.core.service.TCQService`
        automates exactly that grouping (window-clustered pools with
        mid-flight admission); this method remains the single-pool,
        fixed-batch entry point.

        Per-query ``QueryStats`` carry that query's schedule counters;
        pipeline counters (device_steps, host_syncs, occupancy, ...)
        describe the shared batch and are reported on every member (see
        :class:`~repro.core.results.QueryStats`).

        wave: lane count, or "auto" (default) — autotuned from the vertex
        count, the union window's edge count, the batch size and depth.
        depth: slot-ring depth D (D steps in flight).
        """
        t0 = time.perf_counter()
        reqs = [dict(r) for r in requests]
        prune = algorithm == "otcd"
        if self._degree_fn is not None:
            # custom degree semantics: fall back to per-query scheduling
            # (the scalar TCD path honors degree_fn; the fused wave step
            # does not)
            return [self.query(int(r["k"]), int(r["ts"]), int(r["te"]),
                               h=int(r.get("h", 1)), algorithm=algorithm)
                    for r in reqs]
        outs: List[Optional[TCQResult]] = [None] * len(reqs)
        states: List[Tuple[int, QueryState]] = []
        for qi, r in enumerate(reqs):
            uts = self.graph.unique_ts
            uts = uts[(uts >= int(r["ts"])) & (uts <= int(r["te"]))]
            uts = uts.astype(np.int64)
            n = int(uts.size)
            stats = QueryStats(n_timestamps=n,
                               cells_total=n * (n + 1) // 2,
                               batch_size=len(reqs))
            if n == 0:
                outs[qi] = TCQResult([], stats)
                continue
            states.append((qi, QueryState(
                uts, int(r["k"]), int(r.get("h", 1)), prune, stats,
                qid=qi,
                cache=self._cache_view(int(r["k"]), int(r.get("h", 1))))))
        if states:
            lo = min(int(s.uts[0]) for _, s in states)
            hi = max(int(s.uts[-1]) for _, s in states)
            pipe, wt, wave = self.make_pool(lo, hi,
                                            num_queries=len(states),
                                            wave=wave, depth=depth)
            pool_stats = QueryStats()
            pipe.run_pool([s for _, s in states], pool_stats)
            for qi, s in states:
                st = s.stats
                st.absorb_pool(pool_stats, window_edges=wt.window_edges,
                               batch_size=len(reqs))
                cores = s.decode_results(wt.num_vertices)
                outs[qi] = TCQResult(list(cores.values()), st)
        wall = time.perf_counter() - t0
        for out in outs:
            out.stats.wall_time_s = wall
        return outs

    # ----------------------------------------------------------- serial mode
    def _run_serial(self, uts, k, h, prune, stats,
                    wt: Optional[WindowTEL] = None):
        n = uts.size
        idx_of = {int(t): i for i, t in enumerate(uts)}
        pruned: Dict[int, IntervalSet] = defaultdict(IntervalSet)
        results: Dict[Tuple[int, int], CoreResult] = {}
        ones = self._ones if wt is None or \
            wt.num_vertices == self._ones.shape[0] \
            else jnp.ones((wt.num_vertices,), dtype=bool)
        empty_col_max = -1          # cells (r, c<=bound) are provably empty
        row_alive = None            # warm start across rows (Theorem 1)
        row_alive_j = -1
        for i in range(n):
            iv = pruned.pop(i, IntervalSet())
            j: Optional[int] = n - 1
            cur_alive = None
            first_in_row = True
            while j is not None and j >= i:
                j = iv.highest_uncovered_leq(j)
                if j is None or j < i:
                    break
                if j <= empty_col_max:
                    stats.cells_trivial += (j - i + 1) - iv.total_covered(i, j)
                    break
                if cur_alive is not None:
                    warm = cur_alive
                elif row_alive is not None and j <= row_alive_j:
                    warm = row_alive
                else:
                    warm = ones
                res = self._tcd(warm, int(uts[i]), int(uts[j]), k, h, wt)
                stats.cells_evaluated += 1
                stats.device_steps += 1
                if int(res.n_edges) == 0:
                    if j > i:
                        stats.pruned_empty += (j - i) - iv.total_covered(i, j - 1)
                    empty_col_max = max(empty_col_max, j)
                    if j == n - 1:
                        # T[ts_i, Te] empty => all deeper rows empty
                        stats.cells_trivial += sum(
                            n - r for r in range(i + 1, n))
                        return results
                    break
                cur_alive = res.alive
                if first_in_row:
                    row_alive, row_alive_j = res.alive, j
                    first_in_row = False
                a_idx = idx_of[int(res.tti_lo)]
                b_idx = idx_of[int(res.tti_hi)]
                self._collect(results, res, a_idx, b_idx, uts, k, stats)
                if prune:
                    if b_idx < j:                       # Rule 1: PoR
                        stats.por_triggers += 1
                        stats.pruned_por += (j - b_idx) - iv.total_covered(
                            b_idx, j - 1)
                    if a_idx > i:                       # Rule 2: PoU
                        stats.pou_triggers += 1
                        for r in range(i + 1, a_idx + 1):
                            stats.pruned_pou += pruned[r].add(r, j)
                    if a_idx > i and b_idx < j:         # Rule 3: PoL
                        stats.pol_triggers += 1
                        for r in range(a_idx + 1, b_idx + 1):
                            stats.pruned_pol += pruned[r].add(b_idx + 1, j)
                    j = (b_idx - 1) if b_idx < j else j - 1
                else:
                    j = j - 1
        return results

    # ---------------------------------------------------------------- collect
    def _collect(self, results, res, a_idx, b_idx, uts, k, stats):
        key = (int(uts[a_idx]), int(uts[b_idx]))
        if key in results:
            stats.duplicates += 1
            return
        alive = np.asarray(res.alive)          # full [V] bool transfer
        stats.host_syncs += 1
        stats.bytes_synced += alive.nbytes
        verts = np.flatnonzero(alive)
        results[key] = CoreResult(k=k, tti=key, vertices=verts,
                                  n_edges=int(res.n_edges))


def temporal_kcore_query(graph: TemporalGraph, k: int, Ts: int, Te: int,
                         **kw) -> TCQResult:
    """One-shot convenience wrapper (builds a throwaway engine)."""
    return TCQEngine(graph).query(k, Ts, Te, **kw)
