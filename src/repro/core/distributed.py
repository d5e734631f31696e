"""Distributed TCQ engine: the paper's system at pod scale via shard_map.

Layout (mesh (pod, data, model) or (data, model)):
  * edges + pairs shard over `model`, split at PAIR boundaries so the
    edge->pair reduction never crosses shards (zero-collective pair stage);
    shards are padded to equal length with never-active sentinel edges.
  * query lanes (the OTCD wave) shard over `pod` x `data` — embarrassingly
    parallel, linear scaling.
  * the only cross-shard exchange is the per-iteration vertex-degree
    combine over `model`.  Two variants (EXPERIMENTS §Perf hillclimbs them):
      combine="psum":  all-reduce of the dense [V, Q_loc] f32 degrees;
      combine="rs_ag": psum_scatter the degrees, threshold locally, then
                       all-gather the 1-bit alive mask — ~36x less wire.

The paper's Table 5 notes billion-edge TELs "would require the distributed
memory cluster"; this module is that cluster design, with the tcq-billion
config lowering on the 512-chip multi-pod mesh.

Two generations of the sharded layout live here:

* :class:`ShardPlan` — the serving path.  Pair-to-shard ownership is
  *frozen* at build time as half-open ranges over the canonical 64-bit
  pair key ``(u << 32) | v`` (pair tables are key-sorted, so a range of
  keys is a range of pair ids on every snapshot).  Per-shard edge/pair
  buffers are power-of-two *capacity classes* with the same sentinel
  conventions as ``graph.tel_arrays`` (t = int32 min, local pair id =
  pair capacity, hp_src = vertex capacity), so a streaming append
  refreshes every shard **in place**: same shapes, same owners — no
  reshard, no recompile (``refresh`` only grows a capacity when the
  live count outruns it, amortized O(1) by doubling).

* :func:`build_wave_step` / :class:`DistributedTCQ` — the original
  scalar-threshold one-shot engine, kept for the collective-lowering
  dry runs (launch/dryrun.py) and as the minimal reference.

The serving hot path (``engine.WavePipeline`` subclassed as
:class:`ShardedWavePipeline`) runs :func:`make_sharded_step_fn`'s
per-lane-vector step: the same ``StepResult`` contract as
``core.wave.make_wave_step_fn`` — per-lane (ts, te, k, h), packed uint32
bitmask, TTI + edge counts — so the QueryState pool scheduler,
mid-flight admission, EmptyStaircase pruning and TTI-cache probes drive
sharded lanes unchanged, and every result is bit-identical to the
single-device engine (lanes are mathematically independent; a lane past
its fixpoint just rides idempotent extra iterations).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as PS

from repro.core.engine import WavePipeline, _Slot, unpack_alive_u32
from repro.core.graph import TemporalGraph, pow2_capacity
from repro.core.wave import (DegradationLadder, ResilienceConfig,
                             StepResult, _pack_u32, make_oracle_step_fn)
from repro.launch.mesh import dp_axes

_I32_MAX = jnp.iinfo(jnp.int32).max
_I32_MIN = jnp.iinfo(jnp.int32).min


def mesh_shard_counts(mesh) -> Tuple[int, int]:
    """(lane_shards, model_shards) of a mesh: lanes shard over pod x data,
    edges over model."""
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    m = shape.get("model", 1)
    return mesh.devices.size // m, m


def _lane_axes(mesh):
    dp = dp_axes(mesh)
    return dp if len(dp) > 1 else dp[0]


# ===================================================================== plans
class ShardedTEL(NamedTuple):
    """Host-side pair-aligned edge partition, stacked as [m, ...] arrays."""
    src: np.ndarray        # [m, E_s]
    dst: np.ndarray        # [m, E_s]
    t: np.ndarray          # [m, E_s]  (int32 min => sentinel padding)
    pair_local: np.ndarray  # [m, E_s]  local pair id (P_s => sentinel)
    hp_src: np.ndarray     # [m, HP_s] vertex of half-pair (V_pad => sentinel)
    hp_pair: np.ndarray    # [m, HP_s] local pair id
    num_vertices: int      # padded to a multiple of 8*m
    num_pairs_shard: int
    num_shards: int


@dataclasses.dataclass(eq=False)
class ShardPlan:
    """Capacity-class sharded TEL with frozen pair-key ownership.

    ``bounds`` are m+1 half-open cuts over the canonical 64-bit pair key
    ``(pair_u << 32) | pair_v``: shard i owns every pair whose key falls
    in ``[bounds[i], bounds[i+1])``.  Pair tables are key-sorted on every
    snapshot (``TemporalGraph`` builds them that way), so ownership maps
    to contiguous pair-id ranges via one ``searchsorted`` — including for
    pairs that did not exist when the plan was built.  Edge/pair buffers
    are pow2 capacity classes with ``tel_arrays``-compatible sentinels,
    so :meth:`refresh` absorbs appends without changing shapes (the
    compiled sharded step's jit cache stays warm across epochs).

    Duck-types :class:`ShardedTEL`'s fields, so the legacy one-shot
    engine (`build_wave_step`, `DistributedTCQ`) runs on it unchanged.
    """

    src: np.ndarray          # [m, e_cap]
    dst: np.ndarray          # [m, e_cap]
    t: np.ndarray            # [m, e_cap]   (int32 min => sentinel)
    pair_local: np.ndarray   # [m, e_cap]   (p_cap => sentinel)
    hp_src: np.ndarray       # [m, 2*p_cap] (v_pad => sentinel)
    hp_pair: np.ndarray      # [m, 2*p_cap]
    num_vertices: int        # v_pad: multiple of 8*m
    num_pairs_shard: int     # p_cap
    num_shards: int          # m
    bounds: np.ndarray       # [m+1] int64 frozen pair-key cuts
    epoch: int = 0

    @property
    def e_cap(self) -> int:
        return int(self.src.shape[1])

    @property
    def p_cap(self) -> int:
        return int(self.num_pairs_shard)

    # ------------------------------------------------------------- building
    @staticmethod
    def _pair_keys(graph: TemporalGraph) -> np.ndarray:
        return ((graph.pair_u.astype(np.int64) << 32)
                | graph.pair_v.astype(np.int64))

    @staticmethod
    def _cuts(graph: TemporalGraph, bounds: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray]:
        """(pair cuts [m+1], edge cuts [m+1]) of a snapshot under frozen
        key bounds.  Edges are (pair, t)-sorted, so each shard's edges
        are one contiguous slice."""
        keys = ShardPlan._pair_keys(graph)
        pcuts = np.searchsorted(keys, bounds).astype(np.int64)
        ecuts = np.searchsorted(graph.pair_id, pcuts).astype(np.int64)
        return pcuts, ecuts

    @classmethod
    def build(cls, graph: TemporalGraph, m: int, *,
              vertex_capacity: Optional[int] = None) -> "ShardPlan":
        """Freeze edge-balanced pair-aligned ownership over ``graph``."""
        e, p = graph.num_edges, graph.num_pairs
        keys = cls._pair_keys(graph)
        # edge-balanced cuts, frozen as the KEY of the pair at each cut
        # so ownership survives pair renumbering across appends
        bounds = np.empty(m + 1, np.int64)
        bounds[0] = np.iinfo(np.int64).min
        bounds[m] = np.iinfo(np.int64).max
        for i in range(1, m):
            target = min(i * (-(-e // m)), e)
            if e == 0 or target >= e:
                bounds[i] = bounds[m]
                continue
            pid = int(graph.pair_id[min(target, e - 1)])
            bounds[i] = keys[pid]
        v_pad = cls._round_vertices(
            graph.num_vertices if vertex_capacity is None
            else vertex_capacity, m)
        plan = cls(src=None, dst=None, t=None, pair_local=None, hp_src=None,
                   hp_pair=None, num_vertices=v_pad, num_pairs_shard=0,
                   num_shards=m, bounds=bounds, epoch=int(graph.epoch))
        plan._refill(graph, grow_only=False)
        return plan

    @staticmethod
    def _round_vertices(v: int, m: int) -> int:
        # byte-aligned per model shard: the rs_ag alive exchange slices V/m
        # columns and the packed transfer works in whole bytes
        return -(-max(1, int(v)) // (8 * m)) * 8 * m

    def refresh(self, graph: TemporalGraph, *,
                vertex_capacity: Optional[int] = None) -> bool:
        """Re-fill every shard from a new snapshot under the frozen
        ownership bounds.  Returns True when no buffer changed shape —
        the streaming steady state: the sharded step's compiled program
        is reused as-is.  A capacity that overflows grows to the next
        power of two (new shapes, one recompile — amortized O(1))."""
        if vertex_capacity is not None:
            v_pad = self._round_vertices(vertex_capacity, self.num_shards)
            if v_pad < self.num_vertices:
                v_pad = self.num_vertices    # vertex width never shrinks
        else:
            v_pad = max(self.num_vertices,
                        self._round_vertices(graph.num_vertices,
                                             self.num_shards))
        same_v = v_pad == self.num_vertices
        self.num_vertices = v_pad
        same = self._refill(graph, grow_only=True) and same_v
        self.epoch = int(graph.epoch)
        return same

    def _refill(self, graph: TemporalGraph, *, grow_only: bool) -> bool:
        m = self.num_shards
        pcuts, ecuts = self._cuts(graph, self.bounds)
        n_e = int((ecuts[1:] - ecuts[:-1]).max()) if m else 0
        n_p = int((pcuts[1:] - pcuts[:-1]).max()) if m else 0
        e_cap = pow2_capacity(n_e)
        p_cap = pow2_capacity(n_p)
        if grow_only:
            same = e_cap <= self.e_cap and p_cap <= self.p_cap
            e_cap = max(e_cap, self.e_cap)
            p_cap = max(p_cap, self.p_cap)
        else:
            same = False
        v_pad = self.num_vertices
        src = np.zeros((m, e_cap), np.int32)
        dst = np.zeros((m, e_cap), np.int32)
        tt = np.full((m, e_cap), _I32_MIN, np.int32)
        pl = np.full((m, e_cap), p_cap, np.int32)
        hps = np.full((m, 2 * p_cap), v_pad, np.int32)
        hpp = np.zeros((m, 2 * p_cap), np.int32)
        for i in range(m):
            a, b = int(ecuts[i]), int(ecuts[i + 1])
            lo, hi = int(pcuts[i]), int(pcuts[i + 1])
            n = b - a
            src[i, :n] = graph.src[a:b]
            dst[i, :n] = graph.dst[a:b]
            tt[i, :n] = graph.t[a:b]
            pl[i, :n] = graph.pair_id[a:b] - lo
            np_l = hi - lo
            h_src = np.concatenate([graph.pair_u[lo:hi],
                                    graph.pair_v[lo:hi]])
            h_pair = np.concatenate([np.arange(np_l), np.arange(np_l)])
            order = np.argsort(h_src, kind="stable")
            hps[i, :2 * np_l] = h_src[order]
            hpp[i, :2 * np_l] = h_pair[order]
        self.src, self.dst, self.t, self.pair_local = src, dst, tt, pl
        self.hp_src, self.hp_pair = hps, hpp
        self.num_pairs_shard = p_cap
        return same

    def window_arrays(self, graph: TemporalGraph, ts: int, te: int
                      ) -> Tuple[np.ndarray, ...]:
        """Window-truncated per-shard edge arrays (src, dst, t,
        pair_local), pow2-bucketed like ``TCQEngine._window_tel``'s
        single-device truncation so compiled step programs are shared
        across windows of similar size.  ``graph`` may be any snapshot
        whose pairs the frozen bounds cover (ancestors always qualify);
        the half-pair tables come from :meth:`hp_arrays`."""
        m = self.num_shards
        pcuts, ecuts = self._cuts(graph, self.bounds)
        win = (graph.t >= ts) & (graph.t <= te)
        locs = []
        for i in range(m):
            a, b = int(ecuts[i]), int(ecuts[i + 1])
            locs.append(np.flatnonzero(win[a:b]) + a)
        e_cap = pow2_capacity(max((loc.size for loc in locs), default=0))
        src = np.zeros((m, e_cap), np.int32)
        dst = np.zeros((m, e_cap), np.int32)
        tt = np.full((m, e_cap), _I32_MIN, np.int32)
        pl = np.full((m, e_cap), self.p_cap, np.int32)
        for i, loc in enumerate(locs):
            n = loc.size
            src[i, :n] = graph.src[loc]
            dst[i, :n] = graph.dst[loc]
            tt[i, :n] = graph.t[loc]
            pl[i, :n] = graph.pair_id[loc] - int(pcuts[i])
        return src, dst, tt, pl

    def hp_arrays(self, graph: TemporalGraph) -> Tuple[np.ndarray, ...]:
        """Half-pair tables (hp_src, hp_pair) for any covered snapshot at
        the plan's current capacities.  For the plan's own snapshot these
        are just ``(self.hp_src, self.hp_pair)``."""
        if int(graph.epoch) == self.epoch:
            return self.hp_src, self.hp_pair
        m = self.num_shards
        pcuts, _ = self._cuts(graph, self.bounds)
        n_p = int((pcuts[1:] - pcuts[:-1]).max()) if m else 0
        if n_p > self.p_cap:
            raise ValueError("snapshot exceeds plan pair capacity — not "
                             "an ancestor of the plan's current graph")
        hps = np.full((m, 2 * self.p_cap), self.num_vertices, np.int32)
        hpp = np.zeros((m, 2 * self.p_cap), np.int32)
        for i in range(m):
            lo, hi = int(pcuts[i]), int(pcuts[i + 1])
            np_l = hi - lo
            h_src = np.concatenate([graph.pair_u[lo:hi],
                                    graph.pair_v[lo:hi]])
            h_pair = np.concatenate([np.arange(np_l), np.arange(np_l)])
            order = np.argsort(h_src, kind="stable")
            hps[i, :2 * np_l] = h_src[order]
            hpp[i, :2 * np_l] = h_pair[order]
        return hps, hpp


def shard_graph(graph: TemporalGraph, m: int) -> ShardPlan:
    """Pair-aligned edge partition over ``m`` model shards.

    Returns a capacity-class :class:`ShardPlan` (pow2 sentinel-padded,
    ``refresh``-able in place across appends); duck-types the legacy
    :class:`ShardedTEL` fields.
    """
    return ShardPlan.build(graph, m)


def abstract_sharded_tel(num_vertices: int, num_edges: int, num_pairs: int,
                         m: int) -> ShardedTEL:
    """ShapeDtypeStruct stand-in for the dry-run (no allocation)."""
    e_s = -(-num_edges // m)
    p_s = -(-num_pairs // m)
    v_pad = -(-num_vertices // (8 * m)) * 8 * m
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    tel = ShardedTEL(sds((m, e_s)), sds((m, e_s)), sds((m, e_s)),
                     sds((m, e_s)), sds((m, 2 * p_s)), sds((m, 2 * p_s)),
                     v_pad, p_s, m)
    return tel


# ======================================================= degree primitives
def _local_degrees(src, dst, t, pair_l, hp_src, hp_pair, alive, ts, te, h,
                   *, p_s, v_pad):
    """One shard's partial degrees.  alive: [Qloc, V]; returns [V, Qloc]."""
    win = (t[None, :] >= ts[:, None]) & (t[None, :] <= te[:, None])
    ea = win & alive[:, src] & alive[:, dst]                 # [Qloc, E_s]
    paircnt = jax.ops.segment_sum(ea.T.astype(jnp.float32), pair_l,
                                  num_segments=p_s + 1,
                                  indices_are_sorted=True)[:p_s]
    pairact = (paircnt >= h).astype(jnp.float32)             # [P_s, Qloc]
    contrib = pairact[jnp.minimum(hp_pair, p_s - 1), :]
    deg = jax.ops.segment_sum(contrib, hp_src,
                              num_segments=v_pad + 1,
                              indices_are_sorted=True)[:v_pad]
    return deg                                               # [V, Qloc]


def build_wave_step(mesh, *, num_vertices: int, combine: str = "rs_ag",
                    p_s: int, max_iters: int = 0, single_iteration=False):
    """shard_map'd batched peel over (pod, data | data) query lanes and
    model-axis edge shards.  Returns a jit-able
    step(tel_arrays..., alive, ts, te, k, h) -> (alive, tti_lo, tti_hi,
    n_edges, iters)."""
    dp = dp_axes(mesh)
    m = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    v_pad = num_vertices
    assert v_pad % m == 0

    def one_iter(src, dst, t, pair_l, hp_src, hp_pair, alive, ts, te, k, h):
        deg_part = _local_degrees(src, dst, t, pair_l, hp_src, hp_pair,
                                  alive, ts, te, h, p_s=p_s, v_pad=v_pad)
        if combine == "psum":
            deg = lax.psum(deg_part, "model").T              # [Qloc, V]
            return alive & (deg >= k)
        deg_s = lax.psum_scatter(deg_part, "model",
                                 scatter_dimension=0, tiled=True).T
        idx = lax.axis_index("model")
        v_m = v_pad // m
        alive_slice = lax.dynamic_slice_in_dim(alive, idx * v_m, v_m, axis=1)
        new_slice = alive_slice & (deg_s >= k)
        if combine == "rs_ag_packed":
            # §Perf iteration 3: gather 1 BIT per vertex instead of one
            # byte — 8x less wire on the alive exchange
            packed = jnp.packbits(new_slice, axis=1)
            gathered = lax.all_gather(packed, "model", axis=1, tiled=True)
            return jnp.unpackbits(
                gathered, axis=1, count=v_pad).astype(bool)
        return lax.all_gather(new_slice, "model", axis=1, tiled=True)

    def step(src, dst, t, pair_l, hp_src, hp_pair, alive, ts, te, k, h):
        src, dst, t = src[0], dst[0], t[0]
        pair_l, hp_src, hp_pair = pair_l[0], hp_src[0], hp_pair[0]
        if single_iteration:
            alive = one_iter(src, dst, t, pair_l, hp_src, hp_pair, alive,
                             ts, te, k, h)
            iters = jnp.int32(1)
        else:
            def cond(s):
                a, changed, it = s
                more = changed
                if max_iters:
                    more = more & (it < max_iters)
                return more

            def body(s):
                a, _, it = s
                na = one_iter(src, dst, t, pair_l, hp_src, hp_pair, a,
                              ts, te, k, h)
                return na, jnp.any(na != a), it + 1

            alive, _, iters = lax.while_loop(
                cond, body, (alive, jnp.bool_(True), jnp.int32(0)))
        # TTI + edge counts: local then min/max/sum over the model axis
        win = (t[None, :] >= ts[:, None]) & (t[None, :] <= te[:, None])
        ea = win & alive[:, src] & alive[:, dst]
        n_edges = lax.psum(jnp.sum(ea, axis=1, dtype=jnp.int32), "model")
        lo = lax.pmin(jnp.min(jnp.where(ea, t[None, :], _I32_MAX), axis=1),
                      "model")
        hi = lax.pmax(jnp.max(jnp.where(ea, t[None, :], _I32_MIN),
                              axis=1), "model")
        return alive, lo, hi, n_edges, iters

    edge_spec = PS("model", None)
    lane_axes = dp if len(dp) > 1 else dp[0]
    lane = PS(lane_axes)
    alive_spec = PS(lane_axes, None)
    smapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(edge_spec, edge_spec, edge_spec, edge_spec, edge_spec,
                  edge_spec, alive_spec, lane, lane, PS(), PS()),
        out_specs=(alive_spec, lane, lane, lane, PS()),
        check_vma=False)
    return smapped


def wave_shardings(mesh, num_vertices: int, m: int):
    dp = dp_axes(mesh)
    lane = dp if len(dp) > 1 else dp[0]
    return {
        "edges": NamedSharding(mesh, PS("model", None)),
        "alive": NamedSharding(mesh, PS(lane, None)),
        "lane": NamedSharding(mesh, PS(lane)),
        "scalar": NamedSharding(mesh, PS()),
    }


# ============================================== serving step (per-lane k/h)
def combine_bytes_per_lane_iter(combine: str, num_vertices: int,
                                model_shards: int) -> int:
    """Analytic wire bytes one lane moves through the degree combine per
    fixpoint iteration (ring-collective model, summed across the mesh).

    psum:  all-reduce of [V] f32 partial degrees — 2*(m-1)/m * 4V bytes
           per shard, m shards.
    rs_ag: psum_scatter the same payload one direction ((m-1)/m * 4V per
           shard) plus an all-gather of the V/m-slice bool alive mask
           ((m-1)/m * V bytes per shard).
    """
    m = int(model_shards)
    if m <= 1:
        return 0
    v = int(num_vertices)
    if combine == "psum":
        return 2 * (m - 1) * 4 * v
    return (m - 1) * (4 * v + v)


def _all_axes(mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


@functools.lru_cache(maxsize=32)
def _sharded_step_jit(mesh, v_pad: int, p_cap: int, combine: str,
                      donate: bool):
    """jit(shard_map) for the per-lane-vector sharded step.  Cached per
    (mesh, capacities, combine): jit itself re-specializes per edge-cap
    bucket, so one entry serves every window in a capacity class."""
    L, m = mesh_shard_counts(mesh)
    assert v_pad % max(1, m) == 0
    axes = _all_axes(mesh)
    lane_axes = _lane_axes(mesh)
    edge_spec = PS("model", None)
    lane = PS(lane_axes)
    alive_spec = PS(lane_axes, None)

    def local_step(src, dst, t, pair_l, hp_src, hp_pair, alive,
                   ts, te, k, h):
        src, dst, t, pair_l = src[0], dst[0], t[0], pair_l[0]
        hp_src, hp_pair = hp_src[0], hp_pair[0]
        # the [Wloc, E_s] window mask depends only on (ts, te) — hoisted
        # out of the fixpoint loop exactly like peel_to_fixpoint
        win = (t[None, :] >= ts[:, None]) & (t[None, :] <= te[:, None])

        def cond(s):
            return s[2]

        def body(s):
            cur, _, _, it = s
            ea = win & cur[:, src] & cur[:, dst]
            paircnt = jax.ops.segment_sum(
                ea.T.astype(jnp.float32), pair_l,
                num_segments=p_cap + 1, indices_are_sorted=True)[:p_cap]
            pairact = (paircnt >= h[None, :]).astype(jnp.float32)
            contrib = pairact[hp_pair, :]
            deg_part = jax.ops.segment_sum(
                contrib, hp_src,
                num_segments=v_pad + 1, indices_are_sorted=True)[:v_pad]
            if m == 1 or combine == "psum":
                deg = deg_part if m == 1 else lax.psum(deg_part, "model")
                new = cur & (deg.T >= k[:, None])
            else:
                deg_s = lax.psum_scatter(deg_part, "model",
                                         scatter_dimension=0, tiled=True).T
                idx = lax.axis_index("model")
                v_m = v_pad // m
                a_slice = lax.dynamic_slice_in_dim(cur, idx * v_m, v_m,
                                                   axis=1)
                new_slice = a_slice & (deg_s >= k[:, None])
                new = lax.all_gather(new_slice, "model", axis=1, tiled=True)
            return new, ea, jnp.any(new != cur), it + 1

        ea0 = jnp.zeros((alive.shape[0], t.shape[0]), dtype=bool)
        alive, ea, _, iters = lax.while_loop(
            cond, body, (alive, ea0, jnp.bool_(True), jnp.int32(0)))
        # the final iteration observed new == cur, so the carried ea is
        # the fixpoint's edge activity — local stats then mesh reductions
        n_edges = jnp.sum(ea, axis=1, dtype=jnp.int32)
        lo = jnp.min(jnp.where(ea, t[None, :], _I32_MAX), axis=1)
        hi = jnp.max(jnp.where(ea, t[None, :], _I32_MIN), axis=1)
        if m > 1:
            n_edges = lax.psum(n_edges, "model")
            lo = lax.pmin(lo, "model")
            hi = lax.pmax(hi, "model")
        iters = lax.pmax(iters, axes)
        return StepResult(alive, _pack_u32(alive, v_pad), lo, hi,
                          n_edges, iters)

    smapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(edge_spec, edge_spec, edge_spec, edge_spec, edge_spec,
                  edge_spec, alive_spec, lane, lane, lane, lane),
        out_specs=StepResult(alive_spec, PS(lane_axes, None), lane, lane,
                             lane, PS()),
        check_vma=False)
    return jax.jit(smapped, donate_argnums=(6,) if donate else ())


def make_sharded_step_fn(mesh, arrays, *, num_vertices: int, p_cap: int,
                         combine: str = "psum", donate: bool = True):
    """Per-lane-vector sharded device step with the single-device
    ``StepResult`` contract: ``step(alive, ts, te, k, h)``, ts/te/k/h
    per-lane [W] vectors, alive [W, V] sharded over the lane axis.

    ``arrays`` are the six device edge/pair shards (src, dst, t,
    pair_local, hp_src, hp_pair), each [m, ...] with PS("model", None)
    placement.  The alive buffer is donated through the step when
    ``donate`` (the pipeline's persistent lane slab); ladder rungs pass
    ``donate=False`` so failed calls replay intact.
    """
    L, m = mesh_shard_counts(mesh)
    jitted = _sharded_step_jit(mesh, int(num_vertices), int(p_cap),
                               combine, bool(donate))
    lane_sh = NamedSharding(mesh, PS(_lane_axes(mesh)))

    def step(alive, ts, te, k, h):
        w = alive.shape[0]
        lanes = [x if (isinstance(x, jax.Array) and x.shape == (w,)
                       and x.sharding == lane_sh)
                 else jax.device_put(
                     jnp.broadcast_to(jnp.asarray(x, jnp.int32), (w,)),
                     lane_sh)
                 for x in (ts, te, k, h)]
        return jitted(*arrays, alive, *lanes)

    step.backend = "xla_sharded"
    step.interpret = False
    step.combine = combine
    step.lane_shards = L
    step.model_shards = m
    step.bytes_per_lane_iter = combine_bytes_per_lane_iter(
        combine, num_vertices, m)
    return step


def make_sharded_kernel_step(mesh, tel, num_vertices: int, *,
                             interpret: Optional[bool] = None,
                             vmem_budget_bytes: Optional[int] = None,
                             on_refuse=None):
    """Fused Pallas peel-to-fixpoint kernel as the per-shard local step.

    Only meshes with a trivial model axis qualify (model=1 — edges
    replicated, lanes sharded over pod x data): the kernel's host-side
    band analysis bakes one TEL's segment structure into the program,
    and shard_map is SPMD — m model shards would need m different
    programs.  On model-sharded meshes callers fall back to the XLA
    composite local step (the ladder logs the unavailable rung).

    Returns None when the kernel itself declines (SMEM or VMEM budget;
    ``on_refuse`` then gets the budget's name, as in
    ``make_fused_wave_step``).
    """
    L, m = mesh_shard_counts(mesh)
    if m != 1:
        return None
    from repro.kernels.wave_peel.ops import (DEFAULT_VMEM_BUDGET,
                                             make_fused_wave_step)

    budget = (DEFAULT_VMEM_BUDGET if vmem_budget_bytes is None
              else int(vmem_budget_bytes))
    fused = make_fused_wave_step(tel, num_vertices,
                                 interpret=interpret, donate=False,
                                 vmem_budget_bytes=budget,
                                 on_refuse=on_refuse)
    if fused is None:
        return None
    axes = _all_axes(mesh)
    lane_axes = _lane_axes(mesh)
    lane = PS(lane_axes)
    alive_spec = PS(lane_axes, None)
    lane_sh = NamedSharding(mesh, lane)

    def local_step(alive, ts, te, k, h):
        res = fused(alive, ts, te, k, h)     # inlines: kernel per shard
        return res._replace(iters=lax.pmax(res.iters, axes))

    smapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(alive_spec, lane, lane, lane, lane),
        out_specs=StepResult(alive_spec, PS(lane_axes, None), lane, lane,
                             lane, PS()),
        check_vma=False)
    jitted = jax.jit(smapped)

    def step(alive, ts, te, k, h):
        w = alive.shape[0]
        lanes = [jax.device_put(
            jnp.broadcast_to(jnp.asarray(x, jnp.int32), (w,)), lane_sh)
            for x in (ts, te, k, h)]
        return jitted(alive, *lanes)

    step.jitted = jitted         # the shard_map program (compile checks)
    step.backend = "pallas"
    step.interpret = bool(getattr(fused, "interpret", False))
    step.combine = "none"
    step.lane_shards = L
    step.model_shards = m
    step.bytes_per_lane_iter = 0
    return step


class ShardedDegradationLadder(DegradationLadder):
    """PR 5's graceful-degradation ladder over the *sharded* lowerings:
    fused Pallas within-shard (lane-sharded meshes) -> sharded XLA
    composite -> serial numpy oracle.

    shard_map programs are SPMD, so demotion swaps the local step for
    every shard at once (per-shard host control flow cannot live inside
    one program); the kernel rung *is* the per-shard local step when the
    mesh qualifies (model=1).  Inherits the call/tripwire/demote
    machinery from :class:`core.wave.DegradationLadder` — the tripwire
    recomputes one random lane on the unsharded numpy oracle, which the
    sharded step must match bit-for-bit (lanes are independent), and a
    demoted-to-oracle pool keeps running: the pipeline's refill jits
    re-pin the unsharded oracle output to the mesh on the next assemble.
    """

    def __init__(self, mesh, arrays, tel, num_vertices: int, *,
                 p_cap: int, combine: str = "psum",
                 use_kernel: bool = False,
                 config: Optional[ResilienceConfig] = None):
        # rebuild DegradationLadder.__init__'s state by hand: the rungs
        # here are sharded lowerings, not the single-device ones
        self.config = config or ResilienceConfig()
        self.events = []
        self.calls = 0
        self.rung = 0
        self.fallback = None
        self._rng = np.random.default_rng(self.config.seed)
        L, m = mesh_shard_counts(mesh)
        interpret = self.config.interpret
        rungs = []
        if use_kernel:
            if m != 1:
                self._log("pallas", "multi_shard",
                          f"model={m}: the fused kernel bakes one TEL's "
                          "band structure; kernel-within-shard needs a "
                          "lane-only mesh")
            else:
                refused = []
                try:
                    fused = make_sharded_kernel_step(
                        mesh, tel, num_vertices, interpret=interpret,
                        vmem_budget_bytes=self.config.vmem_budget_bytes,
                        on_refuse=refused.append)
                    if fused is None:
                        self.fallback = refused[0]
                        self._log("pallas", self.fallback, "")
                    else:
                        rungs.append(("pallas", fused))
                except Exception as e:               # pragma: no cover
                    self.fallback = "build_error"
                    self._log("pallas", "build_error", repr(e))
        rungs.append(("xla", make_sharded_step_fn(
            mesh, arrays, num_vertices=num_vertices, p_cap=p_cap,
            combine=combine, donate=False)))
        oracle = make_oracle_step_fn(tel, num_vertices)
        self._truth = oracle
        rungs.append(("oracle", oracle))
        wrap = self.config.rung_wrapper
        if wrap is not None:
            rungs = [(name, wrap(name, fn) or fn) for name, fn in rungs]
        self.rungs = rungs
        self.combine = combine
        self.lane_shards = L
        self.model_shards = m
        self.bytes_per_lane_iter = combine_bytes_per_lane_iter(
            combine, num_vertices, m)


# ================================================== sharded lane pipeline
@functools.lru_cache(maxsize=64)
def _sharded_lane_fns(ash: NamedSharding):
    """Batched lane-refill jits pinned to one alive sharding.

    At W = 64-512 sharded lanes, per-lane refill dispatch (one jit call
    per lane, ~0.1 ms each) would dominate the step itself; instead every
    assemble issues at most two device calls: one codes-vector constant
    fill (0=keep, 1=ones, 2=zeros) and one row-scatter for the warm
    starts.  Both donate the buffer and pin the sharded layout.
    """
    fill = jax.jit(
        lambda buf, codes: jnp.where((codes == 0)[:, None], buf,
                                     (codes == 1)[:, None]),
        donate_argnums=(0,), out_shardings=ash)
    scatter = jax.jit(
        lambda buf, idx, rows: buf.at[idx].set(rows),
        donate_argnums=(0,), out_shardings=ash)
    return fill, scatter


class ShardedWavePipeline(WavePipeline):
    """Mesh-spanning depth-D slot ring: ``engine.WavePipeline`` whose
    lane buffers live sharded over the mesh's lane axis and whose device
    step is the shard_map'd peel.

    The pool scheduler — EDF claiming, mid-flight admission, staircase
    pruning, TTI-cache probes — runs unchanged on host (it only ever
    touches lanes through the step's StepResult and the refill hooks);
    what changes is the device side:

    * slot buffers are allocated sharded ([W, V] with lanes split over
      pod x data) and stay sharded through every donated step;
    * lane refills are *batched*: one constant-fill call + one warm-row
      scatter per assemble instead of up to W per-lane dispatches — at
      W = 64-512 sharded lanes the per-call dispatch overhead would
      otherwise swallow the step-amortization win (the single-device
      pipeline keeps its historical per-lane refills);
    * per-shard occupancy and combine-collective wire bytes are
      accounted per pool and surfaced through ``QueryStats`` /
      ``TCQEngine.stats()["distributed"]``.
    """

    def __init__(self, step_fn, *, mesh, num_vertices: int, wave: int,
                 depth: int = 2, dist_counters: Optional[dict] = None):
        L, m = mesh_shard_counts(mesh)
        if wave % L:
            raise ValueError(
                f"wave={wave} not a multiple of lane shards {L}")
        super().__init__(None, num_vertices, wave, depth, step_fn=step_fn)
        self.mesh = mesh
        self.lane_shards = L
        self.model_shards = m
        self._w_loc = wave // L
        self._ash = NamedSharding(mesh, PS(_lane_axes(mesh), None))
        self._lsh = NamedSharding(mesh, PS(_lane_axes(mesh)))
        self._fill_codes, self._scatter = _sharded_lane_fns(self._ash)
        self._bytes_per_lane_iter = int(
            getattr(step_fn, "bytes_per_lane_iter", 0))
        self._shard_occupied = [0] * L
        self._dist = dist_counters

    # ----------------------------------------------------------- hooks
    def _new_slot(self) -> _Slot:
        buf = jax.device_put(
            np.zeros((self.wave, self.num_vertices), dtype=bool),
            self._ash)
        return _Slot(self.wave, self.num_vertices, buf=buf)

    def _refill_lanes(self, buf, sets, fills):
        if fills:
            codes = np.zeros(self.wave, np.int32)
            for li, value in fills:
                codes[li] = 1 if value else 2
            buf = self._fill_codes(buf, codes)
        if sets:
            # pow2-bucketed scatter width: pad by repeating the first
            # (index, row) pair — duplicate scatters of identical rows
            # commute — so R in [1, W] warm rows costs log2(W) compiled
            # variants instead of W.  Rows are stacked host-side (warm
            # rows arrive as host bitmask unpacks) so the whole batch
            # commits in the one scatter dispatch instead of per-row.
            r = pow2_capacity(len(sets), floor=1)
            idx = np.empty(r, np.int32)
            rows = np.empty((r, buf.shape[1]), bool)
            for j in range(r):
                li, row = sets[min(j, len(sets) - 1)]
                idx[j] = li
                rows[j] = np.asarray(row, dtype=bool)
            buf = self._scatter(buf, idx, rows)
        return buf

    def _record_occupied(self, occupied) -> None:
        for li in occupied:
            self._shard_occupied[li // self._w_loc] += 1

    def _warm_row(self, res, packed, li):
        """Host-unpack the lane's already-fetched u32 bitmask: slicing
        the mesh-sharded ``res.alive`` would be an eager 8-device gather
        per promoted row (the dominant retire cost at W >= 256)."""
        v = self.num_vertices
        return lambda: unpack_alive_u32(packed[li], v)

    def _commit_params(self, slot, params):
        """Lane params only change when lanes are refilled; committing
        the (ts, te, k, h) vectors across L shards every step would cost
        4L host->device transfers per step.  Cache the committed arrays
        on the slot and re-place them only when the host vectors moved."""
        cached = getattr(slot, "_params_np", None)
        if cached is not None and all(
                np.array_equal(a, b) for a, b in zip(cached, params)):
            return slot._params_dev
        slot._params_np = tuple(p.copy() for p in params)
        slot._params_dev = tuple(
            jax.device_put(list(params), [self._lsh] * len(params)))
        return slot._params_dev

    def _finish_pool(self, pool_stats) -> None:
        steps = pool_stats.device_steps
        if steps:
            pool_stats.shard_occupancy = [
                c / (steps * self._w_loc) for c in self._shard_occupied]
        pool_stats.collective_bytes = (
            self._bytes_per_lane_iter * self.wave * pool_stats.peel_iters)
        self._shard_occupied = [0] * self.lane_shards
        if self._dist is not None:
            self._dist["pool_runs"] += 1
            self._dist["device_steps"] += steps
            self._dist["collective_bytes"] += pool_stats.collective_bytes


# =============================================== one-shot reference engine
class DistributedTCQ:
    """Runnable distributed engine (any mesh, incl. degenerate test meshes).

    On a single-device mesh the shard_map program degenerates to the
    plain composite with collective no-ops, so the single-shard block
    routes through ``core.wave.make_wave_step_fn`` instead — the fused
    Pallas peel-to-fixpoint kernel on TPU, the XLA composite elsewhere
    (``use_fused=False`` restores the pure shard_map path, e.g. for the
    collective-lowering dry runs; ``True`` forces the kernel).  Multi-
    device meshes always run the sharded step — the fused kernel owns
    the *intra-shard* work and the model-axis degree combine stays a
    collective.
    """

    def __init__(self, graph: TemporalGraph, mesh, combine: str = "rs_ag",
                 *, use_fused: Optional[bool] = None):
        self.graph = graph
        self.mesh = mesh
        m = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
        plan = shard_graph(graph, m)
        self.plan = plan
        sh = wave_shardings(mesh, plan.num_vertices, m)
        self.arrays = tuple(
            jax.device_put(a, sh["edges"])
            for a in (plan.src, plan.dst, plan.t, plan.pair_local,
                      plan.hp_src, plan.hp_pair))
        self.step = jax.jit(build_wave_step(
            mesh, num_vertices=plan.num_vertices, combine=combine,
            p_s=plan.num_pairs_shard))
        self._sh = sh
        self._fused = None
        if mesh.devices.size == 1 and use_fused is not False:
            from repro.core.wave import make_wave_step_fn

            tel = graph.device_tel(vertex_capacity=plan.num_vertices)
            self._fused = make_wave_step_fn(tel, plan.num_vertices,
                                            use_kernel=use_fused)

    def query_wave(self, ts, te, k: int, h: int = 1, alive=None, *,
                   packed: bool = False):
        """Batched peel over the sharded TEL.  With ``packed=True`` the
        alive masks come back as [Q, ceil(V/32)] uint32 bitmasks (the
        engine's packed result-transfer path — 8x less wire than bool
        masks when the caller only needs them host-side; decode with
        ``engine.unpack_alive_u32``)."""
        q = len(ts)
        v = self.plan.num_vertices
        if alive is None:
            alive = jnp.ones((q, v), dtype=bool)
        if self._fused is not None:
            # single-shard block: the fused step already emits the packed
            # bitmask, so the packed transfer costs nothing extra here
            r = self._fused(jnp.asarray(alive, dtype=bool),
                            jnp.asarray(ts, jnp.int32),
                            jnp.asarray(te, jnp.int32),
                            jnp.int32(k), jnp.int32(h))
            if packed:
                return r.packed, r.tti_lo, r.tti_hi, r.n_edges, r.iters
            return r.alive, r.tti_lo, r.tti_hi, r.n_edges, r.iters
        alive = jax.device_put(alive, self._sh["alive"])
        ts = jax.device_put(jnp.asarray(ts, jnp.int32), self._sh["lane"])
        te = jax.device_put(jnp.asarray(te, jnp.int32), self._sh["lane"])
        out = self.step(*self.arrays, alive, ts, te, jnp.int32(k),
                        jnp.int32(h))
        if packed:
            from repro.core.engine import pack_alive_u32

            alive_out, lo, hi, ne, iters = out
            return (pack_alive_u32(alive_out, num_vertices=v),
                    lo, hi, ne, iters)
        return out
