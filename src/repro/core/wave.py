"""Wave-native batched TCD: Q query cells peeled in lockstep, kernel-ready.

`tcd_batch` (tcd.py) vmaps the scalar path; this module lays the data out
the way the MXU wants it — values [E, Q] / [2P, Q] — so the two segment
reductions become banded one-hot matmuls (the Pallas segdeg kernel), and
the whole wave shares one fixpoint loop.  The edge-activity / degree
split lets callers carry edge activity through the fixpoint loop and
skip the post-loop edge pass.  This is also the single-shard block of
the distributed engine (distributed.py wraps it in shard_map with a
cross-shard degree combine).

The device step itself — :func:`wave_step` (peel + TTI + stats + uint32
bitmask pack in one program) — lives here too, with two lowerings behind
one dispatcher, :func:`make_wave_step_fn`:

  * **fused Pallas** (``kernels/wave_peel``): the entire fixpoint loop
    runs on-chip per W-tile — no [W, E] HBM round-trips between
    iterations (compiled on TPU, interpret mode for CPU gates);
  * **XLA composite** (this module's ``peel_to_fixpoint`` chain): the
    portable fallback, also used when a TEL's VMEM working set exceeds
    the kernel budget.

Both lowerings are bit-identical (seeded fuzz gate in
tests/test_kernels.py); ``engine.WavePipeline``, :func:`tcd_wave` and
the distributed engine's single-shard block all route through the
dispatcher, so one kernel serves the single-query, batched and sharded
engines.
"""

from __future__ import annotations

import dataclasses
import functools
import weakref
from collections import OrderedDict
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.graph import DeviceTEL, TemporalGraph

_I32_MAX = jnp.iinfo(jnp.int32).max
_I32_MIN = jnp.iinfo(jnp.int32).min


class WaveResult(NamedTuple):
    alive: jnp.ndarray    # [Q, V]
    tti_lo: jnp.ndarray   # [Q]
    tti_hi: jnp.ndarray   # [Q]
    n_edges: jnp.ndarray  # [Q]
    n_verts: jnp.ndarray  # [Q]
    iters: jnp.ndarray    # scalar: fixpoint iterations of the wave


# ------------------------------------------------------- segsum closures
# (id(graph), epoch, use_kernel, interpret) -> (weakref(graph), closures).
# The band analysis (np.sort over 2P half-pairs + the kernel's k_max pass)
# used to rerun on every engine/bench construction for the same snapshot;
# epochs are immutable, so it is cacheable.  Keyed on the graph's
# process-unique ``uid`` — unlike ``id()``, never reused after GC, so a
# fresh graph allocated at a dead graph's address cannot inherit its
# closures.
_SEGSUM_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_SEGSUM_CACHE_MAX = 16


def make_segsum_fns(graph: TemporalGraph, *, use_kernel: Optional[bool] = None,
                    interpret: Optional[bool] = None):
    """(edges->pairs, halfpairs->vertices) segment-sum closures for a graph.

    use_kernel=True routes through the Pallas banded kernel (interpret mode
    on CPU); False uses jax.ops.segment_sum (XLA scatter path); None (the
    default) auto-dispatches — compiled Pallas on TPU, XLA elsewhere.  The
    band analysis (k_max) runs once per ``(graph, epoch)`` and is cached
    (graphs are immutable snapshots; appends bump ``epoch``).
    """
    from repro.kernels.segdeg.ops import make_banded_segsum, on_tpu

    if use_kernel is None:
        use_kernel = on_tpu()
    key = (graph.uid, graph.epoch, bool(use_kernel), interpret)
    hit = _SEGSUM_CACHE.get(key)
    if hit is not None:
        _SEGSUM_CACHE.move_to_end(key)
        return hit[1]
    tel_hp_src = np.sort(np.concatenate([graph.pair_u, graph.pair_v]))
    seg_pair = make_banded_segsum(graph.pair_id, graph.num_pairs,
                                  use_kernel=use_kernel, interpret=interpret)
    seg_vert = make_banded_segsum(tel_hp_src, graph.num_vertices,
                                  use_kernel=use_kernel, interpret=interpret)
    fns = (seg_pair, seg_vert)
    # identity lives entirely in the uid key; the weakref is kept only so
    # the entry does not extend the snapshot's lifetime
    _SEGSUM_CACHE[key] = (weakref.ref(graph), fns)
    while len(_SEGSUM_CACHE) > _SEGSUM_CACHE_MAX:
        _SEGSUM_CACHE.popitem(last=False)
    return fns


def wave_edge_activity(tel: DeviceTEL, alive: jnp.ndarray, ts, te
                       ) -> jnp.ndarray:
    """alive: [Q, V]; ts/te: [Q].  Returns [Q, E] bool edge activity."""
    win = (tel.t[None, :] >= ts[:, None]) & (tel.t[None, :] <= te[:, None])
    return win & alive[:, tel.src] & alive[:, tel.dst]


def wave_degrees_from_ea(tel: DeviceTEL, ea: jnp.ndarray, h,
                         *, num_vertices: int, seg_pair: Callable,
                         seg_vert: Callable) -> jnp.ndarray:
    """ea: [Q, E] edge activity; h: scalar or per-lane [Q].
    Returns [Q, V] int32 degrees."""
    paircnt = seg_pair(ea.T.astype(jnp.float32), tel.pair_id)  # [P, Q]
    pairact = (paircnt >= h).astype(jnp.float32)   # h broadcasts over lanes
    contrib = pairact[tel.hp_pair, :]                          # [2P, Q]
    deg = seg_vert(contrib, tel.hp_src)                        # [V, Q]
    return deg.T.astype(jnp.int32)


def wave_degrees(tel: DeviceTEL, alive: jnp.ndarray, ts, te, h,
                 *, num_vertices: int, seg_pair: Callable, seg_vert: Callable
                 ) -> jnp.ndarray:
    """alive: [Q, V]; ts/te: [Q].  Returns [Q, V] int32 degrees."""
    ea = wave_edge_activity(tel, alive, ts, te)
    return wave_degrees_from_ea(tel, ea, h, num_vertices=num_vertices,
                                seg_pair=seg_pair, seg_vert=seg_vert)


def peel_to_fixpoint(tel: DeviceTEL, alive: jnp.ndarray, ts, te, k, h,
                     *, num_vertices: int, seg_pair, seg_vert,
                     max_iters: int = 0):
    """Shared batched peel loop -> (alive, ea, iters); trace-time building
    block for `tcd_wave` and the composite ``wave_step`` lowering.

    k and h may be scalars (one threshold for the whole wave) or per-lane
    [Q] vectors — the multi-tenant scheduler packs cells from queries with
    different (k, h) into one wave, so the survivor test broadcasts the
    thresholds per lane.

    ea rides in the carry (as in tcd.tcd): the final iteration observed
    new == cur, so the carried ea is exactly the fixpoint's edge activity
    and callers skip the post-loop edge pass.
    """
    q = alive.shape[0]
    k_lane = jnp.broadcast_to(jnp.asarray(k, jnp.int32), (q,))
    h_lane = jnp.broadcast_to(jnp.asarray(h, jnp.int32), (q,))
    ts = jnp.broadcast_to(jnp.asarray(ts, jnp.int32), (q,))
    te = jnp.broadcast_to(jnp.asarray(te, jnp.int32), (q,))
    # the [Q, E] window mask depends only on (ts, te), never on alive —
    # computed once, reused by every fixpoint iteration (it used to be
    # rebuilt inside the loop body on this path)
    win = (tel.t[None, :] >= ts[:, None]) & (tel.t[None, :] <= te[:, None])

    def edge_activity(cur):
        return win & cur[:, tel.src] & cur[:, tel.dst]

    def cond(state):
        _, _, changed, it = state
        more = changed
        if max_iters:
            more = more & (it < max_iters)
        return more

    def body(state):
        cur, _, _, it = state
        ea = edge_activity(cur)
        deg = wave_degrees_from_ea(tel, ea, h_lane,
                                   num_vertices=num_vertices,
                                   seg_pair=seg_pair, seg_vert=seg_vert)
        new = cur & (deg >= k_lane[:, None])
        return new, ea, jnp.any(new != cur), it + 1

    ea0 = jnp.zeros((alive.shape[0], tel.t.shape[0]), dtype=bool)
    alive, ea, _, iters = lax.while_loop(
        cond, body, (alive, ea0, jnp.bool_(True), jnp.int32(0)))
    if max_iters:  # truncated peel may exit pre-fixpoint: ea would be stale
        ea = edge_activity(alive)
    return alive, ea, iters


# ------------------------------------------------------------ bitmask pack
def packed_width(num_vertices: int) -> int:
    """uint32 words per packed [V] vertex mask."""
    return max(1, -(-num_vertices // 32))


def _pack_u32(alive: jnp.ndarray, num_vertices: int) -> jnp.ndarray:
    """[..., V] bool -> [..., ceil(V/32)] uint32; vertex v = bit v%32 of
    word v//32 (LSB-first, matching np.unpackbits(bitorder="little"))."""
    w = packed_width(num_vertices)
    pad = w * 32 - num_vertices
    a = jnp.pad(alive, [(0, 0)] * (alive.ndim - 1) + [(0, pad)])
    a = a.reshape(a.shape[:-1] + (w, 32)).astype(jnp.uint32)
    return jnp.sum(a << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("num_vertices",))
def pack_alive_u32(alive: jnp.ndarray, *, num_vertices: int) -> jnp.ndarray:
    """Standalone jitted pack (used by the distributed engine's packed
    result transfer; ``wave_step`` fuses the same computation inline)."""
    return _pack_u32(alive, num_vertices)


def unpack_alive_u32(packed: np.ndarray, num_vertices: int) -> np.ndarray:
    """Host-side inverse of :func:`pack_alive_u32` — one bulk unpackbits."""
    packed = np.ascontiguousarray(np.asarray(packed).astype("<u4",
                                                            copy=False))
    bits = np.unpackbits(packed.view(np.uint8), axis=-1, bitorder="little")
    return bits[..., :num_vertices].astype(bool)


# ------------------------------------------------------------- fused step
class StepResult(NamedTuple):
    alive: jnp.ndarray    # [W, V] bool — the persistent lane buffer
    packed: jnp.ndarray   # [W, ceil(V/32)] uint32 bitmask of `alive`
    tti_lo: jnp.ndarray   # [W] int32 (I32_MAX when lane core is empty)
    tti_hi: jnp.ndarray   # [W] int32 (I32_MIN when lane core is empty)
    n_edges: jnp.ndarray  # [W] int32
    iters: jnp.ndarray    # scalar int32 — shared fixpoint iterations


def _wave_step_impl(tel: DeviceTEL, alive: jnp.ndarray, ts, te, k, h,
                    *, num_vertices: int, seg_pair, seg_vert) -> StepResult:
    alive, ea, iters = peel_to_fixpoint(
        tel, alive, ts, te, k, h, num_vertices=num_vertices,
        seg_pair=seg_pair, seg_vert=seg_vert)
    n_edges = jnp.sum(ea, axis=1, dtype=jnp.int32)
    tti_lo = jnp.min(jnp.where(ea, tel.t[None, :], _I32_MAX), axis=1)
    tti_hi = jnp.max(jnp.where(ea, tel.t[None, :], _I32_MIN), axis=1)
    return StepResult(alive, _pack_u32(alive, num_vertices),
                      tti_lo, tti_hi, n_edges, iters)


#: XLA-composite device step: peel W lanes to the fixpoint + TTI + stats +
#: bitmask pack in one jitted program.  ``ts``/``te``/``k``/``h`` are
#: per-lane [W] vectors — every lane may carry a different query's window
#: and thresholds.  ``alive`` is donated — the lane buffer is peeled in
#: place and handed back as ``StepResult.alive``.
wave_step = functools.partial(
    jax.jit, static_argnames=("num_vertices", "seg_pair", "seg_vert"),
    donate_argnums=(1,))(_wave_step_impl)

# non-donating twin for callers that reuse their alive buffer across calls
# (tcd_wave, benches); same trace, separate jit cache
_wave_step_nodonate = functools.partial(
    jax.jit, static_argnames=("num_vertices", "seg_pair",
                              "seg_vert"))(_wave_step_impl)


def _make_xla_step(tel: DeviceTEL, num_vertices: int, *,
                   seg_pair=None, seg_vert=None, donate: bool = False):
    """The XLA-composite lowering as a ``make_wave_step_fn``-shaped
    closure (also the degradation ladder's middle rung)."""
    if seg_pair is None or seg_vert is None:
        from repro.kernels.segdeg.ref import banded_segsum_ref

        if seg_pair is None:
            seg_pair = functools.partial(banded_segsum_ref,
                                         num_segments=tel.num_pairs)
        if seg_vert is None:
            seg_vert = functools.partial(banded_segsum_ref,
                                         num_segments=num_vertices)
    inner = wave_step if donate else _wave_step_nodonate

    def step(alive, ts, te, k, h):
        return inner(tel, alive, ts, te, k, h, num_vertices=num_vertices,
                     seg_pair=seg_pair, seg_vert=seg_vert)

    step.backend = "xla"
    step.interpret = False
    return step


def make_oracle_step_fn(tel: DeviceTEL, num_vertices: int):
    """Serial numpy reference step — the degradation ladder's last rung
    and the divergence tripwire's ground truth.

    Pure host-side numpy over host copies of the (possibly capacity- or
    bucket-padded) TEL: no jit, no Pallas, no XLA — nothing left to
    degrade to.  Bit-identical to the composite on every ``StepResult``
    field including the shared iteration count: the loop mirrors the
    composite's ``lax.while_loop`` (body runs while any lane changed, the
    final iteration observes the fixpoint), the segment reductions mirror
    the scatter paths' sentinel-drop semantics (``pair_id == P`` and
    ``hp_src == V`` fall outside the bincount slice), and the bitmask
    pack is the same LSB-first uint32 layout.
    """
    t = np.asarray(tel.t)
    src = np.asarray(tel.src)
    dst = np.asarray(tel.dst)
    pair_id = np.asarray(tel.pair_id).astype(np.int64)
    hp_src = np.asarray(tel.hp_src).astype(np.int64)
    hp_pair = np.asarray(tel.hp_pair).astype(np.int64)
    p_cap = int(tel.pair_u.shape[0])
    v = int(num_vertices)
    pw = packed_width(v)

    def _lanes(x, w, dtype=np.int64):
        return np.broadcast_to(np.asarray(x), (w,)).astype(dtype)

    def step(alive, ts, te, k, h):
        cur = np.array(np.asarray(alive), dtype=bool)
        w = cur.shape[0]
        ts_l, te_l = _lanes(ts, w), _lanes(te, w)
        k_l, h_l = _lanes(k, w), _lanes(h, w)
        win = (t[None, :] >= ts_l[:, None]) & (t[None, :] <= te_l[:, None])
        it = 0
        while True:
            ea = win & cur[:, src] & cur[:, dst]
            it += 1
            new = np.empty_like(cur)
            for li in range(w):
                paircnt = np.bincount(pair_id[ea[li]],
                                      minlength=p_cap + 1)[:p_cap]
                contrib = (paircnt >= h_l[li])[hp_pair]
                # sentinel halfpairs (hp_src == V) fall outside the slice,
                # like the scatter reduction's out-of-range segment drop
                deg = np.bincount(hp_src[contrib], minlength=v + 1)[:v]
                new[li] = cur[li] & (deg >= k_l[li])
            if np.array_equal(new, cur):
                break
            cur = new
        n_edges = ea.sum(axis=1).astype(np.int32)
        tti_lo = np.full(w, _I32_MAX, np.int32)
        tti_hi = np.full(w, _I32_MIN, np.int32)
        for li in range(w):
            if n_edges[li]:
                t_act = t[ea[li]]
                tti_lo[li] = t_act.min()
                tti_hi[li] = t_act.max()
        pad = pw * 32 - v
        bits = np.pad(cur, [(0, 0), (0, pad)])
        packed = np.packbits(bits, axis=-1,
                             bitorder="little").view("<u4")
        return StepResult(jnp.asarray(cur), jnp.asarray(packed),
                          jnp.asarray(tti_lo), jnp.asarray(tti_hi),
                          jnp.asarray(n_edges), jnp.int32(it))

    step.backend = "oracle"
    step.interpret = False
    return step


# --------------------------------------------------- degradation ladder
@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Knobs for the graceful-degradation ladder (pass as
    ``make_wave_step_fn(resilience=...)`` / ``TCQEngine(resilience=...)``).

    tripwire_every:
        Sample every Nth step call: recompute one random lane on the
        numpy oracle and compare bit-for-bit; a divergence quarantines
        the current rung and replays the call one rung down.  0 disables
        the tripwire (errors still demote).
    seed:
        Seeds the tripwire's lane sampling (determinism for the chaos
        harness).
    interpret / vmem_budget_bytes:
        Overrides for the Pallas rung's build (None = the dispatcher
        defaults).
    rung_wrapper:
        ``wrapper(name, step_fn) -> step_fn`` applied to each rung at
        build time — the fault-injection seam (``core/faultinject.py``).
    """

    tripwire_every: int = 64
    seed: int = 0
    interpret: Optional[bool] = None
    vmem_budget_bytes: Optional[int] = None
    rung_wrapper: Optional[Callable] = None


class DegradationLadder:
    """Graceful degradation across the step lowerings: fused Pallas ->
    XLA composite -> serial numpy oracle.

    Built like a step_fn, called like a step_fn.  Every rung is
    *non-donating*, so when a rung fails — a build/compile error, a
    raised fault, or a tripwire divergence — the same inputs replay on
    the next rung bit-identically: demotion is invisible in the results,
    it only shows up in ``events`` and latency.  A demoted rung is
    quarantined for this ladder's lifetime (ladders are pinned per
    ``(epoch, Ts, Te)`` window entry, so a quarantine lasts the epoch);
    an unavailable Pallas rung (VMEM budget, build failure) starts the
    ladder on the composite with the reason recorded.
    """

    def __init__(self, tel: DeviceTEL, num_vertices: int, *,
                 seg_pair=None, seg_vert=None,
                 use_kernel: bool = False,
                 interpret: Optional[bool] = None,
                 config: Optional[ResilienceConfig] = None):
        self.config = config or ResilienceConfig()
        self.events = []            # [{rung, reason, detail, call}]
        self.calls = 0
        self.rung = 0
        # why a wanted Pallas rung never built: "smem_tables",
        # "vmem_budget" or "build_error" (None: built, or not wanted)
        self.fallback = None
        self._rng = np.random.default_rng(self.config.seed)
        if self.config.interpret is not None:
            interpret = self.config.interpret
        rungs = []
        if use_kernel:
            from repro.kernels.wave_peel.ops import (DEFAULT_VMEM_BUDGET,
                                                     make_fused_wave_step)

            budget = (DEFAULT_VMEM_BUDGET
                      if self.config.vmem_budget_bytes is None
                      else int(self.config.vmem_budget_bytes))
            refused = []
            try:
                fused = make_fused_wave_step(tel, num_vertices,
                                             interpret=interpret,
                                             donate=False,
                                             vmem_budget_bytes=budget,
                                             on_refuse=refused.append)
                if fused is None:
                    self.fallback = refused[0]
                    self._log("pallas", self.fallback,
                              f"budget={budget} bytes"
                              if self.fallback == "vmem_budget" else "")
                else:
                    rungs.append(("pallas", fused))
            except Exception as e:
                self.fallback = "build_error"
                self._log("pallas", "build_error", repr(e))
        rungs.append(("xla", _make_xla_step(tel, num_vertices,
                                            seg_pair=seg_pair,
                                            seg_vert=seg_vert,
                                            donate=False)))
        oracle = make_oracle_step_fn(tel, num_vertices)
        self._truth = oracle        # tripwire ground truth stays unwrapped
        rungs.append(("oracle", oracle))
        wrap = self.config.rung_wrapper
        if wrap is not None:
            rungs = [(name, wrap(name, fn) or fn) for name, fn in rungs]
        self.rungs = rungs

    def _log(self, rung: str, reason: str, detail: str = "") -> None:
        self.events.append({"rung": rung, "reason": reason,
                            "detail": detail, "call": self.calls})

    @property
    def backend(self) -> str:
        return self.rungs[self.rung][0]

    @property
    def interpret(self) -> bool:
        return bool(getattr(self.rungs[self.rung][1], "interpret", False))

    def program_warm(self, alive, ts, te, k, h) -> bool:
        """Whether the current rung would reuse a compiled program: the
        fused rung's size class already ran (its ``program_warm``); any
        other rung, or a wrapped one, is taken to compile."""
        warm = getattr(self.rungs[self.rung][1], "program_warm", None)
        return bool(warm is not None and warm(alive, ts, te, k, h))

    def _demote(self, name: str, reason: str, detail: str = "") -> None:
        self._log(name, reason, detail)
        self.rung += 1

    @staticmethod
    def _lane_slice(x, lane: int, w: int) -> np.ndarray:
        return np.broadcast_to(np.asarray(x), (w,))[lane:lane + 1]

    def _lane_check(self, res: StepResult, alive, ts, te, k, h) -> bool:
        """Sampled cross-check: one random lane recomputed on the oracle
        (lanes are mathematically independent, so a single-lane oracle
        run must match that lane of the wave exactly — except the shared
        iteration count, which is a max over lanes)."""
        w = int(res.alive.shape[0])
        lane = int(self._rng.integers(w))
        truth = self._truth(
            np.asarray(alive)[lane:lane + 1],
            self._lane_slice(ts, lane, w), self._lane_slice(te, lane, w),
            self._lane_slice(k, lane, w), self._lane_slice(h, lane, w))
        got = jax.device_get((res.alive[lane], res.packed[lane],
                              res.tti_lo[lane], res.tti_hi[lane],
                              res.n_edges[lane]))
        want = jax.device_get((truth.alive[0], truth.packed[0],
                               truth.tti_lo[0], truth.tti_hi[0],
                               truth.n_edges[0]))
        return all(np.array_equal(g, x) for g, x in zip(got, want))

    def __call__(self, alive, ts, te, k, h) -> StepResult:
        self.calls += 1
        every = self.config.tripwire_every
        check = bool(every) and self.calls % every == 0
        while True:
            name, fn = self.rungs[self.rung]
            last = self.rung == len(self.rungs) - 1
            try:
                res = fn(alive, ts, te, k, h)
            except Exception as e:
                if last:
                    raise
                self._demote(name, "error", repr(e))
                continue            # replay the same cells one rung down
            if check and not last and not self._lane_check(
                    res, alive, ts, te, k, h):
                self._demote(name, "divergence", f"call {self.calls}")
                continue            # quarantine + bit-identical replay
            return res


def make_wave_step_fn(tel: DeviceTEL, num_vertices: int, *,
                      seg_pair=None, seg_vert=None,
                      use_kernel: Optional[bool] = None,
                      interpret: Optional[bool] = None,
                      donate: bool = False,
                      vmem_budget_bytes: Optional[int] = None,
                      resilience: Optional[ResilienceConfig] = None):
    """Build the device step for one TEL: ``step(alive, ts, te, k, h) ->
    StepResult``, with ``.backend`` ("pallas" | "xla" | "oracle") and
    ``.interpret`` attributes.

    use_kernel=True routes through the fused Pallas peel-to-fixpoint
    kernel (interpret mode off-TPU unless ``interpret`` says otherwise);
    False through the XLA composite; None (default) auto-dispatches —
    compiled Pallas on TPU, XLA elsewhere.  A TEL whose index tables
    exceed the kernel's SMEM budget, or whose VMEM working set exceeds
    its VMEM budget, falls back to the composite, whose ``.fallback``
    then names the budget ("smem_tables" | "vmem_budget").
    ``donate=True`` donates the alive buffer (the pipeline's persistent
    lane slab); leave False when the caller reuses its buffer across
    calls.

    With ``resilience`` set, the returned step is a
    :class:`DegradationLadder` over the same lowerings (Pallas -> XLA ->
    numpy oracle) that demotes on build/VMEM failure, raised errors, or
    a sampled divergence tripwire and replays failed calls on the next
    rung bit-identically.  Ladder rungs never donate (``donate`` is
    ignored): a replay needs its inputs intact.

    The lowerings are bit-identical — alive, packed words, TTI lo/hi,
    edge counts and the iteration count all match exactly (seeded fuzz
    gates in tests/test_kernels.py and tests/test_resilience.py).
    """
    from repro.kernels.segdeg.ops import on_tpu

    if use_kernel is None:
        use_kernel = on_tpu()
    if resilience is not None:
        if resilience.vmem_budget_bytes is None and \
                vmem_budget_bytes is not None:
            resilience = dataclasses.replace(
                resilience, vmem_budget_bytes=int(vmem_budget_bytes))
        return DegradationLadder(tel, num_vertices, seg_pair=seg_pair,
                                 seg_vert=seg_vert, use_kernel=use_kernel,
                                 interpret=interpret, config=resilience)
    if use_kernel:
        from repro.kernels.wave_peel.ops import (DEFAULT_VMEM_BUDGET,
                                                 make_fused_wave_step)

        budget = (DEFAULT_VMEM_BUDGET if vmem_budget_bytes is None
                  else int(vmem_budget_bytes))
        refused = []
        fused = make_fused_wave_step(tel, num_vertices,
                                     interpret=interpret, donate=donate,
                                     vmem_budget_bytes=budget,
                                     on_refuse=refused.append)
        if fused is not None:
            return fused
    step = _make_xla_step(tel, num_vertices, seg_pair=seg_pair,
                          seg_vert=seg_vert, donate=donate)
    if use_kernel:
        step.fallback = refused[0]
    return step


def tcd_wave(tel: DeviceTEL, alive: jnp.ndarray, ts, te, k, h,
             *, num_vertices: int, seg_pair=None, seg_vert=None,
             max_iters: int = 0, step_fn=None) -> WaveResult:
    """Batched TCD to the fixpoint.  alive: [Q, V] warm-start supersets;
    k/h: scalars or per-lane [Q] vectors (mixed-threshold waves).

    Pass ``step_fn`` (from :func:`make_wave_step_fn`) to route through a
    prebuilt device step — the fused Pallas kernel on TPU; otherwise the
    jitted XLA composite runs against ``seg_pair``/``seg_vert``.
    """
    if step_fn is not None:
        if max_iters:
            raise ValueError(
                "step_fn peels to the fixpoint; max_iters is only "
                "supported on the composite path")
        r = step_fn(alive, ts, te, k, h)
        n_verts = jnp.sum(r.alive, axis=1, dtype=jnp.int32)
        return WaveResult(r.alive, r.tti_lo, r.tti_hi, r.n_edges,
                          n_verts, r.iters)
    return _tcd_wave_xla(tel, alive, ts, te, k, h,
                         num_vertices=num_vertices, seg_pair=seg_pair,
                         seg_vert=seg_vert, max_iters=max_iters)


@functools.partial(jax.jit, static_argnames=("num_vertices", "seg_pair",
                                             "seg_vert", "max_iters"))
def _tcd_wave_xla(tel: DeviceTEL, alive: jnp.ndarray, ts, te, k, h,
                  *, num_vertices: int, seg_pair, seg_vert,
                  max_iters: int = 0) -> WaveResult:
    alive, ea, iters = peel_to_fixpoint(
        tel, alive, ts, te, k, h, num_vertices=num_vertices,
        seg_pair=seg_pair, seg_vert=seg_vert, max_iters=max_iters)
    n_edges = jnp.sum(ea, axis=1, dtype=jnp.int32)
    tti_lo = jnp.min(jnp.where(ea, tel.t[None, :], _I32_MAX), axis=1)
    tti_hi = jnp.max(jnp.where(ea, tel.t[None, :], _I32_MIN), axis=1)
    n_verts = jnp.sum(alive, axis=1, dtype=jnp.int32)
    return WaveResult(alive, tti_lo, tti_hi, n_edges, n_verts, iters)
