"""Dispatching wrapper + cost model for the fused wave-peel kernel.

``make_fused_wave_step`` does the host-side analysis once per TEL (the
window's live edges grouped by pair, its local pairs and vertices, the
half-pair tables sorted by local vertex) and returns a
``step(alive, ts, te, k, h) -> StepResult`` closure, or ``None`` when
the tables exceed the kernel's SMEM capacity or its VMEM working set
exceeds the budget (``fused_step_refusal`` names which) — callers
(``core.wave.make_wave_step_fn``) then use the XLA composite.

The compiled program is shared by every window of a *size class*
(``fused_step_class``): the tables are padded to the class's power-of-two
rows and passed as arguments, the live pair and vertex counts ride in
SMEM as the kernel's loop bounds, and one module-level ``jax.jit``
(``_step``) compiles once per (class, W, V).  A window that is new to
the process but whose class has already run pays its host-side analysis
and uploads, not a trace, lower and compile.

Vertices with no live edge in the TEL never reach the kernel: their
degree is 0 in every iteration, so they survive iff ``k <= 0`` and can
only change state on the first iteration.  The wrapper applies that rule
directly and folds it into the iteration count, which keeps the step
bit-identical to the composite on every ``StepResult`` field.

``fused_step_cost`` is the structural HBM/op model used by
``benchmarks/bench_wave.py``: the fused step's HBM bytes are
*iteration-independent* (tables once per lane tile + the lane slab in
and out), which is the whole point vs the unfused chain's per-iteration
[W, E] round-trips.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import pow2_capacity
from repro.kernels.segdeg.ops import on_tpu
from repro.kernels.wave_peel.kernel import LANES, wave_peel_pallas

# Per-core VMEM is 16 MiB of default scoped space on v5e; leave headroom
# for Mosaic's own temporaries.
DEFAULT_VMEM_BUDGET = 12 << 20
# SMEM is 1 MiB on v5e; the index tables may take at most this much.
SMEM_TABLE_BUDGET = 768 << 10


def _align(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def fused_step_smem_bytes(num_edges: int, num_pairs: int,
                          num_vertices: int) -> int:
    """SMEM taken by the index tables of a window with that many live
    edges, local pairs and local vertices."""
    return 4 * (max(num_edges, 1) + 3 * max(num_pairs, 1)
                + max(num_vertices, 1) + 4 * max(num_pairs, 1))


def fused_step_vmem_bytes(num_pairs: int, num_vertices: int) -> int:
    """VMEM working set of one grid program (bytes): the lane slab in and
    out (double-buffered by the pipeline), the degree and pair-ok
    scratch, and the parameter / stats blocks."""
    v = _align(max(num_vertices, 1), 8)
    p = _align(max(num_pairs, 1), 8)
    return 4 * LANES * (4 * v + v + p + 4 * 8)


def fused_step_refusal(num_edges: int, num_pairs: int, num_vertices: int,
                       vmem_budget_bytes: int) -> Optional[str]:
    """The budget a window's fused step would exceed: ``"smem_tables"``
    (its index tables), ``"vmem_budget"`` (one program's working set), or
    None when the kernel fits."""
    if fused_step_smem_bytes(num_edges, num_pairs,
                             num_vertices) > SMEM_TABLE_BUDGET:
        return "smem_tables"
    if fused_step_vmem_bytes(num_pairs, num_vertices) > vmem_budget_bytes:
        return "vmem_budget"
    return None


def fused_step_cost(num_edges: int, num_pairs: int, num_vertices: int,
                    wave: int, *, iters: int = 1) -> dict:
    """Structural cost model of one fused step (per device), in terms of
    the window's live edges, local pairs and local vertices.

    HBM bytes are iteration-independent: each lane-tile program reads
    the tables once and the lane slab in and out once; every fixpoint
    intermediate stays in VMEM.  Ops scale with ``iters`` (one row op of
    ``LANES`` lanes per half-pair and per vertex each iteration, one per
    edge in the prologue and epilogue).
    """
    v = _align(max(num_vertices, 1), 8)
    w_pad = _align(max(wave, 1), LANES)
    tiles = w_pad // LANES
    table_bytes = float(fused_step_smem_bytes(num_edges, num_pairs,
                                              num_vertices))
    lane_bytes = 4.0 * w_pad * (2 * v + 2 * 8)
    ops_per_iter = float(w_pad) * (2 * 2 * num_pairs + 2 * num_vertices)
    ops_once = float(w_pad) * (2 * 3 * num_edges + 2 * num_pairs)
    return {
        "bytes_per_step": tiles * table_bytes + lane_bytes,
        "bytes_per_iter_hbm": 0.0,
        "flops_per_iter": ops_per_iter,
        "flops_per_step": ops_once + ops_per_iter * max(int(iters), 1),
        "vmem_bytes": fused_step_vmem_bytes(num_pairs, num_vertices),
    }


def fused_step_class(num_edges: int, num_pairs: int, num_vertices: int,
                     vertex_capacity: int,
                     vmem_budget_bytes: int) -> Tuple[int, int, int]:
    """Rows of a window's edge, pair and vertex tables: its size class.

    The first choice is keyed by the live edges alone: ``e =
    pow2_capacity(num_edges)`` rows of edges and of pairs (a window has
    no more pairs than edges) and ``min(align8(2 e), vertex_capacity)``
    rows of vertices (nor more local vertices than twice its pairs).
    Under the default VMEM budget that slab fits up to e = 2,048; above
    it the pairs and vertices take their own power-of-two rows, which
    fit wherever the live counts are well under the budget.  Where
    neither class fits a budget that the exact sizes fit, the window's
    class is its exact sizes, so padding never moves a window off the
    kernel."""
    e_cap = pow2_capacity(num_edges)
    v_limit = int(vertex_capacity)
    for p_cap, v_cap in ((e_cap, _align(2 * e_cap, 8)),
                         (pow2_capacity(num_pairs),
                          pow2_capacity(num_vertices))):
        v_cap = min(v_cap, v_limit)
        if fused_step_refusal(e_cap, p_cap, v_cap,
                              vmem_budget_bytes) is None:
            return e_cap, p_cap, v_cap
    return max(num_edges, 1), max(num_pairs, 1), max(num_vertices, 1)


def _window_tables(tel):
    """Host-side compaction of a (possibly padded) TEL to its live edges.

    Returns (verts, tables, n_edges, n_pairs): ``verts`` are the global
    ids of the local vertices (sorted); ``tables`` the kernel's seven
    1-D int32 tables at their live lengths (``n_edges``; ``n_pairs`` for
    pair_end, pair_u, pair_v; ``verts.size`` for vertex_end; ``2 *
    n_pairs`` for hp_other, hp_pair)."""
    num_pairs = int(tel.pair_u.shape[0])
    pair_id = np.asarray(tel.pair_id)
    live = pair_id < num_pairs          # sentinel edges carry pair_id == P
    pid = pair_id[live]
    t = np.asarray(tel.t)[live]
    src = np.asarray(tel.src)[live]
    dst = np.asarray(tel.dst)[live]
    if np.any(pid[1:] < pid[:-1]):
        raise ValueError("TEL edges are not grouped by pair_id")
    first = np.ones(pid.size, dtype=bool)
    first[1:] = pid[1:] != pid[:-1]
    starts = np.flatnonzero(first)
    pair_end = np.append(starts[1:], pid.size)
    verts = np.unique(np.concatenate([src[first], dst[first]]))
    pu = np.searchsorted(verts, src[first])
    pv = np.searchsorted(verts, dst[first])
    n_pairs = int(starts.size)
    hp_vert = np.concatenate([pu, pv])
    order = np.argsort(hp_vert, kind="stable")
    hp_other = np.concatenate([pv, pu])[order]
    hp_pair = np.concatenate([np.arange(n_pairs)] * 2)[order]
    vert_end = np.searchsorted(hp_vert[order], np.arange(verts.size),
                               side="right")
    tables = (t, pair_end, pu, pv, vert_end, hp_other, hp_pair)
    return verts, tables, int(pid.size), n_pairs


def _padded(a, rows: int, fill: int = 0):
    out = np.full(rows, fill, np.int32)
    out[:a.size] = a
    return jnp.asarray(out)


def _step(tables, counts, verts, isolated, alive, ts, te, k, h, *,
          interpret: bool):
    """One fused step over a window of some size class: ``tables`` padded
    to the class's rows, ``counts`` the live (pairs, vertices), ``verts``
    the local vertices' global ids padded with the out-of-range
    ``alive.shape[1]``, ``isolated`` the [V] mask of vertices without a
    live edge."""
    from repro.core.wave import StepResult, _pack_u32

    w, v = alive.shape
    w_pad = _align(max(w, 1), LANES)
    # padding lanes carry the empty window (ts=0 > te=-1) and k=h=1
    # with an all-dead mask: they converge on iteration 1
    def lanes(x, fill):
        x = jnp.broadcast_to(jnp.asarray(x, jnp.int32), (w,))
        return jnp.pad(x, (0, w_pad - w), constant_values=fill)

    k_l = jnp.broadcast_to(jnp.asarray(k, jnp.int32), (w,))
    prm = jnp.zeros((8, w_pad), jnp.int32)
    prm = prm.at[:4].set(jnp.stack([lanes(ts, 0), lanes(te, -1),
                                    lanes(k, 1), lanes(h, 1)]))
    # padded vertex rows gather 0 (dead), stay 0 through the peel, and
    # their scatter back is dropped
    local = alive.at[:, verts].get(mode="fill", fill_value=False)
    slab = jnp.pad(local.T.astype(jnp.int32), ((0, 0), (0, w_pad - w)))
    out, st = wave_peel_pallas(tables, counts, prm, slab,
                               pair_rows=_align(tables[1].shape[0], 8),
                               interpret=interpret)
    # vertices without live edges: degree 0, so they survive iff
    # k <= 0 and change at most once (on the first iteration)
    rest = alive & (k_l <= 0)[:, None]
    new = rest.at[:, verts].set(out[:, :w].T > 0, mode="drop")
    moved = jnp.any(alive & isolated[None, :] & (k_l > 0)[:, None])
    iters = jnp.maximum(jnp.max(st[3]), jnp.where(moved, 2, 1))
    return StepResult(new, _pack_u32(new, v), st[1, :w], st[2, :w],
                      st[0, :w], iters.astype(jnp.int32))


# keyed by donation; JAX's own cache holds one program per (class, W, V)
_JITTED = {donate: jax.jit(_step, static_argnames=("interpret",),
                           donate_argnames=("alive",) if donate else ())
           for donate in (False, True)}
# per donation variant, the (table rows, interpret, alive shape) of
# every program it has run.  The other arguments are fixed by the step:
# its own device arrays, which the rows shape, and ts, te, k, h as int32
# [W] vectors, as the engine passes them.
_RAN = {False: set(), True: set()}


def _ran(donate: bool) -> set:
    """The programs that the donation variant's JAX cache still holds:
    emptied with it, as ``jax.clear_caches()`` does."""
    ran = _RAN[donate]
    if ran and not _JITTED[donate]._cache_size():
        ran.clear()
    return ran


def make_fused_wave_step(tel, num_vertices: int, *,
                         interpret: Optional[bool] = None,
                         donate: bool = False,
                         vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET,
                         on_refuse: Optional[Callable[[str], None]] = None):
    """Build the fused Pallas step for one (capacity-shaped) DeviceTEL.

    Returns ``step(alive [W, V] bool, ts, te, k, h) -> StepResult`` (the
    ``core.wave`` result type, bit-identical to the composite), or
    ``None`` when the window's tables exceed the SMEM capacity or the
    per-program VMEM working set exceeds the budget; ``on_refuse`` is
    then called with the budget's name (``fused_step_refusal``).  Both
    budgets are judged on the window's exact sizes.
    ``interpret=None`` auto-resolves: compiled on TPU, interpret mode
    elsewhere (the CPU correctness gates).

    The step is a thin closure over the window's device arrays, padded
    to its size class (``fused_step_class``); every window of one class
    shares the compiled program.  ``step.program_warm(alive, ts, te, k,
    h)`` says whether a call with those arguments would reuse a program
    this process has already run, judged on the class and ``alive``'s
    shape (``ts``, ``te``, ``k``, ``h`` are int32 [W] vectors).
    """
    interp = (not on_tpu()) if interpret is None else bool(interpret)
    v = int(num_vertices)
    budget = int(vmem_budget_bytes)
    verts, live, n_e, n_p = _window_tables(tel)
    n_v = int(verts.size)
    refusal = None if interp else fused_step_refusal(n_e, n_p, n_v, budget)
    if refusal is not None:
        if on_refuse is not None:
            on_refuse(refusal)
        return None
    e_rows, p_rows, v_rows = fused_step_class(n_e, n_p, n_v, v, budget)
    rows = (e_rows, p_rows, p_rows, p_rows, v_rows, 2 * p_rows, 2 * p_rows)
    tables = tuple(_padded(a, r) for a, r in zip(live, rows))
    counts = jnp.asarray(np.array([n_p, n_v], np.int32))
    verts_d = _padded(verts, _align(v_rows, 8), fill=v)
    isolated = np.ones(v, dtype=bool)
    isolated[verts] = False
    isolated_d = jnp.asarray(isolated)
    donate = bool(donate)
    jitted = _JITTED[donate]

    def step(alive, ts, te, k, h):
        ran = _ran(donate)       # before the call refills JAX's cache
        out = jitted(tables, counts, verts_d, isolated_d, alive, ts, te, k,
                     h, interpret=interp)
        if not isinstance(alive, jax.core.Tracer):   # not inlined
            ran.add((rows, interp, tuple(alive.shape)))
        return out

    def program_warm(alive, ts, te, k, h):
        return (rows, interp, tuple(alive.shape)) in _ran(donate)

    step.program_warm = program_warm
    # the shared program and this window's leading arguments to it
    # (compile checks)
    step.jitted = jitted
    step.window_args = (tables, counts, verts_d, isolated_d)
    step.backend = "pallas"
    step.interpret = interp
    step.local_counts = (n_e, n_p, n_v)
    # operand census for perf_lower's structural assert: nothing
    # [W, E]-shaped ever crosses HBM on this path
    step.operand_shapes = [tuple(x.shape) for x in tables]
    return step
