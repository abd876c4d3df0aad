"""Batched backward dispatch engine (ROADMAP item 4, third ceiling).

The per-node walker in ``tape.run_backward`` pays host work per
GradNode: cotangent slot assembly, hook/target bookkeeping through
dict-backed accumulation slots, queue management, and — dominating all
of it — one XLA dispatch per node. PR 8's dispatch-gap profiler put
numbers on exactly that host gap; PR 10 batched maximal runs of
consecutive SINGLE-CONSUMER nodes into one fused jitted call and met
the <=1.5 eager-over-TrainStep bar. What remained was structural:
fan-in junctions (a tensor consumed by several ops), root-seeded
interior nodes, and non-empty ready queues all ended a run, so real
models still fragmented into many fused sub-chains and the measured
remainder was pure host dispatch. This module closes that
(cf. FusionStitching, PAPERS.md — the win comes from fusing *across*
fan-in/fan-out junctions, not stopping at them):

* **Whole-graph fusion (mode ``whole_graph``, the default)**: a fused
  run no longer ends at a multi-consumer node. Segment formation
  simulates the per-node FIFO walk forward and absorbs every
  consecutively-ready fusable node — fan-in cotangent accumulation
  happens *inside* the fused trace (each junction's incoming edges
  accumulate in the exact per-node FIFO order, so sums associate
  identically and gradients stay bit-identical), root seeds and
  already-ready queue entries ride along as host-seed operands. In the
  steady state one backward = ONE fused dispatch.

* **Whole-graph trace cache**: fused executables are cached per graph
  signature — per node in dispatch order: the exec-cache entry ``uid``
  (monotonic, never reused — ids can't alias even across entry
  eviction; entries are additionally pinned by the cached executable),
  output arity, host-seed slot layout, and full edge routing
  (in-segment accumulation targets vs emitted leaf/boundary
  cotangents). A steady-state eager train loop computes the signature
  (O(nodes) cheap host work), hits the cache, packs seeds + per-node
  primals, and dispatches once. ``clear_chain_cache()`` clears it (the
  chain and whole-graph caches are one cache).

* **Degradation ladder** — only genuinely host-coupled nodes break a
  segment, and they break it *locally*: a node with tensor hooks /
  ``retain_grad`` / a ``paddle.grad`` target on its outputs ends the
  current segment, fires its host work when popped, and may then HEAD
  the next segment; nodes without ``fuse_info`` (PyLayer,
  RNG-consuming, uncacheable ops), with non-inexact outputs, float0
  host seeds, or leaf hooks dispatch per-node; a segment whose
  composed trace fails is disabled (kept in-cache pinning its entries)
  and its head dispatches per-node from then on. ``create_graph``
  backward stays on the per-node tape path entirely.

* **Observability**: each dispatch records its run length into
  ``paddle_tpu_dispatch_batch_size`` (whole-graph runs = the graph
  size), dispatch gaps keep per-op attribution, and
  ``paddle_tpu_backward_graph_cache_total{outcome=hit|miss|bypass}``
  records, per backward in whole_graph mode, whether the entire graph
  dispatched as one cached fused call (hit), one freshly traced call
  (miss), or fragmented (bypass) — steady-state O(1) dispatch is a
  monotonically growing ``hit`` count.

Modes: ``whole_graph`` (default) > ``batched`` (the PR 10
single-consumer-chain engine, kept verbatim as an A/B rung) >
``per_node`` (the legacy walker). ``PADDLE_TPU_BACKWARD_DISPATCH`` /
``set_dispatch_mode`` / ``backward_dispatch_mode`` select; no
benchmark cell runs any of them yet (ROADMAP C4). Gradients are
bit-identical across all modes — pinned by
tests/test_backward_dispatch.py.
"""
from __future__ import annotations

import os
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# mode control
# ---------------------------------------------------------------------------
_MODE_ENV = "PADDLE_TPU_BACKWARD_DISPATCH"
_VALID_MODES = ("whole_graph", "batched", "per_node")
_mode = os.environ.get(_MODE_ENV, "whole_graph")
if _mode not in _VALID_MODES:
    _mode = "whole_graph"


def dispatch_mode() -> str:
    """Current backward dispatch mode: 'whole_graph' (default —
    fan-in-crossing fused runs + the whole-graph trace cache),
    'batched' (the PR 10 single-consumer-chain engine) or 'per_node'
    (the pre-ISSUE-10 walker, the always-correct fallback)."""
    return _mode


def set_dispatch_mode(mode: str) -> str:
    """Set the backward dispatch mode; returns the previous mode."""
    global _mode
    if mode not in _VALID_MODES:
        raise ValueError(
            f"backward dispatch mode must be one of {_VALID_MODES}, "
            f"got {mode!r}")
    old = _mode
    _mode = mode
    return old


class backward_dispatch_mode:
    """Context manager pinning the backward dispatch mode (the
    bit-identical test suite runs all modes through it)."""

    def __init__(self, mode: str):
        self._new = mode

    def __enter__(self):
        self._old = set_dispatch_mode(self._new)
        return self

    def __exit__(self, *exc):
        set_dispatch_mode(self._old)
        return False


# ---------------------------------------------------------------------------
# const caches (satellite of ISSUE 10: jnp.zeros per dead output slot /
# jnp.ones per implicit seed were eager device allocations on EVERY
# dispatch; arrays are immutable, so one per aval serves every backward)
# ---------------------------------------------------------------------------
_FLOAT0 = jax.dtypes.float0
_ZEROS: Dict[Tuple, Any] = {}
_ONES: Dict[Tuple, Any] = {}
_CONST_CACHE_MAX = 256


def is_float0(x) -> bool:
    """Cheap float0 test. float0 cotangents are always numpy arrays
    (jax Arrays never carry the float0 extended dtype), so the
    expensive structured-np-dtype ``__eq__`` never runs for device
    values — this check was measurable per-node host overhead when
    written as ``x.dtype == float0`` unconditionally."""
    return isinstance(x, np.ndarray) and x.dtype == _FLOAT0


def zero_cotangent_array(aval):
    """Cached zero cotangent for an output aval (inexact -> device
    zeros, everything else -> numpy float0 zeros)."""
    key = (tuple(aval.shape), aval.dtype)
    hit = _ZEROS.get(key)
    if hit is None:
        if len(_ZEROS) >= _CONST_CACHE_MAX:
            _ZEROS.clear()
        if jnp.issubdtype(aval.dtype, jnp.inexact):
            hit = jnp.zeros(aval.shape, aval.dtype)
        else:
            hit = np.zeros(aval.shape, _FLOAT0)
        _ZEROS[key] = hit
    return hit


def ones_seed_array(shape, dtype):
    """Cached implicit-seed ones (the scalar-loss ``backward()``
    cotangent built once per (shape, dtype) instead of per call)."""
    key = (tuple(shape), dtype)
    hit = _ONES.get(key)
    if hit is None:
        if len(_ONES) >= _CONST_CACHE_MAX:
            _ONES.clear()
        hit = jnp.ones(shape, dtype)
        _ONES[key] = hit
    return hit


def clear_const_caches() -> None:
    _ZEROS.clear()
    _ONES.clear()


# ---------------------------------------------------------------------------
# fused-segment executable cache (chains AND whole graphs — a linear
# chain is the degenerate fan-in-free segment, so both modes share one
# cache and one builder)
# ---------------------------------------------------------------------------
MAX_CHAIN = 64          # batched-mode run cap (PR 10 A/B rung)
MAX_GRAPH = 256         # whole-graph segment cap: bigger graphs split
                        # into consecutive fused calls (still O(n/256))
_CHAIN_CACHE: Dict[Tuple, "_FusedChain"] = {}
_CHAIN_CACHE_MAX = 256


class _FusedChain:
    """One compiled backward segment: the vjp bodies of N grad nodes —
    a linear chain or a fan-in-crossing whole-graph region — composed
    behind one jitted callable. Holds strong refs to the exec-cache
    entries it traced through (belt and braces over the never-reused
    entry uids in the cache key).

    Compile telemetry (family ``backward_fused``) uses a first-call
    shim like perf.CompileTimed but deliberately does NOT keep the AOT
    executable for dispatch: ``jax.stages.Compiled.__call__`` goes
    through a slow python argument path (~2x a pjit C++ fast-path
    call, measured on the CPU box), and the whole point of this module
    is dispatch latency. The AOT lower+compile runs once for the
    cost-model read (only while observability is enabled), then every
    call — including the first — dispatches through the jit fast
    path."""

    __slots__ = ("jit_fn", "entries", "pending", "disabled")

    def __init__(self, fn, entries):
        self.jit_fn = fn
        self.entries = entries
        self.pending = True
        # flips True when the composed trace fails (concrete-path-only
        # grads, exotic op): the segment dispatches per-node from then
        # on. The disabled segment STAYS in the cache holding its
        # entry refs — a bare None sentinel would not pin them.
        self.disabled = False

    def __call__(self, *args):
        if not self.pending:
            return self.jit_fn(*args)
        from ..observability import metrics as _m
        from ..observability import perf as _pf
        t0 = time.perf_counter()
        if _m._ENABLED:
            try:
                _pf.record_compile(
                    "backward_fused", self.jit_fn.lower(*args).compile())
            except Exception:
                pass        # cost model stays unrecorded, jit decides
        out = self.jit_fn(*args)
        # cleared only on success: a first call that raises leaves the
        # compile un-recorded and the retry records it instead
        self.pending = False
        if _m._ENABLED:
            c, h = _m.compile_metrics()
            c.labels(family="backward_fused", outcome="compile").inc()
            h.labels(family="backward_fused").observe(
                time.perf_counter() - t0)
        return out


# heads whose whole-graph segment previously composed into an
# untraceable body (entry uid -> True). Without this, a graph holding
# one exotic op pays the cascade on EVERY backward: each suffix
# segment from each successive head re-plans O(remaining) host work
# (and, on the first backward, re-traces) before hitting its disabled
# cache entry — O(n^2) per step and up to n distinct cache keys
# churning the trim. The memo skips whole-graph formation from a
# known-bad head outright: the head dispatches per-node (exactly the
# disabled outcome) and the first head PAST the bad region still
# fuses. False positives are bounded — a uid suppressed by one graph
# costs other graphs at most that single head's membership.
_DISABLED_HEAD_UIDS: Dict[int, bool] = {}
_DISABLED_HEAD_UIDS_MAX = 1024


def _note_disabled_head(entry) -> None:
    if len(_DISABLED_HEAD_UIDS) >= _DISABLED_HEAD_UIDS_MAX:
        _DISABLED_HEAD_UIDS.clear()
    _DISABLED_HEAD_UIDS[entry.uid] = True


def clear_chain_cache() -> None:
    """Drop every cached fused backward executable — chains and
    whole-graph segments live in the same cache — plus the
    disabled-head memo that fronts it."""
    _CHAIN_CACHE.clear()
    _DISABLED_HEAD_UIDS.clear()


def chain_cache_size() -> int:
    return sum(1 for v in _CHAIN_CACHE.values() if not v.disabled)


def _build_fused(descs, tap=False):
    """Trace-time composition of one fused segment: each node's
    cotangent contraction is re-derived from its captured primals
    exactly like the per-node ``entry.bwd`` executable does, but
    inside ONE trace — XLA sees the whole region and intermediate
    cotangents (including fan-in accumulations) never surface to the
    host.

    descs, per node in per-node FIFO dispatch order:
    ``(entry, out_avals, seed_slots, edge_plan, leaf_flags)`` where
    seed_slots names the output slots receiving host-side seed values
    (root seeds, contributions from nodes dispatched before this
    segment, hook-transformed head cotangents) and edge_plan routes
    each input cotangent: ``("a", node_pos, out_idx)`` accumulates
    into a later in-segment node's slot — ``g`` if first, else
    ``acc + g``, in edge order, which IS the per-node FIFO
    accumulation order, so fan-in sums associate bit-identically —
    ``("o",)`` emits (leaf edge or out-of-segment boundary), ``("d",)``
    drops (stop edge). leaf_flags marks which edges are LEAF edges.

    ``tap`` (ISSUE 15, whole-graph mode with the numerics plane on):
    append one f32[2] ``[grad_sq, nonfinite_count]`` in-trace
    reduction over the emitted LEAF cotangents as a final extra
    output — a read-only tap, the emitted cotangents themselves are
    untouched (gradients bit-identical tap on vs off, test-pinned).
    Boundary emissions are excluded: their contributions reach leaves
    through later segments and would double-count."""

    def fused(seed_vals, packs):
        acc = [[None] * len(d[1]) for d in descs]
        si = 0
        for pos, d in enumerate(descs):
            for j in d[2]:
                acc[pos][j] = seed_vals[si]
                si += 1
        outs = []
        tap_g2 = tap_nf = None
        for pos, ((entry, out_avals, _seeds, edge_plan, leaf_flags),
                  (primals, nondiffs)) in enumerate(zip(descs, packs)):
            cots = tuple(
                a if a is not None else jnp.zeros(av.shape, av.dtype)
                for a, av in zip(acc[pos], out_avals))

            def _fwd(*d, _e=entry, _nd=nondiffs):
                return _e._run_raw(d, _nd)

            _, vf = jax.vjp(_fwd, *primals)
            in_cots = vf(cots)
            for plan, g, is_leaf in zip(edge_plan, in_cots, leaf_flags):
                kind = plan[0]
                if kind == "o":
                    outs.append(g)
                    if tap and is_leaf and jnp.issubdtype(
                            g.dtype, jnp.inexact):
                        gf = g.astype(jnp.float32)
                        g2 = jnp.sum(gf * gf)
                        nf = jnp.sum(~jnp.isfinite(gf)).astype(
                            jnp.float32)
                        tap_g2 = g2 if tap_g2 is None else tap_g2 + g2
                        tap_nf = nf if tap_nf is None else tap_nf + nf
                elif kind == "a":
                    cur = acc[plan[1]][plan[2]]
                    acc[plan[1]][plan[2]] = g if cur is None else cur + g
        if tap:
            z = jnp.float32(0.0)
            outs.append(jnp.stack([tap_g2 if tap_g2 is not None else z,
                                   tap_nf if tap_nf is not None else z]))
        return tuple(outs)

    return jax.jit(fused)


def _segment_plan(segment, head_slots, cot, tap=False):
    """descs + graph-signature cache key + flat host-seed values for a
    segment (nodes in dispatch order). The key is the whole-graph
    signature: per node (entry uid, output arity, host-seed slot
    layout, edge routing with in-segment parents as positional
    accumulation targets) — entry uids are monotonic and never reused
    (ops.registry), so two backwards over the same op signatures and
    topology hit the same executable and a changed exec-cache entry,
    topology, routing, or seed layout can never alias. A numerics-tap
    segment (ISSUE 15) keys separately (a trailing marker): the tap
    variant is its own executable, and with the plane off the keys —
    and every cached steady-state entry — are byte-identical to
    before."""
    pos = {id(n): i for i, n in enumerate(segment)}
    descs = []
    key_parts = []
    seed_vals: List[Any] = []
    for i, n in enumerate(segment):
        entry = n.fuse_info[0]
        slots = head_slots if i == 0 else cot.get(id(n))
        if slots is None:
            seed_slots: Tuple[int, ...] = ()
        else:
            seed_slots = tuple(j for j, s in enumerate(slots)
                               if s is not None)
            seed_vals.extend(slots[j] for j in seed_slots)
        plan = []
        leaf = []
        for e in n.edges:
            leaf.append(e.kind == "leaf")
            if e.kind == "node" and id(e.node) in pos:
                plan.append(("a", pos[id(e.node)], e.out_idx))
            elif e.kind == "stop":
                plan.append(("d",))
            else:
                plan.append(("o",))
        plan = tuple(plan)
        descs.append((entry, tuple(n.out_avals), seed_slots, plan,
                      tuple(leaf)))
        key_parts.append((entry.uid, len(n.out_avals), seed_slots, plan))
    key = tuple(key_parts)
    if tap:
        # the tap variant's key additionally folds in each node's
        # leaf-vs-boundary edge classification: the base plan encodes
        # both as ("o",), which is exactly right for routing (the
        # emitted value is the same) but NOT for the tap — a leaf
        # emission is reduced into the tap, a boundary emission is
        # excluded (it reaches leaves through later segments). Two
        # same-keyed segments differing only in that classification
        # must not share a tap executable (review finding).
        key = key + (("numtap",) + tuple(d[4] for d in descs),)
    return descs, key, seed_vals


def _get_fused(descs, key, tap=False):
    """(fused executable, cache_hit) for this segment signature —
    possibly disabled, when a previous attempt found the composition
    untraceable."""
    hit = _CHAIN_CACHE.get(key)
    if hit is not None:
        return hit, True
    fused = _FusedChain(_build_fused(descs, tap),
                        tuple(d[0] for d in descs))
    if len(_CHAIN_CACHE) >= _CHAIN_CACHE_MAX:
        # simple LRU-ish trim: drop the oldest half (insertion order)
        for k in list(_CHAIN_CACHE)[:_CHAIN_CACHE_MAX // 2]:
            del _CHAIN_CACHE[k]
    _CHAIN_CACHE[key] = fused
    return fused, False


# ---------------------------------------------------------------------------
# fusability predicates
# ---------------------------------------------------------------------------
_INEXACT_MEMO: Dict[Any, bool] = {}


def _all_inexact(node) -> bool:
    for a in node.out_avals:
        v = _INEXACT_MEMO.get(a.dtype)
        if v is None:
            v = _INEXACT_MEMO[a.dtype] = bool(
                jnp.issubdtype(a.dtype, jnp.inexact))
        if not v:
            return False
    return True


def _leaf_hooked(node) -> bool:
    for e in node.edges:
        if e.kind == "leaf" and e.tensor_ref is not None:
            t = e.tensor_ref()
            if t is not None and t._hooks:
                return True
    return False


def _head_fusable(node) -> bool:
    fi = node.fuse_info
    return (fi is not None and fi[0].bwd_ok and _all_inexact(node)
            and not _leaf_hooked(node))


def _grow_chain(node, ok):
    """PR 10 batched-mode run formation: follow the single node-edge
    continuation while each next node passes ``ok`` (single consumer,
    not root-seeded, clean outputs). Returns the run or None."""
    chain = [node]
    cur = node
    while len(chain) < MAX_CHAIN:
        cont = None
        for e in cur.edges:
            if e.kind == "node":
                if cont is not None:
                    cont = None
                    break
                cont = e
        if cont is None:
            break
        nxt = cont.node
        if not ok(nxt):
            break
        chain.append(nxt)
        cur = nxt
    return chain if len(chain) > 1 else None


def _grow_graph(node, queue, pending, ok):
    """Whole-graph segment formation: simulate the per-node FIFO walk
    forward from ``node`` (already popped, output hooks fired),
    absorbing every consecutively-ready node that passes ``ok``. The
    simulation copies the ready queue and decrements pending counts
    copy-on-write, so the real walk state is untouched until the fused
    dispatch actually succeeds. Because pops come strictly from the
    FIFO front, the absorbed nodes are exactly the per-node dispatch
    prefix — fused order == per-node order, and the first
    ``min(pops, len(queue))`` entries of the real queue are the
    absorbed already-ready nodes.

    Returns (segment | None, absorbed_from_queue_count)."""
    segment = [node]
    sim_queue = deque(queue)
    sim_pending: Dict[int, int] = {}
    pops = 0
    i = 0
    while len(segment) < MAX_GRAPH:
        cur = segment[i]
        for e in cur.edges:
            if e.kind == "node":
                nid = id(e.node)
                left = sim_pending.get(nid, pending.get(nid, 0)) - 1
                sim_pending[nid] = left
                if left == 0:
                    sim_queue.append(e.node)
        i += 1
        if not sim_queue:
            break
        nxt = sim_queue[0]
        if not ok(nxt):
            break
        sim_queue.popleft()
        segment.append(nxt)
        pops += 1
    if len(segment) < 2:
        return None, 0
    return segment, min(pops, len(queue))


# ---------------------------------------------------------------------------
# the batched walker (modes whole_graph and batched)
# ---------------------------------------------------------------------------
def run_batched(node_by_id, consumers, cot, node_store, seed,
                target_ids, target_results, accumulate_leaf_grads,
                retain_graph):
    """The fused-mode hot loop of ``tape.run_backward`` (roots already
    seeded; ``seed`` is the tape's accumulation closure over
    ``cot``/``node_store``). Same semantics as the per-node walker —
    FIFO dispatch order, hook/retain/target handling, leaf
    accumulation order — with fusable regions dispatched as one fused
    call: whole-graph segments across fan-in junctions in whole_graph
    mode, maximal single-consumer runs in batched mode."""
    from . import tape
    from ..observability import metrics as _om
    from ..observability import numerics as _nm
    from ..observability import perf as _pf

    whole = _mode == "whole_graph"
    # numerics in-trace grad tap (ISSUE 15): whole-graph segments on
    # SAMPLED steps only — batched (chain) mode stays the PR 10 A/B
    # rung verbatim, and per-node/eager stats come from the
    # optimizer-side fallback. One flag read per backward when the
    # plane is off; both tap variants stay cached, so the cadence
    # alternates between two warm executables, never recompiles.
    tap = whole and _nm._ENABLED and _nm.want_stats()
    pending = dict(consumers)
    queue = deque(n for nid, n in node_by_id.items()
                  if pending.get(nid, 0) == 0)
    root_seeded = frozenset(cot)
    n_total = len(node_by_id)
    fusable_memo: Dict[int, bool] = {}
    n_dispatches = 0
    first_whole_hit: Optional[bool] = None

    def clean_outputs(n) -> bool:
        for ref in n.out_tensor_refs:
            t = ref() if ref is not None else None
            if t is not None and (
                    t._hooks or t._retain_grad
                    or (target_ids and id(t) in target_ids)):
                return False
        return True

    def nonhead_fusable(n) -> bool:
        nid = id(n)
        v = fusable_memo.get(nid)
        if v is None:
            v = _head_fusable(n) and clean_outputs(n)
            if v and not whole:
                # batched (chain) mode keeps the PR 10 restrictions:
                # exactly one consumer, no root seed riding along
                v = (consumers.get(nid, 0) == 1
                     and nid not in root_seeded)
            fusable_memo[nid] = v
        return v

    def candidate_ok(n) -> bool:
        # host-seed float0 check stays OUT of the memo: seeds can grow
        # between a failed segment attempt and the next (per-node
        # dispatches in between), and float0 slots must degrade
        if not nonhead_fusable(n):
            return False
        slots = cot.get(id(n))
        return slots is None or not any(
            s is not None and is_float0(s) for s in slots)

    def apply_leaf_edge(e, g):
        """Leaf-edge cotangent handling — identical to the per-node
        walker's edge loop body (in-segment nodes never carry leaf
        hooks, so fused post-processing runs no user code here)."""
        t = e.tensor_ref() if e.tensor_ref is not None else None
        if t is None:
            return
        if t._hooks:
            g = tape._apply_hooks(t._hooks, g, False)
            fusable_memo.clear()    # a hook may register hooks/retain
        if target_ids and id(t) in target_ids:
            i = target_ids[id(t)]
            r = target_results[i]
            target_results[i] = g if r is None else r + g
        if accumulate_leaf_grads:
            tape._apply_leaf_grad(t, g, False)

    def seed_node_edge(e, g):
        seed(e.node, e.out_idx, g)
        pending[id(e.node)] -= 1
        if pending[id(e.node)] == 0:
            queue.append(e.node)

    def release(n):
        n.vjp_fn = None
        n.replay_fn = None
        n.primal_arrays = None
        n.record_vjp = None
        n.fuse_info = None

    last_dispatch = None
    while queue:
        node = queue.popleft()
        slots = cot.get(id(node))
        if slots is None:
            slots = [None] * len(node.out_avals)
        # hooks / retain_grad / targets on this node's outputs fire
        # exactly like the per-node walker (before the device call),
        # materializing only the slots they observe — untouched None
        # slots stay symbolic and become in-trace zeros when the node
        # heads a fused segment
        for i, ref in enumerate(node.out_tensor_refs):
            t = ref() if ref is not None else None
            if t is None:
                continue
            is_target = target_ids and id(t) in target_ids
            if not (t._hooks or t._retain_grad or is_target):
                continue
            if slots[i] is None:
                slots[i] = zero_cotangent_array(node.out_avals[i])
            if t._hooks:
                slots[i] = tape._apply_hooks(t._hooks, slots[i], False)
                fusable_memo.clear()
            if is_target:
                r = target_results[target_ids[id(t)]]
                target_results[target_ids[id(t)]] = (
                    slots[i] if r is None else r + slots[i])
            if t._retain_grad and accumulate_leaf_grads:
                tape._apply_leaf_grad(t, slots[i], False)

        # segment formation: whole_graph mode absorbs across fan-in
        # junctions and the live ready queue (the simulation preserves
        # exact FIFO order); batched mode keeps the PR 10 rule — runs
        # form only while the queue is empty, along single-consumer
        # continuations
        segment = None
        absorbed_q = 0
        if _head_fusable(node) and not any(
                s is not None and is_float0(s) for s in slots):
            if whole:
                # known-bad head (its composed segment failed to trace
                # before): dispatch per-node without re-planning — the
                # first head past the bad region still fuses
                if node.fuse_info[0].uid not in _DISABLED_HEAD_UIDS:
                    segment, absorbed_q = _grow_graph(
                        node, queue, pending, candidate_ok)
            elif not queue:
                segment = _grow_chain(node, candidate_ok)

        enabled = _om._ENABLED
        if enabled:
            now = time.perf_counter()
            if last_dispatch is not None:
                _pf.note_dispatch_gap(now - last_dispatch, node.name)

        dispatched_fused = False
        if segment is not None:
            descs, key, seed_vals = _segment_plan(segment, slots, cot,
                                                  tap)
            fused, cache_hit = _get_fused(descs, key, tap)
            if fused.disabled:
                if whole:
                    _note_disabled_head(node.fuse_info[0])
            else:
                packs = tuple((n.fuse_info[1], n.fuse_info[2])
                              for n in segment)
                try:
                    outs = fused(tuple(seed_vals), packs)
                    if tap:
                        # trailing in-trace [grad_sq, nonfinite] tap —
                        # a device array handed over un-materialized
                        _nm.note_backward_tap(outs[-1])
                        outs = outs[:-1]
                    dispatched_fused = True
                except Exception:
                    # untraceable composition (concrete-path-only
                    # grads, exotic op): remember and degrade — the
                    # per-node path below redispatches this head, and
                    # (whole mode) the head memo stops future
                    # backwards from re-planning the doomed segment.
                    # Chain (batched) mode keeps PR 10 behavior
                    # verbatim: disabled hits re-plan, never memoize.
                    fused.disabled = True
                    if whole:
                        _note_disabled_head(node.fuse_info[0])
        if dispatched_fused:
            n_dispatches += 1
            if n_dispatches == 1 and len(segment) == n_total:
                first_whole_hit = cache_hit
            if enabled:
                last_dispatch = time.perf_counter()
                _pf.note_dispatch_batch(len(segment))
            # the absorbed already-ready nodes are exactly the next
            # `absorbed_q` FIFO entries (see _grow_graph)
            for _ in range(absorbed_q):
                queue.popleft()
            oi = 0
            for n, (_e, _avals, _seeds, plan, _leaf) in zip(segment,
                                                            descs):
                for e, p in zip(n.edges, plan):
                    if p[0] != "o":
                        continue        # in-trace accumulation / stop
                    g = outs[oi]
                    oi += 1
                    if e.kind == "leaf":
                        apply_leaf_edge(e, g)
                    else:       # out-of-segment boundary node edge
                        seed_node_edge(e, g)
                if not retain_graph:
                    release(n)
                cot.pop(id(n), None)
            continue

        # per-node dispatch (degraded or unfused) — the original walker
        cots = [s if s is not None else zero_cotangent_array(a)
                for s, a in zip(slots, node.out_avals)]
        in_cots = node.vjp_fn(tuple(cots))
        n_dispatches += 1
        if enabled:
            last_dispatch = time.perf_counter()
            _pf.note_dispatch_batch(1)
        if not isinstance(in_cots, (tuple, list)):
            in_cots = (in_cots,)
        assert len(in_cots) == len(node.edges), (
            f"{node}: vjp returned {len(in_cots)} cotangents for "
            f"{len(node.edges)} edges")
        for e, g in zip(node.edges, in_cots):
            if e.kind == "stop":
                continue
            if e.kind == "leaf":
                apply_leaf_edge(e, g)
            else:
                seed_node_edge(e, g)
        if not retain_graph:
            release(node)
        cot.pop(id(node), None)

    if whole and _om._ENABLED and n_dispatches:
        if n_dispatches == 1 and first_whole_hit is not None:
            _pf.note_graph_cache("hit" if first_whole_hit else "miss")
        else:
            _pf.note_graph_cache("bypass")
