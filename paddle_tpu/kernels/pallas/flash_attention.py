"""Pallas TPU flash attention (forward + blockwise backward).

Replaces the reference's dynloaded CUDA flashattn
(/root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu:128,
backends/dynload/flashattn.cc) with a TPU-native blockwise online-softmax
kernel: Q blocks stay resident in VMEM while K/V blocks stream from HBM;
scores never materialize in HBM (O(S) memory instead of O(S^2)).

Backward is the flash-attention-2 scheme: the forward saves the per-row
logsumexp; backward recomputes score blocks in VMEM from (q, k, lse) and
accumulates dq / dk / dv blockwise, so the [s, s] score matrix never
touches HBM in either direction. Two kernels: one gridded over K blocks
(produces dk, dv), one over Q blocks (produces dq) — mirroring the split
of the reference's flash_attn_bwd
(/root/reference/paddle/phi/kernels/gpu/flash_attn_grad_kernel.cu).

Fused-head layout: the kernels run on [batch, seq, heads*head_dim] — the
layout a fused QKV projection naturally produces — and slice heads
in-kernel (lane offsets h*D). Measured on v5e at [16, 1024, 12, 64] this
beats the per-head [b*h, s, d] fold two ways:
  * no [b,s,h,d] <-> [b*h,s,d] transposes (sublane-shuffle copies that
    cost more than the attention math itself at d=64), and
  * no HBM padding: minor dim h*d is lane-aligned, whereas a d=64 minor
    dim is padded to 128 lanes (2x footprint and bandwidth).

sm_scale is folded into q before the kernel (drops one [bq, bk] VPU
pass per head per block pair).

Causality follows the diagonal inside the block a grid step holds: the
step walks its resident K/V block in key sub-blocks of `_WALK` keys up to
the last one a q row of the step sees, every visit under the tile mask,
and visits none above it; a step wholly above the diagonal names its
neighbour's block in its index map, so the pipeline fetches nothing for
it. The resident block stays wide: few grid steps and few DMAs.
A visit is narrow, so it is kept cheap: the forward's running max and sum
live in every lane of a [bq, 128] tile a head, as a row reduction leaves
them, and never cross lanes; the backward's score tiles are [keys, q
rows], so the log-sums and deltas arrive as the lane vectors they are
stored as; and two heads of 64 are worked as one 128-lane slab under lane
masks (`_head_slabs`), with no lane rotation and no half-empty register.
The timings are in PERF.md (PR 28).

Inputs are fed to the MXU in their native dtype (bf16 in, f32 accumulate
via preferred_element_type) — no f32 upcast before the dot.

Layout contract of the public API matches paddle: [batch, seq, heads,
head_dim] (ref: python/paddle/nn/functional/flash_attention.py:146);
the [b,s,h,d] <-> [b,s,h*d] reshape is free (no axis reordering).
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# raised scoped-VMEM budget: the 1024-wide resident K/V blocks need ~17MB
# with double buffering (the default scoped limit is 16MB)
_VMEM_LIMIT = 64 * 1024 * 1024
_LANES = 128
_SUBL = 8   # per-head stats ride as [b, h*_SUBL, s]: seq in lanes, each
            # head's row replicated over one sublane tile (minimum height)


def _causal_tile_mask(q0, k0, shape, q_axis=0):
    """Bool validity (q_pos >= k_pos) of a score tile of `shape` whose
    first q row sits at causal position q0 and whose first key at k0; q
    rows run along `q_axis`, keys along the other. Only called on tiles
    that straddle the diagonal.

    q0 carries offset = sk - sq, which gives the FlashAttention-2
    bottom-right-aligned causal mask for cross-length attention (the
    reference's dynloaded FA2 library aligns this way; ADVICE r2 finding
    on top-left drift)."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return q_pos >= k_pos


# Keys a visit of the causal walk takes: the granularity at which the
# masked half of a resident block is skipped. Narrower skips more and pays
# the accumulators' read-modify-write more often (PERF.md, PR 28).
_WALK = 256


def _sub_block(block_k, width):
    """The walk's width fitted to a block_k it has to divide. The walk
    stops at the diagonal, so its width is the granularity at which
    masked work is skipped; without causality (width None) there is
    nothing to skip and the block is one visit."""
    if width is None:
        return block_k
    sub = min(width, block_k)
    while block_k % sub:
        sub -= _LANES
    return sub


# The kernels' bodies are unrolled over heads and traced once a call of a
# layer, a few thousand operations a step: they are written with `lax`
# primitives on operands of equal shape, which skip the `jnp` wrappers'
# promotion and dispatch.
def _cols(x, start, width):
    return jax.lax.slice_in_dim(x, start, start + width, axis=1)


def _rows_to_lanes(x, lanes):
    """A per-row value [rows] in every one of `lanes` lanes."""
    return jax.lax.broadcast_in_dim(x, (x.shape[0], lanes), (0,))


def _over_rows(x, rows):
    """A lane vector [1, n] over `rows` sublanes."""
    return jax.lax.broadcast_in_dim(x, (rows, x.shape[1]), (0, 1))


def _lanes(x, width):
    """A [rows, _LANES] value that is the same in every lane, at `width`
    lanes (a multiple of _LANES, or a head size under it)."""
    if width == _LANES:
        return x
    if width < _LANES:
        return _cols(x, 0, width)
    return jax.lax.concatenate([x] * (width // _LANES), 1)


def _head_slabs(H, Hk, D):
    """[(q lanes, k/v lanes, [(head, its lanes of the slab or None)])],
    lanes as (start, width): the lane slabs a visit works through. A head
    of 128 or 256 lanes is a slab of its own. Two neighbouring heads of
    64 share one 128-lane tile: slicing either out costs lane rotations
    and half-empty registers on every visit, so the pair is taken as one
    slab and each head of it is told apart by a lane mask (`mine`,
    [1, _LANES]). With the other head's lanes of q zeroed, its products
    drop out of the contraction over the slab, and a product with the
    slab of v holds the head's own output in its own lanes; the matrix
    unit passes a 128-deep tile either way. Grouped heads of 64 sit on
    other lanes than their k/v head and keep their own slices, as do the
    heads of an odd count (no lane-aligned layout; the interpreter's)."""
    G = H // Hk
    if D * 2 == _LANES and G == 1 and H % 2 == 0:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        halves = (lane < D, lane >= D)
        return [((u * _LANES, _LANES),) * 2
                + ([(2 * u, halves[0]), (2 * u + 1, halves[1])],)
                for u in range(H // 2)]
    return [((h * D, D), ((h // G) * D, D), [(h, None)]) for h in range(H)]


def _pick(mine, x, other):
    """x on the lanes of `mine` ([1, lanes]), `other` elsewhere."""
    return jax.lax.select(_over_rows(mine, x.shape[0]), x, other)


def _nt_dot(a, b):
    """a @ b^T in float32: both contract their lanes."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b, a_axis=1):
    """a @ b in float32 (a^T @ b with a_axis=0)."""
    return jax.lax.dot_general(a, b, (((a_axis,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _div(x, n):
    """x // n of a traced x >= 0 (the floor division of `//` lowers to a
    handful of sign fix-ups a use)."""
    return jax.lax.div(x, jnp.int32(n))


def _walk_bounds(qi, ki, block_q, block_k, sub, offset):
    """How many of a causal (q block, k block) pair's block_k // sub key
    sub-blocks have a key some q row of the pair sees: those are visited,
    in order; the rest lie above the diagonal. A pair wholly above it
    visits none."""
    # keys of this block the pair's last q row sees
    seen = offset + (qi + 1) * block_q - ki * block_k
    return _div(jnp.clip(seen, 0, block_k) + sub - 1, sub)


def _walk(n_visit, sub, block_k, visit):
    """visit(k0) over a causal pair's first n_visit key sub-blocks, k0 the
    sub-block's first key within the block. The bound comes from the
    program ids, so the walk is a loop and not a second unrolled level
    under the unrolled heads; a block that is one sub-block is visited
    under a condition. One body serves every visit, the tile mask on in
    all of them: a second, unmasked body for the sub-blocks wholly below
    the diagonal doubles what the kernels cost to trace and lower."""
    if sub == block_k:
        pl.when(n_visit > 0)(lambda: visit(0))
        return

    def body(j, carry):
        visit(pl.multiple_of(j * sub, sub))
        return carry
    jax.lax.fori_loop(0, n_visit, body, 0)


def causal_tiles(sq, sk, block_q, block_k, sub, causal=True):
    """(visited, total) [block_q, sub]-tiles of the [sq, sk] scores a
    kernel with these blocks computes: pure arithmetic on shapes, the
    host-side count of what `_walk_bounds` makes the kernels do."""
    sub = _sub_block(block_k, sub if causal else None)
    nq, ns = sq // block_q, sk // sub
    if not causal:
        return nq * ns, nq * ns
    offset = sk - sq
    visited = sum(
        min(max(-(-(offset + (i + 1) * block_q) // sub), 0), ns)
        for i in range(nq))
    return visited, nq * ns


def _note_causal(kind, sq, sk, block_q, block_k, sub, causal):
    """Say in `compile_record(<family>)["flash_causal"]` how much of
    [sq, sk] this kernel visits."""
    from ...observability import perf
    visited, total = causal_tiles(sq, sk, block_q, block_k, sub, causal)
    perf.trace_note("flash_causal",
                    f"{kind} {visited}/{total} of {sub}-wide tiles")


def _seg_tile_mask(row_ref, lane_ref, r0, rows, l0, lanes):
    """Segment-equality mask [rows, lanes] from the streamed id tiles: the
    ids of the tile's rows from r0 on against those of its lanes from l0.

    Layout (TPU-friendly, same convention as the public jax pallas flash
    attention): the row side's ids ride as [n, _LANES] (value replicated
    over lanes), the lane side's as [_SUBL, n] (value replicated over
    sublanes) — both are natural 2D tiles, no in-kernel transposes. The
    forward's rows are q and its lanes keys; the backward's the reverse."""
    rs = jnp.tile(row_ref[0, pl.ds(r0, rows), :], (1, lanes // _LANES))
    ls = lane_ref[0, :1, pl.ds(l0, lanes)]        # [1, lanes]
    return rs == ls


# ======================= forward =======================

def _fwd_kernel(*refs, causal, block_q, block_k, sub, H, Hk, D, offset,
                has_seg):
    if has_seg:
        (q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _visit(k0):
        """Keys k0 .. k0 + sub of the resident block against the q block,
        every head; the online-softmax state lives in scratch."""
        qf = q_ref[0]                      # [bq, H*D], pre-scaled
        kf = k_ref[0, pl.ds(k0, sub), :]   # [sub, Hk*D]
        vf = v_ref[0, pl.ds(k0, sub), :]
        ok = (_causal_tile_mask(offset + qi * block_q, ki * block_k + k0,
                                (block_q, sub)) if causal else None)
        if has_seg:
            seg_ok = _seg_tile_mask(qseg_ref, kseg_ref, 0, block_q, k0, sub)
            ok = seg_ok if ok is None else jnp.logical_and(ok, seg_ok)
        if ok is not None:
            neg = jnp.full((block_q, sub), _NEG_INF, jnp.float32)
            zero = jnp.zeros((block_q, sub), jnp.float32)
        for (c, w), (ck, wk), heads in _head_slabs(H, Hk, D):
            q2, k2, v2 = _cols(qf, c, w), _cols(kf, ck, wk), _cols(vf, ck, wk)
            pv = scale = None
            for h, mine in heads:
                s = _nt_dot(q2 if mine is None
                            else _pick(mine, q2, jnp.zeros_like(q2)),
                            k2)                          # [bq, sub] f32
                if ok is not None:
                    s = jax.lax.select(ok, s, neg)
                m_prev = m_ref[h]                        # [bq, LANES]
                m_new = jax.lax.max(m_prev, _rows_to_lanes(
                    jax.lax.reduce_max(s, (1,)), _LANES))
                p = jax.lax.exp(jax.lax.sub(s, _lanes(m_new, sub)))
                if ok is not None:
                    # rows with NO valid key in this tile (segment
                    # mismatch, or bottom-right causal with sq > sk):
                    # m_new stays at _NEG_INF and exp(s - m_new) = 1 —
                    # zero those explicitly
                    p = jax.lax.select(ok, p, zero)
                alpha = jax.lax.exp(jax.lax.sub(m_prev, m_new))
                l_ref[h] = jax.lax.add(
                    jax.lax.mul(alpha, l_ref[h]),
                    _rows_to_lanes(jax.lax.reduce_sum(p, (1,)), _LANES))
                m_ref[h] = m_new
                pv_h = _dot(p.astype(v2.dtype), v2)      # [bq, slab]
                alpha = _lanes(alpha, w)
                pv = pv_h if pv is None else _pick(mine, pv_h, pv)
                scale = alpha if scale is None else _pick(mine, alpha,
                                                          scale)
            sl = slice(c, c + w)
            acc_ref[:, sl] = jax.lax.add(
                jax.lax.mul(acc_ref[:, sl], scale), pv)

    if causal:
        _walk(_walk_bounds(qi, ki, block_q, block_k, sub, offset), sub,
              block_k, _visit)
    else:
        _visit(0)

    @pl.when(ki == nk - 1)
    def _finalize():
        # head h's statistics sit in every lane of m_ref[h] and l_ref[h];
        # the log-sums leave as one column a head, so that per-head lse
        # rows with seq in lanes are one [bq, LANES] transpose away
        lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, _LANES), 1)
        zero = jnp.zeros((block_q, _LANES), jnp.float32)
        one = jnp.ones((block_q, _LANES), jnp.float32)
        lse_c = zero
        for h in range(H):
            sl = slice(h * D, (h + 1) * D)
            l = l_ref[h]
            safe_l = jax.lax.select(jax.lax.eq(l, zero), one, l)
            o_ref[0, :, sl] = jax.lax.div(
                acc_ref[:, sl], _lanes(safe_l, D)).astype(o_ref.dtype)
            lse_c = jax.lax.select(
                jax.lax.eq(lane, jax.lax.full_like(lane, h)),
                jax.lax.add(m_ref[h], jax.lax.log(safe_l)), lse_c)
        lse_t = jax.lax.transpose(lse_c, (1, 0))
        for h in range(H):
            lse_ref[0, h * _SUBL:(h + 1) * _SUBL, :] = jnp.broadcast_to(
                lse_t[h:h + 1], (_SUBL, lse_t.shape[1]))


def _seg_operands(segment_ids, b, n_rows, n_lanes):
    """Broadcast (row side's ids [b, n_rows], lane side's [b, n_lanes])
    int32 into the TPU tile layouts _seg_tile_mask expects."""
    row_seg, lane_seg = segment_ids
    row_seg = jnp.broadcast_to(jnp.asarray(row_seg, jnp.int32)[:, :, None],
                               (b, n_rows, _LANES))
    lane_seg = jnp.broadcast_to(jnp.asarray(lane_seg, jnp.int32)[:, None, :],
                                (b, _SUBL, n_lanes))
    return row_seg, lane_seg


def _autotuned_blocks(kind, q, k, H, Hk, causal, has_seg, defaults,
                      run_shape, normalize):
    """Per-(shape-class, device-generation) {block_q, block_k} search
    (ref: phi/kernels/autotune/switch_autotune.cc). First call measures
    a candidate set (hand-tuned defaults included, so tuned >= default
    up to noise) on synthetic data and persists the winner; later calls
    and later PROCESSES pay one dict lookup. Tracer-safe: measurement
    uses fresh concrete arrays, never the traced operands."""
    from . import autotune
    import jax as _jax
    if not autotune.enabled():
        # the kill-switch restores hand-tuned defaults even when a
        # (possibly noise-picked) winner is already cached
        return defaults
    b, sq, HD = q.shape
    sk = k.shape[1]
    HkD = k.shape[2]
    # batch size is deliberately NOT in the key: blocks are per-tile
    # choices and b only multiplies the grid — keying on it would stall
    # a variable-batch serving workload with a fresh search per b
    # (the backward gets H/Hk back from custom_vjp residuals as typed
    # scalars: plain ints keep one spelling of the key)
    key = (kind, sq, sk, int(H), int(Hk), HD // int(H), str(q.dtype),
           int(causal), int(has_seg))
    hit = autotune.lookup(key)
    if hit is not None:
        return hit
    if _jax.process_count() > 1:
        # multi-host SPMD needs IDENTICAL programs on every host; noisy
        # per-host searches could pick different winners and diverge at
        # the first collective. Use defaults unless the operator
        # distributed one pre-seeded cache file to all hosts.
        return defaults
    cands = [defaults] + [c for c in
                          [(256, 512), (128, 512), (512, 512),
                           (128, 1024), (512, 1024)]
                          if c != defaults]
    # normalize through the same fit/pick THE USE SITE applies (fwd and
    # bwd differ: bwd grows block_k for long sk and buffers more), so
    # candidates that collapse to one real config are deduped (the
    # ragged autotuner's divisibility-normalized dedup, shared)
    norm = autotune.dedup_candidates(cands, normalize)
    if len(norm) == 1:
        return norm[0]

    # run_shape(bq, bk) returns a ZERO-ARG jitted runner: one compile
    # per candidate across ALL timing rounds (a fresh pallas_call
    # closure per invocation would recompile every sample — measured
    # 500 s of tuning vs ~90 s with cached runners)
    runners: dict = {}

    def _timed(c):
        if c not in runners:
            runners[c] = run_shape(*c)
        return autotune._time_call(runners[c])

    return autotune.tune(key, norm, _timed)


def _flash_fwd_fused(q, k, v, H, causal, block_q=256, block_k=1024,
                     interpret=False, Hk=None, segment_ids=None,
                     autotune_ok=True):
    """q: [b, s, H*D]; k,v: [b, sk, Hk*D] (q pre-scaled by sm_scale).
    Hk < H = grouped-query attention (q-head h reads kv-head h // (H//Hk)).
    segment_ids: optional (q_seg [b, sq], kv_seg [b, sk]) int32 — scores
    are masked to segment equality (padding/varlen-packing mask).
    Returns (out [b, s, H*D], lse [b, H*_SUBL, s] f32)."""
    b, sq, HD = q.shape
    sk = k.shape[1]
    D = HD // H
    Hk = H if Hk is None else Hk
    HkD = Hk * D
    has_seg = segment_ids is not None
    walk = _WALK if causal else None
    if autotune_ok and not interpret and (block_q, block_k) == (256, 1024):

        def run_shape(bq, bk):
            rng = np.random.default_rng(0)
            qs = jnp.asarray(rng.standard_normal((b, sq, HD)) * 0.1,
                             q.dtype)
            ks = jnp.asarray(rng.standard_normal((sk, HkD)) * 0.1,
                             q.dtype)[None].repeat(b, 0)
            seg = None
            if has_seg:
                seg = (jnp.zeros((b, sq), jnp.int32),
                       jnp.zeros((b, sk), jnp.int32))

            @jax.jit
            def f(qs, ks):
                out, _ = _flash_fwd_fused(
                    qs, ks, ks, H, causal, block_q=bq, block_k=bk,
                    Hk=Hk, segment_ids=seg, autotune_ok=False)
                return out

            return lambda: f(qs, ks)

        def _norm_fwd(bq, bk):
            bq2, bk2 = _fit_blocks(bq, bk, HD, n_bufs_q=2, n_bufs_k=2,
                                   HDk=HkD, sub=walk, stat_heads=H)
            return (_pick_block(sq, bq2), _pick_block(sk, bk2))

        block_q, block_k = _autotuned_blocks(
            "fwd", q, k, H, Hk, causal, has_seg, (block_q, block_k),
            run_shape, _norm_fwd)
    block_q, block_k = _fit_blocks(block_q, block_k, HD, n_bufs_q=2,
                                   n_bufs_k=2, HDk=HkD, sub=walk,
                                   stat_heads=H)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    sub = _sub_block(block_k, walk)
    _note_causal("fwd", sq, sk, block_q, block_k, sub, causal)
    return _fwd_call(q, k, v, segment_ids, H=H, Hk=Hk, causal=causal,
                     block_q=block_q, block_k=block_k, sub=sub,
                     interpret=interpret)


# The calls below are traced once for each shape and setting and inlined
# wherever they are made: a model's layers call one kernel a dozen times
# or more, and tracing its unrolled body each time was most of what the
# kernels cost a step's lowering.
_CALL_STATICS = ("H", "Hk", "causal", "block_q", "block_k", "sub",
                 "interpret")


@functools.partial(jax.jit, static_argnames=_CALL_STATICS, inline=True)
def _fwd_call(q, k, v, segment_ids, *, H, Hk, causal, block_q, block_k, sub,
              interpret):
    b, sq, HD = q.shape
    sk, HkD = k.shape[1], k.shape[2]
    D = HD // H
    has_seg = segment_ids is not None
    offset = sk - sq
    nk = sk // block_k
    grid = (b, sq // block_q, nk)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q, block_k=block_k,
        sub=sub, H=H, Hk=Hk, D=D, offset=offset, has_seg=has_seg)

    def kj(i, j):
        """The k block step (i, j) needs: a step wholly above the
        diagonal does no work, so it names the row's last block that
        runs, which is resident already, and the pipeline fetches
        nothing for it."""
        if not causal:
            return j
        last_q = jnp.maximum(offset + (i + 1) * block_q - 1, 0)
        return jnp.minimum(j, jnp.minimum(_div(last_q, block_k), nk - 1))

    in_specs = [
        pl.BlockSpec((1, block_q, HD), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, HkD), lambda b, i, j: (b, kj(i, j), 0)),
        pl.BlockSpec((1, block_k, HkD), lambda b, i, j: (b, kj(i, j), 0)),
    ]
    operands = [q, k, v]
    if has_seg:
        qseg, kseg = _seg_operands(segment_ids, b, sq, sk)
        in_specs += [
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, _SUBL, block_k),
                         lambda b, i, j: (b, 0, kj(i, j))),
        ]
        operands += [qseg, kseg]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, HD), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, H * _SUBL, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, HD), q.dtype),
            jax.ShapeDtypeStruct((b, H * _SUBL, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, HD), jnp.float32),
            # running max and sum of a head's rows, in every lane: what
            # a row reduction leaves and a row-wise update takes, so no
            # visit moves a column across lanes
            pltpu.VMEM((H, block_q, _LANES), jnp.float32),
            pltpu.VMEM((H, block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flash_fwd",   # also the innermost jax.named_scope
    )(*operands)


# ======================= backward =======================

def _bwd_kernel(*refs, causal, block_q, block_k, sub, H, Hk, D, offset,
                has_seg):
    """Single-pass backward: one s/p recompute per block pair feeds dk, dv
    AND this pair's dq contribution (vs. the classic two-kernel split that
    recomputes s/p and the dp dot twice). dq contributions can't accumulate
    in scratch here (the k-block axis is the outer grid dim), so each pair
    writes a partial into dqp [b, n_kblocks, sq, HD]; the caller sums
    over the k-block axis in XLA — a few hundred MB of streaming traffic
    that costs far less than a second full recompute pass. A causal pair
    is walked in key sub-blocks up to the diagonal, like the forward's;
    its partial is then the sum over the visited ones."""
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
         kseg_ref, dqp_ref, dk_ref, dv_ref, dk_acc, dv_acc, *dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dqp_ref, dk_ref, dv_ref, dk_acc, dv_acc, *dq_acc) = refs
        qseg_ref = kseg_ref = None
    if causal:
        dq_acc, = dq_acc    # [bq, HD] f32: the walk's sum of dq partials
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    def _visit(k0):
        """Keys k0 .. k0 + sub of the resident block against the q block.
        The score tiles are [sub, bq], keys in sublanes and q rows in
        lanes: a q row's log-sum and delta arrive as lane vectors and
        spread over sublanes for nothing, where a [bq, sub] tile would
        move each across lanes once a head and visit."""
        rows = pl.ds(k0, sub)
        qf = q_ref[0]                        # [bq, HD] (pre-scaled)
        dof = do_ref[0]
        kf = k_ref[0, rows, :]               # [sub, Hk*D]
        vf = v_ref[0, rows, :]
        ok = (_causal_tile_mask(offset + qi * block_q, ki * block_k + k0,
                                (sub, block_q), q_axis=1)
              if causal else None)
        if has_seg:
            seg_ok = _seg_tile_mask(kseg_ref, qseg_ref, k0, sub, 0, block_q)
            ok = seg_ok if ok is None else jnp.logical_and(ok, seg_ok)
        if ok is not None:
            zero = jnp.zeros((sub, block_q), jnp.float32)
        for (c, w), (ck, wk), heads in _head_slabs(H, Hk, D):
            q2, do2 = _cols(qf, c, w), _cols(dof, c, w)
            k2, v2 = _cols(kf, ck, wk), _cols(vf, ck, wk)
            dv = dk = dq = None
            for h, mine in heads:
                st = slice(h * _SUBL, h * _SUBL + 1)     # the head's stats
                q1, do1 = q2, do2                        # the head's alone
                if mine is not None:
                    q1 = _pick(mine, q2, jnp.zeros_like(q2))
                    do1 = _pick(mine, do2, jnp.zeros_like(do2))
                s = _nt_dot(k2, q1)                      # [sub, bq]
                p = jax.lax.exp(jax.lax.sub(
                    s, _over_rows(lse_ref[0, st, :], sub)))
                if ok is not None:
                    p = jax.lax.select(ok, p, zero)
                # dv = p^T @ do, the tile being p^T already
                dv_h = _dot(p.astype(do2.dtype), do2)    # [sub, slab]
                dp = _nt_dot(v2, do1)                    # [sub, bq]
                ds = jax.lax.mul(p, jax.lax.sub(
                    dp, _over_rows(delta_ref[0, st, :], sub))).astype(
                        q2.dtype)
                dk_h = _dot(ds, q2)          # dk = ds^T @ q_scaled
                dq_h = _dot(ds, k2, 0)       # this visit's dq: ds @ k
                if dv is None:
                    dv, dk, dq = dv_h, dk_h, dq_h
                else:
                    dv = _pick(mine, dv_h, dv)
                    dk = _pick(mine, dk_h, dk)
                    dq = _pick(mine, dq_h, dq)
            sl, slk = slice(c, c + w), slice(ck, ck + wk)
            dv_acc[rows, slk] = jax.lax.add(dv_acc[rows, slk], dv)
            dk_acc[rows, slk] = jax.lax.add(dk_acc[rows, slk], dk)
            # the block pair's dq partial is stored in dqp's dtype: the
            # input dtype while nk <= 8 (each partial individually rounded
            # before the f32-accumulated sum), f32 beyond that — the
            # caller picks (ADVICE r2: _fit_blocks can shrink block_k so
            # nk may exceed 8)
            if causal:      # summed over the walk in f32, rounded once
                dq_acc[:, sl] = jax.lax.add(dq_acc[:, sl], dq)
            else:
                dqp_ref[0, 0, :, sl] = dq.astype(dqp_ref.dtype)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if causal:
        n_visit = _walk_bounds(qi, ki, block_q, block_k, sub, offset)

        # skipped pairs (fully above the diagonal) still own an output
        # block in dqp — zero it so the XLA-side sum sees no garbage.
        @pl.when(n_visit == 0)
        def _skip():
            dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])

        @pl.when(n_visit > 0)
        def _run():
            dq_acc[:] = jnp.zeros_like(dq_acc)
            _walk(n_visit, sub, block_k, _visit)
            dqp_ref[0, 0] = dq_acc[:].astype(dqp_ref.dtype)
    else:
        _visit(0)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_fused(q, k, v, o, lse, do, H, causal,
                     block_q=256, block_k=512, interpret=False,
                     Hk=None, segment_ids=None, autotune_ok=True):
    """Blockwise dq/dk/dv on the fused-head layout.

    q,o,do: [b, sq, H*D] (q pre-scaled); k,v: [b, sk, Hk*D];
    lse: [b, H*_SUBL, sq] f32.
    Returns (dq_scaled f32, dk, dv) — caller multiplies dq by sm_scale.
    """
    b, sq, HD = q.shape
    sk = k.shape[1]
    D = HD // H
    Hk = H if Hk is None else Hk
    HkD = Hk * D
    walk = _WALK if causal else None
    if autotune_ok and not interpret and (block_q, block_k) == (256, 512):

        def run_shape(bq, bk):
            rng = np.random.default_rng(0)
            qs = jnp.asarray(rng.standard_normal((b, sq, HD)) * 0.1,
                             q.dtype)
            ks = jnp.asarray(rng.standard_normal((sk, HkD)) * 0.1,
                             q.dtype)[None].repeat(b, 0)
            lses = jnp.full((b, H * _SUBL, sq), 3.0, jnp.float32)
            seg = None
            if segment_ids is not None:
                seg = (jnp.zeros((b, sq), jnp.int32),
                       jnp.zeros((b, sk), jnp.int32))

            @jax.jit
            def f(qs, ks, lses):
                dq, _, _ = _flash_bwd_fused(
                    qs, ks, ks, qs, lses, qs, H, causal, block_q=bq,
                    block_k=bk, Hk=Hk, segment_ids=seg,
                    autotune_ok=False)
                return dq

            return lambda: f(qs, ks, lses)

        def _norm_bwd(bq, bk):
            bk = max(bk, sk // 8)       # the use-site's long-seq grow
            bq2, bk2 = _fit_blocks(bq, bk, HD, n_bufs_q=3, n_bufs_k=4,
                                   HDk=HkD, sub=walk)
            return (_pick_block(sq, bq2), _pick_block(sk, bk2))

        block_q, block_k = _autotuned_blocks(
            "bwd", q, k, H, Hk, causal, segment_ids is not None,
            (block_q, block_k), run_shape, _norm_bwd)
    # long sequences: grow K blocks so the dq partial-sum buffer
    # (b * nk * sq * HD) stays bounded at nk <= 8 — _fit_blocks may shrink
    # them back if HD is too wide for VMEM, which keeps correctness and
    # trades the extra partials for compile-safety.
    block_k = max(block_k, sk // 8)
    block_q, block_k = _fit_blocks(block_q, block_k, HD, n_bufs_q=3,
                                   n_bufs_k=4, HDk=HkD, sub=walk)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    sub = _sub_block(block_k, walk)
    _note_causal("bwd", sq, sk, block_q, block_k, sub, causal)
    return _bwd_call(q, k, v, o, lse, do, segment_ids, H=H, Hk=Hk,
                     causal=causal, block_q=block_q, block_k=block_k,
                     sub=sub, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_CALL_STATICS, inline=True)
def _bwd_call(q, k, v, o, lse, do, segment_ids, *, H, Hk, causal, block_q,
              block_k, sub, interpret):
    b, sq, HD = q.shape
    sk, HkD = k.shape[1], k.shape[2]
    D = HD // H
    offset = sk - sq
    nk, nq = sk // block_k, sq // block_q
    # dq partials in the input dtype are only safe while few partials are
    # summed; past nk=8 (e.g. _fit_blocks shrank block_k for a wide HD)
    # keep them f32 so rounding doesn't scale with nk (ADVICE r2)
    dqp_dtype = q.dtype if nk <= 8 else jnp.float32

    # delta_i = rowsum(do_i * o_i) per head — fused elementwise in XLA,
    # laid out like lse: [b, H*_SUBL, sq].
    dof = do.reshape(b, sq, H, D).astype(jnp.float32)
    of = o.reshape(b, sq, H, D).astype(jnp.float32)
    delta = jnp.einsum("bshd,bshd->bhs", dof, of)         # [b, H, sq]
    delta = jnp.broadcast_to(delta[:, :, None, :],
                             (b, H, _SUBL, sq)).reshape(b, H * _SUBL, sq)

    def qi(j, i):
        """The q block step (j, i) needs: the q blocks wholly above k
        block j's diagonal do no work, so they name the first one that
        does, which the pipeline then fetches once, for all of them."""
        if not causal:
            return i
        first = _div(jnp.maximum(j * block_k - offset, 0), block_q)
        return jnp.maximum(i, jnp.minimum(first, nq - 1))

    q_spec_i = pl.BlockSpec((1, block_q, HD),
                            lambda b, j, i: (b, qi(j, i), 0))
    k_spec_j = pl.BlockSpec((1, block_k, HkD), lambda b, j, i: (b, j, 0))
    stat_i = pl.BlockSpec((1, H * _SUBL, block_q),
                          lambda b, j, i: (b, 0, qi(j, i)))
    dqp_spec = pl.BlockSpec((1, 1, block_q, HD),
                            lambda b, j, i: (b, j, i, 0))

    has_seg = segment_ids is not None
    in_specs = [q_spec_i, k_spec_j, k_spec_j, q_spec_i, stat_i, stat_i]
    operands = [q, k, v, do, lse, delta]
    if has_seg:
        # the backward's tiles have keys in rows and q in lanes
        kseg, qseg = _seg_operands(segment_ids[::-1], b, sk, sq)
        in_specs += [
            pl.BlockSpec((1, _SUBL, block_q),
                         lambda b, j, i: (b, 0, qi(j, i))),
            pl.BlockSpec((1, block_k, _LANES), lambda b, j, i: (b, j, 0)),
        ]
        operands += [qseg, kseg]

    dqp, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, block_q=block_q,
                          block_k=block_k, sub=sub, H=H, Hk=Hk, D=D,
                          offset=offset, has_seg=has_seg),
        grid=(b, nk, nq),
        in_specs=in_specs,
        out_specs=[dqp_spec, k_spec_j, k_spec_j],
        out_shape=[
            jax.ShapeDtypeStruct((b, nk, sq, HD), dqp_dtype),
            jax.ShapeDtypeStruct((b, sk, HkD), k.dtype),
            jax.ShapeDtypeStruct((b, sk, HkD), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, HkD), jnp.float32),
            pltpu.VMEM((block_k, HkD), jnp.float32),
        ] + ([pltpu.VMEM((block_q, HD), jnp.float32)] if causal else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        # XLA names the custom call after the innermost scope, which
        # `name=` is. `transpose` (jax's word for the backward pass)
        # stays in it because benchmarks/kernel_costs/flash.py:classify
        # tells the backward kernel from the forward one by that word
        name="flash_bwd_transpose",
    )(*operands)
    return jnp.sum(dqp, axis=1, dtype=jnp.float32), dk, dv


def _pick_block(s, target):
    """Largest block <= target that divides s (s is a multiple of 128)."""
    if s % 128:
        raise ValueError(f"seq {s} must be a multiple of 128")
    blk = min(target, s)
    while s % blk:
        blk -= 128
    return blk


def _fit_blocks(block_q, block_k, HD, n_bufs_q, n_bufs_k, HDk=None,
                budget=_VMEM_LIMIT, sub=None, stat_heads=0):
    """Shrink (block_q, block_k) until the kernel's VMEM appetite fits.

    The dominant consumers scale linearly with the operand widths
    (double-buffered block DMAs + f32 accumulators) and with the
    score-tile transients (block_q*block_k, or block_q*sub under a causal
    walk of `sub`-wide visits), so large-model head widths
    (e.g. HD=4096) must trade block size rather than crash the Pallas
    compile. HDk: k/v-side width (Hk*D) — narrower than HD under GQA/MQA,
    so k-side blocks aren't shrunk for q-side bytes. stat_heads: heads
    whose running statistics the kernel keeps in every lane (the
    forward's; the backward keeps none)."""
    HDk = HD if HDk is None else HDk

    def est(bq, bk):
        io = 2 * (n_bufs_q * bq * HD + n_bufs_k * bk * HDk) * 2  # dbuf DMAs
        acc = (bq * HD + bk * HDk) * 4                   # f32 accumulators
        acc += 2 * stat_heads * bq * _LANES * 4          # the forward's m, l
        tile = 3 * bq * min(bk, sub or bk) * 4           # score transients
        return io + acc + tile
    while est(block_q, block_k) > budget * 0.75 and (
            block_q > 128 or block_k > 128):
        if block_k >= block_q and block_k > 128:
            block_k //= 2
        else:
            block_q //= 2
    return max(block_q, 128), max(block_k, 128)


# ======================= dispatch =======================

def _xla_attention(q, k, v, attn_mask, causal, sm_scale, segment_ids=None):
    """Reference composite ([b,s,h,d] in/out) — the non-Pallas fallback.
    Handles GQA (kv heads dividing q heads), bottom-right-aligned causal
    masking for sq != sk (FA2 semantics), and segment-id masking."""
    h, hk = q.shape[2], k.shape[2]
    if hk != h:
        rep = h // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                   preferred_element_type=jnp.float32) * sm_scale
    neg = jnp.asarray(_NEG_INF, s.dtype)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        qpos = (sk - sq) + jnp.arange(sq)[:, None]
        kpos = jnp.arange(sk)[None, :]
        s = jnp.where(qpos >= kpos, s, neg)
    if segment_ids is not None:
        q_seg, kv_seg = segment_ids
        ok = (jnp.asarray(q_seg)[:, None, :, None]
              == jnp.asarray(kv_seg)[:, None, None, :])
        s = jnp.where(ok, s, neg)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            s = jnp.where(attn_mask, s, neg)
        else:
            s = s + attn_mask.astype(s.dtype)
    # fully-masked rows (padding / cross-length causal): softmax of all
    # -inf would give uniform garbage; zero them instead
    any_valid = jnp.max(s, axis=-1, keepdims=True) > _NEG_INF / 2
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    p = jnp.where(any_valid, p, jnp.zeros_like(p))
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def _pallas_available():
    """The Pallas kernels are the path on a TPU backend and only there.
    Nothing is tried and nothing is caught: a kernel the chip's
    compiler refuses raises from the call that launched it."""
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_core(q, k, v, segment_ids, causal, sm_scale, use_pallas):
    """[b, s, h, d] in/out; k, v may carry fewer (kv) heads (GQA/MQA).
    segment_ids: None or (q_seg [b,sq], kv_seg [b,sk]) int32."""
    out, _ = _flash_core_fwd(q, k, v, segment_ids, causal, sm_scale,
                             use_pallas)
    return out


def _flash_core_fwd(q, k, v, segment_ids, causal, sm_scale, use_pallas):
    if use_pallas:
        b, s, h, d = q.shape
        hk = k.shape[2]
        qs = (q * sm_scale).astype(q.dtype).reshape(b, s, h * d)
        km = k.reshape(b, -1, hk * d)
        vm = v.reshape(b, -1, hk * d)
        o, lse = _flash_fwd_fused(qs, km, vm, h, causal, Hk=hk,
                                  segment_ids=segment_ids)
        return o.reshape(b, s, h, d), (qs, km, vm, o, lse, h, hk,
                                       segment_ids)
    out = _xla_attention(q, k, v, None, causal, sm_scale,
                         segment_ids=segment_ids)
    return out, (q, k, v, None, None, None, None, segment_ids)


def _flash_core_bwd(causal, sm_scale, use_pallas, res, g):
    q, k, v, o, lse, h, hk, segment_ids = res
    if use_pallas:
        b, s, hd = q.shape
        gm = g.reshape(b, s, hd)
        dq, dk, dv = _flash_bwd_fused(q, k, v, o, lse, gm, h, causal,
                                      Hk=hk, segment_ids=segment_ids)
        d = hd // h
        dq = (dq * sm_scale).astype(q.dtype)  # dq arrives as f32 partial-sum
        return (dq.reshape(b, s, h, d), dk.reshape(b, -1, hk, d),
                dv.reshape(b, -1, hk, d), None)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _xla_attention(q_, k_, v_, None, causal, sm_scale,
                                          segment_ids=segment_ids),
        q, k, v)
    return vjp(g) + (None,)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _shapes_ok(q_shape, k_shape):
    return not _shape_reject_reason(q_shape, k_shape)


def _shape_reject_reason(q_shape, k_shape):
    """None if the Pallas kernel applies, else a human-readable reason."""
    sq, sk, h, d = q_shape[1], k_shape[1], q_shape[2], q_shape[-1]
    hk = k_shape[2]
    if d not in (64, 128, 256):
        return f"head_dim {d} not in (64, 128, 256)"
    if sq < 128 or sk < 128 or sq % 128 or sk % 128:
        return (f"seq lengths ({sq}, {sk}) must be >=128 multiples of 128 "
                "(pad or pack, e.g. via segment_ids)")
    if (h * d) % _LANES or h > _LANES:
        return f"h*d={h * d} must be lane-aligned (%128==0) with h<=128"
    if h % max(hk, 1) or (hk * d) % _LANES:
        return (f"kv heads {hk} must divide q heads {h} with hk*d "
                "lane-aligned (%128==0)")
    return None


def attention_path(q_shape, k_shape, masked=False):
    """('pallas'|'xla', reason) — which implementation flash_attention will
    take for these shapes and why. Lets callers (chip_smoke.py checks it;
    nn.functional.flash_attention warns on fallback) see when the Pallas
    kernel disengages. masked=True means a dense attn_mask (XLA
    composite); segment-id masking stays on the Pallas path and needs no
    flag."""
    if masked:
        return ("xla", "dense attn_mask forces the XLA composite — use "
                "segment_ids or causal for the Pallas path")
    if not _pallas_available():
        return ("xla", f"no TPU Pallas backend ({jax.default_backend()})")
    reason = _shape_reject_reason(q_shape, k_shape)
    if reason:
        return ("xla", reason)
    return ("pallas", "")


# (mesh, batch_axes) while a program that GSPMD will partition over
# `mesh` is being traced; None otherwise
_MESH_PLAN: contextvars.ContextVar = contextvars.ContextVar(
    "flash_mesh_plan", default=None)


@contextlib.contextmanager
def mesh_plan(mesh, batch_axes=()):
    """Tell the kernels traced inside this block that the program will
    be partitioned over `mesh`, with the batch dimension of its data
    split over `batch_axes`. The compiler cannot partition a Mosaic
    kernel by itself, so under a plan `flash_attention` splits its call
    with `shard_map`: batch over `batch_axes`, heads over the mesh's
    other axes (the Megatron layout) where the per-device head count
    still fits the kernel, whole on every device of an axis where it
    does not."""
    token = _MESH_PLAN.set((mesh, tuple(batch_axes)))
    try:
        yield
    finally:
        _MESH_PLAN.reset(token)


def _planned_specs(plan, q_shape, k_shape):
    """PartitionSpecs (q/k/v/out [b, s, h, d], segment ids [b, s]) that
    split the kernel over the plan's mesh."""
    from jax.sharding import PartitionSpec as P
    mesh, batch_axes = plan
    b, sq, h, d = q_shape
    hk = k_shape[2]
    batch_axes = tuple(a for a in batch_axes if mesh.shape[a] > 1)
    if b % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = ()
    head_axes = tuple(a for a in mesh.axis_names
                      if a not in batch_axes and mesh.shape[a] > 1)
    n = math.prod(mesh.shape[a] for a in head_axes)
    if h % n or hk % n or not _shapes_ok(
            (b, sq, h // n, d), (b, k_shape[1], hk // n, d)):
        head_axes = ()
    return (P(batch_axes or None, None, head_axes or None, None),
            P(batch_axes or None, None))


def flash_attention(q, k, v, attn_mask=None, causal=False,
                    softmax_scale=None, segment_ids=None):
    """[b, s, h, d] in and out; k/v may have fewer heads (GQA/MQA).

    segment_ids: (q_seg [b, sq], kv_seg [b, sk]) int32 — attention is
    masked to equal ids (padding / packed-varlen, stays on the Pallas
    path). A dense attn_mask forces the XLA composite.
    Causal masking is bottom-right aligned when sq != sk (FA2 semantics,
    ref: python/paddle/nn/functional/flash_attention.py:146 routing to the
    FlashAttention-2 library)."""
    d = q.shape[-1]
    sm_scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(d)
    if attn_mask is not None:
        return _xla_attention(q, k, v, attn_mask, causal, sm_scale,
                              segment_ids=segment_ids)
    use_pallas = _pallas_available() and _shapes_ok(q.shape, k.shape)
    if segment_ids is not None:
        segment_ids = (jnp.asarray(segment_ids[0], jnp.int32),
                       jnp.asarray(segment_ids[1], jnp.int32))
    plan = _MESH_PLAN.get()
    if plan is not None and use_pallas:
        spec, seg_spec = _planned_specs(plan, q.shape, k.shape)
        return jax.shard_map(
            lambda q, k, v, seg: _flash_core(q, k, v, seg, causal,
                                             sm_scale, True),
            mesh=plan[0],
            in_specs=(spec, spec, spec,
                      None if segment_ids is None else (seg_spec,) * 2),
            out_specs=spec, check_vma=False)(q, k, v, segment_ids)
    return _flash_core(q, k, v, segment_ids, causal, sm_scale,
                       bool(use_pallas))
