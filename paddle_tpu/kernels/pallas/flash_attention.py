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

sm_scale is folded into q inside the kernels, once a q block, where the
block is loaded (no [bq, bk] VPU pass per head per block pair, and no
pass over q in HBM before the call); dq's scale is applied where dq is
rounded.

Every operand is an (array, column block) pair: the kernels' BlockSpecs
read q, k and v where they lie, three arrays at column block 0 for the
[b, s, h, d] entry or one fused projection's [b, s, 3*h*d] at column
blocks 0, 1, 2 (`flash_attention_qkv`), so nothing is sliced, copied or
relaid between a fused projection and the kernels.

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
head_dim] (ref: python/paddle/nn/functional/flash_attention.py:146).
The [b,s,h,d] <-> [b,s,h*d] reshape reorders no axis, but on a TPU the
two are tiled differently and XLA materialises it: a caller with a fused
projection takes `flash_attention_qkv`, which has none.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from ...core.mesh_plan import current_mesh_plan
from ...observability import perf as _pf

_NEG_INF = -1e30
# raised scoped-VMEM budget: the 1024-wide resident K/V blocks need ~17MB
# with double buffering (the default scoped limit is 16MB)
_VMEM_LIMIT = 64 * 1024 * 1024
# the backward's: beside k and v it holds their two float32 accumulators
# and the gradient's block, 70 MB at 1024 keys of 2048 lanes, of the 128
# MiB a core has
_VMEM_LIMIT_BWD = 100 * 1024 * 1024
_LANES = 128
_SUBL = 8   # per-head stats ride as [b, h*_SUBL, s]: seq in lanes, each
            # head's row replicated over one sublane tile (minimum height)


def _causal_tile_mask(q0, k0, shape, q_axis=0, window=None):
    """Bool validity (q_pos >= k_pos, and q_pos - k_pos < window where
    there is one) of a score tile of `shape` whose
    first q row sits at causal position q0 and whose first key at k0; q
    rows run along `q_axis`, keys along the other. Only called on tiles
    that straddle the diagonal.

    q0 carries offset = sk - sq, which gives the FlashAttention-2
    bottom-right-aligned causal mask for cross-length attention (the
    reference's dynloaded FA2 library aligns this way; ADVICE r2 finding
    on top-left drift)."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    if window is None:
        return q_pos >= k_pos
    return jnp.logical_and(q_pos >= k_pos, q_pos - k_pos < window)


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


def _shared_q(qf, h, HD, R):
    """Head h's second part of a q block whose key comes in two parts,
    qf [bq, HD + H*R]: after the H heads' own parts of D lanes come their
    second parts of R, which all multiply the ONE shared key head. The
    128-lane slab that holds the head's R lanes, the other heads' lanes
    of it zeroed, the mask of its own, and the slab's first lane: against
    the shared keys repeated along the slab's lanes (`_shared_rows`), the
    zeros drop the neighbours' products out of the contraction, as in
    `_head_slabs`."""
    at = HD + h * R
    start, lo = at - at % _LANES, at % _LANES
    slab = _cols(qf, start, _LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    mine = jnp.logical_and(lane >= lo, lane < lo + R)
    return _pick(mine, slab, jnp.zeros_like(slab)), mine, start


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


def _scaled(x, sm_scale, dtype):
    """x * sm_scale in float32, rounded to `dtype` once."""
    return jax.lax.mul(x.astype(jnp.float32),
                       jnp.full(x.shape, sm_scale, jnp.float32)).astype(dtype)


def _heads_to_rows(dst, by_column, H):
    """A per-row value a head, head h's in column h of `by_column`
    [rows, _LANES], stored as the kernels read statistics: dst
    [H*_SUBL, rows], a head's rows in lanes over one sublane tile, one
    [rows, _LANES] transpose for all heads."""
    by_row = jax.lax.transpose(by_column, (1, 0))
    for h in range(H):
        dst[h * _SUBL:(h + 1) * _SUBL, :] = jnp.broadcast_to(
            by_row[h:h + 1], (_SUBL, by_row.shape[1]))


def _walk_bounds(qi, ki, block_q, block_k, sub, offset):
    """How many of a causal (q block, k block) pair's block_k // sub key
    sub-blocks have a key some q row of the pair sees: those are visited,
    in order; the rest lie above the diagonal. A pair wholly above it
    visits none."""
    # keys of this block the pair's last q row sees
    seen = offset + (qi + 1) * block_q - ki * block_k
    return _div(jnp.clip(seen, 0, block_k) + sub - 1, sub)


def _walk_from(qi, ki, block_q, block_k, sub, offset, window):
    """The first key sub-block of a windowed causal (q block, k block)
    pair that some q row of the pair sees: the pair's first row sees no
    key before its own position less window - 1, so the sub-blocks
    wholly before that are skipped. All of them, for a pair wholly
    before the window."""
    unseen = offset + qi * block_q - (window - 1) - ki * block_k
    return _div(jnp.clip(unseen, 0, block_k), sub)


def _first_kblock(qi, block_q, block_k, offset, window):
    """The first k block a windowed q block sees a key of."""
    return _div(jnp.maximum(offset + qi * block_q - (window - 1), 0),
                block_k)


def _first_qblock(ki, block_q, block_k, offset):
    """The first q block that sees a key of causal k block ki."""
    return _div(jnp.maximum(ki * block_k - offset, 0), block_q)


def _last_qblock(ki, block_q, block_k, offset, window, nq):
    """The last q block that sees a key of windowed k block ki."""
    last_row = (ki + 1) * block_k - 1 + (window - 1) - offset
    return jnp.minimum(_div(jnp.maximum(last_row, 0), block_q), nq - 1)


def _window_kblocks(block_q, block_k, window, nk):
    """K blocks a windowed q block can see keys of: its rows' keys span
    block_q + window - 1 positions."""
    return min(nk, (block_q + window - 2) // block_k + 2)


def _window_qblocks(block_q, block_k, window, nq):
    """Q blocks that can see keys of one windowed k block."""
    return min(nq, (block_k + window - 2) // block_q + 2)


def _walk(n_visit, sub, block_k, visit, lo=0):
    """visit(k0) over a causal pair's key sub-blocks lo .. n_visit (lo: a
    window's lower edge, `_walk_from`), k0 the
    sub-block's first key within the block. The bound comes from the
    program ids, so the walk is a loop and not a second unrolled level
    under the unrolled heads; a block that is one sub-block is visited
    under a condition. One body serves every visit, the tile mask on in
    all of them: a second, unmasked body for the sub-blocks wholly below
    the diagonal doubles what the kernels cost to trace and lower."""
    if sub == block_k:
        pl.when(n_visit > lo)(lambda: visit(0))
        return

    def body(j, carry):
        visit(pl.multiple_of(j * sub, sub))
        return carry
    jax.lax.fori_loop(lo, n_visit, body, 0)


def causal_tiles(sq, sk, block_q, block_k, sub, causal=True, window=None):
    """(visited, total) [block_q, sub]-tiles of the [sq, sk] scores a
    kernel with these blocks computes: pure arithmetic on shapes, the
    host-side count of what `_walk_bounds` (and, under a window,
    `_walk_from`) makes the kernels do."""
    sub = _sub_block(block_k, sub if causal else None)
    nq, ns = sq // block_q, sk // sub
    if not causal:
        return nq * ns, nq * ns
    offset = sk - sq
    visited = 0
    for i in range(nq):
        hi = min(max(-(-(offset + (i + 1) * block_q) // sub), 0), ns)
        lo = 0 if window is None else min(
            max(offset + i * block_q - (window - 1), 0) // sub, ns)
        visited += max(hi - lo, 0)
    return visited, nq * ns


def _note_causal(kind, sq, sk, block_q, block_k, sub, causal, more="",
                 window=None):
    """Say in `compile_record(<family>)["flash_causal"]` how much of
    [sq, sk] this kernel visits (and `more`: the backward's dq)."""
    visited, total = causal_tiles(sq, sk, block_q, block_k, sub, causal,
                                  window)
    if window is not None:
        more += f", window {window}"
    _pf.trace_note("flash_causal",
                   f"{kind} {visited}/{total} of {sub}-wide tiles{more}")


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

def _fwd_kernel(*refs, sm_scale, causal, block_q, block_k, sub, H, Hk, D,
                offset, has_seg, window=None, shared=0):
    """`shared`: 0, or the size of the key's second part (see
    `_shared_q`): q_ref then holds [bq, H*D + H*shared] and a fourth
    input the shared head's keys, and a score is the sum of the two
    products, taken as one contraction over a head's lanes and the
    shared slab's."""
    q_ref, k_ref, v_ref, *refs = refs
    kpe_ref = qseg_ref = kseg_ref = None
    if shared:
        kpe_ref, *refs = refs
    if has_seg:
        qseg_ref, kseg_ref, *refs = refs
    o_ref, lse_ref, qs_ref, acc_ref, m_ref, l_ref = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    # the k block this step holds: under a window the grid's k axis
    # counts from the first block the q block sees a key of
    kb = ki if window is None else ki + _first_kblock(
        qi, block_q, block_k, offset, window)

    @pl.when(ki == 0)
    def _init():
        # the q block, scaled once for all of its key blocks and visits
        qs_ref[:] = _scaled(q_ref[0], sm_scale, qs_ref.dtype)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _visit(k0):
        """Keys k0 .. k0 + sub of the resident block against the q block,
        every head; the online-softmax state lives in scratch."""
        qf = qs_ref[:]                     # [bq, H*D], scaled
        kf = k_ref[0, pl.ds(k0, sub), :]   # [sub, Hk*D]
        vf = v_ref[0, pl.ds(k0, sub), :]
        if shared:
            kpe = kpe_ref[0, pl.ds(k0, sub), :]          # [sub, LANES]
        ok = (_causal_tile_mask(offset + qi * block_q, kb * block_k + k0,
                                (block_q, sub), window=window)
              if causal else None)
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
                qh, kh = (q2 if mine is None
                          else _pick(mine, q2, jnp.zeros_like(q2))), k2
                if shared:
                    qh = jax.lax.concatenate(
                        [qh, _shared_q(qf, h, H * D, shared)[0]], 1)
                    kh = jax.lax.concatenate([k2, kpe], 1)
                s = _nt_dot(qh, kh)                      # [bq, sub] f32
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
        _walk(_walk_bounds(qi, kb, block_q, block_k, sub, offset), sub,
              block_k, _visit, 0 if window is None else _walk_from(
                  qi, kb, block_q, block_k, sub, offset, window))
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
        _heads_to_rows(lse_ref.at[0], lse_c, H)


def _seg_operands(segment_ids, b, n_rows, n_lanes):
    """Broadcast (row side's ids [b, n_rows], lane side's [b, n_lanes])
    int32 into the TPU tile layouts _seg_tile_mask expects."""
    row_seg, lane_seg = segment_ids
    row_seg = jnp.broadcast_to(jnp.asarray(row_seg, jnp.int32)[:, :, None],
                               (b, n_rows, _LANES))
    lane_seg = jnp.broadcast_to(jnp.asarray(lane_seg, jnp.int32)[:, None, :],
                                (b, _SUBL, n_lanes))
    return row_seg, lane_seg


def _autotuned_blocks(kind, shape, H, Hk, causal, has_seg, defaults,
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
    sq, sk, D, dtype = shape
    # batch size is deliberately NOT in the key: blocks are per-tile
    # choices and b only multiplies the grid — keying on it would stall
    # a variable-batch serving workload with a fresh search per b
    key = (kind, sq, sk, int(H), int(Hk), int(D), dtype,
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
                     autotune_ok=True, sm_scale=1.0, cols=(0, 0, 0),
                     D=None, window=None):
    """q: [b, s, H*D]; k,v: [b, sk, Hk*D], each read at its column block
    of `cols` (in units of its own width): three arrays at (0, 0, 0), or
    one [b, s, 3*H*D] projection passed three times at (0, 1, 2) with its
    head size `D`. q is scaled by sm_scale in the kernel.
    Hk < H = grouped-query attention (q-head h reads kv-head h // (H//Hk)).
    segment_ids: optional (q_seg [b, sq], kv_seg [b, sk]) int32 — scores
    are masked to segment equality (padding/varlen-packing mask).
    Returns (out [b, s, H*D], lse [b, H*_SUBL, s] f32)."""
    b, sq = q.shape[:2]
    sk = k.shape[1]
    D = q.shape[2] // H if D is None else D
    Hk = H if Hk is None else Hk
    HD, HkD = H * D, Hk * D
    has_seg = segment_ids is not None
    walk = _WALK if causal else None
    if autotune_ok and not interpret and window is None \
            and (block_q, block_k) == (256, 1024):

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
            "fwd", (sq, sk, D, str(q.dtype)), H, Hk, causal, has_seg,
            (block_q, block_k), run_shape, _norm_fwd)
    block_q, block_k = _fit_blocks(block_q, block_k, HD, n_bufs_q=2,
                                   n_bufs_k=2, HDk=HkD, sub=walk,
                                   stat_heads=H)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    sub = _sub_block(block_k, walk)
    _note_causal("fwd", sq, sk, block_q, block_k, sub, causal,
                 window=window)
    return _fwd_call(q, k, v, segment_ids, cols=cols,
                     sm_scale=sm_scale, H=H, Hk=Hk, D=D,
                     causal=causal, block_q=block_q, block_k=block_k,
                     sub=sub, interpret=interpret, window=window)


# The calls below are traced once for each shape and setting and inlined
# wherever they are made: a model's layers call one kernel a dozen times
# or more, and tracing its unrolled body each time was most of what the
# kernels cost a step's lowering.
_CALL_STATICS = ("cols", "sm_scale", "H", "Hk", "D", "causal", "block_q",
                 "block_k", "sub", "interpret", "window")
_QKV = (0, 1, 2)    # q, k, v as column blocks of one fused projection


@functools.partial(jax.jit, static_argnames=_CALL_STATICS, inline=True)
@_pf.trace_timed_call("flash_fwd")
def _fwd_call(q, k, v, segment_ids, *, cols, sm_scale, H, Hk, D, causal,
              block_q, block_k, sub, interpret, window=None):
    b, sq = q.shape[:2]
    sk = k.shape[1]
    HD, HkD = H * D, Hk * D
    cq, ck, cv = cols
    has_seg = segment_ids is not None
    offset = sk - sq
    nk = sk // block_k
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, sub=sub, H=H, Hk=Hk, D=D, offset=offset,
        has_seg=has_seg)
    if window is None:
        grid = (b, sq // block_q, nk)
    else:
        # the grid's k axis holds only the blocks a q block can see
        kernel = functools.partial(kernel, window=window)
        grid = (b, sq // block_q,
                _window_kblocks(block_q, block_k, window, nk))

    def kj(i, j):
        """The k block step (i, j) needs: a step wholly above the
        diagonal does no work, so it names the row's last block that
        runs, which is resident already, and the pipeline fetches
        nothing for it."""
        if not causal:
            return j
        last_q = jnp.maximum(offset + (i + 1) * block_q - 1, 0)
        if window is not None:
            j = j + _first_kblock(i, block_q, block_k, offset, window)
        return jnp.minimum(j, jnp.minimum(_div(last_q, block_k), nk - 1))

    in_specs = [
        pl.BlockSpec((1, block_q, HD), lambda b, i, j: (b, i, cq)),
        pl.BlockSpec((1, block_k, HkD), lambda b, i, j: (b, kj(i, j), ck)),
        pl.BlockSpec((1, block_k, HkD), lambda b, i, j: (b, kj(i, j), cv)),
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
            pltpu.VMEM((block_q, HD), q.dtype),      # the q block, scaled
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

def _bwd_kernel(*refs, sm_scale, causal, block_q, block_k, sub, H, Hk, D,
                offset, has_seg, one_array, dq_whole, window=None,
                n_qblocks=None, shared=0):
    """Single-pass backward: one s/p recompute per block pair feeds dk, dv
    AND this pair's dq contribution (vs. the classic two-kernel split that
    recomputes s/p and the dp dot twice). dq contributions can't accumulate
    in scratch across k blocks (the k-block axis is the outer grid dim).
    Where one K/V block holds the key sequence there is nothing to
    accumulate across (`dq_whole`): dq_ref is the pair's [bq, HD] block
    of dq itself, summed over the walk in float32, scaled and rounded
    once. Otherwise it is the pair's block of dqp [b, n_kblocks, sq, HD],
    a scaled partial the caller sums over the k-block axis in XLA —
    streaming traffic that costs far less than a second full recompute
    pass. A causal pair is walked in key sub-blocks up to the diagonal,
    like the forward's; its dq is then the sum over the visited ones.

    `one_array`: q, k, v are column blocks of one projection, and the
    gradient leaves the same way: the k block's rows of dqkv [b, s,
    3*HD] are one output block, resident over the q steps, dk and dv
    written into their columns at the last one and, when dq is whole
    (then the block holds every row), each step's dq into its rows.

    Under a `window` the grid's q axis holds only the q blocks that can
    see a key of the k block, counted from the first that does; a step
    past the last such block (`n_qblocks`: how many q blocks there are)
    does nothing, and its index maps name the last one's blocks again.
    The partials then have a slot for each k block a q block can see
    (`_window_kblocks`), not for every k block; a slot no pair writes
    keeps the zero it was handed.

    `shared`: as the forward's. The key's second part is a seventh input,
    its gradient one output more (float32, this grid row's heads' sum)
    with an accumulator of its own; dq's block has the layout of q's."""
    refs = list(refs)
    kpe_ref = refs.pop(6) if shared else None
    n_in = 8 if has_seg else 6
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = refs[:6]
    qseg_ref, kseg_ref = refs[6:n_in] or (None, None)
    if window is not None and not dq_whole:
        n_in += 1           # the zeros the partials' slots start as
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    step = qi               # the q step of this k block, for first and last
    if window is not None:
        qi = qi + _first_qblock(ki, block_q, block_k, offset)
        in_sight = qi <= _last_qblock(ki, block_q, block_k, offset, window,
                                      n_qblocks)
    if one_array:
        dqkv_ref, *rest = refs[n_in:]
        HD = H * D
        dk_ref = dqkv_ref.at[0, :, pl.ds(HD, HD)]
        dv_ref = dqkv_ref.at[0, :, pl.ds(2 * HD, HD)]
        if dq_whole:
            dq_ref = dqkv_ref.at[0, pl.ds(pl.multiple_of(
                qi * block_q, block_q), block_q), pl.ds(0, HD)]
        else:
            dq_ref, *rest = rest
    else:
        dq_ref, dk_ref, dv_ref, *rest = refs[n_in:]
        dk_ref, dv_ref = dk_ref.at[0], dv_ref.at[0]
    if shared:
        dkpe_ref, *rest = rest
        *rest, dkpe_acc = rest
    qs_ref, delta_ref, dk_acc, dv_acc, *dq_acc = rest
    if causal:
        dq_acc, = dq_acc    # [bq, HD] f32: the walk's sum of dq

    def _load_q_block():
        """What every visit of the step reads of its q block, made once:
        q scaled, and delta_i = rowsum(do_i * o_i) a head, laid out like
        the log-sums ([H*_SUBL, bq]: a head's q rows in lanes)."""
        qs_ref[:] = _scaled(q_ref[0], sm_scale, qs_ref.dtype)
        dof, of = do_ref[0], o_ref[0]
        lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, _LANES), 1)
        delta_c = jnp.zeros((block_q, _LANES), jnp.float32)
        for (c, w), _, heads in _head_slabs(H, Hk, D):
            prod = jax.lax.mul(_cols(dof, c, w).astype(jnp.float32),
                               _cols(of, c, w).astype(jnp.float32))
            for h, mine in heads:
                mine_prod = (prod if mine is None
                             else _pick(mine, prod, jnp.zeros_like(prod)))
                delta_c = jax.lax.select(
                    jax.lax.eq(lane, jax.lax.full_like(lane, h)),
                    _rows_to_lanes(jax.lax.reduce_sum(mine_prod, (1,)),
                                   _LANES), delta_c)
        _heads_to_rows(delta_ref, delta_c, H)

    def _visit(k0):
        """Keys k0 .. k0 + sub of the resident block against the q block.
        The score tiles are [sub, bq], keys in sublanes and q rows in
        lanes: a q row's log-sum and delta arrive as lane vectors and
        spread over sublanes for nothing, where a [bq, sub] tile would
        move each across lanes once a head and visit."""
        rows = pl.ds(k0, sub)
        qf = qs_ref[:]                       # [bq, HD], scaled
        dof = do_ref[0]
        kf = k_ref[0, rows, :]               # [sub, Hk*D]
        vf = v_ref[0, rows, :]
        if shared:
            kpe = kpe_ref[0, rows, :]        # [sub, LANES]
            dkpe, dq_slabs = None, {}
        ok = (_causal_tile_mask(offset + qi * block_q, ki * block_k + k0,
                                (sub, block_q), q_axis=1, window=window)
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
                qh, kh, qs2 = q1, k2, q2
                if shared:
                    qr, its, at = _shared_q(qf, h, H * D, shared)
                    qh = jax.lax.concatenate([q1, qr], 1)
                    qs2 = jax.lax.concatenate([q2, qr], 1)
                    kh = jax.lax.concatenate([k2, kpe], 1)
                s = _nt_dot(kh, qh)                      # [sub, bq]
                p = jax.lax.exp(jax.lax.sub(
                    s, _over_rows(lse_ref[0, st, :], sub)))
                if ok is not None:
                    p = jax.lax.select(ok, p, zero)
                # dv = p^T @ do, the tile being p^T already
                dv_h = _dot(p.astype(do2.dtype), do2)    # [sub, slab]
                dp = _nt_dot(v2, do1)                    # [sub, bq]
                ds = jax.lax.mul(p, jax.lax.sub(
                    dp, _over_rows(delta_ref[st, :], sub))).astype(
                        q2.dtype)
                dk_h = _dot(ds, qs2)         # dk = ds^T @ q_scaled
                dq_h = _dot(ds, kh, 0)       # this visit's dq: ds @ k
                if shared:
                    # the slab's lanes past the head's own: the shared
                    # keys' gradient, zero off the head's lanes of the
                    # slab as qr is; and the head's second part of dq,
                    # in every R lanes of the slab, its own kept
                    dk_r = _cols(dk_h, w, _LANES)
                    dkpe = dk_r if dkpe is None else jax.lax.add(dkpe, dk_r)
                    dq_r = _cols(dq_h, w, _LANES)
                    dq_slabs[at] = _pick(
                        its, dq_r, dq_slabs.get(at, jnp.zeros_like(dq_r)))
                    dk_h, dq_h = _cols(dk_h, 0, w), _cols(dq_h, 0, w)
                if dv is None:
                    dv, dk, dq = dv_h, dk_h, dq_h
                else:
                    dv = _pick(mine, dv_h, dv)
                    dk = _pick(mine, dk_h, dk)
                    dq = _pick(mine, dq_h, dq)
            sl, slk = slice(c, c + w), slice(ck, ck + wk)
            dv_acc[rows, slk] = jax.lax.add(dv_acc[rows, slk], dv)
            dk_acc[rows, slk] = jax.lax.add(dk_acc[rows, slk], dk)
            if causal:      # summed over the walk in f32, rounded once
                dq_acc[:, sl] = jax.lax.add(dq_acc[:, sl], dq)
            else:
                dq_ref[:, sl] = _scaled(dq, sm_scale, dq_ref.dtype)
        if shared:
            dkpe_acc[rows, :] = jax.lax.add(dkpe_acc[rows, :], dkpe)
            for at, dq in dq_slabs.items():
                sl = slice(at, at + _LANES)
                if causal:
                    dq_acc[:, sl] = jax.lax.add(dq_acc[:, sl], dq)
                else:
                    dq_ref[:, sl] = _scaled(dq, sm_scale, dq_ref.dtype)

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if shared:
            dkpe_acc[:] = jnp.zeros_like(dkpe_acc)

    if window is not None:
        n_visit = _walk_bounds(qi, ki, block_q, block_k, sub, offset)
        lo = _walk_from(qi, ki, block_q, block_k, sub, offset, window)

        @pl.when(in_sight)
        def _run():
            _load_q_block()
            dq_acc[:] = jnp.zeros_like(dq_acc)
            _walk(n_visit, sub, block_k, _visit, lo)
            dq_ref[:] = _scaled(dq_acc[:], sm_scale, dq_ref.dtype)
    elif causal:
        n_visit = _walk_bounds(qi, ki, block_q, block_k, sub, offset)

        # a pair wholly above the diagonal still owns its block of dq
        # (rows that see no key) or of dqp (the XLA-side sum reads it)
        @pl.when(n_visit == 0)
        def _skip():
            dq_ref[:] = jnp.zeros(dq_ref.shape, dq_ref.dtype)

        @pl.when(n_visit > 0)
        def _run():
            _load_q_block()
            dq_acc[:] = jnp.zeros_like(dq_acc)
            _walk(n_visit, sub, block_k, _visit)
            # scaled where it is rounded
            dq_ref[:] = _scaled(dq_acc[:], sm_scale, dq_ref.dtype)
    else:
        _load_q_block()
        _visit(0)

    @pl.when(step == nq - 1)
    def _finalize():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)
        if shared:
            dkpe_ref[0] = dkpe_acc[:]


def _flash_bwd_fused(q, k, v, o, lse, do, H, causal,
                     block_q=256, block_k=None, interpret=False,
                     Hk=None, segment_ids=None, autotune_ok=True,
                     sm_scale=1.0, cols=(0, 0, 0), D=None, window=None):
    """Blockwise dq/dk/dv on the fused-head layout.

    q: [b, sq, H*D]; k,v: [b, sk, Hk*D], each at its column block of
    `cols` as in the forward; o,do: [b, sq, H*D]; lse: [b, H*_SUBL, sq]
    f32. block_k None: the whole key sequence, as far as VMEM holds it.
    Returns (dq [b, sq, H*D], dk, dv [b, sk, Hk*D]) in the operands'
    dtype, dq with sm_scale applied; for one projection's column blocks
    (`cols` (0, 1, 2)) the three side by side as it holds q, k, v:
    dqkv [b, s, 3*H*D]."""
    b, sq = q.shape[:2]
    sk = k.shape[1]
    D = q.shape[2] // H if D is None else D
    Hk = H if Hk is None else Hk
    HD, HkD = H * D, Hk * D
    walk = _WALK if causal else None
    # k-side blocks in VMEM: k, v and dk, dv, or the three-wide block of
    # one gradient array
    n_bufs_k = 5 if cols == _QKV else 4
    if block_k is None:
        block_k = sk
        if autotune_ok and not interpret and window is None \
                and block_q == 256:

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
                bq2, bk2 = _fit_blocks(bq, bk, HD, n_bufs_q=4,
                                       n_bufs_k=n_bufs_k, HDk=HkD, sub=walk,
                                       budget=_VMEM_LIMIT_BWD, k_accs=2)
                return (_pick_block(sq, bq2), _pick_block(sk, bk2))

            block_q, block_k = _autotuned_blocks(
                "bwd", (sq, sk, D, str(q.dtype)), H, Hk, causal,
                segment_ids is not None, (block_q, block_k), run_shape,
                _norm_bwd)
    # long sequences: keep K blocks wide enough that the dq partials
    # (b * nk * sq * HD) stay bounded at nk <= 8 — _fit_blocks may shrink
    # them back if HD is too wide for VMEM, which keeps correctness and
    # trades the extra partials for compile-safety.
    block_k = max(block_k, sk // 8)
    block_q, block_k = _fit_blocks(block_q, block_k, HD, n_bufs_q=4,
                                   n_bufs_k=n_bufs_k, HDk=HkD, sub=walk,
                                   budget=_VMEM_LIMIT_BWD, k_accs=2)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    sub = _sub_block(block_k, walk)
    nk = sk // block_k
    slots = nk if window is None else _window_kblocks(
        block_q, block_k, window, nk)
    _note_causal("bwd", sq, sk, block_q, block_k, sub, causal,
                 f", dq partials {slots}" if nk > 1 else ", dq whole",
                 window=window)
    return _bwd_call(q, k, v, o, lse, do, segment_ids, cols=cols,
                     sm_scale=sm_scale, H=H, Hk=Hk, D=D,
                     causal=causal, block_q=block_q, block_k=block_k,
                     sub=sub, interpret=interpret, window=window)


@functools.partial(jax.jit, static_argnames=_CALL_STATICS, inline=True)
@_pf.trace_timed_call("flash_bwd_transpose")
def _bwd_call(q, k, v, o, lse, do, segment_ids, *, cols, sm_scale, H, Hk, D,
              causal, block_q, block_k, sub, interpret, window=None):
    b, sq = q.shape[:2]
    sk = k.shape[1]
    HD, HkD = H * D, Hk * D
    cq, ck, cv = cols
    offset = sk - sq
    nk, nq = sk // block_k, sq // block_q
    # the grid's q axis and the partials' slots (see the kernel)
    steps, slots = nq, nk
    if window is not None:
        steps = _window_qblocks(block_q, block_k, window, nq)
        slots = _window_kblocks(block_q, block_k, window, nk)

    def qi(j, i):
        """The q block step (j, i) needs: the q blocks wholly above k
        block j's diagonal do no work, so they name the first one that
        does, which the pipeline then fetches once, for all of them.
        Under a window step i is the i-th block from that first one,
        and the steps past the last block in sight name it again."""
        if not causal:
            return i
        first = _div(jnp.maximum(j * block_k - offset, 0), block_q)
        if window is not None:
            return jnp.minimum(first + i, _last_qblock(
                j, block_q, block_k, offset, window, nq))
        return jnp.maximum(i, jnp.minimum(first, nq - 1))

    def q_spec_i(c):
        return pl.BlockSpec((1, block_q, HD),
                            lambda b, j, i: (b, qi(j, i), c))

    def k_spec_j(c):
        return pl.BlockSpec((1, block_k, HkD), lambda b, j, i: (b, j, c))

    stat_i = pl.BlockSpec((1, H * _SUBL, block_q),
                          lambda b, j, i: (b, 0, qi(j, i)))
    one_array = cols == _QKV
    if one_array:
        # the gradient as the projection's backward reads it
        out_specs = [pl.BlockSpec((1, block_k, 3 * HD),
                                  lambda b, j, i: (b, j, 0))]
        out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    else:
        out_specs = [k_spec_j(0), k_spec_j(0)]
        out_shape = [jax.ShapeDtypeStruct((b, sk, HkD), k.dtype),
                     jax.ShapeDtypeStruct((b, sk, HkD), v.dtype)]
    dq_at = 1 if one_array else 0   # dqkv, dqp | dq or dqp, dk, dv
    if nk > 1:
        # partials in the input dtype are only safe while few are
        # summed; past nk=8 (e.g. _fit_blocks shrank block_k for a wide
        # HD) keep them f32 so rounding doesn't scale with nk (ADVICE r2)
        def partial_at(b, j, i):
            if window is None:
                return (b, j, i, 0)
            iq = qi(j, i)
            return (b, j - _first_kblock(iq, block_q, block_k, offset,
                                         window), iq, 0)
        out_specs.insert(dq_at, pl.BlockSpec(
            (None, None, block_q, HD), partial_at))
        out_shape.insert(dq_at, jax.ShapeDtypeStruct(
            (b, slots, sq, HD), q.dtype if slots <= 8 else jnp.float32))
    elif not one_array:
        # one K/V block holds the key sequence: dq leaves finished
        def whole_at(b, j, i):
            return (b, i if window is None else qi(j, i), 0)
        out_specs.insert(0, pl.BlockSpec((None, block_q, HD), whole_at))
        out_shape.insert(0, jax.ShapeDtypeStruct((b, sq, HD), q.dtype))

    has_seg = segment_ids is not None
    in_specs = [q_spec_i(cq), k_spec_j(ck), k_spec_j(cv), q_spec_i(0),
                q_spec_i(0), stat_i]
    operands = [q, k, v, o, do, lse]
    if has_seg:
        # the backward's tiles have keys in rows and q in lanes
        kseg, qseg = _seg_operands(segment_ids[::-1], b, sk, sq)
        in_specs += [
            pl.BlockSpec((1, _SUBL, block_q),
                         lambda b, j, i: (b, 0, qi(j, i))),
            pl.BlockSpec((1, block_k, _LANES), lambda b, j, i: (b, j, 0)),
        ]
        operands += [qseg, kseg]
    kernel = functools.partial(
        _bwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, sub=sub, H=H, Hk=Hk, D=D, offset=offset,
        has_seg=has_seg, one_array=one_array, dq_whole=nk == 1)
    aliases = {}
    if window is not None:
        if one_array:
            raise NotImplementedError(
                "flash attention: no window on one fused projection")
        kernel = functools.partial(kernel, window=window, n_qblocks=nq)
        if nk > 1:
            # a q block sees fewer k blocks than it has slots where the
            # sequence starts: those slots keep these zeros
            aliases = {len(operands): dq_at}
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            operands.append(jnp.zeros(out_shape[dq_at].shape,
                                      out_shape[dq_at].dtype))

    outs = list(pl.pallas_call(
        kernel,
        grid=(b, nk, steps),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        scratch_shapes=[
            pltpu.VMEM((block_q, HD), q.dtype),      # the q block, scaled
            pltpu.VMEM((H * _SUBL, block_q), jnp.float32),   # its delta
            pltpu.VMEM((block_k, HkD), jnp.float32),
            pltpu.VMEM((block_k, HkD), jnp.float32),
        ] + ([pltpu.VMEM((block_q, HD), jnp.float32)] if causal else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BWD),
        interpret=interpret,
        # XLA names the custom call after the innermost scope, which
        # `name=` is. `transpose` (jax's word for the backward pass)
        # stays in it because benchmarks/kernel_costs/flash.py:classify
        # tells the backward kernel from the forward one by that word
        name="flash_bwd_transpose",
    )(*operands))
    if nk > 1:
        # slice by slice: elementwise, so XLA fuses the sum into what
        # reads it (a reduction is a pass of its own)
        outs[dq_at] = functools.reduce(jax.lax.add, (
            outs[dq_at][:, j].astype(jnp.float32) for j in range(slots))
            ).astype(q.dtype)
    if not one_array:
        return tuple(outs)
    if nk > 1:      # the kernel left dq's columns for the partials' sum
        return jax.lax.dynamic_update_slice_in_dim(outs[0], outs[1], 0,
                                                   axis=2)
    return outs[0]


def _pick_block(s, target):
    """Largest block <= target that divides s (s is a multiple of 128)."""
    if s % 128:
        raise ValueError(f"seq {s} must be a multiple of 128")
    blk = min(target, s)
    while s % blk:
        blk -= 128
    return blk


def _fit_blocks(block_q, block_k, HD, n_bufs_q, n_bufs_k, HDk=None,
                budget=None, sub=None, stat_heads=0, k_accs=1):
    """Shrink (block_q, block_k) until the kernel's VMEM appetite fits.

    The dominant consumers scale linearly with the operand widths
    (double-buffered block DMAs + f32 accumulators) and with the
    score-tile transients (block_q*block_k, or block_q*sub under a causal
    walk of `sub`-wide visits), so large-model head widths
    (e.g. HD=4096) must trade block size rather than crash the Pallas
    compile. HDk: k/v-side width (Hk*D) — narrower than HD under GQA/MQA,
    so k-side blocks aren't shrunk for q-side bytes. stat_heads: heads
    whose running statistics the kernel keeps in every lane (the
    forward's; the backward keeps none). k_accs: float32 accumulators of
    a k-side block (the backward's dk and dv). budget: the kernel's
    scoped-VMEM limit (the forward's unless given)."""
    HDk = HD if HDk is None else HDk
    budget = _VMEM_LIMIT if budget is None else budget

    def est(bq, bk):
        io = 2 * (n_bufs_q * bq * HD + n_bufs_k * bk * HDk) * 2  # dbuf DMAs
        acc = (bq * HD + k_accs * bk * HDk) * 4          # f32 accumulators
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


# ============ a key in two parts (multi-head latent attention) ============
#
# A query head's score is q . k + q' . k': k [heads of D] a head's own
# part, k' ONE head of R that every query head reads (the rotary part of a
# latent key). The kernels above take it as the option `shared`; what
# differs around them is here. The grid's first axis runs over (batch,
# group of `_SHARED_GROUP` heads): a grid row holds a group's lanes of q,
# k, v, o and the whole shared head, so the unrolled body is a group's
# and not sixteen heads', the resident key block of the backward is wide
# in keys at a group's width in lanes, and dq's partials are few.

_SHARED_GROUP = 2       # heads a grid row works: R = 64, one 128-lane slab
# q rows and keys a grid step holds. A group's lanes are few, so the
# blocks are long in rows: at 16 heads and 32,768 keys the forward took
# 65.7 / 50.9 / 44.2 / 41.3 ms a call at 256 / 512 / 1024 / 2048 q rows
# of 1024 keys, the backward 167.0 / 104.1 / 99.7 ms at 128 / 256 / 512 q
# rows of 8192 keys (PERF.md, PR 46)
_FWD_SHARED_BLOCKS = (2048, 1024)
_BWD_SHARED_BLOCK_Q = 512


def _shared_rows(operands):
    """(q, q', k, k', v), [b, s, H, D], [b, s, H, R], [b, sk, H, D],
    [b, sk, 1, R], [b, sk, H, D] -> the kernels' rows: q by group, a
    group's own parts then its second parts [b, s, H*(D + R)]; k and v
    [b, sk, H*D]; k' repeated along one slab's lanes [b, sk, 128] (what
    a tile of 64 lanes is padded to in memory anyway)."""
    q, q_pe, k, k_pe, v = operands
    (b, sq, H, D), R, sk = q.shape, q_pe.shape[-1], k.shape[1]
    n = H // _SHARED_GROUP
    q_rows = jnp.concatenate(
        [q.reshape(b, sq, n, -1), q_pe.reshape(b, sq, n, -1)],
        axis=-1).reshape(b, sq, H * (D + R))
    kpe = jnp.tile(k_pe.reshape(b, sk, R), (1, 1, _LANES // R))
    return (q_rows, k.reshape(b, sk, H * D), kpe, v.reshape(b, sk, H * D))


def _shared_grads(dq_rows, dk, dkpe, dv, H, D, R):
    """The kernels' gradients back in the operands' shapes; k''s summed
    over the groups and over the slab's repeats, in float32."""
    b, sq, _ = dq_rows.shape
    sk, n = dk.shape[1], H // _SHARED_GROUP
    dq_rows = dq_rows.reshape(b, sq, n, -1)
    own = _SHARED_GROUP * D
    dk_pe = dkpe.reshape(b, n, sk, _LANES // R, R).sum((1, 3))
    return (dq_rows[..., :own].reshape(b, sq, H, D),
            dq_rows[..., own:].reshape(b, sq, H, R),
            dk.reshape(b, sk, H, D),
            dk_pe.reshape(b, sk, 1, R).astype(dk.dtype),
            dv.reshape(b, sk, H, D))


def _whole_heads(operands):
    """(q, k, v) at whole heads from a key in two parts (the composite's
    operands): q and q' side by side a head, k' beside every head's k."""
    if len(operands) != 5:
        return operands
    q, q_pe, k, k_pe, v = operands
    k_pe = jnp.broadcast_to(k_pe, k.shape[:3] + k_pe.shape[3:])
    return (jnp.concatenate([q, q_pe], -1), jnp.concatenate([k, k_pe], -1),
            v)


def _fit_shared_bwd(sk, block_q, D, R, itemsize):
    """The backward's key block for a key in two parts: all the keys (dq
    then leaves whole), halved until a group's blocks, twice buffered,
    and their float32 accumulators fit: 8192 keys at 32768 of two heads
    of 128, dq in four partials."""
    G = _SHARED_GROUP
    block_k = sk

    def est(bk):
        k_side = bk * (2 * G * D + _LANES) * itemsize        # k, v, k'
        outs = bk * (2 * G * D * itemsize + _LANES * 4)      # dk, dv, dk'
        accs = bk * (2 * G * D + _LANES) * 4
        q_side = block_q * (2 * G * (D + R) + 2 * G * D) * itemsize
        return 2 * (k_side + outs + q_side) + accs
    while est(block_k) > _VMEM_LIMIT_BWD * 0.75 and block_k > 128:
        block_k = _pick_block(sk, block_k // 2)
    return block_k


def _flash_fwd_shared(q, k, kpe, v, H, D, R, causal, sm_scale,
                      interpret=False, blocks=None):
    """The forward on `_shared_rows`' rows -> (o [b, s, H*D], lse)."""
    sq, sk = q.shape[1], k.shape[1]
    block_q, block_k = blocks or _FWD_SHARED_BLOCKS
    block_q, block_k = _pick_block(sq, block_q), _pick_block(sk, block_k)
    sub = _sub_block(block_k, _WALK if causal else None)
    _note_causal("fwd", sq, sk, block_q, block_k, sub, causal,
                 f", key in two parts ({D} a head + {R} shared)")
    return _fwd_call_shared(q, k, v, kpe, sm_scale=sm_scale, H=H, D=D, R=R,
                            causal=causal, block_q=block_q, block_k=block_k,
                            sub=sub, interpret=interpret)


def _flash_bwd_shared(q, k, kpe, v, o, lse, do, H, D, R, causal, sm_scale,
                      interpret=False, blocks=None):
    """The backward on `_shared_rows`' rows -> (dq in q's layout, dk, dk'
    [b * groups, sk, 128] float32, dv)."""
    sq, sk = q.shape[1], k.shape[1]
    block_q, block_k = blocks or (
        _BWD_SHARED_BLOCK_Q,
        _fit_shared_bwd(sk, _BWD_SHARED_BLOCK_Q, D, R, q.dtype.itemsize))
    block_q, block_k = _pick_block(sq, block_q), _pick_block(sk, block_k)
    sub = _sub_block(block_k, _WALK if causal else None)
    nk = sk // block_k
    _note_causal("bwd", sq, sk, block_q, block_k, sub, causal,
                 (f", dq partials {nk}" if nk > 1 else ", dq whole")
                 + ", key in two parts")
    return _bwd_call_shared(q, k, v, kpe, o, lse, do, sm_scale=sm_scale,
                            H=H, D=D, R=R, causal=causal, block_q=block_q,
                            block_k=block_k, sub=sub, interpret=interpret)


_SHARED_STATICS = ("sm_scale", "H", "D", "R", "causal", "block_q",
                   "block_k", "sub", "interpret")


def _group_of(n):
    """A grid row g of b * n -> (its batch row, its group of heads)."""
    return lambda g: (_div(g, n), jax.lax.rem(g, jnp.int32(n)))


@functools.partial(jax.jit, static_argnames=_SHARED_STATICS, inline=True)
@_pf.trace_timed_call("flash_mla_fwd")
def _fwd_call_shared(q, k, v, kpe, *, sm_scale, H, D, R, causal, block_q,
                     block_k, sub, interpret):
    b, sq = q.shape[:2]
    sk = k.shape[1]
    G, n = _SHARED_GROUP, H // _SHARED_GROUP
    GD, GQ = G * D, G * (D + R)
    offset, nk = sk - sq, sk // block_k
    at = _group_of(n)

    def kj(i, j):       # as `_fwd_call`'s: nothing fetched above the diagonal
        if not causal:
            return j
        last_q = jnp.maximum(offset + (i + 1) * block_q - 1, 0)
        return jnp.minimum(j, jnp.minimum(_div(last_q, block_k), nk - 1))

    def q_at(g, i, j):
        return (at(g)[0], i, at(g)[1])

    def k_at(g, i, j):
        return (at(g)[0], kj(i, j), at(g)[1])

    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
            block_k=block_k, sub=sub, H=G, Hk=G, D=D, offset=offset,
            has_seg=False, shared=R),
        grid=(b * n, sq // block_q, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, GQ), q_at),
            pl.BlockSpec((1, block_k, GD), k_at),
            pl.BlockSpec((1, block_k, GD), k_at),
            pl.BlockSpec((1, block_k, _LANES),
                         lambda g, i, j: (at(g)[0], kj(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, GD), q_at),
            pl.BlockSpec((1, G * _SUBL, block_q),
                         lambda g, i, j: (at(g)[0], at(g)[1], i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, H * D), q.dtype),
            jax.ShapeDtypeStruct((b, H * _SUBL, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, GQ), q.dtype),      # the q block, scaled
            pltpu.VMEM((block_q, GD), jnp.float32),
            pltpu.VMEM((G, block_q, _LANES), jnp.float32),
            pltpu.VMEM((G, block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flash_mla_fwd",
    )(q, k, v, kpe)


@functools.partial(jax.jit, static_argnames=_SHARED_STATICS, inline=True)
@_pf.trace_timed_call("flash_mla_bwd_transpose")
def _bwd_call_shared(q, k, v, kpe, o, lse, do, *, sm_scale, H, D, R, causal,
                     block_q, block_k, sub, interpret):
    b, sq = q.shape[:2]
    sk = k.shape[1]
    G, n = _SHARED_GROUP, H // _SHARED_GROUP
    GD, GQ = G * D, G * (D + R)
    offset = sk - sq
    nk, nq = sk // block_k, sq // block_q
    at = _group_of(n)

    def qi(j, i):       # as `_bwd_call`'s: the first q block that does work
        if not causal:
            return i
        first = _div(jnp.maximum(j * block_k - offset, 0), block_q)
        return jnp.maximum(i, jnp.minimum(first, nq - 1))

    def q_spec(width):
        return pl.BlockSpec((1, block_q, width), lambda g, j, i: (
            at(g)[0], qi(j, i), at(g)[1]))

    def k_spec(width):
        return pl.BlockSpec((1, block_k, width),
                            lambda g, j, i: (at(g)[0], j, at(g)[1]))

    if nk > 1:          # partials, summed over the k blocks below
        dq_spec = pl.BlockSpec((None, None, block_q, GQ), lambda g, j, i: (
            at(g)[0], j, i, at(g)[1]))
        dq_shape = jax.ShapeDtypeStruct(
            (b, nk, sq, n * GQ), q.dtype if nk <= 8 else jnp.float32)
    else:
        dq_spec = pl.BlockSpec((None, block_q, GQ), lambda g, j, i: (
            at(g)[0], i, at(g)[1]))
        dq_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    dq, dk, dv, dkpe = pl.pallas_call(
        functools.partial(
            _bwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
            block_k=block_k, sub=sub, H=G, Hk=G, D=D, offset=offset,
            has_seg=False, one_array=False, dq_whole=nk == 1, shared=R),
        grid=(b * n, nk, nq),
        in_specs=[
            q_spec(GQ), k_spec(GD), k_spec(GD), q_spec(GD), q_spec(GD),
            pl.BlockSpec((1, G * _SUBL, block_q), lambda g, j, i: (
                at(g)[0], at(g)[1], qi(j, i))),
            pl.BlockSpec((1, block_k, _LANES),
                         lambda g, j, i: (at(g)[0], j, 0)),
        ],
        out_specs=[
            dq_spec, k_spec(GD), k_spec(GD),
            pl.BlockSpec((1, block_k, _LANES), lambda g, j, i: (g, j, 0)),
        ],
        out_shape=[
            dq_shape,
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((b * n, sk, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, GQ), q.dtype),      # the q block, scaled
            pltpu.VMEM((G * _SUBL, block_q), jnp.float32),   # its delta
            pltpu.VMEM((block_k, GD), jnp.float32),
            pltpu.VMEM((block_k, GD), jnp.float32),
        ] + ([pltpu.VMEM((block_q, GQ), jnp.float32)] if causal else [])
        + [pltpu.VMEM((block_k, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BWD),
        interpret=interpret,
        name="flash_mla_bwd_transpose",
    )(q, k, v, o, do, lse, kpe)
    if nk > 1:
        dq = functools.reduce(jax.lax.add, (
            dq[:, j].astype(jnp.float32) for j in range(nk))).astype(q.dtype)
    return dq, dk, dkpe, dv


# ======================= dispatch =======================

def _xla_attention(q, k, v, attn_mask, causal, sm_scale, segment_ids=None,
                   window=None):
    """Reference composite ([b,s,h,d] in/out) — the non-Pallas fallback.
    Handles GQA (kv heads dividing q heads), bottom-right-aligned causal
    masking for sq != sk (FA2 semantics), a causal window (a row sees
    its own position and the window - 1 before it), and segment-id
    masking."""
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
        if window is not None:
            s = jnp.where(qpos - kpos < window, s, neg)
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


#: The forward kernel's two outputs, by the names a `jax.checkpoint`
#: policy can ask for (`recompute(..., policy="flash_outputs")`): a
#: recomputed block that keeps both has nothing left to run the forward
#: kernel for, since q, k, v, o and lse are all its backward reads. An
#: identity where no policy names them.
FLASH_O, FLASH_LSE = "flash_o", "flash_lse"


def _kept(o, lse):
    return checkpoint_name(o, FLASH_O), checkpoint_name(lse, FLASH_LSE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _flash_core(operands, segment_ids, causal, sm_scale, use_pallas,
                window=None, heads=None):
    """`operands`: (q, k, v), each [b, s, h, d], k and v maybe on fewer
    (kv) heads (GQA/MQA), and [b, s, h, d] out; or (q, q', k, k', v), a
    key in two parts: q, k, v [b, s, h, d], q' [b, s, h, r] and k'
    [b, s, 1, r], ONE head that every query head reads, a score
    q . k + q' . k' (`_shared_rows`); or (qkv,), one fused
    projection [b, s, 3*h*d] of `heads` heads (the Pallas path only),
    [b, s, h*d] out, whose gradient leaves as the one [b, s, 3*h*d]
    array the projection's backward reads. segment_ids: None or (q_seg
    [b, sq], kv_seg [b, sk]) int32. On the Pallas path the kernels read
    q, k and v as [b, s, h*d] rows (three arrays at column block 0 each,
    or the projection's column blocks 0, 1, 2), scale q themselves and
    return dq scaled."""
    return _flash_core_fwd(operands, segment_ids, causal, sm_scale,
                           use_pallas, window, heads)[0]


def _flash_core_fwd(operands, segment_ids, causal, sm_scale, use_pallas,
                    window=None, heads=None):
    if not use_pallas:
        out = _xla_attention(*_whole_heads(operands), None, causal,
                             sm_scale, segment_ids=segment_ids,
                             window=window)
        return out, (operands, None, None, segment_ids)
    if len(operands) == 5:      # a key in two parts
        rows = _shared_rows(operands)
        (_, _, h, d), r = operands[0].shape, operands[1].shape[-1]
        o, lse = _kept(*_flash_fwd_shared(*rows, h, d, r, causal, sm_scale))
        return o.reshape(operands[0].shape), (rows, o, lse, segment_ids)
    if len(operands) == 1:
        rows, cols, h, hk = operands, _QKV, heads, heads
        d = rows[0].shape[2] // (3 * h)
    else:
        (b, _, h, d), hk = operands[0].shape, operands[1].shape[2]
        rows, cols = tuple(x.reshape(b, x.shape[1], -1)
                           for x in operands), (0, 0, 0)
    # one array stands for q, k and v: the kernels take it three times
    o, lse = _kept(*_flash_fwd_fused(
        *rows * (3 // len(rows)), h, causal, Hk=hk, segment_ids=segment_ids,
        sm_scale=sm_scale, cols=cols, D=d, window=window))
    out = o if len(rows) == 1 else o.reshape(operands[0].shape)
    return out, (rows, o, lse, segment_ids)


def _flash_core_bwd(causal, sm_scale, use_pallas, window, heads, res, g):
    rows, o, lse, segment_ids = res
    if not use_pallas:
        _, vjp = jax.vjp(
            lambda *x: _xla_attention(*_whole_heads(x), None, causal,
                                      sm_scale, segment_ids=segment_ids,
                                      window=window), *rows)
        return vjp(g), None
    if len(rows) == 4:          # `_shared_rows`
        h, d = g.shape[2:]
        r = rows[0].shape[2] // h - d
        return _shared_grads(*_flash_bwd_shared(
            *rows, o, lse, g.reshape(o.shape), h, d, r, causal, sm_scale),
            h, d, r), None
    if len(rows) == 1:
        cols, h, hk, d = _QKV, heads, heads, g.shape[2] // heads
    else:
        cols, (h, d) = (0, 0, 0), g.shape[2:]
        hk = rows[1].shape[2] // d
    grads = _flash_bwd_fused(
        *rows * (3 // len(rows)), o, lse, g.reshape(o.shape), h, causal,
        Hk=hk, segment_ids=segment_ids, sm_scale=sm_scale, cols=cols, D=d,
        window=window)
    if len(rows) == 1:
        return (grads,), None       # the one [b, s, 3*h*d] array
    return tuple(x.reshape(*x.shape[:2], n, d)
                 for x, n in zip(grads, (h, hk, hk))), None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _shapes_ok(q_shape, k_shape, shared=None, v_shape=None):
    return not _shape_reject_reason(q_shape, k_shape, shared, v_shape)


def _shape_reject_reason(q_shape, k_shape, shared=None, v_shape=None):
    """None if the Pallas kernel applies, else a human-readable reason.
    What it admits: heads of 64, 128 or 256, q, k and v alike; or, with
    `shared` (the shapes of q' and k', a key in two parts), a head part
    of one of those sizes on as many key heads as query heads, an even
    number of them, a shared part of ONE head of 64 and a value
    (`v_shape`) of the head part's size."""
    sq, sk, h, d = q_shape[1], k_shape[1], q_shape[2], q_shape[-1]
    hk = k_shape[2]
    if shared is not None:
        (*_, r), (_, _, hr, rk) = shared
        if (r, rk, hr) != (64, 64, 1):
            return (f"shared key part: {hr} head(s) of {rk} for a query "
                    f"part of {r}; the kernel takes one shared head of 64")
        if v_shape is not None and v_shape[-1] != d:
            return (f"value part {v_shape[-1]} is not the head part's "
                    f"size {d}")
        if hk != h or h % _SHARED_GROUP:
            return (f"a key in two parts needs its head part on every "
                    f"query head and an even number of them, not "
                    f"{hk} for {h}")
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


def attention_path(q_shape, k_shape, masked=False, shared=None,
                   v_shape=None):
    """('pallas'|'xla', reason) — which implementation flash_attention will
    take for these shapes and why. Lets callers (chip_smoke.py checks it;
    nn.functional.flash_attention warns on fallback) see when the Pallas
    kernel disengages. masked=True means a dense attn_mask (XLA
    composite); segment-id masking stays on the Pallas path and needs no
    flag. `shared` = (q' shape, k' shape) and `v_shape`: a key in two
    parts, as `_shape_reject_reason` takes them."""
    if masked:
        return ("xla", "dense attn_mask forces the XLA composite — use "
                "segment_ids or causal for the Pallas path")
    if not _pallas_available():
        return ("xla", f"no TPU Pallas backend ({jax.default_backend()})")
    if shared is not None and current_mesh_plan() is not None:
        return ("xla", "a key in two parts under a mesh_plan takes the "
                "composite (its kernels are not split over a mesh)")
    reason = _shape_reject_reason(q_shape, k_shape, shared, v_shape)
    if reason:
        return ("xla", reason)
    return ("pallas", "")


def _planned_specs(plan, q_shape, k_shape):
    """PartitionSpecs (q/k/v/out [b, s, h, d], segment ids [b, s]) that
    split the kernel over the plan's mesh."""
    from jax.sharding import PartitionSpec as P
    mesh, batch_axes = plan[:2]
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


def _scale(softmax_scale, d):
    return float(1.0 / np.sqrt(d) if softmax_scale is None
                 else softmax_scale)


def _int32_pair(segment_ids):
    return segment_ids and (jnp.asarray(segment_ids[0], jnp.int32),
                            jnp.asarray(segment_ids[1], jnp.int32))


def flash_attention(q, k, v, attn_mask=None, causal=False,
                    softmax_scale=None, segment_ids=None, window=None,
                    shared=None):
    """[b, s, h, d] in and out; k/v may have fewer heads (GQA/MQA).

    shared: None, or (q' [b, s, h, r], k' [b, sk, 1, r]), the second part
    of a key in two parts: head j's score is q_j . k_j + q'_j . k', k'
    ONE head that every query head reads (multi-head latent attention's
    rotary part), scaled by 1 / sqrt(d + r) unless `softmax_scale` says
    otherwise. The kernels take the two products as they are: no copy of
    k' a head and no head padded to a lane multiple is written, and k''s
    gradient is summed over the heads where it is made. Without a
    window, segment ids, a mask or a `mesh_plan`.

    window: None, or how many keys a row sees, its own position and the
    window - 1 before it (causal only). A static argument: None traces
    the kernels as they are without one; with a window the kernels
    neither fetch nor visit the key blocks wholly before it.

    segment_ids: (q_seg [b, sq], kv_seg [b, sk]) int32 — attention is
    masked to equal ids (padding / packed-varlen, stays on the Pallas
    path). A dense attn_mask forces the XLA composite.
    Causal masking is bottom-right aligned when sq != sk (FA2 semantics,
    ref: python/paddle/nn/functional/flash_attention.py:146 routing to the
    FlashAttention-2 library)."""
    if shared is not None:
        if (attn_mask is not None or segment_ids is not None
                or window is not None):
            raise NotImplementedError(
                "flash_attention: a key in two parts takes no mask, "
                "segment ids or window")
        return _flash_attention_shared(q, k, v, shared, causal,
                                       softmax_scale)
    sm_scale = _scale(softmax_scale, q.shape[-1])
    if window is not None and not causal:
        raise ValueError("flash_attention: a window needs causal=True")
    if attn_mask is not None:
        return _xla_attention(q, k, v, attn_mask, causal, sm_scale,
                              segment_ids=segment_ids, window=window)
    use_pallas = bool(_pallas_available() and _shapes_ok(q.shape, k.shape))
    segment_ids = _int32_pair(segment_ids)
    window = window if window is None else int(window)
    if use_pallas:
        _pf.trace_note("flash_operands", "split")
    plan = current_mesh_plan()
    if plan is not None and use_pallas:
        spec, seg_spec = _planned_specs(plan, q.shape, k.shape)
        return jax.shard_map(
            lambda q, k, v, seg: _flash_core((q, k, v), seg, causal,
                                             sm_scale, True, window),
            mesh=plan[0],
            in_specs=(spec, spec, spec,
                      None if segment_ids is None else (seg_spec,) * 2),
            out_specs=spec, check_vma=False)(q, k, v, segment_ids)
    return _flash_core((q, k, v), segment_ids, causal, sm_scale, use_pallas,
                       window)


def _flash_attention_shared(q, k, v, shared, causal, softmax_scale):
    """`flash_attention` with a key in two parts."""
    q_pe, k_pe = shared
    sm_scale = _scale(softmax_scale, q.shape[-1] + q_pe.shape[-1])
    use_pallas = attention_path(
        q.shape, k.shape, shared=(q_pe.shape, k_pe.shape),
        v_shape=v.shape)[0] == "pallas"
    if use_pallas:
        _pf.trace_note("flash_operands", "key in two parts")
    return _flash_core((q, q_pe, k, k_pe, v), None, causal, sm_scale,
                       use_pallas)


def flash_attention_qkv(qkv, num_heads, causal=False, softmax_scale=None,
                        segment_ids=None):
    """Attention on a fused projection's output: qkv [b, s, 3*h*d] (q, k
    and v side by side in the last axis, heads of each side by side) in,
    [b, s, h*d] out, `flash_attention`'s other arguments as there.

    On the Pallas path the kernels read the three where they lie and
    return one gradient in the same layout. Everything else (no TPU, a
    shape the kernels reject, a `mesh_plan`: a [b, s, 3*h*d] array split
    over heads is not head-contiguous) splits qkv into [b, s, h, d] views
    and takes `flash_attention`."""
    b, s, w = qkv.shape
    hd = w // 3
    shape = (b, s, num_heads, hd // num_heads)
    if (attention_path(shape, shape)[0] == "pallas"
            and current_mesh_plan() is None):
        _pf.trace_note("flash_operands", "qkv in place")
        return _flash_core((qkv,), _int32_pair(segment_ids), causal,
                           _scale(softmax_scale, shape[3]), True, None,
                           num_heads)
    q, k, v = (qkv[:, :, i * hd:(i + 1) * hd].reshape(shape)
               for i in range(3))
    return flash_attention(q, k, v, causal=causal,
                           softmax_scale=softmax_scale,
                           segment_ids=segment_ids).reshape(b, s, hd)
