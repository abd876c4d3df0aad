"""Ragged paged attention: one kernel family for mixed prefill /
decode / verify rows (PAPERS.md: "Ragged Paged Attention ... for TPU").

The op takes rows of arbitrary per-row lengths — a fresh prompt's
uncached suffix, a speculative verify window, down to a single decode
token — packed into ONE [total_tokens] stream with per-token metadata,
and computes attention for all of them in one launch. (The serving
engine packs its prefill / prefix-resume / verify waves this way;
steady-state decode stays on the chunked scan, whose side-buffer
staging amortizes pool writes across a whole chunk of steps.)

  * each packed query token attends to (a) its row's already-cached
    context read straight from the token-major paged KV pool through
    the per-row block-ownership map, and (b) the packed fresh k/v of
    its OWN row at positions <= its own (causal within the row);
  * rows are arbitrary lengths — the executable is shaped only by the
    total-token bucket, so a 100-token prefill and three 8-token
    verify windows share one compiled program instead of one bucketed
    executable per (kind, length) pair;
  * fp (bf16/f32) and int8 pools (per-kv-head dequant scales fold into
    the score/output tensors, the pool streams in int8);
  * GQA/MQA: packed k/v carry kv_heads <= heads.

Two implementations behind one dispatcher:

  * a pure-jnp reference path — the CPU tier-1 / oracle path, and the
    float-op-structure twin of the engine's previous prefix-resume
    executable so greedy outputs stay bit-identical with the dense
    `generate()` oracle on CPU;
  * a Pallas TPU kernel — flash-style online softmax; K/V stream from
    HBM in page-granularity tiles while the [T, T_pool] score matrix
    never materializes. Per-row ownership masks are rebuilt IN-KERNEL
    from a compact [T, num_blocks] per-token page-offset operand (no
    [T, T_pool] mask array ever touches HBM) and the packed-vs-packed
    causal/row mask streams as replicated row/pos id tiles (the same
    layout trick as flash_attention's segment ids). Block sizes are
    autotuned per (shape-class, device) via kernels.pallas.autotune.

Known cost (accepted for now): the packed phase visits every packed
kv tile for every q tile — cross-row tiles are fully masked, not
skipped — so a launch pays O(T^2) packed-phase scores across rows
(the jnp reference additionally materializes the [H, T, T] masked
score array, which is fine at oracle/test shapes but rules it out as
a serving path at large T). Serving waves keep T small (verify is
pinned at B*(k+1); prefill suffixes are shortened by prefix caching);
per-tile row-range skipping via scalar prefetch is the known
follow-up if profile shows the masked tiles mattering.

Layout contract: q [T, H, D]; k_new/v_new [T, Hk, D]; pools
[T_pool, Hk, D] token-major (block b's slot s at row b*block_size+s —
PagedKVCache layout="token"); rows [T] int32 (-1 = dead padding);
pos [T] int32 absolute positions; kv_start [B] int32 tokens already
in the pool per row; off [B, NB] int32 block -> start position in the
row's sequence, -1 when not owned. Output [T, H, D] float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _pallas_available

_NEG_INF = -1e30
_LANES = 128
_SUBL = 8
_VMEM_LIMIT = 64 * 1024 * 1024


# ---------------------------------------------------------------------------
# reference path (CPU tier-1 + oracle; also the TPU fallback)
# ---------------------------------------------------------------------------
def _masks_reference(rows, pos, kv_start, off, block_size, with_pool):
    """(pool_ok [T, T_pool] | None, pack_ok [T, T]) bool validity masks
    from the packed metadata — the same ownership/causality the
    engine's per-(kind, bucket) executables used to compute."""
    T = rows.shape[0]
    B, NB = off.shape
    live = rows >= 0
    rc = jnp.clip(rows, 0, B - 1)
    pool_ok = None
    if with_pool:
        toff = jnp.repeat(off, block_size, axis=1)        # [B, T_pool]
        gpos = toff + jnp.tile(
            jnp.arange(block_size, dtype=jnp.int32), NB)[None, :]
        ok_rows = (toff >= 0) & (gpos < kv_start[:, None])
        pool_ok = ok_rows[rc] & live[:, None]             # [T, T_pool]
    pack_ok = (rows[None, :] == rows[:, None]) \
        & (pos[None, :] <= pos[:, None]) \
        & live[:, None] & live[None, :]                   # [T, T]
    return pool_ok, pack_ok


def _ragged_reference(q, k_new, v_new, kpool, vpool, rows, pos,
                      kv_start, off, block_size, scale,
                      kdq=None, vdq=None, with_pool=True):
    """Masked dense ragged attention, float-op-structure-identical to
    the engine's previous prefix-resume/verify executables (score
    scaling, dtype casts, [pool, packed] concat order, softmax
    nan-guard) so greedy CPU outputs stay bit-identical with the dense
    oracle. Returns [T, H, D] float32."""
    T, H, D = q.shape
    Hk = k_new.shape[1]
    rep = H // Hk
    pool_ok, pack_ok = _masks_reference(rows, pos, kv_start, off,
                                        block_size, with_pool)
    qs = q.astype(jnp.float32) * scale                     # [T, H, D]
    # packed-vs-packed: own-row causal self-attention (k/v still in
    # registers — the legacy prefill's in-register suffix math)
    kr = jnp.repeat(k_new, rep, axis=1) if rep > 1 else k_new
    vr = jnp.repeat(v_new, rep, axis=1) if rep > 1 else v_new
    ss = jnp.einsum("qhd,khd->hqk", qs.astype(q.dtype), kr,
                    preferred_element_type=jnp.float32)    # [H, T, T]
    ss = jnp.where(pack_ok[None, :, :], ss, -jnp.inf)
    if with_pool:
        cdtype = kpool.dtype
        T_pool = kpool.shape[0]
        q4 = qs.reshape(T, Hk, rep, D)
        if cdtype == jnp.int8:
            # int8 pools: correctness-first upcast (the capacity win is
            # the point); per-kv-head dequant folds into the scores
            qop, kp = q4, kpool.astype(jnp.float32)
        else:
            qop, kp = q4.astype(cdtype), kpool
        sp = jnp.einsum("qkrd,tkd->krqt", qop, kp,
                        preferred_element_type=jnp.float32)
        if kdq is not None:
            sp = sp * kdq[:, None, None, None]
        sp = sp.reshape(H, T, T_pool)
        sp = jnp.where(pool_ok[None, :, :], sp, -jnp.inf)
        s = jnp.concatenate([sp, ss], axis=-1)
    else:
        T_pool = 0
        s = ss
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)                    # dead rows
    pp, psf = p[..., :T_pool], p[..., T_pool:]
    if with_pool:
        pp = pp.reshape(Hk, rep, T, T_pool)
        if cdtype == jnp.int8:
            vp, ppo = vpool.astype(jnp.float32), pp
        else:
            vp, ppo = vpool, pp.astype(cdtype)
        o = jnp.einsum("krqt,tkd->qkrd", ppo, vp,
                       preferred_element_type=jnp.float32)
        if vdq is not None:
            o = o * vdq[None, :, None, None]
        o = o.reshape(T, H, D)
    else:
        o = jnp.zeros((T, H, D), jnp.float32)
    o = o + jnp.einsum("hqk,khd->qhd", psf.astype(vr.dtype), vr,
                       preferred_element_type=jnp.float32)
    return o


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------
def _ragged_kernel(voff_ref, qrow_ref, qpos_ref, krow_ref, kpos_ref,
                   dq_ref, q_ref, kp_ref, vp_ref, kn_ref, vn_ref,
                   o_ref, acc_ref, m_ref, l_ref,
                   *, H, Hk, D, bq, bkp, bkn, nkp, nkn, bs, tpg,
                   int8_pool):
    """One (q-tile, kv-tile) program of the online-softmax sweep. The
    kv axis is [pool tiles..., packed tiles...]: programs j < nkp read
    the paged pool (validity from the per-token page-offset operand),
    later programs read the packed fresh k/v (validity from the
    row/pos id tiles). Scratch (acc, m, l) carries the running
    softmax state across the whole kv axis; the output block is
    finalized on the last program."""
    j = pl.program_id(1)
    G = H // Hk

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _online(kf, vf, ok, dequant):
        qf = q_ref[:]                                  # [bq, H*D]
        for h in range(H):
            sl = slice(h * D, (h + 1) * D)
            slk = slice((h // G) * D, (h // G) * D + D)
            s = jax.lax.dot_general(
                qf[:, sl].astype(kf.dtype), kf[:, slk],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)    # [bq, bk]
            if dequant:
                s = s * dq_ref[0, h // G]
            s = jnp.where(ok, s, _NEG_INF)
            m_prev = m_ref[:, h:h + 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1,
                                                keepdims=True))
            p = jnp.exp(s - m_new)
            p = jnp.where(ok, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[:, h:h + 1] = alpha * l_ref[:, h:h + 1] + jnp.sum(
                p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(vf.dtype), vf[:, slk], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if dequant:
                pv = pv * dq_ref[1, h // G]
            acc_ref[:, sl] = acc_ref[:, sl] * alpha + pv
            m_ref[:, h:h + 1] = m_new

    if nkp:     # statically absent when the launch reads no pool
        @pl.when(j < nkp)
        def _pool_phase():
            # ownership mask rebuilt in-kernel. voff_ref is the lane
            # group (selected by the BlockSpec from j) that holds this
            # tile's per-(q-token, page) valid-slot counts at lanes
            # [o, o + bkp//bs). A one-hot [LANES, bkp] page->column
            # expansion on the MXU spreads each count over its page's
            # bs columns — Mosaic has no dynamic lane slice, and the
            # counts (<= bs <= 256) and the 0/1 selector are exact at
            # any matmul precision.
            kf = kp_ref[:]
            vf = vp_ref[:]
            if int8_pool:
                kf = kf.astype(jnp.float32)
                vf = vf.astype(jnp.float32)
            o = (j % tpg) * (bkp // bs)
            page = jax.lax.broadcasted_iota(
                jnp.int32, (_LANES, bkp), 0) - o
            col = jax.lax.broadcasted_iota(jnp.int32, (_LANES, bkp), 1)
            sel = (col >= page * bs) & (col < (page + 1) * bs)
            vc = jax.lax.dot_general(
                voff_ref[:], sel.astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # [bq, bkp]
            base = jnp.sum(
                jnp.where(sel, (page * bs).astype(jnp.float32), 0.0),
                axis=0, keepdims=True)                  # [1, bkp]
            slot = jax.lax.broadcasted_iota(
                jnp.int32, (1, bkp), 1).astype(jnp.float32) - base
            ok = slot < vc                              # [bq, bkp]
            _online(kf, vf, ok, int8_pool)

    @pl.when(j >= nkp)
    def _packed_phase():
        # row-equality + causal-position mask from the replicated id
        # tiles (the segment-ids layout: q ids [bq, LANES], kv ids
        # [SUBL, bkn] — no in-kernel transposes)
        if bkn >= _LANES:
            qr = jnp.tile(qrow_ref[:], (1, bkn // _LANES))  # [bq, bkn]
            qp = jnp.tile(qpos_ref[:], (1, bkn // _LANES))
        else:
            qr = qrow_ref[:, :bkn]
            qp = qpos_ref[:, :bkn]
        kr = krow_ref[:1, :]                           # [1, bkn]
        kp = kpos_ref[:1, :]
        ok = (qr == kr) & (kp <= qp) & (qr >= 0) & (kr >= 0)
        _online(kn_ref[:], vn_ref[:], ok, False)

    @pl.when(j == nkp + nkn - 1)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        acc = acc_ref[:]
        for h in range(H):
            sl = slice(h * D, (h + 1) * D)
            o_ref[:, sl] = jnp.where(
                l[:, h:h + 1] == 0.0, 0.0,
                acc[:, sl] / safe_l[:, h:h + 1])


def _pick_div(n, target, quantum):
    """Largest multiple of `quantum` <= target that divides n (or None)."""
    b = min(target, n)
    b -= b % quantum
    while b >= quantum:
        if n % b == 0:
            return b
        b -= quantum
    return None


def _autotuned_ragged_blocks(T, T_pool, H, Hk, D, dtype, int8_pool, bs,
                             defaults, run_shape, normalize):
    """Per-(shape-class, device) {block_q, block_k} search through the
    shared autotune cache — the hand-tuned defaults are always in the
    candidate set, so tuned can only tie or beat them."""
    from . import autotune
    if not autotune.enabled():
        return defaults
    key = ("ragged", T, T_pool, H, Hk, D, str(dtype), int(int8_pool), bs)
    hit = autotune.lookup(key)
    if hit is not None:
        return hit
    if jax.process_count() > 1:
        # multi-host SPMD needs identical programs on every host
        return defaults
    cands = [defaults] + [c for c in [(128, 512), (256, 1024), (512, 512)]
                          if c != defaults]
    # dedup candidates that collapse to one effective block config
    # after the divisibility clamps the use site applies (shared
    # helper; keep the RAW candidates — the runner re-applies clamps)
    keep = autotune.dedup_candidates(cands, normalize,
                                     keep_original=True)
    if len(keep) == 1:
        return keep[0]
    runners: dict = {}

    def _runner(c):
        # build (host RNG + device transfer of the dummy operands) once
        # per candidate, not once per timing call
        if c not in runners:
            runners[c] = run_shape(*c)
        return runners[c]

    return autotune.tune(
        key, keep, lambda c: autotune._time_call(_runner(c)))


def _ragged_pallas(q, k_new, v_new, kpool, vpool, rows, pos, kv_start,
                   off, block_size, scale, kdq=None, vdq=None,
                   with_pool=True, interpret=False, block_q=256,
                   block_k=512, autotune_ok=True):
    """Pallas path. Operand prep (all cheap [T]-sized int work in XLA):
      voff [T, lane groups * LANES] f32: per packed token, per page:
        how many leading slots of that page are valid context for the
        token's row (min(kv_start[row] - page_start, bs), clipped to
        [0, bs]), laid out so each pool tile's pages sit inside one
        LANES-wide group the BlockSpec can select;
      row/pos replicated id tiles for the packed phase;
      dq [2, Hk] f32 in SMEM: per-kv-head k/v dequant scales (ones
        when the pool is fp)."""
    T, H, D = q.shape
    Hk = k_new.shape[1]
    B, NB = off.shape
    bs = block_size
    int8_pool = bool(with_pool) and kpool.dtype == jnp.int8
    if with_pool:
        T_pool = kpool.shape[0]
        if NB * bs != T_pool:
            raise ValueError(
                f"off maps {NB} blocks of {bs} but the pool holds "
                f"{T_pool} tokens")
    else:
        # tiny dummy pool keeps one kernel shape: nkp=0 drops the phase
        T_pool = 0
        kpool = jnp.zeros((_SUBL, Hk, D), q.dtype)
        vpool = kpool

    def _eff(bq, bk):
        """Effective (block_q, block_kn, block_kp) after divisibility
        clamps — the dedup key for the autotune candidate set."""
        ebq = _pick_div(T, bq, min(T, _SUBL)) or T
        ekn = (_pick_div(T, bk, _LANES) or T) if T >= _LANES else T
        # a pool tile's pages must fit one lane group of voff
        ekp = (_pick_div(T_pool, min(max(bk, bs), _LANES * bs), bs)
               or T_pool) if T_pool else 0
        return (ebq, ekn, ekp)

    if autotune_ok and not interpret and (block_q, block_k) == (256, 512):

        def run_shape(bqc, bkc):
            rng = np.random.default_rng(0)
            qs = jnp.asarray(rng.standard_normal((T, H, D)) * 0.1,
                             q.dtype)
            ks = jnp.asarray(rng.standard_normal((T, Hk, D)) * 0.1,
                             q.dtype)
            kps = jnp.zeros((max(T_pool, _SUBL), Hk, D), kpool.dtype)
            rws = jnp.zeros((T,), jnp.int32)
            pss = jnp.arange(T, dtype=jnp.int32)
            kvs = jnp.zeros((B,), jnp.int32)
            offs = jnp.full((B, NB), -1, jnp.int32)

            @jax.jit
            def f(qs, ks):
                return _ragged_pallas(
                    qs, ks, ks, kps, kps, rws, pss, kvs, offs, bs,
                    scale, kdq=kdq, vdq=vdq, with_pool=with_pool,
                    block_q=bqc, block_k=bkc, autotune_ok=False)

            return lambda: f(qs, ks)

        block_q, block_k = _autotuned_ragged_blocks(
            T, T_pool, H, Hk, D, q.dtype, int8_pool, bs,
            (block_q, block_k), run_shape, _eff)
    bq, bkn, bkp = _eff(block_q, block_k)
    nkp = (T_pool // bkp) if T_pool else 0
    nkn = T // bkn
    # pool tile j's ppt pages live at lanes [(j % tpg) * ppt, +ppt) of
    # voff's lane group j // tpg
    ppt = (bkp // bs) if nkp else 1
    tpg = _LANES // ppt
    ngrp = -(-max(nkp, 1) // tpg)

    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    q2 = qs.reshape(T, H * D)
    kp2 = kpool.reshape(kpool.shape[0], Hk * D)
    vp2 = vpool.reshape(vpool.shape[0], Hk * D)
    kn2 = k_new.reshape(T, Hk * D)
    vn2 = v_new.reshape(T, Hk * D)

    live = rows >= 0
    rc = jnp.clip(rows, 0, B - 1)
    # voff[t, p] = valid leading slots of page p for token t's row
    page_start = off[rc]                               # [T, NB]
    vcount = jnp.clip(
        jnp.where(page_start >= 0,
                  kv_start[rc][:, None] - page_start, 0),
        0, bs)
    vcount = jnp.where(live[:, None], vcount, 0).astype(jnp.float32)
    if nkp:
        vcount = jnp.pad(vcount.reshape(T, nkp, ppt),
                         ((0, 0), (0, ngrp * tpg - nkp), (0, 0)))
        voff = jnp.pad(vcount.reshape(T, ngrp, tpg * ppt),
                       ((0, 0), (0, 0), (0, _LANES - tpg * ppt)))
        voff = voff.reshape(T, ngrp * _LANES)
    else:
        voff = jnp.zeros((T, _LANES), jnp.float32)

    qrow = jnp.broadcast_to(rows[:, None], (T, _LANES))
    qpos = jnp.broadcast_to(pos[:, None], (T, _LANES))
    krow = jnp.broadcast_to(rows[None, :], (_SUBL, T))
    kpos = jnp.broadcast_to(pos[None, :], (_SUBL, T))
    dq = jnp.ones((2, Hk), jnp.float32)
    if kdq is not None:
        dq = dq.at[0].set(kdq.astype(jnp.float32))
    if vdq is not None:
        dq = dq.at[1].set(vdq.astype(jnp.float32))

    def _pool_idx(i, j):
        return (jnp.minimum(j, max(nkp - 1, 0)), 0)

    def _voff_idx(i, j):
        return (i, jnp.minimum(j, max(nkp - 1, 0)) // tpg)

    def _pack_idx(i, j):
        return (jnp.clip(j - nkp, 0, nkn - 1), 0)

    grid = (T // bq, nkp + nkn)
    kernel = functools.partial(
        _ragged_kernel, H=H, Hk=Hk, D=D, bq=bq,
        bkp=bkp if nkp else bs, bkn=bkn, nkp=nkp, nkn=nkn, bs=bs,
        tpg=tpg, int8_pool=int8_pool)
    def _pack_idx_ids(i, j):
        # kv-side id tiles are [_SUBL, T]: block column j - nkp
        return (0, jnp.clip(j - nkp, 0, nkn - 1))

    in_specs = [
        pl.BlockSpec((bq, _LANES), _voff_idx),                # voff
        pl.BlockSpec((bq, _LANES), lambda i, j: (i, 0)),      # qrow
        pl.BlockSpec((bq, _LANES), lambda i, j: (i, 0)),      # qpos
        pl.BlockSpec((_SUBL, bkn), _pack_idx_ids),            # krow
        pl.BlockSpec((_SUBL, bkn), _pack_idx_ids),            # kpos
        pl.BlockSpec(memory_space=pltpu.SMEM),                # dq
        pl.BlockSpec((bq, H * D), lambda i, j: (i, 0)),       # q
        pl.BlockSpec((bkp if nkp else _SUBL, Hk * D),
                     _pool_idx),                              # kpool
        pl.BlockSpec((bkp if nkp else _SUBL, Hk * D),
                     _pool_idx),                              # vpool
        pl.BlockSpec((bkn, Hk * D), _pack_idx),               # k_new
        pl.BlockSpec((bkn, Hk * D), _pack_idx),               # v_new
    ]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bq, H * D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, H * D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, H * D), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ragged_attention",    # also the innermost jax.named_scope
    )(voff, qrow, qpos, krow, kpos, dq, q2, kp2, vp2, kn2, vn2)
    return out.reshape(T, H, D)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------
def _shape_reject_reason(T, T_pool, H, Hk, D, block_size, with_pool):
    """None if the Pallas kernel applies, else a human-readable reason."""
    if T < _SUBL or T % _SUBL:
        return f"total tokens {T} must be a multiple of {_SUBL}"
    if T >= _LANES and T % _LANES:
        return (f"total tokens {T} must be a multiple of {_LANES} "
                "(or smaller than it) for the packed-phase id tiles")
    if (H * D) % _LANES or (Hk * D) % _LANES:
        return (f"H*D={H * D} and Hk*D={Hk * D} must be lane-aligned "
                "(%128==0)")
    if H > _LANES:
        # the kernel's running m/l softmax state is one [bq, _LANES]
        # scratch with one column per head
        return f"q heads {H} must be <= {_LANES}"
    if H % max(Hk, 1):
        return f"kv heads {Hk} must divide q heads {H}"
    if with_pool:
        if block_size % _SUBL:
            return f"block_size {block_size} must be a multiple of {_SUBL}"
        if block_size > 256:
            # per-page slot counts ride a matmul that may round its
            # operands to bfloat16: integers stay exact up to 256
            return f"block_size {block_size} must be <= 256"
        if T_pool % block_size:
            return "pool length must be a multiple of block_size"
    return None


def ragged_attention_path(T, T_pool, H, Hk, D, block_size,
                          with_pool=True):
    """('pallas'|'jnp', reason) — which implementation the dispatcher
    takes for this launch shape and why (the engine's observability
    surfaces fallbacks)."""
    if not _pallas_available():
        return ("jnp", f"no TPU Pallas backend ({jax.default_backend()})")
    reason = _shape_reject_reason(T, T_pool, H, Hk, D, block_size,
                                  with_pool)
    if reason:
        return ("jnp", reason)
    return ("pallas", "")


def ragged_paged_attention(q, k_new, v_new, kpool, vpool, rows, pos,
                           kv_start, off, *, block_size, scale,
                           kdq=None, vdq=None, with_pool=True,
                           path=None):
    """Mixed prefill/decode/verify attention over the paged pool for a
    packed token stream (module docstring has the layout contract).

    path: None = auto (Pallas on TPU when the launch shape fits, jnp
    reference otherwise); "jnp" | "pallas" | "pallas_interpret" force a
    specific implementation (tests)."""
    T, H, D = q.shape
    Hk = k_new.shape[1]
    T_pool = kpool.shape[0] if (with_pool and kpool is not None) else 0
    if path is None:
        path, _ = ragged_attention_path(T, T_pool, H, Hk, D, block_size,
                                        with_pool)
    if path == "pallas":
        return _ragged_pallas(q, k_new, v_new, kpool, vpool, rows, pos,
                              kv_start, off, block_size, scale,
                              kdq=kdq, vdq=vdq, with_pool=with_pool)
    if path == "pallas_interpret":
        return _ragged_pallas(q, k_new, v_new, kpool, vpool, rows, pos,
                              kv_start, off, block_size, scale,
                              kdq=kdq, vdq=vdq, with_pool=with_pool,
                              interpret=True, autotune_ok=False)
    return _ragged_reference(q, k_new, v_new, kpool, vpool, rows, pos,
                             kv_start, off, block_size, scale,
                             kdq=kdq, vdq=vdq, with_pool=with_pool)
