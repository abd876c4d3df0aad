"""Ablate the LLMEngine decode-chunk body: full (legacy read+write of
the pool inside the scan) vs no-write vs no-attention vs staged (the
shipped side-buffer design), to locate the per-step cost over the dense
fused loop.

The "full" variant scatters into the pool AND reads it back through the
whole-pool attention in the same scan body — the aliasing pattern that
costs XLA a full pool copy per step (a deleted pre-round record read ~617 MB/step at
1.3B). The "staged" variant is the engine's current body: k/v writes
land in a small [L, B, chunk] side buffer, the pool stays read-only in
the scan, and one flat token-major scatter per cache merges the chunk
at the end. Write+read is no longer superlinear when
staged_ms_per_step tracks no_write_ms_per_step instead of
full_ms_per_step.

    python tools/ablate_engine_step.py           # 1.3B (TPU box)
    python tools/ablate_engine_step.py --tiny    # CPU smoke shapes
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    import math
    import paddle_tpu as pt
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.inference.llm_engine import _pool_decode_attention
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.jit import _functional_params
    from paddle_tpu.autograd import tape as _tape

    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-size model/engine (runs on the CPU box)")
    args = ap.parse_args()

    if args.tiny:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_position_embeddings=256,
                        hidden_dropout_prob=0.0,
                        attention_dropout_prob=0.0)
        model = GPTForCausalLM(cfg)
        eng = LLMEngine(model, max_batch=2, num_blocks=24,
                        block_size=16, decode_chunk=4,
                        prompt_quantum=16, max_model_len=256)
        chunk, start_len = 4, 40
    else:
        cfg = GPTConfig(vocab_size=50304, hidden_size=2048,
                        num_layers=24, num_heads=16,
                        max_position_embeddings=2048,
                        hidden_dropout_prob=0.0,
                        attention_dropout_prob=0.0)
        model = GPTForCausalLM(cfg).bfloat16()
        eng = LLMEngine(model, max_batch=8, num_blocks=49,
                        block_size=64, decode_chunk=16,
                        prompt_quantum=128, max_model_len=2048)
        chunk, start_len = 16, 200
    model.eval()
    fam, B, bs = eng.fam, eng.max_batch, eng.block_size
    H_D, kvH = fam.head_dim, fam.kv_heads
    L = cfg.num_layers
    scale = 1.0 / math.sqrt(H_D)
    tensors = eng._tensors

    def make(variant):
        def decode(params, kcs, vcs, cur, lens, tbl, off, key):
            with _tape.no_grad(), _functional_params(tensors, params):
                cdtype = kcs[0].dtype
                st_k = jnp.zeros((L, B, chunk, kvH, H_D), cdtype)
                st_v = jnp.zeros((L, B, chunk, kvH, H_D), cdtype)
                jpos = jnp.arange(chunk, dtype=jnp.int32)

                def body(carry, i):
                    kcs_c, vcs_c, st_k, st_v, cur, lens = carry
                    x = Tensor._wrap(fam.embed(cur, lens)[:, None])
                    bidx = jnp.arange(B)
                    page = jnp.clip(lens // bs, 0, tbl.shape[1] - 1)
                    phys = jnp.maximum(tbl[bidx, page], 0)
                    flat = phys * bs + lens % bs
                    kcs2, vcs2 = [], []
                    for li, layer in enumerate(fam.layers()):
                        qkv = fam.qkv(layer, Tensor._wrap(x._data[:, 0]))
                        nH = qkv.shape[-1] // H_D - 2 * kvH
                        q = qkv[:, :nH * H_D].reshape(B, nH, H_D)
                        k = qkv[:, nH * H_D:(nH + kvH) * H_D].reshape(
                            B, kvH, H_D)
                        v = qkv[:, (nH + kvH) * H_D:].reshape(
                            B, kvH, H_D)
                        if variant == "full":
                            # legacy: pool written AND read in-body —
                            # the superlinear read+write hazard
                            kc = kcs_c[li].at[flat].set(
                                k.astype(cdtype))
                            vc = vcs_c[li].at[flat].set(
                                v.astype(cdtype))
                        else:
                            kc, vc = kcs_c[li], vcs_c[li]
                        kcs2.append(kc)
                        vcs2.append(vc)
                        if variant == "staged":
                            st_k = jax.lax.dynamic_update_slice(
                                st_k, k.astype(cdtype)[None, :, None],
                                (li, 0, i, 0, 0))
                            st_v = jax.lax.dynamic_update_slice(
                                st_v, v.astype(cdtype)[None, :, None],
                                (li, 0, i, 0, 0))
                        if variant == "no_attn":
                            rep = nH // kvH
                            o = (q + jnp.repeat(k, rep, axis=1) * 0.01
                                 ).reshape(B, nH * H_D)
                        else:
                            o = _pool_decode_attention(
                                q, kc, vc, off, lens, scale, bs)
                            if variant == "staged":
                                # the engine's staged body also attends
                                # over the side buffer; the tiny extra
                                # einsum stands in for that term
                                q4 = (q.astype(jnp.float32) * scale
                                      ).reshape(B, kvH, nH // kvH, H_D)
                                ss = jnp.einsum(
                                    "bkrd,bjkd->bkrj", q4,
                                    st_k[li].astype(jnp.float32))
                                ss = jnp.where(
                                    (jpos <= i)[None, None, None, :],
                                    ss, -jnp.inf)
                                ps = jax.nn.softmax(ss, axis=-1)
                                o = o + jnp.einsum(
                                    "bkrj,bjkd->bkrd", ps,
                                    st_v[li].astype(jnp.float32)
                                ).reshape(B, nH * H_D) * 0.0
                        x = fam.attn_out(
                            layer, x,
                            o.astype(x._data.dtype)[:, None, :])
                        x = fam.mlp(layer, x)
                    x = fam.final(x)
                    lg = fam.logits(x)._data[:, -1]
                    nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    return (kcs2, vcs2, st_k, st_v, nxt, lens + 1), nxt

                carry = (list(kcs), list(vcs), st_k, st_v, cur, lens)
                carry, toks = jax.lax.scan(body, carry, jpos)
                kcs2, vcs2, st_k, st_v, cur, lens = carry
                if variant == "staged":
                    # merge: ONE flat token-major scatter per cache
                    gpos = (lens - chunk)[:, None] + jpos[None, :]
                    page = jnp.clip(gpos // bs, 0, tbl.shape[1] - 1)
                    phys = jnp.maximum(
                        jnp.take_along_axis(tbl, page, axis=1), 0)
                    flat = (phys * bs + gpos % bs).reshape(-1)
                    kcs2 = [kcs2[li].at[flat].set(
                        st_k[li].reshape(B * chunk, kvH, H_D))
                        for li in range(L)]
                    vcs2 = [vcs2[li].at[flat].set(
                        st_v[li].reshape(B * chunk, kvH, H_D))
                        for li in range(L)]
                return kcs2, vcs2, jnp.transpose(toks)

        return jax.jit(decode, donate_argnums=(1, 2))

    params = [t._data for t in tensors]
    NB = eng.cache.allocator.num_blocks
    cur = jnp.zeros((B,), jnp.int32)
    lens = jnp.asarray(np.full((B,), start_len, np.int32))
    tbln = np.full((B, eng.npb_full), eng._trash_page, np.int32)
    offn = np.full((B, NB), -1, np.int32)
    npages = min(5, NB - 1)
    for b in range(B):
        blks = [1 + (b * npages + j) % (NB - 1) for j in range(npages)]
        tbln[b, :npages] = blks
        offn[b, blks] = np.arange(npages) * bs
    tblj, offj = jnp.asarray(tbln), jnp.asarray(offn)
    out = {"tiny": bool(args.tiny)}
    for variant in ("full", "staged", "no_write", "no_attn"):
        fn = make(variant)
        kcs = [jnp.zeros_like(a) for a in eng.cache.key_caches]
        vcs = [jnp.zeros_like(a) for a in eng.cache.value_caches]
        kcs, vcs, toks = fn(params, kcs, vcs, cur, lens, tblj, offj,
                            jax.random.PRNGKey(0))
        np.asarray(toks)
        t0 = time.perf_counter()
        for i in range(3):
            kcs, vcs, toks = fn(params, kcs, vcs, cur + i, lens, tblj,
                                offj, jax.random.PRNGKey(i))
            np.asarray(toks)
        out[variant + "_ms_per_step"] = round(
            (time.perf_counter() - t0) / 3 / chunk * 1e3, 2)
    out["write_read_overhead_full"] = round(
        out["full_ms_per_step"] - out["no_write_ms_per_step"], 2)
    out["write_read_overhead_staged"] = round(
        out["staged_ms_per_step"] - out["no_write_ms_per_step"], 2)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
