"""Decompose LLMEngine serving time (why is a decode chunk slower than
chunk_len x the dense decode step?).

Times, with warm executables and a full batch:
  - one ragged packed-batch executable call (the prefill/prefix-resume/
    verify family), host logic bypassed
  - one decode-chunk executable call (host logic bypassed)
  - one engine.step() (admission + chunk + host bookkeeping)

    python tools/profile_engine.py           # 1.3B (TPU box)
    python tools/profile_engine.py --tiny    # CPU smoke shapes (the
                                             # 1.3B compile times out on
                                             # the CPU box)
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
    import paddle_tpu as pt
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig

    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-size model/engine (runs on the CPU box)")
    args = ap.parse_args()

    if args.tiny:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_position_embeddings=256,
                        hidden_dropout_prob=0.0,
                        attention_dropout_prob=0.0)
        eng_kw = dict(max_batch=2, num_blocks=24, block_size=16,
                      decode_chunk=4, prompt_quantum=16,
                      max_model_len=256)
        prompt_len, max_new = 20, 64
        model = GPTForCausalLM(cfg)
    else:
        cfg = GPTConfig(vocab_size=50304, hidden_size=2048,
                        num_layers=24, num_heads=16,
                        max_position_embeddings=2048,
                        hidden_dropout_prob=0.0,
                        attention_dropout_prob=0.0)
        eng_kw = dict(max_batch=8, num_blocks=49, block_size=64,
                      decode_chunk=16, prompt_quantum=128,
                      max_model_len=2048)
        prompt_len, max_new = 100, 1024
        model = GPTForCausalLM(cfg).bfloat16()
    model.eval()
    rng = np.random.default_rng(0)
    eng = LLMEngine(model, **eng_kw)
    B = eng.max_batch
    out = {"tiny": bool(args.tiny)}

    # fill all slots with long-lived requests
    for i in range(B):
        eng.add_request(i, rng.integers(0, cfg.vocab_size,
                                        (prompt_len,)).astype(np.int32),
                        max_new_tokens=max_new)
    t0 = time.perf_counter()
    eng.step()          # admits + packed prefill + first chunk (compiles)
    out["first_step_s"] = round(time.perf_counter() - t0, 2)

    # warm ragged timing: the prefill wave compiled a
    # ("ragged", token_bucket, with_pool, all_pos) executable — time it
    # directly on synthetic all-dead operands (weight stream + lm head
    # cost; the pool stream rides along when with_pool)
    rkey = next((k for k in eng._fns if k[0] == "ragged"), None)
    if rkey is not None:
        _, tb, _wp, _ap = rkey
        fn = eng._fns[rkey]
        NB = eng.cache.allocator.num_blocks
        T_pool = NB * eng.block_size
        ids = np.zeros((tb,), np.int32)
        rows = np.full((tb,), -1, np.int32)
        pos = np.zeros((tb,), np.int32)
        kvs = np.zeros((B,), np.int32)
        off = np.full((B, NB), -1, np.int32)
        wf = np.full((tb,), T_pool, np.int32)   # all writes dropped
        sel = np.zeros((B,), np.int32)
        params = [t._data for t in eng._tensors]

        def one_ragged(salt):
            nxt, kcs, vcs = fn(params, eng.cache.key_caches,
                               eng.cache.value_caches,
                               jnp.asarray(ids + salt),
                               jnp.asarray(rows), jnp.asarray(pos),
                               jnp.asarray(kvs), jnp.asarray(off),
                               jnp.asarray(wf), jnp.asarray(sel),
                               jax.random.PRNGKey(salt))
            for i in range(eng.cache.num_layers):
                eng.cache.update(i, kcs[i], vcs[i])
            return nxt

        np.asarray(one_ragged(0))          # real sync (D2H)
        t0 = time.perf_counter()
        for i in range(4):
            np.asarray(one_ragged(i + 1))
        out["ragged_tokens_bucket"] = tb
        out["ragged_launch_ms"] = round(
            (time.perf_counter() - t0) / 4 * 1e3, 1)

    # warm chunk call, host logic included (step) vs bypassed
    t0 = time.perf_counter()
    eng.step()
    out["warm_step_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    t0 = time.perf_counter()
    for _ in range(4):
        eng.step()
    out["steady_step_ms"] = round(
        (time.perf_counter() - t0) / 4 * 1e3, 1)
    chunk = eng.decode_chunk
    out["steady_ms_per_token_row"] = round(
        out["steady_step_ms"] / chunk, 2)

    # bypass host bookkeeping: repeat the raw chunk executable (the
    # post-rewire cache keys the chunked scan as ("decode", chunk))
    fn = eng._fns.get(("decode", chunk))
    if fn is None:
        # steady state may have bucketed the chunk down (headroom)
        dkey = next(k for k in eng._fns if k[0] == "decode")
        chunk = dkey[1]
        fn = eng._fns[dkey]
    params = [t._data for t in eng._tensors]
    NB = eng.cache.allocator.num_blocks
    cur = jnp.zeros((B,), jnp.int32)
    lens = jnp.asarray(np.full((B,), 2 * prompt_len, np.int32))
    # give every row ownership of a few real blocks
    offn = np.full((B, NB), -1, np.int32)
    tbln = np.full((B, eng.npb_full), eng._trash_page, np.int32)
    npages = min(5, NB - 1)
    for b in range(B):
        blks = [1 + (b * npages + j) % (NB - 1) for j in range(npages)]
        tbln[b, :npages] = blks
        offn[b, blks] = np.arange(npages) * eng.block_size
    tblj, offj = jnp.asarray(tbln), jnp.asarray(offn)
    kcs, vcs = eng.cache.key_caches, eng.cache.value_caches
    kcs, vcs, toks = fn(params, kcs, vcs, cur, lens, tblj, offj,
                        jax.random.PRNGKey(0))
    np.asarray(toks)        # sync: the tokens to the host
    t0 = time.perf_counter()
    for i in range(4):
        kcs, vcs, toks = fn(params, kcs, vcs, cur + i, lens, tblj,
                            offj, jax.random.PRNGKey(i))
        np.asarray(toks)
    dt = (time.perf_counter() - t0) / 4
    out["raw_chunk_ms"] = round(dt * 1e3, 1)
    out["raw_ms_per_scan_step"] = round(dt / chunk * 1e3, 2)

    # per-executable cost-model expectations: every _fns entry is a
    # CompileTimed whose first (AOT) call recorded XLA's expected
    # flops/bytes — the static side of the roofline the timings above
    # are the measured side of
    out["fns"] = [
        {
            "key": "/".join(str(p) for p in key),
            "expected_gflops":
                None if fn.expected is None
                else round(fn.expected.flops / 1e9, 3),
            "expected_gb":
                None if fn.expected is None
                else round(fn.expected.bytes_accessed / 1e9, 3),
        }
        for key, fn in sorted(eng._fns.items(), key=lambda kv: str(kv[0]))
    ]
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
