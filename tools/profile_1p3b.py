"""Decompose the 1.3B training step + sweep remat variants (VERDICT r4
next-1: name where the time goes, then close the MFU gap).

Usage (one variant per process so HBM state never carries over):
    python tools/profile_1p3b.py step --policy full --batch 4
    python tools/profile_1p3b.py step --policy dots --batch 4
    python tools/profile_1p3b.py step --policy full --interval 2
    python tools/profile_1p3b.py parts          # fwd / fwd+bwd / opt split
    python tools/profile_1p3b.py micro          # flash + matmul + head/CE

Each prints one JSON line; tools/sweep_1p3b.sh drives the full sweep.
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


def _cfg(batch, seq, policy, interval, flash=True):
    from paddle_tpu.models.gpt import GPTConfig
    return GPTConfig(
        vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
        max_position_embeddings=seq, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0, use_flash_attention=flash,
        recompute=policy != "none", recompute_policy=policy
        if policy != "none" else "full", recompute_interval=interval)


def _build(cfg, moment_dtype="bfloat16"):
    from paddle_tpu import amp
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW

    model = GPTForCausalLM(cfg)
    model.train()
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                weight_decay=0.01, moment_dtype=moment_dtype)
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels)

    return model, opt, TrainStep(model, opt, loss_fn)


def _time(fn, steps=5, windows=2):
    fn()
    out = fn()
    np.asarray(out)
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn()
        np.asarray(out)
        best = min(best, time.perf_counter() - t0)
    return best / steps


def cmd_step(args):
    import jax
    from paddle_tpu.models.gpt import num_params
    from bench import peak_flops

    cfg = _cfg(args.batch, args.seq, args.policy, args.interval)
    model, opt, step = _build(cfg)
    rng = np.random.default_rng(0)
    ids = jax.device_put(rng.integers(
        0, cfg.vocab_size, (args.batch, args.seq)).astype(np.int32))
    labels = jax.device_put(rng.integers(
        0, cfg.vocab_size, (args.batch, args.seq)).astype(np.int32))
    dt = _time(lambda: step(ids, labels).numpy(), steps=args.steps)
    tok_s = args.batch * args.seq / dt
    n = num_params(cfg)
    mfu = 6.0 * n * tok_s / peak_flops(jax.devices()[0])
    print(json.dumps({
        "variant": f"policy={args.policy},interval={args.interval},"
                   f"b={args.batch}",
        "step_ms": round(dt * 1e3, 1), "tokens_per_sec": round(tok_s, 1),
        "mfu": round(mfu, 4)}), flush=True)


def cmd_parts(args):
    """Split: fwd-only, grad-only (fwd+bwd), full step -> opt overhead."""
    import jax
    from paddle_tpu import amp
    from paddle_tpu.jit import _collect_params, _functional_params
    import paddle_tpu.autograd.tape as _tape
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion

    cfg = _cfg(args.batch, args.seq, args.policy, args.interval)
    model, opt, step = _build(cfg)
    crit = GPTPretrainingCriterion()
    _, pts, _, bts = _collect_params(model)
    tensors = pts + bts
    arrs = [t._data for t in tensors]
    rng = np.random.default_rng(0)
    ids = jax.device_put(rng.integers(
        0, cfg.vocab_size, (args.batch, args.seq)).astype(np.int32))
    labels = jax.device_put(rng.integers(
        0, cfg.vocab_size, (args.batch, args.seq)).astype(np.int32))

    def loss_of(params, ids, labels):
        with _tape.no_grad(), _functional_params(tensors, params):
            with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
                return crit(model(ids), labels)._data

    fwd = jax.jit(loss_of)
    grad = jax.jit(lambda p, i, l: jax.grad(loss_of)(p, i, l)[0])
    t_fwd = _time(lambda: fwd(arrs, ids, labels), steps=args.steps)
    t_grad = _time(lambda: np.asarray(
        grad(arrs, ids, labels).ravel()[0]), steps=args.steps)
    t_step = _time(lambda: step(ids, labels).numpy(), steps=args.steps)
    print(json.dumps({
        "variant": f"parts policy={args.policy} b={args.batch}",
        "fwd_ms": round(t_fwd * 1e3, 1),
        "fwd_bwd_ms": round(t_grad * 1e3, 1),
        "full_step_ms": round(t_step * 1e3, 1),
        "opt_update_ms": round((t_step - t_grad) * 1e3, 1)}), flush=True)


def _scan_time(body, init, iters=10):
    """Time `body` by scanning it `iters` times INSIDE one executable
    (a device-side loop with data dependence: no host dispatch between
    iterations), syncing by fetching one element to the host."""
    import jax

    f = jax.jit(lambda c: jax.lax.scan(
        lambda c_, _: (body(c_), None), c, None, length=iters)[0])

    def sync(r):
        leaf = jax.tree_util.tree_leaves(r)[0]
        np.asarray(leaf.reshape(-1)[0])

    r = f(init)
    sync(r)
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        r = f(r)
        sync(r)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def cmd_micro(args):
    """Component microbenches at the 1.3B shapes."""
    import jax
    import jax.numpy as jnp
    from bench import peak_flops
    dev = jax.devices()[0]
    peak = peak_flops(dev)
    b, s, h, H, D, v = args.batch, args.seq, 2048, 16, 128, 50304
    key = jax.random.PRNGKey(0)
    out = {}

    # flash attention fwd and fwd+bwd (carry the output forward so each
    # iteration has fresh content)
    from paddle_tpu.kernels.pallas.flash_attention import flash_attention
    q = jax.random.normal(key, (b, s, H, D), jnp.bfloat16)

    t = _scan_time(lambda q: flash_attention(q, q, q, causal=True)
                   .astype(jnp.bfloat16), q)
    fl = 4.0 * b * s * s * H * D / 2  # causal halves the work
    out["flash_fwd_ms"] = round(t * 1e3, 2)
    out["flash_fwd_util"] = round(fl / t / peak, 3)

    g = jax.grad(lambda q: flash_attention(q, q, q, causal=True)
                 .astype(jnp.float32).sum())
    t = _scan_time(lambda q: (q + 1e-3 * g(q)).astype(jnp.bfloat16), q)
    out["flash_fwdbwd_ms"] = round(t * 1e3, 2)
    out["flash_fwdbwd_util"] = round(4.5 * fl / t / peak, 3)

    # the MLP-ish matmul at model shape: [b*s, h] x [h, 4h] x [4h, h]
    x = jax.random.normal(key, (b * s, h), jnp.bfloat16)
    w1 = jax.random.normal(key, (h, 4 * h), jnp.bfloat16) * 0.02
    w2 = jax.random.normal(key, (4 * h, h), jnp.bfloat16) * 0.02
    t = _scan_time(lambda x: ((x @ w1) @ w2).astype(jnp.bfloat16), x)
    out["matmul_pair_ms"] = round(t * 1e3, 2)
    out["matmul_util"] = round(2.0 * 2 * b * s * h * 4 * h / t / peak,
                               3)

    # lm head + softmax cross-entropy (the vocab-wide tail) fwd+bwd
    hid = jax.random.normal(key, (b * s, h), jnp.bfloat16)
    wv = jax.random.normal(key, (v, h), jnp.bfloat16) * 0.02
    lab = jax.random.randint(key, (b * s,), 0, v)

    def head(hid):
        logits = (hid @ wv.T).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return (lse - jnp.take_along_axis(
            logits, lab[:, None], axis=-1)[:, 0]).mean()

    hgrad = jax.grad(head)
    t = _scan_time(lambda hid: (hid - 1e-3 * hgrad(hid)).astype(
        jnp.bfloat16), hid)
    out["head_ce_fwdbwd_ms"] = round(t * 1e3, 2)
    out["head_ce_util"] = round(4.0 * b * s * h * v / t / peak, 3)

    # optimizer-update-shaped stream: fp32 param + grad + 2 bf16 moments
    from bench import hbm_bw
    p32 = jax.random.normal(key, (n32 := 330_000_000,), jnp.float32)
    g32 = jax.random.normal(key, (n32,), jnp.float32)
    m16 = jnp.zeros((n32,), jnp.bfloat16)
    v16 = jnp.zeros((n32,), jnp.bfloat16)   # distinct buffer: both donate

    def upd(p, g, m, v_):
        m = 0.9 * m.astype(jnp.float32) + 0.1 * g
        v_ = 0.99 * v_.astype(jnp.float32) + 0.01 * g * g
        p = p - 0.001 * m / (jnp.sqrt(v_) + 1e-8)
        return p, m.astype(jnp.bfloat16), v_.astype(jnp.bfloat16)

    ju = jax.jit(upd, donate_argnums=(0, 2, 3))
    st = (p32, g32, m16, v16)

    def run():
        nonlocal st
        p, m, v_ = ju(st[0], st[1], st[2], st[3])
        st = (p, g32, m, v_)
        return p

    run()
    np.asarray(st[0][0])        # sync: one element to the host
    t0 = time.perf_counter()
    for _ in range(10):
        run()
    np.asarray(st[0][0])
    t = (time.perf_counter() - t0) / 10
    bytes_ = n32 * (4 + 4 + 4 * 2 + 4)  # read p,g,m,v + write p,m,v
    out["optstream_330M_ms"] = round(t * 1e3, 2)
    out["optstream_gbps"] = round(bytes_ / t / 1e9, 1)
    out["hbm_peak_gbps"] = round(hbm_bw(dev) / 1e9, 1)
    print(json.dumps(out), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cmd", choices=["step", "parts", "micro"])
    ap.add_argument("--policy", default="full",
                    choices=["full", "dots", "dots_no_batch", "none"])
    ap.add_argument("--interval", type=int, default=1)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()
    {"step": cmd_step, "parts": cmd_parts, "micro": cmd_micro}[args.cmd](
        args)


if __name__ == "__main__":
    main()
