"""6.7B (GPT-3 class) dry-fit paths (VERDICT r4 next-1b): the
north-star config must compile and produce a measured number on this
one-chip box.

  python tools/dryfit_6p7b.py layer    # single-chip proxy on the REAL
      chip: one 6.7B transformer block + embedding/head, fwd+bwd+update
      at seq 2048, extrapolated to the 32-layer model analytically
      (prints the projected step time / MFU and each measured part)
  python tools/dryfit_6p7b.py zero3    # the FULL 6.7B model, ZeRO-3
      (p_g_os) over the virtual 8-device CPU mesh, ONE tiny-seq step —
      proves the sharded state + step compile end-to-end (slow: minutes
      of CPU time; run deliberately)

Each prints one JSON line.
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


def cmd_layer(args):
    import jax
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.models.gpt import (GPTConfig, GPTDecoderLayer,
                                       gpt3_6p7b, num_params)
    from bench import peak_flops
    import paddle_tpu as pt
    import paddle_tpu.ops as ops

    cfg = gpt3_6p7b(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=True)
    b, s = args.batch, args.seq
    dev = jax.devices()[0]

    def timed_step(model, loss_fn, batch, steps=5):
        opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                    weight_decay=0.01, moment_dtype="bfloat16")
        step = TrainStep(model, opt, loss_fn)
        batch = tuple(jax.device_put(a) for a in batch)
        step(*batch)
        out = step(*batch)
        float(out.numpy())
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(steps):
                out = step(*batch)
            float(out.numpy())
            best = min(best, (time.perf_counter() - t0) / steps)
        return best

    rng = np.random.default_rng(0)

    # --- one decoder block, rematted like the full model would be ---
    class OneBlock(pt.nn.Layer):
        def __init__(self):
            super().__init__()
            self.blk = GPTDecoderLayer(cfg)

        def forward(self, x):
            from paddle_tpu.distributed.meta_parallel.recompute import \
                recompute
            return recompute(self.blk, x)

    x = rng.standard_normal((b, s, cfg.hidden_size)).astype(np.float32)

    def blk_loss(m, x):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            return ops.mean(m(x) ** 2)

    t_layer = timed_step(OneBlock(), blk_loss, (x,))

    # --- embedding + tied head + CE at the same shape ---
    class EmbHead(pt.nn.Layer):
        def __init__(self):
            super().__init__()
            from paddle_tpu.models.gpt import (GPTEmbeddings,
                                               GPTPretrainingCriterion)
            self.emb = GPTEmbeddings(cfg)
            self.crit = GPTPretrainingCriterion()

        def forward(self, ids, labels):
            h = self.emb(ids)
            w = self.emb.word_embeddings.weight
            logits = ops.matmul(h, w, transpose_y=True)
            return self.crit(logits, labels)

    ids = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)

    def eh_loss(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            return m(ids, labels)

    t_embhead = timed_step(EmbHead(), eh_loss, (ids, labels))

    proj = cfg.num_layers * t_layer + t_embhead
    n = num_params(cfg)
    tok_s = b * s / proj
    mfu = 6.0 * n * tok_s / peak_flops(dev)
    print(json.dumps({
        "mode": "layer_proxy", "config": "gpt3_6p7b",
        "batch": b, "seq": s,
        "layer_step_ms": round(t_layer * 1e3, 1),
        "embhead_step_ms": round(t_embhead * 1e3, 1),
        "projected_step_ms": round(proj * 1e3, 1),
        "projected_tokens_per_sec": round(tok_s, 1),
        "projected_mfu": round(mfu, 4),
        "note": "32*layer + embed/head measured on the real chip; "
                "inter-layer residual traffic is inside the layer "
                "timing (its input/output live in HBM)"}), flush=True)


def cmd_zero3(args):
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    from paddle_tpu import amp
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
    from paddle_tpu.models.gpt import gpt3_6p7b, num_params
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.device import memory

    cfg = gpt3_6p7b(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    recompute=True)
    b, s = 8, args.seq
    t0 = time.perf_counter()
    pt.seed(0)
    model = GPTForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                weight_decay=0.01, moment_dtype="bfloat16")
    model, opt = dist.sharding.group_sharded_parallel(model, opt,
                                                      "p_g_os")
    t_build = time.perf_counter() - t0
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            inner = getattr(m, "_layers", m)
            return crit(inner(ids), labels)

    step = TrainStep(model, opt, loss_fn)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
    t0 = time.perf_counter()
    loss = step(ids, labels)
    val = float(loss.numpy())
    t_step = time.perf_counter() - t0
    state = list(step.params) + [v for st in step.opt_states
                                 for v in st.values()]
    per_dev = memory.state_bytes_per_device(state)
    print(json.dumps({
        "mode": "zero3_dryfit", "config": "gpt3_6p7b",
        "devices": len(jax.devices()), "batch": b, "seq": s,
        "params": num_params(cfg),
        "build_s": round(t_build, 1),
        "first_step_s": round(t_step, 1),
        "loss": round(val, 4),
        "max_state_bytes_per_device_gb": round(
            max(per_dev.values()) / 1e9, 2) if per_dev else None,
    }), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cmd", choices=["layer", "zero3"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    args = ap.parse_args()
    if args.cmd == "zero3" and args.seq == 2048:
        args.seq = 64      # tiny-seq default for the CPU dry-fit
    {"layer": cmd_layer, "zero3": cmd_zero3}[args.cmd](args)


if __name__ == "__main__":
    main()
