"""Train a GPT-2-small-class model on one TPU chip.

The round-trip a PaddlePaddle user expects, TPU-native:
  model/optimizer/loss exactly like dygraph paddle, then ONE fused
  donated-buffer XLA executable per step via paddle_tpu.jit.TrainStep
  (fwd + bwd + update), bf16 autocast, Pallas flash attention.

Run: python examples/train_gpt.py [--steps 20]
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import paddle_tpu as pt
from paddle_tpu import amp
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.optimizer import AdamW
from paddle_tpu.utils.runtime_env import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    args = ap.parse_args()
    use_compile_cache()

    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, max_position_embeddings=1024,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=True)
    model = GPTForCausalLM(cfg)
    model.train()
    opt = AdamW(learning_rate=3e-4, parameters=model.parameters(),
                weight_decay=0.01)
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels)

    step = TrainStep(model, opt, loss_fn)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size,
                       (args.batch, args.seq)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)

    loss = step(ids, labels)          # compiles on first call
    print(f"step 0  loss {float(loss.numpy()):.4f}")
    t0 = time.perf_counter()
    for i in range(1, args.steps):
        loss = step(ids, labels)
    print(f"step {args.steps - 1}  loss {float(loss.numpy()):.4f}  "
          f"({args.batch * args.seq * (args.steps - 1) / (time.perf_counter() - t0):,.0f} tok/s)")


if __name__ == "__main__":
    main()
