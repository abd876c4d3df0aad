"""Serve (greedy-decode) a GPT-class model on one TPU chip.

The serving story end-to-end:
  generate() runs the WHOLE decode loop as one jitted lax.scan
  executable with the KV caches donated (in-place on device), a fused
  prefill, and 128-bucketed cache lengths so nearby requests share
  executables. The same kernels back the reference-parity serving ops
  (incubate.nn.functional.masked_multihead_attention /
  block_multihead_attention / fused_multi_transformer).

Run: python examples/serve_gpt.py [--new-tokens 64]
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import paddle_tpu as pt
from paddle_tpu.models import GPTForCausalLM
from paddle_tpu.models.generation import generate
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.utils.runtime_env import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="0 = greedy; >0 = nucleus sampling")
    args = ap.parse_args()
    use_compile_cache()

    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, max_position_embeddings=1024,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = GPTForCausalLM(cfg).bfloat16()
    model.eval()

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size,
                          (args.batch, args.prompt_len)).astype(np.int32)
    kw = {}
    if args.top_p > 0:
        kw = dict(do_sample=True, top_p=args.top_p, seed=0)

    out = generate(model, pt.to_tensor(prompt),
                   max_new_tokens=args.new_tokens, **kw)   # compiles
    t0 = time.perf_counter()
    out = generate(model, pt.to_tensor(prompt),
                   max_new_tokens=args.new_tokens, **kw)
    out.numpy()
    dt = time.perf_counter() - t0
    print(f"generated {args.batch}x{args.new_tokens} tokens in "
          f"{dt:.2f}s  ({args.batch * args.new_tokens / dt:,.0f} tok/s)")
    print("first row:", out.numpy()[0, -10:])


if __name__ == "__main__":
    main()
