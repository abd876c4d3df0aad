"""Records and cuts the trace `tests/test_qwen3next_trace.py` reads
(`tests/data/qwen3next.xplane.pb`): on the chip, four steps of a tiny
Qwen3-Next `TrainStep` (`TINY`, `HELD`, `ROWS`, `SEQ` below, which the
test reads from here: one Gated DeltaNet layer and one gated attention
layer at head size 256, each over a softmax-routed sparse feed-forward
and under `jax.checkpoint`; the state kernels at a 128 x 128 state, the
flash kernels at 4 heads on 2 of 256) under the harness's spans, the
first compiling inside the session. Cut as `record_jamba_trace.py` cuts
its trace, by its `cut`.

    python benchmarks/tools/record_qwen3next_trace.py <output file>"""
import glob
import os
import shutil
import sys
import tempfile

import _common  # noqa: F401  (puts the checkout on the path)
from record_jamba_trace import cut

TINY = dict(vocab_size=512, hidden_size=512, num_hidden_layers=2,
            full_attention_interval=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=256, linear_num_key_heads=2,
            linear_num_value_heads=4, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=256, shared_expert_intermediate_size=256)
HELD = (0, 4)           # experts 0 to 3 of the 8
ROWS, SEQ = 1, 1024


def record(out_dir: str) -> str:
    import jax
    import numpy as np
    from harness.runlib import annotate
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTPretrainingCriterion
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextForCausalLM)
    from paddle_tpu.optimizer import AdamW

    os.environ["PADDLE_TPU_PALLAS_AUTOTUNE"] = "0"
    model = Qwen3NextForCausalLM(Qwen3NextConfig(
        **TINY, experts_held=HELD, use_flash_attention=True,
        recompute=True))
    model.train()
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters(),
                moment_dtype="bfloat16")
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels), m.expert_counts

    step = TrainStep(model, opt, loss_fn, has_aux=True)
    rng = np.random.default_rng(0)

    def batch():
        toks = rng.integers(0, TINY["vocab_size"],
                            (ROWS, SEQ + 1)).astype(np.int32)
        return toks[:, :-1], toks[:, 1:]

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=options)
    # the first call compiles inside the session: compile.* spans
    for _ in range(4):
        with annotate("harness.train.next_batch"):
            ids, labels = batch()
        with annotate("harness.train.step"):
            loss = step(ids, labels)
        with annotate("harness.train.read_loss"):
            float(loss.numpy())
    jax.profiler.stop_trace()
    print("counts", np.asarray(step.aux).tolist())
    return glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]


def main():
    out = os.path.abspath(sys.argv[1])
    os.makedirs(os.path.dirname(out), exist_ok=True)
    tmp = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    raw = record(tmp)
    cut(raw, out)
    print(out, os.path.getsize(raw), "bytes recorded,",
          os.path.getsize(out), "kept")
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
