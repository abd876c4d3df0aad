"""Records and cuts the trace `tests/test_deepseek_v2_trace.py` reads
(`tests/data/deepseek_v2.xplane.pb`): on the chip, four steps of a tiny
DeepSeek-V2 `TrainStep` (`TINY`, `ROWS`, `SEQ` below, which the test
reads from here: a dense layer and two sparse ones, 4 heads of 128
beside one shared rotary head of 64 over a 256-wide latent, 8 of 16
experts held, each layer under `jax.checkpoint` with the two-part flash
kernel's outputs kept, the balance term in the loss) under the harness's
spans, the first compiling inside the session. Cut as
`record_jamba_trace.py` cuts its trace, by its `cut`.

    python benchmarks/tools/record_deepseek_v2_trace.py <output file>"""
import glob
import os
import shutil
import sys
import tempfile

import _common  # noqa: F401  (puts the checkout on the path)
from record_jamba_trace import cut

TINY = dict(vocab_size=512, hidden_size=512, intermediate_size=1024,
            moe_intermediate_size=256, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4,
            n_routed_experts=16, num_experts_per_tok=4, kv_lora_rank=256,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
HELD = (0, 8)
ROWS, SEQ = 1, 1024


def record(out_dir: str) -> str:
    import jax
    import numpy as np
    from harness.runlib import annotate
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.deepseek_v2 import (
        DeepseekV2Config, DeepseekV2ForCausalLM,
        DeepseekV2PretrainingCriterion)
    from paddle_tpu.optimizer import AdamW

    os.environ["PADDLE_TPU_PALLAS_AUTOTUNE"] = "0"
    model = DeepseekV2ForCausalLM(DeepseekV2Config(
        **TINY, experts_held=HELD, use_flash_attention=True,
        recompute=True))
    model.train()
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters(),
                moment_dtype="bfloat16")
    crit = DeepseekV2PretrainingCriterion(0.001)

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        loss, balance = crit(logits, labels, m.balance_terms)
        return loss, (m.expert_counts, balance)

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
    counts, balance = step.aux
    print("counts", np.asarray(counts).tolist(), "balance",
          float(np.asarray(balance)))
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
