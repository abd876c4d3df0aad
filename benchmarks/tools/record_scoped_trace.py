"""Records the trace `tests/test_trace_scopes.py` reads
(`tests/data/scoped.xplane.pb`): on the chip, a few steps of a tiny
`TrainStep` (GPT, two blocks, `jax.checkpoint` on the first, the flash
kernels) under the harness's spans, so that the trace holds device
operations under the program's `jax.named_scope` paths in every phase
and the program's own spans (`train_step*`, `compile.*`) on the host's
plane.

The profiler writes about 1.8 MB for this. The committed file is that
recording cut once, by a script that was not kept, to what the readers
ask for: chip 0's `XLA Ops` and `XLA Modules` lines with the metadata
they point at (of its stats `tf_op` and `hlo_category`), and of the
host's planes the harness's and the program's spans. Times, ids and
op_names are as recorded; an instruction's HLO text is cut to
`%name = opcode(`.

    python benchmarks/tools/record_scoped_trace.py <output file>"""
import glob
import os
import shutil
import sys
import tempfile

import _common  # noqa: F401  (puts the checkout on the path)


def main():
    import jax
    import numpy as np
    from harness.runlib import annotate
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.optimizer import AdamW

    os.environ["PADDLE_TPU_PALLAS_AUTOTUNE"] = "0"
    cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=2,
                    num_heads=2, max_position_embeddings=256,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=True, recompute=True,
                    recompute_interval=2)
    model = GPTForCausalLM(cfg)
    model.train()
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels)

    step = TrainStep(model, opt, loss_fn)
    rng = np.random.default_rng(0)

    def batch():
        toks = rng.integers(0, 512, (8, 257)).astype(np.int32)
        return toks[:, :-1], toks[:, 1:]

    out = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=options)
    # the first call compiles inside the session: compile.* spans
    for _ in range(4):
        with annotate("harness.train.next_batch"):
            ids, labels = batch()
        with annotate("harness.train.step"):
            loss = step(ids, labels)
        with annotate("harness.train.read_loss"):
            float(loss.numpy())
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    os.makedirs(os.path.dirname(os.path.abspath(sys.argv[1])), exist_ok=True)
    shutil.copy(found[0], sys.argv[1])
    shutil.rmtree(out, ignore_errors=True)
    print(sys.argv[1], os.path.getsize(sys.argv[1]), "bytes")


if __name__ == "__main__":
    main()
