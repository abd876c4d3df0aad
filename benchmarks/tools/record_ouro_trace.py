"""Records and cuts the trace `tests/test_ouro_trace.py` reads
(`tests/data/ouro.xplane.pb`): on the chip, four steps of a tiny Ouro
`TrainStep` (`TINY`, `ROWS`, `SEQ` below, which the test reads from
here: two layers of 4 heads of 128 run three times over as one scanned
body, each layer and the final norm under `jax.checkpoint` with the
flash kernel's outputs kept, the exit gate, the expected loss through
one fused head call) under the harness's spans, the first compiling
inside the session. Cut as `record_jamba_trace.py` cuts its trace, by
its `cut`.

    python benchmarks/tools/record_ouro_trace.py <output file>"""
import glob
import os
import shutil
import sys
import tempfile

import _common  # noqa: F401  (puts the checkout on the path)
from record_jamba_trace import cut

TINY = dict(vocab_size=512, hidden_size=512, intermediate_size=1024,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, head_dim=128, total_ut_steps=3)
ROWS, SEQ = 1, 1024


def record(out_dir: str) -> str:
    import jax
    import numpy as np
    from harness.runlib import annotate
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.ouro import (OuroConfig, OuroForCausalLM,
                                        OuroPretrainingCriterion)
    from paddle_tpu.optimizer import AdamW

    os.environ["PADDLE_TPU_PALLAS_AUTOTUNE"] = "0"
    model = OuroForCausalLM(OuroConfig(
        **TINY, use_flash_attention=True, recompute=True))
    model.train()
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters(),
                moment_dtype="bfloat16")
    crit = OuroPretrainingCriterion(0.05)

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            outputs = m(ids)
        return crit(outputs, labels)

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
    print("aux", np.asarray(step.aux).tolist())
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
