"""Records and cuts the trace `tests/test_mellum2_trace.py` reads
(`tests/data/mellum2.xplane.pb`): on a four-chip host, four steps of a
tiny Mellum2 `TrainStep` over a one-axis mesh of the four (`TINY`,
`ROWS`, `SEQ` below, which the test reads from here: two sliding layers
and a full one, all sparse, each under `jax.checkpoint`; 16 experts, 4 a
chip, with their exchange; the vocabulary in four slices) under the
harness's spans, the first compiling inside the session. Cut as
`record_jamba_trace.py` cuts its trace, by its `cut`, which keeps one
device plane: it is given each device plane alone in turn (the host's
planes with the first), and the four results are one file again (an
XSpace is its planes one after the other).

    python benchmarks/tools/record_mellum2_trace.py <output file>"""
import glob
import os
import shutil
import sys
import tempfile

import _common  # noqa: F401  (puts the checkout on the path)
from record_jamba_trace import cut, field

TINY = dict(vocab_size=1024, hidden_size=256, num_hidden_layers=3,
            num_attention_heads=2, num_key_value_heads=1, head_dim=128,
            num_experts=16, num_experts_per_tok=4,
            moe_intermediate_size=128, sliding_window=128,
            layer_types=["sliding_attention", "sliding_attention",
                         "full_attention"])
ROWS, SEQ, CHIPS = 4, 256, 4


def record(out_dir: str) -> str:
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from harness.runlib import annotate
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTPretrainingCriterion
    from paddle_tpu.models.mellum2 import Mellum2Config, Mellum2ForCausalLM
    from paddle_tpu.models.shard_plans import expert_parallel_rules
    from paddle_tpu.optimizer import AdamW

    os.environ["PADDLE_TPU_PALLAS_AUTOTUNE"] = "0"
    model = Mellum2ForCausalLM(Mellum2Config(
        **TINY, use_flash_attention=True, recompute=True))
    model.train()
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters(),
                moment_dtype="bfloat16")
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels), m.expert_counts

    mesh = Mesh(np.array(jax.devices()[:CHIPS]), ("ep",))
    step = TrainStep(model, opt, loss_fn, has_aux=True, mesh=mesh,
                     shard_param=expert_parallel_rules("ep"),
                     shard_data=P("ep", None), expert_axis="ep")
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


def cut_every_chip(raw: str, out: str, tmp: str):
    """`cut`, which keeps the first device plane it meets, on each
    device plane in turn."""
    from harness.trace_reduce import DEVICE_PLANE
    from harness.trace_scopes import XPlane, fields
    with open(raw, "rb") as f:
        space = memoryview(f.read())
    planes = [(XPlane(buf).name, bytes(buf))
              for no, _wt, buf in fields(space) if no == 1]
    devices = sorted(n for n, _b in planes if DEVICE_PLANE.match(n))
    parts = []
    for i, device in enumerate(devices):
        one, kept = os.path.join(tmp, "one.pb"), os.path.join(tmp, "cut.pb")
        with open(one, "wb") as f:
            f.write(b"".join(
                field(1, 2, b) for n, b in planes
                if n == device or (i == 0 and not DEVICE_PLANE.match(n))))
        cut(one, kept)
        with open(kept, "rb") as f:
            parts.append(f.read())
    with open(out, "wb") as f:
        f.write(b"".join(parts))


def main():
    out = os.path.abspath(sys.argv[1])
    os.makedirs(os.path.dirname(out), exist_ok=True)
    tmp = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    raw = sys.argv[2] if len(sys.argv) > 2 else record(tmp)
    cut_every_chip(raw, out, tmp)
    print(out, os.path.getsize(raw), "bytes recorded,",
          os.path.getsize(out), "kept")
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
