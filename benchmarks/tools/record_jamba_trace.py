"""Records and cuts the trace `tests/test_jamba_trace.py` reads
(`tests/data/jamba.xplane.pb`): on the chip, four steps of a tiny Jamba
`TrainStep` (one Mamba and one attention layer, both under
`jax.checkpoint`, the scan and the flash kernels; `TINY` and `ROWS`,
`SEQ` below, which the test reads from here) under the harness's spans,
the first compiling inside the session.

The profiler writes megabytes for this. What is kept is what the readers
ask for: chip 0's `XLA Ops` and `XLA Modules` lines with the metadata
they point at (of its stats `tf_op`, `hlo_category` and `program_id`),
and of the host's planes the harness's and the program's spans. Times,
ids and op_names are as recorded; an instruction's HLO text is cut to
`%name = opcode(`.

    python benchmarks/tools/record_jamba_trace.py <output file>"""
import glob
import os
import re
import shutil
import sys
import tempfile

import _common  # noqa: F401  (puts the checkout on the path)

TINY = dict(vocab_size=512, hidden_size=256, intermediate_size=512,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=1, head_dim=128, attn_layer_period=2,
            attn_layer_offset=1, mamba_d_state=16, mamba_d_conv=4,
            mamba_expand=2, mamba_dt_rank=16)
ROWS, SEQ = 2, 256
KEEP_LINES = ("XLA Ops", "XLA Modules")
KEEP_STATS = ("tf_op", "hlo_category", "program_id")
KEEP_SPANS = ("harness.", "train_step", "compile.")


# -- the wire format, written (harness/trace_scopes.py reads it) -------------
def varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(no: int, wt: int, value) -> bytes:
    key = varint(no << 3 | wt)
    if wt == 0:
        return key + varint(value)
    if wt == 2:
        value = bytes(value)
        return key + varint(len(value)) + value
    return key + int(value).to_bytes(8 if wt == 1 else 4, "little")


def rebuild(buf, change):
    """The message with each field passed through `change(no, wt, value)`
    -> a new value, or None to drop it."""
    from harness.trace_scopes import fields
    out = []
    for no, wt, v in fields(buf):
        v = change(no, wt, v)
        if v is not None:
            out.append(field(no, wt, v))
    return b"".join(out)


def cut(path_in: str, path_out: str):
    from harness.trace_reduce import DEVICE_PLANE
    from harness.trace_scopes import XPlane, _text, fields
    with open(path_in, "rb") as f:
        space = memoryview(f.read())
    planes, seen_device = [], False
    for no, _wt, buf in fields(space):
        if no != 1:
            continue
        plane = XPlane(buf)
        device = bool(DEVICE_PLANE.match(plane.name))
        if device and seen_device or not (
                device or plane.name.startswith("/host:")):
            continue
        seen_device |= device
        stat_ids = {n: k for k, n in _stat_names(buf).items()}
        keep_stats = {stat_ids[n] for n in KEEP_STATS if n in stat_ids}
        used = set()

        def event(no, wt, v):
            return None if no == 4 else v           # an event's own stats

        def line(buf):
            name = next(_text(v) for n, _w, v in fields(buf) if n == 2)
            if device and name not in KEEP_LINES:
                return None
            kept = []
            for n, w, v in fields(buf):
                if n == 4:
                    mid = next(x for k, _w, x in fields(v) if k == 1)
                    if not device and not plane.event_names.get(
                            mid, "").startswith(KEEP_SPANS):
                        continue
                    used.add(mid)
                    v = rebuild(v, event)
                kept.append(field(n, w, v))
            return b"".join(kept)

        lines = [ln for ln in (line(v) for n, _w, v in fields(buf)
                               if n == 3) if ln is not None]
        refs = set()

        def stat_ok(v):
            sid = ref = None
            for n, _w, x in fields(v):
                if n == 1:
                    sid = x
                elif n == 7:
                    ref = x
            if sid not in keep_stats:
                return False
            if ref is not None:
                refs.add(ref)
            return True

        def metadata(no, wt, v):
            if no == 2:                             # the HLO text
                text = _text(v)
                m = re.match(r"^(%\S+ = )(?:.*?\s)?([\w\-]+)\(", text)
                return (m.group(1) + m.group(2) + "(").encode() \
                    if m else v
            if no == 5:
                return v if stat_ok(v) else None
            return v

        def entry(v, inner):
            key, val = XPlane._map_entry(v)
            return key, (None if val is None else
                         field(1, 0, key) + field(2, 2, inner(val)))

        out = []
        for n, w, v in fields(buf):
            if n == 3:
                continue
            if n == 4:
                key, new = entry(v, lambda m: rebuild(m, metadata))
                if key in used:
                    out.append(field(4, 2, new))
            elif n == 5:
                pass                                # below, once refs are in
            elif n == 6:
                continue                            # the plane's own stats
            else:
                out.append(field(n, w, v))
        for n, w, v in fields(buf):
            if n == 5:
                key, new = entry(v, bytes)
                if key in keep_stats or key in refs:
                    out.append(field(5, 2, new))
        planes.append(b"".join(out) + b"".join(field(3, 2, ln)
                                                 for ln in lines))
    with open(path_out, "wb") as f:
        f.write(b"".join(field(1, 2, p) for p in planes))


def _stat_names(plane_buf):
    from harness.trace_scopes import XPlane, _text, fields
    names = {}
    for n, _w, v in fields(plane_buf):
        if n == 5:
            key, meta = XPlane._map_entry(v)
            names[key] = next((_text(x) for k, _w2, x in fields(meta)
                               if k == 2), "")
    return names


def record(out_dir: str) -> str:
    import jax
    import numpy as np
    from harness.runlib import annotate
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTPretrainingCriterion, JambaForCausalLM
    from paddle_tpu.models.jamba import JambaConfig
    from paddle_tpu.optimizer import AdamW

    os.environ["PADDLE_TPU_PALLAS_AUTOTUNE"] = "0"
    model = JambaForCausalLM(JambaConfig(
        **TINY, use_flash_attention=True, recompute=True))
    model.train()
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters(),
                moment_dtype="bfloat16")
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels)

    step = TrainStep(model, opt, loss_fn)
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
