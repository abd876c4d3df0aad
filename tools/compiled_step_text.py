"""Has a change to shared code changed what the TPU's compiler makes of a
whole step? Compile the steps `tests/test_tpu_aot_compile.py` builds for
a described v5e (its fixtures: the Pallas path, which the CPU never
takes) in the checkout this is run from, and write for each one its
Mosaic calls by name, its `memory_analysis()` and its compiled text;
then compare two such directories. No chip; ten minutes a checkout.

    JAX_PLATFORMS=cpu python tools/compiled_step_text.py dump <dir> [fixture ...]
    JAX_PLATFORMS=cpu python tools/compiled_step_text.py compare <dir a> <dir b>

`compare` leaves out what only says where the code stood: the tables of
source files and stack frames at the text's head, each instruction's
`stack_frame_id`, and the debug locations inside the serialized Mosaic
bodies (each is parsed and printed without them). Equal texts are equal
programs, schedule and every instruction's `op_name` (its
`jax.named_scope` path, which the per-layer metrics read) included.
PR 45 read all seven steps equal this way;
`benchmarks/tools/step_text_hash.py` is the same question for the
lowered step of each cell at the tests' size.
While another process here holds libtpu: `ALLOW_MULTIPLE_LIBTPU_LOAD=1`."""
import base64
import collections
import importlib.util
import json
import os
import re
import sys

FIXTURES = ["head_steps", "jamba_step", "laguna_step", "zaya_step",
            "qwen3next_step", "ouro_step"]
_PAYLOAD = re.compile(r"[A-Za-z0-9+/=]{200,}")


def dump(out_dir, names):
    root = os.getcwd()
    sys.path.insert(0, root)
    os.makedirs(out_dir, exist_ok=True)
    os.environ["PADDLE_TPU_PALLAS_AUTOTUNE"] = "0"
    from jax._src import stages
    from jax.experimental import topologies
    spec = importlib.util.spec_from_file_location(
        "aot", os.path.join(root, "tests", "test_tpu_aot_compile.py"))
    tests = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tests)
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    kept, compile_ = [], stages.Lowered.compile

    def keep(self, *args, **kw):
        kept.append(compile_(self, *args, **kw))
        return kept[-1]

    stages.Lowered.compile = keep
    summary = {}
    for name in names:
        fixture = getattr(tests, name)
        del kept[:]
        (getattr(fixture, "__wrapped__", None)
         or fixture._get_wrapped_function())(devices)
        for i, compiled in enumerate(kept):
            text = compiled.as_text()
            memory = compiled.memory_analysis()
            with open(os.path.join(out_dir, f"{name}.{i}.txt"), "w") as f:
                f.write(text)
            summary[f"{name}.{i}"] = {
                "calls": dict(sorted(collections.Counter(re.findall(
                    r"%([A-Za-z_]\w*?)[.\d]* = .*custom-call\(.*"
                    r"tpu_custom_call", text)).items())),
                "memory": {k: getattr(memory, k) for k in dir(memory)
                           if k.endswith("_in_bytes")}}
        print(name, "compiled", flush=True)
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True)


def _text(path):
    """(the compiled text less the source tables, its Mosaic payloads
    masked; the payloads as MLIR without debug locations)."""
    from jax._src.interpreters import mlir
    text = open(path).read()
    head = text.find("\nFileNames\n")
    if head >= 0:
        text = text[:head] + text[text.index(
            "\n\n", text.index("\nStackFrames\n", head)):]
    # `op_name` (the `jax.named_scope` path the per-layer metrics read)
    # stays in; which stack frame an instruction came from does not
    text = re.sub(r' (stack_frame_id=\d+|source_file="[^"]*"|'
                  r'source_line=\d+)', "", text)
    bodies = []
    for payload in _PAYLOAD.findall(text):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            bodies.append(mlir.ir.Module.parse(base64.b64decode(
                payload)).operation.get_asm(enable_debug_info=False))
    return _PAYLOAD.sub("<mosaic>", text), bodies


def compare(dir_a, dir_b):
    a, b = (json.load(open(os.path.join(d, "summary.json")))
            for d in (dir_a, dir_b))
    same = a.keys() == b.keys()
    for key in sorted(a.keys() & b.keys()):
        text_a, bodies_a = _text(os.path.join(dir_a, key + ".txt"))
        text_b, bodies_b = _text(os.path.join(dir_b, key + ".txt"))
        verdict = {"calls": a[key]["calls"] == b[key]["calls"],
                   "memory": a[key]["memory"] == b[key]["memory"],
                   "text": text_a == text_b, "mosaic": bodies_a == bodies_b}
        same = same and all(verdict.values())
        print(key, sum(a[key]["calls"].values()), "Mosaic calls,",
              a[key]["memory"]["temp_size_in_bytes"], "bytes of temporaries:",
              ", ".join(f"{k} {'equal' if v else 'DIFFER'}"
                        for k, v in verdict.items()))
    return 0 if same else 1


if __name__ == "__main__":
    if sys.argv[1] == "dump":
        dump(sys.argv[2], sys.argv[3:] or FIXTURES)
    else:
        sys.exit(compare(sys.argv[2], sys.argv[3]))
