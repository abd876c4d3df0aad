"""The program's own names in the profiler's trace: every
`observability.tracing.span` is a `jax.profiler.TraceAnnotation` while a
session records; a traced program's layers run under `jax.named_scope`
and an eager call never does; `CompileTimed` counts a first call's
lowering and compile where they happen; `LLMEngine.step` splits into
phase spans."""
import collections
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import amp, nn
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.observability import perf, tracing
from paddle_tpu.optimizer import AdamW


@pytest.fixture(autouse=True)
def _obs_clean():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _host_events(trace_dir):
    """{name: count} over the host planes of the session's .xplane.pb."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    names = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


class _Session:
    """A `jax.profiler` session without the Python tracer (megabytes of
    host events no test here reads)."""

    def __init__(self, out):
        self.out = str(out)

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.out, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        return False


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------
class TestSpanIsAnAnnotation:
    def test_outside_a_session_a_disabled_span_is_the_null_object(self):
        assert tracing.span("t.outside", step=1) is tracing._NULL_SPAN
        assert tracing.events() == []

    def test_disabled_span_enters_the_profilers_trace_not_the_ring(
            self, tmp_path):
        with _Session(tmp_path):
            with tracing.span("t.annotated", step=3) as sp:
                assert sp.trace_id is None and sp.span_id is None
            sp = tracing.span("t.ended_by_hand")
            sp.__enter__()
            sp.end()
            with profiler.RecordEvent("t.record_event"):
                pass
        # no ring state was made: the null object outside again
        assert tracing.span("t.after") is tracing._NULL_SPAN
        assert tracing.events() == []
        names = _host_events(tmp_path)
        assert names["t.annotated"] == 1
        assert names["t.ended_by_hand"] == 1
        assert names["t.record_event"] == 1
        assert names["t.after"] == 0

    def test_ring_events_are_unchanged_and_annotated_too(self, tmp_path):
        def record():
            tracing.clear()
            with tracing.span("t.root", request_id="r1", k=2) as root:
                with tracing.span("t.child") as child:
                    pass
            return root, child, {e["name"]: e for e in tracing.events()}

        obs.enable()
        _r, _c, plain = record()
        with _Session(tmp_path):
            root, child, traced = record()
        for evs in (plain, traced):
            assert list(evs) == ["t.child", "t.root"]
            assert evs["t.root"]["args"] == {"request_id": "r1", "k": 2}
            assert "parent_id" not in evs["t.root"]
            assert "args" not in evs["t.child"]
            assert evs["t.child"]["parent_id"] == evs["t.root"]["span_id"]
            assert evs["t.child"]["trace_id"] == evs["t.root"]["trace_id"]
            assert set(evs["t.root"]) == {"name", "ph", "pid", "tid", "ts",
                                          "dur", "trace_id", "span_id",
                                          "args"}
        assert traced["t.root"]["span_id"] == root.span_id
        assert child.parent_id == root.span_id
        names = _host_events(tmp_path)
        assert names["t.root"] == 1 and names["t.child"] == 1

    def test_profiler_session_yields_one_timeline(self, tmp_path,
                                                  monkeypatch):
        """`Profiler` starts the ring and `jax.profiler`: a span opened
        under it is in both, under one name."""
        monkeypatch.setenv("PADDLE_TPU_TRACE_DIR", str(tmp_path))
        with profiler.Profiler() as prof:
            with profiler.RecordEvent("t.both"):
                pass
        assert [e["name"] for e in prof.events()] == ["t.both"]
        assert _host_events(tmp_path)["t.both"] == 1


# ---------------------------------------------------------------------------
# device time by the program's own components: scopes in op_names
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_step():
    """Two blocks, `jax.checkpoint` on the first, tied head, amp."""
    pt.seed(0)
    model = GPTForCausalLM(gpt_tiny(recompute=True, recompute_interval=2))
    model.train()
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels)

    return TrainStep(model, opt, loss_fn)


@pytest.fixture(scope="module")
def step_op_names(tiny_step):
    ids = np.zeros((2, 32), np.int32)
    lowered = tiny_step._step_fn.jit_fn.lower(
        tiny_step.params, tiny_step.opt_states, tiny_step.buffers,
        jax.random.PRNGKey(0), jnp.float32(1e-3), [ids, ids], {})
    return set(re.findall(r'op_name="([^"]*)"',
                          lowered.compile().as_text()))


ROOT = "gptforcausallm"


@pytest.mark.parametrize("fragment", [
    # forward, backward, what jax.checkpoint runs again; plain and
    # checkpointed blocks; the tied head, the criterion, the update
    f"jit(step)/jvp({ROOT})/gpt/layers/1/attn/qkv_proj/",
    f"jit(step)/jvp({ROOT})/gpt/layers/1/mlp/fc1/",
    f"jit(step)/transpose(jvp({ROOT}))/gpt/layers/1/attn/out_proj/",
    f"jit(step)/transpose(jvp({ROOT}))/gpt/layers/1/mlp/fc2/",
    f"/gpt/checkpoint/layers/0/attn/",
    f"/gpt/checkpoint/layers/0/mlp/fc1/",
    f"/gpt/checkpoint/rematted_computation/layers/0/attn/qkv_proj/",
    f"/gpt/checkpoint/rematted_computation/layers/0/mlp/",
    f"jit(step)/jvp({ROOT})/gpt/embeddings/word_embeddings/",
    f"jit(step)/jvp({ROOT})/gpt/final_norm/",
    # the head and the loss in chunks (ops.linear_cross_entropy): every
    # matmul of the head runs in the forward's loop, under the
    # criterion's scope and the head's; the backward only scales
    "jit(step)/jvp(gptpretrainingcriterion)/lm_head/while/body/"
    "closed_call/dot_general",
    "jit(step)/jvp(gptpretrainingcriterion)/lm_head/while/body/"
    "closed_call/exp",
    "jit(step)/transpose(jvp(gptpretrainingcriterion))/lm_head/",
    "jit(step)/optimizer/",
])
def test_a_traced_steps_op_names_carry_the_programs_paths(step_op_names,
                                                          fragment):
    assert any(fragment in n for n in step_op_names), fragment


def test_no_head_operation_of_the_fused_step_lies_outside_its_scopes(
        step_op_names):
    """`head_loss_ms.train` finds the head by `lm_head` or `criterion`
    in the path: every dot_general outside the blocks has one."""
    dots = [n for n in step_op_names
            if n.endswith("dot_general") and "/layers/" not in n]
    assert dots and all("lm_head" in n and "criterion" in n for n in dots)


@pytest.fixture(scope="module")
def whole_step_op_names():
    """The same step, its loss function reading the logits itself."""
    pt.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    model.train()
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return pt.ops.mean(pt.ops.cross_entropy(logits, labels,
                                                reduction="none"))

    step = TrainStep(model, opt, loss_fn)
    ids = np.zeros((2, 32), np.int32)
    perf._FAMILY_COMPILE.pop("train_step", None)
    step(ids, ids)
    lowered = step._step_fn.jit_fn.lower(
        step.params, step.opt_states, step.buffers,
        jax.random.PRNGKey(0), jnp.float32(1e-3), [ids, ids], {})
    return (perf.compile_record("train_step"),
            set(re.findall(r'op_name="([^"]*)"',
                           lowered.compile().as_text())))


@pytest.mark.parametrize("fragment", [
    # the whole product is computed where it is first read, under the
    # head's name
    "jit(step)/jvp(lm_head)/dot_general",
    "jit(step)/transpose(jvp(lm_head))/dot_general",
])
def test_whole_logits_keep_the_heads_name(whole_step_op_names, fragment):
    record, names = whole_step_op_names
    assert record["head_loss"] == "whole"
    assert any(fragment in n for n in names), fragment
    assert not any("lm_head/while" in n for n in names)


def test_the_compile_record_says_which_path_the_head_took():
    """`tiny_step`'s loss function is the benchmark driver's: logits
    under `auto_cast`, the criterion outside."""
    pt.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    model.train()
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels)

    step = TrainStep(model, AdamW(learning_rate=1e-3,
                                  parameters=model.parameters()), loss_fn)
    perf._FAMILY_COMPILE.pop("train_step", None)
    ids = np.zeros((2, 32), np.int32)
    step(ids, ids)
    assert perf.compile_record("train_step")["head_loss"] == \
        "fused, chunks 1"


def test_the_flash_kernels_name_themselves():
    """In interpret mode, as on the chip: `name=` is the innermost scope,
    and XLA names the custom call after it. `transpose` in the backward
    kernel's name is what benchmarks/kernel_costs/flash.py tells it by."""
    from importlib import import_module
    fa = import_module("paddle_tpu.kernels.pallas.flash_attention")
    H, D = 2, 128
    q = jnp.ones((1, 256, H * D), jnp.float32)
    lse = jnp.zeros((1, H * fa._SUBL, 256), jnp.float32)

    def fwd(q):
        with jax.named_scope("attn"):
            return fa._flash_fwd_fused(q, q, q, H, True, interpret=True,
                                       autotune_ok=False)

    def bwd(q):
        with jax.named_scope("attn"):
            return fa._flash_bwd_fused(q, q, q, q, lse, q, H, True,
                                       interpret=True, autotune_ok=False)

    text = jax.jit(fwd).lower(q).as_text(debug_info=True)
    assert "attn/flash_fwd/pallas_call" in text
    text = jax.jit(bwd).lower(q).as_text(debug_info=True)
    assert "attn/flash_bwd_transpose/pallas_call" in text


def test_layers_take_the_name_their_parent_holds_them_by():
    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.proj = nn.Linear(4, 4)

        def forward(self, x):
            return self.proj(x)

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.blocks = nn.LayerList([Block(), Block()])
            self.again = self.blocks[0]         # held twice: first name
            self.seq = nn.Sequential(nn.Linear(4, 4))
            self.add_sublayer("extra", Block())

        def forward(self, x):
            for b in self.blocks:
                x = b(x)
            return self.extra(self.seq(self.again(x)))

    net = Net()
    assert net.scope_name() == "net"            # a root: its class
    # a list is never called: its layers carry its name before theirs
    assert [b.scope_name() for b in net.blocks] == ["blocks/0", "blocks/1"]
    assert net.again.scope_name() == "blocks/0"
    assert net.seq.scope_name() == "seq"
    assert net.seq[0].scope_name() == "0"       # a Sequential is called
    assert net.extra.scope_name() == "extra"
    assert net.blocks[1].proj.scope_name() == "proj"

    from paddle_tpu.jit import _collect_params, _functional_params
    _n, ptensors, _b, _bt = _collect_params(net)

    def fn(params, x):
        with _functional_params(ptensors, params):
            return net(pt.Tensor._wrap(x))._data

    text = jax.jit(fn).lower([p._data for p in ptensors],
                             jnp.ones((2, 4))).as_text(debug_info=True)
    for path in ("net/blocks/0/proj/", "net/blocks/1/proj/",
                 "net/seq/0/", "net/extra/proj/"):
        assert path in text, path


def _append(holder, layer):
    holder.append(layer)


def _setitem(holder, layer):
    holder["late"] = layer


def _nested(holder, layer):
    holder.append(nn.LayerList([layer]))


def _replace(holder, layer):
    holder.append(nn.Linear(2, 2))
    holder[0] = layer


@pytest.mark.parametrize("make,join,want", [
    (nn.LayerList, _append, "layers/0"),
    (nn.LayerDict, _setitem, "layers/late"),
    (nn.LayerList, _nested, "layers/0/0"),
    (nn.LayerList, _replace, "layers/0"),
], ids=["list-append", "dict-setitem", "nested-list", "list-setitem"])
@pytest.mark.parametrize("attach_first", [True, False],
                         ids=["after-attach", "before-attach"])
def test_a_holders_layers_carry_its_name_whenever_they_join(
        make, join, want, attach_first):
    """`self.layers = LayerList()` and then `.append(block)` in a loop is
    how most models are written: the block is `layers/3` either way."""
    net, holder, block = nn.Layer(), make(), nn.Linear(2, 2)
    if attach_first:
        net.layers = holder
    join(holder, block)
    if not attach_first:
        net.layers = holder
    assert block.scope_name() == want


def test_the_tracing_mark_is_per_thread(monkeypatch):
    """While one thread traces a program (an engine prewarm), an eager
    call on another enters no scope."""
    import threading
    from paddle_tpu.jit import _collect_params, _functional_params
    entered = []
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: entered.append(name) or real(name))
    lin = nn.Linear(4, 4)
    x = pt.Tensor(np.ones((2, 4), np.float32))
    _n, ptensors, _b, _bt = _collect_params(lin)
    with _functional_params(ptensors, [p._data for p in ptensors]):
        other = threading.Thread(target=lin, args=(x,))
        other.start()
        other.join()
        assert entered == []
        lin(x)
    assert entered == ["linear"]


def test_an_eager_layer_call_enters_no_scope(monkeypatch):
    """Eager dispatch cost must not change: no `jax.named_scope` there."""
    from paddle_tpu.jit import _collect_params, _functional_params
    entered = []
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: entered.append(name) or real(name))
    lin = nn.Linear(4, 4)
    x = pt.Tensor(np.ones((2, 4), np.float32))
    lin(x)
    assert entered == []
    _n, ptensors, _b, _bt = _collect_params(lin)
    with _functional_params(ptensors, [p._data for p in ptensors]):
        lin(x)
    assert entered == ["linear"]
    lin(x)                                      # the mark is put down
    assert entered == ["linear"]


# ---------------------------------------------------------------------------
# TrainStep's spans, and the compile counted where it happens
# ---------------------------------------------------------------------------
def test_train_step_spans_and_the_compile_record(tiny_step):
    """Metrics are never enabled here; the ring is, to read the spans."""
    perf._FAMILY_COMPILE.pop("train_step", None)
    assert perf.compile_record("train_step") is None
    tracing.enable()
    ids = np.zeros((2, 32), np.int32)
    for _ in range(3):
        tiny_step(ids, ids)
    tracing.disable()
    rec = perf.compile_record("train_step")
    assert rec["compiles"] == 1 and rec["outcome"] == "compile"
    assert rec["lower_s"] > 0 and rec["backend_s"] > 0
    assert rec["first_run_s"] >= 0
    rec["lower_s"] = -1.0                       # a copy: the record is safe
    rec = perf.compile_record("train_step")
    assert rec["lower_s"] > 0

    evs = tracing.events()
    roots = [e for e in evs if e["name"] == "train_step"]
    assert [e["args"]["step"] for e in roots] == sorted(
        e["args"]["step"] for e in roots) and len(roots) == 3
    for root in roots:
        kids = [e for e in evs if e.get("parent_id") == root["span_id"]]
        assert [k["name"] for k in kids] == ["train_step.feed",
                                             "train_step.dispatch"]
        assert all(k["args"]["step"] == root["args"]["step"] for k in kids)
        assert sum(k["dur"] for k in kids) <= root["dur"]
    # the first dispatch holds the three phases of the first call
    first = next(e for e in evs if e["name"] == "train_step.dispatch")
    phases = [e for e in evs if e.get("parent_id") == first["span_id"]]
    assert [p["name"] for p in phases] == [
        "compile.lower", "compile.backend", "compile.first_run"]
    assert all(p["args"] == {"family": "train_step"} for p in phases)
    # the trace to a jaxpr is a span of its own inside the lowering's
    trace, = [e for e in evs if e.get("parent_id") == phases[0]["span_id"]]
    assert trace["name"] == "compile.trace"
    assert trace["args"] == {"family": "train_step"}
    assert sum(e["name"].startswith("compile.") for e in evs) == 4
    assert phases[0]["dur"] * 1e-6 == pytest.approx(rec["lower_s"], rel=0.05)
    assert trace["dur"] * 1e-6 == pytest.approx(rec["trace_s"], rel=0.05)
    assert 0 < rec["trace_s"] < rec["lower_s"]


def test_set_ups_phases_are_spans_by_their_names():
    """With the ring on, what `perf.setup_record()` counts is also a
    `setup.<phase>` span: sublayers' constructors and the initialisers
    inside their parent's, the accumulators inside `TrainStep.__init__`."""
    tracing.enable()
    pt.seed(0)
    model = nn.Sequential(nn.Linear(4, 4), nn.Linear(4, 2))
    step = TrainStep(model, AdamW(learning_rate=1e-3,
                                  parameters=model.parameters()),
                     lambda m, x: pt.ops.mean(m(x)))
    tracing.disable()
    evs = tracing.events()
    by_id = {e["span_id"]: e for e in evs}

    def parents(name):
        return {by_id[e["parent_id"]]["name"] if "parent_id" in e else None
                for e in evs if e["name"] == name}

    assert {e["name"] for e in evs} == {
        "setup.build.model", "setup.build.params", "setup.build.optimizer",
        "setup.build.train_step"}
    # Sequential's own constructor is the root; Layer.__init__ and the
    # two Linears' constructors run inside one
    assert parents("setup.build.model") == {None, "setup.build.model"}
    assert parents("setup.build.params") == {"setup.build.model"}
    assert parents("setup.build.optimizer") == {"setup.build.train_step"}
    assert parents("setup.build.train_step") == {None}
    assert sum(e["name"] == "setup.build.params" for e in evs) == 4
    assert sum(e["name"] == "setup.build.optimizer" for e in evs) == 4
    assert step._step_fn.pending                # nothing was compiled


# ---------------------------------------------------------------------------
# the engine's step by phase
# ---------------------------------------------------------------------------
def test_engine_step_splits_into_phase_spans():
    from paddle_tpu.inference import LLMEngine
    pt.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    eng = LLMEngine(model, max_batch=2, block_size=16, decode_chunk=4,
                    prompt_quantum=16, max_model_len=64)
    rng = np.random.default_rng(0)
    for rid, n in enumerate((5, 9)):
        eng.add_request(rid, rng.integers(0, 1024, (n,)).astype(np.int32),
                        max_new_tokens=6)
    tracing.enable()
    eng.step()
    tracing.disable()
    evs = tracing.events()
    step, = [e for e in evs if e["name"] == "engine.step"]
    by_id = {e["span_id"]: e for e in evs if "span_id" in e}
    kids = [e for e in evs if e.get("parent_id") == step["span_id"]]
    assert [k["name"] for k in kids] == [
        "engine.schedule", "engine.prefill", "engine.commit",
        "engine.decode_chunk", "engine.commit"]
    assert sum(k["dur"] for k in kids) <= step["dur"]
    assert [k["args"]["of"] for k in kids
            if k["name"] == "engine.commit"] == ["prefill", "decode"]

    def children(parent):
        return [e["name"] for e in evs
                if e.get("parent_id") == parent["span_id"]]

    assert children(kids[1]) == ["engine.pack", "engine.ragged"]
    # the decode program's first call compiles under its launch
    assert children(kids[3]) == [
        "engine.schedule", "engine.pack", "compile.lower",
        "compile.backend", "compile.first_run"]
    lower, = [e for e in evs if e["name"] == "compile.lower"
              and e.get("parent_id") == kids[3]["span_id"]]
    assert children(lower) == ["compile.trace"]
    assert perf.compile_record("engine_decode")["compiles"] >= 1
    # every engine span of the step hangs under engine.step
    for e in evs:
        if e["name"].startswith("engine.") and e is not step:
            top = e
            while top.get("parent_id") in by_id:
                top = by_id[top["parent_id"]]
            assert top is step, e["name"]
