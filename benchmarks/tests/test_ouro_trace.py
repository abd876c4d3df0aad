"""The Ouro cell's readers against a trace recorded on the chip
(`tools/record_ouro_trace.py` on a TPU v5 lite: four steps of a
`TrainStep` over two layers of 4 heads of 128 run three times over as
one scanned body, each layer and the final norm under `jax.checkpoint`
with flash's outputs kept, the first step compiling inside the session;
cut as `record_jamba_trace.py`'s docstring says). What the trace has to
show: an operation inside the `while` body is one event for each time it
ran, under the scope of the one layer that holds it."""
import collections
import os
import sys
import types

import pytest

from harness import trace_scopes
from harness.spec import BENCH_DIR, REPO, Spec
from harness.trace_reduce import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
PATH = os.path.join(HERE, "data", "ouro.xplane.pb")
sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))
import record_ouro_trace as recorded    # noqa: E402

BODY = "ouroforcausallm/model/ut_loop/while/body/closed_call"
LAYERS, PASSES, STEPS = (recorded.TINY["num_hidden_layers"],
                         recorded.TINY["total_ut_steps"], 4)
NEW = ("mfu_ouro.train", "ut_loop_ms.train", "ut_grad_sum_ms.train",
       "exit_mix_ms.train", "flash_ouro_roofline.train")


def _run(path, cfg=None):
    return types.SimpleNamespace(
        spec=Spec(REPO), cfg=cfg if cfg is not None else dict(recorded.TINY),
        mix={"batch": recorded.ROWS, "seq": recorded.SEQ},
        trace_summary=Trace.from_file(path),
        device={"kind": "TPU v5 lite"},
        tracer=types.SimpleNamespace(xplane=lambda: path),
        window={"tokens_per_step": recorded.ROWS * recorded.SEQ})


@pytest.fixture(scope="module")
def run():
    """What `run.py` hands a reader, for the recorded session."""
    return _run(PATH)


def read(run, name):
    return run.spec.module("layer_metrics", name).read(run)


def test_an_operation_in_the_body_is_an_event_each_time_it_ran(run):
    """Two layers, three passes, four steps: the flash forward kernel
    ran 24 times, once a layer application (its outputs are kept), the
    backward kernel as often, the rotary 6 times a layer application (q
    and k: forward, again, back)."""
    scoped = trace_scopes.of(run)
    assert len(scoped.runs("jit_step")) == STEPS
    calls = collections.Counter()
    for mid, _s, _t in scoped.ops():
        _prog, component, phase = scoped.scope(mid)
        last = component.rsplit("/", 1)[-1]
        if last.startswith(("flash", "rope_rotate")):
            calls[last, phase] += 1
    applications = LAYERS * PASSES * STEPS
    assert calls == {("flash_fwd", "forward"): applications,
                     ("flash_bwd_transpose", "backward"): applications,
                     ("rope_rotate", "forward"): 2 * applications,
                     ("rope_rotate", "recompute"): 2 * applications,
                     ("rope_rotate", "backward"): 2 * applications}


def test_the_programs_scopes_are_the_issues(run):
    table = trace_scopes.of(run).by_scope("jit_step")
    for layer in range(LAYERS):
        for part in ("input_layernorm", "attn/q_proj", "attn/k_proj",
                     "attn/v_proj", "attn/rope", "attn/flash_fwd",
                     "attn/flash_bwd_transpose", "attn/o_proj",
                     "input_layernorm_2", "post_attention_layernorm",
                     "mlp/gate_proj", "mlp/up_proj", "mlp/down_proj",
                     "post_attention_layernorm_2"):
            assert any(c.startswith(f"{BODY}/layers/{layer}/{part}")
                       for c, _p in table), (layer, part)
        # every matrix forward, again and back; flash only once
        for phase in ("forward", "recompute", "backward"):
            assert (f"{BODY}/layers/{layer}/mlp/down_proj", phase) in table
        assert (f"{BODY}/layers/{layer}/attn/flash_fwd",
                "recompute") not in table
    assert any(c.startswith(f"{BODY}/norm") for c, _p in table)
    assert any(c == "ouroforcausallm/exit_gate" for c, _p in table)
    assert any(c.startswith("ouropretrainingcriterion/exit_loss")
               for c, _p in table)
    assert any(c.startswith("ouropretrainingcriterion/lm_head")
               for c, _p in table)
    # a layer's scope stands once in the program: no layers/2
    assert not any("layers/2" in c for c, _p in table)


def test_the_loops_time_is_everything_under_ut_loop(run):
    table = trace_scopes.of(run).by_scope("jit_step")
    inside = {(c, p): t for (c, p), t in table.items()
              if "ut_loop" in c.split("/")}
    loop = read(run, "ut_loop_ms.train")
    assert loop == pytest.approx(1e3 * sum(inside.values()))
    assert {p for _c, p in inside} == {"forward", "recompute", "backward"}
    step = read(run, "step_device_ms.train")
    assert 0.5 * step < loop < step
    # what the loop adds beside the layers: the residuals' stacks, the sums
    own = 1e3 * sum(t for (c, _p), t in inside.items()
                    if "layers" not in c.split("/"))
    assert 0 < own < loop


def test_the_gradient_sums_are_the_loops_own_add_any(run):
    """Here every sum stands alone (tiny matrices: XLA folds none into a
    product): one `add_any` a parameter of the stack a pass back, less
    the first pass back, whose sum with zero XLA drops."""
    reader = run.spec.module("layer_metrics", "ut_grad_sum_ms.train")
    assert reader.is_grad_sum(
        "jit(step)/transpose(jvp(ouroforcausallm))/model/ut_loop/while/"
        "body/closed_call/add_any")
    for other in (
            "jit(step)/transpose(jvp(ouroforcausallm))/model/ut_loop/while/"
            "body/closed_call/checkpoint/layers/7/mlp/jit(silu)/add_any",
            "jit(step)/transpose(jvp(ouroforcausallm))/model/ut_loop/while/"
            "body/closed_call/checkpoint/norm/add_any",
            "jit(step)/transpose(jvp(ouropretrainingcriterion))/exit_loss/"
            "add_any",
            "jit(step)/transpose(jvp(ouroforcausallm))/model/ut_loop/while/"
            "body/closed_call/checkpoint/layers/0/mlp/up_proj/dot_general",
            "jit(step)/optimizer/add"):
        assert not reader.is_grad_sum(other), other
    scoped = trace_scopes.of(run)
    sums = [t for mid, _s, t in scoped.ops() if reader.is_grad_sum(
        trace_scopes.op_name_of(
            scoped.plane.event_stats[mid].get("tf_op", "")))]
    assert len(sums) % (PASSES * STEPS) == 0 and len(sums) >= PASSES * STEPS
    value = read(run, "ut_grad_sum_ms.train")
    assert value == pytest.approx(1e3 * sum(sums) / STEPS)
    assert 0 < value < 0.05 * read(run, "ut_loop_ms.train")


def test_the_exit_mix_is_the_gate_and_the_exit_loss(run):
    table = trace_scopes.of(run).by_scope("jit_step")
    want = 1e3 * sum(t for (c, _p), t in table.items()
                     if {"exit_gate", "exit_loss"} & set(c.split("/")))
    assert read(run, "exit_mix_ms.train") == pytest.approx(want)
    assert 0 < want < 0.02 * read(run, "step_device_ms.train")


def test_the_flash_share_costs_every_call_of_the_one_kernel(run):
    from harness import peaks
    fw = run.spec.module("kernel_costs", "flash_window")
    scoped = trace_scopes.of(run)
    peak = peaks.peaks("TPU v5 lite")
    least = measured = 0.0
    kinds = collections.Counter()
    for mid, _s, t in scoped.ops():
        found = fw.classify(scoped.scope(mid)[1])
        if found:
            kinds[found] += 1
            least += peaks.least_seconds(*fw.cost(
                found[0], recorded.ROWS, recorded.SEQ, 4, 4, 128), peak)
            measured += t
    # a layer's kernel is one instruction and PASSES x STEPS events
    assert kinds == {(kind, layer): PASSES * STEPS
                     for kind in ("fwd", "bwd") for layer in range(LAYERS)}
    share = read(run, "flash_ouro_roofline.train")
    assert share == pytest.approx(100 * least / measured)
    assert 0 < share <= 100


def test_mfu_is_required_operations_over_cadence_and_peak(run):
    from harness import ouro_flops
    period = run.trace_summary.module_period_s("jit_step")
    per_token = ouro_flops.train_flops_per_token(run.cfg, recorded.SEQ)
    want = 100 * per_token * recorded.ROWS * recorded.SEQ / period / 197e12
    assert read(run, "mfu_ouro.train") == pytest.approx(want)
    assert 0 < want < 100


def test_the_joined_readers_read_the_body_once_for_each_time_it_ran(run):
    """`step_device_ms`, `recompute_ms`, `head_loss_ms` and `rope_ms`
    (and the rest of what the cell joins) on operations inside the
    `while` body: per step, every pass counted."""
    for name in ("step_device_ms.train", "device_idle.train",
                 "head_loss_ms.train", "optimizer_unfused_ms.train",
                 "recompute_ms.train", "host_step_ms.train",
                 "rope_ms.train"):
        value = read(run, name)
        assert value is not None and value >= 0, name
    scoped = trace_scopes.of(run)
    rope = [t for mid, _s, t in scoped.ops()
            if "rope" in scoped.scope(mid)[1].split("/")]
    assert len(rope) >= 6 * LAYERS * PASSES * STEPS
    assert read(run, "rope_ms.train") == pytest.approx(
        1e3 * sum(rope) / STEPS)
    again = [t for mid, _s, t in scoped.ops()
             if scoped.scope(mid)[2] in ("recompute", "xla_remat")]
    assert read(run, "recompute_ms.train") == pytest.approx(
        1e3 * sum(again) / STEPS)
    assert 0 < read(run, "recompute_ms.train") < read(run,
                                                      "ut_loop_ms.train")
    assert 0 < read(run, "head_loss_ms.train") < read(
        run, "step_device_ms.train")
    # the whole update stands outside the gradients' fusions here
    assert read(run, "optimizer_unfused_ms.train") > 0
    parts = sum(read(run, n) for n in ("ut_loop_ms.train",
                                       "head_loss_ms.train",
                                       "optimizer_unfused_ms.train",
                                       "exit_mix_ms.train"))
    assert parts <= read(run, "step_device_ms.train")


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("other", ["scoped.xplane.pb", "laguna.xplane.pb",
                                   "qwen3next.xplane.pb"])
def test_a_program_without_the_loop_gives_the_new_readers_nothing(other,
                                                                 name):
    """The GPT trace of PR 25, the Laguna trace of PR 31 and the
    Qwen3-Next trace of PR 39 hold no `ut_loop`, `exit_gate` or
    `exit_loss` scope and their configurations no `total_ut_steps`: the
    readers that look for them return nothing and do not raise (what the
    parent's traced runs give the driver)."""
    run = _run(os.path.join(HERE, "data", other),
               cfg={"hidden_size": 256, "num_attention_heads": 8,
                    "num_key_value_heads": 2, "head_dim": 128})
    assert not read(run, name)
