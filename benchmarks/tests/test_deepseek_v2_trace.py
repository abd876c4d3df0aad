"""The DeepSeek-V2 cell's readers against a trace recorded on the chip
(`tools/record_deepseek_v2_trace.py` on a TPU v5 lite: four steps of a
`TrainStep` over a dense layer and two sparse ones, 4 heads of 128
beside one shared rotary head of 64 over a 256-wide latent, 8 of 16
experts held, each layer under `jax.checkpoint` with the two-part flash
kernel's outputs kept, the first step compiling inside the session; cut
as `record_jamba_trace.py`'s docstring says)."""
import collections
import os
import sys
import types

import pytest

from harness import trace_scopes
from harness.spec import BENCH_DIR, REPO, Spec
from harness.trace_reduce import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
PATH = os.path.join(HERE, "data", "deepseek_v2.xplane.pb")
sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))
import record_deepseek_v2_trace as recorded    # noqa: E402

MODEL = "deepseekv2forcausallm/model"
LAYERS, STEPS = recorded.TINY["num_hidden_layers"], 4
NEW = ("mla_latent_ms.train", "flash_mla_roofline.train",
       "mfu_deepseek_v2.train")


def _run(path, cfg=None):
    held = recorded.HELD[1]
    return types.SimpleNamespace(
        spec=Spec(REPO),
        cfg=cfg if cfg is not None else dict(
            recorded.TINY, first_k_dense_replace=1, n_shared_experts=2,
            published={"n_routed_experts":
                       recorded.TINY["n_routed_experts"]},
            n_routed_experts=held, num_experts=held),
        mix={"batch": recorded.ROWS, "seq": recorded.SEQ},
        trace_summary=Trace.from_file(path),
        device={"kind": "TPU v5 lite"},
        tracer=types.SimpleNamespace(xplane=lambda: path),
        window={"tokens_per_step": recorded.ROWS * recorded.SEQ,
                "moe": {"moe.assignments_held": 0.5}})


@pytest.fixture(scope="module")
def run():
    """What `run.py` hands a reader, for the recorded session."""
    return _run(PATH)


def read(run, name):
    return run.spec.module("layer_metrics", name).read(run)


def test_the_two_part_kernels_run_once_a_layer_and_step(run):
    """Three layers, four steps: the forward kernel ran 12 times, once a
    layer (its outputs are kept across the recompute), the backward
    kernel as often; no plain flash kernel ran. (The step that compiled
    inside the session has its operations in the cut and no whole run
    on the modules' line.)"""
    scoped = trace_scopes.of(run)
    assert len(scoped.runs("jit_step")) == STEPS - 1
    calls = collections.Counter()
    for mid, _s, _t in scoped.ops():
        _prog, component, phase = scoped.scope(mid)
        last = component.rsplit("/", 1)[-1]
        if last.startswith("flash"):
            calls[last, phase] += 1
    assert calls == {("flash_mla_fwd", "forward"): LAYERS * STEPS,
                     ("flash_mla_bwd_transpose", "backward"): LAYERS * STEPS}


def test_the_programs_scopes_are_the_issues(run):
    table = trace_scopes.of(run).by_scope("jit_step")
    for layer in range(LAYERS):
        for part in ("input_layernorm", "attn/mla_latent/q_proj",
                     "attn/mla_latent/kv_a_proj_with_mqa",
                     "attn/mla_latent/kv_a_layernorm",
                     "attn/mla_latent/kv_b_proj", "attn/rope",
                     "attn/flash_mla_fwd", "attn/flash_mla_bwd_transpose",
                     "attn/o_proj", "post_attention_layernorm"):
            assert any(c.startswith(f"{MODEL}/layers/{layer}/{part}")
                       for c, _p in table), (layer, part)
        # every projection forward, again and back; flash only once
        for phase in ("forward", "recompute", "backward"):
            assert (f"{MODEL}/layers/{layer}/attn/mla_latent/kv_b_proj",
                    phase) in table
        assert (f"{MODEL}/layers/{layer}/attn/flash_mla_fwd",
                "recompute") not in table
    assert any(c.startswith(f"{MODEL}/layers/0/mlp/down_proj")
               for c, _p in table)
    for layer in (1, 2):
        for part in ("moe/router", "moe/shared_expert/down_proj"):
            assert any(c.startswith(f"{MODEL}/layers/{layer}/{part}")
                       for c, _p in table), (layer, part)
    assert not any("layers/0/moe" in c for c, _p in table)
    assert any("lm_head" in c.split("/") for c, _p in table)


def test_the_latents_time_is_everything_under_mla_latent(run):
    table = trace_scopes.of(run).by_scope("jit_step")
    inside = {(c, p): t for (c, p), t in table.items()
              if "mla_latent" in c.split("/")}
    value = read(run, "mla_latent_ms.train")
    assert value == pytest.approx(1e3 * sum(inside.values()))
    assert {p for _c, p in inside} >= {"forward", "recompute", "backward"}
    assert {c.split("/")[3] for c, _p in inside} == {"0", "1", "2"}
    assert 0 < value < 0.5 * read(run, "step_device_ms.train")


def test_the_flash_share_costs_every_call_of_the_two_kernels(run):
    from harness import peaks
    costs = run.spec.module("kernel_costs", "flash_mla")
    scoped = trace_scopes.of(run)
    peak = peaks.peaks("TPU v5 lite")
    least = measured = 0.0
    kinds = collections.Counter()
    for mid, _s, t in scoped.ops():
        found = costs.classify(scoped.scope(mid)[1])
        if found:
            kinds[found] += 1
            least += peaks.least_seconds(*costs.cost(
                found[0], recorded.ROWS, recorded.SEQ, 4, 128, 64, 128),
                peak)
            measured += t
    assert kinds == {(kind, layer): STEPS
                     for kind in ("fwd", "bwd") for layer in range(LAYERS)}
    share = read(run, "flash_mla_roofline.train")
    assert share == pytest.approx(100 * least / measured)
    assert 0 < share <= 100


def test_mfu_is_required_operations_over_cadence_and_peak(run):
    from harness import deepseek_v2_flops as flops
    period = run.trace_summary.module_period_s("jit_step")
    per_token = flops.train_flops_per_token(run.cfg, recorded.SEQ)
    want = 100 * per_token * recorded.ROWS * recorded.SEQ / period / 197e12
    assert read(run, "mfu_deepseek_v2.train") == pytest.approx(want)
    assert 0 < want < 100
    assert flops.held_per_token(run.cfg) == 2.0     # 4 choices, half held


def test_the_joined_readers_read_this_programs_scopes(run):
    """`step_device_ms`, `recompute_ms`, `head_loss_ms`, `rope_ms`, the
    experts' three device readers and the rest of what the cell joins."""
    for name in ("step_device_ms.train", "device_idle.train",
                 "head_loss_ms.train", "optimizer_unfused_ms.train",
                 "recompute_ms.train", "host_step_ms.train",
                 "rope_ms.train", "moe_ffn_ms.train", "moe_route_ms.train",
                 "gmm_roofline.train"):
        value = read(run, name)
        assert value is not None and value >= 0, name
    scoped = trace_scopes.of(run)
    rope = [t for mid, _s, t in scoped.ops()
            if "rope" in scoped.scope(mid)[1].split("/")]
    assert read(run, "rope_ms.train") == pytest.approx(
        1e3 * sum(rope) / STEPS, rel=0.02)
    moe = [t for mid, _s, t in scoped.ops()
           if "moe" in scoped.scope(mid)[1].split("/")]
    assert read(run, "moe_ffn_ms.train") == pytest.approx(
        1e3 * sum(moe) / STEPS, rel=0.02)
    assert 0 < read(run, "moe_route_ms.train") < read(run,
                                                      "moe_ffn_ms.train")
    assert 0 < read(run, "gmm_roofline.train") <= 100
    assert 0 < read(run, "recompute_ms.train") < read(
        run, "step_device_ms.train")
    parts = sum(read(run, n) for n in (
        "mla_latent_ms.train", "moe_ffn_ms.train", "head_loss_ms.train",
        "rope_ms.train"))
    assert parts <= read(run, "step_device_ms.train")


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("other", ["scoped.xplane.pb", "laguna.xplane.pb",
                                   "ouro.xplane.pb"])
def test_a_program_without_the_latent_gives_the_new_readers_nothing(other,
                                                                   name):
    """The GPT trace of PR 25, the Laguna trace of PR 31 and the Ouro
    trace of PR 41 hold no `mla_latent` scope and no two-part kernel,
    and their configurations no `kv_lora_rank`: the readers that look
    for them return nothing and do not raise (what the parent's traced
    runs give the driver)."""
    run = _run(os.path.join(HERE, "data", other),
               cfg={"hidden_size": 256, "num_attention_heads": 8,
                    "num_key_value_heads": 2, "head_dim": 128})
    assert not read(run, name)
