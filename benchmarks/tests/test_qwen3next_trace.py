"""The Qwen3-Next cell's readers against a trace recorded on the chip
(`tools/record_qwen3next_trace.py` on a TPU v5 lite: four steps of a
`TrainStep` over one Gated DeltaNet layer and one gated attention layer
at head size 256, each over a softmax-routed sparse feed-forward and
under `jax.checkpoint`, the first step compiling inside the session; cut
as `record_jamba_trace.py`'s docstring says)."""
import os
import sys
import types

import pytest

from harness import peaks, trace_scopes
from harness.spec import BENCH_DIR, REPO, Spec
from harness.trace_reduce import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
PATH = os.path.join(HERE, "data", "qwen3next.xplane.pb")
sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))
import record_qwen3next_trace as recorded    # noqa: E402

ROOT = "qwen3nextforcausallm/model/layers"
HELD_SHARE = 0.5        # 4 of 8 experts held, uniform in expectation
NEW = ("gdn_mixer_ms.train", "gdn_state_ms.train",
       "gdn_state_roofline.train", "flash_d256_roofline.train",
       "gdn_prepare_ms.train")


def _run(path, cfg=None, **window):
    cfg = cfg if cfg is not None else dict(
        recorded.TINY, num_experts=recorded.HELD[1],
        linear_key_head_dim=128, linear_value_head_dim=128,
        published={"num_experts": 8})
    return types.SimpleNamespace(
        spec=Spec(REPO), cfg=cfg,
        mix={"batch": recorded.ROWS, "seq": recorded.SEQ},
        trace_summary=Trace.from_file(path),
        device={"kind": "TPU v5 lite"},
        tracer=types.SimpleNamespace(xplane=lambda: path),
        window={"tokens_per_step": recorded.ROWS * recorded.SEQ, **window})


@pytest.fixture(scope="module")
def run():
    """What `run.py` hands a reader, for the recorded session."""
    return _run(PATH, moe={"moe.assignments_held": HELD_SHARE,
                           "moe.load_max_over_mean": 1.2})


def read(run, name):
    return run.spec.module("layer_metrics", name).read(run)


def test_the_programs_scopes_are_the_issues(run):
    table = trace_scopes.of(run).by_scope("jit_step")
    kernels = {(c, p) for c, p in table if c.rsplit("/", 1)[-1].startswith(
        ("gdn_state", "flash", "moe_gmm"))}
    rule = f"{ROOT}/0/gdn/delta_rule"
    want = {(f"{rule}/gdn_state_fwd", "forward"),
            (f"{rule}/gdn_state_fwd", "recompute"),
            (f"{rule}/gdn_state_bwd", "backward"),
            # the full layer's block keeps its flash outputs: no second run
            (f"{ROOT}/1/attn/flash_fwd", "forward"),
            (f"{ROOT}/1/attn/flash_bwd_transpose", "backward")}
    for layer in (0, 1):
        want |= {(f"{ROOT}/{layer}/moe/experts/moe_gmm", p)
                 for p in ("forward", "recompute", "backward")}
        want.add((f"{ROOT}/{layer}/moe/experts/moe_gmm_dw", "backward"))
    assert kernels == want
    for part in ("0/gdn/in_proj_qkvz", "0/gdn/in_proj_ba", "0/gdn/conv",
                 "0/gdn/gates", "0/gdn/delta_rule",
                 "0/gdn/delta_rule/prepare", "0/gdn/gated_norm",
                 "0/gdn/out_proj", "1/attn/q_proj", "1/attn/q_norm",
                 "1/attn/k_norm", "1/attn/rope", "1/attn/out_gate",
                 "1/attn/o_proj", "1/moe/router", "1/moe/permute",
                 "1/moe/experts", "1/moe/combine", "1/moe/shared_expert",
                 "0/moe/shared_expert_gate"):
        assert any(c.startswith(f"{ROOT}/{part}") for c, _p in table), part
    assert not any("/gdn" in c for c, _p in table if f"{ROOT}/1/" in c)


def test_the_mixers_time_is_everything_under_gdn(run):
    table = trace_scopes.of(run).by_scope("jit_step")
    mixer = 1e3 * sum(t for (c, _p), t in table.items()
                      if "gdn" in c.split("/"))
    phases = {p for (c, p) in table if "gdn" in c.split("/")}
    assert read(run, "gdn_mixer_ms.train") == pytest.approx(mixer)
    assert {"forward", "backward", "recompute"} <= phases
    assert 0 < mixer < 1e3 * sum(table.values())


def test_the_preparation_is_its_scope_in_every_phase(run):
    """`prepare` stands under `delta_rule` beside the kernels, forward,
    run again and backward (where it is made once more and
    differentiated); with the kernels it is nearly all of the rule."""
    rule = run.spec.module("kernel_costs", "gated_delta")
    table = trace_scopes.of(run).by_scope("jit_step")
    inside = {(c, p): t for (c, p), t in table.items()
              if {"gdn", "prepare"} <= set(c.split("/"))}
    assert all(f"{ROOT}/0/gdn/delta_rule/prepare" in c for c, _p in inside)
    assert not any(rule.classify(c) for c, _p in inside)
    assert {p for _c, p in inside} >= {"forward", "recompute", "backward"}
    prepare = 1e3 * sum(inside.values())
    assert read(run, "gdn_prepare_ms.train") == pytest.approx(prepare)
    whole = 1e3 * sum(t for (c, _p), t in table.items()
                      if "delta_rule" in c.split("/"))
    kernels = read(run, "gdn_state_ms.train")
    assert 0 < prepare and 0.9 * whole < prepare + kernels <= whole + 1e-9


def test_the_state_kernels_time_and_share(run):
    rule = run.spec.module("kernel_costs", "gated_delta")
    scoped = trace_scopes.of(run)
    calls = {"fwd": [], "bwd": []}
    for mid, _s, t in scoped.ops():
        kind = rule.classify(scoped.scope(mid)[1])
        if kind:
            calls[kind].append(t)
    # one linear layer, four steps: forward and again, once backward
    assert (len(calls["fwd"]), len(calls["bwd"])) == (8, 4)
    table = scoped.by_scope("jit_step")
    kernels = 1e3 * sum(t for (c, _p), t in table.items()
                        if rule.classify(c))
    assert read(run, "gdn_state_ms.train") == pytest.approx(kernels)
    assert 0 < kernels < read(run, "gdn_mixer_ms.train")
    peak = peaks.peaks("TPU v5 lite")
    shape = (recorded.ROWS * recorded.TINY["linear_num_value_heads"],
             recorded.SEQ // 64, 64, 128, 128)
    least = sum(peaks.least_seconds(*rule.cost(kind, *shape), peak)
                * len(ts) for kind, ts in calls.items())
    share = read(run, "gdn_state_roofline.train")
    assert share == pytest.approx(
        100 * least / sum(calls["fwd"] + calls["bwd"]))
    assert 0 < share <= 100


def test_the_flash_share_counts_heads_of_256(run):
    fw = run.spec.module("kernel_costs", "flash_window")
    scoped = trace_scopes.of(run)
    least = measured = 0.0
    peak = peaks.peaks("TPU v5 lite")
    kinds = []
    for mid, _s, t in scoped.ops():
        found = fw.classify(scoped.scope(mid)[1])
        if found:
            kinds.append(found)
            least += peaks.least_seconds(*fw.cost(
                found[0], recorded.ROWS, recorded.SEQ, 4, 2, 256), peak)
            measured += t
    assert sorted(kinds) == [("bwd", 1)] * 4 + [("fwd", 1)] * 4
    share = read(run, "flash_d256_roofline.train")
    assert share == pytest.approx(100 * least / measured)
    assert 0 < share <= 100


def test_mfu_is_required_operations_over_cadence_and_peak(run):
    from harness import qwen3next_flops
    period = run.trace_summary.module_period_s("jit_step")
    per_token = qwen3next_flops.train_flops_per_token(run.cfg, recorded.SEQ)
    want = 100 * per_token * recorded.ROWS * recorded.SEQ / period / 197e12
    assert read(run, "mfu_qwen3next.train") == pytest.approx(want)
    assert 0 < want < 100
    assert read(run, "moe_load_max_over_mean.train") == 1.2


def test_the_joined_readers_read_this_trace_too(run):
    for name in ("step_device_ms.train", "device_idle.train",
                 "head_loss_ms.train", "optimizer_unfused_ms.train",
                 "recompute_ms.train", "host_step_ms.train",
                 "moe_ffn_ms.train", "moe_route_ms.train",
                 "gmm_roofline.train", "rope_ms.train"):
        value = read(run, name)
        assert value is not None and value >= 0, name
    assert read(run, "recompute_ms.train") > 0      # every block runs again
    assert read(run, "rope_ms.train") > 0           # the full layer's q, k
    assert 0 < read(run, "moe_route_ms.train") < read(run,
                                                      "moe_ffn_ms.train")
    assert 0 < read(run, "gmm_roofline.train") <= 100


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("other", ["scoped.xplane.pb", "laguna.xplane.pb",
                                   "zaya.xplane.pb"])
def test_a_program_without_the_mixer_gives_the_new_readers_nothing(other,
                                                                  name):
    """The GPT trace of PR 25, the Laguna trace of PR 31 and the ZAYA1
    trace of PR 35 hold no `gdn` scope and their configurations no
    `linear_num_value_heads` or `full_attention_interval`: the readers
    that look for them return nothing and do not raise."""
    run = _run(os.path.join(HERE, "data", other),
               cfg={"num_experts": 8, "hidden_size": 256,
                    "num_attention_heads": 8, "num_key_value_heads": 2,
                    "head_dim": 128})
    assert not read(run, name)
