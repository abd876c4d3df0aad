"""The ZAYA1 cell's readers against a trace recorded on the chip
(`tools/record_zaya_trace.py` on a TPU v5 lite: four steps of a
`TrainStep` over two layers of compressed convolutional attention and
one-choice sparse feed-forwards, each under `jax.checkpoint`, the first
step compiling inside the session; cut as `record_jamba_trace.py`'s
docstring says)."""
import os
import sys
import types

import pytest

from harness import peaks, trace_scopes
from harness.spec import BENCH_DIR, REPO, Spec
from harness.trace_reduce import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
PATH = os.path.join(HERE, "data", "zaya.xplane.pb")
sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))
import record_zaya_trace as recorded    # noqa: E402

ROOT = "zayaforcausallm/zaya/layers"
HELD_SHARE = 0.5        # 2 of 4 experts held, uniform in expectation


def _run(path, **window):
    cfg = dict(recorded.TINY, num_experts=recorded.HELD[1], cca_time0=2,
               cca_time1=2, published={"num_experts": 4})
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
                           "moe.load_max_over_mean": 1.1})


def read(run, name):
    return run.spec.module("layer_metrics", name).read(run)


def test_the_programs_scopes_are_the_issues(run):
    table = trace_scopes.of(run).by_scope("jit_step")
    kernels = {(c, p) for c, p in table
               if c.rsplit("/", 1)[-1].startswith(("moe_gmm", "flash"))}
    want = set()
    for layer in (0, 1):
        want |= {(f"{ROOT}/{layer}/attn/flash_fwd", "forward"),
                 (f"{ROOT}/{layer}/attn/flash_fwd", "recompute"),
                 (f"{ROOT}/{layer}/attn/flash_bwd_transpose", "backward")}
        want |= {(f"{ROOT}/{layer}/moe/experts/moe_gmm", p)
                 for p in ("forward", "recompute", "backward")}
        want.add((f"{ROOT}/{layer}/moe/experts/moe_gmm_dw", "backward"))
    assert kernels == want
    for part in ("attn/cca_proj", "attn/cca_mix", "attn/rope",
                 "attn/out_proj", "moe/router", "moe/permute",
                 "moe/experts", "moe/combine"):
        assert any(f"{ROOT}/1/" in c and part in c for c, _p in table), part
    # `res_scale` names no row: XLA fuses the residual's four vectors
    # into their neighbours' fusions, and a fusion takes its root's scope
    # (the compiled step holds the scope: tests/test_tpu_aot_compile.py)
    assert not any("shared_expert" in c for c, _p in table)


def test_cca_mix_time_is_everything_under_that_scope(run):
    table = trace_scopes.of(run).by_scope("jit_step")
    mix = 1e3 * sum(t for (c, _p), t in table.items()
                    if "cca_mix" in c.split("/"))
    phases = {p for (c, p) in table if "cca_mix" in c.split("/")}
    assert read(run, "cca_mix_ms.train") == pytest.approx(mix)
    assert {"forward", "backward", "recompute"} <= phases
    assert 0 < mix < 1e3 * sum(table.values())


def test_moe_time_and_routing_read_this_cells_scopes_too(run):
    table = trace_scopes.of(run).by_scope("jit_step")
    moe = 1e3 * sum(t for (c, _p), t in table.items()
                    if "moe" in c.split("/"))
    route = 1e3 * sum(
        t for (c, _p), t in table.items() if "moe" in c.split("/")
        and {"router", "permute", "combine"} & set(c.split("/")))
    assert read(run, "moe_ffn_ms.train") == pytest.approx(moe)
    assert read(run, "moe_route_ms.train") == pytest.approx(route)
    assert 0 < route < moe < 1e3 * sum(table.values())


def test_the_gmm_share_takes_one_choice_and_wide_experts(run):
    gmm = run.spec.module("kernel_costs", "gmm")
    scoped = trace_scopes.of(run)
    calls = {"gmm": [], "dw": []}
    for mid, _s, t in scoped.ops():
        kind = gmm.classify(scoped.scope(mid)[1])
        if kind:
            calls[kind].append(t)
    assert len(calls["gmm"]) == 3 * len(calls["dw"]) > 0
    peak = peaks.peaks("TPU v5 lite")
    rows = HELD_SHARE * recorded.ROWS * recorded.SEQ * 1
    shapes = gmm.variants("gmm", rows, 2, 1024, 2048)
    one = sum(peaks.least_seconds(*s, peak) for s in shapes) / 2
    share = read(run, "gmm_roofline.train")
    assert share == pytest.approx(
        100 * one * len(calls["gmm"] + calls["dw"])
        / sum(calls["gmm"] + calls["dw"]))
    assert 0 < share <= 100


def test_the_flash_share_counts_the_latent_heads(run):
    fw = run.spec.module("kernel_costs", "flash_window")
    scoped = trace_scopes.of(run)
    least = measured = 0.0
    peak = peaks.peaks("TPU v5 lite")
    for mid, _s, t in scoped.ops():
        found = fw.classify(scoped.scope(mid)[1])
        if found:
            least += peaks.least_seconds(*fw.cost(
                found[0], recorded.ROWS, recorded.SEQ, 8, 2, 128), peak)
            measured += t
    share = read(run, "flash_cca_roofline.train")
    assert share == pytest.approx(100 * least / measured)
    assert 0 < share <= 100


def test_mfu_is_required_operations_over_cadence_and_peak(run):
    from harness import zaya_flops
    period = run.trace_summary.module_period_s("jit_step")
    per_token = zaya_flops.train_flops_per_token(run.cfg, recorded.SEQ)
    want = 100 * per_token * recorded.ROWS * recorded.SEQ / period / 197e12
    assert read(run, "mfu_zaya.train") == pytest.approx(want)
    assert 0 < want < 100
    assert read(run, "moe_load_max_over_mean.train") == 1.1


def test_the_shared_train_readers_read_this_trace_too(run):
    for name in ("step_device_ms.train", "device_idle.train",
                 "head_loss_ms.train", "optimizer_unfused_ms.train",
                 "recompute_ms.train", "host_step_ms.train"):
        value = read(run, name)
        assert value is not None and value >= 0, name
    assert read(run, "recompute_ms.train") > 0      # every block runs again
    assert read(run, "head_loss_ms.train") > 0      # the tied head's scope


@pytest.mark.parametrize("other", ["scoped.xplane.pb", "laguna.xplane.pb"])
def test_a_program_without_cca_gives_the_new_readers_nothing(other):
    """The GPT trace of PR 25 and the Laguna trace of PR 31 hold no
    `cca_mix` scope and their configurations no `cca_time0`: the two
    readers that look for them return nothing and do not raise."""
    run = _run(os.path.join(HERE, "data", other))
    run.cfg = {"num_experts": 8, "hidden_size": 256}
    assert read(run, "cca_mix_ms.train") is None
    assert read(run, "flash_cca_roofline.train") is None
