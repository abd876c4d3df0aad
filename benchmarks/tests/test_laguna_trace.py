"""The Laguna cell's readers against a trace recorded on the chip
(`tools/record_laguna_trace.py` on a TPU v5 lite: four steps of a
`TrainStep` over a dense full-attention layer, a sparse window layer and
a sparse full layer, each under `jax.checkpoint`, the first step
compiling inside the session; cut as `record_jamba_trace.py`'s docstring
says)."""
import os
import sys
import types

import pytest

from harness import peaks, trace_scopes
from harness.spec import BENCH_DIR, REPO, Spec
from harness.trace_reduce import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
PATH = os.path.join(HERE, "data", "laguna.xplane.pb")
sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))
import record_laguna_trace as recorded    # noqa: E402

ROOT = "lagunaforcausallm/laguna/layers"
HELD_SHARE = 0.5        # 8 of 16 experts held, uniform in expectation


def _run(path, **window):
    cfg = dict(recorded.TINY, num_experts=recorded.HELD[1])
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
                           "moe.load_max_over_mean": 1.25})


def read(run, name):
    return run.spec.module("layer_metrics", name).read(run)


def test_the_kernels_lie_under_their_layers_by_name(run):
    table = trace_scopes.of(run).by_scope("jit_step")
    kernels = {(c, p) for c, p in table
               if c.rsplit("/", 1)[-1].startswith(("moe_gmm", "flash"))}
    want = set()
    for layer in (0, 1, 2):
        want |= {(f"{ROOT}/{layer}/attn/flash_fwd", "forward"),
                 (f"{ROOT}/{layer}/attn/flash_fwd", "recompute"),
                 (f"{ROOT}/{layer}/attn/flash_bwd_transpose", "backward")}
    for layer in (1, 2):
        want |= {(f"{ROOT}/{layer}/moe/experts/moe_gmm", p)
                 for p in ("forward", "recompute", "backward")}
        want.add((f"{ROOT}/{layer}/moe/experts/moe_gmm_dw", "backward"))
    assert kernels == want
    for part in ("router", "permute", "experts", "combine",
                 "shared_expert"):
        assert any(f"{ROOT}/1/moe/{part}" in c for c, _p in table), part
    assert any(c.startswith(f"{ROOT}/0/mlp/") for c, _p in table)
    assert any("/attn/rope" in c for c, _p in table)
    assert not any("/0/moe" in c or "/1/mlp" in c for c, _p in table)


def test_moe_time_is_everything_under_moe_and_routing_its_three_parts(run):
    table = trace_scopes.of(run).by_scope("jit_step")
    moe = 1e3 * sum(t for (c, _p), t in table.items()
                    if "moe" in c.split("/"))
    route = 1e3 * sum(
        t for (c, _p), t in table.items() if "moe" in c.split("/")
        and {"router", "permute", "combine"} & set(c.split("/")))
    assert read(run, "moe_ffn_ms.train") == pytest.approx(moe)
    assert read(run, "moe_route_ms.train") == pytest.approx(route)
    assert 0 < route < moe < 1e3 * sum(table.values())


def test_the_gmm_share_is_least_time_over_measured(run):
    gmm = run.spec.module("kernel_costs", "gmm")
    scoped = trace_scopes.of(run)
    calls = {"gmm": [], "dw": []}
    for mid, _s, t in scoped.ops():
        kind = gmm.classify(scoped.scope(mid)[1])
        if kind:
            calls[kind].append(t)
    # two products forward, again, and to the rows; two weight gradients
    assert len(calls["gmm"]) == 3 * len(calls["dw"]) > 0
    peak = peaks.peaks("TPU v5 lite")
    rows = HELD_SHARE * recorded.ROWS * recorded.SEQ * 4
    shapes = gmm.variants("gmm", rows, 8, 256, 128)
    one = sum(peaks.least_seconds(*s, peak) for s in shapes) / 2
    share = read(run, "gmm_roofline.train")
    assert share == pytest.approx(
        100 * one * len(calls["gmm"] + calls["dw"])
        / sum(calls["gmm"] + calls["dw"]))
    assert 0 < share <= 100


def test_the_flash_share_counts_each_layers_heads_and_window(run):
    fw = run.spec.module("kernel_costs", "flash_window")
    scoped = trace_scopes.of(run)
    least = measured = 0.0
    peak = peaks.peaks("TPU v5 lite")
    seen = set()
    for mid, _s, t in scoped.ops():
        found = fw.classify(scoped.scope(mid)[1])
        if found:
            kind, layer = found
            seen.add(layer)
            heads, window = {0: (2, None), 1: (4, 128), 2: (2, None)}[layer]
            least += peaks.least_seconds(*fw.cost(
                kind, recorded.ROWS, recorded.SEQ, heads, 1, 128, window),
                peak)
            measured += t
    assert seen == {0, 1, 2}
    share = read(run, "flash_window_roofline.train")
    assert share == pytest.approx(100 * least / measured)
    assert 0 < share <= 100


def test_mfu_is_required_operations_over_cadence_and_peak(run):
    from harness import laguna_flops
    period = run.trace_summary.module_period_s("jit_step")
    per_token = laguna_flops.train_flops_per_token(run.cfg, recorded.SEQ)
    want = 100 * per_token * recorded.ROWS * recorded.SEQ / period / 197e12
    assert read(run, "mfu_laguna.train") == pytest.approx(want)
    assert 0 < want < 100
    assert read(run, "moe_load_max_over_mean.train") == 1.25


def test_the_shared_train_readers_read_this_trace_too(run):
    for name in ("step_device_ms.train", "device_idle.train",
                 "head_loss_ms.train", "optimizer_unfused_ms.train",
                 "recompute_ms.train", "host_step_ms.train"):
        value = read(run, name)
        assert value is not None and value >= 0, name
    assert read(run, "recompute_ms.train") > 0      # every block runs again


def test_a_program_without_experts_gives_the_readers_nothing():
    """The GPT trace of PR 25 holds no expert layer, no layer index the
    flash reader could read heads from, and its run no counter: each
    new reader returns nothing and does not raise."""
    other = _run(os.path.join(HERE, "data", "scoped.xplane.pb"))
    for name in ("moe_ffn_ms.train", "moe_route_ms.train",
                 "gmm_roofline.train", "moe_load_max_over_mean.train"):
        assert read(other, name) is None, name
