"""The Jamba cell's readers against a trace recorded on the chip
(`tools/record_jamba_trace.py` on a TPU v5 lite: four steps of a
`TrainStep` over one Mamba and one attention layer, both under
`jax.checkpoint`, the first step compiling inside the session; cut as
that file's docstring says)."""
import os
import sys
import types

import pytest

from harness import peaks, trace_scopes
from harness.spec import BENCH_DIR, REPO, Spec
from harness.trace_reduce import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
PATH = os.path.join(HERE, "data", "jamba.xplane.pb")
sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))
import record_jamba_trace as recorded    # noqa: E402

ROOT = "jambaforcausallm/jamba/layers"


@pytest.fixture(scope="module")
def run():
    """What `run.py` hands a reader, for the recorded session."""
    trace = Trace.from_file(PATH)
    cfg = dict(recorded.TINY)
    mix = {"batch": recorded.ROWS, "seq": recorded.SEQ}
    return types.SimpleNamespace(
        spec=Spec(REPO), cfg=cfg, mix=mix, trace_summary=trace,
        device={"kind": "TPU v5 lite"},
        tracer=types.SimpleNamespace(xplane=lambda: PATH),
        window={"tokens_per_step": recorded.ROWS * recorded.SEQ})


def read(run, name):
    return run.spec.module("layer_metrics", name).read(run)


def test_the_kernels_lie_under_their_layers_by_name(run):
    table = trace_scopes.of(run).by_scope("jit_step")
    kernels = {(c, p) for c, p in table
               if c.rsplit("/", 1)[-1].startswith(("ssm_scan", "flash"))}
    assert kernels == {
        (f"{ROOT}/0/mamba/ssm_scan_fwd", "forward"),
        (f"{ROOT}/0/mamba/ssm_scan_fwd", "recompute"),
        (f"{ROOT}/0/mamba/ssm_scan_bwd", "backward"),
        (f"{ROOT}/1/attn/flash_fwd", "forward"),
        (f"{ROOT}/1/attn/flash_fwd", "recompute"),
        (f"{ROOT}/1/attn/flash_bwd_transpose", "backward")}
    for part in ("in_proj", "conv1d", "x_proj", "dt_proj", "out_proj"):
        assert any(c == f"{ROOT}/0/mamba/{part}" for c, _p in table), part
    assert any(c.startswith(f"{ROOT}/1/mlp/") for c, _p in table)
    assert not any("/1/mamba" in c or "/0/attn" in c for c, _p in table)


def test_scan_time_is_the_two_kernels_in_every_phase(run):
    scoped = trace_scopes.of(run)
    table = scoped.by_scope("jit_step")
    want = 1e3 * sum(t for (c, _p), t in table.items()
                     if c.endswith(("/ssm_scan_fwd", "/ssm_scan_bwd")))
    assert want > 0
    assert read(run, "ssm_scan_ms.train") == pytest.approx(want)
    mixer = read(run, "ssm_mixer_ms.train")
    assert mixer == pytest.approx(1e3 * sum(
        t for (c, _p), t in table.items() if "/mamba" in c))
    assert want < mixer < 1e3 * sum(table.values())


def test_the_roofline_share_is_least_time_over_measured(run):
    scan = run.spec.module("kernel_costs", "ssm_scan")
    scoped = trace_scopes.of(run)
    calls = {"fwd": [], "bwd": []}
    for mid, _s, t in scoped.ops():
        kind = scan.classify(scoped.scope(mid)[1])
        if kind:
            calls[kind].append(t)
    # a forward, the forward again and a backward in each of the steps
    # the window holds whole (the first compiles: its run is there too)
    assert len(calls["fwd"]) == 2 * len(calls["bwd"]) > 0
    peak = peaks.peaks("TPU v5 lite")
    shape = (recorded.ROWS, recorded.SEQ, 512, 16)
    least = sum(len(calls[k]) * peaks.least_seconds(
        *scan.cost(k, *shape), peak) for k in calls)
    share = read(run, "ssm_scan_roofline.train")
    assert share == pytest.approx(
        100 * least / sum(calls["fwd"] + calls["bwd"]))
    assert 0 < share <= 100


def test_mfu_is_required_operations_over_cadence_and_peak(run):
    from harness import jamba_flops
    period = run.trace_summary.module_period_s("jit_step")
    per_token = jamba_flops.train_flops_per_token(
        run.cfg, recorded.SEQ,
        run.spec.module("kernel_costs", "ssm_scan").cost)
    want = 100 * per_token * recorded.ROWS * recorded.SEQ / period / 197e12
    assert read(run, "mfu_jamba.train") == pytest.approx(want)
    assert 0 < want < 100


def test_the_shared_train_readers_read_this_trace_too(run):
    for name in ("step_device_ms.train", "device_idle.train",
                 "head_loss_ms.train", "optimizer_unfused_ms.train",
                 "recompute_ms.train", "host_step_ms.train"):
        value = read(run, name)
        assert value is not None and value >= 0, name
    assert read(run, "recompute_ms.train") > 0      # both blocks run again


def test_a_program_without_the_scan_gives_the_readers_nothing():
    """The GPT trace of PR 25 holds no Mamba layer: each scan reader
    returns nothing and does not raise (the parent's side of a traced
    run under this PR's benchmark files)."""
    path = os.path.join(HERE, "data", "scoped.xplane.pb")
    other = types.SimpleNamespace(
        spec=Spec(REPO), cfg=dict(recorded.TINY),
        mix={"batch": 8, "seq": 256}, trace_summary=Trace.from_file(path),
        device={"kind": "TPU v5 lite"},
        tracer=types.SimpleNamespace(xplane=lambda: path),
        window={"tokens_per_step": 2048})
    for name in ("ssm_scan_ms.train", "ssm_mixer_ms.train",
                 "ssm_scan_roofline.train"):
        assert read(other, name) is None, name


def test_the_decoder_reads_what_profiledata_reads_of_the_cut_file():
    from jax.profiler import ProfileData
    theirs = {p.name: p for p in ProfileData.from_file(PATH).planes}
    planes = trace_scopes.XSpace.from_file(PATH).planes
    assert [p.name for p in planes] == list(theirs)
    assert planes[0].name == "/device:TPU:0" and "/host:CPU" in theirs
    for plane in planes:
        lines = {ln.name: list(ln.events) for ln in theirs[plane.name].lines}
        assert [n for n, _e in plane.lines] == list(lines)
        for name, events in plane.lines:
            assert len(events) == len(lines[name])
            for (mid, start, end), ev in zip(events, lines[name]):
                assert plane.event_names[mid] == ev.name
                assert start == pytest.approx(ev.start_ns * 1e-9, abs=1e-9)
