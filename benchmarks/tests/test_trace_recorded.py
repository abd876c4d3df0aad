"""The reduction against a small trace recorded on the chip
(`tools/record_small_trace.py`: four runs of a three-fusion program on a
TPU v5 lite, under `harness.train.*` spans)."""
import os

import pytest

from harness.trace_reduce import Trace, short_name

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    return Trace.from_file(os.path.join(HERE, "data", "small.xplane.pb"))


def test_planes_lines_and_spans_are_found(trace):
    assert list(trace.device_ops) == ["/device:TPU:0"]
    assert len(trace.device_ops["/device:TPU:0"]) == 20
    assert len(trace.device_modules["/device:TPU:0"]) == 4
    assert [n for n, _s, _e in trace.spans] == [
        "harness.train.next_batch", "harness.train.step",
        "harness.train.read_loss"] * 4


def test_busy_union_and_idle_share(trace):
    # the window is the harness's spans' extent: 13.8 ms, in which the
    # four program runs of 35.8 us each are all that ran
    assert trace.window_s == pytest.approx(0.013803712, rel=1e-6)
    runs, n = trace.module_seconds("jit_small_step")
    assert n == 4 and runs == pytest.approx(4 * 35.808e-6, rel=1e-3)
    assert trace.busy_s == pytest.approx(runs, rel=2e-3)
    assert trace.idle_share_pct() == pytest.approx(98.9627, abs=1e-3)


def test_kernel_time_by_name(trace):
    seconds, calls = trace.op_seconds(r"^%fusion(\.\d+)? = ")
    assert calls == 12 and seconds == pytest.approx(1.4312e-4, rel=1e-3)
    one, calls = trace.op_seconds(r"^%fusion\.2 = ")
    assert calls == 4 and one == pytest.approx(4.6309e-5, rel=1e-3)
    top = trace.top_ops()
    assert [n for n, _t in top[:3]] == ["fusion", "fusion.1", "fusion.2"]
    assert short_name("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)") \
        == "fusion.1"


def test_gaps_are_named_by_what_the_host_was_doing(trace):
    gaps = dict(trace.idle_gaps())
    assert set(gaps) == {"harness.train.next_batch", "harness.train.step",
                         "harness.train.read_loss"}
    assert sum(gaps.values()) == pytest.approx(
        trace.window_s - trace.busy_s, rel=1e-6)
    # the sleeps were in next_batch and the waits in read_loss; the
    # dispatch itself is the shortest
    assert gaps["harness.train.step"] < gaps["harness.train.next_batch"]
