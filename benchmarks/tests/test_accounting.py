"""What counts as an operation: a finished request is attempted, a
request that ended in anything but eos/length has failed, a request
still in flight is the backlog and nothing else; a training step whose
loss is not finite has failed."""
import math

import pytest

FLOOD = "gpt3-1.3b.serve-chat-flood"
FAR_ABOVE = {"base_rate": 400.0, "burst_rate": 800.0}


@pytest.mark.parametrize("seed", [3, 2147483999, 4294967311])
def test_flood_far_above_capacity_fails_nothing(rehearse, seed):
    line = rehearse(FLOOD, seed=seed, seconds=1.0, trace=1, rates=FAR_ABOVE)
    assert line["failed"] == 0
    assert line["attempted"] > 0
    # the unfinished requests are the backlog, and only that
    assert line["metrics"]["backlog_end.flood"]["value"] > 0


def test_attempted_is_what_finished_inside_the_window(tmp_path):
    import tiny
    from harness.spec import Spec
    spec = Spec(tiny.make_tiny_repo(str(tmp_path), rates=FAR_ABOVE))
    sw = spec.module("drivers", "serve_window")
    cfg = spec.data("configs", "gpt3-1.3b")
    mix = spec.data("traffic", "chat-sessions-flood")
    ref = spec.module("reference", "gpt3-1.3b")
    engine = sw.build_engine(cfg, 5, ref)
    sw.warm_up(engine, cfg, mix)
    w = sw.measure(engine, sw.schedule(cfg, mix, 5, 1.0), mix, 1.0)
    terminal = [r for r in w["reqs"] if r.reason is not None
                and w["t0"] < r.last <= w["t1"]]
    assert len(w["done"]) == len(terminal) > 0
    assert all(r.ok for r in w["done"])
    arrived = [r for r in w["reqs"] if r.due <= w["t1"]]
    finished_by_close = [r for r in arrived
                         if r.last is not None and r.last <= w["t1"]]
    assert w["backlog"] == len(arrived) - len(finished_by_close) > 0
    assert not set(map(id, w["done"])) & {
        id(r) for r in arrived if r.last is None or r.last > w["t1"]}


def test_a_request_that_ends_in_error_is_a_failed_operation(
        rehearse, monkeypatch):
    from paddle_tpu.inference import llm_engine
    real = llm_engine.LLMEngine._maybe_finish
    state = {"n": 0}

    def finish(self, seq, finished):
        before = len(finished)
        real(self, seq, finished)
        for r in finished[before:]:
            state["n"] += 1
            if not str(r.request_id).startswith("warm") and \
                    state["n"] % 5 == 0:
                r.finish_reason, r.error = "error", "made to fail"

    monkeypatch.setattr(llm_engine.LLMEngine, "_maybe_finish", finish)
    line = rehearse(FLOOD, seconds=1.0)
    assert 0 < line["failed"] < line["attempted"]


def test_a_step_whose_loss_is_not_finite_has_failed(rehearse, monkeypatch):
    from paddle_tpu.jit import TrainStep
    import paddle_tpu as pt
    real = TrainStep.__call__
    calls = {"n": 0}

    def call(self, *a, **kw):
        loss = real(self, *a, **kw)
        calls["n"] += 1
        return pt.to_tensor(math.nan) if calls["n"] % 7 == 0 else loss

    monkeypatch.setattr(TrainStep, "__call__", call)
    line = rehearse("gpt3-1.3b.train-2k", seconds=0.5)
    assert 0 < line["failed"] < line["attempted"]
