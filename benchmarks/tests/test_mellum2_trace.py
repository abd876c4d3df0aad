"""The Mellum2 cell's readers against a trace recorded on a four-chip
host (`tools/record_mellum2_trace.py` on four TPU v5 lite: four steps of
a `TrainStep` over a one-axis mesh of the four, two sliding layers and a
full one, all sparse with 16 experts 4 a chip and their exchange, each
under `jax.checkpoint`, the vocabulary in four slices, the first step
compiling inside the session; cut as `record_jamba_trace.py`'s docstring
says, every device plane kept): FOUR device planes, which every other
recorded trace has one of."""
import os
import statistics
import sys
import types

import pytest

from harness import peaks, trace_chips, trace_scopes
from harness.spec import BENCH_DIR, REPO, Spec
from harness.trace_reduce import OPS_LINE, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
PATH = os.path.join(HERE, "data", "mellum2.xplane.pb")
sys.path.insert(0, os.path.join(BENCH_DIR, "tools"))
import record_mellum2_trace as recorded    # noqa: E402

ROOT = "mellum2forcausallm/laguna/layers"
CHIP0_SHARE = 0.25      # 4 of 16 experts a chip, uniform in expectation
SENT = 1.5e6            # bytes a chip and step, for the share's arithmetic


def _run(path, **window):
    return types.SimpleNamespace(
        spec=Spec(REPO), cfg=dict(recorded.TINY),
        mix={"batch": recorded.ROWS, "seq": recorded.SEQ},
        trace_summary=Trace.from_file(path),
        device={"kind": "TPU v5 lite"},
        tracer=types.SimpleNamespace(xplane=lambda: path),
        window={"tokens_per_step": recorded.ROWS * recorded.SEQ,
                "chips": recorded.CHIPS, **window})


@pytest.fixture(scope="module")
def run():
    """What `run.py` hands a reader, for the recorded session."""
    return _run(PATH, moe={"moe.assignments_held": 1.0,
                           "moe.assignments_chip0": CHIP0_SHARE,
                           "moe.load_max_over_mean": 1.25},
                exchange_bytes_per_step=SENT)


def read(run, name):
    return run.spec.module("layer_metrics", name).read(run)


def test_the_trace_holds_four_device_planes_and_each_is_read(run):
    chips = trace_chips.of(run)
    assert len(chips) == recorded.CHIPS == 4
    assert len({chip.plane.name for chip in chips}) == 4
    assert trace_chips.of(run) is chips         # made once for all readers
    for chip in chips:
        assert len(chip.runs("jit_step")) >= 2
        assert chip.plane.line(OPS_LINE)
    # the one-plane reduction reads the first of them
    assert trace_scopes.of(run).plane.name == min(
        chip.plane.name for chip in chips)


def test_the_kernels_lie_inside_the_layers_shard_maps_on_every_chip(run):
    for chip in trace_chips.of(run):
        table = chip.by_scope("jit_step")
        leaves = {c.rsplit("/", 1)[-1] for c, _p in table}
        assert {"moe_gmm", "moe_gmm_dw", "flash_fwd",
                "flash_bwd_transpose"} <= leaves
        for layer in (0, 1, 2):
            for phase in ("forward", "recompute", "backward"):
                assert (f"{ROOT}/{layer}/moe/shard_map/experts/moe_gmm",
                        phase) in table, (layer, phase)
            assert (f"{ROOT}/{layer}/moe/shard_map/experts/moe_gmm_dw",
                    "backward") in table
        assert any("lm_head/shard_map" in c for c, _p in table)
        assert any("/attn/rope/shard_map" in c for c, _p in table)


def test_the_exchange_is_its_two_scopes_and_the_fused_reduce_scatters(run):
    per_chip = []
    for chip in trace_chips.of(run):
        table = chip.by_scope("jit_step")
        mine = {(c, p): t for (c, p), t in table.items()
                if trace_chips.in_exchange(c)}
        assert mine and all("/moe/shard_map/exchange_" in c for c, _p in mine)
        # the way out in every phase; the backward's collectives keep the
        # forward's scopes
        assert {p for (c, p) in mine if c.endswith("exchange_out")} >= {
            "forward", "recompute", "backward"}
        assert {p for (c, p) in mine if c.endswith("exchange_back")} >= {
            "backward"}
        scoped = 1e3 * sum(mine.values())
        # beside them the compiler's fused reduce-scatters that follow an
        # expert layer's operations and carry no scope; never the head's
        events, runs = trace_chips.exchange_events(chip)
        assert runs == len(chip.runs("jit_step"))
        loose = [mid for mid, _s, _e in events if not chip.scope(mid)[1]]
        assert all("all-reduce-scatter" in chip.plane.event_stats[mid][
            "hlo_category"] for mid in loose)
        total = trace_chips.exchange_ms(chip)
        assert total >= scoped * 0.999 and (loose or total == pytest.approx(
            scoped))
        per_chip.append(total)
    assert read(run, "moe_exchange_ms.train") == pytest.approx(
        statistics.mean(per_chip))
    # a part of the step on every chip
    assert 0 < max(per_chip) < read(run, "step_device_ms.train")
    assert not trace_chips.in_exchange(f"{ROOT}/0/moe/shard_map/permute")


def test_a_fused_reduce_scatter_is_the_exchanges_by_what_ran_before_it():
    """`exchange_events` on events written out: an unscoped fused
    reduce-scatter after an expert layer's operation counts, the head's
    does not, and one past the last whole run does not."""
    names = {1: "%moe_sum_rows.1 = custom-call(", 2: "%fusion.8 = fusion(",
             3: "%fusion.9 = fusion(", 4: "%all-gather.2 = all-gather(",
             5: "%fusion.4 = fusion("}
    paths = {1: "m/layers/0/moe/shard_map/combine/moe_sum_rows", 2: "",
             3: "", 4: "m/layers/0/moe/shard_map/exchange_out",
             5: "c/lm_head/shard_map/while/body"}
    fused = {"hlo_category": "all-reduce-scatter fusion"}

    class Chip:
        plane = types.SimpleNamespace(
            event_names=names, event_stats={2: fused, 3: fused},
            line=lambda _l: [(4, 0.001, 0.002), (1, 0.002, 0.003),
                             (2, 0.003, 0.005), (5, 0.010, 0.011),
                             (3, 0.011, 0.013), (1, 0.060, 0.061),
                             (2, 0.061, 0.063)])

        def runs(self, _p):
            return [(0.0, 0.05)]

        def scope(self, mid):
            return ("jit_step", paths[mid], "forward")

        def ops(self):
            return [(mid, s, e - s) for mid, s, e in self.plane.line(None)]

    events, runs = trace_chips.exchange_events(Chip())
    assert (events, runs) == ([(4, 0.001, 0.002), (2, 0.003, 0.005)], 1)
    assert trace_chips.exchange_ms(Chip()) == pytest.approx(3.0)
    assert trace_chips.in_flight_s(Chip()) == pytest.approx(0.003)


def test_the_ici_share_is_bytes_over_the_time_in_flight_and_the_peak(run):
    ici = run.spec.module("layer_metrics", "moe_exchange_ici_share.train")
    assert ici.ici_peak("TPU v5 lite") == 200e9
    assert ici.ici_peak("TPU v9") is None
    times = [trace_chips.in_flight_s(chip) for chip in trace_chips.of(run)]
    assert all(t and t > 0 for t in times)
    # a transfer is under way for at least as long as its operations run
    for chip, t in zip(trace_chips.of(run), times):
        assert t >= 1e-3 * trace_chips.exchange_ms(chip) * 0.999
    share = read(run, "moe_exchange_ici_share.train")
    assert share == pytest.approx(
        100 * SENT / statistics.mean(times) / 200e9)
    assert 0 < share < 100


def test_a_start_and_its_done_are_one_transfer():
    """`in_flight_s` on events written out: an asynchronous collective
    counts from its `-start` to its `-done`, whatever ran between."""
    names = {1: "%all-gather-start.3 = all-gather-start(",
             2: "%all-gather-done.3 = all-gather-done(",
             3: "%fusion.7 = fusion(", 4: "%reduce-scatter.2 = reduce-scatter("}
    scopes = {1: "exchange_out", 2: "exchange_out", 3: "experts",
              4: "exchange_back"}

    class Chip:
        plane = types.SimpleNamespace(
            event_names=names, event_stats={},
            line=lambda _l: [(1, 0.010, 0.011), (3, 0.011, 0.019),
                             (2, 0.019, 0.020), (4, 0.030, 0.034),
                             (4, 0.090, 0.094)])

        def runs(self, _p):
            return [(0.0, 0.05)]

        def scope(self, mid):
            return ("jit_step", f"m/layers/0/moe/shard_map/{scopes[mid]}",
                    "forward")

    # 10 ms from the start to the done's end, 4 of the reduce-scatter;
    # the one past the run's end does not count
    assert trace_chips.in_flight_s(Chip()) == pytest.approx(0.014)


def test_mfu_is_required_operations_over_cadence_and_four_chips_peak(run):
    from harness import mellum2_flops
    period = run.trace_summary.module_period_s("jit_step")
    per_token = mellum2_flops.train_flops_per_token(run.cfg, recorded.SEQ)
    want = 100 * per_token * recorded.ROWS * recorded.SEQ / period / (
        4 * 197e12)
    assert read(run, "mfu_mellum2.train") == pytest.approx(want)
    assert 0 < want < 100


def test_the_gmm_share_counts_chip_0s_rows_and_experts(run):
    gmm = run.spec.module("kernel_costs", "gmm")
    scoped = trace_scopes.of(run)
    calls = {"gmm": [], "dw": []}
    for mid, _s, t in scoped.ops():
        kind = gmm.classify(scoped.scope(mid)[1])
        if kind:
            calls[kind].append(t)
    assert len(calls["gmm"]) == 3 * len(calls["dw"]) > 0
    peak = peaks.peaks("TPU v5 lite")
    rows = CHIP0_SHARE * recorded.ROWS * recorded.SEQ * 4
    shapes = gmm.variants("gmm", rows, 16 // 4, 256, 128)
    one = sum(peaks.least_seconds(*s, peak) for s in shapes) / 2
    share = read(run, "gmm_ep_roofline.train")
    assert share == pytest.approx(
        100 * one * len(calls["gmm"] + calls["dw"])
        / sum(calls["gmm"] + calls["dw"]))
    assert 0 < share <= 100
    # the one-chip reader would count every chip's rows and experts
    assert read(run, "gmm_roofline.train") > share


def test_the_shared_train_readers_read_this_trace_too(run):
    for name in ("step_device_ms.train", "device_idle.train",
                 "head_loss_ms.train", "optimizer_unfused_ms.train",
                 "recompute_ms.train", "host_step_ms.train", "rope_ms.train",
                 "moe_ffn_ms.train", "moe_route_ms.train"):
        value = read(run, name)
        assert value is not None and value >= 0, name
    assert read(run, "recompute_ms.train") > 0      # every block runs again
    assert read(run, "moe_load_max_over_mean.train") == 1.25
    assert read(run, "moe_route_ms.train") < read(run, "moe_ffn_ms.train")


@pytest.mark.parametrize("other", ["laguna.xplane.pb", "scoped.xplane.pb"])
def test_a_program_without_the_exchange_gives_the_readers_nothing(other):
    """The parent's programs hold no such scopes and their runs no such
    counters: each new reader returns nothing and does not raise (the
    driver runs this PR's readers over the parent's checkout too)."""
    run = _run(os.path.join(HERE, "data", other))
    run.window = {"tokens_per_step": 512, "chips": 1}
    for name in ("moe_exchange_ms.train", "moe_exchange_ici_share.train",
                 "gmm_ep_roofline.train"):
        assert read(run, name) is None, name
    run.window = None
    assert read(run, "moe_exchange_ici_share.train") is None
    assert read(run, "gmm_ep_roofline.train") is None
