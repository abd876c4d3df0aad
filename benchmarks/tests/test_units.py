"""The yardstick's arithmetic against hand counts."""
import pytest

from harness import model_flops, peaks, trace_reduce
from harness.runlib import percentile
from harness.spec import Spec


def test_union_gaps_and_self_times():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert trace_reduce.union_seconds(iv) == pytest.approx(3.0)
    assert trace_reduce.gaps(iv, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                                (4.0, 5.0)]
    # a while of 10 s holding two bodies of 3 s and 4 s, then a lone op
    evs = [("while", 0.0, 10.0), ("body.a", 1.0, 4.0), ("body.b", 5.0, 9.0),
           ("lone", 11.0, 12.0)]
    assert dict(trace_reduce.self_times(evs)) == pytest.approx(
        {"while": 3.0, "body.a": 3.0, "body.b": 4.0, "lone": 1.0})


def test_busy_idle_and_gap_attribution():
    ops = {"/device:TPU:0": [("a", 1.0, 2.0), ("b", 2.5, 3.0),
                             ("late", 9.0, 9.5)]}
    mods = {"/device:TPU:0": [("jit_step(1)", 1.0, 3.0)]}
    spans = [("harness.train.step", 0.0, 2.2),
             ("harness.train.read_loss", 2.2, 4.0)]
    t = trace_reduce.Trace(ops, mods, spans)
    assert (t.lo, t.hi) == (0.0, 4.0)
    assert t.busy_s == pytest.approx(1.5)
    assert t.idle_share_pct() == pytest.approx(100 * 2.5 / 4.0)
    assert t.op_seconds("^a$") == (pytest.approx(1.0), 1)
    assert t.module_seconds("jit_step") == (pytest.approx(2.0), 1)
    gaps = dict(t.idle_gaps())
    assert gaps["harness.train.step"] == pytest.approx(1.0)     # 0..1
    assert gaps["harness.train.read_loss"] == pytest.approx(1.5)  # 2..2.5, 3..4
    assert [n for n, _ in t.top_ops()] == ["a", "b"]   # `late` is outside


def test_a_run_cut_by_the_trace_edge_does_not_move_the_median_run():
    # five whole steps of 0.72 s and the part of a sixth the trace saw
    mods = {"/device:TPU:0": [("jit_step(1)", 0.72 * k, 0.72 * k + 0.719)
                              for k in range(5)] + [("jit_step(1)", 3.6,
                                                     3.617)]}
    t = trace_reduce.Trace({"/device:TPU:0": [("a", 0.0, 3.617)]}, mods,
                           [("harness.train.step", 0.0, 4.0)])
    seconds, runs = t.module_seconds("jit_step")
    assert runs == 6 and seconds / runs == pytest.approx(0.602, abs=1e-3)
    assert t.module_run_s("jit_step") == pytest.approx(0.719)
    assert t.module_run_s("jit_decode") is None
    assert t.module_period_s("jit_step") == pytest.approx(0.72)
    # a first run the trace cut starts late; the cadence is still 0.72
    mods["/device:TPU:0"][0] = ("jit_step(1)", 0.4, 0.719)
    assert trace_reduce.Trace({}, mods, [("harness.train.step", 0.0, 4.0)]
                              ).module_period_s("jit_step") \
        == pytest.approx(0.72)


def test_percentile_is_of_all_values():
    assert percentile([1, 2, 3, 4, 100], 95) == pytest.approx(80.8)
    assert percentile([], 95) is None


def test_flash_cost_by_hand():
    flash = Spec().module("kernel_costs", "flash")
    # 1 row, 1 head, seq 4, dim 2: scores 4*4*2*2 flops, halved; twice
    flops, nbytes = flash.cost("fwd", 1, 4, 1, 2)
    assert flops == 2 * (2 * 4 * 4 * 2 / 2)
    assert nbytes == 4 * (4 * 2 * 2) + 4 * 4
    flops_b, nbytes_b = flash.cost("bwd", 1, 4, 1, 2)
    assert flops_b == 2 * flops
    assert nbytes_b == 8 * (4 * 2 * 2) + 2 * 4 * 4


def test_ragged_cost_by_hand():
    ragged = Spec().module("kernel_costs", "ragged")
    # one row: 2 fresh tokens after 3 cached, 1 head of dim 4
    flops, nbytes = ragged.cost([(2, 3)], 1, 4)
    pairs = 2 * 3 + 3            # each fresh token sees 3 cached; 1 + 2
    assert flops == 2 * 2 * 4 * pairs
    assert nbytes == 3 * 2 * 4 * 2 + 2 * 4 * 4 + 2 * 3 * 4 * 2


def test_model_flops_by_hand():
    cfg = dict(hidden_size=4, intermediate_size=16, num_layers=2,
               vocab_size=10)
    assert model_flops.matmul_params(cfg) == 2 * (3 * 16 + 16 + 2 * 64) + 40
    assert model_flops.train_flops_per_token(cfg, 8) == \
        6 * model_flops.matmul_params(cfg) + 6 * 2 * 8 * 4


def test_peaks_have_no_default():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks("cpu") is None and peaks.peaks("source") is None
    p = peaks.peaks("TPU v5 lite")
    assert peaks.least_seconds(197e12, 1.0, p) == pytest.approx(1.0)
    assert peaks.least_seconds(1.0, 819e9, p) == pytest.approx(1.0)


def test_kernels_are_tpu_custom_calls_not_xla_bookkeeping():
    flash = Spec().module("kernel_costs", "flash")
    fwd = ('%jvp__.3 = (bf16[4,2048,2048]{2,1,0}) custom-call(bf16[4,2048,'
           '2048]{2,1,0} %a), custom_call_target="tpu_custom_call"')
    bwd = fwd.replace("%jvp__.3", "%transpose_jvp___.18")
    remat_bwd = fwd.replace("%jvp__.3", "%checkpoint.2")
    junk = ('%custom-call.77 = f32[8]{0} custom-call(f32[8]{0} %b), '
            'custom_call_target="AllocateBuffer"')
    assert [flash.classify(n) for n in (fwd, bwd, remat_bwd, junk,
                                        "%fusion.1 = f32[] fusion()")] \
        == ["fwd", "bwd", "bwd", None, None]
    assert trace_reduce.is_kernel("%ragged.24 = f32[8]{0} custom-call(%q)")
