"""The Jamba cell at a size a CPU test can hold: the one command end to
end, the comparison's verdicts, the scan kernels' counted costs against
hand counts. The readers against a trace recorded on the chip are in
`test_jamba_trace.py`."""
import pytest

from harness.spec import REPO, Spec

CELL = "jamba2-3b-l14.train-4k"
# the tiny size's own: 64 tokens a step and weights of 0.15 make a bf16
# step's worst leaf read 0.07 to 0.14 and the fp8 control's 1.0. The cell
# sets no limit on the losses (drivers/jamba_train_window.py:compare).
TINY_LIMITS = {"grad_norm_worst_leaf": 0.3, "change_norm_median_leaf": 0.05}


@pytest.fixture
def tiny_spec(tmp_path):
    import tiny
    return Spec(tiny.make_tiny_repo(str(tmp_path / "r"), limits=TINY_LIMITS))


def test_the_cell_runs_end_to_end_and_is_correct(rehearse):
    line = rehearse(CELL, seconds=0.5, limits=TINY_LIMITS)
    assert line["correct"] is True and line["failed"] == 0, line
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert set(line["compared"]) == {"grad_norm_worst_leaf",
                                     "change_norm_median_leaf"}


def test_a_traced_rehearsal_reads_nothing_from_a_cpu_and_does_not_raise(
        rehearse):
    line = rehearse(CELL, seconds=0.6, trace=1, limits=TINY_LIMITS)
    assert line["correct"] is True
    # no TPU plane in a CPU trace: every device reader returns nothing
    assert not {"ssm_scan_roofline.train", "ssm_scan_ms.train",
                "ssm_mixer_ms.train", "mfu_jamba.train"} & set(
                    line["metrics"])


@pytest.mark.parametrize("seed", [1, 2])
def test_the_fp8_control_reads_above_the_program(tiny_spec, seed):
    tw = tiny_spec.module("drivers", "jamba_train_window")
    ref = tiny_spec.module("reference", "jamba2-3b-l14")
    cfg = tiny_spec.data("configs", "jamba2-3b-l14")
    mix = tiny_spec.data("traffic", "pretrain-4k")
    n = ref.CHECK_STEPS
    prog = tw.first_steps(tw.build_step(cfg, seed, ref), cfg, mix, seed,
                          ref, n)
    exact = tw.reference_steps(cfg, mix, seed, ref, n)
    control = tw.reference_steps(cfg, mix, seed, ref, n, rnd=ref.fp8)
    sound = tw.compare(prog, exact, TINY_LIMITS)
    broken = tw.compare(control, exact, TINY_LIMITS)
    assert all(v["value"] <= v["limit"] for v in sound.values()), sound
    assert any(v["value"] > v["limit"] for v in broken.values()), broken


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        rehearse, monkeypatch):
    from paddle_tpu.jit import TrainStep
    real_build = TrainStep._build

    def build(self, donate):
        fn = real_build(self, False)

        class Unchanged:
            pending = False

            def __call__(_self, params, opt_states, *rest):
                loss, _p, _s = fn(params, opt_states, *rest)
                return loss, params, opt_states

        return Unchanged()

    monkeypatch.setattr(TrainStep, "_build", build)
    line = rehearse(CELL, seconds=0.3, limits=TINY_LIMITS)
    assert line["correct"] is False
    assert line["compared"]["change_norm_median_leaf"]["value"] > 0.9


def test_a_part_of_the_batch_left_out_is_not_correct(rehearse, monkeypatch):
    """Without a limit on the losses it is the first gradient that
    tells: half the rows give other leaves' norms."""
    from paddle_tpu.jit import TrainStep
    real = TrainStep.__call__

    def call(self, ids, labels):
        half = len(ids) // 2
        return real(self, ids[:half], labels[:half])

    monkeypatch.setattr(TrainStep, "__call__", call)
    line = rehearse(CELL, seconds=0.3, limits=TINY_LIMITS)
    assert line["correct"] is False


def test_a_limit_on_the_losses_is_compared_where_a_cell_gives_one(
        tiny_spec):
    tw = tiny_spec.module("drivers", "jamba_train_window")
    side = {"losses": [2.0, 3.0], "grad_norms": [1.0, 2.0],
            "change_norms": [1.0, 1.0]}
    assert set(tw.compare(side, side, TINY_LIMITS)) == {
        "grad_norm_worst_leaf", "change_norm_median_leaf"}
    with_loss = tw.compare(side, side, {"loss": 1e-4, **TINY_LIMITS})
    assert with_loss["loss_step2"] == {"value": 0.0, "limit": 1e-4}


def test_scan_costs_against_hand_counts():
    scan = Spec(REPO).module("kernel_costs", "ssm_scan")
    # 2 rows x 8 steps, 4 channels, 3 states: 64 channel-steps, 192
    # state elements a pass, 48 B/C elements, a 12-element table
    ops, nbytes = scan.cost("fwd", 2, 8, 4, 3)
    assert ops == 7 * 192 + 3 * 64
    assert nbytes == 64 * 12 + 2 * 48 * 4 + (12 + 4) * 4
    ops, nbytes = scan.cost("bwd", 2, 8, 4, 3)
    assert ops == 17 * 192 + 8 * 64
    assert nbytes == 64 * 20 + 4 * 48 * 4 + 2 * (12 + 4) * 4
    # bf16 x, y, dy and dx
    assert scan.cost("fwd", 2, 8, 4, 3, x_bytes=2)[1] == \
        64 * 8 + 2 * 48 * 4 + 16 * 4
    assert scan.classify("jambaforcausallm/jamba/layers/3/mamba/"
                         "ssm_scan_fwd") == "fwd"
    assert scan.classify("jambaforcausallm/jamba/layers/3/mamba/"
                         "ssm_scan_bwd") == "bwd"
    assert scan.classify("jambaforcausallm/jamba/layers/7/attn/"
                         "flash_bwd_transpose") is None
    assert scan.classify("jambaforcausallm/jamba/layers/3/mamba") is None


def test_flops_against_the_issues_count():
    from harness import jamba_flops
    cfg = Spec(REPO).data("configs", "jamba2-3b-l14")
    assert jamba_flops.layer_kinds(cfg) == (13, 1)
    n = jamba_flops.matmul_params(cfg)
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attn = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert n == 13 * mamba + attn + 14 * 3 * 2560 * 8192 + 65536 * 2560
    per_token = jamba_flops.train_flops_per_token(
        cfg, 4096, Spec(REPO).module("kernel_costs", "ssm_scan").cost)
    scan = 13 * (24 * 5120 * 16 + 11 * 5120)
    assert per_token == 6.0 * n + 6.0 * 4096 * 2560 + scan
    assert scan < 0.01 * per_token
