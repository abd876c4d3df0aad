"""How `correct` is decided, at a size a test can hold.

* the control (the plain reference with every matmul operand rounded to
  fp8, put in the program's place) reads far above what the program
  reads, in at least one of a cell's numbers;
* a run whose timed path is broken underneath comes out not correct:
  a training step that returns its state unchanged, a served token
  altered where it is produced.
The limits used here are the tiny size's own; the cells' limits are set
from chip readings (PERF.md)."""
import pytest

from harness.spec import Spec

TINY_LIMITS = {"loss": 1e-3, "grad_norm_worst_leaf": 0.05,
               "change_norm_median_leaf": 0.1, "served_logit_gap_max": 0.05}


@pytest.fixture
def tiny_spec(tmp_path):
    import tiny
    return Spec(tiny.make_tiny_repo(str(tmp_path / "r"), limits=TINY_LIMITS))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_train_control_reads_above_the_program(tiny_spec, seed):
    tw = tiny_spec.module("drivers", "train_window")
    ref = tiny_spec.module("reference", "gpt3-1.3b")
    cfg = tiny_spec.data("configs", "gpt3-1.3b")
    mix = tiny_spec.data("traffic", "pretrain-2k")
    n = ref.CHECK_STEPS
    prog = tw.first_steps(tw.build_step(cfg, seed, ref), cfg, mix, seed,
                          ref, n)
    exact = tw.reference_steps(cfg, mix, seed, ref, n)
    control = tw.reference_steps(cfg, mix, seed, ref, n, rnd=ref.fp8)
    sound = tw.compare(prog, exact, TINY_LIMITS)
    broken = tw.compare(control, exact, TINY_LIMITS)
    assert all(v["value"] <= v["limit"] for v in sound.values()), sound
    assert broken["grad_norm_worst_leaf"]["value"] > \
        3 * sound["grad_norm_worst_leaf"]["value"]
    assert any(v["value"] > v["limit"] for v in broken.values()), broken


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_reads_above_the_program(tiny_spec, seed):
    sw = tiny_spec.module("drivers", "serve_window")
    ref = tiny_spec.module("reference", "gpt3-1.3b")
    cfg = tiny_spec.data("configs", "gpt3-1.3b")
    mix = tiny_spec.data("traffic", "chat-sessions-steady")
    engine = sw.build_engine(cfg, seed, ref)
    sw.warm_up(engine, cfg, mix)
    w = sw.measure(engine, sw.schedule(cfg, mix, seed, 1.0), mix, 1.0)
    picked = sw.sample_requests(w["done"], seed, 12)
    samples = [(r.ev.prompt, w["outputs"][r.ev.rid]) for r in picked]
    model = ref.Model(cfg, seed, dtype="bfloat16")
    control = ref.Model(cfg, seed, dtype="bfloat16", rnd=ref.fp8)
    sound, n = sw.logit_gaps(model, samples, 128)
    broken, _ = sw.logit_gaps(model, samples, 128, chooser=control)
    assert n > 20 and sound <= TINY_LIMITS["served_logit_gap_max"]
    assert broken > 3 * max(sound, 0.01)
    assert broken > TINY_LIMITS["served_logit_gap_max"]


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
    line = rehearse("gpt3-1.3b.train-2k", seconds=0.3, limits=TINY_LIMITS)
    assert line["correct"] is False
    assert line["compared"]["change_norm_median_leaf"]["value"] > 0.9


def test_a_part_of_the_batch_left_out_is_not_correct(rehearse, monkeypatch):
    from paddle_tpu.jit import TrainStep
    real = TrainStep.__call__

    def call(self, ids, labels):
        half = len(ids) // 2
        return real(self, ids[:half], labels[:half])

    monkeypatch.setattr(TrainStep, "__call__", call)
    line = rehearse("gpt3-1.3b.train-2k", seconds=0.3, limits=TINY_LIMITS)
    assert line["correct"] is False


def test_a_token_altered_where_it_is_produced_is_not_correct(
        rehearse, monkeypatch):
    import jax.numpy as jnp
    from paddle_tpu.models import generation
    real = generation._pick_token

    def second_best(logits, *a, **kw):
        tok, extra = real(logits, *a, **kw)
        masked = jnp.where(
            jnp.arange(logits.shape[-1])[None] == tok[:, None], -jnp.inf,
            logits)
        return jnp.argmax(masked, axis=-1).astype(tok.dtype), extra

    monkeypatch.setattr(generation, "_pick_token", second_best)
    line = rehearse("gpt3-1.3b.serve-chat-steady", seconds=1.0,
                    limits=TINY_LIMITS)
    assert line["correct"] is False
    assert line["failed"] == 0      # wrong answers are not failed operations


def test_a_sound_run_is_correct(rehearse):
    for cell in ("gpt3-1.3b.train-2k", "gpt3-1.3b.serve-chat-steady"):
        line = rehearse(cell, seconds=0.5, limits=TINY_LIMITS)
        assert line["correct"] is True and line["failed"] == 0, line
