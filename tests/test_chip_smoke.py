"""chip_smoke.py rehearsed without the chip.

The script's phases are functions of the model and the sizes, so the
same control flow that runs GPT-3 1.3B on the chip runs here at a tiny
size on the CPU (where the attention paths are the references, which
the phases are told to expect): wrong arguments, a wave that never
reads the pool, a sharding rule that does not match are found before
any chip time is spent. `main()` itself must refuse a machine without
a TPU and print no result line."""
import ast
import json

import jax
import pytest

import chip_smoke
from paddle_tpu.kernels.pallas import autotune

TINY = dict(vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
            max_position_embeddings=256, hidden_dropout_prob=0.0,
            attention_dropout_prob=0.0)


def _records(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()]


def test_train_phase_tiny(capsys):
    # sweeps another test file left undrained in this xdist worker are
    # not this phase's
    autotune.drain_sweeps()
    losses = chip_smoke.train_phase(TINY, batch=2, seq=128, steps=3, seed=0,
                                    expect_path="xla")
    assert len(losses) == 3 and losses[-1] < losses[0]
    (rec,) = _records(capsys)
    assert rec["phase"] == "train" and rec["attention_path"] == "xla"
    assert rec["tuning_sweeps"] == 0        # no kernel, nothing to tune


def test_train_phase_refuses_another_attention_path():
    with pytest.raises(RuntimeError, match="attention path"):
        chip_smoke.train_phase(TINY, batch=2, seq=128, steps=2, seed=0,
                               expect_path="pallas")


def test_serve_phase_tiny(capsys):
    # the chip's waves at a quarter of the lengths: block 16, prefix of
    # two blocks, the same sharing pattern and wave order
    assert _tiny_serve() == 1.0     # float32, one backend: bit-identical
    (rec,) = _records(capsys)
    assert rec["served_tokens_not_dense_best"] == 0
    assert rec["first_difference_from_generate_at"] == [None] * 12
    assert rec["finished"] == rec["requests"] == 12 and rec["failed"] == 0
    assert rec["prefix_cache_hit_tokens"] > 0
    # the later waves read the pool; the buckets are the chip's, scaled
    keys = [ast.literal_eval(k) for k in rec["ragged_paths"]]
    assert any(with_pool for _r, _tb, with_pool, _ap in keys)
    assert {tb for _r, tb, _wp, _ap in keys} <= {16, 32, 64, 128}


def _tiny_serve():
    waves = tuple(tuple((p // 4, n // 4, shares) for p, n, shares in wave)
                  for wave in chip_smoke.WAVES)
    return chip_smoke.serve_phase(
        TINY, dict(max_batch=8, block_size=16, decode_chunk=4,
                   prompt_quantum=32, num_blocks=64),
        waves, chip_smoke.PREFIX_LEN // 4, seed=0, expect_path="jnp")


@pytest.mark.parametrize("fault,message", [
    ("raises", "requests failed"),          # the kernel refuses to run
    ("wrong", "below the dense forward"),   # it runs and answers wrongly
])
def test_serve_phase_fails_when_the_attention_kernel_does(monkeypatch,
                                                          fault, message):
    """A kernel made to fail fails the run — no phase turns a failure
    into a note. A kernel that raises reaches the check through the
    engine's own per-request isolation (every request failed); one that
    answers wrongly is caught by the dense forward."""
    from importlib import import_module
    import jax.numpy as jnp
    rpa = import_module("paddle_tpu.kernels.pallas.ragged_paged_attention")

    def broken(q, *args, **kwargs):
        if fault == "raises":
            raise NotImplementedError("Mosaic refuses this kernel")
        return jnp.ones(q.shape, jnp.float32)

    monkeypatch.setattr(rpa, "_ragged_reference", broken)
    with pytest.raises(RuntimeError, match=message):
        _tiny_serve()


def test_sharded_phase_tiny(capsys):
    chip_smoke.sharded_phase(TINY, batch=4, seq=128, steps=3, seed=0,
                             devices=jax.devices()[:4])
    (rec,) = _records(capsys)
    assert rec["phase"] == "sharded_train"
    assert rec["max_rel_diff"] <= chip_smoke.SHARDED_RTOL
    assert rec["params_split"] > 0
    assert sum(rec["collectives"].values()) > 0
    assert min(rec["state_bytes_per_device"]) > 0


def test_main_refuses_a_machine_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok"' not in out
