"""`models/laguna.py` on the CPU at a tiny size: the per-layer
description, the share read from a configuration's dict, the counts a
`TrainStep(has_aux=True)` step hands out, and the load gauges. The
program against the plain reference is `benchmarks/tests/test_laguna.py`."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import amp
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import (GPTPretrainingCriterion, LagunaConfig,
                               LagunaForCausalLM, laguna_tiny,
                               observe_expert_load)
from paddle_tpu.optimizer import AdamW


def _batch(rows=2, seq=32, vocab=512, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, vocab, (rows, seq + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def test_layers_differ_by_index_in_three_ways_at_once():
    pt.seed(0)
    model = LagunaForCausalLM(laguna_tiny())
    layers = model.laguna.layers
    assert [lay.attn.heads for lay in layers] == [4, 6, 6, 6, 4]
    assert [lay.attn.window for lay in layers] == [None, 8, 8, 8, None]
    assert [hasattr(lay, "moe") for lay in layers] == [False] + [True] * 4
    assert [hasattr(lay, "mlp") for lay in layers] == [True] + [False] * 4
    assert layers[1].attn.q_proj.weight.shape == [64, 6 * 16]
    assert layers[1].attn.k_proj.weight.shape == [64, 2 * 16]
    assert layers[1].moe.gate_up_proj.shape == [16, 64, 64]
    assert layers[1].moe.router.weight.shape == [64, 16]


def test_the_published_defaults_are_laguna_xs2():
    c = LagunaConfig(num_hidden_layers=8)
    assert c.layer_types[:5] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert c.num_attention_heads_per_layer == [48, 64, 64, 64] * 2
    assert c.mlp_layer_types == ["dense"] + ["sparse"] * 7
    assert tuple(c.experts_held) == (0, 256)
    assert c.rope_parameters["full_attention"]["rope_type"] == "yarn"
    with pytest.raises(ValueError, match="entries"):
        LagunaConfig(num_hidden_layers=3, layer_types=["full_attention"])
    with pytest.raises(NotImplementedError):
        LagunaConfig(num_hidden_layers=1, tie_word_embeddings=True)


def test_from_dict_reads_the_share_of_a_benchmark_configuration():
    d = {"hidden_size": 64, "num_hidden_layers": 2, "num_experts": 4,
         "expert_first": 8, "published": {"num_experts": 16},
         "layer_types": ["full_attention", "sliding_attention"],
         "mlp_layer_types": ["dense", "sparse"],
         "num_attention_heads_per_layer": [8, 16], "model_type": "laguna"}
    c = LagunaConfig.from_dict(d, recompute=True)
    assert (c.num_experts, tuple(c.experts_held)) == (16, (8, 4))
    assert c.recompute and c.hidden_size == 64
    whole = LagunaConfig.from_dict({k: v for k, v in d.items()
                                    if k != "published"})
    assert (whole.num_experts, tuple(whole.experts_held)) == (4, (0, 4))


@pytest.mark.parametrize("recompute", [False, True])
def test_a_step_hands_the_counts_out_with_its_loss(recompute):
    pt.seed(0)
    model = LagunaForCausalLM(laguna_tiny(experts_held=(4, 8),
                                          recompute=recompute))
    model.train()
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        return crit(logits, labels), m.expert_counts

    step = TrainStep(model, AdamW(learning_rate=1e-3,
                                  parameters=model.parameters()),
                     loss_fn, has_aux=True)
    ids, labels = _batch()
    first = float(step(ids, labels).numpy())
    counts = np.asarray(step.aux)
    assert counts.shape == (4, 8) and counts.dtype == np.int32
    # 64 tokens x 4 choices over 16 experts, half of them held
    assert 0 < counts.sum(axis=1).min() and counts.sum(axis=1).max() <= 256
    for _ in range(3):
        last = float(step(ids, labels).numpy())
    assert last < first


def test_without_aux_the_step_is_as_it_was():
    pt.seed(0)
    model = LagunaForCausalLM(laguna_tiny())
    model.train()
    crit = GPTPretrainingCriterion()
    step = TrainStep(model, AdamW(learning_rate=1e-3,
                                  parameters=model.parameters()),
                     lambda m, ids, labels: crit(m(ids), labels))
    assert np.isfinite(float(step(*_batch()).numpy()))
    assert step.aux is None


def test_the_composite_path_sees_the_same_window():
    pt.seed(0)
    ids, _ = _batch()
    outs = []
    for flash in (False, True):
        pt.seed(0)
        model = LagunaForCausalLM(laguna_tiny(use_flash_attention=flash))
        model.eval()
        outs.append(model(pt.to_tensor(ids)).numpy())
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-5)


@pytest.mark.parametrize("backend,way_back", [("cpu", 1.0), ("tpu", 0.25)])
def test_load_gauges_from_a_steps_counts(monkeypatch, backend, way_back):
    """The way back reads every slot where it is the gather and the held
    rows where it is the kernel (a TPU backend)."""
    import jax
    from paddle_tpu.observability import metrics
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    counts = np.array([[10, 30, 20, 20], [40, 0, 20, 20]], np.int32)
    got = observe_expert_load(counts, 320)
    assert got == {"moe.assignments_held": 0.25,
                   "moe.load_max_over_mean": (1.5 + 2.0) / 2,
                   "moe.way_back_rows_share": way_back}
    metrics.enable()
    try:
        observe_expert_load(counts, 320)
        text = metrics.registry().to_prometheus()
    finally:
        metrics.disable()
    assert "paddle_tpu_moe_assignments_held 0.25" in text
    assert "paddle_tpu_moe_load_max_over_mean 1.75" in text
    assert f"paddle_tpu_moe_way_back_rows_share {way_back:g}\n" in text
