"""`paddle_tpu.models.JambaForCausalLM` against the benchmark's plain
reference (benchmarks/harness/jamba_reference.py: float32 jax.numpy, the
recurrence a literal scan over time steps) on seeded weights at a tiny
size with both kinds of layer: period 4, offset 2, 8 layers, one
key/value head. The reference imports nothing of the program."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import amp
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import GPTPretrainingCriterion
from paddle_tpu.models.jamba import JambaConfig, jamba_tiny
from paddle_tpu.observability import perf
from paddle_tpu.optimizer import AdamW

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import jamba_program               # noqa: E402
from harness import jamba_reference as ref      # noqa: E402
from harness import weights                     # noqa: E402

CFG = dict(
    vocab_size=320, hidden_size=64, intermediate_size=96,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=1,
    attn_layer_period=4, attn_layer_offset=2, mamba_d_state=16,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8, rms_norm_eps=1e-6,
    initializer_range=0.08, num_experts=1, tie_word_embeddings=True,
    # published keys the model does not read
    model_type="jamba", use_mamba_kernels=True, num_logits_to_keep=1)
OPT = dict(learning_rate=1e-3, beta1=0.9, beta2=0.999, epsilon=1e-8,
           weight_decay=0.01, moment_dtype="bfloat16")
ROWS, SEQ = 2, 24


def build(seed, **kw):
    """The program's model holding the seed's float32 weights, handed
    over as the benchmark hands them (harness/jamba_program.py)."""
    return jamba_program.build_model(CFG, seed, ref, **kw)


def tokens(seed, k=0):
    rng = np.random.default_rng([seed, k])
    t = rng.integers(0, CFG["vocab_size"], (ROWS, SEQ + 1)).astype(np.int32)
    return t[:, :-1], t[:, 1:]


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def test_the_layers_are_of_two_kinds_by_index_in_the_references_order():
    model = build(1)
    kinds = ["attn" if hasattr(lay, "attn") else "mamba"
             for lay in model.jamba.layers]
    assert kinds == ["mamba", "mamba", "attn", "mamba"] * 2
    assert [n for n, _p in model.named_parameters()] == \
        [n for n, _s, _i in ref.param_specs(CFG)]
    assert model.jamba.layers[2].attn.k_proj.weight.shape == [64, 16]
    with pytest.raises(NotImplementedError, match="sparse"):
        JambaConfig(num_experts=16)
    assert jamba_tiny().mamba_inner == 128


@pytest.mark.parametrize("seed", [3, 11])
def test_logits_loss_and_every_gradient_agree_in_float32(seed):
    model = build(seed)
    model.eval()
    ids, labels = tokens(seed)
    want = ref.Model(CFG, seed)
    logits = want.logits(ids)
    assert rel(model(pt.to_tensor(ids))._data, logits) < 2e-5

    names = [n for n, _p in model.named_parameters()]
    params = [p._data for _n, p in model.named_parameters()]

    def program_loss(ps):
        for (_n, p), a in zip(model.named_parameters(), ps):
            p._data = a
        out = model(pt.to_tensor(ids))
        return GPTPretrainingCriterion()(out, pt.to_tensor(labels))._data

    def reference_loss(ps):
        x = ps[0][ids]
        for i in range(CFG["num_hidden_layers"]):
            lo, hi = want.bounds[i]
            x = ref.block(ps[lo:hi], x, cfg=CFG,
                          attn=ref.is_attention(CFG, i), rnd=ref.exact)
        return ref.head_loss(x, ps[-1], ps[0], jnp.asarray(labels),
                             eps=1e-6, rnd=ref.exact) / labels.size

    loss, grads = jax.value_and_grad(program_loss)(params)
    loss0, grads0 = jax.value_and_grad(reference_loss)(want.params)
    for (_n, p), a in zip(model.named_parameters(), params):
        p._data = a
    assert abs(float(loss) - float(loss0)) < 2e-6 * float(loss0)
    for name, g, g0 in zip(names, grads, grads0):
        assert rel(g, g0) < 2e-4, (name, rel(g, g0))


def driver_step(seed, read_logits_first=False):
    """TrainStep + AdamW(bf16 moments) + amp O1 + every block recomputed,
    written as benchmarks/drivers/jamba_train_window.py writes it."""
    model = build(seed, use_flash_attention=True, recompute=True,
                  recompute_interval=1)
    model.train()
    opt = AdamW(parameters=model.parameters(), **OPT)
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        if read_logits_first:
            logits._data
        return crit(logits, labels)

    return TrainStep(model, opt, loss_fn)


@pytest.mark.parametrize("seed", [5, 6])
def test_two_train_steps_follow_the_reference_trainer(seed):
    step = driver_step(seed)
    trainer = ref.Trainer(CFG, seed, OPT, 2)
    for k in range(2):
        ids, labels = tokens(seed, k)
        loss = float(step(ids, labels).numpy())
        loss0, _norms = trainer.step(ids, labels)
        assert abs(loss - loss0) < 3e-3 * loss0, (k, loss, loss0)
    specs = ref.param_specs(CFG)
    got = weights.change_norms(step.params, specs, seed)
    want = trainer.change_norms()
    gaps = sorted(abs(a - b) / b for a, b in zip(got, want))
    # a leaf the step left alone would read 1.0; bf16 arithmetic and
    # moments at 48 tokens a step read a few hundredths
    assert gaps[len(gaps) // 2] < 0.03 and gaps[-1] < 0.35, gaps[-3:]
    record = perf.compile_record("train_step")
    assert record["ssm_scan"] == "xla" and record["attention"] == "xla"


def test_the_head_is_deferred_and_the_whole_product_still_agrees():
    ids, labels = tokens(7)
    fused = driver_step(7)
    loss = float(fused(ids, labels).numpy())
    assert perf.compile_record("train_step")["head_loss"] == \
        "fused, chunks 1"
    whole = driver_step(7, read_logits_first=True)
    loss_whole = float(whole(ids, labels).numpy())
    assert perf.compile_record("train_step")["head_loss"] == "whole"
    assert abs(loss - loss_whole) < 2e-3 * loss_whole
    # every leaf's gradient, as the optimizer got it (the first moment
    # after one step, held in bfloat16)
    for a, b in zip(fused.opt_states, whole.opt_states):
        assert rel(a["moment1"], b["moment1"]) < 2e-2


def test_the_traced_step_names_every_layer_by_its_holder():
    """`…/layers/0/mamba/in_proj`, `…/mamba/ssm_scan_fwd|bwd` (the
    kernels' names, here from the interpreter-free CPU path's absence:
    the scopes of the projections and the head are what a CPU lowering
    shows), `…/layers/2/attn/q_proj`, `…/mlp/gate_proj`, `lm_head`."""
    step = driver_step(9)
    ids, labels = tokens(9)
    text = step._step_fn.jit_fn.lower(
        step.params, step.opt_states, step.buffers, jax.random.PRNGKey(0),
        jnp.float32(1e-3), [jnp.asarray(ids), jnp.asarray(labels)],
        {}).as_text(debug_info=True)
    for path in ("jamba/layers/0/mamba/in_proj", "layers/0/mamba/conv1d",
                 "layers/0/mamba/x_proj", "layers/0/mamba/dt_proj",
                 "layers/0/mamba/out_proj", "layers/0/mamba/dt_layernorm",
                 "layers/2/attn/q_proj", "layers/2/attn/o_proj",
                 "layers/3/mlp/gate_proj", "layers/3/mlp/down_proj",
                 "jamba/final_layernorm", "lm_head", "optimizer"):
        assert path in text, path
    assert "layers/2/mamba" not in text and "layers/0/attn" not in text
