"""`recompute(..., policy="flash_outputs")`: a recomputed block keeps the
flash forward kernel's `o` and `lse` (`FLASH_O`, `FLASH_LSE`), so its
backward holds no second forward kernel; the three models ask for it in
a layer whose attention has no window. On the CPU, the kernels
interpreted at the smallest sizes they take. The compiled steps' kernel
counts at the models' widths are `tests/test_tpu_aot_compile.py`'s.

And what a layer keeps where its class declares `branch_outputs` (the
products a norm inside a residual branch reads: Ouro's sandwich): the
policy of names, the note's count of layers and bytes, and that every
other family's step is what it was (Ouro's own bits and products:
`tests/test_ouro_model.py`)."""
import functools
import re
from importlib import import_module

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models, nn, ops
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import (GPTForCausalLM, GPTPretrainingCriterion,
                               JambaForCausalLM, LagunaForCausalLM,
                               OuroDecoderLayer, OuroForCausalLM,
                               ZayaForCausalLM, ouro_tiny)
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.jamba import JambaConfig
from paddle_tpu.models.laguna import LagunaConfig
from paddle_tpu.models.zaya import ZayaConfig
from paddle_tpu.observability import perf
from paddle_tpu.optimizer import AdamW

fa = import_module("paddle_tpu.kernels.pallas.flash_attention")
rc = import_module("paddle_tpu.distributed.meta_parallel.recompute")
# the other kernels copy `fa._pallas_available` as they are imported: they
# are imported here, before a test stands another in its place
for _kernel in ("selective_scan", "grouped_matmul", "norms", "gated_delta"):
    import_module(f"paddle_tpu.kernels.pallas.{_kernel}")

SEQ, VOCAB = 128, 256       # one q block of 64-wide heads: what the kernels take


def _interpreted_kernels(monkeypatch):
    """Attention goes to the flash kernels, which run interpreted (and
    nothing else to a kernel: the backend still answers "cpu")."""
    monkeypatch.setattr(fa, "_pallas_available", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_AUTOTUNE", "0")
    for name in ("_flash_fwd_fused", "_flash_bwd_fused"):
        monkeypatch.setattr(fa, name, functools.partial(
            getattr(fa, name), interpret=True))


def _jamba(**kw):
    """A Mamba layer, then an attention layer."""
    return JambaForCausalLM(JambaConfig(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        head_dim=64, attn_layer_period=2, attn_layer_offset=1,
        mamba_dt_rank=8, **kw))


def _laguna(layer_types=("sliding_attention", "full_attention"), **kw):
    n = len(layer_types)
    return LagunaForCausalLM(LagunaConfig(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
        num_hidden_layers=n, num_attention_heads=2, num_key_value_heads=2,
        head_dim=64, num_experts=4, num_experts_per_tok=2,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        sliding_window=32, layer_types=list(layer_types),
        num_attention_heads_per_layer=[2] * n, **kw))


def _zaya(layers=2, **kw):
    return ZayaForCausalLM(ZayaConfig(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=layers,
        num_attention_heads=4, num_key_value_heads=2, head_dim=64,
        num_experts=4, moe_intermediate_size=64, router_hidden_size=32,
        max_position_embeddings=256, **kw))


MODELS = {"jamba": _jamba, "laguna": _laguna, "zaya": _zaya}


def _batch(seed=0):
    toks = np.random.default_rng(seed).integers(
        0, VOCAB, (1, SEQ + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def _loss_and_grads(build, flash):
    pt.seed(0)
    model = build(recompute=True, use_flash_attention=flash)
    model.train()
    ids, labels = _batch()
    loss = GPTPretrainingCriterion()(model(pt.to_tensor(ids)),
                                     pt.to_tensor(labels))
    loss.backward()
    return loss.numpy(), {n: p.grad.numpy()
                          for n, p in model.named_parameters()}


# -- (a) the same bits -------------------------------------------------------
@pytest.mark.parametrize("path", ["composite", "interpreted kernels"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_keeping_the_flash_outputs_changes_no_bit(monkeypatch, name, path):
    """Loss and every gradient leaf of a model that recomputes every
    block, with the policy its loop asks for and with none."""
    flash = path != "composite"
    if flash:
        _interpreted_kernels(monkeypatch)
    asked = []
    policy = rc.flash_policy

    def spy(attention):
        asked.append(policy(attention))
        return asked[-1]

    monkeypatch.setattr(rc, "flash_policy", spy)
    loss, grads = _loss_and_grads(MODELS[name], flash)
    # the composite names nothing, and nobody asks it to
    assert ("flash_outputs" in asked) == flash, asked
    monkeypatch.setattr(rc, "flash_policy", lambda attention: None)
    loss0, grads0 = _loss_and_grads(MODELS[name], flash)
    assert np.isfinite(loss) and loss.tobytes() == loss0.tobytes()
    assert grads.keys() == grads0.keys() and len(grads) > 8
    for leaf, g in grads.items():
        assert g.tobytes() == grads0[leaf].tobytes(), leaf
        assert np.isfinite(g).all(), leaf


# -- (b) the second forward kernel is gone, where it was asked to go ---------
def _step_jaxpr(model):
    """The jaxpr of the model's whole `TrainStep` program, as text."""
    model.train()
    crit = GPTPretrainingCriterion()
    step = TrainStep(
        model, AdamW(learning_rate=1e-3, parameters=model.parameters()),
        lambda m, ids, labels: crit(m(ids), labels))
    ids, labels = _batch()
    return str(step._step_fn.jit_fn.trace(
        step.params, step.opt_states, step.buffers, jax.random.PRNGKey(0),
        np.float32(1e-3), [ids, labels], {}).jaxpr)


def _kernel_calls(text):
    names = re.findall(r"\bname=(flash_(?:fwd|bwd)\w*)", text)
    return {n: names.count(n) for n in set(names)}


@pytest.mark.parametrize("build,kw,forwards", [
    (_laguna, {"layer_types": ("full_attention",)}, 1),
    (_laguna, {"layer_types": ("sliding_attention",)}, 2),
    (_laguna, {}, 3),           # a window layer and a full one
    (_zaya, {"layers": 1}, 1),
    (_jamba, {}, 1),
], ids=["laguna full", "laguna window", "laguna both", "zaya", "jamba"])
def test_a_kept_blocks_backward_holds_no_forward_kernel(monkeypatch, build,
                                                        kw, forwards):
    """The step's program holds the forward kernel once for a recomputed
    block that keeps `o` and `lse`, twice for a window layer's (which
    keeps nothing), and a backward kernel a layer either way."""
    _interpreted_kernels(monkeypatch)
    pt.seed(0)
    calls = _kernel_calls(_step_jaxpr(build(
        recompute=True, use_flash_attention=True, **kw)))
    layers = 2 if forwards == 3 else 1
    assert calls == {"flash_fwd": forwards, "flash_bwd_transpose": layers}


def test_without_the_policy_every_block_runs_its_forward_kernel_twice(
        monkeypatch):
    """What the count above is against: the same Zaya step with no policy."""
    _interpreted_kernels(monkeypatch)
    monkeypatch.setattr(rc, "flash_policy", lambda attention: None)
    pt.seed(0)
    calls = _kernel_calls(_step_jaxpr(_zaya(
        layers=1, recompute=True, use_flash_attention=True)))
    assert calls == {"flash_fwd": 2, "flash_bwd_transpose": 1}


def test_a_name_nobody_asks_for_changes_no_program(monkeypatch):
    """A GPT step that recomputes under policy `full` (the benchmark's
    1.3B cell) compiles to the text it had before the outputs had names."""
    _interpreted_kernels(monkeypatch)

    def compiled():
        pt.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=VOCAB, hidden_size=128, num_layers=1, num_heads=2,
            max_position_embeddings=SEQ, use_flash_attention=True,
            recompute=True, recompute_policy="full"))
        model.train()
        crit = GPTPretrainingCriterion()
        step = TrainStep(
            model, AdamW(learning_rate=1e-3, parameters=model.parameters()),
            lambda m, ids, labels: crit(m(ids), labels))
        ids, labels = _batch()
        return step._step_fn.jit_fn.lower(
            step.params, step.opt_states, step.buffers,
            jax.random.PRNGKey(0), np.float32(1e-3), [ids, labels],
            {}).compile().as_text()

    def program(text):
        # without where in the sources an instruction came from
        text = re.sub(r" stack_frame_id=\d+", "", text)
        return [line for line in text.splitlines() if not re.match(
            r"\d+ |FileNames|FunctionNames|FileLocations|StackFrames", line)]

    with_names = program(compiled())
    monkeypatch.setattr(fa, "_kept", lambda o, lse: (o, lse))
    assert len(with_names) > 100 and program(compiled()) == with_names


# -- (c), (d) the policy by itself -------------------------------------------
def test_the_policy_is_inert_in_a_block_with_no_attention():
    pt.seed(0)
    block = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 16))
    x = np.random.default_rng(0).standard_normal((4, 16)).astype(np.float32)
    got = []
    for policy in (None, "flash_outputs"):
        for p in block.parameters():
            p.clear_grad()
        inp = pt.to_tensor(x, stop_gradient=False)
        out = rc.recompute(block, inp, policy=policy)
        ops.mean(out ** 2).backward()
        got.append([out.numpy(), inp.grad.numpy()]
                   + [p.grad.numpy() for p in block.parameters()])
    assert len(got[0]) == 6
    for a, b in zip(*got):
        assert a.tobytes() == b.tobytes()


def test_an_unknown_policy_still_raises():
    with pytest.raises(ValueError, match="flash_outputs"):
        rc.recompute(lambda x: x, pt.to_tensor(np.zeros(2, np.float32)),
                     policy="flash_output")


# -- which layers ask --------------------------------------------------------
class _Attention:
    def __init__(self, flash=True, **kw):
        self.use_flash_attention = flash
        self.__dict__.update(kw)


@pytest.mark.parametrize("attention,policy", [
    (None, None),                               # a Mamba mixer's block
    (_Attention(), "flash_outputs"),            # no window to have
    (_Attention(window=None), "flash_outputs"),
    (_Attention(window=512), None),
    (_Attention(flash=False), None),            # the composite: no kernel
], ids=["no attention", "full", "window None", "window 512", "composite"])
def test_a_layer_keeps_where_every_key_is_in_sight(attention, policy):
    assert rc.flash_policy(attention) == policy


def _notes(run):
    """(`run()`, what it noted) as a traced first call would see it."""
    notes = {}
    outer, perf._TRACE_NOTES.notes = perf._TRACE_NOTES.notes, notes
    try:
        return run(), notes
    finally:
        perf._TRACE_NOTES.notes = outer


SANDWICH = (fa.FLASH_O, fa.FLASH_LSE, rc.MLP_OUT)


@pytest.mark.parametrize("policies,note", [
    (["flash_outputs"] * 5, "5 of 5"),
    (["flash_outputs", None, None, None, "flash_outputs"], "2 of 5"),
    ([None] * 3, "0 of 3"),
    ([], None),                                 # nothing recomputed
    # sandwich layers: a clause behind the text, which stays as it was
    ([SANDWICH] * 3, "3 of 3 recomputed layers, branch outputs a norm "
                     "reads in 3 (4096 bytes a pass)"),
    ([SANDWICH, "flash_outputs", None, (rc.ATTN_OUT, rc.MLP_OUT)],
     "2 of 4 recomputed layers, branch outputs a norm reads in 2 "
     "(4096 bytes a pass)"),
], ids=["all", "some", "none", "no recompute", "sandwich", "mixed"])
def test_the_note_counts_the_recomputed_layers_that_keep(policies, note):
    _, notes = _notes(lambda: rc.note_flash_kept(policies, 4096))
    if note is not None and "branch" not in note:
        note += " recomputed layers"
    assert notes == ({} if note is None else {
        "flash_kept": f"o and lse kept across recompute in {note}"})


# -- a layer that declares branch outputs -------------------------------------
class _Sandwich:
    branch_outputs = (rc.MLP_OUT,)

    def __init__(self, attention=None):
        self.attn = attention


@pytest.mark.parametrize("layer,policy", [
    (_Sandwich(_Attention()), SANDWICH),
    (_Sandwich(_Attention(flash=False)), (rc.MLP_OUT,)),
    (_Sandwich(_Attention(window=512)), (rc.MLP_OUT,)),
    (_Sandwich(), (rc.MLP_OUT,)),
    (_Attention(attn=_Attention()), "flash_outputs"),   # declares nothing
    (_Attention(), None),                               # ... and no `attn`
], ids=["flash", "composite", "window", "no attention", "pre-norm",
        "pre-norm, no attention"])
def test_a_layers_policy_is_its_flash_policy_and_what_it_declares(layer,
                                                                 policy):
    assert rc.layer_policy(layer) == policy


def test_a_policy_of_names_keeps_those_names_and_nothing_else():
    """`recompute(..., policy=(names))`: the block's backward reads the
    named value and makes the rest again; the bits are the block's."""
    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.first, self.second = nn.Linear(16, 32), nn.Linear(32, 16)

        def forward(self, x):
            return self.second(ops.tanh(
                rc.branch_output(self.first(x), rc.MLP_OUT)))

    pt.seed(0)
    block = Block()
    x = np.random.default_rng(0).standard_normal((4, 16)).astype(np.float32)
    got = []
    for policy in (None, (rc.MLP_OUT,), (rc.ATTN_OUT,)):
        for p in block.parameters():
            p.clear_grad()
        inp = pt.to_tensor(x, stop_gradient=False)
        out = rc.recompute(block, inp, policy=policy)
        ops.mean(out ** 2).backward()
        got.append([out.numpy(), inp.grad.numpy()]
                   + [p.grad.numpy() for p in block.parameters()])
    assert len(got[0]) == 6
    for other in got[1:]:
        for a, b in zip(got[0], other):
            assert a.tobytes() == b.tobytes()

    def dots(policy):
        def loss(a):
            with pt.no_grad():      # JAX's own gradient, past the tape
                return rc.recompute(block, pt.to_tensor(a),
                                    policy=policy)._data.sum()
        return str(jax.make_jaxpr(jax.grad(loss))(x)).count("dot_general")

    # to the input alone: both products forward, a product back each,
    # and the first one again for tanh's slope, unless its output is kept
    assert (dots(None), dots((rc.MLP_OUT,))) == (5, 4)
    assert dots((rc.ATTN_OUT,)) == 5                # a name nobody gave


@pytest.mark.parametrize("declared,layers,size", [
    ((), 0, 0), ((rc.MLP_OUT,), 3, 1), ((rc.ATTN_OUT, rc.MLP_OUT), 3, 2)],
    ids=["none", "down_proj's", "both"])
def test_the_walk_counts_the_branch_outputs_a_sandwich_stack_keeps(
        monkeypatch, declared, layers, size):
    """One forward of `ouro_tiny` (three layers, recomputed): the note's
    clause counts the layers that keep and the bytes of what they named
    for their policy, float32 [2, 24, 64] an output here; a stack that
    declares nothing says what it said."""
    monkeypatch.setattr(OuroDecoderLayer, "branch_outputs", declared)
    pt.seed(0)
    model = OuroForCausalLM(ouro_tiny(recompute=True))
    model.train()
    ids, _ = _batch()
    _, notes = _notes(lambda: model(pt.to_tensor(ids[:, :24].repeat(2, 0))))
    said = "o and lse kept across recompute in 0 of 3 recomputed layers"
    if layers:
        said += (f", branch outputs a norm reads in {layers} "
                 f"({size * 3 * 2 * 24 * 64 * 4} bytes a pass)")
    assert notes["flash_kept"] == said


FAMILIES = {"jamba": "JambaForCausalLM", "laguna": "LagunaForCausalLM",
            "zaya": "ZayaForCausalLM", "qwen3_next": "Qwen3NextForCausalLM",
            "deepseek_v2": "DeepseekV2ForCausalLM"}


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_a_family_that_declares_nothing_lowers_to_the_step_it_had(
        monkeypatch, name):
    """The five other families that walk `layer_calls`, at their tiny
    configurations with every block recomputed and flash asked for: the
    lowered `TrainStep`'s text under `layer_policy` and under
    `flash_policy` of the layer's `attn` alone, the walk's policy before
    a layer could declare branch outputs, is the same text."""
    tiny = getattr(import_module(f"paddle_tpu.models.{name}"),
                   f"{name}_tiny")

    def lowered():
        pt.seed(0)
        model = getattr(models, FAMILIES[name])(tiny(
            recompute=True, use_flash_attention=True))
        model.train()
        crit = GPTPretrainingCriterion()
        step = TrainStep(
            model, AdamW(learning_rate=1e-3, parameters=model.parameters()),
            lambda m, ids, labels: crit(m(ids), labels))
        toks = np.random.default_rng(0).integers(
            0, 512, (1, 33)).astype(np.int32)
        return step._step_fn.jit_fn.lower(
            step.params, step.opt_states, step.buffers,
            jax.random.PRNGKey(0), np.float32(1e-3),
            [toks[:, :-1], toks[:, 1:]], {}).as_text()

    asked = []
    policy = rc.layer_policy

    def spy(layer):
        asked.append(policy(layer))
        return asked[-1]

    monkeypatch.setattr(rc, "layer_policy", spy)
    text = lowered()
    assert asked and set(asked) <= {None, "flash_outputs"}
    monkeypatch.setattr(rc, "layer_policy", lambda layer: rc.flash_policy(
        getattr(layer, "attn", None)))
    assert len(text) > 10000 and lowered() == text
