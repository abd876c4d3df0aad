"""The head and the loss in token chunks (`ops.linear_cross_entropy`),
and how a traced training forward of GPT gets there: deferred logits
that `GPTPretrainingCriterion` never asks for, and that any other reader
gets whole.

Everything here is tiny and runs on the CPU: values, gradients, which
path a program took. What the path is worth is a chip's to say
(PERF.md)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import amp, ops
from paddle_tpu.core.tensor import DeferredTensor
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.observability import perf
from paddle_tpu.ops import nn_ops
from paddle_tpu.optimizer import SGD

N, H, V = 300, 64, 512


def _operands(seed, tied=True):
    """hidden [N, H], the head's weight ([V, H] tied, [H, V] untied) and
    labels; float32 masters that take the gradients."""
    rng = np.random.default_rng(seed)
    hidden = pt.to_tensor(rng.standard_normal((N, H)).astype(np.float32),
                          stop_gradient=False)
    w = (rng.standard_normal((V, H) if tied else (H, V)) * 0.1)
    weight = pt.to_tensor(w.astype(np.float32), stop_gradient=False)
    labels = pt.to_tensor(rng.integers(0, V, (N,)).astype(np.int32))
    return hidden, weight, labels


def _whole(hidden, weight, labels, mask, tied):
    """The criterion on whole logits: what the fused op has to equal."""
    logits = ops.matmul(hidden, weight, transpose_y=tied)
    return GPTPretrainingCriterion()(logits, labels, mask)


def _fused(hidden, weight, labels, mask, tied, chunk):
    token_weight = None
    if mask is not None:
        m = ops.cast(mask, "float32")
        token_weight = m / ops.maximum(ops.sum(m), 1e-6)
    return ops.linear_cross_entropy(hidden, weight, labels, token_weight,
                                    transpose_y=tied, chunk=chunk)


def _loss_and_grads(fn, hidden, weight, scale, dtype):
    hidden.clear_grad()
    weight.clear_grad()
    h, w = hidden, weight
    if dtype != "float32":
        h, w = ops.cast(hidden, dtype), ops.cast(weight, dtype)
    loss = fn(h, w)
    (loss * scale).backward()
    return float(loss), hidden.grad.numpy(), weight.grad.numpy()


CASES = {
    # chunk, mask, labels ignored, cotangent, tied head
    "chunk-divides": dict(chunk=100),
    "chunk-does-not-divide": dict(chunk=128),
    "one-chunk": dict(chunk=4096),
    "loss-mask": dict(chunk=128, mask=True),
    "ignore-index": dict(chunk=128, ignored=True),
    "cotangent-not-one": dict(chunk=128, scale=37.5),
    "untied-head": dict(chunk=128, tied=False),
    "untied-masked-scaled": dict(chunk=100, tied=False, mask=True,
                                 scale=0.25),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_fused_op_equals_cross_entropy_of_the_whole_product(case, dtype):
    c = dict(chunk=128, mask=False, ignored=False, scale=1.0, tied=True)
    c.update(CASES[case])
    hidden, weight, labels = _operands(list(CASES).index(case), c["tied"])
    rng = np.random.default_rng(7)
    if c["ignored"]:
        lbl = labels.numpy().copy()
        lbl[rng.random(N) < 0.3] = -100
        labels = pt.to_tensor(lbl)
    mask = (pt.to_tensor((rng.random(N) < 0.6).astype(np.float32))
            if c["mask"] else None)

    want = _loss_and_grads(
        lambda h, w: _whole(h, w, labels, mask, c["tied"]),
        hidden, weight, c["scale"], dtype)
    got = _loss_and_grads(
        lambda h, w: _fused(h, w, labels, mask, c["tied"], c["chunk"]),
        hidden, weight, c["scale"], dtype)
    if dtype == "float32":
        tol = dict(rtol=1e-6, atol=1e-6 * c["scale"])
    else:
        # both round the logits and their gradient to bfloat16; the whole
        # path also rounds dW and adds the two halves of the logits'
        # gradient in bfloat16, where the fused op stays in float32
        tol = dict(rtol=2e-2, atol=2e-4 * c["scale"])
    assert got[0] == pytest.approx(want[0], rel=1e-6)
    np.testing.assert_allclose(got[1], want[1], **tol)
    np.testing.assert_allclose(got[2], want[2], **tol)
    if c["ignored"]:
        # a token whose label is ignored moves nothing
        assert not got[1][labels.numpy() == -100].any()


@pytest.mark.parametrize("n,chunk,want", [
    (8192, 4096, (2, 4096)), (16384, 4096, (4, 4096)),
    (300, 4096, (1, 300)), (300, 100, (3, 100)),
    (4097, 4096, (2, 2176)), (300, 128, (3, 128)), (1000, 512, (2, 512)),
])
def test_chunk_plan(n, chunk, want):
    """The fewest chunks of at most `chunk` tokens, padded to whole
    tiles where they do not divide."""
    k, c = nn_ops._lce_plan(n, chunk)
    assert (k, c) == want and k * c >= n and c <= max(chunk, n)


def test_undifferentiated_call_computes_no_gradient():
    """The primal of the custom_vjp: a loss with no dW in the program."""
    hidden, weight, labels = _operands(0)
    text = jax.jit(nn_ops.linear_cross_entropy.raw_fn).lower(
        hidden._data, weight._data, labels._data).as_text()
    assert text.count("stablehlo.dot_general") == 1
    assert float(ops.linear_cross_entropy(
        hidden.detach(), weight.detach(), labels, chunk=128)) == \
        pytest.approx(float(_whole(hidden, weight, labels, None, True)),
                      rel=1e-6)


# ---------------------------------------------------------------------------
# through the model: the two calls a training loop makes
# ---------------------------------------------------------------------------
def _model(tied=True):
    pt.seed(0)
    model = GPTForCausalLM(gpt_tiny(
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
        tie_word_embeddings=tied))
    model.train()
    return model


def _driver_step(model, read=None):
    """`loss_fn` as benchmarks/drivers/train_window.py writes it: logits
    under `auto_cast`, the criterion outside. `read(logits)` stands for
    any other reader of the logits."""
    crit = GPTPretrainingCriterion()

    def loss_fn(m, ids, labels):
        with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            logits = m(ids)
        if read is not None:
            read(logits)
        return crit(logits, labels)

    opt = SGD(learning_rate=0.1, parameters=model.parameters())
    return TrainStep(model, opt, loss_fn)


def _run(step, steps=3):
    rng = np.random.default_rng(1)
    losses = []
    for _ in range(steps):
        toks = rng.integers(0, 1024, (4, 65)).astype(np.int32)
        losses.append(float(step(toks[:, :-1], toks[:, 1:]).numpy()))
    return losses, [np.asarray(p, np.float32) for p in step.params]


def _record_of(step):
    perf._FAMILY_COMPILE.pop("train_step", None)
    out = _run(step)
    return out, perf.compile_record("train_step")


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_driver_shaped_step_equals_the_step_with_whole_logits(tied):
    (fused, p_fused), rec_f = _record_of(_driver_step(_model(tied)))
    (whole, p_whole), rec_w = _record_of(
        _driver_step(_model(tied), read=lambda logits: logits._data))
    # which path each program took is in its record
    assert rec_f["head_loss"] == "fused, chunks 1"
    assert rec_w["head_loss"] == "whole"
    np.testing.assert_allclose(fused, whole, rtol=3e-5)
    # three updates apart by what bfloat16 leaves of a gradient (the
    # whole path rounds dW to it, the fused one does not)
    for a, b in zip(p_fused, p_whole):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=3e-4)
    assert fused[-1] < fused[0]         # and it trains


def test_another_reader_of_the_logits_gets_the_whole_array():
    seen = {}

    def read(logits):
        seen["before"] = (type(logits), logits.computed, logits.shape,
                          logits.dtype.name)
        seen["argmax"] = ops.argmax(logits, axis=-1).shape
        seen["after"] = logits.computed

    (_losses, _p), rec = _record_of(_driver_step(_model(), read=read))
    assert seen["before"] == (DeferredTensor, False, [4, 64, 1024],
                              "bfloat16")
    assert seen["argmax"] == [4, 64] and seen["after"] is True
    assert rec["head_loss"] == "whole"


def test_a_step_that_never_meets_the_criterion_records_whole():
    model = _model()

    def loss_fn(m, ids, labels):
        return ops.mean(ops.cross_entropy(m(ids), labels,
                                          reduction="none"))

    step = TrainStep(model, SGD(learning_rate=0.5,
                                parameters=model.parameters()), loss_fn)
    (losses, _p), rec = _record_of(step)
    assert rec["head_loss"] == "whole" and losses[-1] < losses[0]


def test_loss_mask_goes_through_the_fused_path():
    rng = np.random.default_rng(3)
    toks = rng.integers(0, 1024, (4, 65)).astype(np.int32)
    mask = (rng.random((4, 64)) < 0.5).astype(np.float32)
    crit = GPTPretrainingCriterion()
    losses = {}
    for name, force in (("fused", False), ("whole", True)):
        model = _model()

        def loss_fn(m, ids, labels, mask):
            logits = m(ids)
            if force:
                logits._data
            return crit(logits, labels, mask)

        step = TrainStep(model, SGD(learning_rate=0.5,
                                    parameters=model.parameters()), loss_fn)
        perf._FAMILY_COMPILE.pop("train_step", None)
        losses[name] = [float(step(toks[:, :-1], toks[:, 1:], mask).numpy())
                        for _ in range(2)]
        assert perf.compile_record("train_step")["head_loss"].startswith(
            name)
    np.testing.assert_allclose(losses["fused"], losses["whole"], rtol=2e-6)


@pytest.mark.parametrize("mode", ["eager-train", "eager-eval",
                                  "traced-eval", "traced-decode"])
def test_every_other_forward_returns_a_plain_tensor(mode):
    from paddle_tpu.jit import _collect_params, _functional_params
    model = _model()
    ids = pt.to_tensor(np.zeros((2, 8), np.int32))
    if mode.endswith("eval"):
        model.eval()
    if mode.startswith("eager"):
        out = model(ids)
    else:
        _n, ptensors, _b, _bt = _collect_params(model)
        caches = None
        if mode == "traced-decode":
            caches = [(pt.to_tensor(np.zeros((2, 4, 4, 32), np.float32)),) * 2
                      for _ in range(2)]
        with _functional_params(ptensors, [p._data for p in ptensors]), \
                pt.no_grad():
            out = model(ids, caches=caches)
        if caches is not None:
            out = out[0]
    assert type(out) is pt.Tensor and out.shape == [2, 8, 1024]


def test_a_deferred_tensor_leaves_a_traced_function_as_its_array():
    """jit flattens what a function returns: the promise is kept there."""
    from paddle_tpu.jit import _collect_params, _functional_params
    model = _model()
    _n, ptensors, _b, _bt = _collect_params(model)

    def fwd(params, ids):
        with _functional_params(ptensors, params), pt.no_grad():
            out = model(pt.Tensor._wrap(ids))
        assert isinstance(out, DeferredTensor) and not out.computed
        return out

    ids = jnp.zeros((2, 8), jnp.int32)
    got = jax.jit(fwd)([p._data for p in ptensors], ids)
    model.eval()
    want = model(pt.Tensor._wrap(ids))
    np.testing.assert_allclose(np.asarray(got._data), want.numpy(),
                               rtol=1e-5, atol=1e-5)


def test_under_a_mesh_the_step_takes_the_whole_path():
    """`gpt_tp_rules` shards the tied embedding over `mp`; that program
    is the one the sharded tests hold to the unsharded one, and it stays
    as it was."""
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.models.shard_plans import gpt_tp_rules
    model = _model()
    crit = GPTPretrainingCriterion()
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
    step = TrainStep(
        model, SGD(learning_rate=0.5, parameters=model.parameters()),
        lambda m, ids, labels: crit(m(ids), labels), mesh=mesh,
        shard_param=gpt_tp_rules, shard_data=P("dp"))
    (sharded, _p), rec = _record_of(step)
    assert rec["head_loss"] == "whole"
    model = _model()
    plain = TrainStep(
        model, SGD(learning_rate=0.5, parameters=model.parameters()),
        lambda m, ids, labels: crit(m(ids), labels))
    (fused, _p), rec = _record_of(plain)
    assert rec["head_loss"] == "fused, chunks 1"
    np.testing.assert_allclose(sharded, fused, rtol=2e-5)


@pytest.mark.parametrize("vocab,want", [
    (25088, 4096), (50304, 4096), (65536, 4096),    # the cells that ran so
    (100352, 4096), (131136, 2048), (262272, 1024)])
def test_a_wider_head_takes_fewer_tokens_a_chunk(vocab, want):
    assert nn_ops.lce_chunk(vocab) == want
