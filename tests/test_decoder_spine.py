"""What the decoder families share, each written once:
`causal_attention` (incubate/nn/functional: where an attention layer
reaches a kernel), `layer_calls` (distributed/meta_parallel/recompute.py:
the walk over a stack's layers, recomputed or not) and `_flash_core`
(kernels/pallas/flash_attention.py: the one `custom_vjp` over the kernel
pair, on three arrays or on one fused projection). On the CPU; the flash
kernels interpreted."""
import functools
from importlib import import_module

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.incubate.nn.functional import causal_attention
from paddle_tpu.observability import perf

fa = import_module("paddle_tpu.kernels.pallas.flash_attention")
rc = import_module("paddle_tpu.distributed.meta_parallel.recompute")


def _notes(run):
    """`run()` as a traced first call would see it: (its result, what
    it noted)."""
    notes = {}
    outer, perf._TRACE_NOTES.notes = perf._TRACE_NOTES.notes, notes
    try:
        return run(), notes
    finally:
        perf._TRACE_NOTES.notes = outer


# -- the attention call -------------------------------------------------------
def _softmax_reference(q, k, v, window):
    """float32 `jax.numpy`: query head h on key/value head h // (H // Hk),
    row i on keys max(i - window + 1, 0) .. i."""
    q, k, v = (jnp.asarray(x, jnp.float32) for x in (q, k, v))
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    gap = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])[None]
    ok = gap >= 0 if window is None else (gap >= 0) & (gap < window)
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("flash", [True, False], ids=["flash", "composite"])
@pytest.mark.parametrize("window", [None, 8])
@pytest.mark.parametrize("H,Hk", [(4, 4), (4, 2), (6, 2)])
def test_the_attention_call_against_a_float32_softmax(H, Hk, window, flash):
    rng = np.random.default_rng(H * 10 + Hk)
    q, k, v = (rng.standard_normal((2, 32, n, 16)).astype(np.float32)
               for n in (H, Hk, Hk))
    out, notes = _notes(lambda: causal_attention(
        pt.to_tensor(q), pt.to_tensor(k), pt.to_tensor(v), flash, window))
    np.testing.assert_allclose(
        out.numpy(), np.asarray(_softmax_reference(q, k, v, window)),
        rtol=2e-5, atol=2e-5)
    # off a TPU the flash path is the kernels' XLA form; the window's
    # own note is the caller's (it names the layer)
    assert notes == {"attention": "xla" if flash else "composite"}


# -- the walk over a stack's layers -------------------------------------------
class _Attn:
    def __init__(self, flash, window=None):
        self.use_flash_attention, self.window = flash, window


class _Block:
    def __init__(self, attn=None):
        if attn is not None:
            self.attn = attn


def test_the_walk_asks_a_policy_of_each_recomputed_layers_attention():
    """Flash outputs are kept where every key is in sight; a window
    layer, a layer without `attn` (a recurrence) and a layer that did
    not ask for flash get no policy; `flash_kept` is said once."""
    layers = [_Block(_Attn(True)), _Block(_Attn(True, window=8)), _Block(),
              _Block(_Attn(False)), _Block(_Attn(True))]
    calls, notes = _notes(lambda: list(rc.layer_calls(layers, True, 1)))
    assert all(isinstance(c, functools.partial) and c.func is rc.recompute
               for c in calls)
    assert [c.args for c in calls] == [(layer,) for layer in layers]
    assert [c.keywords for c in calls] == [
        {"policy": p} for p in ("flash_outputs", None, None, None,
                                "flash_outputs")]
    assert notes == {"flash_kept": "o and lse kept across recompute in 2 "
                                   "of 5 recomputed layers"}


@pytest.mark.parametrize("interval,recomputed", [(1, [0, 1, 2, 3, 4]),
                                                 (2, [0, 2, 4]),
                                                 (3, [0, 3])])
def test_the_walk_recomputes_every_interval_th_layer(interval, recomputed):
    layers = [_Block(_Attn(True)) for _ in range(5)]
    calls, notes = _notes(
        lambda: list(rc.layer_calls(layers, True, interval)))
    assert [i for i, c in enumerate(calls)
            if isinstance(c, functools.partial)] == recomputed
    assert [c for c in calls if not isinstance(c, functools.partial)] == [
        layer for i, layer in enumerate(layers) if i not in recomputed]
    assert notes["flash_kept"].endswith(
        f"in {len(recomputed)} of {len(recomputed)} recomputed layers")


def test_the_walk_without_recompute_hands_out_the_layers_and_notes_nothing():
    layers = [_Block(_Attn(True)), _Block()]
    calls, notes = _notes(lambda: list(rc.layer_calls(layers, False, 1)))
    assert calls == layers and notes == {}


def _stack(name, **kw):
    """(a tiny model of family `name`, its list of layers)."""
    tiny = getattr(import_module(f"paddle_tpu.models.{name}"),
                   f"{name}_tiny")
    model = {"jamba": models.JambaForCausalLM,
             "laguna": models.LagunaForCausalLM,
             "zaya": models.ZayaForCausalLM,
             "qwen3_next": models.Qwen3NextForCausalLM,
             "ouro": models.OuroForCausalLM}[name](tiny(**kw))
    inner = {"qwen3_next": "model", "ouro": "model"}.get(name, name)
    return model, list(getattr(model, inner).layers)


FAMILIES = ["jamba", "laguna", "zaya", "qwen3_next", "ouro"]


def _recomputed(monkeypatch, model, layers):
    """The places in `layers` of the layers one forward recomputes, in
    the order it reaches them, with each one's policy."""
    seen, real = [], rc.recompute

    def spy(function, *args, policy=None, **kw):
        if any(function is layer for layer in layers):
            seen.append((layers.index(function), policy))
        return real(function, *args, policy=policy, **kw)

    monkeypatch.setattr(rc, "recompute", spy)
    ids = np.random.default_rng(0).integers(0, 512, (1, 32)).astype(np.int32)
    model(pt.to_tensor(ids))
    return seen


@pytest.mark.parametrize("interval", [1, 2])
@pytest.mark.parametrize("name", FAMILIES)
def test_a_training_stack_recomputes_by_its_interval(monkeypatch, name,
                                                     interval):
    """Every family's loop is the one walk: layer i is recomputed where
    i % interval == 0, once a forward (Ouro's passes are one scanned
    body), only a layer with an `attn` that sees every key keeps its
    flash outputs, and only a layer that declares `branch_outputs` those
    (then the policy is the names)."""
    pt.seed(0)
    model, layers = _stack(name, recompute=True, use_flash_attention=True,
                           recompute_interval=interval)
    model.train()
    seen = _recomputed(monkeypatch, model, layers)
    assert [i for i, _ in seen] == list(range(0, len(layers), interval))
    for i, policy in seen:
        attn = getattr(layers[i], "attn", None)
        full = attn is not None and getattr(attn, "window", None) is None
        branch = getattr(layers[i], "branch_outputs", ())
        if branch:      # Ouro's sandwich: the names, the branch's among them
            assert full and policy == (fa.FLASH_O, fa.FLASH_LSE) + branch
        else:
            assert policy == ("flash_outputs" if full else None), (i, policy)


@pytest.mark.parametrize("name", FAMILIES)
def test_a_stack_that_is_not_training_recomputes_nothing(monkeypatch, name):
    pt.seed(0)
    model, layers = _stack(name, recompute=True, use_flash_attention=True)
    model.eval()
    assert _recomputed(monkeypatch, model, layers) == []


# -- the flash core -----------------------------------------------------------
@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_AUTOTUNE", "0")
    for name in ("_flash_fwd_fused", "_flash_bwd_fused"):
        monkeypatch.setattr(fa, name, functools.partial(
            getattr(fa, name), interpret=True))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("h,d", [(4, 64), (2, 128)])
def test_one_array_and_three_through_the_one_core(interpreted, h, d, causal):
    """The fused projection's form and the split form of the one
    `custom_vjp`, differentiated: the same `o`, and the one gradient
    array [b, s, 3*h*d] is the three side by side, to the bit."""
    rng = np.random.default_rng(h)
    b, s = 1, 256
    qkv = jnp.asarray(rng.standard_normal((b, s, 3 * h * d)), jnp.bfloat16)
    g = jnp.asarray(rng.standard_normal((b, s, h * d)), jnp.bfloat16)
    sc = d ** -0.5
    o1, back1 = jax.vjp(lambda x: fa._flash_core(
        (x,), None, causal, sc, True, None, h), qkv)
    (dqkv,) = back1(g)
    q, k, v = (x.reshape(b, s, h, d) for x in jnp.split(qkv, 3, axis=2))
    o3, back3 = jax.vjp(lambda *x: fa._flash_core(
        x, None, causal, sc, True), q, k, v)
    grads = back3(g.reshape(b, s, h, d))
    assert dqkv.shape == qkv.shape and len(grads) == 3
    np.testing.assert_array_equal(np.asarray(o1),
                                  np.asarray(o3.reshape(b, s, h * d)))
    np.testing.assert_array_equal(np.asarray(dqkv), np.asarray(
        jnp.concatenate([x.reshape(b, s, h * d) for x in grads], axis=2)))
    # and both are the composite's, which the core holds for a shape
    # the kernels reject
    ox, backx = jax.vjp(lambda *x: fa._flash_core(
        x, None, causal, sc, False), q, k, v)
    np.testing.assert_allclose(np.asarray(o3, np.float32),
                               np.asarray(ox, np.float32),
                               rtol=2e-2, atol=2e-2)
    for got, want in zip(grads, backx(g.reshape(b, s, h, d))):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=5e-2)


def test_the_grouped_split_form_hands_back_gradients_on_the_kv_heads(
        interpreted):
    """k and v on fewer heads: their gradients come back on those heads,
    against the composite's."""
    rng = np.random.default_rng(0)
    b, s, h, hk, d = 1, 256, 4, 2, 64
    q, k, v = (jnp.asarray(rng.standard_normal((b, s, n, d)), jnp.float32)
               for n in (h, hk, hk))
    g = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    outs = []
    for use_pallas in (True, False):
        o, back = jax.vjp(lambda *x: fa._flash_core(
            x, None, True, d ** -0.5, use_pallas, 64), q, k, v)
        outs.append((o,) + back(g))
    assert [x.shape for x in outs[0]] == [q.shape, q.shape, k.shape, v.shape]
    for got, want in zip(*outs):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
