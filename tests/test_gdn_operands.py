"""`ops.gdn_operands` (kernels/pallas/gdn_operands.py) and the gated delta
rule at the key heads, on the CPU: the fused kernels in Pallas's
interpreter and the op's own chain of XLA ops against the chain the
layer was written as (concat -> `causal_conv1d` -> silu -> split -> the
unit norms), values and the gradients to `qkvz` and `conv_weight`, over
two row blocks with a part of one, a row block wider than the sequence,
and two batch rows; `gated_delta_rule` with q and k at Hk heads against
the same call on q and k copied a value head, o and all five gradients,
on the kernels and in XLA; its two entries against each other. The
kernels compiled for the chip: tests/test_tpu_aot_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import ops
from paddle_tpu.kernels.pallas import gated_delta as gd
from paddle_tpu.kernels.pallas import gdn_operands as fused
from paddle_tpu.ops import linear_attn_ops as la

D, TAPS = 128, 4

SHAPES = [      # batch rows, tokens a row, key heads, value heads a key head
    # 640 rows of outputs in blocks of 512: the second block a quarter
    # full, its last 40 rows beyond s
    pytest.param(2, 600, 2, 2, id="rep2-two-row-blocks"),
    # one block of 128 rows over 100
    pytest.param(2, 100, 3, 1, id="rep1-a-block-past-the-row")]


def _drawn(b, s, Hk, rep, dtype=jnp.float32, seed=0):
    r = np.random.default_rng(seed)
    Hv = Hk * rep
    qkvz = jnp.asarray(r.normal(size=(b, s, (2 * Hk + 2 * Hv) * D)), dtype)
    w = jnp.asarray(r.normal(size=((2 * Hk + Hv) * D, TAPS)) * 0.5,
                    jnp.float32)
    return qkvz, w, Hv


def chain(qkvz, w, Hk, Hv):
    """The layer as it stood: -> q, k [b, s, Hk, d] float32, v
    [b, s, Hv, d], z [b, s, Hv, d], tokens first."""
    b, s, _ = qkvz.shape
    rep = Hv // Hk
    q, k, v, z = jnp.split(qkvz.reshape(b, s, Hk, -1),
                           [D, 2 * D, (2 + rep) * D], axis=-1)
    mixed = jnp.concatenate([x.reshape(b, s, -1) for x in (q, k, v)], -1)
    mixed = jax.nn.silu(ops.causal_conv1d.raw_fn(mixed, w))
    q, k, v = jnp.split(mixed, [Hk * D, 2 * Hk * D], axis=-1)

    def unit(x, scale):
        x = x.astype(jnp.float32)
        return x * (jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
                    * scale)
    return (unit(q.reshape(b, s, Hk, D), D ** -0.5),
            unit(k.reshape(b, s, Hk, D), 1.0), v.reshape(b, s, Hv, D),
            z.reshape(b, s, Hv, D))


def _heads_first(mode, qkvz, w, Hk, Hv):
    """q, k, v [b, H, sp, d] and z from the op's path `mode`."""
    z = qkvz.reshape(qkvz.shape[:2] + (Hk, -1))[..., w.shape[0] // Hk:]
    made = la._operands_in_xla(qkvz, w, Hk, Hv) if mode == "xla" else \
        fused.operands(qkvz, fused.taps_by_head(w, Hk, Hv), Hk, Hv, True)
    return (*made, z.reshape(qkvz.shape[:2] + (Hv, D)))


def _made(mode, qkvz, w, Hk, Hv):
    """The same with q, k and v [b, s, H, d], as the chain has them."""
    *qkv, z = _heads_first(mode, qkvz, w, Hk, Hv)
    return tuple(jnp.moveaxis(x[:, :, :qkvz.shape[1]], 1, 2)
                 for x in qkv) + (z,)


def _close(got, want, tol):
    assert got.shape == want.shape and got.dtype == want.dtype
    assert float(jnp.max(jnp.abs(got - want))) <= tol * max(
        float(jnp.max(jnp.abs(want))), 1e-30)


@pytest.mark.parametrize("mode", ["interpret", "xla"])
@pytest.mark.parametrize("b,s,Hk,rep", SHAPES)
def test_the_operands_are_the_chains(b, s, Hk, rep, mode):
    qkvz, w, Hv = _drawn(b, s, Hk, rep)
    for x in _heads_first(mode, qkvz, w, Hk, Hv)[:3]:   # whole chunks
        assert x.shape[2] == -(-s // 64) * 64 and not np.asarray(
            x[:, :, s:]).any()
    for a, c in zip(_made(mode, qkvz, w, Hk, Hv), chain(qkvz, w, Hk, Hv)):
        _close(a, c, 1e-6)


@pytest.mark.parametrize("mode", ["interpret", "xla"])
@pytest.mark.parametrize("b,s,Hk,rep", SHAPES)
def test_the_gradients_to_qkvz_and_the_taps_are_the_chains(b, s, Hk, rep,
                                                           mode):
    qkvz, w, Hv = _drawn(b, s, Hk, rep)
    r = np.random.default_rng(1)
    cots = [jnp.asarray(r.normal(size=x.shape), jnp.float32)
            for x in chain(qkvz, w, Hk, Hv)]

    def loss(make):
        return lambda qkvz, w: sum(
            jnp.sum(x * c) for x, c in zip(make(qkvz, w, Hk, Hv), cots))

    want = jax.grad(loss(chain), argnums=(0, 1))(qkvz, w)
    got = jax.grad(loss(lambda *a: _made(mode, *a)), argnums=(0, 1))(qkvz, w)
    for a, c in zip(got, want):
        _close(a, c, 2e-6)


def test_a_bfloat16_projection_is_read_as_it_is_and_worked_in_float32():
    """bfloat16 `qkvz`, as amp's projection writes it: the kernels make
    float32 of it in VMEM, so q, k and v are the float32 chain's on the
    same numbers, with none of the chain's roundings to bfloat16; the
    gradient of qkvz is rounded once."""
    qkvz, w, Hv = _drawn(1, 200, 2, 2, jnp.bfloat16)
    exact = chain(qkvz.astype(jnp.float32), w, 2, Hv)
    got = _made("interpret", qkvz, w, 2, Hv)
    for a, c in zip(got[:3], exact):
        _close(a, c, 1e-6)
    assert got[3].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got[3], np.float32),
                                  np.asarray(exact[3]))
    r = np.random.default_rng(2)
    cots = [jnp.asarray(r.normal(size=x.shape), x.dtype) for x in got]

    def loss(make, x):
        return sum(jnp.sum((a * c).astype(jnp.float32))
                   for a, c in zip(make(x, w, 2, Hv), cots))

    dx = jax.grad(lambda x: loss(lambda *a: _made("interpret", *a), x))(qkvz)
    want = jax.grad(lambda x: loss(chain, x))(qkvz.astype(jnp.float32))
    assert dx.dtype == jnp.bfloat16
    _close(dx.astype(jnp.float32), want, 1e-2)


def _rule_operands(s, Hk, rep, seed=0, dk=16, dv=32):
    """float32 operands of two rows, q and k at Hk heads, v at Hk * rep."""
    r = np.random.default_rng(seed)
    b, H = 2, Hk * rep
    q, k = r.normal(size=(2, b, s, Hk, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = np.log(0.9) * np.exp(r.uniform(-1, 1, size=(b, s, H)))
    beta = 1 / (1 + np.exp(-r.normal(size=(b, s, H))))
    return tuple(jnp.asarray(x, jnp.float32) for x in (
        q, k, r.normal(size=(b, s, H, dv)), g, beta))


def _with_gradients(rule, ins):
    w = jnp.asarray(np.random.default_rng(3).normal(size=ins[2].shape),
                    jnp.float32)
    o, back = jax.vjp(rule, *ins)
    return (o, *back(w))


@pytest.mark.parametrize("mode", ["interpret", "xla"])
@pytest.mark.parametrize("s,Hk,rep", [
    pytest.param(512, 1, 2, id="a-heads-chunks-fill-its-steps"),
    pytest.param(192, 2, 2, id="steps-that-cross-heads"),    # copied there
    pytest.param(512, 2, 1, id="a-key-head-a-value-head")])
def test_the_rule_reads_q_and_k_at_their_key_heads(monkeypatch, s, Hk, rep,
                                                   mode):
    """q and k at Hk heads against the same call on their copies a value
    head: o and the gradients to q, k (a key head's value heads summed),
    v, g and beta."""
    monkeypatch.setattr(gd, "prepare_path", lambda: mode)
    q, k, v, g, beta = _rule_operands(s, Hk, rep)
    got = _with_gradients(gd.gated_delta_rule, (q, k, v, g, beta))
    want = _with_gradients(
        lambda q, k, *rest: gd.gated_delta_rule(
            jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2), *rest),
        (q, k, v, g, beta))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for name, a, c in zip("q k v g beta".split(), got[1:], want[1:]):
        assert a.shape == c.shape, name
        _close(a, c, 1e-6)


@pytest.mark.parametrize("mode", ["interpret", "xla"])
def test_operands_that_lie_heads_first_give_what_tokens_first_give(
        monkeypatch, mode):
    """`gated_delta_rule_heads_first` on q, k and v turned and padded
    beforehand (a row of 100 tokens: two chunks, 28 rows of zeros)
    against `gated_delta_rule`, bit for bit, o and the five gradients."""
    monkeypatch.setattr(gd, "prepare_path", lambda: mode)
    ins = _rule_operands(100, 2, 2)

    def turned(q, k, v, g, beta):
        q, k, v = (jnp.pad(jnp.moveaxis(x, 2, 1),
                           ((0, 0), (0, 0), (0, 28), (0, 0)))
                   for x in (q, k, v))
        return gd.gated_delta_rule_heads_first(q, k, v, g, beta)

    for a, c in zip(_with_gradients(turned, ins),
                    _with_gradients(gd.gated_delta_rule, ins)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_the_op_takes_the_chain_here_and_says_so():
    """No TPU here: `ops.gdn_operands` on Tensors is the chain, and
    `ops.gated_delta_rule(heads_first=True)` on what it made is the rule
    on the chain's q, k and v."""
    qkvz, w, Hv = _drawn(1, 70, 2, 2)
    assert la.gdn_operands_path(qkvz.shape, qkvz.dtype, TAPS, 2, Hv) == "xla"
    q, k, v, z = ops.gdn_operands(pt.to_tensor(qkvz), pt.to_tensor(w), 2, Hv)
    assert tuple(q.shape) == (1, 2, 128, D) and tuple(v.shape) == (
        1, Hv, 128, D) and tuple(z.shape) == (1, 70, Hv, D)
    want = chain(qkvz, w, 2, Hv)
    r = np.random.default_rng(4)
    g = jnp.asarray(-r.uniform(0.01, 0.2, size=(1, 70, Hv)), jnp.float32)
    beta = jnp.asarray(r.uniform(size=(1, 70, Hv)), jnp.float32)
    o = ops.gated_delta_rule(q, k, v, pt.to_tensor(g), pt.to_tensor(beta),
                             heads_first=True)
    np.testing.assert_allclose(
        o.numpy(), gd.gated_delta_rule(*want[:3], g, beta), atol=1e-6)


@pytest.mark.parametrize("shape,dtype,taps,why", [
    ((1, 64, 6 * 2 * 128), jnp.bfloat16, 4, None),
    ((1, 64, 6 * 2 * 128), jnp.float32, 4, None),
    ((1, 64, 6 * 2 * 64), jnp.bfloat16, 4, "a head of 64"),
    ((1, 64, 6 * 2 * 128), jnp.float16, 4, "float16"),
    ((1, 64, 6 * 2 * 128), jnp.bfloat16, 12, "12 taps"),
    ((64, 6 * 2 * 128), jnp.bfloat16, 4, "2 dimensions")])
def test_what_the_kernels_take(monkeypatch, shape, dtype, taps, why):
    """Two key heads, four value heads. On a path that runs kernels
    (`prepare_path` other than `xla`) a shape they refuse takes the
    chain."""
    got = fused.reject_reason(shape, dtype, taps, 2, 4)
    assert (got is None) if why is None else (why in got)
    monkeypatch.setattr(gd, "prepare_path", lambda: "pallas")
    assert la.gdn_operands_path(shape, dtype, taps, 2, 4) == (
        "xla" if why else "pallas")
