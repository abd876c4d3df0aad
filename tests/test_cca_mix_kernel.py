"""`ops.cca_mix` on its kernels (kernels/pallas/cca_mix.py), on the CPU
through Pallas's interpreter, against the chain of XLA ops it replaces
on a TPU (`cca_ops._chain`): q, k, v and the gradients to qkv and the
five parameters at float32, over two row blocks and a part of one, a
sequence shorter than a block, two batch rows (no tap, no second tap of
the grouped convolution and no shifted value crosses a row's start) and
groups of one and of four query heads a key head; a bfloat16 projection
within bfloat16's rounding of the chain; the lengths q and k come out
with; what the kernels refuse, and that the op then takes the chain and
says why. The kernels compiled for the chip:
tests/test_tpu_aot_compile.py."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import ops
from paddle_tpu.kernels.pallas import cca_mix as fused
from paddle_tpu.kernels.pallas import gated_delta as gd
from paddle_tpu.observability import perf
from paddle_tpu.ops import cca_ops

D, HK = 128, 2
PARAMS = ("qkv", "conv_dw_weight", "conv_dw_bias", "conv_group_weight",
          "conv_group_bias", "temperature")

SHAPES = [      # batch rows, tokens a row, query heads
    # blocks of 512 rows: two and 76 rows of a third, each row of the
    # batch its own start
    pytest.param(2, 1100, 2, id="a-head-a-key-head-two-blocks-and-a-part"),
    # a block of 512 and 88 rows of the next, four query heads a key head
    pytest.param(1, 600, 8, id="four-heads-a-key-head"),
    # one block of 112 rows over 100
    pytest.param(2, 100, 2, id="a-block-past-the-row")]


def _drawn(b, s, H, dtype=jnp.float32, seed=0):
    """qkv and the five parameters, none of them at its initial value."""
    r = np.random.default_rng(seed)
    n = (H + HK) * D

    def f(*shape):
        return jnp.asarray(r.normal(size=shape), jnp.float32)

    return (jnp.asarray(r.normal(size=(b, s, (H + 2 * HK) * D)), dtype),
            f(2, n) * 0.7, f(n) * 0.5, f(H + HK, 2 * D, D) / math.sqrt(2 * D),
            f(n) * 0.5, f(HK) * 0.3 + 1.0)


def _chain(H):
    return lambda *a: cca_ops._chain(*a, H, HK)


def _kernels(H):
    return lambda *a: fused.mix(*a, H, HK, True)


def _close(got, want, tol):
    assert got.shape == want.shape and got.dtype == want.dtype
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32)))) <= tol * max(
        float(jnp.max(jnp.abs(want.astype(jnp.float32)))), 1e-30)


@pytest.fixture(scope="module")
def both():
    """(b, s, H) -> the operands, what the chain and the kernels make of
    them, and both sets of six gradients under one set of weights on q,
    k and v: computed once a shape, read by a test a part."""
    seen = {}

    def of(b, s, H):
        if (b, s, H) not in seen:
            operands = _drawn(b, s, H)
            want = _chain(H)(*operands)
            r = np.random.default_rng(1)
            cots = [jnp.asarray(r.normal(size=x.shape), jnp.float32)
                    for x in want]

            def loss(make):
                return lambda *a: sum(jnp.sum(x * c)
                                      for x, c in zip(make(*a), cots))

            every = tuple(range(len(PARAMS)))
            seen[b, s, H] = (
                want, _kernels(H)(*operands),
                jax.grad(loss(_chain(H)), argnums=every)(*operands),
                jax.grad(loss(_kernels(H)), argnums=every)(*operands))
        return seen[b, s, H]
    return of


@pytest.mark.parametrize("part", [0, 1, 2], ids=["q", "k", "v"])
@pytest.mark.parametrize("b,s,H", SHAPES)
def test_q_k_and_v_are_the_chains(both, b, s, H, part):
    want, got, _dwant, _dgot = both(b, s, H)
    assert got[part].shape == (b, s, (H, HK, HK)[part], D)
    _close(got[part], want[part], 2e-6)


@pytest.mark.parametrize("part", range(len(PARAMS)), ids=PARAMS)
@pytest.mark.parametrize("b,s,H", SHAPES)
def test_the_gradients_are_the_chains(both, b, s, H, part):
    """To qkv (every lane: the latent's through both convolutions, the
    mean and the norms, the values' through the shift) and to the
    depthwise taps and bias, the grouped taps and bias, the
    temperature: sums over every row of every batch row."""
    _want, _got, dwant, dgot = both(b, s, H)
    _close(dgot[part], dwant[part], 1e-5)


@pytest.mark.parametrize("b,s,H", SHAPES[1:])
def test_q_and_k_come_out_at_length_sqrt_d_and_tau_sqrt_d(both, b, s, H):
    _want, (q, k, _v), _dwant, _dgot = both(b, s, H)
    tau = _drawn(b, s, H)[-1]
    np.testing.assert_allclose(jnp.linalg.norm(q, axis=-1), math.sqrt(D),
                               rtol=1e-5)
    np.testing.assert_allclose(
        jnp.linalg.norm(k, axis=-1),
        jnp.broadcast_to(jnp.abs(tau) * math.sqrt(D), k.shape[:3]),
        rtol=1e-5)


def test_a_bfloat16_projection_is_within_its_rounding_of_the_chain():
    """bfloat16 `qkv`, as amp's projection writes it: the kernels round
    z' to bfloat16 for the product as the chain does and keep the
    product's float32 sums, which the chain rounds once more; q, k and
    v come out bfloat16, and so does the gradient of qkv, rounded
    once."""
    H = 8
    operands = _drawn(1, 200, H, jnp.bfloat16)
    want, got = _chain(H)(*operands), _kernels(H)(*operands)
    for a, c in zip(got, want):
        assert a.dtype == jnp.bfloat16
        _close(a, c, 2 ** -7)
    np.testing.assert_array_equal(np.asarray(got[2], np.float32),
                                  np.asarray(want[2], np.float32))
    r = np.random.default_rng(2)
    cots = [jnp.asarray(r.normal(size=x.shape), x.dtype) for x in want]

    def loss(make):
        return lambda *a: sum(jnp.sum((x * c).astype(jnp.float32))
                              for x, c in zip(make(*a), cots))

    every = tuple(range(len(PARAMS)))
    dwant = jax.grad(loss(_chain(H)), argnums=every)(*operands)
    dgot = jax.grad(loss(_kernels(H)), argnums=every)(*operands)
    assert dgot[0].dtype == jnp.bfloat16
    for a, c in zip(dgot, dwant):
        _close(a, c, 2e-2)


def _noted(call):
    """What `call` leaves under `cca_mix` in a step's compile record."""
    notes = {}
    outer, perf._TRACE_NOTES.notes = perf._TRACE_NOTES.notes, notes
    try:
        made = call()
    finally:
        perf._TRACE_NOTES.notes = outer
    return made, notes.get("cca_mix")


def _on_tensors(operands, H):
    return _noted(lambda: ops.cca_mix(
        *(pt.to_tensor(x) for x in operands), H, HK))


def test_the_op_takes_the_chain_here_and_says_so():
    """No TPU here: `ops.cca_mix` on Tensors is the chain."""
    operands = _drawn(1, 40, 2)
    assert cca_ops.cca_mix_path(operands[0].shape, operands[0].dtype, 2,
                                HK) == ("xla", "no TPU backend")
    made, note = _on_tensors(operands, 2)
    assert note == "xla: no TPU backend"
    for a, c in zip(made, _chain(2)(*operands)):
        _close(a._data, c, 1e-6)


def test_the_op_runs_the_kernels_where_the_program_does_and_says_so(
        monkeypatch):
    """With the interpreter for the chip: the op on Tensors is the
    kernels, forward and through the tape, and its note names them."""
    monkeypatch.setattr(gd, "prepare_path", lambda: "interpret")
    operands = _drawn(1, 200, 2)
    tensors = [pt.to_tensor(x) for x in operands]
    for t in tensors:
        t.stop_gradient = False
    made, note = _noted(lambda: ops.cca_mix(*tensors, 2, HK))
    assert note == "interpret: cca_mix_fwd, cca_mix_bwd, rows of 208"
    want = _chain(2)(*operands)
    for a, c in zip(made, want):
        _close(a._data, c, 2e-6)
    ops.sum(made[0] * made[0].detach() + made[1] * 2.0).backward()
    dwant = jax.grad(lambda *a: sum(
        jnp.sum(x * c) for x, c in zip(_chain(2)(*a)[:2],
                                       (want[0], 2.0))), argnums=(0, 5))(
        *operands)
    _close(tensors[0].grad._data, dwant[0], 1e-5)
    _close(tensors[5].grad._data, dwant[1], 1e-5)


@pytest.mark.parametrize("shape,dtype,H,Hk,why", [
    ((1, 64, 12 * 128), jnp.bfloat16, 8, 2, None),
    ((1, 64, 6 * 256), jnp.float32, 2, 2, None),
    ((1, 64, 12 * 64), jnp.bfloat16, 8, 2, "a head of 64"),
    ((1, 64, 12 * 128), jnp.bfloat16, 4, 4, "4 key heads"),
    ((1, 64, 12 * 128), jnp.int8, 8, 2, "int8"),
    ((1, 64, 12 * 128), jnp.float16, 8, 2, "float16"),
    ((1, 64, 9 * 128), jnp.bfloat16, 5, 2, "5 heads on 2"),
    ((64, 12 * 128), jnp.bfloat16, 8, 2, "2 dimensions")])
def test_what_the_kernels_take(monkeypatch, shape, dtype, H, Hk, why):
    """On a path that runs kernels (`prepare_path` other than `xla`) a
    call they refuse takes the chain, and the path says why."""
    got = fused.reject_reason(shape, dtype, H, Hk)
    assert (got is None) if why is None else (why in got)
    monkeypatch.setattr(gd, "prepare_path", lambda: "pallas")
    assert cca_ops.cca_mix_path(shape, dtype, H, Hk) == (
        ("xla", got) if why else ("pallas", None))


def test_a_head_the_kernels_refuse_is_mixed_by_the_chain(monkeypatch):
    """A head of 16 under the interpreter's path: the op's note gives
    the reason and q, k and v are the chain's."""
    monkeypatch.setattr(gd, "prepare_path", lambda: "interpret")
    r = np.random.default_rng(3)
    d, H = 16, 4
    n = (H + HK) * d
    operands = tuple(jnp.asarray(r.normal(size=shape), jnp.float32)
                     for shape in ((2, 12, n + HK * d), (2, n), (n,),
                                   (H + HK, 2 * d, d), (n,), (HK,)))
    made, note = _on_tensors(operands, H)
    assert note == "xla: a head of 16 is no multiple of 128 lanes"
    for a, c in zip(made, _chain(H)(*operands)):
        _close(a._data, c, 1e-6)
