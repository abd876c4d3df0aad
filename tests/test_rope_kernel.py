"""The kernel `rope_rotate` (`kernels/pallas/rope.py`) under the Pallas
interpreter (`pltpu.roll` runs there on the CPU) against the composite
`ops.rope_rotate_half` takes off the TPU: forward and gradient, one
rounding from the float32 result, what passes through untouched. What
the chip's compiler makes of it is `tests/test_tpu_aot_compile.py`'s;
times are the chip's (PERF.md)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import ops
from paddle_tpu.kernels.pallas import rope
from paddle_tpu.observability import perf
from paddle_tpu.ops import rope_ops

F32 = jnp.float32
SEQ = 600           # no multiple of a block of rows, nor of a bf16 tile


def _inputs(heads, rot, dtype, seq=SEQ, batch=2, d=128, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((batch, seq, heads, d)), dtype)
    w = jnp.asarray(rng.standard_normal((batch, seq, heads, d)), dtype)
    ang = rng.uniform(0, 6, (seq, rot // 2))
    ang = np.concatenate([ang, ang], axis=1)
    # an attention factor as YaRN's: the tables are no unit vectors
    return (x, w, jnp.asarray(1.4 * np.cos(ang), F32),
            jnp.asarray(1.4 * np.sin(ang), F32))


def _f32(a):
    return np.asarray(a.astype(F32))


def _one_rounding(got, exact, dtype):
    """`got` is the float32 `exact` rounded once to `dtype`: equal to
    that rounding, or its neighbour where the float32 sums differ in
    their last bits (the order of the two products' sum)."""
    rounded = _f32(exact.astype(dtype))
    if dtype == F32:
        np.testing.assert_allclose(_f32(got), rounded, rtol=2e-6, atol=1e-6)
        return
    # where the two products cancel, the float32 sum's own last bits show
    ulp = np.abs(rounded) * 2.0 ** -7 + 1e-6
    assert np.all(np.abs(_f32(got) - rounded) <= ulp)
    assert np.mean(_f32(got) != rounded) < 1e-3


@pytest.mark.parametrize("dtype", [jnp.bfloat16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("heads", [64, 48, 8, 2])
@pytest.mark.parametrize("rot", [128, 64])
def test_the_kernel_is_the_composite_rounded_once(rot, heads, dtype):
    batch = 1 if heads > 8 else 2
    x, w, cos, sin = _inputs(heads, rot, dtype, batch=batch)
    hb = rope.head_block(heads)
    rows = rope.row_block(SEQ, hb * 128 * jnp.dtype(dtype).itemsize)
    assert SEQ % rows and heads % hb == 0 and hb <= 8

    def loss(turn, x):
        return jnp.sum(turn(x).astype(F32) * w.astype(F32))

    def kernel(x):
        return rope_ops._turned(x, cos, sin, True)

    def exact(x):
        return rope_ops._composite(x.astype(F32), cos, sin)

    got = kernel(x)
    assert got.dtype == x.dtype and got.shape == x.shape
    _one_rounding(got, exact(x), dtype)
    # the lanes beyond rot pass through bit for bit
    np.testing.assert_array_equal(_f32(got[..., rot:]), _f32(x[..., rot:]))
    # and the program's own path off the chip agrees to its roundings
    np.testing.assert_allclose(
        _f32(got), _f32(rope_ops._composite(x, cos, sin)),
        rtol=2 ** -7 if dtype != F32 else 2e-6, atol=1e-6)

    grad = jax.grad(lambda x: loss(kernel, x))(x)
    assert grad.dtype == x.dtype
    _one_rounding(grad, jax.grad(lambda x: loss(exact, x))(x.astype(F32)),
                  dtype)
    np.testing.assert_array_equal(_f32(grad[..., rot:]), _f32(w[..., rot:]))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("rot", [128, 64])
def test_the_gradient_is_exact_for_any_tables(rot, dtype):
    """Tables whose halves differ (no `rope_tables` makes such, the op
    takes them): the sine does not commute with the roll there, so the
    gradient is the gradient times the sine rolled back, not the forward
    with the sine negated. Against autodiff's transpose of the
    composite."""
    x, w, _c, _s = _inputs(8, rot, dtype, seq=200, batch=1)
    rng = np.random.default_rng(7)
    cos = jnp.asarray(rng.standard_normal((200, rot)), F32)
    sin = jnp.asarray(rng.standard_normal((200, rot)), F32)
    assert not np.allclose(sin[:, :rot // 2], sin[:, rot // 2:])

    def loss(turn, x):
        return jnp.sum(turn(x).astype(F32) * w.astype(F32))

    _one_rounding(rope_ops._turned(x, cos, sin, True),
                  rope_ops._composite(x.astype(F32), cos, sin), dtype)
    grad = jax.grad(lambda x: loss(
        lambda x: rope_ops._turned(x, cos, sin, True), x))(x)
    exact = jax.grad(lambda x: loss(
        lambda x: rope_ops._composite(x, cos, sin), x))(x.astype(F32))
    _one_rounding(grad, exact, dtype)
    np.testing.assert_array_equal(_f32(grad[..., rot:]), _f32(w[..., rot:]))


def test_the_gradient_is_the_turn_back_and_none_flows_to_the_tables():
    x, w, cos, sin = _inputs(8, 64, F32, seq=48, batch=1)
    _out, back = jax.vjp(
        lambda x, c, s: rope_ops._turned(x, c, s, True), x, cos, sin)
    dx, dcos, dsin = back(w)
    assert not np.any(np.asarray(dcos)) and not np.any(np.asarray(dsin))
    # a rotation's transpose undoes it: the tables scaled back to unit
    again = _f32(rope_ops._turned(dx, cos, sin, True))
    np.testing.assert_allclose(again[..., :64] / (1.4 * 1.4),
                               _f32(w)[..., :64], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(again[..., 64:], _f32(w)[..., 64:])


@pytest.mark.parametrize("d,rot", [(256, 256), (256, 64), (128, 2)])
def test_wider_heads_and_narrow_turns(d, rot):
    x, _w, cos, sin = _inputs(3, rot, F32, seq=40, batch=1, d=d)
    got = rope_ops._turned(x, cos, sin, True)
    np.testing.assert_allclose(
        _f32(got), _f32(rope_ops._composite(x, cos, sin)),
        rtol=2e-6, atol=1e-6)


@pytest.mark.parametrize("shape,dtype,rot,why", [
    ((1, 32, 4, 64), jnp.bfloat16, 64, "a head of 64 is no multiple of 128"),
    ((1, 32, 4, 128), jnp.bfloat16, 63, "rot 63 of 128"),
    ((1, 32, 4, 128), jnp.bfloat16, 130, "rot 130 of 128"),
    ((1, 32, 4, 128), jnp.float16, 64, "x of float16")])
def test_what_the_kernel_does_not_take(shape, dtype, rot, why):
    assert why in rope.reject_reason(shape, dtype, rot)
    table = jnp.zeros((32, rot), F32)
    with pytest.raises(ValueError, match="rope_rotate"):
        rope.rotate(jnp.zeros(shape, dtype), table, table, interpret=True)
    assert "3 dimensions" in rope.reject_reason(shape[1:], dtype, rot)


def test_a_head_of_64_and_an_odd_rot_take_the_composite_and_say_why(
        monkeypatch):
    """On a TPU backend, told from the input alone; off it, everything
    is the composite's and the note says that."""
    notes = {}
    monkeypatch.setattr(perf._TRACE_NOTES, "notes", notes)
    x = pt.to_tensor(np.ones((1, 8, 2, 64), np.float32))
    table = pt.to_tensor(np.ones((8, 64), np.float32))
    ops.rope_rotate_half(x, table, table)
    assert notes == {"rope": "composite: no TPU Pallas backend (cpu)"}

    notes.clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert rope_ops.rotate_path((2, 8192, 64, 128), jnp.bfloat16, 128) == (
        "rope_rotate", "")
    # (another shape: the same would run the executable traced above)
    ops.rope_rotate_half(pt.to_tensor(np.ones((1, 8, 4, 64), np.float32)),
                         table, table)
    odd = pt.to_tensor(np.ones((8, 3), np.float32))
    wide = pt.to_tensor(np.ones((1, 8, 2, 128), np.float32))
    out = ops.rope_rotate_half(wide, odd, odd)
    assert out.shape == [1, 8, 2, 128]
    assert notes == {"rope": "composite: a head of 64 is no multiple of "
                             "128 lanes; composite: rot 3 of 128"}
