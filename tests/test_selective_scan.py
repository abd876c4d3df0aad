"""`ops.selective_scan` (kernels/pallas/selective_scan.py) against the
Mamba-1 recurrence written one float32 time step after the other and
differentiated by jax: values and the gradient of every operand, on the
path a CPU takes (`lax.scan` over chunks with saved chunk starts) and
with the Pallas kernels run by the interpreter; and `ops.causal_conv1d`
against a grouped convolution."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import ops
from paddle_tpu.kernels.pallas import selective_scan as ss

OPERANDS = ("x", "delta", "A", "B", "C", "D")
CHUNK = ss.CHUNK


def literal(x, delta, A, B, C, D):
    x, delta, B, C = (a.astype(jnp.float32) for a in (x, delta, B, C))

    def step(h, inp):
        x_t, d_t, b_t, c_t = inp
        h = jnp.exp(d_t[..., None] * A) * h \
            + (d_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], -1) + D * x_t

    h0 = jnp.zeros((x.shape[0], x.shape[2], A.shape[1]), jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, delta, B, C)))
    return jnp.moveaxis(y, 0, 1)


def operands(L, E=128, N=16, b=2, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (b, L, E)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, L, E)) - 3.0)
    A = -jnp.exp(jax.random.normal(ks[2], (E, N)))
    B = jax.random.normal(ks[3], (b, L, N)).astype(dtype)
    C = jax.random.normal(ks[4], (b, L, N)).astype(dtype)
    D = jax.random.normal(ks[5], (E,))
    return (x, delta, A, B, C, D), jax.random.normal(ks[6], (b, L, E))


def gap(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["chunked-xla", "pallas-interpret"])
@pytest.mark.parametrize("L", [1, CHUNK, CHUNK + 1, 3 * CHUNK + 8],
                         ids=["one-step", "a-chunk", "a-chunk-and-one",
                              "several-chunks"])
def test_values_and_every_gradient_match_the_recurrence(L, interpret):
    args, w = operands(L, seed=L)

    def run(*a):
        return ss.selective_scan(*a, interpret=interpret)

    assert gap(run(*args), literal(*args)) < 2e-6
    got = jax.grad(lambda *a: jnp.sum(run(*a) * w), range(6))(*args)
    want = jax.grad(lambda *a: jnp.sum(literal(*a) * w), range(6))(*args)
    for name, g, g0 in zip(OPERANDS, got, want):
        assert g.shape == g0.shape and g.dtype == g0.dtype, name
        assert gap(g, g0) < 5e-6, name


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["chunked-xla", "pallas-interpret"])
def test_bf16_operands_keep_a_float32_state(interpret):
    """x, B and C in bfloat16: the result is what the float32 recurrence
    gives on those rounded operands, rounded once at the end, so the
    state was not held in bfloat16 (96 decaying steps would show it)."""
    args, w = operands(96, seed=5, dtype=jnp.bfloat16)
    y = ss.selective_scan(*args, interpret=interpret)
    assert y.dtype == jnp.bfloat16
    assert gap(y, literal(*args)) < 6e-3            # one bf16 rounding
    got = jax.grad(lambda *a: jnp.sum(
        ss.selective_scan(*a, interpret=interpret).astype(jnp.float32)
        * w), range(6))(*args)
    want = jax.grad(lambda *a: jnp.sum(literal(*a) * w), range(6))(*args)
    for name, g, g0 in zip(OPERANDS, got, want):
        assert g.dtype == g0.dtype, name
        assert gap(g, g0) < 1e-2, name      # dy arrives in bfloat16


def test_pallas_interpret_agrees_with_the_chunked_path_on_two_tiles():
    """640 channels are tiles of 128 (512 does not divide them) and one
    row: the tiles' states and the lane partials of dB and dC are kept
    apart and summed right."""
    args, w = operands(2 * CHUNK, E=640, b=1, seed=9)
    assert ss._tile(640) == 128
    f = {i: jax.grad(lambda *a, i=i: jnp.sum(
        ss.selective_scan(*a, interpret=i) * w), range(6))
        for i in (False, True)}
    for name, g, g0 in zip(OPERANDS, f[True](*args), f[False](*args)):
        assert gap(g, g0) < 5e-6, name


def test_a_width_the_kernel_cannot_tile_raises_on_a_tpu(monkeypatch):
    args, _w = operands(8, E=96)
    assert ss.scan_path(8, 96) == "xla"     # here, on the CPU
    ss.selective_scan(*args)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ss.scan_path(4096, 5120) == f"pallas, chunk {CHUNK}, tile 512"
    with pytest.raises(ValueError, match="no multiple of 128"):
        ss.selective_scan(*args)


def test_the_op_is_float32_under_amp_and_differentiable_on_the_tape():
    import paddle_tpu as pt
    from paddle_tpu import amp
    args, _w = operands(12, E=128, b=1, seed=3, dtype=jnp.bfloat16)
    with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
        y = ops.selective_scan(*(pt.to_tensor(a) for a in args))
    assert y._data.dtype == jnp.float32         # black list
    assert gap(y._data, literal(*args)) < 2e-6
    y = ops.selective_scan(*(pt.to_tensor(a) for a in args))
    assert y._data.dtype == jnp.bfloat16        # outside amp: x's type


@pytest.mark.parametrize("taps,bias", [(4, True), (4, False), (2, True)])
def test_causal_conv1d_is_a_left_padded_depthwise_convolution(taps, bias):
    ks = jax.random.split(jax.random.PRNGKey(taps), 3)
    x = jax.random.normal(ks[0], (2, 10, 6))
    w = jax.random.normal(ks[1], (6, taps))
    b = jax.random.normal(ks[2], (6,)) if bias else None
    want = jax.lax.conv_general_dilated(
        jnp.swapaxes(x, 1, 2), w[:, None, :], (1,), [(taps - 1, 0)],
        feature_group_count=6, precision="highest")
    want = jnp.swapaxes(want, 1, 2) + (0.0 if b is None else b)
    got = ops.causal_conv1d(x, w, b)
    assert gap(got._data if hasattr(got, "_data") else got, want) < 1e-6
    # nothing after t reaches t
    x2 = x.at[:, 7:].set(0.0)
    got2 = ops.causal_conv1d(x2, w, b)
    np.testing.assert_array_equal(np.asarray(got2)[:, :7],
                                  np.asarray(got)[:, :7])
