"""`ops.gated_delta_rule` (kernels/pallas/gated_delta.py) on the CPU:
the chunked form against the token-by-token recurrence in float64
(values and all five gradients, at 1, 2 and 5 chunks and a row that is
no whole number of them, with a decay near 0 and near 1), the Pallas
state kernels in interpret mode against the `lax.scan` pass and against
jax's own differentiation of it, the chunk preparation's kernels in
interpret mode against `prepare` in XLA and its `jax.vjp`, the whole rule
on them against the recurrence, the triangular inverse, and amp's black
list. The kernels compiled for the chip: tests/test_tpu_aot_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import amp, ops
from paddle_tpu.kernels.pallas import gated_delta as gd


def recurrence(q, k, v, g, beta):
    """S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T,
    o_t = S_t^T q_t, a head; operands [b, s, H, d] and [b, s, H]."""
    def head(q, k, v, g, beta):
        def step(S, x):
            q, k, v, g, b = x
            S = jnp.exp(g) * S
            S = S + b * jnp.outer(k, v - S.T @ k)
            return S, S.T @ q
        return jax.lax.scan(
            step, jnp.zeros((q.shape[-1], v.shape[-1]), q.dtype),
            (q, k, v, g, beta))[1]
    return jax.vmap(jax.vmap(head, in_axes=1, out_axes=1))(q, k, v, g, beta)


def operands(s, decay, b=2, H=3, dk=16, dv=32, seed=0):
    """float64: q of length 1 / sqrt(dk), k of length 1, g = log decay
    spread by a factor of e around `decay`'s."""
    r = np.random.default_rng(seed)
    q, k = r.normal(size=(2, b, s, H, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = np.log(decay) * np.exp(r.uniform(-1, 1, size=(b, s, H)))
    beta = 1 / (1 + np.exp(-r.normal(size=(b, s, H))))
    return tuple(jnp.asarray(x) for x in (
        q, k, r.normal(size=(b, s, H, dv)), g, beta))


def gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.fixture
def x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


@pytest.mark.parametrize("decay", [0.05, 0.999], ids=["fast", "slow"])
@pytest.mark.parametrize("s", [64, 128, 320, 40])
def test_the_chunked_rule_is_the_recurrence(x64, s, decay):
    """Values and the gradients to q, k, v, g and beta against the
    recurrence in float64; the program's float32 beside it."""
    ins = operands(s, decay)
    w = jnp.asarray(np.random.default_rng(1).normal(size=ins[2].shape))
    want = recurrence(*ins)
    want_g = jax.grad(lambda *xs: jnp.sum(recurrence(*xs) * w),
                      argnums=range(5))(*ins)
    xs = tuple(x.astype(jnp.float32) for x in ins)
    got = gd.gated_delta_rule(*xs)
    got_g = jax.grad(
        lambda *xs: jnp.sum(gd.gated_delta_rule(*xs) * w.astype(xs[0].dtype)),
        argnums=range(5))(*xs)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    assert gap(got, want) < 2e-5
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        assert a.shape == b.shape and gap(a, b) < 5e-5, name


def _pass_operands(B=4, nc=3, C=64, dk=128, dv=128, seed=0):
    r = np.random.default_rng(seed)

    def mk(*s, scale=0.3):
        return jnp.asarray(r.normal(size=s) * scale, jnp.float32)
    return (mk(B, nc, C, dv), mk(B, nc, C, dk, scale=0.1), mk(B, nc, C, dk),
            mk(B, nc, C, dk, scale=0.1), jnp.tril(mk(B, nc, C, C)),
            jnp.asarray(r.uniform(0.1, 1, size=(B, nc)), jnp.float32))


def _pass(xs, dO, mode):
    """(O, the six gradients) of the state pass for the cotangent dO."""
    O, res = gd._state_vjp_fwd(*xs, mode)
    return (O,) + tuple(gd._state_vjp_bwd(mode, res, dO))


@pytest.mark.parametrize("B", [4, 6, 1], ids=["heads4", "heads2", "heads1"])
def test_the_state_kernels_are_the_scan(B):
    """`gdn_state_fwd` and `gdn_state_bwd` in Pallas's interpreter against
    the `lax.scan` pass, at the chip's state (128 x 128) and chunk: O and
    the six gradients, in blocks of 4, 2 and 1 heads."""
    xs = _pass_operands(B)
    dO = jnp.asarray(np.random.default_rng(2).normal(size=xs[0].shape),
                     jnp.float32)
    for a, b in zip(_pass(xs, dO, "lax.scan"), _pass(xs, dO, "interpret")):
        assert a.shape == b.shape and gap(b, a) < 1e-5


def test_the_backward_formulas_are_the_scans_own_gradient():
    """The hand-written backward (dV' = P^T dO + Kd dS', ..., dS = Qg^T dO
    + a dS' - W^T dV') against jax's differentiation of the forward
    scan."""
    xs = _pass_operands(2, 4, 16, 8, 8)
    dO = jnp.asarray(np.random.default_rng(3).normal(size=xs[0].shape),
                     jnp.float32)

    def plain(*xs):
        return jnp.sum(gd._state_fwd_xla(
            *xs[:5], gd._spread(xs[5], xs[0].shape[-1]))[0] * dO)

    want = jax.grad(plain, argnums=range(6))(*xs)
    for a, b in zip(_pass(xs, dO, "lax.scan")[1:], want):
        assert gap(a, b) < 1e-5


def test_the_state_entering_each_chunk_is_what_the_forward_keeps():
    U, W, Qg, Kd, P, a = _pass_operands(2, 3, 16, 8, 8)
    _O, states = gd._state_fwd_xla(U, W, Qg, Kd, P, gd._spread(a, 8))
    assert states.shape == (2, 3, 8, 8) and not np.asarray(states[:, 0]).any()
    vp = U[:, 0] - W[:, 0] @ states[:, 0]
    np.testing.assert_allclose(
        states[:, 1], jnp.swapaxes(Kd[:, 0], -1, -2) @ vp, atol=1e-6)


def test_the_inverse_of_a_nilpotent_matrix_and_its_gradient():
    r = np.random.default_rng(0)
    A = jnp.asarray(np.tril(r.normal(size=(3, 64, 64)) * 0.2, -1),
                    jnp.float32)
    T = gd._inverse(A)
    np.testing.assert_allclose(T @ (jnp.eye(64) - A),
                               np.broadcast_to(np.eye(64), A.shape),
                               atol=2e-5)
    w = jnp.asarray(r.normal(size=A.shape), jnp.float32)
    got = jax.grad(lambda A: jnp.sum(gd._inverse(A) * w))(A)
    want = jax.grad(lambda A: jnp.sum(
        jnp.linalg.inv(jnp.eye(64) - A) * w))(A)
    assert gap(got, want) < 1e-4


def _chunks_apart(T, n):
    """The forward kernel's T, a pair side by side [steps, PAIRS, C, 2C]
    -> the first n chunks' [n, C, C]."""
    C = gd.CHUNK
    return jnp.moveaxis(T.reshape(-1, C, 2, C), 2, 1).reshape(-1, C, C)[:n]


def _close(got, want, tol=1e-5):
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) <= tol * max(
        float(jnp.max(jnp.abs(want))), 1e-30)


PREPARED = [    # tokens a row, heads: the chunks a step holds, and beyond
    pytest.param(64, 1, id="heads1-one-chunk"),         # padded to a step
    pytest.param(256, 2, id="heads2-one-step"),         # 8 chunks: a step
    pytest.param(320, 4, id="heads4-steps-and-a-part"),     # 20: 2.5 steps
    pytest.param(128, 2, id="a-row-padded-to-a-chunk")]     # 24 tokens of it


def _prepare_operands(s, H, decay, pad=0, **kw):
    """float32 operands of one row of s tokens, the last `pad` of them
    as `gated_delta_rule` pads a row to a chunk: zeros, so beta = 0 and
    g = 0; heads first and in chunks, as `prepare` and its kernels take
    them."""
    ins = operands(s, decay, b=1, H=H, **kw)
    keep = (jnp.arange(s) < s - pad).astype(jnp.float32)
    return tuple(gd._heads_first(
        (x * keep.reshape((1, s) + (1,) * (x.ndim - 2))).astype(jnp.float32))
        for x in ins)


@pytest.mark.parametrize("decay", [0.05, 0.999], ids=["fast", "slow"])
@pytest.mark.parametrize("s,H", PREPARED)
def test_the_preparation_kernel_is_prepare(s, H, decay, request):
    """`gdn_prepare_fwd` in Pallas's interpreter against `prepare` in XLA:
    U, W, Qg, Kd, P, a and the chunks' T, where the chunks fill a grid
    step, fall short of one and run over, and where a row's last 24
    tokens are padding."""
    pad = 24 if "padded" in request.node.name else 0
    ins = _prepare_operands(s, H, decay, pad)
    want = gd._prepare(*ins)
    *got, T = gd._prepare_fwd_pallas(*ins, interpret=True)
    for a, b in zip(got, want):
        _close(a, b)
    _close(_chunks_apart(T, H * s // gd.CHUNK),
           want[6].reshape((-1,) + want[6].shape[2:]))


@pytest.mark.parametrize("decay", [0.05, 0.999], ids=["fast", "slow"])
@pytest.mark.parametrize("s,H", PREPARED)
def test_the_preparation_backward_kernel_is_prepares_own_gradient(
        s, H, decay, request):
    """`gdn_prepare_bwd` in Pallas's interpreter (T read from the forward
    kernel, the formulas of `_pair_bwd`) against `jax.vjp(prepare)`: the
    gradients to q, k, v, g and beta for a cotangent to each of U, W, Qg,
    Kd, P and a."""
    pad = 24 if "padded" in request.node.name else 0
    ins = _prepare_operands(s, H, decay, pad)
    made, back = jax.vjp(gd.prepare, *ins)
    r = np.random.default_rng(4)
    cots = tuple(jnp.asarray(r.normal(size=x.shape), jnp.float32)
                 for x in made)
    T = gd._prepare_fwd_pallas(*ins, interpret=True)[6]
    got = gd._prepare_bwd_pallas(*ins, T, cots, interpret=True)
    for name, a, b in zip("q k v g beta".split(), got, back(cots)):
        assert a.shape == b.shape and gap(a, b) < 2e-5, name


def test_the_preparation_kernels_at_the_chips_widths():
    """Keys and values of 128, as the cell runs them: the seven outputs
    and the five gradients."""
    ins = _prepare_operands(512, 2, 0.9, dk=128, dv=128)
    want = gd._prepare(*ins)
    *got, T = gd._prepare_fwd_pallas(*ins, interpret=True)
    for a, b in zip(got, want):
        _close(a, b)
    _close(_chunks_apart(T, 16), want[6].reshape((-1, 64, 64)))
    cots = tuple(jnp.asarray(np.random.default_rng(5).normal(size=x.shape),
                             jnp.float32) for x in want[:6])
    grads = jax.vjp(gd.prepare, *ins)[1](cots)
    got = gd._prepare_bwd_pallas(*ins, T, cots, interpret=True)
    for name, a, b in zip("q k v g beta".split(), got, grads):
        assert gap(a, b) < 2e-5, name


@pytest.mark.parametrize("decay", [0.05, 0.999], ids=["fast", "slow"])
@pytest.mark.parametrize("s", [64, 128, 320, 40])
def test_the_chunked_rule_on_the_preparation_kernels_is_the_recurrence(
        x64, monkeypatch, s, decay):
    """`test_the_chunked_rule_is_the_recurrence` with the chunk
    preparation on its kernels (in Pallas's interpreter), forward and
    backward, around the `lax.scan` pass."""
    monkeypatch.setattr(gd, "prepare_path", lambda: "interpret")
    ins = operands(s, decay)
    w = jnp.asarray(np.random.default_rng(1).normal(size=ins[2].shape))
    want = recurrence(*ins)
    want_g = jax.grad(lambda *xs: jnp.sum(recurrence(*xs) * w),
                      argnums=range(5))(*ins)
    xs = tuple(x.astype(jnp.float32) for x in ins)
    got = gd.gated_delta_rule(*xs)
    got_g = jax.grad(
        lambda *xs: jnp.sum(gd.gated_delta_rule(*xs) * w.astype(xs[0].dtype)),
        argnums=range(5))(*xs)
    assert got.dtype == jnp.float32 and gap(got, want) < 2e-5
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        assert a.shape == b.shape and gap(a, b) < 5e-5, name


def test_amp_keeps_the_rule_in_float32():
    """On amp's black list: bf16 operands reach the rule as float32, and
    the output is v's type."""
    ins = [pt.to_tensor(np.asarray(x, np.float32))
           for x in operands(64, 0.9)]
    want = ops.gated_delta_rule(*ins).numpy()
    with amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
        got = ops.gated_delta_rule(*ins)
    assert str(got.dtype).endswith("float32")
    np.testing.assert_allclose(got.numpy(), want, atol=1e-6)
    from paddle_tpu.ops.registry import OPS
    assert OPS["gated_delta_rule"].amp_policy == "black"


def test_which_path_a_program_traced_here_takes():
    assert gd.state_path() == "lax.scan"        # no TPU here
    assert gd.prepare_path() == "xla"
