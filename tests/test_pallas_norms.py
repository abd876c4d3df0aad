"""Pallas fused-norm kernel conformance via interpret mode (the same CI
strategy as test_flash_attention.py; VERDICT r1 weak item 3 asked that
every Pallas kernel be exercised off-TPU)."""
import importlib

import numpy as np
import pytest
import jax.numpy as jnp

norms = importlib.import_module("paddle_tpu.kernels.pallas.norms")


def _x(n=64, h=256, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((n, h)), jnp.float32)


@pytest.mark.parametrize("with_affine", [False, True])
def test_layer_norm_interpret_matches_xla(with_affine):
    x = _x()
    w = jnp.asarray(np.random.default_rng(1).standard_normal(256),
                    jnp.float32) if with_affine else None
    b = jnp.asarray(np.random.default_rng(2).standard_normal(256),
                    jnp.float32) if with_affine else None
    got = norms._ln_pallas(x, w, b, 1e-5, interpret=True)
    ref = norms._ln_xla(x, w, b, 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("with_w", [False, True])
def test_rms_norm_interpret_matches_xla(with_w):
    x = _x(seed=3)
    w = jnp.asarray(np.random.default_rng(4).standard_normal(256),
                    jnp.float32) if with_w else None
    got = norms._rms_pallas(x, w, 1e-6, interpret=True)
    ref = norms._rms_xla(x, w, 1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_bf16_rows_blocking():
    # bf16 path picks its own row blocking; just conformance-check it
    x = _x(n=128, h=512, seed=5).astype(jnp.bfloat16)
    got = norms._rms_pallas(x, None, 1e-6, interpret=True)
    ref = norms._rms_xla(x, None, 1e-6)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("norm", ["layer_norm", "rms_norm"])
def test_tpu_backend_kernel_failure_raises_not_falls_back(monkeypatch, norm):
    """Off tpu the XLA norm serves, untried. On a tpu backend the fused
    kernel is the path for shapes it takes, and a kernel that cannot
    compile (here: Mosaic handed to the CPU compiler) raises instead of
    latching the XLA norm in; shapes it does not take still route to
    XLA — that is a decision from something the code can see."""
    import jax
    fn = getattr(norms, norm)
    x = _x()
    ref = np.asarray(fn(x))                     # cpu: XLA
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(Exception):
        jax.block_until_ready(fn(x))
    odd = fn(x[:, :100])                        # 100 lanes: not the kernel's
    assert odd.shape == (64, 100)
    monkeypatch.undo()
    np.testing.assert_array_equal(np.asarray(fn(x)), ref)
