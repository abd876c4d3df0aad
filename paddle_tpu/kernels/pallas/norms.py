"""Pallas fused normalization kernels (layer_norm / rms_norm).

Replaces the reference's fused CUDA norms
(/root/reference/paddle/phi/kernels/fusion/gpu/fused_layernorm_kernel.cu,
fused_rms_norm via incubate). One VMEM pass: stats + normalize + affine,
fp32 accumulation regardless of input dtype (bf16-safe)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _pallas_available


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps, has_w, has_b):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    if has_w:
        y = y * w_ref[:].astype(jnp.float32)
    if has_b:
        y = y + b_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps, has_w):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    if has_w:
        y = y * w_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _rows_block(n_rows, hidden, dtype):
    # target ~1MB blocks in VMEM
    bytes_per_row = hidden * 4
    rows = max(1, (1 << 20) // bytes_per_row)
    rows = min(rows, n_rows, 1024)
    # keep divisibility
    while n_rows % rows:
        rows -= 1
    return rows


def _ln_pallas(x2d, w, b, eps, interpret=False):
    n, h = x2d.shape
    rows = _rows_block(n, h, x2d.dtype)
    grid = (n // rows,)
    has_w, has_b = w is not None, b is not None
    kernel = functools.partial(_ln_kernel, eps=eps, has_w=has_w, has_b=has_b)
    in_specs = [pl.BlockSpec((rows, h), lambda i: (i, 0))]
    args = [x2d]
    in_specs.append(pl.BlockSpec((h,), lambda i: (0,)))
    args.append(w if has_w else jnp.ones((h,), x2d.dtype))
    in_specs.append(pl.BlockSpec((h,), lambda i: (0,)))
    args.append(b if has_b else jnp.zeros((h,), x2d.dtype))
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), x2d.dtype),
        interpret=interpret,
    )(*args)


def _rms_pallas(x2d, w, eps, interpret=False):
    n, h = x2d.shape
    rows = _rows_block(n, h, x2d.dtype)
    grid = (n // rows,)
    has_w = w is not None
    kernel = functools.partial(_rms_kernel, eps=eps, has_w=has_w)
    in_specs = [pl.BlockSpec((rows, h), lambda i: (i, 0)),
                pl.BlockSpec((h,), lambda i: (0,))]
    args = [x2d, w if has_w else jnp.ones((h,), x2d.dtype)]
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), x2d.dtype),
        interpret=interpret,
    )(*args)


def _ln_xla(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y.astype(x.dtype)
    if w is not None:
        y = y * w
    if b is not None:
        y = y + b
    return y


def _rms_xla(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    if w is not None:
        y = y * w
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln_core(x, w, b, eps):
    shape = x.shape
    h = shape[-1]
    x2d = x.reshape(-1, h)
    if _pallas_available() and x2d.shape[0] % 8 == 0 and h % 128 == 0:
        return _ln_pallas(x2d, w, b, eps).reshape(shape)
    return _ln_xla(x, w, b, eps)


def _ln_fwd(x, w, b, eps):
    return _ln_core(x, w, b, eps), (x, w, b)


def _ln_bwd(eps, res, g):
    x, w, b = res
    _, vjp = jax.vjp(lambda x_, w_, b_: _ln_xla_grad_form(x_, w_, b_, eps),
                     x, w if w is not None else jnp.ones(x.shape[-1:], x.dtype),
                     b if b is not None else jnp.zeros(x.shape[-1:], x.dtype))
    dx, dw, db = vjp(g)
    return dx, (dw if w is not None else None), (db if b is not None else None)


def _ln_xla_grad_form(x, w, b, eps):
    return _ln_xla(x, w, b, eps)


_ln_core.defvjp(_ln_fwd, _ln_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_core(x, w, eps):
    shape = x.shape
    h = shape[-1]
    x2d = x.reshape(-1, h)
    if _pallas_available() and x2d.shape[0] % 8 == 0 and h % 128 == 0:
        return _rms_pallas(x2d, w, eps).reshape(shape)
    return _rms_xla(x, w, eps)


def _rms_fwd(x, w, eps):
    return _rms_core(x, w, eps), (x, w)


def _rms_bwd(eps, res, g):
    x, w = res
    _, vjp = jax.vjp(
        lambda x_, w_: _rms_xla(x_, w_, eps), x,
        w if w is not None else jnp.ones(x.shape[-1:], x.dtype))
    dx, dw = vjp(g)
    return dx, (dw if w is not None else None)


_rms_core.defvjp(_rms_fwd, _rms_bwd)


def layer_norm(x, weight=None, bias=None, eps=1e-5):
    return _ln_core(x, weight, bias, eps)


def rms_norm(x, weight=None, eps=1e-6):
    return _rms_core(x, weight, eps)
