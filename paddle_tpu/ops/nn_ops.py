"""Neural-net ops: activations, convs, pools, norms, embedding, losses,
attention (ref: python/paddle/nn/functional/*; kernels phi/kernels/gpu/*).

Convs/matmuls lower to MXU-native XLA ops; norms and softmax are written so
XLA fuses them into surrounding ops (Pallas fused variants live in
paddle_tpu/kernels/pallas and are swapped in by incubate.nn.functional)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtype as dtypes
from .registry import register_op


# ======================= activations =======================
@register_op("relu")
def relu(x):
    return jax.nn.relu(x)


@register_op("relu6")
def relu6(x):
    return jax.nn.relu6(x)


@register_op("leaky_relu")
def leaky_relu(x, negative_slope=0.01):
    return jax.nn.leaky_relu(x, negative_slope)


@register_op("prelu")
def prelu(x, weight, data_format="NCHW"):
    w = weight
    if w.ndim == 1 and x.ndim > 1 and w.shape[0] > 1:
        ch_axis = 1 if data_format == "NCHW" else x.ndim - 1
        shape = [1] * x.ndim
        shape[ch_axis] = w.shape[0]
        w = w.reshape(shape)
    return jnp.where(x > 0, x, w * x)


@register_op("elu")
def elu(x, alpha=1.0):
    return jax.nn.elu(x, alpha)


@register_op("selu")
def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772):
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))


@register_op("celu")
def celu(x, alpha=1.0):
    return jax.nn.celu(x, alpha)


@register_op("gelu")
def gelu(x, approximate=False):
    return jax.nn.gelu(x, approximate=approximate)


@register_op("silu")
def silu(x):
    return jax.nn.silu(x)


swish = silu


@register_op("mish")
def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


@register_op("hardswish")
def hardswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


@register_op("hardsigmoid")
def hardsigmoid(x, slope=1.0 / 6.0, offset=0.5):
    return jnp.clip(x * slope + offset, 0.0, 1.0)


@register_op("hardtanh")
def hardtanh(x, min=-1.0, max=1.0):
    return jnp.clip(x, min, max)


@register_op("hardshrink")
def hardshrink(x, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


@register_op("softshrink")
def softshrink(x, threshold=0.5):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold, 0.0))


@register_op("tanhshrink")
def tanhshrink(x):
    return x - jnp.tanh(x)


@register_op("softplus")
def softplus(x, beta=1.0, threshold=20.0):
    scaled = beta * x
    return jnp.where(scaled > threshold, x, jax.nn.softplus(scaled) / beta)


@register_op("softsign")
def softsign(x):
    return jax.nn.soft_sign(x)


@register_op("thresholded_relu")
def thresholded_relu(x, threshold=1.0, value=0.0):
    return jnp.where(x > threshold, x, value)


@register_op("maxout")
def maxout(x, groups, axis=1):
    axis = axis % x.ndim
    c = x.shape[axis]
    shape = list(x.shape)
    shape[axis:axis + 1] = [c // groups, groups]
    return jnp.max(x.reshape(shape), axis=axis + 1)


@register_op("glu")
def glu(x, axis=-1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)


@register_op("softmax", amp_policy="black")
def softmax(x, axis=-1):
    return jax.nn.softmax(x, axis=axis)


@register_op("log_softmax", amp_policy="black")
def log_softmax(x, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


@register_op("gumbel_softmax")
def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1):
    from ..core.generator import next_key
    g = jax.random.gumbel(next_key(), x.shape, x.dtype)
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        idx = jnp.argmax(y, axis=axis, keepdims=True)
        y_hard = jnp.zeros_like(y)
        y_hard = jnp.put_along_axis(y_hard, idx, 1.0, axis=axis, inplace=False)
        # straight-through: hard value forward, soft gradient backward
        y = y_hard + y - jax.lax.stop_gradient(y)
    return y


# ======================= dropout =======================
@register_op("dropout")
def dropout(x, p=0.5, training=True, mode="upscale_in_train", key=None):
    if not training or p == 0.0:
        return x
    if key is None:
        from ..core.generator import next_key
        key = next_key()
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    if mode == "upscale_in_train":
        return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    return jnp.where(keep, x, 0.0).astype(x.dtype)


@register_op("dropout2d")
def dropout2d(x, p=0.5, training=True, data_format="NCHW", key=None):
    if not training or p == 0.0:
        return x
    if key is None:
        from ..core.generator import next_key
        key = next_key()
    if data_format == "NCHW":
        mshape = x.shape[:2] + (1, 1)
    else:
        mshape = (x.shape[0], 1, 1, x.shape[3])
    keep = jax.random.bernoulli(key, 1.0 - p, mshape)
    return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)


@register_op("alpha_dropout")
def alpha_dropout(x, p=0.5, training=True, key=None):
    if not training or p == 0.0:
        return x
    if key is None:
        from ..core.generator import next_key
        key = next_key()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    a = (1.0 / (1.0 - p) * (1 + p * alpha_p ** 2)) ** -0.5
    b = -a * alpha_p * p
    return (a * jnp.where(keep, x, alpha_p) + b).astype(x.dtype)


# ======================= linear / embedding =======================
@register_op("linear", amp_policy="white")
def linear(x, weight, bias=None):
    acc = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else None
    prec = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    out = jnp.matmul(x, weight, preferred_element_type=acc, precision=prec)
    if acc is not None:
        out = out.astype(x.dtype)
    if bias is not None:
        out = out + bias
    return out


@register_op("embedding")
def embedding(x, weight, padding_idx=None, sparse=False):
    out = jnp.take(weight, x, axis=0)
    if padding_idx is not None:
        mask = (x != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return out


@register_op("one_hot")
def one_hot(x, num_classes):
    return jax.nn.one_hot(x, num_classes, dtype=jnp.float32)


# ======================= conv =======================
def _conv_dn(ndim, channel_last):
    # the kernel layout is ALWAYS paddle's [out, in/groups, spatial...]
    # regardless of data_format — only the activation layout changes
    if ndim == 3:
        return ("NWC", "OIW", "NWC") if channel_last else \
            ("NCW", "OIW", "NCW")
    if ndim == 4:
        return (("NHWC", "OIHW", "NHWC") if channel_last
                else ("NCHW", "OIHW", "NCHW"))
    return (("NDHWC", "OIDHW", "NDHWC") if channel_last
            else ("NCDHW", "OIDHW", "NCDHW"))


def _norm_tuple(v, n):
    if isinstance(v, (int, np.integer)):
        return (int(v),) * n
    return tuple(int(i) for i in v)


def _conv_padding(padding, n):
    if isinstance(padding, str):
        return padding.upper()  # SAME / VALID
    if isinstance(padding, (int, np.integer)):
        return [(int(padding),) * 2] * n
    padding = list(padding)
    if len(padding) == n and all(isinstance(p, (int, np.integer)) for p in padding):
        return [(int(p), int(p)) for p in padding]
    if len(padding) == 2 * n:
        return [(int(padding[2 * i]), int(padding[2 * i + 1])) for i in range(n)]
    return [tuple(p) for p in padding]


def _conv(x, weight, bias, stride, padding, dilation, groups, data_format):
    n = x.ndim - 2
    channel_last = data_format[-1] == "C"
    dn = jax.lax.conv_dimension_numbers(x.shape, weight.shape,
                                        _conv_dn(x.ndim, channel_last))
    # NOTE: no preferred_element_type here — the TPU MXU accumulates conv
    # in f32 regardless and we'd round back to x.dtype below anyway, while
    # jax's conv transpose rule rejects the mixed-dtype (f32 cotangent,
    # bf16 operand) call an f32-preferred conv produces under autodiff
    prec = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    out = jax.lax.conv_general_dilated(
        x, weight,
        window_strides=_norm_tuple(stride, n),
        padding=_conv_padding(padding, n),
        rhs_dilation=_norm_tuple(dilation, n),
        dimension_numbers=dn,
        feature_group_count=groups,
        precision=prec)
    if bias is not None:
        bshape = [1] * x.ndim
        bshape[-1 if channel_last else 1] = bias.shape[0]
        out = out + bias.reshape(bshape)
    return out


@register_op("conv1d", amp_policy="white")
def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL"):
    return _conv(x, weight, bias, stride, padding, dilation, groups,
                 "NWC" if data_format == "NLC" else "NCW")


@register_op("conv2d", amp_policy="white")
def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW"):
    return _conv(x, weight, bias, stride, padding, dilation, groups,
                 data_format)


@register_op("conv3d", amp_policy="white")
def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW"):
    return _conv(x, weight, bias, stride, padding, dilation, groups,
                 data_format)


@register_op("conv2d_transpose", amp_policy="white")
def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCHW"):
    n = 2
    channel_last = data_format[-1] == "C"
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    pad = _conv_padding(padding, n)
    outpad = _norm_tuple(output_padding, n)
    # weight layout for paddle transpose conv: [in, out/groups, kh, kw]
    # paddle transpose-conv weights are [in, out/groups, ...] in EVERY
    # data_format; _conv_dn declares O-I-spatial, so always swap
    kernel = jnp.swapaxes(weight, 0, 1)
    kh, kw = kernel.shape[-2:]
    if isinstance(pad, str):
        lax_pad = pad
    else:
        lax_pad = []
        for i, (lo, hi) in enumerate(pad):
            k = (kernel.shape[2 + i] - 1) * dilation[i]
            lax_pad.append((k - lo, k - hi + outpad[i]))
    dn = jax.lax.conv_dimension_numbers(
        x.shape, kernel.shape, _conv_dn(x.ndim, channel_last))
    out = jax.lax.conv_general_dilated(
        x, jnp.flip(kernel, (-1, -2)),
        window_strides=(1, 1),
        padding=lax_pad,
        lhs_dilation=stride,
        rhs_dilation=dilation,
        dimension_numbers=dn,
        feature_group_count=groups)
    if bias is not None:
        bshape = [1] * x.ndim
        bshape[-1 if channel_last else 1] = bias.shape[0]
        out = out + bias.reshape(bshape)
    return out


# ======================= pooling =======================
def _pool(x, kernel, stride, padding, reducer, init, data_format="NCHW",
          ceil_mode=False, norm=None):
    n = x.ndim - 2
    channel_last = data_format[-1] == "C"
    kernel = _norm_tuple(kernel, n)
    stride = _norm_tuple(stride if stride is not None else kernel, n)
    pad = _conv_padding(padding, n)
    if channel_last:
        dims = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        pads = [(0, 0)] + (pad if not isinstance(pad, str) else pad) + [(0, 0)] \
            if not isinstance(pad, str) else pad
    else:
        dims = (1, 1) + kernel
        strides = (1, 1) + stride
        pads = ([(0, 0), (0, 0)] + pad) if not isinstance(pad, str) else pad
    out = jax.lax.reduce_window(x, init, reducer, dims, strides,
                                pads if not isinstance(pads, str) else pads)
    if norm is not None:
        ones = jnp.ones_like(x)
        cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, dims, strides,
                                    pads if not isinstance(pads, str) else pads)
        out = out / cnt if norm == "count" else out / float(np.prod(kernel))
    return out


@register_op("max_pool2d")
def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               data_format="NCHW"):
    return _pool(x, kernel_size, stride, padding, jax.lax.max,
                 -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                 else jnp.iinfo(x.dtype).min,
                 data_format, ceil_mode)


@register_op("avg_pool2d")
def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, data_format="NCHW"):
    return _pool(x, kernel_size, stride, padding, jax.lax.add, 0.0,
                 data_format, ceil_mode,
                 norm="count" if exclusive else "size")


@register_op("max_pool1d")
def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False):
    return _pool(x, kernel_size, stride, padding, jax.lax.max, -jnp.inf,
                 "NCW", ceil_mode)


@register_op("avg_pool1d")
def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False):
    return _pool(x, kernel_size, stride, padding, jax.lax.add, 0.0, "NCW",
                 ceil_mode, norm="count" if exclusive else "size")


@register_op("max_pool3d")
def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               data_format="NCDHW"):
    return _pool(x, kernel_size, stride, padding, jax.lax.max, -jnp.inf,
                 data_format, ceil_mode)


@register_op("avg_pool3d")
def avg_pool3d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, data_format="NCDHW"):
    return _pool(x, kernel_size, stride, padding, jax.lax.add, 0.0,
                 data_format, ceil_mode, norm="count" if exclusive else "size")


@register_op("adaptive_avg_pool2d")
def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    out = _norm_tuple(output_size, 2)
    if data_format == "NCHW":
        h, w = x.shape[2], x.shape[3]
    else:
        h, w = x.shape[1], x.shape[2]
    if h % out[0] == 0 and w % out[1] == 0:
        kh, kw = h // out[0], w // out[1]
        return avg_pool2d.raw_fn(x, (kh, kw), (kh, kw), 0,
                                 data_format=data_format)
    # general case: mean over variable windows via interpolation-style gather
    return _adaptive_pool(x, out, jnp.mean, data_format)


@register_op("adaptive_max_pool2d")
def adaptive_max_pool2d(x, output_size, data_format="NCHW"):
    out = _norm_tuple(output_size, 2)
    if data_format == "NCHW":
        h, w = x.shape[2], x.shape[3]
    else:
        h, w = x.shape[1], x.shape[2]
    if h % out[0] == 0 and w % out[1] == 0:
        kh, kw = h // out[0], w // out[1]
        return max_pool2d.raw_fn(x, (kh, kw), (kh, kw), 0,
                                 data_format=data_format)
    return _adaptive_pool(x, out, jnp.max, data_format)


def _adaptive_pool(x, out, reducer, data_format):
    # slow general path (rare shapes): python loop over output cells
    channel_last = data_format[-1] == "C"
    hax, wax = (1, 2) if channel_last else (2, 3)
    h, w = x.shape[hax], x.shape[wax]
    rows = []
    for i in range(out[0]):
        h0, h1 = (i * h) // out[0], -(-((i + 1) * h) // out[0])
        cols = []
        for j in range(out[1]):
            w0, w1 = (j * w) // out[1], -(-((j + 1) * w) // out[1])
            sl = [slice(None)] * x.ndim
            sl[hax] = slice(h0, h1)
            sl[wax] = slice(w0, w1)
            cols.append(reducer(x[tuple(sl)], axis=(hax, wax)))
        rows.append(jnp.stack(cols, axis=-1))
    stacked = jnp.stack(rows, axis=-2)  # [n, c, out_h, out_w]
    if channel_last:
        return jnp.transpose(stacked, (0, 2, 3, 1))
    return stacked


@register_op("adaptive_avg_pool1d")
def adaptive_avg_pool1d(x, output_size):
    out = output_size if isinstance(output_size, int) else output_size[0]
    l = x.shape[2]
    if l % out == 0:
        k = l // out
        return avg_pool1d.raw_fn(x, k, k, 0)
    cols = []
    for j in range(out):
        w0, w1 = (j * l) // out, -(-((j + 1) * l) // out)
        cols.append(jnp.mean(x[:, :, w0:w1], axis=2))
    return jnp.stack(cols, axis=-1)


# ======================= normalization =======================
@register_op("layer_norm", amp_policy="black")
def layer_norm(x, weight=None, bias=None, epsilon=1e-5,
               begin_norm_axis=None, normalized_shape=None):
    if begin_norm_axis is None:
        if normalized_shape is not None:
            n = len(normalized_shape) if isinstance(
                normalized_shape, (list, tuple)) else 1
            begin_norm_axis = x.ndim - n
        else:
            begin_norm_axis = x.ndim - 1
    axes = tuple(range(begin_norm_axis, x.ndim))
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes, keepdims=True)
    var = jnp.var(x32, axis=axes, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + epsilon)
    out = out.astype(x.dtype)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


@register_op("rms_norm", amp_policy="black")
def rms_norm(x, weight=None, epsilon=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = (x32 * jax.lax.rsqrt(var + epsilon)).astype(x.dtype)
    if weight is not None:
        out = out * weight
    return out


@register_op("batch_norm", amp_policy="black", tags=("multi_out",))
def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW"):
    channel_last = data_format[-1] == "C" and x.ndim > 2
    ch_axis = x.ndim - 1 if channel_last else 1
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    if training:
        x32 = x.astype(jnp.float32)
        from ..core.flags import flag_value
        if flag_value("FLAGS_fast_bn_stats"):
            # one-pass statistics: E[(x-p)^2] - (E[x]-p)^2 with the
            # running mean as pivot p. Both sums reduce the SAME
            # centered input, so XLA multi-output fusion computes them
            # in ONE read of the activation (jnp.mean+jnp.var re-read
            # it: 27.5 -> 20.6 GB/step on ResNet-50 in a deleted
            # pre-round record; a Welford lax.reduce is stable but
            # defeats the fusion). Precision caveat on the flag help.
            n = 1.0
            for a in axes:
                n *= x.shape[a]
            shape = [1] * x.ndim
            shape[ch_axis] = x.shape[ch_axis]
            pivot = jax.lax.stop_gradient(
                running_mean.astype(jnp.float32)).reshape(shape)
            xc = x32 - pivot
            s1 = jnp.sum(xc, axis=axes)
            s2 = jnp.sum(xc * xc, axis=axes)
            d = s1 / n
            mean = d + pivot.reshape(-1)
            var = jnp.maximum(s2 / n - d * d, 0.0)
        else:
            # default: exact two-pass moments (reference cuDNN parity)
            mean = jnp.mean(x32, axis=axes)
            var = jnp.var(x32, axis=axes)
        new_rm = momentum * running_mean + (1 - momentum) * mean
        new_rv = momentum * running_var + (1 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_rm, new_rv = running_mean, running_var
    shape = [1] * x.ndim
    shape[ch_axis] = x.shape[ch_axis]
    out = (x - mean.reshape(shape).astype(x.dtype)) * jax.lax.rsqrt(
        var.reshape(shape).astype(jnp.float32) + epsilon).astype(x.dtype)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out, new_rm, new_rv


@register_op("group_norm", amp_policy="black")
def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW"):
    channel_last = data_format[-1] == "C" and x.ndim > 2
    if channel_last:
        x_ = jnp.moveaxis(x, -1, 1)
    else:
        x_ = x
    n, c = x_.shape[0], x_.shape[1]
    g = num_groups
    rest = x_.shape[2:]
    xg = x_.reshape((n, g, c // g) + rest).astype(jnp.float32)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    out = ((xg - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x_.shape)
    out = out.astype(x.dtype)
    shape = [1, c] + [1] * len(rest)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    if channel_last:
        out = jnp.moveaxis(out, 1, -1)
    return out


@register_op("instance_norm", amp_policy="black")
def instance_norm(x, weight=None, bias=None, epsilon=1e-5):
    axes = tuple(range(2, x.ndim))
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes, keepdims=True)
    var = jnp.var(x32, axis=axes, keepdims=True)
    out = ((x32 - mean) * jax.lax.rsqrt(var + epsilon)).astype(x.dtype)
    shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


@register_op("local_response_norm")
def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0):
    sq = jnp.square(x)
    half = size // 2
    c = x.shape[1]
    pad = jnp.pad(sq, [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (x.ndim - 2))
    acc = jnp.zeros_like(x)
    for i in range(size):
        acc = acc + jax.lax.slice_in_dim(pad, i, i + c, axis=1)
    return x / jnp.power(k + alpha * acc, beta)


# ======================= losses =======================
@register_op("mse_loss")
def mse_loss(input, label, reduction="mean"):
    out = jnp.square(input - label)
    return _reduce(out, reduction)


@register_op("l1_loss")
def l1_loss(input, label, reduction="mean"):
    out = jnp.abs(input - label)
    return _reduce(out, reduction)


@register_op("smooth_l1_loss")
def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    d = input - label
    out = jnp.where(jnp.abs(d) < delta, 0.5 * d * d / delta,
                    jnp.abs(d) - 0.5 * delta)
    return _reduce(out, reduction)


def _reduce(x, reduction):
    if reduction == "mean":
        return jnp.mean(x)
    if reduction == "sum":
        return jnp.sum(x)
    return x


@register_op("cross_entropy", amp_policy="black")
def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0):
    if soft_label:
        if use_softmax:
            logp = jax.nn.log_softmax(input.astype(jnp.float32),
                                      axis=axis)
        else:
            logp = jnp.log(jnp.maximum(input.astype(jnp.float32), 1e-30))
        lbl = label.astype(jnp.float32)
        if label_smoothing > 0:
            n = input.shape[axis]
            lbl = lbl * (1 - label_smoothing) + label_smoothing / n
        loss = -jnp.sum(lbl * logp, axis=axis)
        valid, w_tok = None, None
    else:
        lbl = label
        if lbl.ndim == input.ndim:
            lbl = jnp.squeeze(lbl, axis=axis)
        lbl = lbl.astype(jnp.int32)
        valid = (lbl != ignore_index)
        safe = jnp.where(valid, lbl, 0)
        if use_softmax:
            # loss = logsumexp(z) - z[label]. Never materialize the full
            # [.., vocab] f32 log-softmax (3+ GB at GPT scale) — the
            # logsumexp fuses the f32 accumulation into one reduction
            # pass and the backward recomputes softmax rows from bf16
            # logits.
            lse = jax.scipy.special.logsumexp(
                input.astype(jnp.float32), axis=axis)
            picked = jnp.take_along_axis(
                input, jnp.expand_dims(safe, axis), axis=axis)
            picked = jnp.squeeze(picked, axis=axis).astype(jnp.float32)
            if label_smoothing > 0:
                mean_logit = jnp.mean(input.astype(jnp.float32),
                                      axis=axis)
                picked = ((1 - label_smoothing) * picked
                          + label_smoothing * mean_logit)
            loss = jnp.where(valid, lse - picked, 0.0)
        else:
            logp = jnp.log(jnp.maximum(input.astype(jnp.float32), 1e-30))
            picked = jnp.take_along_axis(
                logp, jnp.expand_dims(safe, axis), axis=axis)
            picked = jnp.squeeze(picked, axis=axis)
            if label_smoothing > 0:
                smooth = jnp.mean(logp, axis=axis)
                picked = ((1 - label_smoothing) * picked
                          + label_smoothing * smooth)
            loss = jnp.where(valid, -picked, 0.0)
        w_tok = None
        if weight is not None:
            w_tok = jnp.where(valid, jnp.take(weight, safe), 0.0)
            loss = loss * w_tok
    if reduction == "mean":
        if valid is not None:
            denom = (jnp.maximum(jnp.sum(w_tok), 1e-12)
                     if w_tok is not None else
                     jnp.maximum(jnp.sum(valid.astype(jnp.float32)),
                                 1.0))
            return jnp.sum(loss) / denom
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


# Tokens in one chunk of `linear_cross_entropy`. Each chunk reads and
# writes the float32 dW accumulator: 8*h*v bytes against the 2*c*h*v
# operations of the matmul it rides on, c/4 operations a byte, and a
# v5e's ridge is 240 (197 TFLOP/s over 819 GB/s). At 4096 the
# accumulator costs a quarter of one of the chunk's three matmuls, and
# the chunk's bf16 logits are 412 MB at a 50304-wide vocabulary,
# whatever the hidden size.
LCE_CHUNK = 4096
# A head wider than that takes fewer tokens a chunk (half at 131072 rows
# and more, a quarter at 262144), 1024 at least, where the accumulator
# costs as much as one of the chunk's three matmuls.
LCE_WIDEST = 65536
# ... and more chunks than this run one after the other in a loop: in
# line, the step's program for a 131136-row tied head kept every chunk's
# bf16 logits alive at once (nine of 1 GiB at 4096 tokens, thirty-two of
# 256 MiB at 1024) and did not fit a v5e; as a loop it holds one chunk's
# (PERF.md, PR 35). Up to here they stay in line (see `_lce_run`).
LCE_INLINE_CHUNKS = 4


def lce_chunk(vocab: int) -> int:
    """Tokens in a chunk for a head `vocab` wide."""
    return max(1024, LCE_CHUNK // max(1, vocab // LCE_WIDEST))


def _lce_plan(n, chunk):
    """(chunks, tokens in each): the fewest chunks of at most `chunk`
    tokens. A count they do not divide is padded with tokens of weight
    zero (they add nothing to the loss or to a gradient), each chunk a
    multiple of 128 rows so the tiles stay whole."""
    k = max(1, -(-n // chunk))
    c = -(-n // k)
    if k > 1 and c % 128:
        c = min(chunk, c + 128 - c % 128)
    return k, c


def _lce_dot(a, b, contract, out_dtype=None):
    """matmul's arithmetic (linalg.matmul): float32 operands at HIGHEST,
    low-precision ones at the MXU's speed into a float32 accumulator,
    rounded to the operands' dtype unless the caller keeps float32."""
    low = a.dtype in (jnp.bfloat16, jnp.float16)
    out = jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=None if low else jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32 if low else None)
    return out.astype(out_dtype or a.dtype)


def _lce_chunk(hidden, weight, safe, coef, transpose_y, with_grads,
               axis=None):
    """One chunk. `coef` is each token's weight in the loss, zero where
    its label is ignored. The logits are rounded as `matmul` rounds
    them; the log-sum-exp and the softmax are float32. With `axis`
    (inside a `shard_map` over it) `weight` is this device's slice of
    the vocabulary: the maximum and the sum of exponentials are reduced
    over the axis and the label's logit comes from the device that
    holds its row."""
    wdim = 1 if transpose_y else 0          # weight's hidden axis
    logits = _lce_dot(hidden, weight, ((1,), (wdim,)))         # [c, v]
    z = logits.astype(jnp.float32)
    if axis is None:
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    else:
        width = z.shape[1]
        top = jax.lax.pmax(jnp.max(z, axis=-1), axis)
        lse = top + jnp.log(jax.lax.psum(
            jnp.sum(jnp.exp(z - top[:, None]), axis=-1), axis))
        safe = safe - jax.lax.axis_index(axis) * width  # in the slice or not
        mine = jnp.logical_and(safe >= 0, safe < width)
        picked = jnp.take_along_axis(
            logits, jnp.clip(safe, 0, width - 1)[:, None], axis=-1)[:, 0]
        picked = jax.lax.psum(jnp.where(mine, picked.astype(jnp.float32),
                                        0.0), axis)
    ce = lse - picked.astype(jnp.float32)
    if not with_grads:
        return ce, None, None
    hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) == safe[:, None]
    dlogits = ((jnp.exp(z - lse[:, None]) - hit.astype(jnp.float32))
               * coef[:, None]).astype(logits.dtype)
    dh = _lce_dot(dlogits, weight, ((1,), (1 - wdim,)), jnp.float32)
    if transpose_y:
        dw = _lce_dot(dlogits, hidden, ((0,), (0,)), jnp.float32)  # [v, h]
    else:
        dw = _lce_dot(hidden, dlogits, ((0,), (0,)), jnp.float32)  # [h, v]
    return ce, dh, dw


def _lce_run(hidden, weight, label, token_weight, transpose_y,
             ignore_index, chunk, with_grads, axis=None):
    n, h = hidden.shape
    k, c = _lce_plan(n, chunk)
    lbl = label.astype(jnp.int32)
    valid = lbl != ignore_index
    safe = jnp.where(valid, lbl, 0)
    coef = jnp.where(valid, token_weight.astype(jnp.float32), 0.0)
    pad = k * c - n
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        safe = jnp.pad(safe, (0, pad))
        coef = jnp.pad(coef, (0, pad))
    xs = (hidden.reshape(k, c, h), safe.reshape(k, c), coef.reshape(k, c))

    def body(dw_acc, x):
        ce, dh, dw = _lce_chunk(x[0], weight, x[1], x[2], transpose_y,
                                with_grads, axis)
        if with_grads:
            dw_acc = dw_acc + dw
        return dw_acc, (ce, dh)

    # a few chunks unrolled: as a `while` the GPT-3 1.3B step does not fit a v5e
    # (the float32 dW would have to live across the whole backward; in
    # line, XLA may compute a chunk's share of it late from logits made
    # again, as it does with whole logits under the same pressure), and
    # where nothing presses it was no faster (PERF.md, PR 26)
    dw0 = jnp.zeros(weight.shape, jnp.float32) if with_grads else None
    dw, (ce, dh) = jax.lax.scan(body, dw0, xs,
                                unroll=k <= LCE_INLINE_CHUNKS or 1)
    ce = jnp.where(valid, ce.reshape(k * c)[:n], 0.0)
    loss = jnp.sum(ce * token_weight.astype(jnp.float32))
    if not with_grads:
        return loss, (None, None, ce)
    return loss, (dh.reshape(k * c, h)[:n], dw, ce)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _lce(hidden, weight, label, token_weight, transpose_y, ignore_index,
         chunk):
    return _lce_run(hidden, weight, label, token_weight, transpose_y,
                    ignore_index, chunk, False)[0]


def _lce_fwd(hidden, weight, label, token_weight, transpose_y,
             ignore_index, chunk):
    loss, (dh, dw, ce) = _lce_run(hidden, weight, label, token_weight,
                                  transpose_y, ignore_index, chunk, True)
    # what the backward hands on, already in its final form but for the
    # scalar that arrives there; dtypes are kept as zero-size arrays
    like = (jnp.zeros((0,), hidden.dtype), jnp.zeros((0,), weight.dtype),
            jnp.zeros((0,), token_weight.dtype))
    return loss, (dh, dw, ce, like)


def _lce_bwd(transpose_y, ignore_index, chunk, res, g):
    dh, dw, ce, like = res
    g = g.astype(jnp.float32)
    return ((dh * g).astype(like[0].dtype), (dw * g).astype(like[1].dtype),
            None, (ce * g).astype(like[2].dtype))


_lce.defvjp(_lce_fwd, _lce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _lce_rows(hidden, weight, label, token_weight, transpose_y,
              ignore_index, chunk):
    """`_lce`, and beside the loss every row's cross-entropy [n]
    float32, as a reading: no gradient flows back through it (its
    cotangent is dropped: the rows' `d hidden` was folded into the
    loss's when the forward ran). A `custom_vjp` of its own beside
    `_lce`, and not a second output of `_lce`, so that the step text of
    every caller that reads no rows stays what it was, character for
    character (the accepted cells' lowered steps are compared so)."""
    loss, (_dh, _dw, ce) = _lce_run(hidden, weight, label, token_weight,
                                    transpose_y, ignore_index, chunk, False)
    return loss, ce


def _lce_rows_fwd(*args):
    loss, res = _lce_fwd(*args)
    return (loss, res[2]), res


def _lce_rows_bwd(transpose_y, ignore_index, chunk, res, g):
    return _lce_bwd(transpose_y, ignore_index, chunk, res, g[0])


_lce_rows.defvjp(_lce_rows_fwd, _lce_rows_bwd)


def _lce_sliced_run(hidden, weight, label, token_weight, transpose_y,
                    ignore_index, chunk, over, with_grads):
    """`_lce_run` with the vocabulary in slices over a mesh axis (`over`:
    (mesh, axis)) and the rows split over the same axis: every device
    gathers all the rows, takes them through its slice of `weight` chunk
    by chunk (`_lce_chunk` with `axis`), keeps its slice's whole `dW`,
    and `d hidden`, the sum of every slice's part, is summed over the
    axis and scattered to the rows' owners. No device holds more than a
    chunk's rows of its slice's logits."""
    from jax.sharding import PartitionSpec as P
    mesh, axis = over
    rows, whole = P(axis), P()
    w_spec = P(axis, None) if transpose_y else P(None, axis)

    def on_device(hidden, weight, label, token_weight):
        hidden, label, token_weight = (
            jax.lax.all_gather(a, axis, axis=0, tiled=True)
            for a in (hidden, label, token_weight))
        loss, (dh, dw, ce) = _lce_run(
            hidden, weight, label, token_weight, transpose_y, ignore_index,
            chunk, with_grads, axis)
        if not with_grads:
            return loss
        mine = ce.shape[0] // mesh.shape[axis]
        return loss, (
            jax.lax.psum_scatter(dh, axis, scatter_dimension=0, tiled=True),
            dw, jax.lax.dynamic_slice_in_dim(
                ce, jax.lax.axis_index(axis) * mine, mine))

    return jax.shard_map(
        on_device, mesh=mesh, in_specs=(rows, w_spec, rows, rows),
        out_specs=(whole, (rows, w_spec, rows)) if with_grads else whole,
        check_vma=False)(hidden, weight, label, token_weight)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _lce_sliced(hidden, weight, label, token_weight, transpose_y,
                ignore_index, chunk, over):
    """`_lce` under a mesh that lays the vocabulary over an axis. The
    `shard_map` is inside the `custom_vjp`, so nothing differentiates
    through its collectives: the forward makes loss and gradients, the
    backward scales them."""
    return _lce_sliced_run(hidden, weight, label, token_weight, transpose_y,
                           ignore_index, chunk, over, False)


def _lce_sliced_fwd(hidden, weight, label, token_weight, transpose_y,
                    ignore_index, chunk, over):
    loss, (dh, dw, ce) = _lce_sliced_run(
        hidden, weight, label, token_weight, transpose_y, ignore_index,
        chunk, over, True)
    like = (jnp.zeros((0,), hidden.dtype), jnp.zeros((0,), weight.dtype),
            jnp.zeros((0,), token_weight.dtype))
    return loss, (dh, dw, ce, like)


def _lce_sliced_bwd(transpose_y, ignore_index, chunk, over, res, g):
    return _lce_bwd(transpose_y, ignore_index, chunk, res, g)


_lce_sliced.defvjp(_lce_sliced_fwd, _lce_sliced_bwd)


@register_op("linear_cross_entropy", amp_policy="keep")
def linear_cross_entropy(hidden, weight, label, token_weight=None,
                         transpose_y=True, ignore_index=-100,
                         chunk=LCE_CHUNK, with_rows=False, over=None):
    """sum_i token_weight[i] * cross_entropy(hidden[i] @ W, label[i]):
    the vocabulary projection and the loss in one op, so that no
    [tokens, vocab] array exists. `hidden` [n, h]; `weight` [v, h] (a
    tied embedding, transpose_y=True) or [h, v] (a Linear's); `label`
    [n]; `token_weight` [n] float32 (default 1/n each: the mean over all
    tokens), which is how a mask and its mean come in. A label equal to
    `ignore_index` costs nothing.

    The tokens go through in chunks under one `jax.custom_vjp` (an
    unrolled `lax.scan`). Differentiated, the forward computes each chunk's
    logits once and in the same pass its `softmax - onehot`, its
    `d hidden` and its share of `dW` (float32, summed across chunks);
    the backward only scales them by the scalar it receives, which is
    exact because cross-entropy's gradient depends on what follows it
    by that scalar alone. Three matmuls of the head's size where
    `cross_entropy(matmul(hidden, W))` differentiates into three and
    keeps the logits alive (or computes them again) for each reader.
    Rounding is `matmul`'s and `cross_entropy`'s: logits in the
    operands' dtype from a float32 accumulator, log-sum-exp and softmax
    in float32, the logits' gradient rounded once to their dtype.
    Operands are used as given (amp "keep": the caller casts).

    `token_weight` is differentiable: its gradient is each row's
    cross-entropy (a loss whose weights are themselves learnt, as an
    expected loss over a learnt distribution, gets the distribution's
    gradient from here). With `with_rows` the op returns (loss, rows):
    every row's cross-entropy [n] float32 beside the sum, for reading
    (a mean by group); nothing is differentiated through the rows.

    `over=(mesh, axis)`: the program will be partitioned over `mesh`
    with `weight`'s vocabulary and the rows of `hidden` both split over
    `axis`; the op then takes the vocabulary in the axis's slices
    (`_lce_sliced_run`). Without rows."""
    if token_weight is None:
        token_weight = jnp.full((hidden.shape[0],), 1.0 / hidden.shape[0],
                                jnp.float32)
    if over is not None:
        if with_rows:
            raise NotImplementedError(
                "linear_cross_entropy: no rows beside a sliced vocabulary")
        return _lce_sliced(hidden, weight, label, token_weight,
                           bool(transpose_y), int(ignore_index), int(chunk),
                           tuple(over))
    run = _lce_rows if with_rows else _lce
    return run(hidden, weight, label, token_weight, bool(transpose_y),
               int(ignore_index), int(chunk))


@register_op("softmax_with_cross_entropy", amp_policy="black")
def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, axis=-1,
                               return_softmax=False):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
    if soft_label:
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lbl = label
        if lbl.ndim == logp.ndim:
            lbl = jnp.squeeze(lbl, axis=axis)
        lbl = lbl.astype(jnp.int32)
        valid = lbl != ignore_index
        safe = jnp.where(valid, lbl, 0)
        picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, axis),
                                     axis=axis)
        loss = jnp.where(jnp.expand_dims(valid, axis), -picked, 0.0)
    loss = loss.astype(logits.dtype)
    if return_softmax:
        return loss, jnp.exp(logp).astype(logits.dtype)
    return loss


@register_op("nll_loss", amp_policy="black")
def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean"):
    lbl = label.astype(jnp.int32)
    valid = lbl != ignore_index
    safe = jnp.where(valid, lbl, 0)
    picked = jnp.take_along_axis(input, safe[:, None], axis=1)[:, 0]
    loss = jnp.where(valid, -picked, 0.0)
    if weight is not None:
        w = jnp.take(weight, safe)
        loss = loss * jnp.where(valid, w, 0.0)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(
                jnp.sum(jnp.where(valid, w, 0.0)), 1e-12)
    return _reduce(loss, reduction)


@register_op("binary_cross_entropy", amp_policy="black")
def binary_cross_entropy(input, label, weight=None, reduction="mean"):
    eps = 1e-12
    out = -(label * jnp.log(jnp.maximum(input, eps)) +
            (1 - label) * jnp.log(jnp.maximum(1 - input, eps)))
    if weight is not None:
        out = out * weight
    return _reduce(out, reduction)


@register_op("binary_cross_entropy_with_logits", amp_policy="black")
def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None):
    logit = logit.astype(jnp.float32)
    max_val = jnp.maximum(-logit, 0.0)
    if pos_weight is not None:
        log_w = (pos_weight - 1.0) * label + 1.0
        out = (1 - label) * logit + log_w * (
            jnp.log(1 + jnp.exp(-jnp.abs(logit))) + max_val)
    else:
        out = (1 - label) * logit + max_val + jnp.log(
            jnp.exp(-max_val) + jnp.exp(-logit - max_val))
    if weight is not None:
        out = out * weight
    return _reduce(out, reduction)


@register_op("sigmoid_cross_entropy_with_logits", amp_policy="black")
def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100,
                                      normalize=False):
    x32 = x.astype(jnp.float32)
    loss = jnp.maximum(x32, 0.0) - x32 * label + jnp.log1p(
        jnp.exp(-jnp.abs(x32)))
    valid = label != ignore_index
    loss = jnp.where(valid, loss, 0.0)
    if normalize:
        loss = loss / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    return loss


@register_op("kl_div", amp_policy="black")
def kl_div(input, label, reduction="mean", log_target=False):
    if log_target:
        out = jnp.exp(label) * (label - input)
    else:
        out = label * (jnp.log(jnp.maximum(label, 1e-30)) - input)
    if reduction == "batchmean":
        return jnp.sum(out) / input.shape[0]
    return _reduce(out, reduction)


@register_op("margin_ranking_loss")
def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean"):
    out = jnp.maximum(-label * (input - other) + margin, 0.0)
    return _reduce(out, reduction)


@register_op("hinge_embedding_loss")
def hinge_embedding_loss(input, label, margin=1.0, reduction="mean"):
    out = jnp.where(label == 1.0, input,
                    jnp.maximum(0.0, margin - input))
    return _reduce(out, reduction)


@register_op("cosine_embedding_loss")
def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean"):
    cos = jnp.sum(input1 * input2, axis=-1) / (
        jnp.linalg.norm(input1, axis=-1) * jnp.linalg.norm(input2, axis=-1)
        + 1e-12)
    out = jnp.where(label == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
    return _reduce(out, reduction)


@register_op("triplet_margin_loss")
def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean"):
    def dist(a, b):
        return jnp.power(jnp.sum(jnp.power(jnp.abs(a - b) + epsilon, p),
                                 axis=-1), 1.0 / p)
    d_pos = dist(input, positive)
    d_neg = dist(input, negative)
    if swap:
        d_neg = jnp.minimum(d_neg, dist(positive, negative))
    out = jnp.maximum(d_pos - d_neg + margin, 0.0)
    return _reduce(out, reduction)


@register_op("square_error_cost")
def square_error_cost(input, label):
    return jnp.square(input - label)


@register_op("log_loss")
def log_loss(input, label, epsilon=1e-4):
    return -label * jnp.log(input + epsilon) - (
        1 - label) * jnp.log(1 - input + epsilon)


# ======================= attention =======================
@register_op("scaled_dot_product_attention", amp_policy="white")
def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True):
    # [batch, seq, heads, head_dim] (paddle convention,
    # ref: python/paddle/nn/functional/flash_attention.py:441 — which also
    # routes SDPA into the flash library when eligible)
    if attn_mask is None and (dropout_p == 0.0 or not training):
        from ..kernels.pallas import flash_attention as _pk_fa
        from ..kernels.pallas.flash_attention import (
            _pallas_available, _shapes_ok)
        if _pallas_available() and _shapes_ok(query.shape, key.shape):
            return _pk_fa(query, key, value, causal=is_causal)
    q = jnp.swapaxes(query, 1, 2)  # [b, h, s, d]
    k = jnp.swapaxes(key, 1, 2)
    v = jnp.swapaxes(value, 1, 2)
    d = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(d)
    if is_causal:
        # bottom-right aligned causal mask: with a kv-cache (s_k > s_q)
        # query i attends keys <= (s_k - s_q) + i; reduces to plain tril
        # when s_q == s_k and to "attend everything" when s_q == 1
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        causal = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        scores = jnp.where(causal, scores, -jnp.inf)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            scores = jnp.where(attn_mask, scores, -jnp.inf)
        else:
            scores = scores + attn_mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training:
        from ..core.generator import next_key
        keep = jax.random.bernoulli(next_key(), 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return jnp.swapaxes(out, 1, 2)  # back to [b, s, h, d]


# ======================= misc nn =======================
@register_op("label_smooth")
def label_smooth(label, prior_dist=None, epsilon=0.1):
    n = label.shape[-1]
    if prior_dist is not None:
        return (1 - epsilon) * label + epsilon * prior_dist
    return (1 - epsilon) * label + epsilon / n


@register_op("npair_loss")
def npair_loss(anchor, positive, labels, l2_reg=0.002):
    sim = jnp.matmul(anchor, positive.T)
    b = anchor.shape[0]
    tgt = jnp.arange(b)
    logp = jax.nn.log_softmax(sim, axis=1)
    ce = -jnp.take_along_axis(logp, tgt[:, None], axis=1).mean()
    l2 = l2_reg * (jnp.sum(jnp.square(anchor)) +
                   jnp.sum(jnp.square(positive))) / (2.0 * b)
    return ce + l2


@register_op("pixel_shuffle")
def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    r = upscale_factor
    if data_format == "NCHW":
        n, c, h, w = x.shape
        x = x.reshape(n, c // (r * r), r, r, h, w)
        x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
        return x.reshape(n, c // (r * r), h * r, w * r)
    n, h, w, c = x.shape
    x = x.reshape(n, h, w, r, r, c // (r * r))
    x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
    return x.reshape(n, h * r, w * r, c // (r * r))


@register_op("pixel_unshuffle")
def pixel_unshuffle(x, downscale_factor, data_format="NCHW"):
    r = downscale_factor
    n, c, h, w = x.shape
    x = x.reshape(n, c, h // r, r, w // r, r)
    x = jnp.transpose(x, (0, 1, 3, 5, 2, 4))
    return x.reshape(n, c * r * r, h // r, w // r)


@register_op("channel_shuffle")
def channel_shuffle(x, groups, data_format="NCHW"):
    n, c, h, w = x.shape
    x = x.reshape(n, groups, c // groups, h, w)
    x = jnp.swapaxes(x, 1, 2)
    return x.reshape(n, c, h, w)


@register_op("interpolate")
def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW"):
    channel_last = data_format[-1] == "C"
    spatial = x.shape[1:-1] if channel_last else x.shape[2:]
    if size is None:
        if isinstance(scale_factor, (int, float)):
            scale_factor = [scale_factor] * len(spatial)
        size = [int(s * f) for s, f in zip(spatial, scale_factor)]
    size = [int(s.item()) if hasattr(s, "item") else int(s) for s in (
        size if isinstance(size, (list, tuple)) else [size])]
    jmode = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
             "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]
    if channel_last:
        shape = (x.shape[0],) + tuple(size) + (x.shape[-1],)
    else:
        shape = x.shape[:2] + tuple(size)
    if mode == "nearest":
        return jax.image.resize(x, shape, method="nearest")
    if align_corners:
        # emulate align_corners with explicit coordinate map
        return _resize_align_corners(x, shape, jmode, channel_last)
    return jax.image.resize(x, shape, method=jmode)


def _resize_align_corners(x, shape, method, channel_last):
    import jax.image as jimage
    spatial_axes = range(1, x.ndim - 1) if channel_last else range(2, x.ndim)
    out = x
    for ax in spatial_axes:
        n_in, n_out = x.shape[ax], shape[ax]
        if n_in == n_out:
            continue
        pos = jnp.linspace(0, n_in - 1, n_out)
        lo = jnp.floor(pos).astype(jnp.int32)
        hi = jnp.minimum(lo + 1, n_in - 1)
        w = (pos - lo).astype(x.dtype)
        lo_v = jnp.take(out, lo, axis=ax)
        hi_v = jnp.take(out, hi, axis=ax)
        bshape = [1] * out.ndim
        bshape[ax] = n_out
        w = w.reshape(bshape)
        out = lo_v * (1 - w) + hi_v * w
    return out


@register_op("upsample")
def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, data_format="NCHW"):
    return interpolate.raw_fn(x, size, scale_factor, mode, align_corners,
                              data_format)


@register_op("unfold_im2col")
def unfold_im2col(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    n, c, h, w = x.shape
    kh, kw = _norm_tuple(kernel_sizes, 2)
    sh, sw = _norm_tuple(strides, 2)
    ph, pw = _norm_tuple(paddings, 2)
    dh, dw = _norm_tuple(dilations, 2)
    xp = jnp.pad(x, [(0, 0), (0, 0), (ph, ph), (pw, pw)])
    oh = (h + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    ow = (w + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    patches = []
    for i in range(kh):
        for j in range(kw):
            patch = xp[:, :, i * dh:i * dh + oh * sh:sh,
                       j * dw:j * dw + ow * sw:sw]
            patches.append(patch)
    out = jnp.stack(patches, axis=2)  # [n, c, kh*kw, oh, ow]
    return out.reshape(n, c * kh * kw, oh * ow)


@register_op("temporal_shift")
def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW"):
    nt, c, h, w = x.shape
    n = nt // seg_num
    xr = x.reshape(n, seg_num, c, h, w)
    fold = int(c * shift_ratio)
    left = jnp.concatenate([xr[:, 1:, :fold], jnp.zeros_like(xr[:, :1, :fold])], 1)
    right = jnp.concatenate([jnp.zeros_like(xr[:, :1, fold:2 * fold]),
                             xr[:, :-1, fold:2 * fold]], 1)
    rest = xr[:, :, 2 * fold:]
    return jnp.concatenate([left, right, rest], axis=2).reshape(nt, c, h, w)


@register_op("affine_grid")
def affine_grid(theta, out_shape, align_corners=True):
    n, c, h, w = [int(v) for v in out_shape]
    if align_corners:
        ys = jnp.linspace(-1, 1, h)
        xs = jnp.linspace(-1, 1, w)
    else:
        ys = (jnp.arange(h) + 0.5) / h * 2 - 1
        xs = (jnp.arange(w) + 0.5) / w * 2 - 1
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1)  # [h, w, 3]
    return jnp.einsum("hwk,njk->nhwj", base, theta)


@register_op("huber_loss", amp_policy="black")
def huber_loss(input, label, delta=1.0, reduction="mean"):
    """ref: phi/kernels/impl/huber_loss_kernel_impl.h"""
    d = (input - label).astype(jnp.float32)
    ad = jnp.abs(d)
    loss = jnp.where(ad <= delta, 0.5 * d * d, delta * (ad - 0.5 * delta))
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def bce_loss(input, label, weight=None, reduction="mean"):
    """Alias of binary_cross_entropy kept for ops.yaml name parity."""
    return binary_cross_entropy(input, label, weight=weight,
                                reduction=reduction)


@register_op("rrelu")
def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=True, key=None):
    """Randomized leaky ReLU (ref: rrelu in ops.yaml): training samples
    the negative slope per element from U(lower, upper); eval uses the
    mean slope."""
    if not training:
        return jnp.where(x >= 0, x, x * ((lower + upper) / 2.0))
    if key is None:
        from ..core.generator import next_key
        key = next_key()
    slope = jax.random.uniform(key, x.shape, jnp.float32,
                               minval=lower, maxval=upper).astype(x.dtype)
    return jnp.where(x >= 0, x, x * slope)


@register_op("hsigmoid_loss", amp_policy="black")
def hsigmoid_loss(x, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None):
    """Hierarchical sigmoid loss over the default complete binary tree
    (ref: phi/kernels/cpu/hsigmoid_loss_kernel.cc + the SimpleCode scheme
    in phi/kernels/funcs/matrix_bit_code.h: for class c the tree walk is
    the binary expansion of c + num_classes).

    x: [B, F]; label: [B]; weight: [num_classes - 1, F]; bias:
    [num_classes - 1]. Custom trees pass path_table/path_code:
    [B, max_depth] with -1 padding.
    """
    B = x.shape[0]
    xf = x.astype(jnp.float32)
    if path_table is None:
        code = label.astype(jnp.int32) + num_classes
        max_depth = int(np.floor(np.log2(max(num_classes, 2)))) + 1
        ds = jnp.arange(max_depth)
        length = jnp.floor(jnp.log2(code.astype(jnp.float32))).astype(
            jnp.int32)
        # node index at depth d (from the msb side): (code >> (len - d)) - 1
        shift = jnp.maximum(length[:, None] - ds[None, :], 0)
        node = (code[:, None] >> shift) - 1                 # [B, D]
        bit = (code[:, None] >> jnp.maximum(shift - 1, 0)) & 1
        valid = ds[None, :] < length[:, None]
    else:
        node = path_table.astype(jnp.int32)
        bit = path_code.astype(jnp.int32)
        valid = node >= 0
    node = jnp.where(valid, node, 0)
    w = weight[node]                                        # [B, D, F]
    logits = jnp.einsum("bdf,bf->bd", w.astype(jnp.float32), xf)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)[node]
    # BCE with target = bit
    t = bit.astype(jnp.float32)
    per = jnp.maximum(logits, 0) - logits * t + jnp.log1p(
        jnp.exp(-jnp.abs(logits)))
    per = jnp.where(valid, per, 0.0)
    return jnp.sum(per, axis=1, keepdims=True)


@register_op("margin_cross_entropy", amp_policy="black")
def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, return_softmax=False):
    """ArcFace/CosFace-style margin softmax CE (ref:
    phi/kernels/gpu/margin_cross_entropy_kernel.cu). logits are cosine
    similarities in [-1, 1]; the target class logit cos(theta) becomes
    cos(margin1*theta + margin2) - margin3 before scaling."""
    lf = logits.astype(jnp.float32)
    lbl = label.astype(jnp.int32).reshape(-1)
    cos_t = jnp.clip(
        jnp.take_along_axis(lf, lbl[:, None], axis=1)[:, 0], -1.0, 1.0)
    theta = jnp.arccos(cos_t)
    cos_m = jnp.cos(margin1 * theta + margin2) - margin3
    adjusted = jnp.put_along_axis(lf, lbl[:, None], cos_m[:, None],
                                  axis=1, inplace=False)
    z = adjusted * scale
    lse = jax.scipy.special.logsumexp(z, axis=1)
    tgt = jnp.take_along_axis(z, lbl[:, None], axis=1)[:, 0]
    loss = (lse - tgt)[:, None]
    if return_softmax:
        return loss, jax.nn.softmax(z, axis=1)
    return loss


@register_op("bilinear", amp_policy="white")
def bilinear(x1, x2, weight, bias=None):
    """out[b, o] = x1[b]^T W[o] x2[b] (ref: bilinear in ops.yaml)."""
    out = jnp.einsum("bi,oij,bj->bo", x1, weight, x2,
                     preferred_element_type=jnp.float32).astype(x1.dtype)
    if bias is not None:
        out = out + bias.reshape(1, -1)
    return out


@register_op("adaptive_max_pool1d")
def adaptive_max_pool1d(x, output_size, return_mask=False):
    """ref: max_pool2d_with_index family, 1-D adaptive variant.
    return_mask=True also returns the int32 argmax positions along L
    (indices into the unpadded input, the unpool contract)."""
    L = x.shape[-1]
    o = output_size if isinstance(output_size, int) else output_size[0]
    cols, idxs = [], []
    for i in range(o):
        lo, hi = (i * L) // o, -(-((i + 1) * L) // o)
        win = x[..., lo:hi]
        cols.append(jnp.max(win, axis=-1))
        if return_mask:
            idxs.append(jnp.argmax(win, axis=-1).astype(jnp.int32)
                        + lo)
    out = jnp.stack(cols, axis=-1)
    if return_mask:
        return out, jnp.stack(idxs, axis=-1)
    return out


@register_op("adaptive_avg_pool3d")
def adaptive_avg_pool3d(x, output_size, data_format="NCDHW"):
    return _adaptive_pool3d(x, output_size, jnp.mean, data_format)


@register_op("adaptive_max_pool3d")
def adaptive_max_pool3d(x, output_size, data_format="NCDHW",
                        return_mask=False):
    """return_mask=True also returns int32 argmax indices FLAT into
    the input's D*H*W spatial volume (the reference
    max_pool3d_with_index contract; feeds unpool3d). Mask output is
    NCDHW-only, matching the reference layer surface."""
    if not return_mask:
        return _adaptive_pool3d(x, output_size, jnp.max, data_format)
    if data_format[-1] == "C":
        raise NotImplementedError(
            "adaptive_max_pool3d(return_mask=True) supports NCDHW "
            "only (the reference AdaptiveMaxPool3D has no "
            "data_format)")
    if isinstance(output_size, int):
        output_size = (output_size,) * 3
    N, C, D, H, W = x.shape
    od, oh, ow = output_size
    planes, idxp = [], []
    for i in range(od):
        d0, d1 = (i * D) // od, -(-((i + 1) * D) // od)
        rows, idxr = [], []
        for j in range(oh):
            h0, h1 = (j * H) // oh, -(-((j + 1) * H) // oh)
            cols, idxc = [], []
            for k in range(ow):
                w0, w1 = (k * W) // ow, -(-((k + 1) * W) // ow)
                win = x[:, :, d0:d1, h0:h1, w0:w1]
                flat = win.reshape(N, C, -1)
                arg = jnp.argmax(flat, axis=-1)
                cols.append(jnp.max(flat, axis=-1))
                hh, ww = h1 - h0, w1 - w0
                ld, rem = arg // (hh * ww), arg % (hh * ww)
                g = ((ld + d0) * H + (rem // ww + h0)) * W \
                    + (rem % ww + w0)
                idxc.append(g.astype(jnp.int32))
            rows.append(jnp.stack(cols, axis=-1))
            idxr.append(jnp.stack(idxc, axis=-1))
        planes.append(jnp.stack(rows, axis=-2))
        idxp.append(jnp.stack(idxr, axis=-2))
    return (jnp.stack(planes, axis=-3), jnp.stack(idxp, axis=-3))


def _adaptive_pool3d(x, output_size, reducer, data_format):
    if isinstance(output_size, int):
        output_size = (output_size,) * 3
    channel_last = data_format[-1] == "C"
    axes = (1, 2, 3) if channel_last else (2, 3, 4)
    dims = [x.shape[a] for a in axes]
    if all(d % o == 0 for d, o in zip(dims, output_size)) \
            and not channel_last:
        # evenly divisible: one reshape + one fused reduction
        n, c = x.shape[:2]
        od, oh, ow = output_size
        r = x.reshape(n, c, od, dims[0] // od, oh, dims[1] // oh,
                      ow, dims[2] // ow)
        return reducer(r, axis=(3, 5, 7))
    planes = []
    for i in range(output_size[0]):
        d0, d1 = (i * dims[0]) // output_size[0], \
            -(-((i + 1) * dims[0]) // output_size[0])
        rows = []
        for j in range(output_size[1]):
            h0, h1 = (j * dims[1]) // output_size[1], \
                -(-((j + 1) * dims[1]) // output_size[1])
            cols = []
            for k in range(output_size[2]):
                w0, w1 = (k * dims[2]) // output_size[2], \
                    -(-((k + 1) * dims[2]) // output_size[2])
                sl = [slice(None)] * x.ndim
                sl[axes[0]] = slice(d0, d1)
                sl[axes[1]] = slice(h0, h1)
                sl[axes[2]] = slice(w0, w1)
                cols.append(reducer(x[tuple(sl)], axis=axes))
            rows.append(jnp.stack(cols, axis=-1))
        planes.append(jnp.stack(rows, axis=-2))
    stacked = jnp.stack(planes, axis=-3)
    if channel_last:
        return jnp.moveaxis(stacked, 1, -1)
    return stacked
