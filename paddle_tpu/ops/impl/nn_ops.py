"""NN op implementations (linear/conv/pool/norm/loss/embedding/attention).

ref API: python/paddle/nn/functional/*. Layout note: the reference defaults
to NCHW; XLA:TPU internally prefers NHWC and its layout assignment pass
transposes convolutions automatically, so we keep NCHW as the user-visible
default (data_format attr switches) and let XLA pick device layouts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---- linear --------------------------------------------------------------
def linear(x, weight, bias=None):
    # paddle weight layout: [in, out] (nn/functional/common.py linear)
    out = jnp.matmul(x, weight)
    if bias is not None:
        out = out + bias
    return out


# ---- convolutions --------------------------------------------------------
def _normalize_tuple(v, n):
    if isinstance(v, (int, np.integer)):
        return (int(v),) * n
    v = tuple(int(i) for i in v)
    if len(v) == 1:
        return v * n
    return v


def _conv_padding(padding, n, stride, kernel, dilation):
    """paddle padding: int | list | 'SAME' | 'VALID'."""
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, (int, np.integer)):
        return [(int(padding), int(padding))] * n
    padding = list(padding)
    if len(padding) == n and all(isinstance(p, (int, np.integer)) for p in padding):
        return [(int(p), int(p)) for p in padding]
    if len(padding) == 2 * n:
        return [
            (int(padding[2 * i]), int(padding[2 * i + 1])) for i in range(n)
        ]
    if all(isinstance(p, (list, tuple)) for p in padding):
        return [tuple(int(v) for v in p) for p in padding]
    raise ValueError(f"bad padding: {padding}")


def _dim_numbers(n, channel_last):
    if channel_last:
        lhs = "N" + "".join("DHW"[3 - n :][i] for i in range(n)) + "C"
    else:
        lhs = "NC" + "".join("DHW"[3 - n :][i] for i in range(n))
    rhs = "OI" + "".join("DHW"[3 - n :][i] for i in range(n))
    out = lhs
    return jax.lax.conv_dimension_numbers((1,) * (n + 2), (1,) * (n + 2), (lhs, rhs, out))


def conv_nd(
    x,
    weight,
    bias=None,
    *,
    stride=1,
    padding=0,
    dilation=1,
    groups=1,
    data_format="NCHW",
    n=2,
):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    stride = _normalize_tuple(stride, n)
    dilation = _normalize_tuple(dilation, n)
    kernel = weight.shape[2:]
    pad = _conv_padding(padding, n, stride, kernel, dilation)
    dn = _dim_numbers(n, channel_last)
    out = jax.lax.conv_general_dilated(
        x,
        weight,
        window_strides=stride,
        padding=pad,
        rhs_dilation=dilation,
        feature_group_count=groups,
        dimension_numbers=dn,
        preferred_element_type=None,
    )
    if bias is not None:
        if channel_last:
            out = out + bias.reshape((1,) * (n + 1) + (-1,))
        else:
            out = out + bias.reshape((1, -1) + (1,) * n)
    return out


def conv1d(x, weight, bias=None, *, stride=1, padding=0, dilation=1, groups=1, data_format="NCL"):
    return conv_nd(
        x, weight, bias, stride=stride, padding=padding, dilation=dilation,
        groups=groups, data_format=data_format, n=1,
    )


def conv2d(x, weight, bias=None, *, stride=1, padding=0, dilation=1, groups=1, data_format="NCHW"):
    return conv_nd(
        x, weight, bias, stride=stride, padding=padding, dilation=dilation,
        groups=groups, data_format=data_format, n=2,
    )


def conv3d(x, weight, bias=None, *, stride=1, padding=0, dilation=1, groups=1, data_format="NCDHW"):
    return conv_nd(
        x, weight, bias, stride=stride, padding=padding, dilation=dilation,
        groups=groups, data_format=data_format, n=3,
    )


def conv_transpose_nd(
    x, weight, bias=None, *, stride=1, padding=0, output_padding=0, dilation=1,
    groups=1, data_format="NCHW", n=2,
):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    stride = _normalize_tuple(stride, n)
    dilation = _normalize_tuple(dilation, n)
    # weight layout [in, out//groups, *k] (paddle conv_transpose)
    kernel = weight.shape[2:]
    if isinstance(padding, str):
        pad_pairs = None
        pad_str = padding.upper()
    else:
        pad_pairs = _conv_padding(padding, n, stride, kernel, dilation)
        pad_str = None
    out_padding = _normalize_tuple(output_padding, n)

    # Express as gradient-of-conv: lhs_dilation = stride.
    if pad_pairs is None:
        padding_arg = pad_str
    else:
        padding_arg = []
        for (lo, hi), k, d, op_ in zip(pad_pairs, kernel, dilation, out_padding):
            eff_k = (k - 1) * d + 1
            padding_arg.append((eff_k - 1 - lo, eff_k - 1 - hi + op_))
    dn = _dim_numbers(n, channel_last)
    # flip spatial dims and swap I/O of the kernel: [in, out, *k] -> [out, in, *k]
    w = jnp.flip(weight, axis=tuple(range(2, 2 + n)))
    if groups > 1:
        in_c = weight.shape[0]
        w = w.reshape((groups, in_c // groups) + w.shape[1:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((w.shape[0] * w.shape[1], in_c // groups) + w.shape[3:])
    else:
        w = jnp.swapaxes(w, 0, 1)
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(1,) * n,
        padding=padding_arg,
        lhs_dilation=stride,
        rhs_dilation=dilation,
        feature_group_count=groups,
        dimension_numbers=dn,
    )
    if bias is not None:
        if channel_last:
            out = out + bias.reshape((1,) * (n + 1) + (-1,))
        else:
            out = out + bias.reshape((1, -1) + (1,) * n)
    return out


def conv1d_transpose(x, weight, bias=None, **kw):
    return conv_transpose_nd(x, weight, bias, n=1, **kw)


def conv2d_transpose(x, weight, bias=None, **kw):
    return conv_transpose_nd(x, weight, bias, n=2, **kw)


def conv3d_transpose(x, weight, bias=None, **kw):
    return conv_transpose_nd(x, weight, bias, n=3, **kw)


# ---- pooling -------------------------------------------------------------
def _pool(x, *, kernel_size, stride, padding, n, reducer, init, data_format, ceil_mode=False, count_include_pad=True):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    k = _normalize_tuple(kernel_size, n)
    s = _normalize_tuple(stride if stride is not None else kernel_size, n)
    pad = _conv_padding(padding, n, s, k, (1,) * n)
    if channel_last:
        dims = (1,) + k + (1,)
        strides = (1,) + s + (1,)
        pads = [(0, 0)] + (pad if isinstance(pad, list) else pad) + [(0, 0)] if isinstance(pad, list) else pad
    else:
        dims = (1, 1) + k
        strides = (1, 1) + s
        pads = [(0, 0), (0, 0)] + pad if isinstance(pad, list) else pad
    if isinstance(pad, str):
        pads = pad
    return jax.lax.reduce_window(x, init, reducer, dims, strides, pads)


def max_pool_nd(x, *, kernel_size, stride=None, padding=0, ceil_mode=False, data_format="NCHW", n=2):
    neg = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
    return _pool(
        x, kernel_size=kernel_size, stride=stride, padding=padding, n=n,
        reducer=jax.lax.max, init=neg, data_format=data_format, ceil_mode=ceil_mode,
    )


def avg_pool_nd(x, *, kernel_size, stride=None, padding=0, ceil_mode=False,
                count_include_pad=True, data_format="NCHW", n=2):
    summed = _pool(
        x, kernel_size=kernel_size, stride=stride, padding=padding, n=n,
        reducer=jax.lax.add, init=0.0 if jnp.issubdtype(x.dtype, jnp.floating) else 0,
        data_format=data_format, ceil_mode=ceil_mode,
    )
    k = _normalize_tuple(kernel_size, n)
    if count_include_pad:
        denom = np.prod(k)
        return summed / jnp.asarray(denom, dtype=x.dtype)
    ones = jnp.ones_like(x)
    counts = _pool(
        ones, kernel_size=kernel_size, stride=stride, padding=padding, n=n,
        reducer=jax.lax.add, init=0.0, data_format=data_format, ceil_mode=ceil_mode,
    )
    return summed / counts


def max_pool2d(x, *, kernel_size, stride=None, padding=0, ceil_mode=False, data_format="NCHW"):
    return max_pool_nd(x, kernel_size=kernel_size, stride=stride, padding=padding,
                       ceil_mode=ceil_mode, data_format=data_format, n=2)


def max_pool1d(x, *, kernel_size, stride=None, padding=0, ceil_mode=False, data_format="NCL"):
    return max_pool_nd(x, kernel_size=kernel_size, stride=stride, padding=padding,
                       ceil_mode=ceil_mode, data_format=data_format, n=1)


def max_pool3d(x, *, kernel_size, stride=None, padding=0, ceil_mode=False, data_format="NCDHW"):
    return max_pool_nd(x, kernel_size=kernel_size, stride=stride, padding=padding,
                       ceil_mode=ceil_mode, data_format=data_format, n=3)


def avg_pool2d(x, *, kernel_size, stride=None, padding=0, ceil_mode=False,
               count_include_pad=True, data_format="NCHW"):
    return avg_pool_nd(x, kernel_size=kernel_size, stride=stride, padding=padding,
                       ceil_mode=ceil_mode, count_include_pad=count_include_pad,
                       data_format=data_format, n=2)


def avg_pool1d(x, *, kernel_size, stride=None, padding=0, ceil_mode=False,
               count_include_pad=True, data_format="NCL"):
    return avg_pool_nd(x, kernel_size=kernel_size, stride=stride, padding=padding,
                       ceil_mode=ceil_mode, count_include_pad=count_include_pad,
                       data_format=data_format, n=1)


def avg_pool3d(x, *, kernel_size, stride=None, padding=0, ceil_mode=False,
               count_include_pad=True, data_format="NCDHW"):
    return avg_pool_nd(x, kernel_size=kernel_size, stride=stride, padding=padding,
                       ceil_mode=ceil_mode, count_include_pad=count_include_pad,
                       data_format=data_format, n=3)


def adaptive_avg_pool2d(x, *, output_size, data_format="NCHW"):
    if data_format != "NCHW":
        raise NotImplementedError("adaptive pool expects NCHW")
    out_h, out_w = _normalize_tuple(output_size, 2)
    n, c, h, w = x.shape
    if h % out_h == 0 and w % out_w == 0:
        x5 = x.reshape(n, c, out_h, h // out_h, out_w, w // out_w)
        return x5.mean(axis=(3, 5))
    # generic: per-output-window mean (paddle adaptive bucketing)
    rows = [x[:, :, (i * h) // out_h : -(-(i + 1) * h // out_h), :] for i in range(out_h)]
    pooled_rows = []
    for r in rows:
        cols = [
            r[:, :, :, (j * w) // out_w : -(-(j + 1) * w // out_w)].mean(axis=(2, 3))
            for j in range(out_w)
        ]
        pooled_rows.append(jnp.stack(cols, axis=-1))
    return jnp.stack(pooled_rows, axis=-2)


def adaptive_max_pool2d(x, *, output_size, data_format="NCHW"):
    out_h, out_w = _normalize_tuple(output_size, 2)
    n, c, h, w = x.shape
    if h % out_h == 0 and w % out_w == 0:
        x5 = x.reshape(n, c, out_h, h // out_h, out_w, w // out_w)
        return x5.max(axis=(3, 5))
    rows = [x[:, :, (i * h) // out_h : -(-(i + 1) * h // out_h), :] for i in range(out_h)]
    pooled_rows = []
    for r in rows:
        cols = [
            r[:, :, :, (j * w) // out_w : -(-(j + 1) * w // out_w)].max(axis=(2, 3))
            for j in range(out_w)
        ]
        pooled_rows.append(jnp.stack(cols, axis=-1))
    return jnp.stack(pooled_rows, axis=-2)


def adaptive_avg_pool1d(x, *, output_size):
    n, c, l = x.shape
    out = _normalize_tuple(output_size, 1)[0]
    if l % out == 0:
        return x.reshape(n, c, out, l // out).mean(axis=3)
    segs = [
        x[:, :, (i * l) // out : -(-(i + 1) * l // out)].mean(axis=2) for i in range(out)
    ]
    return jnp.stack(segs, axis=-1)


# ---- normalization -------------------------------------------------------
def layer_norm(x, weight=None, bias=None, *, normalized_shape=None, epsilon=1e-5):
    if normalized_shape is None:
        axes = (x.ndim - 1,)
    else:
        k = len(normalized_shape) if isinstance(normalized_shape, (list, tuple)) else 1
        axes = tuple(range(x.ndim - k, x.ndim))
    mean = jnp.mean(x.astype(jnp.float32), axis=axes, keepdims=True)
    var = jnp.var(x.astype(jnp.float32), axis=axes, keepdims=True)
    out = (x.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + epsilon)
    out = out.astype(x.dtype)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


def rms_norm(x, weight=None, bias=None, *, epsilon=1e-6, begin_norm_axis=-1):
    """ref: phi/kernels/gpu/rms_norm_kernel.cu + incubate fused_rms_norm —
    fp32 accumulation then cast back, the Llama-family norm."""
    ax = begin_norm_axis % x.ndim
    axes = tuple(range(ax, x.ndim)) if ax != x.ndim - 1 else (x.ndim - 1,)
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
    out = (xf * jax.lax.rsqrt(var + epsilon)).astype(x.dtype)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


def batch_norm_infer(x, running_mean, running_var, weight=None, bias=None, *,
                     epsilon=1e-5, data_format="NCHW"):
    c_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    shape = [1] * x.ndim
    shape[c_axis] = -1
    inv = jax.lax.rsqrt(running_var.reshape(shape) + epsilon)
    out = (x - running_mean.reshape(shape)) * inv
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


def batch_norm_train(x, running_mean, running_var, weight=None, bias=None, *,
                     momentum=0.9, epsilon=1e-5, data_format="NCHW"):
    """Returns (out, new_running_mean, new_running_var). The stateful update
    is applied by the Layer (functional core stays pure)."""
    c_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes)
    var = jnp.var(xf, axis=axes)
    shape = [1] * x.ndim
    shape[c_axis] = -1
    inv = jax.lax.rsqrt(var.reshape(shape) + epsilon)
    out = ((xf - mean.reshape(shape)) * inv).astype(x.dtype)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    new_mean = momentum * running_mean + (1 - momentum) * mean
    new_var = momentum * running_var + (1 - momentum) * var
    return out, new_mean, new_var


def instance_norm(x, weight=None, bias=None, *, epsilon=1e-5, data_format="NCHW"):
    c_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    axes = tuple(i for i in range(2, x.ndim)) if c_axis == 1 else tuple(range(1, x.ndim - 1))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    shape = [1] * x.ndim
    shape[c_axis] = -1
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


def group_norm(x, weight=None, bias=None, *, num_groups=1, epsilon=1e-5, data_format="NCHW"):
    if not data_format.startswith("NC"):
        x = jnp.moveaxis(x, -1, 1)
    n, c = x.shape[:2]
    spatial = x.shape[2:]
    g = x.reshape((n, num_groups, c // num_groups) + spatial)
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.var(g, axis=axes, keepdims=True)
    g = (g - mean) * jax.lax.rsqrt(var + epsilon)
    out = g.reshape((n, c) + spatial)
    shape = [1, c] + [1] * len(spatial)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    if not data_format.startswith("NC"):
        out = jnp.moveaxis(out, 1, -1)
    return out


def local_response_norm(x, *, size=5, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW"):
    c_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    sq = jnp.square(x)
    half = size // 2
    c = x.shape[c_axis]
    sq_m = jnp.moveaxis(sq, c_axis, 0)
    padded = jnp.pad(sq_m, [(half, size - 1 - half)] + [(0, 0)] * (x.ndim - 1))
    acc = jnp.zeros_like(sq_m)
    for i in range(size):
        acc = acc + padded[i : i + c]
    denom = (k + alpha * acc) ** beta
    return x / jnp.moveaxis(denom, 0, c_axis)


# ---- embedding / dropout -------------------------------------------------
def embedding(x, weight, *, padding_idx=None, sparse=False):
    out = jnp.take(weight, x, axis=0)
    if padding_idx is not None:
        mask = (x != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return out


def dropout(x, *, key, p=0.5, training=True, mode="upscale_in_train", axis=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return x * (1.0 - p)
        return x
    if p == 1.0:
        return jnp.zeros_like(x)
    shape = list(x.shape)
    if axis is not None:
        axes = [axis] if isinstance(axis, int) else list(axis)
        shape = [s if i in axes else 1 for i, s in enumerate(shape)]
    keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
    if mode == "upscale_in_train":
        return jnp.where(keep, x / (1.0 - p), jnp.zeros_like(x))
    return jnp.where(keep, x, jnp.zeros_like(x))


def alpha_dropout(x, *, key, p=0.5, training=True):
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    a = (1.0 / ((1.0 - p) * (1.0 + p * alpha_p**2)) ** 0.5)
    b = -a * alpha_p * p
    return a * jnp.where(keep, x, alpha_p) + b


# ---- losses --------------------------------------------------------------
def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def cross_entropy(
    logits,
    label,
    weight=None,
    *,
    ignore_index=-100,
    reduction="mean",
    soft_label=False,
    axis=-1,
    use_softmax=True,
    label_smoothing=0.0,
):
    """ref: python/paddle/nn/functional/loss.py cross_entropy. Computed as
    fused log-softmax + gather (XLA fuses; the vocab-parallel variant lives
    in distributed.fleet)."""
    if use_softmax:
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
    else:
        logp = jnp.log(jnp.clip(logits.astype(jnp.float32), 1e-30, None))
    if soft_label or (label.ndim == logits.ndim and label.shape == logits.shape):
        soft = label.astype(jnp.float32)
        if label_smoothing:
            n = logits.shape[axis]
            soft = soft * (1 - label_smoothing) + label_smoothing / n
        loss = -jnp.sum(soft * logp, axis=axis)
    else:
        lab = label
        if lab.ndim == logits.ndim:
            lab = jnp.squeeze(lab, axis=axis)
        valid = lab != ignore_index
        safe = jnp.where(valid, lab, 0)
        picked = jnp.take_along_axis(
            logp, safe[..., None].astype(jnp.int32), axis=-1 if axis in (-1, logits.ndim - 1) else axis
        )[..., 0]
        if label_smoothing:
            n = logits.shape[axis]
            smooth_loss = -jnp.mean(logp, axis=axis)
            loss = (1 - label_smoothing) * (-picked) + label_smoothing * smooth_loss
        else:
            loss = -picked
        if weight is not None:
            w = jnp.take(weight, safe, axis=0)
            loss = loss * w
        loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
            if weight is not None:
                denom = jnp.maximum(
                    jnp.sum(jnp.where(valid, jnp.take(weight, safe, axis=0), 0.0)), 1e-12
                )
            return jnp.sum(loss) / denom
    return _reduce_loss(loss, reduction)


def softmax_with_cross_entropy(logits, label, *, soft_label=False, ignore_index=-100,
                               axis=-1, return_softmax=False):
    loss = cross_entropy(
        logits, label, soft_label=soft_label, ignore_index=ignore_index,
        reduction="none", axis=axis,
    )[..., None]
    if return_softmax:
        return loss, jax.nn.softmax(logits, axis=axis)
    return loss


def binary_cross_entropy(input, label, weight=None, *, reduction="mean"):
    eps = 1e-12
    loss = -(label * jnp.log(jnp.clip(input, eps, None))
             + (1 - label) * jnp.log(jnp.clip(1 - input, eps, None)))
    if weight is not None:
        loss = loss * weight
    return _reduce_loss(loss, reduction)


def binary_cross_entropy_with_logits(logit, label, weight=None, pos_weight=None, *, reduction="mean"):
    max_val = jnp.clip(-logit, 0, None)
    if pos_weight is not None:
        log_w = (pos_weight - 1) * label + 1
        loss = (1 - label) * logit + log_w * (
            jnp.log1p(jnp.exp(-jnp.abs(logit))) + max_val
        )
    else:
        loss = (1 - label) * logit + jnp.log1p(jnp.exp(-jnp.abs(logit))) + max_val
    if weight is not None:
        loss = loss * weight
    return _reduce_loss(loss, reduction)


def mse_loss(input, label, *, reduction="mean"):
    return _reduce_loss(jnp.square(input - label), reduction)


def l1_loss(input, label, *, reduction="mean"):
    return _reduce_loss(jnp.abs(input - label), reduction)


def smooth_l1_loss(input, label, *, reduction="mean", delta=1.0):
    diff = jnp.abs(input - label)
    loss = jnp.where(diff < delta, 0.5 * diff * diff / delta, diff - 0.5 * delta)
    return _reduce_loss(loss, reduction)


def nll_loss(log_prob, label, weight=None, *, ignore_index=-100, reduction="mean"):
    valid = label != ignore_index
    safe = jnp.where(valid, label, 0)
    picked = jnp.take_along_axis(log_prob, safe[..., None].astype(jnp.int32), axis=-1)[..., 0]
    loss = -picked
    if weight is not None:
        loss = loss * jnp.take(weight, safe, axis=0)
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        denom = jnp.sum(valid.astype(jnp.float32))
        if weight is not None:
            denom = jnp.sum(jnp.where(valid, jnp.take(weight, safe, axis=0), 0.0))
        return jnp.sum(loss) / jnp.maximum(denom, 1e-12)
    return _reduce_loss(loss, reduction)


def kl_div(input, label, *, reduction="mean", log_target=False):
    if log_target:
        loss = jnp.exp(label) * (label - input)
    else:
        loss = label * (jnp.log(jnp.clip(label, 1e-12, None)) - input)
    if reduction == "batchmean":
        return jnp.sum(loss) / input.shape[0]
    return _reduce_loss(loss, reduction)


def hinge_embedding_loss(input, label, *, margin=1.0, reduction="mean"):
    loss = jnp.where(label == 1, input, jnp.clip(margin - input, 0, None))
    return _reduce_loss(loss, reduction)


def margin_ranking_loss(input, other, label, *, margin=0.0, reduction="mean"):
    loss = jnp.clip(-label * (input - other) + margin, 0, None)
    return _reduce_loss(loss, reduction)


def cosine_embedding_loss(input1, input2, label, *, margin=0.0, reduction="mean"):
    cos = jnp.sum(input1 * input2, axis=-1) / (
        jnp.linalg.norm(input1, axis=-1) * jnp.linalg.norm(input2, axis=-1) + 1e-12
    )
    loss = jnp.where(label == 1, 1 - cos, jnp.clip(cos - margin, 0, None))
    return _reduce_loss(loss, reduction)


def triplet_margin_loss(input, positive, negative, *, margin=1.0, p=2.0, reduction="mean"):
    d_pos = jnp.sum(jnp.abs(input - positive) ** p, axis=-1) ** (1 / p)
    d_neg = jnp.sum(jnp.abs(input - negative) ** p, axis=-1) ** (1 / p)
    loss = jnp.clip(d_pos - d_neg + margin, 0, None)
    return _reduce_loss(loss, reduction)


def log_loss(input, label, *, epsilon=1e-4):
    return -label * jnp.log(input + epsilon) - (1 - label) * jnp.log(1 - input + epsilon)


def square_error_cost(input, label):
    return jnp.square(input - label)


# ---- misc functional -----------------------------------------------------
def cosine_similarity(x1, x2, *, axis=1, eps=1e-8):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.linalg.norm(x1, axis=axis)
    n2 = jnp.linalg.norm(x2, axis=axis)
    return dot / jnp.clip(n1 * n2, eps, None)


def normalize(x, *, p=2.0, axis=1, epsilon=1e-12):
    denom = jnp.clip(
        jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=True) ** (1.0 / p), epsilon, None
    )
    return x / denom


def label_smooth(label, *, epsilon=0.1):
    n = label.shape[-1]
    return (1 - epsilon) * label + epsilon / n


def pixel_shuffle(x, *, upscale_factor, data_format="NCHW"):
    r = upscale_factor
    n, c, h, w = x.shape
    oc = c // (r * r)
    x = x.reshape(n, oc, r, r, h, w)
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return x.reshape(n, oc, h * r, w * r)


def pixel_unshuffle(x, *, downscale_factor, data_format="NCHW"):
    r = downscale_factor
    n, c, h, w = x.shape
    x = x.reshape(n, c, h // r, r, w // r, r)
    x = jnp.transpose(x, (0, 1, 3, 5, 2, 4))
    return x.reshape(n, c * r * r, h // r, w // r)


def unfold(x, *, kernel_sizes, strides=1, paddings=0, dilations=1):
    k = _normalize_tuple(kernel_sizes, 2)
    s = _normalize_tuple(strides, 2)
    d = _normalize_tuple(dilations, 2)
    p = _conv_padding(paddings, 2, s, k, d)
    n, c, h, w = x.shape
    xp = jnp.pad(x, [(0, 0), (0, 0), p[0], p[1]])
    oh = (xp.shape[2] - (d[0] * (k[0] - 1) + 1)) // s[0] + 1
    ow = (xp.shape[3] - (d[1] * (k[1] - 1) + 1)) // s[1] + 1
    patches = []
    for i in range(k[0]):
        for j in range(k[1]):
            sl = xp[:, :, i * d[0] : i * d[0] + oh * s[0] : s[0],
                    j * d[1] : j * d[1] + ow * s[1] : s[1]]
            patches.append(sl)
    out = jnp.stack(patches, axis=2)  # [n, c, k*k, oh, ow]
    return out.reshape(n, c * k[0] * k[1], oh * ow)


def interpolate(x, *, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW"):
    if data_format not in ("NCHW", "NCL", "NCDHW"):
        raise NotImplementedError("interpolate expects channel-first")
    spatial = x.shape[2:]
    if size is None:
        if isinstance(scale_factor, (int, float)):
            scale_factor = [scale_factor] * len(spatial)
        size = [int(s * f) for s, f in zip(spatial, scale_factor)]
    size = [int(v) for v in (size if isinstance(size, (list, tuple)) else [size])]
    method = {
        "nearest": "nearest",
        "bilinear": "linear",
        "trilinear": "linear",
        "linear": "linear",
        "bicubic": "cubic",
        "area": "linear",
    }[mode]
    out_shape = x.shape[:2] + tuple(size)
    if mode == "nearest":
        # exact paddle nearest (floor) semantics
        idxs = [
            jnp.floor(jnp.arange(o) * (s / o)).astype(jnp.int32)
            for s, o in zip(spatial, size)
        ]
        out = x
        for dim, idx in enumerate(idxs):
            out = jnp.take(out, idx, axis=2 + dim)
        return out
    if align_corners:
        # build index grids per dim and linearly interpolate
        out = x.astype(jnp.float32)
        for dim, (s, o) in enumerate(zip(spatial, size)):
            pos = jnp.linspace(0.0, s - 1, o)
            lo = jnp.floor(pos).astype(jnp.int32)
            hi = jnp.clip(lo + 1, 0, s - 1)
            frac = (pos - lo).reshape([-1 if i == dim else 1 for i in range(len(spatial))])
            frac = jnp.expand_dims(frac, (0, 1))
            a = jnp.take(out, lo, axis=2 + dim)
            b = jnp.take(out, hi, axis=2 + dim)
            out = a * (1 - frac) + b * frac
        return out.astype(x.dtype)
    return jax.image.resize(x.astype(jnp.float32), out_shape, method=method).astype(x.dtype)


def affine_grid(theta, out_shape, *, align_corners=True):
    """ref: python/paddle/nn/functional/vision.py affine_grid — theta
    [N, 2, 3] -> grid [N, H, W, 2] (4-D out_shape [N, C, H, W]) or
    [N, 3, 4] -> [N, D, H, W, 3] (5-D). Pure dot_general lowering; pairs
    with grid_sample below."""
    out_shape = [int(s) for s in out_shape]
    dt = theta.dtype

    def axis_coords(n):
        if align_corners:
            if n == 1:
                return jnp.zeros((1,), dt)
            return jnp.linspace(-1.0, 1.0, n).astype(dt)
        return (((jnp.arange(n) * 2 + 1) / n) - 1.0).astype(dt)

    if len(out_shape) == 4:
        n, _, h, w = out_shape
        ys, xs = axis_coords(h), axis_coords(w)
        gx, gy = jnp.meshgrid(xs, ys)            # [h, w] each
        base = jnp.stack(
            [gx, gy, jnp.ones_like(gx)], axis=-1
        )                                        # [h, w, 3]
        # [n, h, w, 2] = base @ theta^T
        return jnp.einsum("hwk,nok->nhwo", base, theta.astype(dt))
    if len(out_shape) == 5:
        n, _, d, h, w = out_shape
        zs, ys, xs = axis_coords(d), axis_coords(h), axis_coords(w)
        gz, gy, gx = jnp.meshgrid(zs, ys, xs, indexing="ij")
        base = jnp.stack(
            [gx, gy, gz, jnp.ones_like(gx)], axis=-1
        )                                        # [d, h, w, 4]
        return jnp.einsum("dhwk,nok->ndhwo", base, theta.astype(dt))
    raise ValueError(
        f"affine_grid expects a 4-D or 5-D out_shape, got {out_shape}"
    )


def grid_sample(x, grid, *, mode="bilinear", padding_mode="zeros", align_corners=True):
    n, c, h, w = x.shape
    gx = grid[..., 0]
    gy = grid[..., 1]
    if align_corners:
        fx = (gx + 1) * (w - 1) / 2
        fy = (gy + 1) * (h - 1) / 2
    else:
        fx = ((gx + 1) * w - 1) / 2
        fy = ((gy + 1) * h - 1) / 2

    def sample(img, yy, xx):
        yy = jnp.clip(yy, 0, h - 1)
        xx = jnp.clip(xx, 0, w - 1)
        return img[:, :, yy, xx]  # unsupported fancy pattern; use vmap below

    x0 = jnp.floor(fx).astype(jnp.int32)
    y0 = jnp.floor(fy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wx = fx - x0
    wy = fy - y0

    def gather(img, yy, xx):
        yy_c = jnp.clip(yy, 0, h - 1)
        xx_c = jnp.clip(xx, 0, w - 1)
        out = jax.vmap(lambda im, y_, x_: im[:, y_, x_])(img, yy_c, xx_c)
        if padding_mode == "zeros":
            valid = ((yy >= 0) & (yy <= h - 1) & (xx >= 0) & (xx <= w - 1))
            out = out * valid[:, None].astype(out.dtype) if out.ndim == 2 else out * valid[:, None, ...].astype(out.dtype)
        return out

    v00 = gather(x, y0, x0)
    v01 = gather(x, y0, x1)
    v10 = gather(x, y1, x0)
    v11 = gather(x, y1, x1)
    wx_b = wx[:, None]
    wy_b = wy[:, None]
    out = (
        v00 * (1 - wx_b) * (1 - wy_b)
        + v01 * wx_b * (1 - wy_b)
        + v10 * (1 - wx_b) * wy_b
        + v11 * wx_b * wy_b
    )
    return out


# ---- attention -----------------------------------------------------------
def _pallas_attention_eligible(query, key, value, attn_mask, dropout_p,
                               is_causal):
    """Kernel contract: flag on, no mask/dropout, block-divisible seq
    lengths, head_dim within one VMEM tile budget, matching q/k/v head
    counts and dims. Causal cross-length attention is excluded: the
    kernel masks with absolute (top-left aligned) indices while the math
    fallback bottom-right aligns (tril k=kl-ql) — KV-cache decode must
    take the math path."""
    from ...core import flags

    if not flags.get_flag("FLAGS_use_pallas_kernels"):
        return False
    if attn_mask is not None or dropout_p > 0.0:
        return False
    b, sq, h, d = query.shape
    sk = key.shape[1]
    if key.shape[2] != h or value.shape[2] != h or value.shape[3] != d:
        return False
    if is_causal and sq != sk:
        return False
    if d > 256 or d % 8 != 0:
        return False
    # below the crossover, XLA's fused attention beat the kernel: 130ms
    # vs 155ms full-model step at seq 1024 on v5e, measured against the
    # kernel's old fixed 128 x 128 tile. The kernel now takes its tile
    # from the shape and is several times faster at 4096; the crossover
    # has not been measured again (PERF.md section 7).
    if max(sq, sk) < flags.get_flag("FLAGS_flash_attention_min_seq"):
        return False
    # real-TPU tile constraint: sequence blocks of 128 lanes
    return sq % 128 == 0 and sk % 128 == 0


def scaled_dot_product_attention(
    query, key, value, attn_mask=None, *, key_rng=None, dropout_p=0.0,
    is_causal=False, scale=None
):
    """ref: nn/functional/flash_attention.py:976 (math form) + :242
    (flash path). Layout: [batch, seq, heads, head_dim] like the
    reference. When FLAGS_use_pallas_kernels is set and the call fits the
    kernel contract (no mask, no dropout, block-divisible lengths), the
    Pallas flash kernel (kernels/pallas/flash_attention.py) runs instead
    of the math fallback. Attention dropout applies to the probabilities
    when dropout_p > 0 (key_rng plumbed by the generated wrapper)."""
    if _pallas_attention_eligible(query, key, value, attn_mask, dropout_p,
                                  is_causal):
        from ...kernels.pallas.flash_attention import flash_attention

        return flash_attention(
            query, key, value, causal=is_causal, scale=scale
        )
    q = jnp.swapaxes(query, 1, 2).astype(jnp.float32)  # [b, h, s, d]
    k = jnp.swapaxes(key, 1, 2).astype(jnp.float32)
    v = jnp.swapaxes(value, 1, 2).astype(jnp.float32)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / np.sqrt(d)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * s
    if is_causal:
        ql, kl = scores.shape[-2], scores.shape[-1]
        causal = jnp.tril(jnp.ones((ql, kl), dtype=bool), k=kl - ql)
        scores = jnp.where(causal, scores, -jnp.inf)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            scores = jnp.where(attn_mask, scores, -jnp.inf)
        else:
            scores = scores + attn_mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_p > 0.0 and key_rng is not None:
        keep = jax.random.bernoulli(key_rng, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return jnp.swapaxes(out, 1, 2).astype(query.dtype)


def window_attention(query, key, value, *, window, scale=None, impl="auto"):
    """Sliding-window causal self-attention, [batch, seq, heads, head_dim]
    in and out: a query at position p sees keys p - window + 1 .. p. impl
    ``"auto"`` is the flash kernels' band schedule where
    ``scaled_dot_product_attention`` would take the causal kernels, and the
    band-masked ``jax.numpy`` form elsewhere; ``"pallas"`` always the
    kernels (the interpreter off the TPU), ``"xla"`` always the math."""
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(
            f'window_attention impl must be "auto", "pallas" or "xla", got '
            f"{impl!r}")
    if impl == "auto":
        from ...core import device

        impl = "pallas" if device.on_tpu() and _pallas_attention_eligible(
            query, key, value, None, 0.0, True) else "xla"
    if impl == "pallas":
        from ...kernels.pallas.flash_attention import flash_attention

        return flash_attention(query, key, value, causal=True, scale=scale,
                               window=window)
    s = query.shape[1]
    pos = jnp.arange(s)
    gap = pos[:, None] - pos[None, :]
    band = jnp.logical_and(gap >= 0, gap < window)
    q = jnp.swapaxes(query, 1, 2).astype(jnp.float32)
    k = jnp.swapaxes(key, 1, 2).astype(jnp.float32)
    v = jnp.swapaxes(value, 1, 2).astype(jnp.float32)
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (
        scale if scale is not None else 1.0 / np.sqrt(q.shape[-1]))
    probs = jax.nn.softmax(jnp.where(band, sc, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return jnp.swapaxes(out, 1, 2).astype(query.dtype)


def mla_attention(q_nope, q_rope, k_nope, k_rope, v, *, scale, causal=True,
                  impl="auto"):
    """Latent attention's core: the score is ``(q_nope . k_nope + q_rope .
    k_rope) * scale`` with the rotary key [b, s, 1, d_rope] one head read
    by all, the values of their own width. The public op face of
    ``kernels.pallas.flash_attention.mla_attention`` (its kernels on a
    TPU, its ``jax.numpy`` form elsewhere)."""
    from ...kernels.pallas.flash_attention import mla_attention as _mla

    return _mla(q_nope, q_rope, k_nope, k_rope, v, scale=scale,
                causal=causal, impl=impl)


def batch_norm(x, running_mean, running_var, weight=None, bias=None, *,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=False):
    """Pure functional batch_norm (ref: nn/functional/norm.py batch_norm).
    Uses batch statistics when training (unless use_global_stats); running
    stats are NOT mutated here — the BatchNorm layer owns that state and
    calls batch_norm_with_stats."""
    if training and not use_global_stats:
        out, _, _ = batch_norm_train(
            x, running_mean, running_var, weight, bias,
            momentum=momentum, epsilon=epsilon, data_format=data_format,
        )
        return out
    return batch_norm_infer(
        x, running_mean, running_var, weight, bias,
        epsilon=epsilon, data_format=data_format,
    )


def bilinear(x1, x2, weight, bias=None):
    """out[n,o] = x1[n,:] @ W[o] @ x2[n,:] + b (ref: nn/functional/common.py
    bilinear; phi BilinearInferMeta)."""
    out = jnp.einsum("ni,oij,nj->no", x1, weight, x2)
    if bias is not None:
        out = out + bias
    return out


def dropout2d(x, *, key, p=0.5, training=True, data_format="NCHW"):
    """Channel-wise dropout on 4-D input (ref: nn/functional/common.py
    dropout2d — zeroes whole channels)."""
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, key=key, p=p, training=training, axis=axis)


def dropout3d(x, *, key, p=0.5, training=True, data_format="NCDHW"):
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, key=key, p=p, training=training, axis=axis)


def upsample(x, *, size=None, scale_factor=None, mode="nearest",
             align_corners=False, data_format="NCHW"):
    """Alias of interpolate (ref: nn/functional/common.py upsample)."""
    return interpolate(
        x, size=size, scale_factor=scale_factor, mode=mode,
        align_corners=align_corners, data_format=data_format,
    )


def max_pool2d_with_index(x, *, kernel_size, stride=None, padding=0,
                          ceil_mode=False, data_format="NCHW"):
    """(out, mask) where mask holds the flattened input H*W index of each
    window max (ref: phi MaxPoolWithIndexInferMeta; python
    nn/functional/pooling.py max_pool2d return_mask=True).

    Implemented with conv_general_dilated_patches + argmax over the window
    axis — one fused XLA computation, no select_and_scatter."""
    if data_format != "NCHW":
        raise ValueError("max_pool2d_with_index requires NCHW")
    k = _normalize_tuple(kernel_size, 2)
    s = _normalize_tuple(stride if stride is not None else kernel_size, 2)
    pad = _conv_padding(padding, 2, s, k, (1, 1))
    if isinstance(pad, str):
        raise ValueError("string padding unsupported for return_mask")
    n, c, h, w = x.shape
    neg = jnp.asarray(-jnp.inf, x.dtype) if jnp.issubdtype(
        x.dtype, jnp.floating
    ) else jnp.iinfo(x.dtype).min
    # patches: [N, C*kh*kw, OH, OW] (channel-major over C then window)
    patches = jax.lax.conv_general_dilated_patches(
        jnp.where(jnp.isfinite(x.astype(jnp.float32)), x, x),
        filter_shape=k, window_strides=s, padding=pad,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=None,
    )
    oh, ow = patches.shape[2], patches.shape[3]
    patches = patches.reshape(n, c, k[0] * k[1], oh, ow)
    # padded positions must lose the argmax: rebuild the same patches from
    # a validity mask
    valid = jax.lax.conv_general_dilated_patches(
        jnp.ones((n, c, h, w), jnp.float32), filter_shape=k,
        window_strides=s, padding=pad,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    ).reshape(n, c, k[0] * k[1], oh, ow)
    scored = jnp.where(valid > 0, patches.astype(jnp.float32), -jnp.inf)
    local = jnp.argmax(scored, axis=2)  # [N, C, OH, OW]
    out = jnp.max(scored, axis=2).astype(x.dtype)
    # local window idx -> global flat H*W idx
    ky = local // k[1]
    kx = local % k[1]
    oy = jnp.arange(oh).reshape(1, 1, oh, 1)
    ox = jnp.arange(ow).reshape(1, 1, 1, ow)
    iy = oy * s[0] - pad[0][0] + ky
    ix = ox * s[1] - pad[1][0] + kx
    mask = (iy * w + ix).astype(jnp.int32)
    return out, mask


# ---- r5 breadth additions (ref python/paddle/nn/functional) --------------
def huber_loss(input, label, *, delta=1.0, reduction="mean"):
    err = input - label
    a = jnp.abs(err)
    loss = jnp.where(a <= delta, 0.5 * err * err,
                     delta * (a - 0.5 * delta))
    return _reduce_loss(loss, reduction)


def hinge_loss(logits, labels):
    # ref hinge_loss: labels in {0,1}; elementwise max(0, 1 - (2y-1)*x)
    sign = 2.0 * labels - 1.0
    return jnp.maximum(0.0, 1.0 - sign * logits)


def sequence_mask(lengths, *, maxlen=None, dtype="int64"):
    import numpy as _np

    if maxlen is None:
        # deliberate graph break: the mask width is a SHAPE, so it must
        # be concrete — callers staging this op pass maxlen explicitly
        # analysis: allow(host-sync-in-traced) dynamic-shape graph break
        maxlen = int(_np.asarray(jax.device_get(lengths)).max())
    pos = jnp.arange(maxlen)
    mask = pos[None, :] < lengths.reshape(-1, 1)
    return mask.reshape(tuple(lengths.shape) + (maxlen,)).astype(dtype)


def _max_unpool_nd(x, indices, rank, kernel_size, stride, padding,
                   output_size):
    """Shared scatter body for max_unpool2d/3d: place each pooled value
    at its flat argmax slot in the restored spatial volume."""
    if stride is None:
        stride = kernel_size
    ks = (kernel_size,) * rank if isinstance(kernel_size, int) \
        else tuple(kernel_size)
    st = (stride,) * rank if isinstance(stride, int) else tuple(stride)
    n, c = x.shape[:2]
    pooled = x.shape[2:]
    if output_size is None:
        out_sp = tuple(
            (pooled[d] - 1) * st[d] + ks[d] - 2 * padding
            for d in range(rank)
        )
    else:
        out_sp = tuple(output_size[-rank:])
    numel = 1
    for v in out_sp:
        numel *= v
    flat_out = jnp.zeros((n, c, numel), x.dtype)
    idx = indices.reshape(n, c, -1)
    vals = x.reshape(n, c, -1)
    ni = jnp.arange(n)[:, None, None]
    ci = jnp.arange(c)[None, :, None]
    flat_out = flat_out.at[ni, ci, idx].set(vals)
    return flat_out.reshape((n, c) + out_sp)


def max_unpool2d(x, indices, *, kernel_size, stride=None, padding=0,
                 output_size=None):
    """Inverse of max_pool2d_with_index (ref functional/pooling.py
    max_unpool2d): scatter pooled values back to their argmax slots."""
    return _max_unpool_nd(x, indices, 2, kernel_size, stride, padding,
                          output_size)


def fold(x, *, output_sizes, kernel_sizes, strides=1, paddings=0,
         dilations=1):
    """col2im — the inverse of unfold (ref functional/common.py fold):
    scatter-add each column back to its image patch."""
    def _pair(v):
        if isinstance(v, int):
            return (v, v)
        t = tuple(v)
        return (t[0], t[0]) if len(t) == 1 else t

    oh, ow = _pair(output_sizes)
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    ph, pw = _pair(paddings)
    dh, dw = _pair(dilations)
    n, ckk, l = x.shape
    c = ckk // (kh * kw)
    lh = (oh + 2 * ph - (dh * (kh - 1) + 1)) // sh + 1
    lw = (ow + 2 * pw - (dw * (kw - 1) + 1)) // sw + 1
    cols = x.reshape(n, c, kh, kw, lh, lw)
    out = jnp.zeros((n, c, oh + 2 * ph, ow + 2 * pw), x.dtype)
    for i in range(kh):
        for j in range(kw):
            hi = i * dh
            wi = j * dw
            out = out.at[:, :, hi:hi + lh * sh:sh,
                         wi:wi + lw * sw:sw].add(cols[:, :, i, j])
    if ph or pw:
        out = out[:, :, ph:ph + oh, pw:pw + ow]
    return out


def spectral_norm(weight, *, dim=0, power_iters=1, eps=1e-12):
    """Power-iteration spectral normalization (ref nn/functional
    spectral_norm; the reference keeps u/v as persistent buffers — the
    functional form re-runs the iteration from a fixed start, which is
    deterministic under jit)."""
    w = jnp.moveaxis(weight, dim, 0)
    h = w.shape[0]
    mat = w.reshape(h, -1).astype(jnp.float32)
    u = jnp.ones((h,), jnp.float32) / (h ** 0.5)

    def body(u, _):
        v = mat.T @ u
        v = v / jnp.maximum(jnp.linalg.norm(v), eps)
        u2 = mat @ v
        u2 = u2 / jnp.maximum(jnp.linalg.norm(u2), eps)
        return u2, v

    u, vs = jax.lax.scan(body, u, None, length=max(1, power_iters))
    v = vs[-1]
    sigma = u @ mat @ v
    return (w / sigma).reshape(w.shape).astype(weight.dtype) \
        if dim == 0 else jnp.moveaxis(
            (w / sigma).astype(weight.dtype), 0, dim)


def ctc_loss(log_probs, labels, input_lengths, label_lengths, *,
             blank=0, reduction="mean", norm_by_times=False):
    """CTC loss (ref nn/functional/loss.py ctc_loss over the warpctc op).

    TPU-native form: the alpha (forward-variable) recursion in log space
    as one lax.scan over time — jax.vjp supplies the gradient, replacing
    warpctc's hand-written backward. log_probs [T, B, C] (time-major,
    the reference's layout), labels [B, L] padded, lengths int."""
    T, B, C = log_probs.shape
    L = labels.shape[1]
    S = 2 * L + 1
    neg_inf = -1e30

    lp = jax.nn.log_softmax(log_probs.astype(jnp.float32), axis=-1)
    labels = labels.astype(jnp.int32)
    # extended sequence: blank, l1, blank, l2, ..., blank  [B, S]
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(labels)
    # can alpha skip the previous blank? only between DIFFERENT labels
    prev_lab = jnp.concatenate(
        [jnp.full((B, 2), -1, jnp.int32), ext[:, :-2]], axis=1
    )
    can_skip = (ext != blank) & (ext != prev_lab)

    # state mask: states beyond 2*label_len stay -inf
    smask = jnp.arange(S)[None, :] < (
        2 * label_lengths.astype(jnp.int32) + 1
    )[:, None]

    emit0 = jnp.take_along_axis(lp[0], ext, axis=1)  # [B, S]
    alpha0 = jnp.where(
        (jnp.arange(S)[None, :] < 2) & smask, emit0, neg_inf
    )

    def step(alpha, lp_t):
        stay = alpha
        prev1 = jnp.concatenate(
            [jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1
        )
        prev2 = jnp.concatenate(
            [jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1
        )
        prev2 = jnp.where(can_skip, prev2, neg_inf)
        merged = jnp.logaddexp(jnp.logaddexp(stay, prev1), prev2)
        emit = jnp.take_along_axis(lp_t, ext, axis=1)
        new = jnp.where(smask, merged + emit, neg_inf)
        return new, new

    _, alphas = jax.lax.scan(step, alpha0, lp[1:])
    alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # [T,B,S]

    # read alpha at each sequence's LAST valid frame, summed over the
    # final two states (last label, trailing blank)
    t_idx = jnp.clip(input_lengths.astype(jnp.int32) - 1, 0, T - 1)
    alpha_last = alphas[t_idx, jnp.arange(B)]  # [B, S]
    s_last = 2 * label_lengths.astype(jnp.int32)  # trailing blank state
    a_blank = jnp.take_along_axis(
        alpha_last, s_last[:, None], axis=1
    )[:, 0]
    a_label = jnp.take_along_axis(
        alpha_last, jnp.maximum(s_last - 1, 0)[:, None], axis=1
    )[:, 0]
    a_label = jnp.where(label_lengths > 0, a_label, neg_inf)
    nll = -jnp.logaddexp(a_blank, a_label)
    if norm_by_times:
        nll = nll / jnp.maximum(input_lengths.astype(jnp.float32), 1.0)
    if reduction == "mean":
        # the reference (and torch) divide by label length under mean
        return (nll / jnp.maximum(
            label_lengths.astype(jnp.float32), 1.0)).mean()
    if reduction == "sum":
        return nll.sum()
    return nll


def lp_pool2d(x, *, norm_type=2.0, kernel_size=2, stride=None,
              padding=0, ceil_mode=False, data_format="NCHW"):
    """Power-average pooling (ref functional/pooling.py lp_pool2d):
    (sum |x|^p over window)^(1/p), built on the existing avg pool."""
    if stride is None:
        stride = kernel_size
    ks = (kernel_size, kernel_size) if isinstance(kernel_size, int) \
        else tuple(kernel_size)
    p = float(norm_type)
    powed = jnp.abs(x) ** p
    avg = avg_pool2d(powed, kernel_size=kernel_size, stride=stride,
                     padding=padding, ceil_mode=ceil_mode,
                     data_format=data_format)
    n_win = ks[0] * ks[1]
    return (avg * n_win) ** (1.0 / p)


def fractional_max_pool2d(x, *, output_size, kernel_size=None,
                          random_u=None):
    """Fractional max pooling (ref functional/pooling.py
    fractional_max_pool2d): pseudo-random pooling regions whose sizes
    average H/out_h. Deterministic region boundaries from `random_u`
    (the reference's test-mode contract; None -> u=0.5)."""
    if kernel_size is not None:
        raise NotImplementedError(
            "fractional_max_pool2d overlapping mode (kernel_size) is "
            "not supported; omit kernel_size for disjoint regions"
        )
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    t = tuple(output_size)  # IntArray coercion may yield a 1-elt list
    oh, ow = (t[0], t[0]) if len(t) == 1 else t
    n, c, h, w = x.shape
    u = 0.5 if random_u is None else float(random_u)

    def bounds(inp, out):
        # ref formula: ceil((i + u) * inp / out) - ceil(u * inp / out)
        import math

        alpha = inp / out
        return [int(math.ceil((i + u) * alpha)
                    - math.ceil(u * alpha)) for i in range(out + 1)]

    ys = bounds(h, oh)
    xs = bounds(w, ow)
    rows = []
    for i in range(oh):
        cols = []
        y0, y1 = ys[i], max(ys[i + 1], ys[i] + 1)
        for j in range(ow):
            x0, x1 = xs[j], max(xs[j + 1], xs[j] + 1)
            cols.append(x[:, :, y0:y1, x0:x1].max(axis=(-2, -1)))
        rows.append(jnp.stack(cols, -1))
    return jnp.stack(rows, -2)


def max_unpool3d(x, indices, *, kernel_size, stride=None, padding=0,
                 output_size=None):
    """3-D inverse of max pooling (ref functional/pooling.py
    max_unpool3d) — the 3-D instance of the shared scatter body."""
    return _max_unpool_nd(x, indices, 3, kernel_size, stride, padding,
                          output_size)
