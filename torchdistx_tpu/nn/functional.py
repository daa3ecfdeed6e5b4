"""Functional ops for module forwards.  Plain ``jax.numpy`` / ``jax.lax`` —
forwards run on real arrays (eagerly or under jit); only construction-time
ops go through the interposition layer."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "relu",
    "gelu",
    "silu",
    "softmax",
    "log_softmax",
    "dropout",
    "layer_norm",
    "rms_norm",
    "embedding",
    "linear",
    "conv2d",
    "max_pool2d",
    "avg_pool2d",
    "cross_entropy",
]


def relu(x):
    return jnp.maximum(x, 0)


def gelu(x, approximate: bool = True):
    return jax.nn.gelu(x, approximate=approximate)


def silu(x):
    return jax.nn.silu(x)


def softmax(x, axis=-1):
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


def dropout(x, rate: float, key: Optional[jax.Array] = None, training: bool = True):
    if not training or rate <= 0.0 or key is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


def layer_norm(x, weight=None, bias=None, eps: float = 1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight
    if bias is not None:
        y = y + bias
    return y


def rms_norm(x, weight=None, eps: float = 1e-6):
    # compute the statistic in f32 for bf16 inputs (standard practice on TPU)
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = y.astype(dt)
    if weight is not None:
        y = y * weight
    return y


def embedding(ids, table):
    return jnp.take(table, ids, axis=0)


def linear(x, weight, bias=None):
    # weight convention: (out_features, in_features), matching the reference
    # ecosystem's torch.nn.Linear
    y = x @ weight.T
    if bias is not None:
        y = y + bias
    return y


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1):
    """NCHW conv with OIHW weights (torch layout, mapped onto XLA's
    conv_general_dilated which tiles onto the MXU)."""
    if isinstance(stride, int):
        stride = (stride, stride)
    if isinstance(dilation, int):
        dilation = (dilation, dilation)
    if isinstance(padding, int):
        padding = ((padding, padding), (padding, padding))
    elif isinstance(padding, tuple) and len(padding) == 2 and isinstance(padding[0], int):
        padding = ((padding[0], padding[0]), (padding[1], padding[1]))
    y = jax.lax.conv_general_dilated(
        x,
        weight,
        window_strides=stride,
        padding=padding,
        rhs_dilation=dilation,
        feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    if bias is not None:
        y = y + bias.reshape(1, -1, 1, 1)
    return y


def _pool2d(x, window, stride, padding, init, op):
    if isinstance(window, int):
        window = (window, window)
    if stride is None:
        stride = window
    if isinstance(stride, int):
        stride = (stride, stride)
    if isinstance(padding, int):
        padding = ((0, 0), (0, 0), (padding, padding), (padding, padding))
    return jax.lax.reduce_window(
        x,
        init,
        op,
        window_dimensions=(1, 1) + window,
        window_strides=(1, 1) + stride,
        padding=padding,
    )


def max_pool2d(x, window, stride=None, padding=0):
    return _pool2d(x, window, stride, padding, -jnp.inf, jax.lax.max)


def avg_pool2d(x, window, stride=None, padding=0):
    if isinstance(window, int):
        window = (window, window)
    summed = _pool2d(x, window, stride, padding, 0.0, jax.lax.add)
    return summed / (window[0] * window[1])


def cross_entropy(logits, labels, axis=-1):
    """Mean token cross-entropy; logits (..., vocab), integer labels."""
    with jax.named_scope("loss"):  # metadata only: names the fusions
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=axis)[..., 0]
        return jnp.mean(nll)
