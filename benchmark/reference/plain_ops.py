"""Plain float32 building blocks shared by the reference families.

Straightforward ``jax.numpy`` / ``lax.conv_general_dilated``: no flax, no
bf16, no fusion tricks.  Callers run under
``jax.default_matmul_precision("highest")`` (check.py sets it), because a
float32 convolution on a TPU is otherwise computed in bf16 passes.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5  # flax.linen.BatchNorm's default epsilon


def conv(x, kernel, bias=None):
    """Same-padded stride-1 convolution, NHWC x HWIO."""
    y = lax.conv_general_dilated(
        x, kernel, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    return y if bias is None else y + bias


def batch_norm_train(x, scale, bias):
    """BatchNorm with the batch's own statistics (training mode)."""
    mean = x.mean(axis=(0, 1, 2))
    var = ((x - mean) ** 2).mean(axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BN_EPS) * scale + bias


def conv_bn_relu(x, p):
    """One ConvNormAct: bias-free 3x3 conv, BatchNorm, ReLU."""
    bn = p["Norm_0"]["BatchNorm_0"]
    y = conv(x, p["Conv_0"]["kernel"])
    return jnp.maximum(batch_norm_train(y, bn["scale"], bn["bias"]), 0.0)


def double_conv(x, p):
    return conv_bn_relu(conv_bn_relu(x, p["ConvNormAct_0"]), p["ConvNormAct_1"])


def max_pool_2x2(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def up_conv_2x2(x, p):
    """Transposed 2x2 convolution with stride 2: every input pixel writes its
    own 2x2 output block (kernel = stride, so blocks never overlap).

    The checkpoint stores the kernel as ``flax.linen.ConvTranspose`` uses it,
    correlated with the zero-dilated input: that is the scatter kernel below
    flipped in both spatial axes."""
    n, h, w, _ = x.shape
    k = p["kernel"][::-1, ::-1]  # [2, 2, Cin, Cout], as scattered
    y = jnp.einsum("nhwc,abcd->nhawbd", x, k)
    return y.reshape(n, 2 * h, 2 * w, k.shape[-1]) + p["bias"]


def space_to_depth(x, r):
    n, h, w, c = x.shape
    x = x.reshape(n, h // r, r, w // r, r, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // r, w // r, r * r * c)


def depth_to_space(x, r):
    n, h, w, c = x.shape
    x = x.reshape(n, h, w, r, r, c // (r * r))
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h * r, w * r, c // (r * r))


def cross_entropy(logits, labels):
    """Mean pixel cross-entropy; logits [..., C] may carry leading head axes
    that the labels broadcast over (the mean of the per-head means)."""
    m = logits.max(axis=-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    onehot = labels[..., None] == jnp.arange(logits.shape[-1])
    picked = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    return (lse - picked).mean()
