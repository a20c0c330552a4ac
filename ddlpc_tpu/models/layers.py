"""Shared Flax building blocks for the segmentation model zoo.

TPU-first conventions used throughout the zoo:
- NHWC activations (TPU conv layout; the reference is NCHW torch, кластер.py:737).
- bfloat16 compute / float32 params, selected per-module via ``dtype``.
- Normalization is pluggable: 'batch' (optionally cross-replica synced via
  ``axis_name`` — fixing the reference's silently drifting per-replica BN
  running stats, SURVEY §3.1), 'group', or 'none'.

Reference parity: DoubleConv = (Conv3×3 → BatchNorm2d → ReLU) ×2
(кластер.py:575-588); DownBlock = DoubleConv + MaxPool2d(2) returning
(down, skip) (кластер.py:591-600); UpBlock = ConvTranspose2d(k=2,s=2) or
bilinear upsample, concat skip, DoubleConv (кластер.py:603-617).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

Dtype = Any


class Norm(nn.Module):
    """Pluggable normalization layer.

    kind='batch' uses running-average BatchNorm; when ``axis_name`` is set and
    the module runs inside a mapped axis (shard_map/pmap), batch statistics
    are averaged across that axis — true sync-BN, unlike the reference which
    never re-syncs running stats after the init broadcast (кластер.py:560-565).
    """

    kind: str = "batch"
    axis_name: Optional[str] = None
    groups: int = 8
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, train: bool) -> jax.Array:
        if self.kind == "batch":
            return nn.BatchNorm(
                use_running_average=not train,
                axis_name=self.axis_name if train else None,
                momentum=0.9,
                dtype=self.dtype,
                param_dtype=jnp.float32,
            )(x)
        if self.kind == "group":
            groups = min(self.groups, x.shape[-1])
            while x.shape[-1] % groups:
                groups -= 1
            return nn.GroupNorm(
                num_groups=groups, dtype=self.dtype, param_dtype=jnp.float32
            )(x)
        if self.kind == "none":
            return x
        raise ValueError(f"unknown norm kind {self.kind!r}")


class ConvNormAct(nn.Module):
    """3×3 same-padding conv → norm → ReLU (one half of reference DoubleConv)."""

    features: int
    kernel_size: Tuple[int, int] = (3, 3)
    strides: Tuple[int, int] = (1, 1)
    dilation: int = 1
    norm: str = "batch"
    norm_axis_name: Optional[str] = None
    norm_groups: int = 8
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True) -> jax.Array:
        x = nn.Conv(
            self.features,
            self.kernel_size,
            strides=self.strides,
            padding="SAME",
            kernel_dilation=(self.dilation, self.dilation),
            use_bias=self.norm == "none",
            dtype=self.dtype,
            param_dtype=jnp.float32,
        )(x)
        x = Norm(
            kind=self.norm,
            axis_name=self.norm_axis_name,
            groups=self.norm_groups,
            dtype=self.dtype,
        )(x, train)
        return nn.relu(x)


class DoubleConv(nn.Module):
    """(Conv3×3 → norm → ReLU) ×2 — reference DoubleConv (кластер.py:575-588)."""

    features: int
    norm: str = "batch"
    norm_axis_name: Optional[str] = None
    norm_groups: int = 8
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True) -> jax.Array:
        for _ in range(2):
            x = ConvNormAct(
                self.features,
                norm=self.norm,
                norm_axis_name=self.norm_axis_name,
                norm_groups=self.norm_groups,
                dtype=self.dtype,
            )(x, train)
        return x


def max_pool_2x2(x: jax.Array) -> jax.Array:
    """2×2/stride-2 max pool over NHWC (reference MaxPool2d(2), кластер.py:596)."""
    return nn.max_pool(x, window_shape=(2, 2), strides=(2, 2))


class DownBlock(nn.Module):
    """DoubleConv then 2× downsample; returns (downsampled, skip)
    (reference DownBlock, кластер.py:591-600)."""

    features: int
    norm: str = "batch"
    norm_axis_name: Optional[str] = None
    norm_groups: int = 8
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True):
        skip = DoubleConv(
            self.features,
            norm=self.norm,
            norm_axis_name=self.norm_axis_name,
            norm_groups=self.norm_groups,
            dtype=self.dtype,
        )(x, train)
        return max_pool_2x2(skip), skip


def space_to_depth(x: jax.Array, r: int) -> jax.Array:
    """[B, H, W, C] → [B, H/r, W/r, C·r²] — trades spatial for channel extent.

    TPU-first stem transform: the MXU wants large channel counts, but a
    segmentation net's first levels run few channels at high resolution,
    where the (8, 128) register tiling pads C=3/C=32 up to full lanes and
    wastes most of the bandwidth and systolic array (measured: the s2d stem
    is ~2.6× faster end-to-end for the flagship U-Net at 512²).
    """
    b, h, w, c = x.shape
    if h % r or w % r:
        raise ValueError(f"spatial dims {(h, w)} not divisible by r={r}")
    x = x.reshape(b, h // r, r, w // r, r, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // r, w // r, c * r * r)


def depth_to_space(x: jax.Array, r: int) -> jax.Array:
    """Inverse of :func:`space_to_depth` — the subpixel upsampling head."""
    b, h, w, c = x.shape
    if c % (r * r):
        raise ValueError(f"channels {c} not divisible by r²={r * r}")
    x = x.reshape(b, h, w, r, r, c // (r * r))
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h * r, w * r, c // (r * r))


def apply_stem(x: jax.Array, stem: str, factor: int) -> jax.Array:
    """Shared input-stem dispatch for the zoo: 'none' passes through, 's2d'
    space-to-depths by ``factor``.  One implementation so U-Net and U-Net++
    cannot diverge on validation or semantics."""
    if stem == "s2d":
        return space_to_depth(x, factor)
    if stem == "none":
        return x
    raise ValueError(f"unknown stem {stem!r}")


def head_channels(num_classes: int, stem: str, factor: int) -> int:
    """Logit-head channel count: ×factor² for subpixel heads under s2d."""
    return num_classes * factor * factor if stem == "s2d" else num_classes


def restore_head(logits: jax.Array, stem: str, factor: int) -> jax.Array:
    """Inverse of the stem on the logit grid (subpixel upsampling)."""
    return depth_to_space(logits, factor) if stem == "s2d" else logits


def _phase_conv(full: jax.Array, k4: jax.Array) -> jax.Array:
    """[B, H, W, C] → [B, H/r, W/r, F]: each r×r block of pixels against
    ``k4`` [r, r, F, C].  The transpose of :func:`subpixel_conv`."""
    r = k4.shape[0]
    return jax.lax.conv_general_dilated(
        full, k4, (r, r), "VALID", dimension_numbers=("NHWC", "HWOI", "NHWC")
    )


@jax.custom_vjp
def subpixel_conv(x: jax.Array, k4: jax.Array) -> jax.Array:
    """``depth_to_space(conv1x1(x))`` as ONE transposed conv: x [B, h, w, F]
    against k4 [r, r, F, C] (the 1×1 kernel [F, r²·C] with its phases
    unpacked) → [B, h·r, w·r, C].  XLA writes the full-resolution tensor
    straight from the conv in the layout its consumers want; the separate
    depth_to_space costs the flagship four layout copies a micro-batch."""
    r = k4.shape[0]
    return jax.lax.conv_general_dilated(
        x,
        k4[::-1, ::-1],  # a correlation over the r-dilated input: phase p meets tap r-1-p
        (1, 1),
        ((r - 1, r - 1), (r - 1, r - 1)),
        lhs_dilation=(r, r),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _subpixel_conv_fwd(x, k4):
    return subpixel_conv(x, k4), (x, k4)


def _subpixel_conv_bwd(res, g):
    # Both gradients from the strided conv that this op transposes: autodiff
    # of the dilated conv itself makes XLA reverse the full-resolution
    # cotangent before the weight gradient (6.5 ms a flagship step).
    x, k4 = res
    dk4 = jax.linear_transpose(lambda k: _phase_conv(g, k), k4)(x)[0]
    return _phase_conv(g, k4), dk4


subpixel_conv.defvjp(_subpixel_conv_fwd, _subpixel_conv_bwd)


class SubpixelHead(nn.Module):
    """The zoo's logit head: a 1×1 conv to ``head_channels`` at the stem
    grid and, under an s2d stem, the subpixel restore to full resolution.
    Parameters are ``nn.Conv``'s (``kernel`` [1, 1, F, r²·C], ``bias``
    [r²·C], float32).  ``restore=False`` stops at the stem grid (the grouped
    train layout and the stem-grid refinement read it); otherwise conv and
    restore are one transposed conv (:func:`subpixel_conv`)."""

    num_classes: int
    stem: str = "none"
    factor: int = 1
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, restore: bool = True) -> jax.Array:
        r = self.factor if self.stem == "s2d" else 1
        f, c = x.shape[-1], self.num_classes
        channels = head_channels(c, self.stem, self.factor)
        kernel = self.param(
            "kernel", nn.linear.default_kernel_init, (1, 1, f, channels), jnp.float32
        )
        bias = self.param("bias", nn.initializers.zeros_init(), (channels,), jnp.float32)
        x, kernel, bias = (a.astype(self.dtype) for a in (x, kernel, bias))
        if r == 1 or not restore:
            return (
                jax.lax.conv_general_dilated(
                    x, kernel, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC")
                )
                + bias
            )
        b, h, w, _ = x.shape
        y = subpixel_conv(x, kernel.reshape(f, r, r, c).transpose(1, 2, 0, 3))
        y = y.reshape(b, h, r, w, r, c) + bias.reshape(r, 1, r, c)
        return y.reshape(b, h * r, w * r, c)


class DetailHead(nn.Module):
    """Full-resolution residual refinement for subpixel (s2d) heads.

    The subpixel head reconstructs full-res logits from 1/r-resolution
    features; structure finer than r px is measurably degraded — on the
    HardTiles stem A/B the 2-6 px disc class collapses to IoU 0.03 under
    s2d (docs/QUANTIZATION.md hard-task table) because the pyramid never
    sees the raw pixels at full resolution.  This head concatenates the RAW
    input image with the full-resolution logits and applies two
    full-resolution convs as a residual correction:

        logits += Conv3x3(classes) . relu . Conv3x3(hidden) (logits ++ image)

    It is the largest named region of the flagship step: 102 of 328 ms,
    25.5 of 82 ms at the pod point (``detail_head_device_ms``, ledger PR 25)
    for 14 % of the step's conv FLOPs.  The cost is HBM traffic, not
    arithmetic: at micro-batch 128 XLA puts the batch in the lanes and the
    channels in sublanes, each of the head's seven full-resolution tensors
    is 0.5-1.1 GB, four of its six conv fusions run at 80-86 % of the HBM
    roofline and the two weight gradients at 41 % and 60 %.  Width-folded
    operands ([B,H,W/r,r·C], banded or halo kernels, r 2-16) make the weight
    gradients 2.4x faster and everything round them slower, because the 6-
    and 9-channel tensors are sublane-padded and do not fold by a bitcast
    (PERF.md §6, PR 26): the plain form stays.  No normalization: at C=16 a
    BatchNorm's scalar DMA chatter would cost more than the conv.
    """

    num_classes: int
    hidden: int = 16
    dtype: Dtype = jnp.bfloat16
    head_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, logits: jax.Array, image: jax.Array) -> jax.Array:
        z = jnp.concatenate(
            [logits.astype(self.dtype), image.astype(self.dtype)], axis=-1
        )
        z = nn.relu(
            nn.Conv(self.hidden, (3, 3), dtype=self.dtype, param_dtype=jnp.float32)(z)
        )
        delta = nn.Conv(
            self.num_classes, (3, 3), dtype=self.head_dtype, param_dtype=jnp.float32
        )(z.astype(self.head_dtype))
        return logits + delta


class StemGridDetailHead(nn.Module):
    """Residual refinement computed AT THE STEM GRID (detail_head_kind='s2d').

    The full-resolution DetailHead above buys its quality with the worst-
    shaped convs in the net: C=9→16 at 512² runs lane-padded at 9-37 TF/s
    and its weight gradients contract over [B, H·W] — measured ~43% of the
    round-3 flagship step (docs/PERF.md roofline).  This variant computes
    the SAME residual-correction idea without ever leaving the stem grid:

        z += Conv3x3(C·r²) . relu . Conv3x3(hidden) (z ++ s2d(image))

    where z is the pre-depth_to_space logit tensor [B, H/r, W/r, C·r²] and
    s2d(image) packs every raw pixel losslessly into 3·r² channels — the
    head sees exactly the information the full-res head sees.  What changes
    is the equivariance group: weights are shared across stem CELLS, not
    pixels, so each of the r² subpixel phases gets its own filters (more
    parameters per FLOP, cell-level instead of pixel-level translation
    equivariance).  A 3×3 conv here spans 3r×3r raw pixels of context vs
    the full-res head's 3×3.  Every conv lands in the MXU-efficient
    channel regime (C≥96 for the flagship's r=4).

    Quality is an empirical question per task — measured on the HardTiles
    sweep (docs/HARD_TASK.md round-4 table) rather than assumed.
    """

    num_classes: int
    stem_factor: int
    hidden: int = 64
    dtype: Dtype = jnp.bfloat16
    head_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, z: jax.Array, image: jax.Array) -> jax.Array:
        r = self.stem_factor
        zin = jnp.concatenate(
            [z.astype(self.dtype), space_to_depth(image.astype(self.dtype), r)],
            axis=-1,
        )
        y = nn.relu(
            nn.Conv(self.hidden, (3, 3), dtype=self.dtype, param_dtype=jnp.float32)(zin)
        )
        delta = nn.Conv(
            self.num_classes * r * r,
            (3, 3),
            dtype=self.head_dtype,
            param_dtype=jnp.float32,
        )(y.astype(self.head_dtype))
        return z + delta


def group_labels(labels: jax.Array, r: int) -> jax.Array:
    """[..., H, W] int labels → [..., H/r, W/r, r²], phase-major — the label
    grouping that matches the channel order of pre-depth_to_space logits
    [..., H/r, W/r, r²·C] (reshape to [..., r², C] pairs phase p's class row
    with this function's phase-p label).  With it, the train path can run
    losses/metrics on the grouped view — identical math to full resolution,
    same multiset of (logit row, label) pairs — without the d2s transpose or
    any full-res tensor (ModelConfig.train_head_layout='grouped')."""
    *lead, h, w = labels.shape
    if h % r or w % r:
        raise ValueError(f"spatial dims {(h, w)} not divisible by r={r}")
    x = labels.reshape(*lead, h // r, r, w // r, r)
    x = jnp.moveaxis(x, -3, -2)  # [..., h/r, w/r, r, r]
    return x.reshape(*lead, h // r, w // r, r * r)


def upsample_2x(x: jax.Array, method: str = "bilinear") -> jax.Array:
    """2× spatial upsample of NHWC via jax.image.resize."""
    n, h, w, c = x.shape
    return jax.image.resize(x, (n, 2 * h, 2 * w, c), method=method).astype(x.dtype)


class UpBlock(nn.Module):
    """2× upsample (transposed conv or bilinear), concat skip(s), DoubleConv
    (reference UpBlock, кластер.py:603-617)."""

    features: int
    up_sample_mode: str = "conv_transpose"
    norm: str = "batch"
    norm_axis_name: Optional[str] = None
    norm_groups: int = 8
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, skips, train: bool = True) -> jax.Array:
        if self.up_sample_mode == "conv_transpose":
            x = nn.ConvTranspose(
                self.features,
                kernel_size=(2, 2),
                strides=(2, 2),
                dtype=self.dtype,
                param_dtype=jnp.float32,
                name="ConvTranspose_0",
            )(x)
        elif self.up_sample_mode == "bilinear":
            x = upsample_2x(x, "bilinear")
        else:
            raise ValueError(f"unknown up_sample_mode {self.up_sample_mode!r}")
        if not isinstance(skips, (list, tuple)):
            skips = (skips,)
        x = jnp.concatenate([*skips, x], axis=-1)
        return DoubleConv(
            self.features,
            norm=self.norm,
            norm_axis_name=self.norm_axis_name,
            norm_groups=self.norm_groups,
            dtype=self.dtype,
            name="DoubleConv_0",
        )(x, train)
