"""U-Net for semantic segmentation, NHWC / bf16, Flax.

Reference parity: UNet with 5 down blocks (3→64/N→128/N→256/N→512/N→512/N),
a DoubleConv(512/N) bottleneck, 5 up blocks and a final 1×1 conv to
``out_classes`` logits, with ``up_sample_mode`` ∈ {conv_transpose, bilinear}
and global width divisor N = ``NN_in_model`` (кластер.py:620-656,687).

Differences (deliberate, TPU-first): NHWC layout, bf16 compute with fp32
params, pluggable/synced normalization, arbitrary depth via ``features``.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ddlpc_tpu.models.layers import (
    DetailHead,
    DoubleConv,
    DownBlock,
    StemGridDetailHead,
    SubpixelHead,
    UpBlock,
    apply_stem,
    restore_head,
)


class UNet(nn.Module):
    num_classes: int = 6
    features: Tuple[int, ...] = (64, 128, 256, 512, 512)
    bottleneck_features: int = 512
    width_divisor: int = 1
    up_sample_mode: str = "conv_transpose"
    norm: str = "batch"
    norm_axis_name: Optional[str] = None
    norm_groups: int = 8
    stem: str = "none"  # none | s2d (see ModelConfig.stem)
    stem_factor: int = 2
    # Residual refinement after the subpixel head — restores
    # sub-stem_factor-px structure the 1/r pyramid cannot carry.  Kind
    # selects the architecture: 'fullres' = DetailHead (two full-res convs),
    # 's2d' = StemGridDetailHead (same idea computed at the stem grid on
    # MXU-shaped channels) — see ModelConfig.detail_head_kind.
    detail_head: bool = False
    detail_head_kind: str = "fullres"  # fullres | s2d
    detail_head_hidden: int = 16
    # 'grouped': under train=True with an s2d stem, return pre-d2s
    # phase-major logits [B,H/r,W/r,r²·C] instead of full-res — the train
    # step pairs them with group_labels for identical loss math without any
    # full-res tensor (ModelConfig.train_head_layout).  Eval/predict
    # (train=False) always return full-res logits.
    train_head_layout: str = "fullres"  # fullres | grouped
    dtype: Any = jnp.bfloat16
    head_dtype: Any = jnp.float32  # see ModelConfig.head_dtype

    def _w(self, f: int) -> int:
        return max(1, f // self.width_divisor)

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True):
        """x: [N, H, W, C] float, H and W divisible by
        2**len(features) (× ``stem_factor`` with the s2d stem); returns
        logits [N, H, W, num_classes] in ``head_dtype`` (float32 default).

        The explicit ``name=`` kwargs equal the flax auto-names the
        parameter tree has always used, so checkpoints do not depend on
        the order submodules are created in."""
        x = x.astype(self.dtype)
        image = x  # raw full-res input, kept for the optional DetailHead
        # s2d: run the whole pyramid at 1/r resolution on r²-richer
        # channels; logits return to full resolution via a subpixel head.
        x = apply_stem(x, self.stem, self.stem_factor)
        min_px = 2 ** len(self.features)
        if x.shape[1] < min_px or x.shape[2] < min_px:
            # A too-shallow input silently pools to a ZERO-size tensor at
            # the deepest level, and BatchNorm over 0 elements is NaN that
            # the codec's global max-abs then spreads to every gradient —
            # fail loudly instead (found the hard way on a 64² smoke run).
            raise ValueError(
                f"input {image.shape[1:3]} too small for a "
                f"{len(self.features)}-level pyramid behind the "
                f"{self.stem!r} stem (grid {x.shape[1:3]} after the stem; "
                f"the deepest pool needs ≥ {min_px} px) — use a larger "
                f"tile, fewer features, or a smaller stem_factor"
            )
        common = dict(
            norm=self.norm,
            norm_axis_name=self.norm_axis_name,
            norm_groups=self.norm_groups,
            dtype=self.dtype,
        )
        skips = []
        for i, f in enumerate(self.features):
            x, skip = DownBlock(self._w(f), name=f"DownBlock_{i}", **common)(
                x, train
            )
            skips.append(skip)
        x = DoubleConv(
            self._w(self.bottleneck_features), name="DoubleConv_0", **common
        )(x, train)
        for i, f in enumerate(reversed(self.features)):
            x = UpBlock(
                self._w(f),
                up_sample_mode=self.up_sample_mode,
                name=f"UpBlock_{i}",
                **common,
            )(x, skips.pop(), train)
        return self._head(x, image, train)

    def _head(self, x: jax.Array, image: jax.Array, train: bool):
        """The subpixel logit head + optional detail refinement (submodule
        creation from a helper is fine: the compact context of
        ``__call__`` is active)."""
        head = SubpixelHead(
            self.num_classes,
            self.stem,
            self.stem_factor,
            dtype=self.head_dtype,
            name="Conv_0",
        )
        stem_grid_refine = self.detail_head and self.detail_head_kind == "s2d"
        # Phase-major grouped logits: d2s is a pure layout permutation, so
        # the grouped loss path skips it entirely (train_head_layout).
        grouped = (
            train
            and self.train_head_layout == "grouped"
            and self.stem == "s2d"
            and not (self.detail_head and self.detail_head_kind == "fullres")
        )
        if stem_grid_refine or grouped:
            z = head(x, restore=False)
            if stem_grid_refine:
                if self.stem != "s2d":
                    raise ValueError(
                        "detail_head_kind='s2d' refines the pre-d2s logit grid — "
                        "it requires stem='s2d' (with stem='none' there is no "
                        "stem grid; use detail_head_kind='fullres')"
                    )
                z = StemGridDetailHead(
                    self.num_classes,
                    self.stem_factor,
                    hidden=self.detail_head_hidden,
                    dtype=self.dtype,
                    head_dtype=self.head_dtype,
                    name="StemGridDetailHead_0",
                )(z, image)
            if grouped:
                return z
            logits = restore_head(z, self.stem, self.stem_factor)
        else:
            logits = head(x)  # conv and restore in one transposed conv
        if self.detail_head and self.detail_head_kind == "fullres":
            logits = DetailHead(
                self.num_classes,
                hidden=self.detail_head_hidden,
                dtype=self.dtype,
                head_dtype=self.head_dtype,
                name="DetailHead_0",
            )(logits, image)
        return logits
