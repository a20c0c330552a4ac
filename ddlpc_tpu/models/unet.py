"""U-Net for semantic segmentation, NHWC / bf16, Flax.

Reference parity: UNet with 5 down blocks (3→64/N→128/N→256/N→512/N→512/N),
a DoubleConv(512/N) bottleneck, 5 up blocks and a final 1×1 conv to
``out_classes`` logits, with ``up_sample_mode`` ∈ {conv_transpose, bilinear}
and global width divisor N = ``NN_in_model`` (кластер.py:620-656,687).

Differences (deliberate, TPU-first): NHWC layout, bf16 compute with fp32
params, pluggable/synced normalization, arbitrary depth via ``features``.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

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

    # -- pipeline staging (parallel/pipeline.py, docs/SHARDING.md) --------
    # The encoder–decoder as an ordered list of cut-point blocks.  Names
    # equal the flax auto-names the parameter tree has always used (the
    # explicit ``name=`` kwargs below pin them call-order-independent), so
    # stage rule tables over param paths and the execution slice agree by
    # construction and checkpoints are unaffected.

    def pipeline_block_names(self) -> Tuple[str, ...]:
        k = len(self.features)
        names = [f"DownBlock_{i}" for i in range(k)] + ["DoubleConv_0"]
        for i in range(k):
            # Each UpBlock splits into two cut points — the decoder's
            # DoubleConvs are the heaviest modules in the tree, and a
            # balanced 2-stage cut needs to land between upsample+concat
            # and the convs (UpBlock ``phase``, models/layers.py).
            names += [f"UpBlock_{i}:up", f"UpBlock_{i}:conv"]
        return tuple(names + ["head"])

    def pipeline_block_modules(self) -> dict:
        """Block name → the param-tree module paths ("/"-joined) it owns
        (the stage rule table covers params by these)."""
        out: dict = {}
        for b in self.pipeline_block_names():
            if b == "head":
                head = ["Conv_0"]
                if self.detail_head and self.detail_head_kind == "s2d":
                    head.append("StemGridDetailHead_0")
                if self.detail_head and self.detail_head_kind == "fullres":
                    head.append("DetailHead_0")
                out[b] = tuple(head)
            elif b.endswith(":up"):
                out[b] = (b[: -len(":up")] + "/ConvTranspose_0",)
            elif b.endswith(":conv"):
                out[b] = (b[: -len(":conv")] + "/DoubleConv_0",)
            else:
                out[b] = (b,)
        return out

    def carry_has_image(self) -> bool:
        """Whether the inter-stage carry must ship the raw full-res input
        forward (only the detail heads consume it at the tail)."""
        return bool(self.detail_head)

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        train: bool = True,
        blocks: Optional[Sequence[str]] = None,
        carry: Optional[dict] = None,
    ):
        """x: [N, H, W, C] float, H and W divisible by
        2**len(features) (× ``stem_factor`` with the s2d stem); returns
        logits [N, H, W, num_classes] in ``head_dtype`` (float32 default).

        Staged execution (``parallel/pipeline.py``): ``blocks`` names a
        contiguous slice of :meth:`pipeline_block_names` to run.  The first
        stage (``carry=None``) consumes the raw image; later stages resume
        from the ``carry`` dict the previous stage returned (``x`` is
        ignored then).  A slice that does not end in ``'head'`` returns the
        carry ``{'x', 'skips'[, 'image']}`` instead of logits — every leaf
        stays in ``self.dtype``, so no dtype widening crosses a stage
        boundary (the program auditor's per-stage contract pins this).
        ``blocks=None`` (default) runs everything — byte- and
        program-identical to the unstaged revisions."""
        names = self.pipeline_block_names()
        if blocks is None:
            blocks = names
        else:
            blocks = tuple(blocks)
            lo = names.index(blocks[0])
            if blocks != names[lo : lo + len(blocks)]:
                raise ValueError(
                    f"blocks {blocks} is not a contiguous slice of the "
                    f"pipeline block order {names}"
                )
            if (carry is None) != (lo == 0):
                raise ValueError(
                    "the first stage (and only it) starts from the raw "
                    "image: pass carry=None exactly when blocks starts at "
                    f"{names[0]!r}"
                )
        if carry is None:
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
            skips = []
        else:
            x = carry["x"]
            skips = list(carry["skips"])
            image = carry.get("image")
        common = dict(
            norm=self.norm,
            norm_axis_name=self.norm_axis_name,
            norm_groups=self.norm_groups,
            dtype=self.dtype,
        )
        k = len(self.features)
        i = 0
        while i < len(blocks):
            b = blocks[i]
            if b.startswith("DownBlock_"):
                f = self.features[int(b.rsplit("_", 1)[1])]
                x, skip = DownBlock(self._w(f), name=b, **common)(x, train)
                skips.append(skip)
            elif b == "DoubleConv_0":
                x = DoubleConv(
                    self._w(self.bottleneck_features), name=b, **common
                )(x, train)
            elif b.startswith("UpBlock_"):
                base, phase = b.split(":")
                f = self.features[k - 1 - int(base.rsplit("_", 1)[1])]
                up = UpBlock(
                    self._w(f),
                    up_sample_mode=self.up_sample_mode,
                    name=base,
                    **common,
                )
                if phase == "up" and i + 1 < len(blocks):
                    # Both halves in this slice: one call (the unstaged
                    # program, byte-identical to pre-phase revisions).
                    x = up(x, skips.pop(), train)
                    i += 2
                    continue
                if phase == "up":
                    x = up(x, skips.pop(), train, phase="up")
                else:  # the cut landed inside this UpBlock
                    x = up(x, (), train, phase="conv")
            elif b == "head":
                return self._head(x, image, train)
            else:  # pragma: no cover - guarded by the slice check above
                raise ValueError(f"unknown pipeline block {b!r}")
            i += 1
        out = {"x": x, "skips": tuple(skips)}
        if self.carry_has_image():
            out["image"] = image
        return out

    def _head(self, x: jax.Array, image: Optional[jax.Array], train: bool):
        """The subpixel logit head + optional detail refinement — the atomic
        last pipeline block (submodule creation from a helper is fine: the
        compact context of ``__call__`` is active)."""
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
