"""Plain float32 reference of the U-Net family (Ronneberger et al. 2015) as
this repo ships it: optional space-to-depth stem with a subpixel logit head,
optional full-resolution DetailHead.  Follows ``ddlpc_tpu/models/unet.py``'s
published layout (parameter names are the checkpoint's) and nothing else of
the program: no flax, no bf16.

Departures from the paper, all the program's and all kept: same-padded
convolutions, BatchNorm after every 3x3 conv, a 2x2 transposed conv for
up-sampling, widths divided by ``width_divisor``.
"""

from __future__ import annotations

import jax.numpy as jnp

from reference.plain_ops import (
    conv,
    depth_to_space,
    double_conv,
    max_pool_2x2,
    space_to_depth,
    up_conv_2x2,
)

# Relative L2 errors (loss: relative difference) by which a program computing
# in the stated dtype may differ from this float32 reference.
#   logits  the sharp measure.  bf16 keeps 8 significant bits (unit round-off
#           2^-8 = 3.9e-3); through the ~25 conv+BN layers of these nets the
#           logits differ by 0.2..1.7 %: on the chip at 512^2, after three
#           warm-up steps, U-Net 0.0104..0.0167 in 13 runs and U-Net++
#           0.0022..0.0025 in 12 (builder's chip runs, PR 24); CPU tests at
#           tiny size 0.006..0.014.  The limit is 3x the largest reading,
#           far below the 16x coarser result an 8-bit float (3..4
#           significant bits) would give.  float32 agrees to 1e-5; its limit
#           is 1e-4.
#   loss    a mean over 10^5..10^6 pixels, so rounding averages out: bf16 read
#           2.7e-5..2.5e-4 on the chip; the limit is 4x that.
#   grad    all leaves together.  Ill-conditioned where BatchNorm divides by
#           the deviation of a nearly dead channel: two float32
#           implementations already differ by up to 8e-3 there, bf16 by
#           0.03..0.054 on the chip and 0.10..0.16 at the tests' tiny size.
#           It is held loosely and guards the backward pass against gross
#           faults (a missing term reads ~1).
# Configurations that state float32 are held to the float32 limits, which a
# bf16 program fails on its logits a hundredfold.
TOLERANCE = {
    "bfloat16": {"loss": 1e-3, "logits": 0.05, "grad": 0.3},
    "float32": {"loss": 1e-5, "logits": 1e-4, "grad": 0.02},
}


def forward(model: dict, params: dict, images):
    """Training-mode logits [N, H, W, classes] in float32."""
    image = images.astype(jnp.float32)
    stem = model.get("stem", "none")
    r = int(model.get("stem_factor", 2))
    x = space_to_depth(image, r) if stem == "s2d" else image
    depth = len(model["features"])
    skips = []
    for i in range(depth):
        skip = double_conv(x, params[f"DownBlock_{i}"]["DoubleConv_0"])
        skips.append(skip)
        x = max_pool_2x2(skip)
    x = double_conv(x, params["DoubleConv_0"])
    for i in range(depth):
        p = params[f"UpBlock_{i}"]
        x = up_conv_2x2(x, p["ConvTranspose_0"])
        x = double_conv(jnp.concatenate([skips.pop(), x], axis=-1), p["DoubleConv_0"])
    z = conv(x, params["Conv_0"]["kernel"], params["Conv_0"]["bias"])
    logits = depth_to_space(z, r) if stem == "s2d" else z
    if model.get("detail_head", False):
        if model.get("detail_head_kind", "fullres") != "fullres":
            raise NotImplementedError("reference covers the full-resolution DetailHead only")
        p = params["DetailHead_0"]
        h = jnp.concatenate([logits, image], axis=-1)
        h = jnp.maximum(conv(h, p["Conv_0"]["kernel"], p["Conv_0"]["bias"]), 0.0)
        logits = logits + conv(h, p["Conv_1"]["kernel"], p["Conv_1"]["bias"])
    return logits
