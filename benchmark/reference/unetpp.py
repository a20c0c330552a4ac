"""Plain float32 reference of U-Net++ (Zhou et al. 2018, "UNet++: A Nested
U-Net Architecture for Medical Image Segmentation") with deep supervision.

Node X[i][j] = DoubleConv(concat(X[i][0..j-1], Up(X[i+1][j-1]))); every
X[0][j], j >= 1, has a 1x1 logit head; training returns the heads stacked
[J, N, H, W, classes], and the loss is the mean of the per-head
cross-entropies (the paper's formulation).  Parameter names are the
checkpoint's (``ddlpc_tpu/models/unetpp.py``); nothing else of the program
is used.  Covers the paper layout (no stem, no detail head).
"""

from __future__ import annotations

import jax.numpy as jnp

from reference.plain_ops import conv, double_conv, max_pool_2x2, up_conv_2x2

# Same limits and reasons as reference/unet.py (read them there): the longest
# path through the nested grid has the same depth of conv+BN layers.
TOLERANCE = {
    "bfloat16": {"loss": 1e-3, "logits": 0.05, "grad": 0.3},
    "float32": {"loss": 1e-5, "logits": 1e-4, "grad": 0.02},
}


def forward(model: dict, params: dict, images):
    if model.get("stem", "none") != "none" or model.get("detail_head", False):
        raise NotImplementedError("reference covers the paper layout only")
    x = images.astype(jnp.float32)
    depth = len(model["features"])
    grid = {}
    for i in range(depth):
        grid[i, 0] = double_conv(x, params[f"x{i}_0"])
        x = max_pool_2x2(grid[i, 0])
    for j in range(1, depth):
        for i in range(depth - j):
            p = params[f"x{i}_{j}"]
            up = up_conv_2x2(grid[i + 1, j - 1], p["ConvTranspose_0"])
            cat = jnp.concatenate([grid[i, k] for k in range(j)] + [up], axis=-1)
            grid[i, j] = double_conv(cat, p["DoubleConv_0"])
    if not model.get("deep_supervision", True):
        h = params["head"]
        return conv(grid[0, depth - 1], h["kernel"], h["bias"])
    return jnp.stack(
        [
            conv(grid[0, j], params[f"head_{j}"]["kernel"], params[f"head_{j}"]["bias"])
            for j in range(1, depth)
        ]
    )
