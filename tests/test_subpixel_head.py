"""The subpixel logit head as one transposed conv (models/layers.py:
SubpixelHead, subpixel_conv) is the parent's ``nn.Conv`` 1×1 followed by
``depth_to_space``: same two leaves, same sums.  Held here against that
plain form, and counted (not timed) in the flagship's and the pod point's
jaxpr: the train program has no depth_to_space left."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from ddlpc_tpu.config import ExperimentConfig, ModelConfig  # noqa: E402
from ddlpc_tpu.models import build_model, unet  # noqa: E402
from ddlpc_tpu.models.layers import (  # noqa: E402
    SubpixelHead,
    depth_to_space,
    head_channels,
    restore_head,
)
from ddlpc_tpu.obs.flops import iter_eqns  # noqa: E402
from ddlpc_tpu.ops.losses import softmax_cross_entropy  # noqa: E402
from ddlpc_tpu.train import checkpoint as ckpt  # noqa: E402
from reference.unet import TOLERANCE  # noqa: E402  (benchmark/reference)


def plain_head(params, x, stem, r, restore=True):
    """Float32 1×1 conv on the stem grid, then the separate depth_to_space."""
    p = params["params"]
    z = jax.lax.conv_general_dilated(
        x, p["kernel"], (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    ) + p["bias"]
    return depth_to_space(z, r) if stem == "s2d" and restore else z


class ParentHead:
    """The parent commit's head, in ``SubpixelHead``'s place in ``UNet._head``:
    ``nn.Conv`` under the same name, then ``restore_head``."""

    def __init__(self, num_classes, stem, factor, dtype, name):
        self.stem, self.factor, self.dtype = stem, factor, dtype
        self.conv = nn.Conv(
            head_channels(num_classes, stem, factor), (1, 1),
            dtype=dtype, param_dtype=jnp.float32, name=name,
        )

    def __call__(self, x, restore=True):
        z = self.conv(x.astype(self.dtype))
        return restore_head(z, self.stem, self.factor) if restore else z


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel()) / (jnp.linalg.norm(b.ravel()) + 1e-30))


def _setup(stem, r, features, classes, dtype=jnp.float32):
    x = jax.random.normal(jax.random.key(0), (2, 6, 5, features), dtype)
    head = SubpixelHead(classes, stem, r, dtype)
    params = head.init(jax.random.key(1), x)
    keys = iter(jax.random.split(jax.random.key(2), 2))
    # The bias starts at zero: move both leaves so each gradient is exercised.
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype), params
    )
    return head, params, x


# stem, factor, features, classes: the flagship's head, the factor-2 zoo rows,
# an odd factor, the 19-class head, and the stem-less reference-parity nets.
HEADS = [
    ("s2d", 4, 32, 6), ("s2d", 2, 8, 6), ("s2d", 3, 5, 4), ("s2d", 4, 16, 19),
    ("s2d", 1, 8, 6), ("none", 2, 8, 6), ("none", 4, 32, 6),
]


@pytest.mark.parametrize("stem,r,features,classes", HEADS)
def test_subpixel_head_equals_conv_then_depth_to_space_float32(stem, r, features, classes):
    head, params, x = _setup(stem, r, features, classes)
    weights = jax.random.normal(jax.random.key(3), plain_head(params, x, stem, r).shape)

    @jax.jit
    def both(params, x):
        fs = (head.apply, lambda p, x_: plain_head(p, x_, stem, r))
        return [
            (f(params, x), jax.grad(lambda p, x_: jnp.sum(f(p, x_) * weights), (0, 1))(params, x))
            for f in fs
        ]

    (out, got), (want_out, want) = both(params, x)
    scale = r if stem == "s2d" else 1
    assert out.shape == (2, 6 * scale, 5 * scale, classes) == want_out.shape
    assert _rel(out, want_out) < 1e-5
    flat = jax.tree_util.tree_leaves_with_path(got)
    assert len(flat) == 3  # bias, kernel, the incoming features
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype, path
        assert _rel(g, w) < 1e-5, (jax.tree_util.keystr(path), _rel(g, w))


@pytest.mark.parametrize("stem,r,features,classes", HEADS)
def test_restore_false_stops_at_the_stem_grid(stem, r, features, classes):
    head, params, x = _setup(stem, r, features, classes)
    z = head.apply(params, x, restore=False)
    assert z.shape == (2, 6, 5, head_channels(classes, stem, r))
    assert _rel(z, plain_head(params, x, stem, r, restore=False)) < 1e-5
    np.testing.assert_allclose(
        restore_head(z, stem, r), head.apply(params, x), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("stem,r,features,classes", HEADS[:4])
def test_subpixel_head_bf16_inside_the_reference_tolerance(stem, r, features, classes):
    tol = TOLERANCE["bfloat16"]
    head, params, x = _setup(stem, r, features, classes, jnp.bfloat16)
    x32 = x.astype(jnp.float32)
    labels = jax.random.randint(jax.random.key(5), (2, 6 * r, 5 * r), 0, classes)

    def loss(f, x_):
        def run(p):
            out = f(p, x_).astype(jnp.float32)
            return softmax_cross_entropy(out, labels, ignore_index=-1), out
        return jax.jit(jax.value_and_grad(run, has_aux=True))

    (l_got, out), g_got = loss(head.apply, x)(params)
    (l_want, want), g_want = loss(lambda p, x_: plain_head(p, x_, stem, r), x32)(params)
    assert _rel(out, want) < tol["logits"]
    assert abs(float(l_got) - float(l_want)) / abs(float(l_want)) < 10 * tol["loss"]  # 10^3 px, not 10^6
    flat = lambda g: jnp.concatenate([a.ravel() for a in jax.tree.leaves(g)])
    assert _rel(flat(g_got), flat(g_want)) < tol["grad"]


# ---- the whole U-Net against the parent's head: tree, checkpoint, results ----

NETS = {
    "flagship": dict(stem="s2d", stem_factor=4, detail_head=True),
    "s2d2": dict(stem="s2d", stem_factor=2),
    "stem_none": dict(),
    "grouped": dict(stem="s2d", stem_factor=2, train_head_layout="grouped"),
    "stem_grid_refine": dict(stem="s2d", stem_factor=2, detail_head=True, detail_head_kind="s2d"),
}


@pytest.mark.parametrize("net", sorted(NETS))
def test_unet_keeps_the_parents_tree_checkpoint_and_results(net, monkeypatch, tmp_path):
    cfg = ModelConfig(
        features=(4, 8), bottleneck_features=8, num_classes=6,
        compute_dtype="float32", head_dtype="float32", **NETS[net],
    )
    x = jax.random.uniform(jax.random.key(0), (2, 16, 16, 3))
    new = build_model(cfg)
    v_new = new.init(jax.random.key(1), x, train=False)
    monkeypatch.setattr(unet, "SubpixelHead", lambda *a, dtype, name: ParentHead(*a, dtype, name))
    old = build_model(cfg)
    v_old = old.init(jax.random.key(1), x, train=False)
    paths = lambda t: [
        (jax.tree_util.keystr(k), v.shape, str(v.dtype))
        for k, v in jax.tree_util.tree_leaves_with_path(t)
    ]
    assert paths(v_old) == paths(v_new)
    for a, b in zip(jax.tree.leaves(v_old), jax.tree.leaves(v_new)):
        np.testing.assert_array_equal(a, b)
    keys = iter(jax.random.split(jax.random.key(2), 200))
    trained = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(next(keys), a.shape, a.dtype), v_old
    )
    ckpt.save_checkpoint(str(tmp_path), trained, step=1)
    restored, _ = ckpt.restore_checkpoint(str(tmp_path), jax.tree.map(jnp.zeros_like, v_new))
    assert paths(restored) == paths(trained)

    def run(model, variables):
        def loss(params):
            out, _ = model.apply(
                {**variables, "params": params}, x, train=True, mutable=["batch_stats"]
            )
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(variables["params"])

    (_, out_new), g_new = run(new, restored)
    (_, out_old), g_old = run(old, trained)
    assert out_new.shape == out_old.shape and _rel(out_new, out_old) < 1e-5
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(g_new), jax.tree.leaves(g_old)):
        assert _rel(g, w) < 1e-4, (jax.tree_util.keystr(path), _rel(g, w))


# ---- a count, not a clock: what the cells' train programs hold ----


def _train_jaxpr(config_path):
    with open(os.path.join(REPO, config_path)) as f:
        cfg = ExperimentConfig.from_json(f.read())
    model = build_model(cfg.model)
    h, w = cfg.data.image_size
    mb = cfg.train.micro_batch_size
    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, h, w, 3)), train=False)
    )

    def loss_fn(params, stats, x, y):
        logits, _ = model.apply(
            {"params": params, "batch_stats": stats}, x, train=True, mutable=["batch_stats"]
        )
        return softmax_cross_entropy(logits, y, ignore_index=-1)

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss_fn))(
        variables["params"], variables["batch_stats"],
        jax.ShapeDtypeStruct((mb, h, w, 3), jnp.float32),
        jax.ShapeDtypeStruct((mb, h, w), jnp.int32),
    )
    return cfg, list(iter_eqns(jaxpr.jaxpr))


@pytest.mark.parametrize(
    "config_path",
    ["configs/vaihingen_unet_tpu_flagship.json", "configs/vaihingen_unet_v5e8.json"],
)
def test_the_cells_train_program_has_no_depth_to_space(config_path):
    cfg, eqns = _train_jaxpr(config_path)
    r, classes = cfg.model.stem_factor, cfg.model.num_classes
    h, w = cfg.data.image_size
    mb = cfg.train.micro_batch_size
    # depth_to_space is a rank-6 transpose; the only one left is the stem's
    # space_to_depth of the 3-channel image (forward only: no gradient).
    rank6 = [e for e in eqns if e.primitive.name == "transpose" and e.invars[0].aval.ndim == 6]
    assert [e.invars[0].aval.shape[-1] for e in rank6] == [3]
    convs = [e for e in eqns if e.primitive.name == "conv_general_dilated"]
    # forward (transposed: input dilated by r, writes full resolution), input
    # gradient (stride r, reads full resolution), weight gradient: one each.
    (fwd,) = [e for e in convs if tuple(e.params["lhs_dilation"]) == (r, r)]
    (dx,) = [e for e in convs if tuple(e.params["window_strides"]) == (r, r)]
    (dk,) = [e for e in convs if tuple(e.params["rhs_dilation"]) == (r, r)]
    assert fwd.outvars[0].aval.shape == (mb, h, w, classes)
    assert dx.invars[0].aval.shape == (mb, h, w, classes)
    assert dx.outvars[0].aval.shape[:3] == (mb, h // r, w // r)
    assert sorted(dk.outvars[0].aval.shape)[:3] == sorted((r, r, classes))
    assert "UNet._head/Conv_0" in str(dx.source_info.name_stack)
