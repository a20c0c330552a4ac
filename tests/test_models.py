import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddlpc_tpu.config import ExperimentConfig, ModelConfig
from ddlpc_tpu.models import build_model


@pytest.mark.parametrize("up_mode", ["conv_transpose", "bilinear"])
def test_unet_shapes(up_mode):
    cfg = ModelConfig(
        name="unet",
        num_classes=6,
        features=(8, 16, 32),
        bottleneck_features=32,
        up_sample_mode=up_mode,
    )
    model = build_model(cfg)
    x = jnp.zeros((2, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 64, 64, 6)
    assert logits.dtype == jnp.float32


def test_unet_width_divisor_halves_params():
    # reference NN_in_model divides every channel count (кластер.py:625,687)
    def nparams(div):
        cfg = ModelConfig(features=(8, 16), bottleneck_features=16, width_divisor=div)
        v = build_model(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False
        )
        return sum(p.size for p in jax.tree.leaves(v["params"]))

    assert nparams(2) < nparams(1)


def test_unet_batchnorm_state_updates():
    cfg = ModelConfig(features=(8, 16), bottleneck_features=16, norm="batch")
    model = build_model(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    _, updates = model.apply(variables, x, train=True, mutable=["batch_stats"])
    changed = jax.tree.map(
        lambda a, b: bool(jnp.any(a != b)),
        variables["batch_stats"],
        updates["batch_stats"],
    )
    assert any(jax.tree.leaves(changed))


@pytest.mark.parametrize("norm", ["group", "none"])
def test_unet_other_norms(norm):
    cfg = ModelConfig(features=(8,), bottleneck_features=8, norm=norm)
    model = build_model(cfg)
    x = jnp.zeros((1, 16, 16, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    assert "batch_stats" not in variables
    logits = model.apply(variables, x, train=True)
    assert logits.shape == (1, 16, 16, 6)


def test_compute_dtype_respected():
    import jax.numpy as jnp
    from flax import linen as nn

    cfg = ModelConfig(features=(4,), bottleneck_features=4, compute_dtype="float32")
    model = build_model(cfg)
    assert model.dtype == jnp.float32

    class Probe(nn.Module):
        inner: nn.Module

        @nn.compact
        def __call__(self, x):
            return self.inner(x, train=False)

    # bf16 default actually computes in bf16 (activations), fp32 in fp32
    for dt_name, want in [("bfloat16", jnp.bfloat16), ("float32", jnp.float32)]:
        m = build_model(ModelConfig(features=(4,), bottleneck_features=4, compute_dtype=dt_name))
        assert m.dtype == want


def test_space_to_depth_roundtrip():
    from ddlpc_tpu.models.layers import depth_to_space, space_to_depth

    x = jnp.arange(2 * 8 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 8, 3)
    s = space_to_depth(x, 2)
    assert s.shape == (2, 4, 4, 12)
    assert jnp.array_equal(depth_to_space(s, 2), x)
    # Each output pixel of s2d is one 2x2 input patch, channel-major.
    assert jnp.array_equal(
        s[0, 0, 0].reshape(2, 2, 3), x[0, 0:2, 0:2, :]
    )
    with pytest.raises(ValueError, match="divisible"):
        space_to_depth(jnp.zeros((1, 5, 4, 3)), 2)
    with pytest.raises(ValueError, match="divisible"):
        depth_to_space(jnp.zeros((1, 4, 4, 5)), 2)


def test_unet_s2d_stem_shapes():
    cfg = ModelConfig(
        features=(8, 16), bottleneck_features=16, num_classes=6,
        stem="s2d", stem_factor=2,
    )
    model = build_model(cfg)
    x = jnp.zeros((2, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    # Full-resolution logits despite the 1/2-resolution pyramid.
    assert logits.shape == (2, 64, 64, 6)


@pytest.mark.parametrize(
    "stem_factor",
    [
        # Factor 2 is slow-only: factor 4 (kept in tier-1) is the flagship
        # operating point and exercises the identical stem/head code path.
        pytest.param(2, marks=pytest.mark.slow),
        # tier-1's fast stem-learn representative is now
        # test_unetpp_s2d_stem_learns (budget maintenance); the unet
        # variant keeps full coverage in the slow tier
        pytest.param(4, marks=pytest.mark.slow),
    ],
)
def test_unet_s2d_stem_learns(tmp_path, stem_factor):
    """The TPU-optimized stem must actually train to the same place the
    plain stem does on synthetic tiles — at BOTH factors; factor 4 is the
    headline bench flagship (bench.py)."""
    from ddlpc_tpu.config import DataConfig, ExperimentConfig, TrainConfig
    from ddlpc_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=ModelConfig(
            features=(8, 16), bottleneck_features=16, num_classes=4,
            stem="s2d", stem_factor=stem_factor,
        ),
        # 64² tiles: at 32² the synthetic label grid degenerates to one
        # class per tile, which under-constrains the factor-4 subpixel head.
        data=DataConfig(dataset="synthetic", image_size=(64, 64),
                        synthetic_len=40, test_split=8, num_classes=4),
        train=TrainConfig(epochs=25, micro_batch_size=1, sync_period=2,
                          learning_rate=3e-3, dump_images_per_epoch=0,
                          checkpoint_every_epochs=0),
        workdir=str(tmp_path),
    )
    rec = Trainer(cfg).fit()
    assert rec["val_miou"] > 0.5


@pytest.mark.slow  # tier-1 keeps test_unet_detail_head_learns, which
# trains the same recipe WITH head_dtype="bfloat16" — the bf16 head
# storage path keeps a fast learn test through it (budget maintenance)
def test_bf16_head_learns(tmp_path):
    """head_dtype='bfloat16' (the bench configs' setting — it halves the
    logit head's HBM traffic) must train to the same place as the fp32
    default: only logit STORAGE rounds, softmax still runs in fp32."""
    from ddlpc_tpu.config import DataConfig, ExperimentConfig, TrainConfig
    from ddlpc_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=ModelConfig(
            features=(8, 16), bottleneck_features=16, num_classes=4,
            stem="s2d", stem_factor=4, head_dtype="bfloat16",
        ),
        data=DataConfig(dataset="synthetic", image_size=(64, 64),
                        synthetic_len=40, test_split=8, num_classes=4),
        train=TrainConfig(epochs=25, micro_batch_size=1, sync_period=2,
                          learning_rate=3e-3, dump_images_per_epoch=0,
                          checkpoint_every_epochs=0),
        workdir=str(tmp_path),
    )
    rec = Trainer(cfg).fit()
    assert rec["val_miou"] > 0.5


def test_unetpp_s2d_stem_learns(tmp_path):
    """U-Net++ with the TPU-first s2d stem (the bench's
    unetpp_vaihingen512_s2d config, 20× the paper layout's throughput) must
    still converge — deep-supervision subpixel heads included."""
    from ddlpc_tpu.config import DataConfig, ExperimentConfig, TrainConfig
    from ddlpc_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=ModelConfig(
            name="unetpp", features=(8, 16), num_classes=4,
            deep_supervision=True, stem="s2d", stem_factor=4,
        ),
        data=DataConfig(dataset="synthetic", image_size=(64, 64),
                        synthetic_len=40, test_split=8, num_classes=4),
        train=TrainConfig(epochs=25, micro_batch_size=1, sync_period=2,
                          learning_rate=3e-3, dump_images_per_epoch=0,
                          checkpoint_every_epochs=0),
        workdir=str(tmp_path),
    )
    rec = Trainer(cfg).fit()
    assert rec["val_miou"] > 0.5


def test_bf16_head_returns_bf16_logits():
    cfg = ModelConfig(
        features=(8, 16), bottleneck_features=16, num_classes=4,
        head_dtype="bfloat16",
    )
    model = build_model(cfg)
    x = jnp.zeros((1, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.dtype == jnp.bfloat16
    assert logits.shape == (1, 32, 32, 4)


@pytest.mark.parametrize("deep_supervision", [True, False])
def test_unetpp_shapes(deep_supervision):
    cfg = ModelConfig(
        name="unetpp",
        num_classes=5,
        features=(8, 16, 32),
        deep_supervision=deep_supervision,
    )
    model = build_model(cfg)
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 32, 32, 5)
    assert logits.dtype == jnp.float32


def test_unetpp_deep_supervision_has_multiple_heads():
    cfg = ModelConfig(name="unetpp", features=(8, 16, 32), deep_supervision=True)
    v = build_model(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False
    )
    heads = [k for k in v["params"] if k.startswith("head")]
    assert sorted(heads) == ["head_1", "head_2"]  # depth-1 supervised heads
    # Dense skip grid exists: X[0][1] and X[0][2] both present.
    assert "x0_1" in v["params"] and "x0_2" in v["params"]


def test_unetpp_trains():
    from ddlpc_tpu.ops.losses import softmax_cross_entropy

    cfg = ModelConfig(
        name="unetpp", num_classes=3, features=(4, 8), deep_supervision=True
    )
    model = build_model(cfg)
    x = jax.random.uniform(jax.random.PRNGKey(0), (2, 16, 16, 3))
    y = jax.random.randint(jax.random.PRNGKey(1), (2, 16, 16), 0, 3)
    variables = model.init(jax.random.PRNGKey(2), x, train=False)

    def loss_fn(params):
        logits, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return softmax_cross_entropy(logits, y)

    grads = jax.grad(loss_fn)(variables["params"])
    norms = [float(jnp.abs(g).max()) for g in jax.tree.leaves(grads)]
    assert all(jnp.isfinite(n) for n in norms)
    assert max(norms) > 0  # gradients actually flow through the nested grid


def test_unetpp_train_returns_stacked_heads_per_head_loss():
    """Deep supervision trains on per-head CE averages (Zhou et al. 2018),
    not on pre-softmax logit averages (ADVICE r1)."""
    from ddlpc_tpu.ops.losses import softmax_cross_entropy

    cfg = ModelConfig(
        name="unetpp", num_classes=3, features=(4, 8, 16), deep_supervision=True
    )
    model = build_model(cfg)
    x = jax.random.uniform(jax.random.PRNGKey(0), (2, 16, 16, 3))
    y = jax.random.randint(jax.random.PRNGKey(1), (2, 16, 16), 0, 3)
    variables = model.init(jax.random.PRNGKey(2), x, train=False)
    stacked, _ = model.apply(
        variables, x, train=True, mutable=["batch_stats"]
    )
    assert stacked.shape == (2, 2, 16, 16, 3)  # [J=depth-1, N, H, W, C]
    # CE over the stacked tensor == mean of the per-head CEs.
    per_head = jnp.stack(
        [softmax_cross_entropy(stacked[j], y) for j in range(2)]
    ).mean()
    np.testing.assert_allclose(
        float(softmax_cross_entropy(stacked, y)), float(per_head), rtol=1e-6
    )
    # Inference still returns one ensemble logit map.
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 16, 16, 3)


@pytest.mark.parametrize("output_stride", [8, 16])
def test_deeplabv3p_shapes(output_stride):
    cfg = ModelConfig(
        name="deeplabv3p",
        num_classes=7,
        output_stride=output_stride,
        width_divisor=8,  # tiny for test speed
    )
    model = build_model(cfg)
    x = jnp.zeros((1, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (1, 64, 64, 7)
    assert logits.dtype == jnp.float32


def test_deeplabv3p_atrous_rates_in_aspp():
    cfg = ModelConfig(name="deeplabv3p", width_divisor=8, aspp_rates=(2, 4))
    model = build_model(cfg)
    x = jnp.zeros((1, 32, 32, 3))
    v = model.init(jax.random.PRNGKey(0), x, train=False)
    aspp = [k for k in v["params"] if k.startswith("ASPP")]
    assert aspp  # ASPP module present
    # 1x1 + 2 rates + pooled + fuse = 5 ConvNormActs inside ASPP.
    assert len(v["params"][aspp[0]]) == 5


def test_deeplabv3p_bad_output_stride_raises():
    cfg = ModelConfig(name="deeplabv3p", output_stride=4)
    model = build_model(cfg)
    with pytest.raises(ValueError, match="output_stride"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)), train=False)


def test_registry_rejects_unknown_model():
    with pytest.raises(ValueError, match="unknown model"):
        build_model(ModelConfig(name="segformer"))


def test_build_model_from_experiment_wires_sync_bn():
    from ddlpc_tpu.config import ExperimentConfig, ParallelConfig
    from ddlpc_tpu.models import build_model_from_experiment

    e = ExperimentConfig(model=ModelConfig(features=(4,), bottleneck_features=4))
    assert build_model_from_experiment(e).norm_axis_name == "data"
    e2 = e.replace(parallel=ParallelConfig(sync_batch_norm=False))
    assert build_model_from_experiment(e2).norm_axis_name is None


def test_unet_detail_head_learns(tmp_path):
    """detail_head=True (full-res residual refinement over the subpixel
    head, models/layers.py:DetailHead) must train end to end — it exists to
    restore sub-stem_factor-px structure the 1/r pyramid cannot carry
    (HardTiles stem A/B: the 2-6 px disc class collapses without it)."""
    from ddlpc_tpu.config import DataConfig, ExperimentConfig, TrainConfig
    from ddlpc_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=ModelConfig(
            features=(8, 16), bottleneck_features=16, num_classes=4,
            stem="s2d", stem_factor=4, detail_head=True,
            head_dtype="bfloat16",
        ),
        data=DataConfig(dataset="synthetic", image_size=(64, 64),
                        synthetic_len=40, test_split=8, num_classes=4),
        train=TrainConfig(epochs=25, micro_batch_size=1, sync_period=2,
                          learning_rate=3e-3, dump_images_per_epoch=0,
                          checkpoint_every_epochs=0),
        workdir=str(tmp_path),
    )
    rec = Trainer(cfg).fit()
    assert rec["val_miou"] > 0.5


def test_detail_head_rejected_where_unimplemented():
    """A config artifact must not claim a refinement head the built model
    does not have (same principle as the GSPMD quantize_local rejection).
    U-Net and U-Net++ implement it; DeepLab does not."""
    from ddlpc_tpu.models import build_model

    with pytest.raises(ValueError, match="detail_head"):
        build_model(ModelConfig(name="deeplabv3p", detail_head=True))


@pytest.mark.slow  # tier-1 keeps test_unet_detail_head_learns (same head)
def test_unetpp_detail_head_learns(tmp_path):
    """U-Net++ shares ONE DetailHead across all supervision heads (shared
    params keep the heads consistent); it must train end to end with deep
    supervision and produce full-res refined logits at inference."""
    from ddlpc_tpu.config import DataConfig, ExperimentConfig, TrainConfig
    from ddlpc_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=ModelConfig(
            name="unetpp", features=(8, 16, 32), num_classes=4,
            deep_supervision=True, stem="s2d", stem_factor=2,
            detail_head=True, head_dtype="bfloat16",
        ),
        data=DataConfig(dataset="synthetic", image_size=(64, 64),
                        synthetic_len=40, test_split=8, num_classes=4),
        train=TrainConfig(epochs=25, micro_batch_size=1, sync_period=2,
                          learning_rate=3e-3, dump_images_per_epoch=0,
                          checkpoint_every_epochs=0),
        workdir=str(tmp_path),
    )
    rec = Trainer(cfg).fit()
    assert rec["val_miou"] > 0.5


# ---- round 4: stem-grid refinement + grouped train-head layout -----------


def test_group_labels_matches_s2d_channel_order():
    """group_labels must pair label phase p with the channel block phase p
    of pre-d2s logits — i.e. agree with space_to_depth's channel order."""
    from ddlpc_tpu.models.layers import group_labels, space_to_depth

    rng = np.random.default_rng(0)
    labels = jnp.asarray(rng.integers(0, 6, (2, 8, 12)), jnp.int32)
    for r in (2, 4):
        via_s2d = space_to_depth(
            labels[..., None].astype(jnp.float32), r
        ).astype(jnp.int32)
        np.testing.assert_array_equal(group_labels(labels, r), via_s2d)


@pytest.mark.parametrize("detail", [False, True])
def test_grouped_layout_loss_and_grads_identical(detail):
    """train_head_layout='grouped' is a LAYOUT change, not a math change:
    same params, same batch -> same loss/accuracy and (to fp reassociation)
    same gradients as the fullres layout.  This is the exactness proof that
    lets the grouped flagship reuse the fullres quality evidence."""
    from ddlpc_tpu.parallel.train_step import _loss_and_metrics

    def build(layout):
        cfg = ModelConfig(
            features=(8, 16), bottleneck_features=16, num_classes=5,
            stem="s2d", stem_factor=4, head_dtype="bfloat16",
            detail_head=detail, detail_head_kind="s2d",
            detail_head_hidden=8, train_head_layout=layout,
        )
        return build_model(cfg)

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.random((2, 64, 64, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 5, (2, 64, 64)), jnp.int32)
    m_full, m_grp = build("fullres"), build("grouped")
    v = m_full.init(jax.random.PRNGKey(0), x, train=False)
    # Identical param structure: grouping only skips the output d2s.
    v2 = m_grp.init(jax.random.PRNGKey(0), x, train=False)
    assert jax.tree.structure(v) == jax.tree.structure(v2)

    def loss_of(model):
        def f(params):
            loss, (stats, acc, _) = _loss_and_metrics(
                model, params, v["batch_stats"], x, y, train=True
            )
            return loss, acc
        return jax.value_and_grad(f, has_aux=True)(v["params"])

    (l1, a1), g1 = loss_of(m_full)
    (l2, a2), g2 = loss_of(m_grp)
    assert np.isclose(float(l1), float(l2), rtol=1e-5)
    assert np.isclose(float(a1), float(a2), rtol=1e-5)
    for p1, p2 in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(
            np.asarray(p1, np.float64), np.asarray(p2, np.float64),
            rtol=2e-4, atol=2e-6,
        )


@pytest.mark.slow  # s2d-grid head variant; fullres head learn stays tier-1
def test_stem_grid_detail_head_learns(tmp_path):
    """detail_head_kind='s2d' + train_head_layout='grouped' (the round-4
    fused-head candidate) must train end to end and produce full-res logits
    at inference."""
    from ddlpc_tpu.config import DataConfig, ExperimentConfig, TrainConfig
    from ddlpc_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=ModelConfig(
            features=(8, 16), bottleneck_features=16, num_classes=4,
            stem="s2d", stem_factor=4, head_dtype="bfloat16",
            detail_head=True, detail_head_kind="s2d", detail_head_hidden=16,
            train_head_layout="grouped",
        ),
        data=DataConfig(dataset="synthetic", image_size=(64, 64),
                        synthetic_len=40, test_split=8, num_classes=4),
        train=TrainConfig(epochs=25, micro_batch_size=1, sync_period=2,
                          learning_rate=3e-3, dump_images_per_epoch=0,
                          checkpoint_every_epochs=0),
        workdir=str(tmp_path),
    )
    rec = Trainer(cfg).fit()
    assert rec["val_miou"] > 0.5


def test_head_option_validation():
    """Invalid layout/kind combinations are rejected at build time — a
    config artifact must never claim semantics the network won't execute."""
    with pytest.raises(ValueError, match="detail_head_kind"):
        build_model(ModelConfig(detail_head=True, detail_head_kind="nope"))
    with pytest.raises(ValueError, match="stem='s2d'"):
        build_model(
            ModelConfig(detail_head=True, detail_head_kind="s2d", stem="none")
        )
    with pytest.raises(ValueError, match="grouped"):
        build_model(ModelConfig(train_head_layout="grouped", stem="none"))
    with pytest.raises(ValueError, match="full-resolution DetailHead"):
        build_model(
            ModelConfig(
                train_head_layout="grouped", stem="s2d",
                detail_head=True, detail_head_kind="fullres",
            )
        )
    with pytest.raises(ValueError, match="grouped"):
        build_model(
            ModelConfig(name="deeplabv3p", train_head_layout="grouped",
                        stem="s2d")
        )
    with pytest.raises(ValueError, match="detail_head_scope"):
        build_model(ModelConfig(detail_head_scope="sometimes"))


@pytest.mark.slow  # scope wiring asserted cheaply elsewhere; learn is slow
def test_unetpp_ensemble_scope_shapes_and_learns(tmp_path):
    """detail_head_scope='ensemble': supervision heads train unrefined plus
    ONE refined ensemble output (stacked last); inference returns the
    refined ensemble.  The refinement compute runs once, not once per head
    (the -43% round-3 cost)."""
    from ddlpc_tpu.config import DataConfig, ExperimentConfig, TrainConfig
    from ddlpc_tpu.train.trainer import Trainer

    mcfg = ModelConfig(
        name="unetpp", features=(8, 16, 32), num_classes=4,
        deep_supervision=True, stem="s2d", stem_factor=2,
        detail_head=True, detail_head_kind="s2d", detail_head_hidden=8,
        detail_head_scope="ensemble", train_head_layout="grouped",
        head_dtype="bfloat16",
    )
    model = build_model(mcfg)
    x = jnp.zeros((2, 64, 64, 3))
    v = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(v, x, train=True, mutable=["batch_stats"])[0]
    # 2 supervision heads + 1 refined ensemble, grouped layout (32² grid).
    assert out.shape == (3, 2, 32, 32, 4 * 4)
    infer = model.apply(v, x, train=False)
    assert infer.shape == (2, 64, 64, 4)

    cfg = ExperimentConfig(
        model=mcfg,
        data=DataConfig(dataset="synthetic", image_size=(64, 64),
                        synthetic_len=40, test_split=8, num_classes=4),
        train=TrainConfig(epochs=25, micro_batch_size=1, sync_period=2,
                          learning_rate=3e-3, dump_images_per_epoch=0,
                          checkpoint_every_epochs=0),
        workdir=str(tmp_path),
    )
    rec = Trainer(cfg).fit()
    assert rec["val_miou"] > 0.5


def test_pyramid_too_shallow_raises():
    """A tile that pools to a zero-size tensor at the deepest level must
    raise at trace time, not silently produce NaN BatchNorm gradients that
    the codec's global max-abs spreads through the whole tree (found on a
    64² smoke run of the s2d×4 flagship geometry)."""
    cfg = ModelConfig(width_divisor=2, num_classes=6, stem="s2d", stem_factor=4)
    model = build_model(cfg)
    with pytest.raises(ValueError, match="too small"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)), train=False)
    cfg = ModelConfig(name="unetpp", features=(8, 16, 32), num_classes=6,
                      stem="s2d", stem_factor=4)
    with pytest.raises(ValueError, match="too small"):
        build_model(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)), train=False
        )


def test_undeclared_grouped_logits_refused():
    """_loss_and_metrics must NOT silently regroup mismatched logits unless
    the model declared train_head_layout='grouped' (advisor find, round 4):
    a buggy model whose output dims happen to divide the labels would
    otherwise train on scrambled (logit, label) pairings."""
    from ddlpc_tpu.parallel.train_step import _loss_and_metrics

    class BadModel:
        # Quacks like a module but emits quarter-res logits while
        # declaring the fullres layout.
        train_head_layout = "fullres"

        def apply(self, variables, x, train=False, mutable=None):
            logits = jnp.zeros((x.shape[0], 16, 16, 80), jnp.float32)
            return (logits, {"batch_stats": {}}) if train else logits

    x = jnp.zeros((2, 64, 64, 3), jnp.float32)
    y = jnp.zeros((2, 64, 64), jnp.int32)
    with pytest.raises(ValueError, match="refusing to reinterpret"):
        _loss_and_metrics(BadModel(), {}, {}, x, y, train=True)
    # Eval never regroups, even for a grouped-declaring model.
    BadModel.train_head_layout = "grouped"
    with pytest.raises(ValueError, match="refusing to reinterpret"):
        _loss_and_metrics(BadModel(), {}, {}, x, y, train=False)


# ---- the parent's parameter tree and logits (tests/data/, PR 29) ----------
# Written at commit d079505 (the last with the block interpreter in
# UNet.__call__) by calling the two helpers below against that tree: old
# checkpoints restore only while every path, shape and dtype stays.

_DATA_DIR = os.path.join(os.path.dirname(__file__), "data")
_CONFIG_DIR = os.path.join(os.path.dirname(__file__), "..", "configs")


def _unet_tree_rows(config_name):
    """``[collection/path, shape, dtype]`` of every leaf the shipped
    configuration's model group initialises (shapes only: eval_shape)."""
    with open(os.path.join(_CONFIG_DIR, config_name + ".json")) as f:
        cfg = ExperimentConfig.from_json(f.read())
    model = build_model(cfg.model, norm_axis_name="data")
    h, w = cfg.data.image_size
    variables = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1, h, w, 3), jnp.float32), train=False
        )
    )
    return [
        [jax.tree_util.keystr(path), list(leaf.shape), str(leaf.dtype)]
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            {k: variables[k] for k in ("params", "batch_stats")}
        )
    ]


_PROBE_MODELS = {
    "plain_stem": ModelConfig(
        features=(8, 16, 32), bottleneck_features=32, num_classes=6,
        compute_dtype="float32",
    ),
    "s2d_stem_fullres_detail_head": ModelConfig(
        features=(8, 16, 32), bottleneck_features=32, num_classes=6,
        compute_dtype="float32", stem="s2d", stem_factor=4,
        detail_head=True, detail_head_kind="fullres",
    ),
}


def _unet_probe_logits(case):
    """Logits of a small float32 U-Net at 32 fixed positions, from the
    training forward (batch statistics) and from the eval forward."""
    model = build_model(_PROBE_MODELS[case])
    x = jax.random.normal(jax.random.key(1), (2, 64, 64, 3), jnp.float32)
    variables = model.init(jax.random.key(0), x, train=False)
    train_logits, _ = model.apply(variables, x, train=True, mutable=["batch_stats"])
    eval_logits = model.apply(variables, x, train=False)
    rng = np.random.default_rng(0)
    idx = tuple(rng.integers(0, n, size=32) for n in eval_logits.shape)
    return {
        "train": np.asarray(train_logits)[idx].tolist(),
        "eval": np.asarray(eval_logits)[idx].tolist(),
    }


@pytest.mark.parametrize(
    "config_name",
    [
        "vaihingen_unet_cpu",
        "vaihingen_unet_tpu_flagship",
        "vaihingen_unet_v5e8",
        "cityscapes_unet_v5e64",
    ],
)
def test_unet_param_tree_is_the_parents(config_name):
    with open(os.path.join(_DATA_DIR, "unet_param_trees_d079505.json")) as f:
        golden = json.load(f)[config_name]
    assert _unet_tree_rows(config_name) == golden


@pytest.mark.parametrize("case", sorted(_PROBE_MODELS))
def test_unet_logits_are_the_parents(case):
    with open(os.path.join(_DATA_DIR, "unet_probe_logits_d079505.json")) as f:
        golden = json.load(f)[case]
    got = _unet_probe_logits(case)
    for mode in ("train", "eval"):
        np.testing.assert_allclose(got[mode], golden[mode], rtol=0, atol=1e-6)
