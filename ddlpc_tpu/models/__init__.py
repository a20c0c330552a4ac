"""Model zoo registry.

Build any registered model from a ``ModelConfig``.  The reference has exactly
one model, U-Net (кластер.py:620-656); BASELINE.json's configs additionally
require U-Net++ (deep supervision) and DeepLabV3+ (ASPP/atrous).  The
decoder families (``lfm2_moe``, ``keye_vl2``, ``olmo_hybrid``,
``deepseek_v3``) are no conv nets: per-position classifiers over 1×S token
tiles.
"""

from __future__ import annotations

from typing import Optional

from flax import linen as nn

from ddlpc_tpu.config import ModelConfig
from ddlpc_tpu.models.deeplabv3p import DeepLabV3Plus
from ddlpc_tpu.models.deepseek_v3 import DeepseekV3
from ddlpc_tpu.models.keye_vl2 import KeyeVL2
from ddlpc_tpu.models.lfm2_moe import LFM2MoE
from ddlpc_tpu.models.olmo_hybrid import OlmoHybrid
from ddlpc_tpu.models.unet import UNet
from ddlpc_tpu.models.unetpp import UNetPP

_REGISTRY = {}
# Models that implement ModelConfig.detail_head.  Checked centrally in
# build_model so a newly registered model is safe by default: a config
# artifact must never claim a refinement head the built network lacks
# (same principle as the GSPMD quantize_local rejection,
# parallel/train_step.py).
_DETAIL_HEAD_MODELS = {"unet", "unetpp"}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn

    return deco


@register("unet")
def _build_unet(cfg: ModelConfig, norm_axis_name: Optional[str]) -> nn.Module:
    import jax.numpy as jnp

    return UNet(
        num_classes=cfg.num_classes,
        features=tuple(cfg.features),
        bottleneck_features=cfg.bottleneck_features,
        width_divisor=cfg.width_divisor,
        up_sample_mode=cfg.up_sample_mode,
        norm=cfg.norm,
        norm_axis_name=norm_axis_name,
        norm_groups=cfg.group_norm_groups,
        stem=cfg.stem,
        stem_factor=cfg.stem_factor,
        detail_head=cfg.detail_head,
        detail_head_kind=cfg.detail_head_kind,
        detail_head_hidden=cfg.detail_head_hidden,
        train_head_layout=cfg.train_head_layout,
        dtype=jnp.dtype(cfg.compute_dtype),
        head_dtype=jnp.dtype(cfg.head_dtype),
    )


@register("unetpp")
def _build_unetpp(cfg: ModelConfig, norm_axis_name: Optional[str]) -> nn.Module:
    import jax.numpy as jnp

    return UNetPP(
        num_classes=cfg.num_classes,
        features=tuple(cfg.features),
        width_divisor=cfg.width_divisor,
        up_sample_mode=cfg.up_sample_mode,
        norm=cfg.norm,
        norm_axis_name=norm_axis_name,
        norm_groups=cfg.group_norm_groups,
        deep_supervision=cfg.deep_supervision,
        stem=cfg.stem,
        stem_factor=cfg.stem_factor,
        detail_head=cfg.detail_head,
        detail_head_kind=cfg.detail_head_kind,
        detail_head_hidden=cfg.detail_head_hidden,
        detail_head_scope=cfg.detail_head_scope,
        train_head_layout=cfg.train_head_layout,
        dtype=jnp.dtype(cfg.compute_dtype),
        head_dtype=jnp.dtype(cfg.head_dtype),
    )


@register("deeplabv3p")
def _build_deeplab(cfg: ModelConfig, norm_axis_name: Optional[str]) -> nn.Module:
    import jax.numpy as jnp

    return DeepLabV3Plus(
        num_classes=cfg.num_classes,
        features=tuple(cfg.features),
        width_divisor=cfg.width_divisor,
        output_stride=cfg.output_stride,
        aspp_rates=tuple(cfg.aspp_rates),
        norm=cfg.norm,
        norm_axis_name=norm_axis_name,
        norm_groups=cfg.group_norm_groups,
        dtype=jnp.dtype(cfg.compute_dtype),
        head_dtype=jnp.dtype(cfg.head_dtype),
    )


@register("lfm2_moe")
def _build_lfm2_moe(cfg: ModelConfig, norm_axis_name: Optional[str]) -> nn.Module:
    del norm_axis_name  # RMSNorm only: no batch statistics to synchronise
    if not cfg.layer_types or set(cfg.layer_types) - {"conv", "full_attention"}:
        raise ValueError(
            f"lfm2_moe needs model.layer_types of 'conv' | 'full_attention', "
            f"got {cfg.layer_types!r}"
        )
    if not 0 <= cfg.expert_offset <= cfg.num_experts - cfg.experts_held:
        raise ValueError(
            f"experts [{cfg.expert_offset}, {cfg.expert_offset + cfg.experts_held}) "
            f"are not among the router's {cfg.num_experts}"
        )
    return LFM2MoE(cfg)


@register("keye_vl2")
def _build_keye_vl2(cfg: ModelConfig, norm_axis_name: Optional[str]) -> nn.Module:
    del norm_axis_name  # RMSNorm only
    if not cfg.layer_types or set(cfg.layer_types) != {"full_attention"}:
        raise ValueError(
            f"keye_vl2 needs model.layer_types of 'full_attention', one a layer, "
            f"got {cfg.layer_types!r}"
        )
    if cfg.indexer_num_kv_heads != 1:
        raise ValueError("keye_vl2's indexer shares one key head (indexer_num_kv_heads 1)")
    if cfg.router_score not in ("sigmoid", "softmax"):
        raise ValueError(f"unknown model.router_score {cfg.router_score!r}")
    if not 0 <= cfg.expert_offset <= cfg.num_experts - cfg.experts_held:
        raise ValueError(
            f"experts [{cfg.expert_offset}, {cfg.expert_offset + cfg.experts_held}) "
            f"are not among the router's {cfg.num_experts}"
        )
    return KeyeVL2(cfg)


@register("olmo_hybrid")
def _build_olmo_hybrid(cfg: ModelConfig, norm_axis_name: Optional[str]) -> nn.Module:
    del norm_axis_name  # RMSNorm only
    if not cfg.layer_types or set(cfg.layer_types) - {"linear_attention", "full_attention"}:
        raise ValueError(
            f"olmo_hybrid needs model.layer_types of 'linear_attention' | 'full_attention', "
            f"got {cfg.layer_types!r}"
        )
    if cfg.tie_word_embeddings or cfg.num_key_value_heads != cfg.num_attention_heads:
        raise ValueError("olmo_hybrid has an untied head and one k/v head a query head")
    if cfg.linear_num_key_heads != cfg.linear_num_value_heads:
        raise ValueError("olmo_hybrid's DeltaNet layers have one key head a value head")
    shared = (cfg.num_attention_heads, cfg.linear_num_value_heads, cfg.intermediate_size)
    if cfg.tensor_shards < 1 or any(n % cfg.tensor_shards for n in shared):
        raise ValueError(
            f"model.tensor_shards {cfg.tensor_shards} does not divide the heads and "
            f"feed-forward columns {shared}"
        )
    return OlmoHybrid(cfg)


@register("deepseek_v3")
def _build_deepseek_v3(cfg: ModelConfig, norm_axis_name: Optional[str]) -> nn.Module:
    del norm_axis_name  # RMSNorm only
    if not cfg.layer_types or set(cfg.layer_types) != {"full_attention"}:
        raise ValueError(
            f"deepseek_v3 needs model.layer_types of 'full_attention', one a layer, "
            f"got {cfg.layer_types!r}"
        )
    # What the family's configs can say and this program does not build is
    # refused, not run as something else.
    if cfg.q_lora_rank:
        raise ValueError("deepseek_v3 is built without query compression (model.q_lora_rank 0)")
    if (cfg.n_group, cfg.topk_group) != (1, 1):
        raise ValueError("deepseek_v3 is built without group-limited routing (n_group = topk_group = 1)")
    if cfg.router_score != "sigmoid" or cfg.tie_word_embeddings:
        raise ValueError("deepseek_v3 scores its experts by sigmoid and has an untied head")
    if not 0 <= cfg.num_dense_layers <= len(cfg.layer_types):
        raise ValueError(f"{cfg.num_dense_layers} leading dense layers of {len(cfg.layer_types)}")
    if not 0 <= cfg.expert_offset <= cfg.num_experts - cfg.experts_held:
        raise ValueError(
            f"experts [{cfg.expert_offset}, {cfg.expert_offset + cfg.experts_held}) "
            f"are not among the router's {cfg.num_experts}"
        )
    return DeepseekV3(cfg)


def build_model(cfg: ModelConfig, norm_axis_name: Optional[str] = None) -> nn.Module:
    """norm_axis_name: mesh axis to sync BatchNorm stats over (None = local)."""
    try:
        builder = _REGISTRY[cfg.name]
    except KeyError:
        raise ValueError(
            f"unknown model {cfg.name!r}; registered: {sorted(_REGISTRY)}"
        ) from None
    if cfg.detail_head and cfg.name not in _DETAIL_HEAD_MODELS:
        raise ValueError(
            f"model {cfg.name!r} does not implement detail_head "
            f"(supported: {sorted(_DETAIL_HEAD_MODELS)}) — set "
            f"model.detail_head=False"
        )
    # The layout/kind combinations are validated HERE, not silently ignored
    # in the model: a config artifact claiming a layout the built network
    # would not execute is a lie in the artifact (same principle as the
    # GSPMD quantize_local rejection, parallel/train_step.py).
    if cfg.detail_head_kind not in ("fullres", "s2d"):
        raise ValueError(
            f"unknown detail_head_kind {cfg.detail_head_kind!r} "
            f"(fullres | s2d)"
        )
    if cfg.train_head_layout not in ("fullres", "grouped"):
        raise ValueError(
            f"unknown train_head_layout {cfg.train_head_layout!r} "
            f"(fullres | grouped)"
        )
    if cfg.detail_head_scope not in ("per_head", "ensemble"):
        raise ValueError(
            f"unknown detail_head_scope {cfg.detail_head_scope!r} "
            f"(per_head | ensemble)"
        )
    if cfg.detail_head and cfg.detail_head_kind == "s2d" and cfg.stem != "s2d":
        raise ValueError(
            "detail_head_kind='s2d' refines the pre-d2s logit grid and "
            "requires stem='s2d'; with stem='none' use "
            "detail_head_kind='fullres'"
        )
    if cfg.train_head_layout == "grouped":
        if cfg.stem != "s2d":
            raise ValueError(
                "train_head_layout='grouped' skips the subpixel d2s in the "
                "train path — it requires stem='s2d'"
            )
        if cfg.detail_head and cfg.detail_head_kind == "fullres":
            raise ValueError(
                "train_head_layout='grouped' cannot feed a full-resolution "
                "DetailHead (it needs full-res logits): use "
                "detail_head_kind='s2d' or train_head_layout='fullres'"
            )
        if cfg.name not in _DETAIL_HEAD_MODELS:
            raise ValueError(
                f"model {cfg.name!r} does not implement "
                f"train_head_layout='grouped' (supported: "
                f"{sorted(_DETAIL_HEAD_MODELS)})"
            )
    return builder(cfg, norm_axis_name)


def build_model_from_experiment(ecfg) -> nn.Module:
    """Build honoring ParallelConfig.sync_batch_norm: per-batch cross-replica
    BN stat averaging over the data axis (the reference never re-syncs BN,
    SURVEY §3.1).

    With a non-trivial space axis the GSPMD step is used
    (parallel/train_step.py:make_train_step_gspmd), where BN statistics are
    computed over the logical global batch — exact sync-BN without an axis
    name — so ``norm_axis_name`` must stay None there.
    """
    axis = (
        ecfg.parallel.data_axis_name
        if ecfg.parallel.sync_batch_norm and ecfg.parallel.space_axis_size <= 1
        else None
    )
    return build_model(ecfg.model, norm_axis_name=axis)
