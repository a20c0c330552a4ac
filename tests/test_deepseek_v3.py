"""The deepseek_v3 family (models/deepseek_v3.py) at a tiny size on the CPU:
the program against the plain float32 reference the benchmark keeps
(benchmark/reference/deepseek_v3.py) on logits, cross-entropy and its
gradients; sixteen expert-parallel shares against the uncut layer, the shared
experts counted once; latent attention through the two-width kernels; the
registry, the counters, the Trainer and the FLOP walk; and the other decoder
families' step programs as they were.

Tiny: hidden 64, 4 heads with q·k of 16 + 8 and values of 16 from a latent of
32, 32 experts top-4 (2 held) beside 2 shared, vocabulary 96, S 64, one dense
layer then two MoE layers.
"""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import check  # noqa: E402  (benchmark/check.py)
from reference import deepseek_v3 as reference  # noqa: E402

from ddlpc_tpu.config import (  # noqa: E402
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    ParallelConfig,
    TrainConfig,
)
from ddlpc_tpu.models import build_model  # noqa: E402
from ddlpc_tpu.models import deepseek_v3 as program  # noqa: E402
from ddlpc_tpu.models import lfm2_moe  # noqa: E402
from ddlpc_tpu.ops import pallas_attention  # noqa: E402

VOCAB, SEQ = 96, 64
TINY = dict(
    name="deepseek_v3", num_classes=VOCAB, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_experts=32, num_experts_per_tok=4,
    experts_held=2, expert_offset=6, num_dense_layers=1, n_shared_experts=2,
    layer_types=("full_attention",) * 3, norm_eps=1e-6, rope_theta=1e6,
    routed_scaling_factor=2.448, tie_word_embeddings=False,
)


QUERY_BLOCK = lfm2_moe.QUERY_BLOCK  # the module's own


@pytest.fixture(autouse=True)
def several_query_blocks(monkeypatch):
    """Four query blocks of the XLA form at S = 64, where the module's 512 would make one."""
    monkeypatch.setattr(lfm2_moe, "QUERY_BLOCK", 16)


def tiny_config(**changes) -> ModelConfig:
    return ModelConfig(**{**TINY, "compute_dtype": "float32", **changes})


def tokens(seed: int, batch: int = 2, seq: int = SEQ):
    ids = jax.random.randint(jax.random.key(seed), (batch, 1, seq + 1, 1), 0, VOCAB)
    return np.asarray(ids[:, :, :seq]), np.asarray(ids[:, :, 1:, 0])


def init(cfg: ModelConfig, seed: int = 0, seq: int = SEQ):
    x = jnp.zeros((1, 1, seq, 1), jnp.int32)
    return build_model(cfg).init(jax.random.key(seed), x, train=False)["params"]


# ---- program against reference ---------------------------------------------


def compare(dtype: str, seed: int, seq: int = SEQ) -> dict:
    """check.py's three errors of the program computing in ``dtype`` (float32
    parameters) against the reference given the same share."""
    cfg = tiny_config(compute_dtype=dtype)
    x, y = tokens(seed, seq=seq)
    params = init(tiny_config(), seed + 10, seq)
    got = check.program_fn(cfg)(params, {}, x, y)
    want = check.reference_fn("deepseek_v3", dataclasses.asdict(cfg))(params, {}, x, y)
    return {k: float(v) for k, v in check._errors(got, want).items()}


@pytest.mark.parametrize("seed", [0, 1])
def test_float32_program_is_the_reference(seed):
    """Rotary turned in place pair by pair (the reference) and written turned
    firsts then seconds (the program) agree, as q·k does not see the order."""
    out = compare("float32", seed)
    limits = reference.TOLERANCE["float32"]
    assert all(out[k] <= limits[k] for k in limits), out
    assert out["logits"] < 1e-5 and out["grad"] < 1e-4, out


# At this size bf16 reads 0.009..0.025 on the logits and 0.009..0.027 on the
# gradients over four seeds (a flipped selection weighs more in 2 of 32
# experts); float8 0.16 and 0.36..0.38 (the chip's readings at the published
# widths are reference.TOLERANCE's notes).
TINY_BF16 = {"loss": 1e-3, "logits": 0.08, "grad": 0.1}


@pytest.mark.parametrize("seed", [0, 1])
def test_bfloat16_program_near_the_reference_and_outside_float32s_limits(seed):
    out = compare("bfloat16", seed)
    assert all(out[k] <= TINY_BF16[k] for k in TINY_BF16), out
    limits = reference.TOLERANCE["float32"]
    assert out["logits"] > limits["logits"] and out["grad"] > limits["grad"], out


def test_float8_program_is_told_from_bfloat16():
    """The nearest precision below the stated one fails the bf16 limits of
    the tiny size and of the chip, by logits and gradients."""
    out = compare("float8_e4m3fn", 0)
    assert out["logits"] > TINY_BF16["logits"] and out["grad"] > TINY_BF16["grad"], out
    assert out["grad"] > reference.TOLERANCE["bfloat16"]["grad"], out


# ---- the shares add up --------------------------------------------------------


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen ranks' MoE layers of two experts each, computed by the program,
    sum to what the reference gives with all 32 experts held once the shared
    experts, which every rank computes alike, are counted once."""
    cfg = tiny_config()
    hidden, n_exp, held = cfg.hidden_size, cfg.num_experts, 2
    shared_width = cfg.n_shared_experts * cfg.moe_intermediate_size
    keys = jax.random.split(jax.random.key(3), 9)
    u = jax.random.normal(keys[0], (2, 1, SEQ, hidden), jnp.float32)
    normal = lambda key, shape, s: jax.random.normal(key, shape) * s  # noqa: E731
    experts = {
        "gate": normal(keys[1], (hidden, n_exp), 0.3),
        "expert_bias": normal(keys[2], (n_exp,), 0.05),  # a bias that selects: the rule holds for any
        "w1": normal(keys[3], (n_exp, hidden, 32), 0.1),
        "w3": normal(keys[4], (n_exp, hidden, 32), 0.1),
        "w2": normal(keys[5], (n_exp, 32, hidden), 0.1),
    }
    shared = {
        "w1": {"kernel": normal(keys[6], (hidden, shared_width), 0.1)},
        "w3": {"kernel": normal(keys[7], (hidden, shared_width), 0.1)},
        "w2": {"kernel": normal(keys[8], (shared_width, hidden), 0.1)},
    }
    uncut = dict(dataclasses.asdict(cfg), experts_held=n_exp, expert_offset=0)
    want = reference.moe(u[:, 0], {"shared_experts": shared, "experts": experts}, uncut)
    layer = functools.partial(
        program.RoutedExperts, hidden, cfg.moe_intermediate_size, n_exp, cfg.num_experts_per_tok,
        held, norm_topk_prob=True, routed_scaling_factor=cfg.routed_scaling_factor,
        dtype=jnp.float32, sigmoid_eps=program.ROUTER_EPS,
    )
    shared_part = lfm2_moe.SwiGLU(hidden, shared_width, jnp.float32).apply({"params": shared}, u)
    total, routed = shared_part, 0
    for offset in range(0, n_exp, held):
        share = dict(experts, **{k: experts[k][offset : offset + held] for k in ("w1", "w3", "w2")})
        part, counts = layer(expert_offset=offset).apply({"params": share}, u)
        total = total + part
        routed += int(counts["sum"]["moe_rows_routed"])
        assert int(counts["sum"]["moe_rows_dropped"]) == 0
    np.testing.assert_allclose(total[:, 0], want, rtol=2e-5, atol=2e-6)
    assert routed == 2 * SEQ * cfg.num_experts_per_tok  # every pair computed by one share


def test_selection_bias_starts_at_zeros_and_takes_no_gradient():
    """The selection bias starts at DeepSeek-V3's published zeros and no
    gradient reaches it (the 1e-20 normaliser and the 2.448 scale are held to
    the reference by the shares' test); layer 0 is the dense one."""
    params = init(tiny_config())
    routed = params["layers_1"]["experts"]
    np.testing.assert_array_equal(routed["expert_bias"], np.zeros(32, np.float32))
    assert "experts" not in params["layers_0"] and "mlp" in params["layers_0"]
    u = jax.random.normal(jax.random.key(5), (1, 1, SEQ, 64))
    layer = program.RoutedExperts(
        64, 32, 32, 4, 2, 6, True, 2.448, True, jnp.float32, "sigmoid", program.ROUTER_EPS,
    )
    _, vjp = jax.vjp(lambda p: layer.apply({"params": p}, u)[0], routed)
    (grads,) = vjp(jnp.ones((1, 1, SEQ, 64)))
    assert float(jnp.abs(grads["expert_bias"]).max()) == 0.0
    assert float(jnp.abs(grads["gate"]).max()) > 0 and float(jnp.abs(grads["w1"]).max()) > 0


# ---- latent attention ---------------------------------------------------------


def test_pair_rotary_turns_adjacent_pairs_and_keeps_relative_phase():
    """The program's rotary is the reference's pair-by-pair turn, its members
    reordered firsts then seconds; q·k after it depends on the offset alone."""
    x = jax.random.normal(jax.random.key(0), (1, 16, 2, 8))
    cos, sin = program.pair_rotary_tables(16, 8, 1e6)
    got = program.apply_pair_rotary(x, cos, sin)
    want = reference.pair_rotary(x, jnp.arange(16), 1e6)
    order = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    np.testing.assert_allclose(got, want[..., order], rtol=1e-6, atol=1e-6)
    q, k = jnp.ones((1, 16, 1, 8)), jnp.ones((1, 16, 1, 8)) * 0.5
    dots = jnp.einsum("bshd,bthd->st", program.apply_pair_rotary(q, cos, sin), program.apply_pair_rotary(k, cos, sin))
    np.testing.assert_allclose(jnp.diagonal(dots, 3), jnp.full(13, dots[3, 0]), rtol=1e-5)


def test_model_is_causal_through_the_two_width_kernels(monkeypatch):
    """The test steers the model onto the kernels (interpreted, q·k of 24 and
    values of 16); the program itself goes by the platform it is lowered for.
    The kernels give the XLA form's logits, and a token changes nothing before it."""
    seq = 256
    cfg = tiny_config()
    model = build_model(cfg)
    x = np.asarray(jax.random.randint(jax.random.key(1), (1, 1, seq, 1), 0, VOCAB))
    params = model.init(jax.random.key(0), x, train=False)["params"]
    xla = model.apply({"params": params}, x)
    monkeypatch.setattr(
        program, "causal_attention",
        functools.partial(pallas_attention.causal_attention, block=128, interpret=True),
    )
    a = model.apply({"params": params}, x)
    np.testing.assert_allclose(a, xla, rtol=2e-4, atol=2e-4)
    changed = x.copy()
    changed[:, :, 200:] = (changed[:, :, 200:] + 17) % VOCAB
    b = model.apply({"params": params}, changed)
    np.testing.assert_array_equal(np.asarray(a[:, :, :200]), np.asarray(b[:, :, :200]))
    assert float(jnp.abs(a[:, :, 200:] - b[:, :, 200:]).max()) > 0


# ---- registry, counters, scopes -------------------------------------------------


@pytest.mark.parametrize(
    "changes,match",
    [
        ({"q_lora_rank": 1536}, "query compression"),
        ({"n_group": 8, "topk_group": 4}, "group-limited"),
        ({"router_score": "softmax"}, "sigmoid"),
        ({"tie_word_embeddings": True}, "untied"),
        ({"layer_types": ("full_attention", "conv")}, "layer_types"),
        ({"expert_offset": 31}, "not among"),
    ],
    ids=["q_lora_rank", "groups", "softmax", "tied", "layer_types", "offset"],
)
def test_registry_refuses_what_is_not_built(changes, match):
    with pytest.raises(ValueError, match=match):
        build_model(tiny_config(**changes))


def test_counters_and_scopes():
    """tokens, routed rows and the kernel layer count (0 off the chip), and
    the program's scopes: the projections apart from the attention core, the
    shared experts apart from the routed ones."""
    cfg = tiny_config()
    model = build_model(cfg)
    x, _ = tokens(0)
    params = init(cfg)
    _, aux = jax.jit(lambda p: model.apply({"params": p}, x, train=True, mutable=["counters"]))(params)
    sums, maxes = aux["counters"]["sum"], aux["counters"]["max"]
    assert int(sums["tokens_per_step"]) == 2 * SEQ and int(maxes["mla_kernel_layers"]) == 0
    assert int(sums["moe_rows_offered"]) == 2 * 2 * SEQ * 4  # two routed layers x tokens x top-4
    assert int(sums["moe_rows_dropped"]) == 0 and 0 < int(sums["moe_rows_routed"]) < int(sums["moe_rows_offered"])
    text = jax.jit(lambda p: model.apply({"params": p}, x)).lower(params).as_text(debug_info=True)
    for scope in ("ddlpc/mla/proj", "ddlpc/attention", "ddlpc/moe/shared", "ddlpc/moe/route",
                  "ddlpc/moe/experts", "ddlpc/dense_ffn", "ddlpc/embed", "ddlpc/head"):
        assert scope in text, scope


def test_kernel_counter_counts_every_layer_where_the_kernels_lower():
    """On a TPU lowering at a length the kernels take, every layer's attention
    is the two-width kernel pair (the forward and the backward of each)."""
    cfg = tiny_config()
    model = build_model(cfg)
    x = jnp.zeros((1, 1, 512, 1), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), x, train=False))["params"]

    def loss(p):
        logits, aux = model.apply({"params": p}, x, train=True, mutable=["counters"])
        return logits.sum(), aux["counters"]["max"]["mla_kernel_layers"]

    text = jax.jit(jax.grad(loss, has_aux=True)).trace(params).lower(lowering_platforms=("tpu",)).as_text()
    # a layer's forward kernel, its second run under nn.remat, and its backward kernel
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 3 * len(cfg.layer_types)


# ---- the Trainer and the FLOP walk ------------------------------------------------


def test_trainer_trains_and_counts(tmp_path):
    from ddlpc_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=dataclasses.replace(tiny_config(), compute_dtype="bfloat16"),
        data=DataConfig(dataset="packed_tokens", image_size=(1, SEQ), num_classes=VOCAB,
                        synthetic_len=12, test_split=4, device_cache=True),
        train=TrainConfig(epochs=3, micro_batch_size=2, sync_period=2, learning_rate=3e-3,
                          eval_every_epochs=0, dump_images_per_epoch=0, checkpoint_every_epochs=0),
        parallel=ParallelConfig(data_axis_size=1, sync_batch_norm=False),
        workdir=str(tmp_path),
    )
    trainer = Trainer(cfg, resume=False)
    before = jax.device_get(trainer.state.params["layers_1"])
    first = trainer.train_epoch(0)
    last = trainer.fit(epochs=3)
    after = jax.device_get(trainer.state.params["layers_1"])
    trainer.close()
    assert last["loss"] < first["loss"] < np.log(VOCAB) + 0.5
    assert last["tokens_per_step"] == 4 * SEQ and last["mla_kernel_layers"] == 0.0
    assert last["moe_rows_dropped"] == 0.0 and last["moe_rows_offered"] == 4 * SEQ * 4 * 2
    np.testing.assert_array_equal(before["experts"]["expert_bias"], after["experts"]["expert_bias"])
    for name in ("gate", "w1"):
        assert not np.array_equal(before["experts"][name], after["experts"][name]), name
    for leaf in ("q_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj"):
        assert not np.array_equal(before["self_attn"][leaf]["kernel"], after["self_attn"][leaf]["kernel"]), leaf
    assert not np.array_equal(before["shared_experts"]["w1"]["kernel"], after["shared_experts"]["w1"]["kernel"])


def test_product_flops_counts_the_family_as_the_benchmarks_file():
    """``obs/flops.product_flops`` walks this family like any other: its
    forward lowered for a TPU at S = 512 (the kernels, whose cost estimate
    states the causal half) is ``deepseek_v3_flops``' forward with every pair
    routed."""
    import deepseek_v3_flops

    from ddlpc_tpu.obs import flops

    seq = 512
    cfg = ExperimentConfig(
        model=tiny_config(), data=DataConfig(dataset="packed_tokens", image_size=(1, seq), num_classes=VOCAB)
    )
    dense, grouped, has_conv = flops.product_flops(cfg, 1, 1, platform="tpu")
    assert not has_conv
    model = dataclasses.asdict(cfg.model)
    layers, routed = 3, 2
    assert grouped == routed * seq * 4 * 3 * 2 * 64 * 32  # every pair of the top-4 routed
    want = deepseek_v3_flops.step_flops(model, seq, seq, rows_routed=routed * seq * 4) / 3
    assert dense + grouped == pytest.approx(want, rel=1e-12)
    assert layers * deepseek_v3_flops.attention_flops(model, 1, seq) / 3 == layers * 2 * (24 + 16) * 4 * seq * seq / 2


# ---- the shipped configuration ------------------------------------------------------


SHIPPED = os.path.join(ROOT, "configs", "kanana2_30b_a3b_ep16.json")


def test_shipped_configuration_counts_425m_parameters():
    cfg = ExperimentConfig.from_dict(json.load(open(SHIPPED)))
    model = build_model(cfg.model)
    assert tuple(cfg.data.image_size) == (1, 8192)
    variables = jax.eval_shape(  # no leaf's shape depends on the sequence's length
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 1, 64, 1), jnp.int32), train=False)
    )
    sizes = {k: sum(x.size for x in jax.tree.leaves(v)) for k, v in variables["params"].items()}
    d = 2048
    mla = d * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + 32 * 128 * d
    norms = 2 * d
    moe = 3 * d * 1536 + d * 128 + 128 + 8 * 3 * d * 768
    assert sizes["layers_0"] == mla + 3 * d * 6144 + norms
    assert all(sizes[f"layers_{i}"] == mla + moe + norms for i in range(1, 5))
    assert sizes["embedding"] == sizes["lm_head"] == 16032 * d and sizes["final_norm"] == d
    assert sum(sizes.values()) == 424_961_024


# ---- the other decoder families' step programs, as they were ---------------------

# The digest made by test_keye_vl2.py's function on the commit before this
# family (5fb1813): the two-width kernels trace the programs of one width as
# they were.  test_keye_vl2.py pins the flagship's and lfm2_moe's,
# test_olmo_hybrid.py keye_vl2's.
OLMO_HYBRID_UNCHANGED = "07b638cc7b7e9db27fbeb3e10d8f44b09fa53f9e9db9ead435f7b2e115890c79"


def test_olmo_hybrid_lowers_as_before(monkeypatch):
    import test_keye_vl2
    import test_olmo_hybrid

    monkeypatch.setattr(lfm2_moe, "QUERY_BLOCK", QUERY_BLOCK)

    digest = test_keye_vl2.step_jaxpr_digest(
        ModelConfig(**test_olmo_hybrid.TINY), (2, 1, 512, 1), jnp.int32  # a length the kernels take
    )
    assert digest == OLMO_HYBRID_UNCHANGED
