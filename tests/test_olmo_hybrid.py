"""The olmo_hybrid family (models/olmo_hybrid.py, ops/gated_delta.py): the
program against the plain float32 reference the benchmark keeps
(benchmark/reference/olmo_hybrid.py) on logits, cross-entropy and its
gradients; the chunkwise delta rule against the token-by-token recurrence; the
tensor share tied to the uncut layer; the registry, the counters, the Trainer
and the FLOP walk; and the other two decoder families' step programs as they
were.  CPU, tiny sizes, seeded weights."""

import dataclasses
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
from reference import olmo_hybrid as reference  # noqa: E402

from ddlpc_tpu.config import (  # noqa: E402
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    TrainConfig,
)
from ddlpc_tpu.models import build_model  # noqa: E402
from ddlpc_tpu.models import olmo_hybrid as program  # noqa: E402
from ddlpc_tpu.ops import gated_delta  # noqa: E402

VOCAB, SEQ = 96, 256  # four chunks of 64
TINY = dict(
    name="olmo_hybrid", num_classes=VOCAB, hidden_size=64, intermediate_size=96,
    num_attention_heads=4, num_key_value_heads=4, tie_word_embeddings=False, norm_eps=1e-6,
    layer_types=("linear_attention",) * 3 + ("full_attention",),
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=32,
    linear_value_head_dim=64, linear_conv_kernel_dim=4, tensor_shards=2,
)


def tiny_config(**changes) -> ModelConfig:
    return ModelConfig(**{**TINY, "compute_dtype": "float32", **changes})


def tokens(seed: int, batch: int = 2, seq: int = SEQ):
    ids = jax.random.randint(jax.random.key(seed), (batch, 1, seq + 1, 1), 0, VOCAB)
    return np.asarray(ids[:, :, :seq]), np.asarray(ids[:, :, 1:, 0])


def init(cfg: ModelConfig, seed: int = 0, seq: int = SEQ):
    x = jnp.zeros((1, 1, seq, 1), jnp.int32)
    return build_model(cfg).init(jax.random.key(seed), x, train=False)["params"]


# ---- program against reference ---------------------------------------------


def compare(dtype: str, seed: int, **changes) -> dict:
    """check.py's three errors of the program computing in ``dtype`` (float32
    parameters) against the reference given the same share."""
    cfg = tiny_config(compute_dtype=dtype, **changes)
    x, y = tokens(seed)
    params = init(tiny_config(**changes), seed + 10)
    got = check.program_fn(cfg)(params, {}, x, y)
    want = check.reference_fn("olmo_hybrid", dataclasses.asdict(cfg))(params, {}, x, y)
    return {k: float(v) for k, v in check._errors(got, want).items()}


@pytest.mark.parametrize("shards", [2, 1], ids=["rank-of-two", "uncut"])
@pytest.mark.parametrize("seed", [0, 1])
def test_float32_program_is_the_reference(seed, shards):
    """The chunkwise program and the token-by-token reference agree to
    float32 rounding, as one of two ranks and as the uncut model
    (``tensor_shards`` 1 holds every head and column)."""
    out = compare("float32", seed, tensor_shards=shards)
    limits = reference.TOLERANCE["float32"]
    assert all(out[k] <= limits[k] for k in limits), out
    assert out["logits"] < 2e-5 and out["grad"] < 2e-4, out


# One DeltaNet layer under one attention layer (hidden 64, two held heads of
# 32 / 64) reads 0.017 on the logits and 0.023..0.027 on the gradients in bf16.
# Three DeltaNet layers one on another, as the period has them, read 0.03..0.05
# and 0.05..0.66 at this size: the output norm over a head divides by the
# length of a state read that can nearly cancel, a few positions then carry
# much of the gradient, and each further layer reads what the last one rounded
# (the float32 program itself moves its gradient by 0.05..0.7 when the
# embedding alone is rounded to bf16).  The published head sizes read lower
# (PERF.md section 6, PR 34); the whole period is held to float32 above.
SHALLOW = dict(layer_types=("linear_attention", "full_attention"))
TINY_BF16 = {"loss": 1e-3, "logits": 0.05, "grad": 0.08}


@pytest.mark.parametrize("seed", [0, 1])
def test_bfloat16_program_near_the_reference_and_outside_float32s_limits(seed):
    out = compare("bfloat16", seed, **SHALLOW)
    assert all(out[k] <= TINY_BF16[k] for k in TINY_BF16), out
    limits = reference.TOLERANCE["float32"]
    assert out["logits"] > limits["logits"] and out["grad"] > limits["grad"], out


def test_float8_program_is_told_from_bfloat16():
    """The nearest precision below the stated one is not a number: the
    feed-forward reads the un-normed stream (post-norm residuals), and its
    SiLU's exponential passes e4m3's largest finite value, 448."""
    out = compare("float8_e4m3fn", 0, **SHALLOW)
    assert not all(out[k] <= TINY_BF16[k] for k in TINY_BF16), out


# ---- the delta rule -----------------------------------------------------------


def delta_inputs(seed: int, repeated: bool, b=2, s=SEQ, h=3, dk=24, dv=40):
    """q, k unit vectors (q scaled), v, a log decay and ``β`` up to 1.998.
    ``repeated``: every key of a head is one of four vectors, so a chunk's
    system has many equal rows (a token that recurs writes the same key)."""
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk**-0.5
    k = unit(jax.random.normal(ks[1], (b, s, h, dk)))
    if repeated:
        pick = jax.random.randint(ks[5], (b, s, h), 0, 4)
        k = jnp.take_along_axis(k[:, :4][:, None], pick[:, :, None, :, None], axis=2)[:, :, 0]
    v = jax.random.normal(ks[2], (b, s, h, dv))
    log_decay = -0.5 * jax.random.uniform(ks[3], (b, s, h))
    beta = 2 * jax.nn.sigmoid(3 + jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, log_decay, beta


@pytest.mark.parametrize("repeated", [False, True], ids=["random-keys", "repeated-keys"])
def test_chunkwise_rule_is_the_token_recurrence(repeated):
    """Forward and every gradient, four chunks, ``β`` near 2."""
    args = delta_inputs(3, repeated)
    assert float(args[4].max()) > 1.99
    recurrence = lambda q, k, v, g, beta: reference.delta_recurrence(q, k, v, jnp.exp(g), beta)  # noqa: E731
    weight = jax.random.normal(jax.random.key(9), args[2].shape)
    got, want = gated_delta.gated_delta_rule(*args), recurrence(*args)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    g_got = jax.grad(lambda *a: jnp.sum(gated_delta.gated_delta_rule(*a) * weight), range(5))(*args)
    g_want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * weight), range(5))(*args)
    for a, b in zip(g_got, g_want):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-4


def test_chunk_is_tiling_only(monkeypatch):
    args = delta_inputs(4, False)
    want = gated_delta.gated_delta_rule(*args)
    for chunk in (16, 128, SEQ):
        monkeypatch.setattr(gated_delta, "CHUNK", chunk)
        np.testing.assert_allclose(gated_delta.gated_delta_rule(*args), want, rtol=2e-4, atol=2e-5)
    monkeypatch.setattr(gated_delta, "CHUNK", 64)
    with pytest.raises(ValueError, match="not a multiple of the chunk"):
        gated_delta.gated_delta_rule(*(x[:, :100] for x in args))


def test_unit_lower_inverse_and_its_gradient():
    a = jnp.tril(jax.random.normal(jax.random.key(0), (3, 16, 16)), -1)
    inverse = lambda a: jnp.linalg.inv(jnp.eye(16) + a)  # noqa: E731
    np.testing.assert_allclose(gated_delta.unit_lower_inverse(a), inverse(a), rtol=1e-4, atol=1e-4)
    weight = jax.random.normal(jax.random.key(1), a.shape)
    got = jax.grad(lambda a: jnp.sum(gated_delta.unit_lower_inverse(a) * weight))(a)
    want = jnp.tril(jax.grad(lambda a: jnp.sum(inverse(a) * weight))(a), -1)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


# ---- the share ----------------------------------------------------------------


def uncut_layer(kind: str, seed: int):
    """An uncut layer's parameters, its input and the uncut configuration."""
    cfg = tiny_config(tensor_shards=1, layer_types=(kind,))
    params = init(cfg, seed)["layers_0"]
    h = jax.random.normal(jax.random.key(seed + 1), (2, 1, SEQ, cfg.hidden_size))
    return cfg, params, h


def columns(p, names, rank):
    """Rank ``rank`` of 2's half of the last axis of each named leaf."""
    half = lambda leaf: leaf[..., rank * (leaf.shape[-1] // 2) : (rank + 1) * (leaf.shape[-1] // 2)]  # noqa: E731
    return dict(p, **{name: jax.tree.map(half, p[name]) for name in names})


def rows(p, name, rank):
    half = p[name]["kernel"].shape[0] // 2
    return dict(p, **{name: {"kernel": p[name]["kernel"][rank * half : (rank + 1) * half]}})


def _delta_net_shares_add_up():
    """The program's DeltaNet mixer on each rank's heads (their columns of q,
    k, v, the gate and the two scalar gates, their taps' channels, their
    decay parameters, their rows of W_o) against the uncut reference."""
    cfg, params, h = uncut_layer("linear_attention", 5)
    p = params["linear_attn"]
    want = reference.gated_delta_net(h[:, 0], p, dataclasses.asdict(cfg))
    total = 0.0
    for rank in (0, 1):
        share = columns(p, ("q_proj", "k_proj", "v_proj", "g_proj", "a_proj", "b_proj",
                            "q_conv", "k_conv", "v_conv", "A_log", "dt_bias"), rank)
        share = rows(share, "o_proj", rank)
        layer = program.GatedDeltaNet(
            cfg.hidden_size, cfg.linear_num_value_heads // 2, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim, True, cfg.norm_eps, jnp.float32,
        )
        total = total + layer.apply({"params": share}, h)
    np.testing.assert_allclose(total[:, 0], want, rtol=2e-4, atol=2e-5)


def _feed_forward_shares_add_up():
    cfg, params, h = uncut_layer("full_attention", 6)
    p = params["feed_forward"]
    want = reference.swiglu(h[:, 0], p)
    total = 0.0
    for rank in (0, 1):
        share = rows(columns(p, ("w1", "w3"), rank), "w2", rank)
        layer = program.SwiGLU(cfg.hidden_size, cfg.intermediate_size // 2, jnp.float32)
        total = total + layer.apply({"params": share}, h)
    np.testing.assert_allclose(total[:, 0], want, rtol=2e-5, atol=2e-6)


def _attention_shares_add_up():
    """The q/k norm is over the whole vector: in the job its mean square is one
    number a token summed over the ranks.  Each rank's part, handed that
    number, adds up to the uncut layer; handed none it norms over its own
    features, which is what the program's rank computes."""
    cfg, params, h = uncut_layer("full_attention", 7)
    p, model = params["self_attn"], dataclasses.asdict(cfg)
    want = reference.attention(h[:, 0], p, model)
    squares = tuple(
        jnp.mean(jnp.square(h[:, 0] @ p[name]["kernel"]), axis=-1, keepdims=True)
        for name in ("q_proj", "k_proj")
    )
    total = 0.0
    for rank in (0, 1):
        share = columns(p, ("q_proj", "k_proj", "v_proj", "q_norm", "k_norm"), rank)
        share = rows(share, "o_proj", rank)
        total = total + reference.attention(h[:, 0], share, model, squares)
        # the program's rank is the reference's rank left to its own mean square
        layer = program.Attention(cfg.hidden_size, 2, 16, cfg.norm_eps, jnp.float32)
        np.testing.assert_allclose(
            layer.apply({"params": share}, h)[:, 0], reference.attention(h[:, 0], share, model),
            rtol=2e-4, atol=2e-5,
        )
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize(
    "check_shares",
    [
        pytest.param(_delta_net_shares_add_up, id="delta-net-W_o"),
        pytest.param(_attention_shares_add_up, id="attention-W_o"),
        pytest.param(_feed_forward_shares_add_up, id="feed-forward-W2"),
    ],
)
def test_two_shares_add_up_to_the_uncut_layer(check_shares):
    """The guide's share test: the two ranks' parts of each output sum, before
    the post-norm, add up to what the uncut reference gives."""
    check_shares()


def test_a_rank_holds_half_of_every_divided_leaf():
    whole, half = init(tiny_config(tensor_shards=1)), init(tiny_config())
    sizes = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    for layer in ("layers_0", "layers_3"):
        for part in ("linear_attn", "self_attn", "feed_forward"):
            if part in whole[layer]:
                shared = sizes(whole[layer][part].get("o_norm", {}))
                assert sizes(half[layer][part]) - shared == (sizes(whole[layer][part]) - shared) // 2
    for leaf in ("embedding", "lm_head", "final_norm"):
        assert sizes(half[leaf]) == sizes(whole[leaf])


# ---- the model ------------------------------------------------------------------


def test_model_is_causal_and_counts_its_chunks():
    cfg = tiny_config()
    model, params = build_model(cfg), init(cfg, 3)
    x, _ = tokens(5)
    logits, updates = model.apply({"params": params}, x, mutable=["counters"])
    assert logits.shape == (2, 1, SEQ, VOCAB) and logits.dtype == jnp.float32
    t = 150  # inside the third chunk
    later = np.array(x)
    later[:, :, t:] = (later[:, :, t:] + 1) % VOCAB
    moved = model.apply({"params": params}, later)
    np.testing.assert_allclose(moved[:, :, :t], logits[:, :, :t], rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(moved[:, :, t:] - logits[:, :, t:]).max()) > 1e-3
    counters = updates["counters"]
    assert int(counters["sum"]["tokens_per_step"]) == 2 * SEQ
    assert int(counters["sum"]["gdn_chunks"]) == 3 * 2 * SEQ // 64
    assert int(counters["max"]["gdn_layers"]) == 3
    assert int(counters["max"]["attention_kernel_layers"]) == 0  # the XLA form on the CPU


def test_no_position_signal_but_the_order_of_the_tokens():
    """An attention layer sees positions through the causal mask alone: the
    last position's logits do not change when the earlier tokens are permuted.
    A DeltaNet layer's taps and state do see the order."""
    x, _ = tokens(6, batch=1)
    shuffled = np.array(x)
    shuffled[0, 0, : SEQ - 1, 0] = x[0, 0, : SEQ - 1, 0][::-1]
    for kind, moves in (("full_attention", False), ("linear_attention", True)):
        cfg = tiny_config(layer_types=(kind,))
        model, params = build_model(cfg), init(cfg, 4)
        a = model.apply({"params": params}, x)[0, 0, -1]
        b = model.apply({"params": params}, shuffled)[0, 0, -1]
        assert (float(jnp.abs(a - b).max()) > 1e-3) == moves, kind


@pytest.mark.parametrize(
    "changes,match",
    [
        (dict(layer_types=()), "layer_types"),
        (dict(layer_types=("conv",)), "layer_types"),
        (dict(tie_word_embeddings=True), "untied head"),
        (dict(num_key_value_heads=2), "one k/v head a query head"),
        (dict(linear_num_key_heads=2), "one key head a value head"),
        (dict(tensor_shards=3), "tensor_shards 3 does not divide"),
        (dict(tensor_shards=0), "tensor_shards 0"),
    ],
)
def test_registry_refuses_what_the_family_cannot_be(changes, match):
    with pytest.raises(ValueError, match=match):
        build_model(tiny_config(**changes))


def test_trainer_fits_two_steps_and_records_the_counters(tmp_path):
    from ddlpc_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=tiny_config(compute_dtype="bfloat16"),
        data=DataConfig(dataset="packed_tokens", image_size=(1, SEQ), num_classes=VOCAB,
                        synthetic_len=10, test_split=2),
        train=TrainConfig(epochs=1, micro_batch_size=1, sync_period=2, learning_rate=3e-3,
                          optimizer="adam", eval_every_epochs=0, checkpoint_every_epochs=0,
                          dump_images_per_epoch=0),
        workdir=str(tmp_path),
    )
    cfg = cfg.replace(parallel=dataclasses.replace(cfg.parallel, data_axis_size=1))
    trainer = Trainer(cfg, resume=False)
    before = jax.device_get(trainer.state.params["layers_0"]["linear_attn"])
    record = trainer.fit(epochs=1)
    trainer.close()
    assert np.isfinite(record["loss"])
    assert record["tokens_per_step"] == 2 * SEQ and record["gdn_chunks"] == 3 * 2 * SEQ // 64
    assert record["gdn_layers"] == 3 and record["attention_kernel_layers"] == 0
    assert record["gdn_kernel_layers"] == 0  # the CPU lowers the walk to the scan
    after = jax.device_get(trainer.state.params["layers_0"]["linear_attn"])
    for name in ("A_log", "dt_bias", "q_conv"):
        assert not np.array_equal(before[name], after[name]), name


# ---- the other decoder families' step programs, as they were ---------------------

# The digest made by test_keye_vl2.py's function; that file pins the
# flagship's and lfm2_moe's the same way.  PR 34 (this family) left the
# parent's (bbe0714: dd6c9d7e…) as it was; PR 35 changed keye_vl2 itself (the
# index scores got their kernels at lengths they take, 512 here) and made it
# again on its own tree.
KEYE_VL2_UNCHANGED = "f68f772cdcd5a9cc45111a883edc65d75b69be08c3864372fc66e39a0a063f07"


def test_keye_vl2_lowers_as_before():
    import test_keye_vl2

    digest = test_keye_vl2.step_jaxpr_digest(
        ModelConfig(**test_keye_vl2.TINY), (2, 1, 512, 1), jnp.int32
    )
    assert digest == KEYE_VL2_UNCHANGED


# ---- the program's FLOP model ----------------------------------------------------


def test_product_flops_counts_the_scan_by_its_length():
    """``obs/flops.product_flops`` walks this family like any other: the
    scan's four products a chunk count ``S / 64`` times."""
    from ddlpc_tpu.obs import flops

    cfg = ExperimentConfig(
        model=tiny_config(layer_types=("linear_attention",)),
        data=DataConfig(dataset="packed_tokens", image_size=(1, SEQ), num_classes=VOCAB),
    )
    dense, grouped, has_conv = flops.product_flops(cfg, 1, channels=1)
    assert grouped == 0 and not has_conv
    d, heads, dk, dv, cols, c = 64, 2, 32, 64, 48, 64
    projections = 2 * d * heads * (2 * dk + 3 * dv + 2) + 3 * 2 * d * cols + 2 * d * VOCAB
    per_head = 2 * (2 * c * dk + c * (dk + dv) + 3 * dk * dv + c * dv)  # olmo_hybrid_flops' count
    inverse = 2 * c * c  # the substitution's row times the rows above it, a token and head
    assert dense == SEQ * (projections + heads * (per_head + inverse))
