"""The lfm2_moe family (models/lfm2_moe.py) at a tiny size on the CPU: the
program against the plain float32 reference the benchmark keeps
(benchmark/reference/lfm2_moe.py), the expert-parallel shares against the
uncut layer, routing, the no-drop grouping, attention, the token data path,
the Trainer, and the shipped configuration.

Tiny: hidden 64, 4/2 heads of 16, 8 experts top-2 (2 held), vocabulary 128,
S 64, one dense layer then attention, conv, conv, conv.
"""

import dataclasses
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
from reference import lfm2_moe as reference  # noqa: E402

from ddlpc_tpu.config import (  # noqa: E402
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    ParallelConfig,
    TrainConfig,
)
from ddlpc_tpu.models import build_model  # noqa: E402
from ddlpc_tpu.models import lfm2_moe as program  # noqa: E402

VOCAB, SEQ = 128, 64
TINY = dict(
    name="lfm2_moe", num_classes=VOCAB, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=48, num_attention_heads=4, num_key_value_heads=2,
    num_experts=8, num_experts_per_tok=2, experts_held=2, expert_offset=2,
    num_dense_layers=1, layer_types=("conv", "full_attention", "conv", "conv", "conv"),
)


@pytest.fixture(autouse=True)
def several_query_blocks(monkeypatch):
    """Four query blocks at S = 64, where the module's 512 would make one."""
    monkeypatch.setattr(program, "QUERY_BLOCK", 16)


def tiny_config(**changes) -> ModelConfig:
    return ModelConfig(**{**TINY, "compute_dtype": "float32", **changes})


def tokens(seed: int, batch: int = 2, seq: int = SEQ):
    ids = jax.random.randint(jax.random.key(seed), (batch, 1, seq + 1, 1), 0, VOCAB)
    return np.asarray(ids[:, :, :seq]), np.asarray(ids[:, :, 1:, 0])


def init(cfg: ModelConfig, seed: int = 0, seq: int = SEQ):
    x = jnp.zeros((1, 1, seq, 1), jnp.int32)
    return build_model(cfg).init(jax.random.key(seed), x, train=False)["params"]


# ---- program against reference ---------------------------------------------


def compare(dtype: str, seed: int, batch: int = 4, seq: int = 256) -> dict:
    """Errors of the program computing in ``dtype`` (float32 parameters)."""
    cfg = tiny_config(compute_dtype=dtype)
    program.QUERY_BLOCK = min(64, seq)  # four blocks of 256 (the fixture restores it)
    x, y = tokens(seed, batch, seq)
    got = check.program_fn(cfg)(init(tiny_config(), seed + 10, seq), {}, x, y)
    want = check.reference_fn("lfm2_moe", dataclasses.asdict(cfg))(
        init(tiny_config(), seed + 10, seq), {}, x, y
    )
    return {k: float(v) for k, v in check._errors(got, want).items()}


@pytest.mark.parametrize("seed", [0, 1])
def test_float32_program_is_the_reference(seed):
    out = compare("float32", seed, batch=2, seq=SEQ)
    limits = reference.TOLERANCE["float32"]
    assert all(out[k] <= limits[k] for k in limits), out
    assert out["logits"] < 1e-5 and out["grad"] < 1e-4, out


# At this size one flipped selection moves 1/256 of a layer's rows outright,
# and bf16 activations flip a few: logits read 0.06..0.12 here where the chip,
# at the published widths, reads 0.057 (reference.TOLERANCE's notes).
TINY_BF16 = {"loss": 1e-3, "logits": 0.2, "grad": 0.3}


@pytest.mark.parametrize("seed", [0, 1])
def test_bfloat16_program_near_the_reference_and_outside_float32s_limits(seed):
    out = compare("bfloat16", seed)
    assert all(out[k] <= TINY_BF16[k] for k in TINY_BF16), out
    limits = reference.TOLERANCE["float32"]
    assert out["logits"] > limits["logits"] and out["grad"] > limits["grad"], out


def test_float8_program_is_told_from_bfloat16():
    """The nearest precision below the stated one fails even the tiny size's
    bound, by logits and gradients (the loss hardly moves)."""
    out = compare("float8_e4m3fn", 0)
    assert out["logits"] > TINY_BF16["logits"] and out["grad"] > TINY_BF16["grad"], out


# ---- the shares add up --------------------------------------------------------


def test_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts, each computed by the program's routed
    layer, sum to what the reference gives with all eight experts held."""
    cfg = tiny_config()
    hidden, width, n_exp = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    keys = jax.random.split(jax.random.key(3), 6)
    u = jax.random.normal(keys[0], (2, 1, SEQ, hidden), jnp.float32)
    whole = {
        "gate": jax.random.normal(keys[1], (hidden, n_exp)) * 0.3,
        "expert_bias": jax.random.normal(keys[2], (n_exp,)) * 0.05,
        "w1": jax.random.normal(keys[3], (n_exp, hidden, width)) * 0.1,
        "w3": jax.random.normal(keys[4], (n_exp, hidden, width)) * 0.1,
        "w2": jax.random.normal(keys[5], (n_exp, width, hidden)) * 0.1,
    }
    uncut = dict(dataclasses.asdict(cfg), experts_held=n_exp, expert_offset=0)
    want = reference.routed_experts(u[:, 0], whole, uncut)
    total, routed = 0.0, 0
    for offset in range(0, n_exp, 2):
        layer = program.RoutedExperts(
            hidden, width, n_exp, cfg.num_experts_per_tok, 2, offset, dtype=jnp.float32
        )
        share = dict(whole, **{k: whole[k][offset : offset + 2] for k in ("w1", "w3", "w2")})
        part, counts = layer.apply({"params": share}, u)
        total = total + part
        routed += int(counts["sum"]["moe_rows_routed"])
        assert int(counts["sum"]["moe_rows_dropped"]) == 0
    np.testing.assert_allclose(total[:, 0], want, rtol=2e-5, atol=2e-6)
    # every (token, expert) pair is computed by exactly one share
    assert routed == 2 * SEQ * cfg.num_experts_per_tok


# ---- routing ---------------------------------------------------------------------


def held_layer(cfg):
    """The routed layer holding the experts ``cfg`` says."""
    return program.RoutedExperts(
        hidden=cfg.hidden_size, width=cfg.moe_intermediate_size, num_experts=cfg.num_experts,
        top_k=cfg.num_experts_per_tok, experts_held=cfg.experts_held,
        expert_offset=cfg.expert_offset, dtype=jnp.float32,
    )


def routed_layer(cfg):
    """The routed layer with every expert held."""
    return held_layer(dataclasses.replace(cfg, experts_held=cfg.num_experts, expert_offset=0))


def test_bias_selects_and_does_not_weigh():
    """A bias large enough to fix the selection changes which experts run and
    leaves each selected expert's weight a function of the scores alone."""
    cfg = tiny_config()
    layer = routed_layer(cfg)
    u = jax.random.normal(jax.random.key(0), (1, 1, 32, cfg.hidden_size))
    params = layer.init(jax.random.key(1), u)["params"]
    forced = jnp.zeros(cfg.num_experts).at[jnp.array([1, 6])].set(10.0)
    out, _ = layer.apply({"params": dict(params, expert_bias=forced)}, u)
    scores = jax.nn.sigmoid(u[0, 0] @ params["gate"])
    w = scores[:, [1, 6]] / (scores[:, [1, 6]].sum(-1, keepdims=True) + 1e-6)
    want = sum(
        w[:, j : j + 1] * reference.swiglu(u[0, 0], params["w1"][e], params["w3"][e], params["w2"][e])
        for j, e in enumerate((1, 6))
    )
    np.testing.assert_allclose(out[0, 0], want, rtol=2e-5, atol=2e-6)
    # and the bias takes no gradient
    grads = jax.grad(lambda p: layer.apply({"params": p}, u)[0].sum())(params)
    assert float(jnp.abs(grads["expert_bias"]).max()) == 0.0
    assert float(jnp.abs(grads["gate"]).max()) > 0.0


def test_weights_are_normalised_over_the_selected():
    """With every expert the identity-like same expert, the layer's output is
    (sum of the selected weights) x that expert's output: 1 - O(1e-6)."""
    cfg = tiny_config()
    layer = routed_layer(cfg)
    u = jax.random.normal(jax.random.key(2), (1, 1, 32, cfg.hidden_size))
    params = layer.init(jax.random.key(3), u)["params"]
    same = {k: jnp.broadcast_to(params[k][:1], params[k].shape) for k in ("w1", "w3", "w2")}
    out, _ = layer.apply({"params": dict(params, **same)}, u)
    one = reference.swiglu(u[0, 0], params["w1"][0], params["w3"][0], params["w2"][0])
    np.testing.assert_allclose(out[0, 0], one, rtol=1e-4, atol=1e-6)


def test_router_keeps_every_score_at_any_share():
    """The router's kernel and bias have the published width whatever is held."""
    params = init(tiny_config(experts_held=2))
    ffn = params["layers_1"]["feed_forward"]
    assert ffn["gate"].shape == (64, 8) and ffn["expert_bias"].shape == (8,)
    assert ffn["w1"].shape == (2, 64, 48) and ffn["w2"].shape == (2, 48, 64)
    with pytest.raises(ValueError, match="not among the router's"):
        build_model(tiny_config(experts_held=4, expert_offset=6))


# ---- no token dropped -----------------------------------------------------------


def test_no_drop_under_the_worst_skew_and_no_recompilation():
    """Every token identical: all rows go to the same two experts.  Held or
    not, nothing is dropped, the groups hold exactly the routed rows, and the
    skewed batch runs the program compiled for the balanced one."""
    cfg = tiny_config(experts_held=8, expert_offset=0)
    model = build_model(cfg)
    params = init(cfg)
    compiles = []

    @jax.jit
    def run(params, x):
        compiles.append(1)
        _, aux = model.apply(
            {"params": params}, x, train=True, mutable=["counters", "intermediates"]
        )
        return aux

    balanced, _ = tokens(0)
    skewed = np.full_like(balanced, 7)
    for x, full in ((balanced, False), (skewed, True)):
        aux = run(params, x)
        counters = aux["counters"]["sum"]
        sizes = [
            np.asarray(v["feed_forward"]["group_sizes"][0])
            for v in aux["intermediates"].values()
        ]
        assert int(counters["moe_rows_dropped"]) == 0
        assert int(counters["moe_rows_routed"]) == sum(int(s.sum()) for s in sizes)
        assert int(counters["moe_rows_routed"]) == int(counters["moe_rows_offered"])
        assert int(counters["tokens_per_step"]) == x.size
        if full:  # nearly all of a layer's rows in top-k groups: load ~ experts / k
            assert float(aux["counters"]["max"]["moe_max_load"]) > 3.5
    assert len(compiles) == 1


@pytest.mark.parametrize("held", [8, 2])
def test_a_row_the_products_leave_unwritten_is_counted_as_dropped(
    monkeypatch, small_row_tiles, held
):
    """moe_rows_dropped reads the grouped products' output, not the routing
    that fed them: a held row that comes back unwritten shows in it, on all
    the pairs (every expert held) and on the compact buffer (2 of 8)."""
    cfg = tiny_config(experts_held=held, expert_offset=0)
    layer = held_layer(cfg)
    u = jax.random.normal(jax.random.key(0), (1, 1, 32, cfg.hidden_size))
    params = layer.init(jax.random.key(1), u)["params"]
    _, counts = layer.apply({"params": params}, u)
    assert int(counts["sum"]["moe_rows_dropped"]) == 0
    compact = int(counts["sum"]["moe_rows_buffered"]) < int(counts["sum"]["moe_rows_offered"])
    assert compact == (held == 2) and int(counts["sum"]["moe_rows_routed"]) >= 3
    whole = jax.lax.ragged_dot

    def leaky(lhs, rhs, group_sizes, **kw):
        return whole(lhs, rhs, group_sizes=group_sizes, **kw).at[:3].set(jnp.nan)

    monkeypatch.setattr(program.lax, "ragged_dot", leaky)
    _, counts = layer.apply({"params": params}, u)
    assert int(counts["sum"]["moe_rows_dropped"]) == 3


def test_skewed_batch_matches_the_reference():
    cfg = tiny_config()
    x = np.full((2, 1, SEQ, 1), 5, np.int32)
    y = np.full((2, 1, SEQ), 9, np.int32)
    out = check.compare(cfg, "lfm2_moe", init(cfg), {}, x, y)
    assert out["ok"], out


# ---- the compact row buffer ---------------------------------------------------------
# 2 of 8 experts held, top-2: twice the share is half the pairs (128 of the
# 256 of two 64-token tiles), and the fixture lets a gather's table hold 96
# float32 rows of the tiny width, in whole tiles of 8 rows (512 would cover
# every pair): a buffer of 96 rows, three of them at the worst skew.

ROW_BYTES = 4 * TINY["hidden_size"]


@pytest.fixture
def small_row_tiles(monkeypatch):
    monkeypatch.setattr(program, "BUFFER_ROW_MULTIPLE", 8)
    monkeypatch.setattr(program, "BUFFER_TABLE_BYTES", 96 * ROW_BYTES)


def test_buffer_rows_come_from_shapes_alone():
    bf16 = 2 * 2048  # a row of the published width
    # the cell (micro 4 x 8,192 x top-4): twice the share is 32,768 rows, 96 MiB hold 24,576
    assert program.buffer_rows(131072, 8, 64, bf16) == 24576
    assert program.buffer_rows(131072, 8, 64, bf16 // 2) == 32768  # half the width: the share rules
    assert program.buffer_rows(131072, 8, 64, 4 * bf16) == 6144  # wider rows: the bytes rule
    assert program.buffer_rows(32768, 8, 64, bf16) == 8192  # the reference check's one sequence
    assert program.buffer_rows(65536, 8, 64, bf16) == 16384  # micro 2
    # every pair may be held: all of them in one buffer, whatever their bytes
    assert program.buffer_rows(131072, 64, 64, bf16) == program.buffer_rows(131072, 32, 64, bf16) == 131072
    assert program.buffer_rows(256, 2, 8, 256) == 256 and program.buffer_rows(4096, 2, 8, 256) == 2048
    assert program.buffer_rows(1000, 1, 3, 256) == 1000 and program.buffer_rows(3000, 1, 8, 256) == 1024
    assert program.buffer_rows(3000, 1, 8, 1 << 30) == 512  # never less than a tile


def value_counters_and_gradients(cfg, params, x, y):
    """Loss, logits, the step's counters, and the gradients of every parameter
    and of the embedded input, of the model in training mode."""
    model = build_model(cfg)

    def loss_fn(params, nudge):
        # the input to the layers is a row of the embedding: its gradient
        # through the layers alone, the tied head's part left out
        perturbed = dict(params, embedding=params["embedding"] + nudge)
        logits, aux = model.apply({"params": perturbed}, x, train=True, mutable=["counters"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()
        return loss, (logits, aux["counters"])

    nudge = jnp.zeros_like(params["embedding"])
    (loss, (logits, counters)), grads = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(
        params, nudge
    )
    return loss, logits, counters, grads


def test_compact_buffer_is_the_full_buffer(monkeypatch, small_row_tiles):
    """With the capacity rule forced to all the pairs and at its own value:
    the same outputs, counters and gradients of every parameter and the input."""
    cfg = tiny_config()
    params, (x, y) = init(cfg), tokens(6)
    compact = value_counters_and_gradients(cfg, params, x, y)
    monkeypatch.setattr(program, "buffer_rows", lambda pairs, *_: pairs)
    full = value_counters_and_gradients(cfg, params, x, y)
    pairs, layers = x.size * cfg.num_experts_per_tok, 4
    assert int(full[2]["sum"].pop("moe_rows_buffered")) == layers * pairs
    assert int(compact[2]["sum"].pop("moe_rows_buffered")) == layers * 96 < layers * pairs
    for a, b in zip(jax.tree.leaves(compact), jax.tree.leaves(full)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    assert int(compact[2]["sum"]["moe_rows_dropped"]) == 0
    assert 0 < int(compact[2]["sum"]["moe_rows_routed"]) < layers * 96


def test_routed_layer_gradients_on_the_compact_buffer(monkeypatch, small_row_tiles):
    """The layer alone: output and the gradients of the input and of every
    parameter, the block's own backward on the compact buffer against reverse
    mode through the plain forward on all the pairs."""
    cfg = tiny_config(expert_offset=0)
    layer = held_layer(cfg)
    u = jax.random.normal(jax.random.key(0), (2, 1, SEQ, cfg.hidden_size))
    params = layer.init(jax.random.key(1), u)["params"]
    probe = jax.random.normal(jax.random.key(2), u.shape)

    def run(params, u):
        out, counts = layer.apply({"params": params}, u)
        return (out * probe).sum(), (out, counts)

    (_, (out, counts)), grads = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, u)
    assert int(counts["sum"]["moe_rows_buffered"]) == 96 < int(counts["sum"]["moe_rows_offered"])

    def plain(rows, *operands):
        return program._buffer_forward(operands[4].size, 0, *operands)[:2]

    monkeypatch.setattr(program, "_expert_block", plain)
    (_, (want, _)), want_grads = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, u)
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(grads[0]["w2"]).max()) > 0 and float(jnp.abs(grads[1]).max()) > 0


@pytest.mark.parametrize("skewed", [False, True])
def test_buffers_that_do_not_divide_the_pairs(monkeypatch, small_row_tiles, skewed):
    """50 tokens, 100 pairs, buffers of 40 rows: the sorted rows are padded to
    three buffers, and at the worst skew the third holds 20 routed rows and a
    token's two pairs lie in different buffers.  Output, counters and
    gradients are those of one buffer of all the pairs."""
    monkeypatch.setattr(program, "BUFFER_TABLE_BYTES", 40 * ROW_BYTES)
    cfg = tiny_config(expert_offset=0)
    layer = held_layer(cfg)
    u = jax.random.normal(jax.random.key(0), (1, 1, 50, cfg.hidden_size))
    params = layer.init(jax.random.key(1), u)["params"]
    if skewed:
        params = dict(params, expert_bias=jnp.zeros(8).at[:2].set(10.0))
    probe = jax.random.normal(jax.random.key(2), u.shape)

    def run(params, u):
        out, counts = layer.apply({"params": params}, u)
        return (out * probe).sum(), (out, counts)

    got = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, u)
    monkeypatch.setattr(program, "buffer_rows", lambda pairs, *_: pairs)
    want = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, u)
    counts, whole = got[0][1][1]["sum"], want[0][1][1]["sum"]
    assert int(counts["moe_rows_buffered"]) == (120 if skewed else 40)
    assert int(whole["moe_rows_buffered"]) == 100 == int(counts["moe_rows_offered"])
    assert int(counts["moe_rows_routed"]) == (100 if skewed else int(whole["moe_rows_routed"]))
    assert int(counts["moe_rows_dropped"]) == int(whole["moe_rows_dropped"]) == 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if a.shape == b.shape and jnp.issubdtype(a.dtype, jnp.floating):
            # float32 sums in another order: the gate's gradient is of the order of 10
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5)


def test_overflowing_the_buffer_falls_back_on_the_device(small_row_tiles):
    """A batch skewed so that every pair is held overflows the buffer: the
    block runs on buffer after buffer until they hold all the pairs, nothing
    is dropped, the result is the reference's, and one jit served the
    balanced and the skewed batch."""
    cfg = tiny_config()
    model, params = build_model(cfg), init(cfg)
    balanced, y = tokens(0)
    pairs, layers = balanced.size * cfg.num_experts_per_tok, 4
    compiles = []

    @jax.jit
    def run(params, x, y):
        compiles.append(1)

        def loss_fn(params):
            logits, aux = model.apply({"params": params}, x, train=True, mutable=["counters"])
            return logits.astype(jnp.float32).mean(), aux["counters"]["sum"]

        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    (_, counters), _ = run(params, balanced, y)
    assert int(counters["moe_rows_buffered"]) == layers * 96
    assert int(counters["moe_rows_dropped"]) == 0

    # every pair held in every routed layer: a bias that selects the held two
    forced = jnp.full((cfg.num_experts,), -10.0, jnp.float32).at[jnp.array([2, 3])].set(10.0)
    skewed = {
        name: dict(layer, feed_forward=dict(layer["feed_forward"], expert_bias=forced))
        if name.startswith("layers_") and "expert_bias" in layer["feed_forward"]
        else layer
        for name, layer in params.items()
    }
    (_, counters), grads = run(skewed, balanced, y)
    assert len(compiles) == 1
    assert int(counters["moe_rows_routed"]) == int(counters["moe_rows_offered"]) == layers * pairs
    assert int(counters["moe_rows_buffered"]) == layers * 3 * 96  # whole buffers: 288 rows for 256
    assert int(counters["moe_rows_dropped"]) == 0
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))
    out = check.compare(cfg, "lfm2_moe", skewed, {}, balanced, y)
    assert out["ok"], out


# ---- attention and rotary positions ------------------------------------------------


def plain_attention(q, k, v):
    b, s, h, d = q.shape
    k = jnp.repeat(k, h // k.shape[2], axis=2)
    v = jnp.repeat(v, h // v.shape[2], axis=2)
    scores = jnp.einsum("bqhd,bthd->bhqt", q, k) / np.sqrt(d)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqt,bthd->bqhd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("block", [8, 16, 64])
def test_blocked_causal_attention_is_plain_attention(block):
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (2, 64, 4, 16))
    k = jax.random.normal(keys[1], (2, 64, 2, 16))
    v = jax.random.normal(keys[2], (2, 64, 2, 16))
    got = program.blocked_causal_attention(q, k, v, block)
    np.testing.assert_allclose(got, plain_attention(q, k, v), rtol=1e-5, atol=1e-5)
    g_got = jax.grad(lambda q: program.blocked_causal_attention(q, k, v, block).sum())(q)
    g_want = jax.grad(lambda q: plain_attention(q, k, v).sum())(q)
    np.testing.assert_allclose(g_got, g_want, rtol=1e-4, atol=1e-5)


def test_query_block_must_divide_the_sequence():
    q = jnp.zeros((1, 24, 4, 16))
    with pytest.raises(ValueError, match="not a multiple"):
        program.blocked_causal_attention(q, q[:, :, :2], q[:, :, :2], 16)


def test_rotary_positions_turn_pairs_and_keep_relative_phase():
    cos, sin = program.rotary_tables(32, 16, 1e6)
    x = jax.random.normal(jax.random.key(0), (1, 32, 2, 16))
    got = program.apply_rotary(x, cos, sin)
    np.testing.assert_allclose(got, reference.rotary(x, 1e6), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)  # position 0 is unturned
    # <rot(q, m), rot(k, n)> depends on m - n alone
    q, k = x[0, 0, 0], x[0, 1, 0]
    spin = lambda vec, t: program.apply_rotary(  # noqa: E731
        jnp.broadcast_to(vec, (1, 32, 1, 16)), cos, sin
    )[0, t, 0]
    assert float(spin(q, 5) @ spin(k, 2)) == pytest.approx(float(spin(q, 13) @ spin(k, 10)), rel=1e-4)


# ---- the whole model ---------------------------------------------------------------


def test_model_is_causal():
    """Logits at positions <= t do not move when tokens after t change."""
    cfg = tiny_config()
    model, params = build_model(cfg), init(cfg)
    x, _ = tokens(4)
    changed = x.copy()
    changed[:, :, 40:] = (changed[:, :, 40:] + 17) % VOCAB
    a = model.apply({"params": params}, x)
    b = model.apply({"params": params}, changed)
    np.testing.assert_array_equal(np.asarray(a[:, :, :40]), np.asarray(b[:, :, :40]))
    assert float(jnp.abs(a[:, :, 40:] - b[:, :, 40:]).max()) > 0


def test_float_ids_are_cast_and_train_mode_is_the_same_function():
    cfg = tiny_config()
    model, params = build_model(cfg), init(cfg)
    x, _ = tokens(5)
    plain = model.apply({"params": params}, x)
    as_float = model.apply({"params": params}, x.astype(np.float32))
    remat, aux = model.apply({"params": params}, x, train=True, mutable=["counters"])
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(as_float))
    np.testing.assert_allclose(plain, remat, rtol=1e-6, atol=1e-6)
    assert set(aux["counters"]["sum"]) == {
        "tokens_per_step", "moe_rows_routed", "moe_rows_offered", "moe_rows_dropped",
        "moe_rows_buffered",
    }
    assert set(aux["counters"]["max"]) == {"moe_max_load", "attention_kernel_layers"}
    assert plain.shape == (2, 1, SEQ, VOCAB) and plain.dtype == jnp.float32


def test_layer_types_are_required():
    with pytest.raises(ValueError, match="layer_types"):
        build_model(tiny_config(layer_types=()))
    with pytest.raises(ValueError, match="layer_types"):
        build_model(tiny_config(layer_types=("conv", "mamba")))


def flops_config() -> ExperimentConfig:
    return ExperimentConfig(
        model=tiny_config(),
        data=DataConfig(dataset="packed_tokens", image_size=(1, SEQ), num_classes=VOCAB),
    )


def test_step_flops_counts_every_product_and_the_grouped_ones_apart():
    from ddlpc_tpu.obs import flops

    import seq_flops

    cfg = flops_config()
    dense, grouped = flops.step_flops(cfg, 2, 3, channels=1)
    model = dataclasses.asdict(cfg.model)
    offered = 3 * 2 * SEQ * cfg.model.num_experts_per_tok * 4  # a step's pairs, 4 routed layers
    # every row offered in a group: three products a row, forward and backward
    assert grouped == seq_flops.expert_flops(model, offered) * 3
    # the program's walk counts the full S x S scores the blocks compute beyond
    # the causal half, the benchmark's count the causal half alone
    want = seq_flops.step_flops(model, SEQ, 3 * 2, 0)
    assert want <= dense <= 1.1 * want


@pytest.mark.parametrize(
    "held, micro, rows",
    [(2, 8, 512), (2, 12, 1024), (1, 32, 1024), (2, 32, 2048), (8, 8, 1024)],
)
def test_grouped_flops_are_per_row_times_routed_rows_whatever_the_buffer(held, micro, rows):
    """128 pairs a sequence and layer.  The walk sees a routed layer's buffer
    once outside the loop over later buffers and once in it: together as many
    rows as the pairs (2 x 512 of 1,024; 2 x 2,048 of 4,096), more (2 x 1,024
    of 1,536) or fewer (2 x 1,024 of 4,096), and one buffer of all the pairs
    with no loop where every expert is held.  It counts what a row costs for
    every pair either way, and the accountant scales it by the routed rows."""
    from ddlpc_tpu.obs import flops
    from ddlpc_tpu.obs.registry import MetricsRegistry

    import seq_flops

    cfg = flops_config()
    cfg = cfg.replace(model=dataclasses.replace(cfg.model, experts_held=held, expert_offset=0))
    pairs = micro * SEQ * cfg.model.num_experts_per_tok
    assert program.buffer_rows(pairs, held, 8, ROW_BYTES) == rows
    dense, grouped = flops.step_flops(cfg, micro, 3, channels=1)
    model = dataclasses.asdict(cfg.model)
    offered = 3 * pairs * 4  # sync 3, four routed layers
    per_row = seq_flops.expert_flops(model, 1) * 3  # three products, forward and backward
    assert grouped == per_row * offered
    perf = flops.PerfAccountant(
        MetricsRegistry(), flops_per_step=dense, grouped_flops_per_step=grouped, peak_flops=1e12
    )
    routed = offered * held // 8
    perf.routed(rows_routed=routed, rows_offered=offered)
    assert perf.flops_per_step == dense + per_row * routed


def test_step_flops_goes_by_the_traced_program_not_by_a_name():
    """The conv zoo's count is conv_step_flops and nothing else; a family
    without a convolution is not sent through the conv walk."""
    from ddlpc_tpu.obs import flops

    conv = ExperimentConfig(
        model=ModelConfig(name="unet", features=(4, 8), bottleneck_features=8, num_classes=3),
        data=DataConfig(image_size=(32, 32), num_classes=3),
    )
    assert flops.step_flops(conv, 2, 3) == (flops.conv_step_flops(conv, 2, 3), 0)
    assert flops.product_flops(conv, 2)[2] and not flops.product_flops(flops_config(), 2, 1)[2]


def test_accountant_counts_grouped_products_by_the_routed_rows():
    from ddlpc_tpu.obs import flops
    from ddlpc_tpu.obs.registry import MetricsRegistry

    perf = flops.PerfAccountant(
        MetricsRegistry(), flops_per_step=1000, grouped_flops_per_step=800, peak_flops=1e3
    )
    assert perf.flops_per_step == 1000 and perf.mfu(2.0) == pytest.approx(0.5)
    perf.routed(rows_routed=25, rows_offered=200)
    assert perf.flops_per_step == 1100 and perf.mfu(2.0) == pytest.approx(0.55)
    assert perf.publish(step_time_s=2.0)["flops_per_step"] == 1100


# ---- data ---------------------------------------------------------------------------


def test_packed_token_tiles():
    from ddlpc_tpu.data.datasets import PackedTokenTiles

    ds = PackedTokenTiles(num_tiles=6, image_size=(1, 512), num_classes=VOCAB, seed=2147495993)
    again = PackedTokenTiles(num_tiles=6, image_size=(1, 512), num_classes=VOCAB, seed=2147495993)
    other = PackedTokenTiles(num_tiles=6, image_size=(1, 512), num_classes=VOCAB, seed=7)
    assert ds.images.dtype == np.int32 and ds.images.shape == (6, 1, 512, 1)
    assert ds.labels.dtype == np.int32 and ds.labels.shape == (6, 1, 512)
    np.testing.assert_array_equal(ds.images, again.images)
    assert not np.array_equal(ds.images, other.images)
    assert ds.images.min() >= 0 and ds.images.max() < VOCAB and ds.labels.min() >= 0
    # labels are the next token, inside a tile
    np.testing.assert_array_equal(ds.images[:, 0, 1:, 0], ds.labels[:, 0, :-1])
    # documents end in the reserved id, 16..S tokens apart, Zipf-headed ids between
    ends = np.flatnonzero(np.concatenate([ds.images[:, 0, :, 0], ds.labels[:, :, -1]], 1) == 0)
    assert len(ends) >= 3 and np.diff(ends).min() >= 16
    counts = np.bincount(ds.images.ravel(), minlength=VOCAB)
    assert counts[1] > counts[10] > counts[100]


def test_token_tiles_stay_int32_through_both_loaders():
    from ddlpc_tpu.data.datasets import PackedTokenTiles
    from ddlpc_tpu.data.loader import DeviceCachedLoader, ShardedLoader
    from ddlpc_tpu.parallel.mesh import make_mesh

    ds = PackedTokenTiles(num_tiles=8, image_size=(1, SEQ), num_classes=VOCAB, seed=1)
    mesh = make_mesh(ParallelConfig(data_axis_size=1), devices=jax.devices()[:1])
    for loader_cls in (DeviceCachedLoader, ShardedLoader):
        loader = loader_cls(ds, mesh, global_micro_batch=2, sync_period=2, seed=0)
        x, y = next(iter(loader))
        assert x.dtype == jnp.int32 and x.shape == (2, 2, 1, SEQ, 1), loader_cls
        assert y.dtype == jnp.int32
        rows = {tuple(r) for r in ds.images[:, 0, :, 0].tolist()}
        assert all(tuple(r) in rows for r in np.asarray(x).reshape(4, SEQ).tolist())
        with pytest.raises(ValueError, match="token"):
            loader_cls(ds, mesh, global_micro_batch=2, sync_period=2, compact=True)


# ---- the Trainer ----------------------------------------------------------------------


def tiny_experiment(workdir: str, **train) -> ExperimentConfig:
    return ExperimentConfig(
        model=tiny_config(),
        data=DataConfig(
            dataset="packed_tokens", image_size=(1, SEQ), num_classes=VOCAB,
            synthetic_len=12, test_split=4, device_cache=True,
        ),
        train=TrainConfig(**{
            "epochs": 6, "micro_batch_size": 2, "sync_period": 2, "learning_rate": 3e-3,
            "eval_every_epochs": 0, "dump_images_per_epoch": 0, "checkpoint_every_epochs": 0,
            **train,
        }),
        parallel=ParallelConfig(data_axis_size=1, sync_batch_norm=False),
        workdir=workdir,
    )


def test_trainer_fits_counts_saves_and_restores(tmp_path):
    from ddlpc_tpu.train.trainer import Trainer

    cfg = tiny_experiment(str(tmp_path / "run"), checkpoint_every_epochs=6)
    trainer = Trainer(cfg, resume=False)
    assert trainer.state.batch_stats == {}
    first = trainer.train_epoch(0)
    last = trainer.fit(epochs=6)
    trainer.close()
    assert last["loss"] < first["loss"] < np.log(VOCAB) + 0.5
    assert last["moe_rows_dropped"] == 0.0
    assert last["tokens_per_step"] == 4 * SEQ
    assert last["moe_rows_offered"] == 4 * SEQ * 2 * 4  # tokens x k x routed layers
    assert 0 < last["moe_rows_routed"] < last["moe_rows_offered"]
    assert last["moe_max_load"] >= 1.0
    assert trainer.registry.get("ddlpc_moe_rows_routed") is not None
    # in the record and a gauge; 0: no attention operator lowers to the kernel on the CPU
    assert last["attention_kernel_layers"] == 0.0
    assert trainer.registry.get("ddlpc_attention_kernel_layers") is not None
    params = jax.device_get(trainer.state.params)

    resumed = Trainer(cfg, resume=True)
    assert resumed.start_epoch == 6
    restored = jax.device_get(resumed.state.params)
    resumed.close()
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(a, b)


def test_expert_bias_does_not_move_under_adam(tmp_path):
    from ddlpc_tpu.train.trainer import Trainer

    trainer = Trainer(tiny_experiment(str(tmp_path)), resume=False)
    before = jax.device_get(trainer.state.params["layers_2"]["feed_forward"])
    trainer.train_epoch(0)
    after = jax.device_get(trainer.state.params["layers_2"]["feed_forward"])
    trainer.close()
    np.testing.assert_array_equal(before["expert_bias"], after["expert_bias"])
    assert not np.array_equal(before["gate"], after["gate"])


def test_compact_upload_is_refused_for_tokens(tmp_path):
    from ddlpc_tpu.train.trainer import Trainer

    cfg = tiny_experiment(str(tmp_path))
    cfg = cfg.replace(data=dataclasses.replace(cfg.data, compact_upload=True))
    with pytest.raises(ValueError, match="compact_upload"):
        Trainer(cfg, resume=False)


# ---- the shipped configuration ------------------------------------------------------


SHIPPED = os.path.join(ROOT, "configs", "lfm2_24b_a2b_ep8.json")
BENCH_COPY = os.path.join(BENCH, "configs", "lfm2_24b_a2b_ep8.json")


def test_shipped_configuration_parses_and_is_the_benchmarks_copy():
    shipped, copy = json.load(open(SHIPPED)), json.load(open(BENCH_COPY))
    cfg = ExperimentConfig.from_dict(shipped)
    assert cfg.model.name == "lfm2_moe" and cfg.data.dataset == "packed_tokens"
    for group in ("model", "data", "train", "parallel", "compression"):
        assert copy[group] == shipped[group], group
    # published widths, whatever was cut
    m = cfg.model
    assert (m.hidden_size, m.intermediate_size, m.moe_intermediate_size) == (2048, 11776, 1536)
    assert (m.num_attention_heads, m.num_key_value_heads, m.conv_L_cache) == (32, 8, 3)
    assert (m.num_experts, m.num_experts_per_tok, m.rope_theta, m.norm_eps) == (64, 4, 1e6, 1e-5)
    # the catalog's keys at the top level agree with what the program reads
    assert copy["hidden_size"] == m.hidden_size and copy["vocab_size"] == m.num_classes
    assert copy["num_experts"] == m.experts_held and copy["num_hidden_layers"] == len(m.layer_types)
    assert tuple(copy["layer_types"]) == m.layer_types
    assert copy["num_dense_layers"] == m.num_dense_layers
    assert copy["rope_parameters"]["rope_theta"] == m.rope_theta
    assert copy["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"
    ]
    assert cfg.train.micro_batch_size * cfg.train.sync_period == 8


def test_shipped_configuration_counts_469m_parameters():
    cfg = ExperimentConfig.from_dict(json.load(open(SHIPPED)))
    model = build_model(cfg.model)
    h, w = cfg.data.image_size
    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, h, w, 1), jnp.int32), train=False)
    )
    sizes = {k: sum(x.size for x in jax.tree.leaves(v)) for k, v in variables["params"].items()}
    d = 2048
    conv_op = d * 3 * d + 3 * d + d * d
    attention = 2 * d * d + 2 * d * 512 + 2 * 64
    routed = d * 64 + 64 + 8 * 3 * d * 1536
    norms = 2 * d
    assert sizes["embedding"] == 8192 * d and sizes["final_norm"] == d
    assert sizes["layers_0"] == conv_op + 3 * d * 11776 + norms
    assert sizes["layers_1"] == attention + routed + norms
    assert sizes["layers_2"] == sizes["layers_3"] == sizes["layers_4"] == conv_op + routed + norms
    assert round(sum(sizes.values()) / 1e6) == 469
    assert "batch_stats" not in variables
