"""The keye_vl2 family (models/keye_vl2.py) at a tiny size on the CPU: the
program against the plain float32 reference the benchmark keeps
(benchmark/reference/keye_vl2.py) on logits, cross-entropy and its gradients,
and on the indexer's loss and its gradients (benchmark/check_indexer.py); the
two exact zeros; the selection rule; sectioned rotary positions; the softmax
router and the eight shares against the uncut layer; the extended attention
kernels interpreted against the XLA form, the KL target's and the index
scores' (forward and backward) among them (alone, and through
``SparseAttention`` on the indexer's loss and gradient); the Trainer; the
step's own-loss seam and the unchanged lowering of the models that sow none.

Tiny: hidden 64, 8/2 heads of 32 (so q is 256 wide, not hidden), indexer 16
heads of 16 picking 24 keys, 16 experts top-2 (2 held), vocabulary 96, S 128
in query blocks of 16 (two scans of four blocks), two layers.
"""

import dataclasses
import hashlib
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
import check_indexer  # noqa: E402  (benchmark/check_indexer.py)
from reference import keye_vl2 as reference  # noqa: E402

from ddlpc_tpu.config import (  # noqa: E402
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    ParallelConfig,
    TrainConfig,
)
from ddlpc_tpu.models import build_model  # noqa: E402
from ddlpc_tpu.models import keye_vl2 as program  # noqa: E402
from ddlpc_tpu.models import lfm2_moe  # noqa: E402

VOCAB, SEQ, TOPK = 96, 128, 24
TINY = dict(
    name="keye_vl2", num_classes=VOCAB, hidden_size=64, moe_intermediate_size=48,
    num_attention_heads=8, num_key_value_heads=2, head_dim=32, mrope_section=(4, 6, 6),
    tie_word_embeddings=False, num_experts=16, num_experts_per_tok=2, experts_held=2,
    expert_offset=4, num_dense_layers=0, layer_types=("full_attention",) * 2, norm_eps=1e-6,
    rope_theta=1e7, use_expert_bias=False, router_score="softmax", indexer_num_heads=16,
    indexer_head_dim=16, indexer_topk=TOPK,
)


@pytest.fixture(autouse=True)
def several_query_blocks(monkeypatch):
    """Eight query blocks at S = 128, in two scans of four."""
    monkeypatch.setattr(program, "QUERY_BLOCK", 16)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)


def tiny_config(**changes) -> ModelConfig:
    return ModelConfig(**{**TINY, "compute_dtype": "float32", **changes})


def tokens(seed: int, batch: int = 2, seq: int = SEQ):
    ids = jax.random.randint(jax.random.key(seed), (batch, 1, seq + 1, 1), 0, VOCAB)
    return np.asarray(ids[:, :, :seq]), np.asarray(ids[:, :, 1:, 0])


def init(cfg: ModelConfig, seed: int = 0, seq: int = SEQ):
    """Seeded parameters with the indexer's matrices scaled up, so that its
    scores spread and few of them tie."""
    x = jnp.zeros((1, 1, seq, 1), jnp.int32)
    params = build_model(cfg).init(jax.random.key(seed), x, train=False)["params"]
    for name in params:
        if name.startswith("layers_"):
            params[name]["self_attn"]["indexer"] = jax.tree.map(
                lambda w: 4.0 * w, params[name]["self_attn"]["indexer"]
            )
    return params


# ---- program against reference ---------------------------------------------


def compare(dtype: str, seed: int) -> dict:
    """Errors of the program computing in ``dtype`` (float32 parameters):
    check.py's three and check_indexer.py's two."""
    cfg = tiny_config(compute_dtype=dtype)
    x, y = tokens(seed)
    params = init(tiny_config(), seed + 10)
    model = dataclasses.asdict(cfg)
    got = check.program_fn(cfg)(params, {}, x, y)
    want = check.reference_fn("keye_vl2", model)(params, {}, x, y)
    out = {k: float(v) for k, v in check._errors(got, want).items()}
    own = check_indexer.compare(cfg, "keye_vl2", params, x)
    # cross-entropy does not reach the indexer, in program and reference alike
    for grads in (got[2], want[2]):
        assert all(not np.any(np.asarray(g)) for g in check_indexer.split(grads)[0])
    return out | {k: own[k] for k in ("indexer_loss", "indexer_grad", "grad_outside_indexer", "first_layer_picks_differ")}


@pytest.mark.parametrize("seed", [0, 1])
def test_float32_program_is_the_reference(seed):
    out = compare("float32", seed)
    limits = reference.TOLERANCE["float32"] | reference.INDEXER_TOLERANCE["float32"]
    assert all(out[k] <= limits[k] for k in limits), out
    assert out["logits"] < 1e-5 and out["grad"] < 1e-4 and out["indexer_grad"] < 1e-4, out
    assert out["first_layer_picks_differ"] == 0.0
    # L_I moves the indexer alone: exactly nothing anywhere else
    assert out["grad_outside_indexer"] == [0.0, 0.0]


# At this size (hidden 64) bf16 reads 0.05..0.07 on logits and gradients, 0.10..
# 0.12 on the indexer's gradient, and moves 1 % of the first layer's picks;
# float8 reads 0.18, 0.29, 0.43 and moves 10 %.
TINY_BF16 = {"loss": 1e-3, "logits": 0.12, "grad": 0.15, "indexer_loss": 0.05, "indexer_grad": 0.25}


@pytest.mark.parametrize("seed", [0, 1])
def test_bfloat16_program_near_the_reference_and_outside_float32s_limits(seed):
    out = compare("bfloat16", seed)
    assert all(out[k] <= TINY_BF16[k] for k in TINY_BF16), out
    limits = reference.TOLERANCE["float32"]
    assert out["logits"] > limits["logits"] and out["grad"] > limits["grad"], out
    assert out["indexer_grad"] > reference.INDEXER_TOLERANCE["float32"]["indexer_grad"], out
    assert out["grad_outside_indexer"] == [0.0, 0.0]


def test_float8_program_is_told_from_bfloat16():
    """The nearest precision below the stated one fails even the tiny size's
    bounds."""
    out = compare("float8_e4m3fn", 0)
    assert out["logits"] > TINY_BF16["logits"] and out["grad"] > TINY_BF16["grad"], out
    assert out["indexer_grad"] > TINY_BF16["indexer_grad"], out
    assert out["first_layer_picks_differ"] > 0.05, out


# ---- the selection -------------------------------------------------------------


@pytest.mark.parametrize("shape,k", [((7, 50), 1), ((16, 128), 24), ((3, 33), 33), ((4, 64), 10)])
def test_kth_largest_is_the_sorted_rows_kth(shape, k):
    x = jax.random.normal(jax.random.key(sum(shape)), shape, jnp.float32)
    x = x.at[:, ::5].set(-jnp.inf).at[0, 1].set(0.0).at[0, 2].set(-0.0).at[1].multiply(1e-30)
    want = -np.sort(-np.asarray(x), axis=-1)[:, k - 1]
    np.testing.assert_array_equal(np.asarray(program.kth_largest(x, k)) + 0.0, want + 0.0)


def selection_of(seed: int, seq: int = SEQ, topk: int = TOPK):
    """(index scores [S, S], selection bias [S, S]) of one random sequence,
    block by block as the model makes them."""
    keys = jax.random.split(jax.random.key(seed), 3)
    qi = jax.random.normal(keys[0], (seq, 24, 16))  # 24 heads: no pair's ReLUs are all zero
    ki = jax.random.normal(keys[1], (seq, 16))
    w = jax.random.normal(keys[2], (seq, 24))
    scores, bias = [], []
    for start in range(0, seq, 16):
        x = program._index_block(qi[start : start + 16], ki[: start + 16], w[start : start + 16], start)
        tau = program._threshold_block(x, start, topk)
        pad = ((0, 0), (0, seq - x.shape[1]))
        scores.append(jnp.pad(x, pad, constant_values=-jnp.inf))
        bias.append(jnp.pad(program._selection_bias(x, tau), pad, constant_values=program.MASKED))
    return np.asarray(jnp.concatenate(scores)), np.asarray(jnp.concatenate(bias))


def test_selection_is_every_key_of_a_short_row_and_the_top_k_of_a_long_one():
    scores, bias = selection_of(0)
    picked = bias == 0
    t = np.arange(SEQ)
    assert not picked[np.triu_indices(SEQ, 1)].any()  # never a later key
    assert (picked.sum(axis=1) == np.minimum(t + 1, TOPK)).all()
    for row in (TOPK - 1, TOPK, 77, SEQ - 1):  # and it is the top_k set
        want = np.argsort(-scores[row], kind="stable")[: min(row + 1, TOPK)]
        assert set(np.flatnonzero(picked[row])) == set(want)


def test_ties_at_the_threshold_are_kept():
    x = jnp.asarray([[3.0, 1.0, 1.0, 1.0, 0.5, -jnp.inf]])
    tau = program.kth_largest(x, 2)
    assert float(tau[0]) == 1.0 and int((program._selection_bias(x, tau) == 0).sum()) == 4


def attention_inputs(seed: int, seq: int, heads: int = 8, kv: int = 2, d: int = 32, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (2, seq, heads, d), dtype)
    k = jax.random.normal(keys[1], (2, seq, kv, d), dtype)
    v = jax.random.normal(keys[2], (2, seq, kv, d), dtype)
    return q, k, v


def test_a_selection_of_every_key_is_causal_attention(monkeypatch):
    monkeypatch.setattr(lfm2_moe, "QUERY_BLOCK", 16)
    q, k, v = attention_inputs(1, SEQ)
    _, bias = selection_of(1, topk=SEQ)  # topk >= S: every earlier key
    bias = jnp.broadcast_to(jnp.asarray(bias, jnp.bfloat16), (2, SEQ, SEQ))
    out, lse = program.blocked_selected_attention(q, k, v, bias, block=16)
    np.testing.assert_allclose(out, lfm2_moe.causal_attention(q, k, v), rtol=2e-5, atol=2e-6)
    assert lse.shape == (2, SEQ, 8)


def test_selected_attention_is_the_softmax_over_the_picked_keys():
    q, k, v = attention_inputs(2, SEQ)
    _, bias = selection_of(2)
    out, lse = program.blocked_selected_attention(
        q, k, v, jnp.broadcast_to(jnp.asarray(bias, jnp.bfloat16), (2, SEQ, SEQ)), block=16
    )
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 4, axis=2)) / np.sqrt(32.0)
    scores = jnp.where(bias == 0, scores, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), jnp.repeat(v, 4, axis=2))
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(
        lse, jax.nn.logsumexp(scores, axis=-1).transpose(0, 2, 1), rtol=2e-5, atol=2e-6
    )


# ---- the extended kernels, interpreted -------------------------------------------


@pytest.mark.parametrize("heads,kv,d", [(8, 1, 128), (4, 2, 64)])
def test_selected_attention_kernels_are_the_xla_form(heads, kv, d):
    """Head size 128, eight query heads a k/v head (two groups of four a grid
    step) and the selection: forward, log-sum-exp and the three gradients."""
    from ddlpc_tpu.ops import pallas_attention

    seq, block = 256, 128
    q, k, v = attention_inputs(3, seq, heads, kv, d)
    q, k, v = q[:1], k[:1], v[:1]
    scores = jax.random.normal(jax.random.key(4), (seq, seq))
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores, -jnp.inf)
    tau = program._threshold_block(scores, 0, 40)
    bias = program._selection_bias(scores, tau).astype(jnp.bfloat16)[None]
    weights = jax.random.normal(jax.random.key(5), (1, seq, heads, d))

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out * weights), (out, lse)

        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    kernel = lambda q, k, v: pallas_attention.selected_attention(  # noqa: E731
        q, k, v, bias, block=block, interpret=True
    )
    xla = lambda q, k, v: program.blocked_selected_attention(q, k, v, bias, block=block)  # noqa: E731
    (_, (out_k, lse_k)), grads_k = loss(kernel)(q, k, v)
    (_, (out_x, lse_x)), grads_x = loss(xla)(q, k, v)
    np.testing.assert_allclose(out_k, out_x, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(lse_k, lse_x, rtol=1e-4, atol=1e-5)
    for got, want in zip(grads_k, grads_x):
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def causal_lse(q, k, kv: int, block: int):
    """The rows' log-sum-exp of plain causal attention, laid out as the model
    hands it to the KL: ``[KV, S/block, G·block]`` for q ``[S, H, D]``."""
    s, heads, d = q.shape
    scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, heads // kv, axis=1)) / np.sqrt(d)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1).T  # [S, H]
    return program._query_blocks(lse[..., None], kv, block)[..., 0]


@pytest.mark.parametrize("heads,kv,d", [(8, 1, 128), (4, 2, 64), (32, 4, 128)])
def test_head_mean_probs_kernel_is_the_xla_form(heads, kv, d):
    """Every query block of a run of four against the run's keys: the blocks
    before the last see keys past their own end (the part of a run above the
    diagonal, which the selection drops afterwards), and there the
    probabilities pass 1.  Two groups of four heads a k/v head at (8, 1) and
    (32, 4), one of two at (4, 2); only the order of the head sum differs."""
    from ddlpc_tpu.ops import pallas_attention

    seq, block = 512, 128
    q, k, _ = attention_inputs(8, seq, heads, kv, d)
    qs, ks = program._query_blocks(q[0], kv, block), k[0].transpose(1, 0, 2)
    lse = causal_lse(q[0], k[0], kv, block)
    beyond = 0.0
    for i in range(seq // block):
        want = program.blocked_head_mean_probs(qs[:, i], ks, lse[:, i], heads)
        got = pallas_attention.head_mean_probs(
            qs[:, i], ks, lse[:, i], heads=heads, block=block, interpret=True
        )
        assert got.shape == (block, seq) and got.dtype == jnp.float32
        assert float(jnp.abs(got - want).max()) <= 1e-5 * float(jnp.abs(want).max())
        # up to the diagonal it is a mean of probabilities: a row's sum is 1
        rows = jnp.where(jnp.arange(seq)[None] <= i * block + jnp.arange(block)[:, None], got, 0.0)
        np.testing.assert_allclose(rows.sum(axis=1), 1.0, rtol=1e-5)
        beyond = max(beyond, float(got[:, (i + 1) * block :].max(initial=0.0)))
    assert beyond > 1.0  # the part above the diagonal was computed, not skipped


def test_head_mean_probs_kernel_refuses_what_its_blocks_do_not_divide():
    from ddlpc_tpu.ops import pallas_attention

    q, k = jnp.zeros((2, 4 * 128, 32)), jnp.zeros((2, 192, 32))
    with pytest.raises(ValueError, match="not a multiple"):
        pallas_attention.head_mean_probs(q, k, jnp.zeros((2, 4 * 128)), heads=8, block=128, interpret=True)
    with pytest.raises(ValueError, match="not a multiple"):  # 64 queries a head
        pallas_attention.head_mean_probs(q[:, :256], k[:, :128], jnp.zeros((2, 256)), heads=8, block=128, interpret=True)


def index_inputs(seed: int, heads: int, d: int, rows: int, keys: int, dtype=jnp.float32):
    """One run's indexer projections: ``keys / rows`` query blocks ``qI
    [rows, heads, d]`` and their weights ``[rows, heads]``, and the run's keys."""
    kq, kk, kw = jax.random.split(jax.random.key(seed), 3)
    qi = jax.random.normal(kq, (keys // rows, rows, heads, d), dtype)
    ki = jax.random.normal(kk, (keys, d), dtype)
    return qi, ki, jax.random.normal(kw, (keys // rows, rows, heads), jnp.float32)


@pytest.mark.parametrize("heads,d", [(16, 64), (6, 32), (1, 128)])
def test_index_scores_kernel_is_the_xla_form(heads, d):
    """A run's four query blocks against the run's keys: every block but the
    last has key blocks wholly after it, which the kernel fills with -inf
    without a product, and every block a diagonal one that it masks.  A
    head is a run of 64, 32 or 128 lanes of q as the model lays it out; only
    the order of the head sum differs."""
    from ddlpc_tpu.ops import pallas_attention

    rows = block = 128
    qi, ki, w = index_inputs(3, heads, d, rows, 4 * rows)
    for i in range(4):
        start = jnp.int32(i * rows)
        want = program._index_block(qi[i], ki, w[i], start)
        got = jax.jit(
            lambda q, k, w, s: pallas_attention.index_scores(q, k, w, s, block=block, interpret=True)
        )(qi[i], ki, w[i], start)
        assert got.shape == (rows, 4 * rows) and got.dtype == jnp.float32
        after = np.arange(4 * rows)[None] > i * rows + np.arange(rows)[:, None]
        assert np.array_equal(np.isneginf(got), after) and np.array_equal(np.isneginf(want), after)
        np.testing.assert_allclose(
            np.where(after, 0.0, got), np.where(after, 0.0, want), rtol=0, atol=1e-6 * float(jnp.abs(want[:, 0]).max())
        )


@pytest.mark.parametrize("heads,d", [(16, 64), (2, 32)])
def test_index_scores_kernel_gradient_is_the_xla_forms(heads, d):
    """dqI, dkI and dw under a random cotangent against ``jax.vjp`` of the
    XLA form, on a late block of a run (a skipped key block, a diagonal one,
    whole ones).  Head 0 of the first queries is zero, so its pre-activations
    are: ReLU's slope there is 0, in both forms exactly."""
    from ddlpc_tpu.ops import pallas_attention

    rows = block = 128
    qi, ki, w = index_inputs(4, heads, d, rows, 4 * rows)
    qi = qi.at[2, :8, 0].set(0.0)
    start = jnp.int32(2 * rows)
    ct = jax.random.normal(jax.random.key(5), (rows, 4 * rows))
    kernel = lambda q, k, w: pallas_attention.index_scores(q, k, w, start, block=block, interpret=True)  # noqa: E731
    got, pull = jax.vjp(kernel, qi[2], ki, w[2])
    want, pull_xla = jax.vjp(lambda q, k, w: program._index_block(q, k, w, start), qi[2], ki, w[2])
    ct = jnp.where(jnp.isfinite(want), ct, 0.0)  # what reaches a -inf is dropped by both
    for name, a, b in zip(("dqI", "dkI", "dw"), pull(ct), pull_xla(ct)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-6 * float(jnp.abs(b).max()), err_msg=name)
    dq, dk, dw = pull(ct)
    assert not np.any(np.asarray(dq[:8, 0])) and not np.any(np.asarray(dw[:8, 0]))
    assert not np.any(np.asarray(dk[3 * rows :]))  # the keys after the block: no query saw them
    # a cotangent on the -inf part changes nothing (0 · inf never arises)
    for a, b in zip(pull(jnp.ones_like(ct)), pull(jnp.where(jnp.isfinite(want), 1.0, 0.0))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_index_scores_kernel_refuses_what_its_blocks_do_not_divide():
    from ddlpc_tpu.ops import pallas_attention

    qi, ki, w = jnp.zeros((128, 4, 32)), jnp.zeros((192, 32)), jnp.zeros((128, 4))
    with pytest.raises(ValueError, match="not a multiple"):  # 192 keys in blocks of 128
        pallas_attention.index_scores(qi, ki, w, 0, block=128, interpret=True)
    with pytest.raises(ValueError, match="not a multiple"):  # 64 queries
        pallas_attention.index_scores(qi[:64], ki[:128], w[:64], 0, block=128, interpret=True)


def indexer_loss_and_gradient(monkeypatch, kernel: bool, index_kernel: bool = False):
    """``L_I`` and its gradient over every leaf of one ``SparseAttention``:
    eight query blocks of 128 in two runs, the target and the index scores
    each by their kernels (interpreted) or by the XLA form."""
    from ddlpc_tpu.ops import pallas_attention

    seq = 1024
    monkeypatch.setattr(program, "QUERY_BLOCK", 128)
    if kernel:
        monkeypatch.setattr(
            program, "head_mean_probs",
            lambda q, k, lse, heads, seq_len: pallas_attention.head_mean_probs(
                q, k, lse, heads=heads, block=128, interpret=True
            ),
        )
    if index_kernel:
        monkeypatch.setattr(
            program, "index_scores",
            lambda qi, ki, w, start, seq_len: pallas_attention.index_scores(
                qi, ki, w, start, block=128, interpret=True
            ),
        )
    cfg = tiny_config(indexer_topk=200)
    layer = program.SparseAttention(cfg, True)
    u = jax.random.normal(jax.random.key(11), (2, 1, seq, cfg.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(seq), (3, seq))
    tables = program.mrope_tables(positions, cfg.head_dim, cfg.rope_theta, cfg.mrope_section)
    index_tables = program.mrope_tables(
        positions[:1], cfg.indexer_head_dim, cfg.rope_theta, (cfg.indexer_head_dim // 2,)
    )
    params = layer.init(jax.random.key(12), u, tables, index_tables)["params"]
    params["indexer"] = jax.tree.map(lambda w: 4.0 * w, params["indexer"])
    loss = lambda p: layer.apply({"params": p}, u, tables, index_tables)[1]  # noqa: E731
    return jax.jit(jax.value_and_grad(loss))(params)


def test_indexer_loss_and_gradient_through_the_kernel_are_the_xla_forms(monkeypatch):
    loss_x, grads_x = indexer_loss_and_gradient(monkeypatch, kernel=False)
    loss_k, grads_k = indexer_loss_and_gradient(monkeypatch, kernel=True)
    assert float(loss_x) > 0
    np.testing.assert_allclose(loss_k, loss_x, rtol=1e-5)
    for grads in (grads_x, grads_k):  # L_I moves the indexer alone, on both paths
        own, rest = check_indexer.split(grads)
        assert all(np.any(np.asarray(g)) for g in own)
        assert all(not np.any(np.asarray(g)) for g in rest)
    for got, want in zip(check_indexer.split(grads_k)[0], check_indexer.split(grads_x)[0]):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5 * float(jnp.abs(want).max()))


def test_indexer_loss_and_gradient_through_the_index_kernels_are_the_xla_forms(monkeypatch):
    """The selection and the loss both from the index kernels, the gradient
    through their backward: the scores differ from the XLA form's in the
    order of a 16-term sum, so a pick at a threshold may, and the loss is
    held to 1e-4; nothing leaves the indexer on either path."""
    loss_x, grads_x = indexer_loss_and_gradient(monkeypatch, kernel=False)
    loss_k, grads_k = indexer_loss_and_gradient(monkeypatch, kernel=True, index_kernel=True)
    np.testing.assert_allclose(loss_k, loss_x, rtol=1e-4)
    for grads in (grads_x, grads_k):
        own, rest = check_indexer.split(grads)
        assert all(np.any(np.asarray(g)) for g in own)
        assert all(not np.any(np.asarray(g)) for g in rest)
    for got, want in zip(check_indexer.split(grads_k)[0], check_indexer.split(grads_x)[0]):
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4 * float(jnp.abs(want).max()))


PATHS = [("tpu", 1024, True), ("tpu", 512, True), ("cpu", 1024, False), ("tpu", 256, False),
         ("tpu", 16384 + 512, False)]


@pytest.mark.parametrize("platform,seq,kernel", PATHS)
def test_index_path_goes_by_platform_and_sequence_length(platform, seq, kernel):
    """As the target's path below: the forward kernel, and under a gradient
    the backward one, where the program is lowered for a TPU and the kernels
    take the sequence; the XLA form, and no error, everywhere else."""
    block = min(512, seq)
    qi = jax.ShapeDtypeStruct((block, 16, 64), jnp.bfloat16)
    ki = jax.ShapeDtypeStruct((seq, 64), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((block, 16), jnp.float32)
    start = jax.ShapeDtypeStruct((), jnp.int32)
    forward = lambda qi, ki, w, start: program.index_scores(qi, ki, w, start, seq)  # noqa: E731
    text = jax.jit(forward).trace(qi, ki, w, start).lower(lowering_platforms=(platform,)).as_text()
    assert text.count("stablehlo.custom_call @tpu_custom_call") == (1 if kernel else 0)
    assert ("index_scores_fwd" in text) == kernel
    assert ("stablehlo.dot_general" in text) != kernel  # the XLA form's product, or none
    gradient = jax.grad(lambda qi, ki, w, start: jnp.sum(jnp.exp(forward(qi, ki, w, start))), (0, 1, 2))
    text = jax.jit(gradient).trace(qi, ki, w, start).lower(lowering_platforms=(platform,)).as_text()
    assert ("index_scores_bwd" in text) == kernel
    assert ("stablehlo.dot_general" in text) != kernel


@pytest.mark.parametrize("platform,seq,kernel", PATHS)
def test_target_path_goes_by_platform_and_sequence_length(platform, seq, kernel):
    """The kernel where the program is lowered for a TPU and the attention's
    kernels take the sequence; the XLA form, and no error, everywhere else."""
    block = min(512, seq)
    q = jax.ShapeDtypeStruct((2, 4 * block, 32), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, seq, 32), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((2, 4 * block), jnp.float32)
    fn = jax.jit(lambda q, k, lse: program.head_mean_probs(q, k, lse, 8, seq))
    text = fn.trace(q, k, lse).lower(lowering_platforms=(platform,)).as_text()
    assert text.count("stablehlo.custom_call @tpu_custom_call") == (1 if kernel else 0)
    assert ("head_mean_probs" in text) == kernel
    assert ("stablehlo.dot_general" in text) != kernel  # the XLA form's product, or none


@pytest.mark.parametrize("collects_losses", [True, False])
def test_model_builds_the_target_only_where_its_loss_is_collected(monkeypatch, collects_losses):
    """Lowered for a TPU at a length the kernels take, a layer holds the
    attention's forward kernel, the selection's index kernel a sequence and
    run, and one target kernel and one more index kernel a sequence and run
    where the caller collects ``losses``; evaluation and the reference check
    collect none and build no target at all."""
    monkeypatch.setattr(program, "QUERY_BLOCK", 512)
    seq, batch = 1024, 2
    cfg = tiny_config(compute_dtype="bfloat16", indexer_topk=256)
    model = build_model(cfg)
    x = jax.ShapeDtypeStruct((batch, 1, seq, 1), jnp.int32)
    variables = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 1, seq, 1), jnp.int32), train=False))
    mutable = ["counters", "losses"] if collects_losses else ["counters"]
    fn = jax.jit(lambda v, x: model.apply(v, x, train=True, mutable=mutable))
    text = fn.trace(variables, x).lower(lowering_platforms=("tpu",)).as_text()
    layers, runs = len(cfg.layer_types), 1  # two blocks of 512: one run
    assert text.count("selected_attention_fwd") >= layers
    calls = text.count("stablehlo.custom_call @tpu_custom_call")
    assert calls == layers * (1 + batch * runs * (1 + 2 * collects_losses))
    assert text.count("index_scores_fwd") >= layers * (1 + collects_losses)
    assert (text.count("head_mean_probs") > 0) == collects_losses


def test_causal_kernels_take_eight_query_heads_of_128():
    from ddlpc_tpu.ops import pallas_attention

    q, k, v = attention_inputs(6, 256, 8, 1, 128)
    fn = lambda f: jax.value_and_grad(lambda q, k, v: jnp.sum(f(q, k, v) ** 2), argnums=(0, 1, 2))  # noqa: E731
    got = fn(lambda q, k, v: pallas_attention.causal_attention(q, k, v, block=128, interpret=True))(q, k, v)
    want = fn(lambda q, k, v: lfm2_moe.blocked_causal_attention(q, k, v, block=128))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


# ---- rotary positions ----------------------------------------------------------------


def test_sectioned_rotary_on_equal_streams_is_the_plain_table():
    positions = jnp.broadcast_to(jnp.arange(SEQ), (3, SEQ))
    got = program.mrope_tables(positions, 32, 1e7, (4, 6, 6))
    want = lfm2_moe.rotary_tables(SEQ, 32, 1e7)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_sectioned_rotary_on_three_streams_is_the_references():
    positions = jnp.stack([jnp.arange(SEQ), jnp.arange(SEQ) // 3, 5 + jnp.arange(SEQ) % 7])
    x = jax.random.normal(jax.random.key(7), (2, SEQ, 8, 32))
    cos, sin = program.mrope_tables(positions, 32, 1e7, (4, 6, 6))
    got = lfm2_moe.apply_rotary(x, cos, sin)
    want = reference.sectioned_rotary(x, positions, 1e7, (4, 6, 6))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # slot 3 turns by the first stream, slot 4 by the second
    assert not np.allclose(cos[:, 3], np.cos(np.asarray(positions[1]) * 1e7 ** (-6 / 32)))
    np.testing.assert_allclose(cos[:, 4], np.cos(np.asarray(positions[1]) * 1e7 ** (-8 / 32)), rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="add up"):
        program.mrope_tables(positions, 32, 1e7, (4, 6, 5))


# ---- the router and the shares -----------------------------------------------------------


def routed_layer(cfg, held, offset):
    return lfm2_moe.RoutedExperts(
        cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts, cfg.num_experts_per_tok,
        held, offset, use_expert_bias=False, dtype=jnp.float32, score="softmax",
    )


def whole_layer(cfg, seed):
    hidden, width, n_exp = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    keys = jax.random.split(jax.random.key(seed), 5)
    u = jax.random.normal(keys[0], (2, 1, SEQ, hidden), jnp.float32)
    return u, {
        "gate": jax.random.normal(keys[1], (hidden, n_exp)) * 0.3,
        "w1": jax.random.normal(keys[2], (n_exp, hidden, width)) * 0.1,
        "w3": jax.random.normal(keys[3], (n_exp, hidden, width)) * 0.1,
        "w2": jax.random.normal(keys[4], (n_exp, width, hidden)) * 0.1,
    }


def test_softmax_router_is_the_references():
    """Softmax over all the experts, the picked two renormalised with no
    epsilon, no bias leaf: value and gradients, every expert held."""
    cfg = tiny_config()
    u, whole = whole_layer(cfg, 8)
    layer = routed_layer(cfg, cfg.num_experts, 0)
    model = dict(dataclasses.asdict(cfg), experts_held=cfg.num_experts, expert_offset=0)
    assert set(layer.init(jax.random.key(0), u)["params"]) == {"gate", "w1", "w2", "w3"}
    got = jax.value_and_grad(lambda p: jnp.sum(layer.apply({"params": p}, u)[0] ** 2))(whole)
    want = jax.value_and_grad(lambda p: jnp.sum(reference.routed_experts(u[:, 0], p, model) ** 2))(whole)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name in whole:
        np.testing.assert_allclose(got[1][name], want[1][name], rtol=2e-3, atol=1e-5)
    # the weights of a token's picks add up to one
    probs = jax.nn.softmax(u.reshape(-1, cfg.hidden_size) @ whole["gate"], axis=-1)
    top = jax.lax.top_k(probs, 2)[0]
    assert np.allclose((top / top.sum(-1, keepdims=True)).sum(-1), 1.0)


def test_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: eight shares of two experts (offsets 0, 2, ...,
    14), each computed by the program's routed layer, sum to what the
    reference gives with all sixteen experts held."""
    cfg = tiny_config()
    u, whole = whole_layer(cfg, 9)
    uncut = dict(dataclasses.asdict(cfg), experts_held=cfg.num_experts, expert_offset=0)
    want = reference.routed_experts(u[:, 0], whole, uncut)
    total, routed = 0.0, 0
    for offset in range(0, cfg.num_experts, 2):
        share = dict(whole, **{k: whole[k][offset : offset + 2] for k in ("w1", "w3", "w2")})
        part, counts = routed_layer(cfg, 2, offset).apply({"params": share}, u)
        total = total + part
        routed += int(counts["sum"]["moe_rows_routed"])
        assert int(counts["sum"]["moe_rows_dropped"]) == 0
    np.testing.assert_allclose(total[:, 0], want, rtol=2e-5, atol=2e-6)
    assert routed == 2 * SEQ * cfg.num_experts_per_tok


# ---- the model ------------------------------------------------------------------------------


def test_model_is_causal_and_counts_its_pairs():
    cfg = tiny_config()
    model, params = build_model(cfg), init(tiny_config(), 3)
    x, _ = tokens(4)
    logits, sown = model.apply({"params": params}, x, train=True, mutable=["counters", "losses"])
    changed = x.copy()
    changed[:, :, 100:] = (changed[:, :, 100:] + 1) % VOCAB
    again = model.apply({"params": params}, changed, train=False)
    np.testing.assert_allclose(logits[:, :, :100], again[:, :, :100], rtol=1e-5, atol=1e-6)
    sums = sown["counters"]["sum"]
    per_sequence = TOPK * (TOPK + 1) // 2 + (SEQ - TOPK) * TOPK
    assert int(sums["dsa_pairs_selected"]) == 2 * 2 * per_sequence  # layers x sequences
    assert int(sums["dsa_pairs_causal"]) == 2 * 2 * SEQ * (SEQ + 1) // 2
    assert int(sown["counters"]["max"]["dsa_kernel_layers"]) == 0  # the XLA form on the CPU
    assert int(sown["counters"]["max"]["dsa_kl_kernel_layers"]) == 0  # of the target too
    assert int(sown["counters"]["max"]["dsa_index_kernel_layers"]) == 0  # and of the index scores
    assert int(sums["moe_rows_dropped"]) == 0 and int(sums["tokens_per_step"]) == 2 * SEQ
    assert float(sown["losses"]["indexer_kl"]) > 0
    # no own loss where nobody collects it, and the head is its own matrix
    _, sown = model.apply({"params": params}, x, train=True, mutable=["counters"])
    assert "losses" not in sown
    assert params["lm_head"].shape == params["embedding"].shape == (VOCAB, 64)
    assert params["layers_0"]["self_attn"]["q_proj"]["kernel"].shape == (64, 8 * 32)


def test_registry_refuses_what_the_family_cannot_be():
    with pytest.raises(ValueError, match="layer_types"):
        build_model(tiny_config(layer_types=("conv", "full_attention")))
    with pytest.raises(ValueError, match="indexer_num_kv_heads"):
        build_model(tiny_config(indexer_num_kv_heads=2))
    with pytest.raises(ValueError, match="router_score"):
        build_model(tiny_config(router_score="tanh"))


# ---- the step's own loss ---------------------------------------------------------------------


def tiny_experiment(workdir: str, **train) -> ExperimentConfig:
    return ExperimentConfig(
        model=tiny_config(),
        data=DataConfig(
            dataset="packed_tokens", image_size=(1, SEQ), num_classes=VOCAB,
            synthetic_len=12, test_split=4, device_cache=True,
        ),
        train=TrainConfig(
            epochs=2, micro_batch_size=2, sync_period=2, learning_rate=3e-3, optimizer="adam",
            eval_every_epochs=0, checkpoint_every_epochs=0, dump_images_per_epoch=0, **train,
        ),
        parallel=ParallelConfig(data_axis_size=1),
        workdir=workdir,
    )


def test_trainer_fits_two_steps_and_records_the_own_loss_and_counters(tmp_path):
    from ddlpc_tpu.train.trainer import Trainer

    trainer = Trainer(tiny_experiment(str(tmp_path / "run")), resume=False)
    before = jax.device_get(trainer.state.params["layers_0"]["self_attn"]["indexer"])
    assert len(trainer.loader) == 2
    record = trainer.fit(epochs=1)
    after = jax.device_get(trainer.state.params["layers_0"]["self_attn"]["indexer"])
    trainer.close()
    assert record["indexer_kl"] > 0 and np.isfinite(record["loss"])
    assert record["loss"] > record["indexer_kl"]  # the step's loss holds it, beside cross-entropy
    per_sequence = TOPK * (TOPK + 1) // 2 + (SEQ - TOPK) * TOPK
    assert record["dsa_pairs_causal"] == 4 * 2 * SEQ * (SEQ + 1) // 2  # sequences x layers
    assert per_sequence * 8 <= record["dsa_pairs_selected"] < 1.05 * per_sequence * 8  # ties only
    assert record["dsa_kernel_layers"] == 0.0 and record["dsa_kl_kernel_layers"] == 0.0
    assert record["dsa_index_kernel_layers"] == 0.0
    assert record["moe_rows_dropped"] == 0.0 and record["tokens_per_step"] == 4 * SEQ
    assert record["moe_rows_offered"] == 4 * SEQ * 2 * 2
    assert 0 < record["moe_rows_routed"] < record["moe_rows_offered"]
    assert record["moe_rows_buffered"] > 0 and record["moe_max_load"] >= 1.0
    # the indexer learns, from its own loss alone
    assert not np.array_equal(before["q_proj"]["kernel"], after["q_proj"]["kernel"])


def test_reduce_counters_means_the_own_losses():
    from ddlpc_tpu.parallel.train_step import _reduce_counters

    stacked = {"sum": {"a": jnp.asarray([1, 2])}, "max": {"b": jnp.asarray([1.0, 3.0])},
               "mean": {"indexer_kl": jnp.asarray([1.0, 2.0])}}
    assert {k: float(v) for k, v in _reduce_counters(stacked).items()} == {"a": 3.0, "b": 3.0, "indexer_kl": 1.5}


def step_jaxpr_digest(model_cfg: ModelConfig, shape, dtype) -> str:
    """sha256 of the jaxpr of one micro-batch's loss and gradients as the step
    computes them (``_loss_and_metrics`` under ``value_and_grad``)."""
    from ddlpc_tpu.parallel.train_step import _loss_and_metrics

    model = build_model(model_cfg)
    x = jnp.zeros(shape, dtype)
    y = jnp.zeros(shape[:3], jnp.int32)
    variables = jax.eval_shape(lambda: model.init(jax.random.key(0), x, train=False))
    stats = variables.get("batch_stats", {})

    def f(params, stats, x, y):
        return jax.value_and_grad(
            lambda p: _loss_and_metrics(model, p, stats, x, y, train=True), has_aux=True
        )(params)

    return hashlib.sha256(str(jax.make_jaxpr(f)(variables["params"], stats, x, y)).encode()).hexdigest()


# The digests of the parent commit (d321959, before the ``losses`` seam and the
# router's ``score``), made by this function there: a model that sows no loss
# of its own gets the program it had.  A later change to either model, or
# another jax, moves them; make them again on the commit before that change.
UNCHANGED = {
    "flagship": "0104633fc85f027941babdc7300144f14f0dd56e9e26c41f6ebb87907022f859",
    "lfm2_moe": "01daede8ef34324c4685be0f113583bea3f6d72d1e8fe48ff97e61adb3b4bba5",
}


@pytest.mark.parametrize("which", sorted(UNCHANGED))
def test_models_that_sow_no_loss_lower_as_before(which):
    if which == "flagship":
        import json

        cfg = ExperimentConfig.from_dict(
            json.load(open(os.path.join(ROOT, "configs", "vaihingen_unet_tpu_flagship.json")))
        ).model
        cfg = dataclasses.replace(cfg, features=(8, 16), bottleneck_features=16)
        digest = step_jaxpr_digest(cfg, (2, 64, 64, 3), jnp.float32)
    else:
        cfg = ModelConfig(
            name="lfm2_moe", num_classes=128, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=48, num_attention_heads=4, num_key_value_heads=2,
            num_experts=8, num_experts_per_tok=2, experts_held=2, expert_offset=2,
            num_dense_layers=1, layer_types=("conv", "full_attention", "conv"),
        )
        digest = step_jaxpr_digest(cfg, (2, 1, 512, 1), jnp.int32)  # a length the kernels take
    assert digest == UNCHANGED[which]


# ---- the program's FLOP model ---------------------------------------------------------------------


def test_product_flops_counts_the_indexer_and_the_kernel_by_its_estimate():
    """``obs/flops.product_flops`` walks this family like any other: the
    indexer's products inside their scans, the attention by its dots in the
    XLA form and by the kernels' ``cost_estimate`` (the causal half) where the
    program is lowered for a TPU; the grouped products by the pair."""
    from ddlpc_tpu.obs import flops

    seq, heads, d, layers, batch = 512, 8, 32, 2, 2

    def count(platform, **changes):
        cfg = ExperimentConfig(
            model=tiny_config(**changes), data=DataConfig(dataset="packed_tokens", image_size=(1, seq))
        )
        return flops.product_flops(cfg, batch, 1, platform=platform)

    dense_cpu, grouped, has_conv = count("cpu")
    dense_tpu, grouped_tpu, _ = count("tpu")
    assert not has_conv and grouped == grouped_tpu
    # every (token, expert) pair: three products of 2 x 64 x 48 a row
    assert grouped == batch * seq * 2 * layers * 3 * 2 * 64 * 48
    # blocks of 16 against the keys up to their end, two products a pair, against
    # the kernels' causal half of S x S
    blocks = seq // 16
    xla = 2 * 2 * heads * d * 16 * 16 * (blocks * (blocks + 1) // 2)
    assert dense_cpu - dense_tpu == batch * layers * (xla - 2 * heads * d * seq * seq)
    # twice the indexer heads: their projections and their scores again (the
    # scores of four blocks a scan against the keys up to the scan's end)
    runs = sum(4 * 16 * (first + 4) * 16 for first in range(0, blocks, 4))  # (query, key) pairs computed
    more = count("cpu", indexer_num_heads=32)[0] - dense_cpu
    assert more == batch * layers * (2 * seq * 64 * (16 * 16 + 16) + 2 * 16 * 16 * runs)
    # the selection's size changes no product
    assert count("cpu", indexer_topk=100)[0] == dense_cpu
    # The KL's target is built under train=True alone, which the walk does not
    # trace; its kernel states the XLA form's one product of every head, every
    # query of the block and every key of the run, and an exponential a pair.
    from ddlpc_tpu.ops import pallas_attention

    block, keys, kv = 128, 512, 2
    shapes = (
        jax.ShapeDtypeStruct((kv, heads // kv * block, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((kv, keys, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((kv, heads // kv * block), jnp.float32),
    )
    kernel = jax.make_jaxpr(
        lambda q, k, lse: pallas_attention.head_mean_probs(q, k, lse, heads=heads, block=block)
    )(*shapes)
    (cost,) = [e.params["cost_estimate"] for e in flops.iter_eqns(kernel.jaxpr) if e.primitive.name == "pallas_call"]
    xla = jax.make_jaxpr(lambda q, k, lse: program.blocked_head_mean_probs(q, k, lse, heads))(*shapes)
    (dot,) = [e for e in flops.iter_eqns(xla.jaxpr) if e.primitive.name == "dot_general"]
    assert cost.flops == 2 * int(np.prod(dot.outvars[0].aval.shape)) * d == 2 * heads * block * keys * d
    assert cost.transcendentals == heads * block * keys
    assert cost.bytes_accessed == 2 * (heads * block * d + kv * keys * d) + 4 * (heads * block + block * keys)
    # The index scores' kernels state the XLA form's products too: the one of
    # the forward, and the two of its gradient (dqI and dkI; the recomputed
    # pre-activations are not counted), no transcendental.
    rows, keys, j, di = 128, 512, 16, 64
    shapes = (
        jax.ShapeDtypeStruct((rows, j, di), jnp.bfloat16), jax.ShapeDtypeStruct((keys, di), jnp.bfloat16),
        jax.ShapeDtypeStruct((rows, j), jnp.float32),
    )

    def products(fn):
        def both(qi, ki, w, ct):
            out, pull = jax.vjp(lambda qi, ki, w: fn(qi, ki, w, jnp.int32(384)), qi, ki, w)
            return out, pull(ct)

        return jax.make_jaxpr(both)(*shapes, jax.ShapeDtypeStruct((rows, keys), jnp.float32)).jaxpr

    costs = {
        e.params["name"]: e.params["cost_estimate"]
        for e in flops.iter_eqns(products(lambda *a: pallas_attention.index_scores(*a, block=block)))
        if e.primitive.name == "pallas_call"
    }
    dots = [e for e in flops.iter_eqns(products(program._index_block)) if e.primitive.name == "dot_general"]
    one = 2 * j * rows * keys * di

    def dot_flops(e):
        (contract, _), _ = e.params["dimension_numbers"]
        return 2 * int(np.prod(e.outvars[0].aval.shape)) * int(np.prod([e.invars[0].aval.shape[c] for c in contract]))

    assert [dot_flops(e) for e in dots] == [one] * 3
    assert costs["index_scores_fwd"].flops == one and costs["index_scores_bwd"].flops == 2 * one
    assert costs["index_scores_fwd"].transcendentals == costs["index_scores_bwd"].transcendentals == 0
    operands = 2 * (rows * j * di + keys * di) + 4 * rows * j
    assert costs["index_scores_fwd"].bytes_accessed == operands + 4 * rows * keys
    assert costs["index_scores_bwd"].bytes_accessed == 2 * operands + 4 * rows * keys
