"""Keye-VL-2.0's decoder (``model_type: KeyeVL2``) as a per-position
classifier over a 1×S token tile, one expert-parallel rank's share.

The family (Kwai-Keye Keye-VL-2.0-30B-A3B, config.json; the language model
alone, text positions): grouped-query attention with an explicit head size
(``head_dim`` 128, so q is wider than ``hidden``), per-head RMS norm on q and
k, rotary positions in three sections (``mrope_section``), and in front of it
DeepSeek-Sparse-Attention's "lightning indexer": a small scorer
``I[t,s] = c · Σ_j w[t,j] · ReLU(qI[t,j] · kI[s])`` over the causal pairs picks
each query's ``indexer_topk`` keys, and the attention's softmax runs over the
picked keys alone.  The indexer reads a detached input and learns from its own
loss, the KL of the attention's head-averaged probabilities over the picked
keys against the softmax of its scores there (the sparse training stage of
DeepSeek-V3.2-Exp): cross-entropy moves everything but the indexer, the KL
moves the indexer alone.  The feed-forward is softmax-scored
top-``num_experts_per_tok``-of-``num_experts`` routed experts in every layer
(``models/lfm2_moe.py:RoutedExperts``, its compact buffer and counters as they
stand); the head is its own matrix.  Equations in ISSUE 32 / PERF.md §4; the
plain float32 reference is ``benchmark/reference/keye_vl2.py``.

One algorithm in three passes over 512-row query blocks, so that nothing
``[heads, S, S]`` exists: (1) the index scores of a block against the keys up
to its end, the threshold ``τ_t`` (the ``topk``-th largest of a row, exact,
by bisection on the float's bits) and the selection as an additive bias
``[S, S]`` (0 where ``I ≥ τ``, ties kept; -1e30 elsewhere, the keys after the
query included); (2) attention over the biased scores, which has two
lowerings chosen as ``lfm2_moe.causal_attention`` chooses (the fused kernels
of ``ops/pallas_attention.py`` where the program is lowered for a TPU and they
take the sequence, blocked XLA everywhere else), and returns each row's
log-sum-exp; (3) the KL a block, the target recomputed from detached q and k
and that log-sum-exp, the index scores computed again (so no ``[S, S]`` scores
and no ``[S, S]`` cotangent wait between the passes).  The target and the
index scores have the attention's two lowerings, chosen the same way: where
the kernels run, ``ops/pallas_attention.head_mean_probs`` keeps every head's
float32 scores of a block pair, their exponentials and the sum over the heads
in VMEM and writes the head-averaged probabilities ``[block, keys]`` alone,
and ``ops/pallas_attention.index_scores`` does the same for the indexer's
ReLU'd, weighted pre-activations, in passes (1) and (3) and in the gradient
of (3), whose kernel makes them again from the inputs; the XLA forms write
all ``[heads, block, keys]`` of them, and the cotangent of as many, and read
them back.  Passes (1) and (3) are ``lax.scan``s over runs of
``BLOCKS_PER_SCAN`` query blocks that share the key width of their last
block: one body's temporaries at a time,
whatever the compiler's schedule (32 unrolled blocks a layer left the index
products of three layers alive at once: 18.5 GB for the chip; PERF.md §6,
PR 32).  Each block of (3) is rematerialised in the backward, and so is each
layer under ``train=True``.

Compute is ``compute_dtype`` (bf16) with float32 parameters; router, norm
statistics, softmax, rotary angles, index scores (products from bf16 operands
accumulated in float32), ReLU, weighting, threshold and KL are float32.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax

from ddlpc_tpu.config import ModelConfig
from ddlpc_tpu.models.lfm2_moe import (
    _INIT,
    QUERY_BLOCK,
    RMSNorm,
    RoutedExperts,
    _kernel_lowers,
    _kernels,
    _proj,
    _softmax_rows,
    apply_rotary,
)

MASKED = -1e30  # as ops/pallas_attention.py: exp(MASKED - m) is exactly 0
# The embedding is drawn N(0, 1) (as PaLM draws its input embeddings, and for
# its reason: no norm follows them on the residual stream).  At the N(0, 0.02)
# of the other leaves a token's row is a sixth of what the first attention
# writes beside it, which over Zipf-distributed ids is nearly the context's mean
# at every position: each layer's router then sees one vector, sends all its
# tokens to the same 8 experts, and a rank holds 0 to 3 of them by the seed
# (routed rows 127k to 266k a step, 3.3 % of the cell's throughput; PERF.md
# section 6, PR 32).
_EMBEDDING_INIT = nn.initializers.normal(stddev=1.0)
# Query blocks that one scan of the indexer's passes runs over, against the
# keys up to the last one's end: 4 of 512 compute 9 % more pairs than the
# causal ones at 16,384 (every block against its own keys alone would be 32
# programs a pass and layer).
BLOCKS_PER_SCAN = 4


def mrope_tables(positions, head_dim: int, theta: float, sections):
    """cos, sin ``[S, head_dim]`` in float32 for ``positions [streams, S]``:
    frequency slot ``j`` of the ``head_dim / 2`` turns by the position of
    stream ``c(j)`` times ``theta^(-2j/head_dim)``, ``c`` running through
    ``sections`` (their sum is ``head_dim / 2``); half-split convention, the
    angles repeated over both halves.  On equal streams this is
    ``lfm2_moe.rotary_tables``."""
    if sum(sections) != head_dim // 2:
        raise ValueError(f"mrope sections {tuple(sections)} do not add up to {head_dim // 2}")
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    stream = np.repeat(np.arange(len(sections)), sections)
    angles = positions.astype(jnp.float32)[stream].T * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def kth_largest(x, k: int):
    """The ``k``-th largest of each row of ``x [R, T]`` (float32, -inf
    allowed), exactly: the order of floats is the order of their bits once
    the negative ones are flipped, so the answer is built bit by bit from the
    top, one count of ``x ≥ candidate`` a bit.  32 passes over the rows and no
    sort (``lax.top_k`` of 2,048 out of 16,384 sorts them: PERF.md §6, PR 32)."""
    bits = lax.bitcast_convert_type(x + 0.0, jnp.int32)  # + 0.0: one zero
    ordered = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    keys = lax.bitcast_convert_type(ordered, jnp.uint32) ^ jnp.uint32(0x80000000)

    def bit(i, found):
        candidate = found | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        enough = (keys >= candidate[:, None]).sum(axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, candidate, found)

    found = lax.fori_loop(0, 32, bit, jnp.zeros(x.shape[:1], jnp.uint32))
    ordered = lax.bitcast_convert_type(found ^ jnp.uint32(0x80000000), jnp.int32)
    bits = jnp.where(ordered < 0, ordered ^ jnp.int32(0x7FFFFFFF), ordered)
    return lax.bitcast_convert_type(bits, jnp.float32)


def _index_block(qi, ki, w, start):
    """Index scores of one query block against the keys up to its end:
    ``qi [Bq, J, Di]``, ``ki [T, Di]``, ``w [Bq, J]`` float32 →
    ``[Bq, T]`` float32, -inf where the key comes after the query."""
    pre = jnp.einsum("tjd,sd->tjs", qi, ki, preferred_element_type=jnp.float32)
    scores = (w[:, :, None] * nn.relu(pre)).sum(axis=1) * (qi.shape[1] * qi.shape[2]) ** -0.5
    seen = jnp.arange(ki.shape[0])[None, :] <= start + jnp.arange(qi.shape[0])[:, None]
    return jnp.where(seen, scores, -jnp.inf)


def index_scores(qi, ki, w, start, seq_len: int):
    """:func:`_index_block` of one block of queries of a sequence of
    ``seq_len``, one algorithm with two lowerings, chosen as
    :func:`selected_attention` chooses: the fused kernels
    (``ops/pallas_attention.index_scores``: no ``[J, Bq, T]`` array in HBM,
    forward or backward) where the program is lowered for a TPU and they take
    the sequence length, and so its query blocks; the XLA form everywhere else."""
    kernels = _kernels(seq_len)
    if kernels is None or qi.shape[0] % kernels.LANES or ki.shape[0] % kernels.BLOCK:
        return _index_block(qi, ki, w, start)
    return lax.platform_dependent(qi, ki, w, start, tpu=kernels.index_scores, default=_index_block)


def _threshold_block(scores, start, topk: int):
    """``τ`` of a block's rows: the ``topk``-th largest index score of a query
    with more than ``topk`` keys, and the lowest float (every key, and none of
    the -inf after the query) of one with fewer."""
    lowest = jnp.full(scores.shape[:1], jnp.finfo(jnp.float32).min)
    if scores.shape[1] <= topk:
        return lowest
    few = start + jnp.arange(scores.shape[0]) < topk
    return jnp.where(few, lowest, kth_largest(scores, topk))


def _query_blocks(q, kv: int, block: int):
    """``[S, H, D]`` as ``[KV, S/block, G·block, D]``: a block's rows are the
    G query heads of a k/v head stacked, head-major."""
    s, h, d = q.shape
    q = q.reshape(s // block, block, kv, h // kv, d).transpose(2, 0, 3, 1, 4)
    return q.reshape(kv, s // block, (h // kv) * block, d)


def _attend_selected_block(q, k, v, bias):
    """One query block of one sequence over its selected keys.  q
    ``[KV, G·Bq, D]``, k and v ``[KV, T, D]``, bias ``[Bq, T]``.  Returns the
    block's output and its rows' log-sum-exp ``[KV, G·Bq]`` (detached)."""
    scores = jnp.einsum("kmd,ktd->kmt", q, k, preferred_element_type=jnp.float32)
    scores = scores * (q.shape[-1] ** -0.5)
    scores = scores + jnp.tile(bias.astype(jnp.float32), (q.shape[1] // bias.shape[0], 1))[None]
    probs = _softmax_rows(scores).astype(v.dtype)
    lse = jax.nn.logsumexp(lax.stop_gradient(scores), axis=-1)
    return jnp.einsum("kmt,ktd->kmd", probs, v), lse


def blocked_selected_attention(q, k, v, bias, block: int = 0):
    """The XLA form of ``ops/pallas_attention.selected_attention``, shapes and
    results as there: sequences one after another, each in query blocks that
    see the keys up to their own end (``block`` rows, ``QUERY_BLOCK`` unless
    given), each block rematerialised."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    block = min(block or QUERY_BLOCK, s)

    def one_sequence(args):
        qs, ks, vs, bs = args
        qs = _query_blocks(qs, kv, block)
        ks, vs = ks.transpose(1, 0, 2), vs.transpose(1, 0, 2)
        outs, lses = zip(*[
            jax.checkpoint(_attend_selected_block)(
                qs[:, i], ks[:, : (i + 1) * block], vs[:, : (i + 1) * block],
                bs[i * block : (i + 1) * block, : (i + 1) * block],
            )
            for i in range(s // block)
        ])
        out = jnp.stack(outs, axis=1).reshape(kv, s // block, h // kv, block, d)
        lse = jnp.stack(lses, axis=1).reshape(kv, s // block, h // kv, block)
        return out.transpose(1, 3, 0, 2, 4).reshape(s, h, d), lse.transpose(1, 3, 0, 2).reshape(s, h)

    return lax.map(one_sequence, (q, k, v, bias))


def selected_attention(q, k, v, bias):
    """Attention over each query's selected keys, one algorithm with two
    lowerings, chosen as ``lfm2_moe.causal_attention`` chooses: the fused
    kernels where the program is lowered for a TPU and they take the sequence
    length, :func:`blocked_selected_attention` everywhere else."""
    kernels = _kernels(q.shape[1])
    if kernels is None:
        return blocked_selected_attention(q, k, v, bias)
    return lax.platform_dependent(
        q, k, v, bias, tpu=kernels.selected_attention, default=blocked_selected_attention
    )


def _selection_bias(scores, tau):
    """0 where a query's index score reaches its threshold (ties kept),
    -1e30 elsewhere: what the attention adds to its scores."""
    return jnp.where(scores >= tau[:, None], 0.0, MASKED)


def blocked_head_mean_probs(q, k, lse, heads: int):
    """The XLA form of ``ops/pallas_attention.head_mean_probs``, shapes and
    results as there: every head's float32 probabilities, then their mean."""
    att = jnp.einsum("kmd,ktd->kmt", q, k, preferred_element_type=jnp.float32)
    probs = jnp.exp(att * (q.shape[-1] ** -0.5) - lse[..., None])
    return probs.reshape(heads, -1, probs.shape[-1]).sum(axis=0) / heads


def head_mean_probs(q, k, lse, heads: int, seq_len: int):
    """The mean over the query heads of the attention's probabilities, one
    block of queries of a sequence of ``seq_len`` against the keys of its run:
    two lowerings, chosen as :func:`selected_attention` chooses."""
    xla = functools.partial(blocked_head_mean_probs, heads=heads)
    kernels = _kernels(seq_len)
    if kernels is None:
        return xla(q, k, lse)
    kernel = functools.partial(kernels.head_mean_probs, heads=heads)
    return lax.platform_dependent(q, k, lse, tpu=kernel, default=xla)


def _kl_block(qi, ki, w, tau, q, k, lse, start, heads: int, seq_len: int):
    """Σ over a block's queries of ``KL(p̄_t ‖ softmax_{s∈S_t} I[t,s])``.  The
    index scores are computed again from ``qi``, ``ki``, ``w`` (as
    :func:`index_scores`; the gradient goes through them and nowhere else),
    the selection again from them and ``tau [Bq]``, and the target ``p̄``, the
    mean over the query heads of the attention's probabilities, from the
    detached q ``[KV, G·Bq, D]``, k ``[KV, T, D]`` and the attention's
    log-sum-exp ``[KV, G·Bq]``: one more ``q·kᵀ`` pass, forward only.  So no
    ``[S, S]`` array waits between the passes, nor its cotangent."""
    with jax.named_scope("ddlpc/dsa/indexer"):  # and their gradient, in the backward
        scores = index_scores(qi, ki, w, start, seq_len)
    with jax.named_scope("ddlpc/dsa/kl"):
        picked = _selection_bias(lax.stop_gradient(scores), tau) == 0
        target = head_mean_probs(q, k, lse, heads, seq_len)
        target = jnp.where(picked, target, 0.0)
        logits = jnp.where(picked, scores, MASKED)
        log_index = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        log_target = jnp.log(jnp.where(target > 0, target, 1.0))
        return jnp.sum(target * (log_target - log_index))


class Indexer(nn.Module):
    """The lightning indexer's projections of a detached input: per-query
    heads ``qI [B,S,J,Di]`` and one shared key ``kI [B,S,Di]``, both with
    plain rotary positions, and the per-head weights ``w [B,S,J]``."""

    num_heads: int
    head_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u, cos, sin):
        b, _, s, _ = u.shape
        qi = _proj(self.num_heads * self.head_dim, self.dtype, "q_proj")(u)
        qi = apply_rotary(qi.reshape(b, s, self.num_heads, self.head_dim), cos, sin)
        ki = _proj(self.head_dim, self.dtype, "k_proj")(u).reshape(b, s, 1, self.head_dim)
        ki = apply_rotary(ki, cos, sin)[:, :, 0]
        w = _proj(self.num_heads, self.dtype, "weights_proj")(u).reshape(b, s, self.num_heads)
        return qi, ki, w.astype(jnp.float32)


class SparseAttention(nn.Module):
    """Grouped-query attention over the keys its indexer picks.  Returns the
    operator's output, the indexer's KL (None unless ``want_kl``) and the
    pairs selected."""

    cfg: ModelConfig
    want_kl: bool

    @nn.compact
    def __call__(self, u, tables, index_tables):
        c, dtype = self.cfg, jnp.dtype(self.cfg.compute_dtype)
        b, _, s, _ = u.shape
        heads, kv = c.num_attention_heads, c.num_key_value_heads
        d = c.head_dim or c.hidden_size // heads
        block = min(QUERY_BLOCK, s)
        if s % block:
            raise ValueError(f"sequence length {s} is not a multiple of the query block {block}")
        # runs of query blocks [first, last) and the keys they see
        firsts = range(0, s // block, BLOCKS_PER_SCAN)
        runs = [(i, j, j * block) for i in firsts for j in [min(i + BLOCKS_PER_SCAN, s // block)]]
        blocked = lambda x, i, j: x[i * block : j * block].reshape(j - i, block, *x.shape[1:])  # noqa: E731
        starts = lambda i, j: jnp.arange(i, j) * block  # noqa: E731

        with jax.named_scope("ddlpc/dsa/indexer"):
            qi, ki, w = Indexer(c.indexer_num_heads, c.indexer_head_dim, dtype, name="indexer")(
                lax.stop_gradient(u), *index_tables
            )

        def select(qs, ks, ws):  # one sequence -> its bias [S, S]; no gradient
            def body(ks, x):
                with jax.named_scope("ddlpc/dsa/indexer"):
                    scores = index_scores(x[0], ks, x[1], x[2], s)
                with jax.named_scope("ddlpc/dsa/select"):
                    tau = _threshold_block(scores, x[2], c.indexer_topk)
                    return ks, (tau, _selection_bias(scores, tau).astype(jnp.bfloat16))

            taus, biases = [], []
            for i, j, keys in runs:
                xs = (blocked(qs, i, j), blocked(ws, i, j), starts(i, j))
                _, (tau, bias) = lax.scan(body, ks[:keys], xs)
                with jax.named_scope("ddlpc/dsa/select"):
                    bias = bias.reshape(-1, keys)
                    biases.append(jnp.pad(bias, ((0, 0), (0, s - keys)), constant_values=MASKED))
                    taus.append(tau.reshape(-1))
            return jnp.concatenate(taus), jnp.concatenate(biases)

        # Sequences one after another: a micro-batch of this family is one or two.
        tau, bias = (
            jnp.stack(x)
            for x in zip(*[select(*lax.stop_gradient((qi[n], ki[n], w[n]))) for n in range(b)])
        )
        with jax.named_scope("ddlpc/dsa/select"):
            selected = (bias == 0).sum(dtype=jnp.int32)

        with jax.named_scope("ddlpc/attention"):
            q = _proj(heads * d, dtype, "q_proj")(u).reshape(b, s, heads, d)
            k = _proj(kv * d, dtype, "k_proj")(u).reshape(b, s, kv, d)
            v = _proj(kv * d, dtype, "v_proj")(u).reshape(b, s, kv, d)
            q = apply_rotary(RMSNorm(c.norm_eps, dtype, name="q_norm")(q), *tables)
            k = apply_rotary(RMSNorm(c.norm_eps, dtype, name="k_norm")(k), *tables)
            out, lse = selected_attention(q, k, v, bias)
            out = _proj(c.hidden_size, dtype, "o_proj")(out.reshape(b, 1, s, heads * d))

        kl = None
        if self.want_kl:

            def kl_of(n, qs, ks, ls):  # sequence n, with its detached q, k, log-sum-exp
                with jax.named_scope("ddlpc/dsa/kl"):
                    qs, ks = _query_blocks(qs, kv, block), ks.transpose(1, 0, 2)
                    ls = _query_blocks(ls[..., None], kv, block)[..., 0]
                # outside the scopes: a block names its own ops, indexer and kl
                block_kl = jax.checkpoint(functools.partial(_kl_block, heads=heads, seq_len=s))
                total = 0.0
                for i, j, keys in runs:
                    seen = (ki[n, :keys], ks[:, :keys])

                    def body(total, x, seen=seen):
                        return total + block_kl(x[0], seen[0], x[1], x[2], x[3], seen[1], x[4], x[5]), None

                    xs = (
                        blocked(qi[n], i, j), blocked(w[n], i, j), blocked(tau[n], i, j),
                        qs[:, i:j].swapaxes(0, 1), ls[:, i:j].swapaxes(0, 1), starts(i, j),
                    )
                    total = total + lax.scan(body, jnp.float32(0.0), xs)[0]
                return total

            qs, ks, ls = lax.stop_gradient((q, k, lse))
            kl = sum(kl_of(n, qs[n], ks[n], ls[n]) for n in range(b)) / (b * s)
        return out, kl, selected


class DecoderLayer(nn.Module):
    """``x = h + Attn(norm(h)); h' = x + MoE(norm(x))``."""

    cfg: ModelConfig
    want_kl: bool

    @nn.compact
    def __call__(self, h, tables, index_tables):
        c, dtype = self.cfg, jnp.dtype(self.cfg.compute_dtype)
        u = RMSNorm(c.norm_eps, dtype, name="operator_norm")(h)
        op, kl, selected = SparseAttention(c, self.want_kl, name="self_attn")(
            u, tables, index_tables
        )
        x = h + op
        u = RMSNorm(c.norm_eps, dtype, name="ffn_norm")(x)
        ffn, counts = RoutedExperts(
            c.hidden_size, c.moe_intermediate_size, c.num_experts, c.num_experts_per_tok,
            c.experts_held, c.expert_offset, c.norm_topk_prob, c.routed_scaling_factor,
            False, dtype, c.router_score, name="feed_forward",
        )(u)
        return x + ffn, kl, selected, counts


class KeyeVL2(nn.Module):
    """``cfg`` is the configuration's ``model`` group itself: the family's
    shapes under their published names (``config.py:ModelConfig``)."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, images, train: bool = False):
        c, dtype = self.cfg, jnp.dtype(self.cfg.compute_dtype)
        ids = images[..., 0]  # [B, 1, S]
        if jnp.issubdtype(ids.dtype, jnp.floating):
            ids = ids.astype(jnp.int32)
        s = ids.shape[-1]
        with jax.named_scope("ddlpc/embed"):
            embedding = self.param(
                "embedding", _EMBEDDING_INIT, (c.num_classes, c.hidden_size), jnp.float32
            )
            h = embedding.astype(dtype).at[ids].get(mode="promise_in_bounds")
        # Text tiles: the three position streams are one (data/datasets.py
        # gives no other); the indexer turns by the first.
        d = c.head_dim or c.hidden_size // c.num_attention_heads
        sections = c.mrope_section or (d // 2,)
        positions = jnp.broadcast_to(jnp.arange(s), (len(sections), s))
        tables = mrope_tables(positions, d, c.rope_theta, sections)
        index_tables = mrope_tables(
            positions[:1], c.indexer_head_dim, c.rope_theta, (c.indexer_head_dim // 2,)
        )
        # The model's own loss exists where the caller collects it (the train
        # step does; benchmark/check.py and evaluation do not).
        want_kl = train and self.is_mutable_collection("losses")
        # The backward keeps each layer's input and recomputes its body.
        layer_cls = nn.remat(DecoderLayer) if train else DecoderLayer
        layers = [
            layer_cls(c, want_kl, name=f"layers_{i}") for i in range(len(c.layer_types))
        ]
        kls, picked, routed = [], [], []
        for layer in layers:
            h, kl, selected, counts = layer(h, tables, index_tables)
            kls.append(kl)
            picked.append(selected)
            routed.append(counts)
        with jax.named_scope("ddlpc/head"):
            hn = RMSNorm(c.norm_eps, dtype, name="final_norm")(h)
            head = embedding
            if not c.tie_word_embeddings:
                head = self.param("lm_head", _INIT, (c.num_classes, c.hidden_size), jnp.float32)
            logits = jnp.einsum(
                "bhsc,vc->bhsv", hn, head.astype(dtype),
                preferred_element_type=jnp.dtype(c.head_dtype),
            )
        if want_kl:
            self.sow(
                "losses", "indexer_kl", sum(kls), reduce_fn=lambda _, v: v, init_fn=lambda: 0
            )
        sums = {
            "tokens_per_step": jnp.int32(ids.size),
            "dsa_pairs_selected": sum(picked),
            "dsa_pairs_causal": jnp.int32(len(layers) * ids.shape[0] * (s * (s + 1) // 2)),
        }
        sums |= jax.tree.map(lambda *v: sum(v), *[r["sum"] for r in routed])
        # layers whose attention, index scores and KL target lowered to the kernels
        lowered = len(layers) * _kernel_lowers(s)
        maxes = {
            "dsa_kernel_layers": lowered,
            "dsa_index_kernel_layers": lowered,
            "dsa_kl_kernel_layers": lowered * int(want_kl),
        }
        maxes |= jax.tree.map(lambda *v: jnp.stack(v).max(), *[r["max"] for r in routed])
        for kind, values in (("sum", sums), ("max", maxes)):
            self.sow("counters", kind, values, reduce_fn=lambda _, v: v, init_fn=lambda: 0)
        return logits
