"""LFM2-MoE (``model_type: lfm2_moe``) as a per-position classifier over a
1×S token tile, one expert-parallel rank's share.

The family (LiquidAI LFM2-24B-A2B, config.json): a decoder whose operator is
a doubly gated depthwise causal convolution of length ``conv_L_cache`` in
``conv`` layers and grouped-query attention (per-head RMS norm on q and k,
rotary positions) in ``full_attention`` layers; the feed-forward is a SwiGLU
in the ``num_dense_layers`` leading layers and sigmoid-scored
top-``num_experts_per_tok``-of-``num_experts`` routed experts, selected with a
bias that does not weigh, in the others.  Equations in ISSUE 27 / PERF.md §4;
the plain float32 reference is ``benchmark/reference/lfm2_moe.py``.

In this system's terms a tile is one packed sequence: ``images`` is
``int32[B, 1, S, 1]`` (token ids), the logits are ``[B, 1, S, vocab]`` and
``labels[B, 1, S]`` is the next token, so the loss, the step and the Trainer
are the zoo's own.  A floating input is cast to ids (``benchmark/flops.py``
and ``create_train_state`` callers trace with float32; float32 holds every id
under 2**24 exactly).

The share: the router scores all ``num_experts`` and picks the top k at any
size; the layer holds experts ``[expert_offset, expert_offset +
experts_held)`` and adds their part for the tokens routed to them.  What the
absent experts would add is left out (model-configs guide §4); nothing stands
in for the other ranks or their exchange.  No token is dropped: the (token,
expert) pairs are sorted with the held experts' groups first, and the expert
block runs on a compact buffer of the first ``C`` sorted rows (``buffer_rows``,
static: twice this rank's share of the ``tokens × k`` pairs under uniform
routing, and no more bytes than the TPU compiler keeps in VMEM as a gather's
table) and, where a device-side count finds more routed rows than that, again
on the next ``C`` until every routed row is held: the worst case costs
buffers, not rows.  The block is differentiated as a whole
(``_expert_block``), because reverse mode through a choice made on the device
would store every buffer's residuals, run or not.

Position-wise projections are ``flax.linen.Dense`` (measured faster than the
zoo's 1×1 ``nn.Conv`` on the chip), so the conv FLOP walks see none of this
family's work: ``obs/flops.step_flops`` counts its matrix products for the
program and ``benchmark/seq_flops.py`` for the benchmark.  Compute is
``compute_dtype`` (bf16) with float32 parameters; router scores, norm
statistics, softmax and rotary angles are float32.  Under ``train=True`` each layer's body is rematerialised
(``nn.remat``), so what the backward keeps is the residual stream.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from ddlpc_tpu.config import ModelConfig

_INIT = nn.initializers.normal(stddev=0.02)
# Query rows per block of the XLA causal attention (no [H, S, S] scores).
QUERY_BLOCK = 512


def _proj(features: int, dtype, name: str) -> nn.Dense:
    """A bias-free position-wise projection over the last axis of [B,1,S,C]:
    a ``dot_general``, not the 1×1 ``nn.Conv`` of the conv zoo, which the TPU
    compiler runs slower at these shapes (2048→6144 over 32,768 positions on a
    v5e: forward 7.93 against 4.65 ms, forward and backward 16.4 against
    13.5; PERF.md §6, PR 27)."""
    return nn.Dense(features, use_bias=False, dtype=dtype, param_dtype=jnp.float32, name=name)


class RMSNorm(nn.Module):
    """``x · rsqrt(mean(x²) + eps) · g`` over the last axis, statistics in float32."""

    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y * g).astype(self.dtype)


def rotary_tables(seq_len: int, head_dim: int, theta: float):
    """cos, sin ``[S, head_dim]`` in float32: the half-split (``rotate_half``)
    convention of the family's published modelling code, angles
    ``t · theta^(-2i/head_dim)`` repeated over both halves."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(x, cos, sin):
    """x ``[..., S, heads, head_dim]``; cos, sin ``[S, head_dim]``."""
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[:, None, :] + rotated * sin[:, None, :]).astype(x.dtype)


@jax.custom_vjp
def _softmax_rows(x):
    """Softmax over the last axis in float32, with each row statistic
    (max, sum, and the backward's Σ dp·p) materialised on its own.  Left to
    fuse a row reduction with its broadcast, the TPU compiler turns it into a
    ``reduce-window`` as wide as the row, which costs O(T) an element: 4.0 of
    5.8 s a step at S = 8192 (PERF.md §6, PR 27)."""
    m = lax.optimization_barrier(jnp.max(x, axis=-1))
    e = jnp.exp(x - m[..., None])
    z = lax.optimization_barrier(jnp.sum(e, axis=-1))
    return e / z[..., None]


def _softmax_rows_fwd(x):
    p = _softmax_rows(x)
    return p, p


def _softmax_rows_bwd(p, g):
    inner = lax.optimization_barrier(jnp.sum(g * p, axis=-1))
    return (p * (g - inner[..., None]),)


_softmax_rows.defvjp(_softmax_rows_fwd, _softmax_rows_bwd)


def _attend_block(q, k, v, start: int):
    """One query block of one sequence against the keys up to its end, one
    batched product per k/v head.  q ``[KV, G·Bq, D]`` (the G query heads of
    a k/v head stacked, head-major), k and v ``[KV, T, D]`` with
    ``T = start + Bq``.  The scores are ``[KV, G·Bq, T]`` with the keys in
    the minor dimension, where the softmax reduces."""
    block = k.shape[1] - start
    scores = jnp.einsum("kmd,ktd->kmt", q, k, preferred_element_type=jnp.float32)
    scores = scores * (q.shape[-1] ** -0.5)
    qpos = start + jnp.arange(q.shape[1]) % block
    visible = jnp.arange(k.shape[1])[None, :] <= qpos[:, None]
    scores = jnp.where(visible, scores, -1e30)
    probs = _softmax_rows(scores).astype(v.dtype)
    return jnp.einsum("kmt,ktd->kmd", probs, v)


def blocked_causal_attention(q, k, v, block: int):
    """Causal softmax(q kᵀ / sqrt(D)) v without the ``[H, S, S]`` scores:
    sequences one after another, each in query blocks that see only the keys
    up to their own end (the causal half, to within a block), each block
    rematerialised in the backward.  q ``[B, S, H, D]``; k, v ``[B, S, KV, D]``
    with each k/v head serving ``H / KV`` query heads."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    block = min(block, s)
    if s % block:
        raise ValueError(f"sequence length {s} is not a multiple of the query block {block}")
    # heads to the front: [B, KV, S/block, G·block, D] and [B, KV, S, D]
    q = q.reshape(b, s // block, block, kv, g, d).transpose(0, 3, 1, 4, 2, 5)
    q = q.reshape(b, kv, s // block, g * block, d)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

    def one_sequence(qkv):
        qs, ks, vs = qkv
        out = [
            jax.checkpoint(functools.partial(_attend_block, start=i * block))(
                qs[:, i], ks[:, : (i + 1) * block], vs[:, : (i + 1) * block]
            )
            for i in range(s // block)
        ]
        return jnp.stack(out, axis=1)  # [KV, S/block, G·block, D]

    out = lax.map(one_sequence, (q, k, v)).reshape(b, kv, s // block, g, block, d)
    return out.transpose(0, 2, 4, 1, 3, 5).reshape(b, s, h, d)


def _kernels(seq_len: int):
    """``ops/pallas_attention`` where its kernels take the sequence length,
    else None.  Imported here: Pallas takes a second to import, and only a
    model that attends pays it."""
    from ddlpc_tpu.ops import pallas_attention

    return pallas_attention if pallas_attention.supported(seq_len) else None


def _kernel_lowers(seq_len: int):
    """int32 1 where :func:`causal_attention` lowers to the fused kernel, 0
    where it lowers to the XLA form: read from the platform the program is
    lowered for and from the sequence length, like the choice itself."""
    if _kernels(seq_len) is None:
        return jnp.int32(0)
    return lax.platform_dependent(tpu=lambda: jnp.int32(1), default=lambda: jnp.int32(0))


def causal_attention(q, k, v):
    """Causal grouped-query attention, one algorithm with two lowerings: the
    fused kernel (``ops/pallas_attention.py``: no scores in HBM) where the
    program is lowered for a TPU and the kernels take the sequence length (a
    multiple of their block, short enough for their VMEM),
    :func:`blocked_causal_attention` everywhere else.  Shapes as there."""
    xla = functools.partial(blocked_causal_attention, block=QUERY_BLOCK)
    kernels = _kernels(q.shape[1])
    if kernels is None:
        return xla(q, k, v)
    return lax.platform_dependent(q, k, v, tpu=kernels.causal_attention, default=xla)


class ShortConv(nn.Module):
    """``W_out (C ⊙ conv(B ⊙ X))`` with ``(B, C, X) = split₃(W_in u)`` and a
    depthwise causal convolution of ``length`` taps (zeros before the start)."""

    hidden: int
    length: int = 3
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        gate_b, gate_c, x = jnp.split(_proj(3 * self.hidden, self.dtype, "in_proj")(u), 3, axis=-1)
        taps = self.param("conv_kernel", _INIT, (self.length, self.hidden), jnp.float32)
        z = gate_b * x
        s = z.shape[-2]
        padded = jnp.pad(z, ((0, 0), (0, 0), (self.length - 1, 0), (0, 0)))
        # c_t = Σ_j taps[j] ⊙ z_{t - (length-1) + j}
        c = sum(
            taps[j].astype(self.dtype) * padded[:, :, j : j + s] for j in range(self.length)
        )
        return _proj(self.hidden, self.dtype, "out_proj")(gate_c * c)


class Attention(nn.Module):
    hidden: int
    num_heads: int
    num_kv_heads: int
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u, cos, sin):
        b, _, s, _ = u.shape
        d = self.hidden // self.num_heads
        q = _proj(self.num_heads * d, self.dtype, "q_proj")(u).reshape(b, s, self.num_heads, d)
        k = _proj(self.num_kv_heads * d, self.dtype, "k_proj")(u).reshape(b, s, self.num_kv_heads, d)
        v = _proj(self.num_kv_heads * d, self.dtype, "v_proj")(u).reshape(b, s, self.num_kv_heads, d)
        q = apply_rotary(RMSNorm(self.eps, self.dtype, name="q_norm")(q), cos, sin)
        k = apply_rotary(RMSNorm(self.eps, self.dtype, name="k_norm")(k), cos, sin)
        out = causal_attention(q, k, v)
        return _proj(self.hidden, self.dtype, "o_proj")(out.reshape(b, 1, s, self.hidden))


class SwiGLU(nn.Module):
    hidden: int
    width: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        h = nn.silu(_proj(self.width, self.dtype, "w1")(u)) * _proj(self.width, self.dtype, "w3")(u)
        return _proj(self.hidden, self.dtype, "w2")(h)


# The expert block's buffer, two rules and the smaller of them.
# BUFFER_SHARES times this rank's share of the (token, expert) pairs under
# uniform routing: in the benchmark's window the held share of the pairs is
# 11-14 % where uniform routing gives 12.5 % (PERF.md section 5), so a whole
# share of room.  (``moe_max_load`` is one expert's load against the mean of
# the held ones; it says nothing of their total, which is what the buffer
# holds.)  And no more than BUFFER_TABLE_BYTES: the token side gathers every
# pair's row out of the buffer, and the TPU compiler stages a gather's table
# in VMEM up to a size, 112 MiB on the v5e with its 128 MiB (131,072 rows of
# 4 KiB gathered in 0.83 ms out of 96 MiB, 4.35 out of 128; the routed layer
# forward and backward 26.1 against 44.5 ms; PERF.md section 6, PR 30).  A
# rank whose routed rows pass the buffer runs the block again on the next
# rows (``_plus_later_buffers``), so a smaller buffer costs passes, not rows.
BUFFER_SHARES = 2
BUFFER_TABLE_BYTES = 96 << 20
BUFFER_ROW_MULTIPLE = 512  # a buffer is whole row tiles of the grouped products


def buffer_rows(pairs: int, experts_held: int, num_experts: int, row_bytes: int) -> int:
    """Rows of the expert block's buffer for ``pairs`` (token, expert) pairs of
    ``row_bytes`` each: from shapes alone, and all the pairs (no later buffer,
    no loop) where BUFFER_SHARES shares of them are all there are."""
    tile = BUFFER_ROW_MULTIPLE
    share = -(-BUFFER_SHARES * pairs * experts_held // num_experts)
    rows = -(-share // tile) * tile
    if rows >= pairs:
        return pairs
    return min(rows, max(BUFFER_TABLE_BYTES // row_bytes // tile, 1) * tile)


def _rows_at(table, at):
    """``table[at]`` along the first axis, for in-bounds ``at``."""
    return table.at[at].get(mode="promise_in_bounds")


def _buffer_view(rows, start, weights, inverse, sizes):
    """Of the buffer holding sorted rows ``[start, start + rows)``: its group
    sizes, the mask of its rows that lie in a group, and per pair ``[N, k]``
    its row in the buffer (clipped) and whether the buffer holds it."""
    ends = jnp.cumsum(sizes)
    local = jnp.clip(ends - start, 0, rows) - jnp.clip(ends - sizes - start, 0, rows)
    in_group = (jnp.arange(rows) < local.sum())[:, None]
    at = inverse.reshape(weights.shape) - start
    return local, in_group, jnp.clip(at, 0, rows - 1), (at >= 0) & (at < rows)


def _buffer_forward(rows, start, x, w1, w3, w2, weights, order, inverse, sizes):
    """The held experts' part ``y [N, d]`` of the tokens ``x [N, d]`` from the
    pairs in sorted rows ``[start, start + rows)`` (held groups first, so the
    buffer at 0 holds every held pair when ``sizes.sum() <= rows``), the count
    of held rows the grouped products wrote, and what the backward reads
    again.  ``weights [N, k]`` is zero where a pair's expert is not held;
    ``order`` maps a sorted row to its pair, ``inverse`` a pair to its row."""
    k = weights.shape[1]
    with jax.named_scope("ddlpc/moe/route"):
        sizes, in_group, at, inside = _buffer_view(rows, start, weights, inverse, sizes)
        token = lax.dynamic_slice(order, (start,), (rows,)) // k
        # The grouped products leave the rows past the last group unwritten
        # (NaN on the TPU), forward and backward, so both ends of the expert
        # block are masked.
        xs = jnp.where(in_group, _rows_at(x, token), 0)
    with jax.named_scope("ddlpc/moe/experts"):
        grouped = functools.partial(
            lax.ragged_dot, group_sizes=sizes, preferred_element_type=x.dtype
        )
        a1, a3 = grouped(xs, w1), grouped(xs, w3)
        h = nn.silu(a1) * a3
        out = grouped(h, w2)
    with jax.named_scope("ddlpc/moe/route"):
        # Rows of the held groups that the products wrote: finite and not
        # all zero (an unwritten row reads NaN on the TPU, zero on the CPU).
        total = jnp.abs(out.astype(jnp.float32)).sum(axis=-1, keepdims=True)
        written = (in_group & jnp.isfinite(total) & (total > 0)).sum(dtype=jnp.int32)
        out = jnp.where(in_group, out, 0)
        # Each token's weighted sum over its k pairs in float32, a gather of
        # [N] rows a pair: pair-major, the [N, k, d] view of the gathered rows
        # would be a copy into another tiling.  A pair outside the buffer
        # weighs 0 and any finite row serves.
        weights = jnp.where(inside, weights, 0.0)
        y = sum(
            weights[:, j : j + 1] * _rows_at(out, at[:, j]).astype(jnp.float32)
            for j in range(k)
        ).astype(x.dtype)
    return y, written, (xs, a1, a3, h, out)


def _grouped_pullback(lhs, rhs, sizes, g):
    """``(d lhs, d rhs)`` of ``ragged_dot(lhs, rhs)`` for the cotangent ``g``:
    the product is linear in each operand, so neither needs the forward."""
    grouped = functools.partial(lax.ragged_dot, group_sizes=sizes, preferred_element_type=g.dtype)
    (d_lhs,) = jax.linear_transpose(lambda a: grouped(a, rhs), lhs)(g)
    (d_rhs,) = jax.linear_transpose(lambda b: grouped(lhs, b), rhs)(g)
    return d_lhs, d_rhs


def _buffer_backward(rows, start, saved, g, x, w1, w3, w2, weights, order, inverse, sizes):
    """Cotangents of ``x, w1, w3, w2, weights`` for ``g = dy [N, d]`` through
    the buffer :func:`_buffer_forward` ran on.  What crosses between tokens and
    buffer rows is a gather either way (by ``order`` into the buffer, by
    ``inverse`` out of it) and a sum over each token's k pairs: a scatter-add
    serialises on the TPU."""
    k = weights.shape[1]
    xs, a1, a3, h, out = saved
    with jax.named_scope("ddlpc/moe/route"):
        sizes, in_group, at, inside = _buffer_view(rows, start, weights, inverse, sizes)
        pair = lax.dynamic_slice(order, (start,), (rows,))
        g_rows = _rows_at(g, pair // k).astype(jnp.float32)
        w_rows = _rows_at(weights.reshape(-1), pair)[:, None]
        d_out = jnp.where(in_group, (w_rows * g_rows).astype(out.dtype), 0)
        d_w_rows = (g_rows * out.astype(jnp.float32)).sum(axis=-1)
    with jax.named_scope("ddlpc/moe/experts"):
        d_h, d_w2 = _grouped_pullback(h, w2, sizes, d_out)
        d_a1, d_a3 = jax.vjp(lambda a, b: nn.silu(a) * b, a1, a3)[1](d_h)
        d_xs1, d_w1 = _grouped_pullback(xs, w1, sizes, d_a1)
        d_xs3, d_w3 = _grouped_pullback(xs, w3, sizes, d_a3)
    with jax.named_scope("ddlpc/moe/route"):
        d_xs = jnp.where(in_group, d_xs1 + d_xs3, 0)
        d_x = sum(
            jnp.where(inside[:, j : j + 1], _rows_at(d_xs, at[:, j]).astype(jnp.float32), 0)
            for j in range(k)
        ).astype(x.dtype)
        d_weights = jnp.where(inside, _rows_at(d_w_rows, at), 0.0)
    return d_x, d_w1, d_w3, d_w2, d_weights


def _plus_later_buffers(rows, order, sizes, total, buffer):
    """``total`` (the buffer at sorted row 0) plus ``buffer(start)`` for every
    later buffer of ``rows`` rows that holds routed rows, as many as a count
    on the device says: none where the routed rows fit the first, enough to
    hold all the pairs at the worst skew, so nothing is dropped and nothing
    recompiles."""
    if order.shape[0] == rows:
        return total
    filled = -(-sizes.sum() // rows)
    add = lambda i, total: jax.tree.map(jnp.add, total, buffer(i * rows))  # noqa: E731
    return lax.fori_loop(1, filled, add, total)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _expert_block(rows, x, w1, w3, w2, weights, order, inverse, sizes):
    """``(y, written)`` of :func:`_buffer_forward` over as many buffers of
    ``rows`` rows as the routed rows fill (``order`` is padded to whole
    buffers).  Differentiated as a whole, because the number of buffers is
    read on the device: reverse mode through that choice would keep every
    buffer's residuals whether it ran or not and fill the idle ones with
    zeros, which costs what the compact buffer saves.  The forward keeps the
    first buffer's residuals alone; the backward recomputes a later buffer's
    before it pulls back through it, so the overflow pays for itself."""
    return _expert_block_fwd(rows, x, w1, w3, w2, weights, order, inverse, sizes)[0]


def _expert_block_fwd(rows, *operands):
    *_, order, _, sizes = operands
    y, written, saved = _buffer_forward(rows, 0, *operands)
    later = lambda start: _buffer_forward(rows, start, *operands)[:2]  # noqa: E731
    return _plus_later_buffers(rows, order, sizes, (y, written), later), (saved, operands)


def _expert_block_bwd(rows, residuals, cotangents):
    saved, operands = residuals
    *_, order, _, sizes = operands
    g = cotangents[0]

    def later(start):
        again = _buffer_forward(rows, start, *operands)[2]
        return _buffer_backward(rows, start, again, g, *operands)

    first = _buffer_backward(rows, 0, saved, g, *operands)
    return (*_plus_later_buffers(rows, order, sizes, first, later), None, None, None)


_expert_block.defvjp(_expert_block_fwd, _expert_block_bwd)


class RoutedExperts(nn.Module):
    """Top-k routing over all ``num_experts`` and the SwiGLU experts held
    here.  Returns the held experts' part of the layer's output and the
    layer's routing counts.  ``score`` says how a token's scores are made of
    the router's outputs: ``sigmoid`` (each expert on its own; the picked
    ones' sum is normalised behind 1e-6) or ``softmax`` (over all the
    experts; the picked ones renormalise with no epsilon).  Everything after
    the scores is the same.

    The buffer: the (token, expert) pairs sort with the held groups first, and
    the expert side of the block (the dispatch gather, the masks, the three
    grouped products and the gate between them, the written-row count) runs
    on the first ``C`` sorted rows, ``C = buffer_rows(...)``, fixed at trace
    time from shapes: twice this rank's share of the ``tokens x k`` pairs
    under uniform routing, no more bytes than a gather's table that stays in
    VMEM, and all the pairs where every expert is held.  The token side (each
    token's weighted sum over its k pairs, and the backward of the dispatch)
    still spans all the pairs, as gathers out of the ``C`` rows.  The
    fallback: ``routed`` is a device scalar, and where ``routed > C`` the same
    block runs again on the next ``C`` sorted rows, and again, until the
    buffers hold every routed row: at most all the pairs' worth, so nothing is
    dropped at any skew, nothing recompiles between a balanced and a skewed
    batch, and no full-size temporary exists (one full-size pass beside the
    compact residuals does not fit the chip: 16.998 of 16.9 GB).  What it
    costs (TPU v5 lite, 32,768 tokens x top-4, 8 of 64 held, ``C`` 24,576,
    forward and backward under ``nn.remat``; PERF.md section 6, PR 30): 25.6 ms
    with one buffer and 30.5 more for each further one, against 73.3 ms on a
    buffer of all the pairs at the same 12.6 % of them routed and 129.1 at
    100 %.  Up to three buffers (55 % of the pairs routed, 4.4 times the share)
    the compact form is ahead, 86.7 against 100.7 ms; from five (77 %) it is
    behind, 133.9 against 115.0, and at the worst, six buffers for every pair,
    164.6 against 129.1.  ``moe_rows_buffered`` counts the buffers' rows.
    :func:`_expert_block` says why the block is differentiated as a whole."""

    hidden: int
    width: int
    num_experts: int
    top_k: int
    experts_held: int
    expert_offset: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    dtype: Any = jnp.bfloat16
    score: str = "sigmoid"  # sigmoid | softmax

    @nn.compact
    def __call__(self, u):
        shape = u.shape
        x = u.reshape(-1, self.hidden)
        n, k, held_n = x.shape[0], self.top_k, self.experts_held
        gate = self.param("gate", _INIT, (self.hidden, self.num_experts), jnp.float32)
        expert_init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,))
        w1 = self.param("w1", expert_init, (held_n, self.hidden, self.width), jnp.float32)
        w3 = self.param("w3", expert_init, (held_n, self.hidden, self.width), jnp.float32)
        w2 = self.param("w2", expert_init, (held_n, self.width, self.hidden), jnp.float32)

        with jax.named_scope("ddlpc/moe/route"):
            # float32 at full precision: a bf16 pass would flip selections.
            squash = {"sigmoid": jax.nn.sigmoid, "softmax": _softmax_rows}[self.score]
            scores = squash(
                jnp.dot(x.astype(jnp.float32), gate, precision=lax.Precision.HIGHEST)
            )
            choose = scores
            if self.use_expert_bias:
                # A constant of the configuration (no update rule is published):
                # it selects and does not weigh, and takes no gradient.
                bias = self.param("expert_bias", _INIT, (self.num_experts,), jnp.float32)
                choose = scores + lax.stop_gradient(bias)
            _, selected = lax.top_k(choose, k)  # [N, k] expert ids
            # the selected experts' scores by compare-and-sum: the backward of
            # take_along_axis is a scatter, which serialises on the TPU
            chosen = selected[..., None] == jnp.arange(self.num_experts)  # [N, k, E]
            weights = jnp.sum(jnp.where(chosen, scores[:, None, :], 0.0), axis=-1)
            if self.norm_topk_prob:
                total = weights.sum(axis=-1, keepdims=True)
                weights = weights / (total + 1e-6 if self.score == "sigmoid" else total)
            weights = weights * self.routed_scaling_factor

            local = selected - self.expert_offset
            held = (local >= 0) & (local < held_n)
            weights = jnp.where(held, weights, 0.0)
            group = jnp.where(held, local, held_n).reshape(-1)  # absent experts sort last
            order = jnp.argsort(group, stable=True)  # sorted row -> pair (token·k + j)
            inverse = jnp.argsort(order)  # pair -> sorted row
            # (a compare-and-sum, not bincount: a scatter-add of every pair
            # into a handful of bins serialises on the TPU)
            sizes = (group[:, None] == jnp.arange(held_n)).sum(axis=0, dtype=jnp.int32)

            # whole buffers of the sorted rows: the padding sorts past every pair
            rows = buffer_rows(n * k, held_n, self.num_experts, x.dtype.itemsize * self.hidden)
            order = jnp.pad(order, (0, -(n * k) % rows))

        # Outside both scopes: the block names its own ops, route and experts.
        y, written = _expert_block(
            rows, x, w1.astype(self.dtype), w3.astype(self.dtype), w2.astype(self.dtype),
            weights, order, inverse, sizes,
        )

        self.sow("intermediates", "group_sizes", sizes)
        held_pairs = held.sum(dtype=jnp.int32)
        sums = {
            "moe_rows_routed": held_pairs,
            "moe_rows_offered": jnp.int32(n * k),
            # Rows of the buffers the block ran on: one, or as many as the
            # routed rows filled.
            "moe_rows_buffered": rows * jnp.maximum(-(-held_pairs // rows), 1),
            # Pairs whose expert is held and whose row the grouped products
            # did not write; a run is not sound unless it stays 0.
            "moe_rows_dropped": held_pairs - written,
        }
        maxes = {"moe_max_load": sizes.max() / jnp.maximum(sizes.mean(dtype=jnp.float32), 1.0)}
        return y.reshape(shape), {"sum": sums, "max": maxes}


class DecoderLayer(nn.Module):
    """``x = h + Op(norm(h)); h' = x + FFN(norm(x))``."""

    cfg: ModelConfig
    kind: str  # conv | full_attention
    dense: bool

    @nn.compact
    def __call__(self, h, cos, sin):
        c, dtype = self.cfg, jnp.dtype(self.cfg.compute_dtype)
        u = RMSNorm(c.norm_eps, dtype, name="operator_norm")(h)
        if self.kind == "full_attention":
            with jax.named_scope("ddlpc/attention"):
                op = Attention(
                    c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                    c.norm_eps, dtype, name="self_attn",
                )(u, cos, sin)
        else:
            with jax.named_scope("ddlpc/short_conv"):
                op = ShortConv(c.hidden_size, c.conv_L_cache, dtype, name="conv")(u)
        x = h + op
        u = RMSNorm(c.norm_eps, dtype, name="ffn_norm")(x)
        if self.dense:
            with jax.named_scope("ddlpc/dense_ffn"):
                ffn = SwiGLU(c.hidden_size, c.intermediate_size, dtype, name="feed_forward")(u)
            counts = None
        else:
            ffn, counts = RoutedExperts(
                c.hidden_size, c.moe_intermediate_size, c.num_experts, c.num_experts_per_tok,
                c.experts_held, c.expert_offset, c.norm_topk_prob, c.routed_scaling_factor,
                c.use_expert_bias, dtype, name="feed_forward",
            )(u)
        return x + ffn, counts


class LFM2MoE(nn.Module):
    """``cfg`` is the configuration's ``model`` group itself: the family's
    shapes under their published names (``config.py:ModelConfig``)."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, images, train: bool = False):
        c, dtype = self.cfg, jnp.dtype(self.cfg.compute_dtype)
        ids = images[..., 0]  # [B, 1, S]
        if jnp.issubdtype(ids.dtype, jnp.floating):
            ids = ids.astype(jnp.int32)
        with jax.named_scope("ddlpc/embed"):
            embedding = self.param(
                "embedding", _INIT, (c.num_classes, c.hidden_size), jnp.float32
            )
            h = embedding.astype(dtype).at[ids].get(mode="promise_in_bounds")
        cos, sin = rotary_tables(
            ids.shape[-1], c.hidden_size // c.num_attention_heads, c.rope_theta
        )
        # The backward keeps each layer's input and recomputes its body.
        layer_cls = nn.remat(DecoderLayer) if train else DecoderLayer
        routed = []
        for i, kind in enumerate(c.layer_types):
            h, counts = layer_cls(c, kind, i < c.num_dense_layers, name=f"layers_{i}")(
                h, cos, sin
            )
            if counts is not None:
                routed.append(counts)
        with jax.named_scope("ddlpc/head"):
            hn = RMSNorm(c.norm_eps, dtype, name="final_norm")(h)
            logits = jnp.einsum(
                "bhsc,vc->bhsv", hn, embedding.astype(dtype),
                preferred_element_type=jnp.dtype(c.head_dtype),
            )
        # What the step adds up over layers, micro-batches and replicas, and
        # what it takes the largest of (parallel/train_step.py:_reduce_counters).
        sums = {"tokens_per_step": jnp.int32(ids.size)}
        # The attention operators that lowered to the fused kernel (the same
        # in every micro-batch and replica, so the step's largest is the count).
        maxes = {
            "attention_kernel_layers": c.layer_types.count("full_attention")
            * _kernel_lowers(ids.shape[-1])
        }
        if routed:
            sums |= jax.tree.map(lambda *v: sum(v), *[r["sum"] for r in routed])
            maxes |= jax.tree.map(lambda *v: jnp.stack(v).max(), *[r["max"] for r in routed])
        for kind, values in (("sum", sums), ("max", maxes)):
            self.sow("counters", kind, values, reduce_fn=lambda _, v: v, init_fn=lambda: 0)
        return logits
