"""Plain float32 reference of Keye-VL-2.0's decoder (Kwai-Keye
Keye-VL-2.0-30B-A3B, ``model_type: KeyeVL2``; the language model alone, text
positions) as one expert-parallel rank's share, written from the equations of
ISSUE 32 / PERF.md §4 and not from the program's module: no flax, no bf16, no
kernel, no bisection, no sorted buffer, no custom gradient.
Parameter names are the checkpoint's.

With ``u`` the RMS-normed input of a sub-block and RMSNorm
``x · rsqrt(mean(x²) + eps) · g``:

  embedding / head   h0 = E[ids];  logits = RMSNorm(h_L) · W_headᵀ  (untied)
  layer              x = h + Attn(RMSNorm(h));  h' = x + MoE(RMSNorm(x))
  q, k, v            W_q u in 32 heads of 128, W_k u and W_v u in 4; RMSNorm
                     over the 128 on q and on k; then rotary
  rotary             three position streams p0, p1, p2; frequency slot j of
                     64 turns by p^{c(j)}_t · theta^(-2j/128), c(j) = 0, 1, 2
                     over slots [0,16), [16,40), [40,64); pairs (j, j + 64)
  indexer            on the detached u: qI = rotary64(W_qI u) in 16 heads of
                     64, kI = rotary64(W_kI u) one head, w = W_w u in R^16
                     (plain rotary on p0);
                     I[t,s] = (16·64)^(-1/2) Σ_j w[t,j] ReLU(qI[t,j]·kI[s]), s <= t
  selection          tau_t = the topk-th largest of I[t, :t+1] (every key
                     where t < topk);  S_t = {s <= t : I[t,s] >= tau_t}, ties
                     kept, no gradient
  attention          o[t,h] = Σ_{s in S_t} softmax_{S_t}(q[t,h]·k[s,g(h)] / sqrt(128)) v[s,g(h)]
  MoE                p = softmax(W_g u) over 128; top 8; w_e = p_e / Σ_picked p;
                     MoE = Σ_{e in top8 ∩ held} w_e · W2ᵉ (silu(W1ᵉ u) ⊙ W3ᵉ u)
  indexer's loss     L_I = Σ_layers mean_t KL( p̄_t ‖ softmax_{S_t} I[t,·] ),
                     p̄[t,s] = stop_gradient( mean_h P[t,h,s] )

The share is the program's: the router scores all ``num_experts``, experts
``[expert_offset, expert_offset + experts_held)`` are held, what the absent
ones would add is left out.  A tile is one sequence: ``images`` ``[N,1,S,1]``
holds ids (int, or float holding ints), logits are ``[N,1,S,vocab]``.

To fit beside the Trainer's state at S = 16,384 each layer is rematerialised
in the backward and attention runs one block of ``QUERY_BLOCK`` queries at a
time against every key under the causal mask (plain softmax and plain
``top_k`` a block, the blocks in a ``lax.map``: one body to compile and one
block's scores in memory); neither changes a value.

The selection is discrete: a program that computes the index scores from
bf16 operands picks other keys than this reference where a score lies within
rounding of its threshold.  ``TOLERANCE`` says what that does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128
HEAD_BLOCK = 2048

# Relative L2 errors (loss: relative difference) by which a program computing
# in the stated dtype may differ from this float32 reference: TOLERANCE is read
# by benchmark/check.py, INDEXER_TOLERANCE by benchmark/check_indexer.py.
# "Chip" readings are one v5e at the published widths on one 16,384-token
# sequence, the Trainer's parameters fifteen warm-up-rate steps from their
# initialisation (builder's chip runs, PR 32; PERF.md section 6 has every run).
#   logits  bf16 reads 0.0131..0.0146 on the chip over six seeds (0.0136..0.0198
#           over six more with the embedding at N(0, 0.02)): the embedding rows,
#           N(0, 1), carry most of the residual stream and bf16 rounds them to 8
#           bits; the picks a bf16 indexer makes otherwise near a threshold move
#           it little (0.6 % of the first layer's picks differ).  float8_e4m3
#           compute reads 0.0996 on the chip (0.092 at the rehearsal size and
#           0.18 at the tier-1 size on the CPU; 9.4 % of the picks differ); the
#           limit lies between, 2.5 times over the largest bf16 reading and
#           half the float8 one.  float32 agrees to 1e-6.
#   loss    a mean over 16,384 positions: rounding averages out; the chip reads
#           2e-6..1.1e-4 over twelve runs; the accepted cells' 1e-3 is nine
#           times the largest.  Precision hardly moves it (float8: 2.3e-4 on
#           the chip), so it guards against gross faults only.
#   grad    all leaves together (the indexer's are zero on both sides under
#           check.py: cross-entropy does not reach it): bf16 0.0046..0.0087 on
#           the chip, float8 0.906 there (0.29..0.30 on the CPU at both small
#           sizes).
#   indexer_loss, indexer_grad   L_I and its gradient over the indexer's three
#           matrices a layer, on the Trainer's initial parameters (the chip,
#           benchmark/check_indexer.py): bf16 reads 1.8e-4 and 0.0053, float8
#           0.0133 and 1.0 (its gradient shares nothing with the reference's:
#           the picked sets differ in a tenth of the pairs).  On the CPU bf16
#           reads 3e-4 / 0.012 at the rehearsal size and 1.5e-3 / 0.10..0.12 at
#           the tier-1 size (where the indexer's matrices are scaled up
#           fourfold; that test carries its own bound), float8 5e-4..7e-3 /
#           0.43..1.0.
TOLERANCE = {
    "bfloat16": {"loss": 1e-3, "logits": 0.05, "grad": 0.05},
    "float32": {"loss": 1e-5, "logits": 1e-4, "grad": 1e-3},
}
# check.py holds a run to every key of TOLERANCE, so the indexer's have their own.
INDEXER_TOLERANCE = {
    "bfloat16": {"indexer_loss": 0.005, "indexer_grad": 0.25},
    "float32": {"indexer_loss": 1e-4, "indexer_grad": 1e-3},
}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def sectioned_rotary(x, positions, theta, sections):
    """x [N, S, heads, D]; positions [streams, S]: slot j of D/2 turns pairs
    (j, j + D/2) by the position of stream c(j) times theta^(-2j/D)."""
    d = x.shape[-1]
    stream = jnp.concatenate([jnp.full((n,), c) for c, n in enumerate(sections)])  # [D/2]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[stream, :].T * inv_freq[None, :]  # [S, D/2]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    lo, hi = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def text_positions(model: dict, seq_len: int):
    """The three streams of a text tile: each is 0..S-1."""
    streams = len(model["mrope_section"]) or 1
    return jnp.broadcast_to(jnp.arange(seq_len), (streams, seq_len))


def index_queries(u, p, model, positions):
    """The indexer's per-query side of the detached u [N, T, hidden] at
    ``positions [streams, T]``: qI [N, T, J, Di] and the weights w [N, T, J]."""
    n, t, _ = u.shape
    heads, dim = model["indexer_num_heads"], model["indexer_head_dim"]
    u = jax.lax.stop_gradient(u)
    qi = (u @ p["q_proj"]["kernel"]).reshape(n, t, heads, dim)
    return sectioned_rotary(qi, positions[:1], model["rope_theta"], (dim // 2,)), u @ p["weights_proj"]["kernel"]


def index_keys(u, p, model, positions):
    """The indexer's one shared key head kI [N, T, Di] of the detached u."""
    n, t, _ = u.shape
    dim = model["indexer_head_dim"]
    ki = (jax.lax.stop_gradient(u) @ p["k_proj"]["kernel"]).reshape(n, t, 1, dim)
    return sectioned_rotary(ki, positions[:1], model["rope_theta"], (dim // 2,))[:, :, 0]


def sparse_attention(u, p, model, positions):
    """The operator's output [N, S, hidden] and the layer's indexer loss."""
    n, s, hidden = u.shape
    heads, kv_heads = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"] or hidden // heads
    eps, theta, topk = model["norm_eps"], model["rope_theta"], model["indexer_topk"]
    sections = tuple(model["mrope_section"]) or (d // 2,)
    k = (u @ p["k_proj"]["kernel"]).reshape(n, s, kv_heads, d)
    v = (u @ p["v_proj"]["kernel"]).reshape(n, s, kv_heads, d)
    k = sectioned_rotary(rms_norm(k, p["k_norm"]["scale"], eps), positions, theta, sections)
    ki = index_keys(u, p["indexer"], model, positions)
    scale = (model["indexer_num_heads"] * model["indexer_head_dim"]) ** -0.5
    block = min(QUERY_BLOCK, s)

    def attend(u_blk, first):
        """A block of queries (positions first, first + 1, ...), from their
        projections to the output projection, against every key."""
        rows = first + jnp.arange(u_blk.shape[1])
        at = positions[:, rows]
        q = (u_blk @ p["q_proj"]["kernel"]).reshape(n, -1, heads, d)
        q = sectioned_rotary(rms_norm(q, p["q_norm"]["scale"], eps), at, theta, sections)
        q = q.reshape(n, -1, kv_heads, heads // kv_heads, d)  # head h reads k/v head h // (H / KV)
        qi, w = index_queries(u_blk, p["indexer"], model, at)
        causal = jnp.arange(s)[None, :] <= rows[:, None]  # [Bq, S]
        index = jnp.einsum("nqjd,ntd->nqjt", qi, ki)
        index = scale * jnp.sum(w[..., None] * jnp.maximum(index, 0.0), axis=2)  # [N, Bq, S]
        index = jnp.where(causal, index, -jnp.inf)
        if s > topk:
            kth = jax.lax.top_k(jax.lax.stop_gradient(index), topk)[0][..., -1:]
            picked = causal & ((rows < topk)[:, None] | (jax.lax.stop_gradient(index) >= kth))
        else:
            picked = jnp.broadcast_to(causal, index.shape)
        scores = jnp.einsum("nqkgd,ntkd->nkgqt", q, k) / jnp.sqrt(float(d))
        probs = jax.nn.softmax(jnp.where(picked[:, None, None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("nkgqt,ntkd->nqkgd", probs, v).reshape(n, -1, heads * d)
        # the indexer's loss over this block's queries
        target = jax.lax.stop_gradient(probs.mean(axis=(1, 2)))  # [N, Bq, S]
        log_index = jax.nn.log_softmax(jnp.where(picked, index, -jnp.inf), axis=-1)
        log_ratio = jnp.log(jnp.where(target > 0, target, 1.0)) - jnp.where(picked, log_index, 0.0)
        return out @ p["o_proj"]["kernel"], jnp.sum(target * log_ratio)

    # one block after another (each rematerialised: the backward keeps no
    # block's scores, and no whole-sequence q), the blocks' first positions alongside
    out, kl = jax.lax.map(
        lambda x: jax.checkpoint(attend)(*x),
        (jnp.moveaxis(u.reshape(n, s // block, block, hidden), 1, 0), jnp.arange(0, s, block)),
    )
    return jnp.moveaxis(out, 0, 1).reshape(n, s, hidden), kl.sum() / (n * s)


def swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def routed_experts(u, p, model):
    """Softmax over all the experts, the top k renormalised, this rank's part."""
    probs = jax.nn.softmax(u @ p["gate"], axis=-1)  # [N, S, num_experts]
    top, sel = jax.lax.top_k(probs, model["num_experts_per_tok"])
    w = top / top.sum(axis=-1, keepdims=True) if model["norm_topk_prob"] else top
    w = w * model["routed_scaling_factor"]

    def part(expert):
        """One held expert over every token, weighted where a token picked it."""
        e, w1, w3, w2 = expert
        mine = sel == e + model["expert_offset"]  # [N, S, k]
        w_e = jnp.sum(jnp.where(mine, w, 0.0), axis=-1, keepdims=True)
        return w_e * swiglu(u, w1, w3, w2)

    # One expert after another, each rematerialised (the sum is outside what
    # is rematerialised, or the backward would keep it once an expert):
    # sixteen experts' activations over 16,384 tokens do not wait together.
    held = jnp.arange(model["experts_held"])
    return jax.lax.scan(
        lambda y, expert: (y + jax.checkpoint(part)(expert), None),
        jnp.zeros_like(u), (held, p["w1"], p["w3"], p["w2"]),
    )[0]


def layer(h, p, model, positions):
    eps = model["norm_eps"]
    op, kl = sparse_attention(rms_norm(h, p["operator_norm"]["scale"], eps), p["self_attn"], model, positions)
    x = h + op
    return x + routed_experts(rms_norm(x, p["ffn_norm"]["scale"], eps), p["feed_forward"], model), kl


def _run(model: dict, params: dict, images):
    ids = images[:, 0, :, 0].astype(jnp.int32)  # [N, S]
    positions = text_positions(model, ids.shape[1])
    h = params["embedding"][ids]
    kls = 0.0
    for i in range(len(model["layer_types"])):
        h, kl = jax.checkpoint(lambda h, p: layer(h, p, model, positions))(h, params[f"layers_{i}"])
        kls = kls + kl
    head = params["embedding"] if model["tie_word_embeddings"] else params["lm_head"]
    hn = rms_norm(h, params["final_norm"]["scale"], model["norm_eps"])
    # The head a block of positions at a time: at full precision the compiler
    # splits a product's float32 operands into three bf16 parts each, and those
    # of the whole [S, vocab] cotangent are 1.9 GB beside it.
    n, s, d = hn.shape
    block = min(HEAD_BLOCK, s)
    logits = [hn[:, i : i + block] @ head.T for i in range(0, s, block)]
    return jnp.concatenate(logits, axis=1)[:, None], kls


def forward(model: dict, params: dict, images):
    """Training-mode logits [N, 1, S, vocab] in float32."""
    return _run(model, params, images)[0]


def indexer_loss(model: dict, params: dict, images):
    """L_I: the indexer's KL summed over the layers, the mean over queries."""
    return _run(model, params, images)[1]
