"""Plain float32 reference of the LFM2-MoE family (LiquidAI LFM2-24B-A2B,
``model_type: lfm2_moe``) as one expert-parallel rank's share, written from
the equations of ISSUE 27 / PERF.md §4 and not from the program's module: no
flax, no bf16, no sort, no grouped product, no custom gradient.  Parameter
names are the checkpoint's.

With ``u`` the RMS-normed input of a sub-block, ``d`` the hidden size and
RMSNorm ``x · rsqrt(mean(x²) + eps) · g``:

  embedding / head   h0 = E[ids];  logits = RMSNorm(h_L) · Eᵀ  (tied)
  layer              x = h + Op(RMSNorm(h));  h' = x + FFN(RMSNorm(x))
  Op, conv           (B, C, X) = split3(W_in u);  z = B ⊙ X;
                     c_t = Σ_j k[j] ⊙ z_{t-(L-1)+j} (zeros before the start);
                     Op = W_out (C ⊙ c)
  Op, attention      q, k, v = W_q u, W_k u, W_v u in heads of D; RMSNorm over
                     D on q and on k; rotary positions on q and k (half-split
                     convention); causal softmax(q kᵀ / sqrt(D)) v, each k/v
                     head serving H/KV query heads;  Op = W_o concat
  FFN, dense         W2 (silu(W1 u) ⊙ W3 u)
  FFN, routed        s = sigmoid(W_g u);  sel = top-k(s + b), b selects and
                     does not weigh;  w = s[sel] / (Σ s[sel] + 1e-6) · scaling;
                     FFN = Σ_{e ∈ sel ∩ held} w_e · W2ᵉ (silu(W1ᵉ u) ⊙ W3ᵉ u)

The share is the program's: the router scores all ``num_experts``, experts
``[expert_offset, expert_offset + experts_held)`` are held, what the absent
ones would add is left out.  A tile is one sequence: ``images`` ``[N,1,S,1]``
holds ids (int, or float holding ints), logits are ``[N,1,S,vocab]``.

To fit beside the Trainer's state at S = 8192 each layer is rematerialised in
the backward and attention runs one query block at a time against the keys
up to its end (a plain softmax per block; ``QUERY_BLOCK`` rows); neither
changes a value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512

# Relative L2 errors (loss: relative difference) by which a program computing
# in the stated dtype may differ from this float32 reference.  "Chip" readings
# are one v5e at the published widths on one 8,192-token sequence (builder's
# chip runs, PR 27; PERF.md section 6 has every run).  The cell's parameters
# at the check are fifteen warm-up-rate steps from their initialisation, so
# the readings that set the limits are those at initialisation.
#   logits  the sharp measure.  At initialisation the logits are small (tied
#           embedding N(0, 0.02)), so bf16's 8 significant bits through five
#           layers and the 2048-wide head read 0.057 on the chip, and the
#           nearest precision below (float8_e4m3 compute) 0.285; the limit
#           lies between, 2.6 times over the one and 1.9 times under the
#           other.  Once the unigram statistics are learnt the logits grow
#           and bf16 reads 0.003..0.008 (fifteen full-rate steps; eight
#           runs).  A bf16 router reads the same as the float32 one (0.0572
#           against 0.0574): the near ties it would flip are already flipped
#           by bf16 activations.  At the CPU tests' tiny size (hidden 64,
#           8 experts) those flips weigh more, bf16 reads 0.06..0.12 and
#           float8 0.39..0.42, so those tests carry their own bound.
#           float32 agrees to 1e-6.
#   loss    a mean over 8,192 positions, so rounding averages out: the chip
#           reads 2e-6..9e-5 (fourteen runs); the limit is the accepted
#           cells' 1e-3, eleven times the largest reading.  Precision hardly
#           moves it (float8: 1.9e-3 on the chip), so it guards against
#           gross faults only.
#   grad    all leaves together: bf16 reads 0.048 at initialisation (0.002..
#           0.010 after fifteen full-rate steps), float8 0.976.
TOLERANCE = {
    "bfloat16": {"loss": 1e-3, "logits": 0.15, "grad": 0.15},
    "float32": {"loss": 1e-5, "logits": 1e-4, "grad": 1e-3},
}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def linear(x, kernel):
    """A bias-free position-wise projection, kernel [in, out]."""
    return x @ kernel


def short_conv(u, p):
    d = u.shape[-1]
    bcx = linear(u, p["in_proj"]["kernel"])
    gate_b, gate_c, x = bcx[..., :d], bcx[..., d : 2 * d], bcx[..., 2 * d :]
    z = gate_b * x  # [N, S, d]
    taps = p["conv_kernel"]  # [L, d]
    length, s = taps.shape[0], z.shape[1]
    zp = jnp.concatenate([jnp.zeros_like(z[:, : length - 1]), z], axis=1)
    c = jnp.zeros_like(z)
    for j in range(length):
        c = c + taps[j] * zp[:, j : j + s]
    return linear(gate_c * c, p["out_proj"]["kernel"])


def rotary(x, theta):
    """x [N, S, heads, D]: pairs (i, i + D/2) turned by t · theta^(-2i/D)."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]  # [S, D/2]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    lo, hi = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def attention(u, p, model):
    n, s, hidden = u.shape
    heads, kv_heads = model["num_attention_heads"], model["num_key_value_heads"]
    d = hidden // heads
    eps = model["norm_eps"]
    q = linear(u, p["q_proj"]["kernel"]).reshape(n, s, heads, d)
    k = linear(u, p["k_proj"]["kernel"]).reshape(n, s, kv_heads, d)
    v = linear(u, p["v_proj"]["kernel"]).reshape(n, s, kv_heads, d)
    q = rotary(rms_norm(q, p["q_norm"]["scale"], eps), model["rope_theta"])
    k = rotary(rms_norm(k, p["k_norm"]["scale"], eps), model["rope_theta"])
    # every k/v head serves heads // kv_heads query heads
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    block = min(QUERY_BLOCK, s)

    def attend(q_block, k_seen, v_seen, start):
        end = k_seen.shape[1]
        scores = jnp.einsum("nqhd,nthd->nhqt", q_block, k_seen) / jnp.sqrt(float(d))
        causal = jnp.arange(end)[None, :] <= jnp.arange(start, end)[:, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("nhqt,nthd->nqhd", jax.nn.softmax(scores, axis=-1), v_seen)

    out = []
    for start in range(0, s, block):
        end = min(start + block, s)
        # rematerialised: the backward keeps no block's scores
        out.append(
            jax.checkpoint(attend, static_argnums=3)(q[:, start:end], k[:, :end], v[:, :end], start)
        )
    out = jnp.concatenate(out, axis=1).reshape(n, s, hidden)
    return linear(out, p["o_proj"]["kernel"])


def swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def routed_experts(u, p, model):
    k = model["num_experts_per_tok"]
    s = jax.nn.sigmoid(u @ p["gate"])  # [N, S, num_experts]
    choose = s + jax.lax.stop_gradient(p["expert_bias"]) if model["use_expert_bias"] else s
    _, sel = jax.lax.top_k(choose, k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if model["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-6)
    w = w * model["routed_scaling_factor"]
    y = jnp.zeros_like(u)
    for e in range(model["experts_held"]):
        mine = sel == e + model["expert_offset"]  # [N, S, k]
        w_e = jnp.sum(jnp.where(mine, w, 0.0), axis=-1, keepdims=True)
        y = y + w_e * swiglu(u, p["w1"][e], p["w3"][e], p["w2"][e])
    return y


def layer(h, p, kind, dense, model):
    eps = model["norm_eps"]
    u = rms_norm(h, p["operator_norm"]["scale"], eps)
    if kind == "full_attention":
        x = h + attention(u, p["self_attn"], model)
    else:
        x = h + short_conv(u, p["conv"])
    u = rms_norm(x, p["ffn_norm"]["scale"], eps)
    f = p["feed_forward"]
    if dense:
        ffn = swiglu(u, f["w1"]["kernel"], f["w3"]["kernel"], f["w2"]["kernel"])
    else:
        ffn = routed_experts(u, f, model)
    return x + ffn


def forward(model: dict, params: dict, images):
    """Training-mode logits [N, 1, S, vocab] in float32."""
    ids = images[:, 0, :, 0].astype(jnp.int32)  # [N, S]
    embedding = params["embedding"]
    h = embedding[ids]
    for i, kind in enumerate(model["layer_types"]):
        dense = i < model["num_dense_layers"]
        h = jax.checkpoint(
            lambda h, p, kind=kind, dense=dense: layer(h, p, kind, dense, model)
        )(h, params[f"layers_{i}"])
    logits = rms_norm(h, params["final_norm"]["scale"], model["norm_eps"]) @ embedding.T
    return logits[:, None]
