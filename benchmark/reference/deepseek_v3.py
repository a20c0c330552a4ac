"""Plain float32 reference of DeepSeek-V3's decoder (``model_type:
deepseek_v3``; Kakao Kanana-2-30B-A3B's shapes) as one expert-parallel
rank's share, written from the equations in PERF.md §4 and not
from the program's module: no flax, no bf16, no kernel, no sorted buffer, no
custom gradient, no rotary tables.  Parameter names are the program's.

With ``u`` the RMS-normed input of a sub-block and RMSNorm
``x · rsqrt(mean(x²) + eps) · g``:

  embedding / head   h0 = E[ids];  logits = RMSNorm(h_L) · W_headᵀ  (untied)
  layer              x = h + MLA(RMSNorm(h));  h' = x + FFN(RMSNorm(x))
  MLA                q = W_q u in heads of (128 | 64);
                     [c_kv (512) | k_pe (64)] = W_kva u;  c_kv <- RMSNorm(c_kv);
                     [k_nope (128) | v (128)] = W_kvb c_kv in heads;
                     q_pe and k_pe (one a token, every head's) turned pair by
                     pair: (x_2i, x_2i+1) by t · theta^(-2i/64);
                     o = causal softmax([q_nope, q_pe]·[k_nope, k_pe]ᵀ / sqrt(192)) v;
                     MLA = W_o concat_h o
  FFN, dense         W2 (silu(W1 u) ⊙ W3 u)
  FFN, MoE           shared = W2ˢ (silu(W1ˢ u) ⊙ W3ˢ u), always;
                     s = sigmoid(W_g u) over all experts; sel = top-k(s + b),
                     b selects and does not weigh;
                     w = scaling · s[sel] / (Σ s[sel] + 1e-20);
                     FFN = shared + Σ_{e ∈ sel ∩ held} w_e · W2ᵉ (silu(W1ᵉ u) ⊙ W3ᵉ u)

The share is the program's: the router scores all ``num_experts``, experts
``[expert_offset, expert_offset + experts_held)`` are held, what the absent
ones would add is left out; the shared expert is every rank's own.  A tile is
one sequence: ``images`` ``[N,1,S,1]`` holds ids (int, or float holding
ints), logits are ``[N,1,S,vocab]``.

To fit beside the Trainer's state at S = 8,192 each layer is rematerialised
in the backward, attention runs one block of ``QUERY_BLOCK`` queries at a
time against every key under the causal mask (the blocks in a ``lax.map``,
each rematerialised: one body to compile, one block's scores in memory), the
held experts one after another in a scan, and the head a block of positions
at a time; none of it changes a value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
HEAD_BLOCK = 2048

# Relative L2 errors (loss: relative difference) by which a program computing
# in the stated dtype may differ from this float32 reference.  "Chip" readings
# are one v5e at the published widths on one 8,192-token sequence, the
# Trainer's parameters fifteen warm-up-rate steps from their initialisation,
# seven seeds; "at init" the seeded initial parameters, one seed (PERF.md
# section 6 has every run).
#   logits  bf16 reads 0.0228..0.0316 on the chip (0.026 at init): the
#           embedding rows, N(0, 1), carry most of the residual stream and
#           bf16 rounds them to 8 bits.  float8_e4m3 compute reads 0.159 at
#           init (0.16 at the tier-1 size and 0.21 at the rehearsal size on
#           the CPU); the limit lies between, 2.2 times over the largest bf16
#           reading and 2.3 times under the float8 one.  float32 agrees to 1e-6.
#   grad    all leaves together: bf16 0.0102..0.0141 on the chip (0.011 at
#           init), float8 0.925 at init; 3.5 times over the one, 18 under the
#           other.
#   loss    a mean over 8,192 positions, so rounding averages out: bf16 reads
#           1.2e-5..8.0e-5 on the chip, and float8 1.1e-4, so precision hardly
#           moves it; the limit is the accepted cells' 1e-3, twelve times the
#           largest bf16 reading, and guards against gross faults only.
TOLERANCE = {
    "bfloat16": {"loss": 1e-3, "logits": 0.07, "grad": 0.05},
    "float32": {"loss": 1e-5, "logits": 1e-4, "grad": 1e-3},
}
ROUTER_EPS = 1e-20


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def pair_rotary(x, positions, theta):
    """x [..., T, heads, D] at ``positions [T]``: each adjacent pair
    (x_2i, x_2i+1) turned in place by positions · theta^(-2i/D)."""
    d = x.shape[-1]
    angle = positions.astype(jnp.float32)[:, None] * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]  # [T, 1, D/2]
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def latent_attention(u, p, model):
    n, s, hidden = u.shape
    heads, latent = model["num_attention_heads"], model["kv_lora_rank"]
    nope, rope, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    theta, positions = model["rope_theta"], jnp.arange(s)
    kv_a = u @ p["kv_a_proj_with_mqa"]["kernel"]  # [N, S, latent + rope]
    c_kv = rms_norm(kv_a[..., :latent], p["kv_a_layernorm"]["scale"], model["norm_eps"])
    kv = (c_kv @ p["kv_b_proj"]["kernel"]).reshape(n, s, heads, nope + dv)
    k_pe = pair_rotary(kv_a[..., None, latent:], positions, theta)  # [N, S, 1, rope]
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (n, s, heads, rope))], axis=-1)
    v = kv[..., nope:]
    block = min(QUERY_BLOCK, s)

    def attend(u_blk, first):
        """A block of queries (positions first, first + 1, ...), from their
        projection to the output projection, against every key."""
        rows = first + jnp.arange(u_blk.shape[1])
        q = (u_blk @ p["q_proj"]["kernel"]).reshape(n, -1, heads, nope + rope)
        q = jnp.concatenate([q[..., :nope], pair_rotary(q[..., nope:], rows, theta)], axis=-1)
        scores = jnp.einsum("nqhd,nthd->nhqt", q, k) / jnp.sqrt(float(nope + rope))
        causal = jnp.arange(s)[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("nhqt,nthd->nqhd", probs, v).reshape(n, -1, heads * dv)
        return out @ p["o_proj"]["kernel"]

    out = jax.lax.map(
        lambda x: jax.checkpoint(attend)(*x),
        (jnp.moveaxis(u.reshape(n, s // block, block, hidden), 1, 0), jnp.arange(0, s, block)),
    )
    return jnp.moveaxis(out, 0, 1).reshape(n, s, hidden)


def swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def moe(u, p, model):
    """The shared experts and this rank's part of the routed ones."""
    shared = p["shared_experts"]
    y = swiglu(u, shared["w1"]["kernel"], shared["w3"]["kernel"], shared["w2"]["kernel"])
    r = p["experts"]
    s = jax.nn.sigmoid(u @ r["gate"])  # [N, S, num_experts]
    choose = s + jax.lax.stop_gradient(r["expert_bias"]) if model["use_expert_bias"] else s
    _, sel = jax.lax.top_k(choose, model["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    if model["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + ROUTER_EPS)
    w = w * model["routed_scaling_factor"]

    def part(expert):
        """One held expert over every token, weighted where a token picked it."""
        e, w1, w3, w2 = expert
        w_e = jnp.sum(jnp.where(sel == e + model["expert_offset"], w, 0.0), axis=-1, keepdims=True)
        return w_e * swiglu(u, w1, w3, w2)

    held = jnp.arange(model["experts_held"])
    return jax.lax.scan(
        lambda y, expert: (y + jax.checkpoint(part)(expert), None), y, (held, r["w1"], r["w3"], r["w2"])
    )[0]


def layer(h, p, dense, model):
    eps = model["norm_eps"]
    x = h + latent_attention(rms_norm(h, p["input_layernorm"]["scale"], eps), p["self_attn"], model)
    u = rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    if dense:
        f = p["mlp"]
        return x + swiglu(u, f["w1"]["kernel"], f["w3"]["kernel"], f["w2"]["kernel"])
    return x + moe(u, p, model)


def forward(model: dict, params: dict, images):
    """Training-mode logits [N, 1, S, vocab] in float32."""
    ids = images[:, 0, :, 0].astype(jnp.int32)  # [N, S]
    h = params["embedding"][ids]
    for i in range(len(model["layer_types"])):
        dense = i < model["num_dense_layers"]
        h = jax.checkpoint(lambda h, p, dense=dense: layer(h, p, dense, model))(h, params[f"layers_{i}"])
    hn = rms_norm(h, params["final_norm"]["scale"], model["norm_eps"])
    # The head a block of positions at a time: at full precision the compiler
    # splits a product's float32 operands into three bf16 parts each, and those
    # of the whole [S, vocab] cotangent would wait beside it.
    block = min(HEAD_BLOCK, hn.shape[1])
    logits = [hn[:, i : i + block] @ params["lm_head"].T for i in range(0, hn.shape[1], block)]
    return jnp.concatenate(logits, axis=1)[:, None]
