"""Plain float32 reference of Olmo-Hybrid (allenai Olmo-Hybrid-7B,
``model_type: olmo_hybrid``) as one tensor-parallel rank's share, written from
the equations of ISSUE 34 / PERF.md §4 and not from the program's modules: no
flax, no bf16, no kernel, no chunkwise algebra, no triangular inverse, no
custom gradient.  Parameter names are the program's tree.

With RMSNorm ``x · rsqrt(mean(x²) + eps) · g`` and ``H`` the heads held:

  embedding / head   h0 = E[ids];  logits = RMSNorm(h_L) · W_headᵀ  (untied)
  layer, both kinds  x = h + RMSNorm_a(Mix(h));  h' = x + RMSNorm_f(SwiGLU(x))
                     (post-norm: the mixer and the feed-forward read the
                     stream itself, their outputs are normed before they join it)
  SwiGLU             W2 (silu(W1 u) ⊙ W3 u) over the columns held
  full_attention     q = W_q h, k = W_k h, v = W_v h in H heads of 128, one
                     k/v head a query head; RMSNorm over the whole held
                     vector [H·128] on q and on k; no rotary, no position
                     signal at all; causal softmax(q kᵀ / sqrt 128) v;  W_o
  linear_attention   q~ = silu(conv4(W_q h)), k~ = silu(conv4(W_k h)),
                     v = silu(conv4(W_v h)): depthwise causal taps, zeros
                     before the start; a head (96 / 96 / 192 wide):
                       q = 96^-1/2 q~ / |q~|,  k = k~ / |k~|    (|x| = sqrt(Σx² + 1e-6))
                       β_t = 2 σ(W_b h)        (the 2: linear_allow_neg_eigval)
                       α_t = exp(−exp(A_log) · softplus(W_a h + dt_bias))
                       S_t = α_t (I − β_t k_t k_tᵀ) S_{t−1} + β_t k_t v_tᵀ,  S_0 = 0
                       o_t = S_tᵀ q_t
                     y = W_o [ RMSNorm_o(o_t) ⊙ silu(W_g h) ],  RMSNorm_o
                     over a head's 192 (one weight for every head)

The share is the program's: ``tensor_shards`` ranks divide each layer's heads
(of both kinds) and feed-forward columns; this rank's parameters are its heads
and columns, its ``W_o`` and ``W2`` products are its part of the two output
sums, the post-norm is applied to that part, and that is what goes on.  The
q/k norm's mean square is over the features held; ``attention`` takes the
whole vector's from a caller that has it (the share test, which adds the
ranks' parts up to the uncut layer).  A tile is one sequence: ``images``
``[N,1,S,1]`` holds ids (int, or float holding ints), logits are
``[N,1,S,vocab]``.

The recurrence runs token by token, as written above.  To fit beside the
Trainer's state at S = 8,192 it is an outer scan over blocks of
``STATE_BLOCK`` positions whose inner steps are rematerialised (the backward
keeps one state a block and a block's own), each layer is rematerialised,
attention runs a block of ``QUERY_BLOCK`` queries at a time against every key,
and the head a block of positions at a time; none changes a value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
STATE_BLOCK = 128
HEAD_BLOCK = 2048

# Relative L2 errors (loss: relative difference) by which a program computing
# in the stated dtype may differ from this float32 reference; benchmark/check.py
# holds a run to every key.  "Chip" readings are one v5e at the published
# widths on one 8,192-token sequence, the Trainer's parameters fifteen steps of
# Adam at 3e-4 from their initialisation (builder's chip runs, PR 34; PERF.md
# section 6 has every run).
#   logits  bf16 reads 0.0086..0.0113 on the chip over fourteen seeds; float8_e5m2
#           (the nearest precision below that is a number here) reads 0.238
#           there (0.69 at hidden 384 on the CPU); the limit lies between, 4.4
#           times over the largest bf16 reading.  float32 agrees to 2e-6.
#   loss    a mean over 8,192 positions: rounding averages out; the chip reads
#           2e-5..2.2e-4; the accepted cells' 1e-3 is 4.5 times the largest.
#           It guards against gross faults only (e5m2: 0.018 on the chip).
#   grad    all leaves together: bf16 reads 0.050..0.114 on the chip, e5m2 0.958
#           there.  Ill-conditioned, not imprecise: the output norm over a
#           head's 192 divides by the length of a state read whose terms can
#           nearly cancel, so a few positions carry much of the gradient, and
#           each DeltaNet layer reads what the last one rounded (the float32
#           program moves its own gradient by 1.3..1.9 % when the embedding
#           alone is rounded to bf16; the delta rule computed wholly in float32
#           inside the bf16 model changes 0.050 / 0.075 to 0.047 / 0.057).  The
#           limit is 2.6 times the largest bf16 reading and under a third of
#           e5m2's.  float8_e4m3 is no number at all: the feed-forward's SiLU
#           reads the un-normed stream and its exponential passes 448.
TOLERANCE = {
    "bfloat16": {"loss": 1e-3, "logits": 0.05, "grad": 0.3},
    "float32": {"loss": 1e-5, "logits": 1e-4, "grad": 1e-3},
}


def rms_norm(x, g, eps, mean_square=None):
    if mean_square is None:
        mean_square = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(mean_square + eps) * g


def swiglu(u, p):
    hidden = jax.nn.silu(u @ p["w1"]["kernel"]) * (u @ p["w3"]["kernel"])
    return hidden @ p["w2"]["kernel"]


def attention(h, p, model, mean_square=(None, None)):
    """This rank's part of the attention output sum, ``[N, S, hidden]``.
    ``mean_square``: the q and k vectors' mean squares ``[N, S, 1]`` where
    the caller knows them over all the ranks' features."""
    n, s, hidden = h.shape
    d = model["head_dim"] or hidden // model["num_attention_heads"]
    eps = model["norm_eps"]
    q = rms_norm(h @ p["q_proj"]["kernel"], p["q_norm"]["scale"], eps, mean_square[0])
    k = rms_norm(h @ p["k_proj"]["kernel"], p["k_norm"]["scale"], eps, mean_square[1])
    q, k = q.reshape(n, s, -1, d), k.reshape(n, s, -1, d)
    v = (h @ p["v_proj"]["kernel"]).reshape(n, s, -1, d)
    block = min(QUERY_BLOCK, s)

    def attend(q_blk, first):
        rows = first + jnp.arange(q_blk.shape[1])
        scores = jnp.einsum("nqhd,nthd->nhqt", q_blk, k) / jnp.sqrt(float(d))
        causal = jnp.arange(s)[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("nhqt,nthd->nqhd", probs, v)

    out = jax.lax.map(
        lambda x: jax.checkpoint(attend)(*x),
        (jnp.moveaxis(q.reshape(n, s // block, block, -1, d), 1, 0), jnp.arange(0, s, block)),
    )
    return jnp.moveaxis(out, 0, 1).reshape(n, s, -1) @ p["o_proj"]["kernel"]


def causal_taps(x, taps):
    """Depthwise causal convolution: ``c_t = Σ_j taps[j] ⊙ x_{t − (K−1) + j}``,
    zeros before the start.  x ``[N, S, C]``, taps ``[K, C]``."""
    length, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (length - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, j : j + s] for j in range(length))


def delta_recurrence(q, k, v, alpha, beta):
    """``o_t = S_tᵀ q_t`` of the gated delta rule, token by token from
    ``S_0 = 0``.  q, k ``[N, S, H, Dk]``, v ``[N, S, H, Dv]``, alpha and beta
    ``[N, S, H]``; returns ``[N, S, H, Dv]``."""
    n, s, h, dk = q.shape
    block = min(STATE_BLOCK, s)

    def token(state, x):  # state [N, H, Dk, Dv]
        q_t, k_t, v_t, a_t, b_t = x
        state = a_t[..., None, None] * state
        seen = jnp.sum(k_t[..., :, None] * state, axis=-2)  # S'ᵀ k
        state = state + k_t[..., :, None] * (b_t[..., None] * (v_t - seen))[..., None, :]
        return state, jnp.sum(q_t[..., :, None] * state, axis=-2)

    def run(state, xs):  # one block of positions, its steps rematerialised
        return jax.lax.scan(token, state, xs)

    xs = tuple(
        jnp.moveaxis(x, 1, 0).reshape(s // block, block, *x.shape[:1], *x.shape[2:])
        for x in (q, k, v, alpha, beta)
    )
    state = jnp.zeros((n, h, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(jax.checkpoint(run), state, xs)
    return jnp.moveaxis(out.reshape(s, n, h, -1), 0, 1)


def gated_delta_net(h, p, model):
    """This rank's part of the Gated-DeltaNet output sum, ``[N, S, hidden]``."""
    n, s, _ = h.shape
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    mixed = lambda name: jax.nn.silu(  # noqa: E731
        causal_taps(h @ p[name + "_proj"]["kernel"], p[name + "_conv"])
    )
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(mixed("q").reshape(n, s, -1, dk)) * dk**-0.5
    k = unit(mixed("k").reshape(n, s, -1, dk))
    v = mixed("v").reshape(n, s, -1, dv)
    beta = jax.nn.sigmoid(h @ p["b_proj"]["kernel"])
    if model["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(h @ p["a_proj"]["kernel"] + p["dt_bias"]))
    out = delta_recurrence(q, k, v, alpha, beta)
    gate = jax.nn.silu(h @ p["g_proj"]["kernel"]).reshape(n, s, -1, dv)
    out = rms_norm(out, p["o_norm"]["scale"], model["norm_eps"]) * gate
    return out.reshape(n, s, -1) @ p["o_proj"]["kernel"]


def layer(h, p, model, kind):
    eps = model["norm_eps"]
    if kind == "full_attention":
        mix = attention(h, p["self_attn"], model)
    else:
        mix = gated_delta_net(h, p["linear_attn"], model)
    x = h + rms_norm(mix, p["post_attention_norm"]["scale"], eps)
    return x + rms_norm(swiglu(x, p["feed_forward"]), p["post_feedforward_norm"]["scale"], eps)


def forward(model: dict, params: dict, images):
    """Training-mode logits [N, 1, S, vocab] in float32."""
    ids = images[:, 0, :, 0].astype(jnp.int32)  # [N, S]
    h = params["embedding"][ids]
    for i, kind in enumerate(model["layer_types"]):
        h = jax.checkpoint(lambda h, p, kind=kind: layer(h, p, model, kind))(h, params[f"layers_{i}"])
    hn = rms_norm(h, params["final_norm"]["scale"], model["norm_eps"])
    # a block of positions at a time, as reference/keye_vl2.py and for its reason
    s = hn.shape[1]
    block = min(HEAD_BLOCK, s)
    logits = [hn[:, i : i + block] @ params["lm_head"].T for i in range(0, s, block)]
    return jnp.concatenate(logits, axis=1)[:, None]
