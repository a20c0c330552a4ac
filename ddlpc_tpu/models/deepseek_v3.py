"""DeepSeek-V3's decoder (``model_type: deepseek_v3``; Kakao's
Kanana-2-30B-A3B is one) as a per-position classifier over a 1×S token tile,
one expert-parallel rank's share.

The family (config.json): multi-head latent attention without query
compression (DeepSeek-V2, arXiv:2405.04434 §2.1) and, after
``num_dense_layers`` (``first_k_dense_replace``) dense SwiGLU layers, a
feed-forward of ``n_shared_experts`` always-on experts beside sigmoid-scored
top-``num_experts_per_tok``-of-``num_experts`` routed ones.  With ``u`` a
sub-block's RMS-normed input:

  layer      x = h + MLA(RMSNorm(h));  h' = x + FFN(RMSNorm(x))
  MLA        q = W_q u in heads of 128 + 64 (q_nope, q_pe);
             [c_kv (512), k_pe (64)] = W_kva u;  c_kv <- RMSNorm(c_kv);
             [k_nope_h (128), v_h (128)] = W_kvb c_kv, per head;
             q_pe and k_pe turned over adjacent pairs (2i, 2i+1) by
             t·theta^(-2i/64), one k_pe a token for every head;
             o_h = softmax_causal([q_nope, q_pe]·[k_nope, k_pe]ᵀ / sqrt(192)) v_h;
             MLA = W_o concat_h o_h
  FFN        SwiGLU of ``intermediate_size`` in the dense layers; else
             SwiGLU_shared(u) (width n_shared · moe_intermediate_size) plus
             Σ_{e ∈ top-k(s + b) ∩ held} w_e SwiGLU_e(u), s = sigmoid(W_g u)
             in float32, b a constant selection bias that does not weigh,
             w_e = scaling · s_e / (Σ_{top-k} s + 1e-20)

Training runs the materialised form: k and v are made for every head
(``[B, S, 32, 192]`` and ``[B, S, 32, 128]``) and go through the causal
attention of ``lfm2_moe.causal_attention``, whose kernels take scores and
values of two widths.  The form with the up-projections absorbed into q and
o is a serving concern and is not built.  The routed experts are
``lfm2_moe.RoutedExperts`` (its compact buffer, its counters), the share as
there: the router scores all ``num_experts``, experts ``[expert_offset,
expert_offset + experts_held)`` are held, what the absent ones would add is
left out; the shared experts are computed in full, as every rank computes
them for its own tokens.  The plain float32 reference is
``benchmark/reference/deepseek_v3.py``.

Compute is ``compute_dtype`` (bf16) with float32 parameters; norm
statistics, rotary angles, router scores and softmax are float32.  Under
``train=True`` each layer's body is rematerialised.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from ddlpc_tpu.config import ModelConfig
from ddlpc_tpu.models.keye_vl2 import _EMBEDDING_INIT
from ddlpc_tpu.models.lfm2_moe import (
    _INIT,
    RMSNorm,
    RoutedExperts,
    SwiGLU,
    _kernel_lowers,
    _proj,
    causal_attention,
)

# DeepSeek-V3's: the picked scores' sum is normalised behind 1e-20.
ROUTER_EPS = 1e-20


def pair_rotary_tables(seq_len: int, dim: int, theta: float):
    """cos, sin ``[S, dim / 2]`` in float32: pair ``i`` at position ``t``
    turns by ``t · theta^(-2i/dim)``."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_pair_rotary(x, cos, sin):
    """x ``[..., S, heads, dim]``: adjacent pairs ``(2i, 2i + 1)`` turned by
    pair ``i``'s angle (``rope_interleave``), written as the published code
    writes them, the turned first members then the turned second ones.  q and
    k share the order, so their products do not see it."""
    x32 = x.astype(jnp.float32)
    first, second = x32[..., 0::2], x32[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([first * c - second * s, second * c + first * s], axis=-1).astype(x.dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention without query compression, materialised:
    returns the operator's output ``[B, 1, S, hidden]``."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, u, cos, sin):
        c, dtype = self.cfg, jnp.dtype(self.cfg.compute_dtype)
        b, _, s, _ = u.shape
        heads, latent = c.num_attention_heads, c.kv_lora_rank
        nope, rope, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        with jax.named_scope("ddlpc/mla/proj"):
            q = _proj(heads * (nope + rope), dtype, "q_proj")(u).reshape(b, s, heads, nope + rope)
            kv_a = _proj(latent + rope, dtype, "kv_a_proj_with_mqa")(u).reshape(b, s, latent + rope)
            c_kv = RMSNorm(c.norm_eps, dtype, name="kv_a_layernorm")(kv_a[..., :latent])
            kv = _proj(heads * (nope + dv), dtype, "kv_b_proj")(c_kv).reshape(b, s, heads, nope + dv)
            q_pe = apply_pair_rotary(q[..., nope:], cos, sin)
            k_pe = apply_pair_rotary(kv_a[..., None, latent:], cos, sin)  # one for every head
            q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, heads, rope))], axis=-1)
            v = kv[..., nope:]
        with jax.named_scope("ddlpc/attention"):
            out = causal_attention(q, k, v)  # [B, S, H, dv]
        with jax.named_scope("ddlpc/mla/proj"):
            return _proj(c.hidden_size, dtype, "o_proj")(out.reshape(b, 1, s, heads * dv))


class DecoderLayer(nn.Module):
    """``x = h + MLA(norm(h)); h' = x + FFN(norm(x))``; returns the routing
    counts of a routed layer, None of a dense one."""

    cfg: ModelConfig
    dense: bool

    @nn.compact
    def __call__(self, h, cos, sin):
        c, dtype = self.cfg, jnp.dtype(self.cfg.compute_dtype)
        u = RMSNorm(c.norm_eps, dtype, name="input_layernorm")(h)
        x = h + LatentAttention(c, name="self_attn")(u, cos, sin)
        u = RMSNorm(c.norm_eps, dtype, name="post_attention_layernorm")(x)
        if self.dense:
            with jax.named_scope("ddlpc/dense_ffn"):
                return x + SwiGLU(c.hidden_size, c.intermediate_size, dtype, name="mlp")(u), None
        with jax.named_scope("ddlpc/moe/shared"):
            width = c.n_shared_experts * c.moe_intermediate_size
            shared = SwiGLU(c.hidden_size, width, dtype, name="shared_experts")(u)
        routed, counts = RoutedExperts(
            c.hidden_size, c.moe_intermediate_size, c.num_experts, c.num_experts_per_tok,
            c.experts_held, c.expert_offset, c.norm_topk_prob, c.routed_scaling_factor,
            c.use_expert_bias, dtype, score="sigmoid", sigmoid_eps=ROUTER_EPS,
            bias_init=nn.initializers.zeros, name="experts",
        )(u)
        return x + shared + routed, counts


class DeepseekV3(nn.Module):
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
        cos, sin = pair_rotary_tables(s, c.qk_rope_head_dim, c.rope_theta)
        # The backward keeps each layer's input and recomputes its body.
        layer_cls = nn.remat(DecoderLayer) if train else DecoderLayer
        routed = []
        for i in range(len(c.layer_types)):
            h, counts = layer_cls(c, i < c.num_dense_layers, name=f"layers_{i}")(h, cos, sin)
            if counts is not None:
                routed.append(counts)
        with jax.named_scope("ddlpc/head"):
            hn = RMSNorm(c.norm_eps, dtype, name="final_norm")(h)
            head = self.param("lm_head", _INIT, (c.num_classes, c.hidden_size), jnp.float32)
            logits = jnp.einsum(
                "bhsc,vc->bhsv", hn, head.astype(dtype),
                preferred_element_type=jnp.dtype(c.head_dtype),
            )
        sums = {"tokens_per_step": jnp.int32(ids.size)}
        # The layers whose attention lowered to the two-width kernels (the same
        # in every micro-batch and replica, so the step's largest is the count).
        maxes = {"mla_kernel_layers": len(c.layer_types) * _kernel_lowers(s)}
        if routed:
            sums |= jax.tree.map(lambda *v: sum(v), *[r["sum"] for r in routed])
            maxes |= jax.tree.map(lambda *v: jnp.stack(v).max(), *[r["max"] for r in routed])
        for kind, values in (("sum", sums), ("max", maxes)):
            self.sow("counters", kind, values, reduce_fn=lambda _, v: v, init_fn=lambda: 0)
        return logits
