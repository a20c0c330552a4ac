"""Olmo-Hybrid (``model_type: olmo_hybrid``) as a per-position classifier
over a 1×S token tile, one tensor-parallel rank's share.

The family (allenai Olmo-Hybrid-7B, config.json): a decoder whose mixer is a
Gated DeltaNet (Yang, Kautz and Hatamizadeh, arXiv:2412.06464) in
``linear_attention`` layers and full causal attention without any position
signal (``rope_theta: null``) in ``full_attention`` layers, three to one; the
feed-forward is a dense SwiGLU in every layer, and the residuals are
post-norm: ``x = h + norm(Mix(h)); h' = x + norm(SwiGLU(x))``.  A DeltaNet
head carries a ``[key dim, value dim]`` matrix along the sequence
(``ops/gated_delta.py``: the chunkwise form over chunks of 64 positions, the
walk over them Pallas kernels on the TPU); q, k and v come through depthwise
causal taps and a SiLU, q and k are normalised to unit length, the write
strength is ``β = 2σ(·)`` (``linear_allow_neg_eigval``) and the decay
``α = exp(−exp(A_log) · softplus(· + dt_bias))``; the output is RMS-normed a
head and gated.  Attention has one k/v head a query head and an RMS norm over
the whole q and the whole k vector.  Equations in ISSUE 34 / PERF.md §4; the
plain float32 reference, which runs the recurrence token by token, is
``benchmark/reference/olmo_hybrid.py``.

The share: ``tensor_shards`` ranks divide each layer as tensor parallelism
divides it.  The configuration gives the published head counts and
feed-forward width; a layer holds ``1 / tensor_shards`` of the heads (of
either kind, with their columns of the gate, their channels of the taps and
their rows of the output projection) and of the feed-forward columns, and
computes its part of the two output sums (``W_o``, ``W2``).  The post-norm is
applied to that part and that is what goes on; the q/k norm's mean square is
over the features held.  Nothing stands in for the other ranks or their two
all-reduces a layer (model-configs guide §4); with ``tensor_shards`` 1 the
model is the uncut one.  The tile, the logits and the labels are as
``models/lfm2_moe.py`` has them, so the loss, the step and the Trainer are the
zoo's own.

Compute is ``compute_dtype`` (bf16) with float32 parameters; norm statistics,
softmax, β, the decay, the triangular system and the carried state are
float32.  Under ``train=True`` each layer's body is rematerialised.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from ddlpc_tpu.config import ModelConfig
from ddlpc_tpu.models.keye_vl2 import _EMBEDDING_INIT
from ddlpc_tpu.models.lfm2_moe import (
    _INIT,
    RMSNorm,
    SwiGLU,
    _kernel_lowers,
    _proj,
    causal_attention,
)
from ddlpc_tpu.ops.gated_delta import CHUNK, gated_delta_rule, kernel_lowers

L2_EPS = 1e-6  # under the root of a head's Σx², as the family's public layer has it


def _taps_init(key, shape, dtype):
    """U(−K^-½, K^-½) over the K taps: what the public layer's depthwise
    ``Conv1d`` draws (a fan-in of K)."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _a_log_init(key, shape, dtype):
    """``log A``, ``A ~ U(0, 16)`` from 1e-3 up: the public layer's draw (Mamba2's)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


def _dt_bias_init(key, shape, dtype):
    """The inverse softplus of a step ``dt`` drawn log-uniformly in
    [1e-3, 1e-1], so that ``softplus(dt_bias) = dt`` (the public layer's)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _causal_taps(x, taps):
    """``c_t = Σ_j taps[j] ⊙ x_{t − (K−1) + j}`` along the sequence axis of
    ``x [B, 1, S, C]``, zeros before the start (``lfm2_moe.ShortConv``'s taps,
    which that module applies between its two gates)."""
    length, s = taps.shape[0], x.shape[-2]
    padded = jnp.pad(x, ((0, 0), (0, 0), (length - 1, 0), (0, 0)))
    return sum(taps[j].astype(x.dtype) * padded[:, :, j : j + s] for j in range(length))


def _unit(x):
    """``x / sqrt(Σx² + L2_EPS)`` over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + L2_EPS)


class GatedDeltaNet(nn.Module):
    """The ``heads`` held Gated-DeltaNet heads' part of the layer's output sum."""

    hidden: int
    heads: int
    key_dim: int
    value_dim: int
    taps: int
    neg_eigval: bool
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h):
        b, _, s, _ = h.shape
        dtype, n = self.dtype, self.heads
        widths = {"q": n * self.key_dim, "k": n * self.key_dim, "v": n * self.value_dim}
        with jax.named_scope("ddlpc/gdn/proj"):
            mixed = {name: _proj(w, dtype, f"{name}_proj")(h) for name, w in widths.items()}
            gate = _proj(n * self.value_dim, dtype, "g_proj")(h)
            a = _proj(n, dtype, "a_proj")(h).astype(jnp.float32)[:, 0]  # [B, S, H]
            write = _proj(n, dtype, "b_proj")(h).astype(jnp.float32)[:, 0]
        with jax.named_scope("ddlpc/gdn/conv"):
            for name, w in widths.items():
                taps = self.param(f"{name}_conv", _taps_init, (self.taps, w), jnp.float32)
                mixed[name] = nn.silu(_causal_taps(mixed[name], taps))
            q = (_unit(mixed["q"].reshape(b, s, n, -1)) * self.key_dim**-0.5).astype(dtype)
            k = _unit(mixed["k"].reshape(b, s, n, -1)).astype(dtype)
            v = mixed["v"].reshape(b, s, n, -1)
            beta = jax.nn.sigmoid(write) * (2.0 if self.neg_eigval else 1.0)
            a_log = self.param("A_log", _a_log_init, (n,), jnp.float32)
            dt_bias = self.param("dt_bias", _dt_bias_init, (n,), jnp.float32)
            log_decay = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
        with jax.named_scope("ddlpc/gdn/scan"):
            out = gated_delta_rule(q, k, v, log_decay, beta)  # [B, S, H, Dv]
        with jax.named_scope("ddlpc/gdn/conv"):
            out = RMSNorm(self.eps, dtype, name="o_norm")(out)
            out = out.reshape(b, 1, s, -1) * nn.silu(gate)
        with jax.named_scope("ddlpc/gdn/proj"):
            return _proj(self.hidden, dtype, "o_proj")(out)


class Attention(nn.Module):
    """The ``heads`` held attention heads' part of the layer's output sum:
    one k/v head a query head, RMS norm over the whole held q and k vectors,
    no position signal."""

    hidden: int
    heads: int
    head_dim: int
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h):
        b, _, s, _ = h.shape
        width, dtype = self.heads * self.head_dim, self.dtype
        q = RMSNorm(self.eps, dtype, name="q_norm")(_proj(width, dtype, "q_proj")(h))
        k = RMSNorm(self.eps, dtype, name="k_norm")(_proj(width, dtype, "k_proj")(h))
        v = _proj(width, dtype, "v_proj")(h)
        q, k, v = (x.reshape(b, s, self.heads, self.head_dim) for x in (q, k, v))
        out = causal_attention(q, k, v)
        return _proj(self.hidden, dtype, "o_proj")(out.reshape(b, 1, s, width))


class HybridLayer(nn.Module):
    """``x = h + norm(Mix(h)); h' = x + norm(SwiGLU(x))``."""

    cfg: ModelConfig
    kind: str  # linear_attention | full_attention

    @nn.compact
    def __call__(self, h):
        c, dtype = self.cfg, jnp.dtype(self.cfg.compute_dtype)
        shards = c.tensor_shards
        if self.kind == "full_attention":
            with jax.named_scope("ddlpc/attention"):
                mix = Attention(
                    c.hidden_size, c.num_attention_heads // shards,
                    c.head_dim or c.hidden_size // c.num_attention_heads, c.norm_eps, dtype,
                    name="self_attn",
                )(h)
        else:  # the module names its own scopes: proj, conv, scan
            mix = GatedDeltaNet(
                c.hidden_size, c.linear_num_value_heads // shards, c.linear_key_head_dim,
                c.linear_value_head_dim, c.linear_conv_kernel_dim, c.linear_allow_neg_eigval,
                c.norm_eps, dtype, name="linear_attn",
            )(h)
        x = h + RMSNorm(c.norm_eps, dtype, name="post_attention_norm")(mix)
        with jax.named_scope("ddlpc/dense_ffn"):
            ffn = SwiGLU(c.hidden_size, c.intermediate_size // shards, dtype, name="feed_forward")(x)
        return x + RMSNorm(c.norm_eps, dtype, name="post_feedforward_norm")(ffn)


class OlmoHybrid(nn.Module):
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
        # The backward keeps each layer's input and recomputes its body.
        layer_cls = nn.remat(HybridLayer) if train else HybridLayer
        for i, kind in enumerate(c.layer_types):
            h = layer_cls(c, kind, name=f"layers_{i}")(h)
        with jax.named_scope("ddlpc/head"):
            hn = RMSNorm(c.norm_eps, dtype, name="final_norm")(h)
            head = self.param("lm_head", _INIT, (c.num_classes, c.hidden_size), jnp.float32)
            logits = jnp.einsum(
                "bhsc,vc->bhsv", hn, head.astype(dtype),
                preferred_element_type=jnp.dtype(c.head_dtype),
            )
        linear = c.layer_types.count("linear_attention")
        sums = {
            "tokens_per_step": jnp.int32(ids.size),
            "gdn_chunks": jnp.int32(linear * ids.shape[0] * (s // min(CHUNK, s))),
        }
        maxes = {
            "attention_kernel_layers": c.layer_types.count("full_attention") * _kernel_lowers(s),
            "gdn_layers": jnp.int32(linear),
            "gdn_kernel_layers": linear * kernel_lowers(s),
        }
        for kind, values in (("sum", sums), ("max", maxes)):
            self.sow("counters", kind, values, reduce_fn=lambda _, v: v, init_fn=lambda: 0)
        return logits
