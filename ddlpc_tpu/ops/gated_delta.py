"""The gated delta rule (Gated DeltaNet; Yang, Kautz and Hatamizadeh,
arXiv:2412.06464) in its chunkwise form, forward and backward.

A head carries a matrix ``S ∈ R^{Dk×Dv}`` along the sequence:

    S_t = α_t (I − β_t k_t k_tᵀ) S_{t−1} + β_t k_t v_tᵀ,   S_0 = 0,   o_t = S_tᵀ q_t

with ``α_t = exp(g_t) ∈ (0, 1]`` the decay and ``β_t ∈ (0, 2)`` the writing
strength (past 1 a step's transition has a negative eigenvalue).  Token by
token that is ``S`` sequential rank-one updates; here the sequence is cut into
chunks of ``CHUNK`` positions and a chunk is matrix products.  With
``γ_i = Σ_{j≤i} g_j`` inside a chunk and ``S`` the state it starts from, every
position's update is ``k_i ṽ_iᵀ`` for a corrected value ``ṽ`` that solves a
unit lower-triangular system (the WY representation):

    A = tril₋₁( diag β · (K Kᵀ ⊙ e^{γ_i − γ_j}) )         [C, C]
    T = (I + A)⁻¹                                          :func:`unit_lower_inverse`
    W = T · (β ⊙ e^γ ⊙ K),   U = T · (β ⊙ V)              every chunk at once
    Ṽ = U − W S                                            the scan over chunks
    O = (e^γ ⊙ Q) S + tril(Q Kᵀ ⊙ e^{γ_i − γ_j}) Ṽ
    S' = e^{γ_C} S + (e^{γ_C − γ} ⊙ K)ᵀ Ṽ

Everything but the last three lines is computed for all chunks together
(:func:`chunk_algebra`); the walk over the chunks carries ``S`` in float32
through ``S / CHUNK`` steps of four small products a head.  Decays, ``β``,
``A`` and its inverse are float32 (the inverse's own products at full
precision); the other products take operands in the caller's compute dtype
and accumulate in float32, the state cast for them as the values are (``W``
and ``U`` as ``T diag(x)`` times ``K`` and ``V``: the scales go on ``T``'s
columns, and ``K`` and ``V`` enter as they are, with no scaled copy).  Every
exponent is a difference ``γ_i − γ_j`` with ``i ≥ j``, so nothing overflows
however long the chunk decays.

The walk has two lowerings (:func:`gated_delta_rule`): a ``lax.scan``
(:func:`walk`) with reverse mode through it, its per-chunk residuals the
states ``S`` and ``Ṽ``; and on the TPU the kernel pair of
``ops/pallas_gated_delta.py``, which keeps the state in VMEM for the whole
sequence.  The inverse is differentiated as a whole: ``Ā = −Tᵀ T̄ Tᵀ``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# Positions of one chunk: tiling only, no equation holds it.
CHUNK = 64


def _substitution(a):
    """``(I + a)⁻¹`` row by row: row ``i`` of the inverse is ``e_i`` less
    ``a[i, :i]`` times the rows above it."""
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=a.dtype)

    def row(i, t):
        a_i = lax.dynamic_slice_in_dim(a, i, 1, axis=-2)
        e_i = lax.dynamic_slice_in_dim(eye, i, 1, axis=-2)
        new = e_i - jnp.matmul(a_i, t, precision=lax.Precision.HIGHEST)
        return lax.dynamic_update_slice_in_dim(t, new, i, axis=-2)

    return lax.fori_loop(0, n, row, jnp.zeros_like(a))


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)⁻¹`` for strictly lower-triangular ``a [..., C, C]`` in
    float32, by forward substitution (as stable as the system itself: with
    ``β`` near 2 and repeated keys the powers of ``a`` that a product form
    ``Π (I + (−a)^{2^i})`` sums reach 1e27 against entries of size 2)."""
    return _substitution(a)


def _inverse_fwd(a):
    t = _substitution(a)
    return t, t


def _inverse_bwd(t, g):
    matmul = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    tt = jnp.swapaxes(t, -1, -2)
    return (jnp.tril(-matmul(matmul(tt, g), tt), -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def chunk_algebra(q, k, v, log_decay, beta, chunk: int):
    """Everything of the chunkwise form but the walk, for all chunks at once:
    ``(W, U, e^γ ⊙ Q, e^{γ_C − γ} ⊙ K, tril(Q Kᵀ ⊙ e^{γ_i − γ_j}), e^{γ_C})``,
    chunk-major ``[N, B, H, C, ...]`` (the last ``[N, B, H]``), the first
    five in ``v``'s dtype.  Arguments as :func:`gated_delta_rule`."""
    b, s, h, _ = q.shape
    dtype = v.dtype
    n = s // chunk
    f32 = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)

    def chunks(x):  # [B, S, H, ...] -> [N, B, H, C, ...]: the walk runs over the first
        x = x.reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(x, (1, 3), (0, 2))

    q, k, v, g, beta = map(chunks, (q, k, v, log_decay, beta))
    gamma = jnp.cumsum(g.astype(jnp.float32), axis=-1)  # [N, B, H, C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # e^{γ_i − γ_j} where i ≥ j, exactly 0 above the diagonal
    decay = jnp.exp(jnp.where(lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    a = jnp.tril(beta[..., None] * f32("...id,...jd->...ij", k, k) * decay, -1)
    t = unit_lower_inverse(a)
    # T diag(x) K: the columns of T scaled, so K and V enter the products as they are
    by_column = lambda x: (t * x[..., None, :]).astype(dtype)  # noqa: E731
    w = f32("...ij,...jd->...id", by_column(beta * jnp.exp(gamma)), k).astype(dtype)
    u = f32("...ij,...jd->...id", by_column(beta), v).astype(dtype)
    scaled = lambda x, by: (x.astype(jnp.float32) * by[..., None]).astype(dtype)  # noqa: E731
    qk = (f32("...id,...jd->...ij", q, k) * decay).astype(dtype)
    q_in = scaled(q, jnp.exp(gamma))
    last = gamma[..., -1]
    k_out = scaled(k, jnp.exp(last[..., None] - gamma))
    return w, u, q_in, k_out, qk, jnp.exp(last)


def walk(w, u, q_in, k_out, qk, kept):
    """``O [N, B, H, C, Dv]`` in ``u``'s dtype: the ``lax.scan`` over the
    chunks of :func:`chunk_algebra`'s outputs, carrying ``S`` in float32."""
    dtype = u.dtype
    f32 = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)

    def step(state, x):
        w, u, q_in, k_out, qk, kept = x
        held = state.astype(dtype)
        new = (u.astype(jnp.float32) - f32("bhcd,bhde->bhce", w, held)).astype(dtype)  # Ṽ
        out = f32("bhcd,bhde->bhce", q_in, held) + f32("bhij,bhje->bhie", qk, new)
        state = state * kept[..., None, None] + f32("bhcd,bhce->bhde", k_out, new)
        return state, out.astype(dtype)

    state = jnp.zeros((*w.shape[1:3], w.shape[-1], u.shape[-1]), jnp.float32)
    return lax.scan(step, state, (w, u, q_in, k_out, qk, kept))[1]


def _kernels():
    """``ops/pallas_gated_delta``, imported here: Pallas takes a second to
    import, and only a model with a DeltaNet layer pays it."""
    from ddlpc_tpu.ops import pallas_gated_delta

    return pallas_gated_delta


def _chunk(seq_len: int) -> int:
    """Positions of a chunk: ``CHUNK``, or the whole sequence where it is
    shorter."""
    chunk = min(CHUNK, seq_len)
    if seq_len % chunk:
        raise ValueError(f"sequence length {seq_len} is not a multiple of the chunk {chunk}")
    return chunk


def _lowering(seq_len: int, kernel, xla):
    """The one choice of the walk's lowering: ``kernel`` where the program is
    lowered for a TPU and the chunk is ``CHUNK`` positions, ``xla`` everywhere
    else (a function of the same arguments either way)."""
    if _chunk(seq_len) != CHUNK:
        return xla
    return functools.partial(lax.platform_dependent, tpu=kernel, default=xla)


def kernel_lowers(seq_len: int):
    """int32 1 where :func:`gated_delta_rule`'s walk lowers to the kernels, 0
    where it lowers to the ``lax.scan``: the same choice, of a constant."""
    return _lowering(seq_len, lambda: jnp.int32(1), lambda: jnp.int32(0))()


def gated_delta_rule(q, k, v, log_decay, beta):
    """``o [B, S, H, Dv]`` of the recurrence above from ``S_0 = 0``, in
    ``v``'s dtype.  ``q``, ``k`` ``[B, S, H, Dk]`` as the recurrence reads
    them (normalised and scaled by the caller), ``v [B, S, H, Dv]``;
    ``log_decay`` (``g ≤ 0``) and ``beta`` ``[B, S, H]`` float32.  ``S`` is a
    multiple of ``CHUNK`` (or shorter than one).  The state runs on across
    whatever the sequence packs: nothing resets it.  The walk over the chunks
    has two lowerings: the Pallas kernels (``ops/pallas_gated_delta.py``: the
    state in VMEM for the whole sequence) where the program is lowered for a
    TPU and the chunk is ``CHUNK`` positions, :func:`walk` everywhere else."""
    b, s, h, _ = q.shape
    chunk = _chunk(s)
    parts = chunk_algebra(q, k, v, log_decay, beta, chunk)
    out = _lowering(s, _kernels().walk, walk)(*parts)
    return jnp.moveaxis(out, (0, 2), (1, 3)).reshape(b, s, h, -1)
