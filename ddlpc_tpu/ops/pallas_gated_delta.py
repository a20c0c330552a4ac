"""Pallas TPU kernels for the walk of the chunkwise gated delta rule over its
chunks (``ops/gated_delta.py``), forward and backward.

The chunk algebra (decays, ``A``, its inverse, ``W``, ``U``, the chunk's own
``Q Kᵀ``) stays batched XLA over all chunks; what the kernels take over is the
sequential part, which the XLA form runs as a ``lax.scan`` whose float32 state
goes back to HBM between steps.  A grid step holds one chunk of every head
(runs of two or four chunks a step measured no faster), and the state
``S [Dk, Dv]`` of every head stays in VMEM scratch, float32, for the whole
sequence.  Per chunk and head the arithmetic is that of
:func:`ops.gated_delta.walk`: products take operands in the compute dtype and
accumulate in float32, the state is cast for them as the values are:

    held = S                          (compute dtype)
    Ṽ    = U − W · held               (compute dtype)
    O    = Q_in · held + (Q Kᵀ) · Ṽ
    S   ← e^{γ_C} S + K_outᵀ · Ṽ

The forward writes ``O`` and, where a backward follows, the float32 state
each chunk starts from and ``Ṽ``.  The backward walks the chunks in
reverse carrying ``dS`` (float32) in VMEM; from ``dO`` and ``dS'``, the
cotangent of the state the chunk ends with, a chunk gives

    dṼ  = (Q Kᵀ)ᵀ · dO + K_out · dS'       dU = dṼ,   dW = −dṼ · heldᵀ
    dQ_in = dO · heldᵀ,   d(Q Kᵀ) = dO · Ṽᵀ,   dK_out = Ṽ · dS'ᵀ
    d e^{γ_C} = Σ S ⊙ dS'
    dS  = e^{γ_C} dS' + Q_inᵀ · dO − Wᵀ · dṼ

with ``S``, ``dS'`` and ``dṼ`` cast to the compute dtype for their products
and the decay's cotangent from the float32 state, as the XLA form's reverse
mode has them on a TPU.

Each head's chain of products is short and serial, so a chunk is written in
two phases over all heads: first every head's product that the chain waits
on (``Ṽ``, ``dṼ``), then every head's rest.  Each head has a scratch buffer
of its own, so no head's loads wait on another head's stores, and the
scheduler interleaves the heads' products on the MXU.  Head sizes 96 and 192
neither fill nor divide the 128 lanes: every block spans the whole of both
head axes.  Each ``pallas_call`` carries a ``pl.CostEstimate`` of the
products it stands for (``obs/flops.product_flops`` counts a kernel by it).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))  # a · bᵀ
_TN = (((0,), (0,)), ((), ()))  # aᵀ · b


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fwd_kernel(kept_ref, w_ref, u_ref, q_ref, k_ref, qk_ref, o_ref, *refs, residuals: bool):
    """One chunk of every head: ``kept_ref`` holds the chunk's ``e^{γ_C}``
    a head (SMEM), the last ``H`` refs the heads' states across the grid."""
    heads = w_ref.shape[0]
    dtype = u_ref.dtype
    if residuals:
        states_ref, new_ref, *states = refs
    else:
        new_ref, *states = refs  # Ṽ of this chunk, scratch

    @pl.when(pl.program_id(1) == 0)
    def _():
        for s in states:
            s[...] = jnp.zeros_like(s)

    for h in range(heads):  # Ṽ, which the rest of the chunk waits on
        if residuals:
            states_ref[h] = states[h][...]
        held = states[h][...].astype(dtype)
        new_ref[h] = (u_ref[h].astype(jnp.float32) - _dot(w_ref[h], held)).astype(dtype)
    for h in range(heads):
        held, new = states[h][...].astype(dtype), new_ref[h]
        o_ref[h] = (_dot(q_ref[h], held) + _dot(qk_ref[h], new)).astype(o_ref.dtype)
        states[h][...] = states[h][...] * kept_ref[h] + _dot(k_ref[h], new, _TN)


def _bwd_kernel(kept_ref, do_ref, states_ref, new_ref, w_ref, q_ref, k_ref, qk_ref,
                dw_ref, du_ref, dq_ref, dk_ref, dqk_ref, dkept_ref, *ds_refs):
    """One chunk of every head, the chunks walked last to first; the last
    ``H`` refs carry the cotangents of the heads' states."""
    heads = w_ref.shape[0]
    dtype = do_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        for ds in ds_refs:
            ds[...] = jnp.zeros_like(ds)

    for h in range(heads):  # dṼ, which the rest of the chunk waits on
        ds_c = ds_refs[h][...].astype(dtype)  # the cotangent of the state this chunk ends with
        du_ref[h] = (_dot(qk_ref[h], do_ref[h], _TN) + _dot(k_ref[h], ds_c)).astype(du_ref.dtype)
    for h in range(heads):
        ds, state = ds_refs[h][...], states_ref[h]
        held, ds_c = state.astype(dtype), ds.astype(dtype)
        do, new, dnew = do_ref[h], new_ref[h], du_ref[h]
        dw_ref[h] = (-_dot(dnew, held, _NT)).astype(dw_ref.dtype)
        dq_ref[h] = _dot(do, held, _NT).astype(dq_ref.dtype)
        dqk_ref[h] = _dot(do, new, _NT).astype(dqk_ref.dtype)
        dk_ref[h] = _dot(new, ds_c, _NT).astype(dk_ref.dtype)
        dkept_ref[h : h + 1, :] = jnp.sum(state * ds, axis=0, keepdims=True)
        ds_refs[h][...] = ds * kept_ref[h] + _dot(q_ref[h], do, _TN) - _dot(w_ref[h], dnew, _TN)


def _nbytes(*arrays) -> int:
    return sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize for x in arrays)


def _call(kernel, name, kept, operands, out_shapes, scratch, *, reverse, products, interpret, **flags):
    """Run ``kernel`` (keyword ``flags`` go to it) on ``kept`` and
    ``operands``; ``scratch`` is ``(shape, dtype)`` pairs."""
    shapes = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (kept, *operands))
    call = _pallas(kernel, name, tuple(flags.items()), shapes, tuple(out_shapes), tuple(scratch),
                   reverse, products, interpret)
    return call(kept, *operands)


@functools.cache
def _pallas(kernel, name, flags, shapes, out_shapes, scratch, reverse, products, interpret):
    """``kernel`` over the grid (batch rows, chunks), every operand and
    output ``[N, B, H, ...]`` blocked one chunk and batch row at a time, the
    chunks walked last to first where ``reverse``; ``scratch`` the VMEM
    carried across the grid, ``products`` the FLOPs of one chunk and head.
    Built and jitted once a process for its shapes, so a model's layers and
    programs share one trace and one lowering of each kernel."""
    kept, *operands = shapes
    n, b, h = operands[0].shape[:3]

    def spec(x, **kw):
        chunk = (lambda j: n - 1 - j) if reverse else (lambda j: j)
        rest = (0,) * (len(x.shape) - 2)
        return pl.BlockSpec((None, None, *x.shape[2:]), lambda i, j: (chunk(j), i, *rest), **kw)

    return jax.jit(pl.pallas_call(
        functools.partial(kernel, **dict(flags)),
        out_shape=out_shapes,
        grid=(b, n),
        in_specs=[spec(kept, memory_space=pltpu.SMEM)] + [spec(x) for x in operands],
        out_specs=tuple(spec(x) for x in out_shapes),
        scratch_shapes=[pltpu.VMEM(shape, dtype) for shape, dtype in scratch],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=products * n * b * h,
            transcendentals=0,
            bytes_accessed=_nbytes(*shapes, *out_shapes),
        ),
        interpret=interpret,
        name=name,
    ))


def _forward(w, u, q_in, k_out, qk, kept, interpret: bool, residuals: bool):
    """``O``, and where ``residuals`` the states and ``Ṽ`` the backward reads."""
    n, b, h, c, dk = w.shape
    dv = u.shape[-1]
    out_shapes = (jax.ShapeDtypeStruct(u.shape, u.dtype),)  # O
    scratch = (((dk, dv), jnp.float32),) * h
    if residuals:
        out_shapes += (
            jax.ShapeDtypeStruct((n, b, h, dk, dv), jnp.float32),  # the state each chunk starts from
            jax.ShapeDtypeStruct(u.shape, u.dtype),  # Ṽ
        )
    else:
        scratch = (((h, c, dv), u.dtype),) + scratch
    return _call(
        _fwd_kernel, "gated_delta_fwd", kept, (w, u, q_in, k_out, qk), out_shapes, scratch, reverse=False,
        products=2 * (3 * c * dk * dv + c * c * dv), interpret=interpret, residuals=residuals,
    )


def _backward(residuals, do, interpret: bool):
    w, q_in, k_out, qk, kept, states, new = residuals
    n, b, h, c, dk = w.shape
    dv = do.shape[-1]
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    out_shapes = (like(w), like(do), like(q_in), like(k_out), like(qk),
                  jax.ShapeDtypeStruct((n, b, h, dv), jnp.float32))
    dw, du, dq, dk_, dqk, dkept = _call(
        _bwd_kernel, "gated_delta_bwd", kept, (do, states, new, w, q_in, k_out, qk), out_shapes,
        (((dk, dv), jnp.float32),) * h, reverse=True,
        products=2 * (6 * c * dk * dv + 2 * c * c * dv), interpret=interpret,
    )
    return dw, du, dq, dk_, dqk, dkept.sum(axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _walk(w, u, q_in, k_out, qk, kept, interpret):
    return _forward(w, u, q_in, k_out, qk, kept, interpret, residuals=False)[0]


def _walk_fwd(w, u, q_in, k_out, qk, kept, interpret):
    out, states, new = _forward(w, u, q_in, k_out, qk, kept, interpret, residuals=True)
    return out, (w, q_in, k_out, qk, kept, states, new)


def _walk_bwd(interpret, residuals, do):
    return _backward(residuals, do, interpret)


_walk.defvjp(_walk_fwd, _walk_bwd)


def walk(w, u, q_in, k_out, qk, kept, *, interpret: bool = False):
    """:func:`ops.gated_delta.walk` as the kernel pair: the arguments as
    :func:`ops.gated_delta.chunk_algebra` returns them (``[N, B, H, C, ...]``,
    ``kept [N, B, H]`` float32).  Returns ``O [N, B, H, C, Dv]`` in ``u``'s
    dtype; differentiable in every argument."""
    return _walk(w, u, q_in, k_out, qk, kept, interpret)
