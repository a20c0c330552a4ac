"""Pallas TPU kernels for causal grouped-query attention, forward and backward,
and for the two block-wise scores of the ``keye_vl2`` indexer's loss.

``softmax(q kᵀ / sqrt(D)) v`` under the causal mask in which no
``[..., rows, keys]`` array ever reaches HBM: a grid step holds one block of
queries (the ``G`` query heads of a k/v head stacked into ``G·block`` rows, so
the four of them read one copy of k and v) against one block of keys; the
scores, their exponentials and the running row statistics live in VMEM.  The
arithmetic is that of ``models/lfm2_moe.py:_attend_block``: ``q·kᵀ`` from the
compute dtype accumulated in float32; mask, running maximum, exponentials and
running sum in float32; the probabilities cast to the compute dtype for
``P·V``, accumulated in float32.  The online (blockwise) softmax re-associates
those sums and approximates nothing.  Only the lower triangle of blocks is in
the grid: the ``(query block, key block)`` pairs are flattened into one grid
axis and read from two prefetched tables, so a block above the diagonal costs
neither a step nor a DMA, and only the diagonal blocks build a mask.

The backward is one kernel over the same pairs that recomputes the
probabilities from the saved row log-sum-exp (five products a pair): dQ
accumulates over a query block's keys; dK and dV of the whole sequence stay
in VMEM until the head's last pair, which bounds the sequence (``MAX_SEQ``).
Every ``pallas_call`` carries a ``pl.CostEstimate`` of the products it stands
for (the causal half here), recomputation not counted;
``obs/flops.product_flops`` counts a kernel by it (the kernel's body holds
one block's products, not the grid's).

:func:`selected_attention` is the same pair of kernels over a per-pair
selection (``models/keye_vl2.py``: a learned indexer picks each query's
keys): an additive ``bias [B, S, S]``, 0 where a query sees a key and
``-1e30`` where it does not (the causal mask included), is read a block a
grid step and added to the float32 scores in place of the diagonal mask.
Every causal block is still computed, so the result is exactly the softmax
over the selected keys and the time is the causal kernel's; skipping blocks
by the selection is not done here.  It also returns the rows' log-sum-exp.
Any head size that is a multiple of 128 lanes or divides them, and any number
of query heads a k/v head, one included (``models/olmo_hybrid.py``); more than
``HEADS_PER_STEP`` are split into that many a grid step (each group reads its
own copy of the k/v blocks, their dK and dV are summed outside).

:func:`head_mean_probs` is the target of the ``keye_vl2`` indexer's loss, the
mean over the query heads of ``exp(q kᵀ / sqrt(D) - lse)`` for one block of
queries against a run of keys (forward only: its inputs are detached): a grid
step holds ``HEADS_PER_STEP`` heads, the groups of heads the innermost axis.
:func:`index_scores` is the indexer's own scores of such a block,
``c Σ_j w[t,j] ReLU(qI[t,j]·kI[s])`` with -inf after the query, forward and
backward (dqI, dkI, dw from the inputs alone: the pre-activations are made
again): a grid step holds every head, each a run of lanes of q as the model
lays it out.  Both hold a step's float32 ``[keys, queries]`` tiles transposed,
as the backward above, so that a row statistic or a head's weights broadcast
along sublanes; the tiles and the sum over the heads stay in VMEM, and what
reaches HBM is one float32 ``[queries, keys]`` block a block of keys.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Query rows per head, and keys, of one grid step.  Measured on a v5e at
# q[4, 8192, 32, 64] (PERF.md §6, PR 28).
BLOCK = 512
LANES = 128
_MASKED = -1e30  # as the XLA form; exp(_MASKED - m) is exactly 0
_NT = (((1,), (1,)), ((), ()))  # a · bᵀ
_TN = (((0,), (0,)), ((), ()))  # aᵀ · b
# The backward keeps dK and dV of one k/v head's whole sequence in VMEM.
MAX_SEQ = 16384
# Query heads of one k/v head that a grid step stacks: the scores of a step
# are [HEADS_PER_STEP · BLOCK, BLOCK] float32 (4 MiB, and four such in the
# backward).
HEADS_PER_STEP = 4


def supported(seq_len: int, block: int = BLOCK) -> bool:
    """Whether the kernels take a sequence of ``seq_len`` at ``block``."""
    return block % LANES == 0 and seq_len % block == 0 and seq_len <= MAX_SEQ


def _vmem_limit(seq_len: int) -> int:
    """16 MiB for a block pair's scores and operands, and 2 KiB a position
    for the backward's whole-sequence dK and dV (float32 accumulators and
    double-buffered outputs, head size padded to 128 lanes)."""
    return (16 << 20) + seq_len * 2048


def _lanes(x, n: int):
    """``x [rows, 128]`` with equal lanes, as ``[rows, n]``."""
    return x[:, :n] if n <= LANES else jnp.tile(x, (1, n // LANES))


def _visible(rows: int, block: int, keys_minor: bool = True):
    """The diagonal block's mask.  Row ``n`` of the stacked queries is position
    ``n mod block`` of its block; it sees the keys up to itself."""
    shape = (rows, block) if keys_minor else (block, rows)
    qpos = lax.rem(lax.broadcasted_iota(jnp.int32, shape, 0 if keys_minor else 1), block)
    kpos = lax.broadcasted_iota(jnp.int32, shape, 1 if keys_minor else 0)
    return kpos <= qpos


def _scores(a, b, scale: float):
    s = lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    return s if scale == 1.0 else s * scale


def _fwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, *refs, scale: float, select: bool = False):
    bias_ref = refs[0] if select else None
    o_ref, lse_ref, m_ref, l_ref, acc_ref = refs[select:]
    t = pl.program_id(2)
    i, j = qi_ref[t], kj_ref[t]
    g, block, d = q_ref.shape
    rows = g * block

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(diagonal: bool):
        k, v = k_ref[...], v_ref[...]
        s = _scores(q_ref[...].reshape(rows, d), k, scale)  # [rows, keys] f32
        if select:  # the selection holds the causal mask; one bias block serves the g heads
            bias = bias_ref[...].astype(jnp.float32)
            s = (s.reshape(g, block, block) + bias[None]).reshape(rows, block)
        elif diagonal:
            s = jnp.where(_visible(rows, block), s, _MASKED)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, block))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_next
        pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * _lanes(alpha, d) + pv

    @pl.when(j < i)
    def _():
        step(False)

    @pl.when(j == i)  # the diagonal block is the row's last
    def _():
        step(True)
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / _lanes(l, d)).reshape(g, block, d).astype(o_ref.dtype)
        # [rows, 128] equal lanes -> one lane-major row for the backward
        lse_ref[...] = (m_ref[...] + jnp.log(l)).T[:1]


def _bwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                scale: float, select: bool = False):
    """One (query block, key block) pair of dQ, dK and dV.  The scores are
    held transposed, ``[keys, rows]``, so the saved row statistics broadcast
    along sublanes.  dQ accumulates over a query block's keys and is written
    on the diagonal; dK and dV of the whole sequence (one k/v head) accumulate
    in VMEM and are written after the last pair."""
    bias_ref = refs[0] if select else None
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs[select:]
    t = pl.program_id(2)
    i, j = qi_ref[t], kj_ref[t]
    g, block, d = q_ref.shape
    rows = g * block

    @pl.when(t == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(diagonal: bool):
        k, v = k_ref[...], v_ref[...]
        q, do = q_ref[...].reshape(rows, d), do_ref[...].reshape(rows, d)
        st = _scores(k, q, scale)
        if select:
            bias_t = bias_ref[...].astype(jnp.float32).T  # [keys, block]
            st = st + jnp.concatenate([bias_t] * g, axis=1)
        elif diagonal:
            st = jnp.where(_visible(rows, block, keys_minor=False), st, _MASKED)
        pt = jnp.exp(st - lse_ref[...])
        keys = pl.ds(pl.multiple_of(j * block, block), block)
        dv_acc[keys, :] += jnp.dot(pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[...])
        if scale != 1.0:
            dst = dst * scale
        dst = dst.astype(q.dtype)
        dk_acc[keys, :] += jnp.dot(dst, q, preferred_element_type=jnp.float32)
        dq_acc[...] += lax.dot_general(dst, k, _TN, preferred_element_type=jnp.float32)

    @pl.when(j < i)
    def _():
        step(False)

    @pl.when(j == i)
    def _():
        step(True)
        dq_ref[...] = dq_acc[...].reshape(g, block, d).astype(dq_ref.dtype)

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _nbytes(*arrays) -> int:
    return sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize for x in arrays)


def _triangle(n: int):
    """The lower triangle of an n×n block grid, flattened query-major, the
    diagonal last in its row: (query blocks, key blocks) as int32 tables."""
    qi, kj = np.array([(i, j) for i in range(n) for j in range(i + 1)], np.int32).T
    return jnp.asarray(qi), jnp.asarray(kj)


def _call(kernel, name, operands, out_shapes, out_specs, scratch, in_specs, *, block, products,
          interpret):
    """A kernel over the lower triangle of one k/v head's block pairs, with
    the cost of the causal half of ``products`` [S, D] x [D, S] products a
    query head."""
    b, kv, g, s, d = operands[0].shape
    tables = _triangle(s // block)
    return pl.pallas_call(
        kernel,
        out_shape=out_shapes,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv, tables[0].shape[0]),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(s),
        ),
        cost_estimate=pl.CostEstimate(
            flops=products * b * kv * g * s * s * d,  # 2·S²·D / 2 a product and head
            transcendentals=b * kv * g * s * s // 2,
            bytes_accessed=_nbytes(*operands, *out_shapes),
        ),
        interpret=interpret,
        name=name,
    )(*tables, *operands)


def _specs(g: int, block: int, d: int, split: int = 1):
    """Block specs of a [B, KV·split, G, S, D] query-side array, a
    [B, KV, S, D] key-side array (``split`` groups of query heads read each
    k/v head), a [B, KV·split, S/block, 1, G·block] row statistic and a
    [B, S, S] selection bias, indexed through the prefetched (query block,
    key block) tables."""
    q = pl.BlockSpec((None, None, g, block, d), lambda b, h, t, qi, kj: (b, h, 0, qi[t], 0))
    if split == 1:  # no division in the index map: the causal kernels' program as it was
        kv = pl.BlockSpec((None, None, block, d), lambda b, h, t, qi, kj: (b, h, kj[t], 0))
    else:
        kv = pl.BlockSpec((None, None, block, d), lambda b, h, t, qi, kj: (b, h // split, kj[t], 0))
    row = pl.BlockSpec((None, None, None, 1, g * block), lambda b, h, t, qi, kj: (b, h, qi[t], 0, 0))
    bias = pl.BlockSpec((None, block, block), lambda b, h, t, qi, kj: (b, qi[t], kj[t]))
    return q, kv, row, bias


def _forward(q, k, v, block: int, scale: float, interpret: bool, bias=None):
    b, kv, g, s, d = q.shape
    q_spec, kv_spec, row_spec, bias_spec = _specs(g, block, d, kv // k.shape[1])
    rows = g * block
    select = bias is not None
    return _call(
        functools.partial(_fwd_kernel, scale=scale, select=select),
        "selected_attention_fwd" if select else "causal_attention_fwd",
        (q, k, v, bias) if select else (q, k, v),
        (jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((b, kv, s // block, 1, rows), jnp.float32)),
        (q_spec, row_spec),
        [pltpu.VMEM((rows, LANES), jnp.float32), pltpu.VMEM((rows, LANES), jnp.float32),
         pltpu.VMEM((rows, d), jnp.float32)],
        [q_spec, kv_spec, kv_spec, bias_spec] if select else [q_spec, kv_spec, kv_spec],
        block=block,
        products=2,
        interpret=interpret,
    )


def _backward(q, k, v, do, lse, delta, block: int, scale: float, interpret: bool, bias=None):
    """dQ like q; dK and dV ``[B, KV·split, S, D]``, one per group of query
    heads (the caller sums the ``split`` groups of a k/v head)."""
    b, kv, g, s, d = q.shape
    q_spec, kv_spec, row_spec, bias_spec = _specs(g, block, d, kv // k.shape[1])
    whole = pl.BlockSpec((None, None, s, d), lambda b_, h, t, qi, kj: (b_, h, 0, 0))
    rows = g * block
    select = bias is not None
    return _call(
        functools.partial(_bwd_kernel, scale=scale, select=select),
        "selected_attention_bwd" if select else "causal_attention_bwd",
        (q, k, v, do, lse, delta, bias) if select else (q, k, v, do, lse, delta),
        (jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct((b, kv, s, d), k.dtype),
         jax.ShapeDtypeStruct((b, kv, s, d), v.dtype)),
        (q_spec, whole, whole),
        [pltpu.VMEM((rows, d), jnp.float32), pltpu.VMEM((s, d), jnp.float32),
         pltpu.VMEM((s, d), jnp.float32)],
        [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec] + [bias_spec] * select,
        block=block,
        products=5,
        interpret=interpret,
    )


def _groups(heads: int, kv: int) -> int:
    """Groups of query heads the kernels see: the k/v heads (each with all its
    query heads, be that one), or more where one serves over HEADS_PER_STEP."""
    g = heads // kv
    return kv * (g // HEADS_PER_STEP) if g > HEADS_PER_STEP and g % HEADS_PER_STEP == 0 else kv


def _query_major(x, kv: int):
    """``[B, S, H, D]`` as ``[B, KV, G, S, D]``, the layout the kernels read
    (``kv`` groups of ``G`` adjacent query heads)."""
    b, s, h, d = x.shape
    return x.reshape(b, s, kv, h // kv, d).transpose(0, 2, 3, 1, 4)


def _key_major(x):
    """``[B, S, KV, D]`` as ``[B, KV, S, D]``, and back."""
    return x.transpose(0, 2, 1, 3)


def _caller_layout(x):
    """``[B, KV, G, S, D]`` back to ``[B, S, H, D]``."""
    b, kv, g, s, d = x.shape
    return x.transpose(0, 3, 1, 2, 4).reshape(b, s, kv * g, d)


def _scales(d: int):
    """``(into q, into the scores)`` of ``1 / sqrt(d)``: a power of two is
    exact in any floating dtype and goes into q (and out of dQ); any other is
    applied to the float32 scores inside the kernels."""
    scale = d ** -0.5
    return (scale, 1.0) if math.frexp(scale)[0] == 0.5 else (1.0, scale)


def _forward_from(q, k, v, block: int, interpret: bool, bias=None):
    into_q, scale = _scales(q.shape[-1])
    q = q * jnp.asarray(into_q, q.dtype)
    out, lse = _forward(
        _query_major(q, _groups(q.shape[2], k.shape[2])), _key_major(k), _key_major(v),
        block, scale, interpret, bias,
    )
    return _caller_layout(out), lse


def _backward_from(residuals, do, block: int, interpret: bool, bias=None):
    q, k, v, out, lse = residuals
    b, s, h, d = q.shape
    kv = k.shape[2]
    groups = _groups(h, kv)
    into_q, scale = _scales(d)
    into_q = jnp.asarray(into_q, q.dtype)
    # Σ_d dO·O a row, laid out as the log-sum-exp: [B, groups, S/block, 1, G·block]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B, S, H]
    delta = delta.reshape(b, s // block, block, groups, h // groups).transpose(0, 3, 1, 4, 2)
    dq, dk, dv = _backward(
        _query_major(q * into_q, groups), _key_major(k), _key_major(v), _query_major(do, groups),
        lse, delta.reshape(lse.shape), block, scale, interpret, bias,
    )
    if groups > kv:  # one dK, dV per group of query heads: add a k/v head's groups
        dk, dv = (x.reshape(b, kv, groups // kv, s, d).sum(axis=2, dtype=x.dtype) for x in (dk, dv))
    return _caller_layout(dq) * into_q, _key_major(dk), _key_major(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention(q, k, v, block, interpret):
    return _forward_from(q, k, v, block, interpret)[0]


def _attention_fwd(q, k, v, block, interpret):
    # The residuals stay in the caller's layout: head-major arrays of head
    # size 64 are lane-padded to twice their size in HBM.
    out, lse = _forward_from(q, k, v, block, interpret)
    return out, (q, k, v, out, lse)


def _attention_bwd(block, interpret, residuals, do):
    return _backward_from(residuals, do, block, interpret)


_attention.defvjp(_attention_fwd, _attention_bwd)


def _check(seq_len: int, block: int):
    if not supported(seq_len, block):
        raise ValueError(
            f"sequence length {seq_len} is not a multiple of the kernel's block {block} "
            f"up to {MAX_SEQ}"
        )


def causal_attention(q, k, v, *, block: int = BLOCK, interpret: bool = False):
    """Causal ``softmax(q kᵀ / sqrt(D)) v``.  q ``[B, S, H, D]``; k, v
    ``[B, S, KV, D]`` with each k/v head serving ``H / KV`` query heads
    (query head ``h`` reads k/v head ``h // (H / KV)``); ``S`` a multiple of
    ``block``.  Returns ``[B, S, H, D]`` in q's dtype."""
    _check(q.shape[1], block)
    return _attention(q, k, v, block, interpret)


def _rows_major(lse, heads: int):
    """The kernels' row statistic ``[B, groups, S/block, 1, G·block]`` as ``[B, S, H]``."""
    b, groups, blocks, _, rows = lse.shape
    g = heads // groups
    lse = lse.reshape(b, groups, blocks, g, rows // g).transpose(0, 2, 4, 1, 3)
    return lse.reshape(b, blocks * (rows // g), heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _selected(q, k, v, bias, block, interpret):
    out, lse = _forward_from(q, k, v, block, interpret, bias)
    return out, _rows_major(lse, q.shape[2])


def _selected_fwd(q, k, v, bias, block, interpret):
    out, lse = _forward_from(q, k, v, block, interpret, bias)
    return (out, _rows_major(lse, q.shape[2])), (q, k, v, out, lse, bias)


def _selected_bwd(block, interpret, residuals, cotangents):
    *residuals, bias = residuals
    # the log-sum-exp is a statistic for detached readers: its cotangent is dropped
    return (*_backward_from(residuals, cotangents[0], block, interpret, bias), None)


_selected.defvjp(_selected_fwd, _selected_bwd)


def selected_attention(q, k, v, bias, *, block: int = BLOCK, interpret: bool = False):
    """``softmax`` over each query's selected keys of ``q kᵀ / sqrt(D)``, times
    v.  Shapes as :func:`causal_attention`; ``bias [B, S, S]`` (any floating
    dtype that holds -1e30, bfloat16 halves its bytes) is 0 where query ``t``
    sees key ``s`` and ``-1e30`` where not, the keys after ``t`` included, and
    every query sees at least one key.  Returns ``(out [B, S, H, D],
    lse [B, S, H])``: the float32 log-sum-exp of each row's selected scores,
    for readers that take no gradient through it.  No gradient reaches
    ``bias``."""
    _check(q.shape[1], block)
    return _selected(q, k, v, bias, block, interpret)


def _head_mean_kernel(q_ref, k_ref, lse_ref, o_ref, acc_ref, *, scale: float, heads: int):
    """One group of query heads against one block of keys.  The scores are
    held transposed, ``[keys, g·queries]``, so the log-sum-exp broadcasts
    along sublanes and a head is a run of lanes; the sum over the heads
    waits in ``acc_ref [keys, queries]`` and is written, transposed and
    divided by ``heads``, after the last group."""
    h = pl.program_id(1)
    g, rows, d = q_ref.shape
    pt = jnp.exp(_scores(k_ref[...], q_ref[...].reshape(g * rows, d), scale) - lse_ref[...])
    part = pt[:, :rows]
    for n in range(1, g):
        part = part + pt[:, n * rows : (n + 1) * rows]

    @pl.when(h == 0)
    def _():
        acc_ref[...] = part

    @pl.when(h > 0)
    def _():
        acc_ref[...] += part

    @pl.when(h == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / heads).T


def head_mean_probs(q, k, lse, *, heads: int, block: int = BLOCK, interpret: bool = False):
    """``(1 / heads) Σ_h exp(q_h kᵀ / sqrt(D) - lse_h)`` of one block of
    queries against ``T`` keys: q ``[KV, G·Bq, D]`` (the ``G = heads / KV``
    query heads of a k/v head stacked head-major, as the kernels above hold a
    block), k ``[KV, T, D]``, lse ``[KV, G·Bq]`` float32, the rows'
    log-sum-exp as :func:`selected_attention` returns it; ``T`` a multiple of
    ``block`` and ``Bq`` of 128.  Returns float32 ``[Bq, T]``; every pair is
    computed, whatever was selected.  Not differentiable."""
    kv, stacked, d = q.shape
    keys, rows = k.shape[1], stacked * kv // heads  # queries a head
    if keys % block or block % LANES or rows % LANES:
        raise ValueError(
            f"{keys} keys are not a multiple of the kernel's block {block}, or the "
            f"{rows} queries of a head not one of {LANES}"
        )
    groups = _groups(heads, kv)
    g, split = heads // groups, groups // kv  # heads a grid step; steps a k/v head
    into_q, scale = _scales(d)
    q = (q * jnp.asarray(into_q, q.dtype)).reshape(groups, g, rows, d)
    lse = lse.reshape(groups, 1, g * rows)
    out = jax.ShapeDtypeStruct((rows, keys), jnp.float32)
    return pl.pallas_call(
        functools.partial(_head_mean_kernel, scale=scale, heads=heads),
        out_shape=out,
        grid=(keys // block, groups),
        in_specs=[
            pl.BlockSpec((None, g, rows, d), lambda j, h: (h, 0, 0, 0)),
            pl.BlockSpec((None, block, d), lambda j, h: (h // split, j, 0)),
            pl.BlockSpec((None, 1, g * rows), lambda j, h: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda j, h: (0, j)),
        scratch_shapes=[pltpu.VMEM((block, rows), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=32 << 20,  # scores and exponentials 4 MiB each, every block twice
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * heads * rows * keys * d,
            transcendentals=heads * rows * keys,
            bytes_accessed=_nbytes(q, k, lse, out),
        ),
        interpret=interpret,
        name="head_mean_probs",
    )(q, k, lse)


def _earlier(start, j, rows: int, block: int):
    """``[rows, block]``: whether key ``j·block + column`` comes no later
    than query ``start + row``."""
    qpos = start + lax.broadcasted_iota(jnp.int32, (rows, block), 0)
    kpos = j * block + lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    return kpos <= qpos


def _index_heads(q_ref, d: int):
    """The heads of ``q_ref [queries, J·d]``, each ``[queries, d]``: a run of lanes."""
    return [q_ref[:, n * d : (n + 1) * d] for n in range(q_ref.shape[1] // d)]


def _index_fwd_kernel(start_ref, q_ref, k_ref, w_ref, o_ref, *, d: int, scale: float):
    """Every indexer head of a block of queries against one block of keys.
    A head's pre-activations are held transposed, ``[keys, queries]``, so its
    weights ``w_ref[n]`` broadcast along sublanes; ReLU, weight and the sum
    over the heads stay in VMEM, and the block is written once, transposed,
    scaled and masked.  A block whose keys all come after the last query is
    -inf without a product."""
    j = pl.program_id(0)
    rows, block = o_ref.shape
    start = start_ref[0]
    seen = j * block < start + rows  # the block's first key against the last query

    @pl.when(seen)
    def _():
        k = k_ref[...]
        total = None
        for n, q in enumerate(_index_heads(q_ref, d)):
            part = jnp.maximum(_scores(k, q, 1.0), 0.0) * w_ref[n : n + 1, :]
            total = part if total is None else total + part
        o_ref[...] = jnp.where(_earlier(start, j, rows, block), total.T * scale, -jnp.inf)

    @pl.when(jnp.logical_not(seen))
    def _():
        o_ref[...] = jnp.full_like(o_ref, -jnp.inf)


def _index_bwd_kernel(start_ref, q_ref, k_ref, w_ref, g_ref, dq_ref, dk_ref, dw_ref, dq_acc, *,
                      d: int, scale: float):
    """The pair of :func:`_index_fwd_kernel`, backward: a head's
    pre-activations again, ``d_pre = [pre > 0] · w · c·ḡ`` (ReLU's slope at 0
    is 0; ``ḡ`` the cotangent, zeroed here where the output was -inf), cast to
    the compute dtype for its two products.  dq accumulates in VMEM over the
    key blocks and is written after the last one, dw in its output block; dk
    of a key block is the sum over the heads."""
    j = pl.program_id(0)
    rows, block = g_ref.shape
    start = start_ref[0]
    seen = j * block < start + rows

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(seen)
    def _():
        k = k_ref[...]
        gt = jnp.where(_earlier(start, j, rows, block), g_ref[...], 0.0).T * scale  # [keys, rows]
        dk, dq, dw = None, [], []
        for n, q in enumerate(_index_heads(q_ref, d)):
            pre = _scores(k, q, 1.0)
            live = jnp.where(pre > 0, gt, 0.0)
            dw.append((live * pre).sum(axis=0, keepdims=True))
            d_pre = (live * w_ref[n : n + 1, :]).astype(q.dtype)
            dq.append(lax.dot_general(d_pre, k, _TN, preferred_element_type=jnp.float32))
            part = jnp.dot(d_pre, q, preferred_element_type=jnp.float32)
            dk = part if dk is None else dk + part
        dq_acc[...] += jnp.concatenate(dq, axis=1)
        dw_ref[...] += jnp.concatenate(dw, axis=0)
        dk_ref[...] = dk.astype(dk_ref.dtype)

    @pl.when(jnp.logical_not(seen))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _index_call(qi, ki, w, start, ct, block: int, interpret: bool):
    """The index scores' forward kernel, or with a cotangent ``ct [Bq, T]``
    the backward one (dqI, dkI, dw like their primals): a grid over the blocks
    of keys, ``start`` prefetched, at the cost of the XLA form's products (one
    ``[Bq·J, Di] x [Di, T]`` forward, two backward).  q stays in its own
    layout, ``[Bq, J·Di]`` (a head is a run of lanes), and is read once; w
    goes head-major, ``[J, Bq]``."""
    rows, heads, d = qi.shape
    keys = ki.shape[0]
    q, w = qi.reshape(rows, heads * d), w.T
    whole = lambda x: pl.BlockSpec(x.shape, lambda j, s: (0,) * len(x.shape))  # noqa: E731
    key_block = pl.BlockSpec((block, d), lambda j, s: (j, 0))
    pair_block = pl.BlockSpec((rows, block), lambda j, s: (0, j))
    if ct is None:
        kernel, name, operands = _index_fwd_kernel, "index_scores_fwd", (q, ki, w)
        out_shape, out_specs = jax.ShapeDtypeStruct((rows, keys), jnp.float32), pair_block
        scratch = []
    else:
        kernel, name, operands = _index_bwd_kernel, "index_scores_bwd", (q, ki, w, ct)
        out_shape = (jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(ki.shape, ki.dtype),
                     jax.ShapeDtypeStruct(w.shape, jnp.float32))
        out_specs = (whole(q), key_block, whole(w))
        scratch = [pltpu.VMEM(q.shape, jnp.float32)]
    out = pl.pallas_call(
        functools.partial(kernel, d=d, scale=float(heads * d) ** -0.5),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(keys // block,),
            in_specs=[whole(q), key_block, whole(w)] + [pair_block] * (ct is not None),
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 << 20,  # a head's float32 [keys, queries] tiles at 1 MiB, dq of every head
        ),
        cost_estimate=pl.CostEstimate(
            flops=(1 if ct is None else 2) * 2 * heads * rows * keys * d,
            transcendentals=0,
            bytes_accessed=_nbytes(*operands, *jax.tree.leaves(out_shape)),
        ),
        interpret=interpret,
        name=name,
    )(jnp.asarray(start, jnp.int32).reshape(1), *operands)
    if ct is None:
        return out
    dq, dk, dw = out
    return dq.reshape(qi.shape), dk, dw.T


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _index(qi, ki, w, start, block, interpret):
    return _index_call(qi, ki, w, start, None, block, interpret)


def _index_fwd(qi, ki, w, start, block, interpret):
    return _index_call(qi, ki, w, start, None, block, interpret), (qi, ki, w, start)


def _index_bwd(block, interpret, residuals, ct):
    return (*_index_call(*residuals, ct, block, interpret), None)  # none for ``start``


_index.defvjp(_index_fwd, _index_bwd)


def index_scores(qi, ki, w, start, *, block: int = BLOCK, interpret: bool = False):
    """The ``keye_vl2`` indexer's scores of one block of queries against
    ``T`` keys, ``(J·Di)^-½ Σ_j w[t,j] · ReLU(qI[t,j] · kI[s])``: qi
    ``[Bq, J, Di]`` and ki ``[T, Di]`` in the compute dtype, w ``[Bq, J]``
    float32, ``start`` the (traced) position of the block's first query;
    ``T`` a multiple of ``block`` and ``Bq`` of 128.  Returns float32
    ``[Bq, T]``, -inf where the key comes after the query.  Products from the
    compute dtype accumulated in float32, everything else float32; only the
    order of the sum over the heads differs from the XLA form
    (``models/keye_vl2.py:_index_block``).  Differentiable in qi, ki and w:
    the backward's residuals are the inputs alone, and its two products take
    ``d_pre`` rounded to the compute dtype and accumulate in float32, which
    is how the XLA form's gradient takes its float32 ``d_pre`` on a TPU at
    the default matmul precision."""
    rows, keys = qi.shape[0], ki.shape[0]
    if keys % block or block % LANES or rows % LANES:
        raise ValueError(
            f"{keys} keys are not a multiple of the kernel's block {block}, or the "
            f"{rows} queries not one of {LANES}"
        )
    return _index(qi, ki, w, start, block, interpret)
