"""Synchronous gradient synchronization with optional lossy compression.

This is the TPU-native replacement for the reference's whole L4 protocol
(кластер.py:255-557): workers quantize accumulated grads and send to the
server; the server averages, re-quantizes the average, broadcasts; everyone
(server included, via its self-application block кластер.py:402-433) steps on
the *same* dequantized gradient.

Semantics preserved:
- optional per-replica quantization before the reduce (the worker wire);
- exact mean across all replicas — fixing the reference's "crooked averaging
  … (fix!)" loop that over-divides earlier contributions and divides by the
  worker count instead of the replica count (кластер.py:268-321, SURVEY §2.8d);
- optional re-quantization of the mean, so every replica applies a
  bit-identical update (SPMD + deterministic psum already guarantees
  identical values; re-quantization reproduces the reference's *information
  loss*, not its mechanism).

Runs inside shard_map over the ``data`` mesh axis: `lax.pmean` lowers to one
fused XLA all-reduce over ICI/DCN instead of N sequential pickled TCP
round-trips.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ddlpc_tpu.config import CompressionConfig
from ddlpc_tpu.ops.quantize import (
    _leaf_keys,
    fake_quantize,
    global_absmax,
    levels_for,
    quantize_with_scale,
    rounding_key,
    safe_divisor,
    snap_to_lattice,
)
from ddlpc_tpu.parallel.bucketing import bucket_index_groups
from ddlpc_tpu.parallel.compressed_allreduce import wire_dtype
from ddlpc_tpu.parallel.shard_update import chunk_leaf, local_chunk

PyTree = Any


def resolve_codec_backend(compression: CompressionConfig):
    """The fake-quantize implementation for the simulate transport: the XLA
    tree transform, or the fused Pallas kernel (interpreted off-TPU so the
    CPU test meshes exercise the same code path)."""
    if compression.codec_backend == "pallas":
        from ddlpc_tpu.ops.pallas_quantize import (
            default_interpret,
            fake_quantize_pallas,
        )

        return functools.partial(
            fake_quantize_pallas, interpret=default_interpret()
        )
    if compression.codec_backend == "xla":
        return fake_quantize
    raise ValueError(
        f"unknown codec_backend {compression.codec_backend!r} "
        "(expected 'xla' or 'pallas')"
    )


def simulate_wire_dtype(
    axis_size: Optional[int], compression: CompressionConfig
):
    """The narrow dtype the simulate transport puts on the wire for this
    config, or None when the exact-fp32 fake-quantize path must stay.

    The fused collective sums LATTICE values — integers in [-levels,
    levels] — so the reduce is exact (and therefore bit-identical across
    program layouts, reduction order included) iff every partial sum is
    representable on the wire: int8/int16 per
    ``compressed_allreduce.wire_dtype``'s bound for the int8 codec, f16
    while ``axis_size·levels ≤ 2048`` for the fp16 codec (every integer up
    to 2048 is exact in fp16; above it the ulp is 2 and sums would round).
    mode='none' has no codec and quantize_local=False has no pre-reduce
    lattice to ship — both keep the fp32 wire.  The program auditor's
    declared wire dtype (analysis/program.py) mirrors this function
    exactly; the HLO dtype-flow contract is what proves the declaration.
    """
    if (
        axis_size is None
        or compression.mode == "none"
        or not compression.quantize_local
        or compression.transport != "simulate"
    ):
        return None
    levels = levels_for(compression)
    if compression.mode == "int8":
        try:
            return wire_dtype(axis_size, levels)
        except ValueError:
            return None
    if axis_size * levels <= 2048:
        return jnp.float16
    return None


def grad_bucket_groups(tree: PyTree, bucket_mb: float):
    """Per-bucket leaf-index lists over ``tree``'s flatten order — a pure
    function of the leaf shapes (parallel/bucketing.py), so the replicated,
    ZeRO-1 and GSPMD step builders all derive the identical partition and
    the auditor's census counts the same buckets in each layout."""
    leaves = jax.tree_util.tree_leaves(tree)
    sizes = [int(l.size) * jnp.dtype(l.dtype).itemsize for l in leaves]
    return bucket_index_groups(sizes, bucket_mb)


def _bucketed(tree: PyTree, bucket_mb: float, key, sync_one) -> PyTree:
    """Run ``sync_one(subtree, key)`` once per size-targeted bucket and
    reassemble.  One bucket (bucket_mb=0, or a target larger than the whole
    tree) short-circuits to a single call on the ORIGINAL tree with the
    ORIGINAL key — trace-identical to the pre-bucketing program, which is
    what keeps the degenerate case bit-identical.  With several buckets
    each gets ``fold_in(key, bucket_index)`` (before the local/mean split,
    so buckets draw independent noise at both loss points) and its own
    scales — the partition is the unit of codec loss."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    groups = grad_bucket_groups(tree, bucket_mb)
    if len(groups) == 1:
        return sync_one(tree, key)
    out: list = [None] * len(leaves)
    for b, idxs in enumerate(groups):
        bkey = None if key is None else jax.random.fold_in(key, b)
        part = sync_one([leaves[i] for i in idxs], bkey)
        for i, v in zip(idxs, jax.tree_util.tree_leaves(part)):
            out[i] = v
    return jax.tree_util.tree_unflatten(treedef, out)


def _fenced_wire_encode(
    grads: PyTree, compression, safe, levels: float, wire, key
) -> PyTree:
    """Fenced encode-to-wire: barrier(grads) → snap to the lattice against
    the shared (pmax'd) scale → cast to the wire dtype → barrier.  The
    fences isolate exactly the codec's elementwise region, same cut points
    as apply_codec_fenced — the downstream DEQUANTIZE is deliberately not
    here (it stays unfenced so XLA can fuse the single scalar multiply
    into the collective's consumer; one multiply cannot FMA-contract, so
    program layouts cannot round it differently)."""
    grads = lax.optimization_barrier(grads)
    if compression.codec_backend == "pallas":
        from ddlpc_tpu.ops.pallas_quantize import (
            default_interpret,
            encode_to_wire_pallas,
        )

        q = encode_to_wire_pallas(
            grads, compression, safe, wire,
            key=key, interpret=default_interpret(),
        )
    else:
        q = jax.tree.map(
            lambda g, k: quantize_with_scale(g, safe, levels, key=k).astype(
                wire
            ),
            grads,
            _leaf_keys(grads, key),
        )
    return lax.optimization_barrier(q)


def _wire_decode(tree: PyTree, inv, compression) -> PyTree:
    """Dequantize a summed wire tree: one multiply by the runtime scalar
    ``inv = scale / (levels · axis_size)`` — quantize.decode's formula with
    the mean division folded into the same single rounding."""
    if compression.codec_backend == "pallas":
        from ddlpc_tpu.ops.pallas_quantize import (
            decode_from_wire_pallas,
            default_interpret,
        )

        return decode_from_wire_pallas(
            tree, inv, interpret=default_interpret()
        )
    return jax.tree.map(lambda q: q.astype(jnp.float32) * inv, tree)


def _fused_allreduce_mean(
    grads: PyTree, axis_name, compression, axis_size, local_key, wire
) -> PyTree:
    """quantize_local's loss point with the NARROW dtype on the wire: the
    all-reduce operand is the int8/int16/f16 lattice, not fp32.  The scale
    is shared across replicas (lax.pmax of the per-replica abs-maxes — the
    ring transport's convention) so the integer row sums dequantize with
    one global scalar; see docs/QUANTIZATION.md "True integer wire" for
    where this is bit-identical and where the shared scale is a declared,
    test-pinned deviation from the per-replica fake-quantize reference."""
    scale = lax.pmax(global_absmax(grads), axis_name)
    safe = safe_divisor(scale)
    levels = float(levels_for(compression))
    q = _fenced_wire_encode(grads, compression, safe, levels, wire, local_key)
    summed = lax.psum(q, axis_name)
    inv = scale / (levels * axis_size)
    return _wire_decode(summed, inv, compression)


def _fused_scatter_mean(
    grads: PyTree, axis_name, compression, axis_size, local_key, wire
) -> PyTree:
    """Reduce-scatter spelling of :func:`_fused_allreduce_mean`: encode the
    FULL leaves (identical call to the replicated path — the precondition
    for bit-identity), chunk the quantized [N, K] layout, psum_scatter the
    narrow rows (integer partial sums are exact, so row r's sum equals the
    corresponding elements of the replicated psum bit-for-bit), and
    dequantize only the local [1, K] shard."""
    scale = lax.pmax(global_absmax(grads), axis_name)
    safe = safe_divisor(scale)
    levels = float(levels_for(compression))
    q = _fenced_wire_encode(grads, compression, safe, levels, wire, local_key)
    summed = jax.tree.map(
        lambda qi: lax.psum_scatter(
            chunk_leaf(qi, axis_size), axis_name,
            scatter_dimension=0, tiled=True,
        ),
        q,
    )
    inv = scale / (levels * axis_size)
    return _wire_decode(summed, inv, compression)


@jax.named_scope("ddlpc/grad_sync")
def sync_gradients(
    grads: PyTree,
    axis_name: str,
    compression: CompressionConfig,
    axis_size: Optional[int] = None,
    key: Optional[jax.Array] = None,
) -> PyTree:
    """All-reduce-mean local gradients across ``axis_name``.

    Call inside shard_map/pmap.  With compression.mode='none' this is a plain
    pmean; otherwise the codec's information loss is injected at the same
    points the reference loses it (client send: quantize_local; server
    rebroadcast: quantize_mean).  When the lattice sums fit the narrow
    dtype (:func:`simulate_wire_dtype`), quantize_local FUSES into the
    collective: the all-reduce operand is the int8/f16 lattice itself —
    the quantized bits are what actually crosses the wire — instead of
    fp32 with the loss simulated around it.  ``compression.bucket_mb``
    splits the tree into size-targeted buckets, each synced by its own
    fused collective (parallel/bucketing.py).

    ``compression.transport='ring'`` swaps the fp32 pmean for the
    byte-compressed ppermute ring (compressed_allreduce.py), which needs the
    static ``axis_size`` of the mesh axis.

    ``key`` drives stochastic rounding (compression.rounding='stochastic');
    all replicas must pass the same key (the step builders derive it from
    the replicated step counter), which keeps the mean-requantization
    bit-identical across replicas.
    """
    if compression.transport not in ("simulate", "ring"):
        raise ValueError(
            f"unknown compression transport {compression.transport!r} "
            "(expected 'simulate' or 'ring')"
        )
    # Validate codec_backend up front on every path: the ring inlines its own
    # formula (backend-independent), but a typo'd backend must not be
    # silently accepted on one transport and rejected on the other.
    fq = resolve_codec_backend(compression)
    if compression.transport == "ring" and compression.mode != "none":
        if axis_size is None:
            raise ValueError(
                "transport='ring' needs the static axis_size (the step "
                "builders pass mesh.shape[data_axis])"
            )
        if compression.bucket_mb > 0:
            raise ValueError(
                "bucket_mb composes only with transport='simulate' — the "
                "ring's flatten/concat transport is whole-tree by "
                "construction (one concatenated wire buffer per sync)"
            )
        if not (compression.quantize_local and compression.quantize_mean):
            raise ValueError(
                "transport='ring' quantizes at both loss points by "
                "construction (integer wire sums + quantized gather hops); "
                "quantize_local/quantize_mean=False ablations need "
                "transport='simulate'"
            )
        from ddlpc_tpu.parallel.compressed_allreduce import (
            ring_allreduce_mean_quantized,
        )

        return ring_allreduce_mean_quantized(
            grads, axis_name, axis_size, compression, key=key
        )
    if compression.mode != "none":
        key = rounding_key(compression, key)
    return _bucketed(
        grads,
        compression.bucket_mb,
        key,
        lambda t, k: _sync_tree(t, axis_name, compression, axis_size, k, fq),
    )


def _sync_tree(grads, axis_name, compression, axis_size, key, fq) -> PyTree:
    """One bucket's all-reduce-mean (the whole tree when bucket_mb=0)."""
    local_key = mean_key = None
    if key is not None:
        local_key, mean_key = jax.random.split(key)
        # Decorrelate the LOCAL rounding noise across replicas: per-replica
        # gradients are highly correlated, so a shared draw would make the
        # rounding errors common-mode and survive the pmean at full-step
        # size instead of averaging down ~1/√N.  The MEAN key must stay
        # shared — every replica requantizes the identical mean and must
        # make identical decisions.
        local_key = jax.random.fold_in(local_key, lax.axis_index(axis_name))
    wire = simulate_wire_dtype(axis_size, compression)
    if wire is not None:
        grads = _fused_allreduce_mean(
            grads, axis_name, compression, axis_size, local_key, wire
        )
    else:
        if compression.quantize_local:
            grads = apply_codec_fenced(fq, grads, compression, key=local_key)
        grads = lax.pmean(grads, axis_name)
    if compression.quantize_mean:
        grads = apply_codec_fenced(fq, grads, compression, key=mean_key)
    return grads


def apply_codec_fenced(fq, grads: PyTree, compression, key=None) -> PyTree:
    """Run a fake-quantize stage inside ``lax.optimization_barrier`` fences.

    The barriers pin the codec's elementwise chain (scale divide, lattice
    snap, cast, dequantize) into an isolated fusion region: without them
    XLA fuses it into the surrounding collectives, and the replicated and
    sharded-update programs then round the SAME codec arithmetic
    differently (1-ulp FMA/fusion drift — the same effect documented at
    train_step._fenced_update, observed on both the shard_map and GSPMD
    paths).  Every step variant quantizes through this wrapper so the
    codec's bits cannot depend on which program surrounds it."""
    if compression.mode == "none":
        return fq(grads, compression, key=key)
    grads = lax.optimization_barrier(grads)
    return lax.optimization_barrier(fq(grads, compression, key=key))


def apply_codec_fenced_bucketed(fq, grads: PyTree, compression, key=None):
    """Bucketed spelling of :func:`apply_codec_fenced` for step builders
    with no explicit collective of their own (GSPMD: the partitioner owns
    the wire) — same per-bucket key schedule and per-bucket scales as the
    bucketed syncs, so the GSPMD codec loss matches the shard_map layouts
    bucket-for-bucket.  One bucket degenerates to apply_codec_fenced on
    the original tree."""
    return _bucketed(
        grads,
        compression.bucket_mb,
        key,
        lambda t, k: apply_codec_fenced(fq, t, compression, key=k),
    )


def validate_scatter_compression(compression: CompressionConfig) -> None:
    """Reject codec combinations the sharded update cannot reproduce
    bit-identically (shared by the step builders, for a build-time error,
    and sync_gradients_scatter, so the invariant cannot be bypassed).
    ``shard_update.resolve_shard_update``'s 'auto' avoids both."""
    if compression.transport not in ("simulate", "ring"):
        raise ValueError(
            f"unknown compression transport {compression.transport!r} "
            "(expected 'simulate' or 'ring')"
        )
    if compression.transport == "ring" and compression.mode != "none":
        raise ValueError(
            "sharded update composes only with transport='simulate' — "
            "transport='ring' owns its own full-tree quantized collective "
            "(set shard_update='off' to keep the ring)"
        )
    if (
        compression.mode != "none"
        and compression.quantize_mean
        and compression.codec_backend == "pallas"
    ):
        raise ValueError(
            "sharded update cannot reproduce the pallas mean-stage codec "
            "bit-identically (hardware-PRNG noise cannot be sliced to a "
            "shard) — use codec_backend='xla' or shard_update='off'"
        )


@jax.named_scope("ddlpc/grad_sync")
def sync_gradients_scatter(
    grads: PyTree,
    axis_name: str,
    compression: CompressionConfig,
    axis_size: int,
    key: Optional[jax.Array] = None,
) -> PyTree:
    """Reduce-scatter variant of :func:`sync_gradients` for the ZeRO-1
    sharded update (shard_update.py): instead of every replica receiving
    the full codec-processed mean, replica ``r`` receives ONLY its ``[1, K]``
    chunk of each leaf (chunk layout per ``shard_update.chunk_leaf``) —
    same wire volume as the all-reduce's reduce-scatter half, 1/N of the
    post-reduce arithmetic and memory per replica.

    Codec loss points map exactly onto :func:`sync_gradients` and are
    BIT-IDENTICAL per element to the replicated path (test-pinned):

    - ``quantize_local`` runs on the full per-replica gradients *before*
      the scatter — identical tensors, identical call.
    - ``quantize_mean`` runs on each replica's chunk of the mean with the
      GLOBAL scale (``lax.pmax`` of the per-chunk abs-maxes reproduces the
      whole-model max exactly — max is associative) and, for stochastic
      rounding, the replica's slice of the full leaf's threefry noise
      field (drawn at full shape from the shared mean key, then chunked —
      a shard-shaped draw would decide differently than the replicated
      path).  The scattered sum itself is bit-identical to ``psum`` on
      XLA's backends (both accumulate in ring order; pinned by the
      shard-vs-replicated identity tests).

    ``transport='ring'`` and the pallas mean-stage are rejected — see
    ``shard_update.resolve_shard_update`` for why they cannot compose.
    """
    validate_scatter_compression(compression)
    fq = resolve_codec_backend(compression)
    if compression.mode != "none":
        key = rounding_key(compression, key)
    return _bucketed(
        grads,
        compression.bucket_mb,
        key,
        lambda t, k: _scatter_tree(
            t, axis_name, compression, axis_size, k, fq
        ),
    )


def _scatter_tree(grads, axis_name, compression, axis_size, key, fq):
    """One bucket's reduce-scatter-mean (the whole tree when bucket_mb=0)."""
    local_key = mean_key = None
    if key is not None:
        local_key, mean_key = jax.random.split(key)
        # Same decorrelation as sync_gradients: local noise per replica,
        # mean noise shared (every replica slices the same field).
        local_key = jax.random.fold_in(local_key, lax.axis_index(axis_name))
    wire = simulate_wire_dtype(axis_size, compression)
    if wire is not None:
        shards = _fused_scatter_mean(
            grads, axis_name, compression, axis_size, local_key, wire
        )
    else:
        if compression.quantize_local:
            grads = apply_codec_fenced(fq, grads, compression, key=local_key)
        # Reduce-scatter the mean: chunk each leaf [N, K] and let replica r
        # keep the summed row r.  Division by the static axis size matches
        # pmean's.
        shards = jax.tree.map(
            lambda g: lax.psum_scatter(
                chunk_leaf(g.astype(jnp.float32), axis_size), axis_name,
                scatter_dimension=0, tiled=True,
            ) / axis_size,
            grads,
        )
    if compression.quantize_mean and compression.mode != "none":
        levels = float(levels_for(compression))
        out_dtype = jnp.int8 if compression.mode == "int8" else jnp.float16
        # Same fusion fence as apply_codec_fenced, cut at the same points
        # (chunk mean in, dequantized chunk mean out) so the per-element
        # quantization arithmetic compiles identically to the replicated
        # path's region.
        shards = lax.optimization_barrier(shards)
        # Global scale over this sync's tree (the whole model at
        # bucket_mb=0, one bucket otherwise), exactly global_absmax of the
        # full mean: padding rows are zero and max is order-independent.
        scale = lax.pmax(global_absmax(shards), axis_name)
        safe = safe_divisor(scale)
        mean_keys = _leaf_keys(shards, mean_key)

        def q_shard(shard, g_full, subkey):
            noise = None
            if subkey is not None:
                # Draw at the FULL leaf shape (same counters as the
                # replicated path's draw), then slice this replica's chunk.
                noise = local_chunk(
                    jax.random.uniform(subkey, g_full.shape),
                    axis_size,
                    axis_name,
                )
            scaled = shard.astype(jnp.float32) / safe * levels
            q = snap_to_lattice(scaled, levels, noise=noise).astype(out_dtype)
            # Single runtime-scalar multiply, exactly quantize.decode's
            # formula (constant-divisor division is not rewrite-stable
            # across programs — see decode's docstring).
            return q.astype(jnp.float32) * (scale / levels)

        shards = lax.optimization_barrier(
            jax.tree.map(q_shard, shards, grads, mean_keys)
        )
    return shards
