"""Pallas TPU kernel for the gradient codec: fused quantize→dequantize.

One VMEM pass per leaf: read the gradient block, snap it to the codec
lattice (nearest, or stochastic rounding driven by the TPU core's hardware
PRNG instead of XLA's ALU-heavy threefry), and write the dequantized value —
no intermediate int8/fp16 tensor ever reaches HBM.

Honest placement (docs/PERF.md): traces show XLA already fuses the simulate
codec into ~bandwidth-bound loops (≈0.07 ms per 8M elements nearest,
+0.16 ms for threefry noise), so this kernel is an opt-in backend
(``CompressionConfig.codec_backend='pallas'``), not a default — it exists as
the framework's template for TPU kernels (grid/block layout, SMEM scalars,
hardware PRNG, interpret-mode testing) and to cap the codec's cost on models
whose gradient volume dwarfs the flagship's 7.8M parameters.

Layout: each leaf is raveled and padded to a [rows, 1024] view — the lane
dimension a multiple of 128 so the VPU runs full-width, rows a multiple of 8
sublanes.  The whole-model scale stays an XLA reduction (it crosses leaves);
it enters the kernel as a (1, 1) SMEM scalar.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddlpc_tpu.config import CompressionConfig
from ddlpc_tpu.ops.quantize import (
    global_absmax,
    levels_for,
    rounding_key,
    safe_divisor,
)

LANES = 1024  # 8 × 128-lane vregs per row
_BLOCK_ROWS = 256  # 256×1024 fp32 = 1 MiB per VMEM block


def default_interpret() -> bool:
    """Run the kernel via the Pallas interpreter on the CPU platform only
    (the test meshes).  Every other platform compiles it for real — a
    backend Mosaic cannot target fails there, loudly, instead of timing the
    interpreter under the kernel's name."""
    return jax.default_backend() == "cpu"


def _fq_kernel(scale_ref, seed_ref, x_ref, out_ref, *, levels: float, stochastic: bool):
    x = x_ref[...].astype(jnp.float32)
    scaled = x / scale_ref[0, 0] * levels
    if stochastic:
        # Decorrelate blocks: one seed per pallas_call + the grid position.
        pltpu.prng_seed(seed_ref[0, 0] + pl.program_id(0))
        # Unsigned shift (a signed shift would smear the sign bit into the
        # noise, u in (-0.5, 1)), then back to int32 for the float cast —
        # after >> 8 the value fits in 24 bits, so int32 is exact, and
        # Mosaic has no uint32→f32 cast.  u is uniform in [0, 1).
        bits = pltpu.bitcast(pltpu.prng_random_bits(scaled.shape), jnp.uint32)
        u = pltpu.bitcast(bits >> 8, jnp.int32).astype(jnp.float32) * (
            1.0 / (1 << 24)
        )
        snapped = jnp.floor(scaled + u)
    else:
        snapped = jnp.round(scaled)
    snapped = jnp.clip(snapped, -levels, levels)
    out_ref[...] = snapped / levels * scale_ref[0, 0]


def _fq_kernel_hostnoise(scale_ref, x_ref, u_ref, out_ref, *, levels: float):
    """Stochastic variant taking precomputed U[0,1) noise as an input — the
    interpret-mode fallback (the Pallas interpreter has no lowering for the
    TPU PRNG primitives), sharing the snap/clip/dequant logic exactly."""
    x = x_ref[...].astype(jnp.float32)
    scaled = x / scale_ref[0, 0] * levels
    snapped = jnp.clip(jnp.floor(scaled + u_ref[...]), -levels, levels)
    out_ref[...] = snapped / levels * scale_ref[0, 0]


def _fq_leaf(
    x: jax.Array,
    safe_scale: jax.Array,
    levels: float,
    seed: jax.Array,
    interpret: bool,
) -> jax.Array:
    """Fused quantize→dequantize of one leaf (any shape/dtype)."""
    flat = x.ravel()
    n = flat.shape[0]
    rows = -(-n // LANES)
    block_rows = min(_BLOCK_ROWS, -(-rows // 8) * 8)
    # Pad rows to a whole number of blocks so every grid step is full.
    rows_padded = -(-rows // block_rows) * block_rows
    padded = jnp.pad(flat, (0, rows_padded * LANES - n)).reshape(rows_padded, LANES)
    grid = (rows_padded // block_rows,)
    block = lambda: pl.BlockSpec(  # noqa: E731 — two identical specs
        (block_rows, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    scale_arg = safe_scale.reshape(1, 1).astype(jnp.float32)
    if seed is not None and interpret:
        # Interpreter has no TPU PRNG lowering: draw the noise outside and
        # run the same snap logic (tests exercise exactly the shipped math).
        u = jax.random.uniform(jax.random.key(jnp.abs(seed)), padded.shape)
        out = pl.pallas_call(
            functools.partial(_fq_kernel_hostnoise, levels=levels),
            # fp32 out, whatever the input dtype — matching the XLA decode()
            # (a bf16 output would round the lattice a second time and feed
            # bf16 into the pmean accumulation).
            out_shape=jax.ShapeDtypeStruct(padded.shape, jnp.float32),
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                block(),
                block(),
            ],
            out_specs=block(),
            interpret=True,
        )(scale_arg, padded, u)
    else:
        out = pl.pallas_call(
            functools.partial(
                _fq_kernel, levels=levels, stochastic=seed is not None
            ),
            out_shape=jax.ShapeDtypeStruct(padded.shape, jnp.float32),
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # scale (1,1)
                pl.BlockSpec(memory_space=pltpu.SMEM),  # seed (1,1)
                block(),
            ],
            out_specs=block(),
            interpret=interpret,
        )(
            scale_arg,
            (jnp.zeros((1, 1), jnp.int32) if seed is None else seed.reshape(1, 1)),
            padded,
        )
    return out.reshape(-1)[:n].reshape(x.shape)


def _encode_kernel(
    scale_ref, seed_ref, x_ref, out_ref, *, levels: float, stochastic: bool
):
    """Encode-to-wire variant of ``_fq_kernel``: same snap/clip against the
    (caller-shared) scale, but the OUTPUT is the narrow lattice itself —
    int8/int16/fp16 for a fused quantized collective — with no dequantize
    multiply (that happens after the collective, on 1/N or summed data)."""
    x = x_ref[...].astype(jnp.float32)
    scaled = x / scale_ref[0, 0] * levels
    if stochastic:
        pltpu.prng_seed(seed_ref[0, 0] + pl.program_id(0))
        bits = pltpu.bitcast(pltpu.prng_random_bits(scaled.shape), jnp.uint32)
        u = pltpu.bitcast(bits >> 8, jnp.int32).astype(jnp.float32) * (
            1.0 / (1 << 24)
        )
        snapped = jnp.floor(scaled + u)
    else:
        snapped = jnp.round(scaled)
    out_ref[...] = jnp.clip(snapped, -levels, levels).astype(out_ref.dtype)


def _encode_kernel_hostnoise(scale_ref, x_ref, u_ref, out_ref, *, levels: float):
    x = x_ref[...].astype(jnp.float32)
    scaled = x / scale_ref[0, 0] * levels
    snapped = jnp.clip(jnp.floor(scaled + u_ref[...]), -levels, levels)
    out_ref[...] = snapped.astype(out_ref.dtype)


def _decode_kernel(inv_ref, q_ref, out_ref):
    """Dequantize wire values: one multiply by the runtime scalar
    ``inv = scale / (levels · axis_size)`` — a single rounding, so it is
    bit-identical to the XLA spelling of the same multiply."""
    out_ref[...] = q_ref[...].astype(jnp.float32) * inv_ref[0, 0]


def _sublane_multiple(dtype) -> int:
    """Minimum second-to-last tile dimension per dtype (TPU tiling): 8
    sublanes for 32-bit, 16 for 16-bit, 32 for 8-bit operands."""
    itemsize = jnp.dtype(dtype).itemsize
    return {1: 32, 2: 16}.get(itemsize, 8)


def _wire_block_layout(x: jax.Array, wire_dtype):
    """Ravel/pad ``x`` to [rows, LANES] with rows a whole number of blocks
    sized for the NARROW dtype's tile multiple (int8 tiles are (32, 128),
    fp16 (16, 128) — the fp32 input trivially satisfies both)."""
    flat = x.ravel()
    n = flat.shape[0]
    mult = _sublane_multiple(wire_dtype)
    rows = -(-n // LANES)
    block_rows = min(_BLOCK_ROWS, -(-rows // mult) * mult)
    rows_padded = -(-rows // block_rows) * block_rows
    padded = jnp.pad(flat, (0, rows_padded * LANES - n)).reshape(
        rows_padded, LANES
    )
    return padded, n, rows_padded // block_rows, block_rows


def _encode_leaf(
    x: jax.Array,
    safe_scale: jax.Array,
    levels: float,
    seed: Optional[jax.Array],
    wire_dtype,
    interpret: bool,
) -> jax.Array:
    padded, n, n_blocks, block_rows = _wire_block_layout(x, wire_dtype)
    block = lambda: pl.BlockSpec(  # noqa: E731 — identical specs
        (block_rows, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    scale_arg = safe_scale.reshape(1, 1).astype(jnp.float32)
    if seed is not None and interpret:
        u = jax.random.uniform(jax.random.key(jnp.abs(seed)), padded.shape)
        out = pl.pallas_call(
            functools.partial(_encode_kernel_hostnoise, levels=levels),
            out_shape=jax.ShapeDtypeStruct(padded.shape, wire_dtype),
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                block(),
                block(),
            ],
            out_specs=block(),
            interpret=True,
        )(scale_arg, padded, u)
    else:
        out = pl.pallas_call(
            functools.partial(
                _encode_kernel, levels=levels, stochastic=seed is not None
            ),
            out_shape=jax.ShapeDtypeStruct(padded.shape, wire_dtype),
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # scale (1,1)
                pl.BlockSpec(memory_space=pltpu.SMEM),  # seed (1,1)
                block(),
            ],
            out_specs=block(),
            interpret=interpret,
        )(
            scale_arg,
            (jnp.zeros((1, 1), jnp.int32) if seed is None else seed.reshape(1, 1)),
            padded,
        )
    return out.reshape(-1)[:n].reshape(x.shape)


def encode_to_wire_pallas(
    tree,
    cfg: CompressionConfig,
    safe_scale: jax.Array,
    wire_dtype,
    key: Optional[jax.Array] = None,
    interpret: bool = False,
):
    """Encode a gradient pytree to its WIRE dtype: the lattice values
    themselves (int8/int16/fp16), quantized against a caller-supplied
    scale — the pmax-shared global scale of the fused collective path
    (grad_sync._fenced_wire_encode) — with no dequantize pass.  Nearest
    rounding lands on integer lattice points, so the cast output is
    bit-identical to the XLA ``quantize_with_scale(...).astype(wire)``
    spelling (unlike fake-quantize, there is no dequant multiply to
    FMA-contract differently).  Seeds mirror ``fake_quantize_pallas``."""
    levels = float(levels_for(cfg))
    key = rounding_key(cfg, key)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if key is None:
        seeds = [None] * len(leaves)
    else:
        seeds = list(
            jax.random.randint(
                key, (len(leaves),), jnp.iinfo(jnp.int32).min, jnp.iinfo(jnp.int32).max
            )
        )
    out = [
        _encode_leaf(l, safe_scale, levels, s, wire_dtype, interpret)
        for l, s in zip(leaves, seeds)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def decode_from_wire_pallas(
    tree, inv_step: jax.Array, interpret: bool = False
):
    """Dequantize summed wire values: ``q · inv_step`` per element, where
    ``inv_step = scale / (levels · axis_size)`` folds the mean division
    into the one runtime-scalar multiply (quantize.decode's convention).
    Deliberately NOT fenced — the fused path leaves the decode free to
    fuse into the collective's consumer (grad_sync._wire_decode)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    inv_arg = inv_step.reshape(1, 1).astype(jnp.float32)
    out = []
    for q in leaves:
        padded, n, n_blocks, block_rows = _wire_block_layout(q, q.dtype)
        block = lambda: pl.BlockSpec(  # noqa: E731 — identical specs
            (block_rows, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
        )
        dec = pl.pallas_call(
            _decode_kernel,
            out_shape=jax.ShapeDtypeStruct(padded.shape, jnp.float32),
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), block()],
            out_specs=block(),
            interpret=interpret,
        )(inv_arg, padded)
        out.append(dec.reshape(-1)[:n].reshape(q.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def fake_quantize_pallas(
    tree,
    cfg: CompressionConfig,
    key: Optional[jax.Array] = None,
    interpret: bool = False,
):
    """Drop-in equivalent of ``ops.quantize.fake_quantize`` running the
    per-element work as one fused Pallas pass per leaf.

    Nearest rounding is bit-identical to the XLA path.  Stochastic rounding
    draws from the TPU hardware PRNG (per-leaf seed derived from ``key``),
    so it matches the XLA path in distribution — unbiased, same error bound
    — but not bit-for-bit.  ``interpret=True`` runs the kernel in the Pallas
    interpreter (any backend; used by the CPU test suite).
    """
    if cfg.mode == "none":
        return tree
    key = rounding_key(cfg, key)
    levels = float(levels_for(cfg))
    scale = global_absmax(tree)
    safe = safe_divisor(scale)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if key is None:
        seeds = [None] * len(leaves)
    else:
        # One int32 seed per leaf from the caller's key, so leaves draw
        # independent noise (mirrors _leaf_keys in the XLA path).
        seeds = list(
            jax.random.randint(
                key, (len(leaves),), jnp.iinfo(jnp.int32).min, jnp.iinfo(jnp.int32).max
            )
        )
    out = [
        _fq_leaf(l, safe, levels, s, interpret) for l, s in zip(leaves, seeds)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)
