"""Device-mesh construction and multi-host bootstrap.

Replaces the reference's entire cluster layer — hand-rolled TCP star with
hostname→ID→IP tables, sequential accept loop and blocking point-to-point
broadcast/gather (кластер.py:172-252, 209-220) — with a
``jax.sharding.Mesh`` over which XLA emits collectives on ICI (intra-slice)
and DCN (inter-host).  Roles disappear: every process runs the same SPMD
program; there is no server.

Axes:
- ``data``  — data parallelism: batch sharded, params replicated, gradients
  all-reduced (the reference's only strategy, SURVEY §2 parallelism table).
- ``space`` — spatial sharding of the image H dimension with halo exchange,
  the conv-segmentation analog of sequence/context parallelism (for tiles too
  large for one chip's HBM).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddlpc_tpu.config import ParallelConfig


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bootstrap.  MUST run before any other JAX call.

    The reference bootstraps by hostname lookup into a hard-coded IP table and
    a TCP accept loop (кластер.py:176-206,226-252).  Here a single call wires
    every host into one JAX runtime.  Arguments fall back to the
    ``COORDINATOR_ADDRESS`` / ``NUM_PROCESSES`` / ``PROCESS_ID`` environment
    variables; on TPU pods / Slurm / OMPI, JAX auto-detects everything and a
    bare call suffices.  No-op when neither arguments nor environment request
    a multi-process run, so single-process users may call it unconditionally.
    """
    # is_initialized() reads the distributed client's state only; it does
    # not initialize the XLA backend (jax.process_count() would, after which
    # jax.distributed.initialize() refuses to run).
    if jax.distributed.is_initialized():
        return
    coordinator_address = coordinator_address or os.environ.get(
        "COORDINATOR_ADDRESS"
    ) or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("NUM_PROCESSES"):
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and os.environ.get("PROCESS_ID"):
        process_id = int(os.environ["PROCESS_ID"])
    if coordinator_address or (num_processes or 0) > 1:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )


def make_mesh(
    cfg: ParallelConfig, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Build a (data, space) mesh from available devices.

    ``data_axis_size=-1`` absorbs all devices not claimed by the space axis.
    Device order follows ``jax.devices()`` so the data axis maps to the
    outermost (DCN, then ICI) links and space stays within a host — the
    layout that keeps halo exchange on fast links.
    """
    devices = list(devices if devices is not None else jax.devices())
    space = max(1, cfg.space_axis_size)
    if len(devices) % space:
        raise ValueError(
            f"space_axis_size={space} does not divide device count {len(devices)}"
        )
    data = cfg.data_axis_size
    if data == -1:
        data = len(devices) // space
    if data * space > len(devices):
        raise ValueError(
            f"mesh {data}×{space} (data×space) needs {data * space} devices, only "
            f"{len(devices)} available"
        )
    if data * space < len(devices):
        import warnings

        warnings.warn(
            f"mesh {data}×{space} uses {data * space} of {len(devices)} devices; "
            f"the rest stay idle",
            stacklevel=2,
        )
        devices = devices[: data * space]
    grid = np.array(devices).reshape(data, space)
    return Mesh(grid, (cfg.data_axis_name, cfg.space_axis_name))


def batch_sharding(mesh: Mesh, cfg: ParallelConfig) -> NamedSharding:
    """Sharding for a [B, H, W, C] batch: B over data, H over space."""
    return NamedSharding(mesh, P(cfg.data_axis_name, cfg.space_axis_name))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
