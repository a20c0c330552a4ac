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
- ``pipe``  — MPMD pipeline stages (arxiv 2412.14374): each index along the
  axis owns one contiguous group of model blocks; stages run as separate
  per-stage programs on disjoint (data, space) sub-meshes
  (:func:`stage_meshes`) driven by the host round-robin schedule in
  ``parallel/pipeline.py``.  Absent (the mesh stays 2-axis, bit-identical to
  pre-pipeline revisions) unless ``pipeline_stages > 1``.
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
    pipe = max(1, getattr(cfg, "pipeline_stages", 1))
    if len(devices) % (space * pipe):
        raise ValueError(
            f"space_axis_size={space} × pipeline_stages={pipe} does not "
            f"divide device count {len(devices)}"
        )
    data = cfg.data_axis_size
    if data == -1:
        data = len(devices) // (space * pipe)
    if pipe * data * space > len(devices):
        raise ValueError(
            f"mesh {pipe}×{data}×{space} (pipe×data×space) needs "
            f"{pipe * data * space} devices, only {len(devices)} available"
        )
    if pipe * data * space < len(devices):
        import warnings

        warnings.warn(
            f"mesh {pipe}×{data}×{space} uses {pipe * data * space} of "
            f"{len(devices)} devices; the rest stay idle",
            stacklevel=2,
        )
        devices = devices[: pipe * data * space]
    if pipe > 1:
        # pipe is OUTERMOST: a stage is a contiguous run of jax.devices(),
        # so the data/space collectives inside a stage stay on the fast
        # links and only the thin activation carry crosses stages — the
        # MPMD layout of arxiv 2412.14374.
        grid = np.array(devices).reshape(pipe, data, space)
        return Mesh(
            grid,
            (cfg.pipe_axis_name, cfg.data_axis_name, cfg.space_axis_name),
        )
    grid = np.array(devices).reshape(data, space)
    return Mesh(grid, (cfg.data_axis_name, cfg.space_axis_name))


def stage_meshes(mesh: Mesh, pipe_axis: str = "pipe") -> list:
    """Slice a (pipe, data, space) mesh into its per-stage (data, space)
    sub-meshes — one ``Mesh`` per index along the pipe axis, over disjoint
    device groups, axis names preserved.  The per-stage programs
    (``parallel/pipeline.py``) compile against these, so every in-stage
    collective (gradient wire, ZeRO chunk traffic, halo exchange) is scoped
    to the stage group.  A mesh without a pipe axis is its own single
    stage."""
    if pipe_axis not in mesh.axis_names:
        return [mesh]
    idx = mesh.axis_names.index(pipe_axis)
    if idx != 0:
        raise ValueError(
            f"pipe axis {pipe_axis!r} must be outermost, got mesh axes "
            f"{mesh.axis_names}"
        )
    rest = tuple(n for n in mesh.axis_names if n != pipe_axis)
    return [
        Mesh(mesh.devices[s], rest) for s in range(mesh.shape[pipe_axis])
    ]


def batch_sharding(mesh: Mesh, cfg: ParallelConfig) -> NamedSharding:
    """Sharding for a [B, H, W, C] batch: B over data, H over space."""
    return NamedSharding(mesh, P(cfg.data_axis_name, cfg.space_axis_name))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
