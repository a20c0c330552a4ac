"""Communication accounting: exact logical wire bytes + a fenced comm probe.

The ROADMAP's biggest open perf item — fused quantized collectives with
comm/compute overlap — cannot be judged without first knowing (a) how many
bytes each codec actually puts on the wire per step and (b) what fraction
of the step is communication.  This module owns both:

- **Byte accounting** (:func:`comm_plan`, :class:`CommAccountant`) —
  closed-form per-step logical payload bytes for every collective the
  step variants issue (``parallel/grad_sync.py``'s ``sync_gradients`` /
  ``sync_gradients_scatter``, ``parallel/compressed_allreduce.py``'s
  ring), pre-codec, post-codec and on-the-wire, published as
  ``ddlpc_comm_bytes_total{collective,codec,stage}`` counters and a
  ``ddlpc_comm_compression_ratio`` gauge.  "Logical" means the tensor
  bytes a replica contributes to the collective — what a compressed wire
  format carries; after the fused rewrite the simulate transport's
  collective operand really IS that narrow dtype wherever the lattice
  sums fit it exactly (``grad_sync.simulate_wire_dtype`` — the ``wire``
  stage rows), fp32 only on the fallback paths; the ring transport's
  numbers are its REAL per-hop wire bytes (``ring_wire_report``).
  Exactness is the contract: int8 → ``n·1 + 4`` (one global fp32 scale),
  float16 → ``n·2 + 4``, none → ``n·4`` (test-pinned against closed
  form).  A singleton data axis has no communication and counts zero.

- **Fenced comm-time probe** (:func:`make_comm_probe`) — a compiled
  program running ONLY the gradient sync (the training step's exact
  ``sync_gradients``/``sync_gradients_scatter`` call, codec fences and
  all) on a parameter-shaped dummy tree.  The trainer samples it on the
  existing ``trace_sync_every_steps`` cadence; the measured seconds yield
  ``ddlpc_comm_fraction`` (comm seconds / step seconds) and
  ``ddlpc_comm_overlap_headroom_s`` — the step-time saving a perfect
  backward/sync overlap could claim, ``min(t_comm, t_step − t_comm)`` —
  which is the committed baseline the future overlap PR is judged
  against (docs/PERF.md "Accounting").

jax stays a lazy import (probe construction only); the byte math is pure.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

# Wire itemsize per codec mode for the simulate transport's logical
# payload (the ring transport computes its own hop dtype — see
# compressed_allreduce.wire_dtype).
CODEC_ITEMSIZE = {"none": 4, "int8": 1, "float16": 2}
# One global (whole-model) fp32 absmax scale per quantized payload
# (ops/quantize.py:Encoded).
SCALE_BYTES = 4


def tree_elements(tree) -> int:
    """Total element count of a pytree of arrays/ShapeDtypeStructs."""
    import jax
    import numpy as np

    return int(
        sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))
    )


def codec_payload_bytes(n_elements: int, mode: str, n_scales: int = 1) -> int:
    """Logical payload bytes for ``n_elements`` after the codec: the wire
    dtype's bytes plus the global scale scalar(s) (quantizing modes only —
    bucketed syncs carry one fp32 scale per bucket)."""
    if mode not in CODEC_ITEMSIZE:
        raise ValueError(f"unknown compression mode {mode!r}")
    nbytes = n_elements * CODEC_ITEMSIZE[mode]
    if mode != "none":
        nbytes += SCALE_BYTES * n_scales
    return nbytes


def simulate_wire_row(compression, axis_size: int):
    """(hlo_dtype_name, itemsize) of the simulate transport's grad
    collective operand — the ACTUAL dtype on the wire after the fused
    rewrite (grad_sync.simulate_wire_dtype), distinct from the codec's
    declared loss model: 's8'/'s16'/'f16' when the lattice sums fit the
    narrow dtype, 'f32' otherwise (mode='none', quantize_local=False, or
    an axis too large for exact narrow sums)."""
    from ddlpc_tpu.parallel.grad_sync import simulate_wire_dtype

    wire = simulate_wire_dtype(axis_size, compression)
    if wire is None:
        return "f32", 4
    import numpy as np

    dt = np.dtype(wire)
    name = {"int8": "s8", "int16": "s16", "float16": "f16"}[dt.name]
    return name, dt.itemsize


def comm_plan(
    n_grad_elements: int,
    n_param_elements: int,
    compression,
    axis_size: int,
    variant: str,
    n_buckets: int = 1,
) -> List[Dict[str, object]]:
    """Per-optimizer-step collective rows for one step variant.

    ``variant`` ∈ ``allreduce`` (replicated shard_map step),
    ``zero1`` (full-mean all-reduce + params all-gather publish: 3·P),
    ``scatter`` (ZeRO-2: reduce-scatter grads + all-gather params tail
    publish: 2·P — the level that stops all-gathering what it just
    scattered), ``zero3`` (reduce-scatter grads + the gather-on-demand
    params all-gather at step HEAD: same 2·P volume as scatter, the
    all-gather just moved from tail publish to forward prologue),
    ``ring`` (compressed ppermute transport), ``gspmd`` (partitioner-
    inserted all-reduce; no per-replica quantize stage exists there, so
    the wire payload is fp32 — train_step.py documents why).

    Each row: ``collective``, ``codec`` (the mode the wire payload is in),
    ``bytes_pre`` (fp32 bytes entering the codec), ``bytes_post`` (the
    DECLARED loss-model payload leaving it — the historical convention,
    kept stable so old streams stay comparable), plus ``wire_dtype`` and
    ``bytes_wire`` — the ACTUAL HLO collective operand bytes after the
    fused rewrite: the narrow lattice payload plus one fp32 scale pmax
    per bucket where the fused path engages, fp32 otherwise (chunk
    padding depends on leaf shapes and is accounted exactly by the
    program auditor, not here).  ``n_buckets`` is the bucket count of
    ``CompressionConfig.bucket_mb`` (grad_sync.grad_bucket_groups): each
    bucket carries its own scale.  Singleton meshes communicate nothing
    → empty plan.
    """
    if axis_size <= 1:
        return []
    mode = compression.mode
    fp32 = n_grad_elements * 4
    if variant == "allreduce":
        # quantize_local is the codec stage ahead of the wire; without it
        # (or with mode none) the payload stays fp32.
        wire_mode = mode if (mode != "none" and compression.quantize_local) else "none"
        wire_name, wire_item = simulate_wire_row(compression, axis_size)
        scale_bytes = 0 if wire_name == "f32" else SCALE_BYTES * n_buckets
        return [
            {
                "collective": "all_reduce",
                "codec": wire_mode,
                "bytes_pre": fp32,
                "bytes_post": codec_payload_bytes(
                    n_grad_elements, wire_mode, n_buckets
                ),
                "wire_dtype": wire_name,
                "bytes_wire": n_grad_elements * wire_item + scale_bytes,
            }
        ]
    if variant == "zero1":
        # Full-mean all-reduce (the codec wire, same as 'allreduce') plus
        # the chunked update's fresh-params all-gather publish: 3·P.
        wire_mode = mode if (mode != "none" and compression.quantize_local) else "none"
        wire_name, wire_item = simulate_wire_row(compression, axis_size)
        scale_bytes = 0 if wire_name == "f32" else SCALE_BYTES * n_buckets
        return [
            {
                "collective": "all_reduce",
                "codec": wire_mode,
                "bytes_pre": fp32,
                "bytes_post": codec_payload_bytes(
                    n_grad_elements, wire_mode, n_buckets
                ),
                "wire_dtype": wire_name,
                "bytes_wire": n_grad_elements * wire_item + scale_bytes,
            },
            {
                "collective": "all_gather",
                "codec": "none",
                "bytes_pre": n_param_elements * 4,
                "bytes_post": n_param_elements * 4,
                "wire_dtype": "f32",
                "bytes_wire": n_param_elements * 4,
            },
        ]
    if variant in ("scatter", "zero3", "zero3_update"):
        wire_mode = mode if (mode != "none" and compression.quantize_local) else "none"
        wire_name, wire_item = simulate_wire_row(compression, axis_size)
        scale_bytes = 0 if wire_name == "f32" else SCALE_BYTES * n_buckets
        rows = [
            {
                "collective": "reduce_scatter",
                "codec": wire_mode,
                "bytes_pre": fp32,
                "bytes_post": codec_payload_bytes(
                    n_grad_elements, wire_mode, n_buckets
                ),
                "wire_dtype": wire_name,
                "bytes_wire": n_grad_elements * wire_item + scale_bytes,
            },
            # ZeRO-2: the fresh-params tail publish.  ZeRO-3: the
            # gather-on-demand at step head (params persist chunked, the
            # forward gathers them per leaf).  Same volume either way —
            # uncompressed by construction (params, not grads).
            {
                "collective": "all_gather",
                "codec": "none",
                "bytes_pre": n_param_elements * 4,
                "bytes_post": n_param_elements * 4,
                "wire_dtype": "f32",
                "bytes_wire": n_param_elements * 4,
            },
        ]
        # 'zero3_update' is the auditor's update-program slice of zero3:
        # the step-head params gather belongs to the TRAIN program, so
        # the bare update moves only the reduce-scatter.
        return rows[:1] if variant == "zero3_update" else rows
    if variant == "ring":
        if mode == "none":
            # The ring falls back to an exact pmean for mode='none'.
            return [
                {
                    "collective": "ring_all_reduce",
                    "codec": "none",
                    "bytes_pre": fp32,
                    "bytes_post": fp32,
                    "wire_dtype": "f32",
                    "bytes_wire": fp32,
                }
            ]
        import numpy as np

        from ddlpc_tpu.parallel.compressed_allreduce import (
            ring_wire_report,
            wire_dtype as ring_wire_dtype,
        )

        rep = ring_wire_report(n_grad_elements, axis_size, compression)
        levels = (
            compression.int8_levels if mode == "int8" else compression.fp16_levels
        )
        ring_name = {"int8": "s8", "int16": "s16"}[
            np.dtype(ring_wire_dtype(axis_size, levels)).name
        ]
        return [
            {
                "collective": "ring_all_reduce",
                "codec": mode,
                # The ring's REAL per-replica hop bytes, fp32 ring vs
                # quantized ring — exact by construction (dtype × chunk ×
                # hops), not the logical-payload convention above.
                "bytes_pre": rep["fp32_bytes_per_replica"],
                "bytes_post": rep["wire_bytes_per_replica"],
                # The ring always had the quantized dtype on the wire.
                "wire_dtype": ring_name,
                "bytes_wire": rep["wire_bytes_per_replica"],
            }
        ]
    if variant == "gspmd":
        return [
            {
                "collective": "all_reduce",
                "codec": "none",
                "bytes_pre": fp32,
                "bytes_post": fp32,
                "wire_dtype": "f32",
                "bytes_wire": fp32,
            }
        ]
    raise ValueError(f"unknown comm plan variant {variant!r}")


class CommAccountant:
    """Registry-backed per-step communication accounting.

    ``on_step`` (called once per optimizer step from the trainer loop —
    a handful of counter increments) accumulates the plan's byte rows
    into ``ddlpc_comm_bytes_total``; ``record_probe`` stores a sampled
    fenced comm-time measurement; ``publish`` refreshes the derived
    gauges and returns the flat ``kind="comm"`` JSONL record.
    """

    def __init__(self, registry, plan: List[Dict[str, object]], variant: str):
        self.plan = list(plan)
        self.variant = variant
        self._lock = threading.Lock()
        self._steps = 0
        self._probe_s: Optional[float] = None
        self._bytes = registry.counter(
            "ddlpc_comm_bytes_total",
            "Logical collective payload bytes per replica (pre_codec = "
            "fp32 entering the codec, post_codec = the DECLARED loss-"
            "model payload leaving it, wire = actual HLO collective "
            "operand bytes — narrow lattice dtype where the fused path "
            "engages; ring rows are real per-hop wire bytes).",
            labelnames=("collective", "codec", "stage"),
        )
        self._ratio = registry.gauge(
            "ddlpc_comm_compression_ratio",
            "Measured pre/post codec byte ratio per collective.",
            labelnames=("collective",),
        )
        self._g_comm_s = registry.gauge(
            "ddlpc_comm_seconds_per_step",
            "Sampled fenced gradient-sync seconds (comm-only program).",
        )
        self._g_frac = registry.gauge(
            "ddlpc_comm_fraction",
            "Sampled comm seconds over mean optimizer-step seconds.",
        )
        self._g_headroom = registry.gauge(
            "ddlpc_comm_overlap_headroom_s",
            "Per-step seconds a perfect comm/compute overlap could save: "
            "min(t_comm, t_step - t_comm).",
        )
        for row in self.plan:
            self._ratio.set(
                row["bytes_pre"] / max(row["bytes_post"], 1),
                collective=row["collective"],
            )

    def on_step(self, n: int = 1) -> None:
        for row in self.plan:
            self._bytes.inc(
                row["bytes_pre"] * n,
                collective=row["collective"],
                codec=row["codec"],
                stage="pre_codec",
            )
            self._bytes.inc(
                row["bytes_post"] * n,
                collective=row["collective"],
                codec=row["codec"],
                stage="post_codec",
            )
            self._bytes.inc(
                row["bytes_wire"] * n,
                collective=row["collective"],
                codec=row["codec"],
                stage="wire",
            )
        with self._lock:
            self._steps += n

    def record_probe(self, comm_seconds: float) -> None:
        with self._lock:
            self._probe_s = float(comm_seconds)
        self._g_comm_s.set(float(comm_seconds))

    def publish(self, step_time_s: Optional[float] = None) -> Dict[str, object]:
        with self._lock:
            steps = self._steps
            probe_s = self._probe_s
        rec: Dict[str, object] = {"kind": "comm", "variant": self.variant,
                                  "steps": steps}
        for row in self.plan:
            name = str(row["collective"])
            rec[f"{name}_bytes_pre_per_step"] = row["bytes_pre"]
            rec[f"{name}_bytes_post_per_step"] = row["bytes_post"]
            rec[f"{name}_codec"] = row["codec"]
            rec[f"{name}_wire_dtype"] = row["wire_dtype"]
            rec[f"{name}_bytes_wire_per_step"] = row["bytes_wire"]
            rec[f"{name}_compression_ratio"] = round(
                row["bytes_pre"] / max(row["bytes_post"], 1), 4
            )
        if probe_s is not None:
            rec["comm_s_per_step"] = round(probe_s, 6)
            if step_time_s and step_time_s > 0:
                frac = min(probe_s / step_time_s, 1.0)
                headroom = max(min(probe_s, step_time_s - probe_s), 0.0)
                self._g_frac.set(frac)
                self._g_headroom.set(headroom)
                rec["comm_fraction"] = round(frac, 4)
                rec["overlap_headroom_s"] = round(headroom, 6)
                rec["step_time_s"] = round(float(step_time_s), 6)
        return rec


def make_comm_probe(
    mesh,
    compression,
    params,
    data_axis: str = "data",
    scatter: bool = False,
    seed: int = 0,
):
    """A callable measuring the fenced gradient-sync seconds in isolation.

    Compiles the training step's EXACT sync call (``sync_gradients`` or,
    under the ZeRO-1 layout, ``sync_gradients_scatter`` — codec fences
    included) over a parameter-shaped dummy gradient tree, replicated the
    way the step sees it.  The first call warms up (compile + one run);
    every call returns the wall seconds of one synchronized execution.
    Runs nothing at construction time.
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddlpc_tpu.parallel.grad_sync import (
        sync_gradients,
        sync_gradients_scatter,
    )

    axis_size = mesh.shape[data_axis]
    use_scatter = bool(scatter) and axis_size > 1
    stochastic = (
        compression.mode != "none" and compression.rounding == "stochastic"
    )

    def body(grads):
        # Static-seed key built inside the program, the _rounding_rng
        # pattern (train_step.py): every probe run rounds with the same
        # noise — right for timing the codec's real threefry cost.
        key = jax.random.key(seed) if stochastic else None
        if use_scatter:
            return sync_gradients_scatter(
                grads, data_axis, compression, axis_size=axis_size, key=key
            )
        return sync_gradients(
            grads, data_axis, compression, axis_size=axis_size, key=key
        )

    out_spec = P(data_axis) if use_scatter else P()
    fn = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(),),
            out_specs=out_spec,
            check_vma=False,
        )
    )

    state = {"warmed": False}

    def probe() -> float:
        # The dummy gradient tree is rebuilt per probe and dropped right
        # after: holding it between once-per-epoch samples would pin a
        # full grads-sized fp32 buffer per device for the whole run —
        # exactly the HBM the accounting exists to watch.  The jit cache
        # keeps the compile across probes (shapes are stable).
        rng = np.random.default_rng(0)
        grads = jax.tree.map(
            lambda p: jax.device_put(
                rng.standard_normal(p.shape).astype(np.float32) * 1e-3,
                NamedSharding(mesh, P()),
            ),
            params,
        )
        if not state["warmed"]:
            jax.block_until_ready(fn(grads))  # compile + warm
            state["warmed"] = True
        t0 = time.perf_counter()
        jax.block_until_ready(fn(grads))
        return time.perf_counter() - t0

    return probe
