"""Live per-device HBM accounting from shape × committed sharding.

The accounting ``scripts/hbm_report.py`` used to carry privately, hoisted
into the package (the ``obs/xplane.py`` precedent: one implementation for
the CLI and the live hooks): every leaf's per-device bytes come exactly
from ``sharding.shard_shape(global_shape) × itemsize`` — decided at
partitioning time, identically on every backend — so the numbers are
backend-independent and free to compute.

:func:`publish_hbm_gauges` turns a placed ``TrainState`` into
``ddlpc_hbm_bytes{kind=params|grads|grads_accum|opt_state|batch_stats}``
per-device gauges on the training ``/metrics`` endpoint.  Two gradient
kinds, because the ZeRO ladder splits the gradient's lifetime in two:

- ``grads`` — the OPTIMIZER-BOUNDARY gradient, what persists from the
  sync to the update.  Full fp32 under off/zero1 (the full mean), a
  1/N ``[1, K]`` chunk per device under zero2/zero3 (the reduce-scatter
  output IS the update input — docs/SHARDING.md).  This is the kind the
  1/N acceptance gauge watches.
- ``grads_accum`` — the full fp32 accumulator every layout materializes
  per replica between the backward pass and the sync (the scatter runs
  AFTER accumulation), counted at ``Σ param_elements × 4`` regardless of
  layout.  Honest ceiling: zero2/zero3 shrink the persistent gradient,
  not the transient backward peak.

:func:`publish_hbm_gauges` also publishes
``ddlpc_hbm_replicated_by_rule_bytes`` — the bytes the partition-rule
engine DECIDED to keep replicated (uneven GSPMD dims,
``partition.Decision.reason == 'replicated-by-rule'``) — so the PR 13
sharding contract budgets the fallback instead of special-casing it.

jax is only needed for the tree walk; imported lazily like the rest of
``obs/``.
"""

from __future__ import annotations

from typing import Dict

# Levels whose optimizer-boundary gradient persists as reduce-scattered
# 1/N chunks (parallel/shard_update.py ladder).
_SCATTERED_GRAD_LEVELS = ("zero2", "zero3")


def leaf_bytes_per_device(tree) -> int:
    """Per-device resident bytes of a pytree of placed jax Arrays (or
    ShapeDtypeStructs with shardings): Σ prod(shard_shape) × itemsize."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        shard_shape = leaf.sharding.shard_shape(leaf.shape)
        n = 1
        for d in shard_shape:
            n *= d
        total += n * leaf.dtype.itemsize
    return total


def grads_accum_bytes_per_device(params) -> int:
    """Bytes of the accumulated fp32 gradient tree one device holds
    between backward and sync: full parameter element count × 4 (every
    layout accumulates full per-replica grads; the scatter runs after)."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(params):
        total += int(np.prod(leaf.shape)) * 4
    return total


def grads_bytes_per_device(
    params, level: str = "off", n_shards: int = 1
) -> int:
    """Bytes of the OPTIMIZER-BOUNDARY gradient one device holds — the
    sync output the update consumes.  Full fp32 for off/zero1 (the full
    mean); the per-leaf ``[1, ceil(n/N)]`` chunk (zero padding included,
    exactly what ``chunk_rows`` allocates) for zero2/zero3."""
    import jax
    import numpy as np

    if level in _SCATTERED_GRAD_LEVELS and n_shards > 1:
        from ddlpc_tpu.parallel.shard_update import chunk_rows

        total = 0
        for leaf in jax.tree.leaves(params):
            total += chunk_rows(int(np.prod(leaf.shape)), n_shards) * 4
        return total
    return grads_accum_bytes_per_device(params)


def state_hbm_bytes(
    state, level: str = "off", n_shards: int = 1
) -> Dict[str, int]:
    """Per-device byte breakdown of a placed TrainState, by kind.
    ``level`` is the resolved shard_update level (off|zero1|zero2|zero3);
    params/opt_state read their placement straight off the committed
    shardings, only the gradient kinds need the level (grads are step
    temporaries with no placed array to inspect)."""
    return {
        "params": leaf_bytes_per_device(state.params),
        "grads": grads_bytes_per_device(state.params, level, n_shards),
        "grads_accum": grads_accum_bytes_per_device(state.params),
        "opt_state": leaf_bytes_per_device(state.opt_state),
        "batch_stats": leaf_bytes_per_device(state.batch_stats),
    }


def publish_hbm_gauges(
    registry,
    state,
    level: str = "off",
    n_shards: int = 1,
    replicated_by_rule: int = 0,
) -> Dict[str, int]:
    """Set ``ddlpc_hbm_bytes{kind}`` gauges from a placed TrainState;
    returns the breakdown.  Static per run layout — the trainer publishes
    once after state placement.  ``replicated_by_rule`` is
    ``StateLayout.replicated_by_rule_bytes()``: what the rule engine
    chose to keep replicated, published as its own gauge so the budget
    is explicit rather than hidden inside params/opt_state."""
    gauge = registry.gauge(
        "ddlpc_hbm_bytes",
        "Per-device resident state bytes from shape x committed sharding "
        "(grads = optimizer-boundary gradient, 1/N chunks under "
        "zero2/zero3; grads_accum = full fp32 backward accumulator, "
        "every layout).",
        labelnames=("kind",),
    )
    breakdown = state_hbm_bytes(state, level, n_shards)
    for kind, nbytes in breakdown.items():
        gauge.set(float(nbytes), kind=kind)
    registry.gauge(
        "ddlpc_hbm_replicated_by_rule_bytes",
        "Per-device bytes the partition-rule engine decided to keep "
        "replicated (uneven GSPMD dims, reason='replicated-by-rule') — "
        "the sharding contract's budgeted fallback.",
    ).set(float(replicated_by_rule))
    return breakdown
