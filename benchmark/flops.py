"""Required convolution FLOPs of one optimizer step, per device.

The benchmark's own copy of the conv walk in ``ddlpc_tpu/obs/flops.py``
(``iter_eqns``, ``conv_flops``, ``collect_convs``, ``conv_step_flops``), kept
here so that no later PR can move the numerator of ``mfu_pct``.  It traces
the per-micro-batch ``value_and_grad`` jaxpr of the configured model and sums
2 x output elements x kernel taps x input channels over every
``conv_general_dilated``: the forward convs and the two backward convs per
layer.  Norms, loss and Adam are left out (convs are >99 % of this zoo's
step), and nothing is executed or compiled.

Conventions, the original's: an lhs-dilated (transposed) conv is counted at
its algorithmic cost, inserted zeros included; a rematerialised forward
would be counted twice (no shipped configuration sets ``train.remat``).
"""

from __future__ import annotations

import math


def _sub_jaxprs(params):
    for v in params.values():
        for q in v if isinstance(v, (list, tuple)) else (v,):
            if hasattr(q, "jaxpr") and hasattr(q.jaxpr, "eqns"):  # ClosedJaxpr
                yield q.jaxpr
            elif hasattr(q, "eqns"):  # raw Jaxpr
                yield q


def iter_eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (scan, remat, pjit) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def conv_flops(eqn) -> int:
    """2 x output elements x KH x KW x Cin per group (MACs x 2)."""
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    cin_per_group = rhs[dn.rhs_spec[1]]
    taps = math.prod(rhs[d] for d in dn.rhs_spec[2:])
    return 2 * math.prod(out) * taps * cin_per_group


def conv_step_flops(cfg, channels: int = 3) -> int:
    """Conv FLOPs of one optimizer step on one device for ``cfg`` (an
    ``ExperimentConfig``): ``sync_period`` micro-batches of forward and
    backward at the per-device ``micro_batch_size``."""
    import jax
    import jax.numpy as jnp

    from ddlpc_tpu.models import build_model
    from ddlpc_tpu.ops.losses import softmax_cross_entropy

    model = build_model(cfg.model)  # SyncBN changes no conv shape
    h, w = cfg.data.image_size
    b = cfg.train.micro_batch_size
    x = jax.ShapeDtypeStruct((b, h, w, channels), jnp.float32)
    y = jax.ShapeDtypeStruct((b, h, w), jnp.int32)
    variables = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1, h, w, channels), jnp.float32), train=False
        )
    )

    def loss_fn(params, stats, x, y):
        logits, _ = model.apply(
            {"params": params, "batch_stats": stats}, x, train=True, mutable=["batch_stats"]
        )
        return softmax_cross_entropy(logits, y, ignore_index=-1)

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss_fn))(
        variables["params"], variables.get("batch_stats", {}), x, y
    )
    per_micro = sum(
        conv_flops(e)
        for e in iter_eqns(jaxpr.jaxpr)
        if e.primitive.name == "conv_general_dilated"
    )
    return cfg.train.sync_period * per_micro
