"""The comparison behind ``correct``: the program's model and loss, in the
dtype its configuration states, against the family's plain float32 reference
on the same parameters and the same tiles.

Three small jitted programs (program, reference, errors) instead of one:
what has to fit the chip is then the larger of the two, not their sum.
"""

from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp


def load_reference(family: str):
    """``benchmark/reference/<family>.py``, found by name."""
    return importlib.import_module(f"reference.{family}")


def program_fn(model_cfg):
    """Loss, logits and gradients as the train step computes them for one
    micro-batch on one device (``models/`` + ``ops/losses.py`` through
    ``loss_from_logits``), in the configuration's compute dtype."""
    from ddlpc_tpu.models import build_model
    from ddlpc_tpu.parallel.train_step import loss_from_logits

    # No norm axis: on one device SyncBN's mean over replicas is the identity.
    model = build_model(model_cfg)

    def run(params, batch_stats, images, labels):
        def loss_fn(p):
            logits, _ = model.apply(
                {"params": p, "batch_stats": batch_stats},
                images,
                train=True,
                mutable=["batch_stats"],
            )
            loss, _ = loss_from_logits(model, logits, labels, True)
            return loss, logits

        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return loss, logits.astype(jnp.float32), grads

    return jax.jit(run)


def reference_fn(family: str, model: dict):
    """The same three from the plain reference, float32 at full precision."""
    from reference.plain_ops import cross_entropy

    ref = load_reference(family)

    def run(params, batch_stats, images, labels):
        del batch_stats  # training mode normalises with the batch's statistics

        def loss_fn(p):
            logits = ref.forward(model, p, images)
            return cross_entropy(logits, labels), logits

        with jax.default_matmul_precision("highest"):
            (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return loss, logits, grads

    return jax.jit(run)


@jax.jit
def _errors(got, want):
    (loss_g, logits_g, grads_g), (loss_w, logits_w, grads_w) = got, want

    def sq(tree):
        return sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree))

    diff = jax.tree.map(jnp.subtract, grads_g, grads_w)
    return {
        "loss": jnp.abs(loss_g - loss_w) / jnp.abs(loss_w),
        "logits": jnp.sqrt(sq(logits_g - logits_w) / sq(logits_w)),
        "grad": jnp.sqrt(sq(diff) / sq(grads_w)),
        "loss_program": loss_g,
        "loss_reference": loss_w,
    }


def compare(model_cfg, family: str, params, batch_stats, images, labels) -> dict:
    """Relative errors of the program against the reference, the limits they
    are held to (the reference file's, for the stated compute dtype) and the
    verdict ``ok``."""
    model = dataclasses.asdict(model_cfg)
    got = program_fn(model_cfg)(params, batch_stats, images, labels)
    want = reference_fn(family, model)(params, batch_stats, images, labels)
    out = {k: float(v) for k, v in jax.device_get(_errors(got, want)).items()}
    limits = load_reference(family).TOLERANCE[model["compute_dtype"]]
    out["limits"] = limits
    out["ok"] = all(out[k] == out[k] and out[k] <= limits[k] for k in limits)
    return out
