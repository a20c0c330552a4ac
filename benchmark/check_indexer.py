#!/usr/bin/env python3
"""A family's own loss against its reference: the indexer's KL of
``keye_vl2`` (``L_I``) and its gradient, which ``check.py`` cannot see
(it differentiates the cross-entropy alone, and that does not reach the
indexer: its gradient there is zero on both sides).

    python3 benchmark/check_indexer.py --workload <cell> --seed <n> [--rehearse]

builds the cell's ``ExperimentConfig`` as ``run.py`` does, constructs the real
``Trainer`` and compares, on its parameters and one seeded training sequence:
the program's ``L_I`` (what the model sows into ``losses`` under
``train=True``, in the configuration's compute dtype) and ``dL_I/dW`` over
every leaf against ``reference/<family>.py``'s ``indexer_loss`` in float32, and
the share of the first layer's (query, key) pairs that the two selections
pick differently.  Prints one JSON line last; ``ok`` holds the errors to the
reference file's ``INDEXER_TOLERANCE`` and the
gradient outside the indexer to exact zero on both sides.  ``run.py`` does not
call this file.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def program_fn(model_cfg):
    """``L_I`` and its gradient as the train step computes them for one
    micro-batch: the model's sown ``indexer_kl`` under ``train=True``."""
    import jax

    from ddlpc_tpu.models import build_model

    model = build_model(model_cfg)

    def run(params, images):
        def loss_fn(p):
            _, sown = model.apply({"params": p}, images, train=True, mutable=["losses"])
            return sown["losses"]["indexer_kl"]

        return jax.value_and_grad(loss_fn)(params)

    return jax.jit(run)


def reference_fn(family: str, model: dict):
    import jax

    import check

    ref = check.load_reference(family)

    def run(params, images):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(lambda p: ref.indexer_loss(model, p, images))(params)

    return jax.jit(run)


def split(grads):
    """(the indexer's leaves, every other leaf) of a gradient tree."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    own = [x for path, x in flat if "indexer" in jax.tree_util.keystr(path)]
    rest = [x for path, x in flat if "indexer" not in jax.tree_util.keystr(path)]
    return own, rest


def first_layer_picks(model_cfg, family: str, params, images, block: int = 512):
    """Pairs (query, key) of the first layer picked by the program's indexer
    (compute dtype, bisection) and not by the reference's (float32, ``top_k``)
    or the reverse, over the pairs the reference picks."""
    import jax
    import jax.numpy as jnp

    import check
    from ddlpc_tpu.models import keye_vl2 as program

    ref = check.load_reference(family)
    model = dataclasses.asdict(model_cfg)
    dtype = jnp.dtype(model_cfg.compute_dtype)
    ids = jnp.asarray(images)[:1, 0, :, 0].astype(jnp.int32)
    s = ids.shape[1]
    block = min(block, s)
    p0 = params["layers_0"]

    @jax.jit
    def count():
        with jax.default_matmul_precision("highest"):
            u = ref.rms_norm(params["embedding"][ids], p0["operator_norm"]["scale"], model["norm_eps"])
            positions = ref.text_positions(model, s)
            qr, wr = ref.index_queries(u, p0["self_attn"]["indexer"], model, positions)
            kr = ref.index_keys(u, p0["self_attn"]["indexer"], model, positions)
        cos_sin = program.mrope_tables(
            positions[:1], model_cfg.indexer_head_dim, model_cfg.rope_theta, (model_cfg.indexer_head_dim // 2,)
        )
        up = program.RMSNorm(model_cfg.norm_eps, dtype).apply(
            {"params": p0["operator_norm"]}, params["embedding"].astype(dtype)[ids]
        )
        qp, kp, wp = program.Indexer(model_cfg.indexer_num_heads, model_cfg.indexer_head_dim, dtype).apply(
            {"params": p0["self_attn"]["indexer"]}, up[:, None], *cos_sin
        )
        differ, picked = 0, 0
        for start in range(0, s, block):
            end = start + block
            got = program._index_block(qp[0, start:end], kp[0, :end], wp[0, start:end], start)
            got = got >= program._threshold_block(got, start, model_cfg.indexer_topk)[:, None]
            with jax.default_matmul_precision("highest"):
                want = program._index_block(qr[0, start:end], kr[0, :end], wr[0, start:end], start)
            if end > model_cfg.indexer_topk:
                kth = jax.lax.top_k(want, model_cfg.indexer_topk)[0][:, -1:]
                few = (jnp.arange(start, end) < model_cfg.indexer_topk)[:, None]
                want = jnp.isfinite(want) & (few | (want >= kth))
            else:
                want = jnp.isfinite(want)
            differ, picked = differ + (got != want).sum(), picked + want.sum()
        return differ, picked

    differ, picked = jax.device_get(count())
    return float(differ) / float(picked)


def compare(model_cfg, family: str, params, images) -> dict:
    import jax
    import jax.numpy as jnp

    import check

    model = dataclasses.asdict(model_cfg)
    (loss_g, grads_g) = program_fn(model_cfg)(params, images)
    (loss_w, grads_w) = reference_fn(family, model)(params, images)

    def norm(leaves):
        return float(jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)))

    own_g, rest_g = split(grads_g)
    own_w, rest_w = split(grads_w)
    out = {
        "indexer_loss": abs(float(loss_g) - float(loss_w)) / abs(float(loss_w)),
        "indexer_grad": norm([a - b for a, b in zip(own_g, own_w)]) / norm(own_w),
        "loss_program": float(loss_g),
        "loss_reference": float(loss_w),
        "grad_outside_indexer": [norm(rest_g), norm(rest_w)],
        "first_layer_picks_differ": first_layer_picks(model_cfg, family, params, images),
    }
    limits = check.load_reference(family).INDEXER_TOLERANCE.get(model["compute_dtype"], {})
    out["limits"] = limits
    out["ok"] = all(out[k] == out[k] and out[k] <= limits[k] for k in limits) and out[
        "grad_outside_indexer"
    ] == [0.0, 0.0]
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    sys.path[:0] = [ROOT, HERE]
    import run

    manifest = run.read_json(ROOT, "BENCHMARK.json")
    cell = run.by_name(manifest["workloads"], args.workload, "workload")
    workload = run.read_json(HERE, "workloads", cell["name"] + ".json")
    config = run.read_json(ROOT, run.by_name(manifest["configs"], cell["config"], "config")["file"])
    traffic = run.read_json(HERE, "traffic", cell["traffic"] + ".json")
    devices, _ = run.open_devices(int(cell["chips"]), args.rehearse)

    import jax
    import numpy as np
    import tempfile

    from ddlpc_tpu.train.trainer import Trainer

    with tempfile.TemporaryDirectory(prefix="ddlpc_check_") as workdir:
        cfg = run.build_config(config, traffic, workload, args.seed, args.rehearse)
        trainer = Trainer(cfg.replace(workdir=os.path.join(workdir, "run")), resume=False)
        params = jax.device_get(trainer.layout.full_params(trainer.state))
        ds = trainer.train_ds
        at = int(np.random.default_rng(args.seed).integers(len(ds)))
        images = ds.images[at : at + 1]
        trainer.close()
        del trainer
        out = compare(cfg.model, config["reference"], params, images)
    out["device"] = {"platform": devices[0].platform, "kind": devices[0].device_kind}
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
