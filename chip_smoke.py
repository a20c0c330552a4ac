"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

Drives the training main path once, end to end, on the TPU, through the
entry point a user calls (``python -m ddlpc_tpu.train`` →
``ddlpc_tpu.train.__main__``), at the flagship's full width
(configs/vaihingen_unet_tpu_flagship.json: half-width U-Net, s2d×4 +
DetailHead, 512², micro 128 × sync 4, fp16 codec, synthetic tiles from a
seed), and checks what comes out by the repo's own means.  Arms:

  train     4 optimizer steps (one per epoch), 4 evals, 4 async chunked
            checkpoints, PNG dumps, a profiler trace of epoch 2, and the
            collectives of the step's optimized HLO
  resume    the same command with one more epoch: restores, steps once,
            and finds its programs in the persistent compile cache
  serve     InferenceEngine restore + warmup + one 2×2-tile scene against
            the trainer's own state and predict function
  host_fed  the ShardedLoader path: native gather kernel, upload ring
  kernel    the Pallas codec kernels compiled by Mosaic against
            ops/quantize.py on a gradient tree of the model's shapes

A failed arm is reported and the others still run; the script then fails.

With more than one device visible the trainer arms run data-parallel over
all of them at the same global batch (ZeRO-2, SyncBN) and the script also
checks that every device holds state.

One process, everything in-process: the chip belongs to one process.  Exits
non-zero unless the platform is ``tpu`` and the device kind is in the one
peak table (obs/flops.py); there is no flag or variable that relaxes that.
The last stdout line is the result:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import gc
import json
import math
import os
import re
import shutil
import subprocess
import sys
import time
import traceback
import warnings

REPO = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(REPO, "configs", "vaihingen_unet_tpu_flagship.json")
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")
TRAIN_EPOCHS = 4  # one optimizer step each: 97 train tiles, super-batch 512

# Failures the Trainer downgrades to warnings in a user's run (accounting
# and profiling must never kill training) — the smoke must see them.
_FATAL_WARNINGS = (
    r".*FLOP model unavailable.*",
    r".*comm probe failed.*",
    r".*profiler trace failed.*",
    r".*native batch kernel unavailable.*",
)


class SmokeFailure(RuntimeError):
    """A check did not hold; the script exits non-zero."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def require_chip() -> dict:
    """The device record of the result line — or no result at all."""
    import jax

    from ddlpc_tpu.obs.flops import device_peak_flops

    devices = jax.devices()
    first = devices[0]
    print(
        f"platform={first.platform} device_kind={first.device_kind} "
        f"devices={len(devices)}",
        flush=True,
    )
    if first.platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py runs on the TPU; JAX found platform "
            f"{first.platform!r}"
        )
    device_peak_flops(first)  # a kind outside the one peak table raises
    return {
        "platform": first.platform,
        "kind": first.device_kind,
        "count": len(devices),
    }


def rebuild_native() -> None:
    """Unconditionally rebuild the two libraries this run loads (``make -B
    all``: ``clean all`` for exactly those), from the sources git would
    commit; no g++ is an error, not a numpy fallback to measure."""
    subprocess.run(
        ["make", "-s", "-B", "-C", os.path.join(REPO, "csrc"), "all"],
        check=True,
        timeout=300,
    )


def cache_counts(cursor) -> dict:
    """The persistent cache's hits and misses since the cursor was last
    taken, from the compile ledger (``utils/compile_cache.py``): a miss is a
    program XLA compiled, whether or not the cache wrote an entry for it."""
    taken = cursor.take()
    return {"cache_hits": taken["programs_loaded"], "cache_misses": taken["programs_compiled"]}


def epoch_records(workdir: str) -> tuple[list, list]:
    """(epoch records, kind="perf" records) of a run's metrics.jsonl."""
    from ddlpc_tpu.obs.merge import read_records

    records = read_records([os.path.join(workdir, "metrics.jsonl")])
    return (
        [r for r in records if "kind" not in r and "epoch" in r],
        [r for r in records if r.get("kind") == "perf"],
    )


def moment_shards(opt_state) -> tuple[dict, int]:
    """(bytes of optimizer moments actually resident per device id, their
    total bytes) read off the arrays' addressable shards; Adam's scalar
    step count is not a moment."""
    import jax

    per_device: dict = {}
    total = 0
    for leaf in jax.tree.leaves(opt_state):
        if leaf.ndim == 0:
            continue
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes
            )
    return per_device, total


_COLLECTIVE = re.compile(
    r"=\s*(?P<shape>.*?)\s(?P<kind>all-reduce|reduce-scatter|all-gather|"
    r"all-to-all|collective-permute)(?:-start)?\("
)


def collective_census(trainer) -> list:
    """Collectives in the OPTIMIZED HLO of the trainer's compiled step
    (after XLA's combiner), counted by kind and element dtype.  Dtypes are
    read off the RESULT shape: jax 0.9.0 prints operands by name only, and
    these collectives keep their operand's dtype."""
    trainer.loader.set_epoch(0)
    batch = next(iter(trainer.loader))
    text = trainer.train_step.lower(trainer.state, *batch).compile().as_text()
    with open(os.path.join(trainer.workdir, "train_step.hlo.txt"), "w") as f:
        f.write(text)
    counts: dict = {}
    for line in text.splitlines():
        m = _COLLECTIVE.search(line)
        if m is not None:
            dtypes = sorted(set(re.findall(r"\b([a-z]+\d+)\[", m.group("shape"))))
            key = (m.group("kind"), "+".join(dtypes))
            counts[key] = counts.get(key, 0) + 1
    return [
        {"kind": kind, "dtype": dtype, "count": n}
        for (kind, dtype), n in sorted(counts.items())
    ]


def check_device_state(trainer, n_devices: int, on_chip: bool) -> dict:
    """Several devices: all of them hold state, and ZeRO-2 really split
    the optimizer moments 1/n."""
    import jax

    check(
        trainer.shard_update == "zero2",
        f"shard_update resolved to {trainer.shard_update!r} (want zero2)",
    )
    per_device, total = moment_shards(trainer.state.opt_state)
    check(
        len(per_device) == n_devices
        and all(b * n_devices == total for b in per_device.values()),
        f"optimizer moments: 1/{n_devices} of {total} bytes on each of "
        f"{len(per_device)} devices",
    )
    out = {"moment_bytes_per_device": total // n_devices}
    if on_chip:
        in_use = {
            d.id: d.memory_stats()["bytes_in_use"] for d in jax.devices()
        }
        check(
            all(b > 0 for b in in_use.values()),
            f"every device holds memory: bytes_in_use {in_use}",
        )
        out["bytes_in_use"] = in_use
    return out


def check_device_trace(profile_dir: str) -> dict:
    """The trace of the profiled epoch must carry a TPU device plane with
    XLA-op self time — through obs/xplane.self_times (device planes only),
    not the host-plane fallback; the per-layer split reads exactly this."""
    from ddlpc_tpu.obs import xplane

    planes = {
        name: sum(agg.values()) / 1e9
        for name, agg, _ in xplane.self_times(profile_dir)
    }
    check(
        any(n.startswith("/device:TPU:") and ms > 0 for n, ms in planes.items()),
        f"device trace: XLA-op self time per plane (ms) {planes}",
    )
    return {"trace_self_ms": {k: round(v, 3) for k, v in planes.items()}}


def arm_train(run, workdir, n_devices, on_chip, cache) -> tuple:
    """-> (results, the finished Trainer)."""
    epochs = TRAIN_EPOCHS
    trainer = run([f"train.epochs={epochs}", "train.profile_epoch=2"], workdir, False)
    records, perfs = epoch_records(workdir)
    losses = [r["loss"] for r in records]
    check(
        len(records) == epochs and int(trainer.state.step) == epochs,
        f"{epochs} epochs ran, one optimizer step each",
    )
    check(
        all(math.isfinite(l) for l in losses) and losses[-1] < losses[0],
        f"losses finite and falling: {[round(l, 4) for l in losses]}",
    )
    check(
        all(math.isfinite(r["val_miou"]) for r in records),
        f"val_miou finite: {[round(r['val_miou'], 4) for r in records]}",
    )
    mfu = perfs[-1]["mfu"]
    check(0.0 < mfu < 1.0, f"0 < MFU < 1: {mfu}")
    from ddlpc_tpu.train import checkpoint as ckpt

    check(
        ckpt.latest_step(trainer.ckpt_dir) == epochs,
        f"newest checkpoint is step {epochs}",
    )
    check(
        os.path.isdir(os.path.join(workdir, "images", f"epoch_{epochs - 1:04d}")),
        "prediction PNGs written",
    )
    out = {
        "first_step_loss": losses[0],
        "last_step_loss": losses[-1],
        "val_miou": records[-1]["val_miou"],
        "mfu_last_epoch": mfu,
        "step_time_s_last_epoch": records[-1]["step_time_s"],
        "first_step_dispatch_s_cold": records[0]["t_step_s"],
        "collectives": collective_census(trainer),
        **cache_counts(cache),
    }
    if on_chip:
        check(
            perfs[-1]["peak_flops_assumed"] is False,
            "MFU denominator is the device's own peak, not an assumption",
        )
        out.update(check_device_trace(os.path.join(workdir, "profile")))
    if n_devices > 1:
        out.update(check_device_state(trainer, n_devices, on_chip))
    return out, trainer


def arm_resume(run, workdir, cold_s, cache_dir, on_chip, cache) -> tuple:
    """-> (results, the resumed Trainer)."""
    trainer = run([f"train.epochs={TRAIN_EPOCHS + 1}"], workdir, True)
    records, _ = epoch_records(workdir)
    check(
        trainer.start_epoch == TRAIN_EPOCHS
        and int(trainer.state.step) == TRAIN_EPOCHS + 1,
        f"resumed at the saved step {TRAIN_EPOCHS} and took one more",
    )
    warm_s = records[-1]["t_step_s"]
    out = {
        "first_step_dispatch_s_cold": cold_s,
        "first_step_dispatch_s_warm": warm_s,
        "compile_cache_dir": cache_dir,
        **cache_counts(cache),
    }
    if on_chip:
        check(
            os.path.isdir(cache_dir) and len(os.listdir(cache_dir)) > 0,
            f"compile cache {cache_dir} is non-empty",
        )
        check(
            out["cache_hits"] > 0 and warm_s < cold_s,
            f"second start hit the compile cache ({out['cache_hits']} hits; "
            f"first step {cold_s:.1f} s cold, {warm_s:.1f} s warm)",
        )
    return out, trainer


def arm_host_fed(run, workdir, cached_loss, on_chip, cache) -> dict:
    from ddlpc_tpu.utils import native

    check(native.load_batch() is not None, "native batch kernel loaded")
    trainer = run(["train.epochs=2", "data.device_cache=False"], workdir, False)
    records, _ = epoch_records(workdir)
    loss = records[0]["loss"]
    check(
        abs(loss - cached_loss) <= 0.02 * abs(cached_loss),
        f"first-step loss {loss:.4f} within 2% of the cached arm's "
        f"{cached_loss:.4f}",
    )
    ring = trainer.loader._ring
    out = {
        "first_step_loss": loss,
        "t_loader_gather_s": records[-1].get("t_loader_gather_s"),
        "t_loader_upload_s": records[-1].get("t_loader_upload_s"),
        "ring_slots_retired": ring.retired,
        **cache_counts(cache),
    }
    if on_chip:
        # CPU clients alias the host buffer (the ring retires the slot);
        # HBM uploads are real copies and every slot must come back.
        check(ring.retired == 0, "upload-ring slots recycled, none re-allocated")
    trainer.close()
    return out


def arm_kernel(cfg, n_devices: int, interpret: bool) -> dict:
    """Pallas codec kernels vs ops/quantize.py on a gradient-shaped tree.
    Every program is one jit over the whole tree and every comparison runs
    on the host, so the arm compiles the kernels under test and little
    else."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddlpc_tpu.config import CompressionConfig
    from ddlpc_tpu.models import build_model
    from ddlpc_tpu.ops import pallas_quantize as pq
    from ddlpc_tpu.ops import quantize as q

    try:
        CompressionConfig(mode="float16", codec_backend="pallas")
    except ValueError:
        refused = True
    else:
        refused = False
    check(refused, "float16 wire (refused by Mosaic) is a config-time ValueError")

    h, w = cfg.data.image_size
    shapes = jax.eval_shape(
        lambda: build_model(cfg.model).init(
            jax.random.key(0), jnp.zeros((1, h, w, 3), jnp.float32), train=False
        )
    )["params"]
    leaves, treedef = jax.tree.flatten(shapes)
    rng = np.random.default_rng(1)
    x = [rng.standard_normal(l.shape, dtype=np.float32) for l in leaves]
    tree = jax.tree.unflatten(treedef, [jnp.asarray(a) for a in x])
    n_params = sum(a.size for a in x)
    # Stochastic rounding is unbiased; its mean error over n elements has
    # sd ~0.41/sqrt(n) lattice steps (1e-4 at the flagship's 7.8 M).
    bias_tol = max(1e-3, 5 * 0.41 / n_params**0.5)
    out: dict = {"params": n_params, "leaves": len(leaves), "interpret": interpret}

    def host(t):
        return [np.asarray(a) for a in jax.tree.leaves(t)]

    def identical(a, b):
        return all(np.array_equal(p, r) for p, r in zip(host(a), host(b)))

    def error_and_bias(resid):
        """(max |r|, mean r) over a list of residual arrays."""
        return (
            max(float(np.max(np.abs(r))) for r in resid),
            float(sum(np.sum(r, dtype=np.float64) for r in resid)) / n_params,
        )

    nearest = CompressionConfig(mode="int8")
    stochastic = CompressionConfig(mode="int8", rounding="stochastic")
    levels = float(q.levels_for(nearest))
    scale = jax.jit(q.global_absmax)(tree)
    safe = q.safe_divisor(scale)
    step = float(scale) / levels
    scaled = [a / np.float32(safe) * np.float32(levels) for a in x]

    # fake-quantize: the dequantize multiply may contract differently in
    # the two compilers — one ulp, the repo's own tolerance
    # (tests/test_pallas_quantize.py), nothing more.
    t0 = time.perf_counter()
    fq = jax.jit(lambda t: pq.fake_quantize_pallas(t, nearest, interpret=interpret))(tree)
    jax.block_until_ready(fq)
    out["fake_quantize_compile_s"] = round(time.perf_counter() - t0, 2)
    ref = jax.jit(lambda t: q.fake_quantize(t, nearest))(tree)
    rel = max(
        float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))
        for a, b in zip(host(fq), host(ref))
    )
    check(rel <= 3e-7, f"fake_quantize nearest within 1 ulp of XLA (max rel {rel:.2e})")
    fqs = jax.jit(
        lambda t, k: pq.fake_quantize_pallas(t, stochastic, key=k, interpret=interpret)
    )(tree, jax.random.key(2))
    err, bias = error_and_bias([(a - b) / step for a, b in zip(host(fqs), x)])
    bound = q.quantization_error_bound(stochastic) * levels  # in lattice steps
    check(
        err <= bound + 1e-4 and abs(bias) <= bias_tol,
        f"fake_quantize stochastic: error {err:.4f} steps <= {bound:.1f}, "
        f"bias {bias:+.2e} steps (|bias| <= {bias_tol:.1e})",
    )

    # The wire dtypes the int8 codec puts on a collective: s8 while the
    # lattice sums fit, s16 beyond (compressed_allreduce.wire_dtype).
    for wire in (jnp.int8, jnp.int16):
        name = jnp.dtype(wire).name
        enc = jax.jit(
            lambda t, s: pq.encode_to_wire_pallas(t, nearest, s, wire, interpret=interpret)
        )(tree, safe)
        ref = jax.jit(
            lambda t, s: jax.tree.map(
                lambda g: q.quantize_with_scale(g, s, levels).astype(wire), t
            )
        )(tree, safe)
        check(identical(enc, ref), f"encode_to_wire nearest -> {name} bit-identical to XLA")
        encs = jax.jit(
            lambda t, s, k: pq.encode_to_wire_pallas(
                t, stochastic, s, wire, key=k, interpret=interpret
            )
        )(tree, safe, jax.random.key(3))
        err, bias = error_and_bias(
            [e.astype(np.float32) - v for e, v in zip(host(encs), scaled)]
        )
        check(
            err <= 1.0 + 1e-4 and abs(bias) <= bias_tol,
            f"encode_to_wire stochastic -> {name}: error {err:.4f} steps <= 1, "
            f"bias {bias:+.2e} steps (|bias| <= {bias_tol:.1e})",
        )
        inv = scale / (levels * max(n_devices, 2))
        dec = jax.jit(lambda e, i: pq.decode_from_wire_pallas(e, i, interpret=interpret))(
            enc, inv
        )
        ref = jax.jit(
            lambda e, i: jax.tree.map(lambda v: v.astype(jnp.float32) * i, e)
        )(enc, inv)
        check(identical(dec, ref), f"decode_from_wire {name} bit-identical to XLA")
        out[f"wire_{name}"] = "compiled, nearest bit-identical, stochastic unbiased"
    out["wire_float16"] = "refused by Mosaic; config-time ValueError"
    return out


def arm_serve(trainer, workdir: str) -> dict:
    """Restore the run into the serving engine and predict one 2×2-tile
    scene (overlap 0, so stitching is a plain tiling) against the
    trainer's own state on the same tiles."""
    import jax
    import numpy as np

    from ddlpc_tpu.parallel.train_step import make_logits_fn
    from ddlpc_tpu.serve.engine import InferenceEngine

    engine = InferenceEngine.from_workdir(workdir, max_bucket=4)
    check(
        engine.checkpoint_step == int(trainer.state.step),
        f"engine restored step {engine.checkpoint_step}",
    )
    compiled = engine.warmup()
    th, tw = engine.tile
    scene = np.random.default_rng(0).uniform(0, 1, (2 * th, 2 * tw, 3)).astype(
        np.float32
    )
    t0 = time.perf_counter()
    classes = engine.predict_classes(scene, overlap=0.0, batch=4)
    predict_s = time.perf_counter() - t0
    tiles = np.stack(
        [scene[y : y + th, x : x + tw] for y in (0, th) for x in (0, tw)]
    )

    def scene_of(per_tile):
        t = np.asarray(per_tile)
        return np.block([[t[0], t[1]], [t[2], t[3]]])

    state = trainer.state.replace(
        params=trainer.layout.full_params(trainer.state), opt_state=()
    )
    # Same program, same weights: the engine's logits function on the
    # trainer's live state (pulled to the host, so it compiles for one
    # device like the engine's) must give the SAME class map — this is the
    # checkpoint round trip plus the tiler, exactly.
    logits = make_logits_fn(trainer.model)(jax.device_get(state), tiles)
    want = scene_of(np.argmax(np.asarray(logits, np.float32), axis=-1))
    check(
        classes.shape == (2 * th, 2 * tw) and np.array_equal(classes, want),
        f"{2 * th}x{2 * tw} scene: class map equals argmax of the trainer "
        f"state's logits on the same tiles, exactly",
    )
    # The trainer's predict function fuses the argmax into the bf16 head,
    # where XLA keeps excess precision: bf16 ties break differently on a
    # few pixels of a five-step model (0.1 % measured on the v5e).
    mismatch = int((classes != scene_of(trainer.predict(state, tiles))).sum())
    check(
        mismatch <= classes.size // 100,
        f"agrees with the trainer's make_predict_fn on all but {mismatch} of "
        f"{classes.size} pixels (<= 1 %)",
    )
    return {
        "compiled_shapes": compiled,
        "scene_predict_s": round(predict_s, 3),
        "predict_fn_mismatch_pixels": mismatch,
        "classes_seen": sorted(int(c) for c in np.unique(classes)),
    }


def smoke(device: dict, width: tuple = (), out_dir: str = OUT) -> dict:
    """Run every arm against ``device`` (what :func:`require_chip`
    returned).  ``width`` holds extra ``--set`` overrides; the script
    itself passes none — the flagship's full width is the point."""
    from ddlpc_tpu.train.__main__ import parse_config
    from ddlpc_tpu.train.__main__ import run as train_run
    from ddlpc_tpu.utils.compile_cache import enable_compile_cache, install_compile_ledger

    on_chip = device["platform"] == "tpu"
    n = device["count"]
    cache_dir = enable_compile_cache()
    rebuild_native()
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)

    base = ["--config", CONFIG]
    sets = list(width)
    cfg, _ = parse_config(base + [a for s in sets for a in ("--set", s)])
    if n > 1:
        # Same global super-batch on every device count, so the first-step
        # loss of a one-chip and a four-chip run can be set side by side.
        check(
            cfg.train.micro_batch_size % n == 0,
            f"micro batch {cfg.train.micro_batch_size} splits over {n} devices",
        )
        sets += [
            "parallel.data_axis_size=-1",
            f"train.micro_batch_size={cfg.train.micro_batch_size // n}",
        ]

    def run(extra, workdir, resume):
        argv = base + ["--workdir", workdir]
        argv += [a for s in sets + extra for a in ("--set", s)]
        return train_run(argv + ([] if resume else ["--no-resume"]))

    cache = install_compile_ledger().cursor()
    results: dict = {}
    failed: list = []

    def arm(name, fn, *a):
        """Run one arm and record its results; returns the Trainer an arm
        hands on (or None).  A failed arm is reported and the rest still
        run — every arm's verdict for the price of one call — but the
        script fails."""
        print(f"== {name}", flush=True)
        t0 = time.perf_counter()
        try:
            ret = fn(*a)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            results[name] = {"failed": True}
            return None
        res, trainer = ret if isinstance(ret, tuple) else (ret, None)
        res["arm_wall_s"] = round(time.perf_counter() - t0, 1)
        results[name] = res
        print(json.dumps({name: res}), flush=True)
        return trainer

    train_wd = os.path.join(out_dir, "train")
    try:
        with warnings.catch_warnings():
            for pattern in _FATAL_WARNINGS:
                warnings.filterwarnings("error", message=pattern)
            trainer = arm("train", arm_train, run, train_wd, n, on_chip, cache)
            if trainer is None:
                raise SmokeFailure("the train arm failed; nothing else can run")
            res = results["train"]
            cold_s, cached_loss = res["first_step_dispatch_s_cold"], res["first_step_loss"]
            trainer.close()
            del trainer
            gc.collect()
            trainer = arm(
                "resume", arm_resume, run, train_wd, cold_s, cache_dir, on_chip, cache
            )
            if trainer is not None:
                arm("serve", arm_serve, trainer, train_wd)
                trainer.close()
                del trainer
                gc.collect()
            arm(
                "host_fed", arm_host_fed, run, os.path.join(out_dir, "host_fed"),
                cached_loss, on_chip, cache,
            )
            gc.collect()
            arm("kernel", arm_kernel, cfg, n, not on_chip)
    finally:
        # What the call brings back is capped: keep the records, drop the
        # blobs (three ~95 MB checkpoints a run, PNGs, the raw trace).
        for run_dir in ("train", "host_fed"):
            for sub in ("checkpoints", "images", "profile"):
                shutil.rmtree(os.path.join(out_dir, run_dir, sub), ignore_errors=True)
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump({"device": device, "arms": results}, f, indent=2)
    if failed:
        raise SmokeFailure(f"failed arms: {failed}")
    return results


def main() -> int:
    from ddlpc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = require_chip()
    smoke(device)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
