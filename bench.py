"""Benchmarks: training throughput per chip for the model zoo.

Default (driver contract): runs the flagship U-Net/Vaihingen configuration
through the real compiled SPMD train step — forward, backward, gradient
accumulation, all-reduce, fp16 codec, Adam — on all available devices and
prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N/400, "mfu": ...,
   "platform": "tpu", "device_kind": ..., "devices": N, ...}

The timed modes (default, --all) run on the TPU or not at all: any other
platform exits non-zero and prints no metric line, and a device kind that
is not in the one peak table (obs/flops._PEAK_BY_DEVICE_KIND) raises.

Baseline: BASELINE.md target >= 400 tiles/sec/chip on v5e-8 (the reference
publishes no numbers, SURVEY §6).

Extra modes (committed artifacts, VERDICT r1 weak #4):
  --all       benchmark every BASELINE config family (U-Net reference-parity
              and s2d stems, U-Net++, DeepLabV3+ 512², Cityscapes 512×1024),
              one JSON line each, and write bench_results.json.
  --scaling   virtual-device 1→2→4→8 DP scaling harness (CPU mesh):
              checks step semantics (same global batch ⇒ same loss) and
              reports per-device step-time overhead.  CPU wall-clock is not
              TPU wall-clock; this validates semantics + overhead shape, not
              ICI bandwidth.

One process per chip: a parent that has touched JAX holds the chip, so this
module imports no jax at module level — --scaling spawns children and
keeps the parent off JAX entirely; everything else runs
in-process and imports the accelerator stack inside the function.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from ddlpc_tpu.config import (
    CompressionConfig,
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    ParallelConfig,
    TrainConfig,
)

BASELINE_TILES_PER_SEC_PER_CHIP = 400.0

# The first executions carry one-time costs (program load, buffer
# allocation) — warm up past them, with a value fetch per call so the
# warmup actually completes before timing starts.
WARMUP_STEPS = 3
# Steady-state timing is PIPELINED: each timed round dispatches
# PIPELINE_STEPS chained steps and fetches one value at the end, the way a
# real epoch runs (the Trainer syncs metrics once per epoch).  A host sync
# per step would charge one host round trip to every step — that measures
# the dispatch path, not the training.
PIPELINE_STEPS = 8
TIMED_ROUNDS = 3

# Benchmark table.  micro_batch is per chip, tuned to fit v5e HBM (16 GB).
# The flagship 'unet_vaihingen512' uses this framework's TPU-first s2d stem
# at factor 4 (space-to-depth input, subpixel head): the 256²-resolution
# C=32 convs of the factor-2 pyramid run at ~9 TFLOP/s on v5e (lane padding
# below C=128), while the 128² C≥48 pyramid more than doubles end-to-end
# throughput.  Convergence at factor 4 is guarded by
# tests/test_models.py::test_unet_s2d_stem_learns[4] and the committed
# stem A/B (scripts/convergence_ab.py --stems 2,4: both reach val_miou
# ≥ 0.999 on synthetic Vaihingen).  'unet_vaihingen512_ref' is the
# reference-parity architecture (full-resolution first level,
# кластер.py:620-656) for apples-to-apples comparison.
BENCHES = {
    "unet_vaihingen512": dict(
        # THE flagship recipe (docs/HARD_TASK.md "Flagship decision"): s2d×4
        # pyramid + full-res DetailHead refinement, bf16 head, fp16 codec,
        # B=128/chip.  The hard-task stem A/B showed plain s2d×4 loses all
        # sub-16-px structure (val mIoU 0.465); the DetailHead recovers it
        # to ~0.9 at −4.6% throughput.  This row, the shipped config
        # (configs/vaihingen_unet_tpu_flagship.json) and the committed
        # convergence curve (docs/flagship_recipe/
        # flagship_b128x4_lr0.002.jsonl, val mIoU 0.925) are the SAME
        # configuration.
        model=dict(
            width_divisor=2,
            num_classes=6,
            stem="s2d",
            stem_factor=4,
            detail_head=True,
            head_dtype="bfloat16",
        ),
        image=(512, 512),
        # Sweep with detail head (docs/PERF.md): 96→1374, 128→1697.
        micro_batch=128,
        sync_period=4,
        compression="float16",
    ),
    "unet_vaihingen512_ref": dict(
        model=dict(width_divisor=2, num_classes=6),
        image=(512, 512),
        micro_batch=16,
        sync_period=4,
        compression="float16",
    ),
    # Middle Pareto point from the round-4 refinement sweep
    # (docs/HARD_TASK.md round-4 table): hidden-32 full-res DetailHead,
    # hard-task 0.9125 @120 epochs vs the flagship h16's 0.897, at −14%
    # throughput (docs/head_bench/results.json rows fullres_h32 1458 vs
    # fullres_h16 1693).
    "unet_vaihingen512_detail32": dict(
        model=dict(
            width_divisor=2,
            num_classes=6,
            stem="s2d",
            stem_factor=4,
            detail_head=True,
            detail_head_hidden=32,
            head_dtype="bfloat16",
        ),
        image=(512, 512),
        micro_batch=128,
        sync_period=4,
        compression="float16",
    ),
    # Quality-first zoo row (docs/HARD_TASK.md): s2d×2 + DetailHead
    # converges to 0.956 on the hard task (vs full-res 0.991 at the same
    # 120-epoch budget; flagship 0.897) at 1.6× the 400 target.
    # Sweep: B=64→484, 96→643.
    "unet_vaihingen512_s2d2_detail": dict(
        model=dict(
            width_divisor=2,
            num_classes=6,
            stem="s2d",
            stem_factor=2,
            detail_head=True,
            head_dtype="bfloat16",
        ),
        image=(512, 512),
        micro_batch=96,
        sync_period=4,
        compression="float16",
    ),
    "unetpp_vaihingen512": dict(
        # bf16 heads are worth 1.76× here: four deep-supervision heads emit
        # full-resolution logits each step.
        model=dict(
            name="unetpp",
            num_classes=6,
            features=(32, 64, 128, 256, 512),
            deep_supervision=True,
            head_dtype="bfloat16",
        ),
        image=(512, 512),
        micro_batch=8,
        sync_period=4,
        compression="none",
    ),
    "unetpp_vaihingen512_s2d": dict(
        # TPU-first U-Net++: the same s2d×4 stem as the flagship applied to
        # the nested grid — the dense full-width X[0][j] row, the grid's
        # biggest nodes, runs at 128² on rich channels.  34 → 679
        # tiles/s/chip (sweep: B=32→419, 48→451, 64→498, 96→679; 128
        # stalls).  The paper-layout row above stays for honest comparison.
        model=dict(
            name="unetpp",
            num_classes=6,
            features=(32, 64, 128, 256, 512),
            deep_supervision=True,
            head_dtype="bfloat16",
            stem="s2d",
            stem_factor=4,
        ),
        image=(512, 512),
        micro_batch=96,
        sync_period=4,
        compression="none",
    ),
    "deeplabv3p_potsdam512": dict(
        model=dict(
            name="deeplabv3p",
            num_classes=6,
            features=(64, 128, 256, 512),
            output_stride=16,
            head_dtype="bfloat16",
        ),
        image=(512, 512),
        micro_batch=32,
        sync_period=4,
        compression="none",
    ),
    "unet_cityscapes512x1024": dict(
        model=dict(
            width_divisor=1,
            num_classes=19,
            stem="s2d",
            stem_factor=4,
            head_dtype="bfloat16",
        ),
        image=(512, 1024),
        # bf16-head sweep: 12→213, 16→268, 24→285, 32→295, 48→269.  Note
        # these tiles are 2× the 512² pixel count: 295 tiles/s/chip is
        # ~590 512²-equivalents/s, 1.5× the 400 target in pixel terms.
        micro_batch=32,
        sync_period=4,
        compression="float16",
    ),
}
HEADLINE = "unet_vaihingen512"


def measure_update_ms(
    tx, mesh, compression, state, shard_update: str,
    rounds: int = TIMED_ROUNDS, param_avals=None,
) -> float:
    """Time the weight-update path alone (grad sync + optimizer + the
    level's own collectives) via the update-only compiled program
    (train_step.make_update_step).  ``state`` must already be in the
    matching run layout; ``param_avals`` supplies the canonical (full)
    gradient shapes when the placed params are chunked (zero3) — grads
    enter the update at full shape on every level.  Returns milliseconds
    per update.  NOTE zero3's number excludes the step-head params
    all-gather (it belongs to the train step's forward prologue, not the
    update program) — ``measure_gather_ms`` prices that separately."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddlpc_tpu.parallel.train_step import make_update_step

    upd = make_update_step(tx, mesh, compression, shard_update=shard_update)
    rng = np.random.default_rng(1)
    grads = jax.tree.map(
        lambda p: jax.device_put(
            rng.standard_normal(p.shape).astype(np.float32) * 1e-3,
            NamedSharding(mesh, P()),
        ),
        param_avals if param_avals is not None else state.params,
    )
    # Private copies: the update program donates its params/opt_state (the
    # realistic in-place layout), which would invalidate the caller's state.
    clone = lambda t: jax.tree.map(
        lambda x: jax.device_put(np.asarray(x), x.sharding), t
    )
    params, opt_state = clone(state.params), clone(state.opt_state)
    for _ in range(WARMUP_STEPS):
        params, opt_state = upd(params, opt_state, grads)
        jax.block_until_ready(params)
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(PIPELINE_STEPS):
            params, opt_state = upd(params, opt_state, grads)
        jax.block_until_ready(params)
        times.append((time.perf_counter() - t0) / PIPELINE_STEPS)
    return float(np.median(times)) * 1e3


def measure_gather_ms(
    mesh, state, param_avals, data_axis: str = "data",
    rounds: int = TIMED_ROUNDS,
) -> float:
    """Time zero3's step-head params all-gather in isolation: the exact
    per-leaf ``all_gather`` + reshape the train step's forward prologue
    runs on the persisted ``[N, K]`` chunks (train_step.shard_body).
    This is the cost zero3 pays that zero2 does not — priced separately
    so docs/sharding/update_ab.json states it instead of hiding it in a
    step time nobody decomposes."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ddlpc_tpu.parallel import shard_update as zero

    def gather(chunks):
        return jax.tree.map(
            lambda ch, av: zero.unchunk_leaf(
                jax.lax.all_gather(ch, data_axis, axis=0, tiled=True),
                av.shape,
            ),
            chunks,
            param_avals,
        )

    # The persisted chunks are [N, K] views sharded P(data) on axis 0 —
    # the same spec _zero_state_specs commits for zero3 params.
    fn = jax.jit(
        jax.shard_map(
            gather, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(data_axis), param_avals),),
            out_specs=jax.tree.map(lambda _: P(), param_avals), check_vma=False,
        )
    )
    for _ in range(WARMUP_STEPS):
        jax.block_until_ready(fn(state.params))
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(PIPELINE_STEPS):
            out = fn(state.params)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / PIPELINE_STEPS)
    return float(np.median(times)) * 1e3


def chip() -> dict:
    """The device a timed line names — and the gate that keeps every timed
    mode off anything but the TPU: a tiles/s/chip line from a CpuDevice is
    not a slow measurement, it is a wrong one."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py times the chip and found platform "
            f"{devices[0].platform!r}, not 'tpu' — there is no fallback. "
            f"The CPU-only count harnesses are --scaling and --update-ab "
            f"--devices N."
        )
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "devices": len(devices),
    }


def run_bench(
    name: str, timed_rounds: int = TIMED_ROUNDS, shard_update: str = "auto"
) -> dict:
    device = chip()
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddlpc_tpu.models import build_model_from_experiment
    from ddlpc_tpu.obs.flops import device_peak_flops
    from ddlpc_tpu.parallel.mesh import make_mesh
    from ddlpc_tpu.parallel.shard_update import (
        StateLayout,
        resolve_shard_update,
    )
    from ddlpc_tpu.parallel.train_step import (
        create_train_state,
        make_train_step,
    )
    from ddlpc_tpu.train.optim import build_optimizer

    peak_flops = device_peak_flops(jax.devices()[0])
    spec = BENCHES[name]
    h, w = spec["image"]
    n_devices = device["devices"]
    cfg = ExperimentConfig(
        model=ModelConfig(**spec["model"]),
        data=DataConfig(image_size=(h, w)),
        train=TrainConfig(
            micro_batch_size=spec["micro_batch"], sync_period=spec["sync_period"]
        ),
        parallel=ParallelConfig(shard_update=shard_update),
        compression=CompressionConfig(mode=spec["compression"]),
    )
    mesh = make_mesh(cfg.parallel)
    model = build_model_from_experiment(cfg)
    tx = build_optimizer(cfg.train)
    state = create_train_state(model, tx, jax.random.key(0), (1, h, w, 3))
    sharded = resolve_shard_update(
        shard_update, cfg.compression, mesh.shape["data"], spatial=False
    )
    layout = StateLayout(
        "replicated" if sharded == "off" else sharded, tx, state, mesh, "data"
    )
    state = layout.place(state)
    t_update_ms = measure_update_ms(
        tx, mesh, cfg.compression, state, sharded, rounds=timed_rounds,
        param_avals=layout.param_avals,
    )
    step = make_train_step(
        model, tx, mesh, cfg.compression, shard_update=sharded,
        param_avals=layout.param_avals,
    )

    A = spec["sync_period"]
    global_batch = spec["micro_batch"] * n_devices
    rng = np.random.default_rng(0)
    images = jax.device_put(
        rng.uniform(0, 1, (A, global_batch, h, w, 3)).astype(np.float32),
        NamedSharding(mesh, P(None, "data")),
    )
    labels = jax.device_put(
        rng.integers(0, cfg.model.num_classes, (A, global_batch, h, w)).astype(
            np.int32
        ),
        NamedSharding(mesh, P(None, "data")),
    )
    # One AOT compile, reused for both cost analysis and the timed calls
    # (jit dispatch would compile the same program a second time).
    compiled = step.lower(state, images, labels).compile()
    # cost_analysis() reports the post-partitioning (per-device) module,
    # so this is already per-chip FLOPs — no further /n_devices.  BUT it
    # counts a while/scan body ONCE regardless of trip count (verified:
    # lowering with sync_period 1 vs 4 reports identical flops), so the
    # A-micro-batch accumulation scan must be re-multiplied — without
    # this every MFU reported here is ~A× understated (the round-2
    # tables were).  The small non-scan epilogue (codec + Adam) gets
    # over-multiplied by the same factor; it is <1% of step FLOPs.
    flops = compiled.cost_analysis()["flops"] * A

    for _ in range(WARMUP_STEPS):
        state, metrics = compiled(state, images, labels)
        # Value fetch per call: the warmup must finish before timing starts.
        float(metrics["loss"])

    times = []
    for _ in range(timed_rounds):
        t0 = time.perf_counter()
        for _ in range(PIPELINE_STEPS):
            state, metrics = compiled(state, images, labels)
        float(metrics["loss"])
        times.append((time.perf_counter() - t0) / PIPELINE_STEPS)
    # Median round: robust to transient host contention.
    dt = float(np.median(times))

    tiles_per_step = A * global_batch
    tps_chip = tiles_per_step / dt / n_devices
    return {
        "metric": f"{name}_train_tiles_per_sec_per_chip",
        "value": round(tps_chip, 2),
        "unit": "tiles/s/chip",
        "vs_baseline": round(tps_chip / BASELINE_TILES_PER_SEC_PER_CHIP, 3),
        "mfu": round(flops / dt / peak_flops, 4),
        **device,
        "step_time_s": round(dt, 4),
        "timing": f"pipelined_{PIPELINE_STEPS}",
        "global_batch": global_batch,
        "sync_period": A,
        # Weight-update path in isolation (grad sync + Adam + the level's
        # collectives), from the update-only compiled program.  The
        # resolved ZeRO level string ("off"|"zero1"|"zero2"|"zero3").
        "shard_update": sharded,
        "t_update_ms": round(t_update_ms, 3),
    }


def run_scaling() -> list[dict]:
    """Re-exec DP runs on 1/2/4/8 virtual CPU devices; same GLOBAL batch.

    Semantics check: pure DP with a fixed global batch must produce the same
    loss trajectory regardless of device count (the exact-mean all-reduce —
    the property the reference's crooked averaging broke, кластер.py:268).
    Reported per-device overhead is CPU-relative, not an ICI measurement.
    """
    import os
    import subprocess
    import sys

    child = r"""
import json, time
import jax
from ddlpc_tpu.utils.compat import force_cpu_devices
force_cpu_devices(%(n)d)
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from ddlpc_tpu.config import (CompressionConfig, DataConfig, ExperimentConfig,
                              ModelConfig, ParallelConfig, TrainConfig)
from ddlpc_tpu.models import build_model_from_experiment
from ddlpc_tpu.parallel.mesh import make_mesh
from ddlpc_tpu.parallel.train_step import create_train_state, make_train_step
from ddlpc_tpu.train.optim import build_optimizer

cfg = ExperimentConfig(
    model=ModelConfig(features=(8, 16), bottleneck_features=16, num_classes=6),
    train=TrainConfig(micro_batch_size=%(b)d, sync_period=2),
    compression=CompressionConfig(mode='none'))
mesh = make_mesh(cfg.parallel)
model = build_model_from_experiment(cfg)
tx = build_optimizer(cfg.train)
state = create_train_state(model, tx, jax.random.key(0), (1, 64, 64, 3))
step = make_train_step(model, tx, mesh, cfg.compression, donate_state=False)
rng = np.random.default_rng(0)
B = 16  # global micro-batch, constant across device counts
images = jax.device_put(rng.uniform(0, 1, (2, B, 64, 64, 3)).astype(np.float32),
                        NamedSharding(mesh, P(None, 'data')))
labels = jax.device_put(rng.integers(0, 6, (2, B, 64, 64)).astype(np.int32),
                        NamedSharding(mesh, P(None, 'data')))
losses = []
for _ in range(3):
    state, m = step(state, images, labels)
    losses.append(float(m['loss']))
t0 = time.perf_counter()
for _ in range(5):
    state, m = step(state, images, labels)
float(m['loss'])
dt = (time.perf_counter() - t0) / 5
print(json.dumps({'n': %(n)d, 'losses': losses, 'step_time_s': dt}))
"""
    out = []
    for n in (1, 2, 4, 8):
        code = child % {"n": n, "b": 16 // n}
        proc = subprocess.run(
            [sys.executable, "-c", code],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True,
            text=True,
            timeout=900,
        )
        if proc.returncode != 0:
            raise RuntimeError(f"scaling run n={n} failed:\n{proc.stderr[-2000:]}")
        out.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    ref = out[0]["losses"]
    for rec in out:
        # Exact-mean DP: identical global batch ⇒ identical trajectory
        # (fp reassociation tolerance only).
        assert np.allclose(rec["losses"], ref, rtol=2e-4), (
            f"DP semantics drift at n={rec['n']}: {rec['losses']} vs {ref}"
        )
        rec["semantics_ok"] = True
        rec["overhead_vs_1dev"] = round(
            rec["step_time_s"] / out[0]["step_time_s"], 3
        )
    return out


def run_update_ab(rounds: int, out_path: str) -> dict:
    """Same-host A/B of the weight-update path across the ZeRO ladder
    (off / zero1 / zero2 / zero3) at the flagship model size: per-step
    ``t_update_ms`` each arm plus the per-device params + optimizer-state
    bytes each layout keeps resident.  The zero3 arm also prices its
    step-head params all-gather (``params_gather_ms``) — the cost zero3
    pays every step that zero2 does not, stated separately because the
    update-only program excludes it by construction.  Writes the
    committed JSON and returns the driver-contract record (the zero2
    arm's ``update_ms_per_step`` — zero2 is the ladder's default, PR 5's
    sharded update renamed)."""
    import jax

    from ddlpc_tpu.models import build_model_from_experiment
    from ddlpc_tpu.parallel.mesh import make_mesh
    from ddlpc_tpu.parallel.shard_update import StateLayout
    from ddlpc_tpu.parallel.train_step import create_train_state
    from ddlpc_tpu.train.optim import build_optimizer

    name = HEADLINE
    spec = BENCHES[name]
    h, w = spec["image"]
    cfg = ExperimentConfig(
        model=ModelConfig(**spec["model"]),
        compression=CompressionConfig(mode=spec["compression"]),
    )
    mesh = make_mesh(cfg.parallel)
    n_devices = mesh.shape["data"]
    if n_devices < 2:
        # Without this the 'on' arm silently times the replicated program
        # (singleton fallback) and the committed artifact would claim a
        # ZeRO measurement that never happened.
        raise SystemExit(
            "--update-ab needs a multi-device data mesh to measure the "
            "sharded arm; pass --devices N (N >= 2) for a virtual CPU mesh"
        )
    model = build_model_from_experiment(cfg)
    tx = build_optimizer(cfg.train)
    # Param shapes (all the update path sees) are resolution-independent:
    # init at the smallest tile the s2d stem + pyramid accepts, not 512².
    state0 = create_train_state(
        model, tx, jax.random.key(0), (1, max(h // 4, 128), max(w // 4, 128), 3)
    )
    def _shard0_bytes(tree):
        return sum(
            s.data.nbytes
            for leaf in jax.tree.leaves(tree)
            for s in leaf.addressable_shards[:1]
        )

    arms = {}
    for level in ("off", "zero1", "zero2", "zero3"):
        layout = StateLayout(
            "replicated" if level == "off" else level, tx, state0, mesh,
            "data",
        )
        state = layout.place(state0)
        arms[level] = {
            "t_update_ms": round(
                measure_update_ms(
                    tx, mesh, cfg.compression, state, level, rounds=rounds,
                    param_avals=layout.param_avals,
                ),
                3,
            ),
            "params_bytes_per_device": _shard0_bytes(state.params),
            "opt_state_bytes_per_device": _shard0_bytes(state.opt_state),
        }
        if level == "zero3":
            # zero3's extra per-step cost: the forward prologue's params
            # all-gather (not in the update-only program) — priced here
            # so the artifact states it rather than letting the update
            # column imply zero3 is free.
            arms[level]["params_gather_ms"] = round(
                measure_gather_ms(
                    mesh, state, layout.param_avals, rounds=rounds
                ),
                3,
            )
    report = {
        "bench": name,
        "devices": n_devices,
        "backend": jax.default_backend(),
        "codec": spec["compression"],
        "params": int(
            sum(int(np.prod(l.shape)) for l in jax.tree.leaves(state0.params))
        ),
        "arms": arms,
        "opt_state_reduction_x": round(
            arms["off"]["opt_state_bytes_per_device"]
            / max(arms["zero2"]["opt_state_bytes_per_device"], 1),
            2,
        ),
        "params_reduction_x_zero3": round(
            arms["off"]["params_bytes_per_device"]
            / max(arms["zero3"]["params_bytes_per_device"], 1),
            2,
        ),
    }
    if out_path:
        import os

        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
    return {
        "metric": "update_ms_per_step",
        "value": arms["zero2"]["t_update_ms"],
        "unit": "ms",
        "replicated_ms": arms["off"]["t_update_ms"],
        "zero1_ms": arms["zero1"]["t_update_ms"],
        "zero3_ms": arms["zero3"]["t_update_ms"],
        "zero3_gather_ms": arms["zero3"]["params_gather_ms"],
        "opt_state_reduction_x": report["opt_state_reduction_x"],
        "devices": n_devices,
    }


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--all", action="store_true", help="run the whole zoo")
    p.add_argument(
        "--scaling", action="store_true", help="virtual-device DP scaling checks"
    )
    p.add_argument(
        "--shard-update",
        choices=("auto", "on", "off", "zero1", "zero2", "zero3"),
        default="auto",
        help="ZeRO level of the benched step's weight update (auto/on "
        "resolve to zero2 on multi-device meshes — docs/SHARDING.md)",
    )
    p.add_argument(
        "--update-ab",
        action="store_true",
        help="A/B the weight-update path (replicated vs sharded) and print "
        "the update_ms_per_step contract line",
    )
    p.add_argument(
        "--update-ab-out",
        default="docs/sharding/update_ab.json",
        help="committed artifact path for --update-ab",
    )
    p.add_argument(
        "--devices",
        type=int,
        default=0,
        help="force an N-device virtual CPU mesh (testing/A-B on hosts "
        "without accelerators); 0 = use the real backend",
    )
    p.add_argument("--rounds", type=int, default=TIMED_ROUNDS)
    args = p.parse_args()

    if args.scaling:
        # Runs entirely in CPU-pinned children; the parent stays off JAX.
        for rec in run_scaling():
            print(json.dumps(rec))
        return

    from ddlpc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.devices:
        from ddlpc_tpu.utils.compat import force_cpu_devices

        force_cpu_devices(args.devices)

    if args.update_ab:
        print(json.dumps(run_update_ab(args.rounds, args.update_ab_out)))
        return

    if args.all:
        results = [
            run_bench(name, args.rounds, shard_update=args.shard_update)
            for name in BENCHES
        ]
        for rec in results:
            print(json.dumps(rec))
        with open("bench_results.json", "w") as f:
            json.dump(results, f, indent=2)
        return
    print(
        json.dumps(
            run_bench(HEADLINE, args.rounds, shard_update=args.shard_update)
        )
    )


if __name__ == "__main__":
    main()
