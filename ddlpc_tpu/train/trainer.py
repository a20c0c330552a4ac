"""The training driver: the reference's server/worker script bodies
(кластер.py:690-790, 792-895) re-designed as one SPMD ``Trainer``.

Where the reference branches on hostname into a server loop and a worker
loop that differ only in which half of the socket protocol they call, here
every process runs the identical program over a shared device mesh; the
"protocol" is the compiled all-reduce inside the train step.  On top of the
reference's behavior (epoch loop, gradient-accumulated sync steps, per-epoch
loss/pixel-acc/timing logs, qualitative PNG dumps) this driver adds what the
reference lacks (SURVEY §5): held-out evaluation with mIoU (the north-star
metric), checkpoint/resume, and a config artifact per run.
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time
import warnings
from typing import Dict, Optional

import jax
import numpy as np

from ddlpc_tpu.config import ExperimentConfig
from ddlpc_tpu.data import ShardedLoader, build_dataset
from ddlpc_tpu.data.loader import DeviceCachedLoader, eval_batches
from ddlpc_tpu.models import build_model_from_experiment
from ddlpc_tpu.ops.metrics import (
    accuracy_from_confusion,
    iou_per_class,
    mean_iou,
)
from ddlpc_tpu.parallel.mesh import initialize_distributed, make_mesh
from ddlpc_tpu.parallel.shard_update import (
    GSPMD_LAYOUT_FOR_LEVEL,
    StateLayout,
    resolve_shard_update,
)
from ddlpc_tpu.parallel.train_step import (
    create_train_state,
    make_eval_step,
    make_eval_step_gspmd,
    make_predict_fn,
    make_train_step,
    make_train_step_gspmd,
)
from ddlpc_tpu.obs import comm as obs_comm
from ddlpc_tpu.obs import flops as obs_flops
from ddlpc_tpu.obs import hbm as obs_hbm
from ddlpc_tpu.obs import lineage as obs_lineage
from ddlpc_tpu.obs.health import HealthMonitor
from ddlpc_tpu.obs.http import TelemetryServer
from ddlpc_tpu.obs.profiling import OnDemandProfiler
from ddlpc_tpu.obs.registry import MetricsRegistry
from ddlpc_tpu.obs.tracing import Tracer
from ddlpc_tpu.resilience import chaos as _chaos_mod
from ddlpc_tpu.resilience.protocol import EXIT_PREEMPTED, write_breadcrumb
from ddlpc_tpu.train import checkpoint as ckpt
from ddlpc_tpu.train.async_checkpoint import AsyncCheckpointer
from ddlpc_tpu.train.observability import (
    MetricsLogger,
    StageTimer,
    dump_prediction_triples,
    maybe_profile,
)
from ddlpc_tpu.train.optim import build_optimizer
from ddlpc_tpu.train.watchdog import StallWatchdog
from ddlpc_tpu.utils.compile_cache import install_compile_ledger


# The step's own metrics; any other key it returns is a model counter.
_STEP_METRICS = ("loss", "pixel_acc", "grad_norm")


def _record_keys(stage_seconds: Dict[str, float]) -> Dict[str, float]:
    """``{stage: seconds}`` as epoch-record keys: ``init/state`` →
    ``t_init_state_s``."""
    return {
        f"t_{name.replace('/', '_')}_s": t for name, t in stage_seconds.items()
    }


class PreemptedRun(Exception):
    """Raised inside the epoch loop when a graceful preemption was
    requested (SIGTERM, :meth:`Trainer.request_preempt`, or a chaos
    ``preempt@N`` fault): carries where the run stopped so the emergency
    checkpoint can record the exact mid-epoch position."""

    def __init__(self, epoch: int, steps_done: int):
        super().__init__(f"preempted at epoch {epoch}, step {steps_done}")
        self.epoch = epoch
        self.steps_done = steps_done


class Trainer:
    """End-to-end training: data, mesh, compiled steps, logging, checkpoints.

    ``TrainConfig.micro_batch_size`` is per-replica (the reference's
    ``batch_size=1`` per node, кластер.py:686); the global micro-batch is
    that times the data-axis size, and one optimizer step consumes
    ``sync_period`` micro-batches (кластер.py:685).
    """

    def __init__(self, cfg: ExperimentConfig, resume: bool = True):
        initialize_distributed()
        # What JAX traces, lowers, compiles or loads from here on (a no-op
        # install where an entry point already did it): construction's share
        # goes into the kind="init" line and the first epoch record as
        # init_compile_*_s / init_programs_*, each record carries its own.
        self._compiles = install_compile_ledger().cursor()
        self.cfg = cfg
        if cfg.model.num_classes != cfg.data.num_classes:
            raise ValueError(
                f"model.num_classes={cfg.model.num_classes} != "
                f"data.num_classes={cfg.data.num_classes}: the loss would "
                f"silently clip out-of-range labels and mIoU would drop them"
            )
        if cfg.data.device_cache and cfg.data.augment:
            raise ValueError(
                "data.device_cache and data.augment are mutually exclusive: "
                "augmentation runs in the host gather path that the device "
                "cache bypasses"
            )
        if cfg.data.compact_upload and cfg.data.num_classes > 127:
            raise ValueError(
                f"data.compact_upload ships int8 labels, which cannot hold "
                f"num_classes={cfg.data.num_classes} (max 127)"
            )
        if cfg.data.lazy_tiles and cfg.data.device_cache:
            raise ValueError(
                "data.lazy_tiles and data.device_cache are mutually "
                "exclusive: the device cache uploads whole resident arrays, "
                "exactly what lazy_tiles exists to avoid"
            )
        if cfg.data.loader_workers > 1 and cfg.data.device_cache:
            raise ValueError(
                "data.loader_workers only affects the ShardedLoader host "
                "path; device_cache gathers batches on device, so worker "
                "threads have nothing to do — unset one of them"
            )
        # Unified telemetry (ddlpc_tpu/obs, docs/OBSERVABILITY.md): one
        # span tracer + one Prometheus-style registry per training process.
        # The tracer is constructed unconditionally — disabled it is a
        # near-free no-op — so every instrumentation site below stays
        # unconditional too.
        self.registry = MetricsRegistry()
        # Model lineage (ISSUE 17): one run id per Trainer construction +
        # the config hash every checkpoint this run saves will carry — the
        # identity the serving fleet resolves responses back to.
        self.run_id = obs_lineage.new_id()
        self.config_hash = obs_lineage.config_hash(
            json.dumps(cfg.to_dict(), sort_keys=True)
        )
        self.tracer = Tracer(
            enabled=cfg.train.trace and jax.process_index() == 0,
            service="train",
            jsonl_path=os.path.join(cfg.workdir, "spans.jsonl"),
            chrome_path=os.path.join(cfg.workdir, "trace.json"),
        )
        # Created before the loader so the ShardedLoader can thread its
        # per-stage host timings (loader_gather/cast/upload) into the same
        # epoch records as t_data/t_step (StageTimer is thread-safe; the
        # stages run on producer threads).  The tracer hook additionally
        # records every stage — including the loop's data/step stages and
        # the loader's per-stage hooks — as spans.
        self.timer = StageTimer(tracer=self.tracer)
        # Every phase of construction is a stage (host span ``ddlpc:init/*``,
        # contiguous from here to the end of __init__): their seconds go
        # into one kind="init" line and the first epoch record as
        # t_init_<phase>_s, then the timer starts the loop's accounting clean.
        with self.timer.stage("init/dataset"):
            self.mesh = make_mesh(cfg.parallel)
            data_size = self.mesh.shape[cfg.parallel.data_axis_name]
            self.global_micro_batch = cfg.train.micro_batch_size * data_size
            # Stochastic rounding's benefit is regime-dependent (measured, not
            # assumed — docs/QUANTIZATION.md round-3 table): at global super-batch
            # 32 it closes the int8 codec's entire convergence lag, but at the
            # flagship's 512 it COSTS −0.045 val mIoU vs nearest rounding (the
            # big batch already averages the rounding error away, so the injected
            # variance is pure noise).  Warn anyone combining it with a
            # large-batch operating point.
            global_super_batch = self.global_micro_batch * cfg.train.sync_period
            if (
                cfg.compression.mode != "none"
                and cfg.compression.rounding == "stochastic"
                and global_super_batch >= 256
            ):
                warnings.warn(
                    f"rounding='stochastic' at global super-batch "
                    f"{global_super_batch} (micro {cfg.train.micro_batch_size} x "
                    f"sync {cfg.train.sync_period} x {data_size} replicas): the "
                    f"committed A/B measured stochastic rounding HELPING at small "
                    f"batch (closes int8's lag at super-batch 32) but COSTING "
                    f"-0.045 val mIoU at super-batch 512 "
                    f"(docs/QUANTIZATION.md round-3 table) — large batches "
                    f"average quantization error away on their own; prefer "
                    f"rounding='nearest' here",
                    stacklevel=2,
                )

            self.train_ds, self.test_ds = build_dataset(cfg.data)
            self.model = build_model_from_experiment(cfg)
            self.spatial = cfg.parallel.space_axis_size > 1
            space = cfg.parallel.space_axis_name if self.spatial else None
            # ZeRO sharded-update level (parallel/shard_update.py,
            # docs/SHARDING.md): resolves to 'off'|'zero1'|'zero2'|'zero3'.
            # 'auto' picks zero2 for data meshes > 1 unless a codec
            # combination cannot compose (those fall back to 'off' — explicit
            # levels raise there instead).
            self.shard_update = resolve_shard_update(
                cfg.parallel.shard_update,
                cfg.compression,
                data_size,
                self.spatial,
                grad_clip_norm=cfg.train.grad_clip_norm,
            )

        with self.timer.stage("init/loader"):
            loader_cls = (
                DeviceCachedLoader if cfg.data.device_cache else ShardedLoader
            )
            # compact composes with BOTH transports: on the ShardedLoader it
            # shrinks the per-batch wire, on the DeviceCachedLoader it shrinks
            # the resident cache itself (44% of the fp32 HBM).
            loader_kw = (
                {"compact": cfg.data.compact_upload} if cfg.data.device_cache
                else {"compact": cfg.data.compact_upload,
                      "workers": cfg.data.loader_workers,
                      "native_gather": cfg.data.native_gather,
                      "timer": self.timer}
            )
            self.loader = loader_cls(
                self.train_ds,
                self.mesh,
                global_micro_batch=self.global_micro_batch,
                sync_period=cfg.train.sync_period,
                shuffle=cfg.data.shuffle,
                seed=cfg.data.seed,
                data_axis=cfg.parallel.data_axis_name,
                space_axis=space,
                **loader_kw,
            )
            # Step horizon for decaying LR schedules comes from the loader (one
            # source of truth for steps/epoch, including tail semantics).
            self.tx = build_optimizer(
                cfg.train, total_steps=cfg.train.epochs * len(self.loader)
            )

        h, w = cfg.data.image_size
        channels = self.train_ds.image_shape[-1]
        with self.timer.stage("init/state"):
            self.state = create_train_state(
                self.model,
                self.tx,
                jax.random.key(cfg.train.seed),
                (1, h, w, channels),
                # Token tiles are integer ids; every other dataset is float32.
                input_dtype=getattr(
                    getattr(self.train_ds, "images", None), "dtype", np.float32
                ),
            )
            # Run layout: replicated, or — under the sharded update — the
            # level's persistent shards: Adam moments chunked 1/N (zero1/2/3),
            # plus the params themselves under zero3; the GSPMD path expresses
            # the same placements as NamedShardings (gspmd/gspmd_zero2/
            # gspmd_zero3).  ``layout`` converts both ways; checkpoints and
            # multi-host broadcasts always move the canonical (gathered)
            # layout, so on-disk state is layout-independent.
            layout_mode = (
                "replicated"
                if self.shard_update == "off"
                else (
                    GSPMD_LAYOUT_FOR_LEVEL[self.shard_update]
                    if self.spatial
                    else self.shard_update
                )
            )
            self.layout = StateLayout(
                layout_mode,
                self.tx,
                self.state,
                self.mesh,
                cfg.parallel.data_axis_name,
            )
            self.state = self.layout.place(self.state)

        with self.timer.stage("init/steps"):
            # Pure data mesh → hand-written shard_map collectives (reference-
            # parity codec semantics); data×space mesh → GSPMD, where XLA
            # partitions convs along H with automatic halo exchange.
            self.train_step = self._build_train_step()
            if self.spatial:
                self.eval_step = make_eval_step_gspmd(
                    self.model,
                    self.mesh,
                    num_classes=cfg.model.num_classes,
                    data_axis=cfg.parallel.data_axis_name,
                    space_axis=space,
                )
            else:
                self.eval_step = make_eval_step(
                    self.model,
                    self.mesh,
                    num_classes=cfg.model.num_classes,
                    data_axis=cfg.parallel.data_axis_name,
                )
            self.predict = make_predict_fn(self.model)

        with self.timer.stage("init/accounting"):
            # Performance accounting (docs/PERF.md "Accounting"): a per-step
            # conv FLOP model traced once (no compute), live MFU/goodput and
            # per-device HBM gauges, and exact per-collective comm byte
            # counters for the configured codec/transport.  The comm-time
            # probe (a compiled sync-only program) is built lazily and sampled
            # at most once per epoch on the trace_sync cadence.
            self.perf: Optional[obs_flops.PerfAccountant] = None
            self.comm: Optional[obs_comm.CommAccountant] = None
            self._comm_probe = None
            self._comm_probed_epoch = False
            if cfg.train.perf_accounting:
                try:
                    flops_per_step, grouped_flops = obs_flops.step_flops(
                        cfg, cfg.train.micro_batch_size, cfg.train.sync_period,
                        channels=channels,
                    )
                    if self.spatial:
                        # The trace is the UNPARTITIONED per-micro-batch
                        # program; under H-sharding each device executes
                        # ~1/space of those convs (halo recompute ignored —
                        # a few rows per conv).  Without this, spatial MFU
                        # overstates by space_axis_size.
                        flops_per_step //= cfg.parallel.space_axis_size
                except Exception as e:  # accounting must never kill the run
                    warnings.warn(
                        f"per-step FLOP model unavailable ({type(e).__name__}: "
                        f"{e}); ddlpc_mfu will read 0",
                        stacklevel=2,
                    )
                    flops_per_step = grouped_flops = 0
                peak, assumed = obs_flops.resolve_peak_flops(
                    cfg.train.peak_flops_per_device
                )
                self.perf = obs_flops.PerfAccountant(
                    self.registry,
                    flops_per_step=flops_per_step,
                    grouped_flops_per_step=grouped_flops,
                    peak_flops=peak,
                    peak_assumed=assumed,
                    # Downtime inherited from a previous supervised attempt
                    # (breadcrumb / resilience.jsonl) — read BEFORE this run's
                    # first breadcrumb write, debited as category 'restart'.
                    restart_gap_s=obs_flops.restart_gap_seconds(cfg.workdir),
                )
                obs_hbm.publish_hbm_gauges(
                    self.registry,
                    self.state,
                    level=self.shard_update,
                    n_shards=data_size,
                    replicated_by_rule=self.layout.replicated_by_rule_bytes(),
                )
                if cfg.compression.transport == "ring" and cfg.compression.mode != "none":
                    variant = "ring"
                elif self.spatial:
                    variant = "gspmd"
                elif self.shard_update == "zero2":
                    variant = "scatter"
                elif self.shard_update in ("zero1", "zero3"):
                    variant = self.shard_update
                else:
                    variant = "allreduce"
                # Canonical (unchunked) parameter shapes: under zero3 the
                # placed params are [N, K] chunks, but the wire accounting
                # and the probe model the sync over the logical grads.
                canonical_params = self.layout.param_avals
                n_params = obs_comm.tree_elements(canonical_params)
                from ddlpc_tpu.parallel.grad_sync import grad_bucket_groups

                n_buckets = len(
                    grad_bucket_groups(
                        canonical_params, cfg.compression.bucket_mb
                    )
                )
                self.comm = obs_comm.CommAccountant(
                    self.registry,
                    obs_comm.comm_plan(
                        n_params,
                        n_params,
                        cfg.compression,
                        data_size,
                        variant,
                        n_buckets=n_buckets,
                    ),
                    variant,
                )
                if not self.spatial and data_size > 1:
                    # Shape-only closure: the probe must not pin the initial
                    # (donated) param buffers alive.
                    param_shapes = jax.tree.map(
                        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
                        canonical_params,
                    )
                    self._comm_probe = obs_comm.make_comm_probe(
                        self.mesh,
                        cfg.compression,
                        param_shapes,
                        data_axis=cfg.parallel.data_axis_name,
                        scatter=self.shard_update in ("zero2", "zero3"),
                        seed=cfg.train.seed,
                    )

        with self.timer.stage("init/restore"):
            self.workdir = cfg.workdir
            self.ckpt_dir = os.path.join(self.workdir, "checkpoints")
            self.start_epoch = 0
            # Preemption-graceful shutdown state (docs/RESILIENCE.md): SIGTERM
            # (or request_preempt(), or a chaos preempt fault) sets the event;
            # the step loop finishes the in-flight step, then fit() writes an
            # emergency checkpoint recording the mid-epoch position and the
            # process exits with EXIT_PREEMPTED.  ``preempted`` is the flag
            # __main__ maps to that exit status.
            self._preempt = threading.Event()
            self._preempt_done = threading.Event()
            self._grace_timer: Optional[threading.Timer] = None
            self.preempted = False
            # Mid-epoch resume: the restore below may find an emergency
            # checkpoint taken ``mid_epoch_steps_done`` steps into an epoch —
            # train_epoch then draws-and-discards exactly that many batches
            # (the loader is epoch-seeded and deterministic), so the resumed
            # trajectory is bit-identical to an uninterrupted run's.
            self._skip_steps = 0
            self._skip_epoch = -1
            # The epoch a running fit() stops before (0 outside fit): an
            # epoch whose successor it runs gathers that epoch's first
            # super-batch ahead of its own end-of-epoch sync.
            self._fit_until = 0
            # Chaos fault injection (resilience/chaos.py): None unless the
            # DDLPC_CHAOS env var schedules faults; the step counter is
            # process-lifetime, matching the schedule's step semantics.
            self._chaos = _chaos_mod.active()
            self._chaos_step = 0
            # Lineage of the checkpoint this run resumed from (None on a cold
            # start; the explicit unknown marker on pre-lineage checkpoints).
            self.restored_lineage: Optional[dict] = None
            if resume:
                self._restore_synchronized()

        with self.timer.stage("init/services"):
            self.logger = MetricsLogger(
                self.workdir,
                run_config_json=cfg.to_json(),
                registry=self.registry,
            )
            # Failure detection (SURVEY §5: the reference has none and hangs
            # forever on a dead peer).  Armed by fit(); beats come from the
            # epoch loop's data/step stages.
            self.watchdog = StallWatchdog(
                timeout_s=cfg.train.stall_timeout_s,
                action=cfg.train.stall_action,
                log_path=os.path.join(self.workdir, "stall.log"),
                # Last breadcrumb before an abort(42): the supervisor reads it
                # to classify the exit even if stderr was lost.
                on_stall=lambda age, tag: (
                    write_breadcrumb(
                        self.workdir, "stalled", stall_age_s=age, stall_tag=tag
                    )
                    if jax.process_index() == 0
                    else None
                ),
            )
            # Health detectors (obs/health.py): EWMA step-time regression and
            # loss NaN/spike alerts, fed per epoch record, fanning out to the
            # JSONL stream, the registry, and the watchdog's diagnosis ring.
            self.health = HealthMonitor(
                logger=self.logger,
                registry=self.registry,
                watchdog=self.watchdog,
                service="train",
            )
            # On-demand profiling (obs/profiling.py): armed by SIGUSR2 (fit
            # installs the handler) or GET /debug/trace on the telemetry
            # endpoint; the step loop drives the capture over the next N steps
            # and the top-ops report lands in the workdir.
            self.profiler = OnDemandProfiler(
                out_dir=self.workdir,
                steps=cfg.train.profile_steps,
                logger=self.logger,
                enabled=jax.process_index() == 0,
            )
            self.telemetry: Optional[TelemetryServer] = None
            if cfg.train.telemetry_port >= 0 and jax.process_index() == 0:
                self.telemetry = TelemetryServer(
                    self.registry,
                    port=cfg.train.telemetry_port,
                    health_fn=self._health_snapshot,
                    arm_profile_fn=self._arm_profile,
                ).start()
            # Async by default: save() pays only the host snapshot; the chunk/
            # compress/fsync chain overlaps the next epoch's compute on a
            # writer thread, with a barrier (and error re-raise) on the next
            # save and at the end of fit() (train/async_checkpoint.py).
            self.checkpointer = AsyncCheckpointer(
                keep=cfg.train.keep_checkpoints,
                format=cfg.train.checkpoint_format,
                chunk_bytes=max(1, cfg.train.checkpoint_chunk_mb) << 20,
                compression=cfg.train.checkpoint_compression,
                background=cfg.train.checkpoint_async,
            )
        self._init_times = _record_keys(self.timer.summary())
        self._init_times.update(
            {f"init_{k}": v for k, v in self._compiles.take().items()}
        )
        self.timer.reset()
        self.logger.log({"kind": "init", **self._init_times}, echo=False)

    def _health_snapshot(self) -> dict:
        return {
            "status": "ok",
            "pid": os.getpid(),
            "alerts": list(self.health.alerts),
        }

    def _arm_profile(self, steps: int) -> dict:
        self.profiler.arm(steps if steps > 0 else None)
        return {
            "armed": True,
            "steps": self.profiler.steps,
            "note": (
                "capture spans the next N dispatched training steps; the "
                "top-ops report lands in the run workdir"
            ),
        }

    def close(self) -> None:
        """Release the telemetry endpoint and the tracer's file handles.

        fit() deliberately leaves both running — the endpoint stays
        scrapeable between/after fits and the tracer supports a
        subsequent fit — so a caller constructing multiple Trainers in
        one process (or binding a fixed telemetry_port twice) must close
        the old one.  Idempotent."""
        if self.telemetry is not None:
            self.telemetry.close()
            self.telemetry = None
        self.tracer.close()

    def _build_train_step(self):
        cfg = self.cfg
        if self.spatial:
            return make_train_step_gspmd(
                self.model,
                self.tx,
                self.mesh,
                cfg.compression,
                data_axis=cfg.parallel.data_axis_name,
                space_axis=cfg.parallel.space_axis_name,
                remat=cfg.train.remat,
                seed=cfg.train.seed,
                shard_update=self.shard_update,
            )
        return make_train_step(
            self.model,
            self.tx,
            self.mesh,
            cfg.compression,
            data_axis=cfg.parallel.data_axis_name,
            remat=cfg.train.remat,
            seed=cfg.train.seed,
            shard_update=self.shard_update,
            # zero3's gather-on-demand restores chunks to these canonical
            # shapes; harmless (ignored) at every other level.
            param_avals=self.layout.param_avals,
        )

    def _restore_synchronized(self) -> None:
        """Resume with process 0 as the single source of truth.

        Only process 0 writes checkpoints (checkpoint.py), so on non-shared
        storage other hosts may see nothing — deciding locally would
        desynchronize the SPMD program (mismatched collective counts hang the
        pod).  Process 0 decides; both the resume epoch and the restored
        state are broadcast to every process.
        """
        if jax.process_count() == 1:
            if ckpt.latest_step(self.ckpt_dir) is not None:
                # The restore target only supplies pytree STRUCTURE (leaf
                # shapes come from the blob) — checkpoints store the
                # canonical gathered layout regardless of the run layout,
                # and place() re-chunks/re-shards for this run.  A corrupt
                # newest blob is quarantined and the restore falls back to
                # the next-newest inside restore_checkpoint itself.
                state, meta = ckpt.restore_checkpoint(self.ckpt_dir, self.state)
                self.state = self.layout.place(state)
                self.start_epoch = int(meta.get("epoch", -1)) + 1
                self.restored_lineage = meta.get("lineage")
                self._apply_mid_epoch(int(meta.get("mid_epoch_steps_done", 0)))
            return
        from jax.experimental import multihost_utils

        if jax.process_index() == 0 and ckpt.latest_step(self.ckpt_dir) is not None:
            state, meta = ckpt.restore_checkpoint(self.ckpt_dir, self.state)
            found, epoch_next = 1, int(meta.get("epoch", -1)) + 1
            skip = int(meta.get("mid_epoch_steps_done", 0))
            self.restored_lineage = meta.get("lineage")
        else:
            state, found, epoch_next, skip = None, 0, 0, 0
        # Separate found flag: a checkpoint with missing/epoch-less metadata
        # must still restore its weights (resuming at epoch 0), matching the
        # single-process branch.
        found, epoch_next, skip = (
            int(v)
            for v in multihost_utils.broadcast_one_to_all(
                np.array([found, epoch_next, skip], np.int32)
            )
        )
        if found:
            # The broadcast moves the CANONICAL layout (every process must
            # contribute a structurally identical pytree; under a sharded
            # run layout the local state's chunk shapes would not match the
            # full-layout restore).  canonical() is a compiled collective,
            # so EVERY process runs it — process 0 included, discarding the
            # result in favor of the restored state.
            template = self.layout.canonical(self.state)
            state = multihost_utils.broadcast_one_to_all(
                state if state is not None else template
            )
            self.state = self.layout.place(state)
            self.start_epoch = epoch_next
            self._apply_mid_epoch(skip)

    def _apply_mid_epoch(self, skip: int) -> None:
        """Arm the skip-replay for an emergency (mid-epoch) checkpoint.

        ``mid_epoch_steps_done`` in the metadata means the restored state
        already contains that many optimizer steps of epoch
        ``start_epoch`` — replaying them would double-apply updates, so
        train_epoch discards exactly that many loader batches first.  A
        recorded position at/past the epoch horizon (possible only if the
        dataset shrank between runs) counts as a completed epoch instead
        of resuming into an empty one.
        """
        if skip <= 0:
            return
        if skip >= len(self.loader):
            self.start_epoch += 1
            return
        self._skip_steps = skip
        self._skip_epoch = self.start_epoch

    # ------------------------------------------------------------------
    # preemption-graceful shutdown (docs/RESILIENCE.md)

    def request_preempt(self) -> None:
        """Begin a graceful preemption: the step loop finishes its
        in-flight step, writes an emergency checkpoint, drains telemetry,
        and ``fit`` returns with ``self.preempted`` set (the CLI maps it
        to exit status 43).  Also arms the grace-window watchdog: if the
        graceful path has not completed within
        ``TrainConfig.preempt_grace_s``, the process hard-exits — the
        last DURABLE checkpoint still resumes (writes are atomic), which
        beats being SIGKILLed mid-write by an impatient scheduler.
        Idempotent; safe from signal handlers and other threads."""
        if self._preempt.is_set():
            return
        self._preempt.set()
        if jax.process_index() == 0:
            write_breadcrumb(
                self.workdir,
                "preempt_requested",
                grace_s=self.cfg.train.preempt_grace_s,
            )
        t = threading.Timer(
            max(self.cfg.train.preempt_grace_s, 0.1), self._grace_expired
        )
        t.daemon = True
        t.start()
        self._grace_timer = t

    def _grace_expired(self) -> None:
        if self._preempt_done.is_set():
            return
        if jax.process_index() == 0:
            write_breadcrumb(self.workdir, "preempt_timeout")
        print(
            f"[preempt] grace window "
            f"({self.cfg.train.preempt_grace_s:.0f}s) expired before the "
            f"emergency checkpoint completed — hard exit; resuming from "
            f"the last durable checkpoint",
            flush=True,
        )
        os._exit(EXIT_PREEMPTED)

    def _graceful_preempt(self, epoch: int, steps_done: int) -> None:
        """The grace-window body: emergency checkpoint (with the exact
        mid-epoch position) + telemetry drain.  Runs between steps, so the
        state is at an optimizer-step boundary — the unit the skip-replay
        resume reasons in."""
        steps_per_epoch = len(self.loader)
        # State at an epoch boundary (steps_done 0 or a full epoch) needs
        # no mid-epoch bookkeeping; anything else records the position.
        completed = epoch if steps_done >= steps_per_epoch else epoch - 1
        meta = {
            "epoch": completed,
            "config": self.cfg.to_dict(),
            "input_channels": int(self.train_ds.image_shape[-1]),
            "preempted": True,
        }
        if 0 < steps_done < steps_per_epoch:
            meta["mid_epoch_steps_done"] = steps_done
        with self.watchdog.paused("preempt_checkpoint"):
            state = self.layout.canonical(self.state)
            step = int(jax.device_get(self.state.step))
            lin = obs_lineage.make_lineage(
                step, run_id=self.run_id, config_hash_hex=self.config_hash
            )
            meta["lineage"] = lin
            self.checkpointer.save(self.ckpt_dir, state, step=step, metadata=meta)
            # The emergency checkpoint must be DURABLE before the process
            # exits — this is the one save that cannot overlap anything.
            self.checkpointer.wait()
        self.logger.log(
            {
                "kind": "preempt",
                "epoch": epoch,
                "steps_done": steps_done,
                "ckpt_step": step,
            },
            echo=True,
        )
        self._log_lineage(
            "checkpoint_saved", lin, epoch=epoch, preempted=True
        )
        if jax.process_index() == 0:
            write_breadcrumb(
                self.workdir,
                "preempted",
                epoch=epoch,
                steps_done=steps_done,
                ckpt_step=step,
            )
        self.preempted = True
        self._preempt_done.set()
        if self._grace_timer is not None:
            self._grace_timer.cancel()
            self._grace_timer = None

    # ------------------------------------------------------------------

    def train_epoch(self, epoch: int) -> Dict[str, float]:
        # Every host phase from here to the record is a stage
        # (StageTimer.stage: a span on the profiler's clock, a t_<stage>_s
        # mean in the record, a Tracer span when train.trace is on), so a
        # device trace can say which of them the chip waited for.
        stage = self.timer.stage
        t_epoch = time.perf_counter()
        with stage("epoch_head", epoch=epoch):
            self.loader.set_epoch(epoch)
            self._comm_probed_epoch = False
            losses, accs, extras = [], [], []
            it = iter(self.loader)
            step_idx = 0
            skipped = 0
            if self._skip_steps and epoch == self._skip_epoch:
                # Skip-replay resume from an emergency (mid-epoch)
                # checkpoint: the restored state already contains these
                # optimizer steps, so draw-and-discard the same
                # deterministic batches the interrupted run consumed.
                # Costs host gather only — no compute — and keeps the
                # resumed trajectory bit-identical to an uninterrupted
                # run's (tests/test_preemption.py pins it).
                for _ in range(self._skip_steps):
                    self.watchdog.beat("resume_skip")
                    if next(it, None) is None:
                        break
                    skipped += 1
                self._skip_steps = 0
        sync_every = self.cfg.train.trace_sync_every_steps
        while True:
            # Stage-resolved timing: the structured version of the
            # reference's per-stage time.time() prints (кластер.py:265-440).
            # "data" = host wait for the next uploaded super-batch (overlaps
            # compute via the loader's prefetch); "step" = compiled SPMD
            # step dispatch.  Both stages double as spans when tracing.
            if self._chaos is not None:
                self._chaos.on_data_fetch()
            self.watchdog.beat("data")
            with stage("data", epoch=epoch, step=step_idx):
                batch = next(it, None)
                if (
                    batch is None
                    and epoch + 1 < self._fit_until
                    and not self._preempt.is_set()
                ):
                    # The next epoch's first gather depends on nothing but
                    # (seed, epoch): queued behind this epoch's last step,
                    # the device runs it while the host fetches the
                    # metrics, logs and publishes (a no-op for a host-fed
                    # loader).
                    self.loader.prefetch(epoch + 1)
            if batch is None:
                break
            self.watchdog.beat("step")
            with stage("step", epoch=epoch, step=step_idx):
                self.state, metrics = self.train_step(self.state, *batch)
            losses.append(metrics["loss"])
            accs.append(metrics["pixel_acc"])
            # Whatever the model counts beside loss and accuracy (the routing
            # counters of models/lfm2_moe.py): fetched with them, below.
            extras.append(
                {k: v for k, v in metrics.items() if k not in _STEP_METRICS}
            )
            step_idx += 1
            if self.comm is not None:
                # Exact logical collective bytes for this optimizer step
                # (obs/comm.py) — a handful of counter increments.
                self.comm.on_step()
            if self._chaos is not None:
                self._chaos_step += 1
                # kill/stall act inside on_step; preempt comes back as an
                # action so it runs the trainer's OWN graceful path.
                if "preempt" in self._chaos.on_step(self._chaos_step):
                    self.request_preempt()
            if self._preempt.is_set():
                # Step boundary reached with a preemption pending: stop
                # here — fit()'s handler writes the emergency checkpoint
                # recording this exact position.
                raise PreemptedRun(epoch, skipped + step_idx)
            # Sampled sync: every K steps a traced run blocks on the step
            # output so the trace carries REAL step latency at that cadence
            # — syncing every step would serialize the async dispatch
            # pipeline and measure a run that doesn't exist.
            if self.tracer.enabled and sync_every and step_idx % sync_every == 0:
                with stage("step_sync", epoch=epoch, step=step_idx):
                    jax.block_until_ready(metrics["loss"])
                # Sampled fenced comm-time measurement, piggybacking on
                # the sync cadence (the pipeline is already drained here,
                # so the probe doesn't serialize dispatch): at most once
                # per epoch, feeding ddlpc_comm_fraction / the overlap-
                # headroom baseline (obs/comm.py).
                if self._comm_probe is not None and not self._comm_probed_epoch:
                    self._comm_probed_epoch = True
                    t_probe = time.perf_counter()
                    try:
                        with stage("comm_probe", epoch=epoch):
                            self.comm.record_probe(self._comm_probe())
                    except Exception as e:  # accounting never kills the run
                        warnings.warn(
                            f"comm probe failed ({type(e).__name__}: {e}); "
                            f"disabling for this run",
                            stacklevel=2,
                        )
                        self._comm_probe = None
                    if self.perf is not None:
                        self.perf.debit(
                            "probe", time.perf_counter() - t_probe
                        )
            # Drive the on-demand profiler (no-op unless armed); the sync
            # closure drains this step's dispatch queue INTO the capture.
            self.profiler.step_done(
                sync=lambda m=metrics: jax.block_until_ready(m["loss"])
            )
        # One host sync per epoch (metrics stayed on device inside the loop).
        # Single batched device_get: per-element float() would cost one
        # blocking host round trip PER STEP and drain the dispatch queue
        # each time.
        if not losses:
            # A zero-step epoch (empty dataset / loader) would otherwise
            # record NaN metrics and a meaningless step_time — fail loudly
            # with the cause instead (ADVICE r3).
            raise RuntimeError(
                f"epoch {epoch} produced 0 training steps: dataset has "
                f"{len(self.train_ds)} tiles against super-batch "
                f"{self.loader.super_batch} — the loader yielded no batches"
            )
        self.watchdog.beat("epoch_metrics_fetch")
        with stage("metrics_fetch", epoch=epoch):
            losses, accs, extras = jax.device_get((losses, accs, extras))
        with stage("epoch_tail", epoch=epoch):
            losses = [float(l) for l in losses]
            accs = [float(a) for a in accs]
            epoch_time = time.perf_counter() - t_epoch
            steps = len(losses)
            record = {
                "epoch": epoch,
                "loss": float(np.mean(losses)),
                "pixel_acc": float(np.mean(accs)),
                "epoch_time_s": epoch_time,
                # Mean time per sync step — the reference's "среднее время
                # на батч" line (кластер.py:767-770).
                "step_time_s": epoch_time / steps,
                # Compute throughput: tile-instances processed (wrap-fill
                # duplicates included — they are real forward/backward
                # work).  ``steps`` not len(loader): a skip-replay resume
                # computes only the remaining steps of its first epoch.
                "tiles_per_s": steps * self.loader.super_batch / epoch_time,
                # 1 where the epoch's first batch came from the previous
                # epoch's lookahead: a fit of n epochs means (n-1)/n.
                "loader_lookahead": float(self.loader.lookahead_used),
            }
            # Model counters, one value per step (already summed or maximised
            # over micro-batches and replicas: train_step.py:_reduce_counters):
            # the epoch's record holds the mean over its steps, and the
            # registry the same as gauges.
            for name in extras[0]:
                record[name] = float(np.mean([float(e[name]) for e in extras]))
                self.registry.gauge(
                    f"ddlpc_{name}", "model counter, per optimizer step"
                ).set(record[name])
            if skipped:
                # Flag the partial epoch: its loss/acc means cover only the
                # post-resume steps (the state is still exact — the skipped
                # steps were already applied before the preemption).
                record["resumed_mid_epoch_at_step"] = skipped
            # When the super-batch exceeds the dataset, an "epoch" processes
            # each tile wrap_factor times — record it so tiles_per_s cannot
            # read as dataset coverage (VERDICT r2: flagship super-batch
            # 2048 vs 97 tiles counts each tile ~21x per epoch).
            wrap = len(self.loader) * self.loader.super_batch / max(len(self.train_ds), 1)
            if wrap > 1.0 + 1e-9:
                record["wrap_fill_factor"] = round(wrap, 2)
            # Stage means since the last record.  Stages that close after
            # this line — epoch_tail itself and fit()'s epoch, log,
            # perf_publish, evaluate, checkpoint, dump stages — land in the
            # NEXT epoch's record (docs/OBSERVABILITY.md).
            record.update(_record_keys(self.timer.means()))
            # The compile ledger's growth since the last record, or since
            # fit() began: programs compiled after this line (evaluate, save)
            # count in the next record, like the stages.
            record.update(self._compiles.take())
            # Construction's phases, once in the Trainer's life.
            record.update(self._init_times)
            self._init_times = {}
            if self.perf is not None:
                # Goodput accounting from the epoch's disjoint training-thread
                # intervals: the compiled step dispatch is productive, the
                # host wait for the next super-batch is a 'data' debit
                # (loader_gather/cast/upload run on producer threads and
                # overlap the step — they are throughput, not wall debits).
                totals = self.timer.summary()
                if "moe_rows_offered" in record:
                    self.perf.routed(record["moe_rows_routed"], record["moe_rows_offered"])
                self.perf.productive(totals.get("step", 0.0), steps)
                self.perf.debit("data", totals.get("data", 0.0))
            self.timer.reset()
        return record

    def evaluate(self) -> Dict[str, float]:
        """Held-out mIoU/accuracy/loss — the metric path the reference lacks
        (it splits a test set and never touches it, SURVEY §3.3)."""
        if len(self.test_ds) == 0:
            return {}
        # Keep the per-batch sums ON DEVICE and fetch once per evaluation:
        # the old per-batch `cm += np.asarray(...)` forced one blocking host
        # round trip per eval batch.  Same pattern as train_epoch's loss
        # list: collect
        # the device arrays, one batched device_get at the end, then the
        # exact float64 accumulation happens on the host — per-batch fp32
        # confusion entries are exact (a batch holds < 2^24 pixels), and
        # no device dtype has to survive a whole evaluation's total (a
        # running uint32 would wrap past 2^32 pixels on Cityscapes-scale
        # splits; float64 is unavailable without jax x64).
        per_batch = []
        # Strip the optimizer state from the eval input: the eval steps pin
        # the state replicated, and resharding sharded Adam moments into an
        # unused argument would all-gather them once per eval batch.
        # Under zero3 the run-layout params are [N, K] chunks — gather
        # them once per evaluation (layout.full_params is the identity
        # for every other layout), not once per batch.
        eval_state = self.state.replace(
            params=self.layout.full_params(self.state), opt_state=()
        )
        for images, labels in eval_batches(
            self.test_ds,
            self.mesh,
            global_batch=self.global_micro_batch,
            data_axis=self.cfg.parallel.data_axis_name,
            space_axis=self.cfg.parallel.space_axis_name if self.spatial else None,
        ):
            self.watchdog.beat("eval")
            out = self.eval_step(eval_state, images, labels)
            per_batch.append(
                (out["confusion"], out["loss_sum"], out["pixel_count"])
            )
        # The batched fetch waits for the WHOLE evaluation's queued device
        # compute (dispatches above are async), which can dwarf the
        # step-sized stall timeout — suspend detection rather than mis-size
        # it, exactly like the checkpoint/image-dump paths.
        with self.watchdog.paused("eval_metrics_fetch"):
            per_batch = jax.device_get(per_batch)
        cm = np.zeros((self.cfg.model.num_classes,) * 2, np.float64)
        loss_sum = 0.0
        pixels = 0.0
        for conf, nll, px in per_batch:
            cm += np.asarray(conf, np.float64)
            loss_sum += float(nll)
            pixels += float(px)
        return {
            "val_loss": loss_sum / max(pixels, 1.0),
            "val_pixel_acc": float(accuracy_from_confusion(cm)),
            "val_miou": float(mean_iou(cm)),
            "val_iou_per_class": [
                round(float(v), 4) for v in np.asarray(iou_per_class(cm))
            ],
        }

    def dump_images(self, epoch: int) -> None:
        n = min(self.cfg.train.dump_images_per_epoch, len(self.test_ds))
        if n <= 0:
            return
        images = self.test_ds.images[:n]
        labels = self.test_ds.labels[:n]
        # full_params: identity except under zero3, where the run-layout
        # params are chunks the predict fn cannot apply.
        predict_state = self.state.replace(
            params=self.layout.full_params(self.state)
        )
        preds = np.asarray(self.predict(predict_state, images))
        dump_prediction_triples(
            self.workdir,
            images,
            labels,
            preds,
            self.cfg.model.num_classes,
            epoch,
            max_samples=n,
        )

    def _log_lineage(self, event: str, lin: dict, **fields) -> None:
        """Append a flat ``kind="lineage"`` record to metrics.jsonl — the
        train-side anchor obs/merge.py joins serve-side streams onto."""
        if jax.process_index() != 0:
            return
        self.logger.log(
            {
                "kind": "lineage",
                "event": event,
                **obs_lineage.flatten(lin),
                **fields,
            },
            echo=False,
        )

    def save(self, epoch: int) -> None:
        # Checkpoints store the canonical gathered layout — under a sharded
        # run layout this all-gathers the moments ONCE per save (a
        # transient; the steady state never holds them replicated), and the
        # on-disk blob restores bit-identically into either layout.  The
        # gather is a collective: every process runs it, then only process
        # 0 snapshots/writes (AsyncCheckpointer's gate).
        step = int(jax.device_get(self.state.step))
        lin = obs_lineage.make_lineage(
            step, run_id=self.run_id, config_hash_hex=self.config_hash
        )
        with self.timer.stage(
            "checkpoint_snapshot",
            epoch=epoch,
            lineage_id=lin["lineage_id"],
            step=step,
        ):
            state = self.layout.canonical(self.state)
            self.checkpointer.save(
                self.ckpt_dir,
                state,
                step=step,
                metadata={
                    "epoch": epoch,
                    "config": self.cfg.to_dict(),
                    # The predict CLI rebuilds its restore target from this —
                    # channels come from the dataset, not the config (ADVICE r1).
                    "input_channels": int(self.train_ds.image_shape[-1]),
                    "lineage": lin,
                },
            )
        self._log_lineage("checkpoint_saved", lin, epoch=epoch)
        if jax.process_index() == 0:
            # Progress breadcrumb: the supervisor resets its crash-loop
            # counter when this step advances between attempts.
            write_breadcrumb(
                self.workdir,
                "running",
                epoch=epoch,
                last_ckpt_step=int(jax.device_get(self.state.step)),
            )

    def fit(self, epochs: Optional[int] = None) -> Dict[str, float]:
        """Run the full training; returns the last epoch's metrics record."""
        cfg = self.cfg.train
        epochs = epochs if epochs is not None else cfg.epochs
        if epochs != cfg.epochs and cfg.lr_schedule != "constant":
            # The decaying schedule's horizon was built from cfg.epochs; an
            # overridden epoch budget would otherwise clamp at LR 0 past the
            # configured horizon (or end early).  Rebuild over the actual
            # horizon — the optimizer state structure is unchanged.
            self.tx = build_optimizer(
                cfg, total_steps=epochs * len(self.loader)
            )
            self.train_step = self._build_train_step()
        record: Dict[str, float] = {}
        stage = self.timer.stage
        # SIGUSR2 → arm the on-demand profiler (kill -USR2 <pid> against a
        # live run; the next profile_steps steps are captured and
        # aggregated).  Installable only from the main thread — tests and
        # embedded fits from worker threads skip the handler and use
        # /debug/trace or profiler.arm() directly.
        prev_handler = None
        sigusr2 = getattr(signal, "SIGUSR2", None)
        if sigusr2 is not None:
            try:
                prev_handler = signal.signal(
                    sigusr2, lambda signum, frame: self.profiler.arm()
                )
            except ValueError:
                pass  # not the main thread
        # SIGTERM → graceful preemption (docs/RESILIENCE.md): finish the
        # in-flight step, emergency-checkpoint, drain, exit 43.  Main
        # thread only, same constraint as SIGUSR2; embedded fits preempt
        # via request_preempt() directly.  NOTE (multi-host): the graceful
        # save runs collectives, so it is only safe when the scheduler
        # signals EVERY process — the normal preemption contract; a
        # partial signal ends in the grace-window hard exit instead.
        prev_term = None
        sigterm = getattr(signal, "SIGTERM", None)
        if sigterm is not None:
            try:
                prev_term = signal.signal(
                    sigterm, lambda signum, frame: self.request_preempt()
                )
            except ValueError:
                pass  # not the main thread
        if jax.process_index() == 0:
            write_breadcrumb(
                self.workdir, "running", start_epoch=self.start_epoch,
                epochs=epochs,
            )
        if self.perf is not None:
            self.perf.start()
        # What the caller compiled since the last record is not this fit's.
        self._compiles.take()
        self._fit_until = epochs
        try:
            with self.watchdog:
                try:
                    for epoch in range(self.start_epoch, epochs):
                        if self._preempt.is_set():
                            # Preemption arrived between epochs (or during
                            # the post-epoch eval/checkpoint/dump phases).
                            raise PreemptedRun(epoch, 0)
                        with stage("epoch", epoch=epoch):
                            with maybe_profile(
                                os.path.join(self.workdir, "profile"),
                                enabled=epoch == cfg.profile_epoch,
                            ):
                                record = self.train_epoch(epoch)
                        if cfg.eval_every_epochs and (epoch + 1) % cfg.eval_every_epochs == 0:
                            # evaluate() beats per batch; per-batch eval cost is
                            # step-like, so the step-sized timeout applies.
                            t_eval = time.perf_counter()
                            with stage("evaluate", epoch=epoch):
                                record.update(self.evaluate())
                            if self.perf is not None:
                                self.perf.debit(
                                    "eval", time.perf_counter() - t_eval
                                )
                        if self._chaos is not None:
                            # nan@N fault: poison what the health detectors
                            # see (the stream logs the same poisoned value).
                            record = self._chaos.corrupt_record(record)
                        with stage("log", epoch=epoch):
                            self.logger.log(record)
                            # Health detectors see exactly what the stream saw.
                            self.health.observe_train(record)
                        if cfg.checkpoint_every_epochs and (
                            epoch + 1
                        ) % cfg.checkpoint_every_epochs == 0:
                            # Snapshot/serialization time is unrelated to the
                            # step-sized timeout — suspend detection rather than
                            # mis-size it.  Under checkpoint_async this blocks
                            # only for the host snapshot (plus a barrier if the
                            # PREVIOUS write is somehow still running); the write
                            # itself overlaps the next epoch.
                            t_ckpt = time.perf_counter()
                            with self.watchdog.paused("checkpoint"):
                                self.save(epoch)
                            if self.perf is not None:
                                # The training-thread STALL (snapshot +
                                # barrier), not the background write.
                                self.perf.debit(
                                    "checkpoint", time.perf_counter() - t_ckpt
                                )
                        if self.perf is not None:
                            # Refresh ddlpc_mfu/ddlpc_goodput and append the
                            # flat kind="perf"/"comm" accounting records
                            # (scripts/perf_report.py renders these).
                            with stage("perf_publish", epoch=epoch):
                                self.logger.log(
                                    self.perf.publish(
                                        step_time_s=record.get("step_time_s")
                                    ),
                                    echo=False,
                                )
                                if self.comm is not None:
                                    self.logger.log(
                                        self.comm.publish(
                                            step_time_s=record.get(
                                                "step_time_s"
                                            )
                                        ),
                                        echo=False,
                                    )
                        if cfg.dump_images_per_epoch:
                            with self.watchdog.paused("image_dump"), stage(
                                "dump_images", epoch=epoch
                            ):
                                self.dump_images(epoch)
                    else:
                        if jax.process_index() == 0:
                            write_breadcrumb(
                                self.workdir, "done", epochs=epochs
                            )
                except PreemptedRun as p:
                    self._graceful_preempt(p.epoch, p.steps_done)
                finally:
                    # Exit barrier: fit() must not return (or unwind) with a
                    # checkpoint still in flight — this also re-raises a writer
                    # failure on the training thread.  close() additionally
                    # shuts the writer thread down (one leaked non-daemon
                    # thread per Trainer otherwise); a later save()/fit() on
                    # this Trainer transparently respawns it.
                    with self.watchdog.paused("checkpoint_flush"):
                        with stage("checkpoint_barrier"):
                            self.checkpointer.close()
        finally:
            self._fit_until = 0
            # A fit that leaves early (a preemption or a health detector
            # after the last epoch-end lookahead) holds no batch in HBM.
            self.loader.drop_lookahead()
            if prev_handler is not None:
                try:
                    signal.signal(sigusr2, prev_handler)
                except ValueError:
                    pass
            if prev_term is not None:
                try:
                    signal.signal(sigterm, prev_term)
                except ValueError:
                    pass
            # A pending grace timer must not outlive fit (it would hard-
            # exit a process that finished its graceful path long ago).
            self._preempt_done.set()
            if self._grace_timer is not None:
                self._grace_timer.cancel()
                self._grace_timer = None
            # A capture the run ended mid-way through still produces its
            # report over the steps that actually happened.
            self.profiler.finalize(
                sync=lambda: jax.block_until_ready(self.state.step)
            )
            # Traced runs drop a Perfetto-loadable trace.json in the
            # workdir at every fit() exit (flush is idempotent; the tracer
            # stays usable for a subsequent fit on this Trainer).
            self.tracer.flush()
        return record
