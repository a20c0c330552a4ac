"""Performance regression gate: replay the cheap bench arms vs a baseline.

The repo's perf evidence used to die in one-shot committed JSON; this gate
makes the cheap arms REPLAYABLE and COMPARABLE: it re-measures

- ``update_step_ms``   — the weight-update-only compiled program
  (``bench.measure_update_ms``: grad sync + codec + Adam + — sharded —
  the params all-gather) on a tiny model;
- ``train_step_ms``    — the full compiled train step (fwd/bwd ×
  sync_period + sync + update) on the same tiny model;
- ``comm_fraction``    — the fenced comm-only probe (obs/comm.py) over
  ``train_step_ms``: the step attribution number the comm/compute
  overlap work is judged against;
- ``comm_fraction_overlapped`` — the same probe/step pair measured with
  ``CompressionConfig.bucket_mb`` set, i.e. the sync issued as
  per-bucket fused quantized collectives (the overlapped spelling);
- ``loader_tiles_per_s`` — the ShardedLoader host gather→cast→upload
  path on a synthetic dataset;
- ``serve_p99_ms``     — the closed-loop serving load
  (scripts/serve_bench.py) against a tiny synthetic checkpoint;
- ``fleet_p99_ms``     — the routed FLEET path: the same load dispatched
  by the router over 2 engine-replica subprocesses
  (scripts/serve_bench.py --fleet), so retries/hedging/breaker machinery
  is inside the measured path;
- ``cache_hit_p99_ms`` — the repeated-scene path answered from the
  router's content-addressed response cache (serve/cache.py): the
  latency floor caching buys, and the hot-path number a lock or
  hashing regression would move;

and fails loudly (exit 1, naming the metric) when any gated metric
regresses past its tolerance band versus the committed
``docs/perf/baseline.json``.  Improvements always pass (the check is
one-sided).  Baselines are HOST-BOUND: re-baseline with
``--update-baseline`` when the hardware changes (the env block records
what the numbers were measured on).

Modes:
  python scripts/perf_gate.py                      # measure + compare
  python scripts/perf_gate.py --update-baseline    # measure + rewrite baseline
  python scripts/perf_gate.py --smoke              # no measurement: validate
        the committed baseline's schema and self-check the comparison
        logic (a synthetic regression must be caught) — tier-1 runs this,
        so a broken gate or stale baseline schema fails the suite.
  python scripts/perf_gate.py --inject update_step_ms=1.15
        # multiply a measured value (regression-injection demonstration)

Exit status: 0 pass, 1 regression/self-check failure (each printed as
``perf_gate: REGRESSION <metric>: ...``), 2 usage/baseline error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "scripts"))
from ddlpc_tpu.utils.fsio import atomic_write_json  # noqa: E402

BASELINE_SCHEMA = 1
DEFAULT_BASELINE = os.path.join(_REPO, "docs", "perf", "baseline.json")

# Gated metrics and their committed tolerance bands.  update_step_ms is
# deliberately tight (the acceptance bar: a >=10% regression must fail);
# loader/serve arms carry more CPU-host noise and get wider bands.  A
# failing gate on an unchanged tree means host noise — rerun once; twice
# means believe it.
GATED = {
    "update_step_ms": dict(unit="ms", direction="lower", tolerance=0.08),
    "train_step_ms": dict(unit="ms", direction="lower", tolerance=0.25),
    "comm_fraction": dict(unit="ratio", direction="lower", tolerance=0.50),
    # The overlapped arm (ISSUE 18): the same comm-only probe and train
    # step measured with CompressionConfig.bucket_mb set, i.e. the sync
    # issued as per-bucket fused collectives.  Gated so the overlap
    # machinery cannot silently regress back toward the whole-tree
    # fraction; compared against comm_fraction in docs/PERF.md "Overlap".
    "comm_fraction_overlapped": dict(
        unit="ratio", direction="lower", tolerance=0.50
    ),
    "loader_tiles_per_s": dict(
        unit="tiles/s", direction="higher", tolerance=0.50
    ),
    "serve_p99_ms": dict(unit="ms", direction="lower", tolerance=0.60),
    # Fleet path: router dispatch over 2 engine-replica subprocesses
    # (scripts/serve_bench.py --fleet).  Carries subprocess + HTTP + CPU
    # scheduling noise on top of the engine, hence the widest band.
    "fleet_p99_ms": dict(unit="ms", direction="lower", tolerance=0.75),
    # Per-replica accelerator throughput on the same fleet arm — the
    # ROADMAP acceptance metric for the continuous-batching/quantized
    # serving work; gated so it cannot silently regress either.
    "fleet_tiles_per_s_per_replica": dict(
        unit="tiles/s", direction="higher", tolerance=0.50
    ),
    # Repeated-scene cache-hit path (ISSUE 16): router dispatch answered
    # from the content-addressed response cache — lookup + accounting,
    # no replica round-trip.  Sub-ms numbers on a noisy CPU host, hence
    # the generous band; what it really guards is the ORDER of magnitude
    # (a lock or hashing regression shows up as 10×, not 1.2×).
    "cache_hit_p99_ms": dict(unit="ms", direction="lower", tolerance=0.75),
}


# --------------------------------------------------------------------------
# comparison logic (pure — unit-tested and self-checked by --smoke)
# --------------------------------------------------------------------------


# Source modules on the measured path of the step/comm arms: a baseline
# whose stamp predates an edit to any of these describes code that no
# longer runs — the gate must SAY so (ISSUE 18 bugfix), not hold the old
# bands with a straight face.  Relative to the repo root.
MEASURED_PATH_MODULES = (
    "ddlpc_tpu/config.py",
    "ddlpc_tpu/obs/comm.py",
    "ddlpc_tpu/ops/pallas_quantize.py",
    "ddlpc_tpu/ops/quantize.py",
    "ddlpc_tpu/parallel/bucketing.py",
    "ddlpc_tpu/parallel/compressed_allreduce.py",
    "ddlpc_tpu/parallel/grad_sync.py",
    "ddlpc_tpu/parallel/partition.py",
    "ddlpc_tpu/parallel/shard_update.py",
    "ddlpc_tpu/parallel/train_step.py",
    "bench.py",
)


def measured_path_files(repo: str = _REPO) -> List[str]:
    return [os.path.join(repo, rel) for rel in MEASURED_PATH_MODULES]


def host_fingerprint() -> Dict[str, object]:
    """What the baseline's numbers were measured ON.  Compared (not
    hashed) so a mismatch warning can say WHICH dimension moved."""
    import platform
    import socket

    return {
        "hostname": socket.gethostname(),
        "machine": platform.machine(),
        "host_cores": os.cpu_count(),
    }


def baseline_warnings(
    baseline: dict, max_age_days: float,
    now: Optional[float] = None,
    current_host: Optional[Dict[str, object]] = None,
    measured_paths: Optional[List[str]] = None,
) -> List[str]:
    """Staleness/provenance warnings for a loaded baseline (ISSUE 14
    satellite).  NON-FATAL by design — the gate still compares — but loud:
    with the driver bench unreachable this gate is the only live
    regression signal, and a silently stale or foreign-host baseline
    would hold the wrong bands with a straight face."""
    warnings: List[str] = []
    now = time.time() if now is None else now
    host = current_host if current_host is not None else host_fingerprint()
    generated_at = baseline.get("generated_at")
    if not isinstance(generated_at, (int, float)) or isinstance(
        generated_at, bool
    ):
        warnings.append(
            "baseline has no generated_at stamp (predates age tracking) — "
            "regenerate with --update-baseline to arm staleness checks"
        )
    else:
        age_days = (now - float(generated_at)) / 86400.0
        if age_days > max_age_days:
            warnings.append(
                f"baseline is {age_days:.1f} days old (> {max_age_days:g}) "
                f"— its tolerance bands may no longer describe this tree; "
                f"regenerate with --update-baseline"
            )
        if measured_paths:
            # mtime vs stamp: a baseline older than an edit to a module
            # on the measured path pins numbers the current code never
            # produced.  Loud, never fatal — same policy as age.
            newer = []
            for path in measured_paths:
                try:
                    mtime = os.path.getmtime(path)
                except OSError:
                    continue
                if mtime > float(generated_at):
                    newer.append(os.path.relpath(path, _REPO))
            if newer:
                warnings.append(
                    "baseline predates changes to measured-path "
                    f"module(s): {', '.join(sorted(newer))} — its numbers "
                    "describe code that no longer runs; re-measure with "
                    "--update-baseline"
                )
    recorded = baseline.get("host")
    if not isinstance(recorded, dict):
        warnings.append(
            "baseline has no host fingerprint — cannot verify it was "
            "measured on THIS host; regenerate with --update-baseline"
        )
    else:
        for key, current in host.items():
            stamped = recorded.get(key)
            if stamped is not None and stamped != current:
                warnings.append(
                    f"baseline was measured on a different host "
                    f"({key}: baseline {stamped!r} vs this host "
                    f"{current!r}) — baselines are host-bound; regenerate "
                    f"with --update-baseline"
                )
    return warnings


def validate_baseline(obj: object) -> List[str]:
    """Schema errors for a decoded baseline document (empty = valid)."""
    errs: List[str] = []
    if not isinstance(obj, dict):
        return ["baseline is not a JSON object"]
    if obj.get("schema") != BASELINE_SCHEMA:
        errs.append(
            f"baseline schema {obj.get('schema')!r} != {BASELINE_SCHEMA}"
        )
    metrics = obj.get("metrics")
    if not isinstance(metrics, dict) or not metrics:
        return errs + ["baseline has no 'metrics' table"]
    for name, spec in metrics.items():
        if not isinstance(spec, dict):
            errs.append(f"metric {name!r}: spec is not an object")
            continue
        v = spec.get("value")
        if not isinstance(v, (int, float)) or isinstance(v, bool) or v <= 0:
            errs.append(f"metric {name!r}: value must be a positive number")
        tol = spec.get("tolerance")
        if not isinstance(tol, (int, float)) or not 0 < tol < 1:
            errs.append(f"metric {name!r}: tolerance must be in (0, 1)")
        if spec.get("direction") not in ("lower", "higher"):
            errs.append(f"metric {name!r}: direction must be lower|higher")
    return errs


def compare(
    baseline_metrics: Dict[str, dict],
    measured: Dict[str, float],
    inject: Optional[Dict[str, float]] = None,
) -> List[str]:
    """``REGRESSION <metric>: ...`` strings for every gated metric in
    ``measured`` that regressed past its band.  Metrics absent from
    ``measured`` (a ``--skip-*`` arm) are not compared; improvements pass.
    ``inject`` multiplies measured values first (the demonstration knob).
    """
    failures: List[str] = []
    inject = inject or {}
    for name, spec in sorted(baseline_metrics.items()):
        if name not in measured:
            continue
        base = float(spec["value"])
        tol = float(spec["tolerance"])
        m = float(measured[name]) * float(inject.get(name, 1.0))
        if spec["direction"] == "lower":
            reg = (m - base) / base
        else:
            reg = (base - m) / base
        if reg > tol:
            failures.append(
                f"REGRESSION {name}: measured {m:.4g} {spec.get('unit', '')} "
                f"vs baseline {base:.4g} "
                f"({'+' if reg >= 0 else ''}{reg * 100:.1f}% worse > "
                f"tolerance {tol * 100:.0f}%)"
            )
    return failures


def smoke(
    baseline_path: str, max_age_days: float = 30.0,
    program_baseline_path: Optional[str] = None,
) -> int:
    """Validate the committed baseline + self-check the gate logic.

    No measurement, no jax import — cheap enough for tier-1.  Fails (1)
    if the baseline is missing/invalid or if a synthetic regression of
    2× tolerance on any gated metric slips through the comparator.
    Staleness/foreign-host findings print as warnings (the tier-1 run
    must not start failing merely because a month passed — but it must
    SAY so on every run until the baseline is regenerated).

    Also validates the compiled-program contract baseline
    (``docs/analysis/program_baseline.json``, scripts/program_audit.py)
    — schema fatal, staleness loud — so the program gate cannot rot
    unnoticed between full audit runs.
    """
    try:
        with open(baseline_path) as f:
            baseline = json.load(f)
    except (OSError, ValueError) as e:
        print(f"perf_gate --smoke: cannot load {baseline_path}: {e}")
        return 1
    errs = validate_baseline(baseline)
    if errs:
        for e in errs:
            print(f"perf_gate --smoke: {e}")
        return 1
    for w in baseline_warnings(
        baseline, max_age_days, measured_paths=measured_path_files()
    ):
        print(f"perf_gate --smoke: WARNING: {w}", file=sys.stderr)

    from ddlpc_tpu.analysis.program import (  # jax-import-free validators
        DEFAULT_BASELINE as PROGRAM_BASELINE,
        baseline_warnings as program_warnings,
        validate_program_baseline,
    )

    prog_path = program_baseline_path or PROGRAM_BASELINE
    try:
        with open(prog_path) as f:
            prog_baseline = json.load(f)
    except (OSError, ValueError) as e:
        print(f"perf_gate --smoke: cannot load program baseline "
              f"{prog_path}: {e}")
        return 1
    prog_errs = validate_program_baseline(prog_baseline)
    if prog_errs:
        for e in prog_errs:
            print(f"perf_gate --smoke: program baseline: {e}")
        return 1
    for w in program_warnings(prog_baseline):
        print(f"perf_gate --smoke: WARNING: {w}", file=sys.stderr)
    metrics = baseline["metrics"]
    clean = {n: float(s["value"]) for n, s in metrics.items()}
    if compare(metrics, clean):
        print("perf_gate --smoke: baseline fails against itself")
        return 1
    for name, spec in metrics.items():
        # Inject a regression 1.5× past the band (capped below 100% for
        # higher-is-better metrics, where regression saturates at 1).
        reg = min(1.5 * float(spec["tolerance"]), 0.95)
        if spec["direction"] == "higher":
            factor = 1.0 - reg
        else:
            factor = 1.0 + reg
        fails = compare(metrics, clean, inject={name: factor})
        if not any(name in f for f in fails):
            print(
                f"perf_gate --smoke: injected {factor:.2f}x regression on "
                f"{name!r} was NOT caught"
            )
            return 1
    print(
        f"perf_gate --smoke: baseline OK ({len(metrics)} gated metric(s), "
        f"regression self-check passed; program baseline OK, "
        f"{len(prog_baseline.get('programs', {}))} program(s))"
    )
    return 0


# --------------------------------------------------------------------------
# measurement arms (tiny, CPU-friendly — minutes, not hours)
# --------------------------------------------------------------------------


def _tiny_cfg():
    from ddlpc_tpu.config import (
        CompressionConfig,
        DataConfig,
        ExperimentConfig,
        ModelConfig,
        TrainConfig,
    )

    return ExperimentConfig(
        model=ModelConfig(
            features=(8, 16), bottleneck_features=16, num_classes=6
        ),
        data=DataConfig(
            dataset="synthetic", image_size=(32, 32), num_classes=6,
            synthetic_len=64,
        ),
        train=TrainConfig(micro_batch_size=2, sync_period=2),
        compression=CompressionConfig(mode="float16"),
    )


# Bucket target for the overlapped arm: the tiny model is ~0.074 MiB of
# fp32 gradient, so 0.02 MiB yields several buckets — the same partition
# the program auditor's bucketed arms pin (analysis/program.py).
OVERLAP_BUCKET_MB = 0.02


def arm_step_and_comm(rounds: int) -> Dict[str, float]:
    """update_step_ms, train_step_ms, comm_ms_per_step, comm_fraction,
    overlap_headroom_ms on the tiny config over all available devices,
    plus the overlapped arm: the same comm probe and train step with
    ``bucket_mb=OVERLAP_BUCKET_MB`` (per-bucket fused collectives) →
    comm_fraction_overlapped."""
    import dataclasses

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import bench
    from ddlpc_tpu.models import build_model_from_experiment
    from ddlpc_tpu.obs.comm import make_comm_probe
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

    cfg = _tiny_cfg()
    mesh = make_mesh(cfg.parallel)
    n = mesh.shape["data"]
    model = build_model_from_experiment(cfg)
    tx = build_optimizer(cfg.train)
    h, w = cfg.data.image_size
    state = create_train_state(model, tx, jax.random.key(0), (1, h, w, 3))
    sharded = resolve_shard_update(
        "auto", cfg.compression, n, spatial=False,
        grad_clip_norm=cfg.train.grad_clip_norm,
    )
    layout = StateLayout(
        "replicated" if sharded == "off" else sharded, tx, state, mesh, "data"
    )
    param_shapes = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), state.params
    )
    state = layout.place(state)
    update_ms = bench.measure_update_ms(
        tx, mesh, cfg.compression, state, sharded, rounds=rounds,
        param_avals=layout.param_avals,
    )

    probe = make_comm_probe(
        mesh, cfg.compression, param_shapes,
        scatter=sharded in ("zero2", "zero3"),
        seed=cfg.train.seed,
    )
    comm_ms = min(probe() for _ in range(max(rounds, 2))) * 1e3

    step = make_train_step(
        model, tx, mesh, cfg.compression, shard_update=sharded,
        param_avals=layout.param_avals,
    )
    A = cfg.train.sync_period
    B = cfg.train.micro_batch_size * n
    rng = np.random.default_rng(0)
    images = jax.device_put(
        rng.uniform(0, 1, (A, B, h, w, 3)).astype(np.float32),
        NamedSharding(mesh, P(None, "data")),
    )
    labels = jax.device_put(
        rng.integers(0, 6, (A, B, h, w)).astype(np.int32),
        NamedSharding(mesh, P(None, "data")),
    )
    for _ in range(2):
        state, metrics = step(state, images, labels)
        float(metrics["loss"])
    times = []
    for _ in range(max(rounds, 3)):
        t0 = time.perf_counter()
        for _ in range(4):
            state, metrics = step(state, images, labels)
        float(metrics["loss"])
        times.append((time.perf_counter() - t0) / 4)
    step_ms = float(np.median(times)) * 1e3
    frac = min(comm_ms / step_ms, 1.0) if step_ms > 0 else 0.0

    # Overlapped arm: identical model/optimizer/load, sync issued as
    # per-bucket fused collectives.  The probe measures the bucketed
    # comm-only program; the step measures the bucketed train step the
    # trainer would actually run at this bucket_mb.
    comp_b = dataclasses.replace(
        cfg.compression, bucket_mb=OVERLAP_BUCKET_MB
    )
    probe_b = make_comm_probe(
        mesh, comp_b, param_shapes,
        scatter=sharded in ("zero2", "zero3"), seed=cfg.train.seed,
    )
    comm_b_ms = min(probe_b() for _ in range(max(rounds, 2))) * 1e3
    step_b = make_train_step(
        model, tx, mesh, comp_b, shard_update=sharded,
        param_avals=layout.param_avals,
    )
    for _ in range(2):
        state, metrics = step_b(state, images, labels)
        float(metrics["loss"])
    times_b = []
    for _ in range(max(rounds, 3)):
        t0 = time.perf_counter()
        for _ in range(4):
            state, metrics = step_b(state, images, labels)
        float(metrics["loss"])
        times_b.append((time.perf_counter() - t0) / 4)
    step_b_ms = float(np.median(times_b)) * 1e3
    frac_b = min(comm_b_ms / step_b_ms, 1.0) if step_b_ms > 0 else 0.0
    return {
        "update_step_ms": round(update_ms, 3),
        "train_step_ms": round(step_ms, 3),
        "comm_ms_per_step": round(comm_ms, 3),
        "comm_fraction": round(frac, 4),
        "overlap_headroom_ms": round(
            max(min(comm_ms, step_ms - comm_ms), 0.0), 3
        ),
        "comm_fraction_overlapped": round(frac_b, 4),
        "comm_ms_per_step_bucketed": round(comm_b_ms, 3),
        "train_step_bucketed_ms": round(step_b_ms, 3),
        "overlap_bucket_mb": OVERLAP_BUCKET_MB,
    }


def arm_loader(rounds: int) -> Dict[str, float]:
    """loader_tiles_per_s: the ShardedLoader gather→cast→upload path."""
    import jax

    from ddlpc_tpu.data import ShardedLoader, build_dataset
    from ddlpc_tpu.parallel.mesh import make_mesh

    cfg = _tiny_cfg()
    train_ds, _ = build_dataset(cfg.data)
    mesh = make_mesh(cfg.parallel)
    n = mesh.shape["data"]
    loader = ShardedLoader(
        train_ds,
        mesh,
        global_micro_batch=2 * n,
        sync_period=2,
        shuffle=True,
        seed=0,
        data_axis="data",
    )
    best = 0.0
    for r in range(max(rounds, 2)):
        loader.set_epoch(r)
        batches = 0
        t0 = time.perf_counter()
        for images, labels in loader:
            jax.block_until_ready(images)
            batches += 1
        dt = time.perf_counter() - t0
        if batches:
            best = max(best, batches * loader.super_batch / dt)
    return {"loader_tiles_per_s": round(best, 2)}


def arm_serve(rounds: int) -> Dict[str, float]:
    """serve_p99_ms: the closed-loop serving load on a tiny checkpoint.

    Best-of-rounds like the other arms: 12 requests make p99 the sample
    max, and this host's ~25 ms-every-100 ms CPU-steal windows turn a
    single draw into a dice roll (see arm_fleet)."""
    import tempfile

    import serve_bench

    with tempfile.TemporaryDirectory() as tmp:
        workdir = os.path.join(tmp, "gate_serve_run")
        serve_bench.make_tiny_run(workdir)
        best = None
        for _ in range(max(rounds, 3)):
            rec = serve_bench.run_load(
                workdir, clients=2, requests=12, scene=40, max_batch=4,
                max_wait_ms=2.0,
            )
            if best is None or rec["value"] < best["value"]:
                best = rec
    return {"serve_p99_ms": float(best["value"])}


def arm_fleet(rounds: int) -> Dict[str, float]:
    """fleet_p99_ms + fleet_tiles_per_s_per_replica: routed load over 2
    replica subprocesses (the fleet path from ISSUE 10 — retries/hedging/
    breaker machinery included in what is measured, exactly like
    production).

    The load is a STORM — 8 closed-loop clients against 2 replicas — not
    a trickle: continuous batching (ISSUE 13) is a saturation/ragged-
    traffic technology, and a 2-client loop never engages the refill path
    at all.  400 requests so p99 is a real percentile, not the max of two
    dozen samples; best-of-rounds like the other arms (this host steals
    ~25 ms of CPU every ~100 ms — one storm landing across fewer steal
    windows is the reproducible number, and both fleet metrics come from
    the SAME best-p99 round so the pair stays internally consistent)."""
    import tempfile

    import serve_bench

    with tempfile.TemporaryDirectory() as tmp:
        workdir = os.path.join(tmp, "gate_fleet_run")
        serve_bench.make_tiny_run(workdir)
        best = None
        for _ in range(max(rounds, 2)):
            rec = serve_bench.run_fleet_load(
                workdir, replicas=2, clients=8, requests=400, tile=32,
                max_batch=4, max_wait_ms=2.0,
            )
            if best is None or rec["value"] < best["value"]:
                best = rec
    return {
        "fleet_p99_ms": float(best["value"]),
        "fleet_tiles_per_s_per_replica": float(
            best["tiles_per_s_per_replica"]
        ),
    }


def arm_cache(rounds: int) -> Dict[str, float]:
    """cache_hit_p99_ms: repeated-scene load answered from the router's
    response cache (scripts/serve_bench.py run_cache_hit_load) — the
    latency floor caching buys, gated so a hot-path regression in
    lookup/locking cannot land silently.  Best-of-rounds like the other
    serving arms (sub-ms numbers ride this host's CPU-steal windows)."""
    import tempfile

    import serve_bench

    with tempfile.TemporaryDirectory() as tmp:
        workdir = os.path.join(tmp, "gate_cache_run")
        serve_bench.make_tiny_run(workdir)
        best = None
        for _ in range(max(rounds, 2)):
            rec = serve_bench.run_cache_hit_load(
                workdir, clients=4, requests=400, tile=32, max_batch=4,
                max_wait_ms=2.0,
            )
            if best is None or rec["value"] < best["value"]:
                best = rec
    return {"cache_hit_p99_ms": float(best["value"])}


def measure(args) -> Dict[str, float]:
    measured: Dict[str, float] = {}
    if not args.skip_step:
        measured.update(arm_step_and_comm(args.rounds))
    if not args.skip_loader:
        measured.update(arm_loader(args.rounds))
    if not args.skip_serve:
        measured.update(arm_serve(args.rounds))
    if not args.skip_fleet:
        measured.update(arm_fleet(args.rounds))
    if not args.skip_cache:
        measured.update(arm_cache(args.rounds))
    return measured


def build_baseline(measured: Dict[str, float]) -> dict:
    import jax

    metrics = {}
    for name, spec in GATED.items():
        if name in measured:
            metrics[name] = dict(value=measured[name], **spec)
    return {
        "schema": BASELINE_SCHEMA,
        "generated_by": "scripts/perf_gate.py --update-baseline",
        # Age + host provenance (ISSUE 14 satellite): the gate warns
        # loudly when the baseline outlives max-baseline-age-days or is
        # replayed on a different host — with the driver bench
        # unreachable, this gate is the only live regression signal and
        # its baseline must not silently go stale.
        "generated_at": time.time(),
        "generated_at_iso": time.strftime(
            "%Y-%m-%dT%H:%M:%S%z", time.localtime()
        ),
        "host": host_fingerprint(),
        "env": {
            "backend": jax.default_backend(),
            "devices": len(jax.devices()),
            "host_cores": os.cpu_count(),
        },
        "metrics": metrics,
        # The step-attribution numbers the comm/compute-overlap work is
        # judged against (informational context for the gated ratios).
        "attribution": {
            k: v
            for k, v in measured.items()
            if k in (
                "comm_ms_per_step", "overlap_headroom_ms",
                "comm_ms_per_step_bucketed", "train_step_bucketed_ms",
                "overlap_bucket_mb",
            )
        },
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--update-baseline", action="store_true",
                    help="measure and rewrite the baseline file")
    ap.add_argument("--smoke", action="store_true",
                    help="validate baseline + gate logic, no measurement")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--max-baseline-age-days", type=float, default=30.0,
                    help="warn (loudly, non-fatally) when the baseline's "
                    "generated_at stamp is older than this")
    ap.add_argument("--devices", type=int, default=0,
                    help="force an N-device virtual CPU mesh (0 = as-is)")
    ap.add_argument("--skip-step", action="store_true")
    ap.add_argument("--skip-loader", action="store_true")
    ap.add_argument("--skip-serve", action="store_true")
    ap.add_argument("--skip-fleet", action="store_true")
    ap.add_argument("--skip-cache", action="store_true")
    ap.add_argument("--inject", action="append", default=[],
                    metavar="METRIC=FACTOR",
                    help="multiply a measured value before comparing "
                    "(regression-injection demonstration; repeatable)")
    ap.add_argument("--inject-only", action="store_true",
                    help="with --inject: no measurement — start from the "
                    "baseline's own values and apply the factors, so the "
                    "demonstration isolates gate sensitivity from host "
                    "noise")
    ap.add_argument("--out", default="", help="write measured values as JSON")
    args = ap.parse_args(argv)

    if args.smoke:
        return smoke(args.baseline, args.max_baseline_age_days)

    inject: Dict[str, float] = {}
    for spec in args.inject:
        if "=" not in spec:
            ap.error(f"--inject takes METRIC=FACTOR, got {spec!r}")
        k, _, v = spec.partition("=")
        if k not in GATED:
            # A typo'd metric would be silently ignored by compare() and
            # the demonstration would print PASS — invert of its meaning.
            ap.error(
                f"--inject: unknown metric {k!r} (gated metrics: "
                f"{', '.join(sorted(GATED))})"
            )
        inject[k] = float(v)

    if args.inject_only:
        if not inject:
            ap.error("--inject-only needs at least one --inject METRIC=FACTOR")
        try:
            with open(args.baseline) as f:
                baseline = json.load(f)
        except (OSError, ValueError) as e:
            print(f"perf_gate: cannot load baseline {args.baseline}: {e}",
                  file=sys.stderr)
            return 2
        errs = validate_baseline(baseline)
        if errs:
            for e in errs:
                print(f"perf_gate: {e}", file=sys.stderr)
            return 2
        measured = {
            n: float(s["value"]) for n, s in baseline["metrics"].items()
        }
        failures = compare(baseline["metrics"], measured, inject=inject)
        for fail in failures:
            print(f"perf_gate: {fail}")
        if failures:
            return 1
        print("perf_gate: PASS (injected factors inside tolerance)")
        return 0

    if args.devices:
        from ddlpc_tpu.utils.compat import force_cpu_devices

        force_cpu_devices(args.devices)

    measured = measure(args)
    print(json.dumps({"measured": measured}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        atomic_write_json(args.out, measured)

    if args.update_baseline:
        baseline = build_baseline(measured)
        os.makedirs(os.path.dirname(args.baseline) or ".", exist_ok=True)
        atomic_write_json(args.baseline, baseline)
        print(f"perf_gate: baseline written to {args.baseline}")
        return 0

    try:
        with open(args.baseline) as f:
            baseline = json.load(f)
    except (OSError, ValueError) as e:
        print(f"perf_gate: cannot load baseline {args.baseline}: {e}",
              file=sys.stderr)
        return 2
    errs = validate_baseline(baseline)
    if errs:
        for e in errs:
            print(f"perf_gate: {e}", file=sys.stderr)
        return 2
    for w in baseline_warnings(
        baseline, args.max_baseline_age_days,
        measured_paths=measured_path_files(),
    ):
        print(f"perf_gate: WARNING: {w}", file=sys.stderr)
    failures = compare(baseline["metrics"], measured, inject=inject)
    for fail in failures:
        print(f"perf_gate: {fail}")
    if failures:
        return 1
    compared = sorted(set(baseline["metrics"]) & set(measured))
    print(f"perf_gate: PASS ({', '.join(compared)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
