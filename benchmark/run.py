#!/usr/bin/env python3
"""One cell, one run, one process:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

builds the cell's ``ExperimentConfig`` from its data files, constructs the
real ``Trainer``, warms up through ``Trainer.fit()``, checks the model and
loss against the plain float32 reference, measures a second ``fit()`` of
about ``--seconds`` and prints one JSON line last.  Everything that belongs
to one cell, configuration, traffic mix or metric is a file found by the
name ``BENCHMARK.json`` gives it; this file holds none of those names.

``--rehearse`` (sandbox only) runs the same flow on virtual CPU devices at
the configuration's tiny ``rehearse`` size; its line says ``"correct":
false`` and names the CPU, so it can never pass for a chip's.
"""

import time

T0 = time.perf_counter()  # process start, to within the interpreter's own start-up

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PREFIX = "bench:"  # every host annotation the benchmark writes
WINDOW = PREFIX + "window"
EPOCH = PREFIX + "train_epoch"
# Trainer methods that get a host span in the trace, besides train_epoch,
# whose span also feeds the records; a method the Trainer lacks is skipped.
SPANNED = ("evaluate", "save", "dump_images")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def die(message: str, code: int = 2):
    print(f"benchmark/run.py: {message}", file=sys.stderr)
    sys.exit(code)


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    die(f"no {what} named {name!r} in BENCHMARK.json")


def load_reader(directory: str, name: str):
    """``benchmark/<directory>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{directory}__{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def set_dotted(d: dict, dotted: str, value):
    *groups, key = dotted.split(".")
    for g in groups:
        d = d[g]
    if key not in d:
        raise KeyError(f"unknown config key {dotted!r}")
    d[key] = value


def build_config(config: dict, traffic: dict, workload: dict, seed: int, rehearse: bool):
    """The cell's ExperimentConfig: the configuration file's groups, then the
    traffic mix's and the cell's dotted overrides, then the seed."""
    from ddlpc_tpu.config import ExperimentConfig

    d = ExperimentConfig.from_dict(config).to_dict()
    layers = [traffic.get("overrides", {}), workload.get("overrides", {})]
    if rehearse:
        layers.append(config.get("rehearse", {}))
    for overrides in layers:
        for dotted, value in overrides.items():
            set_dotted(d, dotted, value)
    d["data"]["seed"] = d["train"]["seed"] = seed
    return ExperimentConfig.from_dict(d)


def span_methods(trainer, records: list, attempts: list):
    """Wrap the instance's methods in profiler annotations; train_epoch also
    appends its record (or the exception) to ``records``."""
    import jax

    def wrap(name, fn):
        def spanned(*a, **kw):
            with jax.profiler.TraceAnnotation(PREFIX + name):
                return fn(*a, **kw)

        return spanned

    def train_epoch(epoch, fn=trainer.train_epoch):
        attempts.append(epoch)
        started = time.perf_counter()
        with jax.profiler.TraceAnnotation(EPOCH):
            record = fn(epoch)
        records.append(dict(record, started_s=started, wall_s=time.perf_counter() - started))
        return record

    for name in SPANNED:
        if hasattr(trainer, name):
            setattr(trainer, name, wrap(name, getattr(trainer, name)))
    trainer.train_epoch = train_epoch


def buffers_peak(devices) -> int:
    """Largest ``peak_bytes_in_use`` over the devices: the peak of live
    buffers.  The TPU runtime leaves program temporaries out of it (PERF.md)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks)) if peaks else 0


def step_temporaries(trainer) -> int:
    """Per-device bytes of temporaries of the compiled train step, from its
    ``memory_analysis()``.  Lowered again from the Trainer's own jitted step
    and live arguments, after the window: the compile is a cache hit."""
    try:
        images, labels = next(iter(trainer.loader))
        compiled = trainer.train_step.lower(trainer.state, images, labels).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)
    except (AttributeError, TypeError) as e:  # a step that is no jitted function
        print(f"benchmark/run.py: no memory analysis of the step ({e})", file=sys.stderr)
        return 0


def reference_check(trainer, cfg, config: dict, seed: int) -> dict:
    """The Trainer's current parameters and a seeded sample of its tiles
    through ``check.compare`` (see there)."""
    import jax
    import numpy as np

    import check

    params = jax.device_get(trainer.layout.full_params(trainer.state))
    stats = jax.device_get(trainer.state.batch_stats)
    ds = trainer.train_ds
    n = min(int(config["reference_sample_tiles"]), len(ds))
    idx = np.sort(np.random.default_rng(seed).choice(len(ds), size=n, replace=False))
    return check.compare(
        cfg.model, config["reference"], params, stats, ds.images[idx], ds.labels[idx]
    )


def open_devices(chips: int, rehearse: bool):
    """Import the program and JAX, place the compile cache, and return the
    cell's devices with their entry of ``peaks.json``.  Exits non-zero without
    the program, without a TPU of a known kind, or with too few chips."""
    sys.path[:0] = [ROOT, HERE]
    try:
        import ddlpc_tpu  # noqa: F401
    except ImportError as e:
        die(f"the program is not in this checkout ({e})")
    import jax

    if rehearse:
        from ddlpc_tpu.utils.compat import force_cpu_devices

        force_cpu_devices(chips)
        jax.config.update("jax_enable_compilation_cache", False)  # leave no CPU entries
    from ddlpc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # Trainer.__init__ initialises the model op by op: some two hundred tiny
    # programs, each under JAX's default one-second threshold for the
    # persistent cache.  Without these two lines every run compiles them again.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devices = jax.devices()
    peak = read_json(HERE, "peaks.json").get(devices[0].device_kind)
    if not rehearse:
        if devices[0].platform != "tpu":
            die(f"no TPU: JAX reports platform {devices[0].platform!r}", 3)
        if not isinstance(peak, dict):
            die(f"device kind {devices[0].device_kind!r} is not in benchmark/peaks.json", 3)
    if len(devices) < chips:
        die(f"the cell needs {chips} chips, JAX reports {len(devices)}", 3)
    return devices[:chips], peak if isinstance(peak, dict) else None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)

    manifest = read_json(ROOT, "BENCHMARK.json")
    cell = by_name(manifest["workloads"], args.workload, "workload")
    workload = read_json(HERE, "workloads", cell["name"] + ".json")
    config = read_json(ROOT, by_name(manifest["configs"], cell["config"], "config")["file"])
    traffic = read_json(HERE, "traffic", cell["traffic"] + ".json")
    chips = int(cell["chips"])

    devices, peak = open_devices(chips, args.rehearse)

    import jax

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(secs) if name == COMPILE_EVENT else None
    )

    from ddlpc_tpu.train.trainer import Trainer

    import flops

    workdir = tempfile.mkdtemp(prefix="ddlpc_bench_")
    phases = {"imports": time.perf_counter() - T0}
    try:
        cfg = build_config(config, traffic, workload, args.seed, args.rehearse)
        cfg = cfg.replace(workdir=os.path.join(workdir, "run"))
        t = time.perf_counter()
        trainer = Trainer(cfg, resume=False)
        phases["trainer_init"] = time.perf_counter() - t
        records: list = []
        attempts: list = []
        span_methods(trainer, records, attempts)

        t = time.perf_counter()
        warmup = int(traffic["warmup_epochs"])
        trainer.fit(epochs=warmup)
        phases["warmup"] = time.perf_counter() - t
        warm_records, train_buffers_peak = list(records), buffers_peak(devices)
        compiles_in_setup = len(compiles)

        t = time.perf_counter()
        verdict = reference_check(trainer, cfg, config, args.seed)
        phases["reference_check"] = time.perf_counter() - t
        print(json.dumps({"reference_check": verdict}), flush=True)

        # The window: a second fit() over as many epochs as fit --seconds,
        # counted from the warm-up's last full epoch cycle (start to start).
        cycle = warm_records[-1]["started_s"] - warm_records[-2]["started_s"]
        seconds = min(args.seconds, float(traffic["trace_seconds"])) if args.trace else args.seconds
        n_epochs = max(2 if args.trace else 1, round(seconds / cycle))
        del records[:], attempts[:]
        trainer.start_epoch = warmup
        trace_dir = os.path.join(workdir, "trace")
        if args.trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        compiles_before = len(compiles)
        error = None
        t_window = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                trainer.fit(epochs=warmup + n_epochs)
        except Exception as e:  # a failed epoch is a result, not a crash
            error = f"{type(e).__name__}: {e}"
        window_s = time.perf_counter() - t_window
        compiles_in_window = len(compiles) - compiles_before
        if args.trace:
            jax.profiler.stop_trace()
        step_temp = step_temporaries(trainer)
        trainer.close()

        steps_per_epoch = len(trainer.loader)
        trace = None
        if args.trace:
            import trace_reduce

            path = trace_reduce.find_trace(trace_dir)
            if path:
                trace = trace_reduce.reduce(
                    trace_reduce.load(path, PREFIX),
                    WINDOW,
                    EPOCH,
                    steps=len(records) * steps_per_epoch,
                    chips=chips,
                )

        finite = [r for r in records if math.isfinite(r["loss"])]
        failed = len(attempts) - len(finite)
        expect = workload.get("expect", {})
        tail = [r["loss"] for r in finite[-5:]]
        verdicts = {
            "reference": bool(verdict["ok"]),
            "no_failed_epoch": error is None and failed == 0 and len(attempts) == n_epochs,
            "no_compilation_in_window": compiles_in_window == 0,
            "loss_fell": bool(tail) and statistics.fmean(tail) < warm_records[0]["loss"],
            "work_as_declared": args.rehearse
            or (
                expect.get("steps_per_epoch", steps_per_epoch) == steps_per_epoch
                and expect.get("tiles_per_step", trainer.loader.super_batch)
                == trainer.loader.super_batch
            ),
        }
        run = {
            "chips": chips,
            "records": records,
            "warmup_records": warm_records,
            "window_s": window_s,
            "setup_seconds": t_window - T0,
            "phase_seconds": phases,
            "steps_per_epoch": steps_per_epoch,
            "tiles_per_step": trainer.loader.super_batch,
            # traced from the model's jaxpr: a second or two, so only where it is read
            "flops_per_step_per_chip": flops.conv_step_flops(
                cfg, channels=trainer.train_ds.image_shape[-1]
            )
            if args.trace
            else None,
            "peak": peak,
            "train_buffers_peak_bytes": train_buffers_peak,
            "step_temporary_bytes": step_temp,
            "trace": trace,
        }
        kind, directory = ("per_layer", "layer_metrics") if args.trace else ("end_to_end", "end_to_end")
        metrics = {}
        for m in manifest[kind]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            value = load_reader(directory, m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        print(
            json.dumps(
                {
                    "verdicts": verdicts,
                    "error": error,
                    "epochs": len(records),
                    "steps": len(records) * steps_per_epoch,
                    "window_s": window_s,
                    "compilations_in_setup": compiles_in_setup,
                    "phases": phases,
                    "first_loss": warm_records[0]["loss"],
                    "last_loss": tail[-1] if tail else None,
                    "shard_update": trainer.shard_update,
                    "buffers_peak_bytes": [train_buffers_peak, buffers_peak(devices)],
                    "step_temporary_bytes": step_temp,
                }
            ),
            flush=True,
        )
        device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": chips,
            "memory_peak_bytes": train_buffers_peak + step_temp,
        }
        line = {
            "correct": all(verdicts.values()) and not args.rehearse,
            "attempted": len(attempts),
            "failed": failed,
            "metrics": metrics,
            "device": device,
        }
        if trace is not None:
            device["busy_s"] = statistics.fmean(trace["busy_s"].values())
            device["window_s"] = trace["window_s"]
            line["breakdown"] = {
                "device_ops": trace["device_ops"],
                "idle_gaps": trace["idle_gaps"],
            }
        print(json.dumps(line), flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
