"""Observability: metrics logs, stage timing, qualitative image dumps.

Reference parity (SURVEY §5): per-epoch loss/accuracy/timing lines appended
to a txt file (кластер.py:715-716,781-782), wall-clock prints per sync stage
(кластер.py:116,265,317,389,397,440), and 5 (prediction, label, image) PNG
triples per epoch (кластер.py:785-790).  Here the txt log is kept (same
human-readable shape) plus a machine-readable JSONL stream, timings come
from a reusable ``StageTimer``, and the PNG dumps color classes through a
fixed palette instead of the reference's ``pred*5`` grayscale trick.
Only process 0 writes.
"""

from __future__ import annotations

import json
import os
import time
from contextlib import contextmanager
from typing import Dict, Optional

import jax
import numpy as np

from ddlpc_tpu.analysis import lockcheck
from ddlpc_tpu.obs.registry import sanitize_name
from ddlpc_tpu.obs.schema import SCHEMA_VERSION
from ddlpc_tpu.obs.tracing import NULL_SPAN
from ddlpc_tpu.utils.fsio import atomic_write_text

# ISPRS-style 6-class palette (imp surface, building, low veg, tree, car,
# clutter) extended by hashing for datasets with more classes.
_PALETTE = np.array(
    [
        [255, 255, 255],
        [0, 0, 255],
        [0, 255, 255],
        [0, 255, 0],
        [255, 255, 0],
        [255, 0, 0],
    ],
    np.uint8,
)


def class_palette(num_classes: int) -> np.ndarray:
    if num_classes <= len(_PALETTE):
        return _PALETTE[:num_classes]
    rng = np.random.default_rng(0)
    extra = rng.integers(0, 256, size=(num_classes - len(_PALETTE), 3), dtype=np.uint8)
    return np.concatenate([_PALETTE, extra])


class MetricsLogger:
    """Append-only txt + JSONL metric streams under ``workdir``.

    txt mirrors the reference's epoch lines (кластер.py:781-782); JSONL is
    the machine-readable record new in this framework.
    """

    def __init__(
        self,
        workdir: str,
        run_config_json: Optional[str] = None,
        basename: str = "metrics",
        registry=None,
    ):
        # ``basename`` lets other subsystems share this stream format
        # without clobbering the training log (serve/metrics.py writes
        # ``serve_metrics.jsonl`` next to ``metrics.jsonl``).
        self.enabled = jax.process_index() == 0
        self.workdir = workdir
        # Optional MetricsRegistry (obs/registry.py): every numeric scalar
        # logged here is also published as a gauge so the Prometheus
        # exposition (/metrics on the telemetry endpoint) always shows the
        # latest value of everything the JSONL stream carries.
        self.registry = None
        self._records_total = None
        if registry is not None:
            self.attach_registry(registry)
        if not self.enabled:
            return
        os.makedirs(workdir, exist_ok=True)
        self.txt_path = os.path.join(workdir, f"{basename}.txt")
        self.jsonl_path = os.path.join(workdir, f"{basename}.jsonl")
        if run_config_json is not None:
            # Run-config header, as the reference writes before epoch 0
            # (кластер.py:715-716) — rename-atomic so restore tooling
            # never reads a torn config; durable=False because this runs
            # once per trainer construction and the ~50ms container fsync
            # would tax every tiny test fit for an advisory file.
            atomic_write_text(
                os.path.join(workdir, "config.json"),
                run_config_json,
                durable=False,
            )

    def attach_registry(self, registry) -> None:
        """Wire (or re-wire) a MetricsRegistry after construction — the
        serve frontend owns its registry but receives a logger built
        before it exists, and the quantile snapshots must still reach the
        Prometheus exposition."""
        self.registry = registry
        self._records_total = registry.counter(
            "ddlpc_log_records_total",
            "JSONL records written, by record kind.",
            labelnames=("kind",),
        )

    def log(self, record: Dict[str, object], echo: bool = True) -> None:
        if not self.enabled:
            return
        record = {
            k: (float(v) if isinstance(v, (np.floating, jax.Array)) else v)
            for k, v in record.items()
        }
        record.setdefault("time", time.time())
        # Every stream record carries the flat-JSONL schema version so any
        # tool (scripts/obs_tail.py, scripts/check_metrics_schema.py) can
        # tail/lint training, serving, span, and alert streams identically.
        record.setdefault("schema", SCHEMA_VERSION)
        with open(self.jsonl_path, "a") as f:
            f.write(json.dumps(record) + "\n")
        if self.registry is not None:
            self._publish(record)
        line = "  ".join(
            f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in record.items()
            if k not in ("time", "schema")
        )
        with open(self.txt_path, "a") as f:
            f.write(line + "\n")
        if echo:
            print(line, flush=True)

    def _publish(self, record: Dict[str, object]) -> None:
        """Numeric scalars → ``ddlpc_<kind>_<key>`` gauges in the registry."""
        kind = str(record.get("kind", "train"))
        self._records_total.inc(kind=kind)
        prefix = sanitize_name(f"ddlpc_{kind}")
        for k, v in record.items():
            if k in ("time", "schema", "kind"):
                continue
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            self.registry.gauge(
                f"{prefix}_{sanitize_name(k)}",
                f"Latest {k!r} from the {kind} JSONL stream.",
            ).set(float(v))


# Every stage also lands on the profiler's clock under this prefix (the
# benchmark's readers and docs/OBSERVABILITY.md key on it).
ANNOTATION_PREFIX = "ddlpc:"


@lockcheck.guarded
class StageTimer:
    """Named wall-clock stage timing — the structured form of the
    reference's scattered ``time.time()`` delta prints (кластер.py:265-440)
    and the ONE way the training process writes a span.  Accumulates
    totals; ``summary()`` gives seconds per stage.

    Every stage is additionally a ``jax.profiler.TraceAnnotation`` named
    ``ddlpc:<name>`` — always, whatever ``train.trace`` says: outside a
    profiler session that is one atomic test; inside one (``maybe_profile``,
    a SIGUSR2 capture, the benchmark's traced window) the stage lands on
    the host plane of the same ``.xplane.pb`` as the device ops, on one
    clock.  ``attrs`` (``epoch=``, ``step=``, ...) ride along as the
    annotation's arguments.

    Thread-safe: the ShardedLoader's producer pool records its
    loader_gather/cast/upload stages from worker threads concurrently with
    the training thread's data/step stages.

    ``tracer`` (obs/tracing.py, optional) additionally records every stage
    as a span with the same name and ``attrs`` — this is how the loader's
    per-stage hooks reach ``spans.jsonl`` without the loader knowing the
    tracer exists.  Spans nest per thread (a stage inside a stage on one
    thread names its parent); a producer thread's stages are roots."""

    def __init__(self, tracer=None):
        self.totals: Dict[str, float] = {}  # guarded-by: _lock
        self.counts: Dict[str, int] = {}  # guarded-by: _lock
        self.tracer = tracer
        self._lock = lockcheck.lock("StageTimer._lock")

    @contextmanager
    def stage(self, name: str, **attrs):
        tracer = self.tracer
        span = (
            tracer.span(name, **attrs)
            if tracer is not None and tracer.enabled
            else NULL_SPAN
        )
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name, **attrs), span:
                yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self.totals[name] = self.totals.get(name, 0.0) + dt
                self.counts[name] = self.counts.get(name, 0) + 1

    def summary(self) -> Dict[str, float]:
        with self._lock:
            return dict(self.totals)

    def means(self) -> Dict[str, float]:
        with self._lock:
            return {
                k: self.totals[k] / max(self.counts[k], 1) for k in self.totals
            }

    def reset(self) -> None:
        with self._lock:
            self.totals.clear()
            self.counts.clear()


@contextmanager
def maybe_profile(trace_dir: Optional[str], enabled: bool = True):
    """XLA/TPU profiler trace around a block (view with TensorBoard or
    xprof).  The reference's only tracing is scattered wall-clock prints
    (SURVEY §5); this wraps ``jax.profiler.trace`` so one config flag
    captures real device timelines.  No-op when disabled or trace_dir is
    None; never fails the run if the profiler is unavailable."""
    if not enabled or not trace_dir or jax.process_index() != 0:
        yield
        return
    # Guard only the profiler's own enter/exit — an exception raised by the
    # profiled body must propagate untouched.
    import warnings

    ctx = jax.profiler.trace(trace_dir)
    try:
        ctx.__enter__()
    except Exception as e:  # profiler may be unsupported on a backend
        warnings.warn(f"profiler trace failed to start: {e}", stacklevel=2)
        yield
        return
    try:
        yield
    finally:
        try:
            ctx.__exit__(None, None, None)
        except Exception as e:
            warnings.warn(f"profiler trace failed to stop: {e}", stacklevel=2)


def dump_prediction_triples(
    workdir: str,
    images: np.ndarray,
    labels: np.ndarray,
    preds: np.ndarray,
    num_classes: int,
    epoch: int,
    max_samples: int = 5,
) -> None:
    """Write (Model i, Label i, Image i) PNG triples (кластер.py:785-790)."""
    if jax.process_index() != 0:
        return
    from PIL import Image

    out_dir = os.path.join(workdir, "images", f"epoch_{epoch:04d}")
    os.makedirs(out_dir, exist_ok=True)
    pal = class_palette(num_classes)
    n = min(max_samples, len(images))
    for i in range(n):
        pred_rgb = pal[np.clip(preds[i], 0, num_classes - 1)]
        lab_rgb = pal[np.clip(labels[i], 0, num_classes - 1)]
        img_u8 = np.clip(images[i] * 255.0, 0, 255).astype(np.uint8)
        if img_u8.shape[-1] == 1:
            img_u8 = np.repeat(img_u8, 3, axis=-1)
        Image.fromarray(pred_rgb).save(os.path.join(out_dir, f"Model {i}.png"))
        Image.fromarray(lab_rgb).save(os.path.join(out_dir, f"Label {i}.png"))
        Image.fromarray(img_u8).save(os.path.join(out_dir, f"Image {i}.png"))
