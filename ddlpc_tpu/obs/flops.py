"""Per-step FLOP model + live MFU / goodput accounting.

Two halves, one file, because they answer the same operating question —
"how far is this run from the hardware roof, and where did the wall-clock
go?" (the pod-scale JAX training playbook treats MFU/goodput as THE
operating metric, arxiv 2204.06514):

- **FLOP model** — the jaxpr conv-walk hoisted out of
  ``scripts/roofline.py`` (the ``obs/xplane.py`` precedent: one
  implementation for the CLI and the live hooks).  It traces the real
  per-micro-batch ``value_and_grad`` program — forward convs AND the two
  backward convs XLA derives per layer — so the per-step FLOP count is
  computed from the program that runs, not an architecture diagram.
  Computed ONCE at trainer start (tracing only, no compile/execute).

- **Accounting** — :class:`PerfAccountant` turns that model plus the
  trainer's stage timings into live gauges on the training ``/metrics``
  endpoint:

  * ``ddlpc_mfu`` — model FLOP utilization of the last epoch's mean step:
    ``flops_per_step / (step_time · peak_flops_per_device)``;
  * ``ddlpc_goodput`` — productive-step seconds over wall seconds since
    fit start, debiting checkpoint stalls, eval, data waits, and restart
    gaps (the downtime between a previous attempt's last breadcrumb and
    this process taking over — read from the resilience breadcrumb /
    ``resilience.jsonl``, docs/RESILIENCE.md);
  * ``ddlpc_goodput_debit_seconds_total{category}`` — where the
    non-productive wall went.

  Per-epoch summaries are also logged as flat ``kind="perf"`` JSONL
  records, which ``scripts/perf_report.py`` renders as the step-time
  attribution table.

Debits are measured on the training thread as disjoint intervals, so the
reconciliation invariant holds by construction (test-pinned):
``productive + Σ debits ≤ wall``.

jax is imported lazily (inside the functions that trace) so this module
stays importable from stdlib-only contexts, like the rest of ``obs/``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Tuple

# Peak dense bf16 FLOP/s per chip — the ONE table behind every MFU this
# repo prints (the Trainer's gauges, bench.py, chip_smoke.py), keyed by
# substrings of ``jax.Device.device_kind``.  Source: Google Cloud TPU
# documentation, per-chip bf16 peaks ("TPU v5e": 197 TFLOP/s; the chip
# reports itself as "TPU v5 lite").  A device that is not here is an error
# (:func:`device_peak_flops`), not a default.
_PEAK_BY_DEVICE_KIND = (
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
)

# The peak the CPU test meshes ASSUME (they have no peak of their own), so
# their MFU gauges stay on the flagship chip's scale;
# ``ddlpc_peak_flops_assumed`` says so.
V5E_PEAK_FLOPS = 197e12


# --------------------------------------------------------------------------
# FLOP model: collect conv ops from the executed program
# --------------------------------------------------------------------------


def _sub_jaxprs(params):
    import jax
    import jax.extend.core  # noqa: F401  (binds jax.extend — plain `import jax` does not)

    for v in params.values():
        if isinstance(v, jax.extend.core.ClosedJaxpr):
            yield v.jaxpr
        elif hasattr(v, "eqns"):  # raw Jaxpr
            yield v
        elif isinstance(v, (list, tuple)):
            for q in v:
                if isinstance(q, jax.extend.core.ClosedJaxpr):
                    yield q.jaxpr
                elif hasattr(q, "eqns"):
                    yield q


def iter_eqns(jaxpr):
    """Every equation in a jaxpr, recursing into sub-jaxprs (scan/remat/...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        yield from (e for sub in _sub_jaxprs(eqn.params) for e in iter_eqns(sub))


def conv_flops(eqn) -> int:
    """2 * output_elements * KH * KW * Cin_per_group (MACs x 2)."""
    import numpy as np

    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    cin_per_group = rhs[dn.rhs_spec[1]]
    k_spatial = int(np.prod([rhs[d] for d in dn.rhs_spec[2:]]))
    return 2 * int(np.prod(out)) * k_spatial * cin_per_group


def collect_convs(cfg, micro_batch: int, channels: int = 3) -> Dict[tuple, dict]:
    """Unique conv signatures (with counts) in one micro-batch fwd+bwd.

    Traces the model's per-micro-batch ``value_and_grad`` jaxpr for
    ``cfg`` (an ``ExperimentConfig``) and collects every
    ``conv_general_dilated`` — this is the program that runs.  Returns
    ``{signature_key: {"eqn", "count", "flops"}}`` (the roofline CLI also
    needs the eqn to rebuild and time each signature).

    ``channels`` is the dataset's input channel count (the Trainer passes
    ``train_ds.image_shape[-1]``; the first conv's FLOPs depend on it).

    FLOPs caveat (same convention as the roofline): lhs-dilated
    (transposed/backward) convs are counted at their algorithmic cost
    including inserted zeros.
    """
    import jax
    import jax.numpy as jnp

    from ddlpc_tpu.models import build_model
    from ddlpc_tpu.ops.losses import softmax_cross_entropy

    # No norm_axis_name: sync-BN's pmean needs a mesh axis and does not
    # change any conv shape — this traces the per-device program.
    model = build_model(cfg.model)
    h, w = cfg.data.image_size
    # Everything abstract: params/stats from eval_shape, inputs as
    # ShapeDtypeStructs passed as ARGUMENTS (closed-over concrete zeros
    # would embed a micro_batch×H×W constant in the jaxpr — ~400 MB at the
    # flagship operating point).  Tracing allocates nothing.
    x_s = jax.ShapeDtypeStruct((micro_batch, h, w, channels), jnp.float32)
    y_s = jax.ShapeDtypeStruct((micro_batch, h, w), jnp.int32)
    variables = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1, h, w, channels), jnp.float32),
            train=False,
        )
    )

    def loss_fn(params, stats, x, y):
        logits, _ = model.apply(
            {"params": params, "batch_stats": stats},
            x,
            train=True,
            mutable=["batch_stats"],
        )
        return softmax_cross_entropy(logits, y, ignore_index=-1)

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss_fn))(
        variables["params"], variables.get("batch_stats", {}), x_s, y_s
    )
    convs: Dict[tuple, dict] = {}
    for eqn in iter_eqns(jaxpr.jaxpr):
        if eqn.primitive.name != "conv_general_dilated":
            continue
        lhs, rhs = (v.aval for v in eqn.invars[:2])
        dn = eqn.params["dimension_numbers"]
        key = (
            tuple(lhs.shape),
            str(lhs.dtype),
            tuple(rhs.shape),
            str(rhs.dtype),
            tuple(eqn.params["window_strides"]),
            tuple(eqn.params["lhs_dilation"]),
            tuple(eqn.params["rhs_dilation"]),
            tuple(map(tuple, eqn.params["padding"])),
            eqn.params["feature_group_count"],
            # The actual layout specs: fwd convs are NHWC/HWIO but the
            # weight-gradient convs XLA derives contract over batch with
            # transposed specs — reconstruction from a fixed layout string
            # would measure a different program.
            (tuple(dn.lhs_spec), tuple(dn.rhs_spec), tuple(dn.out_spec)),
        )
        if key not in convs:
            convs[key] = dict(eqn=eqn, count=0, flops=conv_flops(eqn))
        convs[key]["count"] += 1
    return convs


_STEP_FLOPS_CACHE: Dict[tuple, int] = {}


def conv_step_flops(
    cfg, micro_batch: int, sync_period: int, channels: int = 3
) -> int:
    """Conv FLOPs of one OPTIMIZER step per device: ``sync_period``
    micro-batches of forward+backward at the per-replica ``micro_batch``.
    Non-conv FLOPs (norms, loss, Adam) are deliberately excluded — convs
    are >99% of this zoo's step and the roofline uses the same convention,
    so MFU here composes with the committed per-shape ceiling tables.
    Memoized per (model config, image size, micro_batch, channels): the
    trace costs ~0.5 s warm, and test suites construct many same-config
    Trainers."""
    key = (cfg.model, tuple(cfg.data.image_size), int(micro_batch),
           int(channels))
    per_micro = _STEP_FLOPS_CACHE.get(key)
    if per_micro is None:
        convs = collect_convs(cfg, micro_batch, channels=channels)
        per_micro = sum(c["count"] * c["flops"] for c in convs.values())
        _STEP_FLOPS_CACHE[key] = per_micro
    return sync_period * per_micro


_PRODUCT_FLOPS_CACHE: Dict[tuple, Tuple[int, int, bool]] = {}


def product_flops(
    cfg, micro_batch: int, channels: int = 3, platform: Optional[str] = None
) -> Tuple[int, int, bool]:
    """``(dense, grouped, has_conv)`` of ONE forward of ``cfg``'s model over
    a micro-batch: the FLOPs of every ``dot_general`` (2 · output elements ·
    contracted length), those of the ``ragged_dot``s if every (token, expert)
    pair the program picks lay in a group, and whether the program holds a
    ``conv_general_dilated`` at all.  The grouped products count by the row,
    not by the buffer, all from shapes: a product ``[m, k] x [g, k, n]``
    spends 2·k·n on each of its buffer's ``m`` rows, the products that share
    a ``group_sizes`` vector share a buffer, and the pairs are the ``top_k``s'
    outputs.  ``grouped`` is what a buffer row costs times the pairs, so it is
    the same whether a routed layer's buffer holds every pair or a compact
    share of them, and whether the program runs it once or again in a loop
    on further rows.  Traced with ``train=False``, so a model that
    rematerialises under ``train=True`` is not counted twice; a ``scan`` body
    (attention mapped over sequences) counts ``length`` times.  A
    ``pallas_call`` counts by its ``cost_estimate`` (its body holds one grid
    step's products, not the grid's), and of a ``lax.platform_dependent``
    switch the one branch that is lowered for ``platform`` (the default
    backend's unless given).  Memoized like :func:`conv_step_flops`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddlpc_tpu.models import build_model

    platform = platform or jax.default_backend()
    key = (cfg.model, tuple(cfg.data.image_size), int(micro_batch), int(channels), platform)
    if key in _PRODUCT_FLOPS_CACHE:
        return _PRODUCT_FLOPS_CACHE[key]
    model = build_model(cfg.model)
    h, w = cfg.data.image_size
    # float32 tiles, as collect_convs: a token model casts them to ids.
    x_s = jax.ShapeDtypeStruct((micro_batch, h, w, channels), jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1, h, w, channels), jnp.float32), train=False
        )
    )
    jaxpr = jax.make_jaxpr(lambda v, x: model.apply(v, x, train=False))(variables, x_s)

    def walk(jaxpr):
        """FLOPs of the dense products; FLOPs and rows of the grouped products'
        buffers; the (token, expert) pairs picked; any convolution."""
        dense, grouped, rows, pairs, has_conv = 0, 0, 0, 0, False
        buffers = set()  # the group_sizes vectors seen in this jaxpr
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                cost = eqn.params.get("cost_estimate")
                dense += int(cost.flops) if cost is not None else 0
                continue
            if name == "dot_general":
                (contract, _), _ = eqn.params["dimension_numbers"]
                lhs = eqn.invars[0].aval.shape
                dense += 2 * int(np.prod(eqn.outvars[0].aval.shape)) * int(
                    np.prod([lhs[d] for d in contract])
                )
            elif name == "ragged_dot_general":
                m, k = eqn.invars[0].aval.shape
                grouped += 2 * m * k * eqn.invars[1].aval.shape[-1]
                if eqn.invars[2] not in buffers:
                    buffers.add(eqn.invars[2])
                    rows += m
            elif name == "top_k":
                pairs += int(np.prod(eqn.outvars[0].aval.shape))
            has_conv |= name == "conv_general_dilated"
            times = eqn.params["length"] if name == "scan" else 1
            subs = list(_sub_jaxprs(eqn.params))
            lowered_for = eqn.params.get("branches_platforms") if name == "cond" else None
            if lowered_for:  # the last branch is the default (None)
                subs = [next(b for b, ps in zip(subs, lowered_for) if ps is None or platform in ps)]
            for sub in subs:
                d, g, r, p, c = walk(sub)
                dense, grouped = dense + times * d, grouped + times * g
                rows, pairs, has_conv = rows + times * r, pairs + times * p, has_conv | c
        return dense, grouped, rows, pairs, has_conv

    dense, grouped, rows, pairs, has_conv = walk(jaxpr.jaxpr)
    if rows:
        grouped = grouped * pairs // rows  # what a buffer row costs x the pairs
    _PRODUCT_FLOPS_CACHE[key] = dense, grouped, has_conv
    return _PRODUCT_FLOPS_CACHE[key]


def step_flops(
    cfg, micro_batch: int, sync_period: int, channels: int = 3
) -> Tuple[int, int]:
    """``(dense, grouped)`` required FLOPs of one optimizer step per device,
    by what the model's traced program holds, whatever its name: ``dense`` is
    the convolutions (:func:`conv_step_flops`, the walk over the
    ``value_and_grad`` program, where there is a convolution) plus three
    times the forward's matrix products (forward and backward; recomputation
    is not required work); ``grouped`` is three times the forward's grouped
    products (``ragged_dot``) with every (token, expert) pair in a group.
    How many pairs were, only the run knows: :meth:`PerfAccountant.routed`
    takes the share from the step's counters."""
    dense, grouped, has_conv = product_flops(cfg, micro_batch, channels)
    convs = conv_step_flops(cfg, micro_batch, sync_period, channels) if has_conv else 0
    return convs + 3 * sync_period * dense, 3 * sync_period * grouped


def device_peak_flops(device) -> float:
    """Peak dense bf16 FLOP/s of a ``jax.Device`` from the one table;
    an unknown ``device_kind`` raises."""
    kind = device.device_kind.lower()
    for sub, peak in _PEAK_BY_DEVICE_KIND:
        if sub in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s known for device kind {device.device_kind!r} "
        f"(platform {device.platform!r}): add it to "
        f"obs/flops._PEAK_BY_DEVICE_KIND with its source, or set "
        f"TrainConfig.peak_flops_per_device"
    )


def resolve_peak_flops(configured: float = 0.0) -> Tuple[float, bool]:
    """(peak FLOP/s per device, assumed?) for the MFU denominator.

    ``configured`` > 0 wins (``TrainConfig.peak_flops_per_device``).
    Otherwise the backend's device kind is looked up in the one table.
    Only the CPU platform (the test meshes) may miss it: it gets the v5e
    peak with ``assumed=True``.  An unknown accelerator raises — an MFU
    against somebody else's peak is not a measurement."""
    if configured and configured > 0:
        return float(configured), False
    import jax

    device = jax.devices()[0]
    if device.platform == "cpu":
        return V5E_PEAK_FLOPS, True
    return device_peak_flops(device), False


def restart_gap_seconds(workdir: str, now: Optional[float] = None) -> float:
    """Downtime this attempt inherits from a previous one, in seconds.

    A supervised restart (docs/RESILIENCE.md) leaves two timestamps a new
    process can read before it overwrites them: the previous attempt's
    last ``breadcrumb.json`` (rewritten at every phase transition) and the
    supervisor's ``resilience.jsonl`` records.  The gap — newest such
    timestamp to now — is wall-clock during which no training happened and
    is debited from goodput as category ``restart``.

    The breadcrumb's phase GATES the whole computation: only a crumb from
    an INTERRUPTED run (phase other than ``done``) means this attempt is a
    restart.  A fresh workdir (no crumb) or a completed one (``done``) has
    no gap even when an old ``resilience.jsonl`` is still lying around —
    resuming a finished run days later is a new run, not downtime.
    Best-effort: accounting must never take down the run it describes."""
    now = time.time() if now is None else now
    try:
        from ddlpc_tpu.resilience.protocol import read_breadcrumb

        crumb = read_breadcrumb(workdir)
    except Exception:
        crumb = None
    if not crumb or crumb.get("phase") == "done":
        return 0.0
    latest = 0.0
    t = crumb.get("time")
    if isinstance(t, (int, float)):
        latest = float(t)
    try:
        import json
        import os

        path = os.path.join(workdir, "resilience.jsonl")
        if os.path.exists(path):
            with open(path) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    t = rec.get("time")
                    if isinstance(t, (int, float)):
                        latest = max(latest, float(t))
    except Exception:
        pass
    if latest <= 0.0:
        return 0.0
    return max(now - latest, 0.0)


# --------------------------------------------------------------------------
# Live accounting
# --------------------------------------------------------------------------


class PerfAccountant:
    """Live MFU + goodput gauges over a training run's wall clock.

    The trainer feeds it disjoint measured intervals from the training
    thread — ``productive`` (compiled step dispatch+sync seconds) and
    ``debit`` categories (data waits, eval, checkpoint stalls) — plus the
    one-time restart gap; ``publish`` computes the gauges and returns the
    flat ``kind="perf"`` record for the JSONL stream.  Thread-safe (the
    telemetry endpoint scrapes concurrently with the loop).
    """

    def __init__(
        self,
        registry,
        flops_per_step: int,
        peak_flops: float,
        peak_assumed: bool = False,
        restart_gap_s: float = 0.0,
        service: str = "train",
        grouped_flops_per_step: int = 0,
    ):
        self._lock = threading.Lock()
        # ``flops_per_step`` is what MFU counts: the dense FLOPs, plus the
        # grouped products' at the share of their rows last reported routed.
        self._dense_flops = int(flops_per_step)
        self._grouped_flops = int(grouped_flops_per_step)
        self.flops_per_step = self._dense_flops
        self.peak_flops = float(peak_flops)
        self.peak_assumed = bool(peak_assumed)
        self.restart_gap_s = float(restart_gap_s)
        self._origin: Optional[float] = None
        self._productive_s = 0.0
        self._steps = 0
        self._debits: Dict[str, float] = {}
        if restart_gap_s > 0:
            self._debits["restart"] = float(restart_gap_s)
        self._g_mfu = registry.gauge(
            "ddlpc_mfu",
            "Model FLOP utilization of the last epoch's mean step "
            "(required FLOPs / (step seconds * peak FLOP/s per device)).",
        )
        self._g_goodput = registry.gauge(
            "ddlpc_goodput",
            "Productive-step seconds over wall seconds since fit start, "
            "debiting data waits, eval, checkpoint stalls, restart gaps.",
        )
        self._g_flops = registry.gauge(
            "ddlpc_flops_per_step",
            "Per-device conv and matrix-product FLOPs of one optimizer step "
            "(traced jaxpr; grouped products by the routed rows).",
        )
        self._g_peak = registry.gauge(
            "ddlpc_peak_flops_per_device",
            "Peak FLOP/s per device used as the MFU denominator.",
        )
        self._g_assumed = registry.gauge(
            "ddlpc_peak_flops_assumed",
            "1 when the peak is an assumption (unknown device kind, v5e "
            "peak used for comparability), 0 when known/configured.",
        )
        self._g_debit = registry.gauge(
            "ddlpc_goodput_debit_seconds_total",
            "Cumulative non-productive wall seconds, by category.",
            labelnames=("category",),
        )
        self._g_flops.set(float(self.flops_per_step))
        self._g_peak.set(self.peak_flops)
        self._g_assumed.set(1.0 if peak_assumed else 0.0)
        if restart_gap_s > 0:
            self._g_debit.set(restart_gap_s, category="restart")

    def start(self) -> None:
        """Mark fit start (wall origin).  Idempotent across epochs; a
        second fit() on the same trainer continues the same wall clock."""
        with self._lock:
            if self._origin is None:
                self._origin = time.monotonic()

    def productive(self, seconds: float, steps: int = 0) -> None:
        """Credit compiled-step seconds (the thing goodput counts)."""
        with self._lock:
            self._productive_s += max(float(seconds), 0.0)
            self._steps += int(steps)

    def debit(self, category: str, seconds: float) -> None:
        """Charge non-productive wall seconds to a category (data, eval,
        checkpoint, ...)."""
        seconds = max(float(seconds), 0.0)
        with self._lock:
            self._debits[category] = self._debits.get(category, 0.0) + seconds
        self._g_debit.set(self._debits[category], category=category)

    def routed(self, rows_routed: float, rows_offered: float) -> None:
        """The step's routing counters (``moe_rows_routed``,
        ``moe_rows_offered``): the grouped products count at the share of
        the (token, expert) pairs that lay in a group, whatever buffer held
        their rows, and at nothing before."""
        share = rows_routed / rows_offered if rows_offered > 0 else 0.0
        with self._lock:
            self.flops_per_step = self._dense_flops + int(self._grouped_flops * share)
        self._g_flops.set(float(self.flops_per_step))

    def mfu(self, step_time_s: float) -> float:
        """MFU of a step of ``step_time_s`` seconds under the model."""
        if step_time_s <= 0 or self.peak_flops <= 0:
            return 0.0
        return self.flops_per_step / (step_time_s * self.peak_flops)

    def publish(self, step_time_s: Optional[float] = None) -> Dict[str, object]:
        """Refresh the gauges; returns the flat ``kind="perf"`` record.

        ``step_time_s`` is the last epoch's mean optimizer-step seconds
        (the MFU numerator's denominator); omitted, the cumulative mean
        of credited productive seconds per step is used."""
        with self._lock:
            origin = self._origin
            productive = self._productive_s
            steps = self._steps
            debits = dict(self._debits)
        wall = (
            time.monotonic() - origin if origin is not None else 0.0
        ) + self.restart_gap_s
        if step_time_s is None and steps > 0:
            step_time_s = productive / steps
        mfu = self.mfu(step_time_s) if step_time_s else 0.0
        goodput = productive / wall if wall > 0 else 0.0
        self._g_mfu.set(mfu)
        self._g_goodput.set(goodput)
        rec: Dict[str, object] = {
            "kind": "perf",
            "mfu": round(mfu, 6),
            "goodput": round(goodput, 6),
            "flops_per_step": self.flops_per_step,
            "peak_flops_per_device": self.peak_flops,
            "peak_flops_assumed": self.peak_assumed,
            "productive_s": round(productive, 4),
            "wall_s": round(wall, 4),
            "steps": steps,
        }
        if step_time_s:
            rec["step_time_s"] = round(float(step_time_s), 6)
        attributed = productive
        for cat, secs in sorted(debits.items()):
            rec[f"debit_{cat}_s"] = round(secs, 4)
            attributed += secs
        # The residual the measured intervals do not cover (compile time,
        # logging, loop overhead...).  Negative only by clock skew.
        rec["other_s"] = round(max(wall - attributed, 0.0), 4)
        return rec
