"""Self-time aggregation for JAX xplane profiler traces.

The one implementation behind ``scripts/xplane_top.py`` (offline CLI),
``scripts/trace_step.py`` (head-bench tracing) and the on-demand profiling
hooks (obs/profiling.py: the Trainer's SIGUSR2 trigger and the serve
``/debug/trace`` endpoint) — moved here from the script so the CLI and the
live hooks can never drift.

Self time = event duration minus the time of nested children on the same
line, which is what the tensorboard-plugin-profile op profile would show —
that plugin's converter is incompatible with the TF pinned in this image,
so this parses the xplane proto directly.

Plane selection: TPU/GPU traces put compiled ops on ``/device:...`` planes
under an "XLA Ops" line (:func:`self_times`, the historical behavior).  CPU
traces have no device plane — the ops land on host-plane lines named
``tf_XLAEigen/...`` / ``tf_XLATfrtCpuClient/...`` — so
:func:`self_times_any` falls back to those, which is what makes the
on-demand round trip work on the CPU backend too.

The TF xplane proto import is optional at module level: importing this
module never fails, and every entry point raises :class:`XplaneUnavailable`
with an actionable message when the proto is missing (instead of the bare
ImportError traceback the old script produced).
"""

from __future__ import annotations

import collections
import glob
import os
from typing import Counter, Iterator, List, Tuple

XPLANE_IMPORT_HINT = (
    "the TF xplane proto (tensorflow.tsl.profiler.protobuf.xplane_pb2) is "
    "not importable in this environment, so profiler traces cannot be "
    "aggregated. The raw trace directory is still valid — view it with "
    "TensorBoard/xprof elsewhere, or install a TensorFlow (or tsl protobuf) "
    "build that provides the proto to aggregate here."
)


class XplaneUnavailable(RuntimeError):
    """The TF xplane proto import is missing — aggregation cannot run."""


def _load_pb2():
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception as e:  # ImportError or TF's own init failures
        raise XplaneUnavailable(f"{XPLANE_IMPORT_HINT} ({e!r})") from e
    return xplane_pb2


def have_xplane() -> bool:
    """Whether trace aggregation can run in this environment."""
    try:
        _load_pb2()
        return True
    except XplaneUnavailable:
        return False


def load_xspace(trace_dir: str):
    """Parse the newest ``.xplane.pb`` under a ``jax.profiler`` trace dir."""
    xplane_pb2 = _load_pb2()
    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    xs = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def _line_self_times(events, ev_meta) -> Tuple[Counter, Counter]:
    """(self-time ps, count) per op name for one line's event list.

    Sort children after their enclosing parent at equal offsets (longer
    event first), or same-start nesting inverts the parent/child stack and
    produces negative self-times.
    """
    evs = sorted(
        (e.offset_ps, -e.duration_ps, ev_meta.get(e.metadata_id, "?"))
        for e in events
    )
    evs = [(off, -negdur, name) for off, negdur, name in evs]
    agg: Counter = collections.Counter()
    cnt: Counter = collections.Counter()
    stack: list = []  # [start, end, name, child_time]

    def pop_until(t: float) -> None:
        while stack and stack[-1][1] <= t:
            s, e, n, ct = stack.pop()
            agg[n] += (e - s) - ct
            cnt[n] += 1
            if stack:
                stack[-1][3] += e - s

    for off, dur, name in evs:
        pop_until(off)
        stack.append([off, off + dur, name, 0])
    pop_until(float("inf"))
    return agg, cnt


def self_times(trace_dir: str) -> Iterator[Tuple[str, Counter, Counter]]:
    """(plane name, self-time ps by op, count by op) per ``/device:`` plane
    — the historical TPU/GPU contract (scripts/trace_step.py depends on
    exactly this: device planes only, "XLA Ops" line only)."""
    xs = load_xspace(trace_dir)
    for plane in xs.planes:
        if not plane.name.startswith("/device:"):
            continue
        ev_meta = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            agg, cnt = _line_self_times(line.events, ev_meta)
            yield plane.name, agg, cnt


def self_times_any(trace_dir: str) -> Iterator[Tuple[str, Counter, Counter]]:
    """Like :func:`self_times` but never empty-handed on a valid trace:
    when no ``/device:`` plane exists (CPU backend) it aggregates the host
    plane's XLA executor lines (``tf_XLA*``) instead, merged per plane —
    each line is one executor thread, so self time nests within a line."""
    xs = load_xspace(trace_dir)
    found_device = False
    for plane in xs.planes:
        if not plane.name.startswith("/device:"):
            continue
        ev_meta = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            found_device = True
            agg, cnt = _line_self_times(line.events, ev_meta)
            yield plane.name, agg, cnt
    if found_device:
        return
    for plane in xs.planes:
        if not plane.name.startswith("/host:"):
            continue
        ev_meta = {k: v.name for k, v in plane.event_metadata.items()}
        agg: Counter = collections.Counter()
        cnt: Counter = collections.Counter()
        hit = False
        for line in plane.lines:
            if not line.name.startswith("tf_XLA"):
                continue
            hit = True
            a, c = _line_self_times(line.events, ev_meta)
            agg.update(a)
            cnt.update(c)
        if hit:
            yield plane.name, agg, cnt


def op_scope(tf_op: str) -> str:
    """The scope of a device op from its ``tf_op`` stat (``<op_name>:<type>``):
    ``jit(step)/shard_map/ddlpc/update/mul:`` → ``shard_map/ddlpc/update``.
    The ``ddlpc/*`` components are the step's ``jax.named_scope``s
    (parallel/train_step.py), the rest Flax module names and JAX transforms
    (``transpose(jvp(UNet))/DetailHead_0/Conv_0`` is a backward op of the head)
    — the names the benchmark's per-region device metrics group by."""
    parts = tf_op.rsplit(":", 1)[0].split("/")
    if parts[0].startswith(("jit(", "pjit(")):
        parts = parts[1:]
    return "/".join(parts[:-1])


def op_scopes(trace_dir: str) -> dict:
    """``{op name: scope}`` for every device-plane op whose metadata carries a
    ``tf_op`` stat (TPU/GPU traces; the CPU backend's events carry none)."""
    out: dict = {}
    for plane in load_xspace(trace_dir).planes:
        if not plane.name.startswith("/device:"):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        for meta in plane.event_metadata.values():
            for stat in meta.stats:
                if stat_names.get(stat.metadata_id) == "tf_op":
                    tf_op = stat.str_value or stat_names.get(stat.ref_value, "")
                    if tf_op:
                        out[meta.name] = op_scope(tf_op)
    return out


def top_ops_report(
    trace_dir: str, top: int = 30, steps: int = 1, tag: str = ""
) -> dict:
    """The committed top-ops JSON format (docs/head_bench/trace_*.json
    introduced it; the on-demand hooks emit the same shape, plus the planes
    the ops came from and, where the trace gives one, each op's ``scope``).
    ``steps`` normalizes to per-step milliseconds."""
    steps = max(int(steps), 1)
    agg: Counter = collections.Counter()
    cnt: Counter = collections.Counter()
    planes: List[str] = []
    for plane_name, a, c in self_times_any(trace_dir):
        planes.append(plane_name)
        agg.update(a)
        cnt.update(c)
    total_ps = sum(agg.values())
    scopes = op_scopes(trace_dir)
    return {
        "tag": tag,
        "trace_dir": os.path.abspath(trace_dir),
        "planes": planes,
        "steps_traced": steps,
        "device_total_ms": round(total_ps / 1e9, 3),
        "per_step_ms": round(total_ps / 1e9 / steps, 3),
        "top_self_time": [
            {
                "op": name[:160],
                "self_ms_per_step": round(ps / 1e9 / steps, 4),
                "count": cnt[name],
                # where the trace names one (docs/OBSERVABILITY.md "Device
                # scopes"): the region this op's time belongs to
                **({"scope": scopes[name]} if name in scopes else {}),
            }
            for name, ps in agg.most_common(top)
        ],
    }
