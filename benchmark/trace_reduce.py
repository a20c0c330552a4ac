"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read.  Reads the file with ``jax.profiler.ProfileData`` and nothing
else (no TensorFlow).

What a TPU v5e trace holds (looked at by hand, PR 23's builder run and this
PR's): one plane ``/device:TPU:<n>`` per chip whose line ``XLA Ops`` carries
one event per executed HLO instruction (its name is the instruction's whole
text; a ``while`` encloses its body's events), and a plane ``/host:CPU`` with
one line per thread, where ``jax.profiler.TraceAnnotation`` spans appear
under their own names.  Both are on one clock, nanoseconds from trace start.

All times returned are seconds.
"""

from __future__ import annotations

import glob
import os
import re
from bisect import bisect_right

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
)
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


def find_trace(trace_dir: str) -> str | None:
    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    return files[-1] if files else None


def load(path: str, annotation_prefix: str) -> dict:
    """``{"devices": {n: [(name, start_ns, end_ns), ...]}, "host": [...]}``:
    the op events of every device plane and the host annotations whose name
    starts with ``annotation_prefix``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            events = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    events.extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(annotation_prefix)
                )
    return {"devices": devices, "host": host}


def short_name(hlo: str, limit: int = 100) -> str:
    """``%fusion.7 = bf16[128,512,512,16] fusion``: the instruction's name,
    output shape and opcode, without layouts and operands."""
    text = _LAYOUT.sub("", _LAYOUT.sub("", hlo))
    m = _OPCODE.search(text)
    if m:
        text = text[: m.end() - 1]
    return text[:limit]


def union(intervals, lo=None, hi=None) -> list:
    """Merged, sorted ``[(start, end)]`` of possibly overlapping or nested
    intervals, clipped to ``[lo, hi]`` where given."""
    out: list = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def self_times(events) -> dict:
    """Seconds per event name with the time of enclosed events taken out, so
    a ``while`` does not count its body twice."""
    out: dict[str, float] = {}
    stack: list = []  # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + own / 1e9

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


def _innermost(host, t):
    best = None
    for name, s, e in host:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "no annotation"


def reduce(
    trace: dict, window: str, epoch: str, steps: int, chips: int | None = None, top: int = 10
) -> dict | None:
    """The traced window in numbers.  ``window`` and ``epoch`` are the names
    of the host annotations round the whole measured call and round each
    epoch; ``steps`` is the number of optimizer steps the window ran; of the
    device planes the first ``chips`` count (the cell's, on a larger host).
    Returns None where there is no device plane or no window annotation."""
    spans = [h for h in trace["host"] if h[0] == window]
    if not trace["devices"] or not spans:
        return None
    _, lo, hi = max(spans, key=lambda h: h[2] - h[1])
    busy = {
        n: union(((s, e) for _, s, e in evs), lo, hi)
        for n, evs in sorted(trace["devices"].items())[:chips]
    }
    first = min(busy)
    inside = [ev for ev in trace["devices"][first] if ev[2] > lo and ev[1] < hi]
    own = self_times(inside)

    epochs = sorted((s, e) for name, s, e in trace["host"] if name == epoch and s >= lo and e <= hi)
    overhead = [
        ((e - s) - total(union(busy[first], s, e))) / 1e9 for s, e in epochs
    ]

    gaps: dict[str, float] = {}
    edges = [lo] + [t for iv in busy[first] for t in iv] + [hi]
    ends = sorted(inside, key=lambda ev: ev[2])
    end_times = [ev[2] for ev in ends]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        i = bisect_right(end_times, a)
        before = short_name(ends[i - 1][0], 60) if i else "window start"
        label = f"{_innermost(trace['host'], (a + b) / 2)} after {before}"
        gaps[label] = gaps.get(label, 0.0) + (b - a) / 1e9

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    collective = sum(
        (e - s) for name, s, e in inside if COLLECTIVE.match(name)
    ) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": {n: total(iv) / 1e9 for n, iv in busy.items()},
        "steps": steps,
        "epoch_overhead_s": overhead,
        "collective_s": collective,
        "device_ops": [[short_name(k), v] for k, v in ranked(own)],
        "idle_gaps": ranked(gaps),
    }
