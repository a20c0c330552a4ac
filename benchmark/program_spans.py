"""The program's own spans and scopes, read from the trace this process just
wrote: where the device sat idle, by the ``ddlpc:`` host span open meanwhile,
and where the step's device time went, by the ``ddlpc/`` scope and Flax module
in each instruction's ``op_name``.  Shared by the readers in ``layer_metrics/``
that report them; a program without the spans or scopes (the parent of the PR
that added them) reads as None everywhere, and the line leaves the metric out.

``run.py`` hands a reader no path to the trace and loads only ``bench:`` host
events, so until a ``benchmark`` PR passes both through, this module finds the
trace itself: the newest ``*.xplane.pb`` under ``<tmp>/ddlpc_bench_*/trace``
written since this process started (``run.py`` removes the directory only after
the readers ran).

What a TPU v5e trace holds beyond ``trace_reduce``'s notes (looked at by hand,
PR 25): an ``XLA Ops`` event's name is the instruction's text without its
metadata; the ``op_name`` is the ``tf_op`` stat (``<op_name>:<op_type>``) of the
event's *metadata*, which ``jax.profiler.ProfileData`` does not show, so the
file is parsed with the ``xplane_pb2`` that ships inside TensorFlow, loaded by
path (TensorFlow itself is not imported).  The layout copies XLA inserts carry
no ``op_name`` at all (18 ms of the flagship's 328 ms step, inside the scan):
such an op takes the name of the event that encloses it, and one that no op
encloses (a third of the device cache's gather program) the name of its
program's run on the ``XLA Modules`` line.  A ``TraceAnnotation`` with arguments
appears under its plain name with the arguments as stats.  The CPU backend (a
rehearsal) has no device plane: its ops are the events with an ``hlo_op`` stat
on the host plane's ``tf_XLA*`` lines, and their ``op_name`` is joined from the
``Hlo Proto`` the profiler stores on plane ``/host:metadata``.

All times returned are milliseconds.
"""

from __future__ import annotations

import bisect
import functools
import glob
import importlib.util
import json
import os
import tempfile

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOW = "bench:window"  # run.py's annotation round the measured fit()
SPAN_PREFIX = "ddlpc:"  # ddlpc_tpu/train/observability.py:StageTimer.stage
SCOPE_PREFIX = "ddlpc/"  # jax.named_scope in parallel/train_step.py and beside
UNNAMED = "unnamed"
MODULES_LINE = "XLA Modules"  # one event per run of a compiled program
CPU_OPS_LINE = "tf_XLA"
METADATA_PLANE = "/host:metadata"


def find_trace() -> str | None:
    try:
        import psutil

        started = psutil.Process().create_time()
    except ImportError:
        started = 0.0
    pattern = os.path.join(tempfile.gettempdir(), "ddlpc_bench_*", "trace")
    paths = [trace_reduce.find_trace(d) for d in glob.glob(pattern)]
    paths = [p for p in paths if p and os.path.getmtime(p) >= started]
    return max(paths, key=os.path.getmtime) if paths else None


def _xplane_pb2():
    """TensorFlow's generated ``xplane_pb2`` as a module of its own: it needs
    ``google.protobuf`` alone, and importing TensorFlow takes seconds."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = os.path.join(
        spec.submodule_search_locations[0], "tsl", "profiler", "protobuf", "xplane_pb2.py"
    )
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("ddlpc_bench_xplane_pb2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        value |= (buf[i] & 0x7F) << shift
        i, shift = i + 1, shift + 7
        if buf[i - 1] < 0x80:
            return value, i


def _fields(buf: bytes):
    """``(field number, value)`` of one protobuf message: an int for a varint,
    bytes for a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire}")
            value, i = buf[i : i + size], i + size
        yield key >> 3, value


def hlo_op_names(hlo_proto: bytes) -> dict:
    """``{instruction name: op_name}`` of a serialized ``xla.HloProto``
    (hlo_module=1 > computations=3 > instructions=2 > name=1, metadata=7 >
    op_name=2), read field by field: no generated class for it ships here."""
    out = {}
    for n1, module in _fields(hlo_proto):
        if n1 != 1:
            continue
        for n2, computation in _fields(module):
            if n2 != 3:
                continue
            for n3, instruction in _fields(computation):
                if n3 != 2:
                    continue
                name = op_name = None
                for n4, value in _fields(instruction):
                    if n4 == 1:
                        name = value.decode()
                    elif n4 == 7:
                        for n5, v in _fields(value):
                            if n5 == 2:
                                op_name = v.decode()
                if name and op_name:
                    out[name] = op_name
    return out


def _inherit(ops: list, modules: list) -> list:
    """An op without an ``op_name`` takes that of the event that encloses it:
    the layout copies XLA puts into a ``while`` body carry no metadata and
    belong to the scope the loop runs under; an op of a program with no such
    event belongs to the program, and takes the name of its run on the
    ``XLA Modules`` line (``jit_gather(<id>)``)."""
    out: list = []
    stack: list = []  # (name, end) of the events open at this point
    events = [(n, s, e, True) for n, s, e in modules] + [(n, s, e, False) for n, s, e in ops]
    for name, s, e, module in sorted(events, key=lambda ev: (ev[1], -ev[2], not ev[3])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if not name and stack:
            name = stack[-1][0]
        stack.append((name, e))
        if not module:
            out.append((name, s, e))
    return out


def _stats(stats, names) -> dict:
    out = {}
    for s in stats:
        kind = s.WhichOneof("value")
        value = getattr(s, kind) if kind else None
        out[names.get(s.metadata_id, "")] = names.get(value, "") if kind == "ref_value" else value
    return out


def load(path: str) -> dict | None:
    """``{"ops": [(op_name, start_ns, end_ns)], "host": {thread: [(name,
    start_ns, end_ns)]}}``: the first chip's op events with the ``op_name`` of
    each ("" where the trace gives none) and, per host thread, the annotations
    that start with ``bench:`` or ``ddlpc:``.  None without the proto module."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())

    def events(plane, line):
        base = line.timestamp_ns * 1000
        for e in line.events:
            start = (base + e.offset_ps) / 1000.0
            yield plane.event_metadata[e.metadata_id], e, start, start + e.duration_ps / 1000.0

    planes = {p.name: p for p in space.planes}
    device = min(
        (p for p in planes if trace_reduce.DEVICE_PLANE.match(p)),
        key=lambda p: int(trace_reduce.DEVICE_PLANE.match(p).group(1)),
        default=None,
    )
    ops: list = []
    host: dict = {}
    if device is not None:
        plane = planes[device]
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        op_name = {
            k: str(_stats(m.stats, names).get("tf_op", "")).rsplit(":", 1)[0]
            for k, m in plane.event_metadata.items()
        }
        modules: list = []
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                ops.extend((op_name[e.metadata_id], s, t) for _, e, s, t in events(plane, line))
            elif line.name == MODULES_LINE:
                modules.extend((m.name, s, t) for m, _, s, t in events(plane, line))
        ops = _inherit(ops, modules)
    plane = planes.get(trace_reduce.HOST_PLANE)
    if plane is not None:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        joined: dict = {}
        if device is None and METADATA_PLANE in planes:
            meta = planes[METADATA_PLANE]
            meta_names = {k: v.name for k, v in meta.stat_metadata.items()}
            for program, m in meta.event_metadata.items():
                proto = _stats(m.stats, meta_names).get("Hlo Proto")
                if proto:
                    joined[program] = hlo_op_names(proto)
        for line in plane.lines:
            cpu_ops = device is None and line.name.startswith(CPU_OPS_LINE)
            for m, e, s, t in events(plane, line):
                if m.name.startswith((SPAN_PREFIX, "bench:")):
                    host.setdefault(line.name, []).append((m.name.split("#")[0], s, t))
                elif cpu_ops and t > s:
                    st = _stats(e.stats, names)
                    if "hlo_op" in st and st.get("device_ordinal", 0) == 0:
                        ops.append((joined.get(st.get("program_id"), {}).get(st["hlo_op"], ""), s, t))
    return {"ops": ops, "host": host}


def _window(trace: dict):
    """The measured window and the annotations of its thread."""
    for spans in trace["host"].values():
        windows = [h for h in spans if h[0] == WINDOW]
        if windows:
            _, lo, hi = max(windows, key=lambda h: h[2] - h[1])
            return lo, hi, [h for h in spans if h[0].startswith(SPAN_PREFIX)]
    return None


def leaf_intervals(spans) -> list:
    """``[(name, start, end)]``: each moment under the innermost span open in
    it (a span's own time, its children's taken out); spans of one thread."""
    out: list = []
    stack: list = []  # [name, end, cursor]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, cursor = stack.pop()
            if end > cursor:
                out.append((name, cursor, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, s, e in sorted(spans, key=lambda h: (h[1], -h[2])):
        close(s)
        if stack:
            if s > stack[-1][2]:
                out.append((stack[-1][0], stack[-1][2], s))
            e = min(e, stack[-1][1])
        stack.append([name, e, s])
    close(float("inf"))
    return out


def idle_by_span(trace: dict) -> dict | None:
    """Milliseconds the first chip ran no op inside the window, by the
    ``ddlpc:`` span (prefix dropped) innermost on the window's thread meanwhile,
    each gap split across the spans it overlaps; ``unnamed`` where none was
    open.  None without the window, device ops or any ``ddlpc:`` span."""
    found = _window(trace)
    if not found or not trace["ops"] or not found[2]:
        return None
    lo, hi, spans = found
    busy = trace_reduce.union(((s, e) for _, s, e in trace["ops"]), lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    ends = [b for _, b in gaps]
    out = {UNNAMED: trace_reduce.total(gaps)}
    for name, s, e in leaf_intervals(spans):
        part, i = 0.0, bisect.bisect_right(ends, s)  # the first gap that ends after s
        while i < len(gaps) and gaps[i][0] < e:
            part += min(e, gaps[i][1]) - max(s, gaps[i][0])
            i += 1
        name = name[len(SPAN_PREFIX) :]
        out[name] = out.get(name, 0.0) + part
        out[UNNAMED] -= part
    return {k: v / 1e6 for k, v in out.items()}


@functools.lru_cache(maxsize=1)
def region_table() -> tuple:
    with open(os.path.join(HERE, "regions.json")) as f:
        return tuple((r["region"], r["op_name_has"]) for r in json.load(f)["regions"])


def region_of(op_name: str) -> str:
    for region, needle in region_table():
        if needle in op_name:
            return region
    return UNNAMED


def device_by_region(trace: dict) -> dict | None:
    """Milliseconds of device self time inside the window by region of
    ``regions.json`` (first match on ``op_name``, else ``unnamed``); an event
    that encloses others (a ``while``) counts its own time once.  None without
    the window or any ``ddlpc/`` scope among the op names."""
    found = _window(trace)
    if not found or not any(SCOPE_PREFIX in name for name, _, _ in trace["ops"]):
        return None
    lo, hi, _ = found
    inside = [(region_of(name), s, e) for name, s, e in trace["ops"] if e > lo and s < hi]
    return {k: v * 1e3 for k, v in trace_reduce.self_times(inside).items()}


@functools.lru_cache(maxsize=2)
def _reduced(path: str) -> tuple:
    trace = load(path)
    return (idle_by_span(trace), device_by_region(trace)) if trace else (None, None)


def _this_run() -> tuple:
    """``(idle_by_span, device_by_region)`` of the trace this process wrote,
    reduced once for all the readers."""
    path = find_trace()
    return _reduced(path) if path else (None, None)


def idle_ms_per_epoch(run: dict, spans: tuple | None) -> float | None:
    """Idle under the named spans per epoch of the window; ``spans=None`` means
    every ``ddlpc:`` span that ``GROUPS`` does not name."""
    idle, _ = _this_run()
    if idle is None or not run["records"]:
        return None
    if spans is None:
        named = {n for group in GROUPS.values() for n in group} | {UNNAMED}
        spans = tuple(n for n in idle if n not in named)
    return sum(idle.get(n, 0.0) for n in spans) / len(run["records"])


def idle_unnamed_pct(run: dict) -> float | None:
    idle, _ = _this_run()
    if idle is None or not sum(idle.values()):
        return None
    return 100.0 * idle[UNNAMED] / sum(idle.values())


def region_ms_per_step(run: dict, region: str) -> float | None:
    """Region self time per optimizer step; None where nothing matched."""
    _, regions = _this_run()
    steps = len(run["records"]) * run["steps_per_epoch"]
    if regions is None or not steps or not regions.get(region):
        return None
    return regions[region] / steps


def region_pct(run: dict, region: str) -> float | None:
    _, regions = _this_run()
    if regions is None or not sum(regions.values()):
        return None
    return 100.0 * regions.get(region, 0.0) / sum(regions.values())


# The Trainer loop's spans by the metric that reports the idle time under them.
GROUPS = {
    "fetch_tail": ("metrics_fetch", "epoch_tail"),
    "dispatch": ("epoch_head", "data", "step"),
}


def init_s(run: dict, *phases: str) -> float | None:
    """Seconds of ``Trainer.__init__`` phases from the first record of the
    Trainer's life (``t_init_<phase>_s``); None where the program writes none."""
    first = run["warmup_records"][0] if run["warmup_records"] else {}
    keys = [f"t_init_{p}_s" for p in phases]
    return sum(first[k] for k in keys) if all(k in first for k in keys) else None
