"""``program_spans`` on a hand-built trace (a TPU-shaped one and a CPU-shaped
one, written with the same ``xplane_pb2`` it reads with), and a rehearsed
traced run that prints every metric this module's readers report."""

import json
import os
import subprocess
import sys

import pytest

import program_spans
from conftest import BENCH, ROOT

MS = 1_000_000  # ns


def add_plane(space, name, lines, tf_ops=None):
    """lines: {line name: [(event name, start_ns, end_ns, {stat: value})]};
    tf_ops: {event name: tf_op} put on the event's metadata, as a TPU does."""
    plane = space.planes.add(name=name)
    stat_ids, event_ids = {}, {}

    def stat_id(key):
        if key not in stat_ids:
            stat_ids[key] = len(stat_ids) + 1
            plane.stat_metadata[stat_ids[key]].name = key
        return stat_ids[key]

    def put(stats, key, value):
        stat = stats.add(metadata_id=stat_id(key))
        if isinstance(value, bytes):
            stat.bytes_value = value
        elif isinstance(value, int):
            stat.int64_value = value
        else:
            stat.str_value = value

    for line_name, events in lines.items():
        line = plane.lines.add(name=line_name, timestamp_ns=7)
        for ev, start, end, stats in events:
            if ev not in event_ids:
                event_ids[ev] = len(event_ids) + 1
                meta = plane.event_metadata[event_ids[ev]]
                meta.id, meta.name = event_ids[ev], ev
                if tf_ops and ev in tf_ops:
                    put(meta.stats, "tf_op", tf_ops[ev])
            event = line.events.add(
                metadata_id=event_ids[ev], offset_ps=(start - 7) * 1000, duration_ps=(end - start) * 1000
            )
            for key, value in stats.items():
                put(event.stats, key, value)
    return plane


STEP = "jit(step)/shard_map/"
ACC = STEP + "ddlpc/accumulate/while/body/closed_call/"
TF_OPS = {
    "%while.2 = while(...)": STEP + "ddlpc/accumulate/while:",
    "%fusion.1 = fwd conv": ACC + "jvp(UNet)/DownBlock_0/conv_general_dilated:",
    "%fusion.2 = head bwd": ACC + "transpose(jvp(UNet))/DetailHead_0/Conv_0/conv_general_dilated:",
    "%fusion.3 = bwd conv": ACC + "transpose(jvp(UNet))/DownBlock_0/conv_general_dilated:",
    "%fusion.4 = loss bwd": ACC + "transpose(jvp(ddlpc/loss))/mul:",
    "%all-reduce.5 = wire": STEP + "ddlpc/grad_sync/psum:",
    "%fusion.6 = adam": STEP + "ddlpc/update/mul:",
    "%all-gather.7 = publish": STEP + "ddlpc/update/gather/all_gather:",
    "%fusion.10 = take": "jit(gather)/ddlpc/gather/jit(_take)/select_n:",
}
HOST = {
    "python": [
        ("bench:window", 0, 100 * MS, {}),
        ("ddlpc:epoch", 2 * MS, 60 * MS, {"epoch": 0}),
        ("ddlpc:step#epoch=0,step=0#", 3 * MS, 6 * MS, {}),  # the '#'-suffixed spelling
        ("ddlpc:metrics_fetch", 6 * MS, 58 * MS, {"epoch": 0}),
        ("ddlpc:epoch_tail", 58 * MS, 60 * MS, {"epoch": 0}),
        ("ddlpc:log", 61 * MS, 64 * MS, {"epoch": 0}),
        ("ddlpc:epoch", 66 * MS, 100 * MS, {"epoch": 1}),
        ("ddlpc:data", 66 * MS, 69 * MS, {"epoch": 1}),
        ("ddlpc:step", 69 * MS, 72 * MS, {"epoch": 1}),
        ("unrelated", 0, 100 * MS, {}),
    ],
    "worker": [("ddlpc:loader_gather", 50 * MS, 70 * MS, {})],  # another thread: ignored
}


@pytest.fixture(scope="module")
def pb2():
    module = program_spans._xplane_pb2()
    if module is None:
        pytest.skip("no xplane_pb2 in this installation")
    return module


@pytest.fixture(scope="module")
def tpu_trace(pb2, tmp_path_factory):
    space = pb2.XSpace()
    add_plane(
        space,
        "/device:TPU:0",
        {
            "XLA Ops": [
                ("%while.2 = while(...)", 10 * MS, 50 * MS, {}),  # encloses the next five, 1 ms its own
                ("%fusion.1 = fwd conv", 10 * MS, 20 * MS, {}),
                ("%fusion.2 = head bwd", 20 * MS, 32 * MS, {}),
                ("%fusion.3 = bwd conv", 32 * MS, 40 * MS, {}),
                ("%fusion.4 = loss bwd", 40 * MS, 46 * MS, {}),
                ("%copy.9 = layout copy", 46 * MS, 49 * MS, {}),  # no tf_op: the while's
                ("%all-reduce.5 = wire", 50 * MS, 52 * MS, {}),
                ("%fusion.6 = adam", 52 * MS, 53 * MS, {}),
                ("%all-gather.7 = publish", 53 * MS, 55 * MS, {}),
                ("%copy.8 = parameter copy", 55 * MS, 57 * MS, {}),  # no tf_op at all
                ("%fusion.1 = fwd conv", 70 * MS, 96 * MS, {}),  # after a 13 ms gap
                ("%fusion.10 = take", 96 * MS, 98 * MS, {}),
                ("%pad.11 = pad", 98 * MS, 100 * MS, {}),  # no tf_op, no op round it: its program's
            ],
            "XLA Modules": [("jit_step(3)", 9 * MS, 96 * MS, {}), ("jit_gather(7)", 96 * MS, 100 * MS, {})],
        },
        TF_OPS,
    )
    add_plane(space, "/device:TPU:1", {"XLA Ops": [("%fusion.6 = adam", 0, 100 * MS, {})]}, TF_OPS)
    add_plane(space, "/host:CPU", HOST)
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return program_spans.load(str(path))


def test_loads_first_chip_ops_with_op_names_and_the_windows_thread(tpu_trace):
    assert len(tpu_trace["ops"]) == 13  # plane 0's XLA Ops line alone
    assert tpu_trace["ops"][0] == (STEP + "ddlpc/accumulate/while", 10.0 * MS, 50.0 * MS)
    assert tpu_trace["ops"][5][0] == STEP + "ddlpc/accumulate/while"  # the copy inside it, inherited
    assert tpu_trace["ops"][9][0] == "jit_step(3)"  # the copy no op encloses: its program's run
    assert tpu_trace["ops"][12][0] == "jit_gather(7)"
    assert [h[0] for h in tpu_trace["host"]["python"]].count("ddlpc:step") == 2
    assert set(tpu_trace["host"]) == {"python", "worker"}


def test_a_gap_is_split_across_the_spans_it_straddles(tpu_trace, monkeypatch):
    idle = program_spans.idle_by_span(tpu_trace)
    # 0-10 ms: window start 2, epoch 1, step 3, metrics_fetch 4
    # 57-70 ms: metrics_fetch 1, epoch_tail 2, unnamed 1, log 3, unnamed 2, data 3, step 1
    assert idle == pytest.approx(
        {"unnamed": 5.0, "epoch": 1.0, "step": 4.0, "metrics_fetch": 5.0, "epoch_tail": 2.0,
         "log": 3.0, "data": 3.0}
    )
    assert sum(idle.values()) == pytest.approx(23.0)  # all of the window's idle, once
    run = {"records": [{}, {}], "steps_per_epoch": 1}
    monkeypatch.setattr(program_spans, "_this_run", lambda: (idle, None))
    assert program_spans.idle_ms_per_epoch(run, program_spans.GROUPS["fetch_tail"]) == pytest.approx(3.5)
    assert program_spans.idle_ms_per_epoch(run, program_spans.GROUPS["dispatch"]) == pytest.approx(3.5)
    assert program_spans.idle_ms_per_epoch(run, None) == pytest.approx(2.0)  # epoch + log
    assert program_spans.idle_unnamed_pct(run) == pytest.approx(100 * 5 / 23)


def test_regions_by_precedence_nested_once_and_unnamed(tpu_trace, monkeypatch):
    regions = program_spans.device_by_region(tpu_trace)
    assert regions == pytest.approx(
        {
            "forward": 10.0 + 26.0 + 1.0 + 3.0,  # two convs, the while's own 1 ms, the copy inside it
            "detail_head": 12.0,  # before 'backward', though its op_name holds transpose( too
            "backward": 8.0,
            "loss": 6.0,  # before 'backward' likewise
            "grad_sync": 2.0,
            "update": 1.0 + 2.0,  # ddlpc/update/gather is the update's
            "gather": 2.0 + 2.0,  # by its scope, and by its program where XLA left no name
            "unnamed": 2.0,  # the op without an op_name in the step, outside the loop
        }
    )
    assert sum(regions.values()) == pytest.approx(77.0)  # the busy time, the while's body once
    run = {"records": [{}, {}], "steps_per_epoch": 1}
    monkeypatch.setattr(program_spans, "_this_run", lambda: (None, regions))
    assert program_spans.region_ms_per_step(run, "update") == pytest.approx(1.5)
    assert program_spans.region_ms_per_step(run, "nothing_like_it") is None
    assert program_spans.region_pct(run, "unnamed") == pytest.approx(100 * 2 / 77)


def test_a_program_without_spans_or_scopes_reads_none(pb2, tmp_path):
    """The parent of the PR that added them: Flax names alone, bench: spans alone."""
    space = pb2.XSpace()
    add_plane(
        space, "/device:TPU:0",
        {"XLA Ops": [("%fusion.2 = head bwd", 10 * MS, 20 * MS, {})]},
        {"%fusion.2 = head bwd": "jit(step)/transpose(jvp(UNet))/DetailHead_0/mul:"},
    )
    add_plane(space, "/host:CPU", {"python": [("bench:window", 0, 100 * MS, {}), ("bench:train_epoch", 0, 50 * MS, {})]})
    path = tmp_path / "p.xplane.pb"
    path.write_bytes(space.SerializeToString())
    trace = program_spans.load(str(path))
    assert program_spans.idle_by_span(trace) is None
    assert program_spans.device_by_region(trace) is None
    assert program_spans.init_s({"warmup_records": [{"loss": 1.0}]}, "state") is None


def test_cpu_ops_take_their_op_name_from_the_stored_hlo(pb2, tmp_path):
    def message(*fields):
        out = b""
        for number, value in fields:
            value = value.encode() if isinstance(value, str) else value
            out += bytes([number << 3 | 2, len(value)]) + value
        return out

    instruction = message((1, "fusion.9"), (2, "fusion"), (7, message((1, "mul"), (2, "jit(f)/ddlpc/update/mul"))))
    hlo = message((1, message((1, "jit_f"), (3, message((1, "main"), (2, instruction))))))
    assert program_spans.hlo_op_names(hlo) == {"fusion.9": "jit(f)/ddlpc/update/mul"}
    space = pb2.XSpace()
    meta = add_plane(space, "/host:metadata", {})
    meta.stat_metadata[1].name = "Hlo Proto"
    meta.event_metadata[5].name = "jit_f(5)"
    meta.event_metadata[5].stats.add(metadata_id=1).bytes_value = hlo
    add_plane(
        space, "/host:CPU",
        {
            "python": [("bench:window", 0, 100 * MS, {}), ("ddlpc:step", 0, 40 * MS, {})],
            "tf_XLAPjRtCpuClient/1": [
                ("fusion.9", 10 * MS, 30 * MS, {"hlo_op": "fusion.9", "program_id": 5, "device_ordinal": 0}),
                ("fusion.9", 10 * MS, 30 * MS, {"hlo_op": "fusion.9", "program_id": 5, "device_ordinal": 1}),
                ("end: fusion.9", 30 * MS, 30 * MS, {}),
            ],
        },
    )
    path = tmp_path / "c.xplane.pb"
    path.write_bytes(space.SerializeToString())
    trace = program_spans.load(str(path))
    assert trace["ops"] == [("jit(f)/ddlpc/update/mul", 10.0 * MS, 30.0 * MS)]
    assert program_spans.device_by_region(trace) == pytest.approx({"update": 20.0})
    assert program_spans.idle_by_span(trace) == pytest.approx({"step": 20.0, "unnamed": 60.0})


def test_rehearsed_traced_run_prints_every_metric_of_these_readers():
    """On the CPU the values say nothing; the names must all be there, through
    run.py as it stands, the trace found by program_spans itself."""
    cell = "unet_pod4.cached"  # lists every metric: the DetailHead and the wire are both in it
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mine = {
        m["name"]
        for m in manifest["per_layer"]
        if "program_spans" in open(os.path.join(BENCH, "layer_metrics", m["name"] + ".py")).read()
        and cell in m.get("workloads", [cell])
    }
    assert len(mine) == 15
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147495994", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert mine <= set(line["metrics"]), mine - set(line["metrics"])
    for name in mine:
        assert line["metrics"][name]["value"] >= 0
