"""The trace reduction on a hand-built trace: overlapping and nested events,
two lines on the device plane, a gap under an annotation.  The trace is
encoded as an XSpace protobuf by hand and read back through
``jax.profiler.ProfileData``, as a real one is."""

import pytest

import trace_reduce


def varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def plane(name, lines):
    """lines: {line name: [(event name, start_ns, end_ns)]}"""
    ids, body = {}, field(2, name)
    for line_name, events in lines.items():
        line = field(2, line_name)
        for ev, s, e in events:
            ids.setdefault(ev, len(ids) + 1)
            line += field(4, field(1, ids[ev]) + field(2, s * 1000) + field(3, (e - s) * 1000))
        body += field(3, line)
    for ev, i in ids.items():
        body += field(4, field(1, i) + field(2, field(1, i) + field(2, ev)))
    return field(1, body)


MS = 1_000_000
FUSION = "%fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} %p0), kind=kLoop"
WHILE = "%while.2 = (s32[]{:T(128)}, f32[4]{0}) while((s32[]{:T(128)}, f32[4]{0}) %tuple.1)"
ALLRED = "%all-reduce.3 = f32[4]{0} all-reduce(f32[4]{0} %x), replica_groups={}"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    device = plane(
        "/device:TPU:0",
        {
            "XLA Ops": [
                (WHILE, 10 * MS, 50 * MS),  # encloses the next two
                (FUSION, 10 * MS, 30 * MS),
                (ALLRED, 30 * MS, 40 * MS),
                (FUSION, 70 * MS, 90 * MS),  # after a 20 ms gap
                (FUSION, 85 * MS, 95 * MS),  # overlaps the previous one
            ],
            "XLA Modules": [("jit_step", 0, 100 * MS)],  # another line: ignored
        },
    )
    host = plane(
        "/host:CPU",
        {
            "python": [
                ("bench:window", 0, 100 * MS),
                ("bench:train_epoch", 2 * MS, 55 * MS),
                ("bench:train_epoch", 65 * MS, 100 * MS),
                ("unrelated", 0, 100 * MS),
            ],
            "worker": [("bench:save", 56 * MS, 64 * MS)],
        },
    )
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(device + host)
    return trace_reduce.load(str(path), "bench:")


def test_load_keeps_ops_line_and_annotations(trace):
    assert list(trace["devices"]) == [0] and len(trace["devices"][0]) == 5
    assert sorted({h[0] for h in trace["host"]}) == ["bench:save", "bench:train_epoch", "bench:window"]


def test_reduce(trace):
    r = trace_reduce.reduce(trace, "bench:window", "bench:train_epoch", steps=2)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == {0: pytest.approx(0.065)}  # [10,50] and [70,95], nothing twice
    assert r["epoch_overhead_s"] == pytest.approx([0.013, 0.010])
    assert r["collective_s"] == pytest.approx(0.010)
    ops = dict(r["device_ops"])
    # partly overlapping events of one line: the overlap counts for the later one
    assert ops["%fusion.1 = bf16[8,128] fusion"] == pytest.approx(0.045)
    assert sum(ops.values()) == pytest.approx(r["busy_s"][0])
    assert ops["%while.2 = (s32[], f32[4]) while"] == pytest.approx(0.010)  # 40 less its body's 30
    gaps = dict(r["idle_gaps"])
    assert gaps["bench:save after %while.2 = (s32[], f32[4]) while"] == pytest.approx(0.020)
    assert gaps["bench:train_epoch after window start"] == pytest.approx(0.010)
    assert gaps["bench:train_epoch after %fusion.1 = bf16[8,128] fusion"] == pytest.approx(0.005)
    assert sum(gaps.values()) == pytest.approx(0.035)


def test_no_device_plane_reads_nothing(trace):
    assert trace_reduce.reduce({"devices": {}, "host": trace["host"]}, "bench:window", "x", 1) is None
