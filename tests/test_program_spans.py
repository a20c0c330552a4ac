"""The program's own spans and scopes (ISSUE 25, docs/OBSERVABILITY.md "Spans
on the profiler's clock"): every region of the compiled step carries a
``ddlpc/`` scope in its instructions' ``op_name``, every host phase of the
Trainer is a ``ddlpc:`` ``TraceAnnotation`` that a profiler session records
on the same clock as the device ops, and construction's phases reach the
first epoch record.  CPU: names, nesting and counts only, no times."""

import glob
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from ddlpc_tpu.config import (
    CompressionConfig,
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    ParallelConfig,
    TrainConfig,
)
from ddlpc_tpu.models import build_model
from ddlpc_tpu.obs import tracing
from ddlpc_tpu.obs.schema import check_record
from ddlpc_tpu.obs.tracing import Tracer
from ddlpc_tpu.obs.xplane import op_scope
from ddlpc_tpu.parallel.mesh import make_mesh
from ddlpc_tpu.parallel.train_step import (
    create_train_state,
    make_train_step,
    make_train_step_gspmd,
    make_update_step,
)
from ddlpc_tpu.train.observability import ANNOTATION_PREFIX, StageTimer
from ddlpc_tpu.train.optim import build_optimizer
from ddlpc_tpu.train.trainer import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MCFG = ModelConfig(
    features=(4, 8), bottleneck_features=8, num_classes=3, detail_head=True
)
H = W = 16
# Instructions that move or name data and never show up as a device event of
# their own; XLA gives the constants it hoists the computation's bare name.
PLUMBING = {"constant", "parameter", "tuple", "get-tuple-element", "bitcast", "broadcast"}


def _regions():
    with open(os.path.join(REPO, "benchmark", "regions.json")) as f:
        return [(r["region"], r["op_name_has"]) for r in json.load(f)["regions"]]


def _region_of(op_name, table):
    return next((r for r, needle in table if needle in op_name), "unnamed")


def _lowered(builder):
    tx = build_optimizer(TrainConfig(learning_rate=1e-2))
    images = jnp.zeros((2, 4, H, W, 3))
    labels = jnp.zeros((2, 4, H, W), jnp.int32)
    if builder == "gspmd":
        mesh = make_mesh(
            ParallelConfig(data_axis_size=2, space_axis_size=2), jax.devices()[:4]
        )
        model = build_model(MCFG, norm_axis_name=None)
        codec = CompressionConfig(mode="int8", quantize_local=False)
        step = make_train_step_gspmd(model, tx, mesh, codec, donate_state=False)
    else:
        mesh = make_mesh(
            ParallelConfig(data_axis_size=2, space_axis_size=1), jax.devices()[:2]
        )
        model = build_model(MCFG, norm_axis_name="data")
        codec = CompressionConfig(mode="float16")
        step = (
            make_train_step(model, tx, mesh, codec, donate_state=False)
            if builder == "shard_map"
            else make_update_step(tx, mesh, codec)
        )
    state = create_train_state(model, tx, jax.random.key(0), (1, H, W, 3))
    if builder == "update":
        return step.lower(state.params, state.opt_state, state.params)
    return step.lower(state, images, labels)


@pytest.mark.parametrize(
    "builder,scopes",
    [
        ("shard_map", ("accumulate", "loss", "grad_sync", "update")),
        ("gspmd", ("accumulate", "loss", "grad_sync", "update")),
        ("update", ("grad_sync", "update")),  # no forward or loss in it
    ],
)
def test_compiled_step_names_its_regions(builder, scopes):
    lowered = _lowered(builder)
    text = lowered.as_text(debug_info=True)
    hlo = lowered.compile().as_text()
    for scope in scopes:
        assert f"ddlpc/{scope}" in text, f"lowered {builder} step lacks ddlpc/{scope}"
        assert f"ddlpc/{scope}" in hlo, f"compiled {builder} step lacks ddlpc/{scope}"
    table = _regions()
    counts: dict = {}
    for line in hlo.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        opcode = re.search(r"= \S+ ([a-z\-]+)\(", line)
        if name and opcode and opcode.group(1) not in PLUMBING:
            region = _region_of(name.group(1), table)
            counts[region] = counts.get(region, 0) + 1
    assert counts.get("unnamed", 0) < 0.05 * sum(counts.values()), counts
    if builder != "update":
        assert counts["forward"] and counts["backward"] and counts["detail_head"], counts


def test_device_caches_gather_program_is_named():
    """benchmark/regions.json finds the gather by its scope and, for the ops
    XLA leaves without an op_name, by the program's name."""
    from ddlpc_tpu.data import build_dataset
    from ddlpc_tpu.data.loader import DeviceCachedLoader

    train, _ = build_dataset(_config("unused").data)
    mesh = make_mesh(ParallelConfig(data_axis_size=2, space_axis_size=1), jax.devices()[:2])
    loader = DeviceCachedLoader(train, mesh, global_micro_batch=2, sync_period=2)
    lowered = loader._gather.lower(loader._images, loader._labels, jnp.zeros(4, jnp.int32))
    text = lowered.as_text(debug_info=True)
    assert "module @jit_gather" in text and "ddlpc/gather" in text
    needles = [n for r, n in _regions() if r == "gather"]
    assert "ddlpc/gather" in needles and any("jit_gather(".startswith(n) for n in needles)


# ---- host spans -------------------------------------------------------------

LOOP = ("epoch_head", "data", "step", "metrics_fetch", "epoch_tail")
INIT = ("dataset", "loader", "state", "steps", "accounting", "restore", "services")


def _config(workdir, **train_kw):
    return ExperimentConfig(
        model=ModelConfig(features=(8, 16), bottleneck_features=16, num_classes=4),
        data=DataConfig(
            dataset="synthetic", image_size=(32, 32), synthetic_len=40,
            test_split=8, num_classes=4, device_cache=True,
        ),
        train=TrainConfig(
            epochs=4, micro_batch_size=1, sync_period=2, learning_rate=3e-3,
            eval_every_epochs=0, checkpoint_every_epochs=0,
            dump_images_per_epoch=0, **train_kw,
        ),
        workdir=workdir,
    )


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One Trainer: a warm-up epoch, then three epochs of fit() inside a
    profiler session (the benchmark's traced window in small)."""
    workdir = str(tmp_path_factory.mktemp("run"))
    t0 = time.perf_counter()
    trainer = Trainer(_config(workdir), resume=False)
    init_wall = time.perf_counter() - t0
    records = []
    train_epoch = trainer.train_epoch
    trainer.train_epoch = lambda epoch: records.append(train_epoch(epoch)) or records[-1]
    trainer.fit(epochs=1)
    trainer.start_epoch = 1
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        trainer.fit(epochs=4)
    finally:
        jax.profiler.stop_trace()
    trainer.close()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                spans = [
                    (e.name.split("#")[0][len(ANNOTATION_PREFIX):], e.start_ns, e.end_ns, dict(e.stats))
                    for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX)
                ]
                if spans:
                    lines[line.name] = sorted(spans, key=lambda s: (s[1], -s[2]))
    return workdir, init_wall, records, lines


def test_loop_spans_land_on_the_profilers_host_plane(traced):
    _, _, _, lines = traced
    (spans,) = [s for s in lines.values() if any(n == "epoch" for n, *_ in s)]
    names = {n for n, *_ in spans}
    assert set(LOOP) | {"epoch", "log", "perf_publish", "checkpoint_barrier"} <= names
    epochs = [s for s in spans if s[0] == "epoch"]
    assert [s[3]["epoch"] for s in epochs] == [1, 2, 3]
    for name, start, end, args in spans:
        inside = [e for e in epochs if e[1] <= start and end <= e[2]]
        if name in LOOP:
            # nested in its epoch, and carrying that epoch's number
            assert len(inside) == 1 and args["epoch"] == inside[0][3]["epoch"], (name, args)
        elif name != "epoch":
            assert not inside, name  # fit()'s own stages lie between epochs
    for epoch in (1, 2, 3):
        steps = [s[3]["step"] for s in spans if s[0] == "step" and s[3]["epoch"] == epoch]
        assert steps and steps == list(range(len(steps))), steps


def test_no_unnamed_host_time_from_fetch_to_next_dispatch(traced):
    """From the return of one epoch's device_get to the end of the next
    epoch's first step dispatch, a ddlpc: span is open all but 5 % of the
    time: the idle device there always has a name to be put under."""
    _, _, _, lines = traced
    (spans,) = [s for s in lines.values() if any(n == "epoch" for n, *_ in s)]
    fetches = [s for s in spans if s[0] == "metrics_fetch"]
    total = unnamed = 0.0
    for fetch in fetches[:-1]:
        first_step = min((s for s in spans if s[0] == "step" and s[1] > fetch[2]), key=lambda s: s[1])
        lo, hi = fetch[2], first_step[2]
        covered, cursor = 0.0, lo
        for _, s, e, _ in spans:  # sorted by start: the union's length inside [lo, hi]
            s, e = max(s, cursor), min(e, hi)
            if e > s:
                covered, cursor = covered + e - s, e
        total, unnamed = total + hi - lo, unnamed + (hi - lo) - covered
    assert len(fetches) == 3 and total > 0
    assert unnamed < 0.05 * total, (unnamed, total)


def test_init_phases_reach_the_first_record_and_one_init_line(traced):
    workdir, init_wall, records, lines = traced
    keys = {f"t_init_{p}_s" for p in INIT}
    first = {k: v for k, v in records[0].items() if k.startswith("t_init_")}
    assert set(first) == keys
    assert abs(sum(first.values()) - init_wall) < 0.05 * init_wall, (first, init_wall)
    assert not [k for r in records[1:] for k in r if k.startswith("t_init_")]
    # fit()'s stages close after the record is built: they describe the epoch
    # before the record they appear in, so the first record has none yet.
    assert "t_log_s" not in records[0] and "t_perf_publish_s" in records[1]
    assert {"t_data_s", "t_step_s", "t_epoch_head_s", "t_metrics_fetch_s"} <= set(records[0])
    with open(os.path.join(workdir, "metrics.jsonl")) as f:
        logged = [json.loads(line) for line in f]
    (init,) = [r for r in logged if r.get("kind") == "init"]
    assert check_record(init) == [] and keys <= set(init)
    assert {k: init[k] for k in keys} == first


def test_stage_without_tracer_or_session_allocates_no_span(monkeypatch, tmp_path):
    """train.trace=false and no profiler session: a stage is two clock reads,
    one lock and one inactive TraceMe — no Span object, no file."""
    def no_span(*a, **kw):
        raise AssertionError("a disabled tracer allocated a Span")

    monkeypatch.setattr(tracing.Span, "__init__", no_span)
    tracer = Tracer(enabled=False, jsonl_path=str(tmp_path / "s.jsonl"))
    timer = StageTimer(tracer=tracer)
    for i in range(3):
        with timer.stage("step", epoch=0, step=i):
            pass
    with pytest.raises(KeyError):
        with timer.stage("data"):
            raise KeyError("the stage counts and lets it through")
    assert timer.counts == {"step": 3, "data": 1}
    assert tracer.chrome_events() == [] and not (tmp_path / "s.jsonl").exists()


def test_traced_stage_keeps_name_attributes_and_parent(tmp_path):
    """With train.trace on, a stage is the same span tracer.span() wrote
    (spans.jsonl readers see no difference) and nests under the stage round it."""
    tracer = Tracer(enabled=True, jsonl_path=str(tmp_path / "s.jsonl"))
    timer = StageTimer(tracer=tracer)
    with timer.stage("epoch", epoch=3):
        with timer.stage("checkpoint_snapshot", epoch=3, lineage_id="abc", step=7):
            pass
    tracer.close()
    recs = {r["name"]: r for r in map(json.loads, open(tmp_path / "s.jsonl"))}
    snap = recs["checkpoint_snapshot"]
    assert (snap["epoch"], snap["lineage_id"], snap["step"]) == (3, "abc", 7)
    assert snap["parent_id"] == recs["epoch"]["span_id"] and recs["epoch"]["parent_id"] == 0


@pytest.mark.parametrize(
    "tf_op,scope",
    [
        ("jit(stepper)/shard_map/ddlpc/update/mul:", "shard_map/ddlpc/update"),
        (
            "jit(f)/ddlpc/accumulate/while/body/closed_call/transpose(jvp(UNet))/DetailHead_0/Conv_0/conv_general_dilated:Conv",
            "ddlpc/accumulate/while/body/closed_call/transpose(jvp(UNet))/DetailHead_0/Conv_0",
        ),
        ("copy.3", ""),
    ],
)
def test_profile_report_names_an_ops_scope(tf_op, scope):
    assert op_scope(tf_op) == scope


# ---- the compile ledger's readers (benchmark/layer_metrics/setup_*.py) ------


def _ledger_reader(name):
    import importlib.util
    import sys

    bench = os.path.join(REPO, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)  # the readers import setup_compile by name, as in run.py
    spec = importlib.util.spec_from_file_location(
        f"layer_metrics__{name}", os.path.join(bench, "layer_metrics", name + ".py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _ledger_record(scale, init=False):
    counters = {
        "compile_trace_s": 1.0, "compile_lower_s": 2.0, "compile_load_s": 4.0,
        "compile_xla_s": 8.0, "programs_loaded": 16, "programs_compiled": 32,
    }
    out = {"loss": 1.0, **{k: v * scale for k, v in counters.items()}}
    if init:
        out.update({f"init_{k}": v * 100 for k, v in counters.items()})
    return out


@pytest.mark.parametrize(
    "name,want",
    [
        ("setup_lowering_s", 300.0 + 3.0 * 1.5),  # construction, then epochs 1 + 0.5 + 0
        ("setup_xla_compile_s", 800.0 + 8.0 * 1.5),
        ("setup_cache_load_s", 400.0 + 4.0 * 1.5),
        ("setup_programs_compiled", 3200 + 32 * 1.5),
    ],
)
def test_setup_readers_sum_construction_and_warmup(name, want):
    run = {"warmup_records": [_ledger_record(1, init=True), _ledger_record(0.5), _ledger_record(0)]}
    assert _ledger_reader(name).read(run) == pytest.approx(want)


@pytest.mark.parametrize(
    "name", ["setup_lowering_s", "setup_xla_compile_s", "setup_cache_load_s", "setup_programs_compiled"]
)
def test_setup_readers_read_none_without_the_ledger(name):
    reader = _ledger_reader(name)
    assert reader.read({"warmup_records": [{"loss": 1.0, "t_init_state_s": 2.0}, {"loss": 0.9}]}) is None
    assert reader.read({"warmup_records": []}) is None
    # construction's counters without the records' own is not a ledger either
    partial = [_ledger_record(1, init=True), {"loss": 0.9}]
    assert reader.read({"warmup_records": partial}) is None
