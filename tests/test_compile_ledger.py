"""The compile ledger (ISSUE 36, ``utils/compile_cache.py``): every trace,
lowering and compile-or-cache-load is a ``ddlpc:compile/*`` span on the
profiler's clock and a counter in the Trainer's records.  CPU: counts, nesting
and names; seconds only as "above zero" and "within the call's wall"."""

import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import compilation_cache

from ddlpc_tpu.config import DataConfig, ExperimentConfig, ModelConfig, TrainConfig
from ddlpc_tpu.obs.schema import check_record
from ddlpc_tpu.train.observability import StageTimer
from ddlpc_tpu.train.trainer import Trainer
from ddlpc_tpu.utils import compile_cache
from ddlpc_tpu.utils.compile_cache import (
    BACKEND,
    CACHE_HIT,
    COUNTERS,
    LOWER,
    TRACE,
    CompileLedger,
    install_compile_ledger,
)

ZERO = {k: 0 for k in COUNTERS}


@pytest.fixture
def cursor():
    return install_compile_ledger().cursor()


def test_a_fresh_jit_is_one_compiled_program_with_every_phase_timed(cursor):
    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def outer(x):
        return inner(x) @ inner(x).T

    x = np.ones((8, 8), np.float32)
    t0 = time.perf_counter()
    outer(x).block_until_ready()
    wall = time.perf_counter() - t0
    got = cursor.take()
    assert got["programs_compiled"] == 1 and got["programs_loaded"] == 0
    assert got["programs_compiled_names"] == ["jit(outer)"]
    for key in ("compile_trace_s", "compile_lower_s", "compile_xla_s"):
        assert got[key] > 0, (key, got)
    assert got["compile_load_s"] == 0
    # inner's traces run inside outer's: counted once, the sum fits the call
    phases = got["compile_trace_s"] + got["compile_lower_s"] + got["compile_xla_s"]
    assert phases <= wall, (got, wall)


def test_a_second_call_leaves_every_counter_unchanged(cursor):
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = np.ones(5, np.float32)
    f(x).block_until_ready()
    assert cursor.take()["programs_compiled"] == 1
    f(x).block_until_ready()
    assert cursor.take() == ZERO


def test_nested_phases_count_only_the_outermost_seconds():
    ledger = CompileLedger()  # driven by hand, not installed
    c = ledger.cursor()
    ledger._open(TRACE, 0.0, fun_name="f")
    ledger._open(TRACE, 0.0, fun_name="sin")
    ledger._close(TRACE, 0.25, fun_name="sin")
    ledger._open(TRACE, 0.0, fun_name="matmul")
    ledger._close(TRACE, 0.25, fun_name="matmul")
    ledger._close(TRACE, 1.0, fun_name="f")
    ledger._open(LOWER, 0.0, fun_name="jit_f")
    ledger._close(LOWER, 0.5, fun_name="jit_f")
    ledger._open(BACKEND, 0.0, fun_name="jit_f")
    ledger._hit(CACHE_HIT)
    ledger._close(BACKEND, 0.125, fun_name="jit_f")
    assert c.take() == dict(
        ZERO, compile_trace_s=1.0, compile_lower_s=0.5, compile_load_s=0.125, programs_loaded=1
    )
    assert ledger._stack() == []


def test_compiled_names_keep_the_first_eight_since_the_cursor():
    ledger = CompileLedger()
    c = ledger.cursor()
    for i in range(10):
        ledger._open(BACKEND, 0.0, fun_name=f"jit_p{i}")
        ledger._close(BACKEND, 0.001, fun_name=f"jit_p{i}")
    assert c.take()["programs_compiled_names"] == [f"jit_p{i}" for i in range(8)]
    ledger._open(BACKEND, 0.0, fun_name="jit_late")
    ledger._close(BACKEND, 0.001, fun_name="jit_late")
    got = c.take()
    assert got["programs_compiled"] == 1 and got["programs_compiled_names"] == ["jit_late"]


@pytest.fixture
def persistent_cache(tmp_path):
    """A cache of this test's own, every program written to it; put back."""
    names = (
        "jax_enable_compilation_cache",
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {n: getattr(jax.config, n) for n in names}
    for name, value in zip(names, (True, str(tmp_path), 0.0, 0)):
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    yield
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_a_program_the_persistent_cache_serves_is_loaded_not_compiled(cursor, persistent_cache):
    @jax.jit
    def served(x):
        return jnp.tanh(x) - 0.5

    x = np.ones(7, np.float32)
    served(x).block_until_ready()
    first = cursor.take()
    assert first["programs_compiled"] == 1 and first["programs_loaded"] == 0
    jax.clear_caches()
    served(x).block_until_ready()
    got = cursor.take()
    assert got["programs_loaded"] == 1 and got["programs_compiled"] == 0, got
    assert got["compile_load_s"] > 0 and got["compile_xla_s"] == 0
    assert "programs_compiled_names" not in got


def test_phases_close_on_their_own_thread_also_when_tracing_raises():
    ledger = install_compile_ledger()
    out = {}

    def work(name):
        c = ledger.cursor()
        with pytest.raises(TypeError):
            jax.jit(lambda x: x @ np.ones((3, 5), np.float32))(np.ones((4, 4), np.float32))
        jax.jit(lambda x: x + len(name))(np.ones(4, np.float32)).block_until_ready()
        out[name] = (list(ledger._stack()), c.take()["programs_compiled"])

    threads = [threading.Thread(target=work, args=(n,)) for n in ("a", "bb")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # each thread's phases closed, the failed trace among them; the other
    # thread's compile may land in a cursor taken meanwhile, so at least one
    assert all(stack == [] for stack, _ in out.values()), out
    assert all(n >= 1 for _, n in out.values()), out
    assert ledger._stack() == []


def test_no_count_is_lost_across_threads():
    """More threads than cores drive one ledger at a short switch interval:
    every phase is counted, each on its own thread's stack."""
    import sys

    ledger = CompileLedger()
    c = ledger.cursor()
    n_threads, n = 4 * (os.cpu_count() or 2), 200

    def work(i):
        for _ in range(n):
            ledger._open(LOWER, 0.0, fun_name=f"jit_{i}")
            ledger._open(BACKEND, 0.0, fun_name=f"jit_{i}")  # a phase inside another: counts, no seconds
            ledger._close(BACKEND, 1.0, fun_name=f"jit_{i}")
            ledger._close(LOWER, 0.5, fun_name=f"jit_{i}")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    got = c.take()
    assert got["programs_compiled"] == n_threads * n
    assert got["compile_lower_s"] == 0.5 * n_threads * n and got["compile_xla_s"] == 0


def test_installing_twice_counts_once(cursor):
    assert install_compile_ledger() is install_compile_ledger() is compile_cache.LEDGER
    jax.jit(lambda x: x - 2.0)(np.ones(3, np.float32)).block_until_ready()
    got = cursor.take()
    assert got["programs_compiled"] == 1 and got["programs_loaded"] == 0


def test_spans_nest_under_the_stage_that_compiled(tmp_path):
    install_compile_ledger()
    timer = StageTimer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with timer.stage("step", epoch=0, step=0):
            jax.jit(lambda x: x * 5.0)(np.ones(6, np.float32)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    (plane,) = [p for p in jax.profiler.ProfileData.from_file(path).planes if p.name == "/host:CPU"]
    for line in plane.lines:
        spans = [(e.name.split("#")[0], e.start_ns, e.end_ns, dict(e.stats)) for e in line.events]
        steps = [s for s in spans if s[0] == "ddlpc:step"]
        if steps:
            break
    (step,) = steps
    compiles = [s for s in spans if s[0].startswith("ddlpc:compile/")]
    assert {s[0] for s in compiles} == {f"ddlpc:compile/{p}" for p in ("trace", "lower", "backend")}
    for name, start, end, args in compiles:
        assert step[1] <= start and end <= step[2], name
    # the program by name; the trace of jnp's multiply nests inside its own
    assert all("<lambda>" in str(a["fun"]) for n, *_, a in compiles if n != "ddlpc:compile/trace")
    assert {str(a["fun"]) for n, *_, a in compiles if n == "ddlpc:compile/trace"} >= {"<lambda>"}
    # the spans are not stages: no t_compile_* in what the timer gives a record
    assert set(timer.means()) == {"step"}


# ---- the Trainer's records -------------------------------------------------


def _config(workdir):
    return ExperimentConfig(
        model=ModelConfig(features=(8, 16), bottleneck_features=16, num_classes=4),
        data=DataConfig(
            dataset="synthetic", image_size=(32, 32), synthetic_len=40,
            test_split=8, num_classes=4, device_cache=True,
        ),
        train=TrainConfig(
            epochs=3, micro_batch_size=1, sync_period=2, learning_rate=3e-3,
            eval_every_epochs=0, checkpoint_every_epochs=0, dump_images_per_epoch=0,
        ),
        workdir=workdir,
    )


class _LateProgramLoader:
    """The Trainer's loader, but from epoch 2 on every batch's images pass
    through a program nothing compiled before."""

    def __init__(self, inner):
        self._inner = inner
        self._epoch = 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __len__(self):
        return len(self._inner)

    def set_epoch(self, epoch):
        self._epoch = epoch
        self._inner.set_epoch(epoch)

    def __iter__(self):
        for images, labels in self._inner:
            if self._epoch == 2:
                images = epoch_two_program(images)
            yield images, labels


@jax.jit
def epoch_two_program(images):
    return images * 1.0


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """A tiny Trainer through fit(3); its loader brings a new program in epoch 2."""
    import json

    workdir = str(tmp_path_factory.mktemp("run"))
    trainer = Trainer(_config(workdir), resume=False)
    trainer.loader = _LateProgramLoader(trainer.loader)
    records = []
    train_epoch = trainer.train_epoch
    trainer.train_epoch = lambda epoch: records.append(train_epoch(epoch)) or records[-1]
    trainer.fit(epochs=3)
    trainer.close()
    with open(os.path.join(workdir, "metrics.jsonl")) as f:
        logged = [json.loads(line) for line in f]
    return records, logged


def test_construction_and_the_first_epoch_compile_and_the_second_does_not(fitted):
    records, _ = fitted
    first, second = records[0], records[1]
    assert first["init_programs_compiled"] > 0 and first["init_compile_xla_s"] > 0
    assert first["programs_compiled"] > 0 and first["compile_xla_s"] > 0
    assert {k: second[k] for k in COUNTERS} == ZERO
    assert "programs_compiled_names" not in second
    assert not [k for r in records for k in r if k.startswith("t_compile")]
    assert not [k for r in records[1:] for k in r if k.startswith("init_")]


def test_the_init_line_carries_constructions_counters(fitted):
    records, logged = fitted
    (init,) = [r for r in logged if r.get("kind") == "init"]
    assert check_record(init) == []
    keys = [f"init_{k}" for k in COUNTERS] + ["init_programs_compiled_names"]
    assert {k: init[k] for k in keys} == {k: records[0][k] for k in keys}
    assert len(init["init_programs_compiled_names"]) <= compile_cache.NAMES_KEPT


def test_a_program_first_compiled_in_a_later_epoch_is_named_there_alone(fitted):
    records, _ = fitted
    named = [r["epoch"] for r in records if "jit(epoch_two_program)" in r.get("programs_compiled_names", [])]
    assert named == [2]
    assert records[2]["programs_compiled"] >= 1
