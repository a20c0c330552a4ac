"""No path hides the device: what chip_smoke.py, bench.py, the peak table,
the Pallas interpret default, the upload ring and the compile-cache helper
do when there is no chip — and chip_smoke.py's own arms at a tiny width.

Replaces tests/test_backend_probe.py and tests/test_backlog_scripts.py
(the probe, the CPU fallback and the pollers they pinned are gone).
"""

import importlib.util
import inspect
import os
import subprocess
import sys
import types

import jax
import pytest

from ddlpc_tpu.config import CompressionConfig, ExperimentConfig
from ddlpc_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    path = os.path.join(REPO, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_{name}_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def chip_smoke():
    return _load_script("chip_smoke")


@pytest.fixture(scope="module")
def bench():
    return _load_script("bench")


# ---- compile cache ---------------------------------------------------------


KEY_FLAGS = (
    "jax_compilation_cache_include_metadata_in_key",
    "jax_hlo_source_file_canonicalization_regex",
)


@pytest.fixture
def cache_config():
    """Put back what enable_compile_cache() sets: later tests of this
    worker lower programs and read their source paths."""
    names = ("jax_compilation_cache_dir",) + KEY_FLAGS
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch, cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/x")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/x"
    # the code sets no cache dir of its own
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_the_fixed_checkout_path(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    # Fixed: the directory is part of the cache key, so a second
    # process must name the same one.
    assert compile_cache.enable_compile_cache() == got


@pytest.mark.parametrize("placed", ["/x", None])
def test_compile_cache_key_follows_scope_names_not_the_checkout(
    monkeypatch, cache_config, placed
):
    """A program whose named scopes changed must not load the executable
    compiled before (its op_names are what profiles are read by), and a
    checkout at another path must still hit: metadata is in the key, the
    checkout's prefix is taken out of the source paths in it."""
    if placed:
        monkeypatch.setenv(compile_cache.ENV_VAR, placed)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True

    def f(x):
        with jax.named_scope("ddlpc/probe"):
            return x + 1

    lowered = jax.jit(f).lower(jax.numpy.zeros(2)).as_text(debug_info=True)
    assert "ddlpc/probe" in lowered
    assert REPO + os.sep not in lowered and "tests/test_chip_bringup.py" in lowered


def test_every_entry_point_enables_the_cache(chip_smoke, bench):
    from ddlpc_tpu import predict
    from ddlpc_tpu.serve import server
    from ddlpc_tpu.train import __main__ as train_main

    for fn in (
        train_main.run, server.main, predict.main, bench.main, chip_smoke.main
    ):
        assert "enable_compile_cache()" in inspect.getsource(fn), fn


# ---- no chip, no number ----------------------------------------------------


def test_bench_timed_mode_refuses_cpu(bench, monkeypatch, capsys, cache_config):
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "per_chip" not in out and "cpu_fallback" not in out


def test_bench_import_stays_off_jax():
    """--scaling spawns chip-free children; the parent must
    not even import jax (a parent that touched the backend holds the chip)."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, bench; sys.exit('jax' in sys.modules)"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr


def test_chip_smoke_refuses_cpu(chip_smoke, capsys, cache_config):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def test_chip_smoke_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo the script must fail, not print a result."""
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def _fake_device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_peak_flops_unknown_accelerator_raises(monkeypatch):
    from ddlpc_tpu.obs import flops

    monkeypatch.setattr(
        jax, "devices", lambda: [_fake_device("tpu", "TPU v5 lite")]
    )
    assert flops.resolve_peak_flops() == (197e12, False)
    monkeypatch.setattr(
        jax, "devices", lambda: [_fake_device("tpu", "TPU v9 mystery")]
    )
    with pytest.raises(ValueError, match="v9 mystery"):
        flops.resolve_peak_flops()
    with pytest.raises(ValueError, match="_PEAK_BY_DEVICE_KIND"):
        flops.device_peak_flops(_fake_device("gpu", "H100"))


@pytest.mark.parametrize(
    "backend,interpret", [("cpu", True), ("tpu", False), ("gpu", False)]
)
def test_pallas_interprets_only_on_cpu(monkeypatch, backend, interpret):
    from ddlpc_tpu.ops import pallas_quantize

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert pallas_quantize.default_interpret() is interpret


def test_pallas_float16_wire_is_a_config_error():
    """Mosaic on the v5e refuses the float16 wire store (chip_smoke.py's
    kernel arm records it); the config must, too — at construction."""
    with pytest.raises(ValueError, match="float16"):
        CompressionConfig(mode="float16", codec_backend="pallas")
    with pytest.raises(ValueError, match="float16"):
        ExperimentConfig.from_dict(
            {"compression": {"mode": "float16", "codec_backend": "pallas"}}
        )
    CompressionConfig(mode="int8", codec_backend="pallas")
    CompressionConfig(mode="float16", codec_backend="xla")


def test_unverifiable_upload_pointer_is_an_error():
    from ddlpc_tpu.data import loader

    def boom():
        raise RuntimeError("no pointer on this backend")

    shard = types.SimpleNamespace(
        data=types.SimpleNamespace(unsafe_buffer_pointer=boom)
    )
    array = types.SimpleNamespace(addressable_shards=[shard])
    with pytest.raises(RuntimeError, match="no pointer"):
        loader._aliases_host_storage([array], [(0, 1)])


def test_native_load_always_runs_make(monkeypatch):
    """A present .so is not trusted: make decides whether it is current."""
    from ddlpc_tpu.utils import native

    built = []
    monkeypatch.setattr(native, "_cached", {})
    monkeypatch.setattr(native, "_failed", {})
    monkeypatch.setattr(
        native, "_build", lambda target: built.append(target) or True
    )
    assert os.path.exists(native._BATCH_LIB)  # earlier tests built it
    assert native.load_batch() is not None
    assert built == ["libdwbatch.so"]
    # ...and a failed make means no library, even with a stale file there.
    monkeypatch.setattr(native, "_cached", {})
    monkeypatch.setattr(native, "_build", lambda target: False)
    assert native.load_batch() is None


# ---- the smoke's own arms --------------------------------------------------


@pytest.mark.slow
def test_chip_smoke_arms_at_tiny_width(chip_smoke, tmp_path):
    """Every arm of chip_smoke.py, end to end, on the virtual CPU mesh at
    a tiny width — so a refactor cannot break the script between chip
    runs.  The device record is handed in (the script itself only ever
    passes what require_chip() returned); chip-only facts — Mosaic, HBM
    copies, the device trace, cache hits — are checked on the chip.

    ``slow``: three Trainer builds and the interpreted kernels cost ~40 s
    on the 8-device mesh, and tier-1 runs at ~92 % of its 870 s budget
    (one wrapped run of this PR was killed at the limit).  Run it before
    every chip call: ``pytest tests/test_chip_bringup.py``."""
    n = len(jax.devices())
    results = chip_smoke.smoke(
        {"platform": "cpu", "kind": "cpu", "count": n},
        width=(
            "model.features=(8,)",
            "model.bottleneck_features=8",
            "data.image_size=(32,32)",
            f"train.micro_batch_size={2 * n}",
            "data.synthetic_len=24",
            "data.test_split=8",
        ),
        out_dir=str(tmp_path / "smoke"),
    )
    assert set(results) == {"train", "resume", "serve", "host_fed", "kernel"}
    assert results["kernel"]["interpret"] is True
    kinds = {row["kind"] for row in results["train"]["collectives"]}
    assert "reduce-scatter" in kinds  # zero2's gradient wire
    assert os.path.exists(tmp_path / "smoke" / "result.json")
    # What a chip call brings back is capped: the blobs must be gone.
    assert not os.path.exists(tmp_path / "smoke" / "train" / "checkpoints")
