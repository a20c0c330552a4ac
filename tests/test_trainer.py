"""Trainer driver, checkpoint/resume, observability, CLI (SURVEY §7 steps
5/8: the subsystems the reference lacks entirely)."""

import json
import os

import numpy as np
import pytest

import jax

from ddlpc_tpu.config import (
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    TrainConfig,
)
from ddlpc_tpu.train import checkpoint as ckpt
from ddlpc_tpu.train.observability import MetricsLogger, StageTimer, dump_prediction_triples
from ddlpc_tpu.train.trainer import Trainer


def tiny_config(workdir: str, **train_kw) -> ExperimentConfig:
    return ExperimentConfig(
        model=ModelConfig(
            features=(8, 16), bottleneck_features=16, num_classes=4
        ),
        data=DataConfig(
            dataset="synthetic", image_size=(32, 32), synthetic_len=40, test_split=8, num_classes=4
        ),
        train=TrainConfig(
            epochs=2,
            micro_batch_size=1,
            sync_period=2,
            learning_rate=3e-3,
            dump_images_per_epoch=2,
            **train_kw,
        ),
        workdir=workdir,
    )


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One short fit() shared by the assertions below (compile is the cost)."""
    workdir = str(tmp_path_factory.mktemp("run"))
    trainer = Trainer(tiny_config(workdir))
    record = trainer.fit()
    return workdir, trainer, record


def test_fit_trains_and_evaluates(run):
    _, _, record = run
    assert record["epoch"] == 1
    assert np.isfinite(record["loss"])
    assert 0.0 <= record["val_miou"] <= 1.0
    assert 0.0 <= record["val_pixel_acc"] <= 1.0
    assert record["tiles_per_s"] > 0


def test_fit_writes_logs_and_config(run):
    workdir, _, _ = run
    records = [
        json.loads(l)
        for l in open(os.path.join(workdir, "metrics.jsonl")).read().splitlines()
    ]
    # kind-less training records, one per epoch (perf/comm accounting
    # records interleave into the same stream, like alerts do).
    train = [r for r in records if "kind" not in r]
    assert len(train) == 2
    rec = train[-1]
    assert "loss" in rec and "val_miou" in rec and "epoch_time_s" in rec
    assert os.path.exists(os.path.join(workdir, "metrics.txt"))
    cfg = json.load(open(os.path.join(workdir, "config.json")))
    assert cfg["train"]["sync_period"] == 2


def test_fit_dumps_prediction_triples(run):
    workdir, _, _ = run
    img_dir = os.path.join(workdir, "images", "epoch_0001")
    names = sorted(os.listdir(img_dir))
    # (Model i, Label i, Image i) triples, reference кластер.py:785-790.
    assert names == [
        "Image 0.png", "Image 1.png", "Label 0.png", "Label 1.png",
        "Model 0.png", "Model 1.png",
    ]


def test_checkpoint_resume_continues(run):
    workdir, trainer, record = run
    # Checkpoints exist and resuming picks up after the last epoch.
    assert ckpt.latest_step(os.path.join(workdir, "checkpoints")) is not None
    resumed = Trainer(tiny_config(workdir))
    assert resumed.start_epoch == 2
    # Restored parameters equal the live ones.
    live = jax.tree.leaves(trainer.state.params)
    rest = jax.tree.leaves(resumed.state.params)
    for a, b in zip(live, rest):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    # fit() with the same epoch budget is a no-op after resume.
    rec2 = resumed.fit()
    assert rec2 == {}


def test_checkpoint_prune_and_atomicity(tmp_path):
    state = {"w": np.arange(10, dtype=np.float32)}
    d = str(tmp_path / "ck")
    for step in range(5):
        ckpt.save_checkpoint(d, state, step=step, metadata={"epoch": step}, keep=2)
    assert ckpt._steps(d) == [3, 4]
    restored, meta = ckpt.restore_checkpoint(d, {"w": np.zeros(10, np.float32)})
    np.testing.assert_array_equal(restored["w"], state["w"])
    assert meta["epoch"] == 4
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]


def test_prune_sweeps_orphan_metadata(tmp_path):
    state = {"w": np.zeros(4, np.float32)}
    d = str(tmp_path / "ck")
    ckpt.save_checkpoint(d, state, step=1, keep=2)
    # Simulate a crash between the json and blob renames of step 2.
    open(os.path.join(d, "ckpt_2.json"), "w").write("{}")
    ckpt.save_checkpoint(d, state, step=3, keep=2)
    assert ckpt._steps(d) == [1, 3]
    assert not os.path.exists(os.path.join(d, "ckpt_2.json"))


def test_checkpoint_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        ckpt.restore_checkpoint(str(tmp_path / "none"), {"w": np.zeros(1)})


def test_model_data_class_mismatch_raises(tmp_path):
    import dataclasses

    cfg = tiny_config(str(tmp_path))
    cfg = cfg.replace(model=dataclasses.replace(cfg.model, num_classes=7))
    with pytest.raises(ValueError, match="num_classes"):
        Trainer(cfg)


def test_stage_timer():
    t = StageTimer()
    with t.stage("a"):
        pass
    with t.stage("a"):
        pass
    assert t.counts["a"] == 2 and t.totals["a"] >= 0
    assert set(t.means()) == {"a"}
    t.reset()
    assert t.summary() == {}


def test_metrics_logger_types(tmp_path):
    log = MetricsLogger(str(tmp_path))
    log.log({"epoch": 1, "loss": np.float32(0.5)}, echo=False)
    rec = json.loads(open(tmp_path / "metrics.jsonl").read())
    assert rec["loss"] == 0.5 and rec["epoch"] == 1 and "time" in rec


def test_predict_cli(run, tmp_path):
    import imageio.v2 as imageio

    from ddlpc_tpu.predict import main as predict_main

    workdir, _, _ = run
    in_dir = tmp_path / "imgs"
    in_dir.mkdir()
    rng = np.random.default_rng(0)
    for i in range(3):
        imageio.imwrite(
            in_dir / f"t{i}.png",
            rng.integers(0, 255, (32, 32, 3), dtype=np.uint8),
        )
    out_dir = tmp_path / "preds"
    assert predict_main(
        ["--workdir", workdir, "--input", str(in_dir), "--output", str(out_dir),
         "--batch", "2"]
    ) == 0
    outs = sorted(os.listdir(out_dir))
    assert outs == ["t0_pred.png", "t1_pred.png", "t2_pred.png"]
    img = imageio.imread(out_dir / "t0_pred.png")
    assert img.shape == (32, 32, 3)


def _perpixel_logits(state, imgs):
    """Fake model whose logits depend only on each pixel: blending any
    window decomposition must reproduce the direct full-image answer."""
    x = np.asarray(imgs)[..., 0]
    return np.stack([x, 1.0 - x], axis=-1)


def test_sliding_window_matches_perpixel_model():
    from ddlpc_tpu.predict import sliding_window_logits

    rng = np.random.default_rng(0)
    image = rng.uniform(0, 1, (50, 70, 3)).astype(np.float32)
    expect = _perpixel_logits(None, image[None])[0]
    for overlap in (0.0, 0.25, 0.5):
        got = sliding_window_logits(
            _perpixel_logits, None, image, tile=(32, 32), overlap=overlap,
            batch=4,
        )
        assert got.shape == (50, 70, 2)
        np.testing.assert_allclose(got, expect, atol=1e-5)


def test_sliding_window_scene_smaller_than_tile():
    from ddlpc_tpu.predict import sliding_window_logits

    image = np.full((10, 12, 3), 0.25, np.float32)
    got = sliding_window_logits(
        _perpixel_logits, None, image, tile=(32, 32), batch=2
    )
    assert got.shape == (10, 12, 2)
    np.testing.assert_allclose(got[..., 0], 0.25, atol=1e-6)


def test_predict_cli_full_scene(run, tmp_path):
    """A non-tile-size aerial scene predicts at native size via the
    overlap-blended sliding window (VERDICT r1 missing #3)."""
    import imageio.v2 as imageio

    from ddlpc_tpu.predict import main as predict_main

    workdir, _, _ = run
    in_dir = tmp_path / "scene"
    in_dir.mkdir()
    rng = np.random.default_rng(1)
    imageio.imwrite(
        in_dir / "big.png", rng.integers(0, 255, (80, 112, 3), dtype=np.uint8)
    )
    out_dir = tmp_path / "preds"
    assert predict_main(
        ["--workdir", workdir, "--input", str(in_dir), "--output",
         str(out_dir), "--batch", "2"]
    ) == 0
    img = imageio.imread(out_dir / "big_pred.png")
    assert img.shape == (80, 112, 3)


def test_checkpoint_metadata_records_channels(run):
    workdir, _, _ = run
    meta = ckpt.peek_metadata(os.path.join(workdir, "checkpoints"))
    assert meta["input_channels"] == 3


def test_configs_dir_parses():
    """The shipped BASELINE config artifacts must round-trip through the
    config system."""
    import glob

    from ddlpc_tpu.config import ExperimentConfig, FleetConfig, ServeConfig

    paths = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "..", "configs", "*.json")))
    # 5 BASELINE parity + TPU flagship + s2d U-Net++ + the token-tile
    # lfm2_24b_a2b_ep8, keye_vl2_30b_a3b_ep8 and olmo_hybrid_7b_tp2 + serve + fleet deploys
    assert len(paths) == 12
    for p in paths:
        if os.path.basename(p).startswith("serve_"):
            # serve_*.json are ServeConfig deploy artifacts, not experiments
            ServeConfig.from_json(open(p).read())
            continue
        if os.path.basename(p).startswith("fleet_"):
            # fleet_*.json are FleetConfig deploy artifacts (ISSUE 10)
            FleetConfig.from_json(open(p).read())
            continue
        cfg = ExperimentConfig.from_json(open(p).read())
        assert cfg.model.num_classes == cfg.data.num_classes


def test_cli_overrides(tmp_path):
    from ddlpc_tpu.train.__main__ import parse_config

    cfg_file = tmp_path / "c.json"
    cfg_file.write_text(tiny_config(str(tmp_path)).to_json())
    cfg, resume = parse_config(
        [
            "--config", str(cfg_file),
            "--set", "train.epochs=7",
            "--set", "model.name=unet",
            "--set", "data.image_size=(64,64)",
            "--workdir", str(tmp_path / "w"),
            "--no-resume",
        ]
    )
    assert cfg.train.epochs == 7
    assert cfg.data.image_size == (64, 64)
    assert cfg.workdir == str(tmp_path / "w")
    assert resume is False
    with pytest.raises(KeyError):
        parse_config(["--set", "train.nope=1"])


def test_stochastic_rounding_large_batch_warns(tmp_path):
    """docs/QUANTIZATION.md round-3 table: stochastic rounding helps at
    global super-batch 32 but costs -0.045 val mIoU at 512 — the Trainer
    must warn when the codec's stochastic rounding meets a large-batch
    operating point, and stay silent in the regime where it helps."""
    import dataclasses

    from ddlpc_tpu.config import CompressionConfig

    def build(micro, sync, rounding):
        cfg = tiny_config(str(tmp_path / f"w{micro}x{sync}{rounding}"))
        cfg = cfg.replace(
            train=dataclasses.replace(
                cfg.train, micro_batch_size=micro, sync_period=sync
            ),
            compression=CompressionConfig(mode="int8", rounding=rounding),
        )
        return Trainer(cfg, resume=False)

    n_dev = jax.device_count()
    with pytest.warns(UserWarning, match="super-batch"):
        build(-(-256 // (4 * n_dev)), 4, "stochastic")  # >= 256 global
    import warnings as _warnings

    if 2 * n_dev < 256:  # on a pod-sized host even micro=1 is large-batch
        with _warnings.catch_warnings():
            _warnings.simplefilter("error")  # any codec warning fails
            build(1, 2, "stochastic")  # super-batch 2*n_dev: helping regime
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        build(-(-256 // (4 * n_dev)), 4, "nearest")  # large but deterministic


def test_compact_upload_config_validation(tmp_path):
    """compact_upload's int8 labels cap num_classes at 127, and the flag is
    meaningless (and therefore rejected) under device_cache."""
    import dataclasses

    cfg = tiny_config(str(tmp_path))
    wide = dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, num_classes=200),
        data=dataclasses.replace(
            cfg.data, num_classes=200, compact_upload=True
        ),
    )
    with pytest.raises(ValueError, match="max 127"):
        Trainer(wide, resume=False)
    # compact + device_cache = compact RESIDENT cache (round 5).
    cached = dataclasses.replace(
        cfg,
        data=dataclasses.replace(
            cfg.data, compact_upload=True, device_cache=True
        ),
    )
    tr_cached = Trainer(cached, resume=False)
    assert tr_cached.loader.compact is True
    import jax.numpy as jnp

    assert tr_cached.loader._images.dtype == jnp.bfloat16
    assert tr_cached.loader._labels.dtype == jnp.int8
    threaded_cache = dataclasses.replace(
        cfg,
        data=dataclasses.replace(
            cfg.data, loader_workers=4, device_cache=True
        ),
    )
    with pytest.raises(ValueError, match="loader_workers"):
        Trainer(threaded_cache, resume=False)
    # Valid flags reach the loader.
    ok = dataclasses.replace(
        cfg, data=dataclasses.replace(
            cfg.data, compact_upload=True, loader_workers=2
        )
    )
    tr = Trainer(ok, resume=False)
    assert tr.loader.compact is True and tr.loader.workers == 2
