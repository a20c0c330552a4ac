"""Spatial sharding: halo-exchange primitive + GSPMD data×space training
(SURVEY §4: single-process multi-device distributed tests on a virtual
8-device CPU mesh)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddlpc_tpu.config import (
    CompressionConfig,
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    ParallelConfig,
    TrainConfig,
)
from ddlpc_tpu.parallel.halo import halo_exchange, sharded_same_conv
from ddlpc_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def space_mesh():
    return make_mesh(ParallelConfig(data_axis_size=2, space_axis_size=4))


def test_halo_exchange_matches_neighbor_rows(space_mesh):
    H, halo = 16, 2
    x = jnp.arange(2 * H * 3 * 4, dtype=jnp.float32).reshape(2, H, 3, 4)

    def body(x_local):
        return halo_exchange(x_local, "space", halo)

    out = jax.jit(
        jax.shard_map(
            body,
            mesh=space_mesh,
            in_specs=P(None, "space"),
            out_specs=P(None, "space"),
        )
    )(x)
    out = np.asarray(out)
    Hl = H // 4
    per_shard = Hl + 2 * halo
    xs = np.asarray(x)
    for s in range(4):
        shard = out[:, s * per_shard : (s + 1) * per_shard]
        # Interior rows are the shard itself.
        np.testing.assert_array_equal(shard[:, halo:-halo], xs[:, s * Hl : (s + 1) * Hl])
        # Top halo: previous shard's last rows (zeros at the global edge).
        want_top = (
            np.zeros_like(shard[:, :halo]) if s == 0 else xs[:, s * Hl - halo : s * Hl]
        )
        np.testing.assert_array_equal(shard[:, :halo], want_top)
        want_bot = (
            np.zeros_like(shard[:, :halo])
            if s == 3
            else xs[:, (s + 1) * Hl : (s + 1) * Hl + halo]
        )
        np.testing.assert_array_equal(shard[:, -halo:], want_bot)


def test_halo_too_large_raises(space_mesh):
    x = jnp.zeros((1, 8, 4, 2))  # 2 rows per shard over 4-way space

    def run():
        return jax.jit(
            jax.shard_map(
                lambda v: halo_exchange(v, "space", 3),
                mesh=space_mesh,
                in_specs=P(None, "space"),
                out_specs=P(None, "space"),
            )
        )(x)

    with pytest.raises(ValueError, match="halo"):
        run()


def test_sharded_conv_matches_global_conv(space_mesh):
    """The halo primitive's contract: H-sharded SAME conv == unsharded conv."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 32, 16, 3)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(3, 3, 3, 5)), jnp.float32)

    ref = lax.conv_general_dilated(
        x, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    sharded = jax.jit(
        jax.shard_map(
            lambda v: sharded_same_conv(v, k, "space"),
            mesh=space_mesh,
            in_specs=P(None, "space"),
            out_specs=P(None, "space"),
        )
    )(x)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(ref), atol=1e-5)


def _tiny_cfg(space: int) -> ExperimentConfig:
    return ExperimentConfig(
        model=ModelConfig(
            features=(8, 16), bottleneck_features=16, num_classes=4
        ),
        data=DataConfig(dataset="synthetic", image_size=(32, 32), synthetic_len=24, test_split=8,
                        num_classes=4),
        train=TrainConfig(micro_batch_size=1, sync_period=2),
        parallel=ParallelConfig(data_axis_size=-1, space_axis_size=space),
    )


def test_gspmd_step_runs_and_replicates(space_mesh):
    from ddlpc_tpu.models import build_model_from_experiment
    from ddlpc_tpu.parallel.train_step import create_train_state, make_train_step_gspmd
    from ddlpc_tpu.train.optim import build_optimizer

    cfg = _tiny_cfg(space=4)
    model = build_model_from_experiment(cfg)
    assert model.norm_axis_name is None  # gspmd builds BN without axis name
    tx = build_optimizer(cfg.train)
    state = create_train_state(model, tx, jax.random.key(0), (1, 32, 32, 3))
    state = jax.device_put(state, NamedSharding(space_mesh, P()))
    step = make_train_step_gspmd(model, tx, space_mesh, cfg.compression)
    rng = np.random.default_rng(0)
    x = jax.device_put(
        rng.uniform(0, 1, (2, 2, 32, 32, 3)).astype(np.float32),
        NamedSharding(space_mesh, P(None, "data", "space")),
    )
    y = jax.device_put(
        rng.integers(0, 4, (2, 2, 32, 32)).astype(np.int32),
        NamedSharding(space_mesh, P(None, "data", "space")),
    )
    state, metrics = step(state, x, y)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state.step) == 1
    # Output state is replicated on every device.
    leaf = jax.tree.leaves(state.params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)


def test_gspmd_matches_dataparallel_step():
    """Same data, same init: a (2,4) data×space GSPMD step must produce the
    same parameters as the 8-way pure-DP shard_map step (norm='none' so BN
    statistics semantics can't differ, compression off)."""
    from ddlpc_tpu.models import build_model
    from ddlpc_tpu.parallel.train_step import (
        create_train_state,
        make_train_step,
        make_train_step_gspmd,
    )
    from ddlpc_tpu.train.optim import build_optimizer

    mcfg = ModelConfig(features=(8,), bottleneck_features=8, num_classes=3,
                       norm="none", compute_dtype="float32")
    model = build_model(mcfg)
    tx = build_optimizer(TrainConfig())
    comp = CompressionConfig(mode="none")
    rng = np.random.default_rng(3)
    x = rng.uniform(0, 1, (2, 8, 16, 16, 3)).astype(np.float32)
    y = rng.integers(0, 3, (2, 8, 16, 16)).astype(np.int32)

    results = []
    for mode in ["dp", "gspmd"]:
        if mode == "dp":
            mesh = make_mesh(ParallelConfig(data_axis_size=8, space_axis_size=1))
            step = make_train_step(model, tx, mesh, comp, donate_state=False)
            spec = P(None, "data")
        else:
            mesh = make_mesh(ParallelConfig(data_axis_size=2, space_axis_size=4))
            step = make_train_step_gspmd(model, tx, mesh, comp, donate_state=False)
            spec = P(None, "data", "space")
        state = create_train_state(model, tx, jax.random.key(0), (1, 16, 16, 3))
        state = jax.device_put(state, NamedSharding(mesh, P()))
        xs = jax.device_put(x, NamedSharding(mesh, spec))
        ys = jax.device_put(y, NamedSharding(mesh, spec))
        new_state, metrics = step(state, xs, ys)
        results.append((jax.device_get(new_state.params), float(metrics["loss"])))
    (p_dp, l_dp), (p_sp, l_sp) = results
    assert abs(l_dp - l_sp) < 1e-5
    for a, b in zip(jax.tree.leaves(p_dp), jax.tree.leaves(p_sp)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_trainer_selects_gspmd_and_trains(tmp_path):
    from ddlpc_tpu.train.trainer import Trainer

    cfg = _tiny_cfg(space=2).replace(workdir=str(tmp_path))
    trainer = Trainer(cfg)
    assert trainer.spatial
    rec = trainer.fit(epochs=2)
    assert np.isfinite(rec["loss"])
    assert 0.0 <= rec["val_miou"] <= 1.0
