"""The lfm2_24b_a2b_ep8 step at published widths, compiled for the chip in
the sandbox (on-chip-measurement guide, section 2.3): the TPU compiler accepts
it, and state plus temporaries stay under the chip's memory.  The topology is
described inside a fixture, never at import; this is the one file of the
suite that loads the TPU's library."""

import json
import os

import pytest

from conftest import BENCH

HBM_BYTES = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]["hbm_bytes"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled_step(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddlpc_tpu.config import ExperimentConfig
    from ddlpc_tpu.models import build_model
    from ddlpc_tpu.parallel.mesh import make_mesh
    from ddlpc_tpu.parallel.train_step import create_train_state, make_train_step
    from ddlpc_tpu.train.optim import build_optimizer

    cfg = ExperimentConfig.from_dict(json.load(open(os.path.join(BENCH, "configs", "lfm2_24b_a2b_ep8.json"))))
    mesh = make_mesh(cfg.parallel, devices=topo.devices[:1])
    model, tx = build_model(cfg.model), build_optimizer(cfg.train)
    h, w = cfg.data.image_size
    a, b = cfg.train.sync_period, cfg.train.micro_batch_size
    replicated, batch = NamedSharding(mesh, P()), NamedSharding(mesh, P(None, "data"))
    state = jax.eval_shape(
        lambda: create_train_state(model, tx, jax.random.key(0), (1, h, w, 1), jnp.int32)
    )
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated), state)
    images = jax.ShapeDtypeStruct((a, b, h, w, 1), jnp.int32, sharding=batch)
    labels = jax.ShapeDtypeStruct((a, b, h, w), jnp.int32, sharding=batch)
    step = make_train_step(model, tx, mesh, cfg.compression, shard_update="off")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable without a chip
    try:
        return step.lower(state, images, labels).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def test_step_fits_the_chip(compiled_step):
    m = compiled_step.memory_analysis()
    state = m.argument_size_in_bytes
    assert 5.5e9 < state < 5.8e9  # 469 M parameters x 12 B, resident between steps
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes  # the state is donated
    total = state + m.temp_size_in_bytes
    # over 60 % of the chip (the floor is 25 %) and under it: micro 4 x sync 2
    # compiles to 15.66 GB and reads 15.89 GB on the chip (PERF.md section 6, PR 27)
    assert 0.6 * HBM_BYTES < total < HBM_BYTES, (state, m.temp_size_in_bytes)


def test_step_keeps_its_scopes_and_grouped_products(compiled_step):
    text = compiled_step.as_text()
    for scope in ("ddlpc/embed", "ddlpc/short_conv", "ddlpc/attention", "ddlpc/dense_ffn",
                  "ddlpc/moe/route", "ddlpc/moe/experts", "ddlpc/head", "ddlpc/loss", "ddlpc/update"):
        assert scope in text, scope
