"""The kanana2_30b_a3b_ep16 step and both check programs at published widths,
compiled ahead of time for a v5e chip on a host without one:
the TPU compiler accepts them (the causal-attention kernels with q·k of 192
and values of 128 among them), state plus temporaries stay under the chip's
memory, and the check fits beside the Trainer's state.  The topology is
described inside a fixture, never at import; like the other ``*_fits.py``
files this one loads the TPU's library (run them in one process each, not at
once)."""

import dataclasses
import json
import os

import pytest

from conftest import BENCH

HBM_BYTES = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]["hbm_bytes"]
CONFIG = os.path.join(BENCH, "configs", "kanana2_30b_a3b_ep16.json")
# What the TPU compiler gives a program on a v5e ("Used 17.05G of 15.75G hbm"): GiB;
# peaks.json's round 16e9 is the roofline's figure, not the allocator's.
COMPILER_HBM_BYTES = 15.75 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def uncached():
    import jax

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable without a chip
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def cfg():
    from ddlpc_tpu.config import ExperimentConfig

    return ExperimentConfig.from_dict(json.load(open(CONFIG)))


@pytest.fixture(scope="module")
def compiled_step(topo, uncached, cfg):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddlpc_tpu.models import build_model
    from ddlpc_tpu.parallel.mesh import make_mesh
    from ddlpc_tpu.parallel.train_step import create_train_state, make_train_step
    from ddlpc_tpu.train.optim import build_optimizer

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
    return step.lower(state, images, labels).compile()


def test_step_fits_the_chip(compiled_step):
    m = compiled_step.memory_analysis()
    state = m.argument_size_in_bytes
    assert 5.0e9 < state < 5.2e9  # 425.0 M parameters x 12 B, resident between steps
    assert m.alias_size_in_bytes >= 0.99 * m.output_size_in_bytes  # the state is donated
    total = state + m.temp_size_in_bytes
    # over 60 % of the chip (the floor is 25 %) and under it, at micro 2 x sync 2
    assert 0.6 * HBM_BYTES < total < HBM_BYTES, (state, m.temp_size_in_bytes)


def test_step_keeps_its_scopes_and_kernels(compiled_step):
    text = compiled_step.as_text()
    for scope in ("ddlpc/embed", "ddlpc/mla/proj", "ddlpc/attention", "ddlpc/dense_ffn", "ddlpc/moe/shared",
                  "ddlpc/moe/route", "ddlpc/moe/experts", "ddlpc/head", "ddlpc/loss", "ddlpc/update"):
        assert scope in text, scope
    assert "causal_attention_fwd" in text and "causal_attention_bwd" in text
    assert "ddlpc/short_conv" not in text and "selected_attention" not in text and "ddlpc/gdn" not in text


def test_check_programs_fit_beside_the_trainers_state(topo, uncached, cfg, compiled_step):
    """check.py runs the program, keeps its loss, logits and gradients, then
    runs the reference: state + the program's outputs + the whole reference
    program is the peak, and state + the whole program the other."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import check
    from ddlpc_tpu.models import build_model

    one = SingleDeviceSharding(topo.devices[0])
    h, w = cfg.data.image_size
    n = json.load(open(CONFIG))["reference_sample_tiles"]
    params = jax.eval_shape(
        lambda: build_model(cfg.model).init(jax.random.key(0), jnp.zeros((1, h, w, 1), jnp.int32), train=False)
    )["params"]
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), params)
    images = jax.ShapeDtypeStruct((n, h, w, 1), jnp.int32, sharding=one)
    labels = jax.ShapeDtypeStruct((n, h, w), jnp.int32, sharding=one)
    state = compiled_step.memory_analysis().argument_size_in_bytes
    code = compiled_step.memory_analysis().generated_code_size_in_bytes
    sizes = {}
    for name, fn in (
        ("program", check.program_fn(cfg.model)),
        ("reference", check.reference_fn("deepseek_v3", dataclasses.asdict(cfg.model))),
    ):
        m = fn.lower(params, {}, images, labels).compile().memory_analysis()
        sizes[name] = (m.argument_size_in_bytes + m.temp_size_in_bytes + m.generated_code_size_in_bytes, m.output_size_in_bytes)
    assert state + code + sum(sizes["program"]) < 0.97 * COMPILER_HBM_BYTES, sizes
    assert state + code + sizes["program"][1] + sum(sizes["reference"]) < 0.97 * COMPILER_HBM_BYTES, sizes
