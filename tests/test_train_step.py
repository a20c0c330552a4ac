"""End-to-end SPMD train-step tests on the 8-device virtual CPU mesh
(SURVEY §4: single-process multi-device distributed tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddlpc_tpu.config import (
    CompressionConfig,
    ModelConfig,
    ParallelConfig,
    TrainConfig,
)
from ddlpc_tpu.models import build_model
from ddlpc_tpu.parallel.mesh import make_mesh
from ddlpc_tpu.parallel.train_step import (
    create_train_state,
    make_eval_step,
    make_train_step,
)
from ddlpc_tpu.train.optim import build_optimizer

MCFG = ModelConfig(features=(4, 8), bottleneck_features=8, num_classes=3)
H = W = 16


def _setup(compression=CompressionConfig(), n_data=8, sync_bn=True, optimizer="adam"):
    pcfg = ParallelConfig(data_axis_size=n_data, space_axis_size=1)
    mesh = make_mesh(pcfg, jax.devices()[:n_data])
    model = build_model(MCFG, norm_axis_name="data" if sync_bn else None)
    tx = build_optimizer(TrainConfig(learning_rate=1e-2, optimizer=optimizer))
    state = create_train_state(model, tx, jax.random.PRNGKey(0), (1, H, W, 3))
    step = make_train_step(model, tx, mesh, compression, donate_state=False)
    return mesh, model, tx, state, step


def _batch(a=2, b=8, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    images = jax.random.normal(k1, (a, b, H, W, 3))
    labels = jax.random.randint(k2, (a, b, H, W), 0, 3)
    return images, labels


def test_train_step_runs_and_reduces_loss():
    _, _, _, state, step = _setup()
    images, labels = _batch()
    losses = []
    for _ in range(10):
        state, metrics = step(state, images, labels)
        losses.append(float(metrics["loss"]))
    assert int(state.step) == 10
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


@pytest.mark.parametrize(
    "mode",
    [
        "int8",
        # int8 stays the fast arm (the lossier codec); float16 keeps
        # full coverage in the slow tier (budget maintenance)
        pytest.param("float16", marks=pytest.mark.slow),
    ],
)
def test_train_step_quantized_runs(mode):
    _, _, _, state, step = _setup(CompressionConfig(mode=mode))
    images, labels = _batch()
    for _ in range(5):
        state, metrics = step(state, images, labels)
    assert np.isfinite(float(metrics["loss"]))


def test_remat_matches_plain_step():
    """jax.checkpoint must change memory, never math: one remat'd step's
    params equal the plain step's bitwise-or-close (same program, same
    inputs; SGD so deltas reflect gradients directly)."""
    images, labels = _batch(a=2, b=8)
    _, _, _, state, step = _setup(optimizer="sgd")
    mesh, model, tx, state_r, _ = _setup(optimizer="sgd")
    step_r = make_train_step(
        model, tx, mesh, CompressionConfig(), donate_state=False, remat=True
    )
    s_plain, m_plain = step(state, images, labels)
    s_remat, m_remat = step_r(state_r, images, labels)
    np.testing.assert_allclose(
        float(m_plain["loss"]), float(m_remat["loss"]), rtol=1e-6
    )
    for a, b in zip(
        jax.tree.leaves(s_plain.params), jax.tree.leaves(s_remat.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_dp_matches_single_device():
    """Exact-mean check the reference fails (SURVEY §2.8d 'crooked averaging'):
    8-way DP over a global batch must equal 1-way on the same batch.

    Uses SGD so param deltas reflect gradient deltas directly (Adam divides
    by sqrt(v) and turns ~0 gradients into sign-level lr-sized differences)."""
    images, labels = _batch(a=2, b=8)

    _, _, _, state8, step8 = _setup(n_data=8, optimizer="sgd")
    _, _, _, state1, step1 = _setup(n_data=1, optimizer="sgd")
    s8, _ = step8(state8, images, labels)
    s1, _ = step1(state1, images, labels)
    for a, b in zip(jax.tree.leaves(s8.params), jax.tree.leaves(s1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_grad_accumulation_equivalent_to_big_batch():
    """A=4 micro-batches of B=8 must equal A=1 of B=32 (grad mean linearity).
    Uses norm='none' because BatchNorm statistics are batch-size dependent."""
    mcfg = ModelConfig(features=(4,), bottleneck_features=4, num_classes=3, norm="none")
    pcfg = ParallelConfig(data_axis_size=8, space_axis_size=1)
    mesh = make_mesh(pcfg, jax.devices()[:8])
    model = build_model(mcfg)
    tx = build_optimizer(TrainConfig(learning_rate=1e-2, optimizer="sgd"))
    state = create_train_state(model, tx, jax.random.PRNGKey(0), (1, H, W, 3))
    step = make_train_step(model, tx, mesh, CompressionConfig(), donate_state=False)

    images, labels = _batch(a=4, b=8)
    s_accum, _ = step(state, images, labels)
    s_big, _ = step(
        state, images.reshape(1, 32, H, W, 3), labels.reshape(1, 32, H, W)
    )
    for a, b in zip(jax.tree.leaves(s_accum.params), jax.tree.leaves(s_big.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_params_stay_replicated_and_identical():
    _, _, _, state, step = _setup()
    images, labels = _batch()
    state, _ = step(state, images, labels)
    # replicated sharding => addressable shards must be bit-identical
    leaf = jax.tree.leaves(state.params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)


def test_eval_step_confusion_and_miou():
    mesh, model, tx, state, step = _setup()
    ev = make_eval_step(model, mesh, num_classes=3)
    images, labels = _batch(a=1, b=8)
    out = ev(state, images[0], labels[0])
    cm = np.asarray(out["confusion"])
    assert cm.shape == (3, 3)
    assert cm.sum() == 8 * H * W  # every pixel counted exactly once


def test_batch_stats_replica_identical_even_without_syncbn():
    """Without per-batch sync-BN the train step must still return replicated
    (pmean-averaged) running stats — the reference lets them drift forever
    (SURVEY §3.1)."""
    _, _, _, state, step = _setup(sync_bn=False)
    images, labels = _batch()
    state, _ = step(state, images, labels)
    for leaf in jax.tree.leaves(state.batch_stats):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)


def test_make_mesh_validation():
    import pytest as _pytest

    from ddlpc_tpu.parallel.mesh import make_mesh as _mm

    with _pytest.raises(ValueError, match="needs 16 devices"):
        _mm(ParallelConfig(data_axis_size=16), jax.devices())
    with _pytest.warns(UserWarning, match="stay idle"):
        m = _mm(ParallelConfig(data_axis_size=3), jax.devices())
    assert m.shape["data"] == 3


@pytest.mark.parametrize(
    "data,space,shape", [(8, 1, (8, 1)), (4, 2, (4, 2)), (2, 4, (2, 4)), (-1, 2, (4, 2))]
)
def test_make_mesh_axes(data, space, shape):
    """Exactly the (data, space) axes, over jax.devices() in order."""
    mesh = make_mesh(ParallelConfig(data_axis_size=data, space_axis_size=space))
    assert mesh.axis_names == ("data", "space")
    assert mesh.devices.shape == shape
    assert [d.id for d in mesh.devices.flat] == [d.id for d in jax.devices()]
