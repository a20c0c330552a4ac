"""The device cache's lookahead across the epoch boundary.

``DeviceCachedLoader.prefetch(e)`` gathers epoch ``e``'s first super-batch
before the epoch starts; ``Trainer.train_epoch`` calls it at the end of an
epoch whose successor the running ``fit()`` will train.  The batches, and so
the losses, are those of a loader that never looks ahead: only the moment the
gather is dispatched moves.
"""

import gc
import json
import os
import weakref

import jax
import jax.tree_util as jtu
import numpy as np
import pytest

from ddlpc_tpu.config import (
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    ParallelConfig,
    TrainConfig,
)
from ddlpc_tpu.data import DeviceCachedLoader, ShardedLoader, SyntheticTiles
from ddlpc_tpu.parallel.mesh import make_mesh
from ddlpc_tpu.train.trainer import Trainer


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(ParallelConfig(data_axis_size=-1, space_axis_size=1))


def _loader(mesh, cls=DeviceCachedLoader, **kw):
    # 33 tiles in super-batches of 16: three a epoch, the last one wrapped.
    ds = SyntheticTiles(num_tiles=33, image_size=(8, 8), seed=4)
    return cls(ds, mesh, global_micro_batch=8, sync_period=2, shuffle=True, seed=5, **kw)


def _host(batches):
    return [(np.asarray(x), np.asarray(y)) for x, y in batches]


def _assert_same(a, b):
    assert len(a) == len(b)
    for (ax, ay), (bx, by) in zip(a, b):
        np.testing.assert_array_equal(ax, bx)
        np.testing.assert_array_equal(ay, by)


def test_lookahead_yields_the_batches_of_a_loader_without_it(mesh):
    plain, ahead = _loader(mesh), _loader(mesh)
    used = []
    for epoch in range(3):
        plain.set_epoch(epoch)
        ahead.set_epoch(epoch)
        want = _host(plain)
        it = iter(ahead)
        used.append(ahead.lookahead_used)
        got = _host(it)
        ahead.prefetch(epoch + 1)
        _assert_same(got, want)
        assert not plain.lookahead_used
    assert used == [False, True, True]


def test_lookahead_batch_lives_no_longer_than_a_fresh_one(mesh):
    """Once the consumer lets the first batch go, the iteration holds no
    reference to it: a lookahead costs one batch of memory at the epoch
    boundary and none through the epoch's later steps."""
    ahead = _loader(mesh)
    ahead.prefetch(1)
    ahead.set_epoch(1)
    it = iter(ahead)
    first = weakref.ref(next(it)[0])
    assert ahead.lookahead_used
    second = next(it)
    gc.collect()
    assert first() is None and second is not None


def test_lookahead_for_another_epoch_is_dropped(mesh):
    fresh, ahead = _loader(mesh), _loader(mesh)
    ahead.prefetch(5)
    ahead.set_epoch(7)
    fresh.set_epoch(7)
    got = _host(ahead)
    assert not ahead.lookahead_used
    _assert_same(got, _host(fresh))
    # Dropped, not kept for later: epoch 5 now gathers afresh too.
    ahead.set_epoch(5)
    iter(ahead)
    assert not ahead.lookahead_used


def test_sharded_loader_prefetch_is_a_no_op(mesh):
    plain = _loader(mesh, ShardedLoader, prefetch=0)
    ahead = _loader(mesh, ShardedLoader, prefetch=2)
    plain.set_epoch(1)
    ahead.prefetch(1)
    ahead.set_epoch(1)
    _assert_same(_host(ahead), _host(plain))
    assert not ahead.lookahead_used


def _config(workdir, epochs=3, checkpoint_every_epochs=0):
    # 20 train tiles over the 8-device data mesh, super-batch 16: two steps
    # an epoch, the second wrapped.
    return ExperimentConfig(
        model=ModelConfig(features=(4, 8), bottleneck_features=8, num_classes=4),
        data=DataConfig(
            dataset="synthetic", image_size=(16, 16), synthetic_len=24,
            test_split=4, num_classes=4, device_cache=True,
        ),
        train=TrainConfig(
            epochs=epochs, micro_batch_size=1, sync_period=2,
            dump_images_per_epoch=0, eval_every_epochs=0,
            checkpoint_every_epochs=checkpoint_every_epochs,
        ),
        workdir=str(workdir),
    )


def _records(trainer):
    with open(os.path.join(trainer.workdir, "metrics.jsonl")) as f:
        lines = [json.loads(line) for line in f]
    return [r for r in lines if "loader_lookahead" in r]


def _count_prefetches(trainer, dispatch=True):
    calls = []
    inner = trainer.loader.prefetch

    def prefetch(epoch):
        calls.append(epoch)
        if dispatch:
            inner(epoch)

    trainer.loader.prefetch = prefetch
    return calls


def test_fit_looks_ahead_between_its_epochs_and_trains_the_same(tmp_path):
    ahead = Trainer(_config(tmp_path / "ahead"), resume=False)
    calls = _count_prefetches(ahead)
    ahead.fit()
    plain = Trainer(_config(tmp_path / "plain"), resume=False)
    _count_prefetches(plain, dispatch=False)
    plain.fit()

    got, want = _records(ahead), _records(plain)
    assert [r["loader_lookahead"] for r in got] == [0.0, 1.0, 1.0]
    assert [r["loader_lookahead"] for r in want] == [0.0, 0.0, 0.0]
    assert [r["loss"] for r in got] == [r["loss"] for r in want]
    # The last epoch of the fit looks ahead to no epoch.
    assert calls == [1, 2]
    assert ahead.loader._lookahead is None
    assert ahead.registry.get("ddlpc_train_loader_lookahead").value() == 1.0


def test_train_epoch_outside_fit_dispatches_no_lookahead(tmp_path):
    trainer = Trainer(_config(tmp_path, epochs=2), resume=False)
    calls = _count_prefetches(trainer)
    record = trainer.train_epoch(0)
    assert calls == [] and trainer.loader._lookahead is None
    assert record["loader_lookahead"] == 0.0
    # A second fit() starts from a loader with nothing held.
    trainer.start_epoch = 1
    trainer.fit(epochs=2)
    assert calls == []


def test_fit_that_leaves_early_drops_its_lookahead(tmp_path):
    trainer = Trainer(_config(tmp_path), resume=False)
    held = []

    def observe_train(record):
        # A health detector that raises on epoch 0's record, after the
        # epoch's end dispatched epoch 1's first gather.
        held.append(trainer.loader._lookahead is not None)
        raise RuntimeError("detector")

    trainer.health.observe_train = observe_train
    with pytest.raises(RuntimeError, match="detector"):
        trainer.fit()
    assert held == [True]
    assert trainer.loader._lookahead is None


class _PreemptAfterFirstStep(Trainer):
    """Requests a graceful preemption after epoch 1's first step, whose
    batch is the lookahead that epoch 0's end dispatched."""

    def train_epoch(self, epoch):
        if epoch != 1:
            return super().train_epoch(epoch)
        inner = self.train_step

        def step(state, *batch):
            out = inner(state, *batch)
            self.request_preempt()
            return out

        self.train_step = step
        try:
            return super().train_epoch(epoch)
        finally:
            self.train_step = inner


def test_skip_replay_after_a_lookahead_is_bit_identical(tmp_path):
    ctl = Trainer(_config(tmp_path / "ctl", checkpoint_every_epochs=1), resume=False)
    ctl.fit()

    cfg = _config(tmp_path / "int", checkpoint_every_epochs=1)
    t = _PreemptAfterFirstStep(cfg, resume=False)
    t.fit()
    assert t.preempted and t.loader.lookahead_used
    resumed = Trainer(cfg, resume=True)
    assert (resumed.start_epoch, resumed._skip_steps) == (1, 1)
    resumed.fit()
    assert not resumed.preempted

    for a, b in zip(
        jtu.tree_leaves(jax.device_get(ctl.layout.canonical(ctl.state))),
        jtu.tree_leaves(jax.device_get(resumed.layout.canonical(resumed.state))),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
