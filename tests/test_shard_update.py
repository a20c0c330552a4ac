"""ZeRO-sharded optimizer update ladder (parallel/shard_update.py).

The contract under test is BIT-identity wherever it is claimed: one
optimizer step under ``shard_update`` zero2 or zero3 must produce
byte-identical params and (gathered) optimizer state to the replicated
update, for every supported codec mode — those shardings are a
memory/FLOP layout change, never a semantics change.  zero1 carries a
DECLARED deviation (train_step._apply_update_zero1): its train-step
trajectories match to within FMA-contraction ulps, pinned here at
tolerance, while its fence *inputs* (the sliced full mean vs the scatter
path's shards) and its update-only program stay byte-identical — both
pinned exactly.  Checkpoints store the canonical gathered layout, so
blobs restore across every layout in both directions, byte-identically,
in both on-disk formats.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ddlpc_tpu.config import (
    CompressionConfig,
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    ParallelConfig,
    TrainConfig,
)
from ddlpc_tpu.models import build_model
from ddlpc_tpu.parallel import shard_update as zero
from ddlpc_tpu.parallel.grad_sync import (
    sync_gradients,
    sync_gradients_scatter,
)
from ddlpc_tpu.parallel.mesh import make_mesh
from ddlpc_tpu.parallel.shard_update import StateLayout, resolve_shard_update
from ddlpc_tpu.parallel.train_step import (
    create_train_state,
    make_train_step,
    make_train_step_gspmd,
    make_update_step,
)
from ddlpc_tpu.train.optim import build_optimizer

# Smallest model that still has the interesting leaf zoo (conv kernels,
# biases and BN scale/bias SMALLER than the shard count → padding path):
# compile time is the cost of the identity matrix, not step time.
MCFG = ModelConfig(features=(4,), bottleneck_features=4, num_classes=3)
H = W = 8
N_DATA = 4  # ≥4-device mesh per the acceptance criteria (conftest gives 8)


def _setup(compression, level, remat=False, gspmd=False, n_data=N_DATA,
           optimizer="adam"):
    """Build (state, step, layout, tx, mesh) for a resolved ZeRO level
    string ('off'|'zero1'|'zero2'|'zero3'); ``gspmd=True`` maps the level
    to its GSPMD layout spelling."""
    pcfg = ParallelConfig(data_axis_size=n_data, space_axis_size=1)
    mesh = make_mesh(pcfg, jax.devices()[:n_data])
    model = build_model(MCFG, norm_axis_name=None if gspmd else "data")
    tx = build_optimizer(
        TrainConfig(learning_rate=1e-2, optimizer=optimizer)
    )
    state = create_train_state(model, tx, jax.random.PRNGKey(0), (1, H, W, 3))
    if level == "off" or n_data <= 1:
        mode = "replicated"
    elif gspmd:
        mode = zero.GSPMD_LAYOUT_FOR_LEVEL[level]
    else:
        mode = level
    layout = StateLayout(mode, tx, state, mesh, "data")
    state = layout.place(state)
    if gspmd:
        step = make_train_step_gspmd(
            model, tx, mesh, compression,
            donate_state=False, remat=remat, shard_update=level,
        )
    else:
        step = make_train_step(
            model, tx, mesh, compression,
            donate_state=False, remat=remat, shard_update=level,
            param_avals=layout.param_avals,
        )
    return state, step, layout, tx, mesh


def _batch(a=2, b=8, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    images = jax.random.normal(k1, (a, b, H, W, 3))
    labels = jax.random.randint(k2, (a, b, H, W), 0, 3)
    return images, labels


def _assert_states_identical(ref, got):
    for a, b in zip(
        jax.tree.leaves((ref.params, ref.opt_state, ref.batch_stats)),
        jax.tree.leaves((got.params, got.opt_state, got.batch_stats)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _run_pair(compression, level, remat=False, gspmd=False, steps=3):
    images, labels = _batch()
    s_r, step_r, _, _, _ = _setup(compression, "off", remat, gspmd)
    s_s, step_s, layout, _, _ = _setup(compression, level, remat, gspmd)
    for _ in range(steps):
        s_r, m_r = step_r(s_r, images, labels)
        s_s, m_s = step_s(s_s, images, labels)
    return s_r, layout.canonical(s_s), m_r, m_s


def _run_identity(compression, level="zero2", remat=False, gspmd=False,
                  steps=3):
    s_r, s_c, m_r, m_s = _run_pair(compression, level, remat, gspmd, steps)
    _assert_states_identical(s_r, s_c)
    return m_r, m_s


# -- bit-identity: sharded vs replicated update -----------------------------

CODECS = {
    "none": CompressionConfig(),
    "int8_nearest": CompressionConfig(mode="int8"),
    "fp16": CompressionConfig(mode="float16"),
    "stochastic": CompressionConfig(mode="int8", rounding="stochastic"),
}


def _codec_matrix(extra_slow=()):
    return [
        pytest.param(c, marks=pytest.mark.slow)
        if (c == "stochastic" or c in extra_slow) else c
        for c in sorted(CODECS)
    ]


@pytest.mark.parametrize("codec", _codec_matrix(), ids=sorted(CODECS))
def test_bit_identity_vs_replicated(codec):
    """Multi-step bit-identity on a 4-device mesh: params, gathered opt
    state AND batch stats byte-equal after 3 optimizer steps, per codec
    (zero2 — the ladder's default, PR 5's sharded update renamed).

    Also pins the grad_norm telemetry fix on the same compiled pair: the
    sharded step psums partial squared norms, so the logged value matches
    the replicated step's optax.global_norm (up to reduction-order ulps)
    instead of reporting a 1/N-shard norm."""
    m_r, m_s = _run_identity(CODECS[codec], level="zero2")
    np.testing.assert_allclose(
        float(m_r["grad_norm"]), float(m_s["grad_norm"]), rtol=1e-5
    )
    assert float(m_s["grad_norm"]) > 0


@pytest.mark.parametrize(
    "codec", _codec_matrix(extra_slow=("fp16",)), ids=sorted(CODECS)
)
def test_bit_identity_zero3(codec):
    """zero3 (params persist sharded, gathered on demand at the step
    head) keeps the same byte-for-byte bar as zero2: same scatter wire,
    same fenced chunk update — only the params' resting layout moves."""
    m_r, m_s = _run_identity(CODECS[codec], level="zero3")
    np.testing.assert_allclose(
        float(m_r["grad_norm"]), float(m_s["grad_norm"]), rtol=1e-5
    )


def test_zero1_trajectory_within_declared_tolerance():
    """zero1's DECLARED deviation (train_step._apply_update_zero1): the
    train-step trajectory matches the replicated one to FMA-contraction
    ulps — the chunk slice fuses into the Adam kernel and LLVM contracts
    mul+add differently per fusion shape — NOT byte-for-byte.  Pinned at
    a tolerance three orders tighter than any codec's declared loss; the
    update's INPUTS stay bit-identical
    (test_zero1_fence_inputs_match_scatter_shards) and the update-only
    program is exactly identical (test_update_step_builder_runs)."""
    s_r, s_c, m_r, m_s = _run_pair(CODECS["none"], "zero1")
    for a, b in zip(
        jax.tree.leaves((s_r.params, s_r.opt_state)),
        jax.tree.leaves((s_c.params, s_c.opt_state)),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-6, atol=1e-8
        )
    # batch stats never pass through the chunked update — still exact.
    for a, b in zip(
        jax.tree.leaves(s_r.batch_stats), jax.tree.leaves(s_c.batch_stats)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(
        float(m_r["grad_norm"]), float(m_s["grad_norm"]), rtol=1e-6
    )


@pytest.mark.parametrize("codec", sorted(CODECS), ids=sorted(CODECS))
def test_zero1_fence_inputs_match_scatter_shards(codec):
    """The bit-exact half of zero1's declared deviation: each replica's
    slice of the full (codec'd) mean equals the scatter path's shard
    element-for-element — ``psum`` + ``local_chunk`` ≡ ``psum_scatter``,
    and the scatter codec quantizes shards with the global scale and the
    sliced full-shape noise field, so the equivalence survives every
    codec including stochastic rounding.  This is the pin
    ``_apply_update_zero1``'s docstring cites: the fence INPUTS agree
    bitwise; only downstream fusion drifts."""
    comp = CODECS[codec]
    pcfg = ParallelConfig(data_axis_size=N_DATA, space_axis_size=1)
    mesh = make_mesh(pcfg, jax.devices()[:N_DATA])
    k = jax.random.PRNGKey(3)
    tree = {
        "w": jax.random.normal(k, (7, 5), jnp.float32),  # padded chunking
        "b": jax.random.normal(k, (3,), jnp.float32) * 1e-3,  # < N leaves
    }

    def body(t):
        idx = lax.axis_index("data")
        g = jax.tree.map(lambda x: x * (1.0 + jnp.float32(idx)), t)
        key = (
            jax.random.PRNGKey(11) if comp.rounding == "stochastic" else None
        )
        mean = sync_gradients(g, "data", comp, axis_size=N_DATA, key=key)
        shards = sync_gradients_scatter(
            g, "data", comp, axis_size=N_DATA, key=key
        )
        sliced = jax.tree.map(
            lambda m: zero.local_chunk(m, N_DATA, "data"), mean
        )
        return sliced, shards

    fn = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P(),),
            out_specs=(P("data"), P("data")), check_vma=False,
        )
    )
    sliced, shards = fn(tree)
    for a, b in zip(jax.tree.leaves(sliced), jax.tree.leaves(shards)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bit_identity_with_remat():
    """remat changes memory, never math — sharded remat'd step must equal
    the replicated plain step bitwise (grads are recomputed identically)."""
    images, labels = _batch()
    s_r, step_r, _, _, _ = _setup(CODECS["none"], "off", remat=False)
    s_s, step_s, layout, _, _ = _setup(CODECS["none"], "zero2", remat=True)
    for _ in range(2):
        s_r, _ = step_r(s_r, images, labels)
        s_s, _ = step_s(s_s, images, labels)
    _assert_states_identical(s_r, layout.canonical(s_s))


@pytest.mark.slow
@pytest.mark.parametrize(
    "codec", ["int8_nearest", "fp16", "stochastic"]
)
def test_bit_identity_remat_codec_matrix(codec):
    """Full remat × codec matrix (the fast tier covers remat × none and
    every codec unremat'd; the cross terms are convergence-grade)."""
    _run_identity(CODECS[codec], level="zero2", remat=True)


@pytest.mark.parametrize(
    "level",
    [
        "zero1",
        pytest.param("zero2", marks=pytest.mark.slow),
        "zero3",
    ],
)
def test_bit_identity_gspmd(level):
    """GSPMD spellings: partitioner-inserted collectives over
    P(data)-sharded moments (gspmd/zero1), pinned-scatter gradients
    (gspmd_zero2) and boundary-sharded params (gspmd_zero3) must all be
    byte-identical to the replicated GSPMD step — in the GSPMD family
    even zero1 keeps the exact bar, because the partitioner never
    re-fuses the update differently per layout (the logical program is
    literally the same jaxpr)."""
    _run_identity(CODECS["none"], level=level, gspmd=True)


@pytest.mark.slow
@pytest.mark.parametrize("codec", ["fp16", "int8_nearest"])
def test_bit_identity_gspmd_codec(codec):
    comp = dataclasses.replace(CODECS[codec], quantize_local=False)
    _run_identity(comp, level="zero2", gspmd=True)


def test_sgd_momentum_trace_shards():
    """Non-Adam state (SGD momentum trace) is param-shaped and must shard/
    restore through the same chunk rule."""
    images, labels = _batch()
    s_r, step_r, _, _, _ = _setup(CODECS["none"], "off", optimizer="sgd")
    s_s, step_s, layout, _, _ = _setup(
        CODECS["none"], "zero2", optimizer="sgd"
    )
    for _ in range(2):
        s_r, _ = step_r(s_r, images, labels)
        s_s, _ = step_s(s_s, images, labels)
    _assert_states_identical(s_r, layout.canonical(s_s))


# -- layout mechanics -------------------------------------------------------

def test_opt_state_is_chunked_and_sharded():
    """The run layout actually shards: each device holds 1/N of every
    moment leaf ([1, K] of the [N, K] chunk view), so per-device optimizer
    bytes drop ~N× (the hbm_report.py evidence measures the same thing)."""
    s_s, _, layout, tx, mesh = _setup(CODECS["none"], "zero2")
    template = zero.opt_state_template(tx, s_s.params)
    pshapes = zero.param_shapes(s_s.params)
    n_chunked = 0
    for t, leaf in zip(
        jax.tree.leaves(template), jax.tree.leaves(s_s.opt_state)
    ):
        if zero.chunkable(t.shape, pshapes):
            n_chunked += 1
            size = int(np.prod(t.shape))
            k = zero.chunk_rows(size, N_DATA)
            assert leaf.shape == (N_DATA, k)
            shard = leaf.addressable_shards[0]
            assert shard.data.shape == (1, k)  # 1/N per device
        else:
            assert leaf.shape == t.shape  # scalars stay replicated
    assert n_chunked > 0  # Adam: mu and nu trees


def test_zero3_params_are_chunked_and_sharded():
    """zero3's resting layout: every param leaf persists as its [N, K]
    chunk view, one [1, K] row per device — the ddlpc_hbm_bytes params
    gauge's 1/N claim, structurally."""
    s_s, _, layout, _, _ = _setup(CODECS["none"], "zero3")
    for av, leaf in zip(
        jax.tree.leaves(layout.param_avals), jax.tree.leaves(s_s.params)
    ):
        k = zero.chunk_rows(int(np.prod(av.shape)), N_DATA)
        assert leaf.shape == (N_DATA, k)
        assert leaf.addressable_shards[0].data.shape == (1, k)
    # full_params restores the canonical shapes bit-exactly.
    full = layout.full_params(s_s)
    for av, leaf in zip(
        jax.tree.leaves(layout.param_avals), jax.tree.leaves(full)
    ):
        assert leaf.shape == av.shape


def test_chunk_roundtrip_shapes():
    rng = np.random.default_rng(0)
    for shape in [(3,), (4,), (7, 5), (4, 13), (1,)]:
        x = rng.standard_normal(shape).astype(np.float32)
        c = zero.chunk_leaf(jnp.asarray(x), N_DATA)
        assert c.shape[0] == N_DATA
        np.testing.assert_array_equal(
            np.asarray(zero.unchunk_leaf(c, shape)), x
        )


def test_singleton_mesh_is_noop():
    """shard_update on a 1-device mesh falls back to the replicated
    program: param-shaped opt_state, runnable step, finite loss."""
    s, step, layout, tx, _ = _setup(CODECS["none"], "zero2", n_data=1)
    assert layout.mode == "replicated"
    template = zero.opt_state_template(tx, s.params)
    for t, leaf in zip(
        jax.tree.leaves(template), jax.tree.leaves(s.opt_state)
    ):
        assert leaf.shape == t.shape
    images, labels = _batch(b=2)
    s, metrics = step(s, images, labels)
    assert np.isfinite(float(metrics["loss"]))


# -- config resolution ------------------------------------------------------

def test_resolve_shard_update():
    plain = CompressionConfig()
    ring = CompressionConfig(mode="int8", transport="ring")
    pallas = CompressionConfig(mode="int8", codec_backend="pallas")
    # auto/on keep PR 5's program under its ladder name: zero2.
    assert resolve_shard_update("auto", plain, 4, spatial=False) == "zero2"
    assert resolve_shard_update("on", plain, 4, spatial=False) == "zero2"
    assert resolve_shard_update("off", plain, 4, spatial=False) == "off"
    # Explicit rungs pass through (multi-device).
    for lvl in ("zero1", "zero2", "zero3"):
        assert resolve_shard_update(lvl, plain, 4, spatial=False) == lvl
        # Singleton mesh: every rung is a no-op.
        assert resolve_shard_update(lvl, plain, 1, spatial=False) == "off"
    assert resolve_shard_update("auto", plain, 1, spatial=False) == "off"
    # Incompatible codecs gate the SCATTER rungs only: auto resolves off,
    # explicit zero2/zero3 refuse loudly, zero1 composes (its sync is the
    # unmodified full all-reduce — the ring/pallas codec sees the whole
    # mean before any chunking).
    assert resolve_shard_update("auto", ring, 4, spatial=False) == "off"
    assert resolve_shard_update("zero1", ring, 4, spatial=False) == "zero1"
    with pytest.raises(ValueError, match="ring"):
        resolve_shard_update("on", ring, 4, spatial=False)
    with pytest.raises(ValueError, match="ring"):
        resolve_shard_update("zero3", ring, 4, spatial=False)
    assert resolve_shard_update("auto", pallas, 4, spatial=False) == "off"
    assert (
        resolve_shard_update("zero1", pallas, 4, spatial=False) == "zero1"
    )
    with pytest.raises(ValueError, match="pallas"):
        resolve_shard_update("on", pallas, 4, spatial=False)
    # Global-norm clipping couples leaves across the tree — incompatible
    # with EVERY chunked rung (each replica would clip by its shard norm).
    assert (
        resolve_shard_update(
            "auto", plain, 4, spatial=False, grad_clip_norm=1.0
        )
        == "off"
    )
    with pytest.raises(ValueError, match="grad_clip_norm"):
        resolve_shard_update(
            "zero1", plain, 4, spatial=False, grad_clip_norm=1.0
        )
    # ...but GSPMD keeps its own codec semantics (no per-replica stage):
    assert resolve_shard_update("auto", pallas, 4, spatial=True) == "zero2"
    assert resolve_shard_update("zero3", ring, 4, spatial=True) == "zero3"
    # ring with mode='none' is a plain pmean — composable.
    assert (
        resolve_shard_update(
            "auto", CompressionConfig(transport="ring"), 4, spatial=False
        )
        == "zero2"
    )
    with pytest.raises(ValueError, match="shard_update"):
        resolve_shard_update("sideways", plain, 4, spatial=False)


# -- checkpoint round-trips across layouts ----------------------------------

def _tiny_trainer_cfg(workdir, shard_update, ckpt_format="chunked"):
    return ExperimentConfig(
        model=ModelConfig(features=(4, 8), bottleneck_features=8, num_classes=4),
        data=DataConfig(
            dataset="synthetic", image_size=(16, 16), synthetic_len=16,
            test_split=4, num_classes=4,
        ),
        train=TrainConfig(
            epochs=1, micro_batch_size=1, sync_period=1,
            dump_images_per_epoch=0, checkpoint_format=ckpt_format,
        ),
        parallel=ParallelConfig(shard_update=shard_update),
        workdir=workdir,
    )


def _canonical(trainer):
    return trainer.layout.canonical(trainer.state)


@pytest.fixture(scope="module")
def trained_sources(tmp_path_factory):
    """One trained-and-saved run per source layout — the expensive part
    (a real train-step compile so moments are nonzero; zeros would
    restore trivially) shared by the cross-restore directions.  Each
    source saves BOTH on-disk formats: its own checkpointer writes the
    chunked blob; the same canonical state is re-written monolithic into
    a sibling workdir (identical bytes in, two formats out)."""
    from ddlpc_tpu.train import checkpoint as ckpt
    from ddlpc_tpu.train.trainer import Trainer

    out = {}
    for src in ("zero2", "zero3", "off"):
        workdir = str(tmp_path_factory.mktemp(f"src_{src}"))
        tr = Trainer(_tiny_trainer_cfg(workdir, src), resume=False)
        tr.train_epoch(0)
        tr.save(epoch=0)
        tr.checkpointer.close()
        mono_workdir = str(tmp_path_factory.mktemp(f"src_{src}_mono"))
        state = _canonical(tr)
        ckpt.save_checkpoint(
            os.path.join(mono_workdir, "checkpoints"),
            state,
            step=int(np.asarray(state.step)),
            metadata={"epoch": 0},
            format="monolithic",
        )
        out[src] = {
            "chunked": workdir,
            "monolithic": mono_workdir,
            "want": state,
        }
    return out


@pytest.mark.parametrize("fmt", ["chunked", "monolithic"])
@pytest.mark.parametrize(
    "src,dst",
    [
        ("zero2", "off"),
        ("off", "zero2"),
        ("zero3", "off"),
        ("off", "zero3"),
        ("zero2", "zero3"),
        ("zero3", "zero1"),
    ],
    ids=[
        "zero2_to_repl",
        "repl_to_zero2",
        "zero3_to_repl",
        "repl_to_zero3",
        "zero2_to_zero3",
        "zero3_to_zero1",
    ],
)
def test_checkpoint_roundtrip_across_layouts(trained_sources, fmt, src, dst):
    """A checkpoint saved under any layout restores byte-identically into
    any other (both on-disk formats): blobs always store the canonical
    gathered layout, so the ZeRO rung is a runtime property only — the
    PR 5 cross-layout matrix, extended down the ladder."""
    from ddlpc_tpu.train.trainer import Trainer

    workdir = trained_sources[src][fmt]
    want = trained_sources[src]["want"]
    dst_tr = Trainer(_tiny_trainer_cfg(workdir, dst), resume=True)
    assert dst_tr.start_epoch == 1
    got = _canonical(dst_tr)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_resolves_auto(tmp_path):
    from ddlpc_tpu.train.trainer import Trainer

    tr = Trainer(_tiny_trainer_cfg(str(tmp_path / "auto"), "auto"), resume=False)
    # conftest forces an 8-device mesh → auto resolves to zero2.
    assert tr.shard_update == "zero2"
    assert tr.layout.mode == "zero2"


@pytest.mark.parametrize("level", ["zero1", "zero2", "zero3"])
def test_update_step_builder_runs(level):
    """make_update_step (the bench's update-only program) matches the
    layouts and is EXACTLY identical to the replicated update at every
    rung — including zero1, whose train-step deviation is specific to
    the fused train program (here the chunk slice feeds the Adam kernel
    unfused, so even the FMA contraction matches)."""
    s_r, _, _, tx, mesh = _setup(CODECS["none"], "off")
    s_s, _, layout, _, _ = _setup(CODECS["none"], level)
    grads = jax.tree.map(jnp.ones_like, layout.param_avals)
    grads = jax.tree.map(lambda g: jnp.asarray(g, jnp.float32), grads)
    upd_r = make_update_step(tx, mesh, CODECS["none"], shard_update="off")
    upd_s = make_update_step(tx, mesh, CODECS["none"], shard_update=level)
    p_r, o_r = upd_r(s_r.params, s_r.opt_state, grads)
    p_s, o_s = upd_s(s_s.params, s_s.opt_state, grads)
    full = layout.canonical(s_s.replace(params=p_s, opt_state=o_s))
    for a, b in zip(
        jax.tree.leaves((p_r, o_r)),
        jax.tree.leaves((full.params, full.opt_state)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
