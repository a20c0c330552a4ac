"""SPMD train/eval steps: shard_map over the device mesh.

This module is the TPU-native equivalent of the reference's two training
drivers (server кластер.py:690-790, worker :792-895) collapsed into one SPMD
program:

- micro-batch gradient accumulation over ``sync_period`` steps is a
  ``lax.scan`` (reference: Python loop + loss.backward() accumulating into
  param.grad, кластер.py:750-759);
- gradient synchronization is one fused all-reduce inside the compiled step
  (reference: pickle → mgzip → TCP star round trip, кластер.py:255-557) with
  the optional lossy codec applied at the same points (see grad_sync.py);
- the optimizer step runs identically on every replica on bit-identical
  gradients (reference guarantees this by re-broadcasting the quantized
  average and self-applying it, кластер.py:402-438).

Everything is a pure function of (state, batch); the whole step —
A micro-batches of forward/backward, the all-reduce, the codec, the Adam
update — compiles to a single XLA executable with no host round trips.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax import struct
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddlpc_tpu.config import CompressionConfig, ExperimentConfig
from ddlpc_tpu.models.layers import group_labels
from ddlpc_tpu.ops.losses import nll_correct_valid, softmax_cross_entropy_sum
from ddlpc_tpu.ops.metrics import confusion_from_logits
from ddlpc_tpu.parallel.grad_sync import sync_gradients, sync_gradients_scatter
from ddlpc_tpu.parallel import shard_update as zero

PyTree = Any


def _rounding_rng(
    compression: CompressionConfig, seed: int, step: jax.Array
) -> Optional[jax.Array]:
    """Stochastic-rounding key: a pure function of (experiment seed,
    replicated step counter), so every replica derives the same key
    (bit-identical rounding decisions), resumed runs replay the same noise,
    and different seeds draw different rounding noise (seed-sensitivity
    studies need the noise to vary with the seed).  Shared by both step
    builders so their key schedules cannot diverge."""
    if compression.rounding != "stochastic":
        return None
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.key(0x5EED), seed), step
    )


class TrainState(struct.PyTreeNode):
    """Replicated training state.

    The reference distributes this by pickling the live ``[network,
    optimizer, criterion]`` CUDA object graph over TCP at startup
    (кластер.py:560-565); here it is a pytree that the mesh keeps replicated.
    """

    step: jax.Array
    params: PyTree
    batch_stats: PyTree
    opt_state: PyTree


def create_train_state(
    model: nn.Module,
    tx: optax.GradientTransformation,
    rng: jax.Array,
    input_shape: Tuple[int, ...],
    input_dtype: Any = jnp.float32,
) -> TrainState:
    """Initialize parameters/optimizer on host. input_shape: [N, H, W, C];
    ``input_dtype`` is the dataset's (integer for token tiles)."""
    variables = model.init(rng, jnp.zeros(input_shape, input_dtype), train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=tx.init(params),
    )


def _loss_and_metrics(
    model: nn.Module,
    params: PyTree,
    batch_stats: PyTree,
    images: jax.Array,
    labels: jax.Array,
    train: bool,
):
    """Returns ``loss, (new batch_stats, accuracy, counters)``.  A model
    without a ``batch_stats`` collection keeps the (empty) tree it was given;
    ``counters`` is what the model sowed into its ``counters`` collection under
    ``train=True``: scalars under ``"sum"`` and under ``"max"``, by how the step
    reduces them (``models/lfm2_moe.py``'s routing counts; empty for the conv zoo).

    A model's own losses are the scalars it sowed into its ``losses``
    collection under ``train=True`` (``models/keye_vl2.py``'s ``indexer_kl``):
    each is added to the loss here, inside what the step differentiates, and
    reported beside it under its name as the mean over micro-batches and
    replicas (``counters["mean"]``).  A model that sows none gets the program
    it had."""
    variables = {"params": params, "batch_stats": batch_stats}
    counters = {}
    own = {}
    if train:
        logits, updates = model.apply(
            variables, images, train=True, mutable=["batch_stats", "counters", "losses"]
        )
        new_stats = updates.get("batch_stats", batch_stats)
        counters = dict(updates.get("counters", {}))
        own = dict(updates.get("losses", {}))
    else:
        logits = model.apply(variables, images, train=False)
        new_stats = batch_stats
    loss, acc = loss_from_logits(model, logits, labels, train)
    if own:
        loss = loss + sum(own.values())
        counters["mean"] = own
    return loss, (new_stats, acc, counters)


def _reduce_counters(stacked: dict, axis_name: Optional[str] = None) -> dict:
    """One value per optimizer step from the model's per-micro-batch counters
    (leading axis ``A``): those under ``"sum"`` added up over micro-batches
    and replicas, those under ``"max"`` the largest of them, those under
    ``"mean"`` (the model's own losses) their mean.  The model says
    which is which and nothing downstream asks again: the Trainer records
    every counter's mean over an epoch's steps (the mean of a ``"max"``
    counter's per-step values included).  ``axis_name`` is None in a program
    over global arrays."""
    out = {}
    for name, v in stacked.get("sum", {}).items():
        out[name] = v.sum() if axis_name is None else lax.psum(v.sum(), axis_name)
    for name, v in stacked.get("max", {}).items():
        out[name] = v.max() if axis_name is None else lax.pmax(v.max(), axis_name)
    for name, v in stacked.get("mean", {}).items():
        out[name] = v.mean() if axis_name is None else lax.pmean(v.mean(), axis_name)
    return out


@jax.named_scope("ddlpc/loss")
def loss_from_logits(
    model: nn.Module, logits: jax.Array, labels: jax.Array, train: bool
) -> Tuple[jax.Array, jax.Array]:
    """The loss/accuracy tail of :func:`_loss_and_metrics` — one owner for
    the grouped-head regrouping and the void-pixel mean, under the
    ``ddlpc/loss`` scope the benchmark's layer metrics read."""
    # train_head_layout='grouped': the model returned pre-d2s phase-major
    # logits [..., H/r, W/r, r²·C] (models/layers.py:group_labels).  Group
    # the labels the same way and run the SAME loss/metric functions on the
    # [..., r², C] view — identical math (same multiset of (logit row,
    # label) pairs), no full-res tensor or d2s transpose in the train graph.
    if logits.shape[-3:-1] != labels.shape[-2:]:
        # Only regroup when the model DECLARED the grouped layout — a model
        # bug producing wrong-shaped logits whose dims happen to divide the
        # labels must error, not silently train on scrambled pairings.
        declared = getattr(model, "train_head_layout", "fullres")
        if not (train and declared == "grouped"):
            raise ValueError(
                f"logits spatial shape {logits.shape[-3:-1]} != labels "
                f"{labels.shape[-2:]} but the model declares "
                f"train_head_layout={declared!r} (train={train}) — refusing "
                "to reinterpret as grouped logits"
            )
        r = labels.shape[-2] // logits.shape[-3]
        if (labels.shape[-2] != r * logits.shape[-3]
                or labels.shape[-1] != r * logits.shape[-2]):
            raise ValueError(
                f"grouped logits {logits.shape} are not an integer r×r "
                f"regrouping of labels {labels.shape}"
            )
        labels = group_labels(labels, r)
        logits = logits.reshape(*logits.shape[:-1], r * r, -1)
    # -1 marks void/ignored pixels (e.g. Cityscapes' unlabeled classes,
    # scripts/prepare_cityscapes.py); they contribute neither loss nor
    # accuracy.  Datasets without voids have no -1 labels, so this is a
    # no-op for them.  The mean is per-micro-batch over ITS valid pixels
    # (then gradients average equally across micro-batches/replicas) —
    # deliberately the torch CrossEntropyLoss(reduction='mean') + DDP
    # semantics the reference inherits, not a globally pixel-weighted mean;
    # the eval path (softmax_cross_entropy_sum) is globally weighted.
    # Loss and accuracy come from ONE fused pass over the logits
    # (ops/losses.py:nll_correct_valid) — computing them separately cost
    # ~90 ms/step in fp32 materializations and layout copies of the
    # largest tensor in the step (docs/head_bench/trace_plain_grouped.json).
    nll, correct, valid = nll_correct_valid(logits, labels, ignore_index=-1)
    # Deep-supervision stacks ([J, ...] logits with labels broadcast over
    # J): broadcasting valid to nll's shape makes the denominator count
    # head×pixel terms, so the loss is the MEAN of per-head losses (the
    # documented U-Net++ semantics) and accuracy stays in [0, 1].  The
    # previous sum/valid.sum() form counted pixels once — J× the per-head
    # mean and >1 accuracies (review find, round 4; Adam's update is
    # invariant to the loss scale, so committed r3 U-Net++ curves remain
    # valid trajectories — only the reported loss/acc change).
    valid = jnp.broadcast_to(valid, nll.shape)
    denom = jnp.maximum(valid.sum(), 1.0)
    loss = (nll * valid).sum() / denom
    acc = (correct * valid).sum() / denom
    return loss, acc


def _accumulate_grads(
    model: nn.Module,
    state: "TrainState",
    images: jax.Array,
    labels: jax.Array,
    remat: bool = False,
):
    """Scan ``A`` micro-batches accumulating fp32 grads (the reference's
    loss.backward() accumulation loop, кластер.py:750-759).  Shared by the
    shard_map and GSPMD step builders so their semantics cannot diverge.
    Returns (mean grads, new batch_stats, losses [A], accs [A], counters:
    dict of [A], see :func:`_reduce_counters`).

    ``remat=True`` wraps each micro-batch's forward in ``jax.checkpoint``:
    no activations are stored between forward and backward — the backward
    pass recomputes the forward — trading ~1/3 more FLOPs for the peak-HBM
    headroom to run larger micro-batches (TrainConfig.remat).
    """

    def loss_fn(p, stats, x, y):
        return _loss_and_metrics(model, p, stats, x, y, train=True)

    if remat:
        loss_fn = jax.checkpoint(loss_fn)

    def micro(carry, xy):
        grads_acc, stats = carry
        x, y = xy
        (loss, (stats, acc, counters)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params, stats, x, y)
        grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
        return (grads_acc, stats), (loss, acc, counters)

    # Device scopes (``ddlpc/<region>`` in every instruction's op_name) are
    # metadata only: the optimized HLO, its fusions and its bits do not move.
    with jax.named_scope("ddlpc/accumulate"):
        zeros = jax.tree.map(
            lambda p: jnp.zeros_like(p, jnp.float32), state.params
        )
        (grads, batch_stats), (losses, accs, counters) = lax.scan(
            micro, (zeros, state.batch_stats), (images, labels)
        )
        grads = jax.tree.map(lambda g: g / images.shape[0], grads)
    return grads, batch_stats, losses, accs, counters


@jax.named_scope("ddlpc/update")
def _fenced_update(
    tx: optax.GradientTransformation,
    grads: PyTree,
    opt_state: PyTree,
    params: PyTree,
) -> Tuple[PyTree, PyTree]:
    """tx.update + apply_updates inside ``lax.optimization_barrier`` fences.

    The barriers pin the optimizer arithmetic into an isolated fusion
    region: without them XLA fuses the elementwise Adam chain into its
    *surrounding* ops — the all-reduce consumer in the replicated step, the
    reduce-scatter/all-gather pair in the sharded one — and the two
    programs then contract mul+add into FMA differently on small leaves,
    producing 1-ulp drift between layouts (observed on the CPU backend:
    identical mean gradients and moments in, updates differing by 1 ulp on
    bias/BatchNorm leaves from step 2 on).  With the fence the update
    subprogram is bit-identical across layouts — the property the
    shard-vs-replicated identity tests and cross-layout checkpoint
    restores rely on.  Perf cost: none measurable (the update is a few
    fused elementwise loops either side of the fence).
    """
    grads, opt_state, params = lax.optimization_barrier(
        (grads, opt_state, params)
    )
    updates, new_opt = tx.update(grads, opt_state, params)
    new_params = optax.apply_updates(params, updates)
    return lax.optimization_barrier((new_params, new_opt))


@jax.named_scope("ddlpc/update/gather")
def _gather_params(shards: PyTree, like: PyTree, data_axis: str) -> PyTree:
    """All-gather ``[1, K]`` parameter chunks back to the shapes of ``like``
    (arrays or avals): zero1/zero2's publish after the update, zero3's
    gather-on-demand before the forward."""
    return jax.tree.map(
        lambda sh, p: zero.unchunk_leaf(
            lax.all_gather(sh, data_axis, axis=0, tiled=True), p.shape
        ),
        shards,
        like,
    )


_global_norm = jax.named_scope("ddlpc/grad_sync/norm")(optax.global_norm)


@jax.named_scope("ddlpc/grad_sync/norm")
def _psum_sq_norm(tree: PyTree, axis_name: str) -> jax.Array:
    """Global gradient norm from per-replica partial sums of squares —
    under the sharded update each replica only holds 1/N of the mean
    gradient, so the squared partials are psum'd before the sqrt to keep
    the logged ``grad_norm`` comparable across all step variants."""
    leaves = jax.tree_util.tree_leaves(tree)
    sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    return jnp.sqrt(lax.psum(sq, axis_name))


def _apply_update_sharded(
    tx: optax.GradientTransformation,
    params: PyTree,
    opt_state: PyTree,
    grads: PyTree,
    data_axis: str,
    axis_size: int,
    compression: CompressionConfig,
    key,
):
    """The ZeRO-2 weight-update path, called inside shard_map with LOCAL
    values: full per-replica ``grads``/``params``, this replica's ``[1, K]``
    chunks of the optimizer moments in ``opt_state``.  The gradient sync
    is a reduce-scatter — the optimizer-boundary gradient only ever
    materializes as this replica's shard (1/N of the tree per device),
    which is what makes this zero2: zero1's full-mean all-reduce IS this
    reduce-scatter plus an all-gather of gradients nobody needs
    (``_apply_update_zero1``).  Returns the fresh full params
    (all-gathered), the updated local moment chunks, and the psum'd grad
    norm of the post-codec mean.  Shared by the train step and the
    update-only bench program so their semantics cannot diverge."""
    grad_shards = sync_gradients_scatter(
        grads, data_axis, compression, axis_size=axis_size, key=key
    )
    with jax.named_scope("ddlpc/update/chunk"):
        param_shards = jax.tree.map(
            lambda p: zero.local_chunk(p, axis_size, data_axis), params
        )
    new_param_shards, new_opt = _fenced_update(
        tx, grad_shards, opt_state, param_shards
    )
    new_params = _gather_params(new_param_shards, params, data_axis)
    return new_params, new_opt, _psum_sq_norm(grad_shards, data_axis)


def _apply_update_zero1(
    tx: optax.GradientTransformation,
    params: PyTree,
    opt_state: PyTree,
    grads: PyTree,
    data_axis: str,
    axis_size: int,
    compression: CompressionConfig,
    key,
):
    """The TRUE ZeRO-1 weight-update path (sharded moments, full-mean
    gradient sync): the all-reduce is the unmodified ``sync_gradients``
    — every codec and transport composes, the ring and the pallas mean
    stage included, because the codec sees the whole mean — then each
    replica slices its ``[1, K]`` row of the mean and of the params,
    runs the fenced update on the chunks, and all-gathers fresh params.

    DECLARED DEVIATION (test-pinned): zero1 trajectories match
    replicated/zero2 to within FMA-contraction ulps, not byte-for-byte.
    The update's *inputs* are bit-identical — the sliced mean equals the
    scatter path's shards element-for-element (``psum`` ≡ ``psum_scatter``
    per element is test-pinned, and the scatter codec quantizes shards
    with the global scale and the sliced full-shape noise field precisely
    so its shards equal slices of the full quantized mean; both pins in
    tests/test_shard_update.py) — but the chunk *slice* feeds the update
    through fusable ops, the backend fuses it into the Adam kernel
    (``lax.optimization_barrier`` does not block loop fusion on the CPU
    backend — verified in the optimized HLO), and LLVM then contracts
    mul+add into FMA differently than in the replicated/zero2 kernels,
    whose update inputs are jit-boundary or collective outputs: ≤1-ulp
    drift per step on small leaves.  zero2/zero3 keep the byte-for-byte
    bar; zero1 exists for the combinations the scatter path refuses
    (``resolve_shard_update``: ring transport, pallas mean stage — codecs
    whose *declared* loss dwarfs an update ulp) and as the honest A/B
    baseline for the zero2-≤-zero1 perf claim (``bench.py --update-ab``).
    Wire: zero1 moves 3·P elements per step (2·P all-reduce + P params
    all-gather) where zero2 moves 2·P — zero2 literally stops
    all-gathering what the reduce-scatter just produced."""
    grads = sync_gradients(
        grads, data_axis, compression, axis_size=axis_size, key=key
    )
    grad_norm = _global_norm(grads)
    with jax.named_scope("ddlpc/update/chunk"):
        grad_shards = jax.tree.map(
            lambda g: zero.local_chunk(g, axis_size, data_axis), grads
        )
    with jax.named_scope("ddlpc/update/chunk"):
        param_shards = jax.tree.map(
            lambda p: zero.local_chunk(p, axis_size, data_axis), params
        )
    new_param_shards, new_opt = _fenced_update(
        tx, grad_shards, opt_state, param_shards
    )
    new_params = _gather_params(new_param_shards, params, data_axis)
    return new_params, new_opt, grad_norm


def _zero_state_specs(
    state: TrainState,
    tx: optax.GradientTransformation,
    data_axis: str,
    level: str,
) -> TrainState:
    """shard_map partition specs for the chunked run layouts: stats/step
    replicated, chunked opt-state moments split over ``data_axis``;
    params replicated for zero1/zero2 and chunked (``P(data)`` on the
    ``[N, K]`` view) for zero3.  Built at trace time from the state's
    avals via the partition-rule tables (shard_update.py) — for zero3
    the state's params are already chunk-shaped, which the name-matched
    rules place identically (the opt template derived from chunked
    params has the same treedef and moment names)."""
    opt_specs = zero.opt_partition_specs(tx, state.params, level, data_axis)
    param_spec = P(data_axis) if level == "zero3" else P()
    return state.replace(
        step=P(),
        params=jax.tree.map(lambda _: param_spec, state.params),
        batch_stats=jax.tree.map(lambda _: P(), state.batch_stats),
        opt_state=opt_specs,
    )


def make_train_step(
    model: nn.Module,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    compression: CompressionConfig,
    data_axis: str = "data",
    donate_state: bool = True,
    remat: bool = False,
    seed: int = 0,
    shard_update: bool = False,
    param_avals: Optional[PyTree] = None,
) -> Callable[[TrainState, jax.Array, jax.Array], Tuple[TrainState, dict]]:
    """Build the jitted SPMD train step.

    Inputs per call:
      images [A, B, H, W, C], labels [A, B, H, W] — A = sync_period
    (micro-batches accumulated between optimizer steps, reference
    ``frequency_sending_gradients`` кластер.py:685), B = *global* micro-batch,
    sharded over the data axis.
    Returns (new_state, metrics) with metrics averaged over A and the mesh.

    ``shard_update`` selects the ZeRO level of the weight update
    (shard_update.py, docs/SHARDING.md; the historical bool maps
    ``True`` → ``'zero2'``, the program this repo has always called the
    sharded update):

    - ``'zero1'``: full-mean all-reduce, then each replica updates its
      1/N chunk of params + moments and all-gathers the params.
    - ``'zero2'``: the gradient sync IS a reduce-scatter; the update
      runs on the shards; one all-gather publishes the params.
    - ``'zero3'``: params also persist as ``[N, K]`` chunks; the step
      starts by all-gathering them per leaf for the forward/backward
      (they are step temporaries, freed after use) and the fresh chunks
      are NOT gathered at step end.  Requires ``param_avals`` — the
      canonical parameter shapes the chunks restore to.

    The state must be in the matching run layout
    (``shard_update.StateLayout``).  zero2 and zero3 are bit-identical
    to the replicated update for every supported codec mode
    (test-pinned); zero1 matches to within FMA-contraction ulps — a
    declared, test-pinned deviation (see ``_apply_update_zero1``).  On a
    singleton data mesh all levels fall back to the replicated program
    (sharding into one shard IS replication).

    Precondition on ``tx`` (uncheckable — optax chains are opaque): no
    stage may couple elements across the tree, e.g. ``clip_by_global_norm``
    — under every chunked level each replica's ``tx.update`` sees only its
    1/N chunk, so a global-norm clip would use the shard's partial norm
    (wrong threshold, replica-divergent params).  The config path enforces
    this via ``resolve_shard_update(grad_clip_norm=...)``; direct callers
    own it.
    """
    for name, size in mesh.shape.items():
        if name != data_axis and size > 1:
            raise ValueError(
                f"mesh axis {name!r} (size {size}) is not consumed by the "
                f"shard_map train step — use make_train_step_gspmd for "
                f"data×space meshes (the Trainer selects it automatically)"
            )
    axis_size = mesh.shape[data_axis]
    level = zero.normalize_shard_update(shard_update)
    if axis_size <= 1:
        level = "off"
    if level in ("zero2", "zero3"):
        from ddlpc_tpu.parallel.grad_sync import validate_scatter_compression

        validate_scatter_compression(compression)
    if level == "zero3" and param_avals is None:
        raise ValueError(
            "make_train_step(shard_update='zero3') requires param_avals — "
            "the canonical parameter shapes the chunked leaves restore to "
            "(StateLayout.param_avals)"
        )

    def shard_body(state: TrainState, images: jax.Array, labels: jax.Array):
        # Inside shard_map: images [A, B_local, H, W, C].
        if level == "zero3":
            # Gather-on-demand: the persisted params are this replica's
            # [1, K] chunks; all-gather each leaf back to its canonical
            # shape for the forward/backward.  The gathered tree is a
            # step temporary — XLA frees it after the backward — so the
            # full model never persists in HBM between steps.
            full_params = _gather_params(state.params, param_avals, data_axis)
            fwd_state = state.replace(params=full_params)
        else:
            fwd_state = state
        grads, batch_stats, losses, accs, counters = _accumulate_grads(
            model, fwd_state, images, labels, remat=remat
        )
        # Keep BatchNorm running stats replica-identical at every sync point:
        # with per-batch sync-BN (norm_axis_name set) this pmean is a no-op;
        # without it, it averages the per-replica running stats — either way
        # the returned state is genuinely replicated, unlike the reference,
        # which never re-syncs BN stats after init (SURVEY §3.1).
        with jax.named_scope("ddlpc/grad_sync/stats"):
            batch_stats = jax.tree.map(
                lambda x: lax.pmean(x, data_axis), batch_stats
            )
        # The one (logical) collective of the step — replaces reference
        # L0–L4.  Sharded: reduce-scatter + all-gather, the same wire bytes
        # split around a 1/N-sized update.
        rng = _rounding_rng(compression, seed, state.step)
        if level == "zero2":
            params, opt_state, grad_norm = _apply_update_sharded(
                tx, state.params, state.opt_state, grads,
                data_axis, axis_size, compression, rng,
            )
        elif level == "zero1":
            params, opt_state, grad_norm = _apply_update_zero1(
                tx, state.params, state.opt_state, grads,
                data_axis, axis_size, compression, rng,
            )
        elif level == "zero3":
            # Same wire as zero2's scatter, but the fresh param chunks
            # are the NEW persisted state — no publish all-gather; the
            # next step's gather-on-demand replaces it.
            grad_shards = sync_gradients_scatter(
                grads, data_axis, compression, axis_size=axis_size, key=rng
            )
            params, opt_state = _fenced_update(
                tx, grad_shards, state.opt_state, state.params
            )
            grad_norm = _psum_sq_norm(grad_shards, data_axis)
        else:
            grads = sync_gradients(
                grads, data_axis, compression, axis_size=axis_size, key=rng
            )
            params, opt_state = _fenced_update(
                tx, grads, state.opt_state, state.params
            )
            grad_norm = _global_norm(grads)
        with jax.named_scope("ddlpc/grad_sync/stats"):
            metrics = {
                "loss": lax.pmean(losses.mean(), data_axis),
                "pixel_acc": lax.pmean(accs.mean(), data_axis),
                "grad_norm": grad_norm,
                **_reduce_counters(counters, data_axis),
            }
        new_state = TrainState(
            step=state.step + 1,
            params=params,
            batch_stats=batch_stats,
            opt_state=opt_state,
        )
        return new_state, metrics

    donate = (0,) if donate_state else ()
    if level == "off":
        sharded = jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(P(), P(None, data_axis), P(None, data_axis)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=donate)

    def stepper(state: TrainState, images: jax.Array, labels: jax.Array):
        # Specs depend on the state's (chunked) structure — build them at
        # trace time from the avals; shard_map composes under jit.
        specs = _zero_state_specs(state, tx, data_axis, level)
        sharded = jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(specs, P(None, data_axis), P(None, data_axis)),
            out_specs=(specs, P()),
            check_vma=False,
        )
        return sharded(state, images, labels)

    return jax.jit(stepper, donate_argnums=donate)


def make_train_step_gspmd(
    model: nn.Module,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    compression: CompressionConfig,
    data_axis: str = "data",
    space_axis: Optional[str] = "space",
    donate_state: bool = True,
    remat: bool = False,
    seed: int = 0,
    shard_update: bool = False,
) -> Callable[[TrainState, jax.Array, jax.Array], Tuple[TrainState, dict]]:
    """GSPMD train step: batch sharded over ``data`` AND H over ``space``.
    ``shard_update`` takes the same levels as :func:`make_train_step`
    (bool ``True`` → ``'zero2'``), expressed the GSPMD way — sharding
    constraints instead of hand-written collectives; the Trainer's
    ``StateLayout`` modes ``gspmd``/``gspmd_zero2``/``gspmd_zero3`` are
    the matching placements.

    Where the shard_map path writes the collectives by hand, here the
    program is expressed over *global* arrays and XLA's SPMD partitioner
    inserts everything: the gradient all-reduce over ``data``, and — the
    point of this path — per-conv halo exchanges over ``space`` for
    H-sharded tiles (see parallel/halo.py for the hand-written equivalent).
    This is how the framework trains tiles too large for one chip's HBM,
    the spatial analog of sequence/context parallelism.

    Differences vs the shard_map path, by construction:
    - BatchNorm must be built WITHOUT ``norm_axis_name``: batch statistics
      are computed over the logical global batch, which the partitioner
      turns into exact cross-replica sync-BN on its own.
    - The codec's ``quantize_local`` stage (per-replica quantization before
      the reduce, кластер.py:450-496) has no meaning here — there is no
      per-replica gradient in the program; only ``quantize_mean``
      (кластер.py:328-396) applies.  The shard_map path remains the
      reference-parity codec path.

    Levels, the GSPMD spelling (the mechanism of arxiv 2004.13336 — the
    XLA partitioner materializes the collectives around the elementwise
    update on its own):

    - ``'zero1'``: optimizer moments stay parameter-shaped but are
      *partitioned* over ``data_axis`` (``partition.even_shard_spec``
      picks the dimension), pinned by sharding constraints on both the
      incoming state (Trainer placement) and the step's output.
    - ``'zero2'``: additionally pins the post-codec mean gradient to the
      same rule-derived shardings, so the partitioner is told the
      optimizer-boundary gradient is sharded (it emits a reduce-scatter
      into the update rather than keeping a replicated mean alive).
    - ``'zero3'``: params persist partitioned at the state boundary too
      (rule-engine specs; uneven leaves stay replicated-by-rule) — the
      partitioner gathers them per consuming op in the forward/backward,
      the true gather-on-demand form.

    The codec still sees the full *logical* mean gradient inside the
    partitioned program, so no codec mode is restricted on this path.
    """

    if compression.mode != "none" and not compression.quantize_mean:
        raise ValueError(
            "the GSPMD step cannot represent quantize_local-only compression "
            "(there is no per-replica gradient in the program): set "
            "compression.quantize_mean=True, or mode='none', or use a pure "
            "data mesh for reference-parity codec semantics"
        )
    if compression.transport == "ring" and compression.mode != "none":
        raise ValueError(
            "transport='ring' requires explicit per-replica collectives — "
            "use the shard_map step (pure data mesh); the GSPMD partitioner "
            "owns the collectives in this path"
        )
    if compression.mode != "none" and compression.quantize_local:
        # Refuse rather than silently drop a configured loss point: a config
        # recording quantize_local=True would claim codec semantics the
        # executed program does not have.  The config artifact must match
        # what runs.
        raise ValueError(
            "the GSPMD step cannot apply quantize_local (no per-replica "
            "gradient exists in the program — only the averaged gradient is "
            "representable): set compression.quantize_local=False to record "
            "the semantics that actually execute, or use a pure data mesh "
            "(shard_map step) for reference-parity two-point codec semantics"
        )

    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P(None, data_axis, space_axis))
    n_data = mesh.shape[data_axis]
    level = zero.normalize_shard_update(shard_update)
    if n_data <= 1:
        level = "off"
    layout = zero.GSPMD_LAYOUT_FOR_LEVEL.get(level)

    def _constrain_by_decisions(tree: PyTree, decisions: PyTree) -> PyTree:
        """Pin each rule-sharded leaf to its decision's sharding; leaves
        the rules keep replicated get no constraint (the partitioner may
        place them freely — the state boundary pins what persists)."""
        return jax.tree.map(
            lambda l, d: (
                lax.with_sharding_constraint(l, NamedSharding(mesh, d.spec))
                if d.sharded
                else l
            ),
            tree,
            decisions,
        )

    def step_fn(state: TrainState, images: jax.Array, labels: jax.Array):
        grads, batch_stats, losses, accs, counters = _accumulate_grads(
            model, state, images, labels, remat=remat
        )
        if compression.mode != "none":
            from ddlpc_tpu.parallel.grad_sync import (
                apply_codec_fenced_bucketed,
                resolve_codec_backend,
            )

            rng = _rounding_rng(compression, seed, state.step)
            # Bucketed spelling so the GSPMD codec loss (per-bucket scales
            # and keys) matches the shard_map layouts bucket-for-bucket;
            # bucket_mb=0 degenerates to the single fenced whole-tree stage.
            # The partitioner owns the collectives here; the codec is the
            # part of the gradient sync this program spells out.
            with jax.named_scope("ddlpc/grad_sync"):
                grads = apply_codec_fenced_bucketed(
                    resolve_codec_backend(compression),
                    grads,
                    compression,
                    key=rng,
                )
        if level in ("zero2", "zero3"):
            # ZeRO-2 the GSPMD way: pin the post-codec mean gradient to the
            # rule-derived shardings, telling the partitioner the
            # optimizer-boundary gradient is sharded — it materializes a
            # reduce-scatter into the update instead of keeping a
            # replicated mean alive between codec and update.  Values are
            # untouched (placement only); the codec above already ran on
            # the full logical mean, so bit-identity with the other
            # layouts is unchanged.
            grads = _constrain_by_decisions(
                grads,
                zero.param_decisions(
                    grads, layout, n_data, data_axis, prefix="grads"
                ),
            )
        params, opt_state = _fenced_update(
            tx, grads, state.opt_state, state.params
        )
        if level != "off":
            # With the output state's shardings unconstrained at the jit
            # boundary, pin them here: stats replicated, params replicated
            # (zero1/zero2 — the next forward and eval/predict need them
            # whole) or rule-sharded (zero3 — they persist partitioned and
            # the partitioner gathers per consuming op next step), fresh
            # moments in the ZeRO layout so the partitioner keeps them
            # sharded across steps (and therefore shards the elementwise
            # update math that produces them) instead of replicating the
            # output.
            batch_stats = lax.with_sharding_constraint(batch_stats, repl)
            if level == "zero3":
                params = _constrain_by_decisions(
                    params,
                    zero.param_decisions(params, layout, n_data, data_axis),
                )
            else:
                params = lax.with_sharding_constraint(params, repl)
            opt_state = _constrain_by_decisions(
                opt_state,
                zero.opt_decisions(tx, state.params, layout, n_data, data_axis),
            )
        metrics = {
            "loss": losses.mean(),
            "pixel_acc": accs.mean(),
            "grad_norm": _global_norm(grads),
            **_reduce_counters(counters),
        }
        new_state = TrainState(
            step=state.step + 1,
            params=params,
            batch_stats=batch_stats,
            opt_state=opt_state,
        )
        return new_state, metrics

    if level == "off":
        return jax.jit(
            step_fn,
            in_shardings=(repl, batch_sh, batch_sh),
            out_shardings=(repl, repl),
            donate_argnums=(0,) if donate_state else (),
        )

    # Sharded state: the state's sharding tree mixes replicated and
    # P(data)-partitioned leaves, and its structure is unknown until the
    # first state arrives — build the jit lazily from that state's tree,
    # with EXPLICIT and identical in/out shardings.  (Leaving the state
    # boundary unspecified makes jit infer the donation aliasing across
    # mismatched layouts, which XLA rejects at dispatch: "aliased input
    # and output to have the same size".)
    cache: dict = {}

    def build(state: TrainState):
        """The inner jit for a state of this tree (avals suffice — the
        program auditor lowers it on ShapeDtypeStructs without running;
        ``stepper`` caches it for the real training loop)."""
        opt_sh = zero.opt_shardings(
            tx, state.params, layout, mesh, data_axis
        )
        if level == "zero3":
            param_sh = jax.tree.map(
                lambda d: NamedSharding(mesh, d.spec) if d.sharded else repl,
                zero.param_decisions(state.params, layout, n_data, data_axis),
            )
        else:
            param_sh = jax.tree.map(lambda _: repl, state.params)
        state_sh = state.replace(
            step=repl,
            params=param_sh,
            batch_stats=jax.tree.map(lambda _: repl, state.batch_stats),
            opt_state=opt_sh,
        )
        return jax.jit(
            step_fn,
            in_shardings=(state_sh, batch_sh, batch_sh),
            out_shardings=(state_sh, repl),
            donate_argnums=(0,) if donate_state else (),
        )

    def stepper(state: TrainState, images: jax.Array, labels: jax.Array):
        fn = cache.get("fn")
        if fn is None:
            fn = cache["fn"] = build(state)
        return fn(state, images, labels)

    stepper.build_for = build
    return stepper


def make_update_step(
    tx: optax.GradientTransformation,
    mesh: Mesh,
    compression: CompressionConfig,
    data_axis: str = "data",
    shard_update: bool = False,
    seed: int = 0,
) -> Callable[[PyTree, PyTree, PyTree], Tuple[PyTree, PyTree]]:
    """Update-ONLY SPMD program: (params, opt_state, grads) → (params,
    opt_state) — the gradient sync + optimizer step with no forward/
    backward, for benchmarking the weight-update path in isolation
    (``bench.py --update-ab``, the ``update_ms_per_step`` contract line).
    ``grads`` is the per-replica accumulated gradient tree (replicated
    input); ``shard_update`` takes the same levels as
    :func:`make_train_step` (bool ``True`` → ``'zero2'``), and
    ``params``/``opt_state`` must be in the matching layout: chunked
    moments for every chunked level, chunked params too for ``'zero3'``
    (whose program is the zero2 wire with no publish all-gather — fresh
    chunks ARE the output, so this arm prices exactly the persisted-
    sharded-params update).  Stochastic rounding uses the shared key
    schedule pinned at step 0 (no step counter flows through this
    program): every call rounds with the same noise — right for timing
    the codec's real threefry cost, wrong for training, which the fused
    steps own.  Same ``tx`` precondition as ``make_train_step``: no
    cross-tree coupling (e.g. ``clip_by_global_norm``) when sharded.
    """
    axis_size = mesh.shape[data_axis]
    level = zero.normalize_shard_update(shard_update)
    if axis_size <= 1:
        level = "off"
    if level in ("zero2", "zero3"):
        from ddlpc_tpu.parallel.grad_sync import validate_scatter_compression

        validate_scatter_compression(compression)

    def body(params: PyTree, opt_state: PyTree, grads: PyTree):
        rng = _rounding_rng(compression, seed, 0)
        if level == "zero2":
            params, opt_state, _ = _apply_update_sharded(
                tx, params, opt_state, grads,
                data_axis, axis_size, compression, rng,
            )
        elif level == "zero1":
            params, opt_state, _ = _apply_update_zero1(
                tx, params, opt_state, grads,
                data_axis, axis_size, compression, rng,
            )
        elif level == "zero3":
            grad_shards = sync_gradients_scatter(
                grads, data_axis, compression, axis_size=axis_size, key=rng
            )
            params, opt_state = _fenced_update(
                tx, grad_shards, opt_state, params
            )
        else:
            grads = sync_gradients(
                grads, data_axis, compression, axis_size=axis_size, key=rng
            )
            params, opt_state = _fenced_update(tx, grads, opt_state, params)
        return params, opt_state

    def stepper(params: PyTree, opt_state: PyTree, grads: PyTree):
        if level == "off":
            opt_specs: PyTree = P()
            param_specs: PyTree = P()
        else:
            # Name-matched rules place the chunked-params-derived opt
            # template identically (same treedef, same moment names), so
            # zero3 needs no canonical param shapes here.
            opt_specs = zero.opt_partition_specs(tx, params, level, data_axis)
            param_specs = P(data_axis) if level == "zero3" else P()
        sharded = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(param_specs, opt_specs, P()),
            out_specs=(param_specs, opt_specs),
            check_vma=False,
        )
        return sharded(params, opt_state, grads)

    return jax.jit(stepper, donate_argnums=(0, 1))


def make_eval_step_gspmd(
    model: nn.Module,
    mesh: Mesh,
    num_classes: int,
    data_axis: str = "data",
    space_axis: Optional[str] = "space",
) -> Callable[[TrainState, jax.Array, jax.Array], dict]:
    """GSPMD eval: batch [B,H,W,C] sharded over (data, space)."""

    def eval_fn(state: TrainState, images: jax.Array, labels: jax.Array):
        logits = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            images,
            train=False,
        )
        cm = confusion_from_logits(logits, labels, num_classes)
        with jax.named_scope("ddlpc/loss"):
            nll_sum, count = softmax_cross_entropy_sum(
                logits, labels, ignore_index=-1
            )
        return {"confusion": cm, "loss_sum": nll_sum, "pixel_count": count}

    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P(data_axis, space_axis))
    return jax.jit(
        eval_fn,
        in_shardings=(repl, batch_sh, batch_sh),
        out_shardings=repl,
    )


def make_eval_step(
    model: nn.Module,
    mesh: Mesh,
    num_classes: int,
    data_axis: str = "data",
) -> Callable[[TrainState, jax.Array, jax.Array], dict]:
    """Jitted eval step: batch [B, H, W, C] sharded over data; returns summed
    confusion matrix [C, C] + mean loss (reference never evaluates held-out
    data, SURVEY §3.3 — this is the north-star mIoU path)."""

    def shard_body(state: TrainState, images: jax.Array, labels: jax.Array):
        logits = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            images,
            train=False,
        )
        cm = confusion_from_logits(logits, labels, num_classes)
        # -1 marks batch-padding pixels from the eval loader (data/loader.py).
        # Return summed NLL and valid-pixel count, not a mean: the caller
        # accumulates both across shards AND batches and divides once, so
        # padded shards/tail batches get exactly their valid-pixel weight.
        with jax.named_scope("ddlpc/loss"):
            nll_sum, count = softmax_cross_entropy_sum(
                logits, labels, ignore_index=-1
            )
        return {
            "confusion": lax.psum(cm, data_axis),
            "loss_sum": lax.psum(nll_sum, data_axis),
            "pixel_count": lax.psum(count, data_axis),
        }

    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(), P(data_axis), P(data_axis)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sharded)


def make_predict_fn(
    model: nn.Module,
) -> Callable[[TrainState, jax.Array], jax.Array]:
    """Single-device jitted inference: images [N,H,W,C] → class map [N,H,W]."""

    @jax.jit
    def predict(state: TrainState, images: jax.Array) -> jax.Array:
        logits = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            images,
            train=False,
        )
        return jnp.argmax(logits, axis=-1)

    return predict


def make_logits_fn(
    model: nn.Module,
) -> Callable[[TrainState, jax.Array], jax.Array]:
    """Single-device jitted inference returning raw logits [N,H,W,C] —
    the building block for sliding-window full-scene prediction, where
    overlapping windows blend *logits* (argmaxing per window first would
    make the overlap vote instead of average)."""

    @jax.jit
    def logits_fn(state: TrainState, images: jax.Array) -> jax.Array:
        return model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            images,
            train=False,
        )

    return logits_fn
