"""MPMD pipeline parallelism: staged encoder–decoder execution along a
third ``pipe`` mesh axis (docs/SHARDING.md "Pipeline stages").

The last parallelism family in PAPERS.md with no answer here (arxiv
2412.14374 "Scaling Deep Learning Training with MPMD Pipeline
Parallelism"; arxiv 2204.06514 for pjit-era pod meshes): cut the model
into ``S`` contiguous stages, give each stage its own (data, space)
sub-mesh (``parallel/mesh.py:stage_meshes``), and drive them with a
GPipe-style microbatch round-robin — the reference's 50-microbatch
gradient-accumulation loop (кластер.py:750-759) is exactly the microbatch
stream a pipeline schedule feeds on.

Decomposition of ``train_step.py``'s monolithic builders, piece by piece:

- **stage assignment** is a declarative regex rule table
  (``parallel/partition.py:StageRule``, the ZeRO-table pattern one level
  up): one anchored rule per model block, generated from a balanced
  contiguous partition of per-block parameter bytes
  (``balanced_stage_assignment``), first match wins, an uncovered leaf
  raises.
- **forward/backward segments** are per-stage ``shard_map`` programs over
  the stage sub-mesh.  A non-final segment runs its block slice over the
  inter-stage activation carry (``models/unet.py`` staged ``__call__``);
  its backward *recomputes* the segment forward inside ``jax.vjp``
  (stage-granular remat — only the stage's input carry is stashed, never
  its interior activations).  Segments contain **no collectives**: the
  carry crosses the stage boundary in the model compute dtype (no
  widening), and all gradient traffic belongs to the stage update.
- **per-stage gradient sync + update** reuses the exact wire and fenced
  update of ``make_update_step``: gradients accumulate per replica
  (stacked ``[N_data, ...]`` so ``quantize_local`` keeps reference
  per-replica semantics across the program boundary), and the stage
  update runs the bucketed/fenced quantized collective + the ZeRO
  off/zero1/zero2 ladder **within the stage group**.  zero3's
  gather-on-demand is refused loudly (stage residency already divides
  params by S; composing the per-leaf gather with staged segments is a
  follow-on, see ROADMAP).
- **schedule**: GPipe two-phase round-robin.  Forward cycles ``t`` run
  stage ``s`` on microbatch ``t - s``; backward mirrors it.  Dispatch is
  asynchronous and the stages live on disjoint devices, so cycles
  genuinely overlap; the fill/drain bubble is ``(S-1)/(M+S-1)`` per
  phase (:func:`bubble_fraction`), measured — not guessed — by
  ``bench.py --pipeline-ab``.  1F1B is a follow-on knob: it reorders
  this host loop, nothing below changes.

``pipeline_stages=1`` **delegates** to the unstaged
``make_train_step`` — bit-identical by construction (same fenced update,
same wire bytes), and pinned numerically in tests/test_pipeline.py so
the refactor cannot drift the existing program baseline.

Tier: ``jax`` (analysis/tiers.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddlpc_tpu.config import CompressionConfig
from ddlpc_tpu.parallel import partition
from ddlpc_tpu.parallel import shard_update as zero
from ddlpc_tpu.parallel.grad_sync import (
    sync_gradients,
    validate_scatter_compression,
)
from ddlpc_tpu.parallel.mesh import stage_meshes
from ddlpc_tpu.parallel.train_step import (
    TrainState,
    _apply_update_sharded,
    _apply_update_zero1,
    _fenced_update,
    _rounding_rng,
    loss_from_logits,
    make_train_step,
)

PyTree = Any


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe fill/drain bubble per phase: (S-1)/(M+S-1).  The model the
    measured column of ``bench.py --pipeline-ab`` is compared against."""
    s, m = int(n_stages), int(n_microbatches)
    if s < 1 or m < 1:
        raise ValueError(f"need S >= 1 and M >= 1, got S={s} M={m}")
    return (s - 1) / (m + s - 1)


def _subtree(params: PyTree, path: str):
    """Walk a "/"-joined module path into a nested param dict; None when
    absent (e.g. ``UpBlock_i/ConvTranspose_0`` under bilinear upsampling,
    a legitimately parameterless cut point)."""
    node = params
    for seg in path.split("/"):
        if not hasattr(node, "get"):
            return None
        node = node.get(seg)
        if node is None:
            return None
    return node


def _tree_bytes(tree: PyTree) -> int:
    total = 0
    for leaf in jax.tree.leaves(tree):
        n = 1
        for d in leaf.shape:
            n *= int(d)
        total += n * jnp.dtype(leaf.dtype).itemsize
    return total


# ---------------------------------------------------------------------------
# stage plan


@dataclasses.dataclass(frozen=True)
class StagePlan:
    """The resolved cut: which blocks (and which param-tree modules) each
    stage owns, plus the rule table every tree split reads."""

    block_names: Tuple[str, ...]  # model execution order, incl. 'head'
    assignment: Tuple[int, ...]  # per-block stage index, non-decreasing
    rules: Tuple[partition.StageRule, ...]  # over param-tree module names
    n_stages: int

    def stage_blocks(self, s: int) -> Tuple[str, ...]:
        return tuple(
            b for b, a in zip(self.block_names, self.assignment) if a == s
        )

    def split(self, tree: PyTree, prefix: str = "") -> List[PyTree]:
        return partition.split_tree_by_stage(
            self.rules, tree, self.n_stages, prefix
        )

    @staticmethod
    def merge(stage_trees: Sequence[PyTree]) -> PyTree:
        return partition.merge_stage_trees(stage_trees)


def build_stage_plan(model: nn.Module, params: PyTree, n_stages: int) -> StagePlan:
    """Cut the model's block list into ``n_stages`` contiguous groups by
    balanced per-block parameter bytes — the cut that minimizes the max
    per-stage share, i.e. maximizes the per-device HBM win the pipe axis
    exists for (obs/hbm.py prices it; the U-Net decoder is heavier than
    the encoder, so a naive halfway cut would not reach ~1/S)."""
    if not hasattr(model, "pipeline_block_names"):
        raise ValueError(
            f"{type(model).__name__} does not declare pipeline blocks "
            f"(pipeline_block_names/pipeline_block_modules) — staged "
            f"execution currently covers the U-Net family; see ROADMAP"
        )
    blocks = tuple(model.pipeline_block_names())
    modules = model.pipeline_block_modules()
    block_bytes = []
    for b in blocks:
        total = 0
        for m in modules[b]:
            sub = _subtree(params, m)
            if sub is not None:
                total += _tree_bytes(sub)
        block_bytes.append(total)
    assignment = partition.balanced_stage_assignment(block_bytes, n_stages)
    # The rule table speaks param-tree module names, not block names —
    # 'head' fans out to Conv_0 (+ detail heads).
    mod_names: List[str] = []
    mod_stage: List[int] = []
    for b, a in zip(blocks, assignment):
        for m in modules[b]:
            mod_names.append(m)
            mod_stage.append(a)
    rules = partition.stage_rules_for_blocks(mod_names, mod_stage)
    return StagePlan(blocks, tuple(assignment), rules, n_stages)


def stage_param_bytes(plan: StagePlan, params: PyTree) -> List[int]:
    """Per-stage parameter bytes under the plan — the numerator of the
    ``pipe=S`` HBM claim (params, grads and Adam moments all scale with
    it: 16·P_s bytes/device at fp32 off-layout vs 16·P unstaged)."""
    return [_tree_bytes(t) for t in plan.split(params)]


# ---------------------------------------------------------------------------
# opt-state split/merge (template + named-path fill)


def _named_map(tree: PyTree) -> Dict[str, Any]:
    return dict(partition.named_leaves(tree))


def split_opt_state(
    tx: optax.GradientTransformation,
    full_opt: PyTree,
    stage_params: Sequence[PyTree],
) -> List[PyTree]:
    """Split a canonical opt_state into per-stage opt_states: build each
    stage's template with ``tx.init(stage_params)`` (same optax chain →
    same outer structure, param-subtree inner structure) and fill every
    template leaf from the identically-named leaf of the full opt_state.
    Scalars (``count`` etc.) replicate into every stage — they advance in
    lockstep, so the merge takes stage 0's copy back."""
    full = _named_map(full_opt)
    outs: List[PyTree] = []
    for ps in stage_params:
        template = jax.eval_shape(tx.init, ps)

        def fill(path, leaf):
            name = partition.leaf_name("", path)
            if name not in full:
                raise ValueError(
                    f"opt_state leaf {name!r} of a stage template has no "
                    f"counterpart in the full opt_state — tx must not "
                    f"couple state across the param tree"
                )
            got = full[name]
            if tuple(got.shape) != tuple(leaf.shape):
                raise ValueError(
                    f"opt_state leaf {name!r}: stage template shape "
                    f"{tuple(leaf.shape)} != full shape {tuple(got.shape)}"
                )
            return got

        outs.append(jax.tree_util.tree_map_with_path(fill, template))
    return outs


def merge_opt_state(
    tx: optax.GradientTransformation,
    full_params: PyTree,
    stage_opts: Sequence[PyTree],
) -> PyTree:
    """Inverse of :func:`split_opt_state`: fill the canonical
    ``tx.init(full_params)`` template from the stage opt_states (first
    stage that has the named leaf wins — scalars are lockstep-identical
    replicas; moment leaves exist in exactly one stage)."""
    maps = [_named_map(o) for o in stage_opts]
    template = jax.eval_shape(tx.init, full_params)

    def fill(path, leaf):
        name = partition.leaf_name("", path)
        for m in maps:
            if name in m:
                return m[name]
        raise ValueError(
            f"opt_state leaf {name!r} of the canonical template exists in "
            f"no stage opt_state — the stage plans disagree with tx"
        )

    return jax.tree_util.tree_map_with_path(fill, template)


# ---------------------------------------------------------------------------
# pipeline state


@dataclasses.dataclass
class PipelineState:
    """Per-stage :class:`TrainState` list, each resident on its stage
    sub-mesh (ZeRO-placed within the stage group when the level says so).
    NOT a pytree — stages live on disjoint device groups; host code moves
    between this and the canonical gathered :class:`TrainState` via the
    driver's ``init_state``/``canonical``."""

    stages: List[TrainState]

    @property
    def step(self):
        return self.stages[0].step


# ---------------------------------------------------------------------------
# the driver


class PipelineTrainStep:
    """Host-driven MPMD pipeline train step.

    ``init_state(full_state)`` splits + places a canonical TrainState;
    ``step(pstate, images, labels)`` runs one optimizer step over
    ``images [M, B, H, W, C]`` / ``labels [M, B, H, W]`` (M microbatches,
    B = global microbatch) and returns ``(pstate, metrics)`` with float
    metrics; ``canonical(pstate)`` gathers back to the layout checkpoints
    store — so a ``pipe=S, zeroN`` run round-trips into any other layout
    exactly like the ZeRO rungs do (tests/test_shard_update.py matrix).

    After every ``step`` the driver leaves ``last_schedule`` behind:
    executed vs idle (stage × cycle) slots of the round-robin it just
    ran, and their ratio as the MEASURED bubble fraction —
    ``bench.py --pipeline-ab`` tables it against the closed form.
    """

    def __init__(
        self,
        model: nn.Module,
        tx: optax.GradientTransformation,
        mesh: Mesh,
        compression: CompressionConfig,
        n_microbatches: int,
        data_axis: str = "data",
        space_axis: str = "space",
        pipe_axis: str = "pipe",
        shard_update: str = "off",
        seed: int = 0,
    ):
        self.model, self.tx, self.compression = model, tx, compression
        self.data_axis, self.seed = data_axis, seed
        self.n_stages = int(mesh.shape.get(pipe_axis, 1))
        self.n_microbatches = max(int(n_microbatches), 1)
        level = zero.normalize_shard_update(shard_update)
        if self.n_stages <= 1:
            # Degenerate pipe=1: the unstaged builder IS the program —
            # same fenced update, same wire bytes, bit-identical
            # (pinned in tests/test_pipeline.py).
            self._level = level
            self._mesh = mesh
            self._delegate_build = lambda layout: make_train_step(
                model, tx, mesh, compression, data_axis=data_axis,
                seed=seed, shard_update=level,
                param_avals=layout.param_avals,
            )
            return
        if space_axis in mesh.shape and mesh.shape[space_axis] > 1:
            raise ValueError(
                "pipeline stages × space sharding of the full model is not "
                "wired yet: segment shard_map programs do not emit the "
                "per-conv halo exchanges the GSPMD path gets for free "
                "(parallel/halo.py composes with staged execution at the "
                "carry level — tests/test_pipeline.py — full-model wiring "
                "is a ROADMAP follow-on)"
            )
        if level == "zero3":
            raise ValueError(
                "shard_update='zero3' does not compose with pipeline "
                "stages yet: stage residency already divides params by S; "
                "per-leaf gather-on-demand inside staged segments is a "
                "ROADMAP follow-on (use off/zero1/zero2 within stages)"
            )
        if level in ("zero2",):
            validate_scatter_compression(compression)
        self._level = level
        self._meshes = stage_meshes(mesh, pipe_axis)
        if len(self._meshes) != self.n_stages:
            raise AssertionError("stage_meshes disagrees with pipe axis")
        self._n_data = self._meshes[0].shape[data_axis]
        self.plan: Optional[StagePlan] = None  # built on first init_state
        self._built = False

    # -- canonical <-> placed ------------------------------------------------

    def init_state(self, full_state: TrainState) -> PipelineState:
        if self.n_stages <= 1:
            layout = self._layout_for(full_state)
            self._mono = self._delegate_build(layout)
            self._mono_layout = layout
            return PipelineState([layout.place(full_state)])
        if self.plan is None:
            self.plan = build_stage_plan(
                self.model, full_state.params, self.n_stages
            )
        p_split = self.plan.split(full_state.params)
        s_split = self.plan.split(full_state.batch_stats)
        o_split = split_opt_state(self.tx, full_state.opt_state, p_split)
        stages: List[TrainState] = []
        self._layouts: List[Optional[zero.StateLayout]] = []
        for s in range(self.n_stages):
            st = TrainState(
                step=full_state.step,
                params=p_split[s],
                batch_stats=s_split[s],
                opt_state=o_split[s],
            )
            st = jax.device_get(st)  # host detour: source may be any mesh
            if self._level == "off" or self._n_data <= 1:
                repl = NamedSharding(self._meshes[s], P())
                st = jax.tree.map(lambda x: jax.device_put(x, repl), st)
                self._layouts.append(None)
            else:
                layout = zero.StateLayout(
                    self._level, self.tx, st, self._meshes[s], self.data_axis
                )
                st = layout.place(st)
                self._layouts.append(layout)
            stages.append(st)
        self._p_split, self._s_split = p_split, s_split
        if not self._built:
            self._build_programs(p_split)
            self._built = True
        return PipelineState(stages)

    def carry_avals(self, image_shape, image_dtype=jnp.float32) -> List[PyTree]:
        """Abstract inter-stage carry avals for one microbatch, per stage
        boundary (S-1 entries) — what one activation send moves, and what
        the GPipe input stash holds M of
        (``obs.hbm.pipeline_carry_stash_bytes`` prices it).  Requires
        ``init_state`` to have run (the stage plan fixes the cut)."""
        if self.n_stages <= 1:
            return []
        if self.plan is None:
            raise ValueError("carry_avals needs init_state first (no plan)")
        out: List[PyTree] = []
        cin: Any = jax.ShapeDtypeStruct(tuple(image_shape), image_dtype)
        for s in range(self.n_stages - 1):
            # Through the real stage program (not a bare apply): sync-BN
            # pmeans over the data axis, which only exists inside the
            # stage shard_map.
            cin, _ = jax.eval_shape(
                self._fwd[s], self._p_split[s], self._s_split[s], cin
            )
            out.append(cin)
        return out

    def canonical(self, pstate: PipelineState) -> TrainState:
        if self.n_stages <= 1:
            return self._mono_layout.canonical(pstate.stages[0])
        gathered = []
        for st, layout in zip(pstate.stages, self._layouts):
            gathered.append(
                jax.device_get(layout.canonical(st) if layout else st)
            )
        params = StagePlan.merge([g.params for g in gathered])
        stats = StagePlan.merge([g.batch_stats for g in gathered])
        opt = merge_opt_state(self.tx, params, [g.opt_state for g in gathered])
        return TrainState(
            step=gathered[0].step, params=params,
            batch_stats=stats, opt_state=opt,
        )

    def _layout_for(self, full_state: TrainState) -> zero.StateLayout:
        mode = "replicated" if self._level == "off" else self._level
        return zero.StateLayout(
            mode, self.tx, full_state, self._mesh, self.data_axis
        )

    # -- per-stage compiled programs ----------------------------------------

    def _build_programs(self, p_split) -> None:
        S, model, comp = self.n_stages, self.model, self.compression
        data_axis, N, M = self.data_axis, self._n_data, self.n_microbatches
        self._fwd: List[Callable] = []
        self._bwd: List[Callable] = []
        self._upd: List[Callable] = []
        self._gacc_init: List[Callable] = []

        def apply_blocks(params, stats, x, carry, blocks):
            out, updates = model.apply(
                {"params": params, "batch_stats": stats},
                x, train=True, mutable=["batch_stats"],
                blocks=blocks, carry=carry,
            )
            return out, updates["batch_stats"]

        for s in range(S):
            mesh_s = self._meshes[s]
            blocks = self.plan.stage_blocks(s)
            first, last = s == 0, s == S - 1

            def make_fwd(blocks=blocks, first=first, mesh_s=mesh_s):
                def body(params, stats, cin):
                    x = cin if first else cin["x"]
                    carry = None if first else cin
                    out, new_stats = apply_blocks(params, stats, x, carry, blocks)
                    return out, new_stats

                return jax.jit(jax.shard_map(
                    body, mesh=mesh_s,
                    in_specs=(P(), P(), P(data_axis)),
                    out_specs=(P(data_axis), P()),
                    check_vma=False,
                ))

            def make_bwd(blocks=blocks, first=first, mesh_s=mesh_s):
                # Stage-granular remat: re-run the segment forward inside
                # vjp with the STASHED input stats (the stats this
                # microbatch's forward consumed), discard the recomputed
                # stats, and pull (d_params, d_carry_in) through.  Stage 0
                # skips the carry cotangent (nothing upstream wants it).
                def body(params, stats, cin, dout, gacc):
                    x = cin if first else cin["x"]
                    carry = None if first else cin

                    def seg_p(p):
                        return apply_blocks(p, stats, x, carry, blocks)[0]

                    def seg_pc(p, c):
                        return apply_blocks(p, stats, c["x"], c, blocks)[0]

                    if first:
                        _, vjp_fn = jax.vjp(seg_p, params)
                        (gp,) = vjp_fn(dout)
                        dcin = jnp.zeros((), jnp.float32)  # unused stub
                    else:
                        _, vjp_fn = jax.vjp(seg_pc, params, cin)
                        gp, dcin = vjp_fn(dout)
                    gacc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32)[None], gacc, gp
                    )
                    return dcin, gacc

                dcin_spec = P() if first else P(data_axis)
                return jax.jit(
                    jax.shard_map(
                        body, mesh=mesh_s,
                        in_specs=(P(), P(), P(data_axis), P(data_axis),
                                  P(data_axis)),
                        out_specs=(dcin_spec, P(data_axis)),
                        check_vma=False,
                    ),
                    donate_argnums=(4,),
                )

            def make_loss_bwd(blocks=blocks, first=first, mesh_s=mesh_s):
                # The final stage's forward, loss and backward are ONE
                # program: loss math is loss_from_logits — byte-identical
                # to the monolithic builders' tail.  Per-replica loss/acc
                # leave stacked over the data axis (host averages equal
                # shards) so the segment stays collective-free.
                def body(params, stats, cin, labels, gacc):
                    x = cin if first else cin["x"]
                    carry = None if first else cin

                    def loss_fn(p, c):
                        xx = x if first else c["x"]
                        cc = None if first else c
                        logits, new_stats = apply_blocks(p, stats, xx, cc, blocks)
                        loss, acc = loss_from_logits(
                            model, logits, labels, train=True
                        )
                        return loss, (new_stats, acc)

                    if first:  # S==1 never lands here; guard anyway
                        (loss, (new_stats, acc)), gp = jax.value_and_grad(
                            lambda p: loss_fn(p, None), has_aux=True
                        )(params)
                        dcin = jnp.zeros((), jnp.float32)
                    else:
                        (loss, (new_stats, acc)), (gp, dcin) = (
                            jax.value_and_grad(
                                loss_fn, argnums=(0, 1), has_aux=True
                            )(params, carry)
                        )
                    gacc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32)[None], gacc, gp
                    )
                    return loss[None], acc[None], dcin, new_stats, gacc

                dcin_spec = P() if first else P(data_axis)
                return jax.jit(
                    jax.shard_map(
                        body, mesh=mesh_s,
                        in_specs=(P(), P(), P(data_axis), P(data_axis),
                                  P(data_axis)),
                        out_specs=(P(data_axis), P(data_axis), dcin_spec,
                                   P(), P(data_axis)),
                        check_vma=False,
                    ),
                    donate_argnums=(4,),
                )

            def make_upd(mesh_s=mesh_s, level=self._level):
                axis_size = mesh_s.shape[data_axis]
                lvl = "off" if axis_size <= 1 else level

                def body(params, opt_state, gacc, stats, step):
                    # gacc arrives as this replica's [1, ...] sum over M
                    # microbatch backward passes — squeeze + /M is the
                    # monolithic _accumulate_grads mean, then the EXACT
                    # make_update_step wire/update per ZeRO level.
                    grads = jax.tree.map(lambda a: a[0] / M, gacc)
                    rng = _rounding_rng(comp, self.seed, step)
                    if lvl == "zero2":
                        params, opt_state, norm = _apply_update_sharded(
                            self.tx, params, opt_state, grads,
                            data_axis, axis_size, comp, rng,
                        )
                        grad_sq = jnp.square(norm)
                    elif lvl == "zero1":
                        params, opt_state, norm = _apply_update_zero1(
                            self.tx, params, opt_state, grads,
                            data_axis, axis_size, comp, rng,
                        )
                        grad_sq = jnp.square(norm)
                    else:
                        grads = sync_gradients(
                            grads, data_axis, comp,
                            axis_size=axis_size, key=rng,
                        )
                        params, opt_state = _fenced_update(
                            self.tx, grads, opt_state, params
                        )
                        grad_sq = jnp.square(optax.global_norm(grads))
                    # End-of-step stats sync, the monolithic step's pmean.
                    stats = jax.tree.map(
                        lambda v: lax.pmean(v, data_axis), stats
                    )
                    return params, opt_state, stats, grad_sq, step + 1

                def stepper(params, opt_state, gacc, stats, step):
                    if lvl == "off":
                        opt_specs: PyTree = P()
                        param_specs: PyTree = P()
                    else:
                        opt_specs = zero.opt_partition_specs(
                            self.tx, params, lvl, data_axis
                        )
                        param_specs = P()
                    sharded = jax.shard_map(
                        body, mesh=mesh_s,
                        in_specs=(param_specs, opt_specs, P(data_axis),
                                  P(), P()),
                        out_specs=(param_specs, opt_specs, P(), P(), P()),
                        check_vma=False,
                    )
                    return sharded(params, opt_state, gacc, stats, step)

                return jax.jit(stepper, donate_argnums=(0, 1, 2))

            def make_gacc_init(p_s=p_split[s], mesh_s=mesh_s):
                sh = jax.tree.map(
                    lambda _: NamedSharding(mesh_s, P(data_axis)), p_s
                )

                def zeros():
                    return jax.tree.map(
                        lambda a: jnp.zeros((N,) + tuple(a.shape), jnp.float32),
                        p_s,
                    )

                return jax.jit(zeros, out_shardings=sh)

            self._fwd.append(None if last else make_fwd())
            self._bwd.append(make_loss_bwd() if last else make_bwd())
            self._upd.append(make_upd())
            self._gacc_init.append(make_gacc_init())

    # -- transfers -----------------------------------------------------------

    def _to_stage(self, tree: PyTree, s: int) -> PyTree:
        """Move an activation carry (or cotangent) onto stage ``s``'s
        sub-mesh, batch axis over data — the explicit inter-stage send.
        jax.device_put across disjoint device groups dispatches
        asynchronously, which is what lets forward cycles overlap."""
        sh = NamedSharding(self._meshes[s], P(self.data_axis))
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    # -- the step ------------------------------------------------------------

    def step(
        self, pstate: PipelineState, images, labels
    ) -> Tuple[PipelineState, Dict[str, float]]:
        if self.n_stages <= 1:
            self.last_schedule = {
                "executed_slots": self.n_microbatches,
                "idle_slots": 0,
                "measured_bubble": 0.0,
            }
            new_state, metrics = self._mono(pstate.stages[0], images, labels)
            return (
                PipelineState([new_state]),
                {k: float(np.asarray(jax.device_get(v)))
                 for k, v in metrics.items()},
            )
        S, M = self.n_stages, self.n_microbatches
        executed = 0  # dispatched (stage, cycle) slots — see last_schedule
        if images.shape[0] != M:
            raise ValueError(
                f"images leading dim {images.shape[0]} != "
                f"n_microbatches={M}"
            )
        last = S - 1
        # Input stashes: stage s's microbatch-m input carry and the stats
        # snapshot its forward consumed (the backward recompute needs it).
        cin: List[List[Any]] = [[None] * M for _ in range(S)]
        stats_in: List[List[Any]] = [[None] * M for _ in range(S)]
        stats = [st.batch_stats for st in pstate.stages]
        for m in range(M):
            cin[0][m] = self._to_stage(jnp.asarray(images[m]), 0)
        labels_dev = [
            self._to_stage(jnp.asarray(labels[m]), last) for m in range(M)
        ]
        gacc = [init() for init in self._gacc_init]

        # Forward phase: stages 0..S-2 (the last stage folds its forward
        # into the loss/backward program).  Cycle t runs stage s on
        # microbatch t-s; descending s so a cycle consumes carries the
        # previous cycle stashed — ≤S-1 concurrent programs on disjoint
        # sub-meshes per cycle.
        for t in range(M + S - 2):
            for s in range(min(S - 2, t), -1, -1):
                m = t - s
                if not 0 <= m < M:
                    continue
                stats_in[s][m] = stats[s]
                out, stats[s] = self._fwd[s](
                    pstate.stages[s].params, stats[s], cin[s][m]
                )
                cin[s + 1][m] = self._to_stage(out, s + 1)
                executed += 1

        # Backward phase: stage s at cycle t runs microbatch t-(S-1-s),
        # consuming the cotangent stage s+1 produced last cycle.
        dstash: List[List[Any]] = [[None] * M for _ in range(S)]
        losses, accs = [], []
        for t in range(M + S - 1):
            for s in range(S - 1, -1, -1):
                m = t - (last - s)
                if not 0 <= m < M:
                    continue
                if s == last:
                    stats_in[s][m] = stats[s]
                    loss_m, acc_m, dcin, stats[s], gacc[s] = self._bwd[s](
                        pstate.stages[s].params, stats_in[s][m],
                        cin[s][m], labels_dev[m], gacc[s],
                    )
                    losses.append(loss_m)
                    accs.append(acc_m)
                else:
                    dcin, gacc[s] = self._bwd[s](
                        pstate.stages[s].params, stats_in[s][m],
                        cin[s][m], dstash[s][m], gacc[s],
                    )
                cin[s][m] = None  # free the carry stash
                executed += 1
                if s > 0:
                    dstash[s - 1][m] = self._to_stage(dcin, s - 1)

        # Schedule occupancy, counted off the loops that actually ran —
        # the MEASURED bubble (bench.py --pipeline-ab): idle fraction of
        # the (stage × cycle) grid the two-phase round-robin spans.  On
        # the single-host CPU audit topology wall-clock carries no idle
        # signal (every virtual device shares the same cores), so this is
        # the observable that catches a schedule bug — e.g. a fill/drain
        # mistake dispatches fewer slots per cycle and the fraction jumps,
        # while the closed form (:func:`bubble_fraction`) stays put.
        slots = (S - 1) * (M + S - 2) + S * (M + S - 1)
        self.last_schedule = {
            "executed_slots": executed,
            "idle_slots": slots - executed,
            "measured_bubble": round((slots - executed) / slots, 4),
        }

        # Per-stage update: the quantized bucketed fenced wire + ZeRO
        # ladder within each stage group, dispatched concurrently.
        new_stages, grad_sqs = [], []
        for s in range(S):
            st = pstate.stages[s]
            params, opt, new_stats, grad_sq, step = self._upd[s](
                st.params, st.opt_state, gacc[s], stats[s], st.step
            )
            new_stages.append(TrainState(
                step=step, params=params,
                batch_stats=new_stats, opt_state=opt,
            ))
            grad_sqs.append(grad_sq)
        metrics = {
            "loss": float(np.mean([np.asarray(v).mean() for v in losses])),
            "pixel_acc": float(np.mean([np.asarray(v).mean() for v in accs])),
            "grad_norm": float(np.sqrt(
                np.sum([np.asarray(v) for v in grad_sqs])
            )),
        }
        return PipelineState(new_stages), metrics


def make_pipeline_train_step(
    model: nn.Module,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    compression: CompressionConfig,
    n_microbatches: int,
    data_axis: str = "data",
    space_axis: str = "space",
    pipe_axis: str = "pipe",
    shard_update: str = "off",
    seed: int = 0,
) -> PipelineTrainStep:
    """Build the pipeline driver for ``mesh`` (staged iff it has a
    ``pipe`` axis > 1 — ``make_mesh`` adds one for
    ``ParallelConfig.pipeline_stages > 1``).  See
    :class:`PipelineTrainStep` for the driver API and the module
    docstring for schedule/memory semantics."""
    return PipelineTrainStep(
        model, tx, mesh, compression, n_microbatches,
        data_axis=data_axis, space_axis=space_axis, pipe_axis=pipe_axis,
        shard_update=shard_update, seed=seed,
    )
