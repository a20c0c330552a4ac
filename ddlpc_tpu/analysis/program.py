"""Compiled-program contract auditor (docs/ANALYSIS.md "Program-level contracts").

PR 10's ``ddlpc-check`` proves source-tree contracts; this module audits
one level down — the programs XLA actually emits.  The perf claims the
ROADMAP's top items rest on (fused quantized collectives, ZeRO-2/3,
comm/compute overlap) are claims about *compiled* programs: which
collectives run per optimizer step, what dtype feeds the wire, whether
the ``optimization_barrier`` fences and buffer donation survive
compilation, whether a leaf declared ``P('data')`` is actually sharded
1/N (arxiv 2004.13336 and 2204.06514 both locate the silent losses
exactly here).

Mechanism: the REAL builders — ``parallel/train_step.py``'s two step
builders, ``make_update_step``, ``make_eval_step``, the serve engine's
forward builders — are lowered via ``jax.jit(...).lower(...)`` on
``ShapeDtypeStruct`` trees (the ``obs/flops.py`` eval_shape precedent:
nothing materializes, no program executes), then audited at two levels:

- **jaxpr** (``--fast``, what tier-1 runs) — collective census + fence
  count straight off the traced program, no XLA compile;
- **optimized HLO** — ``lower().compile().as_text()`` parsed by
  ``analysis/hlo.py``: the collective census XLA actually scheduled,
  per-leaf OpSharding vs the declared specs, ``input_output_alias``
  (donation ground truth) and argument/output byte totals.

Contracts checked absolutely (no baseline needed):

- ``comm-closed-form`` — the census' gradient-wire bytes equal
  ``obs/comm.comm_plan``'s closed-form counters byte-for-byte (padding
  from the ZeRO chunk layout accounted explicitly);
- ``dtype-flow`` — the operand dtype feeding each wire collective is no
  wider than the arm's declared wire dtype (int8/fp16 grads must not
  widen to fp32 before the wire on arms that claim a quantized wire);
- ``fence-survival`` — every ``apply_codec_fenced``/``_fenced_update``
  barrier the config implies is present in the jaxpr AND still present
  in the optimized HLO (compiled with XLA's late barrier-expander pass
  disabled — see :data:`FENCE_XLA_FLAG` — so the fences are countable
  after partitioning/fusion);
- ``sharding`` — per-leaf actual sharding equals the declared spec;
  silent full replication of a declared-sharded leaf reports the HBM
  bytes wasted per device;
- ``donation`` — every ``donate_argnums`` leaf is input/output-aliased
  in the compiled module (the HBM the donation was supposed to save is
  reported when it is not).

Everything else (collective counts, argument/output bytes, entry dtype
census) is pinned by the committed per-config baseline
(``docs/analysis/program_baseline.json``, perf_gate-style staleness
stamps): a PR that adds a collective, loses a fence, or un-shards a leaf
fails ``ddlpc-check --programs`` with program + op + contract named.

Tier note: declared ``jax``-tier in ``analysis/tiers.py`` — the program
builders import the full accelerator stack — but every jax import is
function-local, so the baseline validators stay importable from jax-free
contexts (``scripts/perf_gate.py --smoke``).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, List, Optional, Tuple

from ddlpc_tpu.analysis import hlo as hlo_mod
from ddlpc_tpu.obs.comm import SCALE_BYTES, comm_plan

PyTree = Any

PROGRAM_BASELINE_SCHEMA = 1
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "docs", "analysis", "program_baseline.json",
)

# XLA runs OptimizationBarrierExpander ("cse_barrier_expander") late in
# the pipeline — AFTER the fences have done their fusion-blocking job —
# so a normally-compiled module shows zero opt-barriers even when every
# fence survived.  Disabling that one pass makes fences countable in the
# final module without changing what they fenced; the flag must be in
# XLA_FLAGS before the backend initializes (scripts/program_audit.py owns
# that), which :func:`hlo_fences_countable` verifies with a canary.
FENCE_XLA_FLAG = "--xla_disable_hlo_passes=cse_barrier_expander"

# The source files whose collectives ARE the gradient wire — everything
# else (batch-stat pmean, metric reductions, partitioner-inserted
# collectives) is auxiliary and pinned by baseline only.
_WIRE_BASENAMES = frozenset({"grad_sync.py", "compressed_allreduce.py"})

INJECTIONS = (
    "extra-collective", "fp32-widen", "drop-fence", "replicated-leaf"
)


# --------------------------------------------------------------------------
# arm registry: the audited config matrix
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Arm:
    """One audited configuration arm (codec × transport × layout)."""

    name: str
    mode: str = "none"              # none | int8 | float16
    transport: str = "simulate"     # simulate | ring
    rounding: str = "nearest"
    quantize_local: bool = True
    quantize_mean: bool = True
    shard_update: str = "off"       # off | zero1 | zero2 | zero3
    spatial: bool = False           # data×space mesh, GSPMD step
    serve_quantize: str = "off"     # serve arms only
    bucket_mb: float = 0.0          # comm/compute overlap bucket target

    @property
    def comm_variant(self) -> Optional[str]:
        if self.spatial:
            return None  # partitioner owns the collectives — baseline-pinned
        if self.transport == "ring" and self.mode != "none":
            return "ring"
        if self.shard_update == "zero2":
            return "scatter"
        if self.shard_update in ("zero1", "zero3"):
            return self.shard_update
        return "allreduce"

    def declared_wire_dtype(self) -> str:
        """The dtype the arm CLAIMS is on the wire.  The ring transport
        puts real quantized integers on every hop; the fused simulate
        path puts the lattice itself on the collective operand wherever
        the sums fit the narrow dtype exactly — the declaration mirrors
        ``grad_sync.simulate_wire_dtype`` (the single source of truth for
        when the fusion engages) and the HLO dtype-flow + closed-form
        contracts are what prove it."""
        if self.transport == "ring" and self.mode != "none":
            import jax.numpy as jnp

            from ddlpc_tpu.ops.quantize import levels_for
            from ddlpc_tpu.parallel.compressed_allreduce import wire_dtype

            comp = self.compression()
            return hlo_mod.hlo_dtype_name(
                jnp.dtype(wire_dtype(AXIS_SIZE, levels_for(comp)))
            )
        if self.comm_variant in ("allreduce", "scatter", "zero1", "zero3"):
            from ddlpc_tpu.obs.comm import simulate_wire_row

            name, _ = simulate_wire_row(self.compression(), AXIS_SIZE)
            return name
        return "f32"

    def compression(self):
        from ddlpc_tpu.config import CompressionConfig

        return CompressionConfig(
            mode=self.mode,
            transport=self.transport,
            rounding=self.rounding,
            quantize_local=self.quantize_local,
            quantize_mean=self.quantize_mean,
            bucket_mb=self.bucket_mb,
        )


# The audit mesh: 8 virtual CPU devices, the repo's standard collective
# test topology (tests/conftest.py).  Spatial arms split it 4×2.
AXIS_SIZE = 8
SPATIAL_DATA, SPATIAL_SPACE = 4, 2

ARMS: Dict[str, Arm] = {
    a.name: a
    for a in (
        Arm("none_simulate"),
        Arm("int8_simulate", mode="int8"),
        Arm("fp16_simulate", mode="float16"),
        Arm("int8_stochastic", mode="int8", rounding="stochastic"),
        # The ZeRO ladder (shard_update.py module docstring): the *_zero2
        # arms are PR 5's audited programs renamed with the layout
        # taxonomy fix (they persist SCATTERED grad shards — stage 2);
        # *_zero1 audits the new true stage-1 program (full-mean
        # all-reduce + chunked update + params publish), *_zero3 the
        # params-sharded gather-on-demand program.
        Arm("none_zero1", shard_update="zero1"),
        Arm("int8_zero1", mode="int8", shard_update="zero1"),
        Arm("none_zero2", shard_update="zero2"),
        Arm("int8_zero2", mode="int8", shard_update="zero2"),
        Arm("fp16_zero2", mode="float16", shard_update="zero2"),
        Arm("none_zero3", shard_update="zero3"),
        Arm("int8_zero3", mode="int8", shard_update="zero3"),
        Arm("int8_ring", mode="int8", transport="ring"),
        Arm("fp16_ring", mode="float16", transport="ring"),
        Arm("none_gspmd", spatial=True),
        Arm("fp16_gspmd", mode="float16", spatial=True, quantize_local=False),
        Arm("gspmd_zero1", spatial=True, shard_update="zero1"),
        Arm("gspmd_zero2", spatial=True, shard_update="zero2"),
        Arm("gspmd_zero3", spatial=True, shard_update="zero3"),
        # Bucketed comm/compute overlap arms: the same tiny tree split
        # into several size-targeted buckets (0.02 MiB yields B > 1 on
        # the audit model) — one fused collective per bucket, per-bucket
        # scales, and the census parity across the three layouts is what
        # pins that every layout derives the identical partition.
        Arm("int8_bucketed", mode="int8", bucket_mb=0.02),
        Arm("fp16_bucketed_zero2", mode="float16", shard_update="zero2",
            bucket_mb=0.02),
        Arm("fp16_bucketed_gspmd", mode="float16", spatial=True,
            quantize_local=False, bucket_mb=0.02),
        Arm("serve_fp32"),
        Arm("serve_int8", serve_quantize="int8"),
        Arm("serve_bf16", serve_quantize="bf16"),
        Arm("eval"),
        Arm("eval_gspmd", spatial=True),
    )
}

# program name -> (arm, program kind).  update_step is the cheapest
# program containing the full gradient wire, so every codec arm audits
# it; the full train step compiles on a representative subset (it adds
# the aux collectives — batch-stat pmean, metric reductions — and the
# donation/sharding of the whole state).
_TRAIN_ARMS = (
    "none_simulate", "int8_simulate", "int8_zero1", "int8_zero2",
    "int8_zero3", "int8_ring", "none_gspmd", "fp16_gspmd", "gspmd_zero1",
    "gspmd_zero2", "gspmd_zero3", "fp16_bucketed_gspmd",
)


def _program_table() -> Dict[str, Tuple[str, str]]:
    out: Dict[str, Tuple[str, str]] = {}
    for name, arm in ARMS.items():
        if name.startswith("serve_"):
            out[f"{name}/forward"] = (name, "serve_forward")
        elif name.startswith("eval"):
            out[f"{name}/eval_step"] = (name, "eval_step")
        else:
            if not arm.spatial:
                out[f"{name}/update_step"] = (name, "update_step")
            if name in _TRAIN_ARMS:
                out[f"{name}/train_step"] = (name, "train_step")
    return out


PROGRAMS: Dict[str, Tuple[str, str]] = _program_table()


def list_programs() -> List[str]:
    return sorted(PROGRAMS)


# --------------------------------------------------------------------------
# tiny experiment + aval construction (nothing materializes)
# --------------------------------------------------------------------------


def _tiny_experiment(arm: Arm):
    """The audit model/config: perf_gate's tiny shape (the cheapest
    config that exercises every layer class), with the arm's codec and
    mesh topology."""
    from ddlpc_tpu.config import (
        DataConfig,
        ExperimentConfig,
        ModelConfig,
        ParallelConfig,
        TrainConfig,
    )

    parallel = ParallelConfig(
        data_axis_size=SPATIAL_DATA if arm.spatial else -1,
        space_axis_size=SPATIAL_SPACE if arm.spatial else 1,
    )
    return ExperimentConfig(
        model=ModelConfig(
            features=(8, 16), bottleneck_features=16, num_classes=6
        ),
        data=DataConfig(
            dataset="synthetic", image_size=(32, 32), num_classes=6,
            synthetic_len=64,
        ),
        train=TrainConfig(micro_batch_size=2, sync_period=2),
        compression=arm.compression(),
        parallel=parallel,
    )


def _abstract_state(cfg, mesh):
    """TrainState of ShapeDtypeStructs for the tiny model — the
    obs/flops.collect_convs idiom: model init under eval_shape, inputs as
    abstract arguments, zero bytes allocated."""
    import jax
    import jax.numpy as jnp

    from ddlpc_tpu.models import build_model_from_experiment
    from ddlpc_tpu.parallel.train_step import TrainState
    from ddlpc_tpu.train.optim import build_optimizer

    model = build_model_from_experiment(cfg)
    tx = build_optimizer(cfg.train)
    h, w = cfg.data.image_size
    variables = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1, h, w, 3), jnp.float32),
            train=False,
        )
    )
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=jax.eval_shape(tx.init, params),
    )
    return model, tx, state


def _chunked_opt_avals(params, opt_state):
    """The zero1 run-layout opt_state as avals: param-shaped moment
    leaves become their [N, K] chunk views (shard_update.chunk_leaf's
    shapes, computed without touching data)."""
    import jax

    from ddlpc_tpu.parallel import shard_update as zero

    pshapes = zero.param_shapes(params)

    def leaf(t):
        if not zero.chunkable(t.shape, pshapes):
            return t
        size = 1
        for d in t.shape:
            size *= int(d)
        return jax.ShapeDtypeStruct(
            (AXIS_SIZE, zero.chunk_rows(size, AXIS_SIZE)), t.dtype
        )

    return jax.tree.map(leaf, opt_state)


def _tree_elements(tree) -> int:
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        n = 1
        for d in leaf.shape:
            n *= int(d)
        total += n
    return total


def _chunk_padding_bytes(tree, n_shards: int, itemsize: int = 4) -> int:
    """Bytes the [N, K] chunk layout adds over the exact element count
    (shard_update.chunk_rows padding), per full-tree collective, at the
    collective's operand itemsize (the fused scatter pads WIRE-dtype
    elements; the params all-gather pads fp32)."""
    import jax

    from ddlpc_tpu.parallel.shard_update import chunk_rows

    pad = 0
    for leaf in jax.tree.leaves(tree):
        size = 1
        for d in leaf.shape:
            size *= int(d)
        pad += n_shards * chunk_rows(size, n_shards) - size
    return pad * itemsize


# --------------------------------------------------------------------------
# declared contracts + program bundles
# --------------------------------------------------------------------------


@dataclass
class Declared:
    """What the builders CLAIM about one program — the audit reference."""

    comm_variant: Optional[str] = None
    wire_dtype: str = "f32"
    fences: int = 0
    donated_args: Tuple[int, ...] = ()
    n_grad: int = 0
    n_param: int = 0
    axis_size: int = 1
    rs_pad_bytes: int = 0       # zero1 chunk padding on the grad scatter
    ag_pad_bytes: int = 0       # zero1 chunk padding on the params publish
    scale_collectives: int = 0  # live scalar pmaxes of the global scale(s)
    n_buckets: int = 1          # bucket_mb partition size (grad_bucket_groups)
    has_dead_norm_psum: bool = False    # jaxpr-only psum DCE'd by XLA
    # tree of per-leaf expected shard element counts (None = skip audit)
    sharding_in: Any = None
    sharding_out: Any = None


@dataclass
class ProgramBundle:
    """A lowerable program + the avals and declared contracts to audit
    it against.  ``patch`` (injections only) is a context-manager factory
    held open across tracing/lowering — jax resolves module globals at
    TRACE time, so an injection that rewires one (e.g. neutering
    ``apply_codec_fenced``) must stay applied until the jaxpr exists."""

    name: str
    arm: Arm
    kind: str
    fn: Callable
    avals: Tuple
    declared: Declared
    patch: Optional[Callable] = None


def expected_fences(arm: Arm, kind: str, n_buckets: int = 1) -> int:
    """Barrier count the configuration implies (grad_sync.py /
    train_step.py fencing rules — the single place the expectation is
    written down, so a dropped fence is a COUNT mismatch, not a vibe).
    Every quantize stage runs once per bucket (``n_buckets`` =
    grad_bucket_groups of the audited tree), each inside its own fence
    pair: the fused wire encode keeps apply_codec_fenced's cut points
    and count, the dequantize is deliberately unfenced (one scalar
    multiply cannot FMA-contract — grad_sync._fenced_wire_encode)."""
    if kind in ("eval_step", "serve_forward"):
        return 0
    fences = 2  # _fenced_update pins the optimizer chain
    quantizing = arm.mode != "none"
    if not quantizing:
        return fences
    if arm.spatial:
        # one apply_codec_fenced on the mean gradient, per bucket
        return fences + 2 * n_buckets
    if arm.transport == "ring":
        # The N>1 ring owns its own quantized collective; no XLA-level
        # codec stages exist to fence (compressed_allreduce.py).
        return fences
    fences += n_buckets * (
        2 * int(arm.quantize_local) + 2 * int(arm.quantize_mean)
    )
    return fences


def _mesh_for(arm: Arm):
    from ddlpc_tpu.parallel.mesh import make_mesh

    cfg = _tiny_experiment(arm)
    return make_mesh(cfg.parallel)


def _shard_elems(sharding, shape) -> int:
    """Per-device elements under ``sharding``.  Uneven tilings (GSPMD
    pads them) make ``shard_shape`` raise; fall back to the HLO
    sharding's tile-assignment dims with ceil division — the padded
    shard is what lives in HBM."""
    shape = tuple(int(s) for s in shape)
    try:
        n = 1
        for d in sharding.shard_shape(shape):
            n *= int(d)
        return n
    except ValueError:
        pass
    hs = sharding._to_xla_hlo_sharding(len(shape))
    if hs.is_replicated():
        tile = [1] * len(shape)
    else:
        tile = list(hs.tile_assignment_dimensions())[: len(shape)]
    n = 1
    for d, t in zip(shape, tile):
        n *= -(-d // max(int(t), 1))
    return n


def _spec_shard_elems(mesh, spec, shape) -> int:
    """Expected per-device elements for a PartitionSpec over ``mesh`` —
    ceil division per sharded dim (GSPMD pads uneven shards; the padded
    shard is the HBM cost)."""
    shape = tuple(int(s) for s in shape)
    entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    n = 1
    for dim, ax in zip(shape, entries):
        if ax is None:
            n *= dim
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        n *= -(-dim // size)
    return n


def _named_tree(mesh, spec_tree, aval_tree):
    """PartitionSpec tree -> per-leaf expected shard ELEMENT counts."""
    import jax

    return jax.tree.map(
        lambda sp, av: _spec_shard_elems(mesh, sp, av.shape),
        spec_tree,
        aval_tree,
    )


def _repl_tree(aval_tree):
    import jax

    return jax.tree.map(
        lambda av: int(_aval_elems(av)), aval_tree
    )


def _aval_elems(av) -> int:
    n = 1
    for d in av.shape:
        n *= int(d)
    return n


def build_program(name: str) -> ProgramBundle:
    """Construct the jitted program + audit avals for one registry entry.

    Uses the SAME builders the trainer/bench/serve paths call — the
    auditor must audit the program that runs, not a lookalike."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    arm_name, kind = PROGRAMS[name]
    arm = ARMS[arm_name]
    cfg = _tiny_experiment(arm)
    comp = cfg.compression

    if kind == "serve_forward":
        return _build_serve(name, arm, cfg)

    mesh = _mesh_for(arm)
    model, tx, state = _abstract_state(cfg, mesh)
    n_grad = _tree_elements(state.params)
    h, w = cfg.data.image_size

    if kind == "eval_step":
        from ddlpc_tpu.parallel.train_step import (
            make_eval_step,
            make_eval_step_gspmd,
        )

        # The trainer strips opt_state from the eval input (PR 5: no
        # per-batch all-gathers of unused moments) — audit that shape.
        eval_state = state.replace(opt_state=())
        B = AXIS_SIZE
        images = jax.ShapeDtypeStruct((B, h, w, 3), jnp.float32)
        labels = jax.ShapeDtypeStruct((B, h, w), jnp.int32)
        if arm.spatial:
            fn = make_eval_step_gspmd(model, mesh, cfg.model.num_classes)
        else:
            fn = make_eval_step(model, mesh, cfg.model.num_classes)
        img_elems, lbl_elems = _shard_elems_tree_for_batch(
            mesh, arm, images, labels
        )
        declared = Declared(
            fences=expected_fences(arm, kind),
            axis_size=mesh.shape["data"],
            sharding_in=(
                _repl_tree(eval_state), img_elems, lbl_elems
            ),
        )
        return ProgramBundle(
            name, arm, kind, fn, (eval_state, images, labels), declared
        )

    # training-side programs
    from ddlpc_tpu.parallel import shard_update as zero
    from ddlpc_tpu.parallel.train_step import (
        make_train_step,
        make_train_step_gspmd,
        make_update_step,
    )

    from ddlpc_tpu.parallel.grad_sync import grad_bucket_groups

    n_buckets = len(grad_bucket_groups(state.params, comp.bucket_mb))
    declared = Declared(
        comm_variant=arm.comm_variant,
        wire_dtype=arm.declared_wire_dtype(),
        fences=expected_fences(arm, kind, n_buckets),
        n_grad=n_grad,
        n_param=n_grad,
        axis_size=mesh.shape["data"],
        n_buckets=n_buckets,
    )
    quantizing = comp.mode != "none"
    # One live scalar pmax per global scale: the fused wire encode shares
    # its scale across replicas (per bucket), and the scatter's mean
    # stage pmaxes the chunked absmax back to the global one (per
    # bucket).  The non-fused fake-quantize stages use local scales — no
    # collective.
    # zero1 rides the allreduce path's fused wire; zero2 (scatter) and
    # zero3 ride the scatter sync — all of them share the per-bucket
    # global scale when the wire is quantized.  Ring keeps its own
    # single-scale accounting below.
    fused = declared.wire_dtype != "f32" and arm.comm_variant in (
        "allreduce", "scatter", "zero1", "zero3"
    )
    level = arm.shard_update
    if arm.comm_variant in ("allreduce", "zero1"):
        # zero1's sync IS the allreduce path's (full mean) — same fused
        # wire, same scale pmaxes; the extra all-gather carries params.
        declared.scale_collectives = n_buckets if fused else 0
    if level != "off" and not arm.spatial:
        # Every chunk layout all-gathers [1, K] param chunks (zero1/zero2
        # publish fresh params at the tail; zero3 gathers on demand at the
        # head) — fp32 chunk padding either way.
        declared.ag_pad_bytes = _chunk_padding_bytes(
            state.params, AXIS_SIZE, 4
        )
        if level in ("zero2", "zero3"):
            wire_item = hlo_mod.max_operand_itemsize(declared.wire_dtype)
            declared.rs_pad_bytes = _chunk_padding_bytes(
                state.params, AXIS_SIZE, wire_item
            )
            declared.scale_collectives = n_buckets * (
                int(fused) + int(quantizing and comp.quantize_mean)
            )
            declared.has_dead_norm_psum = True
    if arm.comm_variant == "ring":
        declared.scale_collectives = 1

    if kind == "update_step":
        fn = make_update_step(
            tx, mesh, comp, shard_update=level,
            seed=cfg.train.seed,
        )
        opt_avals = state.opt_state
        opt_spec = jax.tree.map(lambda _: P(), opt_avals)
        param_in_avals = state.params
        param_in_elems = _repl_tree(state.params)
        if level != "off":
            opt_avals = _chunked_opt_avals(state.params, state.opt_state)
            # opt_partition_specs is written over the FULL-layout template;
            # the chunk view replaces leaves 1:1, so the spec tree remaps
            # structurally (chunked leaves: P('data') on chunk axis 0).
            opt_spec = _respec_chunked(
                zero.opt_partition_specs(tx, state.params, level, "data"),
                opt_avals,
            )
        if level == "zero3":
            # zero3's update consumes AND produces chunked params; the
            # full model never appears in this program at all.
            param_in_avals = _chunked_opt_avals(state.params, state.params)
            param_in_elems = _named_tree(
                mesh,
                jax.tree.map(lambda _: P("data"), param_in_avals),
                param_in_avals,
            )
            # No params all-gather in the update program (the train step's
            # gather-on-demand prologue owns it) — wire is the RS alone.
            declared.comm_variant = "zero3_update"
            declared.ag_pad_bytes = 0
        avals = (param_in_avals, opt_avals, state.params)
        grad_elems = _repl_tree(state.params)
        opt_elems = _named_tree(mesh, opt_spec, opt_avals)
        declared.donated_args = (0, 1)
        declared.sharding_in = (param_in_elems, opt_elems, grad_elems)
        declared.sharding_out = (param_in_elems, opt_elems)
        # update-only program keeps the dead norm psum only on zero2
        # (train_step._apply_update_sharded): zero1's optax.global_norm
        # is collective-free, and make_update_step's zero3 branch goes
        # straight from scatter to the fenced update — no norm at all.
        declared.has_dead_norm_psum = level == "zero2"
        return ProgramBundle(name, arm, kind, fn, avals, declared)

    # train_step
    A, B = cfg.train.sync_period, cfg.train.micro_batch_size * AXIS_SIZE
    images = jax.ShapeDtypeStruct((A, B, h, w, 3), jnp.float32)
    labels = jax.ShapeDtypeStruct((A, B, h, w), jnp.int32)
    if arm.spatial:
        fn = make_train_step_gspmd(
            model, tx, mesh, comp, shard_update=level,
            seed=cfg.train.seed,
        )
        if level != "off":
            fn = fn.build_for(state)  # the lowerable inner jit
        state_avals = state
        opt_layout = zero.GSPMD_LAYOUT_FOR_LEVEL.get(level)
    else:
        fn = make_train_step(
            model, tx, mesh, comp, shard_update=level,
            seed=cfg.train.seed, param_avals=state.params,
        )
        state_avals = state
        opt_layout = None
        if level != "off":
            state_avals = state.replace(
                opt_state=_chunked_opt_avals(state.params, state.opt_state)
            )
            if level == "zero3":
                # Run-layout params: [N, K] chunks, P('data') on axis 0.
                state_avals = state_avals.replace(
                    params=_chunked_opt_avals(state.params, state.params)
                )
            opt_layout = level
    declared.donated_args = (0,)
    declared.has_dead_norm_psum = False  # the norm psum is live here
    declared.sharding_in = (
        _train_state_shard_tree(mesh, arm, tx, state, state_avals, opt_layout),
        _batch_shard_elems(mesh, arm, images),
        _batch_shard_elems(mesh, arm, labels),
    )
    declared.sharding_out = None  # metrics tree varies; inputs carry the claim
    return ProgramBundle(
        name, arm, kind, fn, (state_avals, images, labels), declared
    )


def _respec_chunked(spec_tree, chunked_avals):
    """zero1 opt specs are written against the full-layout template;
    remap them structurally onto the chunked aval tree (identical
    treedef, leaf-for-leaf)."""
    import jax

    leaves_spec = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: x is None
    )
    treedef = jax.tree_util.tree_structure(chunked_avals)
    return jax.tree_util.tree_unflatten(treedef, leaves_spec)


def _shard_elems_tree_for_batch(mesh, arm: Arm, images, labels):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = (
        P("data", "space") if arm.spatial else P("data")
    )
    return tuple(
        _shard_elems(NamedSharding(mesh, spec), av.shape)
        for av in (images, labels)
    )


def _batch_shard_elems(mesh, arm: Arm, av):
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = (
        P(None, "data", "space") if arm.spatial else P(None, "data")
    )
    return _shard_elems(NamedSharding(mesh, spec), av.shape)


def _train_state_shard_tree(mesh, arm, tx, state, state_avals, opt_layout):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddlpc_tpu.parallel import shard_update as zero

    param_elems = _repl_tree(state_avals.params)
    if opt_layout is None:
        opt_elems = _repl_tree(state_avals.opt_state)
    elif opt_layout in zero.CHUNK_LAYOUTS:
        spec = zero.opt_partition_specs(tx, state.params, opt_layout, "data")
        spec = _respec_chunked(spec, state_avals.opt_state)
        opt_elems = _named_tree(mesh, spec, state_avals.opt_state)
        if opt_layout == "zero3":
            # state_avals.params are the [N, K] chunk views, P('data').
            param_elems = _named_tree(
                mesh,
                jax.tree.map(lambda _: P("data"), state_avals.params),
                state_avals.params,
            )
    else:  # gspmd family
        spec = zero.opt_partition_specs(
            tx, state.params, opt_layout, "data",
            n_shards=mesh.shape["data"],
        )
        opt_elems = _named_tree(mesh, spec, state_avals.opt_state)
        if opt_layout == "gspmd_zero3":
            # Params keep their shapes; the rule engine shards a dim
            # (even_shard_spec) or keeps the leaf replicated-by-rule.
            pd = zero.param_decisions(
                state.params, opt_layout, mesh.shape["data"], "data"
            )
            param_elems = _named_tree(
                mesh,
                jax.tree.map(lambda d: d.spec, pd),
                state_avals.params,
            )
    return state_avals.replace(
        step=_aval_elems(state_avals.step),
        params=param_elems,
        batch_stats=_repl_tree(state_avals.batch_stats),
        opt_state=opt_elems,
    )


def _build_serve(name: str, arm: Arm, cfg) -> ProgramBundle:
    """The serve engine's forward program — the builders the engine's jit
    cache holds (train_step.make_logits_fn / serve.quantized's fused
    dequant), on one power-of-two bucket of the tile geometry."""
    import jax
    import jax.numpy as jnp

    from ddlpc_tpu.models import build_model
    from ddlpc_tpu.parallel.train_step import TrainState, make_logits_fn
    from ddlpc_tpu.serve import quantized as q
    from ddlpc_tpu.train.optim import build_optimizer

    model = build_model(cfg.model, norm_axis_name=None)
    tx = build_optimizer(cfg.train, total_steps=1)
    h, w = cfg.data.image_size
    variables = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1, h, w, 3), jnp.float32),
            train=False,
        )
    )
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    images = jax.ShapeDtypeStruct((4, h, w, 3), jnp.float32)
    declared = Declared(fences=0)
    if arm.serve_quantize == "off":
        state = TrainState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            params=params,
            batch_stats=batch_stats,
            opt_state=jax.eval_shape(tx.init, params),
        )
        fn = make_logits_fn(model)
        return ProgramBundle(name, arm, "serve_forward", fn, (state, images),
                             declared)
    wire = jnp.int8 if arm.serve_quantize == "int8" else jnp.bfloat16
    qstate = q.QuantizedState(
        params=jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, wire), params
        ),
        scales=jax.tree.map(
            lambda p: jax.ShapeDtypeStruct((), jnp.float32), params
        ),
        batch_stats=batch_stats,
    )
    fn = q.make_quantized_logits_fn(model, arm.serve_quantize)
    return ProgramBundle(
        name, arm, "serve_forward", fn, (qstate, images), declared
    )


# --------------------------------------------------------------------------
# audits
# --------------------------------------------------------------------------


@dataclass
class ProgramViolation:
    program: str
    contract: str
    message: str

    def format(self) -> str:
        return f"VIOLATION {self.program}: [{self.contract}] {self.message}"


@dataclass
class ProgramAudit:
    """Everything the auditor measured about one program."""

    name: str
    arm: str
    kind: str
    jaxpr_census: List[Dict[str, object]] = field(default_factory=list)
    jaxpr_fences: int = 0
    # full-mode fields (None when --fast)
    hlo_census: Optional[List[Dict[str, object]]] = None
    hlo_fences: Optional[int] = None          # -1 = expander active
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    aliased_bytes: Optional[int] = None
    donated_bytes: Optional[int] = None
    donated_leaves: Optional[int] = None
    aliased_leaves: Optional[int] = None
    param_dtypes: Optional[Dict[str, int]] = None
    sharded_in_leaves: Optional[int] = None
    sharded_out_leaves: Optional[int] = None
    violations: List[ProgramViolation] = field(default_factory=list)

    def baseline_entry(self) -> Dict[str, object]:
        entry: Dict[str, object] = {
            "jaxpr": {
                "census": self.jaxpr_census,
                "fences": self.jaxpr_fences,
            }
        }
        if self.hlo_census is not None:
            entry["hlo"] = {
                "census": self.hlo_census,
                "fences": self.hlo_fences,
                "argument_bytes": self.argument_bytes,
                "output_bytes": self.output_bytes,
                "aliased_bytes": self.aliased_bytes,
                "donated_bytes": self.donated_bytes,
                "donated_leaves": self.donated_leaves,
                "aliased_leaves": self.aliased_leaves,
                "param_dtypes": self.param_dtypes,
                "sharded_in_leaves": self.sharded_in_leaves,
                "sharded_out_leaves": self.sharded_out_leaves,
            }
        return entry

    def to_record(self) -> Dict[str, object]:
        rec: Dict[str, object] = {
            "kind": "program",
            "program": self.name,
            "arm": self.arm,
            "program_kind": self.kind,
            "jaxpr_fences": self.jaxpr_fences,
            "jaxpr_census": _census_strings(self.jaxpr_census),
            "violations": len(self.violations),
        }
        if self.hlo_census is not None:
            rec.update(
                hlo_fences=self.hlo_fences,
                hlo_census=_census_strings(self.hlo_census),
                argument_bytes=self.argument_bytes,
                output_bytes=self.output_bytes,
                aliased_bytes=self.aliased_bytes,
                donated_bytes=self.donated_bytes,
            )
        return rec


def _census_strings(rows: List[Dict[str, object]]) -> List[str]:
    return [
        f"{r['kind']}|{r['dtype']}|{r.get('group', 'all')}|"
        f"count={r['count']}|elements={r['elements']}|bytes={r['bytes']}"
        for r in rows
    ]


_FENCE_CANARY: Dict[str, bool] = {}


def hlo_fences_countable() -> bool:
    """True when the backend keeps ``opt-barrier`` in the final module
    (the barrier-expander pass was disabled before backend init — the
    program_audit CLI does this).  Checked once per process with a
    two-barrier canary program."""
    if "ok" not in _FENCE_CANARY:
        import jax
        import jax.numpy as jnp
        from jax import lax

        def canary(x):
            return lax.optimization_barrier(
                lax.optimization_barrier(x) * 2
            )

        text = (
            jax.jit(canary)
            .lower(jax.ShapeDtypeStruct((4,), jnp.float32))
            .compile()
            .as_text()
        )
        _FENCE_CANARY["ok"] = (
            hlo_mod.parse_hlo_module(text).fence_count == 2
        )
    return _FENCE_CANARY["ok"]


def _classify_wire(arm: Arm):
    def classify(op: hlo_mod.HloOp) -> str:
        base = os.path.basename(op.source_file)
        if base in _WIRE_BASENAMES:
            return "wire"
        if (
            arm.shard_update != "off"
            and not arm.spatial
            and op.opcode.startswith("all-gather")
            and base in ("train_step.py", "shard_update.py")
        ):
            # The chunk layouts' params all-gather: zero1/zero2 publish
            # fresh params at the tail, zero3 gathers on demand at the
            # step head — wire either way.
            return "wire"
        return "aux"

    return classify


def check_comm_closed_form(
    bundle: ProgramBundle, rows: List[Dict[str, object]], level: str
) -> List[ProgramViolation]:
    """The census' gradient-wire rows vs ``obs/comm.comm_plan`` —
    byte-for-byte, with the ZeRO chunk padding and the scalar control
    collectives (global-scale pmax, the jaxpr-level dead norm psum)
    accounted explicitly.  ``rows`` must already be restricted to the
    wire (HLO: group == "wire"; jaxpr: the update program's census IS the
    wire plus the declared scalars)."""
    d = bundle.declared
    if d.comm_variant is None:
        return []
    comp = bundle.arm.compression()
    plan = comm_plan(
        d.n_grad, d.n_param, comp, d.axis_size, d.comm_variant,
        n_buckets=d.n_buckets,
    )
    expected: Dict[Tuple[str, str], int] = {}
    if d.comm_variant in (
        "allreduce", "scatter", "zero1", "zero3", "zero3_update"
    ):
        # The plan's bytes_wire is payload + one fp32 scale per bucket;
        # in the program those are SEPARATE collectives — the narrow
        # payload reduce and the scalar scale pmax(es), the latter
        # accounted in scalar_bytes below.
        row = plan[0]
        wire = str(row["wire_dtype"])
        scale_in_wire = 0 if wire == "f32" else SCALE_BYTES * d.n_buckets
        payload = int(row["bytes_wire"]) - scale_in_wire
        if d.comm_variant == "allreduce":
            expected[("all-reduce", wire)] = payload
        elif d.comm_variant == "zero1":
            # Full-mean all-reduce + the chunked update's params publish.
            expected[("all-reduce", wire)] = payload
            expected[("all-gather", "f32")] = (
                int(plan[1]["bytes_wire"]) + d.ag_pad_bytes
            )
        else:
            expected[("reduce-scatter", wire)] = payload + d.rs_pad_bytes
            if d.comm_variant != "zero3_update":
                expected[("all-gather", "f32")] = (
                    int(plan[1]["bytes_wire"]) + d.ag_pad_bytes
                )
    elif d.comm_variant == "ring":
        expected[("collective-permute", d.wire_dtype)] = plan[0]["bytes_post"]
    scalar_bytes = SCALE_BYTES * d.scale_collectives
    if d.has_dead_norm_psum and level == "jaxpr":
        scalar_bytes += 4  # psum of the f32[] grad-norm partial (DCE'd by XLA)
    if scalar_bytes:
        expected[("all-reduce", "f32")] = (
            expected.get(("all-reduce", "f32"), 0) + scalar_bytes
        )
    actual: Dict[Tuple[str, str], int] = {}
    for r in rows:
        key = (str(r["kind"]), str(r["dtype"]))
        actual[key] = actual.get(key, 0) + int(r["bytes"])
    out: List[ProgramViolation] = []
    for key in sorted(set(expected) | set(actual)):
        kind, dtype = key
        exp, act = expected.get(key, 0), actual.get(key, 0)
        if exp != act:
            out.append(
                ProgramViolation(
                    bundle.name, "comm-closed-form",
                    f"{level} census {kind}[{dtype}] moves {act} B/replica/"
                    f"step but obs/comm.comm_plan's closed form says {exp} B "
                    f"(variant={d.comm_variant}, codec={comp.mode}) — the "
                    f"program and the accounting have drifted",
                )
            )
    return out


def check_dtype_flow(
    bundle: ProgramBundle, rows: List[Dict[str, object]], level: str
) -> List[ProgramViolation]:
    """No wire collective may be fed a dtype wider than the arm declares.

    Scalar control collectives (the global-scale pmaxes, the grad-norm
    psum) are exempt — they are not the gradient payload; XLA's
    all-reduce combiner may merge several of them into one op, so the
    exemption budget is the DECLARED scalar count, not ops-in-row.  On
    arms that declare a quantized wire (ring, and the fused simulate
    path), an fp32 operand here is exactly the "int8 grads widened to
    fp32 before the wire" regression this contract exists to catch."""
    d = bundle.declared
    if d.comm_variant is None:
        return []
    declared_bytes = hlo_mod.max_operand_itemsize(d.wire_dtype)
    scalar_budget = d.scale_collectives + int(d.has_dead_norm_psum)
    out: List[ProgramViolation] = []
    for r in rows:
        if r["kind"] not in (
            "all-reduce", "reduce-scatter", "collective-permute"
        ):
            continue
        if int(r["elements"]) <= max(int(r["count"]), scalar_budget):
            continue  # scalar control collective(s)
        width = hlo_mod.max_operand_itemsize(str(r["dtype"]))
        if width > declared_bytes:
            out.append(
                ProgramViolation(
                    bundle.name, "dtype-flow",
                    f"{level} {r['kind']} wire operand is {r['dtype']} "
                    f"({width} B/elt), wider than the declared wire dtype "
                    f"{d.wire_dtype} ({declared_bytes} B/elt) — quantized "
                    f"gradients widened before the wire "
                    f"({r['elements']} elements)",
                )
            )
    return out


def _jaxpr_wire_rows(
    bundle: ProgramBundle, census: List[Dict[str, object]]
) -> Optional[List[Dict[str, object]]]:
    """jaxpr census rows usable for the comm/dtype checks.  Only the
    update program's census is pure wire (train/eval programs interleave
    batch-stat and metric collectives, which only HLO metadata can
    separate)."""
    if bundle.kind != "update_step":
        return None
    return census


def audit_program(
    name: str,
    fast: bool = True,
    bundle: Optional[ProgramBundle] = None,
) -> ProgramAudit:
    """Lower (and in full mode compile) one registry program and run
    every absolute contract check.  ``bundle`` override is the injection
    hook (scripts/program_audit.py --inject)."""
    import contextlib

    if bundle is None:
        bundle = build_program(name)
    audit = ProgramAudit(name=bundle.name, arm=bundle.arm.name,
                         kind=bundle.kind)
    stack = contextlib.ExitStack()
    if bundle.patch is not None:
        # keep the patch applied through tracing AND lowering/compile
        stack.enter_context(bundle.patch())
    with stack:
        return _audit_traced(bundle, audit, fast)


def _audit_traced(bundle, audit: ProgramAudit, fast: bool) -> ProgramAudit:
    import jax

    traced = jax.make_jaxpr(lambda *a: bundle.fn(*a), return_shape=True)
    jaxpr, out_shape = traced(*bundle.avals)
    audit.jaxpr_census = hlo_mod.census_to_dicts(
        hlo_mod.jaxpr_collectives(jaxpr)
    )
    audit.jaxpr_fences = hlo_mod.jaxpr_fence_count(jaxpr)

    d = bundle.declared
    if audit.jaxpr_fences != d.fences:
        audit.violations.append(
            ProgramViolation(
                bundle.name, "fence-survival",
                f"jaxpr carries {audit.jaxpr_fences} optimization_barrier "
                f"fence(s) but the codec/update fencing rules imply "
                f"{d.fences} (apply_codec_fenced/_fenced_update dropped?)",
            )
        )
    wire_rows = _jaxpr_wire_rows(bundle, audit.jaxpr_census)
    if wire_rows is not None:
        audit.violations.extend(
            check_comm_closed_form(bundle, wire_rows, "jaxpr")
        )
        audit.violations.extend(
            check_dtype_flow(bundle, wire_rows, "jaxpr")
        )
    if fast:
        return audit

    lowered = bundle.fn.lower(*bundle.avals)
    compiled = lowered.compile()
    module = hlo_mod.parse_hlo_module(compiled.as_text())
    classify = _classify_wire(bundle.arm)
    audit.hlo_census = hlo_mod.census_to_dicts(
        hlo_mod.hlo_collective_census(module.ops, classify)
    )
    audit.hlo_fences = (
        module.fence_count if hlo_fences_countable() else -1
    )
    audit.argument_bytes = sum(s.bytes for s in module.entry_params)
    audit.output_bytes = sum(s.bytes for s in module.entry_outputs)
    dtypes: Dict[str, int] = {}
    for s in module.entry_params:
        dtypes[s.dtype] = dtypes.get(s.dtype, 0) + 1
    audit.param_dtypes = dtypes

    if audit.hlo_fences >= 0 and audit.hlo_fences != d.fences:
        audit.violations.append(
            ProgramViolation(
                bundle.name, "fence-survival",
                f"optimized HLO carries {audit.hlo_fences} opt-barrier "
                f"fence(s), expected {d.fences} — a fence the jaxpr had "
                f"did not survive compilation",
            )
        )
    hlo_wire = [r for r in audit.hlo_census if r.get("group") == "wire"]
    audit.violations.extend(check_comm_closed_form(bundle, hlo_wire, "hlo"))
    audit.violations.extend(check_dtype_flow(bundle, hlo_wire, "hlo"))
    _audit_donation(bundle, compiled, module, audit)
    _audit_sharding(bundle, compiled, audit, out_shape)
    return audit


def _kept_leaf_params(bundle, compiled):
    """Align the lowered aval leaves with the compiled module's entry
    parameters.  ``compiled.input_shardings`` mirrors the args tree with
    ``None`` at PRUNED (unused, ``keep_unused=False``) leaves, so the
    non-None leaves in flatten order correspond 1:1 to entry parameters
    0..P-1 — no shape matching needed (entry shapes are per-device under
    SPMD, the avals are global).

    Returns (flat_idx -> param_number, flat avals, flat shardings,
    per-arg leaf spans)."""
    import jax

    avals_flat = jax.tree_util.tree_leaves(bundle.avals)
    shardings_flat = _flatten_with_none(compiled.input_shardings[0])
    mapping: Dict[int, int] = {}
    p = 0
    for i, sh in enumerate(shardings_flat):
        if sh is not None:
            mapping[i] = p
            p += 1
    spans = []
    offset = 0
    for a in bundle.avals:
        n = len(jax.tree_util.tree_leaves(a))
        spans.append((offset, offset + n))
        offset += n
    return mapping, avals_flat, shardings_flat, spans


def _audit_donation(bundle, compiled, module, audit: ProgramAudit) -> None:
    """Every donate_argnums leaf must be input/output-aliased in the
    compiled module; a donated-but-unaliased buffer is HBM the donation
    was supposed to save (reported in bytes)."""
    d = bundle.declared
    mapping, avals_flat, shardings_flat, spans = _kept_leaf_params(
        bundle, compiled
    )
    if len(mapping) != len(module.entry_params):
        audit.violations.append(
            ProgramViolation(
                bundle.name, "donation",
                f"cannot align avals with entry parameters "
                f"({len(mapping)} kept leaves vs "
                f"{len(module.entry_params)} entry params) — auditor "
                f"assumption broken, treat as drift",
            )
        )
        return
    aliased_params = set(module.aliases.values())
    donated_bytes = aliased_bytes = 0
    donated_leaves = aliased_leaves = 0
    for arg_idx in d.donated_args:
        lo, hi = spans[arg_idx]
        for flat_idx in range(lo, hi):
            leaf = avals_flat[flat_idx]
            leaf_bytes = hlo_mod.shape_bytes(
                hlo_mod.hlo_dtype_name(leaf.dtype),
                tuple(int(x) for x in leaf.shape),
            )
            donated_leaves += 1
            donated_bytes += leaf_bytes
            p = mapping.get(flat_idx)
            if p is None:
                continue  # pruned (unused) donated leaf: jax frees it
            if p in aliased_params:
                aliased_leaves += 1
                aliased_bytes += module.entry_params[p].bytes
            else:
                audit.violations.append(
                    ProgramViolation(
                        bundle.name, "donation",
                        f"donated input leaf (arg {arg_idx}, "
                        f"{leaf.dtype}{list(leaf.shape)}) is NOT "
                        f"input/output-aliased in the compiled module — "
                        f"{leaf_bytes} B of HBM the donation was supposed "
                        f"to save",
                    )
                )
    if not d.donated_args and module.aliases:
        audit.violations.append(
            ProgramViolation(
                bundle.name, "donation",
                f"program declares no donation but the compiled module "
                f"aliases params {sorted(module.aliases.values())} — "
                f"donation semantics drifted",
            )
        )
    audit.donated_bytes = donated_bytes
    audit.donated_leaves = donated_leaves
    audit.aliased_bytes = aliased_bytes
    audit.aliased_leaves = aliased_leaves


def _flatten_with_none(tree):
    import jax

    return jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: x is None
    )[0]


def _audit_sharding(bundle, compiled, audit: ProgramAudit, out_shape) -> None:
    """Per-leaf actual sharding vs the declared spec: a declared-sharded
    leaf that compiles fully replicated silently costs (N-1)/N of its
    bytes on every device — the regression arxiv 2004.13336's mechanism
    exists to avoid.  ``out_shape`` is the output aval tree the tracing
    pass already produced (make_jaxpr return_shape — no re-trace)."""
    d = bundle.declared
    if d.sharding_in is None:
        return
    ins = compiled.input_shardings[0]
    audit.sharded_in_leaves = _check_shard_tree(
        bundle, "input", d.sharding_in, bundle.avals, ins, audit
    )
    if d.sharding_out is not None:
        audit.sharded_out_leaves = _check_shard_tree(
            bundle, "output", d.sharding_out, out_shape,
            compiled.output_shardings, audit,
        )


def _check_shard_tree(
    bundle, where, expected_tree, aval_tree, sharding_tree, audit
) -> int:
    expected = _flatten_with_none(expected_tree)
    shardings = _flatten_with_none(sharding_tree)
    avals = _flatten_with_none(aval_tree)
    if not (len(expected) == len(shardings) == len(avals)):
        # zip() truncation would silently audit a prefix — the exact
        # silent-replication blind spot this contract exists to close.
        audit.violations.append(
            ProgramViolation(
                bundle.name, "sharding",
                f"{where} trees misaligned: {len(expected)} declared vs "
                f"{len(shardings)} compiled shardings vs {len(avals)} "
                f"avals — auditor assumption broken, treat as drift",
            )
        )
        return 0
    sharded = 0
    for i, (exp_elems, sh, av) in enumerate(
        zip(expected, shardings, avals)
    ):
        if sh is None or exp_elems is None:
            continue  # pruned arg / skipped leaf
        shape = tuple(int(x) for x in av.shape)
        itemsize = hlo_mod.max_operand_itemsize(
            hlo_mod.hlo_dtype_name(av.dtype)
        )
        total = 1
        for x in shape:
            total *= x
        actual_elems = _shard_elems(sh, shape)
        if actual_elems < total:
            sharded += 1
        if actual_elems != exp_elems:
            wasted = (actual_elems - exp_elems) * itemsize
            detail = (
                f"silently replicated — wastes {wasted} B/device"
                if actual_elems == total and exp_elems < total
                else f"shard is {actual_elems} elements, declared {exp_elems}"
            )
            audit.violations.append(
                ProgramViolation(
                    bundle.name, "sharding",
                    f"{where} leaf {i} shape {list(shape)}: declared "
                    f"{exp_elems} elements/device but compiled to "
                    f"{actual_elems} — {detail}",
                )
            )
    return sharded


# --------------------------------------------------------------------------
# baseline: build / validate / compare (stdlib-only code paths)
# --------------------------------------------------------------------------


def build_baseline(audits: List[ProgramAudit]) -> dict:
    import jax

    return {
        "schema": PROGRAM_BASELINE_SCHEMA,
        "generated_by": "scripts/program_audit.py --update-baseline",
        "generated_at": time.time(),
        "generated_at_iso": time.strftime(
            "%Y-%m-%dT%H:%M:%S%z", time.localtime()
        ),
        "jax_version": jax.__version__,
        "devices": len(jax.devices()),
        "axis_size": AXIS_SIZE,
        # Structural fields are compared EXACTLY (a census is not a
        # timing); the tolerance block exists so the gate's policy is
        # recorded next to the data it governs, perf_gate-style.
        "tolerances": {"structural": 0},
        "programs": {a.name: a.baseline_entry() for a in audits},
    }


def validate_program_baseline(obj: object) -> List[str]:
    """Schema errors for a decoded program baseline (empty = valid).
    Stdlib-only: perf_gate --smoke calls this without importing jax."""
    errs: List[str] = []
    if not isinstance(obj, dict):
        return ["program baseline is not a JSON object"]
    if obj.get("schema") != PROGRAM_BASELINE_SCHEMA:
        errs.append(
            f"program baseline schema {obj.get('schema')!r} != "
            f"{PROGRAM_BASELINE_SCHEMA}"
        )
    programs = obj.get("programs")
    if not isinstance(programs, dict) or not programs:
        return errs + ["program baseline has no 'programs' table"]
    for name, entry in programs.items():
        if not isinstance(entry, dict) or "jaxpr" not in entry:
            errs.append(f"program {name!r}: entry missing 'jaxpr' block")
            continue
        jx = entry["jaxpr"]
        if not isinstance(jx.get("fences"), int):
            errs.append(f"program {name!r}: jaxpr.fences must be an int")
        if not isinstance(jx.get("census"), list):
            errs.append(f"program {name!r}: jaxpr.census must be a list")
        hl = entry.get("hlo")
        if hl is not None:
            for key in ("fences", "argument_bytes", "aliased_bytes"):
                if not isinstance(hl.get(key), int):
                    errs.append(
                        f"program {name!r}: hlo.{key} must be an int"
                    )
    return errs


def baseline_warnings(
    baseline: dict, max_age_days: float = 90.0,
    now: Optional[float] = None,
) -> List[str]:
    """Staleness/provenance warnings (perf_gate discipline — loud, never
    fatal).  Structural baselines age with the TOOLCHAIN, not the host:
    a jax upgrade can re-schedule collectives, so the stamp records the
    jax version and the warning fires on age or version drift."""
    warnings: List[str] = []
    now = time.time() if now is None else now
    generated_at = baseline.get("generated_at")
    if not isinstance(generated_at, (int, float)) or isinstance(
        generated_at, bool
    ):
        warnings.append(
            "program baseline has no generated_at stamp — regenerate with "
            "scripts/program_audit.py --update-baseline"
        )
    else:
        age_days = (now - float(generated_at)) / 86400.0
        if age_days > max_age_days:
            warnings.append(
                f"program baseline is {age_days:.1f} days old "
                f"(> {max_age_days:g}) — regenerate with --update-baseline"
            )
    recorded = baseline.get("jax_version")
    try:
        # metadata lookup, not `import jax`: perf_gate --smoke calls this
        # on every tier-1 run and must stay jax-import-free.
        from importlib.metadata import version

        current = version("jax")
    except Exception:
        current = None
    if current is not None and recorded not in (None, current):
        warnings.append(
            f"program baseline was generated under jax {recorded}, this "
            f"process runs {current} — XLA may schedule different "
            f"collectives; regenerate with --update-baseline"
        )
    return warnings


def compare_to_baseline(
    audit: ProgramAudit, entry: Optional[dict], fast: bool
) -> List[ProgramViolation]:
    """Drift between one audit and its committed baseline entry.  Exact
    comparison on every structural field; ``--fast`` compares the jaxpr
    block only."""
    out: List[ProgramViolation] = []
    if entry is None:
        out.append(
            ProgramViolation(
                audit.name, "census-drift",
                "program is not in the committed baseline — regenerate "
                "docs/analysis/program_baseline.json (--update-baseline)",
            )
        )
        return out
    jx = entry.get("jaxpr", {})
    for msg in hlo_mod.census_diff(
        jx.get("census", []), audit.jaxpr_census
    ):
        out.append(ProgramViolation(audit.name, "census-drift",
                                    f"jaxpr {msg}"))
    if jx.get("fences") != audit.jaxpr_fences:
        out.append(
            ProgramViolation(
                audit.name, "fence-survival",
                f"jaxpr fence count {audit.jaxpr_fences} != baseline "
                f"{jx.get('fences')}",
            )
        )
    if fast or audit.hlo_census is None:
        return out
    hl = entry.get("hlo")
    if hl is None:
        out.append(
            ProgramViolation(
                audit.name, "census-drift",
                "baseline has no hlo block for this program — regenerate "
                "with --update-baseline (full mode)",
            )
        )
        return out
    for msg in hlo_mod.census_diff(hl.get("census", []), audit.hlo_census):
        out.append(ProgramViolation(audit.name, "census-drift",
                                    f"hlo {msg}"))
    if (
        audit.hlo_fences is not None
        and audit.hlo_fences >= 0
        and isinstance(hl.get("fences"), int)
        and hl["fences"] >= 0
        and audit.hlo_fences != hl["fences"]
    ):
        out.append(
            ProgramViolation(
                audit.name, "fence-survival",
                f"optimized-HLO fence count {audit.hlo_fences} != baseline "
                f"{hl['fences']}",
            )
        )
    for fld, contract in (
        ("argument_bytes", "hbm-bytes"),
        ("output_bytes", "hbm-bytes"),
        ("aliased_bytes", "donation"),
        ("donated_bytes", "donation"),
        ("param_dtypes", "dtype-flow"),
        ("sharded_in_leaves", "sharding"),
        ("sharded_out_leaves", "sharding"),
    ):
        base_v, cur_v = hl.get(fld), getattr(audit, fld)
        if base_v is not None and cur_v is not None and base_v != cur_v:
            out.append(
                ProgramViolation(
                    audit.name, contract,
                    f"{fld} changed: baseline {base_v} -> {cur_v}",
                )
            )
    return out


# --------------------------------------------------------------------------
# injections (the auditor's own regression demonstrations)
# --------------------------------------------------------------------------


def build_injection(which: str) -> ProgramBundle:
    """A deliberately-violating bundle per injection class — the CLI's
    ``--inject`` demonstration that each contract actually fires, exit 1,
    naming program + op + contract (docs/ANALYSIS.md)."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    if which == "extra-collective":
        # An extra live psum smuggled around the real update program: the
        # census gains one all-reduce the closed form does not know.
        bundle = build_program("int8_simulate/update_step")
        mesh = _mesh_for(bundle.arm)
        base = bundle.fn
        extra = jax.shard_map(
            lambda x: lax.psum(x, "data"), mesh=mesh,
            in_specs=(P(),), out_specs=P(), check_vma=False,
        )

        @jax.jit
        def injected(params, opt_state, grads):
            p, o = base(params, opt_state, grads)
            leaves, treedef = jax.tree_util.tree_flatten(p)
            leaves[0] = leaves[0] + 1e-8 * extra(leaves[0])
            return jax.tree_util.tree_unflatten(treedef, leaves), o

        return replace(
            bundle, name="inject/extra-collective", fn=injected,
            declared=replace(bundle.declared, donated_args=()),
        )

    if which == "fp32-widen":
        # The fused wire really IS s8 now, so the widening regression is
        # demonstrated by tracing with the fusion disabled
        # (simulate_wire_dtype -> None: grad_sync falls back to the fp32
        # pmean spelling) while the declaration keeps the honest s8 —
        # exactly what a refactor that quietly reroutes the sync around
        # the fused path would look like.  jax resolves grad_sync's
        # module global at TRACE time, so the patch rides the bundle.
        import contextlib

        @contextlib.contextmanager
        def widened():
            from ddlpc_tpu.parallel import grad_sync

            real = grad_sync.simulate_wire_dtype
            grad_sync.simulate_wire_dtype = lambda axis_size, comp: None
            try:
                yield
            finally:
                grad_sync.simulate_wire_dtype = real

        bundle = build_program("int8_simulate/update_step")
        return replace(bundle, name="inject/fp32-widen", patch=widened)

    if which == "drop-fence":
        # Trace the update program with apply_codec_fenced neutered —
        # the "someone removed the barrier wrapper" regression.  jax
        # resolves the module global at TRACE time, so the patch rides
        # the bundle and audit_program holds it open while tracing.
        import contextlib

        @contextlib.contextmanager
        def unfenced():
            from ddlpc_tpu.parallel import grad_sync

            real = grad_sync.apply_codec_fenced
            grad_sync.apply_codec_fenced = (
                lambda fq, grads, compression, key=None: fq(
                    grads, compression, key=key
                )
            )
            try:
                yield
            finally:
                grad_sync.apply_codec_fenced = real

        bundle = build_program("int8_simulate/update_step")
        return replace(bundle, name="inject/drop-fence", patch=unfenced)

    if which == "replicated-leaf":
        # A leaf declared P('data') compiled fully replicated: audit the
        # REPLICATED update program against the sharded declaration.
        from ddlpc_tpu.parallel import shard_update as zero

        bundle = build_program("none_simulate/update_step")
        arm = bundle.arm
        cfg = _tiny_experiment(arm)
        mesh = _mesh_for(arm)
        _, tx, state = _abstract_state(cfg, mesh)
        spec = zero.opt_partition_specs(
            tx, state.params, "gspmd", "data", n_shards=AXIS_SIZE
        )
        opt_elems = _named_tree(mesh, spec, state.opt_state)
        params_elems = _repl_tree(state.params)
        declared = replace(
            bundle.declared,
            sharding_in=(params_elems, opt_elems, params_elems),
            sharding_out=(params_elems, opt_elems),
        )
        return replace(bundle, name="inject/replicated-leaf",
                       declared=declared)

    raise ValueError(
        f"unknown injection {which!r} (expected one of {INJECTIONS})"
    )
