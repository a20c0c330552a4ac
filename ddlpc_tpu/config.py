"""Typed configuration system.

The reference configures everything through module-level globals and
hostname→ID tables edited by hand on every node (кластер.py:23-25, 223-252,
685-687).  Here configuration is a tree of frozen dataclasses that serializes
to/from JSON, so a run is reproducible from one artifact.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Tuple


@dataclass(frozen=True)
class ModelConfig:
    """Model-zoo selection.

    ``width_divisor`` mirrors the reference's ``NN_in_model`` global channel
    divisor (кластер.py:625,687; value 2 → half-width U-Net).
    ``up_sample_mode`` mirrors UNet(..., up_sample_mode) (кластер.py:621).
    """

    name: str = "unet"  # any name registered in models/__init__.py
    num_classes: int = 6  # Vaihingen has 6 classes (кластер.py:702)
    width_divisor: int = 1
    features: Tuple[int, ...] = (64, 128, 256, 512, 512)
    bottleneck_features: int = 512
    up_sample_mode: str = "conv_transpose"  # conv_transpose | bilinear
    norm: str = "batch"  # batch | group | none
    group_norm_groups: int = 8
    # TPU-first stem: 'none' = reference-parity full-resolution first level;
    # 's2d' = space-to-depth by ``stem_factor`` at the input with a subpixel
    # (depth-to-space) logit head — same task geometry, ~2.6× faster on TPU
    # because early convs run at r²× the channel count on 1/r² the pixels
    # (models/layers.py:space_to_depth).
    stem: str = "none"  # none | s2d
    stem_factor: int = 2
    # Full-resolution residual refinement after the subpixel head
    # (models/layers.py:DetailHead): two full-res convs over concat(logits,
    # raw image) restore sub-stem_factor-px structure the 1/r pyramid cannot
    # carry (HardTiles stem A/B: plain s2d collapses the 2-6 px disc class).
    # Not cheap: 31 % of the step (102 of 328 ms flagship; ledger PR 25).
    detail_head: bool = False
    # Which refinement architecture detail_head selects:
    # - 'fullres': two 3×3 convs at FULL resolution over concat(full-res
    #   logits, raw image) — pixel-translation-equivariant; bound by HBM
    #   traffic on seven full-res tensors of 0.5-1.1 GB a micro-batch, not
    #   by arithmetic: its conv fusions run at 80-86 % of the HBM roofline
    #   (weight gradients 41 % and 60 %), 17 TFLOP/s (PERF.md §5, §6 PR 26);
    # - 's2d': the same residual refinement computed AT THE STEM GRID on the
    #   pre-d2s logits concat s2d(image) — channels (classes·r² + 3·r²) land
    #   in the MXU-efficient regime, weights are per-subpixel-phase (cell-
    #   level equivariance instead of pixel-level; strictly more parameters
    #   per FLOP), and no full-resolution activation exists in the head.
    detail_head_kind: str = "fullres"  # fullres | s2d
    # Hidden width of the refinement convs (round-3 shipped the only point
    # ever trained, 16; VERDICT r3 demanded the capacity sweep).
    detail_head_hidden: int = 16
    # Layout of the logits the model returns under train=True with an s2d
    # stem:
    # - 'fullres': [B,H,W,classes] logits before the loss, written by the
    #   subpixel head's one transposed conv (layers.py:subpixel_conv; no
    #   separate d2s since PR 26) — costs loss/metric reductions over a
    #   512² tensor;
    # - 'grouped': return the pre-d2s phase-major logits [B,H/r,W/r,r²·C];
    #   the train step groups the labels identically and computes the SAME
    #   per-pixel loss/metrics on the [..., r², C] view — bit-equal math
    #   (same multiset of (logit-row, label) pairs), no full-res tensor
    #   anywhere in the train graph.  Eval/predict always return full-res
    #   logits regardless.
    train_head_layout: str = "fullres"  # fullres | grouped
    # U-Net++ only: which logits the (shared) refinement head runs on.
    # - 'per_head': refine every deep-supervision head's logits (round-3
    #   behavior) — the refinement COMPUTE runs once per head, measured
    #   −43% throughput on the s2d×4 zoo row (678 → 383 tiles/s/chip);
    # - 'ensemble': supervision heads train unrefined; ONE refinement pass
    #   runs on the ensemble-mean readout, which joins the deep-supervision
    #   loss as an extra supervised output and is exactly the logits
    #   inference returns.  Refinement cost ×1 instead of ×(depth-1).
    detail_head_scope: str = "per_head"  # per_head | ensemble
    # Deep supervision heads for U-Net++.
    deep_supervision: bool = False
    # DeepLabV3+ specifics.
    output_stride: int = 16
    aspp_rates: Tuple[int, ...] = (6, 12, 18)
    compute_dtype: str = "bfloat16"  # dtype activations are computed in
    # Dtype of the logit head and the logits the model returns.  'float32'
    # is the conservative default; 'bfloat16' halves the HBM traffic of the
    # largest activation in the net ([B,H,W,C·r²] for subpixel heads and the
    # full-resolution logit upsample) — the loss/metrics cast to fp32 before
    # any softmax/reduction either way, so only logit *storage* rounds.
    head_dtype: str = "float32"  # float32 | bfloat16
    # The lfm2_moe family (models/lfm2_moe.py), under the published names of
    # its config.json; every other family ignores them, and a configuration
    # file that lacks them (the conv zoo's) loads as before.  The defaults are
    # LFM2-24B-A2B's published widths.  ``num_classes`` is the vocabulary
    # (slice) held here; ``num_experts`` is the router's width at any size,
    # ``experts_held``/``expert_offset`` say which of them this
    # expert-parallel rank computes (the others' part is left out);
    # ``layer_types`` is the operator of each layer kept, the first
    # ``num_dense_layers`` of which have a dense SwiGLU feed-forward.
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 64
    num_experts_per_tok: int = 4
    experts_held: int = 8
    expert_offset: int = 0
    num_dense_layers: int = 1
    layer_types: Tuple[str, ...] = ()
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    # How a token's scores over the experts are made from the router's
    # outputs (models/lfm2_moe.py:RoutedExperts): 'sigmoid' (lfm2_moe: each
    # expert on its own, the picked ones' sum normalised behind 1e-6) or
    # 'softmax' (keye_vl2: over all num_experts, renormalised over the picked
    # ones, no epsilon).
    router_score: str = "sigmoid"  # sigmoid | softmax
    # The keye_vl2 family (models/keye_vl2.py), under the published names of
    # its config.json (``sa_config`` flattened to ``indexer_*``); the defaults
    # leave every other family's files loading as before.  ``head_dim`` 0
    # means hidden_size / num_attention_heads; ``mrope_section`` () means one
    # position stream; an untied head has its own [num_classes, hidden] leaf.
    head_dim: int = 0
    mrope_section: Tuple[int, ...] = ()
    tie_word_embeddings: bool = True
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_num_kv_heads: int = 1
    indexer_topk: int = 2048
    # The olmo_hybrid family (models/olmo_hybrid.py), under the published names
    # of its config.json; the defaults are Olmo-Hybrid-7B's and leave every
    # other family's files loading as before.  ``layer_types`` holds
    # 'linear_attention' (Gated DeltaNet) and 'full_attention' (no rotary: the
    # family reads no ``rope_theta``).  ``tensor_shards`` is over how many
    # tensor-parallel ranks each layer is divided: a layer holds that share of
    # ``num_attention_heads``, of the ``linear_num_*_heads`` and of the
    # ``intermediate_size`` columns, all given at their published counts, and
    # computes its part of the two output sums; 1 is the uncut model.  Which
    # of the ranks a process is changes nothing it computes, so no key says.
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    tensor_shards: int = 1


@dataclass(frozen=True)
class DataConfig:
    """Tile-dataset pipeline.

    The reference eagerly loads a directory of images + ``.npy`` masks into
    RAM and crops 512×512 (кластер.py:660-674,737).  ``data_dir=None`` selects
    the synthetic generator (for tests/benchmarks without the ISPRS download).
    """

    data_dir: str | None = None
    dataset: str = "vaihingen"  # vaihingen | potsdam | cityscapes | synthetic
    image_size: Tuple[int, int] = (512, 512)  # (H, W)
    num_classes: int = 6
    test_split: int = 30  # last-N split, reference behavior (кластер.py:672-673)
    shuffle: bool = True  # reference computes a shuffle but never applies it (кластер.py:722-723)
    synthetic_len: int = 127  # reference trains on 127 tiles (кластер.py:720)
    seed: int = 0
    # > 0 switches to random-crop scene mode: the data_dir is read at native
    # scene size and each epoch draws this many image_size crops (the
    # many-crop generalization of the reference's fixed [:512,:512] crop,
    # кластер.py:817-823).  Evaluation uses a deterministic grid tiling of
    # the held-out scenes (capped at ``test_split`` tiles).
    crops_per_epoch: int = 0
    test_split_scenes: int = 1  # scenes held out for eval in crop mode
    # Fixed-tile mode: read tiles from disk per gather instead of stacking
    # the whole directory resident (~20 GB for full Cityscapes at
    # 512×1024).  The eval holdout stays eager (it is small by design and
    # prediction dumps need arrays).  Prefer prepare_* --format npy tiles
    # for decode-free reads; incompatible with device_cache.
    lazy_tiles: bool = False
    # Memory-map scene arrays instead of eager-loading them (crop mode
    # only): resident memory stays at the cropped pages, which is what
    # makes Potsdam-scale corpora (~25 GB eager) feasible.  Requires
    # array-format scenes (prepare_isprs.py --format npy); crops are
    # bit-identical to the eager path (tests/test_data.py).
    mmap_scenes: bool = False
    # Dihedral-group augmentation (4 rotations × optional flip) on training
    # tiles — standard for orientation-free aerial imagery; the reference
    # has none.  Requires square tiles; incompatible with device_cache
    # (augmentation happens in the host gather path).
    augment: bool = False
    # Ship bf16 images + int8 labels instead of fp32/int32 — through the
    # ShardedLoader host-upload path (44% of the wire bytes) or, under
    # device_cache, as the resident cache itself (44% of the cached HBM).
    # Numerically identical for this zoo's bf16-compute models — their
    # first conv casts inputs to bf16 regardless, and the loss clips/casts
    # labels itself (tests/test_data.py pins step-level bit-identity).
    # Requires num_classes <= 127.
    compact_upload: bool = False
    # Host-side threads for the ShardedLoader's gather/cast/upload
    # pipeline (SURVEY §7 hard part (c)): numpy's large copies/casts and
    # the device upload release the GIL, so >1 scales with cores on a pod
    # host.  Batch content and order are identical for any value.  NOTE:
    # the loader keeps max(prefetch, workers)+1 super-batches in flight
    # (workers below prefetch would idle), so workers above the default
    # prefetch=2 grow the number of UPLOADED batches resident in HBM —
    # budget accordingly on memory-tight configs.
    loader_workers: int = 1
    # Assemble super-batches with the native fused gather–cast–pack kernel
    # (csrc/batch.cc): one multithreaded memory pass writing straight into
    # the loader's preallocated buffer ring, instead of numpy's separate
    # single-threaded gather copy + astype copy + per-batch allocation.
    # Byte-identical to the numpy path (test-pinned).  When the kernel
    # cannot be built/loaded (no g++, no prebuilt csrc/libdwbatch.so) the
    # loader warns once and falls back to numpy — same discipline as the
    # wire codec.  ShardedLoader only; device_cache gathers on device.
    native_gather: bool = True
    # Upload the whole train set to HBM once and gather batches on device
    # (single-process, fixed-tile datasets that fit HBM — ISPRS scale is
    # ~0.5 GB).  Removes the per-epoch host→device re-upload, which on slow
    # host links costs more than the training compute (docs/PERF.md).
    device_cache: bool = False


@dataclass(frozen=True)
class TrainConfig:
    """Optimization loop.

    ``sync_period`` is the reference's ``frequency_sending_gradients``
    (кластер.py:685): micro-batches whose gradients are accumulated locally
    between synchronizations/optimizer steps.  ``micro_batch_size`` is the
    per-replica batch of one forward/backward (reference ``batch_size=1``,
    кластер.py:686).
    """

    epochs: int = 100
    micro_batch_size: int = 1
    sync_period: int = 50
    learning_rate: float = 1e-3  # torch.optim.Adam default, as the reference uses (кластер.py:704)
    optimizer: str = "adam"
    weight_decay: float = 0.0
    # Global-norm gradient clipping applied AFTER the cross-replica mean and
    # codec (every replica sees the identical gradient, so the clip factor
    # is identical too — replicated updates stay bit-identical).  0 = off,
    # the reference's behavior (no clipping anywhere).
    grad_clip_norm: float = 0.0
    # 'constant' (reference behavior: fixed default-LR Adam, кластер.py:704)
    # or 'cosine' (linear warmup over warmup_steps, cosine decay to 0 over
    # the run's total optimizer steps — the Trainer supplies the horizon).
    lr_schedule: str = "constant"
    warmup_steps: int = 0
    seed: int = 0
    log_every_steps: int = 1
    checkpoint_every_epochs: int = 1
    keep_checkpoints: int = 3
    # Checkpoint subsystem (train/checkpoint.py, docs/CHECKPOINTS.md).
    # checkpoint_async hands the write (chunk → compress → fsync → prune)
    # to a background thread so the next epoch overlaps the I/O; the
    # training thread pays only the host snapshot, with a barrier on the
    # next save/exit and writer failures re-raised on the training thread
    # (train/async_checkpoint.py).
    checkpoint_async: bool = True
    # 'chunked' streams per-leaf bounded chunks through the DWZ1 codec
    # (no whole-state bytes copy; parallel save AND restore);
    # 'monolithic' is the legacy single-msgpack-blob writer.  Both restore
    # through the same reader regardless of this knob.
    checkpoint_format: str = "chunked"  # chunked | monolithic
    checkpoint_chunk_mb: int = 4  # raw MB per compression/IO unit
    # 'adaptive' probes each chunk and STORES entropy-dense fp32 weights
    # (~memcpy speed) while still deflating compressible tensors;
    # 'always' deflates everything at the wire level; 'store' never
    # deflates (fastest, largest).
    checkpoint_compression: str = "adaptive"  # adaptive | always | store
    eval_every_epochs: int = 1
    dump_images_per_epoch: int = 5  # qualitative PNG triples (кластер.py:785-790)
    # Rematerialize each micro-batch's forward during backward
    # (jax.checkpoint): ~1/3 more FLOPs for much lower peak activation HBM,
    # buying larger micro-batches on memory-bound models.  Known limit: the
    # U-Net++ dense grid rematerialized at 512² full width crashes the TPU
    # compiler (graph size); U-Net/DeepLab remat compile and run fine.
    remat: bool = False
    # Epoch index to capture an XLA profiler trace for (into
    # <workdir>/profile); -1 disables.  Replaces the reference's wall-clock
    # print "tracing" (SURVEY §5).
    profile_epoch: int = -1
    # Failure detection (the reference hangs forever on a dead peer,
    # кластер.py:215-220; SURVEY §5 "fault handling: none").  > 0 arms a
    # watchdog thread: if no train-loop heartbeat for this many seconds, it
    # dumps all thread stacks to stderr + <workdir>/stall.log and, with
    # stall_action='abort', exits (status 42) so a supervisor restarts the
    # job — which resumes from the latest checkpoint.  Size it well above a
    # first-compile + slowest-step bound; 0 disables.
    stall_timeout_s: float = 0.0
    stall_action: str = "dump"  # dump | abort
    # Preemption-graceful shutdown (ddlpc_tpu/resilience, docs/RESILIENCE.md).
    # On SIGTERM the trainer finishes the in-flight step, writes an
    # emergency checkpoint (mid-epoch position recorded, so the resume
    # skip-replays to the exact step and stays bit-identical with an
    # uninterrupted run), drains telemetry, and exits with status 43 —
    # which a supervisor treats as a clean restartable exit.  This is the
    # grace window: if the graceful path has not finished within it, the
    # process hard-exits (the last durable checkpoint still resumes).
    preempt_grace_s: float = 30.0
    # Unified telemetry (ddlpc_tpu/obs, docs/OBSERVABILITY.md).
    # trace=True arms the span tracer: per-phase spans (data wait, step
    # dispatch, loader gather/cast/upload, checkpoint, eval) stream to
    # <workdir>/spans.jsonl and a Perfetto-loadable <workdir>/trace.json.
    # Off (the default) the tracer is a no-op costing one attribute test
    # per would-be span.
    trace: bool = False
    # While tracing, block_until_ready on the step output every K steps so
    # spans measure REAL step latency at a sampled cadence without draining
    # the async dispatch pipeline on every step.  0 = never sync.
    trace_sync_every_steps: int = 16
    # >= 0 starts a stdlib HTTP telemetry endpoint on this port (0 =
    # ephemeral, for tests): GET /metrics (Prometheus text or JSON by
    # Accept header), /healthz (+ recent health alerts), /debug/trace
    # (arms the on-demand profiler).  -1 = off.  Process 0 only.
    telemetry_port: int = -1
    # Steps per on-demand profiler capture (SIGUSR2 or /debug/trace
    # without an explicit ?steps=N); the capture ends with a device sync
    # and aggregates into <workdir>/top_ops_NNN.json (obs/profiling.py).
    profile_steps: int = 20
    # Performance accounting (obs/flops.py, obs/comm.py, obs/hbm.py;
    # docs/PERF.md "Accounting").  On, the trainer computes the per-step
    # conv FLOP model once at start (a jaxpr trace, no compute) and
    # publishes live ddlpc_mfu / ddlpc_goodput / ddlpc_hbm_bytes /
    # ddlpc_comm_bytes_total on the telemetry endpoint, plus per-epoch
    # kind="perf"/"comm" JSONL records (scripts/perf_report.py renders
    # them).  Traced runs additionally sample a fenced comm-time probe
    # once per epoch on the trace_sync cadence.  Steady-state cost is a
    # few counter updates per optimizer step (measured inside PR 6's
    # <=2% traced-step bar).
    perf_accounting: bool = True
    # Peak FLOP/s per device for the MFU denominator; 0 = look the device
    # kind up in obs/flops._PEAK_BY_DEVICE_KIND (an unknown accelerator
    # raises; only the CPU test meshes assume the v5e peak, flagged by
    # ddlpc_peak_flops_assumed=1).
    peak_flops_per_device: float = 0.0


@dataclass(frozen=True)
class ParallelConfig:
    """Device-mesh topology.

    Replaces the reference's L0–L4 socket stack (кластер.py:43-252): the data
    axis carries gradient all-reduce (the reference's parameter-server round
    trip), the space axis shards the spatial H dimension with halo exchange
    (the conv analog of sequence/context parallelism).
    ``data_axis_size=-1`` means "all remaining devices".
    """

    data_axis_size: int = -1
    space_axis_size: int = 1
    data_axis_name: str = "data"
    space_axis_name: str = "space"
    sync_batch_norm: bool = True  # reference lets BN stats drift per replica (SURVEY §3.1)
    # ZeRO cross-replica sharded update ladder (docs/SHARDING.md,
    # arxiv 2004.13336, 2204.06514).  Levels:
    # - 'zero1': full-mean all-reduce, then each replica updates only its
    #   1/N chunk of params+moments and all-gathers the fresh params.
    #   Optimizer-state HBM and update FLOPs divide by N; wire is 3·P per
    #   step.  Composes with EVERY codec transport (ring, pallas-mean).
    #   Trajectories match replicated to within FMA-contraction ulps — a
    #   declared, test-pinned deviation (parallel/train_step.py:
    #   _apply_update_zero1), far below any codec's quantization loss.
    # - 'zero2': the gradient sync IS a reduce-scatter (the fused wire
    #   already produces shards — zero2 stops all-gathering what it just
    #   scattered); gradients persist sharded 1/N, wire drops to 2·P.
    #   Bit-identical to the replicated update for every supported codec
    #   mode (test-pinned).  This is PR 5's program, renamed: what
    #   earlier revisions called "zero1" persisted scattered gradient
    #   shards and is ZeRO-2 in the paper's taxonomy.
    # - 'zero3': zero2, plus params persist as [N, K] chunks — the step
    #   all-gathers each leaf on demand for the forward/backward (freed
    #   after use) and never gathers at step end.  Params+grads+moments
    #   HBM all divide by N; the per-step params all-gather is the
    #   honest cost (bench.py --update-ab).  Bit-identical (test-pinned).
    # - 'auto' (default): 'zero2' for data meshes > 1, 'off' for
    #   singleton meshes and for the two codec combinations the scatter
    #   wire cannot reproduce bit-identically (transport='ring';
    #   codec_backend='pallas' with quantize_mean) — those compose with
    #   explicit 'zero1' instead.  'on' = 'zero2' but refuses those
    #   combinations loudly (parallel/shard_update.py:
    #   resolve_shard_update).  Checkpoints are layout-independent
    #   (always stored gathered); every layout restores from every
    #   other's blobs bit-identically.
    shard_update: str = "auto"  # auto | on | off | zero1 | zero2 | zero3


@dataclass(frozen=True)
class CompressionConfig:
    """Lossy gradient codec — the reference's research contribution.

    ``mode`` mirrors ``model_bytes`` ∈ {'float32','float16','int8'}
    (кластер.py:25).  int8 uses ±``int8_levels`` integer levels
    (round(g/max*10), кластер.py:474); float16 uses ±``fp16_levels`` integer
    levels stored as fp16 (round(g/max*100), кластер.py:487).  Unlike the
    reference, 'float32'/'none' is a working identity path (its fp32 branch
    zeroes gradients, кластер.py:315,432) and max==0 cannot crash
    (кластер.py:345-396 NameError).

    ``quantize_local``: quantize each replica's gradient before the
    all-reduce (the worker→server wire, кластер.py:450-496).
    ``quantize_mean``: re-quantize the averaged gradient after the all-reduce
    so every replica applies bit-identical updates (the server's re-quantized
    broadcast + self-application trick, кластер.py:328-433).

    ``transport`` selects how the all-reduce moves bytes:
    - 'simulate' (default): exact fp32 `lax.pmean` with the codec's
      information loss injected around it — fastest within an ICI slice,
      where XLA's native collective wins;
    - 'ring': hand-written `ppermute` ring reduce-scatter/all-gather that
      puts the QUANTIZED values on the wire (int8 hops for the reference's
      ±10-level codec on ≤12 replicas) — 4× fewer interconnect bytes, the
      TPU-native realization of the reference's compressed TCP transport
      for bandwidth-bound DCN meshes (parallel/compressed_allreduce.py).
      Implies quantize_local+quantize_mean semantics with a shared scale.
    """

    mode: str = "none"  # none | int8 | float16
    int8_levels: int = 10
    fp16_levels: int = 100
    quantize_local: bool = True
    quantize_mean: bool = True
    transport: str = "simulate"  # simulate | ring
    # 'nearest' is the reference's deterministic round() (кластер.py:474,487).
    # 'stochastic' rounds up with probability equal to the fractional part:
    # E[quantized] == gradient, so the codec adds variance but no bias — the
    # standard fix for coarse-grid (int8, ±10 levels) convergence drag, which
    # the committed A/B measured for nearest (docs/QUANTIZATION.md).  The
    # noise is keyed off (TrainConfig.seed, replicated step counter) —
    # decorrelated per replica for the local quantization, shared for the
    # mean — so replicas stay bit-identical, same-seed runs replay the same
    # noise, and different seeds draw different noise.
    rounding: str = "nearest"  # nearest | stochastic
    # Which implementation runs the quantize→dequantize element work on the
    # simulate transport: 'xla' (default — traces show XLA fuses it to
    # ~bandwidth already, docs/PERF.md) or 'pallas' (fused single-pass TPU
    # kernel with hardware-PRNG stochastic rounding, ops/pallas_quantize.py).
    # The ring transport keeps its own inlined formula either way.
    codec_backend: str = "xla"  # xla | pallas
    # Comm/compute overlap: split the gradient tree into size-targeted
    # buckets (MiB of fp32 gradient per bucket, greedy over flatten order —
    # parallel/bucketing.py) and issue each bucket's fused quantized
    # collective separately, so backward compute of earlier layers can
    # overlap sync of later ones (the standard DDP trick the paper's
    # 50-microbatch accumulation was approximating).  0 (default) keeps
    # today's single whole-tree sync — bit-identical to pre-bucketing
    # programs.  Buckets quantize with per-bucket scales at both loss
    # points; simulate transport only (the ring's flatten/concat transport
    # is inherently whole-tree and rejects bucket_mb > 0).
    bucket_mb: float = 0.0

    def __post_init__(self) -> None:
        if self.codec_backend == "pallas" and self.mode == "float16":
            # Measured on the chip (TPU v5e, libtpu 0.0.34): the encode
            # kernel's f32 -> f16 store fails to legalize in Mosaic
            # ('tpu.pack_subelements'); the int8/int16 wires compile.
            raise ValueError(
                "codec_backend='pallas' cannot run mode='float16': Mosaic "
                "on TPU v5e refuses the kernel's float16 wire store — use "
                "codec_backend='xla' for the fp16 codec, or mode='int8'"
            )


@dataclass(frozen=True)
class ServeConfig:
    """Inference serving engine (ddlpc_tpu/serve) — one artifact per deploy.

    Batching follows the dynamic micro-batching recipe from the serving
    literature (PAPERS.md: Gemma-on-TPU serving, pjit scaling): coalesce up
    to ``max_batch`` queued tiles or ``max_wait_ms`` from the oldest,
    whichever first.  ``queue_limit`` bounds admission — a submit beyond it
    is shed with a typed ``Overloaded`` error (fail fast, never queue
    unboundedly); ``deadline_ms`` expires requests that outlive their
    usefulness while queued (0 disables).
    """

    workdir: str = "runs/default"  # training run to restore + reload from
    host: str = "127.0.0.1"
    port: int = 8571
    max_batch: int = 8  # tiles coalesced into one forward
    max_wait_ms: float = 5.0  # max coalescing latency (coalesce batcher only)
    queue_limit: int = 64  # admission bound (tiles), then Overloaded
    deadline_ms: float = 2000.0  # per-request queue deadline; 0 = none
    # Admission loop (serve/cbatch.py vs serve/batching.py).
    # 'continuous' (default): ``slots`` worker threads each dispatch
    # whatever is queued the moment they free — no coalescing timer;
    # batching emerges from busy slots, and a freed slot refills the
    # device pipeline without draining.  'coalesce' is PR 1's
    # single-worker coalesce-and-wait MicroBatcher.
    batcher: str = "continuous"  # continuous | coalesce
    slots: int = 2  # concurrent in-flight forwards (continuous batcher)
    # Priority classes (continuous batcher): bulk tiling work
    # (?priority=batch) gets its own deep admission queue and a
    # starvation bound — at least one batch-class item is seated every
    # ``starvation_every``-th assembly — so it queues or sheds without
    # touching interactive p99.
    batch_queue_limit: int = 256  # bulk-class admission bound (tiles)
    starvation_every: int = 4
    # Weight quantization for the restored params (serve/quantized.py):
    # 'bf16' (shipped default — ½ the param HBM, within noise of fp32 on
    # the hard task) | 'int8' (¼ the HBM, per-leaf max-abs scales,
    # within 1 mIoU point on the hard task) | 'off'.  Scales are
    # computed once per restore/reload; dequant is fused into the jitted
    # forward (docs/SERVING.md "Continuous batching & quantized
    # inference").
    quantize: str = "bf16"  # off | int8 | bf16
    # Additionally cast input activations to bf16 inside the compiled
    # forward.  Off by default; enable only where the committed hard-task
    # table holds for your model (docs/SERVING.md).
    quantize_activations: bool = False
    overlap: float = 0.25  # sliding-window overlap for full scenes
    metrics_window: int = 2048  # latency ring size for p50/p95/p99
    metrics_every_s: float = 10.0  # periodic JSONL snapshot cadence; 0 = off
    # Span tracer for the request path (enqueue → coalesce → jit execute →
    # stitch): spans stream to <workdir>/serve_spans.jsonl and a Perfetto
    # trace to <workdir>/serve_trace.json (docs/OBSERVABILITY.md).
    trace: bool = False
    # Default batched forwards per /debug/trace profiler capture.
    profile_steps: int = 8
    # Graceful SIGTERM shutdown: max seconds to wait for in-flight HTTP
    # requests to finish writing their responses before exiting anyway.
    drain_timeout_s: float = 30.0
    # Where serve_metrics.jsonl (and traces) land; "" = workdir.  The
    # fleet gives each replica its own dir so N processes never interleave
    # one JSONL stream.
    metrics_dir: str = ""

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "ServeConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - fields
        if unknown:
            raise ValueError(
                f"unknown config key ServeConfig.{sorted(unknown)[0]}"
            )
        return cls(**d)

    @classmethod
    def from_json(cls, s: str) -> "ServeConfig":
        return cls.from_dict(json.loads(s))

    def replace(self, **kwargs) -> "ServeConfig":
        return dataclasses.replace(self, **kwargs)


@dataclass(frozen=True)
class FleetConfig:
    """Fault-tolerant serving fleet (serve/fleet.py + serve/router.py).

    One router process supervises ``replicas`` engine subprocesses (each a
    ``python -m ddlpc_tpu.serve.server`` on an ephemeral port) and
    dispatches tiles by per-replica health and occupancy, with per-request
    timeout → retry-on-another-replica (full-jitter backoff), hedged
    requests for the tail, and a per-replica circuit breaker.  Rolling
    hot-reload pushes a new checkpoint replica-by-replica
    (drain → /reload → warmup → readmit) and falls back fleet-wide if any
    replica's reload quarantines the blob (docs/SERVING.md "Fleet").
    """

    workdir: str = "runs/default"  # training run every replica serves
    # Router/supervisor state dir (replica logs + port files, router.jsonl);
    # "" = <workdir>/fleet.
    fleet_dir: str = ""
    host: str = "127.0.0.1"
    port: int = 8570  # router HTTP port (0 = ephemeral)
    replicas: int = 3
    # Per-replica serve knobs, forwarded into each replica's ServeConfig.
    max_batch: int = 8
    max_wait_ms: float = 5.0
    queue_limit: int = 64
    deadline_ms: float = 2000.0
    overlap: float = 0.25
    batcher: str = "continuous"  # continuous | coalesce (serve/cbatch.py)
    slots: int = 2
    batch_queue_limit: int = 256
    starvation_every: int = 4
    quantize: str = "bf16"  # off | int8 | bf16 (serve/quantized.py)
    quantize_activations: bool = False
    # Router-side bulk shedding: when EVERY eligible replica's scraped
    # interactive queue depth is at or above this, ?priority=batch
    # requests are shed with a 503 at the router (interactive traffic is
    # never shed by this rule).  0 disables.
    batch_shed_queue_depth: int = 0
    # Bounded wait at admission when ZERO replicas are eligible: a
    # rolling reload's drain→readmit hand-off, a relaunch-readiness gap,
    # and a breaker cooldown can momentarily coincide — transient
    # total-outage blips that should surface as tail latency, not
    # client-visible 503s.  A request that still finds no replica after
    # this wait gets the 503.  0 = fail fast.
    no_replica_wait_ms: float = 1000.0
    # Dispatch: per-attempt replica timeout; a timed-out/failed attempt
    # retries on a DIFFERENT replica up to ``retries`` times with
    # full-jitter backoff; after ``hedge_ms`` without a response a
    # duplicate is hedged to a second replica (first answer wins, the
    # loser is cancelled).  0 disables hedging.
    request_timeout_ms: float = 4000.0
    retries: int = 2
    retry_backoff_ms: float = 25.0
    hedge_ms: float = 1000.0
    hedge_max: int = 1
    # Per-replica circuit breaker: error rate over the last
    # ``breaker_window`` outcomes (once ``breaker_min_samples`` seen)
    # >= ``breaker_error_rate`` opens the circuit; after
    # ``breaker_cooldown_s`` it half-opens and admits
    # ``breaker_half_open_probes`` probes; ``breaker_close_after``
    # consecutive probe successes re-close it, any probe failure re-opens.
    breaker_window: int = 16
    breaker_min_samples: int = 8
    breaker_error_rate: float = 0.5
    breaker_cooldown_s: float = 2.0
    breaker_half_open_probes: int = 1
    breaker_close_after: int = 2
    # Health scraping (one cheap /healthz per replica per interval):
    # ``unhealthy_after`` consecutive failed scrapes take a replica out of
    # dispatch until a scrape succeeds again.
    scrape_every_s: float = 1.0
    scrape_timeout_s: float = 2.0
    unhealthy_after: int = 3
    # Drain / rolling reload.
    drain_timeout_s: float = 30.0
    warmup_timeout_s: float = 180.0  # replica readiness deadline per (re)launch
    # Replica supervision (resilience/supervisor.py RestartPolicy).
    max_restarts: int = 100
    crash_loop_limit: int = 3
    backoff_base_s: float = 0.5
    backoff_cap_s: float = 30.0
    metrics_every_s: float = 10.0  # router.jsonl snapshot cadence; 0 = off
    # Distributed tracing (docs/OBSERVABILITY.md "Distributed tracing"):
    # the router mints one W3C-style trace id per request, records
    # route_request/router_attempt spans to <fleet_dir>/router_spans.jsonl,
    # and forwards the context to the replica on the traceparent header;
    # replicas (which inherit this knob via replica_serve_config) stamp it
    # into their serve_request spans, so obs/merge.py can stitch the
    # per-process streams into ONE fleet timeline.
    trace: bool = False
    # Fleet telemetry aggregation (obs/aggregate.py): the front end
    # scrapes every replica's /metrics (plus the router's own registry)
    # every ``aggregate_every_s`` into ddlpc_fleet_* rollups on the fleet
    # /metrics; a source whose last successful scrape is older than
    # ``aggregate_stale_after_s`` is flagged stale and dropped from the
    # rollups (its last per-replica series stay visible).  0 = off.
    aggregate_every_s: float = 2.0
    aggregate_stale_after_s: float = 15.0
    # SLO layer (obs/health.py:SLOTracker): a routed request is GOOD when
    # it succeeds (no 5xx) within its class's latency objective; the
    # availability objective says what fraction must be good.  Burn-rate
    # alerts fire on two windows (fast = page-grade outage, slow = budget
    # leak), latched like every other health detector; error budgets and
    # burn rates ride the fleet /healthz and kind="slo" records on
    # router.jsonl.
    slo_enabled: bool = True
    slo_interactive_p99_ms: float = 1000.0  # latency objective per class
    slo_batch_p99_ms: float = 10000.0
    slo_availability: float = 0.999  # good-request fraction objective
    slo_budget_window_s: float = 3600.0  # error-budget accounting window
    slo_fast_window_s: float = 300.0
    slo_fast_burn: float = 14.0  # burn-rate threshold (critical)
    slo_slow_window_s: float = 3600.0
    slo_slow_burn: float = 2.0  # burn-rate threshold (warn)
    # Elastic fleet (serve/autoscale.py; docs/SERVING.md "Elastic fleet"):
    # a policy loop scales the replica count between the min/max bounds on
    # SLO burn rate, interactive queue depth, and slot-busy fraction, with
    # a cooldown between actions so it never flaps.  Scale-up triggers
    # when ANY high-water mark is crossed; scale-down requires EVERY
    # signal under its low-water mark (and burn rate < 1.0).
    autoscale_enabled: bool = False
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 8
    autoscale_interval_s: float = 2.0  # policy evaluation cadence
    autoscale_cooldown_s: float = 30.0  # min seconds between actions
    autoscale_burn_threshold: float = 2.0  # interactive fast-window burn
    autoscale_queue_depth_high: float = 8.0  # mean interactive queue/replica
    autoscale_queue_depth_low: float = 1.0
    autoscale_slot_busy_high: float = 0.85  # max replica slot-busy fraction
    autoscale_slot_busy_low: float = 0.30
    # Content-addressed response cache (serve/cache.py): the router
    # answers repeated tiles from memory, keyed by sha256(input bytes +
    # serving step + quant mode), LRU-bounded by payload bytes and
    # invalidated fleet-wide whenever the serving step changes.  0 = off;
    # ?cache=bypass skips it per request.
    cache_max_bytes: int = 0

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "FleetConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - fields
        if unknown:
            raise ValueError(
                f"unknown config key FleetConfig.{sorted(unknown)[0]}"
            )
        return cls(**d)

    @classmethod
    def from_json(cls, s: str) -> "FleetConfig":
        return cls.from_dict(json.loads(s))

    def replace(self, **kwargs) -> "FleetConfig":
        return dataclasses.replace(self, **kwargs)

    def resolved_fleet_dir(self) -> str:
        return self.fleet_dir or os.path.join(self.workdir, "fleet")

    def replica_serve_config(self, metrics_dir: str = "") -> "ServeConfig":
        """The ServeConfig one replica subprocess runs with."""
        return ServeConfig(
            workdir=self.workdir,
            host=self.host,
            port=0,  # ephemeral; the supervisor reads the port file
            max_batch=self.max_batch,
            max_wait_ms=self.max_wait_ms,
            queue_limit=self.queue_limit,
            deadline_ms=self.deadline_ms,
            overlap=self.overlap,
            batcher=self.batcher,
            slots=self.slots,
            batch_queue_limit=self.batch_queue_limit,
            starvation_every=self.starvation_every,
            quantize=self.quantize,
            quantize_activations=self.quantize_activations,
            drain_timeout_s=self.drain_timeout_s,
            metrics_dir=metrics_dir,
            # Trace context crosses the process boundary only if the
            # replica traces too (spans land in ITS metrics_dir).
            trace=self.trace,
        )


# Keys that a past revision's Trainer wrote into ``<workdir>/config.json``
# (it writes every field) and a later revision removed, per config class:
# key -> (the values under which the removed code did nothing, or None where
# it did nothing at any value; what was removed).  A run directory written
# with an inert value still loads; any other value asked for behaviour that
# no longer exists and is refused by name.
_PIPELINE = "the host-driven pipeline, removed in PR 29"
_RETIRED_KEYS: dict[str, dict[str, tuple[Any, str]]] = {
    "ParallelConfig": {
        "pipeline_stages": ((1,), _PIPELINE),
        # Both were read only at pipeline_stages > 1, which is refused above.
        "pipeline_microbatches": (None, _PIPELINE),
        "pipe_axis_name": (None, _PIPELINE),
    },
}


def _drop_retired_key(class_name: str, key: str, value: Any) -> bool:
    """True where ``key`` is a retired key of ``class_name`` holding an inert
    value (the caller drops it); raises where it holds any other value;
    False where the key was never retired."""
    retired = _RETIRED_KEYS.get(class_name, {}).get(key)
    if retired is None:
        return False
    inert, what = retired
    if inert is not None and value not in inert:
        raise ValueError(
            f"config key {class_name}.{key}={value!r} belongs to {what}: "
            f"only {' / '.join(repr(v) for v in inert)} (under which it did "
            f"nothing) still loads"
        )
    return True


@dataclass(frozen=True)
class ExperimentConfig:
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    compression: CompressionConfig = field(default_factory=CompressionConfig)
    workdir: str = "runs/default"

    # ---- (de)serialization -------------------------------------------------

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "ExperimentConfig":
        def build(klass, sub):
            fields = {f.name: f for f in dataclasses.fields(klass)}
            kwargs = {}
            for k, v in sub.items():
                if k not in fields:
                    if _drop_retired_key(klass.__name__, k, v):
                        continue
                    raise ValueError(f"unknown config key {klass.__name__}.{k}")
                if isinstance(v, list):
                    v = tuple(v)
                kwargs[k] = v
            return klass(**kwargs)

        return cls(
            model=build(ModelConfig, d.get("model", {})),
            data=build(DataConfig, d.get("data", {})),
            train=build(TrainConfig, d.get("train", {})),
            parallel=build(ParallelConfig, d.get("parallel", {})),
            compression=build(CompressionConfig, d.get("compression", {})),
            workdir=d.get("workdir", "runs/default"),
        )

    @classmethod
    def from_json(cls, s: str) -> "ExperimentConfig":
        return cls.from_dict(json.loads(s))

    def replace(self, **kwargs) -> "ExperimentConfig":
        return dataclasses.replace(self, **kwargs)
