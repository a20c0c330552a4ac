"""Fused quantized collectives + bucketed comm/compute overlap (ISSUE 18).

What must hold:

- bucket assignment (parallel/bucketing.py) is a pure, greedy, stable
  function of leaf byte sizes: oversized target -> one bucket; tiny
  target -> one leaf per bucket; uneven last bucket allowed; identical
  partition for every layout derived from the same tree;
- a bucket larger than the whole tree is BIT-IDENTICAL to the single
  whole-tree sync (the degenerate path short-circuits to the same trace);
- bucketed syncs stay replica-identical and within the codec's
  documented error bound of the exact mean (per-bucket scales are a
  declared, test-pinned deviation from the whole-tree scale);
- simulate_wire_dtype is the single source of truth for when the fused
  narrow-wire collective engages, mirrored by obs/comm.simulate_wire_row
  and the program auditor's declared wire dtype;
- the auditor's census counts the SAME bucket count in the replicated,
  ZeRO-1 and GSPMD layouts (scale-pmax counts / fence counts are linear
  in B);
- obs/comm.py accounts actual wire bytes in a dedicated stage="wire"
  counter row, distinct from the declared loss-model payload;
- scripts/perf_gate.py gates comm_fraction_overlapped and warns when
  the committed baseline predates edits to any measured-path module.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

from ddlpc_tpu.config import CompressionConfig
from ddlpc_tpu.obs import comm as obs_comm
from ddlpc_tpu.obs.registry import MetricsRegistry
from ddlpc_tpu.ops.quantize import quantization_error_bound
from ddlpc_tpu.parallel import bucketing
from ddlpc_tpu.parallel.grad_sync import (
    grad_bucket_groups,
    simulate_wire_dtype,
    sync_gradients,
    sync_gradients_scatter,
)

N_DEV = 8


# ---- bucket assignment: pure function of leaf sizes -------------------------


def test_assign_buckets_degenerate_and_oversized():
    sizes = [100, 200, 300]
    # bucket_mb <= 0 and a target larger than the whole tree both mean
    # "one bucket" — the single whole-tree collective of every prior PR.
    assert bucketing.assign_buckets(sizes, 0.0) == [0, 0, 0]
    assert bucketing.assign_buckets(sizes, -1.0) == [0, 0, 0]
    assert bucketing.assign_buckets(sizes, 1024.0) == [0, 0, 0]
    assert bucketing.bucket_count(sizes, 1024.0) == 1
    assert bucketing.assign_buckets([], 0.5) == []
    assert bucketing.bucket_count([], 0.5) == 1


def test_assign_buckets_one_leaf_per_bucket_and_uneven_tail():
    mib = int(bucketing.MIB)
    # Every leaf alone exceeds the target -> one bucket per leaf (a leaf
    # is never split).
    sizes = [2 * mib, 2 * mib, 2 * mib]
    assert bucketing.assign_buckets(sizes, 1.0) == [0, 1, 2]
    # Uneven tail: the last bucket holds whatever remains (under target).
    sizes = [mib, mib, mib // 2]
    assert bucketing.assign_buckets(sizes, 2.0) == [0, 0, 1]
    groups = bucketing.bucket_index_groups(sizes, 2.0)
    assert groups == [[0, 1], [2]]
    assert bucketing.bucket_count(sizes, 2.0) == 2


def test_assign_buckets_stable_and_contiguous():
    rng = np.random.default_rng(0)
    sizes = [int(s) for s in rng.integers(1, 500_000, size=40)]
    a1 = bucketing.assign_buckets(sizes, 0.25)
    a2 = bucketing.assign_buckets(list(sizes), 0.25)
    assert a1 == a2  # deterministic: same sizes -> same partition
    # Indices are contiguous from 0 and monotone in flatten order.
    assert a1[0] == 0
    for prev, cur in zip(a1, a1[1:]):
        assert cur in (prev, prev + 1)
    # Greedy invariant: every bucket except possibly a single-oversized-
    # leaf bucket stays <= target once it has one member.
    groups = bucketing.bucket_index_groups(sizes, 0.25)
    for g in groups:
        total = sum(sizes[i] for i in g)
        assert len(g) == 1 or total <= 0.25 * bucketing.MIB + max(
            sizes[i] for i in g
        )


def test_grad_bucket_groups_works_on_shape_structs():
    # Pure function of shapes: ShapeDtypeStructs (what the auditor and
    # trainer hand it) bucket identically to concrete arrays.
    tree = {
        "a": jax.ShapeDtypeStruct((256, 256), jnp.float32),
        "b": jax.ShapeDtypeStruct((64,), jnp.float32),
    }
    concrete = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tree)
    mb = (256 * 256 * 4) / bucketing.MIB  # first leaf exactly fills one
    assert grad_bucket_groups(tree, mb) == grad_bucket_groups(concrete, mb)
    assert len(grad_bucket_groups(tree, mb)) == 2


# ---- simulate_wire_dtype: the fused-path source of truth --------------------


def test_simulate_wire_dtype_pins():
    int8 = CompressionConfig(mode="int8")
    fp16 = CompressionConfig(mode="float16")
    assert simulate_wire_dtype(8, int8) == jnp.int8      # 8*10 <= 127
    assert simulate_wire_dtype(13, int8) == jnp.int16    # 130 > 127
    assert simulate_wire_dtype(8, fp16) == jnp.float16   # 800 <= 2048
    assert simulate_wire_dtype(20, fp16) == jnp.float16  # 2000 <= 2048
    assert simulate_wire_dtype(21, fp16) is None         # 2100 > 2048
    # No codec / no pre-reduce lattice / wrong transport -> fp32 stays.
    assert simulate_wire_dtype(8, CompressionConfig(mode="none")) is None
    assert simulate_wire_dtype(None, int8) is None
    assert (
        simulate_wire_dtype(
            8, CompressionConfig(mode="int8", quantize_local=False)
        )
        is None
    )
    assert (
        simulate_wire_dtype(
            8, CompressionConfig(mode="int8", transport="ring")
        )
        is None
    )
    # int8 sums past int16 too: refuse the fused path, keep exact fp32.
    assert (
        simulate_wire_dtype(
            40_000, CompressionConfig(mode="int8")
        )
        is None
    )


def test_simulate_wire_row_mirrors_grad_sync():
    rows = [
        (CompressionConfig(mode="int8"), ("s8", 1)),
        (CompressionConfig(mode="int8", int8_levels=100), ("s16", 2)),
        (CompressionConfig(mode="float16"), ("f16", 2)),
        (CompressionConfig(mode="none"), ("f32", 4)),
        (CompressionConfig(mode="int8", quantize_local=False), ("f32", 4)),
    ]
    for cfg, expect in rows:
        assert obs_comm.simulate_wire_row(cfg, 8) == expect


# ---- bucketed sync semantics on the 8-device mesh ---------------------------


def _run_sync(tree_per_dev, cfg, scatter=False, key=None):
    mesh = Mesh(np.array(jax.devices()[:N_DEV]), ("data",))
    if scatter:
        fn = functools.partial(
            sync_gradients_scatter,
            axis_name="data",
            compression=cfg,
            axis_size=N_DEV,
            key=key,
        )
    else:
        fn = functools.partial(
            sync_gradients,
            axis_name="data",
            compression=cfg,
            axis_size=N_DEV,
            key=key,
        )
    wrapped = jax.shard_map(
        fn, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False
    )
    return wrapped(tree_per_dev)


def _grad_tree(seed=0):
    rng = np.random.default_rng(seed)
    # Ragged leaf sizes: 257 not divisible by 8 exercises scatter padding;
    # several leaves so tiny bucket targets split them apart.
    return {
        "a": jnp.asarray(rng.normal(size=(N_DEV, 257)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(N_DEV, 3, 5)), jnp.float32),
        "c": jnp.asarray(rng.normal(size=(N_DEV, 33)), jnp.float32),
    }


@pytest.mark.parametrize("scatter", [False, True], ids=["allreduce", "scatter"])
@pytest.mark.parametrize("rounding", ["nearest", "stochastic"])
def test_oversized_bucket_bit_identical_to_single_sync(scatter, rounding):
    """bucket_mb larger than the whole tree must be the SAME program as
    bucket_mb=0 — one bucket, one collective, bit-for-bit."""
    tree = _grad_tree(1)
    key = jax.random.key(7) if rounding == "stochastic" else None
    base = CompressionConfig(mode="int8", rounding=rounding)
    big = CompressionConfig(mode="int8", rounding=rounding, bucket_mb=4096.0)
    out0 = _run_sync(tree, base, scatter=scatter, key=key)
    out1 = _run_sync(tree, big, scatter=scatter, key=key)
    for l0, l1 in zip(jax.tree.leaves(out0), jax.tree.leaves(out1)):
        np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))


@pytest.mark.parametrize("scatter", [False, True], ids=["allreduce", "scatter"])
def test_one_leaf_per_bucket_within_codec_bound(scatter):
    """A tiny target puts every leaf in its own bucket.  Per-bucket scales
    are the declared deviation from the whole-tree codec: the result is
    still replica-identical and within the documented per-stage error
    bound of the exact mean (each bucket's scale <= the global scale, so
    the whole-tree bound is an upper bound)."""
    tree = _grad_tree(2)
    cfg = CompressionConfig(mode="int8", bucket_mb=1e-6)
    sizes = [
        int(l.size // N_DEV) * 4 for l in jax.tree.leaves(tree)
    ]
    assert bucketing.bucket_count(sizes, cfg.bucket_mb) == len(sizes)
    out = _run_sync(tree, cfg, scatter=scatter)
    exact = jax.tree.map(lambda x: x.mean(axis=0, keepdims=True), tree)
    scale = max(float(jnp.abs(l).max()) for l in jax.tree.leaves(tree))
    # quantize_local + quantize_mean: one bound-sized error per stage.
    tol = 2 * quantization_error_bound(cfg) * scale + 1e-6
    for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(exact)):
        got = np.asarray(got)
        if scatter:
            # replica r holds chunk r of the chunk layout; compare just
            # the values each replica owns against its slice of the mean.
            flat = np.asarray(want).reshape(-1)
            per = got.shape[-1]
            for r in range(N_DEV):
                chunk = flat[r * per : (r + 1) * per]
                g = got[r].reshape(-1)[: chunk.size]
                np.testing.assert_allclose(g, chunk, atol=tol)
        else:
            # replica-identical, then within bound of the exact mean
            for r in range(1, N_DEV):
                np.testing.assert_array_equal(got[r], got[0])
            np.testing.assert_allclose(
                got[0], np.asarray(want)[0], atol=tol
            )


def test_ring_rejects_bucketing():
    cfg = CompressionConfig(mode="int8", transport="ring", bucket_mb=0.5)
    with pytest.raises(ValueError, match="bucket_mb"):
        sync_gradients({"w": jnp.ones((8,))}, "data", cfg, axis_size=8)


# ---- auditor census: same bucket count in every layout ----------------------


def test_census_counts_same_buckets_in_every_layout():
    """Satellite pin: replicated, ZeRO-2 and GSPMD derive their buckets
    from the same parameter tree, and the auditor can READ the bucket
    count back off each traced program — B scale pmaxes (replicated
    fused), 2B (ZeRO-2 fused + quantized mean), 2B fence pairs (GSPMD's
    per-bucket mean codec)."""
    from ddlpc_tpu.analysis import program as prog

    b_rep = prog.build_program("int8_bucketed/update_step")
    b_z1 = prog.build_program("fp16_bucketed_zero2/update_step")
    b_gs = prog.build_program("fp16_bucketed_gspmd/train_step")
    B = b_rep.declared.n_buckets
    assert B > 1  # the audit model + bucket_mb=0.02 actually buckets
    assert b_z1.declared.n_buckets == B
    assert b_gs.declared.n_buckets == B

    def f32_allreduce_count(census):
        return sum(
            int(r["count"])
            for r in census
            if r["kind"] == "all-reduce" and r["dtype"] == "f32"
        )

    a_rep = prog.audit_program(
        "int8_bucketed/update_step", fast=True, bundle=b_rep
    )
    assert a_rep.violations == [], [v.format() for v in a_rep.violations]
    # replicated fused: exactly one scalar scale pmax per bucket
    assert f32_allreduce_count(a_rep.jaxpr_census) == B
    # the grad payload itself rides the narrow wire, per bucket
    assert any(
        r["kind"] == "all-reduce" and r["dtype"] == "s8"
        for r in a_rep.jaxpr_census
    )

    a_z1 = prog.audit_program(
        "fp16_bucketed_zero2/update_step", fast=True, bundle=b_z1
    )
    assert a_z1.violations == [], [v.format() for v in a_z1.violations]
    # ZeRO-2 fused + quantized mean: two scale pmaxes per bucket, plus
    # the jaxpr-only dead grad-norm psum XLA DCEs (auditor declares it).
    assert f32_allreduce_count(a_z1.jaxpr_census) == 2 * B + 1
    assert any(
        r["kind"] == "reduce-scatter" and r["dtype"] == "f16"
        for r in a_z1.jaxpr_census
    )

    a_gs = prog.audit_program(
        "fp16_bucketed_gspmd/train_step", fast=True, bundle=b_gs
    )
    assert a_gs.violations == [], [v.format() for v in a_gs.violations]
    # GSPMD quantizes the mean per bucket inside one fence pair each,
    # plus the update fence pair: the fence count exposes B directly.
    assert a_gs.jaxpr_fences == 2 + 2 * B


# ---- obs/comm: the wire stage row -------------------------------------------


def test_comm_plan_wire_rows_and_bucket_scales():
    cfg = CompressionConfig(mode="int8")
    (row,) = obs_comm.comm_plan(1000, 1000, cfg, 8, "allreduce")
    assert row["wire_dtype"] == "s8"
    assert row["bytes_wire"] == 1000 + 4 == row["bytes_post"]
    (row4,) = obs_comm.comm_plan(
        1000, 1000, cfg, 8, "allreduce", n_buckets=4
    )
    assert row4["bytes_wire"] == 1000 + 4 * 4  # one scale per bucket
    # fp16: 2-byte wire; declared loss model and actual wire agree.
    (rowf,) = obs_comm.comm_plan(
        1000, 1000, CompressionConfig(mode="float16"), 8, "allreduce"
    )
    assert rowf["wire_dtype"] == "f16" and rowf["bytes_wire"] == 2004
    # No fused path -> fp32 wire even though the codec bytes are smaller.
    (rown,) = obs_comm.comm_plan(
        1000, 1000,
        CompressionConfig(mode="int8", quantize_local=False), 8, "allreduce",
    )
    assert rown["wire_dtype"] == "f32" and rown["bytes_wire"] == 4000
    # Scatter: the grad leg rides the wire dtype, the params publish is
    # fp32 by construction.
    rs, ag = obs_comm.comm_plan(1000, 1000, cfg, 8, "scatter")
    assert rs["wire_dtype"] == "s8" and rs["bytes_wire"] == 1004
    assert ag["wire_dtype"] == "f32" and ag["bytes_wire"] == 4000
    # Ring rows carry the REAL per-hop wire bytes (they always were the
    # actual wire), renamed into the same dtype lattice.
    (ring,) = obs_comm.comm_plan(
        1000, 1000, CompressionConfig(mode="int8", transport="ring"),
        8, "ring",
    )
    assert ring["wire_dtype"] == "s8"
    assert ring["bytes_wire"] == ring["bytes_post"]


def test_comm_accountant_wire_stage_counter():
    reg = MetricsRegistry()
    plan = obs_comm.comm_plan(
        1000, 1000, CompressionConfig(mode="int8"), 8, "allreduce",
        n_buckets=4,
    )
    acct = obs_comm.CommAccountant(reg, plan, "allreduce")
    acct.on_step(3)
    c = reg.get("ddlpc_comm_bytes_total")
    # Three stages, three distinct answers: fp32 in, declared loss-model
    # payload out, actual bytes on the wire (narrow lattice + 4 scales).
    assert c.value(
        collective="all_reduce", codec="int8", stage="pre_codec"
    ) == 3 * 4000
    assert c.value(
        collective="all_reduce", codec="int8", stage="post_codec"
    ) == 3 * 1016
    assert c.value(
        collective="all_reduce", codec="int8", stage="wire"
    ) == 3 * 1016
    rec = acct.publish()
    assert rec["all_reduce_wire_dtype"] == "s8"
    assert rec["all_reduce_bytes_wire_per_step"] == 1016


# ---- perf_gate: overlap arm + measured-path staleness -----------------------


def test_perf_gate_measured_path_staleness_warning(tmp_path):
    import perf_gate

    host = perf_gate.host_fingerprint()
    mod = tmp_path / "grad_sync.py"
    mod.write_text("# edited after the baseline was measured\n")
    mtime = os.path.getmtime(mod)
    now = mtime + 3600.0

    def baseline(generated_at):
        return {"generated_at": generated_at, "host": host}

    # Stamp newer than every measured-path edit: silent.
    assert (
        perf_gate.baseline_warnings(
            baseline(mtime + 100.0), 30.0, now=now, current_host=host,
            measured_paths=[str(mod)],
        )
        == []
    )
    # Stamp older than an edit: loud, names the module, says re-measure.
    (w,) = perf_gate.baseline_warnings(
        baseline(mtime - 100.0), 30.0, now=now, current_host=host,
        measured_paths=[str(mod)],
    )
    assert "predates changes" in w and "re-measure" in w
    # Vanished paths are skipped, not fatal (measured set can evolve).
    assert (
        perf_gate.baseline_warnings(
            baseline(mtime + 100.0), 30.0, now=now, current_host=host,
            measured_paths=[str(tmp_path / "gone.py"), str(mod)],
        )
        == []
    )
    # The repo's measured-path manifest points at real modules.
    files = perf_gate.measured_path_files()
    assert files and all(os.path.exists(p) for p in files)
    assert any(p.endswith("parallel/bucketing.py") for p in files)


def test_perf_gate_gates_comm_fraction_overlapped():
    """The committed baseline carries the overlap arm and an injected
    regression on it fails the gate BY NAME (satellite demo)."""
    import perf_gate

    assert "comm_fraction_overlapped" in perf_gate.GATED
    repo = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(repo, "docs", "perf", "baseline.json")) as f:
        baseline = json.load(f)
    assert "comm_fraction_overlapped" in baseline["metrics"]
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(repo, "scripts", "perf_gate.py"),
            "--inject-only",
            "--inject",
            "comm_fraction_overlapped=4.0",
        ],
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "comm_fraction_overlapped" in proc.stdout
