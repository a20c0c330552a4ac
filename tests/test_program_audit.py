"""Compiled-program contract auditor (analysis/{hlo,program}.py,
scripts/program_audit.py — docs/ANALYSIS.md "Program-level contracts").

Three layers:

- pure units on the HLO text walker and the baseline validators (no jax
  work at all);
- in-process jaxpr audits of the REAL update programs — the acceptance
  pin that the collective census matches ``obs/comm``'s closed form
  byte-for-byte on every codec × transport arm;
- subprocess runs of the CLI: the committed baseline is green in --fast
  mode, the ``kind="program"`` stream lints, and each of the four
  injected violations (extra collective, fp32 widen before the wire,
  dropped fence, silently replicated leaf) exits 1 naming program +
  contract.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from ddlpc_tpu.analysis import hlo as hlo_mod  # noqa: E402
from ddlpc_tpu.analysis import program as prog  # noqa: E402


# --------------------------------------------------------------------------
# HLO text walker units (no jax)
# --------------------------------------------------------------------------

# The text form jax 0.9.0 prints: operands by name, provenance through the
# module's stack-frame tables (trimmed from a real ``compiled.as_text()``).
_SAMPLE_HLO = """\
HloModule jit_step, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {}, may-alias) }, entry_computation_layout={(f32[7]{0}, f32[64,33]{1,0}, s8[16]{0})->(f32[7]{0}, f32[64,33]{1,0})}, num_partitions=8

FileNames
1 "/repo/ddlpc_tpu/parallel/grad_sync.py"
2 "/repo/ddlpc_tpu/parallel/compressed_allreduce.py"
3 "/repo/ddlpc_tpu/parallel/train_step.py"

FunctionNames
1 "sync_gradients"

FileLocations
1 {file_name_id=1 function_name_id=1 line=135 end_line=135 column=4 end_column=9}
2 {file_name_id=2 function_name_id=1 line=208 end_line=208 column=4 end_column=9}
3 {file_name_id=3 function_name_id=1 line=272 end_line=272 column=4 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}
3 {file_location_id=3 parent_frame_id=1}

%region_4.71 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b)
}

ENTRY %main.10 (p0: f32[7], p1: f32[64,33], p2: s8[16]) -> (f32[7], f32[64,33]) {
  %p0 = f32[7]{0} parameter(0)
  %p1 = f32[64,33]{1,0} parameter(1)
  %p2 = s8[16]{0} parameter(2)
  %a = f32[8,33]{1,0} bitcast(%p1)
  %all-reduce.3 = f32[64,33]{1,0} all-reduce(%p1), channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%region_4.71, metadata={op_name="jit(step)/psum" stack_frame_id=1}
  %opt-barrier.6 = (f32[6]{0}, f32[1,1,8,6]{3,2,1,0}, f32[16]{0}, f32[16]{0}, f32[16]{0}, /*index=5*/f32[16]{0}, f32[7]{0}) opt-barrier(%tuple.2)
  %collective-permute.1 = s8[16]{0} collective-permute(%p2), channel_id=3, source_target_pairs={{0,1},{1,2}}, metadata={op_name="jit(step)/ppermute" stack_frame_id=2}
  %all-gather.2 = f32[64,33]{1,0} all-gather(%a), channel_id=4, dimensions={0}, metadata={op_name="jit(step)/all_gather" stack_frame_id=3}
  ROOT %tuple.9 = (f32[7]{0}, f32[64,33]{1,0}) tuple(%p0, %all-reduce.3)
}
"""


def test_parse_hlo_module_header_and_ops():
    mod = hlo_mod.parse_hlo_module(_SAMPLE_HLO)
    # alias map: output 0 -> param 0, output 1 -> param 2
    assert mod.aliases == {(0,): 0, (1,): 2}
    assert [s.dtype for s in mod.entry_params] == ["f32", "f32", "s8"]
    assert mod.entry_params[1].bytes == 64 * 33 * 4
    assert mod.entry_params[2].bytes == 16
    assert [s.dtype for s in mod.entry_outputs] == ["f32", "f32"]
    # the tuple-shaped opt-barrier (with /*index=N*/ comments) parses
    assert mod.fence_count == 1
    ops = {op.name: op for op in mod.ops}
    ar = ops["all-reduce.3"]
    assert ar.opcode == "all-reduce"
    assert ar.source_file.endswith("grad_sync.py")
    assert ar.source_line == 135
    # operands are printed by name: the reference resolves to the shape
    # its computation defined (the region's own %a is a different scope)
    assert ar.operand_bytes == 64 * 33 * 4
    assert ops["all-gather.2"].operand_bytes == 8 * 33 * 4


def test_hlo_collective_census_groups_and_bytes():
    mod = hlo_mod.parse_hlo_module(_SAMPLE_HLO)

    def classify(op):
        base = os.path.basename(op.source_file)
        return "wire" if base == "grad_sync.py" else "aux"

    rows = {
        (r.kind, r.dtype, r.group): r
        for r in hlo_mod.hlo_collective_census(mod.ops, classify)
    }
    assert rows[("all-reduce", "f32", "wire")].bytes == 64 * 33 * 4
    assert rows[("collective-permute", "s8", "aux")].bytes == 16
    # all-gather counts RESULT bytes (the published tensor), not operand
    assert rows[("all-gather", "f32", "aux")].bytes == 64 * 33 * 4


def test_census_diff_names_what_changed():
    base = [
        {"kind": "all-reduce", "dtype": "f32", "group": "all",
         "count": 1, "elements": 100, "bytes": 400},
    ]
    cur = [
        {"kind": "all-reduce", "dtype": "f32", "group": "all",
         "count": 2, "elements": 100, "bytes": 400},
        {"kind": "all-gather", "dtype": "f32", "group": "all",
         "count": 1, "elements": 10, "bytes": 40},
    ]
    msgs = hlo_mod.census_diff(base, cur)
    assert any("count changed: baseline 1 -> 2" in m for m in msgs)
    assert any("new collective: all-gather[f32]" in m for m in msgs)
    assert hlo_mod.census_diff(base, base) == []


def test_shape_bytes_rejects_unknown_dtype():
    assert hlo_mod.shape_bytes("bf16", (8, 2)) == 32
    assert hlo_mod.shape_bytes("s8", (10,)) == 10
    with pytest.raises(ValueError):
        hlo_mod.shape_bytes("q3", (4,))


# --------------------------------------------------------------------------
# baseline validators (no jax)
# --------------------------------------------------------------------------


def _good_baseline():
    return {
        "schema": prog.PROGRAM_BASELINE_SCHEMA,
        "generated_at": 1e9,
        "jax_version": "0.4.37",
        "programs": {
            "a/update_step": {
                "jaxpr": {"census": [], "fences": 2},
                "hlo": {
                    "census": [], "fences": 2, "argument_bytes": 10,
                    "output_bytes": 4, "aliased_bytes": 4,
                    "donated_bytes": 4,
                },
            }
        },
    }


def test_validate_program_baseline_good_and_bad():
    assert prog.validate_program_baseline(_good_baseline()) == []
    assert prog.validate_program_baseline([]) != []
    bad = _good_baseline()
    bad["schema"] = 99
    assert any("schema" in e for e in prog.validate_program_baseline(bad))
    bad = _good_baseline()
    del bad["programs"]["a/update_step"]["jaxpr"]
    assert any("jaxpr" in e for e in prog.validate_program_baseline(bad))
    bad = _good_baseline()
    bad["programs"]["a/update_step"]["hlo"]["fences"] = "two"
    assert any("hlo.fences" in e for e in prog.validate_program_baseline(bad))


def test_baseline_warnings_staleness_and_version():
    b = _good_baseline()
    # fresh + matching version: no age warning expected
    b["generated_at"] = 2e9
    import importlib.metadata

    b["jax_version"] = importlib.metadata.version("jax")
    assert prog.baseline_warnings(b, max_age_days=90, now=2e9) == []
    # stale
    warns = prog.baseline_warnings(b, max_age_days=1, now=2e9 + 10 * 86400)
    assert any("days old" in w for w in warns)
    # toolchain drift
    b["jax_version"] = "0.0.1"
    warns = prog.baseline_warnings(b, max_age_days=10**6, now=2e9)
    assert any("jax 0.0.1" in w for w in warns)
    # missing stamp
    del b["generated_at"]
    warns = prog.baseline_warnings(b)
    assert any("generated_at" in w for w in warns)


def test_committed_baseline_is_valid_and_covers_registry():
    with open(prog.DEFAULT_BASELINE) as f:
        baseline = json.load(f)
    assert prog.validate_program_baseline(baseline) == []
    missing = set(prog.list_programs()) - set(baseline["programs"])
    assert not missing, f"baseline missing programs: {sorted(missing)}"
    # every entry carries the full-mode hlo block (regenerated full)
    for name, entry in baseline["programs"].items():
        assert "hlo" in entry, f"{name} baseline has no hlo block"


def test_expected_fences_matrix():
    f = lambda name, kind: prog.expected_fences(prog.ARMS[name], kind)
    assert f("none_simulate", "update_step") == 2   # _fenced_update only
    assert f("int8_simulate", "update_step") == 6   # local + mean + update
    assert f("fp16_zero2", "train_step") == 6       # scatter mean stage fenced
    assert f("int8_ring", "update_step") == 2       # ring owns its collective
    assert f("fp16_gspmd", "train_step") == 4       # one codec fence + update
    assert f("int8_simulate", "eval_step") == 0
    assert f("serve_int8", "serve_forward") == 0


# --------------------------------------------------------------------------
# in-process jaxpr audits: census == obs/comm closed form, all arms
# --------------------------------------------------------------------------

_UPDATE_PROGRAMS = sorted(
    n for n, (_, kind) in prog.PROGRAMS.items() if kind == "update_step"
)


@pytest.mark.parametrize("name", _UPDATE_PROGRAMS)
def test_update_census_matches_comm_closed_form(name):
    """The acceptance pin: for every codec × transport arm, the traced
    update program's collective census reconciles byte-for-byte with
    obs/comm.comm_plan (fences and dtype flow ride the same audit)."""
    audit = prog.audit_program(name, fast=True)
    assert audit.violations == [], [
        v.format() for v in audit.violations
    ]


def test_ring_census_bytes_are_ring_wire_report():
    """The ring arm's collective-permute bytes ARE ring_wire_report's
    wire_bytes_per_replica — the auditor reads them off the program, the
    report computes them from the algorithm; they must agree exactly."""
    from ddlpc_tpu.parallel.compressed_allreduce import ring_wire_report

    audit = prog.audit_program("int8_ring/update_step", fast=True)
    arm = prog.ARMS["int8_ring"]
    n_grad = [
        r for r in audit.jaxpr_census if r["kind"] == "collective-permute"
    ]
    assert len(n_grad) == 1
    rep = ring_wire_report(19366, prog.AXIS_SIZE, arm.compression())
    assert n_grad[0]["bytes"] == rep["wire_bytes_per_replica"]
    assert n_grad[0]["dtype"] == "s8"


def test_gspmd_zero1_train_step_builds_and_traces():
    """make_train_step_gspmd's shard path exposes build_for() so the
    auditor can lower the inner jit; the traced program carries the
    expected fences and no absolute violations."""
    audit = prog.audit_program("gspmd_zero1/train_step", fast=True)
    assert audit.jaxpr_fences == 2
    assert audit.violations == [], [v.format() for v in audit.violations]


def test_zero_leaf_spec_never_picks_uneven_dims():
    """Surfaced by this auditor: an uneven pick compiles into an
    in_shardings NamedSharding that jit REJECTS (a 6-class bias on a
    4-way mesh crashed at placement) — such leaves stay replicated."""
    from jax.sharding import PartitionSpec as P

    from ddlpc_tpu.parallel.shard_update import zero_leaf_spec

    assert zero_leaf_spec((6,), 4, "data") == P()
    assert zero_leaf_spec((8,), 4, "data") == P("data")
    assert zero_leaf_spec((6, 8), 4, "data") == P(None, "data")
    assert zero_leaf_spec((), 4, "data") == P()


def test_fence_canary_reports_expander_active_in_normal_process():
    """In a process compiled WITHOUT the barrier-expander disable flag
    (this test process), the canary must say HLO fences are NOT
    countable — the auditor then skips HLO fence comparison instead of
    reporting every fence as dropped."""
    prog._FENCE_CANARY.clear()
    try:
        assert prog.hlo_fences_countable() is False
    finally:
        prog._FENCE_CANARY.clear()


def test_drop_fence_injection_fires_in_process():
    bundle = prog.build_injection("drop-fence")
    audit = prog.audit_program(bundle.name, fast=True, bundle=bundle)
    assert any(v.contract == "fence-survival" for v in audit.violations)
    # and the patch was rolled back: the real program still audits clean
    clean = prog.audit_program("int8_simulate/update_step", fast=True)
    assert clean.violations == []


# --------------------------------------------------------------------------
# CLI subprocess: committed-baseline green, stream lint, injections exit 1
# --------------------------------------------------------------------------


def _run_cli(*args, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # The CLI owns its own XLA_FLAGS (device count + barrier expander);
    # drop the suite's so the subprocess decision is the one under test.
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "program_audit.py"),
         *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env,
    )


def test_cli_fast_check_green_and_stream_lints(tmp_path):
    out = tmp_path / "programs.jsonl"
    proc = _run_cli("--check", "--fast", "--out", str(out))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    from ddlpc_tpu.obs.schema import check_record

    records = [
        json.loads(line) for line in out.read_text().splitlines()
    ]
    assert records, "no kind='program' records emitted"
    for rec in records:
        assert check_record(rec) == [], rec
        assert rec["kind"] == "program"
    summary = records[-1]
    assert summary["record"] == "summary"
    assert summary["violations"] == 0
    assert summary["programs"] == len(prog.list_programs())


@pytest.mark.parametrize(
    "injection,contract",
    [
        ("extra-collective", "comm-closed-form"),
        ("fp32-widen", "dtype-flow"),
        ("drop-fence", "fence-survival"),
        ("replicated-leaf", "sharding"),
    ],
)
def test_injected_violation_exits_1_naming_program_and_contract(
    injection, contract
):
    proc = _run_cli("--inject", injection)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert f"VIOLATION inject/{injection}" in proc.stdout
    assert f"[{contract}]" in proc.stdout


@pytest.mark.slow
def test_cli_full_check_single_program_green():
    """One full-mode (jaxpr+HLO) program against the committed baseline:
    donation aliasing, sharding table, HLO census and counted fences all
    reconcile in a fresh process with the audit's own XLA flags."""
    proc = _run_cli("--check", "--programs", "int8_zero1/update_step")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "jaxpr+hlo" in proc.stderr


def test_cli_rejects_unknown_program():
    proc = _run_cli("--check", "--fast", "--programs", "nope/nothing")
    assert proc.returncode == 2
    assert "unknown program" in proc.stderr


def test_program_kind_registered():
    from ddlpc_tpu.obs.schema import KNOWN_KINDS

    assert "program" in KNOWN_KINDS


# --------------------------------------------------------------------------
# ddlpc-check --programs integration
# --------------------------------------------------------------------------


def _load_ddlpc_check():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ddlpc_check_cli_for_programs",
        os.path.join(REPO, "scripts", "ddlpc_check.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ddlpc_check_parses_program_violations(monkeypatch):
    """The --programs bridge folds `VIOLATION <program>: [<contract>]`
    lines from the audit subprocess into analyzer violations with the
    contract as the rule id — and a silent non-zero exit still fails."""
    mod = _load_ddlpc_check()

    class FakeProc:
        def __init__(self, stdout, rc):
            self.stdout, self.stderr, self.returncode = stdout, "", rc

    out = (
        "program_audit: VIOLATION int8_zero1/update_step: "
        "[fence-survival] jaxpr carries 2 fences, expected 6\n"
    )
    monkeypatch.setattr(
        mod.subprocess, "run", lambda *a, **k: FakeProc(out, 1)
    )
    vs = mod._run_program_audit(REPO, fast=True)
    assert len(vs) == 1
    assert vs[0].rule == "program-fence-survival"
    assert vs[0].path == "int8_zero1/update_step"
    assert "expected 6" in vs[0].message

    monkeypatch.setattr(
        mod.subprocess, "run", lambda *a, **k: FakeProc("boom", 2)
    )
    vs = mod._run_program_audit(REPO, fast=True)
    assert len(vs) == 1 and vs[0].rule == "program"


@pytest.mark.slow
def test_ddlpc_check_programs_flag_green_end_to_end():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "ddlpc_check.py"),
         "--programs", "--programs-fast"],
        capture_output=True, text=True, timeout=420, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
