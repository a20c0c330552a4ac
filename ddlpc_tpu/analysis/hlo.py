"""Walkers over compiled XLA programs: optimized-HLO text and jaxprs.

The compiled-program auditor (``analysis/program.py``, docs/ANALYSIS.md
"Program-level contracts") needs to *read* what XLA actually emitted —
which collectives run, what dtype feeds them, whether the
``optimization_barrier`` fences survived, which buffers were
input/output-aliased — without depending on XLA protobuf bindings.  This
module owns the two read paths:

- **HLO text** (:func:`parse_hlo_module`) — ``jit(f).lower(...).compile()
  .as_text()`` is stable, line-oriented HLO: one instruction per line,
  shapes spelled ``f32[64,33]{1,0}``, per-op ``metadata={...
  stack_frame_id=N}`` tracing each op — through the module's stack-frame
  tables — back to the Python that built it, operands referenced by
  name, and the module header carrying ``input_output_alias``
  (the donation ground truth) and ``entry_computation_layout``.  The
  parser extracts exactly what the auditor consumes — opcodes, result/
  operand shapes with byte sizes, source attribution, aliasing — and
  nothing else, so it does not pretend to be a full HLO grammar.

- **jaxpr** (:func:`jaxpr_collectives`, :func:`jaxpr_fence_count`) — the
  pre-lowering census for the ``--fast`` tier-1 mode: collective
  primitives and barrier equations counted straight off the traced
  program (``obs/flops.iter_eqns`` recursion, so scan/remat/shard_map
  bodies are included), no XLA compile paid.

Stdlib tier (analysis/tiers.py): pure text/structure walking; the jaxpr
helpers receive already-traced jaxpr objects and only touch their public
``eqns``/``avals`` attributes, so importing this module never pays jax.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

from ddlpc_tpu.obs.flops import iter_eqns

# Bits per element for the HLO primitive types the repo's programs emit.
# (s4/u4 exist upstream but no program here produces them; unknown dtypes
# fail loudly in shape_bytes rather than silently counting zero.)
DTYPE_BITS: Dict[str, int] = {
    "pred": 8,
    "s8": 8, "u8": 8,
    "s16": 16, "u16": 16, "f16": 16, "bf16": 16,
    "s32": 32, "u32": 32, "f32": 32,
    "s64": 64, "u64": 64, "f64": 64,
    "c64": 64, "c128": 128,
}

# HLO opcodes that move bytes between replicas.  Async forms (``-start``)
# are normalized to the base opcode; their ``-done`` halves carry no
# payload and are skipped.
COLLECTIVE_OPCODES = frozenset(
    {
        "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
        "collective-permute", "collective-broadcast",
    }
)
_ASYNC_SUFFIX = "-start"
_ASYNC_DONE = frozenset(
    c + "-done" for c in COLLECTIVE_OPCODES
) | frozenset({"all-reduce-done", "collective-permute-done"})

# jaxpr collective primitive -> HLO opcode family.  ``pmean`` is not a
# primitive (psum + divide); ``pmax``/``pmin`` lower to all-reduce with a
# max/min computation.
JAXPR_COLLECTIVES: Dict[str, str] = {
    "psum": "all-reduce",
    "pmax": "all-reduce",
    "pmin": "all-reduce",
    "all_gather": "all-gather",
    "reduce_scatter": "reduce-scatter",  # lax.psum_scatter's primitive
    "ppermute": "collective-permute",
    "pbroadcast": "collective-broadcast",
    "all_to_all": "all-to-all",
}

FENCE_PRIMITIVE = "optimization_barrier"
FENCE_OPCODE = "opt-barrier"


# --------------------------------------------------------------------------
# shapes
# --------------------------------------------------------------------------


_SHAPE_RE = re.compile(r"(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")


@dataclass(frozen=True)
class Shape:
    dtype: str
    dims: Tuple[int, ...]

    @property
    def elements(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n

    @property
    def bytes(self) -> int:
        return shape_bytes(self.dtype, self.dims)


def shape_bytes(dtype: str, dims: Tuple[int, ...]) -> int:
    """Payload bytes of one dense array shape."""
    if dtype in ("token", "opaque"):
        return 0
    bits = DTYPE_BITS.get(dtype)
    if bits is None:
        raise ValueError(f"unknown HLO element type {dtype!r}")
    n = 1
    for d in dims:
        n *= d
    return (n * bits) // 8


def parse_shapes(text: str) -> List[Shape]:
    """Every array shape spelled in ``text`` (tuple shapes contribute one
    entry per element)."""
    out = []
    for m in _SHAPE_RE.finditer(text):
        dims = tuple(int(d) for d in m.group("dims").split(",") if d)
        out.append(Shape(m.group("dtype"), dims))
    return out


# --------------------------------------------------------------------------
# HLO instruction parsing
# --------------------------------------------------------------------------


# Result shapes are either one array (`f32[64,33]{1,0}`) or a tuple
# (`(f32[6]{0}, /*index=5*/f32[16]{0}, ...)`) — tuple bodies never nest
# parens but DO carry `/*index=N*/` comments, so match on non-paren
# content, not on "no '='".
_INSN_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\([^()]*\)|[a-z]+\d*\[[\d,]*\](?:\{[^}]*\})?)\s+"
    r"(?P<opcode>[\w\-]+)\("
)
_META_RE = re.compile(
    r'metadata=\{[^}]*?op_name="(?P<op_name>[^"]*)"'
    r"(?:[^}]*?stack_frame_id=(?P<frame>\d+))?"
)
_OPERAND_REF_RE = re.compile(r"%([\w.\-]+)")
# The module-level provenance index jax 0.9.0 prints in place of per-op
# source_file/source_line: three numbered tables, one row per line.
_FILE_NAME_RE = re.compile(r'^(\d+) "(.*)"$')
_FILE_LOCATION_RE = re.compile(
    r"^(\d+) \{file_name_id=(\d+) function_name_id=\d+ line=(\d+)"
)
_STACK_FRAME_RE = re.compile(r"^(\d+) \{file_location_id=(\d+) ")
_ALIAS_ENTRY_RE = re.compile(
    r"\{(?P<out>[\d,\s]*)\}:\s*\((?P<param>\d+),\s*\{(?P<pidx>[\d,\s]*)\},"
    r"\s*(?P<kind>may-alias|must-alias)\)"
)


def _brace_block(text: str, marker: str) -> str:
    """The ``{...}`` block (content only) following ``marker=``, matched by
    brace depth — header attributes nest braces (shape layouts, alias
    entries), so regex-to-first-close is wrong."""
    start = text.find(marker + "={")
    if start < 0:
        return ""
    i = text.index("{", start)
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "{":
            depth += 1
        elif text[j] == "}":
            depth -= 1
            if depth == 0:
                return text[i + 1 : j]
    return text[i + 1 :]


@dataclass
class HloOp:
    """One HLO instruction: opcode + result/operand shapes + provenance."""

    name: str
    opcode: str
    results: List[Shape]
    operands: List[Shape] = field(default_factory=list)
    op_name: str = ""
    source_file: str = ""
    source_line: int = 0

    @property
    def result_bytes(self) -> int:
        return sum(s.bytes for s in self.results)

    @property
    def operand_bytes(self) -> int:
        return sum(s.bytes for s in self.operands)


def _operand_section(line: str, open_idx: int) -> str:
    """The text between the opcode's ``(`` and its matching ``)``."""
    depth = 0
    for i in range(open_idx, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return line[open_idx + 1 : i]
    return line[open_idx + 1 :]


def parse_stack_frames(text: str) -> Dict[int, Tuple[str, int]]:
    """``stack_frame_id`` -> (source file, line) of the frame's own
    location — the Python that built the op.  jax 0.9.0 prints this once
    per module as the ``FileNames`` / ``FileLocations`` / ``StackFrames``
    tables instead of repeating ``source_file=`` on every instruction."""
    files: Dict[int, str] = {}
    locations: Dict[int, Tuple[int, int]] = {}
    frames: Dict[int, int] = {}
    table = ""
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            table = line
        elif not line:
            table = ""
        elif table == "FileNames":
            m = _FILE_NAME_RE.match(line)
            if m:
                files[int(m.group(1))] = m.group(2)
        elif table == "FileLocations":
            m = _FILE_LOCATION_RE.match(line)
            if m:
                locations[int(m.group(1))] = (int(m.group(2)), int(m.group(3)))
        elif table == "StackFrames":
            m = _STACK_FRAME_RE.match(line)
            if m:
                frames[int(m.group(1))] = int(m.group(2))
    out: Dict[int, Tuple[str, int]] = {}
    for frame, loc in frames.items():
        file_id, lineno = locations.get(loc, (0, 0))
        out[frame] = (files.get(file_id, ""), lineno)
    return out


def parse_hlo_ops(text: str) -> List[HloOp]:
    """Every instruction in an HLO module dump, in order.

    Operands are the ``%name`` references between the opcode's
    parentheses (attribute text after the closing paren — ``to_apply``,
    ``metadata``, constant literals — never contributes): jax 0.9.0 prints
    them by name only (``all-reduce(%fusion.9)``), so each reference
    resolves to the result shapes of the instruction that defined it
    earlier in the same computation.
    """
    frames = parse_stack_frames(text)
    ops: List[HloOp] = []
    defined: Dict[str, List[Shape]] = {}
    for line in text.splitlines():
        m = _INSN_RE.match(line)
        if m is None:
            if line.rstrip().endswith("{"):
                defined = {}  # a new computation: names are scoped to it
            continue
        opcode = m.group("opcode")
        results = parse_shapes(m.group("shape"))
        open_idx = line.index("(", m.end() - 1)
        operands = [
            sh
            for ref in _OPERAND_REF_RE.findall(_operand_section(line, open_idx))
            for sh in defined.get(ref, ())
        ]
        defined[m.group("name")] = results
        op = HloOp(
            name=m.group("name"), opcode=opcode,
            results=results, operands=operands,
        )
        meta = _META_RE.search(line)
        if meta is not None:
            op.op_name = meta.group("op_name") or ""
            if meta.group("frame"):
                op.source_file, op.source_line = frames.get(
                    int(meta.group("frame")), ("", 0)
                )
        ops.append(op)
    return ops


@dataclass
class HloModule:
    """Parsed view of one optimized-HLO text dump."""

    ops: List[HloOp]
    # output-tuple index -> entry parameter number (the donation map)
    aliases: Dict[Tuple[int, ...], int]
    entry_params: List[Shape]
    entry_outputs: List[Shape]

    def count(self, opcode: str) -> int:
        return sum(1 for op in self.ops if op.opcode == opcode)

    @property
    def fence_count(self) -> int:
        return self.count(FENCE_OPCODE)

    @property
    def aliased_params(self) -> List[int]:
        return sorted({p for p in self.aliases.values()})


def _parse_entry_layout(text: str) -> Tuple[List[Shape], List[Shape]]:
    body = _brace_block(text, "entry_computation_layout")
    if not body:
        return [], []
    arrow = body.find("->")
    if arrow < 0:
        return parse_shapes(body), []
    return parse_shapes(body[:arrow]), parse_shapes(body[arrow + 2 :])


def parse_hlo_module(text: str) -> HloModule:
    """Parse a ``compiled.as_text()`` dump: instructions + header facts."""
    aliases: Dict[Tuple[int, ...], int] = {}
    header = text.splitlines()[0] if text else ""
    for entry in _ALIAS_ENTRY_RE.finditer(
        _brace_block(header, "input_output_alias")
    ):
        out_idx = tuple(
            int(x) for x in entry.group("out").replace(" ", "").split(",")
            if x
        )
        aliases[out_idx] = int(entry.group("param"))
    params, outputs = _parse_entry_layout(header)
    return HloModule(
        ops=parse_hlo_ops(text),
        aliases=aliases,
        entry_params=params,
        entry_outputs=outputs,
    )


# --------------------------------------------------------------------------
# census rows (shared shape between the HLO and jaxpr levels)
# --------------------------------------------------------------------------


@dataclass
class CensusRow:
    """Aggregated collectives of one (kind, dtype, group) signature.

    ``bytes`` is the per-replica payload under the convention the byte
    accounting in ``obs/comm.py`` uses: all-reduce and reduce-scatter
    count the bytes a replica CONTRIBUTES (operand bytes), all-gather
    counts the bytes it RECEIVES (result bytes — the full published
    tensor, matching ``comm_plan``'s all_gather row), collective-permute
    counts the bytes each hop sends (operand bytes).
    """

    kind: str
    dtype: str
    group: str = "wire"
    count: int = 0
    elements: int = 0
    bytes: int = 0

    def key(self) -> Tuple[str, str, str]:
        return (self.kind, self.dtype, self.group)

    def to_dict(self) -> Dict[str, object]:
        return {
            "kind": self.kind, "dtype": self.dtype, "group": self.group,
            "count": self.count, "elements": self.elements,
            "bytes": self.bytes,
        }


def _payload_shapes(kind: str, results: List[Shape], operands: List[Shape]):
    if kind == "all-gather":
        return results
    return operands


def hlo_collective_census(
    ops: List[HloOp], classify=None
) -> List[CensusRow]:
    """Aggregate the module's collectives into :class:`CensusRow` rows.

    ``classify(op) -> group name`` buckets each collective (the auditor
    separates gradient-wire collectives from auxiliary ones by source
    attribution); default: everything in one ``"all"`` group.
    """
    rows: Dict[Tuple[str, str, str], CensusRow] = {}
    for op in ops:
        kind = op.opcode
        if kind.endswith(_ASYNC_SUFFIX):
            kind = kind[: -len(_ASYNC_SUFFIX)]
        if kind not in COLLECTIVE_OPCODES or op.opcode in _ASYNC_DONE:
            continue
        payload = _payload_shapes(kind, op.results, op.operands)
        if not payload:
            continue
        group = classify(op) if classify is not None else "all"
        for sh in payload:
            row = rows.setdefault(
                (kind, sh.dtype, group), CensusRow(kind, sh.dtype, group)
            )
            row.elements += sh.elements
            row.bytes += sh.bytes
        # The instruction counts once, attributed to its first payload
        # dtype (multi-dtype tuple collectives split bytes per dtype row).
        rows[(kind, payload[0].dtype, group)].count += 1
    return sorted(rows.values(), key=CensusRow.key)


# --------------------------------------------------------------------------
# jaxpr level (fast mode — no compile)
# --------------------------------------------------------------------------


_JAX_DTYPE_TO_HLO = {
    "float32": "f32", "float16": "f16", "bfloat16": "bf16",
    "float64": "f64",
    "int8": "s8", "uint8": "u8", "int16": "s16", "uint16": "u16",
    "int32": "s32", "uint32": "u32", "int64": "s64", "uint64": "u64",
    "bool": "pred",
}


def hlo_dtype_name(dtype) -> str:
    """HLO spelling of a numpy/jax dtype (so both census levels speak the
    same dtype vocabulary)."""
    name = getattr(dtype, "name", str(dtype))
    return _JAX_DTYPE_TO_HLO.get(name, name)


def jaxpr_collectives(jaxpr) -> List[CensusRow]:
    """Collective census of a (closed or raw) jaxpr, recursing into
    sub-jaxprs.  One equation counts once, with payload bytes summed over
    its array operands (all-gather: its outputs, matching the HLO
    convention)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    rows: Dict[Tuple[str, str, str], CensusRow] = {}
    for eqn in iter_eqns(inner):
        kind = JAXPR_COLLECTIVES.get(eqn.primitive.name)
        if kind is None:
            continue
        vs = eqn.outvars if kind == "all-gather" else eqn.invars
        avals = [
            v.aval for v in vs if getattr(v, "aval", None) is not None
            and hasattr(v.aval, "shape")
        ]
        for aval in avals:
            dtype = hlo_dtype_name(aval.dtype)
            row = rows.setdefault(
                (kind, dtype, "all"), CensusRow(kind, dtype, "all")
            )
            n = 1
            for d in aval.shape:
                n *= int(d)
            row.elements += n
            row.bytes += shape_bytes(dtype, tuple(int(d) for d in aval.shape))
        if avals:
            first = hlo_dtype_name(avals[0].dtype)
            rows[(kind, first, "all")].count += 1
    return sorted(rows.values(), key=CensusRow.key)


def jaxpr_fence_count(jaxpr) -> int:
    """Number of ``optimization_barrier`` equations (fences) in a jaxpr,
    sub-jaxprs included."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    return sum(
        1 for eqn in iter_eqns(inner)
        if eqn.primitive.name == FENCE_PRIMITIVE
    )


def census_to_dicts(rows: List[CensusRow]) -> List[Dict[str, object]]:
    return [r.to_dict() for r in rows]


def census_diff(
    expected: List[Dict[str, object]], actual: List[Dict[str, object]]
) -> List[str]:
    """Human-readable drift between two census tables (empty = identical).

    Keys on (kind, dtype, group); any field difference — a new collective,
    a changed dtype, different counts or bytes — is one message naming the
    op signature, so a failing gate says WHAT changed, not just "drift".
    """

    def index(rows):
        return {
            (r["kind"], r["dtype"], r.get("group", "all")): r for r in rows
        }

    exp, act = index(expected), index(actual)
    out: List[str] = []
    for key in sorted(set(exp) | set(act)):
        kind, dtype, group = key
        sig = f"{kind}[{dtype}] ({group})"
        if key not in act:
            out.append(f"collective disappeared: {sig} "
                       f"(baseline count={exp[key]['count']})")
        elif key not in exp:
            out.append(
                f"new collective: {sig} count={act[key]['count']} "
                f"bytes={act[key]['bytes']}"
            )
        else:
            for fld in ("count", "elements", "bytes"):
                if exp[key][fld] != act[key][fld]:
                    out.append(
                        f"{sig} {fld} changed: baseline {exp[key][fld]} "
                        f"-> {act[key][fld]}"
                    )
    return out


def max_operand_itemsize(row_dtype: str) -> int:
    """Bytes per element of an HLO dtype (dtype-flow comparisons)."""
    return DTYPE_BITS[row_dtype] // 8
