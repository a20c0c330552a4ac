"""Declarative regex partition rules for the ZeRO state layouts.

PR 5's ZeRO-1 built its placement leaf by leaf: ``chunkable`` decided
which optimizer leaves shard by shape, ``zero_leaf_spec`` hand-picked a
GSPMD dimension, and the checkpoint gather/shard paths re-derived both.
Extending that to ZeRO-2 (gradients persist sharded) and ZeRO-3 (params
persist sharded) would triple the ad-hoc sites — exactly the drift PR 13
had to debug when ``zero_leaf_spec`` picked uneven dims.  This module
replaces all of it with the ``match_partition_rules`` /
``make_shard_and_gather_fns`` pattern (SNIPPETS.md [2], the pjit-era
idiom of arxiv 2204.06514): an ordered table of ``(regex,
PartitionSpec)`` rules over **named flattened leaves** is the single
owner of every placement decision, and ``StateLayout``, the GSPMD
builders, the HBM gauges and the checkpoint shard/gather fns all read
the same :class:`Decision` tree.

Naming: a leaf's name is its "/"-joined tree path, e.g.
``opt_state/0/mu/Conv_0/kernel`` or ``params/ConvBlock_2/Conv_0/bias``.
Rules are tried in order; the FIRST ``re.search`` match wins; a leaf no
rule matches is an error (a silent default is how leaves end up
replicated by accident — the failure mode the PR 13 sharding contract
exists to catch).  A rule's spec is either a concrete
``PartitionSpec`` or the :data:`SHARD` sentinel, which resolves
per-layout:

- **chunk mode** (shard_map layouts zero1/zero2/zero3): the leaf is
  flattened to the ``[N, K]`` chunk view (``shard_update.chunk_leaf``)
  and sharded ``P(data)`` on the chunk axis — every leaf chunks, so the
  only fallback is ``not-param-shaped`` (step counters, schedule
  scalars).
- **leaf mode** (GSPMD layouts): :func:`even_shard_spec` partitions the
  largest dimension that divides evenly by the data-axis size; a leaf
  with no such dimension stays replicated with the explicit reason
  ``replicated-by-rule`` — a budgeted decision the sharding contract and
  the ``ddlpc_hbm_replicated_by_rule_bytes`` gauge can see, not a
  silent special case.

Tier: ``jax`` (analysis/tiers.py) — jax.tree walks and PartitionSpec
construction only; nothing here launches a computation.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P

PyTree = Any


class _ShardSentinel:
    """Marker spec: "shard this leaf, the layout picks how" — chunk view
    in the shard_map layouts, :func:`even_shard_spec` under GSPMD."""

    def __repr__(self) -> str:  # pragma: no cover - repr cosmetics
        return "SHARD"


SHARD = _ShardSentinel()

# Decision.reason values — why a leaf got its spec.
REASON_RULE = "rule"                        # concrete spec straight from a rule
REASON_AUTO = "auto-shard"                  # SHARD resolved to a sharded spec
REASON_REPLICATED_BY_RULE = "replicated-by-rule"  # SHARD, but no even dim
REASON_NOT_PARAM_SHAPED = "not-param-shaped"      # SHARD, but not a tensor the
#                                                   param-shape safety gate accepts


@dataclass(frozen=True)
class Rule:
    """One ordered partition rule: ``re.search(pattern, leaf_name)``."""

    pattern: str
    spec: Any  # PartitionSpec | SHARD


@dataclass(frozen=True)
class Decision:
    """The resolved placement of one named leaf — the audit trail every
    consumer (StateLayout, GSPMD constraints, HBM gauges, checkpoint
    fns) reads instead of re-deriving placement."""

    name: str
    shape: Tuple[int, ...]
    spec: P
    rule: Optional[str]  # the pattern that matched (None never happens —
    #                      a no-match is an error, not a decision)
    reason: str

    @property
    def sharded(self) -> bool:
        return any(ax is not None for ax in tuple(self.spec))


# ---------------------------------------------------------------------------
# leaf naming


def _key_str(key) -> str:
    """One path entry -> its name segment ('/'-joined by callers)."""
    tu = jax.tree_util
    if isinstance(key, tu.DictKey):
        return str(key.key)
    if isinstance(key, tu.SequenceKey):
        return str(key.idx)
    if isinstance(key, tu.GetAttrKey):
        return str(key.name)
    if isinstance(key, tu.FlattenedIndexKey):
        return str(key.key)
    return str(key)


def leaf_name(prefix: str, path) -> str:
    segs = [_key_str(k) for k in path]
    return "/".join(([prefix] if prefix else []) + segs)


def named_leaves(tree: PyTree, prefix: str = "") -> List[Tuple[str, Any]]:
    """Flatten ``tree`` to ``[(name, leaf)]`` with "/"-joined path names
    (``prefix`` prepended) — the namespace the rule table matches."""
    return [
        (leaf_name(prefix, path), leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    ]


# ---------------------------------------------------------------------------
# rule matching


def match_partition_rules(rules: Sequence[Rule], name: str):
    """First rule whose pattern ``re.search``-matches ``name``.  A leaf
    no rule covers is a hard error: the table must be total (end it with
    ``Rule(".*", P())``), so an unplaced leaf is a missing-rule bug, not
    a silent replication."""
    for rule in rules:
        if re.search(rule.pattern, name):
            return rule
    raise ValueError(
        f"no partition rule matches leaf {name!r} — the rule table must "
        f"be total (end it with Rule('.*', P()))"
    )


def even_shard_spec(
    shape: Tuple[int, ...], n_shards: int, data_axis: str
) -> P:
    """GSPMD auto-placement for a SHARD-matched leaf: partition the
    largest dimension that divides EVENLY by the data axis; no such
    dimension -> ``P()`` (the caller records ``replicated-by-rule``).
    An uneven pick used to fall back to the largest dimension >= N on
    the theory that GSPMD pads — but an uneven NamedSharding is rejected
    by ``jit in_shardings`` at the state boundary, so any model with
    e.g. a 6-class bias on a 4-way mesh crashed at placement (surfaced
    by the compiled-program auditor, docs/ANALYSIS.md)."""
    if not shape:
        return P()
    pick = None
    for d in sorted(range(len(shape)), key=lambda d: shape[d], reverse=True):
        if shape[d] >= n_shards and shape[d] % n_shards == 0:
            pick = d
            break
    if pick is None:
        return P()
    spec = [None] * len(shape)
    spec[pick] = data_axis
    return P(*spec)


def decide(
    rules: Sequence[Rule],
    name: str,
    shape: Tuple[int, ...],
    *,
    mode: str,
    n_shards: int,
    data_axis: str,
    param_shaped: bool = True,
) -> Decision:
    """Resolve one named leaf against the rule table.

    ``mode='chunk'``: SHARD -> ``P(data_axis)`` over the leaf's chunk
    view.  ``mode='leaf'``: SHARD -> :func:`even_shard_spec`.
    ``param_shaped`` is the shape-based safety gate the chunk layout has
    always had (a SHARD-matched leaf that is not parameter-shaped — a
    step counter a too-broad rule caught — stays replicated with its own
    reason rather than corrupting the chunk arithmetic)."""
    if mode not in ("chunk", "leaf"):
        raise ValueError(f"unknown partition mode {mode!r}")
    shape = tuple(int(d) for d in shape)
    rule = match_partition_rules(rules, name)
    if not isinstance(rule.spec, _ShardSentinel):
        return Decision(name, shape, rule.spec, rule.pattern, REASON_RULE)
    if not param_shaped:
        return Decision(name, shape, P(), rule.pattern,
                        REASON_NOT_PARAM_SHAPED)
    if mode == "chunk":
        return Decision(name, shape, P(data_axis), rule.pattern, REASON_AUTO)
    spec = even_shard_spec(shape, n_shards, data_axis)
    reason = (
        REASON_AUTO if any(ax is not None for ax in tuple(spec))
        else REASON_REPLICATED_BY_RULE
    )
    return Decision(name, shape, spec, rule.pattern, reason)


def decide_tree(
    rules: Sequence[Rule],
    tree: PyTree,
    prefix: str,
    *,
    mode: str,
    n_shards: int,
    data_axis: str,
    pshapes: Optional[frozenset] = None,
) -> PyTree:
    """Map :func:`decide` over a tree -> same-structure tree of
    :class:`Decision`.  ``pshapes`` (the parameter-shape set) feeds the
    param-shaped safety gate; ``None`` disables it (params/grads trees
    are param-shaped by construction)."""

    def one(path, leaf):
        shape = tuple(int(d) for d in leaf.shape)
        param_shaped = True
        if pshapes is not None:
            param_shaped = len(shape) > 0 and shape in pshapes
        return decide(
            rules, leaf_name(prefix, path), shape,
            mode=mode, n_shards=n_shards, data_axis=data_axis,
            param_shaped=param_shaped,
        )

    return jax.tree_util.tree_map_with_path(one, tree)


# ---------------------------------------------------------------------------
# the state-wide rule tables


def state_partition_rules(level: str, data_axis: str = "data") -> Tuple[Rule, ...]:
    """The ZeRO ladder as ONE ordered rule table over TrainState leaf
    names (``params/...``, ``grads/...``, ``opt_state/...``; the grads
    namespace is the optimizer-boundary gradient — what persists between
    the wire collective and the update).

    =========  ======================================================
    level      what shards (everything else replicated by the catch-all)
    =========  ======================================================
    zero1      optimizer moments (``mu``/``nu``/``trace``)
    zero2      + gradients (they arrive reduce-scattered and stay so)
    zero3      + parameters (gathered on demand per step)
    =========  ======================================================

    Precedence is positional: first match wins, and the table always
    ends with the total catch-all ``Rule('.*', P())`` so every leaf gets
    an explicit decision."""
    if level not in ("replicated", "zero1", "zero2", "zero3"):
        raise ValueError(
            f"unknown ZeRO level {level!r} "
            f"(expected replicated|zero1|zero2|zero3)"
        )
    del data_axis  # placement axis is resolved by decide(), not the table
    rules: List[Rule] = []
    if level == "zero3":
        rules.append(Rule(r"^params/", SHARD))
    if level in ("zero2", "zero3"):
        rules.append(Rule(r"^grads/", SHARD))
    if level != "replicated":
        rules.append(Rule(r"^opt_state/(.*/)?(mu|nu|trace)(/|$)", SHARD))
    rules.append(Rule(r".*", P()))
    return tuple(rules)


def replicated_by_rule_bytes(decisions: PyTree, tree: PyTree) -> int:
    """Per-device bytes of leaves the rule engine DECIDED to replicate
    (``replicated-by-rule``) — the explicit HBM budget line the PR 13
    sharding contract and the ``ddlpc_hbm`` gauges charge instead of
    special-casing uneven leaves."""
    total = 0
    for d, leaf in zip(jax.tree.leaves(decisions), jax.tree.leaves(tree)):
        if d.reason != REASON_REPLICATED_BY_RULE:
            continue
        n = 1
        for s in leaf.shape:
            n *= int(s)
        total += n * jax.numpy.dtype(leaf.dtype).itemsize
    return total


# ---------------------------------------------------------------------------
# checkpoint shard / gather fns


def make_shard_and_gather_fns(
    decisions: PyTree, n_shards: int, mode: str
) -> Tuple[PyTree, PyTree]:
    """Per-leaf ``(shard_fns, gather_fns)`` callables derived from one
    decision tree (the SNIPPETS.md [2] pattern): ``shard_fn(full_leaf)``
    produces the run-layout value a checkpoint restore places,
    ``gather_fn(run_leaf)`` restores the canonical full leaf a
    checkpoint stores.  In ``mode='chunk'``, auto-sharded decisions
    chunk/unchunk the ``[N, K]`` view; in ``mode='leaf'`` (and for every
    replicated decision) the fns are the identity — those layout changes
    are placement-only, owned by the sharding trees.
    ``StateLayout.place``/``canonical`` jit these, so checkpoints stay
    layout-independent from the same table that places the live state."""
    from ddlpc_tpu.parallel.shard_update import chunk_leaf, unchunk_leaf

    if mode not in ("chunk", "leaf"):
        raise ValueError(f"unknown partition mode {mode!r}")
    chunked = mode == "chunk"

    def shard_fn(d: Decision):
        if chunked and d.reason == REASON_AUTO:
            return lambda x, n=n_shards: chunk_leaf(x, n)
        return lambda x: x

    def gather_fn(d: Decision):
        if chunked and d.reason == REASON_AUTO:
            return lambda x, shape=d.shape: unchunk_leaf(x, shape)
        return lambda x: x

    return jax.tree.map(shard_fn, decisions), jax.tree.map(gather_fn, decisions)
