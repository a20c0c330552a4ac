"""Device self time of the ops whose ``op_name`` holds a needle (a
``jax.named_scope`` of the program), forward, backward and recomputation
alike, inside the measured window: for scopes that ``regions.json`` does not
name, so that ``forward_device_ms`` and ``backward_device_ms`` keep their
meaning.  Reads the trace this process wrote (``program_spans.find_trace``,
``program_spans.load``) and ``trace_reduce.self_times``, so an enclosing
``while`` does not count its body twice.  A program without the scope (the
parent of the PR that added it) reads as None.
"""

from __future__ import annotations

import functools

import program_spans
import trace_reduce


# The expert products: the program's scope, and the Mosaic kernels the TPU
# compiler makes of ``lax.ragged_dot``, which it names ``ragged-dot-<n>`` and
# strips of every scope (the experts' are the program's only grouped products).
EXPERT_NEEDLES = ("ddlpc/moe/experts", "ragged-dot-")


@functools.lru_cache(maxsize=2)
def _ops_in_window(path: str):
    trace = program_spans.load(path)
    found = program_spans._window(trace) if trace else None
    if not found:
        return None
    lo, hi, _ = found
    return tuple((name, s, e) for name, s, e in trace["ops"] if e > lo and s < hi)


def needle_ms(ops, *needles: str) -> float:
    """Milliseconds of self time of the ops whose name holds any of ``needles``."""
    labelled = [("x" if any(n in name for n in needles) else "", s, e) for name, s, e in ops]
    return trace_reduce.self_times(labelled).get("x", 0.0) * 1e3


def ms_per_step(run: dict, *needles: str) -> float | None:
    path = program_spans.find_trace()
    ops = _ops_in_window(path) if path else None
    steps = len(run["records"]) * run["steps_per_epoch"]
    if not ops or not steps:
        return None
    return needle_ms(ops, *needles) / steps or None
