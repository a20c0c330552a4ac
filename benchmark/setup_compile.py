"""The program's compile ledger (``ddlpc_tpu/utils/compile_cache.py``) over
set-up: construction's counters, which the first warm-up record carries as
``init_<counter>``, plus each warm-up record's own.  Shared by the
``setup_*`` readers in ``layer_metrics/``; a program without the ledger (the
parent of the PR that added it) reads as None, and the line leaves the metric
out."""


def total(run: dict, *counters: str):
    """Σ of the counters over construction and warm-up; None where a record
    lacks one."""
    records = run["warmup_records"]
    keys = [f"init_{c}" for c in counters]
    if not records or any(k not in records[0] for k in keys):
        return None
    if any(c not in r for r in records for c in counters):
        return None
    return sum(records[0][k] for k in keys) + sum(r[c] for r in records for c in counters)
