"""CLI: ``python -m ddlpc_tpu.train --config cfg.json --set train.epochs=5``.

The reference has no CLI at all — role and every hyperparameter are
hard-coded globals edited per machine (кластер.py:223-252,685-687).  Here a
run is one JSON config artifact plus dotted-path overrides; the same command
works single-chip, v5e-8, or multi-host (set COORDINATOR_ADDRESS /
NUM_PROCESSES / PROCESS_ID or rely on TPU pod auto-detection).
"""

from __future__ import annotations

import argparse
import ast
import sys

from ddlpc_tpu.config import ExperimentConfig
from ddlpc_tpu.utils.compile_cache import enable_compile_cache


def apply_override(d: dict, dotted: str, value: str) -> None:
    keys = dotted.split(".")
    cur = d
    for k in keys[:-1]:
        if k not in cur or not isinstance(cur[k], dict):
            raise KeyError(f"unknown config section {dotted!r}")
        cur = cur[k]
    if keys[-1] not in cur:
        raise KeyError(f"unknown config key {dotted!r}")
    try:
        cur[keys[-1]] = ast.literal_eval(value)
    except (ValueError, SyntaxError):
        cur[keys[-1]] = value  # bare string


def parse_config(argv=None) -> tuple[ExperimentConfig, bool]:
    p = argparse.ArgumentParser(
        prog="python -m ddlpc_tpu.train", description=__doc__
    )
    p.add_argument("--config", help="JSON config file (ExperimentConfig.to_json)")
    p.add_argument(
        "--set",
        action="append",
        default=[],
        metavar="KEY=VALUE",
        help="dotted override, e.g. train.epochs=5 model.name=unetpp",
    )
    p.add_argument("--workdir", help="run directory (logs/checkpoints/images)")
    p.add_argument(
        "--no-resume", action="store_true", help="ignore existing checkpoints"
    )
    args = p.parse_args(argv)

    if args.config:
        with open(args.config) as f:
            cfg = ExperimentConfig.from_json(f.read())
    else:
        cfg = ExperimentConfig()
    d = cfg.to_dict()
    for item in args.set:
        if "=" not in item:
            p.error(f"--set expects KEY=VALUE, got {item!r}")
        key, value = item.split("=", 1)
        apply_override(d, key, value)
    cfg = ExperimentConfig.from_dict(d)
    if args.workdir:
        cfg = cfg.replace(workdir=args.workdir)
    return cfg, not args.no_resume


def run(argv=None):
    """The CLI's whole job — parse, build the Trainer, fit — returning the
    finished Trainer: ``main`` maps it to an exit status, and
    ``chip_smoke.py`` reads its device state after the same call."""
    enable_compile_cache()
    cfg, resume = parse_config(argv)
    from ddlpc_tpu.train.trainer import Trainer

    trainer = Trainer(cfg, resume=resume)
    record = trainer.fit()
    print({k: round(v, 4) if isinstance(v, float) else v for k, v in record.items()})
    return trainer


def main(argv=None) -> int:
    from ddlpc_tpu.resilience.protocol import EXIT_PREEMPTED

    if run(argv).preempted:
        # Distinct restartable-clean status (resilience/protocol.py): the
        # supervisor relaunches without backoff and the resume skip-replays
        # to the exact preempted step.
        return EXIT_PREEMPTED
    return 0


if __name__ == "__main__":
    sys.exit(main())
