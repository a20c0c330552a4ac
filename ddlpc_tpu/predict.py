"""Inference CLI: ``python -m ddlpc_tpu.predict --workdir runs/x --input dir``.

The reference has no inference path at all — its closest artifact is the
in-training PNG dump of fixed 512×512 crops (кластер.py:785-790,817-823).
This restores a trained checkpoint and predicts each input image at its
NATIVE size via overlap-blended sliding windows, writing a color-mapped
class-map PNG per input.

This is now a thin client of :mod:`ddlpc_tpu.serve.engine`: the tiler and
restore logic live there (one tested path shared with the serving engine);
``sliding_window_logits`` and ``load_run`` stay re-exported here for
existing callers.  Restore goes through the format-dispatching checkpoint
reader (train/checkpoint.py): both the chunked ``.dwc`` format and legacy
single-blob ``.msgpack.z`` checkpoints load here unchanged
(docs/CHECKPOINTS.md).
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

from ddlpc_tpu.serve.engine import (  # noqa: F401  (public re-exports)
    InferenceEngine,
    _blend_window,
    sliding_window_logits,
)
from ddlpc_tpu.utils.compile_cache import enable_compile_cache


def load_run(workdir: str):
    """(cfg, state, logits_fn, channels) restored from a training run.

    Back-compat shim over ``InferenceEngine.from_workdir`` — new code should
    use the engine directly (it adds the compiled-shape cache + hot reload).
    """
    from ddlpc_tpu.parallel.train_step import make_logits_fn

    eng = InferenceEngine.from_workdir(workdir)
    return eng.cfg, eng.state, make_logits_fn(eng.model), eng.channels


def main(argv=None) -> int:
    enable_compile_cache()
    p = argparse.ArgumentParser(prog="python -m ddlpc_tpu.predict")
    p.add_argument("--workdir", required=True, help="training run directory")
    p.add_argument("--input", required=True, help="directory of images")
    p.add_argument("--output", help="output directory (default <workdir>/predictions)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument(
        "--overlap",
        type=float,
        default=0.25,
        help="sliding-window overlap fraction (0 = edge-to-edge tiling)",
    )
    args = p.parse_args(argv)

    from PIL import Image

    from ddlpc_tpu.train.observability import class_palette

    engine = InferenceEngine.from_workdir(args.workdir, max_bucket=args.batch)
    cfg = engine.cfg

    out_dir = args.output or os.path.join(args.workdir, "predictions")
    os.makedirs(out_dir, exist_ok=True)
    pal = class_palette(cfg.model.num_classes)

    names = sorted(
        n
        for n in os.listdir(args.input)
        if not n.endswith(".npy") and os.path.isfile(os.path.join(args.input, n))
    )
    if not names:
        print(f"no images found in {args.input}", file=sys.stderr)
        return 1
    from ddlpc_tpu.data.datasets import load_image_file

    for n in names:
        # Native size (image_size=None): the sliding window handles any
        # geometry; preprocessing stays shared with the training readers.
        image = load_image_file(
            os.path.join(args.input, n), None, channels=engine.channels
        )
        pred = engine.predict_classes(
            image, overlap=args.overlap, batch=args.batch
        )
        stem = n.rsplit(".", 1)[0]
        Image.fromarray(pal[np.clip(pred, 0, cfg.model.num_classes - 1)]).save(
            os.path.join(out_dir, f"{stem}_pred.png")
        )
    print(f"wrote {len(names)} predictions to {out_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
