"""ShardedLoader host-upload path: an isolated throughput number.

The disk-fit run proved the plumbing, but its tiles/s was bound by the
host-to-device link it ran over — the host-upload path every real pod
would use (`device_cache=False`, host gather → `make_global_array` → HBM)
had no throughput claim of its own.  This bench isolates the loader:

- `gather` arm: `_local_batches()` alone — the host-side index/gather/
  cast/pack rate with NO device involvement (the absolute host ceiling).
- `upload` arm: the full `__iter__` path (gather + `make_global_array` +
  prefetch overlap) with a per-super-batch scalar fetch as the consumer —
  the realistic cadence (a train step consumes each batch and forces it).

`--native {auto,on,off}` selects the assembly engine: `on`/`auto` use the
fused gather–cast–pack kernel (csrc/batch.cc) writing into the loader's
buffer ring; `off` forces the single-threaded numpy path (the pre-native
baseline).  `on` errors when the kernel is unavailable so a CI arm cannot
silently measure the wrong engine; `auto` takes the loader's logged
fallback.  Per-stage means (`loader_gather`/`loader_cast`/
`loader_upload`, via StageTimer) land in the record so a regression is
attributable to gather vs cast vs upload rather than re-isolated by hand.

On `--backend cpu` the device "upload" is a host memcpy, so the upload arm
measures the path at memory-bandwidth realism.  On the default backend
(the chip) the same arm measures the real host link.  BASELINE context: the
reference feeds ≥400 tiles/s/chip equivalents through a blocking host copy
(кластер.py:754); the prefetch design must beat that on a real host link.

Writes/merges docs/disk_fit/loader_throughput.json (key: backend+shape)
and prints the driver-contract line
  {"metric": "loader_tiles_per_s", "value": <gather-arm tiles/s>, ...}
as the LAST stdout line.

Usage: python scripts/loader_throughput_bench.py --backend cpu
       [--native auto] [--tiles 256] [--micro-batch 32] [--sync 4]
       [--epochs 3] [--workers N] [--compact] [--source memory]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_SCRIPTS_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_SCRIPTS_DIR))
from ddlpc_tpu.utils.fsio import atomic_write_json  # noqa: E402


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--backend", default="cpu", choices=["cpu", "device"],
                   help="cpu = forced CPU backend (memory-bandwidth realism);"
                        " device = default backend (the chip)")
    p.add_argument("--tiles", type=int, default=256)
    p.add_argument("--size", type=int, default=512)
    p.add_argument("--micro-batch", type=int, default=32)
    p.add_argument("--sync", type=int, default=4)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--compact", action="store_true",
                   help="bf16 images + int8 labels on the wire "
                        "(ShardedLoader(compact=True), bit-identical for "
                        "bf16-compute models)")
    p.add_argument("--workers", type=int, default=1,
                   help="producer threads (ShardedLoader(workers=...)); "
                        "the native kernel additionally multithreads "
                        "INSIDE each batch")
    p.add_argument("--native", default="auto", choices=["auto", "on", "off"],
                   help="fused native gather-cast-pack (csrc/batch.cc): "
                        "on = require it (error if unavailable), off = "
                        "force the numpy path, auto = native with logged "
                        "fallback")
    p.add_argument("--source", default="memory",
                   choices=["memory", "lazy-npy", "lazy-png"],
                   help="memory: resident SyntheticTiles; lazy-*: a "
                        "LazyTileDataset over a generated tile dir "
                        "(per-gather disk reads; npy = decode-free)")
    p.add_argument("--out", default="docs/disk_fit/loader_throughput.json")
    args = p.parse_args()

    import jax

    if args.backend == "cpu":
        # Host-side arm: stay off the chip.
        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from ddlpc_tpu.config import ParallelConfig
    from ddlpc_tpu.data.datasets import SyntheticTiles, load_tile_dir
    from ddlpc_tpu.data.loader import ShardedLoader
    from ddlpc_tpu.parallel.mesh import make_mesh
    from ddlpc_tpu.train.observability import StageTimer
    from ddlpc_tpu.utils import native

    if args.native == "on" and native.load_batch() is None:
        raise SystemExit(
            "--native on: csrc/libdwbatch.so unavailable and not buildable "
            "(is g++ installed?); use --native auto for logged fallback"
        )

    ds = SyntheticTiles(
        num_tiles=args.tiles, image_size=(args.size, args.size)
    )
    tmp_ctx = None
    if args.source != "memory":
        # Write the same tiles to disk once, then measure the lazy path's
        # per-gather reads (npy = decode-free uint8 arrays; png = decode).
        import tempfile

        import imageio.v2 as imageio

        tmp_ctx = tempfile.TemporaryDirectory(prefix="lazy_tiles_")
        for i in range(len(ds)):
            u8 = (ds.images[i] * 255).astype(np.uint8)
            if args.source == "lazy-npy":
                np.save(os.path.join(tmp_ctx.name, f"t{i:04d}_img.npy"), u8)
            else:
                imageio.imwrite(
                    os.path.join(tmp_ctx.name, f"t{i:04d}.png"), u8
                )
            np.save(
                os.path.join(tmp_ctx.name, f"t{i:04d}.npy"),
                ds.labels[i].astype(np.int32),
            )
        ds = load_tile_dir(tmp_ctx.name, lazy=True)
    mesh = make_mesh(ParallelConfig())
    timer = StageTimer()
    loader = ShardedLoader(
        ds, mesh, global_micro_batch=args.micro_batch,
        sync_period=args.sync, compact=args.compact, workers=args.workers,
        native_gather=args.native != "off", timer=timer,
    )
    # "native" must record that the kernel is actually ON THE MEASURED
    # PATH, not merely loaded: non-compact lazy sources never invoke it
    # (per-tile disk reads can't fuse and there is no cast stage), so such
    # a run is the numpy path and must not carry a _native key/label.
    native_used = loader._native is not None and (
        loader._native_source() is not None or args.compact
    )
    bytes_per_tile = args.size * args.size * (
        (3 * 2 + 1) if args.compact else (3 * 4 + 4)
    )  # bf16 image + int8 label | fp32 image + int32 label

    rec = {
        "backend": jax.default_backend(),
        "tiles": args.tiles, "tile_px": args.size,
        "micro_batch": args.micro_batch, "sync_period": args.sync,
        "epochs": args.epochs,
        "compact": args.compact,
        "workers": args.workers,
        "native": native_used,
        "host_cores": os.cpu_count(),
        "source": args.source,
        "mb_per_tile": round(bytes_per_tile / 2**20, 3),
    }

    def stage_means() -> dict:
        # Per-batch stage means in ms — the attribution column: a future
        # regression shows up as gather vs cast vs upload, not as one
        # opaque tiles/s drop.
        return {
            k.replace("loader_", ""): round(v * 1e3, 1)
            for k, v in sorted(timer.means().items())
        }

    # -- gather arm: host-side ceiling, no device involvement.
    loader.set_epoch(0)
    next(iter(loader._local_batches()))  # warm caches
    timer.reset()
    t0 = time.perf_counter()
    n = 0
    for ep in range(args.epochs):
        loader.set_epoch(ep)
        for imgs, labs in loader._local_batches():
            n += imgs.shape[0] * imgs.shape[1]
    dt = time.perf_counter() - t0
    rec["gather_tiles_per_s"] = round(n / dt, 1)
    rec["gather_gb_per_s"] = round(n * bytes_per_tile / dt / 2**30, 2)
    rec["gather_stage_ms"] = stage_means()

    # -- upload arm: full iter path, per-super-batch scalar fetch (the
    # train-step consumer cadence; every fetch is a host round trip —
    # that cost is part of the path being measured).
    loader.set_epoch(0)
    for imgs, labs in loader:  # warm epoch: compile/layout/alloc paths
        float(imgs.ravel()[0])
        break
    timer.reset()
    t0 = time.perf_counter()
    n = 0
    for ep in range(args.epochs):
        loader.set_epoch(ep)
        for imgs, labs in loader:
            float(imgs.ravel()[0])
            n += imgs.shape[0] * imgs.shape[1]
    dt = time.perf_counter() - t0
    rec["upload_tiles_per_s"] = round(n / dt, 1)
    rec["upload_gb_per_s"] = round(n * bytes_per_tile / dt / 2**30, 2)
    rec["upload_vs_baseline_400"] = round(rec["upload_tiles_per_s"] / 400, 2)
    rec["upload_stage_ms"] = stage_means()

    key = f"{rec['backend']}_{args.size}px_b{args.micro_batch}x{args.sync}" + (
        "_compact" if args.compact else ""
    ) + ("" if args.source == "memory" else f"_{args.source}") + (
        "" if args.workers == 1 else f"_w{args.workers}"
    ) + ("_native" if native_used else "")
    if tmp_ctx is not None:
        tmp_ctx.cleanup()
    merged = {}
    if os.path.exists(args.out):
        merged = json.load(open(args.out))
    merged[key] = rec
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    atomic_write_json(args.out, merged)
    print(json.dumps({key: rec}))
    # Driver contract (same shape as bench.py / serve_bench.py): exactly
    # one {"metric": ...} line, last on stdout.  The gather arm is the
    # host-path headline — device-independent, the number the ≥2×-numpy
    # acceptance gate reads.
    print(json.dumps({
        "metric": "loader_tiles_per_s",
        "value": rec["gather_tiles_per_s"],
        "unit": "tiles/s",
        "vs_baseline": round(rec["gather_tiles_per_s"] / 400, 2),
    }))


if __name__ == "__main__":
    main()
