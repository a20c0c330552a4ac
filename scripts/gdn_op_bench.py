"""Time the gated delta rule alone (``ops/gated_delta.py``) at the shapes of
one Olmo-Hybrid DeltaNet layer and sequence, and split its device time.

    python scripts/gdn_op_bench.py [--walk xla kernel] [--reps 5]

For each walk over the chunks (``xla``: the ``lax.scan``; ``kernel``: the
Pallas pair of ``ops/pallas_gated_delta.py``) it profiles ``--reps`` calls of
the op forward, and of forward + backward of ``Σ o ⊙ w``, and prints one JSON
line of device milliseconds a call, from the first chip's "XLA Ops" line
(a ``while``'s own event is left out: its body's ops are on the line too):

  op_fwd, op_fwd_bwd  every op of the call
  a_fwd, a_bwd        the chunk algebra and its pullback (all the rest)
  b_fwd, b_bwd        the 64-row substitution and its pullback
  c_walk_fwd          the forward walk (in op_fwd_bwd too, where it writes
                      the backward's residuals; c here is op_fwd's)
  d_walk_bwd          the reverse walk
  loss_bwd            the benchmark's own Σ o ⊙ w and its cotangent

The parts are told apart by the named scopes this script wraps round them
(``bench/walk``, ``bench/inverse``, ``bench/loss``), which the ops' ``tf_op``
keeps, backward ops under ``transpose(``.  Needs a TPU; ``--rehearse`` runs
a tiny size on the CPU, kernels interpreted, to check the script, and
prints no split (the CPU trace has no "XLA Ops" line).  The lines also go
to ``chiprun_out/gdn_op_bench.jsonl``.
"""

from __future__ import annotations

import argparse
import collections
import functools
import glob
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import program_spans  # noqa: E402
import trace_reduce  # noqa: E402
from ddlpc_tpu.obs.schema import stamp  # noqa: E402
from ddlpc_tpu.ops import gated_delta, pallas_gated_delta  # noqa: E402

# One held-head share of an Olmo-Hybrid-7B DeltaNet layer, one 8,192-token sequence.
SHAPE = dict(b=1, s=8192, h=15, dk=96, dv=192)
TINY = dict(b=1, s=256, h=2, dk=96, dv=192)


def inputs(seed: int, b: int, s: int, h: int, dk: int, dv: int):
    """bf16 q, k (unit rows, q scaled), v; float32 log decay and β up to 2, and a weight."""
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = (unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk**-0.5).astype(jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], (b, s, h, dk))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, h, dv), jnp.bfloat16)
    log_decay = -0.5 * jax.random.uniform(ks[3], (b, s, h))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    weight = jax.random.normal(ks[5], (b, s, h, dv))
    return (q, k, v, log_decay, beta), weight


def scoped(name, f):
    def inner(*a):
        with jax.named_scope(name):
            return f(*a)

    return inner


def device_ms(f, *args, reps: int) -> dict:
    """Device milliseconds a call of ``f`` by ``tf_op`` (``{}`` without a TPU trace)."""
    jax.block_until_ready(f(*args))
    jax.block_until_ready(f(*args))
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir)
    for _ in range(reps):
        jax.block_until_ready(f(*args))
    jax.profiler.stop_trace()
    pb2 = program_spans._xplane_pb2()
    space = pb2.XSpace()
    with open(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)[0], "rb") as fh:
        space.ParseFromString(fh.read())
    per: collections.Counter = collections.Counter()
    for plane in space.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) != 0:
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for e in line.events:
                meta = plane.event_metadata[e.metadata_id]
                if meta.name.startswith("%while"):
                    continue
                op = str(program_spans._stats(meta.stats, names).get("tf_op", ""))
                per[op] += e.duration_ps / 1e9 / reps
    return per


def parts_ms(per: dict) -> dict:
    out = collections.Counter()
    for op, ms in per.items():
        side = "bwd" if "transpose(" in op else "fwd"
        part = next((p for p in ("walk", "inverse", "loss") if f"bench/{p}" in op), "algebra")
        out[f"{part}_{side}"] += ms
    return out


def split(walk, args, weight, reps: int) -> dict:
    """The op's device milliseconds and those of its parts, with ``walk`` over the chunks."""
    inverse = gated_delta.unit_lower_inverse
    gated_delta.unit_lower_inverse = scoped("bench/inverse", inverse)
    try:
        chunk = min(gated_delta.CHUNK, args[0].shape[1])

        def op(*a):
            b, s, h, _ = a[0].shape
            out = scoped("bench/walk", walk)(*gated_delta.chunk_algebra(*a, chunk))
            return jnp.moveaxis(out, (0, 2), (1, 3)).reshape(b, s, h, -1)

        loss = scoped("bench/loss", lambda o: jnp.sum(o.astype(jnp.float32) * weight))
        fwd = device_ms(jax.jit(op), *args, reps=reps)
        both = device_ms(jax.jit(jax.value_and_grad(lambda *a: loss(op(*a)), range(5))), *args, reps=reps)
    finally:
        gated_delta.unit_lower_inverse = inverse
    f, fb = parts_ms(fwd), parts_ms(both)
    return {
        "op_fwd": sum(fwd.values()), "op_fwd_bwd": sum(both.values()),
        "a_fwd": f["algebra_fwd"], "a_bwd": fb["algebra_bwd"],
        "b_fwd": f["inverse_fwd"], "b_bwd": fb["inverse_bwd"],
        "c_walk_fwd": f["walk_fwd"], "d_walk_bwd": fb["walk_bwd"],
        "loss_bwd": fb["loss_fwd"] + fb["loss_bwd"],
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--walk", nargs="+", default=["xla", "kernel"], choices=["xla", "kernel"])
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true", help="tiny size on the CPU, kernels interpreted")
    a = p.parse_args(argv)
    device = jax.devices()[0]
    if device.platform != "tpu" and not a.rehearse:
        print(f"no TPU here ({device.platform}); --rehearse checks the script on the CPU", file=sys.stderr)
        return 2
    shape = TINY if a.rehearse else SHAPE
    args, weight = inputs(a.seed, **shape)
    walks = {"xla": gated_delta.walk,
             "kernel": functools.partial(pallas_gated_delta.walk, interpret=a.rehearse)}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "gdn_op_bench.jsonl"), "a") as log:
        for name in a.walk:
            line = stamp({"walk": name, **shape, "platform": device.platform, "device_kind": device.device_kind,
                          **{k: round(v, 4) for k, v in split(walks[name], args, weight, a.reps).items()}})
            print(json.dumps(line), flush=True)
            log.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
