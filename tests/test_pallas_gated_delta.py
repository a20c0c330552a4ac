"""The gated delta rule's walk as a Pallas kernel pair
(ops/pallas_gated_delta.py) on the CPU in interpret mode: forward and every
gradient against the lax.scan it replaces on the chip and against the
token-by-token recurrence of the benchmark's reference, which lowering a
program takes, the engagement counter, and what the FLOP walk makes of the
kernels.  Small shapes at the family's head sizes, 96 and 192."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import olmo_hybrid as reference  # noqa: E402
from test_olmo_hybrid import SEQ, delta_inputs, init, tiny_config, tokens  # noqa: E402

from ddlpc_tpu.config import DataConfig, ExperimentConfig  # noqa: E402
from ddlpc_tpu.models import build_model  # noqa: E402
from ddlpc_tpu.ops import gated_delta, pallas_gated_delta  # noqa: E402

DK, DV = 96, 192


def kernel_rule(q, k, v, log_decay, beta):
    """:func:`gated_delta.gated_delta_rule` with its walk on the kernels, interpreted."""
    b, s, h, _ = q.shape
    parts = gated_delta.chunk_algebra(q, k, v, log_decay, beta, gated_delta.CHUNK)
    out = pallas_gated_delta.walk(*parts, interpret=True)
    return jnp.moveaxis(out, (0, 2), (1, 3)).reshape(b, s, h, -1)


def recurrence(q, k, v, log_decay, beta):
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    return reference.delta_recurrence(q, k, v, jnp.exp(log_decay), beta)


def relative(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize(
    "repeated,dtype",
    [(False, jnp.float32), (True, jnp.float32), (False, jnp.bfloat16), (True, jnp.bfloat16)],
    ids=["random-f32", "repeated-f32", "random-bf16", "repeated-bf16"],
)
def test_kernel_pair_is_the_scan_and_the_recurrence(repeated, dtype):
    """Batch 2, four chunks, ``β`` up to 1.998, random or repeated keys: the
    output and the gradients of q, k, v, the log decay and ``β``.  In float32
    the kernels are the scan to rounding, and the recurrence within the
    chunkwise form's own limits; in bf16 within its rounding of the scan and
    no further from the recurrence than the scan is."""
    q, k, v, log_decay, beta = delta_inputs(3, repeated, b=2, s=SEQ, h=2, dk=DK, dv=DV)
    assert float(beta.max()) > 1.99
    args = (q.astype(dtype), k.astype(dtype), v.astype(dtype), log_decay, beta)
    weight = jax.random.normal(jax.random.key(9), v.shape)
    forms = {
        "kernel": kernel_rule,
        "scan": gated_delta.gated_delta_rule,
        "recurrence": recurrence,
    }
    outs, grads = {}, {}
    for name, f in forms.items():
        outs[name] = f(*args)
        loss = lambda *a: jnp.sum(f(*a).astype(jnp.float32) * weight)  # noqa: E731, B023
        grads[name] = jax.grad(loss, range(5))(*args)
    assert outs["kernel"].shape == v.shape and outs["kernel"].dtype == dtype
    if dtype == jnp.float32:
        np.testing.assert_allclose(outs["kernel"], outs["scan"], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(outs["kernel"], outs["recurrence"], rtol=2e-4, atol=2e-5)
        for got, scan, want in zip(grads["kernel"], grads["scan"], grads["recurrence"]):
            assert got.dtype == scan.dtype and got.shape == scan.shape
            assert relative(got, scan) < 1e-5
            assert relative(got, want) < 1e-4
        return
    assert relative(outs["kernel"], outs["scan"]) < 1e-2
    assert relative(outs["kernel"], outs["recurrence"]) < 1.5 * relative(outs["scan"], outs["recurrence"])
    for got, scan, want in zip(grads["kernel"], grads["scan"], grads["recurrence"]):
        assert got.dtype == scan.dtype and got.shape == scan.shape
        assert relative(got, scan) < 2e-2
        assert relative(got, want) < 1.5 * relative(scan, want) + 1e-3


def test_backward_reads_the_float32_state():
    """The forward's residual states are the float32 ``S``, as the scan's
    reverse mode keeps them: the decay's cotangent ``Σ S ⊙ dS'`` is not
    taken from the bf16 copy the products read."""
    q, k, v, log_decay, beta = delta_inputs(4, False, b=1, s=SEQ, h=2, dk=DK, dv=DV)
    parts = gated_delta.chunk_algebra(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), log_decay, beta,
        gated_delta.CHUNK,
    )
    _, (*_, states, new) = jax.eval_shape(lambda *p: pallas_gated_delta._walk_fwd(*p, True), *parts)
    assert states.dtype == jnp.float32 and new.dtype == jnp.bfloat16
    assert states.shape == (SEQ // gated_delta.CHUNK, 1, 2, DK, DV)


def lowered(platform: str, seq: int) -> str:
    args = delta_inputs(5, False, b=1, s=seq, h=2, dk=DK, dv=DV)
    args = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    grad = jax.grad(
        lambda *a: gated_delta.gated_delta_rule(*a).astype(jnp.float32).sum(), range(5)
    )
    return jax.jit(grad).trace(*args).lower(lowering_platforms=(platform,)).as_text()


@pytest.mark.parametrize(
    "platform,seq,kernel",
    [("tpu", SEQ, True), ("tpu", 192, True), ("cpu", SEQ, False), ("tpu", 32, False)],
    ids=["tpu", "tpu-three-chunks", "cpu", "tpu-under-a-chunk"],
)
def test_path_goes_by_platform_and_sequence_length(platform, seq, kernel):
    """The kernel pair where the program is lowered for a TPU and the
    sequence is whole chunks of 64; the scan, and no error, everywhere else
    (a sequence under one chunk is one chunk of its own length)."""
    text = lowered(platform, seq)
    # the forward and the backward kernel, and no while over the chunks
    assert text.count("stablehlo.custom_call @tpu_custom_call") == (2 if kernel else 0)
    assert int(gated_delta.kernel_lowers(seq)) == 0  # the CPU's lowering, whatever the length


def test_engagement_counter_reads_zero_on_the_cpu():
    """gdn_kernel_layers: the DeltaNet layers whose walk lowered to the
    kernels, a "max" counter beside gdn_layers; 0 on the CPU, at a length
    the kernels take or not."""
    cfg = tiny_config()
    model, params = build_model(cfg), init(cfg, 3)
    for seq in (SEQ, 32):
        x, _ = tokens(5, batch=1, seq=seq)
        _, aux = jax.jit(
            lambda p, x: model.apply({"params": p}, x, train=True, mutable=["counters"])
        )(params, x)
        assert int(aux["counters"]["max"]["gdn_kernel_layers"]) == 0
        assert int(aux["counters"]["max"]["gdn_layers"]) == 3


def test_model_through_the_kernels_is_the_model_through_the_scan(monkeypatch):
    """The test steers the model's walk onto the kernels (interpreted; the
    program itself goes by the platform it is lowered for): logits and the
    gradient of every parameter of the tiny family in float32."""
    cfg = tiny_config()
    model, params = build_model(cfg), init(cfg, 2)
    x, y = tokens(7, batch=2)

    def loss(p):
        logits = model.apply({"params": p}, x)
        return jnp.sum(jax.nn.log_softmax(logits, -1)[..., 0] * 0 + logits[..., 0] * y), logits

    (_, want), g_want = jax.value_and_grad(loss, has_aux=True)(params)
    monkeypatch.setattr(gated_delta, "walk", functools.partial(pallas_gated_delta.walk, interpret=True))
    (_, got), g_got = jax.value_and_grad(loss, has_aux=True)(params)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert relative(a, b) < 1e-5


def test_flop_walk_counts_the_kernels_as_the_scan():
    """``obs/flops.product_flops`` takes a pallas_call's cost estimate and, of
    a platform switch, the branch lowered for the platform asked for: the
    forward kernel stands for the scan's four products a chunk and head, so
    both lowerings count the same."""
    from ddlpc_tpu.obs import flops

    cfg = ExperimentConfig(
        model=tiny_config(layer_types=("linear_attention",)),
        data=DataConfig(dataset="packed_tokens", image_size=(1, SEQ), num_classes=96),
    )
    scan = flops.product_flops(cfg, 1, channels=1, platform="cpu")
    kernel = flops.product_flops(cfg, 1, channels=1, platform="tpu")
    assert kernel == scan
    assert dataclasses.asdict(cfg.model)["linear_key_head_dim"] == 32  # not the kernels' own test sizes
