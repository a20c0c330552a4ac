"""The fused causal attention kernels (ops/pallas_attention.py) on the CPU in
Pallas interpret mode at small shapes: against the XLA form they replace on
the chip and against plain float32 attention, causality through the kernel,
which lowering a program takes, and what the FLOP walk makes of the kernel."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddlpc_tpu.config import DataConfig, ExperimentConfig, ModelConfig
from ddlpc_tpu.models import build_model
from ddlpc_tpu.models import lfm2_moe as program
from ddlpc_tpu.ops import pallas_attention

HEAD = 64


def qkv(seq: int, heads: int, kv_heads: int, seed: int = 0, batch: int = 1):
    keys = jax.random.split(jax.random.key(seed), 4)
    q = 2 * jax.random.normal(keys[0], (batch, seq, heads, HEAD), jnp.bfloat16)
    k = 2 * jax.random.normal(keys[1], (batch, seq, kv_heads, HEAD), jnp.bfloat16)
    v = jax.random.normal(keys[2], (batch, seq, kv_heads, HEAD), jnp.bfloat16)
    weight = jax.random.normal(keys[3], (batch, seq, heads, HEAD), jnp.float32)
    return q, k, v, weight


def plain_attention(q, k, v):
    """softmax(q kT / sqrt(D)) v over the causal mask in float32."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bthd->bhqt", q, k, precision="highest") / np.sqrt(q.shape[-1])
    scores = jnp.where(jnp.tril(jnp.ones((q.shape[1],) * 2, bool)), scores, -jnp.inf)
    return jnp.einsum("bhqt,bthd->bqhd", jax.nn.softmax(scores, -1), v, precision="highest")


def relative(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize(
    "seq,heads,kv_heads,block",
    [(256, 4, 2, 128), (512, 4, 2, 128), (256, 8, 2, 128), (512, 8, 2, 256), (256, 2, 2, 128)],
)
def test_kernel_is_the_xla_form_and_plain_attention(seq, heads, kv_heads, block):
    """Output and the gradients of q, k and v: within bf16 rounding of the
    XLA form, and no further from plain float32 attention than the XLA form
    is (both round the probabilities to bf16 for P.V)."""
    q, k, v, weight = qkv(seq, heads, kv_heads, seed=seq + heads)
    forms = {
        "kernel": functools.partial(pallas_attention.causal_attention, block=block, interpret=True),
        "xla": functools.partial(program.blocked_causal_attention, block=block // 2),
        "plain": plain_attention,
    }
    outs, grads = {}, {}
    for name, f in forms.items():
        outs[name] = f(q, k, v)
        loss = lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * weight)  # noqa: E731
        grads[name] = jax.grad(loss, (0, 1, 2))(q, k, v)
    assert outs["kernel"].shape == q.shape and outs["kernel"].dtype == q.dtype
    assert relative(outs["kernel"], outs["xla"]) < 6e-3
    assert relative(outs["kernel"], outs["plain"]) < 1.5 * relative(outs["xla"], outs["plain"]) < 6e-3
    for got, xla, plain in zip(grads["kernel"], grads["xla"], grads["plain"]):
        assert got.dtype == xla.dtype and got.shape == xla.shape
        assert relative(got, xla) < 1e-2
        assert relative(got, plain) < 2 * relative(xla, plain) < 1e-2


@pytest.mark.parametrize("heads", [1, 3])
def test_one_query_head_a_kv_head_of_128(heads):
    """No grouping at all (``models/olmo_hybrid.py``: 15 heads of 128, each with
    its own k and v): a grid step holds one head's block, ``_groups`` is the
    head count, and an odd count is as good as any.  Float32, so the kernel
    differs from plain attention by the order of its sums alone."""
    assert pallas_attention._groups(heads, heads) == heads
    keys = jax.random.split(jax.random.key(heads), 3)
    q, k, v = (jax.random.normal(key, (2, 256, heads, 128)) for key in keys)
    kernel = functools.partial(pallas_attention.causal_attention, block=128, interpret=True)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(kernel(q, k, v), plain_attention(q, k, v), rtol=1e-5, atol=1e-5)
        g_got = jax.grad(lambda q, k, v: kernel(q, k, v).sum(), (0, 1, 2))(q, k, v)
        g_want = jax.grad(lambda q, k, v: plain_attention(q, k, v).sum(), (0, 1, 2))(q, k, v)
    for got, want in zip(g_got, g_want):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("head", [64, 32])
def test_kernel_in_float32_is_plain_attention(head):
    """In float32 the kernel differs from plain attention by the order of its
    sums alone: the limits of test_blocked_causal_attention_is_plain_attention.
    Head size 64 scales q by a power of two outside the kernels, 32 the scores inside."""
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (2, 256, 4, head))
    k = jax.random.normal(keys[1], (2, 256, 2, head))
    v = jax.random.normal(keys[2], (2, 256, 2, head))
    kernel = functools.partial(pallas_attention.causal_attention, block=128, interpret=True)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(kernel(q, k, v), plain_attention(q, k, v), rtol=1e-5, atol=1e-5)
        g_got = jax.grad(lambda q, k, v: kernel(q, k, v).sum(), (0, 1, 2))(q, k, v)
        g_want = jax.grad(lambda q, k, v: plain_attention(q, k, v).sum(), (0, 1, 2))(q, k, v)
    for got, want in zip(g_got, g_want):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_kernel_is_causal():
    """Rows up to t do not move, bit for bit, when q, k and v change after t."""
    q, k, v, _ = qkv(512, 4, 2)
    kernel = functools.partial(pallas_attention.causal_attention, block=128, interpret=True)
    t = 300  # inside the third block
    later = lambda x: x.at[:, t:].set(-x[:, t:])  # noqa: E731
    a, b = kernel(q, k, v), kernel(later(q), later(k), later(v))
    np.testing.assert_array_equal(np.asarray(a[:, :t], np.float32), np.asarray(b[:, :t], np.float32))
    assert float(jnp.abs(a[:, t:].astype(jnp.float32) - b[:, t:].astype(jnp.float32)).max()) > 0


TINY = dict(
    name="lfm2_moe", num_classes=128, hidden_size=128, intermediate_size=96,
    moe_intermediate_size=48, num_attention_heads=2, num_key_value_heads=1,
    num_experts=8, num_experts_per_tok=2, experts_held=2, expert_offset=2,
    num_dense_layers=1, layer_types=("conv", "full_attention", "conv"),
)


def test_model_is_causal_through_the_kernel(monkeypatch):
    """The test steers the model onto the kernel (interpreted); the program
    itself goes by the platform it is lowered for."""
    seq = 256
    monkeypatch.setattr(
        program, "causal_attention",
        functools.partial(pallas_attention.causal_attention, block=128, interpret=True),
    )
    cfg = ModelConfig(**TINY, compute_dtype="float32")
    model = build_model(cfg)
    x = np.asarray(jax.random.randint(jax.random.key(1), (1, 1, seq, 1), 0, 128))
    params = model.init(jax.random.key(0), x, train=False)["params"]
    changed = x.copy()
    changed[:, :, 200:] = (changed[:, :, 200:] + 17) % 128
    a = model.apply({"params": params}, x)
    b = model.apply({"params": params}, changed)
    np.testing.assert_array_equal(np.asarray(a[:, :, :200]), np.asarray(b[:, :, :200]))
    assert float(jnp.abs(a[:, :, 200:] - b[:, :, 200:]).max()) > 0


def lowered(platform: str, seq: int) -> str:
    q, k, v, _ = qkv(seq, 4, 2)
    grad = jax.grad(
        lambda q, k, v: program.causal_attention(q, k, v).astype(jnp.float32).sum(), (0, 1, 2)
    )
    return jax.jit(grad).trace(q, k, v).lower(lowering_platforms=(platform,)).as_text()


@pytest.mark.parametrize(
    "platform,seq,kernel",
    [("tpu", 1024, True), ("tpu", 512, True), ("cpu", 1024, False), ("tpu", 256, False), ("tpu", 64, False)],
)
def test_path_goes_by_platform_and_sequence_length(platform, seq, kernel):
    """The kernel where the program is lowered for a TPU and the kernel's
    block divides the sequence; the XLA form, and no error, everywhere else."""
    text = lowered(platform, seq)
    assert ("tpu_custom_call" in text) == kernel
    # the forward and the backward kernel, and none of the XLA form's per-block products
    assert text.count("stablehlo.custom_call @tpu_custom_call") == (2 if kernel else 0)
    assert pallas_attention.supported(seq) == (seq % pallas_attention.BLOCK == 0)
    # the backward holds a sequence's dK and dV in VMEM: past MAX_SEQ the XLA form takes over
    assert pallas_attention.supported(pallas_attention.MAX_SEQ)
    assert not pallas_attention.supported(pallas_attention.MAX_SEQ + pallas_attention.BLOCK)


def test_kernel_refuses_a_sequence_its_block_does_not_divide():
    q, k, v, _ = qkv(192, 4, 2)
    with pytest.raises(ValueError, match="not a multiple"):
        pallas_attention.causal_attention(q, k, v, block=128, interpret=True)
    assert program.causal_attention(q, k, v).shape == q.shape  # path (b) takes it


def flops_config(seq: int) -> ExperimentConfig:
    return ExperimentConfig(
        model=ModelConfig(**TINY),
        data=DataConfig(dataset="packed_tokens", image_size=(1, seq), num_classes=128),
    )


def test_flop_walk_counts_the_kernel_by_its_cost_estimate():
    """A pallas_call's body holds one grid step's products; the walk takes the
    call's cost_estimate (the causal half) and, of a platform switch, the
    branch of the platform asked for.  The XLA form's query blocks see all the
    keys up to their own end: (n + 1) / n of the causal half at n blocks."""
    from ddlpc_tpu.obs import flops

    seq = 8192  # 16 query blocks of the XLA form: 17/16 of the causal half
    cfg = flops_config(seq)
    xla, _, _ = flops.product_flops(cfg, 1, 1, platform="cpu")
    kernel, _, _ = flops.product_flops(cfg, 1, 1, platform="tpu")
    assert flops.product_flops(cfg, 1, 1) == flops.product_flops(cfg, 1, 1, platform="cpu")
    model = dataclasses.asdict(cfg.model)
    heads, head = model["num_attention_heads"], model["hidden_size"] // model["num_attention_heads"]
    causal_half = 4 * seq * seq * heads * head // 2
    assert xla - kernel == causal_half // 16
    assert kernel < xla < 1.07 * kernel
    assert kernel > causal_half  # the scores are in the count, and so is everything else


def test_kernel_counter_reads_zero_off_the_chip():
    """attention_kernel_layers: the attention operators that lowered to the
    kernel.  0 on the CPU, whether or not the kernel would take the length."""
    model = build_model(ModelConfig(**TINY))
    for seq in (64, 512):
        x = jnp.zeros((1, 1, seq, 1), jnp.int32)
        params = jax.eval_shape(lambda: model.init(jax.random.key(0), x, train=False))["params"]
        counters = jax.eval_shape(
            lambda p: model.apply({"params": p}, x, train=True, mutable=["counters"])[1], params
        )
        assert "attention_kernel_layers" in counters["counters"]["max"]
    params = model.init(jax.random.key(0), x, train=False)["params"]
    _, aux = jax.jit(lambda p: model.apply({"params": p}, x, train=True, mutable=["counters"]))(params)
    assert int(aux["counters"]["max"]["attention_kernel_layers"]) == 0
