"""The fused causal flash-attention kernel and where ``causal_attention``
takes it.

The kernel runs here in Pallas's TPU interpret mode.  Interpret mode (jax
0.9.0) cannot run this kernel under ``jax.vmap`` (its grid and dimension
semantics then differ in length), so the tests fold a worker axis into the
batch instead; on the chip the Mosaic lowering batches the grid itself.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import load_arch
from repro.kernels import flash_attention as FA
from repro.models import layers as L
from repro.models import transformer as T

W, B = 2, 1         # W workers folded into the batch
# bf16 inputs and output: the forward may differ from the einsum path by
# about one bf16 step at |out| near 2 (2**-6); gradients by the rounding of
# the probabilities and their cotangents to bf16, relative to their norm.
FWD_ATOL = 2 ** -5
GRAD_RTOL = 1e-2


def _qkv(S, H, hd, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    return [jax.random.normal(k, (W * B, S, H, hd), jnp.float32).astype(jnp.bfloat16)
            for k in ks]


def _einsum(q, k, v):
    return L.causal_attention(q, k, v, q_block=q.shape[1], seq_sharded=True)


def _value_and_grads(fn, q, k, v, cot):
    out, vjp = jax.vjp(fn, q, k, v)
    return [np.asarray(x, np.float32) for x in (out, *vjp(cot))]


@pytest.mark.parametrize("S,H,hd", [
    # forward: one tile; backward: 3x3 tiles of 128
    (384, 2, 64),    # the cell's head dim: two heads a step
    (384, 2, 128),   # one head a step; a scale that is no power of two
    (256, 4, 64),    # two head groups
    (256, 16, 64),   # eight head groups: GPT-2 medium's heads
    (1536, 2, 64),   # forward and backward: 3x3 tiles of 512
    (256, 1, 256),   # a head two lane tiles wide
])
def test_kernel_matches_einsum_path(S, H, hd):
    q, k, v, cot = _qkv(S, H, hd)
    with pltpu.force_tpu_interpret_mode():
        got = _value_and_grads(FA.flash_attention, q, k, v, cot)
    want = _value_and_grads(_einsum, q, k, v, cot)
    np.testing.assert_allclose(got[0], want[0], rtol=0, atol=FWD_ATOL)
    for name, g, w in zip("qkv", got[1:], want[1:]):
        rel = np.linalg.norm(g - w) / np.linalg.norm(w)
        assert rel < GRAD_RTOL, (name, rel)


def test_blocks_and_head_groups_come_from_the_shapes():
    seqs = (128, 384, 512, 768, 1024, 4096)
    assert [FA.block_size(s) for s in seqs] == [128, 128, 512, 256, 512, 512]
    assert [FA.forward_block_size(s) for s in seqs] == [
        128, 384, 512, 768, 1024, 512]
    groups = {(12, 64): 2, (20, 64): 2, (25, 64): None, (8, 128): 1,
              (4, 256): 1, (4, 96): None, (4, 32): None}
    assert {hk: FA.heads_per_step(*hk) for hk in groups} == groups
    assert FA.supported(8192, 12, 64)          # dq accumulator at its 4 MiB
    assert not FA.supported(16384, 12, 64)
    assert not FA.supported(1000, 12, 64)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

@pytest.fixture
def on_tpu(monkeypatch):
    """``causal_attention`` as it decides on a TPU backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shapes(S=1024, H=12, KVH=12, hd=64):
    return (jax.ShapeDtypeStruct((4, S, H, hd), jnp.bfloat16),
            jax.ShapeDtypeStruct((4, S, KVH, hd), jnp.bfloat16))


@pytest.mark.parametrize("case,kw,takes", [
    ("mha_s1024", {}, True),
    ("mha_hd128", dict(shape=dict(hd=128)), True),
    ("mha_hd256", dict(shape=dict(hd=256)), True),
    ("window", dict(window=512), False),
    ("gqa", dict(shape=dict(KVH=4)), False),
    ("mqa", dict(shape=dict(KVH=1)), False),
    ("s_not_128_multiple", dict(shape=dict(S=1000)), False),
    ("hd_96", dict(shape=dict(hd=96)), False),
    ("odd_heads_of_64", dict(shape=dict(H=25, KVH=25)), False),
    ("seq_sharded", dict(seq_sharded=True), False),
])
def test_dispatch_on_tpu(on_tpu, case, kw, takes):
    q, k = _shapes(**kw.get("shape", {}))
    assert L.fused_attention_applies(q, k, kw.get("window"),
                                     kw.get("seq_sharded", False)) is takes


def test_dispatch_keeps_einsum_on_cpu():
    q, k = _shapes()
    assert not L.fused_attention_applies(q, k)


def _loss_jaxpr(cfg, seq):
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((2, seq), jnp.int32)}
    if cfg.family == "encdec":
        batch["frames"] = jax.ShapeDtypeStruct((2, 16, cfg.d_model), cfg.act_dtype)
    if cfg.family == "vlm":
        batch["patches"] = jax.ShapeDtypeStruct((2, 8, cfg.d_model), cfg.act_dtype)
    grad = jax.grad(lambda p, b: T.loss_fn(p, b, cfg))
    return str(jax.make_jaxpr(grad)(params, batch))


@pytest.mark.parametrize("arch", [
    "gemma3_1b", "recurrentgemma_2b",                      # sliding window
    "deepseek_67b", "granite_34b", "granite_moe_3b_a800m",  # GQA / MQA
    "llama4_maverick_400b_a17b", "llava_next_34b", "minitron_4b",
])
def test_gqa_and_swa_configs_trace_as_before(monkeypatch, arch):
    # heads of 64, so that only GQA or the window keeps them off the kernel
    cfg = dataclasses.replace(load_arch(arch).SMOKE, head_dim=64)
    assert FA.supported(128, cfg.n_heads, cfg.hd)
    before = _loss_jaxpr(cfg, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    after = _loss_jaxpr(cfg, 128)
    assert "pallas_call" not in after
    assert after == before


@pytest.mark.parametrize("arch,change,takes", [
    ("gpt2_small", {}, True),
    ("gpt2_medium", {}, True),
    ("whisper_large_v3", {}, True),     # the decoder's causal self-attention
    ("gpt2_small", dict(attn_seq_shard=True), False),
])
def test_mha_config_takes_the_kernel_on_tpu(on_tpu, arch, change, takes):
    cfg = dataclasses.replace(load_arch(arch).SMOKE, head_dim=64, **change)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    with jax.set_mesh(mesh):   # for attn_seq_shard's sharding constraints
        assert ("pallas_call" in _loss_jaxpr(cfg, 128)) is takes
        assert "pallas_call" not in _loss_jaxpr(cfg, 96)    # S % 128 != 0


# ---------------------------------------------------------------------------
# Remat: the layer checkpoint keeps the kernel's output and log-sum-exp
# ---------------------------------------------------------------------------

def _pallas_calls(jaxpr) -> int:
    return str(jaxpr).count("pallas_call[")


@pytest.mark.parametrize("wrap,calls", [
    (lambda layer: layer, 2),                    # forward, backward
    (jax.checkpoint, 3),                         # and the forward again
    (lambda layer: jax.checkpoint(layer, policy=T.SAVE_KERNEL_RESIDUALS), 2),
], ids=["no_remat", "remat_saving_nothing", "remat_saving_the_residuals"])
def test_layer_checkpoint_keeps_the_kernels_residuals(wrap, calls):
    """A layer through the kernel and an output projection, traced (not
    run: interpret mode cannot partially evaluate a checkpoint around the
    kernel)."""
    S, H, hd = 256, 2, 64

    def layer(x, w):
        q, k, v = ((x @ w[i]).reshape(1, S, H, hd) for i in range(3))
        return FA.flash_attention(q, k, v).reshape(1, S, H * hd) @ w[3]

    x = jax.ShapeDtypeStruct((1, S, H * hd), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, H * hd, H * hd), jnp.bfloat16)
    grad = jax.grad(lambda x, w: wrap(layer)(x, w).astype(jnp.float32).sum(),
                    argnums=1)
    assert _pallas_calls(jax.make_jaxpr(grad)(x, w)) == calls


@pytest.mark.parametrize("remat_policy", ["full", "dots"])
def test_model_remat_runs_the_kernel_twice(on_tpu, remat_policy):
    """Through ``loss_fn``'s layer scan, with either remat policy: the
    forward and the fused backward, no third call."""
    cfg = dataclasses.replace(load_arch("gpt2_small").SMOKE, head_dim=64)
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 128), jnp.int32)}
    grad = jax.grad(lambda p, b: T.loss_fn(p, b, cfg, remat=True,
                                           remat_policy=remat_policy))
    assert _pallas_calls(jax.make_jaxpr(grad)(params, batch)) == 2


def _saved_by_remat(cfg, seq=128):
    """Shapes and dtypes of what the gradient of ``loss_fn`` (remat on)
    keeps from its forward for its backward."""
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((2, seq), jnp.int32)}
    if cfg.family == "encdec":
        batch["frames"] = jax.ShapeDtypeStruct((2, 16, cfg.d_model), cfg.act_dtype)

    def residuals(p, b):
        _, vjp = jax.vjp(lambda p: T.loss_fn(p, b, cfg, remat=True), p)
        return jax.tree.leaves(vjp)

    return sorted((r.shape, str(r.dtype))
                  for r in jax.eval_shape(residuals, params, batch))


def _with_plain_checkpoint(monkeypatch):
    """``jax.checkpoint`` with no policy: remat that saves nothing."""
    checkpoint = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint",
                        lambda fn, policy=None: checkpoint(fn))


@pytest.mark.parametrize("arch,backend", [
    ("gpt2_small", "cpu"),            # the einsum path
    ("whisper_large_v3", "cpu"),      # and the encoder's full attention
    ("gemma3_1b", "tpu"),             # sliding window, GQA
    ("recurrentgemma_2b", "tpu"),     # RG-LRU, sliding window
    ("granite_moe_3b_a800m", "tpu"),  # GQA, MoE
])
def test_remat_saves_nothing_where_the_kernel_is_not_taken(monkeypatch, arch,
                                                           backend):
    cfg = dataclasses.replace(load_arch(arch).SMOKE, head_dim=64)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    saved = _saved_by_remat(cfg)
    _with_plain_checkpoint(monkeypatch)
    assert saved == _saved_by_remat(cfg)


def test_remat_saves_the_kernels_output_and_lse_per_layer(on_tpu,
                                                          monkeypatch):
    """Where the kernel is taken, remat keeps two more stacked values than
    a checkpoint that saves nothing: each layer's output (B, S, H*hd) in
    the activations' dtype and its log-sum-exp (B, H, 1, S) in f32."""
    cfg = dataclasses.replace(load_arch("gpt2_small").SMOKE, head_dim=64)
    saved = _saved_by_remat(cfg)
    _with_plain_checkpoint(monkeypatch)
    plain = _saved_by_remat(cfg)
    for r in plain:
        saved.remove(r)
    n, S, H = cfg.n_layers, 128, cfg.n_heads
    assert sorted(saved) == sorted([
        ((n, 2, S, H * 64), str(jnp.dtype(cfg.act_dtype))),
        ((n, 2, H, 1, S), "float32")])
