"""Per-architecture smoke tests (deliverable f): instantiate the REDUCED
variant of each assigned arch family, run one forward + one DSM outer
train step + one decode step on CPU; assert output shapes and finiteness.

FULL configs are not allocated here (``jax.eval_shape`` only).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, PAPER_ARCH_IDS, load_arch
from repro.core import DSMConfig, constant, dsm_init, get_base_optimizer, make_dsm_step
from repro.models import transformer as T

ALL_IDS = ARCH_IDS + PAPER_ARCH_IDS

# archs whose smoke step takes >~8s on CPU (recurrent scans, MoE routing,
# audio/VLM encoders): tier-2.  The cheap decoder-only ones stay in tier-1
# so every commit still exercises the full forward+DSM+decode path.
_SLOW_ARCHS = {
    "recurrentgemma_2b", "llama4_maverick_400b_a17b", "mamba2_780m",
    "whisper_large_v3", "minitron_4b", "deepseek_67b", "llava_next_34b",
    "granite_moe_3b_a800m", "gpt2_large", "gemma3_1b",
}
_PARAMS = [
    pytest.param(a, marks=pytest.mark.slow) if a in _SLOW_ARCHS
    else pytest.param(a)
    for a in ALL_IDS
]


def _smoke_batch(cfg, key, W=2, tau=2, accum=1, B=2, S=32):
    lead = (W, tau, accum, B)
    batch = {}
    if cfg.family == "vlm":
        batch["tokens"] = jax.random.randint(key, lead + (S - cfg.n_patches,), 0, cfg.vocab_size)
        batch["patches"] = jax.random.normal(key, lead + (cfg.n_patches, cfg.d_model), jnp.float32)
    elif cfg.family == "encdec":
        batch["tokens"] = jax.random.randint(key, lead + (S,), 0, cfg.vocab_size)
        batch["frames"] = jax.random.normal(key, lead + (cfg.enc_len, cfg.d_model), jnp.float32)
    else:
        batch["tokens"] = jax.random.randint(key, lead + (S,), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch_id", _PARAMS)
def test_smoke_forward_and_train_step(arch_id):
    mod = load_arch(arch_id)
    cfg, topo = mod.SMOKE, mod.TOPO
    assert cfg.n_layers <= 2 and cfg.d_model <= 512 and cfg.n_experts <= 4

    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)

    # forward / loss
    batch = _smoke_batch(cfg, key)
    micro = jax.tree.map(lambda x: x[0, 0, 0], batch)
    loss = T.loss_fn(params, micro, cfg, remat=False)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), arch_id

    # one DSM outer step with the arch's configured base optimizer
    base = get_base_optimizer(topo.base_opt)
    step = make_dsm_step(
        lambda p, b: T.loss_fn(p, b, cfg, remat=False),
        base, DSMConfig(tau=2, global_lr=0.5), constant(1e-3),
    )
    state = dsm_init(params, base, n_workers=2)
    state, metrics = jax.jit(step)(state, batch)
    assert np.isfinite(float(metrics["loss"])), arch_id
    for leaf in jax.tree.leaves(state.x0):
        assert np.isfinite(np.asarray(leaf, np.float32)).all(), arch_id

    # params actually moved
    moved = any(
        float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()) > 0
        for a, b in zip(jax.tree.leaves(state.x0), jax.tree.leaves(params))
    )
    assert moved, arch_id


@pytest.mark.parametrize("arch_id", ALL_IDS)
def test_smoke_decode_step(arch_id):
    mod = load_arch(arch_id)
    cfg = mod.SMOKE
    key = jax.random.PRNGKey(1)
    params = T.init_params(key, cfg)
    B, S_max = 2, 48
    cache = T.init_cache(cfg, B, S_max, jnp.float32)
    if cfg.family == "encdec":
        # fill cross-attn cache entries with encoder output shapes
        pass  # init_cache already allocates kx/vx at enc_len
    tok = jax.random.randint(key, (B,), 0, cfg.vocab_size)
    logits, new_cache = jax.jit(
        lambda p, c, t, pos: T.decode_step(p, c, t, pos, cfg)
    )(params, cache, tok, jnp.int32(3))
    assert logits.shape == (B, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits)).all(), arch_id


@pytest.mark.parametrize("arch_id", ALL_IDS)
def test_full_config_abstract_shapes(arch_id):
    """FULL configs must eval_shape cleanly (no allocation)."""
    from repro.configs import specs as S

    mod = load_arch(arch_id)
    n = S.param_count(mod.FULL)
    assert n > 0
    aps = S.abstract_params(mod.FULL)
    assert all(l.shape is not None for l in jax.tree.leaves(aps))
