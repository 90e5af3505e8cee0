"""Substrate tests: data pipeline, checkpointing, serving, sharding rules."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as CK
from repro.configs import ARCH_IDS, load_arch
from repro.configs import specs as S
from repro.configs.base import ModelConfig
from repro.data.pipeline import MarkovCorpus, TextCorpus, dsm_batches
from repro.distributed import sharding as shd
from repro.models import transformer as T
from repro.train.serve import generate


def test_markov_corpus_shapes_and_determinism():
    c = MarkovCorpus(100, seed=3)
    r1 = c.sample(np.random.default_rng(0), 4, 32)
    r2 = c.sample(np.random.default_rng(0), 4, 32)
    np.testing.assert_array_equal(r1, r2)
    assert r1.shape == (4, 32) and r1.dtype == np.int32
    assert r1.min() >= 0 and r1.max() < 100


def test_markov_corpus_scales_to_gpt2_vocab():
    """Transitions are hashed, not tabled: a 50,257-token chain costs a few
    MB and well under a second to build, whatever the vocabulary."""
    import time
    import tracemalloc

    tracemalloc.start()
    t0 = time.perf_counter()
    c = MarkovCorpus(50257, seed=1)
    seconds = time.perf_counter() - t0
    peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    assert seconds < 0.5 and peak < 4 * 2**20, (seconds, peak)
    r1 = c.sample(np.random.default_rng(0), 8, 256)
    r2 = MarkovCorpus(50257, seed=1).sample(np.random.default_rng(0), 8, 256)
    np.testing.assert_array_equal(r1, r2)
    r3 = MarkovCorpus(50257, seed=2).sample(np.random.default_rng(0), 8, 256)
    assert not np.array_equal(r1[:, 2:], r3[:, 2:])
    assert r1.min() >= 0 and r1.max() < 50257
    # tokens reach the top of the range, not just a low slice of it
    assert r1.max() > 50257 * 0.9


def test_compile_cache_dir_defers_to_env(monkeypatch):
    """The launcher's compile cache is a fixed directory in the checkout,
    unless JAX_COMPILATION_CACHE_DIR names one (JAX then reads it)."""
    from repro.launch import train as LT

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert LT.compile_cache_dir() is None
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = LT.compile_cache_dir()
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
    assert path == os.path.join(root, ".jax_cache")
    assert LT.compile_cache_dir() == path


def test_markov_corpus_is_learnable_structure():
    """An order-2 table must make the chain's bigram-conditional entropy
    far below uniform — i.e. there's signal for training curves."""
    c = MarkovCorpus(50, branch=4, seed=0)
    seq = c.sample(np.random.default_rng(1), 1, 5000)[0]
    # empirical conditional entropy given (t-2,t-1) — estimate on pairs
    from collections import Counter, defaultdict

    ctx = defaultdict(Counter)
    for i in range(2, len(seq)):
        ctx[(seq[i - 2], seq[i - 1])][seq[i]] += 1
    ents = []
    for counter in ctx.values():
        tot = sum(counter.values())
        if tot < 5:
            continue
        p = np.array([v / tot for v in counter.values()])
        ents.append(-(p * np.log(p)).sum())
    assert np.mean(ents) < np.log(50) * 0.75


def test_dsm_batches_layout_and_heterogeneity():
    c = MarkovCorpus(64, seed=0)
    it = dsm_batches(c, n_workers=3, tau=2, accum=2, b_micro=4, seq=16, seed=5)
    b = next(it)
    assert b["tokens"].shape == (3, 2, 2, 4, 16)
    # heterogeneous: workers draw from distinct streams
    assert not np.array_equal(b["tokens"][0], b["tokens"][1])


def test_text_corpus_self_hosting():
    c = TextCorpus(root=os.path.join(os.path.dirname(__file__), ".."),
                   pattern="src/**/*.py")
    s = c.sample(np.random.default_rng(0), 2, 64)
    assert s.shape == (2, 64) and s.max() < 256


def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "nested": {"b": jnp.ones((4,), jnp.bfloat16) * 1.5,
                   "c": jnp.arange(3, dtype=jnp.int32)},
    }
    path = str(tmp_path / "ck")
    CK.save(path, tree, step=42)
    like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    restored, step = CK.restore(path, like)
    assert step == 42
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_generate_matches_forward_oracle():
    cfg = ModelConfig(
        name="t", family="lm", n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab_size=300, head_dim=16,
        pattern=("swa:dense", "attn:dense"), window=8,
        dtype="float32", param_dtype="float32",
    )
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    prompt = jax.random.randint(key, (2, 21), 0, 300)
    toks, stats = generate(params, cfg, prompt, max_new_tokens=4)
    cur = prompt
    for i in range(4):
        h, _, _ = T.hidden_states(params, {"tokens": cur}, cfg, remat=False)
        lg = T._logits(params, h, cfg)[:, -1, : cfg.vocab_size]
        nxt = jnp.argmax(lg, -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(nxt), np.asarray(toks[:, i]))
        cur = jnp.concatenate([cur, nxt[:, None]], axis=1)
    assert stats["tok_per_s"] > 0


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_sharding_rules_divisible(arch_id):
    """Every sharded dim must divide by its mesh-axis product (16x16 mesh)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.workers import broadcast_workers

    mod = load_arch(arch_id)
    aps = S.abstract_params(mod.FULL)
    W = mod.TOPO.n_workers_single
    zero = max(16 // W, 1)
    # per-worker params in the state's layout (worker second when stacked)
    wparams = jax.eval_shape(lambda p: broadcast_workers(p, W), aps)
    specs = shd.param_pspecs(wparams, model=16, zero=zero, worker_axis=True)
    sizes = {"worker": W, "zero": zero, "model": 16}

    flat_l = jax.tree_util.tree_flatten_with_path(wparams)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_l) == len(flat_s)
    for (path, leaf), spec in zip(flat_l, flat_s):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            prod = 1
            for a in axes:
                prod *= sizes[a]
            assert dim % prod == 0, (arch_id, path, leaf.shape, spec)
