"""Config/spec invariants across ALL archs x input shapes (catches config
drift before it reaches a compile)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (
    ARCH_IDS,
    INPUT_SHAPES,
    PAPER_ARCH_IDS,
    arch_supports_shape,
    load_arch,
)
from repro.analysis.comm import bytes_per_outer_step
from repro.configs import specs as S
from repro.core.schedules import cosine_with_warmup


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_train_batch_specs_consistent(arch_id):
    mod = load_arch(arch_id)
    cfg, topo = mod.FULL, mod.TOPO
    shape = INPUT_SHAPES["train_4k"]
    for W in (topo.n_workers_single, topo.n_workers_multi):
        batch = S.train_batch_specs(cfg, topo, shape, W)
        toks = batch["tokens"]
        Wb, tau, acc, bm = toks.shape[:4]
        assert (Wb, tau, acc) == (W, topo.tau, topo.grad_accum)
        assert W * acc * bm == shape.global_batch
        if cfg.family == "vlm":
            assert toks.shape[-1] + cfg.n_patches == shape.seq_len
            assert batch["patches"].shape[-2:] == (cfg.n_patches, cfg.d_model)
        elif cfg.family == "encdec":
            assert batch["frames"].shape[-2:] == (cfg.enc_len, cfg.d_model)
        else:
            assert toks.shape[-1] == shape.seq_len


@pytest.mark.parametrize("arch_id", ARCH_IDS)
@pytest.mark.parametrize("shape_name", ["prefill_32k", "decode_32k", "long_500k"])
def test_serve_specs_build(arch_id, shape_name):
    mod = load_arch(arch_id)
    cfg, topo = mod.FULL, mod.TOPO
    if not arch_supports_shape(cfg, topo, shape_name):
        pytest.skip("spec-sanctioned long-context skip (DESIGN.md)")
    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "prefill":
        b = S.prefill_batch_specs(cfg, shape)
        assert b["tokens"].shape[0] == shape.global_batch
    else:
        d = S.decode_specs(cfg, shape)
        assert d["tokens"].shape == (shape.global_batch,)
        # cache tree must be non-empty and finite-sized
        leaves = jax.tree.leaves(d["cache"])
        assert leaves, arch_id
        total = sum(np.prod(l.shape) * l.dtype.itemsize for l in leaves)
        assert total > 0


@pytest.mark.parametrize("arch_id", ARCH_IDS + PAPER_ARCH_IDS)
def test_vocab_padding_divides_model_axis(arch_id):
    cfg = load_arch(arch_id).FULL
    assert cfg.padded_vocab % 16 == 0  # model-axis shardable
    assert cfg.padded_vocab >= cfg.vocab_size


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_pattern_covers_layers(arch_id):
    cfg = load_arch(arch_id).FULL
    kinds = cfg.layer_kinds()
    assert len(kinds) == cfg.n_layers
    assert cfg.n_scan_blocks * len(cfg.pattern) + cfg.n_rem_layers == cfg.n_layers


def test_cosine_schedule_endpoints():
    sched = cosine_with_warmup(1e-3, total_steps=1000, warmup_steps=100,
                               final_frac=0.05)
    assert float(sched(0)) < 2e-5  # warmup start
    np.testing.assert_allclose(float(sched(100)), 1e-3, rtol=0.02)  # peak
    np.testing.assert_allclose(float(sched(999)), 5e-5, rtol=0.05)  # floor
    # monotone decay after warmup
    vals = [float(sched(t)) for t in range(100, 1000, 100)]
    assert all(a >= b for a, b in zip(vals, vals[1:]))


def test_comm_model_reduction_matches_tau():
    r_dsm = bytes_per_outer_step("gpt2_small", "dsm", tau=12)
    r_ps = bytes_per_outer_step("gpt2_small", "perstep", tau=12)
    assert r_ps["wire_bytes_per_outer"] == 12 * r_dsm["wire_bytes_per_outer"]
    np.testing.assert_allclose(r_dsm["reduction_vs_perstep"], 12.0)


def test_momentum_dtype_knob():
    from repro.core import dsm_init, sgd

    params = {"w": jnp.zeros((8,), jnp.bfloat16)}
    st = dsm_init(params, sgd(), 2, momentum_dtype=jnp.bfloat16)
    assert st.m["w"].dtype == jnp.bfloat16
    st32 = dsm_init(params, sgd(), 2)
    assert st32.m["w"].dtype == jnp.float32
