"""Compiles for a described TPU v5e chip: nothing here runs on a chip.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
Pallas tile the chip cannot hold, a step that does not fit its HBM.  These
tests compile the fused DSM kernel at GPT-2 small slab shapes and whole DSM
outer steps at GPT-2 small widths for one v5e chip, from shapes alone.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so describing it while the
module is collected would fail in every other test worker.
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.dsm_update import LANES, dsm_update_2d

GIB = 2 ** 30
V5E_HBM = 16 * GIB


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("rows", [
    50688 * 768 // LANES,   # padded GPT-2 embedding (50688, 768)
    768 * 3072 // LANES,    # one MLP matrix
    6,                      # a short slab: one partial tile
])
def test_dsm_kernel_compiles_for_v5e(one_chip, rows):
    x = jax.ShapeDtypeStruct((rows, LANES), jnp.bfloat16, sharding=one_chip)
    m = jax.ShapeDtypeStruct((rows, LANES), jnp.float32, sharding=one_chip)
    gamma = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    fn = functools.partial(dsm_update_2d, eta=1.0, beta1=0.95, beta2=0.98,
                           lam=0.1, interpret=False)
    compiled = jax.jit(fn).lower(x, m, x, gamma).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_gpt2_small_step(one_chip, n_layers):
    """The trainer's outer step at GPT-2 small widths, W=4 workers, tau=12,
    b_micro=4, seq=1024, remat on, state donated: (compiled, state shapes)."""
    from repro.configs import load_arch
    from repro.models import transformer as T
    from repro.train.trainer import TrainSettings, build_algorithm

    cfg = dataclasses.replace(load_arch("gpt2_small").FULL, n_layers=n_layers)
    s = TrainSettings(algorithm="dsm", base_opt="adamw", n_workers=4, tau=12,
                      b_micro=4, seq=1024, remat=True)

    def loss_fn(p, mb):
        return T.loss_fn(p, mb, cfg, remat=s.remat)

    init, step, _, _ = build_algorithm(loss_fn, s)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
    state = _on(one_chip, jax.eval_shape(lambda p: init(p, s.n_workers), params))
    batch = _on(one_chip, {"tokens": jax.ShapeDtypeStruct(
        (s.n_workers, s.tau, 1, s.b_micro, s.seq), jnp.int32)})
    rng = _on(one_chip, jax.eval_shape(lambda: key))

    compiled = jax.jit(step, donate_argnums=0).lower(state, batch, rng).compile()
    return compiled, state


def _fits_one_v5e(compiled, state):
    mem = compiled.memory_analysis()
    state_bytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(state))
    # donation: the new state is written over the old one
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM


def test_gpt2_small_dsm_step_fits_one_v5e(one_chip):
    """2 of the 12 layers, on the einsum attention path (CPU backend)."""
    _fits_one_v5e(*_compile_gpt2_small_step(one_chip, n_layers=2))


def test_gpt2_small_dsm_step_with_flash_attention_fits_one_v5e(one_chip,
                                                                monkeypatch):
    """All 12 layers with the fused attention kernel, as the step takes it
    on a TPU backend: Mosaic accepts its tiles, the step fits, the kernel's
    calls keep the attention and remat scopes, and no (S, S) score
    convolution is left."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, state = _compile_gpt2_small_step(one_chip, n_layers=12)
    _fits_one_v5e(compiled, state)
    hlo = compiled.as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = [re.search(r'op_name="([^"]*)"', c).group(1) for c in calls]
    # forward, remat forward, fused backward
    assert len(names) == 3 and all("/attention/" in n for n in names), names
    assert sum("rematted_computation" in n for n in names) == 1, names
    scores = re.compile(r"= \w+\[[\d,]*1024,1024\]\S* convolution")
    assert not [line for line in hlo.splitlines() if scores.search(line)]


def test_flash_attention_fits_half_the_scoped_vmem(one_chip, monkeypatch):
    """The kernel's forward and backward at the cell's shapes (W=4 vmapped,
    B 4, S 1024, 12 heads of 64) within 8 MiB of VMEM, half the default
    scoped limit: inside a whole step the compiler leaves a kernel less
    than it does alone."""
    from repro.kernels import flash_attention as FA

    monkeypatch.setattr(FA.pltpu, "CompilerParams", functools.partial(
        FA.pltpu.CompilerParams, vmem_limit_bytes=8 * 2 ** 20))
    x = jax.ShapeDtypeStruct((4, 4, 1024, 12, 64), jnp.bfloat16,
                             sharding=one_chip)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(jax.vmap(FA.flash_attention), q, k, v)
        return out, vjp(out)

    compiled = jax.jit(fwd_bwd).lower(x, x, x).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2
