"""Compiles for a described TPU v5e chip: nothing here runs on a chip.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
Pallas tile the chip cannot hold, a step that does not fit its HBM.  These
tests compile the fused DSM kernel at GPT-2 small slab shapes and one whole
DSM outer step at GPT-2 small widths for one v5e chip, from shapes alone.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so describing it while the
module is collected would fail in every other test worker.
"""

import dataclasses
import functools
import os

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


def test_gpt2_small_dsm_step_fits_one_v5e(one_chip):
    """The trainer's outer step at GPT-2 small widths (2 of 12 layers), W=4
    workers, tau=12, b_micro=4, seq=1024, remat on, state donated."""
    from repro.configs import load_arch
    from repro.models import transformer as T
    from repro.train.trainer import TrainSettings, build_algorithm

    cfg = dataclasses.replace(load_arch("gpt2_small").FULL, n_layers=2)
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
    mem = compiled.memory_analysis()
    state_bytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(state))
    # donation: the new state is written over the old one
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM
