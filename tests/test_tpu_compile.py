"""Compiles for a described TPU v5e chip: nothing here runs on a chip.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
Pallas tile the chip cannot hold, a step that does not fit its HBM.  These
tests compile the fused DSM kernel at GPT-2 small slab shapes and whole DSM
outer steps at GPT-2 small and medium widths for one v5e chip, from shapes
alone.  The whole steps also read ``hlo_audit.relayout_report``: with the
worker axis second in layer-stacked state (repro.core.workers) the tau
loop re-lays out no state, XLA recomputes nothing for want of memory, and
the layer checkpoint keeps the attention kernel's output and log-sum-exp,
so no kernel call runs again under remat.

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


def _compile_dsm_step(one_chip, arch, n_workers, n_layers):
    """The trainer's outer step at ``arch``'s published widths, ``n_workers``
    vmapped workers, tau=12, b_micro=4, seq=1024, remat on, state donated:
    (compiled, state shapes)."""
    from repro.configs import load_arch
    from repro.models import transformer as T
    from repro.train.trainer import TrainSettings, build_algorithm

    cfg = dataclasses.replace(load_arch(arch).FULL, n_layers=n_layers)
    s = TrainSettings(algorithm="dsm", base_opt="adamw", n_workers=n_workers,
                      tau=12, b_micro=4, seq=1024, remat=True)

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


def _aliases_the_state(mem, state):
    state_bytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(state))
    # donation: the new state is written over the old one
    assert mem.alias_size_in_bytes >= state_bytes


def _fits_one_v5e(compiled, state):
    mem = compiled.memory_analysis()
    _aliases_the_state(mem, state)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM


def _no_relayout(hlo):
    """No XLA rematerialization clone and no re-laid-out state leaf."""
    from repro.analysis.hlo_audit import relayout_report

    report = relayout_report(hlo)
    assert report["remat_clones"] == 0, report
    assert report["state_copy_bytes"] == 0, report


@pytest.mark.parametrize("worker_axis,relaid_out", [(0, 1), (1, 0)],
                         ids=["worker_first", "worker_second"])
def test_relayout_report_reads_the_compilers_copies(one_chip, worker_axis,
                                                    relaid_out):
    """The counter on the compiler's own text: a tau loop of vmapped
    gradient steps through a layer scan, state donated.  With the worker
    first the compiler re-lays out the (W, L, D, D) state at the entry, as
    the DSM step did before the worker axis moved; with it second it does
    not."""
    from repro.analysis.hlo_audit import relayout_report

    n_workers, n_layers, d, b, tau = 2, 4, 256, 16, 3

    def per_worker(w, x):
        h, _ = jax.lax.scan(lambda h, wl: (jnp.tanh(h @ wl), None), x, w)
        return (h ** 2).sum()

    grad = jax.vmap(jax.grad(per_worker), in_axes=(worker_axis, 0),
                    out_axes=worker_axis)

    def step(w, xs):
        return jax.lax.scan(lambda w, x: (w - 0.1 * grad(w, x), None),
                            w, xs)[0]

    shape = [n_layers, d, d]
    shape.insert(worker_axis, n_workers)
    w = jax.ShapeDtypeStruct(tuple(shape), jnp.float32, sharding=one_chip)
    xs = jax.ShapeDtypeStruct((tau, n_workers, b, d), jnp.float32,
                              sharding=one_chip)
    compiled = jax.jit(step, donate_argnums=0).lower(w, xs).compile()
    report = relayout_report(compiled.as_text())
    assert report["state_copies"] == relaid_out, report
    assert report["state_copy_bytes"] == relaid_out * 4 * w.size, report


def _kernel_path_in_attention(hlo, score_dims=r"[\d,]*1024,1024"):
    """The fused attention kernel's calls keep the attention scope, the
    layer checkpoint keeps the kernel's output and log-sum-exp so the
    backward runs no forward call again, and no (S, S) score convolution
    (output dims ``score_dims``) is left."""
    from repro.analysis.hlo_audit import relayout_report

    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = [re.search(r'op_name="([^"]*)"', c).group(1) for c in calls]
    # forward, fused backward
    assert len(names) == 2 and all("/attention/" in n for n in names), names
    assert relayout_report(hlo)["kernel_recomputes"] == 0, names
    scores = re.compile(rf"= \w+\[{score_dims}\]\S* convolution")
    assert not [line for line in hlo.splitlines() if scores.search(line)]


def test_gpt2_small_dsm_step_fits_one_v5e(one_chip):
    """2 of the 12 layers, on the einsum attention path (CPU backend)."""
    _fits_one_v5e(*_compile_dsm_step(one_chip, "gpt2_small", n_workers=4,
                                     n_layers=2))


def test_gpt2_small_dsm_step_with_flash_attention_fits_one_v5e(one_chip,
                                                                monkeypatch):
    """All 12 layers with the fused attention kernel, as the step takes it
    on a TPU backend: Mosaic accepts its tiles, the step fits, and the state
    is not re-laid out."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, state = _compile_dsm_step(one_chip, "gpt2_small", n_workers=4,
                                        n_layers=12)
    _fits_one_v5e(compiled, state)
    _kernel_path_in_attention(compiled.as_text())
    _no_relayout(compiled.as_text())


def test_gpt2_medium_dsm_step_with_flash_attention_fits_one_v5e(one_chip,
                                                                 monkeypatch):
    """All 24 layers of GPT-2 medium, W=2, with the fused attention kernel
    (eight groups of two heads of 64).  The bound is the compiler's own
    peak of live bytes: 11.89 GB with each layer's kernel output kept
    across the layer checkpoint (11.47 GB without), where worker-first
    stacked state took 15.90 GB and made XLA recompute the logits, the
    MLP's w1 product and six attention projections to fit.  With n_embd =
    S = 1024 every projection is (W, B, S, 1024) too, so a score product is
    told by its 16-head axis."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, state = _compile_dsm_step(one_chip, "gpt2_medium", n_workers=2,
                                        n_layers=24)
    mem = compiled.memory_analysis()
    _aliases_the_state(mem, state)
    assert mem.peak_memory_in_bytes < 12.5e9
    _kernel_path_in_attention(compiled.as_text(),
                              score_dims=r"[\d,]*16,1024,1024")
    _no_relayout(compiled.as_text())


@pytest.mark.parametrize("shape", [
    (4, 4, 1024, 12, 64),   # gpt2_small.w4.tau12: W=4 vmapped, B 4
    (2, 4, 1024, 16, 64),   # gpt2_medium.w2.tau12: W=2, eight head groups
], ids=["gpt2_small", "gpt2_medium"])
def test_flash_attention_fits_half_the_scoped_vmem(one_chip, monkeypatch,
                                                   shape):
    """The kernel's forward and backward at a cell's shapes (W workers
    vmapped, B, S 1024, heads of 64) within 8 MiB of VMEM, half the default
    scoped limit: inside a whole step the compiler leaves a kernel less
    than it does alone."""
    from repro.kernels import flash_attention as FA

    monkeypatch.setattr(FA.pltpu, "CompilerParams", functools.partial(
        FA.pltpu.CompilerParams, vmem_limit_bytes=8 * 2 ** 20))
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(jax.vmap(FA.flash_attention), q, k, v)
        return out, vjp(out)

    compiled = jax.jit(fwd_bwd).lower(x, x, x).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2
