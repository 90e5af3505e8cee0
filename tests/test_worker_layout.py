"""Where the worker axis sits in per-worker state (repro.core.workers).

Leaves under the model's layer stack hold the worker second, ``(L, W,
...)``; every other leaf holds it first.  The math is that of a
worker-first state: these tests compare one outer step against a
worker-first reference written here, check the layout leaf by leaf, and
check the code that reads the worker axis (faults, the survivor mean,
checkpoints, the relayout counter).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as CK
from repro.configs import NANO
from repro.core import DSMConfig, adamw, dsm_init, global_sign_momentum_step
from repro.core import workers as WK
from repro.core.dsm import masked_worker_mean, worker_finite_mask
from repro.data.pipeline import MarkovCorpus, dsm_batches
from repro.models import transformer as T
from repro.robustness.faults import FaultRound, apply_faults

GAMMA = 2e-2


def _under_stack(path) -> bool:
    return any(getattr(k, "key", None) == "blocks" for k in path)


def _worker_first(tree):
    """The test's own view of the layout: stacked leaves (L, W, ...) ->
    (W, L, ...)."""
    return jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.swapaxes(x, 0, 1) if _under_stack(p) else x, tree)


def _loss(p, mb):
    return T.loss_fn(p, mb, NANO, remat=False)


def _batch(n_workers, tau):
    return jax.tree.map(jnp.asarray, next(dsm_batches(
        MarkovCorpus(NANO.vocab_size, seed=1), n_workers, tau, 1, 2, 32,
        seed=3, heterogeneous=True)))


def _reference_step(params, batch, tau, global_update):
    """One outer step with every per-worker leaf worker-first (vmapped on
    axis 0, as the local phase was written before the worker axis moved):
    (new x0, new aux, (tau, W) losses, base state)."""
    base = adamw()
    n_workers = batch["tokens"].shape[0]
    grad_fn = jax.value_and_grad(_loss)

    def one_local_step(carry, microbatch):
        pw, bs, k = carry

        def per_worker(p, s, mb):
            def acc_step(acc, mbi):
                g_sum, loss_sum = acc
                loss, g = grad_fn(p, mbi)
                return (jax.tree.map(jnp.add, g_sum, g), loss_sum + loss), None

            (g, loss), _ = jax.lax.scan(
                acc_step, (jax.tree.map(jnp.zeros_like, p),
                           jnp.zeros((), jnp.float32)), mb)
            d, s = base.direction(g, s, p, k)
            return jax.tree.map(lambda x, dd: x - GAMMA * dd, p, d), s, loss

        pw, bs, losses = jax.vmap(per_worker)(pw, bs, microbatch)
        return (pw, bs, k + 1), losses

    @jax.jit
    def run(params, batch):
        pw = jax.tree.map(lambda x: jnp.stack([x] * n_workers), params)
        (pw, bs, _), losses = jax.lax.scan(
            one_local_step, (pw, jax.vmap(base.init)(pw), jnp.int32(0)),
            jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), batch))
        x_tau = jax.tree.map(lambda x: x.mean(axis=0), pw)
        return (*global_update(params, x_tau), losses, bs)

    return run(params, batch)


def _dsm_update(x0, x_tau):
    m = jax.tree.map(jnp.zeros_like, x0)
    return global_sign_momentum_step(x0, m, x_tau, jnp.float32(GAMMA),
                                     DSMConfig(tau=1))


def _assert_close(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("algorithm,n_workers,tau,layout", [
    ("dsm", 2, 2, "vmapped"),
    ("dsm", 4, 3, "vmapped"),
    ("dsm", 2, 2, "device_parallel_zero"),
    ("local_avg", 4, 2, "vmapped"),
])
def test_outer_step_matches_a_worker_first_reference(algorithm, n_workers, tau,
                                                     layout):
    """One outer step of a 2-layer transformer through build_algorithm:
    x0, the global buffer, the losses and the base state moved back to
    worker-first match the worker-first reference."""
    from repro.launch.mesh import host_training_mesh
    from repro.train.trainer import TrainSettings, build_algorithm

    sharded = layout == "device_parallel_zero"
    s = TrainSettings(algorithm=algorithm, base_opt="adamw",
                      n_workers=n_workers, tau=tau, peak_lr=GAMMA,
                      schedule="constant", global_lr=1.0,
                      dsm_beta1=0.95, dsm_beta2=0.98, dsm_wd=0.1,
                      zero_sharded=sharded, device_parallel_local=sharded)
    mesh = host_training_mesh(n_workers) if sharded else None
    init, step, _, _ = build_algorithm(_loss, s, mesh=mesh)
    params = T.init_params(jax.random.PRNGKey(3), NANO)
    batch = _batch(n_workers, tau)
    state = init(params, n_workers)
    if algorithm == "dsm":
        state, metrics = jax.jit(step)(state, batch, None)
        aux, update = state.m, _dsm_update
    else:  # the local-step baselines take no accumulation axis
        state, metrics = jax.jit(step)(
            state, {k: v[:, :, 0] for k, v in batch.items()}, None)
        aux, update = state.aux, (lambda x0, x_tau: (x_tau, ()))

    x0, ref_aux, ref_losses, ref_bs = _reference_step(params, batch, tau,
                                                      update)
    _assert_close(state.x0, x0)
    _assert_close(aux, ref_aux)
    np.testing.assert_allclose(float(metrics["loss"]),
                               float(ref_losses.mean()), rtol=1e-6)
    _assert_close(_worker_first(state.base_state), ref_bs)
    _assert_close(_worker_first(state.params),
                  jax.tree.map(lambda x: jnp.stack([x] * n_workers), x0))


@pytest.mark.parametrize("n_workers", [2, 4])
def test_stacked_leaves_hold_the_worker_second(n_workers):
    params = T.init_params(jax.random.PRNGKey(0), NANO)
    state = dsm_init(params, adamw(), n_workers)
    n_stacked = 0
    for tree in (state.params, state.base_state.m, state.base_state.v):
        for (path, leaf), x in zip(
                jax.tree_util.tree_flatten_with_path(tree)[0],
                jax.tree.leaves(params)):
            if _under_stack(path):
                n_stacked += 1
                assert leaf.shape == x.shape[:1] + (n_workers,) + x.shape[1:]
            else:
                assert leaf.shape == (n_workers,) + x.shape
    assert n_stacked > 0
    assert WK.n_workers_of(state.params) == n_workers


def test_a_tree_without_a_stack_is_worker_first():
    tree = {"x": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((4,))}
    w = WK.broadcast_workers(tree, 3)
    assert w["x"].shape == (3, 2, 3) and w["b"].shape == (3, 4)
    assert WK.worker_axes(w) == {"x": 0, "b": 0}
    np.testing.assert_array_equal(WK.worker_mean(w)["x"], tree["x"])


def _stacked_tree():
    """W=3 workers: a stacked leaf (L=2, W, 4) and a plain one (W, 5)."""
    key = jax.random.PRNGKey(1)
    return {"blocks": {"w": jax.random.normal(key, (2, 3, 4))},
            "e": jax.random.normal(jax.random.fold_in(key, 1), (3, 5))}


def test_faults_and_survivor_mean_read_the_worker_axis():
    tree = _stacked_tree()
    x0 = {"blocks": {"w": jnp.full((2, 4), 7.0)}, "e": jnp.full((5,), 7.0)}
    fr = FaultRound(survivors=jnp.array([True, True, False]),
                    stale=jnp.array([False, True, False]),
                    corrupt=jnp.array([True, False, False]))
    out = _worker_first(apply_faults(tree, x0, fr))
    first = _worker_first(tree)
    for got, want in ((out["e"], first["e"]),
                      (out["blocks"]["w"], first["blocks"]["w"])):
        assert np.isnan(np.asarray(got[0])).all()         # corrupt
        np.testing.assert_array_equal(got[1], 7.0)        # stale: x0
        np.testing.assert_array_equal(got[2], want[2])    # delivered

    np.testing.assert_array_equal(worker_finite_mask(apply_faults(tree, x0, fr)),
                                  [False, True, True])
    weights = jnp.array([0.0, 1.0, 1.0])
    mean = masked_worker_mean(apply_faults(tree, x0, fr), weights)
    np.testing.assert_allclose(
        mean["blocks"]["w"], (7.0 + first["blocks"]["w"][2]) / 2, rtol=1e-6)
    np.testing.assert_allclose(mean["e"], (7.0 + first["e"][2]) / 2,
                               rtol=1e-6)


def test_worker_pspecs_shard_the_worker_axis():
    from jax.sharding import PartitionSpec as P

    specs = WK.worker_pspecs({**_stacked_tree(), "t": jnp.zeros(())})
    assert specs["blocks"]["w"] == P(None, "worker")
    assert specs["e"] == P("worker")
    assert specs["t"] == P()


def _drop_layout_record(path):
    """Make a checkpoint read as one written before the worker axis moved."""
    with open(path + ".json") as f:
        meta = json.load(f)
    del meta["worker_layout"]
    with open(path + ".json", "w") as f:
        json.dump(meta, f)


@pytest.mark.parametrize("n_workers", [3, 4])
def test_restoring_a_worker_first_checkpoint_names_the_leaf(tmp_path,
                                                           n_workers):
    """A checkpoint written before the worker axis moved holds (W, L, ...)
    stacked leaves and no layout record: restore refuses it and names the
    leaf (NANO has two layers, so W != L).  A checkpoint of the current
    layout restores bit-exactly."""
    params = T.init_params(jax.random.PRNGKey(0), NANO)
    state = dsm_init(params, adamw(), n_workers)
    old = str(tmp_path / "old")
    CK.save(old, state._replace(params=_worker_first(state.params),
                                base_state=_worker_first(state.base_state)))
    _drop_layout_record(old)
    with pytest.raises(ValueError,
                       match=r"params/decoder/blocks/p0/\S+: .*worker axis"):
        CK.restore(old, state)

    new = str(tmp_path / "new")
    CK.save(new, state)
    restored, _ = CK.restore(new, state)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_an_old_checkpoint_without_a_worker_axis_restores(tmp_path):
    """A stacked leaf with no worker axis (x0, params alone) restores from
    a checkpoint with no layout record, also where its first two axes have
    one length."""
    tree = {"blocks": {"w": jnp.arange(18.0).reshape(3, 3, 2)},
            "e": jnp.ones((3, 2))}
    path = str(tmp_path / "x0")
    CK.save(path, tree)
    _drop_layout_record(path)
    restored, _ = CK.restore(path, tree)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_HLO = """HloModule m

%fused (p: f32[2,3]) -> f32[2,3] {
  %p = f32[2,3]{1,0} parameter(0)
  ROOT %fusion.4.remat2 = f32[2,3]{1,0} negate(%p)
}

ENTRY %main (a: f32[2,3], b: bf16[4,8], t: s32[]) -> f32[2,3] {
  %a = f32[2,3]{1,0:T(8,128)} parameter(0)
  %b = bf16[4,8]{1,0} parameter(1)
  %t = s32[]{:T(128)} parameter(2)
  %copy.1 = f32[2,3]{0,1:T(8,128)} copy(%a)
  %copy-start.2 = (bf16[4,8]{1,0:S(1)}, bf16[4,8]{1,0}, u32[]) copy-start(%b)
  %copy-done.2 = bf16[4,8]{1,0:S(1)} copy-done(%copy-start.2)
  %copy.3 = bf16[4,8]{0,1} copy(%copy-done.2)
  %copy.4 = s32[]{:T(128)S(6)} copy(%t)
  %fusion.5.remat = f32[2,3]{1,0} negate(%copy.1)
  %custom-call.7 = f32[2,3]{1,0} custom-call(%fusion.5.remat), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/checkpoint/rematted_computation/attention/flash_attention/pallas_call"}
  %custom-call.8 = f32[2,3]{1,0} custom-call(%custom-call.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/transpose(jvp(attention))/flash_attention/pallas_call"}
  ROOT %copy.6 = f32[2,3]{1,0} copy(%custom-call.8)
}
"""


def test_relayout_report_counts_clones_and_state_relayouts():
    """Two remat clones; one kernel call under JAX's remat, of two; two
    copies that re-lay out an entry parameter, one through an async copy
    (24 + 64 bytes); a copy into scalar memory and a copy of a computed
    value are not state relayouts."""
    from repro.analysis.hlo_audit import relayout_report

    assert relayout_report(_HLO) == {"remat_clones": 2, "kernel_recomputes": 1,
                                     "state_copies": 2,
                                     "state_copy_bytes": 88}
