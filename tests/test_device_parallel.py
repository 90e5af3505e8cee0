"""Device-parallel local phase (DSMConfig.device_parallel_local) tests.

The multi-device equivalence tests run in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main pytest
process must keep seeing one CPU device; XLA fixes the device count at
first jax use) and are marked ``multidevice`` — CI runs them in their own
job.  Everything else runs in-process on the 1-device degenerate mesh
(worker=1), which exercises the identical shard_map code path cheaply.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DSMConfig,
    constant,
    dsm_init,
    make_dsm_step,
    make_local_phase,
    sgd,
)
from repro.launch.mesh import host_training_mesh

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


# ---------------------------------------------------------------------------
# factory contracts
# ---------------------------------------------------------------------------

def test_make_local_phase_requires_worker_mesh():
    with pytest.raises(ValueError, match="worker"):
        make_local_phase(lambda p, b: 0.0, sgd(), device_parallel=True, mesh=None)


def test_local_phase_returns_per_worker_losses():
    """losses come back unreduced (tau, W): the worker mean must happen
    OUTSIDE the (collective-free) local phase."""

    def loss(params, mb):
        return jnp.mean((params["x"] - mb) ** 2)

    lp = make_local_phase(loss, sgd(), accum=False)
    params_w = {"x": jnp.zeros((3, 4))}
    batch = jnp.ones((3, 2, 5, 4))  # (W=3, tau=2, B=5, d)
    _, _, losses = lp(params_w, (), batch, jnp.float32(0.1), jnp.int32(0))
    assert losses.shape == (2, 3)


def test_host_training_mesh_rejects_indivisible_worker_count(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: [object() for _ in range(8)])
    with pytest.raises(ValueError, match="does not divide"):
        host_training_mesh(3)


# ---------------------------------------------------------------------------
# 1-device degenerate mesh: device_parallel_local == vmapped, in-process
# ---------------------------------------------------------------------------

def _quad_setup(device_parallel, zero_sharded, use_kernel, steps=3):
    d = 48
    key = jax.random.PRNGKey(7)
    center = jax.random.normal(key, (d,))

    def loss(params, batch):
        tgt = center + batch["noise"]
        return 0.5 * jnp.mean(jnp.sum((params["x"][None] - tgt) ** 2, axis=-1))

    mesh = host_training_mesh(2) if (device_parallel or zero_sharded) else None
    cfg = DSMConfig(tau=2, global_lr=0.7, use_kernel=use_kernel,
                    zero_sharded=zero_sharded,
                    device_parallel_local=device_parallel)
    step = jax.jit(make_dsm_step(loss, sgd(), cfg, constant(0.05), mesh=mesh))
    state = dsm_init({"x": jnp.zeros((d,))}, sgd(), n_workers=2, mesh=mesh,
                     global_sharded=zero_sharded)
    losses = []
    for t in range(steps):
        batch = {"noise": 0.1 * jax.random.normal(
            jax.random.fold_in(key, t), (2, 2, 1, 4, d))}
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return state, losses


@pytest.mark.parametrize("zero_sharded", [False, True])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_device_parallel_single_device_matches(zero_sharded, use_kernel):
    ref, ref_losses = _quad_setup(False, False, use_kernel)
    dp, dp_losses = _quad_setup(True, zero_sharded, use_kernel)
    np.testing.assert_allclose(np.asarray(dp.x0["x"]), np.asarray(ref.x0["x"]),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dp.m["x"]), np.asarray(ref.m["x"]),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(dp_losses, ref_losses, rtol=0, atol=1e-6)


def test_trainer_device_parallel_wiring():
    """run_training hoists ONE mesh for any mesh-consuming flag and threads
    device_parallel_local through DSM and the shared-local-phase baselines."""
    from repro.configs.base import ModelConfig
    from repro.data.pipeline import MarkovCorpus
    from repro.train.trainer import TrainSettings, run_training

    nano = ModelConfig(
        name="nano", family="lm", n_layers=1, d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=64, vocab_size=64, head_dim=16, mlp_gated=False,
        act="gelu", dtype="float32", param_dtype="float32", vocab_pad_to=64,
    )
    corpus = MarkovCorpus(nano.vocab_size, branch=4, seed=7)
    for algo in ("dsm", "slowmo"):
        s = TrainSettings(algorithm=algo, n_workers=2, tau=2, steps=2,
                          b_micro=2, seq=32, eval_every=2,
                          device_parallel_local=True)
        r = run_training(nano, s, corpus)
        assert np.isfinite(r["final_eval"]), algo


# ---------------------------------------------------------------------------
# comm model: the layout's accounting
# ---------------------------------------------------------------------------

def test_comm_model_reports_local_compute_deduplication():
    from repro.analysis.comm import bytes_per_outer_step

    rep = bytes_per_outer_step("gpt2_small", "dsm", tau=12, n_workers=8)
    dp = bytes_per_outer_step("gpt2_small", "dsm", tau=12, n_workers=8,
                              device_parallel=True)
    assert rep["local_step_flops_replication"] == 8
    assert dp["local_step_flops_replication"] == 1
    # the local phase was always collective-free: wire volume must not move
    assert dp["wire_bytes_per_outer"] == rep["wire_bytes_per_outer"]
    assert dp["comm_rounds_per_outer"] == rep["comm_rounds_per_outer"]
    # non-local-step algorithms don't carry the field
    ps = bytes_per_outer_step("gpt2_small", "perstep", tau=12)
    assert "local_step_flops_replication" not in ps


# ---------------------------------------------------------------------------
# 8-device equivalence: device-parallel == vmapped trajectories, and the
# compiled local phase contains no inter-worker collectives
# ---------------------------------------------------------------------------

_EQUIV_SCRIPT = r"""
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import (DSMConfig, constant, dsm_init, make_dsm_step,
                        make_local_phase, get_base_optimizer)
from repro.core import baselines as BL
from repro.data.pipeline import MarkovCorpus, dsm_batches
from repro.launch.mesh import host_training_mesh
from repro.models import transformer as T

NANO = ModelConfig(
    name="nano", family="lm", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=128, vocab_size=64, head_dim=16, mlp_gated=False, act="gelu",
    dtype="float32", param_dtype="float32", vocab_pad_to=64,
)
W, TAU, STEPS = 4, 2, 5
loss = lambda p, mb: T.loss_fn(p, mb, NANO, remat=False)
base = get_base_optimizer("adamw")


def run(device_parallel, zero_sharded, use_kernel):
    mesh = host_training_mesh(W) if (device_parallel or zero_sharded) else None
    cfg = DSMConfig(tau=TAU, global_lr=1.0, zero_sharded=zero_sharded,
                    use_kernel=use_kernel, device_parallel_local=device_parallel)
    step = jax.jit(make_dsm_step(loss, base, cfg, constant(2e-2), mesh=mesh))
    params = T.init_params(jax.random.PRNGKey(3), NANO)
    state = dsm_init(params, base, W, mesh=mesh, global_sharded=zero_sharded)
    # heterogeneous=True: each worker consumes its own stream (paper's D_i)
    batches = dsm_batches(MarkovCorpus(64, seed=1), W, TAU, 1, 2, 32, seed=3,
                          heterogeneous=True)
    hist = []
    for _ in range(STEPS):
        state, m = step(state, jax.tree.map(jnp.asarray, next(batches)))
        hist.append(float(m["loss"]))
    return state, hist


def maxdiff(a, b):
    return max(float(jnp.abs(x - y).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


rec = {"n_devices": jax.device_count()}

for name, use_kernel in (("jnp", False), ("kernel", True)):
    ref, href = run(False, False, use_kernel)
    out = {}
    for tag, zero_sharded in (("plain", False), ("zero", True)):
        dp, hdp = run(True, zero_sharded, use_kernel)
        leaf = jax.tree.leaves(dp.params)[0]
        shard_elems = int(np.prod(leaf.sharding.shard_shape(leaf.shape)))
        out[tag] = {
            "x0": maxdiff(ref.x0, dp.x0),
            "m": maxdiff(ref.m, dp.m),
            "loss": max(abs(a - b) for a, b in zip(href, hdp)),
            "param_shard_frac": shard_elems / leaf.size,
        }
    rec[name] = out

# the shared local phase serves the baselines too: slowmo dp == vmapped
def run_slowmo(device_parallel):
    mesh = host_training_mesh(W) if device_parallel else None
    init, step = BL.slowmo(loss, base, TAU, constant(2e-2), beta=0.5,
                           device_parallel=device_parallel, mesh=mesh)
    step = jax.jit(step)
    state = init(T.init_params(jax.random.PRNGKey(3), NANO), W)
    batches = dsm_batches(MarkovCorpus(64, seed=1), W, TAU, 1, 2, 32, seed=3)
    hist = []
    for _ in range(STEPS):
        batch = jax.tree.map(lambda x: jnp.asarray(x)[:, :, 0], next(batches))
        state, m = step(state, batch)
        hist.append(float(m["loss"]))
    return state, hist

sref, shref = run_slowmo(False)
sdp, shdp = run_slowmo(True)
rec["slowmo"] = {
    "x0": maxdiff(sref.x0, sdp.x0),
    "loss": max(abs(a - b) for a, b in zip(shref, shdp)),
}

# compiled device-parallel local phase: ZERO inter-worker collectives,
# checked by the HLO auditor against the "local" phase budget
from repro.analysis.hlo_audit import CollectiveBudget, audit_jitted

mesh = host_training_mesh(W)
lp = make_local_phase(loss, base, accum=True, device_parallel=True, mesh=mesh)
params = T.init_params(jax.random.PRNGKey(3), NANO)
state = dsm_init(params, base, W, mesh=mesh, global_sharded=False)
batch = jax.tree.map(jnp.asarray, next(
    dsm_batches(MarkovCorpus(64, seed=1), W, TAU, 1, 2, 32, seed=3)))
rec["local_phase_audit"] = audit_jitted(
    lp, (state.params, state.base_state, batch, jnp.float32(2e-2),
         jnp.int32(0)),
    CollectiveBudget.for_phase("local", state.x0),
    name="local_phase").to_json()

# ... while one full outer step DOES communicate — within the dense global
# budget (sanity: the local check is not vacuously passing on
# collective-free whole-step HLO)
cfg = DSMConfig(tau=TAU, device_parallel_local=True)
rec["outer_step_audit"] = audit_jitted(
    make_dsm_step(loss, base, cfg, constant(2e-2), mesh=mesh),
    (state, batch),
    CollectiveBudget.for_phase("global_dense", state.x0),
    name="outer_step").to_json()

print("RESULT " + json.dumps(rec))
"""


@pytest.mark.multidevice
def test_device_parallel_matches_vmapped_8dev():
    """device_parallel_local == vmapped x0/m/loss trajectories to 1e-5 over
    5 outer steps on a forced 8-device host (worker=4, zero=2), for the jnp
    and fused-kernel global paths, with and without the ZeRO-sharded global
    step, heterogeneous per-worker batches; and the compiled local phase
    contains no inter-worker collective ops."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _EQUIV_SCRIPT],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    rec = json.loads(line[len("RESULT "):])
    assert rec["n_devices"] == 8
    for path in ("jnp", "kernel"):
        for tag in ("plain", "zero"):
            r = rec[path][tag]
            assert r["x0"] <= 1e-5, (path, tag, rec)
            assert r["m"] <= 1e-5, (path, tag, rec)
            assert r["loss"] <= 1e-5, (path, tag, rec)
            # per-worker params genuinely live in 1/W shards
            assert abs(r["param_shard_frac"] - 0.25) < 1e-9, (path, tag, rec)
    assert rec["slowmo"]["x0"] <= 1e-5, rec
    assert rec["slowmo"]["loss"] <= 1e-5, rec
    lp_audit, os_audit = rec["local_phase_audit"], rec["outer_step_audit"]
    assert lp_audit["passed"], lp_audit
    assert lp_audit["counts"] == {}, lp_audit  # truly collective-free
    assert os_audit["passed"], os_audit
    assert os_audit["counts"] != {}, os_audit  # the ONE reduction round


# ---------------------------------------------------------------------------
# heterogeneous batches under the sharded layout: every worker's shard is
# its own stream, not a replica
# ---------------------------------------------------------------------------

_HET_SCRIPT = r"""
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.data.pipeline import MarkovCorpus, dsm_batches
from repro.launch.mesh import host_training_mesh

W, STEPS = 4, 3
mesh = host_training_mesh(W)
sh = NamedSharding(mesh, P("worker"))


def worker_blocks(tokens):
    arr = jax.device_put(jnp.asarray(tokens), sh)
    blocks = {}
    for s in arr.addressable_shards:
        w = s.index[0].start or 0
        blocks.setdefault(w, np.asarray(s.data))
    return [blocks[k] for k in sorted(blocks)]


rec = {"n_devices": jax.device_count()}
for het in (True, False):
    corpus = MarkovCorpus(64, seed=1)
    batches = dsm_batches(corpus, W, 2, 1, 2, 32, seed=5, heterogeneous=het)
    cross_worker_equal, cross_step_equal = 0, 0
    prev = None
    for _ in range(STEPS):
        blocks = worker_blocks(next(batches)["tokens"])
        assert len(blocks) == W
        cross_worker_equal += sum(
            np.array_equal(blocks[i], blocks[j])
            for i in range(W) for j in range(i + 1, W))
        if prev is not None:
            cross_step_equal += sum(
                np.array_equal(a, b) for a, b in zip(prev, blocks))
        prev = blocks
    rec["het" if het else "iid"] = {
        "cross_worker_equal": cross_worker_equal,
        "cross_step_equal": cross_step_equal,
    }
print("RESULT " + json.dumps(rec))
"""


@pytest.mark.multidevice
def test_heterogeneous_batches_shard_distinct_streams_8dev():
    """Under the P("worker") layout each worker's device shard carries its
    OWN stream (the paper's D_i) and advances across outer steps; with
    heterogeneous=False all workers see one replicated stream."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _HET_SCRIPT],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    rec = json.loads(line[len("RESULT "):])
    assert rec["n_devices"] == 8
    # heterogeneous: no two workers ever agree, and no worker repeats a step
    assert rec["het"]["cross_worker_equal"] == 0, rec
    assert rec["het"]["cross_step_equal"] == 0, rec
    # iid split: every worker's shard is the same replicated stream
    assert rec["iid"]["cross_worker_equal"] == 3 * 6, rec
