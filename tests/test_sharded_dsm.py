"""ZeRO-sharded global step (DSMConfig.zero_sharded) tests.

The multi-device equivalence test runs in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``: per the conftest
note, the main pytest process must keep seeing a single CPU device, and XLA
device count is fixed at first jax use.  Everything else runs in-process on
the 1-device degenerate mesh (worker=1, zero=1), which exercises the same
code path cheaply.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DSMConfig, constant, dsm_init, make_dsm_step, sgd
from repro.distributed import zero as Z
from repro.launch.mesh import host_training_mesh

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


# ---------------------------------------------------------------------------
# slab plumbing
# ---------------------------------------------------------------------------

def test_slab_roundtrip():
    key = jax.random.PRNGKey(0)
    for shape in ((5,), (33, 40), (2, 64, 16), ()):
        x = jax.random.normal(key, shape)
        slab = Z._to_slab(x, 8)
        assert slab.shape[1] == 128 and slab.shape[0] % 8 == 0
        np.testing.assert_array_equal(np.asarray(Z._from_slab(slab, x)),
                                      np.asarray(x))


def test_global_buffer_pspecs_use_worker_and_zero():
    from repro.distributed.sharding import param_pspecs

    tree = {"w": jnp.zeros((16, 8)), "tiny": jnp.zeros((3,))}
    specs = param_pspecs(tree, model=1, zero=8, zero_axes=Z.GLOBAL_AXES)
    # divisible leaf: largest divisible dim carries the flattened axes
    assert tuple(specs["w"]) == (Z.GLOBAL_AXES, None)
    # indivisible leaf stays replicated
    assert all(s is None for s in tuple(specs["tiny"]))


# ---------------------------------------------------------------------------
# 1-device degenerate mesh: zero_sharded wiring == replicated, in-process
# ---------------------------------------------------------------------------

def _quad_setup(use_kernel, zero_sharded, steps=3):
    d = 48
    key = jax.random.PRNGKey(7)
    center = jax.random.normal(key, (d,))

    def loss(params, batch):
        tgt = center + batch["noise"]
        return 0.5 * jnp.mean(jnp.sum((params["x"][None] - tgt) ** 2, axis=-1))

    mesh = host_training_mesh(2) if zero_sharded else None
    cfg = DSMConfig(tau=2, global_lr=0.7, use_kernel=use_kernel,
                    zero_sharded=zero_sharded)
    step = jax.jit(make_dsm_step(loss, sgd(), cfg, constant(0.05), mesh=mesh))
    state = dsm_init({"x": jnp.zeros((d,))}, sgd(), n_workers=2, mesh=mesh)
    for t in range(steps):
        batch = {"noise": 0.1 * jax.random.normal(
            jax.random.fold_in(key, t), (2, 2, 1, 4, d))}
        state, _ = step(state, batch)
    return state


@pytest.mark.parametrize("use_kernel", [False, True])
def test_zero_sharded_single_device_matches(use_kernel):
    ref = _quad_setup(use_kernel, zero_sharded=False)
    sh = _quad_setup(use_kernel, zero_sharded=True)
    np.testing.assert_allclose(np.asarray(sh.x0["x"]), np.asarray(ref.x0["x"]),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sh.m["x"]), np.asarray(ref.m["x"]),
                               rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# 8-device equivalence: sharded vs replicated training, jnp AND kernel paths
# ---------------------------------------------------------------------------

_EQUIV_SCRIPT = r"""
import json
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import DSMConfig, constant, dsm_init, make_dsm_step, get_base_optimizer
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


def run(zero_sharded, use_kernel):
    mesh = host_training_mesh(W) if zero_sharded else None
    cfg = DSMConfig(tau=TAU, global_lr=1.0, zero_sharded=zero_sharded,
                    use_kernel=use_kernel)
    step = jax.jit(make_dsm_step(loss, base, cfg, constant(2e-2), mesh=mesh))
    params = T.init_params(jax.random.PRNGKey(3), NANO)
    state = dsm_init(params, base, W, mesh=mesh)
    batches = dsm_batches(MarkovCorpus(64, seed=1), W, TAU, 1, 2, 32, seed=3)
    for _ in range(STEPS):
        state, _ = step(state, jax.tree.map(jnp.asarray, next(batches)))
    return state


def maxdiff(a, b):
    return max(float(jnp.abs(x - y).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


rec = {"n_devices": jax.device_count()}
for name, use_kernel in (("jnp", False), ("kernel", True)):
    ref = run(False, use_kernel)
    sh = run(True, use_kernel)
    # every large leaf of the sharded x0 / m must really live in 1/8 shards
    shard_frac = []
    for leaf in jax.tree.leaves(sh.x0) + jax.tree.leaves(sh.m):
        ss = leaf.sharding.shard_shape(leaf.shape)
        shard_frac.append(
            int(jnp.prod(jnp.array(ss))) / max(leaf.size, 1))
    rec[name] = {
        "x0": maxdiff(ref.x0, sh.x0),
        "m": maxdiff(ref.m, sh.m),
        "min_shard_frac": min(shard_frac),
    }
print("RESULT " + json.dumps(rec))
"""


@pytest.mark.multidevice
def test_sharded_training_matches_replicated_8dev():
    """zero_sharded=True == replicated to 1e-5 after 5 outer steps, on a
    forced 8-device host (worker=4, zero=2), jnp and fused-kernel paths."""
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
        assert rec[path]["x0"] <= 1e-5, (path, rec)
        assert rec[path]["m"] <= 1e-5, (path, rec)
        # the big buffers are genuinely 8-way sharded (small leaves replicate)
        assert rec[path]["min_shard_frac"] <= 1 / 8 + 1e-9, (path, rec)


# ---------------------------------------------------------------------------
# comm model: the sharding's accounting
# ---------------------------------------------------------------------------

def test_comm_model_reports_sharded_reduction():
    from repro.analysis.comm import bytes_per_outer_step

    rep = bytes_per_outer_step("gpt2_small", "dsm", tau=12)
    sh = bytes_per_outer_step("gpt2_small", "dsm", tau=12,
                              zero_sharded=True, shards=8)
    assert rep["global_state_shards"] == 1 and sh["global_state_shards"] == 8
    for key in ("global_buffer_bytes_per_rank", "global_state_bytes_per_rank",
                "broadcast_src_bytes_per_rank"):
        ratio = sh[key] / rep[key]
        assert abs(ratio - 1 / 8) < 1e-6, (key, ratio)
    # wire volume is tau-amortized either way; sharding must not change it
    assert sh["wire_bytes_per_outer"] == rep["wire_bytes_per_outer"]
