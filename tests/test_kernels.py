"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret=True)."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [(7,), (128,), (129,), (1000,), (33, 77), (4, 128, 130), (2, 3, 5, 64)]
DTYPES = [jnp.float32, jnp.bfloat16]


def case_key(shape, dtype, kernel="dsm"):
    """A PRNG key fixed by the case itself, so every process draws the same
    arrays (``hash()`` of a ``str`` is salted per process)."""
    name = f"{kernel}-{shape}-{jnp.dtype(dtype).name}"
    return jax.random.PRNGKey(zlib.crc32(name.encode()))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_dsm_update_kernel(shape, dtype):
    key = case_key(shape, dtype)
    ks = jax.random.split(key, 3)
    x0 = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    m = jax.random.normal(ks[1], shape, jnp.float32)
    xt = (x0.astype(jnp.float32) - 0.01 * jax.random.normal(ks[2], shape)).astype(dtype)
    gamma = jnp.float32(0.02)
    hp = dict(eta=0.8, beta1=0.95, beta2=0.98, lam=0.1)
    xr, mr = ref.dsm_update_ref(x0, m, xt, gamma, **hp)
    xk, mk = ops.dsm_update_tree({"a": x0}, {"a": m}, {"a": xt}, gamma, **hp)
    np.testing.assert_allclose(
        np.asarray(xk["a"], np.float32), np.asarray(xr, np.float32),
        rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_allclose(np.asarray(mk["a"]), np.asarray(mr), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_adamw_update_kernel(shape, dtype):
    key = case_key(shape, dtype, "adamw")
    ks = jax.random.split(key, 4)
    p = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    g = jax.random.normal(ks[1], shape, jnp.float32).astype(dtype)
    m = jax.random.normal(ks[2], shape, jnp.float32)
    v = jnp.abs(jax.random.normal(ks[3], shape, jnp.float32))
    gamma, step = jnp.float32(1e-3), jnp.float32(11)
    pr, mr, vr = ref.adamw_update_ref(
        p, g, m, v, gamma, step, beta1=0.9, beta2=0.95, eps=1e-8, wd=0.1)
    pk, mk, vk = ops.adamw_update_tree(
        {"a": p}, {"a": g}, {"a": m}, {"a": v}, gamma, step)
    np.testing.assert_allclose(
        np.asarray(pk["a"], np.float32), np.asarray(pr, np.float32),
        rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_allclose(np.asarray(mk["a"]), np.asarray(mr), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(vk["a"]), np.asarray(vr), rtol=1e-5, atol=1e-6)


def test_kernel_inside_jit_grad_free_path():
    """The kernel path composes under jit with a full pytree."""
    key = jax.random.PRNGKey(0)
    tree = {
        "layer": {"w": jax.random.normal(key, (64, 48)), "b": jnp.zeros((48,))},
        "emb": jax.random.normal(key, (100, 16)).astype(jnp.bfloat16),
    }
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)
    xt = jax.tree.map(lambda x: x - jnp.asarray(0.01, x.dtype), tree)

    @jax.jit
    def f(x0, m, xt):
        return ops.dsm_update_tree(
            x0, m, xt, jnp.float32(0.01), eta=1.0, beta1=0.9, beta2=0.99, lam=0.0)

    new_x, new_m = f(tree, m, xt)
    for leaf in jax.tree.leaves(new_x):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
