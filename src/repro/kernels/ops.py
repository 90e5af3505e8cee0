"""jit'd wrappers: pytree <-> lane-aligned 2D slabs for the Pallas kernels.

``interpret`` defaults to True off-TPU: the kernels target the TPU, and on
the CPU (tests) they run in Pallas interpret mode.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.adamw_update import adamw_update_2d
from repro.kernels.dsm_update import LANES, dsm_update_2d

PyTree = Any


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _to_2d(x: jnp.ndarray) -> tuple[jnp.ndarray, int]:
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = -(-n // LANES)
    pad = rows * LANES - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, LANES), n


def _from_2d(x2: jnp.ndarray, n: int, shape, dtype) -> jnp.ndarray:
    return x2.reshape(-1)[:n].reshape(shape).astype(dtype)


def dsm_update_tree(x0: PyTree, m: PyTree, x_tau: PyTree, gamma, *,
                    eta: float, beta1: float, beta2: float, lam: float,
                    interpret: bool = None) -> tuple[PyTree, PyTree]:
    """Apply the fused global sign-momentum kernel leafwise."""
    interpret = _default_interpret() if interpret is None else interpret
    gamma = jnp.asarray(gamma, jnp.float32)

    def leaf(x0_l, m_l, xt_l):
        x2, n = _to_2d(x0_l)
        m2, _ = _to_2d(m_l)
        t2, _ = _to_2d(xt_l.astype(x0_l.dtype))
        xn, mn = dsm_update_2d(
            x2, m2, t2, gamma, eta=eta, beta1=beta1, beta2=beta2, lam=lam,
            interpret=interpret,
        )
        return (
            _from_2d(xn, n, x0_l.shape, x0_l.dtype),
            _from_2d(mn, n, m_l.shape, m_l.dtype),
        )

    x_leaves, treedef = jax.tree.flatten(x0)
    m_leaves = jax.tree.leaves(m)
    t_leaves = jax.tree.leaves(x_tau)
    outs = [leaf(a, b, c) for a, b, c in zip(x_leaves, m_leaves, t_leaves)]
    new_x = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_m = jax.tree.unflatten(treedef, [o[1] for o in outs])
    return new_x, new_m


def adamw_update_tree(params: PyTree, grads: PyTree, m: PyTree, v: PyTree,
                      gamma, step, *, beta1: float = 0.9, beta2: float = 0.95,
                      eps: float = 1e-8, wd: float = 0.1,
                      interpret: bool = None):
    """Apply the fused AdamW kernel leafwise. Returns (params, m, v)."""
    interpret = _default_interpret() if interpret is None else interpret
    gamma = jnp.asarray(gamma, jnp.float32)
    step = jnp.asarray(step, jnp.float32)

    def leaf(p_l, g_l, m_l, v_l):
        p2, n = _to_2d(p_l)
        g2, _ = _to_2d(g_l)
        m2, _ = _to_2d(m_l)
        v2, _ = _to_2d(v_l)
        pn, mn, vn = adamw_update_2d(
            p2, g2, m2, v2, gamma, step,
            beta1=beta1, beta2=beta2, eps=eps, wd=wd, interpret=interpret,
        )
        return (
            _from_2d(pn, n, p_l.shape, p_l.dtype),
            _from_2d(mn, n, m_l.shape, jnp.float32),
            _from_2d(vn, n, v_l.shape, jnp.float32),
        )

    p_leaves, treedef = jax.tree.flatten(params)
    outs = [
        leaf(a, b, c, d)
        for a, b, c, d in zip(
            p_leaves, jax.tree.leaves(grads), jax.tree.leaves(m), jax.tree.leaves(v)
        )
    ]
    return (
        jax.tree.unflatten(treedef, [o[0] for o in outs]),
        jax.tree.unflatten(treedef, [o[1] for o in outs]),
        jax.tree.unflatten(treedef, [o[2] for o in outs]),
    )
