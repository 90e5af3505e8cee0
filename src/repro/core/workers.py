"""Where the worker axis sits in per-worker state.

Per-worker params and base-optimizer state carry a worker axis ``W``.  A
leaf under a ``repro.layout.STACK_KEY`` dict key is a stack of layers that
the model scans along its leading axis (``repro.models.transformer``); it
keeps that layer axis first and holds the worker second, ``(L, W, ...)``.
Every other leaf holds the worker first, ``(W, ...)``.  A tree with no
stacked leaf is therefore worker-first throughout.

Why: the local phase vmaps the per-worker step over ``W`` and, inside it,
the model scans its stacked leaves along ``L``.  A vmapped scan slices its
inputs and stacks its outputs with the mapped axis second, so state stored
as ``(L, W, ...)`` enters and leaves the tau loop as it is.  Stored
worker-first, every stacked leaf was copied to ``(L, W, ...)`` at the
loop's entry and back at its exit (docs/local_phase.md).

Every function that reads or writes per-worker state finds the worker axis
of a leaf here, from the leaf's key path.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.layout import is_stacked

PyTree = Any


def worker_axis(path) -> int:
    return 1 if is_stacked(path) else 0


def worker_axes(tree: PyTree) -> PyTree:
    """The worker axis of every leaf: ``in_axes`` / ``out_axes`` for vmap."""
    return jax.tree_util.tree_map_with_path(lambda p, _: worker_axis(p), tree)


def worker_pspecs(tree: PyTree) -> PyTree:
    """PartitionSpecs sharding each leaf's worker axis over the mesh's
    ``"worker"`` axis (scalars replicated)."""
    def spec(path, x):
        if jnp.ndim(x) == 0:
            return P()
        return P(*([None] * worker_axis(path)), "worker")

    return jax.tree_util.tree_map_with_path(spec, tree)


def n_workers_of(tree: PyTree) -> int:
    path, leaf = jax.tree_util.tree_flatten_with_path(tree)[0][0]
    return leaf.shape[worker_axis(path)]


def broadcast_workers(tree: PyTree, n_workers: int) -> PyTree:
    """Give every leaf of a single-worker tree a worker axis of size
    ``n_workers``, each worker holding the same value."""
    def leaf(path, x):
        ax = worker_axis(path)
        return jnp.broadcast_to(jnp.expand_dims(x, ax),
                                x.shape[:ax] + (n_workers,) + x.shape[ax:])

    return jax.tree_util.tree_map_with_path(leaf, tree)


def worker_mean(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map_with_path(
        lambda p, x: x.mean(axis=worker_axis(p)), tree)


def along_workers(v: jnp.ndarray, path, x: jnp.ndarray) -> jnp.ndarray:
    """Reshape a ``(W,)`` vector to broadcast along leaf ``x``'s worker
    axis."""
    shape = [1] * x.ndim
    shape[worker_axis(path)] = v.shape[0]
    return v.reshape(shape)
