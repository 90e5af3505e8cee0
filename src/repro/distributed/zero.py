"""ZeRO-sharded DSM global step (DSMConfig.zero_sharded=True).

The replicated global step keeps full copies of x0 / m on every rank and
re-does the identical sign-momentum update everywhere — O(N) HBM residency
and O(N) update traffic per rank, regardless of how many chips participate.
This module shards the *global* optimizer state over the flattened
``("worker", "zero")`` mesh axes (R = W * Z ranks) and rewrites the outer
step as

    reduce-scatter(x_tau)  ->  shard-local sign-momentum update  ->  all-gather(x_{t+1,0})

so each rank holds and updates only 1/R of x0 and m (paper §2 pairs local
steps with ZeRO-2 sharding for exactly this reason; the same split is how
SignMuon / DeMo scale their global optimizer state).

Both implementations express the reduce-scatter as the worker mean *pinned
to the shard layout* (``with_sharding_constraint`` with
``param_pspecs(..., zero_axes=("worker", "zero"))``): the SPMD partitioner
reduces over the worker axis directly into shards on collective-capable
backends, and each rank only ever consumes its own slice.  We deliberately
do NOT hand-write a ring ``psum_scatter``: an explicit ring fixes a
summation order different from the replicated baseline's, and the resulting
few-ulp difference in x_tau is amplified by 1/gamma through sign() into
training-visible divergence — whereas the partitioner-chosen reduction is
numerically identical to the replicated mean (tier-1 asserts 1e-5 agreement
over multiple outer steps; see tests/test_sharded_dsm.py).

  * jnp path: the leafwise eqs. (6)-(8) run under the shard constraint —
    elementwise, so the update itself never leaves the shard.
  * kernel path: x0 / m / x_tau are flattened into lane-aligned
    ``(rows, 128)`` slabs (rows padded to a multiple of R) sharded
    ``P(("worker", "zero"))`` on rows, and a ``shard_map`` runs the fused
    Pallas ``dsm_update_2d`` kernel on each rank's local slab.

The collective structure described above is machine-checked: the HLO
auditor (``python -m repro.analysis audit``, docs/analysis.md) compiles
this step and asserts it stays within the ``global_zero`` phase budget —
one reduction round (all-reduce/reduce-scatter equivalence class: the CPU
partitioner lowers the scattered mean as all-reduce + slice) plus one
gather round, leafwise, and nothing else.  The kernel slab path is
excluded from the default audit matrix: its per-step re-slabbing emits
collective-permute traffic that the flat-slab-storage ROADMAP item will
remove, and pinning it in a budget today would only entrench the wart.

See docs/sharding.md for the full dataflow.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import workers as WK
from repro.distributed.sharding import param_pspecs
from repro.kernels.dsm_update import LANES, dsm_update_2d

PyTree = Any

GLOBAL_AXES = ("worker", "zero")  # flattened shard axes for x0 / m


def num_shards(mesh: Mesh) -> int:
    """R = worker * zero — the shard count for the global buffers."""
    dims = dict(zip(mesh.axis_names, mesh.devices.shape))
    return dims.get("worker", 1) * dims.get("zero", 1)


def global_buffer_pspecs(tree: PyTree, mesh: Mesh) -> PyTree:
    """Leafwise specs sharding the largest divisible dim over (worker, zero)."""
    return param_pspecs(tree, model=1, zero=num_shards(mesh),
                        zero_axes=GLOBAL_AXES)


def global_buffer_shardings(tree: PyTree, mesh: Mesh) -> PyTree:
    specs = global_buffer_pspecs(tree, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def constrain_global(tree: PyTree, mesh: Mesh) -> PyTree:
    """Pin a global-buffer pytree to its (worker, zero) shard layout."""
    return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                        global_buffer_shardings(tree, mesh))


def worker_shardings(tree: PyTree, mesh: Mesh) -> PyTree:
    """Per-worker leaves: shard each leaf's worker axis only
    (repro.core.workers)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        WK.worker_pspecs(tree),
                        is_leaf=lambda x: isinstance(x, P))


def constrain_workers(tree: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                        worker_shardings(tree, mesh))


def put_workers(tree: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree.map(jax.device_put, tree, worker_shardings(tree, mesh))


def shard_dsm_state(state, mesh: Mesh, global_sharded: bool = True):
    """device_put a fresh DSMState onto the mesh: per-worker params / base
    state sharded over worker; x0 / m in the ZeRO (worker, zero) layout when
    ``global_sharded``, replicated otherwise (device-parallel local phase
    with a replicated global step)."""
    rep = NamedSharding(mesh, P())
    if global_sharded:
        x0_sh = global_buffer_shardings(state.x0, mesh)
        m_sh = global_buffer_shardings(state.m, mesh)
    else:
        x0_sh = jax.tree.map(lambda _: rep, state.x0)
        m_sh = jax.tree.map(lambda _: rep, state.m)

    return type(state)(
        params=put_workers(state.params, mesh),
        x0=jax.tree.map(jax.device_put, state.x0, x0_sh),
        m=jax.tree.map(jax.device_put, state.m, m_sh),
        base_state=put_workers(state.base_state, mesh),
        t=jax.device_put(state.t, rep),
        inner=jax.device_put(state.inner, rep),
    )


# ---------------------------------------------------------------------------
# jnp / GSPMD path
# ---------------------------------------------------------------------------

def _scattered_worker_mean(params_w, mesh, weights=None):
    """x_tau = mean_i x^{(i)}_{t,tau}, reduced directly into the
    (worker, zero) shard layout — the reduce-scatter of the outer step.

    The per-worker iterates are pinned to their worker-sharded layout first, so
    when the local phase ran device-parallel the partitioner consumes the
    already-worker-sharded x_tau in place (worker-axis reduction straight
    into shards) instead of gathering the W copies to every rank and
    re-scattering.

    ``weights`` (optional ``(W,)`` f32): survivor-aware masked mean — zero-
    weight (dropped / non-finite) workers are zeroed before the reduction,
    still elementwise in W, so the reduce-scatter structure is unchanged."""
    params_w = constrain_workers(params_w, mesh)
    if weights is None:
        x_tau = WK.worker_mean(params_w)
    else:
        from repro.core.dsm import masked_worker_mean

        x_tau = masked_worker_mean(params_w, weights)
    return constrain_global(x_tau, mesh)


def _sharded_step_jnp(x0, m, x_tau, gamma, cfg, mesh, rng):
    from repro.core.dsm import global_sign_momentum_step

    # force the jnp path: the elementwise update stays shard-local under the
    # output constraint (the kernel dispatch is handled by the slab path)
    jnp_cfg = dataclasses.replace(cfg, use_kernel=False)
    new_x0, new_m = global_sign_momentum_step(x0, m, x_tau, gamma, jnp_cfg, rng)
    return constrain_global(new_x0, mesh), constrain_global(new_m, mesh)


# ---------------------------------------------------------------------------
# kernel / shard_map path: flat slabs, psum_scatter, fused Pallas update
# ---------------------------------------------------------------------------

def _to_slab(x: jnp.ndarray, row_multiple: int) -> jnp.ndarray:
    """Flatten to a lane-aligned (rows, LANES) slab, rows % row_multiple == 0."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = -(-n // LANES)
    rows = -(-rows // row_multiple) * row_multiple
    pad = rows * LANES - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, LANES)


def _from_slab(slab: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    n = like.size
    return slab.reshape(-1)[:n].reshape(like.shape).astype(like.dtype)


def dsm_update_shard(x0_l, m_l, xt_l, gamma, *, eta, beta1, beta2, lam,
                     interpret):
    """Sharded variant of the fused DSM kernel: one rank's flat slab.

    Inputs are this rank's ``(rows/R, LANES)`` slices of the slabbed
    x0 / m / x_tau; the fused Pallas kernel streams them through VMEM once,
    so the global step's HBM traffic per rank is 1/R of the replicated
    update's.
    """
    return dsm_update_2d(
        x0_l, m_l, xt_l.astype(x0_l.dtype), gamma,
        eta=eta, beta1=beta1, beta2=beta2, lam=lam, interpret=interpret,
    )


def _sharded_step_kernel(x0, m, x_tau, gamma, cfg, mesh,
                         interpret: Optional[bool] = None):
    from repro.kernels.ops import _default_interpret

    interpret = _default_interpret() if interpret is None else interpret
    R = num_shards(mesh)
    gamma32 = jnp.asarray(gamma, jnp.float32)

    x0_leaves, treedef = jax.tree.flatten(x0)
    m_leaves = jax.tree.leaves(m)
    xt_leaves = jax.tree.leaves(x_tau)

    x0_slabs = [_to_slab(l, R) for l in x0_leaves]
    m_slabs = [_to_slab(l, R) for l in m_leaves]
    xt_slabs = [
        _to_slab(l.astype(x0_l.dtype), R)
        for l, x0_l in zip(xt_leaves, x0_leaves)
    ]

    # slab rows sharded over the flattened (worker, zero) ranks: row chunk
    # w*Z + z lives on rank (w, z) for x0, m, and x_tau alike
    slab_spec = [P(GLOBAL_AXES)] * len(x0_slabs)

    def rank_fn(g, x0_ls, m_ls, xt_ls):
        outs = [
            dsm_update_shard(
                a, b, c, g, eta=cfg.global_lr, beta1=cfg.beta1,
                beta2=cfg.beta2, lam=cfg.weight_decay, interpret=interpret,
            )
            for a, b, c in zip(x0_ls, m_ls, xt_ls)
        ]
        return [o[0] for o in outs], [o[1] for o in outs]

    new_x_slabs, new_m_slabs = jax.shard_map(
        rank_fn, mesh=mesh,
        in_specs=(P(), slab_spec, slab_spec, slab_spec),
        out_specs=(slab_spec, slab_spec),
        check_vma=False,
    )(gamma32, x0_slabs, m_slabs, xt_slabs)

    new_x0 = jax.tree.unflatten(
        treedef, [_from_slab(s, l) for s, l in zip(new_x_slabs, x0_leaves)])
    new_m = jax.tree.unflatten(
        treedef, [_from_slab(s, l) for s, l in zip(new_m_slabs, m_leaves)])
    return constrain_global(new_x0, mesh), constrain_global(new_m, mesh)


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------

def sharded_global_sign_momentum_step(
    x0: PyTree,
    m: PyTree,
    params_w: PyTree,
    gamma: jnp.ndarray,
    cfg,
    mesh: Mesh,
    rng: Optional[jax.Array] = None,
    weights: Optional[jnp.ndarray] = None,
    return_x_tau: bool = False,
) -> tuple:
    """ZeRO-sharded eqs. (6)-(8): consumes per-worker iterates directly
    (the reduce-scatter subsumes the worker mean). Returns sharded
    (x_{t+1,0}, m_{t+1}); the caller's worker broadcast is the all-gather.

    ``weights``: optional ``(W,)`` survivor weights for the fault-tolerant
    masked mean (repro.core.dsm.masked_worker_mean); the caller applies
    skip-round semantics when all weights are zero.

    ``return_x_tau`` appends the scattered worker mean to the result so the
    caller can compute diagnostics (repro.obs) against the SAME reduction —
    the partitioner CSEs the shared subgraph, so asking for it compiles no
    second collective.

    The fused-kernel slab path supports the deterministic sign only; the
    randomized-sign modes (theory §3.1) use the jnp/GSPMD path, whose
    sampled bits are layout-independent, so sharded == replicated there too.
    """
    x_tau = _scattered_worker_mean(params_w, mesh, weights)
    if cfg.use_kernel and cfg.sign_mode == "sign":
        new_x0, new_m = _sharded_step_kernel(x0, m, x_tau, gamma, cfg, mesh)
    else:
        new_x0, new_m = _sharded_step_jnp(x0, m, x_tau, gamma, cfg, mesh, rng)
    if return_x_tau:
        return new_x0, new_m, x_tau
    return new_x0, new_m


# ---------------------------------------------------------------------------
# sharded metric-pack support (repro.obs)
# ---------------------------------------------------------------------------

def constrain_replicated(tree: PyTree, mesh: Mesh) -> PyTree:
    """Pin every leaf of a pytree to the fully-replicated layout."""
    rep = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, rep), tree)


def sharded_stat_sums(x0: PyTree, m: PyTree, x_tau: PyTree, gamma,
                      beta1: float, mesh: Mesh) -> jnp.ndarray:
    """``repro.obs.metrics`` stat sums over ZeRO-sharded global buffers,
    with ONE collective for the whole pack.

    Each rank sums its own shard slices of every leaf, stacks the partials
    into a single ``(N_STAT_SUMS,)`` vector, and ONE psum over the
    flattened (worker, zero) ranks combines them — a naive leafwise
    ``jnp.sum`` over sharded buffers would instead lower to one scalar
    all-reduce per (leaf, statistic) and blow the audited ``global_zero``
    budget.  Leaves ``param_pspecs`` left replicated (no divisible dim)
    appear on all R ranks, so their partials are pre-scaled by
    ``global_size / (local_size * R)`` — 1 for sharded leaves, 1/R for
    replicated ones — making the psum count every element exactly once.
    """
    from repro.obs import metrics as OM

    R = num_shards(mesh)
    specs = global_buffer_pspecs(x0, mesh)
    x0_leaves, _ = jax.tree.flatten(x0)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    m_leaves = jax.tree.leaves(m)
    xt_leaves = jax.tree.leaves(x_tau)
    global_sizes = [l.size for l in x0_leaves]

    def rank_fn(g, x0_ls, m_ls, xt_ls):
        tot = jnp.zeros((OM.N_STAT_SUMS,), jnp.float32)
        for gsize, x0l, ml, xtl in zip(global_sizes, x0_ls, m_ls, xt_ls):
            part = OM.stat_sums_block([x0l], [ml], [xtl], g, beta1)
            tot = tot + (gsize / (x0l.size * R)) * part
        return jax.lax.psum(tot, GLOBAL_AXES)

    leaf_specs = list(spec_leaves)
    return jax.shard_map(
        rank_fn, mesh=mesh,
        in_specs=(P(), leaf_specs, leaf_specs, leaf_specs),
        out_specs=P(),
        check_vma=False,
    )(jnp.asarray(gamma, jnp.float32), x0_leaves, m_leaves, xt_leaves)
