"""Baselines the paper compares against, on a shared local-step framework.

Implemented (all referenced in the paper):
  * SlowMo (Alg. 5, Wang et al. 2019)          -> ``slowmo``
  * signed SlowMo (§4.1 ablation)              -> ``signed_slowmo``
  * Lookahead (Zhang et al. 2019; §4.1)        -> ``lookahead``
  * Global AdamW with local steps (Alg. 7)     -> ``global_adamw``
  * Local averaging (local AdamW; App. C.2)    -> ``local_avg``
  * standalone per-step data parallel (AdamW/Sophia per-iteration
    all-reduce; the paper's upper baseline)    -> ``make_perstep_dp_step``
  * Federated MV-sto-signSGD-SIM (Alg. 6, Sun et al. 2023) ->
    ``make_mv_signsgd_step``

All local-step methods share ``make_local_step_method``: a tau-step local
phase identical to DSM's (no inter-worker collectives), followed by a
pluggable global update on ``(x0, aux, x_tau_mean, gamma)``.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import workers as WK
from repro.core.base_opt import BaseOptimizer, adamw
from repro.core.dsm import make_local_phase, randomized_sign_pm

PyTree = Any


class LocalMethodState(NamedTuple):
    params: PyTree      # per-worker (repro.core.workers layout)
    x0: PyTree          # global model buffer
    aux: PyTree         # method-specific global state (momentum etc.)
    base_state: PyTree  # per-worker base-opt state
    t: jnp.ndarray
    inner: jnp.ndarray


def make_local_step_method(
    loss_fn: Callable,
    base_opt: BaseOptimizer,
    tau: int,
    schedule: Callable,
    init_aux: Callable[[PyTree], PyTree],
    global_update: Callable[[PyTree, PyTree, PyTree, jnp.ndarray, jnp.ndarray], tuple],
    device_parallel: bool = False,
    mesh=None,
):
    """Generic: tau local steps -> all-reduce -> ``global_update`` -> sync.

    ``global_update(x0, aux, x_tau_mean, gamma, t) -> (new_x0, new_aux)``.

    The local phase is DSM's (repro.core.dsm.make_local_phase) without the
    accumulation axis; with ``device_parallel`` + a worker mesh it runs
    shard_mapped over the worker axis, like DSM's.
    """

    local_phase = make_local_phase(
        loss_fn, base_opt, accum=False,
        device_parallel=device_parallel, mesh=mesh,
    )

    def init(params: PyTree, n_workers: int) -> LocalMethodState:
        state = LocalMethodState(
            params=WK.broadcast_workers(params, n_workers),
            x0=params,
            aux=init_aux(params),
            base_state=WK.broadcast_workers(base_opt.init(params), n_workers),
            t=jnp.zeros((), jnp.int32),
            inner=jnp.zeros((), jnp.int32),
        )
        if mesh is not None:
            from repro.distributed import zero as Z

            state = state._replace(
                params=Z.put_workers(state.params, mesh),
                base_state=Z.put_workers(state.base_state, mesh),
            )
        return state

    def outer_step(state: LocalMethodState, batch):
        gamma = schedule(state.t)

        params_w, base_state_w, losses = local_phase(
            state.params, state.base_state, batch, gamma, state.inner
        )

        x_tau_mean = WK.worker_mean(params_w)  # all-reduce
        new_x0, new_aux = global_update(state.x0, state.aux, x_tau_mean, gamma, state.t)

        new_params = WK.broadcast_workers(new_x0, WK.n_workers_of(state.params))
        if mesh is not None:
            from repro.distributed import zero as Z

            new_params = Z.constrain_workers(new_params, mesh)
        new_state = LocalMethodState(
            params=new_params,
            x0=new_x0,
            aux=new_aux,
            base_state=base_state_w,
            t=state.t + 1,
            inner=state.inner + tau,
        )
        # losses is (tau, W); reduce outside the collective-free local phase
        return new_state, {"loss": losses.mean(), "gamma": gamma}

    return init, outer_step


# ---------------------------------------------------------------------------
# Global updates
# ---------------------------------------------------------------------------

def _f32(x):
    return x.astype(jnp.float32)


def slowmo(loss_fn, base_opt, tau, schedule, beta: float = 0.5, alpha: float = 1.0,
           **local_kw):
    """SlowMo (Alg. 5): u <- beta*u + Delta ; x <- x0 - alpha*gamma*u."""

    def init_aux(params):
        return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)

    def global_update(x0, u, x_tau, gamma, t):
        new_u = jax.tree.map(
            lambda uu, a, b: beta * uu + (_f32(a) - _f32(b)) / gamma, u, x0, x_tau
        )
        new_x = jax.tree.map(
            lambda a, uu: (_f32(a) - alpha * gamma * uu).astype(a.dtype), x0, new_u
        )
        return new_x, new_u

    return make_local_step_method(loss_fn, base_opt, tau, schedule, init_aux,
                                  global_update, **local_kw)


def signed_slowmo(loss_fn, base_opt, tau, schedule, beta: float = 0.5, eta: float = 1.0,
                  **local_kw):
    """§4.1: u <- beta*m + (1-beta)*sign(x0-x_tau)/gamma ... wait — as printed:
    u_{t+1} = beta*m_t + ((1-beta)/gamma)*sign(x0 - x_tau); x <- x0 - eta*gamma*u.
    We implement exactly the printed form (sign taken *before* momentum)."""

    def init_aux(params):
        return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)

    def global_update(x0, m, x_tau, gamma, t):
        new_m = jax.tree.map(
            lambda mm, a, b: beta * mm
            + (1.0 - beta) / gamma * jnp.sign(_f32(a) - _f32(b)),
            m, x0, x_tau,
        )
        new_x = jax.tree.map(
            lambda a, uu: (_f32(a) - eta * gamma * uu).astype(a.dtype), x0, new_m
        )
        return new_x, new_m

    return make_local_step_method(loss_fn, base_opt, tau, schedule, init_aux,
                                  global_update, **local_kw)


def lookahead(loss_fn, base_opt, tau, schedule, beta: float = 0.2, eta: float = 1.0,
              **local_kw):
    """Lookahead (§4.1): DSM with (7) replaced by x <- x0 - eta*gamma*u (no sign)."""

    def init_aux(params):
        return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)

    def global_update(x0, m, x_tau, gamma, t):
        delta = jax.tree.map(lambda a, b: (_f32(a) - _f32(b)) / gamma, x0, x_tau)
        u = jax.tree.map(lambda mm, dd: beta * mm + (1.0 - beta) * dd, m, delta)
        new_x = jax.tree.map(
            lambda a, uu: (_f32(a) - eta * gamma * uu).astype(a.dtype), x0, u
        )
        return new_x, u

    return make_local_step_method(loss_fn, base_opt, tau, schedule, init_aux,
                                  global_update, **local_kw)


def local_avg(loss_fn, base_opt, tau, schedule, **local_kw):
    """Local AdamW / FedAvg-style: x <- mean_i x^{(i)}_{t,tau} (App. C.2)."""

    def init_aux(params):
        return ()

    def global_update(x0, aux, x_tau, gamma, t):
        return x_tau, aux

    return make_local_step_method(loss_fn, base_opt, tau, schedule, init_aux,
                                  global_update, **local_kw)


class _GlobalAdamWAux(NamedTuple):
    m: PyTree
    v: PyTree


def global_adamw(
    loss_fn, base_opt, tau, schedule,
    eta: float = 1.0, b1: float = 0.9, b2: float = 0.95,
    weight_decay: float = 0.0, eps: float = 1e-8, **local_kw,
):
    """Alg. 7: AdamW on the pseudo-gradient g = (x0 - x_tau)/gamma."""

    def init_aux(params):
        def zeros():
            return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)

        return _GlobalAdamWAux(m=zeros(), v=zeros())

    def global_update(x0, aux, x_tau, gamma, t):
        g = jax.tree.map(lambda a, b: (_f32(a) - _f32(b)) / gamma, x0, x_tau)
        new_m = jax.tree.map(lambda m, gg: b1 * m + (1 - b1) * gg, aux.m, g)
        new_v = jax.tree.map(lambda v, gg: b2 * v + (1 - b2) * gg * gg, aux.v, g)
        tc = (t + 1).astype(jnp.float32)
        bc1, bc2 = 1 - b1 ** tc, 1 - b2 ** tc

        def _upd(x, m, v):
            step = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + weight_decay * _f32(x)
            return (_f32(x) - eta * gamma * step).astype(x.dtype)

        return jax.tree.map(_upd, x0, new_m, new_v), _GlobalAdamWAux(new_m, new_v)

    return make_local_step_method(loss_fn, base_opt, tau, schedule, init_aux,
                                  global_update, **local_kw)


# ---------------------------------------------------------------------------
# Standalone per-step data parallel (the paper's communication-heavy upper
# baseline: all-reduce mini-batch gradients EVERY local computation round).
# ---------------------------------------------------------------------------

class PerStepDPState(NamedTuple):
    params: PyTree      # single global copy
    base_state: PyTree
    t: jnp.ndarray


def make_perstep_dp_step(loss_fn, base_opt: BaseOptimizer, tau: int, schedule):
    """tau compute rounds per call; gradient all-reduce each round.

    batch leaves are (W, tau, ...) like the local-step methods, so one call
    consumes the same tokens as one DSM outer step but communicates tau x more.
    """

    grad_fn = jax.value_and_grad(loss_fn)

    def init(params, n_workers):
        del n_workers
        return PerStepDPState(params, base_opt.init(params), jnp.zeros((), jnp.int32))

    def outer_step(state: PerStepDPState, batch):
        def one_step(carry, microbatch):
            params, base_state, k = carry
            gamma = schedule(k // tau)  # schedule indexed by outer-equivalent step
            losses, grads = jax.vmap(lambda mb: grad_fn(params, mb))(microbatch)
            g_mean = jax.tree.map(lambda g: g.mean(axis=0), grads)  # all-reduce
            d, new_bs = base_opt.direction(g_mean, base_state, params, k)
            new_p = jax.tree.map(lambda x, dd: x - gamma * dd, params, d)
            return (new_p, new_bs, k + 1), losses.mean()

        mb_scan = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), batch)
        (params, base_state, k), losses = jax.lax.scan(
            one_step, (state.params, state.base_state, state.t * tau), mb_scan
        )
        return (
            PerStepDPState(params, base_state, state.t + 1),
            {"loss": losses.mean()},
        )

    return init, outer_step


# ---------------------------------------------------------------------------
# Federated MV-sto-signSGD-SIM (Alg. 6, Sun et al. 2023)
# ---------------------------------------------------------------------------

class MVState(NamedTuple):
    x: PyTree
    x_prev: PyTree
    m: PyTree           # per-worker momentum (repro.core.workers layout)
    t: jnp.ndarray


def make_mv_signsgd_step(
    loss_fn, tau: int, gamma: float, eta: float,
    beta: float = 0.9, alpha: float = 0.5, bound: float = 1.0,
):
    """Alg. 6: local SGD from the extrapolated point, randomized-sign majority vote."""

    grad_fn = jax.value_and_grad(loss_fn)

    def init(params, n_workers):
        return MVState(
            x=params,
            x_prev=jax.tree.map(jnp.copy, params),  # own buffers: state is donated
            m=WK.broadcast_workers(
                jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params), n_workers
            ),
            t=jnp.zeros((), jnp.int32),
        )

    def outer_step(state: MVState, batch, rng: jax.Array):
        # y_t = x_t + alpha (x_t - x_{t-1})
        y = jax.tree.map(lambda a, b: a + alpha * (a - b), state.x, state.x_prev)
        y_w = WK.broadcast_workers(y, WK.n_workers_of(state.m))
        axes = WK.worker_axes(y_w)

        def one_local(carry, microbatch):
            z, k = carry

            def per_worker(p, mb):
                loss, g = grad_fn(p, mb)
                return jax.tree.map(lambda x, gg: x - gamma * gg, p, g), loss

            new_z, losses = jax.vmap(per_worker, in_axes=(axes, 0),
                                     out_axes=(axes, 0))(z, microbatch)
            return (new_z, k + 1), losses.mean()

        mb_scan = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1)[: tau], batch)
        (z_tau, _), losses = jax.lax.scan(
            one_local, (y_w, jnp.zeros((), jnp.int32)), mb_scan
        )

        # local momentum from a fresh gradient at y^{(i)} = z_tau^{(i)}
        last_mb = jax.tree.map(lambda x: x[:, -1], batch)
        _, g_last = jax.vmap(grad_fn, in_axes=(axes, 0),
                             out_axes=(0, axes))(z_tau, last_mb)
        new_m = jax.tree.map(
            lambda m, g: beta * m + (1 - beta) * _f32(g), state.m, g_last
        )

        # randomized sign per worker, sum, majority vote
        leaves, treedef = jax.tree_util.tree_flatten_with_path(new_m)
        keys = jax.random.split(rng, len(leaves))
        votes = [
            jax.vmap(lambda mm, kk: randomized_sign_pm(mm, kk, bound),
                     in_axes=(WK.worker_axis(path), 0))(
                leaf, jax.random.split(key, leaf.shape[WK.worker_axis(path)])
            ).sum(axis=0)
            for (path, leaf), key in zip(leaves, keys)
        ]
        vote_tree = jax.tree.unflatten(treedef, votes)
        new_x = jax.tree.map(
            lambda x, v: (_f32(x) - eta * jnp.sign(v)).astype(x.dtype),
            state.x, vote_tree,
        )
        return (
            MVState(x=new_x, x_prev=state.x, m=new_m, t=state.t + 1),
            {"loss": losses.mean()},
        )

    return init, outer_step
