"""Distributed Sign Momentum with local steps — the paper's Algorithm 1.

Structure (one *outer* step t):

  1. every worker i runs tau local steps of a base optimizer:
         x^{(i)}_{t,k+1} = x^{(i)}_{t,k} - gamma_t * d^{(i)}_{t,k}
  2. ONE all-reduce:  x_{t,tau} = mean_i x^{(i)}_{t,tau}
  3. global Lion-style sign-momentum step on the pseudo-gradient
     Delta_t = (x_{t,0} - x_{t,tau}) / gamma_t :
         u_{t+1}   = beta1 * m_t + (1-beta1) * Delta_t          (eq. 6)
         x_{t+1,0} = x_{t,0} - eta*gamma_t*(sign(u_{t+1}) + lam*x_{t,0})  (eq. 7)
         m_{t+1}   = beta2 * m_t + (1-beta2) * Delta_t          (eq. 8)
  4. broadcast x_{t+1,0} back to all workers.

Workers are represented by an axis ``W`` on params / optimizer state /
batches: leading on batches and on most leaves, second on the layer-stacked
leaves the model scans (``repro.core.workers``).  On a mesh this axis is
sharded over ``"worker"``, so step 1 emits **no inter-worker collectives**
(everything is elementwise in W) and step 2 lowers to a single all-reduce
over ``"worker"`` — the tau-amortized communication the paper is about.

Instances (paper §2 "Algorithm instances"):
  * tau=1, beta1=beta2=beta, lam=0    -> signSGD with momentum (eq. 3)
  * n=1 (W=1)                         -> signed Lookahead (+ decoupled wd)

The randomized sign operators of §3.1 (eqs. 9/10) used by the theory are
provided for validation; training uses the real sign.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import workers as WK
from repro.core.base_opt import BaseOptimizer
from repro.obs import metrics as OM

PyTree = Any


# ---------------------------------------------------------------------------
# Sign operators
# ---------------------------------------------------------------------------

def sign(u: jnp.ndarray) -> jnp.ndarray:
    return jnp.sign(u)


def randomized_sign_pm(u: jnp.ndarray, key: jax.Array, bound: float) -> jnp.ndarray:
    """Eq. (9): +-sign(v_j), P[sign(v_j)] = 1/2 + |v_j|/(2B).  E[.] = v/B."""
    p_keep = 0.5 + jnp.abs(u) / (2.0 * bound)
    flip = jax.random.uniform(key, u.shape, dtype=u.dtype) < p_keep
    return jnp.where(flip, jnp.sign(u), -jnp.sign(u))


def randomized_sign_zero(u: jnp.ndarray, key: jax.Array, bound: float) -> jnp.ndarray:
    """Eq. (10): sign(v_j) w.p. |v_j|/B else 0.  E[.] = v/B."""
    keep = jax.random.uniform(key, u.shape, dtype=u.dtype) < jnp.abs(u) / bound
    return jnp.where(keep, jnp.sign(u), jnp.zeros_like(u))


SIGN_MODES = ("sign", "rand_pm", "rand_zero")


# ---------------------------------------------------------------------------
# Config / state
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DSMConfig:
    """Hyper-parameters of Algorithm 1.

    Defaults are the paper's recommended Lion parameters for the global step
    (beta1=0.95, beta2=0.98, lambda=0.1; §4 Implementations).
    """

    tau: int = 12                 # communication interval (local steps)
    global_lr: float = 1.0        # eta
    beta1: float = 0.95           # u_{t+1} interpolation (eq. 6)
    beta2: float = 0.98           # m_{t+1} interpolation (eq. 8)
    weight_decay: float = 0.1     # decoupled lambda (eq. 7)
    sign_mode: str = "sign"       # "sign" | "rand_pm" | "rand_zero"
    sign_bound: float = 1.0       # B for randomized sign (theory uses tau*R)
    zero_sharded: bool = False    # beyond-paper: ZeRO-style sharded global step
    use_kernel: bool = False      # fused Pallas kernel for the global step
    device_parallel_local: bool = False  # shard_map the local phase over "worker"
    mask_nonfinite: bool = False  # survivor-aware mean masks NaN/inf workers

    def __post_init__(self):
        if self.sign_mode not in SIGN_MODES:
            raise ValueError(f"sign_mode must be one of {SIGN_MODES}")
        if not (0.0 <= self.beta1 <= 1.0 and 0.0 <= self.beta2 <= 1.0):
            raise ValueError("momentum coefficients must lie in [0, 1]")
        if self.tau < 1:
            raise ValueError("tau must be >= 1")


class DSMState(NamedTuple):
    # per-worker leaves are (W, *shape), or (L, W, ...) when layer-stacked
    # (repro.core.workers)
    params: PyTree       # per-worker params
    x0: PyTree           # global model buffer x_{t,0}, leaves (*shape)
    m: PyTree            # global sign momentum m_t, leaves (*shape)
    base_state: PyTree   # per-worker base-opt state
    t: jnp.ndarray       # outer step counter
    inner: jnp.ndarray   # total local-step counter (base-opt bias correction)


# ---------------------------------------------------------------------------
# Survivor-aware aggregation (robustness layer; see docs/fault_tolerance.md).
# Line 7's worker mean becomes a mask-weighted mean: dropped workers are
# excluded by the caller-supplied survivor mask, NaN/inf-corrupted
# contributions are detected on device and masked, and a round with zero
# usable contributions leaves x0 / m bit-untouched (skip-round semantics).
# Everything is elementwise in W, so the same code runs vmapped, under the
# shard_map local phase, and inside the ZeRO-sharded global step.
# ---------------------------------------------------------------------------

def worker_finite_mask(params_w: PyTree) -> jnp.ndarray:
    """``(W,)`` bool: worker i's contribution is finite in EVERY leaf."""
    ok = jnp.ones((WK.n_workers_of(params_w),), bool)
    for path, l in jax.tree_util.tree_flatten_with_path(params_w)[0]:
        if jnp.issubdtype(l.dtype, jnp.floating):
            ax = WK.worker_axis(path)
            ok = ok & jnp.isfinite(l).all(
                axis=tuple(i for i in range(l.ndim) if i != ax))
    return ok


def masked_worker_mean(params_w: PyTree, weights: jnp.ndarray) -> PyTree:
    """Weighted worker mean; zero-weight workers are fully zeroed BEFORE the
    sum so their NaNs cannot propagate (NaN * 0 == NaN).  An all-zero weight
    vector yields 0 (the caller must apply skip-round semantics)."""
    wsum = jnp.maximum(weights.astype(jnp.float32).sum(), 1.0)

    def leaf(path, p):
        w = WK.along_workers(weights.astype(p.dtype), path, p)
        contrib = jnp.where(w > 0, p, jnp.zeros((), p.dtype))
        return ((w * contrib).sum(axis=WK.worker_axis(path))
                / wsum.astype(p.dtype))

    return jax.tree_util.tree_map_with_path(leaf, params_w)


def _contribution_weights(contrib: PyTree, cfg: "DSMConfig",
                          faults) -> Optional[jnp.ndarray]:
    """(W,) f32 weights combining the announced survivor mask (dropouts)
    with on-device finiteness detection, or None for the dense fast path."""
    weights = None
    if faults is not None:
        weights = faults.survivors.astype(jnp.float32)
    if cfg.mask_nonfinite or faults is not None:
        finite = worker_finite_mask(contrib).astype(jnp.float32)
        weights = finite if weights is None else weights * finite
    return weights


def dsm_init(
    params: PyTree,
    base_opt: BaseOptimizer,
    n_workers: int,
    momentum_dtype=jnp.float32,
    mesh=None,
    global_sharded: bool = True,
) -> DSMState:
    """Initialize Algorithm 1 state from a single (global) param pytree.

    With ``mesh`` (a ``("worker", "zero", "model")`` training mesh) the
    per-worker params / base state are sharded over the worker axis, and —
    when ``global_sharded`` — x0 / m are laid out for the ZeRO-sharded
    global step (sharded over the flattened (worker, zero) ranks).  A
    device-parallel local phase without ``zero_sharded`` keeps x0 / m
    replicated (``global_sharded=False``).
    """
    state = DSMState(
        params=WK.broadcast_workers(params, n_workers),
        x0=params,
        m=jax.tree.map(lambda p: jnp.zeros_like(p, dtype=momentum_dtype), params),
        # every worker starts from the same params, so from the same state
        base_state=WK.broadcast_workers(base_opt.init(params), n_workers),
        t=jnp.zeros((), jnp.int32),
        inner=jnp.zeros((), jnp.int32),
    )
    if mesh is not None:
        from repro.distributed import zero as Z

        state = Z.shard_dsm_state(state, mesh, global_sharded=global_sharded)
    return state


# ---------------------------------------------------------------------------
# Global sign-momentum step (eqs. 6-8), jnp reference path.
# The fused Pallas kernel in repro.kernels.dsm_update implements the same
# math in one HBM pass; see kernels/ref.py for the oracle == this function.
# ---------------------------------------------------------------------------

def global_sign_momentum_step(
    x0: PyTree,
    m: PyTree,
    x_tau_mean: PyTree,
    gamma: jnp.ndarray,
    cfg: DSMConfig,
    rng: Optional[jax.Array] = None,
) -> tuple[PyTree, PyTree]:
    """Apply eqs. (6)-(8) leafwise; returns (x_{t+1,0}, m_{t+1})."""
    if cfg.use_kernel:
        # The fused kernel implements the deterministic sign only; the
        # randomized operators (eqs. 9/10) fall back to the jnp path rather
        # than silently applying the wrong sign.
        if cfg.sign_mode == "sign":
            from repro.kernels import ops as kernel_ops

            return kernel_ops.dsm_update_tree(
                x0, m, x_tau_mean, gamma,
                eta=cfg.global_lr, beta1=cfg.beta1, beta2=cfg.beta2,
                lam=cfg.weight_decay,
            )

    leaves, treedef = jax.tree.flatten(x0)
    if cfg.sign_mode == "sign":
        keys = [None] * len(leaves)
    else:
        keys = list(jax.random.split(rng, len(leaves)))

    new_x, new_m = [], []
    for leaf_x0, leaf_m, leaf_xt, key in zip(
        leaves, jax.tree.leaves(m), jax.tree.leaves(x_tau_mean), keys
    ):
        # compute dtype follows the momentum buffer (f32 default; bf16 opt-in
        # for very large models where f32 temporaries would not fit HBM)
        cdt = leaf_m.dtype
        g = gamma.astype(cdt) if hasattr(gamma, "astype") else jnp.asarray(gamma, cdt)
        delta = (leaf_x0.astype(cdt) - leaf_xt.astype(cdt)) / g
        u = jnp.asarray(cfg.beta1, cdt) * leaf_m + jnp.asarray(1.0 - cfg.beta1, cdt) * delta
        if cfg.sign_mode == "sign":
            s = jnp.sign(u)
        elif cfg.sign_mode == "rand_pm":
            s = randomized_sign_pm(u, key, cfg.sign_bound)
        else:
            s = randomized_sign_zero(u, key, cfg.sign_bound)
        x_new = leaf_x0.astype(cdt) - jnp.asarray(cfg.global_lr, cdt) * g * (
            s + jnp.asarray(cfg.weight_decay, cdt) * leaf_x0.astype(cdt)
        )
        m_new = jnp.asarray(cfg.beta2, cdt) * leaf_m + jnp.asarray(1.0 - cfg.beta2, cdt) * delta
        new_x.append(x_new.astype(leaf_x0.dtype))
        new_m.append(m_new.astype(leaf_m.dtype))

    return jax.tree.unflatten(treedef, new_x), jax.tree.unflatten(treedef, new_m)


# ---------------------------------------------------------------------------
# Local phase (Algorithm 1 lines 3-6), shared by DSM and the local-step
# baselines.  Two execution layouts, numerically identical:
#
#   * vmapped (default): the worker axis W lives on one device and is mapped
#     with jax.vmap — a *simulation* of n workers (replicated compute).
#   * device-parallel (``device_parallel=True`` + mesh): the same body runs
#     under shard_map with every per-worker input sharded over "worker" on
#     its worker axis, so each device executes only its own worker block.
#     The body contains no psum/ppermute and never reads across the worker
#     axis, so the compiled local phase emits ZERO inter-worker collectives
#     by construction — the paper's premise that tau local steps are
#     communication-free.
#     Per-worker losses are returned unreduced (tau, W); the caller averages
#     them *outside* the local phase, where a collective is expected anyway.
# ---------------------------------------------------------------------------

def make_local_phase(
    loss_fn: Callable[[PyTree, Any], jnp.ndarray],
    base_opt: BaseOptimizer,
    *,
    accum: bool = True,
    device_parallel: bool = False,
    mesh=None,
):
    """Build ``local_phase(params_w, base_state_w, batch, gamma, inner0) ->
    (params_w, base_state_w, losses)`` with ``losses`` shaped ``(tau, W)``.

    ``accum``: batch leaves carry a gradient-accumulation axis —
    ``(W, tau, accum, B_micro, ...)`` — consumed by an inner scan; otherwise
    leaves are ``(W, tau, B, ...)`` and each local step is one minibatch.
    """

    grad_fn = jax.value_and_grad(loss_fn)

    def local_phase_block(params_w, base_state_w, batch, gamma, inner0):
        """tau local steps over whatever worker block the caller holds."""

        def one_local_step(carry, microbatch):
            params, base_state, k = carry

            def per_worker(p, bs, mb):
                if accum:
                    # mb leaves: (accum, B_micro, ...) -> accumulate grads
                    def acc_step(carry, mbi):
                        g_sum, loss_sum = carry
                        loss, g = grad_fn(p, mbi)
                        return (
                            jax.tree.map(jnp.add, g_sum, g),
                            loss_sum + loss,
                        ), None

                    acc = jax.tree.leaves(mb)[0].shape[0]
                    g0 = jax.tree.map(lambda x: jnp.zeros_like(x), p)
                    (g_sum, loss_sum), _ = jax.lax.scan(
                        acc_step, (g0, jnp.zeros((), jnp.float32)), mb
                    )
                    grads = jax.tree.map(lambda g: g / acc, g_sum)
                    loss = loss_sum / acc
                else:
                    loss, grads = grad_fn(p, mb)
                with jax.named_scope("base_opt"):
                    d, new_bs = base_opt.direction(grads, bs, p, inner0 + k)
                    new_p = jax.tree.map(
                        lambda x, dd: (
                            x.astype(jnp.float32) - gamma * dd.astype(jnp.float32)
                        ).astype(x.dtype),
                        p, d,
                    )
                return new_p, new_bs, loss

            axes = (WK.worker_axes(params), WK.worker_axes(base_state))
            new_params, new_base, losses = jax.vmap(
                per_worker, in_axes=(*axes, 0), out_axes=(*axes, 0),
            )(params, base_state, microbatch)
            return (new_params, new_base, k + 1), losses  # (W_block,)

        # scan over the tau microbatches: batch leaves (W, tau, ...) -> (tau, W, ...)
        mb_scan = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), batch)
        (params_w, base_state_w, _), losses = jax.lax.scan(
            one_local_step, (params_w, base_state_w, jnp.zeros((), jnp.int32)), mb_scan
        )
        return params_w, base_state_w, losses  # losses: (tau, W_block)

    if not device_parallel:
        return local_phase_block

    if mesh is None or "worker" not in mesh.axis_names:
        raise ValueError(
            "device_parallel local phase needs a mesh with a 'worker' axis "
            "(repro.launch.mesh.host_training_mesh)"
        )

    from jax.sharding import PartitionSpec as P

    n_worker_devices = dict(zip(mesh.axis_names, mesh.devices.shape))["worker"]

    def local_phase(params_w, base_state_w, batch, gamma, inner0):
        n_workers = WK.n_workers_of(params_w)
        if n_workers % n_worker_devices:
            raise ValueError(
                f"n_workers={n_workers} must be a multiple of the mesh's "
                f"worker axis ({n_worker_devices}) for the device-parallel "
                "local phase"
            )
        specs = (WK.worker_pspecs(params_w), WK.worker_pspecs(base_state_w))
        return jax.shard_map(
            local_phase_block,
            mesh=mesh,
            in_specs=(*specs, P("worker"), P(), P()),
            out_specs=(*specs, P(None, "worker")),
            check_vma=False,
        )(params_w, base_state_w, batch, gamma, inner0)

    return local_phase


# ---------------------------------------------------------------------------
# Outer-step factory
# ---------------------------------------------------------------------------

def make_dsm_step(
    loss_fn: Callable[[PyTree, Any], jnp.ndarray],
    base_opt: BaseOptimizer,
    cfg: DSMConfig,
    schedule: Callable[[jnp.ndarray], jnp.ndarray],
    mesh=None,
):
    """Build ``outer_step(state, batch[, rng]) -> (state, metrics)``.

    ``batch`` must have leaves shaped ``(W, tau, accum, B_micro, ...)``:
    worker axis first, one microbatch-group per local step, ``accum``
    gradient-accumulation microbatches inside each local step.
    ``loss_fn(params, microbatch)`` consumes single-worker params and one
    ``(B_micro, ...)`` microbatch.

    With ``cfg.zero_sharded`` and a ``("worker", "zero", "model")`` mesh, the
    global step runs ZeRO-sharded (repro.distributed.zero): reduce-scatter of
    x_tau, shard-local update of x0 / m, all-gather of x_{t+1,0} via the
    worker broadcast.  With ``cfg.device_parallel_local`` the tau local steps
    run under shard_map with every per-worker buffer sharded over the mesh's
    worker axis — genuinely data-parallel, zero inter-worker collectives.

    ``faults`` (optional ``repro.robustness.faults.FaultRound``) makes the
    round survivor-aware: announced dropouts are excluded from the x_tau
    mean, straggler/corrupt contributions are injected, and non-finite
    contributions are detected and masked on device.  A round with no
    usable contribution leaves x0 / m bit-untouched (workers still re-sync
    from the unchanged x0).  ``cfg.mask_nonfinite`` enables the detection
    path without injection (real-run protection).
    """

    local_phase = make_local_phase(
        loss_fn, base_opt, accum=True,
        device_parallel=cfg.device_parallel_local, mesh=mesh,
    )

    def outer_step(state: DSMState, batch, rng: Optional[jax.Array] = None,
                   faults=None):
        gamma = schedule(state.t)
        n_workers = WK.n_workers_of(state.params)

        with jax.named_scope("dsm_local_phase"):
            params_w, base_state_w, losses = local_phase(
                state.params, state.base_state, batch, gamma, state.inner
            )

        # --- fault injection + survivor weights (None -> dense fast path,
        # identical to the pre-robustness step) ---
        contrib = params_w
        if faults is not None:
            from repro.robustness.faults import apply_faults

            contrib = apply_faults(params_w, state.x0, faults)
        weights = _contribution_weights(contrib, cfg, faults)

        with jax.named_scope("dsm_global_step"):
            if cfg.zero_sharded and mesh is not None:
                # --- lines 7-10, ZeRO-sharded: reduce-scatter(x_tau) ->
                # shard-local sign momentum on each rank's 1/(W*zero) slice ---
                from repro.distributed import zero as Z

                new_x0, new_m, x_tau = Z.sharded_global_sign_momentum_step(
                    state.x0, state.m, contrib, gamma, cfg, mesh, rng,
                    weights=weights, return_x_tau=True,
                )
                # pre-update Delta/momentum stats on the sharded buffers:
                # ONE psum for the whole pack (repro.obs.metrics)
                stat = Z.sharded_stat_sums(state.x0, state.m, x_tau, gamma,
                                           cfg.beta1, mesh)
            else:
                # --- line 7: THE all-reduce over workers (once per tau local steps) ---
                if weights is None:
                    x_tau = WK.worker_mean(contrib)
                else:
                    x_tau = masked_worker_mean(contrib, weights)
                if mesh is not None:
                    # the worker-axis reduction already replicates its result;
                    # pin that layout so the stat sums below never re-reduce
                    from repro.distributed import zero as Z

                    x_tau = Z.constrain_replicated(x_tau, mesh)

                # --- lines 8-10: global sign momentum ---
                new_x0, new_m = global_sign_momentum_step(
                    state.x0, state.m, x_tau, gamma, cfg, rng
                )
                stat = OM.tree_stat_sums(state.x0, state.m, x_tau, gamma,
                                         cfg.beta1)

        wsum = None
        if weights is not None:
            # skip-round: zero usable contributions -> x0 / m bit-untouched
            wsum = weights.sum()
            ok = wsum > 0
            new_x0 = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                  new_x0, state.x0)
            new_m = jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                                 new_m, state.m)

        # --- line 11: synchronize workers (the all-gather when sharded) ---
        new_params = WK.broadcast_workers(new_x0, n_workers)
        if mesh is not None:
            from repro.distributed import zero as Z

            new_params = Z.constrain_workers(new_params, mesh)

        new_state = DSMState(
            params=new_params,
            x0=new_x0,
            m=new_m,
            base_state=base_state_w,
            t=state.t + 1,
            inner=state.inner + cfg.tau,
        )
        # losses is (tau, W): per-worker means happen HERE, outside the
        # collective-free local phase, as ONE stacked reduction
        loss_mean, last_loss, worker_spread = OM.loss_stats(losses)
        metrics = {"loss": loss_mean, "gamma": gamma, "last_loss": last_loss}
        metrics["pack"] = OM.finish_pack(
            loss=loss_mean, last_loss=last_loss, gamma=gamma,
            worker_spread=worker_spread, stat_sums=stat,
            n_elems=OM.n_elements(state.x0),
            survivor_frac=None if wsum is None else wsum / n_workers,
        )
        if wsum is not None:
            metrics["survivors"] = wsum
        return new_state, metrics

    return outer_step


# ---------------------------------------------------------------------------
# Convenience instances
# ---------------------------------------------------------------------------

def signsgd_momentum_config(beta: float) -> DSMConfig:
    """tau=1, beta1=beta2=beta, lam=0: exactly eq. (3) signSGD w/ momentum."""
    return DSMConfig(tau=1, beta1=beta, beta2=beta, weight_decay=0.0)


def signed_lookahead_config(tau: int, beta: float, weight_decay: float = 0.0) -> DSMConfig:
    """n=1 instance (§4.1 ablation): signed Lookahead with decoupled wd."""
    return DSMConfig(tau=tau, beta1=beta, beta2=beta, weight_decay=weight_decay)
