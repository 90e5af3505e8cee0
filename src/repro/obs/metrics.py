"""On-device metric pack for the DSM outer step (docs/observability.md).

The paper's claims are about optimizer *dynamics* — sign momentum built
from local-step differences — so the quantities worth watching are the l1 /
l2 statistics that govern sign methods (Bernstein et al., 2018: signSGD's
convergence is controlled by the gradient density phi = ||g||_1^2 /
(d * ||g||_2^2)) and the alignment between the momentum ``m`` and each
round's pseudo-gradient ``Delta = (x_0 - x_tau) / gamma``.

Everything here is computed INSIDE the jitted outer step and returned as
one stacked ``(N_METRICS,)`` f32 array (``metrics["pack"]``), so
instrumentation adds **zero host syncs** — the trainer keeps the packs on
device and fetches them asynchronously at log / eval / checkpoint points.
The collective cost is bounded by construction:

  * ``loss_stats`` folds the three per-worker loss statistics into a
    single stacked reduction, so a worker-sharded loss matrix lowers to
    ONE all-reduce (instead of one per statistic);
  * the global-state sums (``stat_sums_block``) are plain elementwise
    sums — collective-free on replicated buffers, and the ZeRO-sharded
    path wraps them in one psum of the stacked partials
    (``repro.distributed.zero.sharded_stat_sums``).

Both fit inside the ``n_metric_reductions = 2`` scalar-reduction allowance
the audited per-phase budgets already carry (``repro.analysis.comm``),
which is how ``python -m repro.analysis audit`` proves the instrumented
step keeps the paper's collective budget unchanged.

This module is jit-reachable: no host reads, no traced-value branches.
Host-side decoding (pack -> dict) lives in ``repro.obs.sinks``.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

PyTree = Any

# Pack layout.  Definitions (d = number of global parameters):
#   loss          mean local-step train loss over (tau, W)
#   last_loss     mean loss of the LAST local step (end-of-round iterate)
#   gamma         inner learning rate of the round
#   pg_l1         ||Delta||_1, Delta = (x_0 - x_tau)/gamma (pseudo-gradient)
#   pg_l2         ||Delta||_2
#   pg_density    ||Delta||_1^2 / (d * ||Delta||_2^2)  in (0, 1]; the
#                 signSGD density phi (1 = uniform, 1/d = one-hot)
#   sign_agree    (1/d) sum_j 1[sign(m_j) * sign(Delta_j) > 0]  — fraction
#                 of coordinates where the momentum and the round's
#                 accumulated difference vote the same sign (0 while m = 0)
#   m_l1          ||m||_1 (momentum mass)
#   update_cos    cos(u, m), u = beta1*m + (1-beta1)*Delta — the round's
#                 pre-sign update direction vs the momentum carried in
#                 from previous rounds
#   worker_spread std over workers of the per-worker mean loss
#   survivor_frac fraction of usable worker contributions (1.0 dense)
#   guard_ok      1.0 accepted / 0.0 rejected (set by the guard wrapper)
METRIC_NAMES = (
    "loss",
    "last_loss",
    "gamma",
    "pg_l1",
    "pg_l2",
    "pg_density",
    "sign_agree",
    "m_l1",
    "update_cos",
    "worker_spread",
    "survivor_frac",
    "guard_ok",
)
IDX = {name: i for i, name in enumerate(METRIC_NAMES)}
N_METRICS = len(METRIC_NAMES)

# Raw sums the pack is finished from; every entry is a plain elementwise
# sum so shard-local partials combine by addition (one psum when sharded).
STAT_SUMS = ("pg_l1", "pg_sq", "m_l1", "sign_agree_count", "u_dot_m",
             "u_sq", "m_sq")
N_STAT_SUMS = len(STAT_SUMS)

_EPS = 1e-12


def loss_stats(losses: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(loss, last_loss, worker_spread)`` from the ``(tau, W)`` per-worker
    loss matrix of one round.

    The three statistics are stacked into a single ``(3, W)`` array before
    the worker reduction, so when ``losses`` is worker-sharded (the
    device-parallel local phase) the whole bundle lowers to ONE all-reduce
    — it rides the metric-scalar allowance of the audited budgets.
    """
    per_worker = losses.mean(axis=0)                     # (W,) shard-local
    bundle = jnp.stack([per_worker, losses[-1], per_worker * per_worker])
    s = bundle.mean(axis=1)                              # the ONE reduction
    spread = jnp.sqrt(jnp.maximum(s[2] - s[0] * s[0], 0.0))
    return s[0], s[1], spread


def stat_sums_block(
    x0_leaves: Sequence[jnp.ndarray],
    m_leaves: Sequence[jnp.ndarray],
    xt_leaves: Sequence[jnp.ndarray],
    gamma: jnp.ndarray,
    beta1: float,
) -> jnp.ndarray:
    """``(N_STAT_SUMS,)`` f32 sums over the given leaf blocks.

    Pure elementwise + local sums: on replicated buffers this compiles to
    zero collectives; the ZeRO-sharded path calls it per-shard inside a
    shard_map and psums the stacked result once.
    """
    g = jnp.asarray(gamma, jnp.float32)
    b1 = jnp.float32(beta1)
    tot = jnp.zeros((N_STAT_SUMS,), jnp.float32)
    for x0l, ml, xtl in zip(x0_leaves, m_leaves, xt_leaves):
        x0f = x0l.astype(jnp.float32)
        mf = ml.astype(jnp.float32)
        delta = (x0f - xtl.astype(jnp.float32)) / g
        u = b1 * mf + (1.0 - b1) * delta
        agree = (jnp.sign(mf) * jnp.sign(delta)) > 0
        tot = tot + jnp.stack([
            jnp.abs(delta).sum(),
            (delta * delta).sum(),
            jnp.abs(mf).sum(),
            agree.sum().astype(jnp.float32),
            (u * mf).sum(),
            (u * u).sum(),
            (mf * mf).sum(),
        ])
    return tot


def tree_stat_sums(x0: PyTree, m: PyTree, x_tau: PyTree, gamma, beta1: float) -> jnp.ndarray:
    """Whole-tree ``stat_sums_block`` (replicated / dense path)."""
    return stat_sums_block(
        jax.tree.leaves(x0), jax.tree.leaves(m), jax.tree.leaves(x_tau),
        gamma, beta1,
    )


def n_elements(tree: PyTree) -> int:
    return sum(l.size for l in jax.tree.leaves(tree))


def finish_pack(
    *,
    loss,
    last_loss,
    gamma,
    worker_spread,
    stat_sums: jnp.ndarray,
    n_elems: int,
    survivor_frac=None,
) -> jnp.ndarray:
    """Assemble the ``(N_METRICS,)`` f32 pack from the raw sums."""
    l1, sq, m_l1, agree, u_dot_m, u_sq, m_sq = (stat_sums[i] for i in range(N_STAT_SUMS))
    pg_l2 = jnp.sqrt(sq)
    density = (l1 * l1) / (n_elems * sq + _EPS)
    cos = u_dot_m / (jnp.sqrt(u_sq) * jnp.sqrt(m_sq) + _EPS)
    sf = (jnp.float32(1.0) if survivor_frac is None
          else jnp.asarray(survivor_frac, jnp.float32))
    return jnp.stack([
        jnp.asarray(loss, jnp.float32),
        jnp.asarray(last_loss, jnp.float32),
        jnp.asarray(gamma, jnp.float32),
        l1,
        pg_l2,
        density,
        agree / n_elems,
        m_l1,
        cos,
        jnp.asarray(worker_spread, jnp.float32),
        sf,
        jnp.float32(1.0),
    ])


def minimal_pack(loss, gamma: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Pack for algorithms without global-state instrumentation (the
    baselines): loss (+ gamma when known), NaN for the DSM-only entries."""
    vals = [jnp.float32(jnp.nan)] * N_METRICS
    vals[IDX["loss"]] = jnp.asarray(loss, jnp.float32)
    if gamma is not None:
        vals[IDX["gamma"]] = jnp.asarray(gamma, jnp.float32)
    vals[IDX["survivor_frac"]] = jnp.float32(1.0)
    vals[IDX["guard_ok"]] = jnp.float32(1.0)
    return jnp.stack(vals)


def set_guard_flag(pack: jnp.ndarray, ok) -> jnp.ndarray:
    """Record the guard verdict in the pack (device-side select)."""
    return pack.at[IDX["guard_ok"]].set(jnp.asarray(ok, jnp.float32))
