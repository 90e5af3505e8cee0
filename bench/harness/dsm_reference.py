"""Plain reference of the DSM outer step (Algorithm 1 of the paper) with
AdamW local steps, independent of the program under test.

W workers each take tau AdamW steps from the shared x0 on their own rows;
the iterates are averaged (the worker mean); the global sign-momentum step
updates x0 and m from Delta = (x0 - x_tau) / gamma:

    u   = beta1 * m + (1 - beta1) * Delta
    x0 <- x0 - eta * gamma * (sign(u) + lam * x0)
    m  <- beta2 * m + (1 - beta2) * Delta

Arithmetic is float32 at ``highest`` matmul precision.  Parameters are
stored in the configuration's parameter dtype after every update (the
local step, the worker mean and the global step), as the configuration
states; optimizer moments and the global momentum stay float32.  AdamW's
state persists across outer steps, its step count runs over all local
steps.

Workers are placed round-robin on ``devices``, so on four chips the four
workers' local phases run at once.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


@dataclasses.dataclass
class Readings:
    """What the comparison reads from K outer steps."""

    loss: list            # per outer step: mean loss over tau x W local steps
    delta0: list          # per leaf: ||Delta of outer step 1||
    change: list          # per leaf: ||x0 after K steps - x0 before step 1||
    delta0_leaves: list = None   # per leaf: Delta of outer step 1 (host)


def leaf_norms(tree) -> list:
    return [jnp.sqrt(jnp.sum(jnp.square(l.astype(F32))))
            for l in jax.tree.leaves(tree)]


def run(loss_fn: Callable, init_fn: Callable, batches: Sequence[np.ndarray],
        mix: dict, gamma: float, devices, dot=None) -> Readings:
    """Follow ``len(batches)`` outer steps from ``init_fn()`` (float32-free
    weights in the parameter dtype) on token batches (W, tau, accum, B, S)."""
    ad, ds = mix["adamw"], mix["dsm"]
    n_workers, tau = mix["n_workers"], mix["tau"]
    b1, b2, eps, wd = ad["b1"], ad["b2"], ad["eps"], ad["weight_decay"]

    with jax.default_matmul_precision("highest"):

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def local_step(p, m, v, count, tokens):
            pdt = jax.tree.leaves(p)[0].dtype
            pf = jax.tree.map(lambda x: x.astype(F32), p)
            # gradient accumulation: mean of the microbatches' gradients
            def one(tok):
                return jax.value_and_grad(loss_fn)(pf, tok, dot)
            losses, grads = jax.lax.map(one, tokens)
            g = jax.tree.map(lambda x: x.mean(0), grads)
            m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
            v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
            bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
            p = jax.tree.map(
                lambda x, a, b: (x - gamma * ((a / bc1) / (jnp.sqrt(b / bc2) + eps)
                                              + wd * x)).astype(pdt),
                pf, m, v)
            return p, m, v, losses.mean()

        @jax.jit
        def global_step(x0, mom, *workers):
            pdt = jax.tree.leaves(x0)[0].dtype
            x_tau = jax.tree.map(
                lambda *ws: (sum(w.astype(F32) for w in ws) / len(ws)).astype(pdt),
                *workers)
            delta = jax.tree.map(lambda a, b: (a.astype(F32) - b.astype(F32)) / gamma,
                                 x0, x_tau)
            u = jax.tree.map(lambda a, b: ds["beta1"] * a + (1 - ds["beta1"]) * b,
                             mom, delta)
            new_x0 = jax.tree.map(
                lambda x, uu: (x.astype(F32) - ds["global_lr"] * gamma
                               * (jnp.sign(uu) + ds["weight_decay"] * x.astype(F32))
                               ).astype(pdt), x0, u)
            new_m = jax.tree.map(lambda a, b: ds["beta2"] * a + (1 - ds["beta2"]) * b,
                                 mom, delta)
            return new_x0, new_m, delta

        @jax.jit
        def change_norms(x, x_start):
            return leaf_norms(jax.tree.map(
                lambda a, b: a.astype(F32) - b.astype(F32), x, x_start))

        dev0 = devices[0]
        x0 = jax.device_put(init_fn(), dev0)
        mom = jax.tree.map(lambda x: jnp.zeros(x.shape, F32), x0)
        wdev = [devices[i % len(devices)] for i in range(n_workers)]
        # every worker's state in buffers of its own: local_step donates them
        def zeros(d):
            return jax.tree.map(lambda x: jnp.zeros(x.shape, F32, device=d), x0)

        ms = [zeros(d) for d in wdev]
        vs = [zeros(d) for d in wdev]
        out = Readings([], [], [])
        count = 0
        for t, batch in enumerate(batches):
            ps = [jax.device_put(x0, d, may_alias=False) for d in wdev]
            step_losses = np.zeros((tau, n_workers))
            pending = []
            for k in range(tau):
                count += 1
                for i in range(n_workers):
                    tok = jax.device_put(jnp.asarray(batch[i, k]), wdev[i])
                    ps[i], ms[i], vs[i], lo = local_step(
                        ps[i], ms[i], vs[i], jnp.asarray(count, F32), tok)
                    pending.append((k, i, lo))
            for k, i, lo in pending:
                step_losses[k, i] = float(lo)
            ps = [jax.device_put(p, dev0) for p in ps]
            x0, mom, delta = global_step(x0, mom, *ps)
            del ps
            out.loss.append(float(step_losses.mean()))
            if t == 0:
                out.delta0 = [float(x) for x in jax.device_get(leaf_norms(delta))]
                out.delta0_leaves = jax.device_get(jax.tree.leaves(delta))
            del delta
        del ms, vs
        # the starting weights are made again rather than kept alongside
        out.change = [float(x) for x in change_norms(
            x0, jax.device_put(init_fn(), dev0))]
        return out
