"""Pure-jnp oracles for the Pallas kernels (the allclose targets).

These mirror, op for op in f32 math, what the fused kernels compute:
  * dsm_update  — the paper's global sign-momentum step (eqs. 6-8)
  * adamw_update — one fused AdamW local step (Alg. 2)

Each oracle is jitted, so XLA fuses its elementwise chain as it fuses the
kernel body.  Run op by op, the CPU rounds every product before the add;
fused, it contracts ``a * b + c`` into one FMA.  The two differ by an f32
ulp here and there, and now and then the bf16 cast of ``x_new`` then
lands one bf16 ulp apart, past the kernel tests' tolerance.  Jitted, the
DSM oracle agrees with the interpret-mode kernel bit for bit on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


@functools.partial(jax.jit, static_argnames=("eta", "beta1", "beta2", "lam"))
def dsm_update_ref(x0, m, x_tau, gamma, *, eta, beta1, beta2, lam):
    """Returns (x_new, m_new). Shapes alike; x dtype preserved, m stays f32."""
    x0f = x0.astype(F32)
    mf = m.astype(F32)
    delta = (x0f - x_tau.astype(F32)) / gamma
    u = beta1 * mf + (1.0 - beta1) * delta
    x_new = x0f - eta * gamma * (jnp.sign(u) + lam * x0f)
    m_new = beta2 * mf + (1.0 - beta2) * delta
    return x_new.astype(x0.dtype), m_new.astype(m.dtype)


@functools.partial(jax.jit, static_argnames=("beta1", "beta2", "eps", "wd"))
def adamw_update_ref(p, g, m, v, gamma, step, *, beta1, beta2, eps, wd):
    """One AdamW step. step is 0-indexed; bias correction uses step+1."""
    pf, gf = p.astype(F32), g.astype(F32)
    m_new = beta1 * m.astype(F32) + (1.0 - beta1) * gf
    v_new = beta2 * v.astype(F32) + (1.0 - beta2) * gf * gf
    c = (step + 1.0).astype(F32)
    mhat = m_new / (1.0 - beta1 ** c)
    vhat = v_new / (1.0 - beta2 ** c)
    p_new = pf - gamma * (mhat / (jnp.sqrt(vhat) + eps) + wd * pf)
    return p_new.astype(p.dtype), m_new.astype(m.dtype), v_new.astype(v.dtype)
