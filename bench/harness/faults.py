"""Faults planted in the timed path, and the lower-precision control.

Each fault builds a broken outer step from the program's own; the check
has to come out as not correct under each:

  * ``unchanged``: the step returns its state unchanged (its loss is real);
  * ``half_batch``: half of each microbatch's rows are left out, the mean
    taken over the rest;
  * ``no_exchange``: the worker mean is left out: x_tau is worker 0's own
    iterate (through the program's survivor mask), so no other worker's, or
    on four chips no other chip's, contribution reaches the update.

The control is the reference put in the program's place with every matrix
product in float8, the nearest precision below the configuration's
bfloat16, as float8 training computes it: the forward operands rounded to
e4m3 and the cotangent that reaches the product in the backward pass to
e5m2, each with a per-tensor scale, so both of the backward products that
make the operands' gradients take float8 operands too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def _round(x, dtype, fmax):
    """``x`` rounded to the float8 ``dtype`` with a per-tensor scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)) / fmax, 1e-30)
    return (x / scale).astype(dtype).astype(x.dtype) * scale


@jax.custom_vjp
def e4m3(x):
    """A forward operand in float8 e4m3; its gradient passes straight
    through (the product's backward already took float8 operands)."""
    return _round(x, jnp.float8_e4m3fn, E4M3_MAX)


e4m3.defvjp(lambda x: (e4m3(x), None), lambda _, g: (g,))


@jax.custom_vjp
def e5m2_cotangent(y):
    """The identity forward; the cotangent of ``y`` rounded to e5m2."""
    return y


e5m2_cotangent.defvjp(lambda y: (y, None),
                      lambda _, g: (_round(g, jnp.float8_e5m2, E5M2_MAX),))


def fp8_dot(spec: str, a, b):
    """``jnp.einsum(spec, a, b)`` computed in float8, forward and backward."""
    return e5m2_cotangent(jnp.einsum(spec, e4m3(a), e4m3(b)))


def unchanged(cell):
    def step(state, batch):
        _, metrics = cell.train_step(state, batch)
        return state, metrics

    return jax.jit(step, donate_argnums=(0,))


def half_batch(cell):
    half = cell.mix["b_micro"] // 2

    def step(state, batch):
        return cell.train_step(state, {"tokens": batch["tokens"][..., :half, :]})

    return jax.jit(step, donate_argnums=(0,))


def no_exchange(cell):
    from repro.robustness.faults import FaultRound

    w = cell.mix["n_workers"]
    only_first = jnp.arange(w) == 0
    fr = FaultRound(survivors=only_first, stale=jnp.zeros(w, bool),
                    corrupt=jnp.zeros(w, bool))

    def step(state, batch):
        return cell.stepper(state, batch, None, fr)

    return jax.jit(step, donate_argnums=(0,))


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange}
