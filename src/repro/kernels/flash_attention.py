"""Fused causal flash attention (Pallas, TPU): forward and backward.

Why a kernel: the einsum path writes each layer's f32 (S, S) scores to HBM,
reads them back for the softmax and the value product, and computes the
masked upper half too.  This kernel keeps score tiles in VMEM, skips the
tiles above the diagonal, masks only the diagonal ones, and saves the
output and the per-row log-sum-exp for the backward.

Arithmetic: scores and the online softmax in f32; the (unnormalised)
probabilities are rounded to the value dtype for the value product, which
accumulates in f32; the output is divided by the softmax sum once, at the
end.  The backward recomputes probabilities from the log-sum-exp in one
fused kernel that accumulates dq, dk and dv in f32 VMEM scratch.

Layout: the model's own (B, S, H, hd), read as (B, S, H * hd) with no
transpose.  A grid step takes a group of heads whose lanes fill whole
128-lane tiles (two heads of 64, or one of a 128 multiple) and slices
each head's lanes inside the kernel.  Square tiles, from the shapes.
The forward puts query rows on sublanes; the backward runs k-major with
key rows on sublanes, so the log-sum-exp (saved lane-dense, (1, S) per
head) and ``sum(o * do)`` broadcast as rows and dk, dv need no transpose.

Started from ``jax.experimental.pallas.ops.tpu.flash_attention``: causal
only, no bias or segment ids, compact residuals, the model's layout and a
fused backward.  Validated on the CPU in TPU interpret mode against the
einsum path.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LANES = 128
# A finite mask value: exp(MASK - m) underflows to 0 and never gives NaN.
MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
NT = (((1,), (1,)), ((), ()))   # a @ b.T
TN = (((0,), (0,)), ((), ()))   # a.T @ b
# Names of the forward's residuals that the backward reads and cannot get
# from q, k and v alone: the output and the log-sum-exp.  A layer
# checkpoint that saves them (``save_only_these_names(*RESIDUALS)``) keeps
# the backward from running the forward kernel again.
RESIDUALS = ("flash_attention_out", "flash_attention_lse")
# Largest sequence x head-group lanes whose f32 dq accumulator the backward
# keeps in VMEM: 4 MiB.
MAX_SEQ_X_LANES = 1 << 20


def block_size(seq: int) -> int:
    """The backward's q and k tile edge: the largest of 512, 256, 128
    dividing seq."""
    return next(b for b in (512, 256, 128) if seq % b == 0)


def forward_block_size(seq: int) -> int:
    """The forward's tile edge: a sequence of up to 1024 in one tile (on a
    v5e at S 1024 and two heads of 64 a step, 0.84 ms a call against 1.12
    with tiles of 512: fewer grid steps outweigh the masked half), longer
    ones in ``block_size`` tiles."""
    return seq if seq <= 1024 else block_size(seq)


def heads_per_step(n_heads: int, hd: int) -> Optional[int]:
    """The fewest heads whose lanes fill whole 128-lane tiles: 2 heads of
    64 (halving the grid steps' fixed cost too), 1 of a 128 multiple."""
    return next((g for g in (1, 2) if g * hd % LANES == 0 and n_heads % g == 0),
                None)


def supported(seq: int, n_heads: int, hd: int) -> bool:
    """Whether the kernel takes these shapes: a 128-multiple sequence, a
    head group that fills lane tiles, and a dq accumulator within VMEM."""
    g = heads_per_step(n_heads, hd)
    return seq % LANES == 0 and g is not None and seq * g * hd <= MAX_SEQ_X_LANES


def _lanes(x, n):
    """A lane-replicated (r, 128) tile as (r, n)."""
    return x[:, :n] if n <= LANES else jnp.tile(x, (1, n // LANES))


def _causal(scores, *, k_rows):
    """Mask the upper triangle of a diagonal tile: key after query.
    ``k_rows``: rows are key positions (backward), else query positions."""
    rows = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    keep = rows <= cols if k_rows else cols <= rows
    return jnp.where(keep, scores, MASK)


def _each_tile(tile, heads, hd, off_diagonal, diagonal):
    """Run ``tile(h, lanes, masked)`` for each head of the group, ``lanes``
    its slice of the packed head dim: unmasked below the diagonal, masked
    on it; tiles above it do nothing."""
    for masked, when in ((False, off_diagonal), (True, diagonal)):
        @pl.when(when)
        def _():
            for h in range(heads):
                tile(h, slice(h * hd, (h + 1) * hd), masked)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, hd):
    i, j = pl.program_id(2), pl.program_id(3)   # q block, k block
    heads, b = lse_ref.shape[1], q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, F32)
        l_sc[...] = jnp.zeros(l_sc.shape, F32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, F32)

    def tile(h, lanes, masked):
        q, k, v = q_ref[0, :, lanes], k_ref[0, :, lanes], v_ref[0, :, lanes]
        s = jax.lax.dot_general(q, k, NT, preferred_element_type=F32) * scale
        if masked:
            s = _causal(s, k_rows=False)
        m_prev = m_sc[h]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, b))
        alpha = jnp.exp(m_prev - m_next)
        l_sc[h] = alpha * l_sc[h] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[h] = m_next
        pv = jax.lax.dot(p.astype(v.dtype), v, preferred_element_type=F32)
        acc_sc[:, lanes] = acc_sc[:, lanes] * _lanes(alpha, hd) + pv

    _each_tile(tile, heads, hd, j < i, j == i)

    @pl.when(j == i)
    def _store():
        for h in range(heads):
            lanes = slice(h * hd, (h + 1) * hd)
            l = l_sc[h]
            o_ref[0, :, lanes] = (acc_sc[:, lanes] / _lanes(l, hd)).astype(
                o_ref.dtype)
            lse = m_sc[h] + jnp.log(l)                 # (b, 128), lanes equal
            lse_ref[0, h] = jnp.transpose(lse)[:1]     # (1, b), lane-dense


def _fwd(q, k, v, *, scale, hd):
    """Packed (B, S, H*hd) x3 -> out (B, S, H*hd), lse (B, H, 1, S) f32."""
    B, S, width = q.shape
    H, b = width // hd, forward_block_size(S)
    g = heads_per_step(H, hd)
    # k/v blocks above the diagonal are not fetched: min(j, i) repeats the
    # diagonal block's index.
    q_spec = pl.BlockSpec((1, b, g * hd), lambda n, h, i, j: (n, i, h))
    kv_spec = pl.BlockSpec((1, b, g * hd),
                           lambda n, h, i, j: (n, jnp.minimum(j, i), h))
    lse_spec = pl.BlockSpec((1, g, 1, b), lambda n, h, i, j: (n, h, 0, i))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, hd=hd),
        grid=(B, H // g, S // b, S // b),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, S), F32)],
        scratch_shapes=[pltpu.VMEM((g, b, LANES), F32),
                        pltpu.VMEM((g, b, LANES), F32),
                        pltpu.VMEM((b, g * hd), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward: one kernel for dq, dk and dv
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc, *, scale, hd):
    j, i = pl.program_id(2), pl.program_id(3)   # k block, q block
    last = pl.num_programs(3) - 1
    heads, b = lse_ref.shape[1], q_ref.shape[1]

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _init_dq():
        dq_sc[...] = jnp.zeros(dq_sc.shape, F32)

    @pl.when(i == 0)
    def _init_dkv():
        dk_sc[...] = jnp.zeros(dk_sc.shape, F32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, F32)

    def tile(h, lanes, masked):
        q, k = q_ref[0, :, lanes], k_ref[0, :, lanes]
        v, do = v_ref[0, :, lanes], do_ref[0, :, lanes]
        # sum(o * do) over the head dim, per query: a (1, b) row
        di = jnp.sum(o_ref[0, :, lanes].astype(F32) * do.astype(F32), axis=1,
                     keepdims=True)
        di = jnp.transpose(jnp.broadcast_to(di, (b, LANES)))[:1]
        # Key rows, query columns: the (1, b) rows broadcast over sublanes.
        s = jax.lax.dot_general(k, q, NT, preferred_element_type=F32) * scale
        if masked:
            s = _causal(s, k_rows=True)
        p = jnp.exp(s - lse_ref[0, h])                      # (b, b)
        dv_sc[:, lanes] += jax.lax.dot(p.astype(do.dtype), do,
                                       preferred_element_type=F32)
        dp = jax.lax.dot_general(v, do, NT, preferred_element_type=F32)
        ds = (p * (dp - di)).astype(q.dtype)                # unscaled
        dk_sc[:, lanes] += jax.lax.dot(ds, q, preferred_element_type=F32)
        rows = pl.ds(pl.multiple_of(i * b, b), b)
        dq_sc[rows, lanes] += jax.lax.dot_general(ds, k, TN,
                                                  preferred_element_type=F32)

    _each_tile(tile, heads, hd, i > j, i == j)

    @pl.when(i == last)
    def _store_dkv():
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(j == last, i == last))
    def _store_dq():
        dq_ref[0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _bwd(q, k, v, o, lse, do, *, scale, hd):
    B, S, width = q.shape
    H, b = width // hd, block_size(S)
    g = heads_per_step(H, hd)
    # q blocks above the diagonal are not fetched: max(i, j) repeats the
    # diagonal block's index.
    q_spec = pl.BlockSpec((1, b, g * hd),
                          lambda n, h, j, i: (n, jnp.maximum(i, j), h))
    lse_spec = pl.BlockSpec((1, g, 1, b),
                            lambda n, h, j, i: (n, h, 0, jnp.maximum(i, j)))
    kv_spec = pl.BlockSpec((1, b, g * hd), lambda n, h, j, i: (n, j, h))
    seq_spec = pl.BlockSpec((1, S, g * hd), lambda n, h, j, i: (n, 0, h))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, hd=hd),
        grid=(B, H // g, S // b, S // b),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, lse_spec],
        out_specs=[seq_spec, kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((S, g * hd), F32),
                        pltpu.VMEM((b, g * hd), F32),
                        pltpu.VMEM((b, g * hd), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
    )(q, k, v, o, do, lse)


# ---------------------------------------------------------------------------
# Differentiable entry points
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attend(q, k, v, scale, hd):
    return _fwd(q, k, v, scale=scale, hd=hd)[0]


def _attend_fwd(q, k, v, scale, hd):
    o, lse = _fwd(q, k, v, scale=scale, hd=hd)
    o, lse = (checkpoint_name(x, name) for x, name in zip((o, lse), RESIDUALS))
    return o, (q, k, v, o, lse)


def _attend_bwd(scale, hd, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, scale=scale, hd=hd)


_attend.defvjp(_attend_fwd, _attend_bwd)


@jax.jit
def flash_attention(q, k, v):
    """Causal softmax attention, q/k/v: (B, S, H, hd) -> (B, S, H, hd).

    Requires ``supported(S, H, hd)``.
    """
    B, S, H, hd = q.shape
    packed = lambda x: x.reshape(B, S, H * hd)
    out = _attend(packed(q), packed(k), packed(v), 1.0 / math.sqrt(hd), hd)
    return out.reshape(B, S, H, hd)
