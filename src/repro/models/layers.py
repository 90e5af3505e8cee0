"""Model building blocks: norms, RoPE, GQA attention (full / sliding-window /
decode), MLP, MoE (ragged_dot grouped matmul), Mamba-2 SSD, RG-LRU.

Everything is pure-functional: ``init_*(key, cfg) -> params`` and
``*_apply(params, x, ...) -> y``.  Activations run in ``cfg.act_dtype``
(bf16 on TPU), matmuls accumulate in f32 via ``preferred_element_type``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention

PyTree = Any
F32 = jnp.float32


def _init_dense(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True)}[name]


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> PyTree:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: PyTree, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(F32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"].astype(F32))
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: (S,) or (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., :, None].astype(F32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]              # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA) — training/prefill path with blockwise-causal computation
# ---------------------------------------------------------------------------

def init_attention(key, cfg, cross: bool = False) -> PyTree:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init_dense(ks[0], (d, h * hd), cfg.p_dtype),
        "wk": _init_dense(ks[1], (d, kvh * hd), cfg.p_dtype),
        "wv": _init_dense(ks[2], (d, kvh * hd), cfg.p_dtype),
        "wo": _init_dense(ks[3], (h * hd, d), cfg.p_dtype, scale=1.0 / math.sqrt(h * hd)),
    }
    return p


def _gqa_scores(q, k):
    """q: (B,Sq,H,hd), k: (B,Sk,KVH,hd) -> (B,KVH,rep,Sq,Sk) f32."""
    B, Sq, H, hd = q.shape
    KVH = k.shape[2]
    rep = H // KVH
    qg = q.reshape(B, Sq, KVH, rep, hd)
    return jnp.einsum(
        "bqgrh,bkgh->bgrqk", qg, k, preferred_element_type=F32
    ) / math.sqrt(hd)


def _gqa_out(probs, v, out_dtype):
    """probs: (B,KVH,rep,Sq,Sk), v: (B,Sk,KVH,hd) -> (B,Sq,H,hd)."""
    B, KVH, rep, Sq, Sk = probs.shape
    out = jnp.einsum(
        "bgrqk,bkgh->bqgrh", probs.astype(v.dtype), v,
        preferred_element_type=v.dtype,
    )
    return out.reshape(B, Sq, KVH * rep, v.shape[-1]).astype(out_dtype)


def fused_attention_applies(q, k, window: Optional[int] = None,
                            seq_sharded: bool = False) -> bool:
    """Whether ``causal_attention`` takes the fused flash-attention kernel:
    on TPU, full causal multi-head attention (as many k/v heads as query
    heads) at shapes the kernel supports, with the sequence not sharded by
    GSPMD (which cannot partition a Pallas call)."""
    _, S, H, hd = q.shape
    return (jax.default_backend() == "tpu" and window is None
            and not seq_sharded and k.shape[2] == H
            and flash_attention.supported(S, H, hd))


def causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    window: Optional[int] = None,
    q_block: int = 1024,
    seq_sharded: bool = False,
) -> jnp.ndarray:
    """Causal (optionally sliding-window) attention.

    Where ``fused_attention_applies``, one fused Pallas kernel that keeps
    score tiles in VMEM (``kernels/flash_attention.py``).  Otherwise an
    unrolled static loop over query tiles; each tile attends only to the
    (block-aligned) keys it can see, so FLOPs match causal/windowed exactly
    (up to one diagonal tile) and the score buffer stays O(q_block * Sk_vis).
    """
    if fused_attention_applies(q, k, window, seq_sharded):
        return flash_attention.flash_attention(q, k, v)
    B, S, H, hd = q.shape
    qb = min(q_block, S)
    n_blocks = -(-S // qb)
    outs = []
    for i in range(n_blocks):
        q_start, q_end = i * qb, min((i + 1) * qb, S)
        qi = q[:, q_start:q_end]
        k_start = 0 if window is None else max(0, (q_start - window) // qb * qb)
        ki = k[:, k_start:q_end]
        vi = v[:, k_start:q_end]
        scores = _gqa_scores(qi, ki)                      # (B,g,r,sq,sk)
        q_pos = jnp.arange(q_start, q_end)[:, None]
        k_pos = jnp.arange(k_start, q_end)[None, :]
        mask = k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(_gqa_out(probs, vi, q.dtype))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def full_attention(q, k, v, mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Bidirectional (encoder / cross) attention, direct."""
    scores = _gqa_scores(q, k)
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(probs, v, q.dtype)


def decode_attention(q, k_cache, v_cache, valid_mask) -> jnp.ndarray:
    """One-token query vs a KV cache.

    q: (B,1,H,hd); caches: (B,S,KVH,hd); valid_mask: (S,) or (B,S) bool.
    """
    scores = _gqa_scores(q, k_cache)                      # (B,g,r,1,S)
    if valid_mask.ndim == 1:
        m = valid_mask[None, None, None, None, :]
    else:
        m = valid_mask[:, None, None, None, :]
    scores = jnp.where(m, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(probs, v_cache, q.dtype)


def attn_qkv(p: PyTree, x: jnp.ndarray, positions, cfg) -> tuple:
    B = x.shape[0]
    S = x.shape[1]
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, cfg.n_heads, cfg.hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_proj_out(p: PyTree, out: jnp.ndarray) -> jnp.ndarray:
    B, S, H, hd = out.shape
    return out.reshape(B, S, H * hd) @ p["wo"].astype(out.dtype)


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU or plain)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, d_ff: Optional[int] = None) -> PyTree:
    d, dff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w1": _init_dense(ks[0], (d, dff), cfg.p_dtype),
        "w2": _init_dense(ks[1], (dff, d), cfg.p_dtype),
    }
    if cfg.mlp_gated:
        p["w3"] = _init_dense(ks[2], (d, dff), cfg.p_dtype)
    return p


def mlp_apply(p: PyTree, x: jnp.ndarray, cfg) -> jnp.ndarray:
    a = act_fn(cfg.act)
    h = a(x @ p["w1"].astype(x.dtype))
    if "w3" in p:
        h = h * (x @ p["w3"].astype(x.dtype))
    return h @ p["w2"].astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE: top-k routing + ragged_dot grouped matmul (FLOPs-exact for active
# experts; expert weights are tensor-parallel over the model axis, see
# DESIGN.md §5 — no all-to-all, the d_ff dims shard like a dense MLP).
# ---------------------------------------------------------------------------

def init_moe(key, cfg) -> PyTree:
    d, dff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": _init_dense(ks[0], (d, E), F32, scale=0.02),
        "we1": _init_dense(ks[1], (E, d, dff), cfg.p_dtype),
        "we2": _init_dense(ks[2], (E, dff, d), cfg.p_dtype),
    }
    if cfg.mlp_gated:
        p["we3"] = _init_dense(ks[3], (E, d, dff), cfg.p_dtype)
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=cfg.d_ff * cfg.n_shared_experts)
    return p


def moe_apply(p: PyTree, x: jnp.ndarray, cfg) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out, aux_loss). x: (B,S,d)."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    xt = x.reshape(B * S, d)
    T = B * S

    logits = (xt.astype(F32) @ p["router"])               # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)       # (T,K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style)
    density = jnp.mean(
        jax.nn.one_hot(expert_idx[:, 0], E, dtype=F32), axis=0
    )
    mean_probs = probs.mean(axis=0)
    aux_loss = E * jnp.sum(density * mean_probs)

    if getattr(cfg, "moe_impl", "ragged") == "dense":
        # masked dense: every expert computes every token; gates zero out the
        # inactive ones.  FLOPs are E/K x the active count, but every matmul
        # is a clean MXU-aligned TP einsum with ONE (T,d) reduce at the end —
        # the right trade for sub-1k d_ff experts (see EXPERIMENTS.md SPerf).
        gates_dense = jnp.zeros((T, E), dtype=xt.dtype)
        gates_dense = gates_dense.at[
            jnp.arange(T)[:, None], expert_idx
        ].set(gate_vals.astype(xt.dtype))
        a = act_fn(cfg.act)
        h = a(jnp.einsum("td,edf->tef", xt, p["we1"].astype(xt.dtype)))
        if "we3" in p:
            h = h * jnp.einsum("td,edf->tef", xt, p["we3"].astype(xt.dtype))
        out = jnp.einsum("tef,efd,te->td", h, p["we2"].astype(xt.dtype), gates_dense)
        if "shared" in p:
            out = out + mlp_apply(p["shared"], xt, cfg)
        return out.reshape(B, S, d), aux_loss

    # sort token-expert assignments by expert
    flat_expert = expert_idx.reshape(T * K)
    sort_idx = jnp.argsort(flat_expert)                   # (TK,)
    token_of = sort_idx // K
    xs = xt[token_of]                                     # (TK, d)
    group_sizes = jnp.bincount(flat_expert, length=E).astype(jnp.int32)

    a = act_fn(cfg.act)
    h = a(jax.lax.ragged_dot(xs, p["we1"].astype(xs.dtype), group_sizes))
    if "we3" in p:
        h = h * jax.lax.ragged_dot(xs, p["we3"].astype(xs.dtype), group_sizes)
    y = jax.lax.ragged_dot(h, p["we2"].astype(xs.dtype), group_sizes)  # (TK, d)

    if getattr(cfg, "moe_combine", "scatter") == "ksum":
        # combine-before-reduce: unsort to (T, K, d) and contract K with the
        # gates BEFORE any cross-shard reduction becomes necessary — shrinks
        # the row-parallel all-reduce from TK rows to T rows (8x for top-8).
        inv = jnp.argsort(sort_idx)
        y_tk = y[inv].reshape(T, K, d)
        out = jnp.einsum("tkd,tk->td", y_tk, gate_vals.astype(y.dtype))
    else:
        w = gate_vals.reshape(T * K)[sort_idx].astype(y.dtype)
        out = jnp.zeros_like(xt).at[token_of].add(y * w[:, None])

    if "shared" in p:
        out = out + mlp_apply(p["shared"], xt, cfg)
    return out.reshape(B, S, d), aux_loss


# ---------------------------------------------------------------------------
# Depthwise causal conv (Mamba-2 / RG-LRU front conv)
# ---------------------------------------------------------------------------

def init_conv1d(key, channels: int, width: int, dtype) -> PyTree:
    return {
        "w": _init_dense(key, (width, channels), dtype, scale=1.0 / math.sqrt(width)),
        "b": jnp.zeros((channels,), dtype),
    }


def conv1d_apply(p: PyTree, x: jnp.ndarray) -> jnp.ndarray:
    """Causal depthwise conv. x: (B,S,C)."""
    width = p["w"].shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(
        xp[:, i : i + x.shape[1]] * p["w"][i].astype(x.dtype) for i in range(width)
    )
    return out + p["b"].astype(x.dtype)


def conv1d_step(p: PyTree, conv_state: jnp.ndarray, x_t: jnp.ndarray):
    """Decode: conv_state (B,width-1,C), x_t (B,C) -> (y_t, new_state)."""
    window = jnp.concatenate([conv_state, x_t[:, None]], axis=1)  # (B,width,C)
    y = jnp.einsum("bwc,wc->bc", window.astype(F32), p["w"].astype(F32))
    y = (y + p["b"].astype(F32)).astype(x_t.dtype)
    return y, window[:, 1:]


# ---------------------------------------------------------------------------
# Mamba-2 (SSD — state-space duality, chunked)  [arXiv:2405.21060]
# ---------------------------------------------------------------------------

def init_mamba2(key, cfg) -> PyTree:
    d, di = cfg.d_model, cfg.d_inner
    N, H = cfg.ssm_state, cfg.ssm_heads
    ks = jax.random.split(key, 5)
    conv_ch = di + 2 * N  # conv over (x, B, C) streams
    return {
        # in_proj -> [z (di), x (di), B (N), C (N), dt (H)]
        "in_proj": _init_dense(ks[0], (d, 2 * di + 2 * N + H), cfg.p_dtype),
        "conv": init_conv1d(ks[1], conv_ch, cfg.conv_width, cfg.p_dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=F32)),
        "D": jnp.ones((H,), F32),
        "dt_bias": jnp.zeros((H,), F32),
        "norm": init_rmsnorm(di, cfg.p_dtype),
        "out_proj": _init_dense(ks[2], (di, d), cfg.p_dtype),
    }


def _segsum(x: jnp.ndarray) -> jnp.ndarray:
    """x: (..., L) -> (..., L, L) with out[i,j] = sum_{j<k<=i} x[k]; -inf above diag."""
    L = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int = 128):
    """Mamba-2 SSD scan, chunked (minimal version of paper Listing 1).

    x: (B,S,H,P) value heads; dt: (B,S,H) >0; A: (H,) >0 decay rate;
    Bm, Cm: (B,S,N) single-group input/output projections.
    Returns y: (B,S,H,P).
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    nc = S // chunk
    assert S % chunk == 0, "sequence length must be divisible by ssd chunk"

    dA = (-A[None, None, :] * dt).astype(F32)             # (B,S,H) log-decay (<0)
    xw = (x.astype(F32) * dt[..., None])                  # dt-weighted input

    # reshape into chunks
    c = lambda t: t.reshape(Bsz, nc, chunk, *t.shape[2:])
    dAc, xc = c(dA), c(xw)                                # (B,nc,Q,H), (B,nc,Q,H,P)
    Bc, Cc = c(Bm.astype(F32)), c(Cm.astype(F32))         # (B,nc,Q,N)

    dAc_h = jnp.moveaxis(dAc, -1, 2)                      # (B,nc,H,Q)
    A_cum = jnp.cumsum(dAc_h, axis=-1)                    # (B,nc,H,Q)

    # 1) intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(dAc_h))                           # (B,nc,H,Q,Q)
    Y_diag = jnp.einsum("bcqn,bckn,bchqk,bckhp->bcqhp", Cc, Bc, L, xc)

    # 2) chunk states
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)       # (B,nc,H,Q)
    states = jnp.einsum("bckn,bchk,bckhp->bchpn", Bc, decay_states, xc)

    # 3) inter-chunk recurrence over nc
    chunk_decay = jnp.exp(A_cum[..., -1])                 # (B,nc,H)

    def scan_fn(carry, inp):
        st, dec = inp
        new = carry * dec[..., None, None] + st
        return new, carry                                  # emit state *before* chunk

    init = jnp.zeros((Bsz, H, P, N), F32)
    _, prev_states = jax.lax.scan(
        scan_fn,
        init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)         # (B,nc,H,P,N)

    # 4) state -> output within chunk
    state_decay_out = jnp.exp(A_cum)                      # (B,nc,H,Q)
    Y_off = jnp.einsum("bcqn,bchpn,bchq->bcqhp", Cc, prev_states, state_decay_out)

    y = (Y_diag + Y_off).reshape(Bsz, S, H, P)
    return y


def mamba2_apply(p: PyTree, x: jnp.ndarray, cfg) -> jnp.ndarray:
    """Training/prefill path. x: (B,S,d) -> (B,S,d)."""
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    proj = x @ p["in_proj"].astype(x.dtype)
    z, xs, Bm, Cm, dt = jnp.split(proj, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], -1)

    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_out = jax.nn.silu(conv1d_apply(p["conv"], conv_in))
    xs, Bm, Cm = jnp.split(conv_out, [di, di + N], axis=-1)

    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])   # (B,S,H)
    A = jnp.exp(p["A_log"])                               # (H,) > 0
    xh = xs.reshape(*xs.shape[:2], H, P)
    y = ssd_chunked(xh, dt, A, Bm, Cm, chunk=min(128, xs.shape[1]))
    y = y + p["D"][None, None, :, None] * xh.astype(F32)
    y = y.reshape(*xs.shape[:2], di).astype(x.dtype)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z))
    return y @ p["out_proj"].astype(x.dtype)


def mamba2_decode(p: PyTree, cache: PyTree, x_t: jnp.ndarray, cfg):
    """One-token recurrent step. x_t: (B,d); cache: {state:(B,H,P,N), conv:(B,w-1,C)}."""
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    proj = x_t @ p["in_proj"].astype(x_t.dtype)
    z, xs, Bm, Cm, dt = jnp.split(proj, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], -1)

    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_y, new_conv = conv1d_step(p["conv"], cache["conv"], conv_in)
    conv_y = jax.nn.silu(conv_y)
    xs, Bm, Cm = jnp.split(conv_y, [di, di + N], axis=-1)

    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])   # (B,H)
    A = jnp.exp(p["A_log"])
    dA = jnp.exp(-A[None] * dt)                           # (B,H)
    xh = xs.reshape(-1, H, P).astype(F32)
    dBx = jnp.einsum("bh,bn,bhp->bhpn", dt, Bm.astype(F32), xh)
    new_state = cache["state"] * dA[..., None, None] + dBx
    y = jnp.einsum("bhpn,bn->bhp", new_state, Cm.astype(F32))
    y = y + p["D"][None, :, None] * xh
    y = y.reshape(-1, di).astype(x_t.dtype)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z))
    out = y @ p["out_proj"].astype(x_t.dtype)
    return out, {"state": new_state, "conv": new_conv}


def mamba2_init_cache(cfg, batch: int, dtype) -> PyTree:
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    conv_ch = di + 2 * N
    return {
        "state": jnp.zeros((batch, H, P, N), F32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_ch), dtype),
    }


# ---------------------------------------------------------------------------
# RG-LRU recurrent block (RecurrentGemma / Griffin)  [arXiv:2402.19427]
# ---------------------------------------------------------------------------

def init_rglru(key, cfg) -> PyTree:
    d, dr = cfg.d_model, cfg.d_rnn
    ks = jax.random.split(key, 6)
    return {
        "in_x": _init_dense(ks[0], (d, dr), cfg.p_dtype),
        "in_gate": _init_dense(ks[1], (d, dr), cfg.p_dtype),
        "conv": init_conv1d(ks[2], dr, cfg.conv_width, cfg.p_dtype),
        "w_a": _init_dense(ks[3], (dr, dr), cfg.p_dtype),   # recurrence gate
        "w_x": _init_dense(ks[4], (dr, dr), cfg.p_dtype),   # input gate
        "lam": jnp.full((dr,), 2.2, F32),  # softplus-param: a ~ sigmoid-ish decay
        "out": _init_dense(ks[5], (dr, d), cfg.p_dtype),
    }


_RGLRU_C = 8.0


def _rglru_coeffs(p, xc):
    """xc: (..., dr) conv output. Returns (a, b) of h = a*h_prev + b, f32."""
    r = jax.nn.sigmoid((xc @ p["w_a"].astype(xc.dtype)).astype(F32))
    i = jax.nn.sigmoid((xc @ p["w_x"].astype(xc.dtype)).astype(F32))
    log_a = -_RGLRU_C * r * jax.nn.softplus(p["lam"])      # <= 0
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6)) * (i * xc.astype(F32))
    return a, b


def rglru_apply(p: PyTree, x: jnp.ndarray, cfg) -> jnp.ndarray:
    """Training/prefill: associative linear scan over S. x: (B,S,d)."""
    gate = jax.nn.gelu((x @ p["in_gate"].astype(x.dtype)).astype(F32), approximate=True)
    xr = x @ p["in_x"].astype(x.dtype)
    xc = conv1d_apply(p["conv"], xr)
    a, b = _rglru_coeffs(p, xc)                            # (B,S,dr)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, br + ar * bl

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    y = (h * gate).astype(x.dtype)
    return y @ p["out"].astype(x.dtype)


def rglru_decode(p: PyTree, cache: PyTree, x_t: jnp.ndarray, cfg):
    """x_t: (B,d); cache: {h:(B,dr) f32, conv:(B,w-1,dr)}."""
    gate = jax.nn.gelu((x_t @ p["in_gate"].astype(x_t.dtype)).astype(F32), approximate=True)
    xr = x_t @ p["in_x"].astype(x_t.dtype)
    xc, new_conv = conv1d_step(p["conv"], cache["conv"], xr)
    a, b = _rglru_coeffs(p, xc)                            # (B,dr)
    new_h = a * cache["h"] + b
    y = (new_h * gate).astype(x_t.dtype)
    return y @ p["out"].astype(x_t.dtype), {"h": new_h, "conv": new_conv}


def rglru_init_cache(cfg, batch: int, dtype) -> PyTree:
    return {
        "h": jnp.zeros((batch, cfg.d_rnn), F32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, cfg.d_rnn), dtype),
    }
