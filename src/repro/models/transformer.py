"""Unified sequence model: decoder-only LM (dense / MoE / sliding-window /
SSM / RG-LRU mixtures), encoder-decoder (whisper backbone), and VLM
(llava backbone) — all driven by ``ModelConfig.pattern``.

Layer stacking: full repeats of the pattern are *scanned* (params stacked on
a leading block axis — keeps HLO size O(pattern) instead of O(n_layers));
the remainder layers are unrolled.

Public API (used by the trainer and serve):
  init_params(key, cfg)                       -> params
  loss_fn(params, batch, cfg)                 -> scalar loss
  prefill(params, batch, cfg)                 -> (last_logits, cache)
  decode_step(params, cache, tokens, pos, cfg)-> (logits, cache)
  init_cache(cfg, batch, max_len, dtype)      -> cache
"""

from __future__ import annotations

import contextlib
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention
from repro.layout import STACK_KEY
from repro.models import layers as L

PyTree = Any
F32 = jnp.float32
MOE_AUX_COEF = 0.01
CE_CHUNK = 2048
# What remat keeps of a layer: the flash kernel's output and log-sum-exp,
# so the backward does not run the kernel's forward again.  Layers that do
# not take the kernel name nothing and are recomputed whole.
SAVE_KERNEL_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *flash_attention.RESIDUALS)


def _parse_kind(kind: str) -> tuple[str, str]:
    mixer, _, ffn = kind.partition(":")
    return mixer, ffn or "dense"


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, kind: str, cfg) -> PyTree:
    mixer, ffn = _parse_kind(kind)
    ks = jax.random.split(key, 4)
    p: dict = {"ln1": L.init_rmsnorm(cfg.d_model, cfg.p_dtype)}
    if mixer in ("attn", "swa", "encattn"):
        p["attn"] = L.init_attention(ks[0], cfg)
    elif mixer == "xattn":
        p["attn"] = L.init_attention(ks[0], cfg)
        p["xattn"] = L.init_attention(ks[3], cfg)
        p["lnx"] = L.init_rmsnorm(cfg.d_model, cfg.p_dtype)
    elif mixer == "ssm":
        p["ssm"] = L.init_mamba2(ks[0], cfg)
    elif mixer == "rglru":
        p["rglru"] = L.init_rglru(ks[0], cfg)
    else:
        raise ValueError(f"unknown mixer {mixer!r}")
    if ffn == "dense":
        p["ln2"] = L.init_rmsnorm(cfg.d_model, cfg.p_dtype)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    elif ffn == "moe":
        p["ln2"] = L.init_rmsnorm(cfg.d_model, cfg.p_dtype)
        p["moe"] = L.init_moe(ks[2], cfg)
    elif ffn != "none":
        raise ValueError(f"unknown ffn {ffn!r}")
    return p


def _init_stack(key, pattern, n_blocks, n_rem, cfg) -> PyTree:
    """Stacked params for scanned repeats + unrolled remainder."""
    kb, kr = jax.random.split(key)
    blocks = {}
    for j, kind in enumerate(pattern):
        keys = jax.random.split(jax.random.fold_in(kb, j), max(n_blocks, 1))
        if n_blocks > 0:
            stacked = jax.vmap(lambda k: _init_block(k, kind, cfg))(keys)
            blocks[f"p{j}"] = stacked
    rem = tuple(
        _init_block(jax.random.fold_in(kr, i), pattern[i], cfg) for i in range(n_rem)
    )
    return {STACK_KEY: blocks, "rem": rem}


def init_params(key, cfg) -> PyTree:
    ks = jax.random.split(key, 6)
    params: dict = {
        "embed": L._init_dense(ks[0], (cfg.padded_vocab, cfg.d_model), cfg.p_dtype, scale=0.02),
        "final_norm": L.init_rmsnorm(cfg.d_model, cfg.p_dtype),
        "decoder": _init_stack(ks[1], cfg.pattern, cfg.n_scan_blocks, cfg.n_rem_layers, cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L._init_dense(
            ks[2], (cfg.d_model, cfg.padded_vocab), cfg.p_dtype, scale=0.02)
    if cfg.family == "encdec":
        enc_pattern = ("encattn:dense",)
        params["encoder"] = _init_stack(ks[3], enc_pattern, cfg.enc_layers, 0, cfg)
        params["enc_norm"] = L.init_rmsnorm(cfg.d_model, cfg.p_dtype)
        # frontend STUB: input_specs provides frame embeddings already at d_model
    if cfg.family == "vlm":
        params["patch_proj"] = L._init_dense(ks[4], (cfg.d_model, cfg.d_model), cfg.p_dtype)
    return params


# ---------------------------------------------------------------------------
# Forward (training / prefill) block application
# ---------------------------------------------------------------------------

def _apply_block(p, kind, x, positions, cfg, enc_out=None, collect_cache=False):
    """Returns (x, aux_loss, cache_entry_or_None)."""
    mixer, ffn = _parse_kind(kind)
    aux = jnp.zeros((), F32)
    cache_entry = None

    # Named scopes reach the compiled HLO's op_name, so a profile can tell
    # the attention sublayer and the MLP apart (docs/observability.md).
    attn_scope = (jax.named_scope("attention") if mixer in ("attn", "swa")
                  else contextlib.nullcontext())
    with attn_scope:
        h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        if mixer in ("attn", "swa"):
            if cfg.attn_seq_shard:
                from jax.sharding import PartitionSpec as _P

                h = jax.lax.with_sharding_constraint(h, _P(None, "model", None))
            q, k, v = L.attn_qkv(p["attn"], h, positions, cfg)
            window = cfg.window if mixer == "swa" else None
            out = L.causal_attention(q, k, v, window=window, q_block=cfg.q_block,
                                     seq_sharded=cfg.attn_seq_shard)
            if cfg.attn_seq_shard:
                from jax.sharding import PartitionSpec as _P

                out = jax.lax.with_sharding_constraint(
                    out, _P(None, "model", None, None))
            x = x + L.attn_proj_out(p["attn"], out)
            if collect_cache:
                if mixer == "swa":
                    w = min(cfg.window, k.shape[1])
                    cache_entry = {"k": k[:, -w:], "v": v[:, -w:]}
                else:
                    cache_entry = {"k": k, "v": v}
        elif mixer == "encattn":
            q, k, v = L.attn_qkv(p["attn"], h, positions, cfg)
            out = L.full_attention(q, k, v)
            x = x + L.attn_proj_out(p["attn"], out)
        elif mixer == "xattn":
            q, k, v = L.attn_qkv(p["attn"], h, positions, cfg)
            out = L.causal_attention(q, k, v, q_block=cfg.q_block)
            x = x + L.attn_proj_out(p["attn"], out)
            hx = L.rmsnorm(p["lnx"], x, cfg.norm_eps)
            B, Se, _ = enc_out.shape
            qx = (hx @ p["xattn"]["wq"].astype(hx.dtype)).reshape(
                B, hx.shape[1], cfg.n_heads, cfg.hd
            )
            kx = (enc_out @ p["xattn"]["wk"].astype(hx.dtype)).reshape(
                B, Se, cfg.n_kv_heads, cfg.hd)
            vx = (enc_out @ p["xattn"]["wv"].astype(hx.dtype)).reshape(
                B, Se, cfg.n_kv_heads, cfg.hd)
            out = L.full_attention(qx, kx, vx)
            x = x + L.attn_proj_out(p["xattn"], out)
            if collect_cache:
                cache_entry = {"k": k, "v": v, "kx": kx, "vx": vx}
        elif mixer == "ssm":
            out = L.mamba2_apply(p["ssm"], h, cfg)
            x = x + out
            if collect_cache:
                cache_entry = "ssm_final"  # filled by caller (needs final state)
        elif mixer == "rglru":
            out = L.rglru_apply(p["rglru"], h, cfg)
            x = x + out
            if collect_cache:
                cache_entry = "rglru_final"

    if ffn == "dense":
        with jax.named_scope("mlp"):
            x = x + L.mlp_apply(p["mlp"], L.rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
    elif ffn == "moe":
        out, moe_aux = L.moe_apply(p["moe"], L.rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
        x = x + out
        aux = aux + moe_aux
    if cfg.attn_seq_shard and x.ndim == 3:
        # Megatron-style sequence parallelism on the residual stream: the
        # row-parallel MLP output becomes a reduce-scatter (1x payload)
        # instead of an all-reduce (2x), and activations shard 16-way.
        from jax.sharding import PartitionSpec as _P

        x = jax.lax.with_sharding_constraint(x, _P(None, "model", None))
    return x, aux, cache_entry


def _run_stack(stack, pattern, x, positions, cfg, enc_out=None, remat=True,
               remat_policy="full"):
    """Scanned pattern repeats + unrolled remainder. Returns (x, aux_sum)."""

    def body(carry, block_params):
        x, aux = carry
        for j, kind in enumerate(pattern):
            if f"p{j}" not in block_params:
                continue
            x, a, _ = _apply_block(block_params[f"p{j}"], kind, x, positions, cfg, enc_out)
            aux = aux + a
        return (x, aux), None

    policies = jax.checkpoint_policies
    if remat and remat_policy == "dots":
        body_fn = jax.checkpoint(body, policy=policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable, SAVE_KERNEL_RESIDUALS))
    elif remat:
        body_fn = jax.checkpoint(body, policy=SAVE_KERNEL_RESIDUALS)
    else:
        body_fn = body
    aux0 = jnp.zeros((), F32)
    if stack[STACK_KEY]:
        (x, aux), _ = jax.lax.scan(body_fn, (x, aux0), stack[STACK_KEY])
    else:
        aux = aux0
    for i, p in enumerate(stack["rem"]):
        x, a, _ = _apply_block(p, pattern[i], x, positions, cfg, enc_out)
        aux = aux + a
    return x, aux


def _embed(params, tokens, cfg):
    e = params["embed"][tokens].astype(cfg.act_dtype)
    return e * math.sqrt(cfg.d_model)


def _encode(params, frames, cfg, remat=True):
    """Whisper-style encoder over (stub) frame embeddings (B, enc_len, d)."""
    x = frames.astype(cfg.act_dtype)
    positions = jnp.arange(x.shape[1])
    x, _ = _run_stack(params["encoder"], ("encattn:dense",), x, positions, cfg,
                      remat=remat)
    return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def hidden_states(params, batch, cfg, remat=True, remat_policy="full"):
    """Full forward to final hidden states. Returns (h, aux, n_prefix).

    ``n_prefix`` = number of non-text positions (VLM patches) to exclude
    from the LM loss.
    """
    enc_out = None
    n_prefix = 0
    if cfg.family == "encdec":
        enc_out = _encode(params, batch["frames"], cfg, remat=remat)
        x = _embed(params, batch["tokens"], cfg)
    elif cfg.family == "vlm":
        patches = (batch["patches"].astype(cfg.act_dtype)
                   @ params["patch_proj"].astype(cfg.act_dtype))
        text = _embed(params, batch["tokens"], cfg)
        x = jnp.concatenate([patches, text], axis=1)
        n_prefix = patches.shape[1]
    else:
        x = _embed(params, batch["tokens"], cfg)

    positions = jnp.arange(x.shape[1])
    x, aux = _run_stack(params["decoder"], cfg.pattern, x, positions, cfg,
                        enc_out, remat, remat_policy)
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps), aux, n_prefix


def _logits(params, h, cfg):
    if cfg.tie_embeddings:
        return h.astype(F32) @ params["embed"].astype(F32).T
    return h.astype(F32) @ params["lm_head"].astype(F32)


def loss_fn(params, batch, cfg, remat: bool = True,
            remat_policy: str = "full") -> jnp.ndarray:
    """Next-token CE, chunked over the sequence to bound logits memory."""
    h, aux, n_prefix = hidden_states(params, batch, cfg, remat=remat,
                                     remat_policy=remat_policy)
    tokens = batch["tokens"]
    B, S_text = tokens.shape
    h_text = h[:, n_prefix:]

    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1
    )
    mask = jnp.concatenate(
        [jnp.ones((B, S_text - 1), F32), jnp.zeros((B, 1), F32)], axis=1
    )

    chunk = min(CE_CHUNK, S_text)
    n_chunks = -(-S_text // chunk)
    pad = n_chunks * chunk - S_text
    if pad:
        h_text = jnp.pad(h_text, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))

    hc = h_text.reshape(B, n_chunks, chunk, -1).swapaxes(0, 1)
    tc = targets.reshape(B, n_chunks, chunk).swapaxes(0, 1)
    mc = mask.reshape(B, n_chunks, chunk).swapaxes(0, 1)

    def ce_chunk(carry, inp):
        hcc, tcc, mcc = inp
        with jax.named_scope("lm_head"):
            logits = _logits(params, hcc, cfg)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, tcc[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mcc
        return carry + nll.sum(), None

    total, _ = jax.lax.scan(ce_chunk, jnp.zeros((), F32), (hc, tc, mc))
    loss = total / jnp.maximum(mask.sum(), 1.0)
    return loss + MOE_AUX_COEF * aux


# ---------------------------------------------------------------------------
# KV cache: init / prefill / decode
# ---------------------------------------------------------------------------

def _init_block_cache(kind, cfg, batch, max_len, dtype):
    mixer, _ = _parse_kind(kind)
    kvh, hd = cfg.n_kv_heads, cfg.hd
    if mixer == "attn":
        return {
            "k": jnp.zeros((batch, max_len, kvh, hd), dtype),
            "v": jnp.zeros((batch, max_len, kvh, hd), dtype),
        }
    if mixer == "swa":
        w = min(cfg.window, max_len)
        return {
            "k": jnp.zeros((batch, w, kvh, hd), dtype),
            "v": jnp.zeros((batch, w, kvh, hd), dtype),
        }
    if mixer == "xattn":
        return {
            "k": jnp.zeros((batch, max_len, kvh, hd), dtype),
            "v": jnp.zeros((batch, max_len, kvh, hd), dtype),
            "kx": jnp.zeros((batch, cfg.enc_len, kvh, hd), dtype),
            "vx": jnp.zeros((batch, cfg.enc_len, kvh, hd), dtype),
        }
    if mixer == "ssm":
        return L.mamba2_init_cache(cfg, batch, dtype)
    if mixer == "rglru":
        return L.rglru_init_cache(cfg, batch, dtype)
    raise ValueError(kind)


def init_cache(cfg, batch: int, max_len: int, dtype=None) -> PyTree:
    dtype = dtype or cfg.act_dtype
    nb = cfg.n_scan_blocks
    blocks = {}
    for j, kind in enumerate(cfg.pattern):
        if nb > 0:
            one = _init_block_cache(kind, cfg, batch, max_len, dtype)
            blocks[f"p{j}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (nb,) + a.shape), one
            )
    rem = tuple(
        _init_block_cache(cfg.pattern[i], cfg, batch, max_len, dtype)
        for i in range(cfg.n_rem_layers)
    )
    return {STACK_KEY: blocks, "rem": rem}


def _decode_block(p, kind, cache, x, pos, cfg, max_len):
    """One-token step through one block. x: (B,1,d). Returns (x, new_cache)."""
    mixer, ffn = _parse_kind(kind)
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    B = x.shape[0]

    if mixer in ("attn", "swa", "xattn"):
        q, k, v = L.attn_qkv(p["attn"], h, pos[None], cfg)  # rope at abs pos
        if mixer == "swa":
            w = cache["k"].shape[1]
            slot = pos % w
            new_k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
            new_v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
            idx = jnp.arange(w)
            slot_pos = idx + w * ((pos - idx) // w)        # latest pos = i (mod w)
            valid = slot_pos >= 0
        else:
            new_k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos, axis=1)
            new_v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos, axis=1)
            valid = jnp.arange(new_k.shape[1]) <= pos
        out = L.decode_attention(q, new_k, new_v, valid)
        x = x + L.attn_proj_out(p["attn"], out)
        new_cache = dict(cache, k=new_k, v=new_v)
        if mixer == "xattn":
            hx = L.rmsnorm(p["lnx"], x, cfg.norm_eps)
            qx = (hx @ p["xattn"]["wq"].astype(hx.dtype)).reshape(B, 1, cfg.n_heads, cfg.hd)
            outx = L.decode_attention(
                qx, cache["kx"], cache["vx"], jnp.ones((cache["kx"].shape[1],), bool)
            )
            x = x + L.attn_proj_out(p["xattn"], outx)
    elif mixer == "ssm":
        out, new_cache = L.mamba2_decode(p["ssm"], cache, h[:, 0], cfg)
        x = x + out[:, None]
    elif mixer == "rglru":
        out, new_cache = L.rglru_decode(p["rglru"], cache, h[:, 0], cfg)
        x = x + out[:, None]
    else:
        raise ValueError(kind)

    if ffn == "dense":
        x = x + L.mlp_apply(p["mlp"], L.rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
    elif ffn == "moe":
        out, _ = L.moe_apply(p["moe"], L.rmsnorm(p["ln2"], x, cfg.norm_eps), cfg)
        x = x + out
    return x, new_cache


def decode_step(params, cache, tokens, pos, cfg):
    """tokens: (B,) int32; pos: scalar int32. Returns (logits (B,V), cache)."""
    x = _embed(params, tokens[:, None], cfg)
    max_len = None

    def body(x, inp):
        block_params, block_cache = inp
        new_caches = {}
        for j, kind in enumerate(cfg.pattern):
            key = f"p{j}"
            if key not in block_params:
                continue
            x, new_caches[key] = _decode_block(
                block_params[key], kind, block_cache[key], x, pos, cfg, max_len
            )
        return x, new_caches

    new_cache = {STACK_KEY: {}, "rem": []}
    if params["decoder"][STACK_KEY]:
        x, new_cache[STACK_KEY] = jax.lax.scan(
            body, x, (params["decoder"][STACK_KEY], cache[STACK_KEY])
        )
    for i, p in enumerate(params["decoder"]["rem"]):
        x, nc = _decode_block(p, cfg.pattern[i], cache["rem"][i], x, pos, cfg, max_len)
        new_cache["rem"].append(nc)
    new_cache["rem"] = tuple(new_cache["rem"])

    h = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, h, cfg)[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------------
# Prefill: forward over a prompt, building the cache.
# ---------------------------------------------------------------------------

def _prefill_block_cache(p, kind, x, positions, cfg, enc_out):
    """Apply block and build its cache entry. Returns (x, cache_entry)."""
    mixer, _ = _parse_kind(kind)
    h_in = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if mixer == "ssm":
        # rerun projections to recover final state (single extra state pass)
        x2, _, _ = _apply_block(p, kind, x, positions, cfg, enc_out)
        state = _mamba2_final_state(p["ssm"], h_in, cfg)
        return x2, state
    if mixer == "rglru":
        x2, _, _ = _apply_block(p, kind, x, positions, cfg, enc_out)
        state = _rglru_final_state(p["rglru"], h_in, cfg)
        return x2, state
    x2, _, entry = _apply_block(p, kind, x, positions, cfg, enc_out, collect_cache=True)
    return x2, entry


def _mamba2_final_state(p, h, cfg):
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    proj = h @ p["in_proj"].astype(h.dtype)
    z, xs, Bm, Cm, dt = jnp.split(proj, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], -1)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_out = jax.nn.silu(L.conv1d_apply(p["conv"], conv_in))
    xs2, Bm2, Cm2 = jnp.split(conv_out, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
    A = jnp.exp(p["A_log"])
    dA = jnp.exp(-A[None, None] * dt)                      # (B,S,H)
    xh = xs2.reshape(*xs2.shape[:2], H, P).astype(F32)
    dBx = jnp.einsum("bsh,bsn,bshp->bshpn", dt, Bm2.astype(F32), xh)

    def step(state, inp):
        dAs, dBxs = inp
        return state * dAs[..., None, None] + dBxs, None

    state0 = jnp.zeros((h.shape[0], H, P, N), F32)
    state, _ = jax.lax.scan(
        step, state0, (jnp.moveaxis(dA, 1, 0), jnp.moveaxis(dBx, 1, 0))
    )
    conv_tail = conv_in[:, -(cfg.conv_width - 1):]
    return {"state": state, "conv": conv_tail}


def _rglru_final_state(p, h, cfg):
    xr = h @ p["in_x"].astype(h.dtype)
    xc = L.conv1d_apply(p["conv"], xr)
    a, b = L._rglru_coeffs(p, xc)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, br + ar * bl

    _, hs = jax.lax.associative_scan(combine, (a, b), axis=1)
    conv_tail = xr[:, -(cfg.conv_width - 1):]
    return {"h": hs[:, -1], "conv": conv_tail}


def prefill(params, batch, cfg, remat: bool = True):
    """Forward over prompt tokens; returns (last-token logits, cache)."""
    enc_out = None
    if cfg.family == "encdec":
        enc_out = _encode(params, batch["frames"], cfg, remat=remat)
        x = _embed(params, batch["tokens"], cfg)
    elif cfg.family == "vlm":
        patches = (batch["patches"].astype(cfg.act_dtype)
                   @ params["patch_proj"].astype(cfg.act_dtype))
        text = _embed(params, batch["tokens"], cfg)
        x = jnp.concatenate([patches, text], axis=1)
    else:
        x = _embed(params, batch["tokens"], cfg)

    positions = jnp.arange(x.shape[1])

    def body(x, block_params):
        caches = {}
        for j, kind in enumerate(cfg.pattern):
            key = f"p{j}"
            if key not in block_params:
                continue
            x, caches[key] = _prefill_block_cache(
                block_params[key], kind, x, positions, cfg, enc_out
            )
        return x, caches

    body_fn = jax.checkpoint(body) if remat else body
    cache = {STACK_KEY: {}, "rem": []}
    if params["decoder"][STACK_KEY]:
        x, cache[STACK_KEY] = jax.lax.scan(body_fn, x, params["decoder"][STACK_KEY])
    for i, p in enumerate(params["decoder"]["rem"]):
        x, entry = _prefill_block_cache(p, cfg.pattern[i], x, positions, cfg, enc_out)
        cache["rem"].append(entry)
    cache["rem"] = tuple(cache["rem"])

    h = L.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = _logits(params, h, cfg)[:, 0]
    return logits, cache
