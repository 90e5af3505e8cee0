"""Batched serving: prefill a prompt batch, then autoregressive decode.

CPU-scale engine used by examples/serve_model.py and the integration tests,
over the model's own ``prefill`` and ``decode_step``.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import transformer as T


def generate(
    params,
    cfg,
    prompt_tokens: jnp.ndarray,      # (B, S_prompt) int32
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    extra_batch: Optional[dict] = None,   # frames/patches for encdec/vlm
):
    """Greedy (or temperature) decoding. Returns (tokens (B, new), stats)."""
    B, S = prompt_tokens.shape
    max_len = S + max_new_tokens
    batch = {"tokens": prompt_tokens, **(extra_batch or {})}

    t0 = time.time()
    # prefill produces a cache sized to the prompt; re-home it into a
    # max_len cache so decode can append.
    logits, pcache = jax.jit(lambda p, b: T.prefill(p, b, cfg, remat=False))(params, batch)
    cache = T.init_cache(cfg, B, max_len, cfg.act_dtype)
    cache = _splice_cache(cache, pcache, cfg, S)
    prefill_s = time.time() - t0

    dec = jax.jit(lambda p, c, t, pos: T.decode_step(p, c, t, pos, cfg))

    def pick(logits, key):
        if temperature <= 0.0:
            return jnp.argmax(logits[:, : cfg.vocab_size], axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits[:, : cfg.vocab_size] / temperature, axis=-1
        ).astype(jnp.int32)

    rng = rng if rng is not None else jax.random.PRNGKey(0)
    out = []
    n_prefix = cfg.n_patches if cfg.family == "vlm" else 0
    tok = pick(logits, rng)
    out.append(tok)
    t0 = time.time()
    for i in range(max_new_tokens - 1):
        rng, sub = jax.random.split(rng)
        pos = jnp.int32(S + n_prefix + i)
        logits, cache = dec(params, cache, tok, pos)
        tok = pick(logits, sub)
        out.append(tok)
    decode_s = time.time() - t0
    return jnp.stack(out, axis=1), {
        "prefill_s": prefill_s,
        "decode_s": decode_s,
        "tok_per_s": (max_new_tokens - 1) * B / max(decode_s, 1e-9),
    }


def _splice_cache(big, small, cfg, prompt_len: int):
    """Copy a prefill cache (length = prompt) into a longer decode cache.

    Structure-aware: sliding-window k/v are ring buffers (position p lives
    at slot p % window; prefill emits the last ``w_small`` positions in
    natural order), full-attention k/v pad at the end, recurrent states
    copy through.
    """

    def splice_leaf(kind: str, name: str, big_leaf, small_leaf):
        mixer = kind.split(":")[0]
        if mixer in ("ssm", "rglru") or name in ("kx", "vx") or (
            big_leaf.shape == small_leaf.shape and mixer not in ("swa",)
        ):
            return small_leaf.astype(big_leaf.dtype)
        ax = big_leaf.ndim - 3  # seq axis of (..., S, kvh, hd)
        w_big, w_small = big_leaf.shape[ax], small_leaf.shape[ax]
        pad = [(0, 0)] * big_leaf.ndim
        pad[ax] = (0, w_big - w_small)
        out = jnp.pad(small_leaf.astype(big_leaf.dtype), pad)
        if mixer == "swa":
            out = jnp.roll(out, (prompt_len - w_small) % w_big, axis=ax)
        return out

    def splice_entry(kind, big_e, small_e):
        return {
            name: splice_leaf(kind, name, big_e[name], small_e[name])
            for name in big_e
        }

    stack = T.STACK_KEY
    out = {stack: {}, "rem": []}
    for j, kind in enumerate(cfg.pattern):
        keyn = f"p{j}"
        if keyn in big[stack]:
            out[stack][keyn] = splice_entry(
                kind, big[stack][keyn], small[stack][keyn])
    for i in range(len(big["rem"])):
        out["rem"].append(
            splice_entry(cfg.pattern[i], big["rem"][i], small["rem"][i]))
    out["rem"] = tuple(out["rem"])
    return out
