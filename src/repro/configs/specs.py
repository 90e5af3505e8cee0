"""input_specs(): ShapeDtypeStruct stand-ins for every model input.

Weak-type-correct, no device allocation: ``jax.eval_shape`` and
``jax.jit(...).lower`` take these in place of arrays.  Audio/VLM frontends
are STUBS per assignment: the specs provide precomputed frame/patch
embeddings at d_model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig, TopologyConfig
from repro.models import transformer as T

SDS = jax.ShapeDtypeStruct


def train_batch_specs(cfg: ModelConfig, topo: TopologyConfig, shape: InputShape,
                      n_workers: int) -> dict:
    """Batch pytree for one DSM outer step: leaves (W, tau, accum, B_micro, ...)."""
    assert shape.kind == "train"
    W, tau, acc = n_workers, topo.tau, topo.grad_accum
    assert shape.global_batch % (W * acc) == 0, (cfg.name, shape.name, W, acc)
    bm = shape.global_batch // (W * acc)
    lead = (W, tau, acc, bm)
    act = cfg.act_dtype

    if cfg.family == "vlm":
        s_text = shape.seq_len - cfg.n_patches
        return {
            "tokens": SDS(lead + (s_text,), jnp.int32),
            "patches": SDS(lead + (cfg.n_patches, cfg.d_model), act),
        }
    if cfg.family == "encdec":
        return {
            "tokens": SDS(lead + (shape.seq_len,), jnp.int32),
            "frames": SDS(lead + (cfg.enc_len, cfg.d_model), act),
        }
    return {"tokens": SDS(lead + (shape.seq_len,), jnp.int32)}


def prefill_batch_specs(cfg: ModelConfig, shape: InputShape) -> dict:
    assert shape.kind == "prefill"
    B, S = shape.global_batch, shape.seq_len
    act = cfg.act_dtype
    if cfg.family == "vlm":
        return {
            "tokens": SDS((B, S - cfg.n_patches), jnp.int32),
            "patches": SDS((B, cfg.n_patches, cfg.d_model), act),
        }
    if cfg.family == "encdec":
        return {
            "tokens": SDS((B, S), jnp.int32),
            "frames": SDS((B, cfg.enc_len, cfg.d_model), act),
        }
    return {"tokens": SDS((B, S), jnp.int32)}


def decode_specs(cfg: ModelConfig, shape: InputShape) -> dict:
    """tokens + pos + KV cache sized to seq_len (the spec'd cache length)."""
    assert shape.kind == "decode"
    B, S = shape.global_batch, shape.seq_len
    cache = jax.eval_shape(
        lambda: T.init_cache(cfg, B, S, cfg.act_dtype)
    )
    return {
        "tokens": SDS((B,), jnp.int32),
        "pos": SDS((), jnp.int32),
        "cache": cache,
    }


def abstract_params(cfg: ModelConfig):
    """Parameter ShapeDtypeStructs without allocating."""
    return jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))


def param_count(cfg: ModelConfig) -> int:
    import math

    return sum(math.prod(l.shape) for l in jax.tree.leaves(abstract_params(cfg)))

