"""Plain reference of the GPT-2 configurations as this benchmark runs them.

Everything here is written from the configuration file alone; nothing is
imported from the program under test.  The forward pass and its loss are
straightforward float32 ``jax.numpy`` at ``highest`` matmul precision:

  * token embedding (the padded table) times sqrt(n_embd);
  * n_layer pre-norm blocks: RMSNorm with a (1 + scale) gain, causal
    self-attention with RoPE (theta 10000) on queries and keys, output
    projection, RMSNorm, GELU (tanh form, GPT-2's ``gelu_new``) MLP;
  * a final RMSNorm, logits against the tied embedding over every row of
    the padded table, mean next-token cross-entropy over positions 0..S-2.

Every matrix product goes through ``dot`` (``jnp.einsum`` unless given):
the control passes one that computes in float8 (``harness.faults.fp8_dot``).

The module also states the configuration's shapes for the harness:
``init_weights`` (seeded weights in the program's parameter layout, made
by the benchmark for the program and for the reference alike) and
``flops_per_token`` (PaLM's model-FLOPs convention).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROPE_THETA = 10000.0
VOCAB_PAD_TO = 512


def d_ff(conf: dict) -> int:
    return conf.get("n_inner") or 4 * conf["n_embd"]


def padded_vocab(conf: dict) -> int:
    return -(-conf["vocab_size"] // VOCAB_PAD_TO) * VOCAB_PAD_TO


def flops_per_token(conf: dict, seq: int) -> float:
    """Model FLOPs per trained token: 6 x matmul parameters (the LM head at
    the published vocabulary) + 12 * n_layer * seq * n_embd for attention.
    Recomputed (remat) FLOPs are not counted."""
    d, L = conf["n_embd"], conf["n_layer"]
    per_layer = 4 * d * d + 2 * d * d_ff(conf)
    matmul_params = L * per_layer + d * conf["vocab_size"]
    return 6.0 * matmul_params + 12.0 * L * seq * d


def weight_shapes(conf: dict) -> dict:
    d, L, f = conf["n_embd"], conf["n_layer"], d_ff(conf)
    return {
        "embed": (padded_vocab(conf), d),
        "final_norm": {"scale": (d,)},
        "decoder": {
            "blocks": {"p0": {
                "ln1": {"scale": (L, d)},
                "attn": {"wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d),
                         "wo": (L, d, d)},
                "ln2": {"scale": (L, d)},
                "mlp": {"w1": (L, d, f), "w2": (L, f, d)},
            }},
            "rem": (),
        },
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and len(x) > 0 and all(isinstance(i, int) for i in x)


def init_weights(conf: dict, key) -> dict:
    """Seeded weights in the program's layout and the configuration's
    parameter dtype: normal(0, 0.02) embedding, normal(0, 1/sqrt(fan_in))
    projections, zero norm gains (a gain of 1 under the (1 + scale) form)."""
    dtype = jnp.dtype(conf["param_dtype"])
    shapes = weight_shapes(conf)
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes,
                                                          is_leaf=_is_shape)
    keys = jax.random.split(key, len(paths))
    leaves = []
    for (path, shape), k in zip(paths, keys):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            leaves.append(jnp.zeros(shape, dtype))
            continue
        std = 0.02 if name == "['embed']" else 1.0 / math.sqrt(shape[-2])
        leaves.append((jax.random.normal(k, shape, F32) * std).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, positions):
    hd = x.shape[-1]
    freqs = 1.0 / (ROPE_THETA ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def loss(weights, tokens, conf: dict, dot=None):
    """Mean next-token cross-entropy of ``tokens`` (B, S) under float32
    ``weights`` (the program's layout); ``dot(spec, a, b)`` computes every
    matrix product."""
    d, H = conf["n_embd"], conf["n_head"]
    hd, eps = d // H, conf["layer_norm_epsilon"]
    B, S = tokens.shape
    dot = dot or jnp.einsum
    mm = lambda a, b: dot("...i,ij->...j", a, b)
    emb = weights["embed"]
    x = emb[tokens] * math.sqrt(d)
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]

    def block(x, p):
        h = _rmsnorm(x, p["ln1"]["scale"], eps)
        q = _rope(mm(h, p["attn"]["wq"]).reshape(B, S, H, hd), pos)
        k = _rope(mm(h, p["attn"]["wk"]).reshape(B, S, H, hd), pos)
        v = mm(h, p["attn"]["wv"]).reshape(B, S, H, hd)
        s = dot("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        o = dot("bhqk,bkhd->bqhd", pr, v)
        x = x + mm(o.reshape(B, S, d), p["attn"]["wo"])
        h = _rmsnorm(x, p["ln2"]["scale"], eps)
        x = x + mm(_gelu_tanh(mm(h, p["mlp"]["w1"])), p["mlp"]["w2"])
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(block), x,
                        weights["decoder"]["blocks"]["p0"])
    h = _rmsnorm(x, weights["final_norm"]["scale"], eps)
    logits = mm(h, emb.T)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse[:, :-1] - gold)
