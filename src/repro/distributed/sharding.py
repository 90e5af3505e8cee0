"""Sharding rules: map parameter pytrees onto the training mesh.

Training mesh axes: ``("worker", "zero", "model")``
  worker — the paper's n workers (local-step isolation)
  zero   — FSDP/ZeRO shard *within* a worker (paper §2: "ZeRO-2 for local
           steps ... faster intra-node communication")
  model  — tensor parallel within a worker

Rules are name-aware (Megatron-style column/row parallel) with a generic
divisibility fallback; dims that don't divide are replicated.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.layout import is_stacked

PyTree = Any

# leaf-name -> which dim (from the end, ignoring stacked prefixes) is the
# model-parallel one. "col": last dim; "row": second-to-last dim.
_COL = ("wq", "wk", "wv", "w1", "w3", "in_proj", "in_x", "in_gate",
        "w_a", "w_x", "lm_head", "patch_proj", "we1", "we3")
_ROW = ("wo", "w2", "out_proj", "out", "we2")


def _model_dim(name: str, shape: tuple, i0: int, model: int) -> Optional[int]:
    nd = len(shape)
    if nd - i0 < 1:
        return None
    cands = []
    if name == "embed":
        # vocab-parallel: logits shard over V (logsumexp psum is tiny);
        # the lookup becomes masked-gather + small psum of (B,S,d).
        cands = [i0, nd - 1]
    elif name in _COL:
        cands = [nd - 1, nd - 2]
    elif name in _ROW:
        cands = [nd - 2, nd - 1]
    else:
        cands = [nd - 1, nd - 2]
    for c in cands:
        if c >= i0 and shape[c] % model == 0 and shape[c] >= model:
            return c
    return None


def _pick_dim(shape: tuple, i0: int, size: int, taken: set) -> Optional[int]:
    """Largest eligible dim divisible by ``size``."""
    best = None
    for i in range(i0, len(shape)):
        if i in taken or shape[i] % size != 0 or shape[i] < size:
            continue
        if best is None or shape[i] > shape[best]:
            best = i
    return best


def _leaf_name(path) -> str:
    for entry in reversed(path):
        key = getattr(entry, "key", None)
        if isinstance(key, str):
            return key
    return ""


def param_pspecs(
    abstract_params: PyTree,
    *,
    model: int,
    zero: int = 1,
    worker_axis: bool = False,
    zero_axes=("zero",),
    model_axis: str = "model",
    replicate_names: tuple = (),
) -> PyTree:
    """PartitionSpecs for a parameter pytree.

    ``worker_axis``: leaves carry a per-worker dim -> "worker": the leading
    dim, or the second of a layer-stacked leaf (repro.core.workers).
    ``zero_axes``: mesh axes for the FSDP dim (e.g. ("zero",) or
    ("worker","zero") for fully-sharded global buffers).
    """
    zero_total = zero

    def spec_for(path, leaf):
        shape = leaf.shape
        name = _leaf_name(path)
        spec = [None] * len(shape)
        if worker_axis and len(shape) == 0:
            return P()
        # stacked-layer dim (scan) first: leave unsharded
        i0 = 1 if is_stacked(path) and len(shape) > 0 else 0
        if worker_axis:
            spec[i0] = "worker"
            i0 += 1
        taken = set()
        md = (
            _model_dim(name, shape, i0, model)
            if model > 1 and name not in replicate_names else None
        )
        if md is not None:
            spec[md] = model_axis
            taken.add(md)
        if zero_total > 1:
            zd = _pick_dim(shape, i0, zero_total, taken)
            if zd is not None:
                spec[zd] = zero_axes if len(zero_axes) > 1 else zero_axes[0]
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, abstract_params)

