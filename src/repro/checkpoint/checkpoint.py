"""Pytree checkpointing (npz-based; orbax is not installed offline).

Flattens any pytree with string-path keys; dtypes (incl. bf16) survive the
round trip via a view-as-uint16 trick, since npz has no bf16 support.

Crash safety: ``save`` writes both files to temporaries and ``os.replace``s
them into place (npz first, json last), so the json is the commit marker —
a checkpoint is *complete* iff both files exist, and a kill mid-write can
only ever leave an ignorable temp or an npz without its json.  On top of
the single-file primitives, the rotated-checkpoint manager
(``save_checkpoint`` / ``latest_checkpoint`` / ``restore_latest``) keeps a
``latest`` pointer and the last ``keep`` complete checkpoints in a
directory, which is what the trainer's ``checkpoint_every`` / ``resume``
settings drive (see docs/fault_tolerance.md).

Each checkpoint records where per-worker state holds its worker axis
(``repro.layout.WORKER_LAYOUT``).  A checkpoint written before layer-stacked
leaves moved their worker axis to second place holds them as ``(W, L, ...)``;
restoring one raises an error that names the leaf (docs/local_phase.md).
"""

from __future__ import annotations

import glob
import json
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.layout import STACK_KEY, WORKER_LAYOUT, is_stacked

PyTree = Any
_BF16_TAG = "__bf16__"
_CKPT_PREFIX = "ckpt_"
_LATEST = "latest"


def _path_str(path) -> str:
    parts = []
    for e in path:
        k = getattr(e, "key", None)
        if k is None:
            k = getattr(e, "idx", None)
        if k is None:
            k = getattr(e, "name", str(e))
        parts.append(str(k))
    return "/".join(parts)


def _atomic_replace(target: str, write_fn, mode: str) -> None:
    """Write via a same-directory temp file + ``os.replace`` (atomic on
    POSIX): readers never observe a torn ``target``."""
    tmp = f"{target}.tmp.{os.getpid()}"
    try:
        with open(tmp, mode) as f:
            write_fn(f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, target)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def save(path: str, tree: PyTree, step: int = 0,
         extra: Optional[dict] = None) -> None:
    """Atomically save ``tree`` as ``path.npz`` + ``path.json``.

    ``extra``: optional JSON-serializable metadata (e.g. loss history)
    stored in the json sidecar, readable via :func:`load_meta`.
    """
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    arrays, meta = {}, {"step": step, "keys": [], "worker_layout": WORKER_LAYOUT}
    if extra is not None:
        meta["extra"] = extra
    for i, (p, leaf) in enumerate(flat):
        key = f"a{i}"
        arr = np.asarray(leaf)
        if arr.dtype == jnp.bfloat16:
            arrays[key] = arr.view(np.uint16)
            meta["keys"].append([_path_str(p), _BF16_TAG])
        else:
            arrays[key] = arr
            meta["keys"].append([_path_str(p), str(arr.dtype)])
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    # npz first, json last: the json is the commit marker
    _atomic_replace(path + ".npz", lambda f: np.savez(f, **arrays), "wb")
    _atomic_replace(path + ".json", lambda f: json.dump(meta, f), "w")


def is_complete(path: str) -> bool:
    return os.path.exists(path + ".npz") and os.path.exists(path + ".json")


def load_meta(path: str) -> dict:
    with open(path + ".json") as f:
        return json.load(f)


def _check_worker_layout(pstr: str, path, got: tuple, want: tuple) -> None:
    """Refuse a layer-stacked leaf of a checkpoint that predates the
    ``(L, W, ...)`` layout where it reads as a worker-first ``(W, L, ...)``
    one: its shape differs from ``want`` by the order of the first two
    axes.  A leaf whose shape agrees is restored: most have no worker axis
    (``x0``, params alone), and one with ``W == L`` cannot be told apart."""
    if (is_stacked(path) and got != want
            and got == (want[1], want[0]) + want[2:]):
        raise ValueError(
            f"{pstr}: the checkpoint was written before per-worker leaves "
            f"under '{STACK_KEY}' moved their worker axis from first to "
            f"second place; it holds {got} where this layout needs {want} "
            "(docs/local_phase.md)")


def restore(path: str, like: PyTree) -> tuple[PyTree, int]:
    """Restore into the structure of ``like`` (shape- AND dtype-checked:
    a f32/i32 layout drift raises instead of silently casting)."""
    meta = load_meta(path)
    stacked_worker_first = meta.get("worker_layout") != WORKER_LAYOUT
    flat_with_paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    saved = {k: i for i, (k, _) in enumerate(meta["keys"])}
    out = []
    with np.load(path + ".npz") as data:
        for path_keys, leaf in flat_with_paths:
            pstr = _path_str(path_keys)
            if pstr not in saved:
                raise KeyError(f"checkpoint missing leaf {pstr}")
            i = saved[pstr]
            arr = data[f"a{i}"]
            got = meta["keys"][i][1]
            leaf_dtype = getattr(leaf, "dtype", None) or np.asarray(leaf).dtype
            want = (_BF16_TAG if leaf_dtype == jnp.bfloat16
                    else str(np.dtype(leaf_dtype)))
            if got != want:
                raise ValueError(
                    f"dtype mismatch for {pstr}: checkpoint has {got}, "
                    f"expected {want}")
            if got == _BF16_TAG:
                arr = arr.view(jnp.bfloat16)
            if stacked_worker_first:
                _check_worker_layout(pstr, path_keys, tuple(arr.shape),
                                     tuple(np.shape(leaf)))
            if tuple(arr.shape) != tuple(np.shape(leaf)):
                raise ValueError(
                    f"shape mismatch for {pstr}: {arr.shape} vs {np.shape(leaf)}")
            out.append(jnp.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out), meta["step"]


# ---------------------------------------------------------------------------
# Rotated checkpoint directory: ckpt_<step> files, a `latest` pointer, and
# retention of the last `keep` complete checkpoints.
# ---------------------------------------------------------------------------

def step_path(directory: str, step: int) -> str:
    return os.path.join(directory, f"{_CKPT_PREFIX}{step:08d}")


def list_checkpoints(directory: str) -> list[tuple[int, str]]:
    """Sorted ``(step, base_path)`` for every COMPLETE checkpoint."""
    out = []
    for j in glob.glob(os.path.join(directory, f"{_CKPT_PREFIX}*.json")):
        base = j[: -len(".json")]
        if not os.path.exists(base + ".npz"):
            continue  # torn write: npz landed, json (commit marker) did not
        try:
            step = int(os.path.basename(base)[len(_CKPT_PREFIX):])
        except ValueError:
            continue
        out.append((step, base))
    return sorted(out)


def latest_checkpoint(directory: str) -> Optional[str]:
    """Base path of the newest complete checkpoint (``latest`` pointer with
    a scan fallback for a stale/missing pointer), or None."""
    ptr = os.path.join(directory, _LATEST)
    if os.path.exists(ptr):
        with open(ptr) as f:
            name = f.read().strip()
        base = os.path.join(directory, name)
        if name and is_complete(base):
            return base
    cks = list_checkpoints(directory)
    return cks[-1][1] if cks else None


def save_checkpoint(directory: str, tree: PyTree, step: int, keep: int = 3,
                    extra: Optional[dict] = None) -> str:
    """Atomic rotated save: write ``ckpt_<step>``, repoint ``latest``, prune
    all but the newest ``keep`` complete checkpoints.  Returns the base path."""
    base = step_path(directory, step)
    save(base, tree, step=step, extra=extra)
    _atomic_replace(os.path.join(directory, _LATEST),
                    lambda f: f.write(os.path.basename(base)), "w")
    if keep and keep > 0:
        for _, old in list_checkpoints(directory)[:-keep]:
            for suffix in (".npz", ".json"):
                try:
                    os.remove(old + suffix)
                except OSError:
                    pass
    return base


def restore_latest(directory: str, like: PyTree
                   ) -> Optional[tuple[PyTree, int, dict]]:
    """Restore the newest complete checkpoint: ``(tree, step, extra)``, or
    None when the directory holds no complete checkpoint."""
    base = latest_checkpoint(directory)
    if base is None:
        return None
    tree, step = restore(base, like)
    return tree, step, load_meta(base).get("extra") or {}
