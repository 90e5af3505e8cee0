"""The key under which a model stacks the layers it scans, and the record
of where per-worker state holds its worker axis.

Models build their layer stacks under ``STACK_KEY`` and scan every leaf
there along its leading axis.  Per-worker state keeps that layer axis
first and holds the worker second in those leaves (``repro.core.workers``);
checkpoints record ``WORKER_LAYOUT`` so that one written before that rule
can be told apart.  This module imports nothing, so models, sharding rules
and checkpoints read it without loading the training algorithms.
"""

STACK_KEY = "blocks"

WORKER_LAYOUT = "stacked-leaves-worker-second"


def is_stacked(path) -> bool:
    """True for a leaf whose key path passes through a ``STACK_KEY`` dict
    key."""
    return any(getattr(k, "key", None) == STACK_KEY for k in path)
