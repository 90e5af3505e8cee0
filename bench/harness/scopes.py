"""Device time of the program's named scopes, read from ``op_name`` paths.

A scope opened inside a function that a transform traces as a call shows
as a path component of its own (``.../while/body/closed_call/attention``);
one opened directly under a transform shows wrapped in the transform's
names (``vmap(base_opt)``, ``transpose(jvp(lm_head))``).  ``carries``
finds both.  Remat recompute is every op under JAX's own
``rematted_computation`` component.

A fusion carries the ``op_name`` of its root instruction, so a fused op
is counted in one scope only: scopes opened side by side stay disjoint,
and an op fused across a scope's edge is counted where its root lies.
"""

from __future__ import annotations

import re

from harness import trace as TR

RECOMPUTE = "rematted_computation"

_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def unwrap(component: str) -> str:
    """``transpose(jvp(lm_head))`` -> ``lm_head``."""
    m = _WRAPPED.match(component)
    while m:
        component = m.group(1)
        m = _WRAPPED.match(component)
    return component


def carries(op_name: str, scope: str) -> bool:
    """Whether the path names ``scope``, bare or wrapped in transforms."""
    return any(unwrap(c) == scope for c in op_name.split("/"))


def is_recompute(op_name: str) -> bool:
    return carries(op_name, RECOMPUTE)


def instructions(op_names: dict, scope: str) -> set:
    """The instructions whose ``op_name`` carries ``scope``."""
    return {name for name, path in op_names.items() if carries(path, scope)}


def scope_ns(ops: list, op_names: dict, scope: str) -> float:
    """Union device time of the ops that carry ``scope``."""
    tagged = instructions(op_names, scope)
    return TR.length(TR.union(o for o in ops if o[2] in tagged))


def ms_per_step(run, scope: str):
    """Device milliseconds per outer step in ``scope``, averaged over the
    chips; None where no op carries it."""
    per_chip = [scope_ns(ops, run.op_names, scope)
                for ops in run.trace.devices.values()]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) * 1e-6 / run.steps
