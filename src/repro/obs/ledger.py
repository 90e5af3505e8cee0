"""Comm ledger: observed (compiled-HLO) vs predicted (analytic) collective
bytes for the training step (docs/observability.md).

The analytic model (``repro.analysis.comm``) predicts what Algorithm 1
*should* communicate per outer step; ``repro.analysis.hlo_audit`` parses
what the compiled program *actually* contains.  The ledger joins the two
at trainer startup: it lowers the live jitted step — same function, same
argument shardings, same mesh — parses its collectives, and emits an
``observed vs predicted`` record into the run's event stream, so every run
directory carries the evidence behind the paper's 'Com. red.' column.

Observed bytes are HLO result-shape payload bytes (what the auditor
bounds); the analytic *wire* figure is ~2x payload under the ring model,
and the ledger reports both so the summarize CLI can show the ratio
explicitly rather than bake the factor in.

The probe lowering happens once, before any sanitizer context is armed
(it is itself a compile, and must not trip the steady-state recompilation
counter), and on a degenerate single-device mesh the partitioner compiles
zero collectives — the record says so instead of reporting a fake match.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

PyTree = Any


def compile_time_ledger(
    step_fn: Any,
    args: Sequence[Any],
    *,
    params: PyTree,
    algo: str,
    tau: int,
    phase: str,
    mesh: Optional[Any] = None,
    name: str = "outer_step",
) -> Dict[str, Any]:
    """Lower ``step_fn(*args)`` and join its collectives with the model.

    ``params``: the global buffer pytree the phase moves (x0) — payload
    bytes use the reduce dtype floor of 4 B/elem, matching the auditor.
    ``phase``: one of ``repro.analysis.comm.PHASES``.
    """
    import jax

    from repro.analysis.comm import (GATHER_CLASS, PHASES, REDUCE_CLASS,
                                     wire_bytes_for_payload)
    from repro.analysis.hlo_audit import parse_collectives

    if phase not in PHASES:
        raise ValueError(f"phase must be one of {PHASES}, got {phase!r}")

    # distinct jit wrapper + distinct __name__, so this compile is never
    # confused with the trainer's own train_step by the recompilation counter
    def ledger_probe(*a):
        return step_fn(*a)

    text = jax.jit(ledger_probe).lower(*args).compile().as_text()
    ops = parse_collectives(text)

    leaves = jax.tree.leaves(params)
    payload = sum(l.size * max(4, getattr(l.dtype, "itemsize", 4))
                  for l in leaves)
    wire, rounds = wire_bytes_for_payload(payload, algo, tau)
    pred_reduce = payload if phase != "local" else 0
    pred_gather = payload if phase == "global_zero" else 0

    obs_reduce = sum(o.bytes for o in ops if o.kind in REDUCE_CLASS)
    obs_gather = sum(o.bytes for o in ops if o.kind in GATHER_CLASS)
    other = [o for o in ops
             if o.kind not in REDUCE_CLASS and o.kind not in GATHER_CLASS]

    mesh_devices = 1
    if mesh is not None:
        mesh_devices = 1
        for v in mesh.shape.values():
            mesh_devices *= int(v)
    degenerate = mesh_devices <= 1

    def _ratio(obs: int, pred: int) -> Optional[float]:
        if pred <= 0 or degenerate:
            return None
        return obs / pred

    return {
        "name": name,
        "phase": phase,
        "algo": algo,
        "tau": int(tau),
        "n_param_leaves": len(leaves),
        "mesh_devices": mesh_devices,
        "degenerate_mesh": degenerate,
        "predicted": {
            "payload_bytes": int(payload),
            "reduce_bytes": int(pred_reduce),
            "gather_bytes": int(pred_gather),
            "wire_bytes_per_outer": int(wire),
            "comm_rounds_per_outer": int(rounds),
        },
        "observed": {
            "reduce_ops": sum(1 for o in ops if o.kind in REDUCE_CLASS),
            "gather_ops": sum(1 for o in ops if o.kind in GATHER_CLASS),
            "other_ops": len(other),
            "other_kinds": sorted({o.kind for o in other}),
            "reduce_bytes": int(obs_reduce),
            "gather_bytes": int(obs_gather),
        },
        "ratio": {
            "reduce": _ratio(obs_reduce, pred_reduce),
            "gather": _ratio(obs_gather, pred_gather),
        },
    }
