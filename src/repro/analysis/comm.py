"""Communication-volume accounting (the paper's 'Com. red.' column).

Analytic bytes-per-outer-step for every algorithm; the runtime comm
ledger (``repro.obs.ledger``) joins it with the compiled step's collectives
as parsed by ``repro.analysis.hlo_audit.parse_collectives``.

``phase_collective_budget`` turns the same model into the machine-checked
per-phase budgets consumed by the static auditor
(``repro.analysis.hlo_audit``): the analytic round counts here are LOGICAL
rounds; the auditor multiplies them out to per-leaf HLO op ceilings and
payload-byte ceilings and checks the compiled program against them.
"""

from __future__ import annotations

from repro.configs import load_arch
from repro.configs import specs as S


GLOBAL_STATE_BYTES = 4      # x0 and m are f32 by default
GLOBAL_STEP_PASSES = 5      # HBM traffic of eqs. 6-8: read x0, m, x_tau; write x0, m


LOCAL_STEP_ALGOS = ("dsm", "slowmo", "signed_slowmo", "lookahead",
                    "global_adamw", "local_avg")


def wire_bytes_for_payload(payload_bytes: int, algo: str, tau: int,
                           param_bytes: int = 2) -> tuple:
    """``(wire_bytes_per_outer, comm_rounds_per_outer)`` for a raw payload.

    The round model shared by ``bytes_per_outer_step`` (which derives the
    payload from an arch id) and the runtime comm ledger
    (``repro.obs.ledger``, which derives it from the live param pytree):
    one all-reduce ~ 2x payload on the ring, per logical round.
    """
    if algo in LOCAL_STEP_ALGOS:
        return 2 * payload_bytes, 1        # one model all-reduce / outer step
    if algo == "perstep":
        return 2 * payload_bytes * tau, tau  # gradient all-reduce every step
    if algo == "mv_signsgd":
        return payload_bytes // (8 * param_bytes) * 2, 1  # 1-bit signs each way
    raise ValueError(algo)


def bytes_per_outer_step(arch_id: str, algo: str, tau: int,
                         param_bytes: int = 2, zero_sharded: bool = False,
                         shards: int = 1, device_parallel: bool = False,
                         n_workers: int = 8,
                         survivor_frac: float = 1.0) -> dict:
    """Inter-worker (slow-network) bytes per tau local steps, per the
    all-reduce ~ 2x payload ring model.  Intra-worker TP traffic excluded
    (that is the fast-network budget).

    ``zero_sharded`` / ``shards``: DSM's ZeRO-sharded global step over
    R = worker * zero ranks.  Wire bytes are unchanged (reduce-scatter +
    all-gather ~ one all-reduce), but each rank now holds and updates only
    1/R of the global x0 / m buffers — the per-rank HBM figures below are
    what the sharding buys.

    ``device_parallel`` / ``n_workers``: the local phase's execution layout.
    The vmapped simulation replicates all ``n_workers`` workers' tau local
    steps onto every rank; the shard_mapped layout runs exactly one worker's
    share per rank (wire bytes unchanged — the local phase is collective-free
    either way).  ``local_step_flops_replication`` is the per-rank local
    compute multiplier the layout implies.

    ``survivor_frac``: expected fraction of worker contributions that arrive
    each round under dropout (``1 - FaultPlan.dropped_frac()``).  A dropped
    worker sources nothing into the round's reduction, so the *expected*
    fabric traffic scales with the survivor fraction while the per-survivor
    bytes (and round count — the all-reduce still happens) do not.
    """
    if not 0.0 <= survivor_frac <= 1.0:
        raise ValueError(f"survivor_frac={survivor_frac} must lie in [0, 1]")
    cfg = load_arch(arch_id).FULL
    n = S.param_count(cfg)
    payload = n * param_bytes
    wire, rounds = wire_bytes_for_payload(payload, algo, tau,
                                          param_bytes=param_bytes)
    out = {
        "arch": arch_id, "algo": algo, "tau": tau,
        "wire_bytes_per_outer": wire,
        "comm_rounds_per_outer": rounds,
        "reduction_vs_perstep": (2 * payload * tau) / max(wire, 1),
        "survivor_frac": survivor_frac,
        "expected_wire_bytes_per_outer": int(round(wire * survivor_frac)),
    }
    if algo in LOCAL_STEP_ALGOS:
        out["local_phase_device_parallel"] = device_parallel
        out["local_step_flops_replication"] = 1 if device_parallel else n_workers
    if algo == "dsm":
        r = shards if zero_sharded else 1
        out["zero_sharded"] = zero_sharded
        out["global_state_shards"] = r
        # per-rank residency of the global buffers (x0 + m) ...
        out["global_state_bytes_per_rank"] = 2 * n * GLOBAL_STATE_BYTES // r
        # ... and per-rank HBM traffic of the global update itself
        out["global_buffer_bytes_per_rank"] = (
            GLOBAL_STEP_PASSES * n * GLOBAL_STATE_BYTES // r
        )
        # bytes each rank sources into the x_{t+1,0} all-gather (replicated
        # ranks all recompute the full update; sharded ranks own 1/R of it)
        out["broadcast_src_bytes_per_rank"] = payload // r
    return out


# ---------------------------------------------------------------------------
# Collective budgets for the static auditor (repro.analysis.hlo_audit)
# ---------------------------------------------------------------------------

# A logical worker reduction may lower as `reduce-scatter` (collective-capable
# backends) or as `all-reduce` + local slice (the CPU partitioner's choice for
# the GSPMD scattered mean — see docs/sharding.md); both implement the same
# single round of the ring model above, so the budget treats them as one
# equivalence class.  Ops outside the declared classes (all-to-all,
# collective-permute, ...) are never part of Algorithm 1's outer step and any
# occurrence is a budget violation.
REDUCE_CLASS = ("all-reduce", "reduce-scatter")
GATHER_CLASS = ("all-gather",)

PHASES = ("local", "global_dense", "global_zero")


def phase_collective_budget(phase: str, *, n_param_leaves: int,
                            payload_bytes: int,
                            n_metric_reductions: int = 2,
                            payload_slack: float = 1.5) -> dict:
    """LOGICAL per-phase budget, derived from the round model above.

    ``bytes_per_outer_step`` counts one model-payload reduction round per
    outer step for every local-step algorithm (``comm_rounds_per_outer=1``)
    and none inside the tau local steps — the paper's communication claim.
    XLA lowers a logical round leafwise, so the op ceilings multiply the
    round count by ``n_param_leaves`` (+ ``n_metric_reductions`` scalar
    reductions for the loss metrics, which ride along with the global step);
    the payload ceilings multiply the model payload by ``payload_slack``
    (dtype/padding headroom — metric scalars are absorbed by a 1 KiB floor).

      * ``local``        — the tau local steps: ZERO collectives of any kind.
      * ``global_dense`` — replicated global step: one reduction round
        (the paper's single all-reduce), nothing else.
      * ``global_zero``  — ZeRO-sharded global step: one reduction round
        (reduce-scatter, or all-reduce on backends without it) plus one
        gather round (the x_{t+1,0} broadcast / all-gather); no stray
        second reduction.
    """
    if phase not in PHASES:
        raise ValueError(f"phase must be one of {PHASES}, got {phase!r}")
    reduce_rounds = 0 if phase == "local" else 1
    gather_rounds = 1 if phase == "global_zero" else 0
    pay = int(payload_slack * payload_bytes) + 1024
    return {
        "phase": phase,
        "reduce_rounds": reduce_rounds,
        "gather_rounds": gather_rounds,
        "max_reduce_ops": reduce_rounds * (n_param_leaves + n_metric_reductions),
        "max_gather_ops": gather_rounds * (n_param_leaves + n_metric_reductions),
        "max_reduce_bytes": reduce_rounds * pay,
        "max_gather_bytes": gather_rounds * pay,
        "reduce_class": list(REDUCE_CLASS),
        "gather_class": list(GATHER_CLASS),
    }
