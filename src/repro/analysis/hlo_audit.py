"""HLO collective auditor: machine-checked communication budgets.

The paper's headline claim is a *collective budget*: sign momentum
communicates once per tau local steps (one worker reduction + , when
ZeRO-sharded, one gather), and the tau local steps themselves are
communication-free.  ``repro.analysis.comm`` models that analytically;
this module checks that the COMPILED program agrees, by lowering any
jitted step to its post-partitioning HLO text, parsing every collective
op with its shape, and comparing op counts and payload bytes against the
declared per-phase budget.

Budget semantics (``repro.analysis.comm.phase_collective_budget``):

  * a LOGICAL reduction round may lower as ``reduce-scatter`` on
    collective-capable backends or as ``all-reduce`` (+ local slice) under
    the CPU partitioner — one equivalence class, bounded together.  A
    *stray* extra reduction (a planted psum, an accidental re-reduce)
    exceeds the per-leaf ceiling either way.
  * XLA lowers a logical round leafwise, so ceilings are
    ``rounds * (n_param_leaves + n_metric_reductions)`` ops and
    ``rounds * payload_slack * payload_bytes`` bytes.
  * op kinds outside the declared classes (``all-to-all``,
    ``collective-permute``) never appear in Algorithm 1's outer step and
    any occurrence is a violation.

``standard_audit()`` runs the matrix the CI gate uses: the dense
(vmapped), device-parallel, and ZeRO-sharded outer steps plus the bare
local phase, on a nano model over the host training mesh.  Run it via
``python -m repro.analysis audit`` (which forces a multi-device host so
the mesh is not degenerate).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Sequence

PyTree = Any

COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
    "collective-broadcast",
)

# `all-reduce(`, `all-reduce-start(`; never `all-reduce-done(` (the async
# completion carries no payload of its own).
_OP_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|\S+)\s+(?P<kind>%s)(?:-start)?\("
    % "|".join(COLLECTIVE_KINDS)
)

_SHAPE_RE = re.compile(r"(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def _shape_bytes(shape: str) -> int:
    """Payload bytes of an HLO shape string (tuples sum their components)."""
    total = 0
    for m in _SHAPE_RE.finditer(shape):
        n = 1
        dims = m.group("dims")
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(m.group("dtype"), 4)
    return total


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    kind: str    # canonical kind, e.g. "all-reduce"
    shape: str   # HLO result shape text, e.g. "f32[2,64,16]{2,1,0}"
    bytes: int   # payload bytes of the result
    line: int    # 1-based line in the HLO text


def parse_collectives(hlo_text: str) -> list[CollectiveOp]:
    """Every collective op in a compiled HLO module, with result shapes."""
    ops = []
    for i, line in enumerate(hlo_text.splitlines(), start=1):
        m = _OP_RE.search(line)
        if m:
            shape = m.group("shape")
            ops.append(CollectiveOp(kind=m.group("kind"), shape=shape,
                                    bytes=_shape_bytes(shape), line=i))
    return ops


# ---------------------------------------------------------------------------
# Relayout: what the compiler does to the state beyond the program's math
# ---------------------------------------------------------------------------

# `%name = shape op(operands)`; the shape may be a tuple
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\(.*?\)|\S+)\s+(?P<op>[\w\-]+)\((?P<args>[^)]*)\)")
_LAYOUT_RE = re.compile(r"\{(?P<order>[\d,]*)")
# ops that hand an entry parameter's buffer on unchanged
_PASS_THROUGH = ("bitcast", "copy-start", "copy-done")
_KERNEL_CALL = 'custom_call_target="tpu_custom_call"'
_OP_NAME_RE = re.compile(r'op_name="(?P<op_name>[^"]*)"')


def relayout_report(hlo_text: str) -> dict:
    """Three counts of a compiled module that the program's math never
    asks for:

    * ``remat_clones``: instructions that XLA's memory rematerialization
      pass cloned (it names them ``<name>.remat<N>``) — values recomputed
      because the program did not fit in memory as scheduled;
    * ``kernel_recomputes``: Pallas kernel calls (``tpu_custom_call``)
      inside JAX's ``rematted_computation`` — a kernel's forward run again
      in the backward because its checkpoint saved none of its outputs;
    * ``state_copy_bytes`` (and ``state_copies``): bytes of the entry
      computation's ``copy`` instructions that change the layout of an
      entry parameter, reached directly or through a bitcast or an async
      copy — state re-laid out at every call.
    """
    remat = set()
    recomputes = 0
    entry, params, copies = False, {}, []
    for line in hlo_text.splitlines():
        if _KERNEL_CALL in line:
            op_name = _OP_NAME_RE.search(line)
            if op_name and "rematted_computation" in op_name.group("op_name"):
                recomputes += 1
        if line.startswith("ENTRY"):
            entry = True
            continue
        if entry and line.startswith("}"):
            entry = False
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, op = m.group("name"), m.group("op")
        if ".remat" in name:
            remat.add(name)
        if not entry:
            continue
        operand = m.group("args").split(",")[0].strip().lstrip("%")
        if op == "parameter":
            params[name] = m.group("shape")
        elif op in _PASS_THROUGH and operand in params:
            params[name] = params[operand]
        elif op == "copy" and operand in params:
            copies.append((m.group("shape"), params[operand]))

    def order(shape):
        found = _LAYOUT_RE.search(shape.split("]", 1)[-1])
        return found.group("order") if found else ""

    moved = [shape for shape, src in copies if order(shape) != order(src)]
    return {"remat_clones": len(remat), "kernel_recomputes": recomputes,
            "state_copies": len(moved),
            "state_copy_bytes": sum(_shape_bytes(s) for s in moved)}


# ---------------------------------------------------------------------------
# Budgets
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CollectiveBudget:
    """Per-phase ceiling on the collectives a compiled step may contain."""

    phase: str
    max_reduce_ops: int
    max_gather_ops: int
    max_reduce_bytes: int
    max_gather_bytes: int
    reduce_class: tuple = ("all-reduce", "reduce-scatter")
    gather_class: tuple = ("all-gather",)

    @classmethod
    def for_phase(cls, phase: str, params: PyTree,
                  n_metric_reductions: int = 2) -> "CollectiveBudget":
        """Derive the budget from the analytic comm model for a live pytree.

        ``params``: the global buffer pytree (x0) the phase moves —
        ``n_param_leaves`` and the payload bytes come from it (reductions
        run in the f32 momentum dtype, so the payload floor is 4 B/elem).
        """
        from repro.analysis.comm import phase_collective_budget

        import jax

        leaves = jax.tree.leaves(params)
        payload = sum(l.size * max(4, getattr(l.dtype, "itemsize", 4))
                      for l in leaves)
        raw = phase_collective_budget(
            phase, n_param_leaves=len(leaves), payload_bytes=payload,
            n_metric_reductions=n_metric_reductions)
        return cls(
            phase=raw["phase"],
            max_reduce_ops=raw["max_reduce_ops"],
            max_gather_ops=raw["max_gather_ops"],
            max_reduce_bytes=raw["max_reduce_bytes"],
            max_gather_bytes=raw["max_gather_bytes"],
            reduce_class=tuple(raw["reduce_class"]),
            gather_class=tuple(raw["gather_class"]),
        )


@dataclasses.dataclass
class AuditReport:
    name: str
    budget: CollectiveBudget
    ops: list
    violations: list
    relayout: dict = dataclasses.field(default_factory=dict)

    @property
    def passed(self) -> bool:
        return not self.violations

    @property
    def counts(self) -> dict:
        c: dict = {}
        for op in self.ops:
            c[op.kind] = c.get(op.kind, 0) + 1
        return c

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "phase": self.budget.phase,
            "passed": self.passed,
            "counts": self.counts,
            "reduce_bytes": sum(o.bytes for o in self.ops
                                if o.kind in self.budget.reduce_class),
            "gather_bytes": sum(o.bytes for o in self.ops
                                if o.kind in self.budget.gather_class),
            "budget": dataclasses.asdict(self.budget),
            "violations": list(self.violations),
            "relayout": dict(self.relayout),
            "ops": [dataclasses.asdict(o) for o in self.ops],
        }


def audit_text(hlo_text: str, budget: CollectiveBudget,
               name: str = "step") -> AuditReport:
    """Check compiled HLO text against a budget; returns the full report."""
    ops = parse_collectives(hlo_text)
    viol = []
    reduce_ops = [o for o in ops if o.kind in budget.reduce_class]
    gather_ops = [o for o in ops if o.kind in budget.gather_class]
    allowed = set(budget.reduce_class) | set(budget.gather_class)
    for o in ops:
        if o.kind not in allowed:
            viol.append(
                f"forbidden collective {o.kind} {o.shape} at HLO line {o.line}")
    if len(reduce_ops) > budget.max_reduce_ops:
        viol.append(
            f"{len(reduce_ops)} reduction ops ({'/'.join(budget.reduce_class)})"
            f" exceed the budget of {budget.max_reduce_ops}"
            " — a stray reduction beyond the phase's "
            f"{'single logical round' if budget.max_reduce_ops else 'zero rounds'}")
    if len(gather_ops) > budget.max_gather_ops:
        viol.append(
            f"{len(gather_ops)} gather ops exceed the budget of "
            f"{budget.max_gather_ops}")
    rbytes = sum(o.bytes for o in reduce_ops)
    gbytes = sum(o.bytes for o in gather_ops)
    if rbytes > budget.max_reduce_bytes:
        viol.append(
            f"reduction payload {rbytes} B exceeds the budget of "
            f"{budget.max_reduce_bytes} B (analytic model x slack)")
    if gbytes > budget.max_gather_bytes:
        viol.append(
            f"gather payload {gbytes} B exceeds the budget of "
            f"{budget.max_gather_bytes} B")
    return AuditReport(name=name, budget=budget, ops=ops, violations=viol,
                       relayout=relayout_report(hlo_text))


def audit_jitted(fn, args: Sequence, budget: CollectiveBudget,
                 name: str = "step") -> AuditReport:
    """Lower ``jax.jit(fn)(*args)`` to compiled HLO and audit it."""
    import jax

    text = jax.jit(fn).lower(*args).compile().as_text()
    return audit_text(text, budget, name=name)


# ---------------------------------------------------------------------------
# The standard audit matrix (the CI gate)
# ---------------------------------------------------------------------------

def standard_audit(n_workers: int = 4, tau: int = 2,
                   self_test: bool = False) -> list[AuditReport]:
    """Audit the dense, device-parallel, and ZeRO-sharded outer steps plus
    the bare local phase of a nano model on the host training mesh.

    ``self_test`` appends a deliberately-planted extra all-reduce variant
    that MUST fail — proof the auditor is not vacuously passing.

    Meaningful only on a multi-device host (the degenerate worker=1 mesh
    compiles no collectives at all); the CLI forces the device count before
    jax is imported and flags a degenerate run in the report.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.configs import NANO
    from repro.core import (DSMConfig, constant, dsm_init, get_base_optimizer,
                            make_dsm_step, make_local_phase)
    from repro.core.workers import worker_pspecs
    from repro.data.pipeline import MarkovCorpus, dsm_batches
    from repro.launch.mesh import host_training_mesh
    from repro.models import transformer as T

    def loss(p, mb):
        return T.loss_fn(p, mb, NANO, remat=False)

    base = get_base_optimizer("adamw")
    sched = constant(2e-2)
    batch = jax.tree.map(jnp.asarray, next(dsm_batches(
        MarkovCorpus(NANO.vocab_size, seed=1), n_workers, tau, 1, 2, 32,
        seed=3)))
    params = T.init_params(jax.random.PRNGKey(3), NANO)
    mesh = host_training_mesh(n_workers)

    variants = [
        # name, device_parallel_local, zero_sharded, mesh, phase
        ("dense", False, False, None, "local"),
        ("device_parallel", True, False, mesh, "global_dense"),
        ("zero_sharded", True, True, mesh, "global_zero"),
    ]
    reports = []
    for name, dp, zs, m, phase in variants:
        cfg = DSMConfig(tau=tau, zero_sharded=zs, device_parallel_local=dp)
        step = make_dsm_step(loss, base, cfg, sched, mesh=m)
        state = dsm_init(params, base, n_workers, mesh=m, global_sharded=zs)
        budget = CollectiveBudget.for_phase(phase, state.x0)
        reports.append(audit_jitted(step, (state, batch), budget, name=name))

    # the bare local phase: ZERO collectives by construction
    lp = make_local_phase(loss, base, accum=True, device_parallel=True,
                          mesh=mesh)
    state = dsm_init(params, base, n_workers, mesh=mesh, global_sharded=False)
    budget = CollectiveBudget.for_phase("local", state.x0)
    reports.append(audit_jitted(
        lp, (state.params, state.base_state, batch, jnp.float32(2e-2),
             jnp.int32(0)),
        budget, name="local_phase"))

    # the TRAINER-built instrumented step (build_algorithm wires the obs
    # metric pack into the outer step): must fit the SAME global_zero
    # budget as the bare zero_sharded step — the proof that observability
    # added no collectives beyond the audited allowance
    from repro.train.trainer import TrainSettings, build_algorithm

    ts = TrainSettings(algorithm="dsm", n_workers=n_workers, tau=tau,
                       steps=4, zero_sharded=True,
                       device_parallel_local=True)
    t_init, t_step, _, _ = build_algorithm(loss, ts, mesh=mesh)
    t_state = t_init(params, n_workers)

    def instrumented(st, b):
        return t_step(st, b, None, None)

    budget = CollectiveBudget.for_phase("global_zero", t_state.x0)
    reports.append(audit_jitted(instrumented, (t_state, batch), budget,
                                name="trainer_instrumented_zero"))

    if self_test:
        # plant one extra all-reduce of every param leaf on top of the
        # device-parallel step: the budget MUST flag it
        cfg = DSMConfig(tau=tau, device_parallel_local=True)
        step = make_dsm_step(loss, base, cfg, sched, mesh=mesh)
        state = dsm_init(params, base, n_workers, mesh=mesh,
                         global_sharded=False)

        def psum_workers(tree):
            return jax.shard_map(
                lambda t: jax.tree.map(
                    lambda x: jax.lax.psum(x, "worker"), t),
                mesh=mesh, in_specs=(worker_pspecs(tree),), out_specs=P(),
                check_vma=False)(tree)

        def planted(state, batch):
            new_state, metrics = step(state, batch)
            extra = psum_workers(new_state.params)
            bias = sum(jnp.sum(l) * 0.0 for l in jax.tree.leaves(extra))
            return new_state, dict(metrics, planted=metrics["loss"] + bias)

        budget = CollectiveBudget.for_phase("global_dense", state.x0)
        reports.append(audit_jitted(planted, (state, batch), budget,
                                    name="self_test_planted_all_reduce"))
    return reports
