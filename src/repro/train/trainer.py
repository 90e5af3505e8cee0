"""Training harness: runs any algorithm (DSM or baseline) on any ModelConfig.

This is the engine behind ``python -m repro.launch.train``, the
paper-table analogs (``examples/paper_tables.py``), the other runnable
examples and ``chip_smoke.py``.  It runs on whatever devices JAX finds: TPU chips, or the
CPU for tests.  Workers are a leading W axis, vmapped on one device or
shard_mapped one per device (``device_parallel_local``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import IDX as METRIC_IDX, METRIC_NAMES

from repro.core import (
    DSMConfig,
    cosine_with_warmup,
    constant,
    dsm_init,
    get_base_optimizer,
    make_dsm_step,
)
from repro.core import baselines as BL
from repro.data.pipeline import MarkovCorpus, dsm_batches, eval_batch
from repro.models import transformer as T

ALGORITHMS = (
    "dsm", "slowmo", "signed_slowmo", "lookahead", "signed_lookahead",
    "global_adamw", "local_avg", "perstep", "mv_signsgd",
)


@dataclasses.dataclass
class TrainSettings:
    algorithm: str = "dsm"
    base_opt: str = "adamw"
    n_workers: int = 8
    tau: int = 12
    steps: int = 60                 # outer steps
    b_micro: int = 4
    seq: int = 128
    peak_lr: float = 1e-3
    warmup: int = 24
    schedule: str = "cosine"
    global_lr: float = 1.0          # eta (DSM) / alpha (SlowMo)
    slow_beta: float = 0.5          # SlowMo / lookahead momentum
    dsm_beta1: float = 0.95
    dsm_beta2: float = 0.98
    dsm_wd: float = 0.1
    sign_mode: str = "sign"
    seed: int = 0
    remat: bool = False             # recompute each layer's activations in
    #                                 the backward pass (TopologyConfig.remat)
    eval_every: int = 10
    eval_batch: int = 16
    heterogeneous: bool = True
    use_kernel: bool = False
    zero_sharded: bool = False      # ZeRO-sharded global step over local devices
    device_parallel_local: bool = False  # shard_map local phase over "worker"
    # --- robustness (docs/fault_tolerance.md) ---
    faults: Any = None              # FaultPlan | FaultSpec | spec str, e.g.
    #                                 "drop=0.25,straggle=0.1,nan=0.05,seed=0"
    mask_nonfinite: bool = False    # survivor-aware mean w/o injection (DSM)
    guard_nonfinite: bool = False   # reject rounds with NaN/inf in the state
    guard_spike_factor: float = 0.0  # reject rounds w/ loss > factor*EMA (0=off)
    guard_ema_beta: float = 0.9     # loss EMA for spike detection
    guard_patience: int = 5         # K consecutive bad rounds -> rollback
    guard_max_rollbacks: int = 2    # bounded retry; exceeded -> RuntimeError
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0       # outer steps; <=0 -> max(1, steps // 5)
    checkpoint_keep: int = 3        # rotated retention
    resume: bool = False            # auto-resume from checkpoint_dir's latest
    # --- runtime sanitizers (docs/analysis.md) ---
    sanitize: bool = False          # transfer guard around the hot loop +
    #                                 recompilation counter (steady-state outer
    #                                 step must compile exactly once)
    sanitize_nans: bool = False     # jax_debug_nans over the whole loop (the
    #                                 chaos tier: masked NaNs must never reach
    #                                 a jit output)
    # --- observability (docs/observability.md) ---
    run_dir: Optional[str] = None   # obs run directory: manifest.json /
    #                                 events.jsonl / scalars.csv / profile/
    log_every: int = 0              # metric flush + log cadence in outer
    #                                 steps; <=0 -> eval_every
    profile_steps: Optional[str] = None  # "A:B": jax.profiler.trace window
    #                                 (inclusive outer-step range)


def _schedule(s: TrainSettings):
    if s.schedule == "cosine":
        return cosine_with_warmup(s.peak_lr, s.steps, warmup_steps=s.warmup)
    return constant(s.peak_lr)


def build_algorithm(loss_fn, s: TrainSettings, mesh=None):
    """Returns (init(params, n_workers) -> state, step(state, batch[, rng]),
    eval_params(state) -> params, comm_multiplier).

    ``mesh``: optional ("worker", "zero", "model") mesh; with
    ``s.zero_sharded`` the DSM global step runs ZeRO-sharded on it, and with
    ``s.device_parallel_local`` the local phase of DSM / the local-step
    baselines runs shard_mapped over its worker axis.
    """
    base = get_base_optimizer(s.base_opt)
    sched = _schedule(s)
    local_kw = dict(device_parallel=s.device_parallel_local, mesh=mesh)

    if s.algorithm in ("dsm", "signed_lookahead"):
        cfg = DSMConfig(
            tau=s.tau, global_lr=s.global_lr, beta1=s.dsm_beta1,
            beta2=s.dsm_beta2, weight_decay=s.dsm_wd, sign_mode=s.sign_mode,
            sign_bound=float(s.tau), use_kernel=s.use_kernel,
            zero_sharded=s.zero_sharded,
            device_parallel_local=s.device_parallel_local,
            mask_nonfinite=s.mask_nonfinite,
        )
        if s.algorithm == "signed_lookahead":
            cfg = dataclasses.replace(cfg, beta1=s.slow_beta, beta2=s.slow_beta,
                                      weight_decay=0.0)
        step = make_dsm_step(loss_fn, base, cfg, sched, mesh=mesh)
        needs_rng = s.sign_mode != "sign"

        def init(params, n_workers):
            return dsm_init(params, base, n_workers, mesh=mesh,
                            global_sharded=s.zero_sharded)

        def stepper(state, batch, rng, faults=None):
            return step(state, batch, rng if needs_rng else None, faults)

        return init, stepper, lambda st: st.x0, 1.0

    if s.algorithm in ("slowmo", "signed_slowmo", "lookahead", "global_adamw",
                       "local_avg"):
        maker = {
            "slowmo": lambda: BL.slowmo(loss_fn, base, s.tau, sched,
                                        beta=s.slow_beta, alpha=s.global_lr,
                                        **local_kw),
            "signed_slowmo": lambda: BL.signed_slowmo(loss_fn, base, s.tau, sched,
                                                      beta=s.slow_beta, eta=s.global_lr,
                                                      **local_kw),
            "lookahead": lambda: BL.lookahead(loss_fn, base, s.tau, sched,
                                              beta=s.slow_beta, eta=s.global_lr,
                                              **local_kw),
            "global_adamw": lambda: BL.global_adamw(loss_fn, base, s.tau, sched,
                                                    eta=s.global_lr, **local_kw),
            "local_avg": lambda: BL.local_avg(loss_fn, base, s.tau, sched,
                                              **local_kw),
        }[s.algorithm]
        init, step = maker()
        return init, (lambda st, b, rng, faults=None: step(st, b)), (lambda st: st.x0), 1.0

    if s.algorithm == "perstep":
        init, step = BL.make_perstep_dp_step(loss_fn, base, s.tau, sched)
        return (init, (lambda st, b, rng, faults=None: step(st, b)),
                (lambda st: st.params), float(s.tau))

    if s.algorithm == "mv_signsgd":
        init, step = BL.make_mv_signsgd_step(
            loss_fn, s.tau, gamma=s.peak_lr, eta=s.global_lr * s.peak_lr,
            beta=s.slow_beta, bound=1.0,
        )
        return init, (lambda st, b, rng, faults=None: step(st, b, rng)), (lambda st: st.x), 1.0

    raise ValueError(f"unknown algorithm {s.algorithm!r}")


_DSM_FAMILY = ("dsm", "signed_lookahead")


def _decode_metrics_row(fetched: dict) -> np.ndarray:
    """Host-side: one scalars.csv row from a fetched per-round metrics dict.

    DSM-family steps carry the full on-device pack; baseline algorithms get
    the loss / gamma (+ guard verdict) slots with NaN elsewhere.
    """
    if "pack" in fetched:
        return np.asarray(fetched["pack"], np.float64).reshape(-1)
    row = np.full((len(METRIC_NAMES),), np.nan)
    for name in ("loss", "last_loss", "gamma", "guard_ok"):
        if name in fetched:
            row[METRIC_IDX[name]] = float(np.asarray(fetched[name]))
    return row


def _resolve_fault_plan(s: TrainSettings):
    if not s.faults:
        return None
    from repro.robustness.faults import FaultPlan

    if s.algorithm not in _DSM_FAMILY:
        raise ValueError(
            "fault injection needs the survivor-aware DSM step family; "
            f"got algorithm={s.algorithm!r}")
    if isinstance(s.faults, FaultPlan):
        return s.faults
    return FaultPlan.from_spec(s.faults, s.n_workers, s.steps)


def run_training(cfg, s: TrainSettings, corpus=None, log: Optional[Callable] = None):
    """Train; returns dict(history, eval_losses, final_eval, tokens, comm_rounds).

    Robustness settings (docs/fault_tolerance.md):

      * ``faults``          — deterministic seeded fault injection (DSM only).
      * ``guard_nonfinite`` / ``guard_spike_factor`` — skip-round guards; with
        ``checkpoint_dir`` set, ``guard_patience`` consecutive bad rounds roll
        the run back to the last checkpoint, at most ``guard_max_rollbacks``
        times before raising RuntimeError.
      * ``checkpoint_dir`` / ``checkpoint_every`` / ``resume`` — atomic rotated
        checkpoints of the FULL training state (optimizer state, PRNG key,
        guard state, metric history, data position via the step index), so a
        killed run restarts bit-exactly from the last complete checkpoint.

    Per-round metrics stay on device (async) and are only fetched at
    eval/log/checkpoint points; ``history`` contents are unchanged.
    """
    corpus = corpus or MarkovCorpus(cfg.vocab_size, seed=1)
    key = jax.random.PRNGKey(s.seed)
    params = T.init_params(key, cfg)

    def loss_fn(p, mb):
        return T.loss_fn(p, mb, cfg, remat=s.remat)

    # ONE mesh construction for every mesh-consuming feature: zero_sharded,
    # device_parallel_local, and whatever comes next all share this path
    # (host_training_mesh raises a clear error when n_workers does not
    # divide the device grid).
    mesh = None
    if s.zero_sharded or s.device_parallel_local:
        from repro.launch.mesh import host_training_mesh

        mesh = host_training_mesh(s.n_workers)

    init, step, eval_params, comm_mult = build_algorithm(loss_fn, s, mesh=mesh)
    state = init(params, s.n_workers)

    plan = _resolve_fault_plan(s)
    guards_on = s.guard_nonfinite or s.guard_spike_factor > 0
    if guards_on:
        from repro.robustness import guards as G

        guard = G.init_guard()
        step_fn = G.make_guarded_step(
            step, nonfinite=s.guard_nonfinite,
            spike_factor=s.guard_spike_factor, ema_beta=s.guard_ema_beta)
    else:
        guard = None
        step_fn = step
    # distinct compile-log names so the sanitizer's recompilation counter can
    # tell the outer step from the (also jitted) eval loss
    step_fn.__name__ = "train_step"
    # the state (and guard state) is donated: the outer step updates it in
    # place, so a full-width model holds one copy of it, not two
    jstep = jax.jit(step_fn, donate_argnums=(0, 1) if guards_on else (0,))

    def eval_loss(p, b):
        return loss_fn(p, b)

    eval_loss_fn = jax.jit(eval_loss)

    ckpt_on = bool(s.checkpoint_dir)
    ckpt_every = s.checkpoint_every if s.checkpoint_every > 0 else max(1, s.steps // 5)
    rollback_on = ckpt_on and guards_on and s.guard_patience > 0
    if ckpt_on:
        from repro.checkpoint import checkpoint as CK

    def ckpt_tree(state, guard, key):
        tree = {"state": state, "key": key}
        if guard is not None:
            tree["guard"] = guard
        return tree

    def reshard(state):
        # npz restore lands on the default device; put DSM state back into
        # its mesh layout so the compiled step consumes it shard-in-place
        if mesh is not None and s.algorithm in _DSM_FAMILY:
            from repro.distributed import zero as Z

            return Z.shard_dsm_state(state, mesh, global_sharded=s.zero_sharded)
        return state

    def make_batches(skip: int = 0):
        # data-pipeline position == outer-step index: the stream is a pure
        # function of (corpus, seed), so resume replays `skip` rounds
        it = dsm_batches(
            corpus, s.n_workers, s.tau, 1, s.b_micro, s.seq,
            seed=s.seed, heterogeneous=s.heterogeneous,
        )
        for _ in range(skip):
            next(it)
        return it

    history, evals = [], []
    start_step, rollbacks = 0, 0
    if s.resume and ckpt_on:
        restored = CK.restore_latest(s.checkpoint_dir, ckpt_tree(state, guard, key))
        if restored is not None:
            tree, start_step, extra = restored
            state, key = reshard(tree["state"]), tree["key"]
            if guards_on:
                guard = tree["guard"]
            history = [float(x) for x in extra.get("history", [])]  # resume = a sync point
            evals = [tuple(e) for e in extra.get("evals", [])]
            # cumulative guard counters survive the restart (the guard state
            # itself is restored bit-exact; the rollback count lives here)
            rollbacks = int(extra.get("rollbacks", 0))
            if log:
                log(f"resumed from checkpoint at step {start_step}")
    if ckpt_on and start_step == 0:
        # step-0 checkpoint: the rollback target always exists
        CK.save_checkpoint(s.checkpoint_dir, ckpt_tree(state, guard, key), 0,
                           keep=s.checkpoint_keep,
                           extra={"history": [], "evals": [],
                                  "rollbacks": 0, "skipped_rounds": 0})

    ev_batch = eval_batch(corpus, s.eval_batch, s.seq)
    needs_accum = s.algorithm in _DSM_FAMILY

    def prep_batch(raw):
        if not needs_accum:
            raw = {k: v[:, :, 0] for k, v in raw.items()}
        return jax.tree.map(jnp.asarray, raw)

    # --- observability (docs/observability.md): run sinks + comm ledger +
    # phase spans + profiler window.  Per-round metrics stay on device in
    # `pending`; ALL host reads happen in flush_metrics() at the sanctioned
    # sync points (log/eval/checkpoint/rollback), outside the transfer
    # guard.  The comm-ledger lowering is itself a compile, so it runs
    # BEFORE the sanitizers arm their recompilation counter. ---
    obs_on = bool(s.run_dir)
    writer = None
    profile = None
    phase_totals = None
    log_every = s.log_every if s.log_every > 0 else s.eval_every
    pending: list = []  # (outer step number, on-device metrics dict)
    if obs_on:
        from repro.obs import sinks as OS
        from repro.obs import tracing as OT
        from repro.obs.ledger import compile_time_ledger

        manifest = OS.build_manifest(
            run_name=os.path.basename(os.path.normpath(s.run_dir)),
            settings=s, model_cfg=cfg, mesh=mesh)
        writer = OS.RunWriter(s.run_dir, manifest, resume=start_step > 0)
        phase_totals = OT.PhaseTotals()
        profile = OT.ProfileWindow(OT.parse_profile_steps(s.profile_steps),
                                   os.path.join(s.run_dir, "profile"))
        if start_step > 0:
            writer.event("resumed", step=start_step)
        probe_batch = prep_batch(next(make_batches(start_step)))
        probe_key = jax.random.PRNGKey(s.seed)
        probe_fr = plan.round(start_step) if plan is not None else None
        probe_args = ((state, guard, probe_batch, probe_key, probe_fr)
                      if guards_on
                      else (state, probe_batch, probe_key, probe_fr))
        ledger = compile_time_ledger(
            step_fn, probe_args,
            params=eval_params(state),
            algo="dsm" if s.algorithm in _DSM_FAMILY else s.algorithm,
            tau=s.tau,
            phase="global_zero" if s.zero_sharded else "global_dense",
            mesh=mesh, name="train_step")
        writer.event("comm_ledger", **ledger)

    def flush_metrics():
        """ONE device_get for every pending round; returns the last decoded
        scalar row (dict) or None.  Closes the running train-window span —
        the fetch is the fence."""
        nonlocal window_t0, window_steps
        if not pending:
            return None
        with jax.profiler.TraceAnnotation("repro.flush"):
            fetched = jax.device_get([m for _, m in pending])
        if obs_on and window_steps:
            dt = time.monotonic() - window_t0
            phase_totals.add("train_window", dt, n=window_steps)
            writer.span("train_window", dt, n=window_steps,
                        step=pending[-1][0])
        row = None
        for (step_no, _), m in zip(pending, fetched):
            vals = _decode_metrics_row(m)
            if writer is not None:
                writer.metrics_row(step_no, vals)
            row = dict(zip(METRIC_NAMES, (float(v) for v in vals)))
        pending.clear()
        window_steps = 0
        window_t0 = time.monotonic()
        return row

    # --- runtime sanitizers (docs/analysis.md): recompilation counter over
    # the whole loop, debug_nans for the chaos tier, transfer guard around
    # each step call (the eval/log/checkpoint host reads below stay OUTSIDE
    # the guard — those are the sanctioned sync points) ---
    recompiles = None
    step_guard = contextlib.nullcontext
    loop_ctx = contextlib.ExitStack()
    if s.sanitize or s.sanitize_nans:
        from repro.analysis import sanitize as SAN

        if s.sanitize:
            recompiles = loop_ctx.enter_context(SAN.RecompilationCounter())
            step_guard = SAN.no_implicit_host_sync
        if s.sanitize_nans:
            loop_ctx.enter_context(SAN.debug_nans())

    def ckpt_extra():
        return {"history": history, "evals": [list(e) for e in evals],
                "rollbacks": rollbacks,
                "skipped_rounds": int(guard.skipped) if guards_on else 0}

    batches = make_batches(start_step)
    t = start_step
    t0 = time.time()
    window_t0 = time.monotonic()
    window_steps = 0
    last_row = None
    try:
        while t < s.steps:
            if profile is not None:
                profile.tick(t)
            # host annotations on the profiler's clock (docs/observability.md
            # section 3): one step annotation per outer step, and within it
            # the input wait, the dispatch and the metric flush
            with jax.profiler.StepTraceAnnotation("repro.step", step_num=t):
                key, sub = jax.random.split(key)
                with jax.profiler.TraceAnnotation("repro.input"):
                    batch = prep_batch(next(batches))
                fr = plan.round(t) if plan is not None else None
                with step_guard(), jax.profiler.TraceAnnotation("repro.dispatch"):
                    if guards_on:
                        state, guard, metrics = jstep(state, guard, batch, sub, fr)
                    else:
                        state, metrics = jstep(state, batch, sub, fr)
                    # device scalars: fetched only at eval/log/checkpoint points
                    # (the old float() here blocked on the device every outer step)
                    history.append(metrics["loss"])
                    pending.append((t + 1, metrics))
                    window_steps += 1

                if rollback_on and int(guard.bad_streak) >= s.guard_patience:
                    # the ONE per-round host read rollback requires (a scalar i32)
                    row = flush_metrics()  # rejected rounds are still observations
                    last_row = row or last_row
                    if rollbacks >= s.guard_max_rollbacks:
                        raise RuntimeError(
                            f"training diverged: {int(guard.bad_streak)} consecutive "
                            f"bad rounds at step {t} after {rollbacks} rollbacks")
                    rollbacks += 1
                    tree, t_ck, extra = CK.restore_latest(
                        s.checkpoint_dir, ckpt_tree(state, guard, key))
                    state, key = reshard(tree["state"]), tree["key"]
                    guard = tree["guard"]._replace(bad_streak=jnp.zeros((), jnp.int32))
                    # rollback = a sync point
                    history = [float(x) for x in extra.get("history", [])]
                    evals = [tuple(e) for e in extra.get("evals", [])]
                    if writer is not None:
                        writer.event("rollback", step=t, to_step=t_ck, n=rollbacks)
                    if log:
                        log(f"rollback #{rollbacks}: step {t} -> checkpoint at {t_ck}")
                    batches = make_batches(t_ck)
                    t = t_ck
                    window_t0 = time.monotonic()
                    continue

                t += 1
                is_eval = t % s.eval_every == 0 or t == s.steps
                is_log = t % log_every == 0
                did_ckpt = ckpt_on and t % ckpt_every == 0
                if is_eval or is_log or did_ckpt:
                    # metric flush: ONE async fetch covering every round since
                    # the last sync point, with a step-consistent row to log
                    row = flush_metrics()
                    last_row = row or last_row
                if is_eval:
                    if obs_on:
                        with OT.Span("eval") as sp:  # float() is the fence
                            el = float(eval_loss_fn(eval_params(state), ev_batch))
                        phase_totals.add("eval", sp.seconds)
                        writer.span("eval", sp.seconds, step=t)
                        writer.event("eval", step=t, eval_loss=el)
                    else:
                        el = float(eval_loss_fn(eval_params(state), ev_batch))
                    evals.append((t, el))
                    if log:
                        train = last_row["loss"] if last_row else float(history[-1])
                        log(f"step {t:4d} train={train:.4f} eval={el:.4f}")
                elif is_log and log and last_row is not None:
                    log(f"step {t:4d} train={last_row['loss']:.4f}")
                if did_ckpt:
                    history = [float(x) for x in history]  # checkpoint = a sync point
                    if obs_on:
                        with OT.Span("checkpoint", state) as sp:
                            CK.save_checkpoint(
                                s.checkpoint_dir, ckpt_tree(state, guard, key), t,
                                keep=s.checkpoint_keep, extra=ckpt_extra())
                        phase_totals.add("checkpoint", sp.seconds)
                        writer.span("checkpoint", sp.seconds, step=t)
                        writer.event("checkpoint", step=t)
                    else:
                        CK.save_checkpoint(
                            s.checkpoint_dir, ckpt_tree(state, guard, key), t,
                            keep=s.checkpoint_keep, extra=ckpt_extra())
                if obs_on and (is_eval or is_log or did_ckpt):
                    # eval/checkpoint time must not leak into the next train window
                    window_t0 = time.monotonic()
    finally:
        loop_ctx.close()
        if profile is not None:
            profile.close()

    if recompiles is not None:
        # steady state: the outer step compiles EXACTLY once; a second
        # compile means a shape/dtype-polymorphic step (SanitizeError)
        recompiles.assert_steady_state("train_step", max_compiles=1)

    wall = time.time() - t0
    tokens = s.steps * s.tau * s.n_workers * s.b_micro * s.seq
    last_row = flush_metrics() or last_row  # tail rounds (early exits)
    phase_ms = None
    if obs_on:
        steps_done = t - start_step
        mem = OT.device_memory_stats()
        if mem is not None:
            writer.event("device_memory", stats=mem)
        writer.event(
            "finished", steps=steps_done, wall_s=wall,
            steps_per_s=steps_done / wall if wall > 0 else None,
            tokens=tokens,
            tokens_per_s=tokens / wall if wall > 0 else None,
            skipped_rounds=int(guard.skipped) if guards_on else 0,
            rollbacks=rollbacks)
        phase_ms = phase_totals.as_dict()
        writer.close()

    history = [float(x) for x in history]
    return {
        "history": history,
        "eval_losses": evals,
        "final_eval": evals[-1][1] if evals else float("nan"),
        "tokens": tokens,
        "comm_rounds": int(s.steps * comm_mult),
        "wall_s": wall,
        "skipped_rounds": int(guard.skipped) if guards_on else 0,
        "rollbacks": rollbacks,
        "step_compiles": recompiles.count("train_step") if recompiles else None,
        "run_dir": s.run_dir,
        "phase_ms": phase_ms,
        "final_metrics": last_row,
        "state": state,
    }
