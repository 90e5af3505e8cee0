"""Chaos suite: fault injection, survivor-aware DSM, guards, kill + resume.

Acceptance criteria (ISSUE 8):
  (a) a run with 25% seeded worker dropout reaches a final eval loss within
      10% of the fault-free run;
  (b) kill-at-round-k + resume reproduces the uninterrupted run's x0
      bit-exactly at the same round;
  (c) injected NaN contributions are masked and never propagate into x0 or
      m (jnp.isfinite over the FULL state every round).

The genuine kill test (SIGKILL mid-run, then --resume) forces 8 host
devices and runs the sharded + device-parallel stack, so it lives in the
``multidevice`` tier; everything else is fast-tier.
"""

import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import (
    DSMConfig,
    constant,
    dsm_init,
    make_dsm_step,
    masked_worker_mean,
    sgd,
    worker_finite_mask,
)
from repro.data.pipeline import MarkovCorpus
from repro.robustness.faults import FaultPlan, FaultRound, FaultSpec, apply_faults
from repro.robustness.guards import init_guard, make_guarded_step
from repro.train.trainer import TrainSettings, run_training

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")

NANO = ModelConfig(
    name="nano", family="lm", n_layers=1, d_model=32, n_heads=2,
    n_kv_heads=2, d_ff=64, vocab_size=64, head_dim=16, mlp_gated=False,
    act="gelu", dtype="float32", param_dtype="float32", vocab_pad_to=64,
)


def nano_settings(**kw):
    base = dict(algorithm="dsm", n_workers=4, tau=2, steps=8, b_micro=2,
                seq=32, eval_every=4)
    base.update(kw)
    return TrainSettings(**base)


# ---------------------------------------------------------------------------
# FaultPlan: deterministic, seeded, parseable
# ---------------------------------------------------------------------------

def test_fault_spec_parse():
    spec = FaultSpec.parse("drop=0.25, straggle=0.1, nan=0.05, seed=3")
    assert spec == FaultSpec(p_drop=0.25, p_straggle=0.1, p_corrupt=0.05, seed=3)
    with pytest.raises(ValueError, match="unknown fault key"):
        FaultSpec.parse("explode=1.0")
    with pytest.raises(ValueError, match="bad fault spec"):
        FaultSpec.parse("drop")
    with pytest.raises(ValueError, match="lie in"):
        FaultSpec(p_drop=1.5)


def test_fault_plan_deterministic_and_shapes():
    spec = FaultSpec(p_drop=0.3, p_straggle=0.2, p_corrupt=0.1, seed=11)
    a = FaultPlan(8, 20, spec)
    b = FaultPlan.from_spec("drop=0.3,straggle=0.2,nan=0.1,seed=11", 8, 20)
    np.testing.assert_array_equal(a.drop, b.drop)
    np.testing.assert_array_equal(a.stale, b.stale)
    np.testing.assert_array_equal(a.corrupt, b.corrupt)
    assert a.drop.shape == (20, 8)
    fr = a.round(5)
    assert fr.survivors.shape == (8,) and fr.survivors.dtype == bool
    np.testing.assert_array_equal(np.asarray(fr.survivors), ~a.drop[5])
    # horizon-independent: round t's faults do not depend on plan length
    short = FaultPlan(8, 5, spec)
    np.testing.assert_array_equal(a.drop[:5], short.drop)
    np.testing.assert_array_equal(a.corrupt[:5], short.corrupt)
    # beyond the planned horizon: fault-free
    late = a.round(99)
    assert bool(late.survivors.all()) and not bool(late.corrupt.any())
    assert 0.0 < a.dropped_frac() < 1.0


def test_apply_faults_stale_and_corrupt():
    params_w = {"x": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) + 100.0}
    x0 = {"x": jnp.arange(4, dtype=jnp.float32)}
    fr = FaultRound(
        survivors=jnp.array([True, True, True]),
        stale=jnp.array([False, True, False]),
        corrupt=jnp.array([False, False, True]),
    )
    out = apply_faults(params_w, x0, fr)["x"]
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(params_w["x"][0]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(x0["x"]))
    assert bool(jnp.isnan(out[2]).all())


# ---------------------------------------------------------------------------
# survivor-aware aggregation primitives
# ---------------------------------------------------------------------------

def test_worker_finite_mask():
    tree = {
        "a": jnp.array([[1.0, 2.0], [jnp.nan, 1.0], [3.0, 4.0]]),
        "b": jnp.array([[0.0], [1.0], [jnp.inf]]),
        "ints": jnp.zeros((3, 2), jnp.int32),  # non-float leaves are ignored
    }
    np.testing.assert_array_equal(
        np.asarray(worker_finite_mask(tree)), [True, False, False])


def test_masked_worker_mean_excludes_dropped_and_is_nan_safe():
    p = jnp.array([[2.0, 4.0], [jnp.nan, jnp.nan], [6.0, 8.0]])
    w = jnp.array([1.0, 0.0, 1.0])
    out = masked_worker_mean({"x": p}, w)["x"]
    np.testing.assert_allclose(np.asarray(out), [4.0, 6.0])
    # all-zero weights: returns zeros (the caller applies skip semantics)
    out0 = masked_worker_mean({"x": p}, jnp.zeros(3))["x"]
    assert bool(jnp.isfinite(out0).all())


# ---------------------------------------------------------------------------
# chaos (c): injected NaNs never reach x0 / m — full-state finiteness every
# round, under heavy simultaneous drop + straggle + corruption
# ---------------------------------------------------------------------------

def _quad_problem(d=24, n_workers=4):
    key = jax.random.PRNGKey(7)
    center = jax.random.normal(key, (d,))

    def loss(params, batch):
        tgt = center + batch["noise"]
        return 0.5 * jnp.mean(jnp.sum((params["x"][None] - tgt) ** 2, axis=-1))

    def batch_at(t):
        return {"noise": 0.1 * jax.random.normal(
            jax.random.fold_in(key, t), (n_workers, 2, 1, 4, d))}

    return loss, batch_at, d


@pytest.mark.parametrize("zero_sharded", [False, True])
def test_injected_nans_never_reach_state(zero_sharded):
    loss, batch_at, d = _quad_problem()
    mesh = None
    if zero_sharded:
        from repro.launch.mesh import host_training_mesh

        mesh = host_training_mesh(4)  # degenerate worker=1 mesh on 1 device
    cfg = DSMConfig(tau=2, global_lr=0.7, zero_sharded=zero_sharded)
    step = jax.jit(make_dsm_step(loss, sgd(), cfg, constant(0.05), mesh=mesh))
    state = dsm_init({"x": jnp.zeros((d,))}, sgd(), n_workers=4, mesh=mesh,
                     global_sharded=zero_sharded)
    plan = FaultPlan(4, 12, FaultSpec(p_drop=0.3, p_straggle=0.3,
                                      p_corrupt=0.5, seed=1))
    assert plan.corrupt.any()  # the injection is not vacuous
    for t in range(12):
        state, metrics = step(state, batch_at(t), None, plan.round(t))
        for leaf in jax.tree.leaves(state):
            assert bool(jnp.isfinite(leaf).all()), (t, zero_sharded)
    # ... and the run actually trained (x0 moved despite the chaos)
    assert float(jnp.abs(state.x0["x"]).max()) > 0.0


def test_all_dropped_round_is_skipped_bit_exactly():
    loss, batch_at, d = _quad_problem()
    cfg = DSMConfig(tau=2, global_lr=0.7)
    step = jax.jit(make_dsm_step(loss, sgd(), cfg, constant(0.05)))
    state = dsm_init({"x": jnp.zeros((d,))}, sgd(), n_workers=4)
    state, _ = step(state, batch_at(0), None, FaultPlan(4, 1, FaultSpec()).round(0))
    dead = FaultRound(survivors=jnp.zeros(4, bool), stale=jnp.zeros(4, bool),
                      corrupt=jnp.zeros(4, bool))
    x0_before = np.asarray(state.x0["x"]).copy()
    m_before = np.asarray(state.m["x"]).copy()
    state2, metrics = step(state, batch_at(1), None, dead)
    np.testing.assert_array_equal(np.asarray(state2.x0["x"]), x0_before)
    np.testing.assert_array_equal(np.asarray(state2.m["x"]), m_before)
    assert float(metrics["survivors"]) == 0.0
    assert int(state2.t) == int(state.t) + 1  # the round still elapsed


def test_faulted_zero_sharded_matches_dense():
    """The weights threading through distributed/zero.py reproduces the
    dense masked mean on the (degenerate) mesh."""
    loss, batch_at, d = _quad_problem()
    from repro.launch.mesh import host_training_mesh

    plan = FaultPlan(4, 6, FaultSpec(p_drop=0.4, p_straggle=0.2,
                                     p_corrupt=0.3, seed=9))

    def run(zero_sharded):
        mesh = host_training_mesh(4) if zero_sharded else None
        cfg = DSMConfig(tau=2, global_lr=0.7, zero_sharded=zero_sharded)
        step = jax.jit(make_dsm_step(loss, sgd(), cfg, constant(0.05), mesh=mesh))
        state = dsm_init({"x": jnp.zeros((d,))}, sgd(), n_workers=4, mesh=mesh,
                         global_sharded=zero_sharded)
        for t in range(6):
            state, _ = step(state, batch_at(t), None, plan.round(t))
        return state

    dense, sharded = run(False), run(True)
    np.testing.assert_allclose(np.asarray(sharded.x0["x"]),
                               np.asarray(dense.x0["x"]), rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sharded.m["x"]),
                               np.asarray(dense.m["x"]), rtol=0, atol=1e-6)


def test_faults_require_dsm_family():
    corpus = MarkovCorpus(NANO.vocab_size, branch=4, seed=7)
    with pytest.raises(ValueError, match="DSM step family"):
        run_training(NANO, nano_settings(algorithm="slowmo", steps=2,
                                         faults="drop=0.5"), corpus)


# ---------------------------------------------------------------------------
# chaos (a): 25% dropout still converges (final eval within 10% of clean)
# ---------------------------------------------------------------------------

def test_dropout_run_converges_near_fault_free():
    corpus = MarkovCorpus(NANO.vocab_size, branch=4, seed=7)
    clean = run_training(NANO, nano_settings(steps=16), corpus)
    faulty = run_training(
        NANO, nano_settings(steps=16, faults="drop=0.25,seed=5",
                            guard_nonfinite=True), corpus)
    assert np.isfinite(clean["final_eval"]) and np.isfinite(faulty["final_eval"])
    assert faulty["final_eval"] <= 1.10 * clean["final_eval"], (
        clean["final_eval"], faulty["final_eval"])
    # the guard never fired: dropout alone must not poison the state
    assert faulty["skipped_rounds"] == 0


# ---------------------------------------------------------------------------
# guards: skip-round semantics on spikes and non-finite updates
# ---------------------------------------------------------------------------

def _fake_step(state, batch, rng, faults=None):
    new_state = {"x": state["x"] + 1.0, "m": state["m"] + batch["poison"]}
    return new_state, {"loss": batch["loss"]}


def test_guard_skips_loss_spike_and_recovers():
    gstep = jax.jit(make_guarded_step(_fake_step, nonfinite=True,
                                      spike_factor=2.0, ema_beta=0.5))
    state, guard = {"x": jnp.zeros(3), "m": jnp.zeros(3)}, init_guard()
    losses, oks = [1.0, 1.1, 10.0, 1.0], []
    for loss in losses:
        batch = {"loss": jnp.float32(loss), "poison": jnp.float32(0.0)}
        state, guard, metrics = gstep(state, guard, batch, None, None)
        oks.append(bool(metrics["guard_ok"]))
    assert oks == [True, True, False, True]
    # the spiked round was skipped: only 3 accepted increments
    np.testing.assert_allclose(np.asarray(state["x"]), 3.0)
    assert int(guard.skipped) == 1 and int(guard.bad_streak) == 0
    assert int(guard.seen) == 3


def test_guard_skips_nonfinite_update_and_m_is_untouched():
    gstep = jax.jit(make_guarded_step(_fake_step, nonfinite=True))
    state, guard = {"x": jnp.zeros(3), "m": jnp.zeros(3)}, init_guard()
    batch = {"loss": jnp.float32(1.0), "poison": jnp.float32(jnp.nan)}
    new_state, guard, metrics = gstep(state, guard, batch, None, None)
    assert not bool(metrics["guard_ok"])
    np.testing.assert_array_equal(np.asarray(new_state["m"]),
                                  np.asarray(state["m"]))  # momentum untouched
    np.testing.assert_array_equal(np.asarray(new_state["x"]),
                                  np.asarray(state["x"]))
    assert int(guard.bad_streak) == 1


def test_guard_rollback_is_bounded():
    corpus = MarkovCorpus(NANO.vocab_size, branch=4, seed=7)
    logs = []
    with tempfile.TemporaryDirectory() as d:
        # spike_factor < 1: every round after the first is "bad" by
        # construction, so the run must roll back, retry, and then abort
        with pytest.raises(RuntimeError, match="training diverged"):
            run_training(NANO, nano_settings(
                n_workers=2, guard_spike_factor=0.5, guard_patience=2,
                guard_max_rollbacks=1, checkpoint_dir=d, checkpoint_every=2,
            ), corpus, log=logs.append)
    assert any("rollback #1" in line for line in logs)


# ---------------------------------------------------------------------------
# chaos (b): kill + resume is bit-exact (in-process: stop at k, resume)
# ---------------------------------------------------------------------------

def test_resume_reproduces_uninterrupted_run_bit_exactly():
    corpus = MarkovCorpus(NANO.vocab_size, branch=4, seed=7)
    ref = run_training(NANO, nano_settings(), corpus)
    with tempfile.TemporaryDirectory() as d:
        run_training(NANO, nano_settings(
            steps=4, checkpoint_dir=d, checkpoint_every=2), corpus)
        resumed = run_training(NANO, nano_settings(
            checkpoint_dir=d, checkpoint_every=2, resume=True), corpus)
    for a, b in zip(jax.tree.leaves(ref["state"].x0),
                    jax.tree.leaves(resumed["state"].x0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ref["history"] == resumed["history"]
    assert ref["eval_losses"] == resumed["eval_losses"]


def test_resume_with_faults_replays_the_plan():
    """FaultPlan rounds are indexed by the outer step, so a resumed faulty
    run sees exactly the faults the uninterrupted run saw."""
    corpus = MarkovCorpus(NANO.vocab_size, branch=4, seed=7)
    kw = dict(faults="drop=0.25,nan=0.2,seed=4", guard_nonfinite=True)
    ref = run_training(NANO, nano_settings(**kw), corpus)
    with tempfile.TemporaryDirectory() as d:
        run_training(NANO, nano_settings(
            steps=4, checkpoint_dir=d, checkpoint_every=2, **kw), corpus)
        resumed = run_training(NANO, nano_settings(
            checkpoint_dir=d, checkpoint_every=2, resume=True, **kw), corpus)
    for a, b in zip(jax.tree.leaves(ref["state"].x0),
                    jax.tree.leaves(resumed["state"].x0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# comm accounting under dropout
# ---------------------------------------------------------------------------

def test_comm_accounting_under_dropout():
    from repro.analysis.comm import bytes_per_outer_step

    full = bytes_per_outer_step("gpt2_small", "dsm", tau=12)
    faulty = bytes_per_outer_step("gpt2_small", "dsm", tau=12,
                                  survivor_frac=0.75)
    assert full["survivor_frac"] == 1.0
    assert full["expected_wire_bytes_per_outer"] == full["wire_bytes_per_outer"]
    # dropped workers source nothing: expected fabric traffic scales ...
    assert faulty["expected_wire_bytes_per_outer"] == int(
        round(0.75 * faulty["wire_bytes_per_outer"]))
    # ... but the survivors' round structure does not change
    assert faulty["comm_rounds_per_outer"] == full["comm_rounds_per_outer"]
    assert faulty["wire_bytes_per_outer"] == full["wire_bytes_per_outer"]
    with pytest.raises(ValueError, match="survivor_frac"):
        bytes_per_outer_step("gpt2_small", "dsm", tau=12, survivor_frac=1.5)


# ---------------------------------------------------------------------------
# the genuine kill: SIGKILL a training subprocess mid-run on the 8-device
# sharded + device-parallel + faulted stack, then --resume and compare
# ---------------------------------------------------------------------------

_KILL_SCRIPT = r"""
import os, signal, sys
import numpy as np
import jax
from repro.configs.base import ModelConfig
from repro.data.pipeline import MarkovCorpus
from repro.train.trainer import TrainSettings, run_training
from repro.checkpoint import checkpoint as CK

mode, ckdir, outdir = sys.argv[1], sys.argv[2], sys.argv[3]
NANO = ModelConfig(
    name="nano", family="lm", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=128, vocab_size=64, head_dim=16, mlp_gated=False, act="gelu",
    dtype="float32", param_dtype="float32", vocab_pad_to=64,
)
corpus = MarkovCorpus(64, seed=1)
kw = dict(algorithm="dsm", n_workers=4, tau=2, steps=6, b_micro=2, seq=32,
          eval_every=1, zero_sharded=True, device_parallel_local=True,
          faults="drop=0.25,nan=0.1,seed=5", guard_nonfinite=True)

if mode == "ref":
    s = TrainSettings(**kw)
elif mode == "victim":
    # checkpoint every round; SIGKILL ourselves at the 3rd log line — a
    # genuine mid-run kill with whatever checkpoints made it to disk
    s = TrainSettings(**kw, checkpoint_dir=ckdir, checkpoint_every=1)
    calls = []
    def killer(msg):
        calls.append(msg)
        if len(calls) == 3:
            os.kill(os.getpid(), signal.SIGKILL)
    run_training(NANO, s, corpus, log=killer)
    raise SystemExit("victim survived the kill")  # pragma: no cover
else:
    s = TrainSettings(**kw, checkpoint_dir=ckdir, checkpoint_every=1,
                      resume=True)

result = run_training(NANO, s, corpus)
x0 = {f"l{i}": np.asarray(l) for i, l in
      enumerate(jax.tree.leaves(result["state"].x0))}
np.savez(os.path.join(outdir, mode + "_x0.npz"), **x0)
print("DONE", mode, jax.device_count())
"""


@pytest.mark.multidevice
def test_kill_and_resume_bit_exact_8dev(tmp_path):
    """SIGKILL a sharded, fault-injected training run mid-flight; --resume
    must reproduce the uninterrupted run's final x0 bit-exactly."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    ckdir, outdir = str(tmp_path / "ck"), str(tmp_path)

    def run(mode, expect_rc=0):
        proc = subprocess.run(
            [sys.executable, "-c", _KILL_SCRIPT, mode, ckdir, outdir],
            capture_output=True, text=True, timeout=900, env=env,
        )
        if expect_rc is not None:
            assert proc.returncode == expect_rc, (mode, proc.stderr[-4000:])
        return proc

    run("ref")
    victim = run("victim", expect_rc=None)
    assert victim.returncode == -9, (victim.returncode, victim.stderr[-2000:])
    # the kill left a complete checkpoint behind but not the final state
    from repro.checkpoint.checkpoint import list_checkpoints

    steps = [s for s, _ in list_checkpoints(ckdir)]
    assert steps and max(steps) < 6, steps
    run("resume")

    ref = np.load(os.path.join(outdir, "ref_x0.npz"))
    res = np.load(os.path.join(outdir, "resume_x0.npz"))
    assert set(ref.files) == set(res.files)
    for k in ref.files:
        np.testing.assert_array_equal(ref[k], res[k])
