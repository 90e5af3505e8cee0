"""Observability subsystem tests (docs/observability.md).

Covers the on-device metric pack (jit/eager parity, budget-free sanity),
the run sinks (JSONL/CSV round-trip, resume append, truncated-tail
tolerance), the summarize CLI against a REAL instrumented smoke run,
guard-counter persistence across --resume, the layer scopes in the compiled
outer step and the host annotations in a profiler capture, and — in the
8-forced-device subprocess tier — sharded-vs-dense pack parity plus a
non-degenerate comm ledger (observed collective bytes with ratios).

The sanitizer-backed test is the load-bearing one: an instrumented run
under ``sanitize=True`` proves the pack adds no host transfers inside the
hot loop and the steady-state outer step still compiles exactly once.
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import DSMConfig, constant, dsm_init, make_dsm_step, sgd
from repro.obs import metrics as OM
from repro.obs import sinks as OS
from repro.obs import tracing as OT
from repro.obs.summarize import diff as summarize_diff
from repro.obs.summarize import render, summarize_run

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

NANO = ModelConfig(
    name="nano", family="lm", n_layers=1, d_model=32, n_heads=2,
    n_kv_heads=2, d_ff=64, vocab_size=64, head_dim=16, mlp_gated=False,
    act="gelu", dtype="float32", param_dtype="float32", vocab_pad_to=64,
)


def _env_8dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = (os.path.abspath(SRC) + os.pathsep + ROOT
                         + os.pathsep + env.get("PYTHONPATH", ""))
    return env


# ---------------------------------------------------------------------------
# metric pack: jit/eager parity and sanity of the formulas
# ---------------------------------------------------------------------------

def _tiny_dsm_step_and_state():
    d = 32
    center = jax.random.normal(jax.random.PRNGKey(0), (d,))

    def loss(params, mb):
        return 0.5 * jnp.mean(jnp.sum((params["x"][None] - center - mb) ** 2,
                                      axis=-1))

    cfg = DSMConfig(tau=2, global_lr=0.5)
    step = make_dsm_step(loss, sgd(), cfg, constant(0.05))
    state = dsm_init({"x": jnp.zeros((d,))}, sgd(), n_workers=2)
    batch = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 2, 1, 4, d))
    return step, state, batch


def test_pack_jit_eager_parity():
    """The pack is pure jnp: jit and eager produce identical values."""
    step, state, batch = _tiny_dsm_step_and_state()
    jstep = jax.jit(step)
    # two rounds so the momentum is non-zero and sign_agree is meaningful
    for _ in range(2):
        state, mj = jstep(state, batch)
    with jax.disable_jit():
        _, st, _ = _tiny_dsm_step_and_state()
        for _ in range(2):
            st, me = step(st, batch)
    pj = np.asarray(mj["pack"], np.float64)
    pe = np.asarray(me["pack"], np.float64)
    assert pj.shape == (OM.N_METRICS,)
    # XLA fusion reassociates the f32 sums; parity is to float tolerance
    # (worker_spread = sqrt(E[x^2] - E[x]^2) cancels ~6 digits, so its
    # error floor scales with the loss, hence the absolute term)
    np.testing.assert_allclose(pj, pe, rtol=5e-4, atol=5e-4)


def test_pack_values_sane():
    step, state, batch = _tiny_dsm_step_and_state()
    jstep = jax.jit(step)
    state, m = jstep(state, batch)          # round 1: m starts at zero
    p1 = OS.pack_to_dict(jax.device_get(m["pack"]))
    assert p1["sign_agree"] == 0.0          # sign(0) * sign(delta) is never > 0
    assert p1["m_l1"] == 0.0
    state, m = jstep(state, batch)          # round 2: momentum is live
    p2 = OS.pack_to_dict(jax.device_get(m["pack"]))
    assert 0.0 < p2["pg_density"] <= 1.0
    assert 0.0 <= p2["sign_agree"] <= 1.0
    assert p2["m_l1"] > 0.0
    assert -1.0 <= p2["update_cos"] <= 1.0
    assert p2["survivor_frac"] == 1.0       # dense round
    assert p2["guard_ok"] == 1.0            # no guard wrapper -> default
    assert p2["worker_spread"] >= 0.0
    assert np.isclose(p2["loss"], float(m["loss"]))
    # ||.||_1 >= ||.||_2 always; equality only for one-hot vectors
    assert p2["pg_l1"] >= p2["pg_l2"] > 0.0


def test_guard_verdict_lands_in_pack():
    """A rejected round gets guard_ok=0 in its pack (device-side select)."""
    from repro.robustness.guards import init_guard, make_guarded_step

    def fake_step(state, loss_val):
        pack = OM.minimal_pack(loss_val)
        return state + 1.0, {"loss": loss_val, "pack": pack}

    guarded = jax.jit(make_guarded_step(fake_step, nonfinite=True))
    state, guard = jnp.zeros(()), init_guard()
    state, guard, m = guarded(state, guard, jnp.float32(1.0))
    assert OS.pack_to_dict(jax.device_get(m["pack"]))["guard_ok"] == 1.0
    state, guard, m = guarded(state, guard, jnp.float32(jnp.nan))
    assert OS.pack_to_dict(jax.device_get(m["pack"]))["guard_ok"] == 0.0
    assert float(state) == 1.0              # rejected round kept the state


def test_pack_to_dict_rejects_wrong_length():
    with pytest.raises(ValueError, match="entries"):
        OS.pack_to_dict(np.zeros(OM.N_METRICS - 1))


# ---------------------------------------------------------------------------
# sinks: JSONL/CSV round-trip, resume append, truncated-tail tolerance
# ---------------------------------------------------------------------------

def test_runwriter_roundtrip_and_resume(tmp_path):
    run_dir = str(tmp_path / "run")
    manifest = OS.build_manifest(run_name="run", extra={"note": "t"})
    with OS.RunWriter(run_dir, manifest) as w:
        w.event("started", steps=3)
        w.metrics_row(1, np.arange(OM.N_METRICS, dtype=np.float64))
        w.span("eval", 0.25, step=1)
    man, events, rows = OS.read_run(run_dir)
    assert man["run_name"] == "run"
    assert man["metric_names"] == list(OM.METRIC_NAMES)
    assert [e["kind"] for e in events] == ["started", "span"]
    assert all("wall" in e for e in events)
    assert rows[0]["step"] == 1 and rows[0]["loss"] == 0.0
    assert rows[0]["guard_ok"] == float(OM.IDX["guard_ok"])

    # resume append: history is kept, the header is not rewritten
    with OS.RunWriter(run_dir, manifest, resume=True) as w:
        w.event("resumed", step=1)
        w.metrics_row(2, np.arange(OM.N_METRICS, dtype=np.float64) + 1)
    _, events, rows = OS.read_run(run_dir)
    assert [e["kind"] for e in events] == ["started", "span", "resumed"]
    assert [r["step"] for r in rows] == [1, 2]
    with open(os.path.join(run_dir, "scalars.csv")) as f:
        assert sum(line.startswith("step,") for line in f) == 1

    # a killed run leaves torn tails; readers must survive both
    with open(os.path.join(run_dir, "events.jsonl"), "a") as f:
        f.write('{"kind": "trunc')
    with open(os.path.join(run_dir, "scalars.csv"), "a") as f:
        f.write("3,0.5,0.1")  # partial row
    _, events, rows = OS.read_run(run_dir)
    assert [e["kind"] for e in events] == ["started", "span", "resumed"]
    assert [r["step"] for r in rows] == [1, 2]


def test_profile_window_raises_when_profiler_cannot_start(monkeypatch, tmp_path):
    def refuse(out_dir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    win = OT.ProfileWindow((1, 2), str(tmp_path))
    win.tick(0)  # before the window: nothing starts
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        win.tick(1)
    assert not win.active


def test_tracing_primitives():
    assert OT.parse_profile_steps(None) is None
    assert OT.parse_profile_steps("3:7") == (3, 7)
    with pytest.raises(ValueError):
        OT.parse_profile_steps("7:3")
    with pytest.raises(ValueError):
        OT.parse_profile_steps("x")

    x = jnp.ones((4,))
    with OT.Span("s", x) as sp:
        sp.add_fence(x * 2)
    assert sp.seconds >= 0.0

    tot = OT.PhaseTotals()
    tot.add("train_window", 1.0, n=4)
    tot.add("train_window", 1.0, n=4)
    d = tot.as_dict()
    assert d["train_window"]["seconds"] == 2.0
    assert d["train_window"]["ms_per"] == 250.0


# ---------------------------------------------------------------------------
# tracing: layer scopes in the compiled outer step, host annotations in a
# profiler capture (docs/observability.md section 3)
# ---------------------------------------------------------------------------

LAYER_SCOPES = ("attention", "mlp", "lm_head", "base_opt")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def _carries(op_name, scope):
    """``scope`` is a component of the path, bare or wrapped in transform
    names (``vmap(base_opt)``, ``transpose(jvp(lm_head))``)."""
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part == scope:
            return True
    return False


def _outer_step_op_names(remat):
    """Instruction -> ``op_name`` of the compiled DSM outer step at NANO."""
    from repro.data.pipeline import MarkovCorpus, dsm_batches
    from repro.models import transformer as T
    from repro.train.trainer import TrainSettings, build_algorithm

    s = TrainSettings(algorithm="dsm", n_workers=2, tau=2, b_micro=2, seq=32,
                      remat=remat)
    init, step, _, _ = build_algorithm(
        lambda p, mb: T.loss_fn(p, mb, NANO, remat=remat), s)
    state = init(T.init_params(jax.random.PRNGKey(0), NANO), s.n_workers)
    batch = jax.tree.map(jnp.asarray, next(dsm_batches(
        MarkovCorpus(NANO.vocab_size, seed=7), s.n_workers, s.tau, 1,
        s.b_micro, s.seq, seed=0)))

    def train_step(state, batch, key):
        return step(state, batch, key)

    text = jax.jit(train_step).lower(
        state, batch, jax.random.PRNGKey(1)).compile().as_text()
    return dict(m.groups() for m in map(_INSTR.match, text.splitlines()) if m)


@pytest.mark.parametrize("remat", [False, True])
def test_outer_step_op_names_carry_the_layer_scopes(remat):
    """The four local-phase scopes reach the compiled HLO in every form a
    profile meets them in, inside ``dsm_local_phase``, and no instruction
    carries two of them (the scope readers' sums stay disjoint).  Reducer
    regions carry a path cut short of ``jit(...)``; they run inside the
    instruction that calls them."""
    names = _outer_step_op_names(remat)
    forms = {scope: set() for scope in LAYER_SCOPES}
    for instr, path in names.items():
        carried = [scope for scope in LAYER_SCOPES if _carries(path, scope)]
        assert len(carried) <= 1, (instr, path)
        if carried and path.startswith("jit("):
            assert _carries(path, "dsm_local_phase"), path
            forms[carried[0]].add(
                "recompute" if _carries(path, "rematted_computation")
                else "backward" if "transpose(" in path else "forward")
    layer_forms = {"forward", "backward"} | ({"recompute"} if remat else set())
    assert forms["attention"] == layer_forms
    assert forms["mlp"] == layer_forms
    # the cross-entropy lies outside the checkpointed layers: never recomputed
    assert forms["lm_head"] == {"forward", "backward"}
    # opened directly under vmap, so it shows only wrapped: vmap(base_opt)
    assert forms["base_opt"] == {"forward"}
    assert any("vmap(base_opt)" in p for p in names.values())


def _host_events(trace_dir):
    """The events on the host plane of a capture."""
    import glob

    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1, found
    data = ProfileData.from_file(found[0])
    return [e for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    x = jnp.ones((4,))
    with jax.profiler.trace(str(tmp_path)):
        with OT.Span("probe_window", x) as sp:
            sp.add_fence(x * 2)
    assert sp.seconds >= 0.0
    assert [e.name for e in _host_events(tmp_path)
            if e.name.startswith("repro.")] == ["repro.probe_window"]


def test_profile_steps_capture_holds_the_step_annotations(tmp_path):
    """``--profile-steps 1:2`` captures outer steps 1 and 2 with their
    input, dispatch and flush annotations and the eval span."""
    from repro.data.pipeline import MarkovCorpus
    from repro.train.trainer import TrainSettings, run_training

    s = TrainSettings(algorithm="dsm", n_workers=2, tau=2, steps=4,
                      b_micro=2, seq=32, eval_every=2, log_every=1,
                      run_dir=str(tmp_path / "run"), profile_steps="1:2")
    run_training(NANO, s, MarkovCorpus(NANO.vocab_size, seed=7))
    events = _host_events(tmp_path / "run" / "profile")
    steps = [dict(e.stats)["step_num"] for e in events
             if e.name == "repro.step"]
    assert sorted(steps) == [1, 2]
    names = [e.name for e in events]
    for name in ("repro.input", "repro.dispatch", "repro.flush"):
        assert names.count(name) == 2, name
    assert names.count("repro.eval") == 1


# ---------------------------------------------------------------------------
# comm model: the analytic side of the ledger
# ---------------------------------------------------------------------------

def test_wire_bytes_model_matches_outer_step_report():
    from repro.analysis.comm import bytes_per_outer_step, wire_bytes_for_payload

    payload = 1 << 20
    assert wire_bytes_for_payload(payload, "dsm", tau=12) == (2 * payload, 1)
    assert wire_bytes_for_payload(payload, "perstep", tau=12) == (
        2 * payload * 12, 12)
    sign_wire, sign_rounds = wire_bytes_for_payload(payload, "mv_signsgd",
                                                    tau=12, param_bytes=2)
    assert sign_wire == payload // 16 * 2 and sign_rounds == 1
    with pytest.raises(ValueError):
        wire_bytes_for_payload(payload, "nope", tau=12)

    # the per-arch report is built on the same helper: tau x reduction
    dsm = bytes_per_outer_step("gpt2_small", "dsm", tau=12)
    ps = bytes_per_outer_step("gpt2_small", "perstep", tau=12)
    assert ps["wire_bytes_per_outer"] == 12 * dsm["wire_bytes_per_outer"]
    assert (dsm["comm_rounds_per_outer"], ps["comm_rounds_per_outer"]) == (1, 12)


# ---------------------------------------------------------------------------
# the real thing: an instrumented smoke run through the trainer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_run(tmp_path_factory):
    """ONE short instrumented DSM training shared by the assertions below."""
    from repro.data.pipeline import MarkovCorpus
    from repro.train.trainer import TrainSettings, run_training

    run_dir = str(tmp_path_factory.mktemp("obs") / "smoke")
    s = TrainSettings(algorithm="dsm", n_workers=2, tau=2, steps=4,
                      b_micro=2, seq=32, eval_every=2, log_every=1,
                      run_dir=run_dir)
    logs = []
    result = run_training(NANO, s, MarkovCorpus(NANO.vocab_size, seed=7),
                          log=logs.append)
    return run_dir, result, logs, s


def test_smoke_run_dir_contents(smoke_run):
    run_dir, result, logs, s = smoke_run
    man, events, rows = OS.read_run(run_dir)
    assert man["settings"]["algorithm"] == "dsm"
    assert man["metric_names"] == list(OM.METRIC_NAMES)
    # outer-step numbering is consistent: one row per round, 1..steps
    assert [r["step"] for r in rows] == list(range(1, s.steps + 1))
    for r in rows:
        assert np.isfinite(r["loss"]) and np.isfinite(r["pg_l1"])
        assert 0.0 <= r["sign_agree"] <= 1.0
        assert r["survivor_frac"] == 1.0 and r["guard_ok"] == 1.0
    # the logged train losses come from the SAME rows (satellite: the log
    # line and scalars.csv can never disagree about a step again)
    by_step = {r["step"]: r for r in rows}
    for line in logs:
        if line.startswith("step"):
            parts = line.split()
            step, train = int(parts[1]), float(parts[2].split("=")[1])
            assert np.isclose(train, by_step[step]["loss"], atol=5e-5), line
    kinds = [e["kind"] for e in events]
    assert kinds.count("comm_ledger") == 1
    assert "finished" in kinds and "eval" in kinds
    ledger = next(e for e in events if e["kind"] == "comm_ledger")
    assert ledger["predicted"]["wire_bytes_per_outer"] > 0
    assert ledger["predicted"]["payload_bytes"] > 0
    assert ledger["degenerate_mesh"]  # 1-device host: ratios suppressed
    assert ledger["ratio"]["reduce"] is None
    span_names = {e["name"] for e in events if e["kind"] == "span"}
    assert {"train_window", "eval"} <= span_names
    fin = next(e for e in events if e["kind"] == "finished")
    assert fin["steps"] == s.steps and fin["tokens"] == result["tokens"]
    assert result["phase_ms"] is not None
    assert result["final_metrics"]["loss"] == rows[-1]["loss"]
    assert result["run_dir"] == run_dir


def test_summarize_api_and_render(smoke_run):
    run_dir, _, _, s = smoke_run
    summary = summarize_run(run_dir)
    assert summary["steps_logged"] == s.steps
    assert summary["scalars"]["sign_agree"]["last"] is not None
    assert summary["comm_ledger"]["predicted"]["wire_bytes_per_outer"] > 0
    text = render(summary)
    assert "sign_agree" in text
    assert "wire" in text or "bytes" in text
    # diff against itself must not crash and mentions both runs
    assert "smoke" in summarize_diff(summary, summary)


def test_summarize_cli_on_real_run(smoke_run):
    run_dir, _, _, _ = smoke_run
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs", "summarize", run_dir],
        capture_output=True, text=True, timeout=120, env=_env_8dev(),
        cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "sign_agree" in proc.stdout
    assert "comm" in proc.stdout.lower()
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs", "summarize", run_dir, "--json"],
        capture_output=True, text=True, timeout=120, env=_env_8dev(),
        cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout)["steps_logged"] == 4
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs", "summarize", run_dir + "_nope"],
        capture_output=True, text=True, timeout=120, env=_env_8dev(),
        cwd=ROOT)
    assert proc.returncode == 2


def test_summarize_dedupes_rollback_duplicate_steps(tmp_path):
    """Rollback/resume re-log step numbers; summarize keeps the LAST row."""
    run_dir = str(tmp_path / "dup")
    with OS.RunWriter(run_dir, OS.build_manifest(run_name="dup")) as w:
        row = np.zeros(OM.N_METRICS)
        for step, loss in ((1, 5.0), (2, 9.0), (2, 4.0)):
            row[OM.IDX["loss"]] = loss
            w.metrics_row(step, row)
        w.event("finished", steps=2, wall_s=1.0, steps_per_s=2.0,
                tokens=10, tokens_per_s=10.0)
    summary = summarize_run(run_dir)
    assert summary["steps_logged"] == 2
    assert summary["scalars"]["loss"]["last"] == 4.0
    assert summary["scalars"]["loss"]["max"] == 5.0  # 9.0 was rolled back


def test_instrumented_run_passes_sanitizers(tmp_path):
    """Sanitizer-backed budget proof: with the pack + async flushes the hot
    loop still makes NO implicit host transfers and the outer step compiles
    exactly once (a second compile or a blocking read raises)."""
    from repro.data.pipeline import MarkovCorpus
    from repro.train.trainer import TrainSettings, run_training

    s = TrainSettings(algorithm="dsm", n_workers=2, tau=2, steps=4,
                      b_micro=2, seq=32, eval_every=2,
                      run_dir=str(tmp_path / "san"), sanitize=True)
    r = run_training(NANO, s, MarkovCorpus(NANO.vocab_size, seed=7))
    assert r["step_compiles"] == 1
    assert np.isfinite(r["final_eval"])


def test_donated_remat_step_matches(tmp_path):
    """The outer step takes its state donated.  With remat on it gives the
    losses it gives with remat off."""
    from repro.data.pipeline import MarkovCorpus
    from repro.train.trainer import TrainSettings, run_training

    hist = {}
    for remat in (False, True):
        s = TrainSettings(algorithm="dsm", n_workers=2, tau=2, steps=2,
                          b_micro=2, seq=32, eval_every=2, remat=remat,
                          run_dir=str(tmp_path / f"remat_{remat}"))
        r = run_training(NANO, s, MarkovCorpus(NANO.vocab_size, seed=7))
        hist[remat] = r["history"]
    np.testing.assert_allclose(hist[True], hist[False], rtol=1e-6)


def test_baseline_rows_have_nan_dsm_slots(tmp_path):
    """Baselines log loss/gamma rows; DSM-only metrics stay NaN, so the CSV
    schema is ONE table for every algorithm."""
    from repro.data.pipeline import MarkovCorpus
    from repro.train.trainer import TrainSettings, run_training

    s = TrainSettings(algorithm="slowmo", n_workers=2, tau=2, steps=2,
                      b_micro=2, seq=32, eval_every=2,
                      run_dir=str(tmp_path / "bl"))
    run_training(NANO, s, MarkovCorpus(NANO.vocab_size, seed=7))
    _, _, rows = OS.read_run(s.run_dir)
    assert [r["step"] for r in rows] == [1, 2]
    for r in rows:
        assert np.isfinite(r["loss"])
        assert np.isnan(r["pg_l1"]) and np.isnan(r["sign_agree"])


def test_guard_counters_survive_resume(tmp_path):
    """Cumulative skipped_rounds persist in the checkpoint extra: a resumed
    run reports totals since step 0, not since the restart."""
    from repro.checkpoint import checkpoint as CK
    from repro.data.pipeline import MarkovCorpus
    from repro.train.trainer import TrainSettings, run_training

    ck = str(tmp_path / "ck")
    # spike_factor ~0: round 1 seeds the EMA, every later round is rejected
    common = dict(algorithm="dsm", n_workers=2, tau=2, b_micro=2, seq=32,
                  eval_every=2, guard_spike_factor=1e-6, guard_patience=100,
                  checkpoint_dir=ck, checkpoint_every=2)
    corpus = MarkovCorpus(NANO.vocab_size, seed=7)
    r1 = run_training(NANO, TrainSettings(steps=4, **common), corpus)
    assert r1["skipped_rounds"] == 3
    extra = CK.load_meta(CK.latest_checkpoint(ck)).get("extra")
    assert extra["skipped_rounds"] == 3 and extra["rollbacks"] == 0

    r2 = run_training(NANO, TrainSettings(steps=8, resume=True, **common),
                      corpus)
    assert r2["skipped_rounds"] == 7  # 3 from before the restart + 4 new
    extra = CK.load_meta(CK.latest_checkpoint(ck)).get("extra")
    assert extra["skipped_rounds"] == 7


# ---------------------------------------------------------------------------
# 8 devices: sharded pack parity + a non-degenerate comm ledger
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = r"""
import json, os, sys
import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.data.pipeline import MarkovCorpus
from repro.obs import sinks as OS
from repro.obs.summarize import summarize_run
from repro.train.trainer import TrainSettings, run_training

NANO = ModelConfig(
    name="nano", family="lm", n_layers=1, d_model=32, n_heads=2,
    n_kv_heads=2, d_ff=64, vocab_size=64, head_dim=16, mlp_gated=False,
    act="gelu", dtype="float32", param_dtype="float32", vocab_pad_to=64,
)
out = sys.argv[1]
rec = {"n_devices": jax.device_count(), "rows": {}}

for tag, kw in (
    ("dense", {}),
    ("sharded", {"zero_sharded": True, "device_parallel_local": True}),
):
    s = TrainSettings(algorithm="dsm", n_workers=4, tau=2, steps=4,
                      b_micro=2, seq=32, eval_every=4,
                      run_dir=os.path.join(out, tag), **kw)
    run_training(NANO, s, MarkovCorpus(NANO.vocab_size, seed=7))
    _, events, rows = OS.read_run(s.run_dir)
    rec["rows"][tag] = rows
    if tag == "sharded":
        rec["ledger"] = next(e for e in events if e["kind"] == "comm_ledger")
        rec["spans"] = sorted({e["name"] for e in events
                               if e["kind"] == "span"})
        rec["summary"] = summarize_run(s.run_dir)

print("RESULT " + json.dumps(rec))
"""


@pytest.mark.multidevice
def test_sharded_pack_and_ledger_8dev(tmp_path):
    """On a forced 8-device host (worker=4, zero=2): the ZeRO-sharded
    instrumented run logs the same pack values as the dense run (the single
    stacked psum reconstructs the replicated sums), and the comm ledger is
    non-degenerate — observed all-reduce bytes > 0 with an observed/
    predicted ratio."""
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT, str(tmp_path)],
        capture_output=True, text=True, timeout=900, env=_env_8dev(),
        cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    rec = json.loads(line[len("RESULT "):])
    assert rec["n_devices"] == 8

    dense, sharded = rec["rows"]["dense"], rec["rows"]["sharded"]
    assert [r["step"] for r in dense] == [r["step"] for r in sharded]
    for rd, rs in zip(dense, sharded):
        for name in ("loss", "pg_l1", "pg_l2", "pg_density", "sign_agree",
                     "m_l1", "update_cos", "worker_spread"):
            a, b = rd[name], rs[name]
            # absolute term: worker_spread's sqrt(E[x^2]-E[x]^2) form
            # cancels, leaving loss-scale float error
            assert abs(a - b) <= 1e-3 + 1e-3 * abs(a), (name, rd, rs)

    ledger = rec["ledger"]
    assert not ledger["degenerate_mesh"]
    assert ledger["observed"]["reduce_bytes"] > 0
    assert ledger["observed"]["reduce_ops"] > 0
    assert ledger["ratio"]["reduce"] is not None
    assert "train_window" in rec["spans"]
    # the summary renders observed-vs-predicted comm volume from real HLO
    assert rec["summary"]["comm_ledger"]["observed"]["reduce_bytes"] > 0
