"""Static-analysis subsystem tests (src/repro/analysis/, docs/analysis.md).

Three layers:
  * HLO collective auditor — parser + budget checks on synthetic HLO text
    (fast, in-process), and the full `python -m repro.analysis audit
    --self-test` matrix in an 8-device subprocess (marked multidevice):
    dense / device-parallel / ZeRO-sharded budgets must pass and the
    PLANTED extra all-reduce must be caught.
  * RPR0xx AST lint — a positive and a negative fixture per rule, plus
    noqa suppression and the CLI's exit codes.
  * runtime sanitizers — the recompilation counter must trip on a
    shape-polymorphic step and stay quiet on a monomorphic one; the
    trainer's --sanitize path reports exactly one steady-state compile.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import lint_source
from repro.analysis.hlo_audit import (
    CollectiveBudget,
    audit_text,
    parse_collectives,
)

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


# ---------------------------------------------------------------------------
# HLO parser
# ---------------------------------------------------------------------------

_HLO = """\
HloModule jit_step, entry_computation_layout={(f32[8,16]{1,0})->f32[]}

ENTRY %main (p0: f32[8,16]) -> f32[] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %ar.1 = f32[8,16]{1,0} all-reduce(%p0), replica_groups={{0,1}}, to_apply=%add
  %ag.1 = f32[32,16]{1,0} all-gather(%p0), dimensions={0}
  %ars = f32[2]{0} all-reduce-start(%small), to_apply=%add
  %ard = f32[2]{0} all-reduce-done(%ars)
  %cp = (f32[4]{0}, u8[128]{0}) collective-permute-start(%p0)
}
"""


def test_parse_collectives_kinds_and_shapes():
    ops = parse_collectives(_HLO)
    kinds = [o.kind for o in ops]
    # -start counts once, -done (payload-free completion) never
    assert kinds == ["all-reduce", "all-gather", "all-reduce",
                     "collective-permute"]
    by_kind = {}
    for o in ops:
        by_kind.setdefault(o.kind, []).append(o)
    assert [o.bytes for o in by_kind["all-reduce"]] == [8 * 16 * 4, 2 * 4]
    assert by_kind["all-gather"][0].bytes == 32 * 16 * 4
    # tuple shapes sum their components: f32[4] + u8[128]
    assert by_kind["collective-permute"][0].bytes == 4 * 4 + 128


def test_parse_collectives_clean_module():
    assert parse_collectives("ENTRY %main {\n  %x = f32[4]{0} add(%a, %b)\n}") == []


# ---------------------------------------------------------------------------
# budgets
# ---------------------------------------------------------------------------

def _budget(phase="global_dense", reduce_ops=2, gather_ops=0,
            reduce_bytes=1 << 20, gather_bytes=1 << 20):
    return CollectiveBudget(
        phase=phase, max_reduce_ops=reduce_ops, max_gather_ops=gather_ops,
        max_reduce_bytes=reduce_bytes, max_gather_bytes=gather_bytes)


def test_audit_flags_forbidden_kind_and_excess_gather():
    rep = audit_text(_HLO, _budget(), name="synthetic")
    assert not rep.passed
    msgs = "\n".join(rep.violations)
    assert "collective-permute" in msgs          # forbidden kind
    assert "gather ops exceed" in msgs           # 1 > 0
    assert rep.counts["all-reduce"] == 2


def test_audit_catches_planted_extra_all_reduce():
    """One reduction round budgeted, two compiled: the stray one trips."""
    rep = audit_text(
        "  %a = f32[64]{0} all-reduce(%x), to_apply=%add\n"
        "  %b = f32[64]{0} all-reduce(%y), to_apply=%add\n",
        _budget(reduce_ops=1), name="planted")
    assert not rep.passed
    assert any("exceed the budget of 1" in v for v in rep.violations)


def test_audit_catches_payload_overrun():
    rep = audit_text(
        "  %a = f32[1024]{0} all-reduce(%x), to_apply=%add\n",
        _budget(reduce_bytes=1024), name="fat")
    assert not rep.passed
    assert any("payload" in v for v in rep.violations)


def test_audit_passes_within_budget():
    rep = audit_text(
        "  %a = f32[64]{0} all-reduce(%x), to_apply=%add\n",
        _budget(reduce_ops=1), name="ok")
    assert rep.passed
    assert rep.to_json()["passed"] is True


def test_phase_budget_shapes():
    from repro.analysis.comm import phase_collective_budget

    local = phase_collective_budget("local", n_param_leaves=10,
                                    payload_bytes=1000)
    assert local["max_reduce_ops"] == 0 and local["max_gather_ops"] == 0
    dense = phase_collective_budget("global_dense", n_param_leaves=10,
                                    payload_bytes=1000)
    assert dense["max_reduce_ops"] == 12       # 10 leaves + 2 metric scalars
    assert dense["max_gather_ops"] == 0
    zero = phase_collective_budget("global_zero", n_param_leaves=10,
                                   payload_bytes=1000)
    assert zero["max_gather_ops"] == 12
    with pytest.raises(ValueError, match="phase"):
        phase_collective_budget("warmup", n_param_leaves=1, payload_bytes=1)


def test_budget_for_phase_derives_from_pytree():
    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}
    b = CollectiveBudget.for_phase("global_dense", params)
    assert b.max_reduce_ops == 2 + 2
    assert b.max_reduce_bytes >= (8 * 4 + 4) * 4
    assert b.max_gather_ops == 0


# ---------------------------------------------------------------------------
# RPR0xx lint: a positive and a negative fixture per rule
# ---------------------------------------------------------------------------

def _lint(src):
    return lint_source(textwrap.dedent(src), "fixture.py")


def _rules(src):
    return [f.rule for f in _lint(src)]


def test_rpr001_key_reuse_positive():
    assert _rules("""
        import jax
        def f(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b
    """) == ["RPR001"]


def test_rpr001_split_negative():
    assert _rules("""
        import jax
        def f(key):
            k1, k2 = jax.random.split(key)
            return jax.random.normal(k1, (3,)) + jax.random.uniform(k2, (3,))
    """) == []


def test_rpr001_use_after_split_positive():
    assert _rules("""
        import jax
        def f(key):
            k1, _ = jax.random.split(key)
            return jax.random.normal(key, (3,))
    """) == ["RPR001"]


def test_rpr001_fold_in_loop_negative():
    assert _rules("""
        import jax
        def f(key, n):
            out = 0.0
            for t in range(n):
                out += jax.random.normal(jax.random.fold_in(key, t), ())
            return out
    """) == []


def test_rpr001_loop_invariant_key_positive():
    assert _rules("""
        import jax
        def f(key, n):
            out = 0.0
            for _ in range(n):
                out += jax.random.normal(key, ())
            return out
    """) == ["RPR001"]


def test_rpr001_branches_negative():
    assert _rules("""
        import jax
        def f(key, flag):
            if flag:
                return jax.random.normal(key, ())
            return jax.random.uniform(key, ())
    """) == []


def test_rpr002_host_sync_in_jitted_positive():
    assert _rules("""
        import jax, jax.numpy as jnp
        def step(x):
            return float(jnp.sum(x))
        jstep = jax.jit(step)
    """) == ["RPR002"]


def test_rpr002_reachable_via_callback_positive():
    assert _rules("""
        import jax
        def inner(x):
            return x.item()
        def step(x):
            return inner(x)
        jstep = jax.jit(step)
    """) == ["RPR002"]


def test_rpr002_unreachable_negative():
    assert _rules("""
        import numpy as np
        def logger(x):
            return float(np.asarray(x).mean())
    """) == []


def test_rpr002_noqa_suppression():
    assert _rules("""
        import jax, jax.numpy as jnp
        def step(x):
            return float(jnp.sum(x))  # noqa: RPR002
        jstep = jax.jit(step)
    """) == []


def test_rpr003_traced_branch_positive():
    assert _rules("""
        import jax, jax.numpy as jnp
        def step(x):
            y = jnp.sum(x)
            if y > 0:
                return y
            return -y
        jstep = jax.jit(step)
    """) == ["RPR003"]


def test_rpr003_static_branch_negative():
    assert _rules("""
        import jax, jax.numpy as jnp
        def step(x, accum):
            if accum:
                x = x + 1
            return jnp.sum(x)
        jstep = jax.jit(step)
    """) == []


def test_rpr004_mutable_default_positive():
    found = _rules("""
        import dataclasses
        @dataclasses.dataclass
        class Config:
            layers: list = []
        def f(xs=[]):
            return xs
    """)
    assert found == ["RPR004", "RPR004"]


def test_rpr004_factory_negative():
    assert _rules("""
        import dataclasses
        @dataclasses.dataclass
        class Config:
            layers: list = dataclasses.field(default_factory=list)
        def f(xs=()):
            return xs
    """) == []


def test_lint_cli_exit_codes(tmp_path, capsys):
    from repro.analysis.__main__ import main

    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax, jax.numpy as jnp
        def step(x):
            return float(jnp.sum(x))
        jstep = jax.jit(step)
    """))
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert main(["lint", str(bad)]) == 1
    assert main(["lint", str(clean)]) == 0
    assert main(["lint", "--select", "RPR999", str(bad)]) == 2
    out = json.loads(
        (capsys.readouterr(), main(["lint", "--json", str(bad)]),
         capsys.readouterr())[2].out)
    assert out[0]["rule"] == "RPR002"
    assert out[0]["path"].endswith("bad.py")


def test_lint_src_is_clean():
    """The repo's own source must stay RPR-clean (sanctioned sync points
    carry noqa with a reason; see docs/analysis.md)."""
    from repro.analysis.lint import lint_paths

    findings = lint_paths([os.path.join(SRC, "repro")])
    assert findings == [], "\n".join(str(f) for f in findings)


# ---------------------------------------------------------------------------
# runtime sanitizers
# ---------------------------------------------------------------------------

def test_recompilation_counter_trips_on_shape_polymorphic_step():
    from repro.analysis import sanitize as SAN

    def shape_poly_probe(x):
        return jnp.sum(x * 2.0)

    probe = jax.jit(shape_poly_probe)
    prev_flag = jax.config.jax_log_compiles
    with SAN.RecompilationCounter() as rc:
        probe(jnp.ones((4,)))
        probe(jnp.ones((4,)))          # cache hit: no second compile
        assert rc.count("shape_poly_probe") == 1
        rc.assert_steady_state("shape_poly_probe")
        probe(jnp.ones((8,)))          # new shape -> silent recompile
    assert rc.count("shape_poly_probe") == 2
    with pytest.raises(SAN.SanitizeError, match="compiled 2 times"):
        rc.assert_steady_state("shape_poly_probe")
    assert jax.config.jax_log_compiles == prev_flag   # restored on exit


def test_debug_nans_restores_config():
    from repro.analysis import sanitize as SAN

    prev = jax.config.jax_debug_nans
    with SAN.debug_nans():
        assert jax.config.jax_debug_nans
    assert jax.config.jax_debug_nans == prev
    with SAN.debug_nans(enabled=False):
        assert jax.config.jax_debug_nans == prev


def test_transfer_guard_context_is_composable():
    from repro.analysis import sanitize as SAN

    # On the CPU backend device buffers ARE host buffers, so the guard
    # blocks nothing here (armed on real accelerators) — but the context
    # must nest and restore cleanly around real work.
    with SAN.no_implicit_host_sync():
        with SAN.no_implicit_host_sync(enabled=False):
            pass
        assert float(jnp.ones(()).sum()) == 1.0


def test_trainer_sanitize_counts_one_steady_state_compile():
    from repro.configs.base import ModelConfig
    from repro.data.pipeline import MarkovCorpus
    from repro.train.trainer import TrainSettings, run_training

    nano = ModelConfig(
        name="nano", family="lm", n_layers=1, d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=64, vocab_size=64, head_dim=16, mlp_gated=False,
        act="gelu", dtype="float32", param_dtype="float32", vocab_pad_to=64,
    )
    corpus = MarkovCorpus(nano.vocab_size, branch=4, seed=7)
    s = TrainSettings(algorithm="dsm", n_workers=2, tau=2, steps=3,
                      b_micro=2, seq=32, eval_every=3, sanitize=True)
    r = run_training(nano, s, corpus)
    assert r["step_compiles"] == 1


# ---------------------------------------------------------------------------
# the full audit matrix: 8-device subprocess (the CI gate)
# ---------------------------------------------------------------------------

@pytest.mark.multidevice
def test_audit_cli_8dev_matrix_and_self_test():
    """`python -m repro.analysis audit --json --self-test` on a forced
    8-device host: dense / device-parallel / ZeRO-sharded budgets pass,
    the local phase compiles ZERO collectives, and the planted extra
    all-reduce variant is caught (reported failed, overall exit 0)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)   # the CLI forces the device count itself
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + env.get(
        "PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "audit", "--json",
         "--self-test"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    payload = json.loads(proc.stdout)
    assert payload["n_devices"] == 8
    assert not payload["degenerate"]
    assert payload["passed"]
    by_name = {r["name"]: r for r in payload["reports"]}
    for name in ("dense", "device_parallel", "zero_sharded"):
        assert by_name[name]["passed"], by_name[name]
    assert by_name["local_phase"]["counts"] == {}, by_name["local_phase"]
    # the ZeRO step genuinely gathers (reduce lowers as all-reduce on CPU)
    assert by_name["zero_sharded"]["counts"].get("all-gather", 0) > 0
    planted = by_name["self_test_planted_all_reduce"]
    assert planted["passed"] is False
    assert any("exceed" in v for v in planted["violations"]), planted
