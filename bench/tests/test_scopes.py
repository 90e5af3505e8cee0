"""The scope reduction (``harness/scopes.py``), on synthetic paths and on a
small trace recorded on a TPU v5e with the program's layer scopes:

    python3 bench/tests/record_trace.py --chips 1 \
        --out bench/tests/data/trace_1chip_scopes
"""

import gzip
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import pytest  # noqa: E402
import tiny  # noqa: E402

from harness import scopes as SC  # noqa: E402
from harness import trace as TR  # noqa: E402

COMPONENTS = ("attention", "mlp", "lm_head", "base_opt")
RECORDED = os.path.join(tiny.TESTS, "data", "trace_1chip_scopes")


@pytest.mark.parametrize("path, scope", [
    ("jit(s)/dsm_local_phase/while/body/closed_call/attention/dot", "attention"),
    ("jit(s)/dsm_local_phase/vmap(jvp(lm_head))/dot", "lm_head"),
    ("jit(s)/dsm_local_phase/vmap(transpose(jvp(lm_head)))/dot", "lm_head"),
    ("jit(s)/dsm_local_phase/while/body/closed_call/vmap(base_opt)/mul", "base_opt"),
    ("jit(s)/x/checkpoint/rematted_computation/mlp/add", "mlp"),
    ("jit(s)/x/checkpoint/rematted_computation/mlp/add", SC.RECOMPUTE),
])
def test_carries_a_scope_bare_or_wrapped_in_transforms(path, scope):
    assert SC.carries(path, scope)


@pytest.mark.parametrize("path, scope", [
    ("jit(s)/dsm_local_phase/attention_out/dot", "attention"),
    ("jit(s)/dsm_local_phase/jit(causal_attention)/dot", "attention"),
    ("st.params['decoder']['blocks']['p0']['mlp']['w1']", "mlp"),
    ("jit(s)/dsm_local_phase/vmap(jvp())/mlp2/add", "mlp"),
])
def test_does_not_carry_a_scope_it_only_resembles(path, scope):
    assert not SC.carries(path, scope)


def test_scope_time_is_a_union_per_scope():
    ops = [(0, 10, "while.1"), (2, 4, "fusion.1"), (3, 6, "fusion.2"),
           (7, 9, "fusion.3")]
    names = {"while.1": "jit(s)/dsm_local_phase/while",
             "fusion.1": "jit(s)/dsm_local_phase/vmap(jvp(attention))/dot",
             "fusion.2": "jit(s)/dsm_local_phase/transpose(jvp(attention))/dot",
             "fusion.3": "jit(s)/dsm_local_phase/x/rematted_computation/mlp/add"}
    assert SC.scope_ns(ops, names, "attention") == 4
    assert SC.scope_ns(ops, names, "mlp") == 2
    assert SC.scope_ns(ops, names, SC.RECOMPUTE) == 2
    assert [SC.is_recompute(names[n]) for n in sorted(names)] == [
        False, False, True, False]
    assert SC.scope_ns(ops, names, "lm_head") == 0


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    if not os.path.exists(os.path.join(RECORDED, "window.xplane.pb.gz")):
        pytest.skip("no recorded trace trace_1chip_scopes")
    out = tmp_path_factory.mktemp("scopes") / "window.xplane.pb"
    with gzip.open(os.path.join(RECORDED, "window.xplane.pb.gz"), "rb") as f, \
            open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    with open(os.path.join(RECORDED, "op_names.json")) as f:
        names = json.load(f)
    tr = TR.load(str(out))
    return tr.devices["/device:TPU:0"], names


def test_recorded_component_scopes_are_disjoint_within_the_local_phase(recorded):
    ops, names = recorded
    tagged = {scope: SC.instructions(names, scope) for scope in COMPONENTS}
    for i, a in enumerate(COMPONENTS):
        assert tagged[a], a
        for b in COMPONENTS[i + 1:]:
            assert not tagged[a] & tagged[b], (a, b)
    parts = {scope: SC.scope_ns(ops, names, scope) for scope in COMPONENTS}
    local = TR.scope_ns(ops, names, "dsm_local_phase")
    assert all(v > 0 for v in parts.values()), parts
    assert sum(parts.values()) <= local
    assert 0 < SC.scope_ns(ops, names, SC.RECOMPUTE) < local


def test_recorded_trace_needs_the_wrapped_forms(recorded):
    """Some scopes appear only wrapped in transform names, which a match of
    whole path components alone would miss."""
    ops, names = recorded
    wrapped = {path for path in names.values()
               if any(SC.carries(path, s) and s not in path.split("/")
                      for s in COMPONENTS)}
    assert wrapped
    assert TR.scope_ns(ops, names, "base_opt") < SC.scope_ns(ops, names,
                                                             "base_opt")
