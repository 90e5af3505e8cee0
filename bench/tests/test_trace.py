"""The trace reduction, on synthetic intervals and on a small trace
recorded on a TPU v5e (``record_trace.py``)."""

import gzip
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import pytest  # noqa: E402
import tiny  # noqa: E402

from harness import trace as TR  # noqa: E402

DATA = os.path.join(tiny.TESTS, "data")


def test_union_merges_nested_and_touching_intervals():
    assert TR.union([(0, 10, "while"), (2, 3, "a"), (10, 12, "b"), (20, 25, "c")]) \
        == [(0, 12), (20, 25)]


def test_subtract_leaves_the_uncovered_parts():
    assert TR.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) \
        == [(0, 2), (4, 8), (22, 30)]


def test_exposed_collective_time_ignores_enclosing_control_flow():
    ops = [(0, 100, "while.1"), (10, 20, "fusion.1"), (15, 40, "all-gather-done.3"),
           (50, 60, "reduce-scatter.2"), (55, 58, "fusion.2")]
    # all-gather: 20..40 exposed; reduce-scatter: 50..55 and 58..60
    assert TR.exposed_collective_ns(ops) == 20 + 5 + 2


def test_busy_is_clipped_to_the_window():
    assert TR.busy_ns([(0, 10, "a"), (5, 30, "b")], (8, 20)) == 12


def test_scope_time_reads_the_op_name_path():
    ops = [(0, 10, "fusion.1"), (5, 8, "fusion.2"), (20, 30, "fusion.3")]
    names = {"fusion.1": "jit(train_step)/dsm_local_phase/while/body/dot",
             "fusion.2": "jit(train_step)/dsm_local_phase/add",
             "fusion.3": "jit(train_step)/dsm_global_step/sign"}
    assert TR.scope_ns(ops, names, "dsm_local_phase") == 10
    assert TR.scope_ns(ops, names, "dsm_global_step") == 10
    assert TR.scope_ns(ops, names, "dsm_local") == 0


def test_hlo_op_names_reads_instruction_metadata():
    text = ('  %fusion.7 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %p), kind=kLoop, '
            'calls=%fused_computation.7, metadata={op_name="jit(train_step)/'
            'dsm_global_step/sub" source_file="dsm.py" source_line=239}\n'
            '  ROOT %tuple.2 = (f32[]) tuple(f32[] %x)\n')
    assert TR.hlo_op_names(text) == {"fusion.7": "jit(train_step)/dsm_global_step/sub"}


def test_idle_gaps_are_named_by_the_innermost_host_span():
    tr = TR.Trace({"/device:TPU:0": [(10, 20, "a"), (50, 60, "b")]},
                  [(0, 100, "window"), (20, 50, "input"), (30, 40, "block")])
    gaps = TR.idle_gaps(tr.devices["/device:TPU:0"], tr)
    assert [g[0] for g in gaps] == ["other", "block", "other"]
    assert [g[1] for g in gaps] == pytest.approx([40e-9, 30e-9, 10e-9])


def _load(name, tmp_path_factory):
    src = os.path.join(DATA, name)
    if not os.path.exists(os.path.join(src, "window.xplane.pb.gz")):
        pytest.skip(f"no recorded trace {name}")
    out = tmp_path_factory.mktemp(name) / "window.xplane.pb"
    with gzip.open(os.path.join(src, "window.xplane.pb.gz"), "rb") as f, \
            open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    with open(os.path.join(src, "op_names.json")) as f:
        names = json.load(f)
    return TR.load(str(out)), names


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    return _load("trace_1chip", tmp_path_factory)


@pytest.fixture(scope="module")
def recorded4(tmp_path_factory):
    return _load("trace_4chip", tmp_path_factory)


def test_recorded_trace_has_the_chip_and_the_harness_spans(recorded):
    tr, _ = recorded
    assert list(tr.devices) == ["/device:TPU:0"]
    kinds = {name for _, _, name in tr.host}
    assert {"window", "input", "dispatch", "block"} <= kinds
    lo, hi = tr.window
    assert hi > lo


def test_recorded_trace_scopes_reach_the_device_ops(recorded):
    tr, names = recorded
    ops = tr.devices["/device:TPU:0"]
    lo, hi = tr.window
    busy = TR.busy_ns(ops, (lo, hi))
    local = TR.scope_ns(ops, names, "dsm_local_phase")
    glob_ = TR.scope_ns(ops, names, "dsm_global_step")
    assert 0 < busy <= hi - lo
    assert local > 0 and glob_ > 0
    assert local + glob_ <= busy * 1.001
    assert local > glob_


def test_recorded_one_chip_trace_has_no_collectives(recorded):
    tr, _ = recorded
    ops = tr.devices["/device:TPU:0"]
    assert not TR.has_collectives(ops)
    assert TR.exposed_collective_ns(ops) == 0


def test_recorded_four_chip_trace_has_the_zero_exchange(recorded4):
    tr, names = recorded4
    assert sorted(tr.devices) == [f"/device:TPU:{i}" for i in range(4)]
    lo, hi = tr.window
    for ops in tr.devices.values():
        assert TR.has_collectives(ops)
        coll = TR.length(TR.union(o for o in ops if TR.is_collective(o[2])))
        assert 0 <= TR.exposed_collective_ns(ops) <= coll
        assert TR.scope_ns(ops, names, "dsm_global_step") > 0
        assert TR.busy_ns(ops, (lo, hi)) <= hi - lo
