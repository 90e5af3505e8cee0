"""The comparison that decides ``correct``, at test size on the CPU.

A sound run of the program passes; the control (the reference with every
matrix product in float8 in the program's place) fails; and a run with the
timed path broken underneath (``harness.faults``) comes out not correct,
once for each fault a training cell can have, as does a run whose input
layer feeds every worker the same rows.  The limits here are the test
size's own (``data/tiny_limits.json``), set from CPU readings of this size
as the cells' limits are set from chip readings of theirs.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import numpy as np  # noqa: E402
import pytest  # noqa: E402
import tiny  # noqa: E402

from harness import check as CH  # noqa: E402
from harness import faults as F  # noqa: E402
from harness import runner  # noqa: E402

SEED = 2**31 + 977
with open(os.path.join(tiny.TESTS, "data", "tiny_limits.json")) as f:
    LIMITS = json.load(f)


def _run(make_step=None, **mix):
    import jax

    spec = tiny.tiny_spec(limits=LIMITS, **mix)
    result = runner.run_cell(spec, SEED, 0.2, False, time.monotonic(),
                             jax.devices()[:1], {"bf16_flops": 1.0},
                             make_step=make_step)
    return result


def test_sound_run_is_correct():
    result = _run()
    assert result["correct"], result["checks"]
    assert set(result["checks"]) == set(LIMITS)


@pytest.mark.parametrize("fault", sorted(F.FAULTS))
def test_broken_timed_path_is_not_correct(fault):
    result = _run(make_step=F.FAULTS[fault])
    assert result["correct"] is False, (fault, result["checks"])


def test_feed_of_one_stream_for_every_worker_is_not_correct(monkeypatch):
    from harness.cell import ProgramCell

    own = ProgramCell.batches

    def one_stream(self, seed):
        for raw in own(self, seed):
            tokens = raw["tokens"]
            yield {**raw, "tokens": np.broadcast_to(tokens[:1], tokens.shape)}

    monkeypatch.setattr(ProgramCell, "batches", one_stream)
    result = _run()
    assert result["correct"] is False, result["checks"]
    assert result["checks"]["rows"]["value"] > 0


MIX = {"n_workers": 2, "tau": 2, "accum": 1, "b_micro": 2, "seq": 4}


@pytest.mark.parametrize("case,bad", [
    ("sound", 0), ("repeated_row", 1), ("token_outside_vocab", 1),
    ("axes_swapped", 8)])
def test_unsound_rows_counts_each_fault_of_the_feed(case, bad):
    tokens = np.arange(2 * 2 * 2 * 4, dtype=np.int32).reshape(2, 2, 1, 2, 4)
    if case == "repeated_row":
        tokens[1, 1, 0, 1] = tokens[0, 0, 0, 0]
    elif case == "token_outside_vocab":
        tokens[0, 1, 0, 0, 3] = 32
    elif case == "axes_swapped":
        tokens = tokens.reshape(2, 1, 2, 2, 4)
    assert CH.unsound_rows([tokens], MIX, vocab=32) == bad


def test_control_fails_the_direction_number():
    import jax

    from harness.cell import ProgramCell

    spec = tiny.tiny_spec(limits=LIMITS)
    cell = ProgramCell(spec)
    batches = cell.batches(SEED)
    fed = [next(batches) for _ in range(spec.mix["check_steps"])]
    devices = jax.devices()[:1]
    ref = runner.reference(spec, SEED, fed, devices)
    control = runner.reference(spec, SEED, fed, devices, dot=F.fp8_dot)
    numbers = {**CH.compare(control, ref), "rows": 0}
    assert numbers["grad_dir"] > LIMITS["grad_dir"], numbers
    assert not CH.verdict(numbers, LIMITS)


def test_reference_agrees_with_itself_exactly():
    import jax

    from harness.cell import ProgramCell

    spec = tiny.tiny_spec(limits=LIMITS)
    cell = ProgramCell(spec)
    batches = cell.batches(SEED)
    fed = [next(batches) for _ in range(spec.mix["check_steps"])]
    a = runner.reference(spec, SEED, fed, jax.devices()[:1])
    b = runner.reference(spec, SEED, fed, jax.devices()[:1])
    assert CH.compare(a, b) == pytest.approx(
        {"loss": 0.0, "grad": 0.0, "change": 0.0, "grad_dir": 0.0}, abs=1e-12)
