"""GPT-2 medium's layout through the program's DSM outer step, against the
plain reference, on the CPU.

The benchmark's cell ``gpt2_medium.w2.tau12`` decides ``correct`` on the
chip at published sizes with ``bench/harness``: the program's own jitted,
donated outer step (``ProgramCell``, driven by ``runner.first_steps``)
against the float32, ``highest``-precision reference of
``bench/configs/gpt2_reference.py`` (``runner.check``).  This is the same
comparison at medium's widths and heads with depth, vocabulary and
sequence cut so that a CPU runs it: n_embd 1024, 16 heads of 64, n_inner
4096, 2 layers, vocabulary 2,048, seq 128, W 2, tau 2, two outer steps,
bf16 parameters.
"""

import json
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import check as CH  # noqa: E402
from harness import faults as F  # noqa: E402
from harness import runner  # noqa: E402
from harness.cell import ProgramCell  # noqa: E402

SEED = 4_100_000_001
# Against the float32 reference the program computes in bf16 (parameters,
# activations, products); at this size the CPU reads, over four seeds,
# loss 0.9-1.2e-4, grad 2.5-3.1e-4, change 2.9-6.8e-5 and grad_dir 4.6e-3.
# Half of each microbatch's rows left out reads loss >= 8.6e-3, grad >=
# 5.9e-2, change >= 1.6e-2; the reference with every product in float8
# (the precision below bf16) reads change >= 6.6e-4 and grad_dir >= 0.09.
LIMITS = {
    "loss": 5e-4,      # relative gap of a step's mean loss: bf16 rounding
    "grad": 3e-3,      # first pseudo-gradient's leaf norms: bf16 rounding
    "change": 3e-4,    # x0's change: the sign step leaves bf16 rounding
    "grad_dir": 2e-2,  # 1 - cos of the first pseudo-gradient: float8 shows
    "rows": 0,         # the reference trains on the rows the program fed
}


@pytest.fixture(scope="module")
def spec():
    cfg_path = os.path.join(BENCH, "configs", "gpt2_medium.json")
    with open(cfg_path) as f:
        conf = json.load(f)
    conf.update(n_layer=2, vocab_size=2048, n_positions=128, n_ctx=128)
    with open(os.path.join(BENCH, "mixes", "w2.tau12.b4.s1024.json")) as f:
        mix = json.load(f)
    mix.update(tau=2, b_micro=2, seq=128, check_steps=2)
    ref, program = runner.config_modules(cfg_path, conf)
    return runner.Spec("gpt2_medium.cut", 1, "gpt2_medium", conf, mix,
                       LIMITS, ref, program, [], [])


def test_cut_keeps_medium_widths(spec):
    cfg = ProgramCell(spec).cfg
    assert (cfg.d_model, cfg.n_heads, cfg.hd, cfg.d_ff) == (1024, 16, 64, 4096)
    assert spec.mix["n_workers"] == 2 and spec.conf["param_dtype"] == "bfloat16"


@pytest.mark.parametrize("fault,correct", [(None, True), ("half_batch", False)],
                         ids=["sound", "half_batch"])
def test_dsm_step_matches_the_reference(spec, fault, correct):
    cell = ProgramCell(spec)
    state = cell.init_state(SEED)
    batches = cell.batches(SEED)
    fed = [next(batches)]
    step = F.FAULTS[fault](cell) if fault else cell.step
    state, prog = runner.first_steps(cell, step, state, batches, fed, SEED,
                                     spec.mix["check_steps"])
    numbers = runner.check(spec, SEED, fed, prog, jax.devices()[:1])
    assert CH.verdict(numbers, LIMITS) is correct, numbers
    if fault:
        # the rows are sound: the trained state is what fails
        assert numbers["rows"] == 0
        assert all(numbers[k] > LIMITS[k] for k in ("loss", "grad", "change")), \
            numbers


def test_float8_control_fails_the_comparison(spec):
    cell = ProgramCell(spec)
    batches = cell.batches(SEED)
    fed = [next(batches) for _ in range(spec.mix["check_steps"])]
    devices = jax.devices()[:1]
    ref = runner.reference(spec, SEED, fed, devices)
    control = runner.reference(spec, SEED, fed, devices, dot=F.fp8_dot)
    numbers = {**CH.compare(control, ref), "rows": 0}
    assert numbers["change"] > LIMITS["change"], numbers
    assert numbers["grad_dir"] > LIMITS["grad_dir"], numbers
