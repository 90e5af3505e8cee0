"""A test-size cell: the gpt2_small configuration and the vmapped mix with
their shapes cut, so a CPU can run a whole cell in seconds."""

import json
import os
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import runner  # noqa: E402


def tiny_spec(limits=None, **mix_changes) -> "runner.Spec":
    with open(os.path.join(TESTS, "data", "tiny_config.json")) as f:
        conf = json.load(f)
    with open(os.path.join(TESTS, "data", "tiny_mix.json")) as f:
        mix = json.load(f)
    mix.update(mix_changes)
    ref, program = runner.config_modules(
        os.path.join(BENCH, "configs", "tiny.json"), conf)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    chips = 4 if mix["layout"] == "device_parallel" else 1
    return runner.Spec("tiny", chips, "tiny", conf, mix,
                       limits or {"loss": 1.0, "grad": 1.0, "change": 1.0},
                       ref, program, bench["end_to_end"],
                       [m for m in bench["per_layer"] if "workloads" not in m])
