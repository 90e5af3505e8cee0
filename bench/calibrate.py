"""Readings that a cell's correctness limits are set from, on the chip at
the cell's own size, in one process:

  * sound runs of the program over ``--seeds`` seeds (the lower reading);
  * the control, the reference with every matrix product in float8
    (``harness.faults.fp8_dot``) in the program's place, over
    ``--control-seeds`` seeds (the upper reading);
  * the half-batch and worker-mean faults planted in the program's step
    (``harness.faults``) over ``--fault-seeds`` seeds.

Every reading is compared with the plain reference of the same seed, and
judged by the cell's limits file as a run would judge it (``verdict``):
the program's runs have to pass, the control and the faults to fail.

    python3 bench/calibrate.py --workload gpt2_small.w4.tau12 \\
        --out chiprun_out/calibrate.gpt2_small.w4.tau12.json

Needs the chips the cell asks for.  Each record is printed as one JSON line
as it comes, and all are written to ``--out`` at the end.
"""

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from harness import check as CH  # noqa: E402
from harness import faults as F  # noqa: E402
from harness import runner  # noqa: E402

SEED_BASE = 3_000_000_017
# a state left unchanged reads 1 on ``change`` by construction: no run
READ_FAULTS = ("half_batch", "no_exchange")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--seed-base", type=int, default=SEED_BASE)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    from harness.cell import ProgramCell

    spec = runner.load_spec(args.workload)
    devices = jax.devices()[:spec.chips]
    if devices[0].platform != "tpu" or len(devices) < spec.chips:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    runner.enable_cache()
    cell = ProgramCell(spec)
    k_steps = spec.mix["check_steps"]
    steps = {}
    records = []

    def program(seed, name):
        state = cell.init_state(seed)
        batches = cell.batches(seed)
        fed = [next(batches)]
        if name not in steps:
            compiled = cell.step.lower(state, cell.put(fed[0])).compile()
            steps[name] = (compiled if name == "program"
                           else F.FAULTS[name](cell))
        state, readings = runner.first_steps(cell, steps[name], state,
                                             batches, fed, seed, k_steps)
        del state
        gc.collect()
        return readings, fed

    for i in range(args.seeds):
        seed = args.seed_base + 7919 * i
        t0 = time.monotonic()
        prog, fed = program(seed, "program")
        rows = CH.unsound_rows([f["tokens"] for f in fed], spec.mix,
                               spec.conf["vocab_size"])
        others = {}
        if i < args.fault_seeds:
            for name in READ_FAULTS:
                others[name] = program(seed, name)[0]
        # the steps' programs hold no state now; free their buffers
        gc.collect()
        t1 = time.monotonic()
        ref = runner.reference(spec, seed, fed, devices)
        t2 = time.monotonic()
        if i < args.control_seeds:
            others["control"] = runner.reference(spec, seed, fed, devices,
                                                 dot=F.fp8_dot)
        t3 = time.monotonic()
        for name, readings in [("program", prog)] + list(others.items()):
            numbers = {**CH.compare(readings, ref), "rows": rows}
            rec = {"workload": spec.name, "seed": seed, "run": name,
                   "numbers": numbers,
                   "verdict": CH.verdict(numbers, spec.limits),
                   "cos_gap": CH.cosine_gaps(readings.delta0_leaves,
                                             ref.delta0_leaves),
                   "loss": readings.loss, "ref_loss": ref.loss,
                   "delta0": readings.delta0, "ref_delta0": ref.delta0,
                   "change": readings.change, "ref_change": ref.change}
            records.append(rec)
            print(json.dumps({k: rec[k] for k in
                              ("workload", "seed", "run", "numbers",
                               "verdict")}),
                  flush=True)
        print(f"seed {seed}: program and faults {t1 - t0:.1f} s, reference "
              f"{t2 - t1:.1f} s, control {t3 - t2:.1f} s", flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(records, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
