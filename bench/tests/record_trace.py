"""Record the small trace that the reduction's tests read, on the chip.

    python3 bench/tests/record_trace.py --chips 1 --out bench/tests/data/trace_1chip
    python3 bench/tests/record_trace.py --chips 4 --out bench/tests/data/trace_4chip

It drives the test-size cell (``tiny.py``: vmapped workers on one chip,
or one worker per chip with the ZeRO global step on four) through the
harness's own window under the profiler, and writes the ``.xplane.pb``
(gzipped), the step's instruction -> ``op_name`` map, and the harness's
per-layer numbers for that trace beside them.
"""

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    import jax

    from harness import runner
    from harness import trace as TR
    from harness.cell import ProgramCell

    changes = ({"layout": "device_parallel", "global_step": "zero"}
               if args.chips == 4 else {})
    spec = tiny.tiny_spec(**changes)
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    cell = ProgramCell(spec)
    seed = 12345
    state = cell.init_state(seed)
    batches = cell.batches(seed)
    fed = [next(batches)]
    compiled = cell.step.lower(state, cell.put(fed[0])).compile()
    state, _ = runner.first_steps(cell, compiled, state, batches, fed, seed, 2)
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    try:
        state, win = runner.run_window(compiled, state, batches, cell.put,
                                       0.05, tmp)
        os.makedirs(args.out, exist_ok=True)
        src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)[0]
        with open(src, "rb") as f, gzip.open(
                os.path.join(args.out, "window.xplane.pb.gz"), "wb") as g:
            shutil.copyfileobj(f, g)
        op_names = TR.hlo_op_names(compiled.as_text())
        with open(os.path.join(args.out, "op_names.json"), "w") as f:
            json.dump(op_names, f, indent=0, sort_keys=True)
        tr = TR.load(src)
        lo, hi = tr.window
        summary = {
            "steps": win.steps,
            "chips": sorted(tr.devices),
            "window_ns": hi - lo,
            "busy_ns": {d: TR.busy_ns(o, (lo, hi)) for d, o in tr.devices.items()},
            "local_phase_ns": {d: TR.scope_ns(o, op_names, "dsm_local_phase")
                               for d, o in tr.devices.items()},
            "global_step_ns": {d: TR.scope_ns(o, op_names, "dsm_global_step")
                               for d, o in tr.devices.items()},
            "exposed_collective_ns": {d: TR.exposed_collective_ns(o)
                                      for d, o in tr.devices.items()},
        }
        with open(os.path.join(args.out, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
        print(json.dumps(summary))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
