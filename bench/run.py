"""Benchmark of DSM training on TPU: one run of one cell.

    python3 bench/run.py --workload gpt2_small.w4.tau12 --seed 7 \\
        --seconds 30 --trace 0

Run from the root of a checkout.  ``BENCHMARK.json`` names the cell's
configuration (``bench/configs``), its mix (``bench/mixes``), its limits
(``bench/limits``) and its per-layer metrics (``bench/metrics``).  With
``--trace 0`` the last line of stdout carries the end-to-end metrics, with
``--trace 1`` the per-layer ones read from a profiler trace of the window.
Without a TPU, with fewer chips than the cell asks for, or on a device kind
missing from ``bench/peaks.json``, it exits non-zero and prints no result.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import runner

    spec = runner.load_spec(args.workload)
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < spec.chips:
        print(f"bench: {args.workload} needs {spec.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    if kind not in peaks:
        print(f"bench: no peaks for device kind {kind!r} in bench/peaks.json",
              file=sys.stderr)
        return 2
    runner.enable_cache()
    result = runner.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                             T_START, devices[:spec.chips], peaks[kind])
    runner.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
