"""Benchmark harness: one function per paper table + kernels + comm volume.

Prints ``name,value,derived`` CSV rows.  ``--quick`` shrinks sweeps/steps.
Roofline terms (deliverable g) live in benchmarks/roofline.py (they need
the 512-device dry-run env and run as a separate process).
"""

import argparse
import json
import os


def _emit(name, value, derived=""):
    print(f"{name},{value},{derived}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="all",
                    help="comma list: table2,table3,table45,table6,curves,comm,"
                         "kernels")
    args, _ = ap.parse_known_args()
    only = set(args.only.split(","))

    def want(x):
        return "all" in only or x in only

    from benchmarks import comm, kernel_bench, tables

    if want("kernels"):
        for fn in (kernel_bench.bench_dsm_kernel, kernel_bench.bench_adamw_kernel,
                   kernel_bench.bench_interpret_correct):
            name, us, derived = fn()
            _emit(name, f"{us:.1f}us", derived)

    if want("comm"):
        for arch in ("gpt2_medium", "deepseek_67b", "llama4_maverick_400b_a17b"):
            for algo in ("dsm", "perstep", "mv_signsgd"):
                r = comm.bytes_per_outer_step(arch, algo, tau=12)
                _emit(f"comm_{arch}_{algo}",
                      f"{r['wire_bytes_per_outer']/1e9:.3f}GB",
                      f"reduction={r['reduction_vs_perstep']:.1f}x")

    os.makedirs("experiments", exist_ok=True)
    results = {}
    for tname, fn in (("table2", tables.table2), ("table3", tables.table3),
                      ("table45", tables.table45), ("table6", tables.table6),
                      ("table_noise", tables.table_noise)):
        if not want(tname):
            continue
        rows = fn(quick=args.quick)
        results[tname] = rows
        for name, red, val, commr, params in rows:
            _emit(f"{tname}_{name}", f"{val:.4f}",
                  f"comm_red={red};rounds={commr};{params}")

    if want("curves"):
        cur = tables.curves(quick=args.quick)
        results["curves"] = cur
        with open("experiments/curves.json", "w") as f:
            json.dump(cur, f)
        for algo, pts in cur.items():
            _emit(f"curve_{algo}_final", f"{pts[-1][3]:.4f}",
                  f"comm_rounds={pts[-1][1]}")

    with open("experiments/bench_results.json", "w") as f:
        json.dump({k: v for k, v in results.items() if k != "curves"}, f, indent=1,
                  default=str)


if __name__ == "__main__":
    main()
