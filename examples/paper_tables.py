"""Paper-table analogs at CPU scale (one function per paper table).

The paper trains GPT-2 124M-770M for 100k steps on OpenWebText across 8-16
GPU workers.  Offline/CPU we reproduce every comparison on a nano GPT over
a structured Markov corpus with simulated workers — same algorithms, same
protocol (tune global LR for Alg. 1, momentum+LR for SlowMo), scaled down.

Paper claims being checked:
  T2: Alg.1 beats SlowMo at every tau; both trail per-step AdamW slightly.
  T3: same ordering with Sophia as the base optimizer.
  T4: Lookahead (n=1) improves on plain AdamW.
  T5: signed Lookahead (n=1) improves on plain AdamW.
  T6: signed SlowMo sits between SlowMo and Alg.1; global AdamW ~ SlowMo.

Prints ``name,value,derived`` CSV rows.  ``--quick`` shrinks sweeps/steps;
``--only`` takes a comma list of targets (default: all of them).

Run:  PYTHONPATH=src python examples/paper_tables.py --quick --only table2
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.configs import NANO
from repro.data.pipeline import MarkovCorpus
from repro.train.trainer import TrainSettings, run_training

_CORPUS = None


def corpus():
    global _CORPUS
    if _CORPUS is None:
        _CORPUS = MarkovCorpus(NANO.vocab_size, branch=4, seed=7)
    return _CORPUS


def _settings(**kw) -> TrainSettings:
    base = dict(
        n_workers=4, tau=8, steps=60, b_micro=8, seq=128, peak_lr=1e-2,
        warmup=5, eval_every=30, heterogeneous=True,
        # CPU-scale horizon is ~1000x shorter than the paper's 100k steps;
        # momentum time-constants are scaled accordingly (beta 0.9/0.95
        # instead of Lion's 0.95/0.98).
        dsm_beta1=0.9, dsm_beta2=0.95,
    )
    base.update(kw)
    return TrainSettings(**base)


def _best(results):
    return min(results, key=lambda r: r["final_eval"])


def run_algo(algo, steps, tau, sweep, **kw):
    """Tune per the paper's protocol; return the best run + its config."""
    out = []
    for params in sweep:
        s = _settings(algorithm=algo, steps=steps, tau=tau, **params, **kw)
        r = run_training(NANO, s, corpus())
        r["sweep_params"] = params
        out.append(r)
    return _best(out)


def table2(steps=60, taus=(4, 8, 12), quick=False):
    """Alg.1 vs SlowMo vs per-step AdamW across communication intervals."""
    if quick:
        taus, steps = (4,), 24
    rows = []
    ps = run_algo("perstep", steps, taus[0], [dict()])
    rows.append(("adamw_perstep", "n/a", ps["final_eval"], ps["comm_rounds"], {}))
    for tau in taus:
        dsm = run_algo("dsm", steps, tau,
                       [dict(global_lr=g) for g in ((0.5,) if quick else (0.5, 1.0, 2.0))])
        slowmo = run_algo("slowmo", steps, tau,
                          [dict(slow_beta=b, global_lr=1.0)
                           for b in ((0.5,) if quick else (0.4, 0.6, 0.8))])
        improv = float(np.exp(slowmo["final_eval"] - dsm["final_eval"]) - 1) * 100
        rows.append((f"dsm_tau{tau}", f"{tau}x", dsm["final_eval"],
                     dsm["comm_rounds"], dsm["sweep_params"]))
        rows.append((f"slowmo_tau{tau}", f"{tau}x", slowmo["final_eval"],
                     slowmo["comm_rounds"], slowmo["sweep_params"]))
        rows.append((f"improv_tau{tau}_pct", f"{tau}x", improv, 0, {}))
    return rows


def table3(steps=60, tau=8, quick=False):
    """Sophia as the base optimizer."""
    if quick:
        steps = 24
    sp = run_algo("perstep", steps, tau, [dict(base_opt="sophia")])
    dsm = run_algo("dsm", steps, tau,
                   [dict(base_opt="sophia", global_lr=g)
                    for g in ((0.5,) if quick else (0.5, 1.0))])
    sm = run_algo("slowmo", steps, tau,
                  [dict(base_opt="sophia", slow_beta=b)
                   for b in ((0.5,) if quick else (0.4, 0.6))])
    return [
        ("sophia_perstep", "n/a", sp["final_eval"], sp["comm_rounds"], {}),
        (f"dsm_sophia_tau{tau}", f"{tau}x", dsm["final_eval"], dsm["comm_rounds"],
         dsm["sweep_params"]),
        (f"slowmo_sophia_tau{tau}", f"{tau}x", sm["final_eval"], sm["comm_rounds"],
         sm["sweep_params"]),
    ]


def table45(steps=60, tau=8, quick=False):
    """Lookahead / signed Lookahead with n=1 (paper Tables 4-5)."""
    if quick:
        steps = 24
    base = run_algo("perstep", steps, 1, [dict(n_workers=1)])
    la = run_algo("lookahead", steps, tau,
                  [dict(n_workers=1, slow_beta=b, global_lr=1.0)
                   for b in ((0.2,) if quick else (0.1, 0.2))])
    sla = run_algo("signed_lookahead", steps, tau,
                   [dict(n_workers=1, slow_beta=b, global_lr=0.3)
                    for b in ((0.6,) if quick else (0.6, 0.8))])
    return [
        ("adamw_n1", "n/a", base["final_eval"], base["comm_rounds"], {}),
        ("lookahead", "n/a", la["final_eval"], la["comm_rounds"], la["sweep_params"]),
        ("signed_lookahead", "n/a", sla["final_eval"], sla["comm_rounds"],
         sla["sweep_params"]),
    ]


def table6(steps=60, tau=8, quick=False):
    """signed SlowMo and global AdamW ablations (paper Table 6)."""
    if quick:
        steps = 24
    sm = run_algo("slowmo", steps, tau,
                  [dict(slow_beta=b) for b in ((0.5,) if quick else (0.4, 0.6))])
    ssm = run_algo("signed_slowmo", steps, tau,
                   [dict(slow_beta=b, global_lr=g)
                    for b in ((0.5,) if quick else (0.5, 0.8))
                    for g in ((0.005,) if quick else (0.005, 0.02))])
    ga = run_algo("global_adamw", steps, tau, [dict(global_lr=1.0)])
    dsm = run_algo("dsm", steps, tau,
                   [dict(global_lr=g) for g in ((0.5,) if quick else (0.5, 1.0))])
    return [
        (f"slowmo_tau{tau}", f"{tau}x", sm["final_eval"], sm["comm_rounds"], sm["sweep_params"]),
        (f"signed_slowmo_tau{tau}", f"{tau}x", ssm["final_eval"], ssm["comm_rounds"],
         ssm["sweep_params"]),
        (f"global_adamw_tau{tau}", f"{tau}x", ga["final_eval"], ga["comm_rounds"], {}),
        (f"dsm_tau{tau}", f"{tau}x", dsm["final_eval"], dsm["comm_rounds"], dsm["sweep_params"]),
    ]


def curves(steps=60, tau=8, quick=False):
    """Fig. 1/2 analog: loss vs communication rounds / computation rounds."""
    if quick:
        steps = 24
    out = {}
    for algo, kw in [("dsm", dict(global_lr=0.4)),
                     ("slowmo", dict(slow_beta=0.6)),
                     ("perstep", dict())]:
        s = _settings(algorithm=algo, steps=steps, tau=tau, **kw)
        r = run_training(NANO, s, corpus())
        comm_per_step = tau if algo == "perstep" else 1
        out[algo] = [
            (t + 1, (t + 1) * comm_per_step, (t + 1) * tau, loss)
            for t, loss in enumerate(r["history"])
        ]
    return out


def table_noise(steps=100, tau=8, quick=False):
    """Large-noise regime (theory Remark 2): DSM's strongest claim at CPU
    scale — sign momentum beats SlowMo when local gradients are noisy."""
    if quick:
        steps = 60  # cheap (batch-1, seq-32); keep enough horizon to separate
    kw = dict(b_micro=1, seq=32)
    dsm = run_algo("dsm", steps, tau, [dict(global_lr=1.0)], **kw)
    sm = run_algo("slowmo", steps, tau,
                  [dict(slow_beta=b) for b in ((0.5,) if quick else (0.5, 0.7))], **kw)
    improv = float(np.exp(sm["final_eval"] - dsm["final_eval"]) - 1) * 100
    return [
        ("dsm_noisy", f"{tau}x", dsm["final_eval"], dsm["comm_rounds"], dsm["sweep_params"]),
        ("slowmo_noisy", f"{tau}x", sm["final_eval"], sm["comm_rounds"], sm["sweep_params"]),
        ("improv_noisy_pct", f"{tau}x", improv, 0, {}),
    ]


TABLES = (("table2", table2), ("table3", table3), ("table45", table45),
          ("table6", table6), ("table_noise", table_noise))
TARGETS = tuple(name for name, _ in TABLES) + ("curves",)


def _emit(name, value, derived=""):
    print(f"{name},{value},{derived}", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="all",
                    help="comma list of " + ",".join(TARGETS))
    args = ap.parse_args(argv)
    only = set(args.only.split(","))
    unknown = only - set(TARGETS) - {"all"}
    if unknown:
        ap.error(f"unknown targets {sorted(unknown)}; pick from {TARGETS}")

    def want(x):
        return "all" in only or x in only

    for tname, fn in TABLES:
        if not want(tname):
            continue
        for name, red, val, commr, params in fn(quick=args.quick):
            _emit(f"{tname}_{name}", f"{val:.4f}",
                  f"comm_red={red};rounds={commr};{params}")

    if want("curves"):
        for algo, pts in curves(quick=args.quick).items():
            _emit(f"curve_{algo}_final", f"{pts[-1][3]:.4f}",
                  f"comm_rounds={pts[-1][1]}")


if __name__ == "__main__":
    main()
