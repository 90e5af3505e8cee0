"""Training launcher.

Trains on the devices JAX finds (TPU chips, or the CPU under
``JAX_PLATFORMS=cpu``):

    PYTHONPATH=src python -m repro.launch.train --arch gpt2_small \\
        --algorithm dsm --tau 12 --seq 1024 --steps 100

``--arch`` accepts ``<id>`` (FULL config at published widths; on one TPU
v5e chip GPT-2 small fits at W=4 and GPT-2 medium at W=2, each at
b_micro=4, seq=1024), ``<id>_smoke`` (reduced family variant), or ``nano``
(``repro.configs.NANO``).  Rematerialization follows the arch's
``TopologyConfig.remat``.
"""

from __future__ import annotations

import argparse
import os
from typing import Callable, Optional

from repro.configs import NANO, load_arch
from repro.configs.base import ModelConfig

# fixed, so the cache key's path is the same on every run of this checkout
CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir, os.pardir,
    ".jax_cache"))


def compile_cache_dir() -> Optional[str]:
    """The persistent compile cache this process should set, or None when
    ``JAX_COMPILATION_CACHE_DIR`` is set and JAX picks it up itself."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return CACHE_DIR


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compile cache; call once at start-up."""
    import jax

    path = compile_cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _resolve_arch(name: str) -> tuple[ModelConfig, object]:
    if name == "nano":
        return NANO, load_arch("gpt2_small").TOPO
    if name.endswith("_smoke"):
        mod = load_arch(name[: -len("_smoke")])
        return mod.SMOKE, mod.TOPO
    mod = load_arch(name)
    return mod.FULL, mod.TOPO


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="nano")
    ap.add_argument("--algorithm", default="dsm",
                    choices=("dsm", "slowmo", "signed_slowmo", "lookahead",
                             "signed_lookahead", "global_adamw", "local_avg",
                             "perstep", "mv_signsgd"))
    ap.add_argument("--base-opt", default=None)
    ap.add_argument("--tau", type=int, default=None)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--n-workers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--b-micro", type=int, default=4)
    ap.add_argument("--peak-lr", type=float, default=5e-3)
    ap.add_argument("--global-lr", type=float, default=0.3)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--use-kernel", action="store_true",
                    help="fused Pallas kernel for the DSM global step")
    ap.add_argument("--zero-sharded", action="store_true",
                    help="ZeRO-sharded global step over the local devices "
                         "(shard x0/m over worker*zero ranks)")
    ap.add_argument("--device-parallel-local", action="store_true",
                    help="run the tau local steps shard_mapped over the "
                         "worker mesh axis (each device computes only its "
                         "own worker; no inter-worker collectives)")
    # --- robustness (docs/fault_tolerance.md) ---
    ap.add_argument("--faults", default=None,
                    help="seeded fault-injection spec, e.g. "
                         "'drop=0.25,straggle=0.1,nan=0.05,seed=0'")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="atomic rotated checkpoints of the full training "
                         "state land here")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="outer steps between checkpoints "
                         "(default: steps // 5)")
    ap.add_argument("--resume", action="store_true",
                    help="auto-resume bit-exactly from the latest complete "
                         "checkpoint in --checkpoint-dir")
    ap.add_argument("--guard-spike-factor", type=float, default=0.0,
                    help="skip rounds whose loss exceeds this factor times "
                         "the accepted-loss EMA (0 disables)")
    ap.add_argument("--guard-nonfinite", action="store_true",
                    help="skip rounds that produce NaN/inf anywhere in the "
                         "training state")
    # --- observability (docs/observability.md) ---
    ap.add_argument("--run-dir", default=None,
                    help="observability run directory: manifest.json, "
                         "events.jsonl (spans, comm ledger), scalars.csv; "
                         "inspect with `python -m repro.obs summarize <dir>`")
    ap.add_argument("--log-every", type=int, default=0,
                    help="metric flush + log cadence in outer steps "
                         "(default: the eval cadence)")
    ap.add_argument("--profile-steps", default=None, metavar="A:B",
                    help="capture a jax.profiler.trace for the inclusive "
                         "outer-step range A:B into <run-dir>/profile")
    # --- runtime sanitizers (docs/analysis.md) ---
    ap.add_argument("--sanitize", action="store_true",
                    help="transfer guard around the hot loop + recompilation "
                         "counter (the steady-state outer step must compile "
                         "exactly once)")
    ap.add_argument("--sanitize-nans", action="store_true",
                    help="run the loop under jax_debug_nans (chaos tier: "
                         "masked NaNs must never reach a jit output)")
    return ap


def train(args: argparse.Namespace, log: Optional[Callable] = print):
    """Build the run that the launcher's arguments describe and train it.

    Returns ``(cfg, settings, corpus, result)``; ``result`` is
    ``run_training``'s dict.
    """
    from repro.data.pipeline import MarkovCorpus
    from repro.train.trainer import TrainSettings, run_training

    cfg, topo = _resolve_arch(args.arch)
    s = TrainSettings(
        algorithm=args.algorithm, base_opt=args.base_opt or topo.base_opt,
        n_workers=args.n_workers, tau=args.tau or topo.tau, steps=args.steps,
        seq=args.seq, b_micro=args.b_micro, peak_lr=args.peak_lr,
        global_lr=args.global_lr, remat=topo.remat,
        eval_every=max(args.steps // 5, 1),
        use_kernel=args.use_kernel, zero_sharded=args.zero_sharded,
        device_parallel_local=args.device_parallel_local,
        faults=args.faults,
        guard_nonfinite=args.guard_nonfinite,
        guard_spike_factor=args.guard_spike_factor,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        resume=args.resume,
        sanitize=args.sanitize,
        sanitize_nans=args.sanitize_nans,
        run_dir=args.run_dir,
        log_every=args.log_every,
        profile_steps=args.profile_steps,
    )
    corpus = MarkovCorpus(cfg.vocab_size, seed=1)
    return cfg, s, corpus, run_training(cfg, s, corpus, log=log)


def main(argv=None):
    args = build_parser().parse_args(argv)

    enable_compile_cache()
    _, _, _, result = train(args)
    print(f"final eval loss: {result['final_eval']:.4f} "
          f"(comm rounds: {result['comm_rounds']}, tokens: {result['tokens']}, "
          f"skipped rounds: {result['skipped_rounds']}, "
          f"rollbacks: {result['rollbacks']})")
    if args.run_dir:
        print(f"run dir: {args.run_dir} "
              f"(summarize: python -m repro.obs summarize {args.run_dir})")

    if args.checkpoint:
        from repro.checkpoint import checkpoint as CK

        CK.save(args.checkpoint, result["state"].x0
                if hasattr(result["state"], "x0") else result["state"].params,
                step=args.steps)
        print(f"saved checkpoint to {args.checkpoint}.npz")


if __name__ == "__main__":
    main()
