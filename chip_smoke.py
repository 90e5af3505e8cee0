"""Smoke test: full-width GPT-2 small trains with DSM on TPU v5e.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips, one worker per chip

One chip: ``python -m repro.launch.train --arch gpt2_small`` with W=4
simulated workers, tau=12, b_micro=4, seq=1024 (remat on, state donated)
for 3 outer steps under the sanitizers.  It checks that every logged loss
is finite, that the loss falls, and that the outer step compiled once.
Then it runs one more outer step with the fused Pallas global-step kernel
from the trained state, checks that the kernel is compiled in
(``tpu_custom_call``), compares its x0 with the jnp path's, and checks that
on identical inputs the kernel's update is bit-exact.

Four chips (``--chips 4``): the same run with ``--device-parallel-local``,
once with the ZeRO-sharded global step and once with the replicated one.
It checks that the mesh spans the four chips and that the per-step losses
of the two runs agree.

Everything runs in this one process.  Without a TPU it exits non-zero.  The
last line of stdout is ``{"ok": true, "device": {...}}``, printed only when
every check passed.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

W, TAU, B_MICRO, SEQ, STEPS = 4, 12, 4, 1024, 3
LAUNCH_ARGS = [
    "--arch", "gpt2_small", "--algorithm", "dsm", "--base-opt", "adamw",
    "--n-workers", str(W), "--tau", str(TAU), "--b-micro", str(B_MICRO),
    "--seq", str(SEQ), "--steps", str(STEPS), "--sanitize",
]
# Per-step losses of the sharded and replicated global steps: x_tau is
# reduced in a different order, and a few-ulp difference can flip signs of
# the global update.  Allow one bf16 ulp of the loss (2^-8 relative).
LOSS_RTOL = 2.0 ** -8


class SmokeError(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


class CompileClock:
    """Backend compile seconds and persistent-cache hits in this process."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def launch(extra):
    from repro.launch import train as LT

    args = LT.build_parser().parse_args(LAUNCH_ARGS + list(extra))
    return LT.train(args, log=print)


def check_training(s, result) -> None:
    hist = result["history"]
    evals = [e for _, e in result["eval_losses"]]
    print(f"train losses: {hist}")
    print(f"eval losses: {evals}")
    check(s.remat, "the gpt2_small topology must turn remat on")
    check(len(hist) == STEPS, f"{len(hist)} outer steps logged, want {STEPS}")
    check(all(math.isfinite(x) for x in hist + evals), "a loss is not finite")
    check(hist[-1] < hist[0], f"train loss did not fall: {hist[0]} -> {hist[-1]}")
    check(result["step_compiles"] == 1,
          f"train_step compiled {result['step_compiles']} times, want 1")


def peak_bytes() -> dict:
    import jax

    return {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()}


def kernel_step_check(cfg, s, corpus, state) -> None:
    """One more outer step from ``state`` on the jnp path and on the fused
    kernel path: the kernel must be compiled in, its x0 must agree with the
    jnp path's, and on identical inputs its update must be bit-exact."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import DSMConfig
    from repro.core.dsm import global_sign_momentum_step
    from repro.data.pipeline import dsm_batches
    from repro.models import transformer as T
    from repro.train.trainer import build_algorithm

    batches = dsm_batches(corpus, s.n_workers, s.tau, 1, s.b_micro, s.seq,
                          seed=s.seed, heterogeneous=s.heterogeneous)
    for _ in range(STEPS):
        next(batches)
    batch = jax.tree.map(jnp.asarray, next(batches))
    key = jax.random.PRNGKey(s.seed)
    # two copies of the state and two steps' temporaries do not fit one
    # chip: keep the state on the host and give each step its own copy
    host_state = jax.device_get(state)
    del state

    def loss_fn(p, mb):
        return T.loss_fn(p, mb, cfg, remat=s.remat)

    x0, gamma = {}, None
    for use_kernel in (False, True):
        _, stepper, _, _ = build_algorithm(
            loss_fn, dataclasses.replace(s, use_kernel=use_kernel))
        dev_state = jax.device_put(host_state)
        compiled = jax.jit(stepper, donate_argnums=0).lower(
            dev_state, batch, key).compile()
        if use_kernel:
            check("tpu_custom_call" in compiled.as_text(),
                  "the kernel step holds no tpu_custom_call: Pallas did not "
                  "compile for the chip")
        new_state, metrics = compiled(dev_state, batch, key)
        x0[use_kernel] = jax.device_get(new_state.x0)
        gamma = float(metrics["gamma"])
        del new_state, dev_state

    def compare(a, b):
        a = [np.asarray(l, np.float32) for l in jax.tree.leaves(a)]
        b = [np.asarray(l, np.float32) for l in jax.tree.leaves(b)]
        return (max(float(np.abs(x - y).max()) for x, y in zip(a, b)),
                sum(int((x != y).sum()) for x, y in zip(a, b)),
                sum(x.size for x in a))

    # The two outer steps are separate programs, and the compiler may keep
    # the jnp path's worker mean unrounded where the kernel reads it as
    # bf16.  Where sign(u) then flips, an element moves by 2*eta*gamma;
    # rounding adds one bf16 ulp of the largest weight.
    diff, n_diff, n_all = compare(x0[False], x0[True])
    tol = 2.0 * s.global_lr * gamma + 2.0 ** -7 * max(
        float(np.abs(l).max()) for l in jax.tree.leaves(x0[False]))
    print(f"kernel outer step: tpu_custom_call present; x0 max |kernel - jnp| "
          f"= {diff!r} (tolerance {tol!r}); elements that differ: "
          f"{n_diff}/{n_all}")
    check(diff <= tol, f"kernel x0 differs from jnp x0 by {diff} > {tol}")

    # On identical inputs the kernel computes the jnp update exactly.
    args = jax.device_put((host_state.x0, host_state.m, x0[False]))
    out = {}
    for use_kernel in (False, True):
        dcfg = DSMConfig(tau=s.tau, global_lr=s.global_lr, beta1=s.dsm_beta1,
                         beta2=s.dsm_beta2, weight_decay=s.dsm_wd,
                         use_kernel=use_kernel)
        out[use_kernel] = jax.device_get(jax.jit(
            lambda x, m, xt: global_sign_momentum_step(x, m, xt, gamma, dcfg)
        )(*args))
    diff_x, _, _ = compare(out[False][0], out[True][0])
    diff_m, _, _ = compare(out[False][1], out[True][1])
    print(f"global update on identical inputs: max |kernel - jnp| "
          f"x0 {diff_x!r}, m {diff_m!r} (tolerance 0)")
    check(diff_x == 0.0 and diff_m == 0.0,
          "the kernel's global update is not bit-exact with the jnp path")


def one_chip(clock) -> None:
    cfg, s, corpus, result = launch([])
    check_training(s, result)
    print(f"tokens trained: {result['tokens']}")
    print(f"wall seconds (train loop, compile included): {result['wall_s']!r}")
    print(f"compile seconds: {clock.seconds!r} "
          f"(persistent cache hits: {clock.cache_hits})")
    print(f"peak_bytes_in_use: {peak_bytes()}")
    kernel_step_check(cfg, s, corpus, result.pop("state"))


def four_chips(clock) -> None:
    import jax

    from repro.core.workers import worker_axis

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, "
          f"JAX has {len(jax.devices())}")
    hists = {}
    for name, extra in (("zero", ["--zero-sharded"]), ("replicated", [])):
        print(f"--- {name} global step ---")
        _, s, _, result = launch(["--device-parallel-local", *extra])
        check_training(s, result)
        state = result.pop("state")
        for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]:
            check(len(leaf.sharding.device_set) == 4,
                  f"worker params span {len(leaf.sharding.device_set)} devices")
            ax = worker_axis(path)
            check(leaf.sharding.shard_shape(leaf.shape)[ax] == W // 4,
                  "each chip must hold exactly one worker")
        x0_shards = [l.sharding.shard_shape(l.shape) != l.shape
                     for l in jax.tree.leaves(state.x0)]
        check(any(x0_shards) == (name == "zero"),
              f"x0 layout does not match the {name} global step")
        del state
        hists[name] = result["history"]
        print(f"{name}: wall seconds {result['wall_s']!r}, "
              f"peak_bytes_in_use {peak_bytes()}")
    diffs = [abs(a - b) for a, b in zip(hists["zero"], hists["replicated"])]
    print(f"per-step |zero - replicated| loss: {diffs} "
          f"(tolerance {LOSS_RTOL!r} relative)")
    for a, b in zip(hists["zero"], hists["replicated"]):
        check(abs(a - b) <= LOSS_RTOL * abs(b),
              f"sharded loss {a} vs replicated {b}")
    print(f"compile seconds: {clock.seconds!r} "
          f"(persistent cache hits: {clock.cache_hits})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = ap.parse_args().chips

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1

    from repro.launch.train import enable_compile_cache

    print(f"compile cache: {enable_compile_cache() or 'JAX_COMPILATION_CACHE_DIR'}")
    clock = CompileClock()
    try:
        (four_chips if chips == 4 else one_chip)(clock)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
