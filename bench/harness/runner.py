"""One run of one cell: set-up, the measured window, the check.

Set-up builds the program's compiled outer step and its state from the
seed and drives them through the mix's ``check_steps`` first outer steps
with the window's own call and feed (they are also the warm-up).  The
comparison reads from those steps: each step's loss, the norm of the first
pseudo-gradient as the global optimizer holds it after step 1 (its
momentum over 1 - beta2), and the norm of x0's change after the last of
them.  The window then runs the same state for ``seconds``; the plain
reference follows the same first steps once the window has closed and the
program's state is freed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from harness import check as CH
from harness import dsm_reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Spec:
    """A cell as ``BENCHMARK.json`` names it, with its files read."""

    name: str
    chips: int
    config_name: str
    conf: dict
    mix: dict
    limits: dict
    ref: object           # the configuration's plain reference module
    program: object       # how the program is told to run the configuration
    end_to_end: list
    per_layer: list


def load_spec(workload: str) -> Spec:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg_path = os.path.join(ROOT, config["file"])
    with open(cfg_path) as f:
        conf = json.load(f)
    with open(os.path.join(BENCH, "mixes", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(BENCH, "limits", workload + ".json")) as f:
        limits = json.load(f)
    ref, program = config_modules(cfg_path, conf)

    def applies(m, reported=None):
        if "workloads" in m:
            return workload in m["workloads"]
        return reported is None or m["moves"] in reported

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    per_layer = [m for m in bench["per_layer"]
                 if applies(m, {e["name"] for e in e2e})]
    return Spec(workload, cell["chips"], cell["config"], conf, mix, limits,
                ref, program, e2e, per_layer)


def config_modules(cfg_path: str, conf: dict) -> tuple:
    """The reference and program modules a configuration file names."""
    here = os.path.dirname(cfg_path)
    return tuple(load_module(os.path.join(here, conf[key] + ".py"),
                             f"{key}_{conf[key]}")
                 for key in ("reference", "program"))


class CompileClock:
    """Backend compiles, their seconds, and persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def enable_cache(path: str = CACHE_DIR) -> None:
    """JAX's persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads it), else at a fixed path in the checkout; every
    program is cached, however short its compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@dataclasses.dataclass
class Window:
    steps: int
    seconds: float
    losses: list          # device scalars, one per step
    trace_dir: Optional[str] = None


def run_window(step, state, batches, put, seconds: float,
               trace_dir: Optional[str] = None):
    """Drive ``step`` for ``seconds``: the host makes each batch and puts it
    on the device while the previous step runs (one step in flight ahead);
    the window closes when the last dispatched step has finished."""
    import jax

    ann = jax.profiler.TraceAnnotation
    ctx = contextlib.nullcontext()
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        ctx = jax.profiler.trace(trace_dir, profiler_options=opts)
    losses, prev = [], None
    with ctx:
        with ann("window"):
            t0 = time.monotonic()
            while True:
                with ann("input"):
                    batch = put(next(batches))
                with ann("dispatch"):
                    state, metrics = step(state, batch)
                losses.append(metrics["loss"])
                if prev is not None:
                    with ann("block"):
                        prev.block_until_ready()
                prev = metrics["loss"]
                if time.monotonic() - t0 >= seconds:
                    break
            with ann("block"):
                jax.block_until_ready((state, prev))
            wall = time.monotonic() - t0
    return state, Window(len(losses), wall, losses, trace_dir)


def device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool, t_start: float,
             devices, peak: dict,
             make_step: Optional[Callable] = None) -> dict:
    """Everything of one run after the device check; returns the result."""
    import jax

    from harness.cell import ProgramCell

    clock = CompileClock()
    cell = ProgramCell(spec)
    state = cell.init_state(seed)
    batches = cell.batches(seed)
    fed = [next(batches)]
    compiled = cell.step.lower(state, cell.put(fed[0])).compile()
    step = make_step(cell) if make_step else compiled
    state, prog = first_steps(cell, step, state, batches, fed, seed,
                              spec.mix["check_steps"])
    hlo = compiled.as_text() if trace else None
    setup_compiles = clock.compiles
    setup_s = time.monotonic() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        state, win = run_window(step, state, batches, cell.put, seconds,
                                trace_dir)
        window_compiles = clock.compiles - setup_compiles
        win_losses = [float(x) for x in jax.device_get(win.losses)]
        device = device_info(devices)
        del state, compiled, step
        gc.collect()
        print(f"set-up: {setup_s:.3f} s, backend compiles {setup_compiles} "
              f"({clock.seconds:.3f} s), persistent-cache hits "
              f"{clock.cache_hits} (in {jax.config.jax_compilation_cache_dir}),"
              f" compiles inside the window {window_compiles}", flush=True)

        tokens_per_s_per_chip = (win.steps * cell.tokens_per_step
                                 / win.seconds / spec.chips)
        result = {
            "correct": None,
            "attempted": win.steps,
            "failed": sum(1 for x in win_losses if not math.isfinite(x)),
            "metrics": {},
            "device": device,
        }
        if trace:
            _read_trace(spec, result, win, hlo, tokens_per_s_per_chip, peak,
                        device)
        else:
            values = {"tokens_per_s_per_chip": tokens_per_s_per_chip,
                      "setup_s": setup_s}
            for m in spec.end_to_end:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.monotonic()
    numbers = check(spec, seed, fed, prog, devices)
    result["correct"] = CH.verdict(numbers, spec.limits) and result["failed"] == 0
    # a number without a limit is printed, not compared
    result["checks"] = {k: {"value": v, "limit": spec.limits.get(k)}
                        for k, v in numbers.items()}
    print(f"reference: {time.monotonic() - t_ref:.3f} s for {len(fed)} "
          "outer steps", flush=True)
    return result


def first_steps(cell, step, state, batches, fed: list, seed: int,
                k_steps: int):
    """Drive ``step`` through the first ``k_steps`` outer steps (``fed``
    holds the first batch and collects the rest) and read what the
    comparison needs; returns the state, ready for the window."""
    import jax

    from harness.cell import seed_key

    losses, delta0 = [], None
    for k in range(k_steps):
        if k:
            fed.append(next(batches))
        state, metrics = step(state, cell.put(fed[k]))
        losses.append(metrics["loss"])
        if k == 0:
            delta0 = jax.device_get(cell.delta0_norms(state))
            beta2 = cell.mix["dsm"]["beta2"]
            delta0_leaves = [x / (1.0 - beta2)
                             for x in jax.device_get(jax.tree.leaves(state.m))]
    change = jax.device_get(cell.change_norms(state, seed_key(seed)))
    return state, dsm_reference.Readings(
        loss=[float(x) for x in jax.device_get(losses)],
        delta0=[float(x) for x in delta0], change=[float(x) for x in change],
        delta0_leaves=delta0_leaves)


def check(spec: Spec, seed: int, fed: list, prog, devices) -> dict:
    """The compared numbers of ``prog``'s readings over the ``fed`` rows;
    rows of the wrong shape leave the reference unrun, and its numbers
    out (a missing number fails)."""
    tokens = [f["tokens"] for f in fed]
    rows = CH.unsound_rows(tokens, spec.mix, spec.conf["vocab_size"])
    numbers = {}
    if all(np.shape(t) == CH.feed_shape(spec.mix) for t in tokens):
        numbers = CH.compare(prog, reference(spec, seed, fed, devices))
    numbers["rows"] = rows
    return numbers


def reference(spec: Spec, seed: int, fed: list, devices,
              dot=None) -> "dsm_reference.Readings":
    """The plain reference over the fed batches, weights made from the
    seed (``dot``: the control's matrix product)."""
    import jax

    from harness.cell import seed_key

    return dsm_reference.run(
        lambda w, t, q: spec.ref.loss(w, t, spec.conf, q),
        lambda: jax.jit(lambda k: spec.ref.init_weights(spec.conf, k))(
            seed_key(seed)),
        [f["tokens"] for f in fed], spec.mix, spec.conf["peak_lr"], devices,
        dot=dot)


def _read_trace(spec, result, win, hlo, tokens_per_s_per_chip, peak,
                device) -> None:
    """The per-layer metrics and the breakdown, from the window's trace."""
    import glob

    from harness import trace as TR

    found = glob.glob(os.path.join(win.trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise RuntimeError("the profiler wrote no trace")
    tr = TR.load(found[0])
    if not tr.devices:
        raise RuntimeError("the trace holds no TPU plane")
    lo, hi = tr.window
    busy = {d: TR.busy_ns(ops, (lo, hi)) for d, ops in tr.devices.items()}
    device["busy_s"] = sum(busy.values()) / len(busy) * 1e-9
    device["window_s"] = (hi - lo) * 1e-9
    idlest = min(busy, key=busy.get)
    result["breakdown"] = {
        "device_ops": TR.top_ops(tr.devices[idlest]),
        "idle_gaps": TR.idle_gaps(tr.devices[idlest], tr),
    }
    run = LayerRun(trace=tr, op_names=TR.hlo_op_names(hlo), steps=win.steps,
                   tokens_per_s_per_chip=tokens_per_s_per_chip,
                   flops_per_token=spec.ref.flops_per_token(
                       spec.conf, spec.mix["seq"]),
                   peak=peak, memory_peak_bytes=device["memory_peak_bytes"])
    for m in spec.per_layer:
        reader = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                             "metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}


@dataclasses.dataclass
class LayerRun:
    """What a per-layer metric reader may read."""

    trace: object                 # harness.trace.Trace of the window
    op_names: dict                # instruction -> op_name of the step
    steps: int                    # outer steps in the traced window
    tokens_per_s_per_chip: float
    flops_per_token: float
    peak: dict                    # the device kind's row of peaks.json
    memory_peak_bytes: int


def report(result: dict) -> None:
    """The compared numbers as the last lines of stderr, the result as the
    last line of stdout (its ``checks`` key last)."""
    checks = result.pop("checks", {})
    result["checks"] = checks
    for k, v in checks.items():
        limit = "not compared" if v["limit"] is None else f"limit {v['limit']!r}"
        print(f"check {k}: {v['value']!r} ({limit})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
