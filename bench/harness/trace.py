"""Reduction of a profiler trace (``.xplane.pb``) to per-layer numbers.

What the trace holds, as recorded on a TPU v5e with jax 0.9:

  * one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one
    event per executed HLO instruction, named by the instruction's text
    (``%fusion.12 = bf16[...] fusion(...)``), with start and duration in
    nanoseconds on the host's clock.  A ``while`` event spans the events of
    its body, so events nest; every sum here is over a union of intervals;
  * the host plane ``/host:CPU``, whose lines are threads; the harness's
    ``jax.profiler.TraceAnnotation`` spans (``window``, ``input``,
    ``dispatch``, ``block``) appear there by name.

The instruction events carry no source metadata, so scopes such as the
program's ``jax.named_scope("dsm_local_phase")`` are read from the compiled
module's HLO text (``op_name`` in each instruction's metadata) and joined
by instruction name.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Iterable

COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all", "collective-broadcast")
CONTROL = ("while", "conditional", "call")
ANNOTATIONS = ("window", "input", "dispatch", "block")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                    r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def hlo_op_names(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` metadata of a compiled module."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def instr_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def kind(name: str) -> str:
    """``all-gather-start.3`` -> ``all-gather-start``."""
    return re.sub(r"(\.\d+)+$", "", name)


def is_collective(name: str) -> bool:
    return kind(name).startswith(COLLECTIVES)


def is_control(name: str) -> bool:
    return kind(name) in CONTROL


@dataclasses.dataclass
class Trace:
    """Device op intervals per chip and the harness's host spans, in ns."""

    devices: dict      # plane name -> [(start, end, instruction name)]
    host: list         # [(start, end, annotation name)]

    @property
    def window(self) -> tuple:
        spans = [(a, b) for a, b, n in self.host if n == "window"]
        if not spans:
            raise ValueError("the trace holds no 'window' annotation")
        return min(a for a, _ in spans), max(b for _, b in spans)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns,
                         instr_name(e.name)) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events if e.name in ANNOTATIONS)
    return Trace(devices, sorted(host))


def union(intervals: Iterable) -> list:
    merged = []
    for a, b in sorted((i[0], i[1]) for i in intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def length(intervals: Iterable) -> float:
    return float(sum(b - a for a, b in intervals))


def clip(intervals: Iterable, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b, *_ in intervals
            if b > lo and a < hi]


def subtract(intervals: list, cover: list) -> list:
    """Parts of merged ``intervals`` that merged ``cover`` leaves free."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def busy_ns(ops: list, window: tuple) -> float:
    return length(union(clip(ops, *window)))


def scope_ns(ops: list, op_names: dict, scope: str) -> float:
    """Device time of the ops whose ``op_name`` path carries ``scope``."""
    tagged = [o for o in ops if scope in op_names.get(o[2], "").split("/")]
    return length(union(tagged))


def exposed_collective_ns(ops: list) -> float:
    """Collective device time during which no other (non-control) op runs."""
    coll = union(o for o in ops if is_collective(o[2]))
    other = union(o for o in ops
                  if not is_collective(o[2]) and not is_control(o[2]))
    return length(subtract(coll, other))


def has_collectives(ops: list) -> bool:
    return any(is_collective(o[2]) for o in ops)


def top_ops(ops: list, n: int = 10) -> list:
    """The device ops (by instruction, control flow left out) that took
    most time, summed over their executions."""
    totals: dict = {}
    for a, b, name in ops:
        if not is_control(name):
            totals[name] = totals.get(name, 0.0) + (b - a)
    best = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(ops: list, trace: Trace, n: int = 10) -> list:
    """The longest idle gaps of one chip in the window, each named by the
    innermost harness span the host was in at the gap's middle."""
    lo, hi = trace.window
    gaps = subtract([(lo, hi)], union(clip(ops, lo, hi)))
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (a + b)
        inner = [(s, e, name) for s, e, name in trace.host
                 if s <= mid <= e and name != "window"]
        what = min(inner, key=lambda h: h[1] - h[0])[2] if inner else "other"
        named.append([what, (b - a) * 1e-9])
    return named
