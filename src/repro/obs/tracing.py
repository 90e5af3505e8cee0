"""Phase span tracing: fenced wall-time spans, optional profiler windows,
and device memory stats (docs/observability.md).

JAX dispatch is asynchronous, so a bare ``time.monotonic()`` pair around a
jitted call measures dispatch, not execution.  ``Span`` fences its exit on
``jax.block_until_ready`` over whatever values the caller hands it, which
makes the wall time honest at the cost of a pipeline bubble — so the
trainer opens spans around *windows* (a whole log interval, an eval, a
checkpoint), never around every step.  Each span is also a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so inside a
profiler capture it lies on the host plane, on the device ops' clock.

``ProfileWindow`` arms ``jax.profiler.trace`` for an inclusive step range
(the ``--profile-steps A:B`` flag); the TensorBoard-loadable capture lands
in ``<run_dir>/profile``.  ``jax.named_scope`` annotations inside the
outer step (``dsm_local_phase`` / ``dsm_global_step``, and within the
local phase ``attention`` / ``mlp`` / ``lm_head`` / ``base_opt``) reach
the compiled HLO's ``op_name``, which names each device op of a capture.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import jax


class Span:
    """Context manager measuring a fenced wall-time span.

    ``fence`` values (any pytrees of arrays) are blocked on at exit before
    the clock stops; add them as they become available via ``add_fence``.
    """

    def __init__(self, name: str, *fence: Any):
        self.name = name
        self.seconds = 0.0
        self._fence = list(fence)
        self._t0 = 0.0
        self._annotation = None

    def add_fence(self, *values: Any) -> None:
        self._fence.extend(values)

    def __enter__(self) -> "Span":
        self._annotation = jax.profiler.TraceAnnotation(f"repro.{self.name}")
        self._annotation.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type: Any, *exc: Any) -> None:
        try:
            if exc_type is None and self._fence:
                jax.block_until_ready(self._fence)
            self.seconds = time.monotonic() - self._t0
            self._fence = []
        finally:
            self._annotation.__exit__(exc_type, *exc)


class PhaseTotals:
    """Accumulates span seconds / counts per phase name."""

    def __init__(self) -> None:
        self.seconds: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}

    def add(self, name: str, seconds: float, n: int = 1) -> None:
        self.seconds[name] = self.seconds.get(name, 0.0) + float(seconds)
        self.counts[name] = self.counts.get(name, 0) + int(n)

    def ms_per(self, name: str) -> Optional[float]:
        n = self.counts.get(name, 0)
        if n <= 0:
            return None
        return 1e3 * self.seconds[name] / n

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        return {
            name: {
                "seconds": self.seconds[name],
                "count": self.counts[name],
                "ms_per": self.ms_per(name) or 0.0,
            }
            for name in sorted(self.seconds)
        }


def parse_profile_steps(spec: Optional[str]) -> Optional[Tuple[int, int]]:
    """Parse ``"A:B"`` into an inclusive step range; None when unset."""
    if not spec:
        return None
    try:
        a_s, b_s = spec.split(":")
        a, b = int(a_s), int(b_s)
    except ValueError as e:
        raise ValueError(
            f"--profile-steps expects 'A:B' (got {spec!r})"
        ) from e
    if a < 0 or b < a:
        raise ValueError(f"--profile-steps needs 0 <= A <= B (got {spec!r})")
    return a, b


class ProfileWindow:
    """Arms ``jax.profiler.trace`` while the outer step is inside [A, B]."""

    def __init__(self, steps: Optional[Tuple[int, int]], out_dir: str):
        self.steps = steps
        self.out_dir = out_dir
        self.active = False

    def tick(self, step: int) -> None:
        """Call once per outer step, before running it.  A profiler that
        cannot start raises: a requested trace is never silently missing."""
        if self.steps is None:
            return
        a, b = self.steps
        if not self.active and a <= step <= b:
            jax.profiler.start_trace(self.out_dir)
            self.active = True
        elif self.active and step > b:
            self._stop()

    def _stop(self) -> None:
        self.active = False
        jax.profiler.stop_trace()

    def close(self) -> None:
        if self.active:
            self._stop()


def device_memory_stats() -> Optional[Dict[str, Any]]:
    """Live/peak bytes per device, or None when the backend (e.g. CPU)
    doesn't expose memory stats."""
    out = {}
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        out[str(d)] = {
            k: int(v)
            for k, v in stats.items()
            if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        }
    return out or None

