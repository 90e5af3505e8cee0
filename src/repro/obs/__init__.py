"""Observability: on-device metrics, structured run sinks, phase tracing,
and the comm ledger (docs/observability.md).

Layout:

  * ``metrics``   — the on-device metric pack computed INSIDE the jitted
                    outer step (pure jnp; safe to import from core).
  * ``sinks``     — per-run directory: manifest.json / events.jsonl /
                    scalars.csv (host-side only).
  * ``tracing``   — wall-time spans, ``jax.profiler.trace`` windows,
                    device memory stats.
  * ``ledger``    — observed (compiled-HLO) vs predicted (analytic model)
                    communication bytes.
  * ``summarize`` — ``python -m repro.obs summarize <run_dir>`` CLI.
"""

import jax

# The device scopes of the outer step (docs/observability.md section 3) are
# op_name metadata, which JAX's persistent compile cache leaves out of its
# key by default: an executable cached by a build with other scopes would be
# served with that build's op_names, and a profile would read stale scopes.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

from repro.obs.metrics import (  # noqa: E402
    IDX,
    METRIC_NAMES,
    N_METRICS,
    finish_pack,
    loss_stats,
    minimal_pack,
    tree_stat_sums,
)
from repro.obs.sinks import RunWriter, build_manifest, read_run  # noqa: E402
from repro.obs.summarize import summarize_run  # noqa: E402

__all__ = [
    "IDX",
    "METRIC_NAMES",
    "N_METRICS",
    "RunWriter",
    "build_manifest",
    "finish_pack",
    "loss_stats",
    "minimal_pack",
    "read_run",
    "summarize_run",
    "tree_stat_sums",
]
