"""Static analysis & sanitizers for the DSM training system.

Three layers (see docs/analysis.md):

  * ``hlo_audit``  — lower any jitted step to compiled HLO, count the
    collective ops with their shapes, and check them against the per-phase
    budgets derived from the analytic model in ``repro.analysis.comm``.
    The paper's claim IS a collective budget (one reduction per tau local
    steps, none inside them); this makes it machine-checked.
  * ``lint``       — RPR0xx AST rules for the bug classes nothing else
    catches statically: reused ``jax.random`` keys, host syncs inside
    jit-reachable code, Python control flow on traced values, mutable
    config defaults.  No jax import — runs anywhere, fast.
  * ``sanitize``   — opt-in runtime guards for the hot loop: transfer
    guard, log_compiles-based recompilation counter, debug_nans tier.

CLI: ``python -m repro.analysis {audit,lint} [--json]``.

This package intentionally does NOT import jax at package level, so the
lint layer stays usable in environments without a working jax install.
"""

from repro.analysis.lint import Finding, lint_paths, lint_source  # noqa: F401
