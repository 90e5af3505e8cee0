"""The benchmark's harness: the cell it drives, the reference it checks
against, and the reduction of traces to per-layer numbers."""
