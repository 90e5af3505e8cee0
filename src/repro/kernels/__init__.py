"""Pallas TPU kernels for the paper's optimizer hot-spots and attention.

  dsm_update.py      — fused global sign-momentum step (paper eqs. 6-8)
  adamw_update.py    — fused AdamW local step (paper Alg. 2)
  flash_attention.py — fused causal attention, forward and backward
  ops.py             — jit'd pytree wrappers (pad + lane-align + unpad)
  ref.py             — pure-jnp oracles (allclose targets for tests)

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are
validated on CPU via interpret=True.
"""

from repro.kernels.ops import adamw_update_tree, dsm_update_tree
