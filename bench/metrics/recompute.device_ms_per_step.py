"""Device milliseconds per outer step in remat recompute: the ops under
JAX's own ``rematted_computation`` component (the forward of each
checkpointed layer run again in the backward), averaged over the chips."""

from harness import scopes as SC


def read(run):
    return SC.ms_per_step(run, SC.RECOMPUTE)
