"""Device milliseconds per outer step in the program's ``attention`` scope
(RMSNorm, QKV projection with RoPE, causal attention, output projection and
residual add of each self-attention sublayer: forward, backward and remat
recompute), averaged over the chips."""

from harness import scopes as SC


def read(run):
    return SC.ms_per_step(run, "attention")
