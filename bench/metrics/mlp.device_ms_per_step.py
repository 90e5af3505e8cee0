"""Device milliseconds per outer step in the program's ``mlp`` scope
(RMSNorm, the two projections, the activation and the residual add of each
dense FFN sublayer: forward, backward and remat recompute), averaged over
the chips."""

from harness import scopes as SC


def read(run):
    return SC.ms_per_step(run, "mlp")
