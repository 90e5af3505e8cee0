"""Device milliseconds per outer step in the program's ``base_opt`` scope
(the base optimizer's direction and the parameter update of every local
step of every worker: AdamW in the benchmark's mixes), averaged over the
chips."""

from harness import scopes as SC


def read(run):
    return SC.ms_per_step(run, "base_opt")
