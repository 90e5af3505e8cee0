"""Device milliseconds per outer step in the program's ``lm_head`` scope
(logits over the padded vocabulary, logsumexp and the gold gather of the
chunked cross-entropy, forward and backward), averaged over the chips."""

from harness import scopes as SC


def read(run):
    return SC.ms_per_step(run, "lm_head")
