"""The comparison that decides ``correct``.

Four numbers of the trained state, each against a limit of its own
(``bench/limits/<cell>.json``):

  * ``loss``: the largest relative gap, over the checked outer steps, of the
    step's mean loss from the reference's;
  * ``grad``: by the worst leaf, the gap between the program's and the
    reference's norm of the first pseudo-gradient Delta (as the global
    optimizer holds it after step 1), over the larger of the reference's
    norm of that leaf and of the median leaf;
  * ``change``: the same for x0's change over the checked steps;
  * ``grad_dir``: by the worst leaf, 1 - cos of the angle between the
    program's and the reference's first pseudo-gradient.  The norms above
    move only to second order under random rounding (and the loss at
    random weights sits near ln(vocab)), so lower-precision arithmetic
    shows in the direction first.

Leaves whose reference Delta is nought to rounding (a norm under a
thousandth of the median leaf's) move by round-off alone; the leaf numbers
leave them out.

The reference trains on the rows the program's input layer fed (that layer
is part of what the window measures, and a later change may draw its rows
another way), so the feed is held to what the mix states, as a fifth
number, ``rows``: the rows of the checked steps that sit in a batch of
another shape than (workers, tau, accum, b_micro, seq), hold a token
outside the vocabulary, or repeat an earlier row, so that every worker,
local step and microbatch trains on rows of its own.  Its limit is 0.
"""

from __future__ import annotations

import statistics

import numpy as np

ZERO_GRAD = 1e-3


def kept_leaves(ref_delta0: list) -> list:
    med = statistics.median(ref_delta0)
    return [i for i, n in enumerate(ref_delta0) if n >= ZERO_GRAD * med]


def leaf_gap(prog: list, ref: list, keep: list) -> float:
    med = statistics.median(ref[i] for i in keep)
    return max(abs(prog[i] - ref[i]) / max(ref[i], med) for i in keep)


def cosine_gaps(prog_leaves: list, ref_leaves: list) -> list:
    """Per leaf, 1 - cos of the angle between the two pseudo-gradients."""
    gaps = []
    for a, b in zip(prog_leaves, ref_leaves):
        a = np.asarray(a, np.float64).ravel()
        b = np.asarray(b, np.float64).ravel()
        norms = float(np.linalg.norm(a) * np.linalg.norm(b))
        # a pseudo-gradient of nought has no direction: the widest gap
        gaps.append(1.0 - float(a @ b) / norms if norms > 0 else 1.0)
    return gaps


def compare(prog, ref) -> dict:
    """``prog``, ``ref``: ``dsm_reference.Readings`` of the same steps."""
    keep = kept_leaves(ref.delta0)
    return {
        "loss": max(abs(p - r) / abs(r) for p, r in zip(prog.loss, ref.loss)),
        "grad": leaf_gap(prog.delta0, ref.delta0, keep),
        "change": leaf_gap(prog.change, ref.change, keep),
        "grad_dir": max(cosine_gaps([prog.delta0_leaves[i] for i in keep],
                                    [ref.delta0_leaves[i] for i in keep])),
    }


def feed_shape(mix: dict) -> tuple:
    """The shape of one outer step's token batch that the mix states."""
    return tuple(mix[k] for k in ("n_workers", "tau", "accum", "b_micro",
                                  "seq"))


def unsound_rows(batches: list, mix: dict, vocab: int) -> int:
    """Rows of ``batches`` (token arrays) in a batch of the wrong shape,
    with a token outside [0, vocab), or equal to an earlier row."""
    shape = feed_shape(mix)
    bad, seen = 0, set()
    for tokens in batches:
        t = np.asarray(tokens)
        if t.shape != shape:
            bad += max(1, t.size // shape[-1])
            continue
        rows = t.reshape(-1, shape[-1])
        bad += int(np.sum(np.any((rows < 0) | (rows >= vocab), axis=1)))
        for row in rows:
            key = row.tobytes()
            bad += key in seen
            seen.add(key)
    return bad


def verdict(numbers: dict, limits: dict) -> bool:
    """Every number that has a limit is there and at or under it (a NaN
    fails)."""
    return all(k in numbers and numbers[k] <= limits[k] for k in limits)
