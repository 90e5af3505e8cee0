"""Deterministic, seeded fault injection for the DSM outer loop.

A :class:`FaultPlan` pre-draws, from one numpy seed, which workers fail in
which outer round and *how*:

  * **drop**     — the worker's contribution never arrives; the survivor-
    aware global step excludes it from the x_tau mean and the worker simply
    re-syncs from x_{t+1,0} at the next round (Algorithm 1's broadcast).
  * **straggle** — the worker misses the communication deadline and delivers
    a stale iterate (its round-start x_{t,0}, i.e. a zero pseudo-gradient
    contribution that dilutes the mean but never poisons it).
  * **corrupt**  — the delivered contribution is NaN-poisoned (flaky HBM /
    wire corruption / a diverged local phase).  The global step must DETECT
    this (per-worker finiteness mask) — corruption is never announced.

The same plan object drives the trainer (``TrainSettings.faults``), the
launcher (``--faults "drop=0.25,straggle=0.1,nan=0.05,seed=0"``), and the
chaos tests, so every faulty run is bit-reproducible — including across a
kill + ``--resume``, because rounds are indexed by the outer step ``t``.

Sign-based aggregation is unusually robust to this fault model (signSGD's
majority-vote heritage, Bernstein et al. 2018): a dropped or stale worker
shifts the pseudo-gradient mean, but only its *sign* reaches x0.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import workers as WK

PyTree = object


class FaultRound(NamedTuple):
    """One outer round's faults, as jit-traceable ``(W,)`` bool arrays."""

    survivors: jnp.ndarray  # True where the contribution arrives at all
    stale: jnp.ndarray      # True where the contribution is the stale x_{t,0}
    corrupt: jnp.ndarray    # True where the contribution is NaN-poisoned


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """Per-round, per-worker fault probabilities + the plan seed."""

    p_drop: float = 0.0
    p_straggle: float = 0.0
    p_corrupt: float = 0.0
    seed: int = 0

    def __post_init__(self):
        for name in ("p_drop", "p_straggle", "p_corrupt"):
            p = getattr(self, name)
            if not 0.0 <= p <= 1.0:
                raise ValueError(f"{name}={p} must lie in [0, 1]")

    _KEYS = {"drop": "p_drop", "straggle": "p_straggle", "nan": "p_corrupt",
             "corrupt": "p_corrupt", "seed": "seed"}

    @classmethod
    def parse(cls, spec: str) -> "FaultSpec":
        """Parse the CLI form ``"drop=0.25,straggle=0.1,nan=0.05,seed=3"``."""
        kw = {}
        for item in spec.split(","):
            item = item.strip()
            if not item:
                continue
            if "=" not in item:
                raise ValueError(f"bad fault spec item {item!r} in {spec!r}")
            k, v = item.split("=", 1)
            k = k.strip().lower()
            if k not in cls._KEYS:
                raise ValueError(
                    f"unknown fault key {k!r}; have {sorted(cls._KEYS)}")
            field = cls._KEYS[k]
            kw[field] = int(v) if field == "seed" else float(v)
        return cls(**kw)


class FaultPlan:
    """Pre-drawn ``(steps, W)`` fault masks; ``round(t)`` yields the round's
    :class:`FaultRound`.  Rounds beyond ``steps`` are fault-free (so a run
    extended past the planned horizon degrades gracefully).

    Each round's draws are seeded by ``(spec.seed, t)``, NOT consumed from
    one stream over the whole plan — so the faults of round t are identical
    no matter the plan horizon.  This is what makes kill + resume exact even
    when the resumed run is configured with a different ``steps``."""

    def __init__(self, n_workers: int, steps: int, spec: FaultSpec):
        if n_workers < 1 or steps < 0:
            raise ValueError("need n_workers >= 1 and steps >= 0")
        self.n_workers = n_workers
        self.steps = steps
        self.spec = spec
        self.drop = np.zeros((steps, n_workers), bool)
        self.stale = np.zeros((steps, n_workers), bool)
        self.corrupt = np.zeros((steps, n_workers), bool)
        for t in range(steps):
            rng = np.random.default_rng((spec.seed, t))
            self.drop[t] = rng.random(n_workers) < spec.p_drop
            self.stale[t] = rng.random(n_workers) < spec.p_straggle
            self.corrupt[t] = rng.random(n_workers) < spec.p_corrupt

    @classmethod
    def from_spec(cls, spec: Union[str, FaultSpec], n_workers: int,
                  steps: int) -> "FaultPlan":
        if isinstance(spec, str):
            spec = FaultSpec.parse(spec)
        return cls(n_workers, steps, spec)

    def round(self, t: int) -> FaultRound:
        if 0 <= t < self.steps:
            drop, stale, corrupt = self.drop[t], self.stale[t], self.corrupt[t]
        else:
            drop = stale = corrupt = np.zeros((self.n_workers,), bool)
        return FaultRound(
            survivors=jnp.asarray(~drop),
            stale=jnp.asarray(stale),
            corrupt=jnp.asarray(corrupt),
        )

    def dropped_frac(self) -> float:
        """Fraction of (round, worker) contributions dropped — for comm
        accounting (``repro.analysis.comm``: ``survivor_frac = 1 -
        dropped_frac``)."""
        return float(self.drop.mean()) if self.drop.size else 0.0


def apply_faults(params_w: PyTree, x0: PyTree, faults: FaultRound) -> PyTree:
    """Transform the delivered per-worker iterates per the round's faults.

    Stale workers deliver the round-start ``x0`` (they never finished their
    tau local steps); corrupt workers deliver NaN.  Dropped workers are NOT
    transformed here — exclusion is the *aggregator's* job (the survivor
    mask in the masked worker mean), since a real dropout delivers nothing.
    """

    def leaf(path, p, g):
        stale = WK.along_workers(faults.stale, path, p)
        corrupt = WK.along_workers(faults.corrupt, path, p)
        g = jnp.expand_dims(g, WK.worker_axis(path)).astype(p.dtype)
        out = jnp.where(stale, g, p)
        return jnp.where(corrupt, jnp.asarray(jnp.nan, p.dtype), out)

    return jax.tree_util.tree_map_with_path(leaf, params_w, x0)
