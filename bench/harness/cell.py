"""The system under test, as one benchmark cell drives it.

Everything called here is the program's own: the DSM outer step that
``repro.train.trainer.build_algorithm`` returns, jitted with its state
donated as ``run_training`` jits it; the state from the algorithm's
``init`` (on four chips through ``launch/mesh.host_training_mesh`` and
``distributed/zero.shard_dsm_state``, as ``run_training`` builds it); the
batches from ``data/pipeline.dsm_batches`` over ``MarkovCorpus``, made on
the host and put on the device step by step.  The weights are made here
from the seed, on the device, in one jitted call.
"""

from __future__ import annotations

import inspect

import jax
import jax.numpy as jnp

F32 = jnp.float32


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def leaf_norms(tree) -> list:
    return [jnp.sqrt(jnp.sum(jnp.square(l.astype(F32))))
            for l in jax.tree.leaves(tree)]


class ProgramCell:
    """One configuration under one mix: the program's compiled outer step,
    its state, and its feed."""

    def __init__(self, spec):
        from repro.core import base_opt
        from repro.models import transformer as T
        from repro.train.trainer import TrainSettings, build_algorithm

        conf, mix, ref = spec.conf, spec.mix, spec.ref
        if mix["algorithm"] != "dsm" or mix["base_opt"] != "adamw":
            raise ValueError("this harness drives the DSM step with AdamW")
        if mix["schedule"] != "constant":
            raise ValueError("the reference follows a constant learning rate")
        defaults = {k: p.default for k, p in
                    inspect.signature(base_opt.adamw).parameters.items()}
        for k, v in mix["adamw"].items():
            if defaults[k] != v:
                raise ValueError(f"the program's AdamW {k}={defaults[k]} is "
                                 f"not the mix's {v}")
        ds = mix["dsm"]
        self.conf, self.mix, self.ref = conf, mix, ref
        self.cfg = spec.program.program_config(conf, spec.config_name)
        self.settings = s = TrainSettings(
            algorithm="dsm", base_opt="adamw", n_workers=mix["n_workers"],
            tau=mix["tau"], b_micro=mix["b_micro"], seq=mix["seq"],
            peak_lr=conf["peak_lr"], schedule="constant",
            global_lr=ds["global_lr"], dsm_beta1=ds["beta1"],
            dsm_beta2=ds["beta2"], dsm_wd=ds["weight_decay"],
            sign_mode=ds["sign_mode"], remat=mix["remat"],
            heterogeneous=mix["heterogeneous"], use_kernel=mix["use_kernel"],
            zero_sharded=mix["global_step"] == "zero",
            device_parallel_local=mix["layout"] == "device_parallel",
        )
        self.mesh = None
        if s.zero_sharded or s.device_parallel_local:
            from repro.launch.mesh import host_training_mesh

            self.mesh = host_training_mesh(s.n_workers)
        cfg = self.cfg

        def loss_fn(p, mb):
            return T.loss_fn(p, mb, cfg, remat=s.remat)

        self._init, self.stepper, _, _ = build_algorithm(loss_fn, s,
                                                          mesh=self.mesh)

        def train_step(state, batch):
            return self.stepper(state, batch, None)

        self.train_step = train_step
        self.step = jax.jit(train_step, donate_argnums=(0,))
        want = jax.tree.structure(jax.eval_shape(
            lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0)))
        got = jax.tree.structure(ref.weight_shapes(conf),
                                 is_leaf=ref._is_shape)
        if want != got:
            raise ValueError(f"the program's parameter layout {want} is not "
                             f"the one the configuration's weights take {got}")
        self.weights = jax.jit(lambda k: ref.init_weights(conf, k))
        beta2 = ds["beta2"]
        # per-leaf norms the comparison reads, compiled in set-up
        self.delta0_norms = jax.jit(
            lambda st: [n / (1.0 - beta2) for n in leaf_norms(st.m)])
        self.change_norms = jax.jit(
            lambda st, k: leaf_norms(jax.tree.map(
                lambda a, b: a.astype(F32) - b.astype(F32),
                st.x0, ref.init_weights(conf, k))))

    @property
    def tokens_per_step(self) -> int:
        m = self.mix
        return m["n_workers"] * m["tau"] * m["accum"] * m["b_micro"] * m["seq"]

    def init_state(self, seed: int):
        return self._init(self.weights(seed_key(seed)), self.mix["n_workers"])

    def batches(self, seed: int):
        from repro.data.pipeline import MarkovCorpus, dsm_batches

        m = self.mix
        corpus = MarkovCorpus(self.conf["vocab_size"], seed=seed)
        return dsm_batches(corpus, m["n_workers"], m["tau"], m["accum"],
                           m["b_micro"], m["seq"], seed=seed,
                           heterogeneous=m["heterogeneous"])

    @staticmethod
    def put(raw: dict):
        return jax.tree.map(jnp.asarray, raw)
