"""How the program is told to run a GPT-2 configuration file: the
``repro.configs.base.ModelConfig`` built from the file's published sizes.
Kept apart from ``gpt2_reference.py``, which imports nothing of the
program."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import gpt2_reference as R  # noqa: E402


def program_config(conf: dict, name: str):
    """The program's ModelConfig for a GPT-2 configuration file."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=name, family="lm", n_layers=conf["n_layer"],
        d_model=conf["n_embd"], n_heads=conf["n_head"],
        n_kv_heads=conf["n_head"], d_ff=R.d_ff(conf),
        vocab_size=conf["vocab_size"],
        head_dim=conf["n_embd"] // conf["n_head"], pattern=("attn:dense",),
        mlp_gated=False, act="gelu", tie_embeddings=True,
        rope_theta=R.ROPE_THETA, norm_eps=conf["layer_norm_epsilon"],
        dtype=conf["activation_dtype"], param_dtype=conf["param_dtype"],
        vocab_pad_to=R.VOCAB_PAD_TO, q_block=conf["n_positions"],
    )
