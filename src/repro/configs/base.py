"""Config system: model architecture + training topology + input shapes.

Every assigned architecture provides a module ``repro.configs.<id>`` with
``FULL`` (the exact assigned config) and ``SMOKE`` (reduced: <=2 layers,
d_model<=512, <=4 experts) ModelConfigs plus a TopologyConfig.

Block pattern language
----------------------
``pattern`` is a repeating tuple of ``"<mixer>:<ffn>"`` strings:
  mixers: attn (full causal GQA) | swa (sliding window) | ssm (Mamba-2 SSD)
          | rglru (RG-LRU recurrent) | encattn (bidirectional)
          | xattn (causal self + cross attention, enc-dec decoder)
  ffn:    dense | moe | none
Layers = pattern tiled to n_layers; full repeats are scanned (stacked
params), the remainder is unrolled.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Tuple

import jax.numpy as jnp


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # "lm" | "encdec" | "vlm"
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None   # default d_model // n_heads
    pattern: Tuple[str, ...] = ("attn:dense",)
    window: int = 1024               # sliding-window size for "swa"
    mlp_gated: bool = True           # SwiGLU vs plain 2-matrix MLP
    act: str = "silu"                # silu | gelu
    tie_embeddings: bool = True
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_combine: str = "scatter"   # "scatter" (baseline) | "ksum" (combine-
                                   # before-reduce; see EXPERIMENTS.md SPerf)
    moe_impl: str = "ragged"       # "ragged" (ragged_dot grouped matmul) |
                                   # "dense" (masked all-experts einsum; MXU-
                                   # aligned + TP-clean for small d_ff experts)
    # SSM (Mamba-2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_width: int = 4
    # RG-LRU
    rnn_width: Optional[int] = None  # default d_model
    # enc-dec (audio)
    enc_layers: int = 0
    enc_len: int = 1500              # whisper: 30s of audio -> 1500 frames
    # VLM
    n_patches: int = 0               # patch-embedding tokens prepended
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"          # activations
    param_dtype: str = "bfloat16"
    vocab_pad_to: int = 512          # pad vocab so the table shards evenly
    q_block: int = 1024              # blockwise-attention query tile
    attn_seq_shard: bool = False     # constrain attention activations to
                                     # sequence-sharding over the model axis
                                     # (SPerf iteration; needs mesh context)

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, self.vocab_pad_to)

    @property
    def act_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def p_dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def d_inner(self) -> int:        # Mamba-2 inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def d_rnn(self) -> int:
        return self.rnn_width if self.rnn_width is not None else self.d_model

    def layer_kinds(self) -> Tuple[str, ...]:
        reps = -(-self.n_layers // len(self.pattern))
        return (self.pattern * reps)[: self.n_layers]

    @property
    def n_scan_blocks(self) -> int:
        return self.n_layers // len(self.pattern)

    @property
    def n_rem_layers(self) -> int:
        return self.n_layers % len(self.pattern)


# The CPU-sized GPT the tests, the CI smoke run (``--arch nano``) and the
# paper-table analogs train.
NANO = ModelConfig(
    name="nano_gpt", family="lm", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=128, vocab_size=64, head_dim=16, mlp_gated=False,
    act="gelu", dtype="float32", param_dtype="float32", vocab_pad_to=64,
)


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """Training defaults for this arch."""

    n_workers_single: int = 16   # paper's n, single-pod (worker axis size)
    n_workers_multi: int = 32    # multi-pod
    grad_accum: int = 1          # microbatches per local step
    base_opt: str = "adamw"      # base optimizer for local steps
    momentum_dtype: str = "float32"  # global sign-momentum buffer dtype
    tau: int = 12                # paper's communication interval
    remat: bool = True
    remat_policy: str = "full"   # "full" | "dots" (save matmul outputs —
                                 # fewer recompute bytes, higher peak)
    # which decode shapes this arch supports (DESIGN.md skips)
    supports_long_context: bool = False


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


ARCH_IDS = (
    "minitron_4b",
    "granite_moe_3b_a800m",
    "gemma3_1b",
    "granite_34b",
    "whisper_large_v3",
    "llava_next_34b",
    "deepseek_67b",
    "mamba2_780m",
    "llama4_maverick_400b_a17b",
    "recurrentgemma_2b",
)

PAPER_ARCH_IDS = ("gpt2_small", "gpt2_medium", "gpt2_large")


def load_arch(arch_id: str):
    """Returns the config module for an arch id (exposes FULL, SMOKE, TOPO)."""
    mod = importlib.import_module(f"repro.configs.{arch_id}")
    return mod


def arch_supports_shape(cfg: ModelConfig, topo: TopologyConfig, shape_name: str) -> bool:
    if shape_name == "long_500k":
        return topo.supports_long_context
    return True
