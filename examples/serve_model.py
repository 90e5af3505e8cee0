"""Serving example: train a byte-level model on this repo's own source code
with DSM, then serve batched greedy completions through the model's
decode path (prefill + KV-cache decode_step).

Run:  PYTHONPATH=src python examples/serve_model.py
"""

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.data.pipeline import TextCorpus
from repro.train.trainer import TrainSettings, run_training
from repro.train.serve import generate

CFG = ModelConfig(
    name="bytelm", family="lm", n_layers=3, d_model=96, n_heads=4,
    n_kv_heads=2, d_ff=256, vocab_size=256, head_dim=24,
    pattern=("swa:dense", "swa:dense", "attn:dense"), window=64,
    dtype="float32", param_dtype="float32", vocab_pad_to=256,
)


def main():
    corpus = TextCorpus(root=".", pattern="src/**/*.py")
    s = TrainSettings(algorithm="dsm", n_workers=2, tau=8, steps=40,
                      b_micro=8, seq=192, peak_lr=1e-2, warmup=6,
                      global_lr=0.3, eval_every=10)
    print("training byte-level LM on repro's own source ...")
    r = run_training(CFG, s, corpus, log=print)
    params = r["state"].x0

    prompts = [b"def make_", b"import ja", b"class Mod", b"    return"]
    width = max(len(p) for p in prompts)
    batch = np.stack([
        np.frombuffer(p.rjust(width, b" "), dtype=np.uint8).astype(np.int32)
        for p in prompts
    ])
    toks, stats = generate(params, CFG, jnp.asarray(batch), max_new_tokens=24)
    print(f"\nbatched decode: {stats['tok_per_s']:.1f} tok/s "
          f"(prefill {stats['prefill_s']:.2f}s)")
    for p, t in zip(prompts, np.asarray(toks)):
        completion = bytes(int(x) % 256 for x in t).decode("latin1")
        print(f"  {p.decode():>12s} -> {completion!r}")


if __name__ == "__main__":
    main()
