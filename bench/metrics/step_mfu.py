"""Model FLOP/s utilization of the whole outer step in the traced window:
model FLOPs per token (PaLM's convention, remat not counted) times tokens
per second per chip, over the device kind's bf16 peak, in percent."""


def read(run):
    return (100.0 * run.flops_per_token * run.tokens_per_s_per_chip
            / run.peak["bf16_flops"])
