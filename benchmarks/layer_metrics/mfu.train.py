"""Train step: model FLOP/s utilisation. Operations the forward and backward
passes need per token (the family's count; recomputation not counted) times
tokens per second per chip, over the chip's bf16 peak from peaks.json."""
from harness import load_module


def read(run):
    rate = load_module("e2e_metrics", "train_tokens_per_s_chip").read(run)
    if rate is None:
        return None
    return 100.0 * run.flops_per_token * rate / run.peaks["bf16_flops_per_s"]
