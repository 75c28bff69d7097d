"""The whole training step's share of the chip's bf16 peak: the forward
and backward FLOPs per token (chipbench.work, recompute not counted)
times the tokens per second of the traced window, over the peak."""
from chipbench.work import train_flops_per_token


def read(run):
    if run.kind != "train" or not run.tokens_per_s:
        return None
    flops = train_flops_per_token(run.model, run.seq) * run.tokens_per_s
    return 100.0 * flops / run.peak["bf16_flops_per_s"]
