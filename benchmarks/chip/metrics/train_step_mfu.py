"""The whole training step's share of the chip's bf16 peak: the forward
and backward FLOPs per token that the configuration's reference module
counts (``train_flops_per_token``, recompute not counted) times the
tokens per second of the traced window, over the peak."""


def read(run):
    if run.kind != "train" or not run.tokens_per_s:
        return None
    flops = run.flops_per_token * run.tokens_per_s
    return 100.0 * flops / run.peak["bf16_flops_per_s"]
