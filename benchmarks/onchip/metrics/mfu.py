"""Model FLOP/s utilization of the whole step, in %: model FLOPs per token
(flops.py) x the traced window's tokens per second, over chips x the
chip's bf16 peak."""


def read(ctx):
    tokens_per_s = ctx["steps"] * ctx["tokens_per_step"] / ctx["window_s"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops"]
    return 100.0 * ctx["flops_per_token"] * tokens_per_s / peak
