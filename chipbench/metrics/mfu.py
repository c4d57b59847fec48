"""Model FLOPs of the traced steps over the window, as a share of the
cell's chips' bf16 peak (counts.train_flops_per_step)."""

from chipbench import counts


def read(ctx):
    flops = counts.train_flops_per_step(ctx.config, ctx.traffic) * ctx.steps
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops / ctx.trace.window_s / peak
