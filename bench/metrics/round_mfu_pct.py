"""The whole round's share of the chips' bf16 peak: training FLOPs per
sample from the layer shapes (grouped layers at their grouped cost) times
the traced run's samples per second, over chips times the peak. Eval
FLOPs are not counted."""


def read(ctx):
    flops = ctx.work.train_flops_per_sample(ctx.model) * ctx.samples_per_s
    return 100.0 * flops / (ctx.chips * ctx.peak["bf16_flops_per_s"])
