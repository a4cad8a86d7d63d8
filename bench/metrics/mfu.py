"""Model FLOP utilisation of the traced steps: useful forward and
backward FLOPs of each step, counted from its real sampled vertex and
edge counts (``bench/work/<model>.py``), over the step's time on the
host clock and the chip's bf16 peak (``bench/peaks.json``)."""


def read(ctx):
    if ctx.peak is None or not ctx.counts:
        return None
    flops = sum(ctx.work.step_flops(ctx.config, c) for c in ctx.counts)
    return 100.0 * flops / len(ctx.counts) / (
        ctx.step_s * ctx.peak["bf16_flops_per_s"])
