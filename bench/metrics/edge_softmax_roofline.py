"""Roofline share of the edge-softmax statistics kernel
(``kernels/edge_softmax``, the Pallas call under
``jit(edge_softmax_stats)``): the least time its calls of the traced
steps need at their real row, edge and head counts
(``bench/work/kernels.py``), over their summed device time."""

KERNEL = "jit(edge_softmax_stats)/pallas_call"


def read(ctx):
    calls = getattr(ctx.work, "edge_softmax_calls", None)
    if calls is None or ctx.peak is None:
        return None
    seconds = ctx.trace.kernel_seconds(lambda path: KERNEL in path)
    if not seconds:
        return None
    from bench.work import kernels
    need = sum(kernels.roofline_seconds(kernels.edge_softmax(*c), ctx.peak)
               for counts in ctx.counts for c in calls(ctx.config, counts))
    return 100.0 * need / seconds
