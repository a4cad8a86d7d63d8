"""Roofline share of the SpMM kernel (``kernels/spmm``, the Pallas call
under ``jit(spmm_sorted)``): the least time its calls of the traced
steps need at their real row, edge and feature counts
(``bench/work/kernels.py``), over their summed device time."""

KERNEL = "jit(spmm_sorted)/pallas_call"


def read(ctx):
    calls = getattr(ctx.work, "spmm_calls", None)
    if calls is None or ctx.peak is None:
        return None
    seconds = ctx.trace.kernel_seconds(lambda path: KERNEL in path)
    if not seconds:
        return None
    from bench.work import kernels
    need = sum(kernels.roofline_seconds(kernels.spmm(*c), ctx.peak)
               for counts in ctx.counts for c in calls(ctx.config, counts))
    return 100.0 * need / seconds
