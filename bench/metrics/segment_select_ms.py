"""Device milliseconds per traced step of every op under the frontier
primitive's scope ``segment_select``, over all layers: neighbour
sampling's exact-k selection, its sort kernels and the XLA glue
around them."""
from bench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "segment_select")
