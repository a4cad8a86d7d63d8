"""Device milliseconds per traced step of every op under the frontier
primitive's scope ``hash_dedup``, over all layers: its sort kernels
and the XLA glue around them."""
from bench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "hash_dedup")
