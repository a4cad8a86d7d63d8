"""Device milliseconds per traced step of every op under the scope
``expand_seed_edges``, over all layers: the CSR expansion of each
layer's seeds into their in-edges, at the static ``expand_cap``."""
from bench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "expand_seed_edges")
