"""Device milliseconds per traced step of every op under the scope
``include``, over all layers: a LABOR-family layer's inclusion decision
from its expanded edges (importance iterations, variates, c_s per edge,
the comparison, and on ``ns`` the exact-k ``segment_select``)."""
from bench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "include")
