"""Device milliseconds per traced step of every op under the fused
step's scope ``model``: the loss's forward ops (``jvp(model)``) and
its backward ops (``transpose(jvp(model))``)."""
from bench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "model")
