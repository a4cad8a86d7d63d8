"""Device milliseconds per traced step of every op under the fused
step's scope ``feature_gather``: the input-feature rows of the deepest
layer's vertices and the seeds' labels."""
from bench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "feature_gather")
