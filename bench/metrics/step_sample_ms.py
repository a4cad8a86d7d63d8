"""Device milliseconds per traced step of every op under the fused
step's scope ``sample``: every sampling layer, the layer salts
included. The in-program successor of ``sample_stage_ms``, which times
a separately compiled sampling program."""
from bench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "sample")
