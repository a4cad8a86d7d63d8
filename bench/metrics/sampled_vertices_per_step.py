"""Mean over the traced steps of the step metrics' ``sampled_v``: the
deepest layer's vertex count |V^L|, the paper's cost proxy. A count."""


def read(ctx):
    if not ctx.sampled_v:
        return None
    return sum(ctx.sampled_v) / len(ctx.sampled_v)
