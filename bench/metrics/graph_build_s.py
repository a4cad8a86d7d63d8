"""Host seconds to build the configuration's graph, or to load it from
the checkout's cache (``bench/graph.py``)."""


def read(ctx):
    return ctx.graph_build_s
