"""Device milliseconds per traced step in the frontier primitives'
bitonic sort kernels (``kernels/frontier/parallel.py::sort_words``):
every Pallas call under one of the primitives' jitted wrappers."""

KERNELS = tuple(f"jit({w})/" for w in (
    "_dedup", "compact_block_parallel", "compact_perm_block_parallel",
    "segment_select_block_parallel", "masked_cdf_draw_block_parallel"))


def read(ctx):
    seconds = ctx.trace.kernel_seconds(
        lambda path: path.endswith("pallas_call")
        and any(w in path for w in KERNELS))
    return None if seconds is None else 1e3 * seconds / ctx.steps
