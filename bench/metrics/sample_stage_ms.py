"""Device milliseconds per step of the engine's staged sampling
program (``TrainEngine.staged.sample``, the sampling half of the fused
step) on the traced steps' batches and keys, found in the trace as the
program ``jit_sample``."""

PROGRAM = "jit_sample"


def read(ctx):
    found = ctx.sample_trace.module_seconds(PROGRAM)
    if found is None:
        return None
    seconds, runs = found
    return 1e3 * seconds / runs
