"""Names of the program's device scopes and host spans.

Device scopes are ``jax.named_scope`` names: they land in the op path
(``metadata={op_name=...}``) of every HLO instruction traced under them,
and cost nothing at run time. Inside ``jax.value_and_grad`` a scope is
wrapped by the transform that traced it: forward ops carry
``jvp(model)`` and backward ops ``transpose(jvp(model))``. Host spans
are ``jax.profiler.TraceAnnotation`` names on the profiler's clock; they
cost nothing unless a profiler is running, and the profiler is their
only collector.

The fused train step (``runtime/engine.py``) is cut into four stage
scopes, the sampler into one scope per layer, and each frontier
primitive gets one scope at the dispatch point every backend passes
through (``ops/frontier.py``, ``graph/csr.py::expand_seed_edges``).
Inside a LABOR-family layer, ``include`` names the inclusion decision
(``core/labor.py::sample_layer``).
"""
from __future__ import annotations

# -- stage scopes of the train step ---------------------------------------
SAMPLE = "sample"                  # every sampling layer, salts included
FEATURE_GATHER = "feature_gather"  # input-feature rows and seed labels
MODEL = "model"                    # the loss; its gradient by autodiff
OPTIMIZER = "optimizer"            # Adam, overflow/guard gate, step metrics
STAGES = (SAMPLE, FEATURE_GATHER, MODEL, OPTIMIZER)


def layer(index: int) -> str:
    """The scope of sampling layer ``index`` (0 = the seed batch's)."""
    return f"layer{index}"


# -- inside a LABOR-family sampling layer ------------------------------------
# the expanded edges to the inclusion mask and 1/p: importance
# iterations, variates, c_s per edge, the comparison or the exact-k
# selection (which holds segment_select)
INCLUDE = "include"

# -- frontier primitives --------------------------------------------------
EXPAND_SEED_EDGES = "expand_seed_edges"
HASH_DEDUP = "hash_dedup"
COMPACT = "compact"
COMPACT_PERM = "compact_perm"
SEGMENT_SELECT = "segment_select"
MASKED_CDF_DRAW = "masked_cdf_draw"

# -- host spans of TrainEngine --------------------------------------------
ENGINE_STEP = "engine.step"        # all of TrainEngine.step
ENGINE_DISPATCH = "engine.dispatch"  # enqueueing one fused program
ENGINE_POLL = "engine.poll"        # reading an earlier batch's overflow flags
ENGINE_REPLAY = "engine.replay"    # re-running an overflowed batch
ENGINE_GROW = "engine.grow"        # growing the caps (a recompile follows)
ENGINE_FLUSH = "engine.flush"      # draining the overflow ledger
