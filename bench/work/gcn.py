"""Useful FLOPs of a GCN training step with residual projections.

Block b (outermost first) applies model layer L-1-b: its input width is
the features' for the deepest block and ``hidden`` otherwise, its
output width ``hidden`` or the class count for the outermost. Per block
with S seeds, T inputs and E sampled edges:

* forward: the weighted aggregation (2 E F_in) and the two projections
  of the seeds (2 x 2 S F_in F_out);
* backward: the gradients of both projections' weights (2 x 2 S F_in
  F_out) and, except for the deepest block whose input is the fixed
  features, the gradient of its input: through both projections
  (2 x 2 S F_in F_out) and the transposed aggregation (2 E F_in).

Bias, activation, loss and optimizer work is under 0.1% and left out.
"""


def _widths(config, n_blocks):
    g = config["graph"]
    dims = ([g["num_features"]] + [config["hidden"]] * (n_blocks - 1)
            + [g["num_classes"]])
    # block b applies layer L-1-b
    return [(dims[n_blocks - 1 - b], dims[n_blocks - b])
            for b in range(n_blocks)]


def step_flops(config, counts):
    total = 0.0
    L = len(counts)
    for b, (c, (fi, fo)) in enumerate(zip(counts, _widths(config, L))):
        S, E = c["seeds"], c["edges"]
        fwd = 2 * E * fi + 4 * S * fi * fo
        bwd = 4 * S * fi * fo
        if b < L - 1:
            bwd += 4 * S * fi * fo + 2 * E * fi
        total += fwd + bwd
    return total


def spmm_calls(config, counts):
    """(rows, edges, feats) of each SpMM of a step: the forward
    aggregation of every block, and the transposed one of every block
    but the deepest."""
    L = len(counts)
    calls = []
    for b, (c, (fi, _)) in enumerate(zip(counts, _widths(config, L))):
        calls.append((c["seeds"], c["edges"], fi))
        if b < L - 1:
            calls.append((c["next"], c["edges"], fi))
    return calls
