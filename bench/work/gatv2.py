"""Useful FLOPs of a GATv2 training step.

Block b (outermost first) applies model layer L-1-b with H heads of P
(D = H P) from width F_in. Per block with S seeds, T inputs and E
sampled edges:

* forward: the two projections (2 S F_in D + 2 T F_in D); per edge the
  sum of the two projected rows, the attention dot product, the message
  scaling and its segment sum (5 E D), and the softmax (4 E H);
* backward: the projections' weight gradients (2 (S + T) F_in D), their
  input gradients except in the deepest block (2 (S + T) F_in D), and
  twice the per-edge work (10 E D + 8 E H).

Bias, activation, loss and optimizer work is left out.
"""


def _layers(config, n_blocks):
    g = config["graph"]
    out, d_in = [], g["num_features"]
    for l in range(n_blocks):
        last = l == n_blocks - 1
        h = config["last_layer_heads"] if last else config["heads"]
        p = g["num_classes"] if last else config["hidden"] // config["heads"]
        out.append((d_in, h, p))
        d_in = h * p
    # block b applies layer L-1-b
    return out[::-1]


def step_flops(config, counts):
    total = 0.0
    L = len(counts)
    for b, (c, (fi, H, P)) in enumerate(zip(counts, _layers(config, L))):
        S, T, E, D = c["seeds"], c["next"], c["edges"], H * P
        proj = 2 * (S + T) * fi * D
        edge = 5 * E * D + 4 * E * H
        total += proj + edge + proj + 2 * edge
        if b < L - 1:
            total += proj
    return total


def edge_softmax_calls(config, counts):
    """(rows, edges, heads) of each edge-softmax statistics pass: one
    per block, in the forward pass."""
    L = len(counts)
    return [(c["seeds"], c["edges"], H)
            for c, (_, H, _) in zip(counts, _layers(config, L))]
