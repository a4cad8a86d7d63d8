"""Operations and bytes that a kernel call needs at its real sizes,
whatever implements it. Values are float32 (4 bytes)."""

F32 = 4


def spmm(rows: int, edges: int, feats: int):
    """Segment sum of per-edge vectors into destination rows: one add
    per edge and feature; reads every edge's vector and destination,
    writes every row."""
    flops = edges * feats
    nbytes = F32 * (edges * feats + edges + rows * feats)
    return flops, nbytes


def edge_softmax(rows: int, edges: int, heads: int):
    """Per-destination softmax statistics of edge logits: a running max
    and a sum of exponentials per row and head (compare, subtract,
    exponential, add per edge and head); reads every logit and
    destination, writes the two statistics of every row."""
    flops = 4 * edges * heads
    nbytes = F32 * (edges * heads + edges + 2 * rows * heads)
    return flops, nbytes


def roofline_seconds(work, peak: dict) -> float:
    """The least time a chip takes for (flops, bytes): the larger of
    the two bounds."""
    flops, nbytes = work
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
