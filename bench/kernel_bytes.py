"""HBM bytes the algorithm needs, counted from the search parameters, for
the kernels whose roofline share the benchmark reports.

The count takes each real candidate row once, at its stored widths (fp32
dense, int32 ids and fp32 values in both ELL leaves), and nothing the
implementation adds: not the 128-lane padding of a candidate tile, not the
gathered copies, not the nnz-major transpose. ``fused_topk`` does about
2 * d_dense flops per ~4.6 KB row, two orders of magnitude below a TPU
v5e's ridge point (197e12 / 819e9 = 240 flop/byte), so its least time is
the bytes over the HBM bandwidth.
"""

from __future__ import annotations


def row_bytes(cfg: dict) -> int:
    """Stored bytes of one passage row."""
    return 4 * cfg["d_dense"] + 8 * cfg["nnz_sparse"] + 8 * cfg["nnz_lexical"]


def search_fused_topk_bytes(cfg: dict, n_requests: int) -> float:
    """Per request and round the traversal scores the semantic neighbour
    list of the node it expands: ``degree`` rows, for ``iters`` rounds."""
    return float(n_requests) * cfg["search"]["iters"] * cfg["build"]["prune"]["degree"] * row_bytes(cfg)
