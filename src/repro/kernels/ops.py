"""Jitted public wrappers around the hybrid distance kernels.

``hybrid_scores``           — (B queries) x (B, C candidate rows) -> (B, C)
``hybrid_scores_vs_ids``    — gather candidate ids from a corpus, score, mask
``fused_topk`` / ``_vs_ids``— distance + in-kernel top-k selection: (B, k)
                              scores + candidate positions, no (B, C) output
``pairwise_scores_chunked`` — brute-force (N x M) scoring in memory-bounded
                              chunks (ground truth / rerank)

Every wrapper takes ``use_kernel: bool | None``. ``None`` (the default at
the config layer) resolves by backend: Pallas on TPU, the jnp oracle on CPU
— the same call sites serve both. An explicit ``True`` on CPU runs the
kernel in interpret mode (tests use this for kernel/oracle equality);
explicit ``False`` forces the oracle anywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.usms import (
    PAD_IDX,
    FusedVectors,
    QuantizedFusedVectors,
    SparseVec,
)
from repro.kernels import ref
from repro.kernels.fused_topk import NEG as NEG  # re-export: callers mask on it
from repro.kernels.fused_topk import fused_topk_pallas
from repro.kernels.hybrid_distance import DEFAULT_C_TILE, hybrid_distance_pallas
from repro.kernels.pairwise_tile import pairwise_tile_pallas
from repro.runtime import dispatch


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def resolve_use_kernel(use_kernel: bool | None) -> bool:
    """Backend-auto kernel dispatch: ``None`` -> Pallas iff not on CPU.

    Config dataclasses (``SearchParams``, ``KnnConfig``, ``PruneConfig``)
    default ``use_kernel`` to ``None``; resolving to a concrete bool happens
    once, at construction/entry time, so jit cache keys and the serving AOT
    executable-cache key always see a pinned kernel mode.
    """
    if use_kernel is None:
        return not _on_cpu()
    return bool(use_kernel)


def _pad_candidates(cands: FusedVectors, c_tile: int) -> tuple[FusedVectors, int]:
    c = cands.dense.shape[1]
    c_pad = (-c) % c_tile
    if c_pad == 0:
        return cands, c
    pad3 = lambda a: jnp.pad(a, ((0, 0), (0, c_pad), (0, 0)))
    padi = lambda a: jnp.pad(a, ((0, 0), (0, c_pad), (0, 0)), constant_values=PAD_IDX)
    return (
        FusedVectors(
            pad3(cands.dense),
            SparseVec(padi(cands.learned.idx), pad3(cands.learned.val)),
            SparseVec(padi(cands.lexical.idx), pad3(cands.lexical.val)),
        ),
        c,
    )


def _pad_candidates_q(
    cands: QuantizedFusedVectors, c_tile: int
) -> tuple[QuantizedFusedVectors, int]:
    """Quantized twin of ``_pad_candidates``: int8 padding rows are 0 with
    scale 0.0, so padded dense scores are exactly 0 before masking."""
    c = cands.dense_q.shape[1]
    c_pad = (-c) % c_tile
    if c_pad == 0:
        return cands, c
    pad3 = lambda a: jnp.pad(a, ((0, 0), (0, c_pad), (0, 0)))
    pad2 = lambda a: jnp.pad(a, ((0, 0), (0, c_pad)))
    padi = lambda a: jnp.pad(a, ((0, 0), (0, c_pad), (0, 0)), constant_values=PAD_IDX)
    return (
        QuantizedFusedVectors(
            pad3(cands.dense_q),
            pad2(cands.dense_scale),
            SparseVec(padi(cands.learned.idx), pad3(cands.learned.val)),
            SparseVec(padi(cands.lexical.idx), pad3(cands.lexical.val)),
        ),
        c,
    )


def _nnz_major(cands) -> tuple[jax.Array, ...]:
    """(B, C, P) ELL leaves -> the kernel's nnz-major (B, P, C) layout (see
    hybrid_distance.py). Values leave the transpose as f32: Mosaic refuses
    fp16 vector loads (quantized storage keeps fp16 ELL values), and the
    kernels upcast every value to f32 before use, so the scores are the
    same bits either way."""
    t = lambda a: jnp.swapaxes(a, 1, 2)
    return (
        t(cands.learned.idx),
        t(cands.learned.val).astype(jnp.float32),
        t(cands.lexical.idx),
        t(cands.lexical.val).astype(jnp.float32),
    )


@functools.partial(jax.jit, static_argnames=("c_tile", "use_kernel", "interpret"))
def hybrid_scores(
    q: FusedVectors,
    cands: FusedVectors,
    *,
    c_tile: int = DEFAULT_C_TILE,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Score B queries against their (B, C, ...) candidate rows -> (B, C) f32.

    Weights must already be folded into ``q`` (usms.weighted_query).
    ``cands`` may be quantized storage (``QuantizedFusedVectors``) — the
    corpus dtype is a trace-time pytree property, never traced data.
    """
    quantized = isinstance(cands, QuantizedFusedVectors)
    if not resolve_use_kernel(use_kernel):
        if quantized:
            return ref.hybrid_scores_quant_ref(q, cands)
        return ref.hybrid_scores_ref(q, cands)
    if interpret is None:
        interpret = _on_cpu()
    if quantized:
        cands, c_orig = _pad_candidates_q(cands, c_tile)
        cd, cscale = cands.dense_q, cands.dense_scale
    else:
        cands, c_orig = _pad_candidates(cands, c_tile)
        cd, cscale = cands.dense, None
    csi, csv, cfi, cfv = _nnz_major(cands)
    out = hybrid_distance_pallas(
        q.dense,
        q.learned.idx,
        q.learned.val,
        q.lexical.idx,
        q.lexical.val,
        cd,
        csi,
        csv,
        cfi,
        cfv,
        cscale,
        c_tile=c_tile,
        interpret=interpret,
    )
    return out[:, :c_orig]


@functools.partial(jax.jit, static_argnames=("c_tile", "use_kernel"))
def hybrid_scores_vs_ids(
    q: FusedVectors,
    corpus: FusedVectors,
    ids: jax.Array,  # (B, C) int32, PAD_IDX entries masked to -inf
    *,
    c_tile: int = DEFAULT_C_TILE,
    use_kernel: bool | None = None,
) -> jax.Array:
    flat = ids.reshape(-1)
    rows = corpus.take(flat)
    cands = jax.tree.map(
        lambda a: a.reshape(ids.shape + a.shape[1:]), rows
    )
    scores = hybrid_scores(q, cands, c_tile=c_tile, use_kernel=use_kernel)
    return jnp.where(ids >= 0, scores, -jnp.inf)


@functools.partial(
    jax.jit, static_argnames=("k", "c_tile", "use_kernel", "interpret")
)
def fused_topk(
    q: FusedVectors,
    cands: FusedVectors,
    cid: jax.Array,  # (B, C) int32 candidate ids; PAD_IDX slots invalid
    k: int,
    *,
    bias: jax.Array | None = None,  # (B, C) f32 pre-selection score bias
    c_tile: int = DEFAULT_C_TILE,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused distance + top-k: score candidate rows, select in-kernel.

    Returns ``(scores, positions)`` of shape (B, k) — the per-query top-k
    biased hybrid scores (descending) and the positions along the C axis
    they came from. The ``(B, C)`` score matrix never reaches HBM on the
    kernel path. Invalid slots (PAD candidates, k beyond the live count)
    hold ``(NEG, PAD_IDX)``; ``bias`` must be finite (mask via PAD ids, not
    via bias). Tie order matches ``lax.top_k`` (lowest position wins).
    """
    quantized = isinstance(cands, QuantizedFusedVectors)
    if not resolve_use_kernel(use_kernel):
        if quantized:
            return ref.fused_topk_quant_ref(q, cands, cid, bias, k)
        return ref.fused_topk_ref(q, cands, cid, bias, k)
    if interpret is None:
        interpret = _on_cpu()
    if quantized:
        cands, c_orig = _pad_candidates_q(cands, c_tile)
        cd, cscale = cands.dense_q, cands.dense_scale
    else:
        cands, c_orig = _pad_candidates(cands, c_tile)
        cd, cscale = cands.dense, None
    c_padded = cd.shape[1]
    if c_padded != c_orig:
        grow = ((0, 0), (0, c_padded - c_orig))
        cid = jnp.pad(cid, grow, constant_values=PAD_IDX)
        if bias is not None:
            bias = jnp.pad(bias, grow)
    csi, csv, cfi, cfv = _nnz_major(cands)
    out_s, out_i = fused_topk_pallas(
        q.dense,
        q.learned.idx,
        q.learned.val,
        q.lexical.idx,
        q.lexical.val,
        cd,
        csi,
        csv,
        cfi,
        cfv,
        cid.astype(jnp.int32),
        None if bias is None else bias.astype(jnp.float32),
        cscale,
        k=k,
        c_tile=c_tile,
        interpret=interpret,
    )
    return out_s[:, :k], out_i[:, :k]


@functools.partial(
    jax.jit, static_argnames=("k", "c_tile", "use_kernel", "interpret")
)
def fused_topk_vs_ids(
    q: FusedVectors,
    corpus: FusedVectors,
    ids: jax.Array,  # (B, C) int32 candidate ids into the corpus
    k: int,
    *,
    bias: jax.Array | None = None,
    c_tile: int = DEFAULT_C_TILE,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Gather candidate rows by id, then fused distance + top-k selection.

    The round's expansion consumer: callers stack a whole round's neighbor
    lists into the C axis (multi-node batching) and gather ids plus any
    per-candidate metadata from the returned positions via ``take_topk``.
    """
    flat = ids.reshape(-1)
    rows = corpus.take(flat)
    cands = jax.tree.map(lambda a: a.reshape(ids.shape + a.shape[1:]), rows)
    return fused_topk(
        q, cands, ids, k,
        bias=bias, c_tile=c_tile, use_kernel=use_kernel, interpret=interpret,
    )


def take_topk(values: jax.Array, pos: jax.Array, fill) -> jax.Array:
    """Gather per-candidate values at fused-top-k positions (PAD -> fill).

    ``values``: (..., C) aligned with the candidate axis the positions were
    selected over; ``pos``: (..., k) from ``fused_topk*``.
    """
    got = jnp.take_along_axis(
        values, jnp.clip(pos, 0, values.shape[-1] - 1), axis=-1
    )
    return jnp.where(pos >= 0, got, fill)


def take_topk_ids(ids: jax.Array, pos: jax.Array) -> jax.Array:
    """Resolve fused-top-k positions back to candidate ids (PAD -> PAD_IDX)."""
    return take_topk(ids, pos, PAD_IDX)


def pairwise_tile_scores(
    tile: FusedVectors,  # (C, K, ...) gathered candidate rows
    *,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """All-pairs hybrid scores within each node's candidate tile -> (C, K, K).

    out[c, i, j] = score(tile[c, i], tile[c, j]). Rows are gathered once by
    the caller (no per-pair re-gather); invalid-candidate masking stays with
    the caller, which holds the id list.
    """
    if not resolve_use_kernel(use_kernel):
        return ref.pairwise_tile_ref(tile)
    if interpret is None:
        interpret = _on_cpu()
    return pairwise_tile_pallas(
        tile.dense,
        tile.learned.idx,
        tile.learned.val,
        tile.lexical.idx,
        tile.lexical.val,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("chunk",))
def _pairwise_scores_mapped(
    queries: FusedVectors, corpus: FusedVectors, chunk: int
) -> jax.Array:
    """In-trace corpus-chunked brute force: lax.map over corpus blocks, so
    ground-truth / rerank scoring is one dispatch regardless of corpus size
    while peak memory stays bounded by one (Nq, chunk) block."""
    n = corpus.dense.shape[0]
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        corpus = jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]
            ),
            corpus,
        )
    blocks = jax.tree.map(
        lambda a: a.reshape((-1, chunk) + a.shape[1:]), corpus
    )
    outs = jax.lax.map(
        lambda blk: ref.pairwise_hybrid_scores_ref(queries, blk), blocks
    )  # (n_blocks, Nq, chunk)
    out = jnp.moveaxis(outs, 0, 1).reshape(queries.dense.shape[0], -1)
    return out[:, :n]


def pairwise_scores_chunked(
    queries: FusedVectors,
    corpus: FusedVectors,
    *,
    chunk: int = 4096,
) -> jax.Array:
    """Brute-force (Nq, Ncorpus) hybrid scores, chunked over the corpus.

    Oracle path (jnp); used for ground truth and exact rerank. The chunk
    loop runs in-trace (lax.map), so this is a single dispatch.
    """
    dispatch.tick()
    return _pairwise_scores_mapped(queries, corpus, chunk)


def topk_hybrid(
    queries: FusedVectors,
    corpus: FusedVectors,
    k: int,
    *,
    chunk: int = 4096,
) -> tuple[jax.Array, jax.Array]:
    """Exact top-k by brute force (ground truth). Returns (scores, ids)."""
    scores = pairwise_scores_chunked(queries, corpus, chunk=chunk)
    top, idx = jax.lax.top_k(scores, k)
    return top, idx.astype(jnp.int32)
