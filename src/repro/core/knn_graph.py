"""Approximate k-NN graph construction with NN-Descent (paper §4.1 Step 1,
Algorithm 1 lines 1-4).

NN-Descent principle: "a neighbor's neighbors are likely neighbors" — each
round explores every node's 2-hop neighborhood, scores the candidates with
the hybrid distance kernel, and keeps the top-k. The GPU paper runs one warp
per distance; here each round is a fixed-shape batched tensor program:
gather (N, K*K) 2-hop candidate ids -> dedup by id-sort -> hybrid-score ->
merge with current neighbors -> top-k. Everything is jittable and chunkable
over nodes so 1M-document segments stream through device memory.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.usms import PAD_IDX, FusedVectors
from repro.kernels import ops
from repro.runtime import dispatch


@dataclasses.dataclass(frozen=True)
class KnnConfig:
    k: int = 32  # neighbors kept per node during descent
    iters: int = 6
    extra_random: int = 8  # random candidates injected per round (escape lows)
    node_chunk: int = 2048  # nodes processed per jit call (memory bound)
    use_kernel: bool | None = None  # None -> backend auto (Pallas off-CPU)


def dedup_mask(ids: jax.Array) -> jax.Array:
    """Boolean mask marking the first occurrence of each id in a 1-D array
    (PAD_IDX entries are always masked out). O(L log L), fixed shape."""
    order = jnp.argsort(ids)
    sorted_ids = ids[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]]
    )
    mask_sorted = first & (sorted_ids != PAD_IDX)
    # scatter back to original positions
    mask = jnp.zeros_like(mask_sorted).at[order].set(mask_sorted)
    return mask


def _merge_topk(
    ids_a, scores_a, ids_b, scores_b, k: int
) -> tuple[jax.Array, jax.Array]:
    """Merge two (.., L) candidate lists into top-k by score with id dedup."""
    ids = jnp.concatenate([ids_a, ids_b], axis=-1)
    scores = jnp.concatenate([scores_a, scores_b], axis=-1)
    keep = jax.vmap(dedup_mask)(ids)
    scores = jnp.where(keep, scores, -jnp.inf)
    top, pos = jax.lax.top_k(scores, k)
    out_ids = jnp.take_along_axis(ids, pos, axis=-1)
    out_ids = jnp.where(jnp.isfinite(top), out_ids, PAD_IDX)
    return out_ids, top


def _descent_round_chunk(
    corpus: FusedVectors,
    nbr_ids: jax.Array,  # (N, K) current graph (global)
    chunk_queries: FusedVectors,  # (C, ...) fused vectors of this node chunk
    chunk_node_ids: jax.Array,  # (C,)
    chunk_nbrs: jax.Array,  # (C, K)
    chunk_scores: jax.Array,  # (C, K)
    rand_ids: jax.Array,  # (C, R) random candidate injection
    cfg: KnnConfig,
):
    k = cfg.k
    # 2-hop candidates: neighbors of my neighbors (K*K) + random restarts
    safe = jnp.where(chunk_nbrs >= 0, chunk_nbrs, 0)
    two_hop = jnp.take(nbr_ids, safe, axis=0).reshape(chunk_nbrs.shape[0], k * k)
    two_hop = jnp.where(
        (chunk_nbrs >= 0).repeat(k, axis=-1), two_hop, PAD_IDX
    )
    cand = jnp.concatenate([two_hop, rand_ids], axis=-1)
    # never propose the node itself or ids already in the neighbor list
    cand = jnp.where(cand == chunk_node_ids[:, None], PAD_IDX, cand)
    already = (cand[:, :, None] == chunk_nbrs[:, None, :]).any(-1)
    cand = jnp.where(already, PAD_IDX, cand)
    keep = jax.vmap(dedup_mask)(cand)
    cand = jnp.where(keep, cand, PAD_IDX)
    # fused distance + per-row top-k: the (C, K*K+R) candidate score matrix
    # never materializes outside the kernel. Pre-selecting the candidates'
    # top-k before the merge is exact — cand is internally deduped and
    # disjoint from chunk_nbrs (the ``already`` mask above), so the merge
    # can keep at most k of them anyway.
    sel_scores, sel_pos = ops.fused_topk_vs_ids(
        chunk_queries, corpus, cand, k, use_kernel=cfg.use_kernel
    )
    sel_ids = ops.take_topk_ids(cand, sel_pos)
    return _merge_topk(chunk_nbrs, chunk_scores, sel_ids, sel_scores, k)


def _pad_rows(a: jax.Array, pad: int, fill) -> jax.Array:
    if pad == 0:
        return a
    return jnp.concatenate([a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)])


def _chunked(a: jax.Array, chunk: int, fill) -> jax.Array:
    """Pad rows to a multiple of ``chunk`` and reshape to (n_chunks, chunk, ...)."""
    pad = (-a.shape[0]) % chunk
    a = _pad_rows(a, pad, fill)
    return a.reshape((-1, chunk) + a.shape[1:])


def _chunked_tree(t, chunk: int, fill):
    return jax.tree.map(lambda a: _chunked(a, chunk, fill), t)


def score_sorted_rows(
    queries: FusedVectors,
    corpus: FusedVectors,
    nbr_ids: jax.Array,  # (N, K) neighbor ids, PAD_IDX allowed
    cfg: KnnConfig,
) -> tuple[jax.Array, jax.Array]:
    """Fused score + full sort of every row's neighbor list (k == row width,
    so the fused top-k IS the sort). Node chunks stream through ``lax.map``
    like the descent rounds, so the candidate gather is bounded by one
    chunk, never (N, K) rows at once. Returns (ids, scores) with -inf
    scores on PAD slots."""
    n, k = nbr_ids.shape
    chunk = min(cfg.node_chunk, n)

    def one(x):
        qs, ids = x
        top, pos = ops.fused_topk_vs_ids(
            qs, corpus, ids, k, use_kernel=cfg.use_kernel
        )
        ids = ops.take_topk_ids(ids, pos)
        return ids, jnp.where(ids >= 0, top, -jnp.inf)

    ids, scores = jax.lax.map(
        one,
        (_chunked_tree(queries, chunk, 0), _chunked(nbr_ids, chunk, PAD_IDX)),
    )
    return ids.reshape(-1, k)[:n], scores.reshape(-1, k)[:n]


# jitted wrapper for the legacy host-driven chunk loop; the device-resident
# pipeline (core/build_pipeline.py) traces the plain body inside lax.map
_descent_round_chunk_jit = jax.jit(
    _descent_round_chunk, static_argnames=("cfg",)
)


def _init_graph(n: int, k: int, key: jax.Array) -> jax.Array:
    """Random initial neighbors, self-loops remapped."""
    ids = jax.random.randint(key, (n, k), 0, n, dtype=jnp.int32)
    return jnp.where(ids == jnp.arange(n, dtype=jnp.int32)[:, None], (ids + 1) % n, ids)


def build_knn_graph(
    corpus: FusedVectors,
    cfg: KnnConfig,
    key: jax.Array,
    *,
    queries: FusedVectors | None = None,
    init_ids: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """NN-Descent over the fused corpus. Returns (nbr_ids (N,K), scores (N,K))
    sorted by hybrid score descending per row.

    queries: optional weight-scaled view of the corpus (Theorem 1) — used for
        the per-path refinement rounds that feed the single-path neighbor
        slots of the pruned edge lists (paper Step 2 tail).
    init_ids: optional (N, >=K) warm-start graph (e.g. the fused k-NN graph).
    """
    n = corpus.n
    k = cfg.k
    queries = corpus if queries is None else queries
    key, k0 = jax.random.split(key)
    if init_ids is None:
        nbr_ids = _init_graph(n, k, k0)
    else:
        nbr_ids = init_ids[:, :k]
        if nbr_ids.shape[1] < k:
            extra = _init_graph(n, k - nbr_ids.shape[1], k0)
            nbr_ids = jnp.concatenate([nbr_ids, extra], axis=1)
    node_ids = jnp.arange(n, dtype=jnp.int32)
    dispatch.tick()
    # the same prologue as the pipeline (build_pipeline._descent_init), so
    # both build paths stay bitwise-identical
    nbr_ids, scores = score_sorted_rows(queries, corpus, nbr_ids, cfg)

    for it in range(cfg.iters):
        key, kr = jax.random.split(key)
        rand_ids = jax.random.randint(kr, (n, cfg.extra_random), 0, n, dtype=jnp.int32)
        new_ids = []
        new_scores = []
        for s in range(0, n, cfg.node_chunk):
            e = min(s + cfg.node_chunk, n)
            dispatch.tick()
            ids_c, sc_c = _descent_round_chunk_jit(
                corpus,
                nbr_ids,
                queries[slice(s, e)],
                node_ids[s:e],
                nbr_ids[s:e],
                scores[s:e],
                rand_ids[s:e],
                cfg,
            )
            new_ids.append(ids_c)
            new_scores.append(sc_c)
        nbr_ids = jnp.concatenate(new_ids, axis=0)
        scores = jnp.concatenate(new_scores, axis=0)
    return nbr_ids, scores


def reverse_neighbors(nbr_ids: jax.Array, cap: int) -> jax.Array:
    """Fixed-width reverse adjacency: rev[v] lists up to ``cap`` nodes u with
    v in N(u). Built via id-sort + per-group position (fixed shapes)."""
    n, k = nbr_ids.shape
    dst = nbr_ids.reshape(-1)
    src = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    valid = dst >= 0
    dst_s = jnp.where(valid, dst, n)  # push invalid to the end
    order = jnp.argsort(dst_s)
    dst_sorted = dst_s[order]
    src_sorted = src[order]
    group_start = jnp.searchsorted(dst_sorted, dst_sorted, side="left")
    pos = jnp.arange(n * k) - group_start
    pos = jnp.where((dst_sorted < n) & (pos < cap), pos, cap)  # cap -> dropped
    rev = jnp.full((n, cap), PAD_IDX, jnp.int32)
    rev = rev.at[jnp.clip(dst_sorted, 0, n - 1), pos].set(src_sorted, mode="drop")
    return rev


def new_node_reverse(
    merged_ids: jax.Array, n_old: int, cap: int
) -> jax.Array:
    """Reverse adjacency among the NEW nodes of an insert batch.

    merged_ids: (n_new, K) candidate lists holding GLOBAL ids — old-corpus
    ids are < n_old, new-node ids are >= n_old. Only new-node targets have
    rows in the returned (n_new, cap) table; old-corpus targets are dropped
    (their back-links are handled by the insert back-link pass). Returned
    source ids are GLOBAL (>= n_old).

    This exists because feeding global ids straight into
    ``reverse_neighbors`` treats old-corpus ids < n_new as new-node-local
    row indices, scattering old-corpus targets into wrong rows.
    """
    local = jnp.where(merged_ids >= n_old, merged_ids - n_old, PAD_IDX)
    rev = reverse_neighbors(local, cap)
    return jnp.where(rev >= 0, rev + n_old, PAD_IDX)


def knn_recall(nbr_ids: jax.Array, truth_ids: jax.Array) -> float:
    """Fraction of true k-NN recovered (diagnostic for NN-Descent quality)."""
    import numpy as np

    nbr = np.asarray(nbr_ids)
    truth = np.asarray(truth_ids)
    hits = sum(
        len(set(a.tolist()) & set(b.tolist())) for a, b in zip(nbr, truth)
    )
    return hits / truth.size
