"""Flexible query processing on the hybrid index (paper §4.2, Algorithm 2).

Decouples computation from storage: path weights live in the *query* (Theorem
1), keyword edges load dynamically only at nodes sharing a query keyword
(§4.2.2), and logical edges load only within ``kg_max_hops`` of the query
entities (§4.2.3) — so one index serves every path combination with zero
reconstruction.

GPU -> TPU: the CUDA best-first loop with hash-table visited sets becomes a
fixed-iteration batched beam search — bounded candidate pool as sorted
arrays, ``lax.top_k`` merges, id-matching dedup against pool + visited ring —
vmapped over the query batch under ``lax.fori_loop``. The hybrid distances of
each expansion go through the same Pallas kernel as construction.

Twin candidate pool (§4.2.2): keyword-satisfying nodes that fall out of the
primary pool are retained in a secondary pool; final results merge both and
filter for required keywords.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core.fusion import (
    FusionSpec,
    as_fusion_spec,
    broadcast_spec,
    fuse_candidates,
)
from repro.core.index import HybridIndex
from repro.core.knn_graph import dedup_mask
from repro.core.usms import (
    PAD_IDX,
    FusedVectors,
    PathWeights,
    SparseVec,
    has_keyword_overlap,
    weighted_query,
)
from repro.kernels import ops
from repro.obs.metrics import GLOBAL as _OBS

NEG = -1e30
INF_HOP = jnp.int32(10**6)


@dataclasses.dataclass(frozen=True)
class SearchParams:
    k: int = 10
    iters: int = 48  # expansion rounds (search breadth ~ iters * expand)
    pool_size: int = 64  # primary candidate pool
    kw_pool_size: int = 16  # twin pool for keyword-satisfying overflow
    expand: int = 1  # nodes expanded per round (CAGRA-style multi-expansion;
    # >1 cuts the sequential merge/top_k rounds ~expand-fold — §Perf)
    use_kernel: bool | None = None  # None -> backend auto: Pallas off-CPU,
    # jnp oracle on CPU (ops.resolve_use_kernel); pin via resolve_params()
    # before using params as a jit/AOT cache key
    use_keywords: bool = False  # enable keyword edge loading + filtering
    use_kg: bool = False  # enable logical edge traversal
    kg_max_hops: int = 3  # x: max entity hops for logical expansion
    corpus_dtype: str = "float32"  # sealed-corpus storage: "float32" or
    # "int8" (symmetric per-row int8 dense + fp16 sparse vals, quantized at
    # seal/compact time; traversal scores on quantized storage, the final
    # pool re-scores at full precision). A build/cache-key property — it
    # selects the corpus pytree the index carries, never traced data.


CORPUS_DTYPES = ("float32", "int8")


def resolve_params(params: SearchParams) -> SearchParams:
    """Pin backend-auto fields to concrete values.

    ``use_kernel=None`` resolves to the backend default (Pallas off-CPU).
    Callers that use ``SearchParams`` as a cache key — the serving AOT
    executable cache above all — must key on the *resolved* params so a
    kernel-mode change can never alias a stale executable.
    """
    if params.corpus_dtype not in CORPUS_DTYPES:
        raise ValueError(
            f"corpus_dtype must be one of {CORPUS_DTYPES}, "
            f"got {params.corpus_dtype!r}"
        )
    if params.use_kernel is None:
        return dataclasses.replace(
            params, use_kernel=ops.resolve_use_kernel(None)
        )
    return params


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["ids", "scores", "expanded", "path_scores"],
    meta_fields=["down_replicas"],
)
@dataclasses.dataclass
class SearchResult:
    ids: jax.Array  # (B, k) int32
    scores: jax.Array  # (B, k) f32 fused scores (mode-dependent scale)
    expanded: jax.Array  # (B,) int32 number of expanded nodes (work measure)
    # per-path raw scores of the winners, (B, k, 3) f32 [dense, learned,
    # lexical], zero on PAD slots — every downstream merge recomputes RRF
    # ranks from these (the cross-segment/replica merge contract, §11)
    path_scores: Optional[jax.Array] = None
    # replica names whose shards this result is missing (degraded scatter
    # read, DESIGN.md §9); None for single-index results and healthy tiers.
    # meta field: a hashable tuple, so tracing never specializes on it
    down_replicas: Optional[tuple] = None


def _entry_state(index: HybridIndex, q_entities: jax.Array, p: SearchParams):
    """Entry points: nodes containing user-specified entities when querying
    with the KG, else the precomputed large-norm nodes (Algorithm 2 l.2-8)."""
    n = index.n
    base = index.entry_points  # (n_entry,)
    base_ent = jnp.full(base.shape, PAD_IDX, jnp.int32)
    if p.use_kg:
        ent_safe = jnp.clip(q_entities, 0, index.entity_to_docs.shape[0] - 1)
        ent_docs = index.entity_to_docs[ent_safe]  # (Eq, M)
        valid_e = (q_entities >= 0)[:, None] & (ent_docs >= 0)
        ent_ids = jnp.where(valid_e, ent_docs, PAD_IDX).reshape(-1)
        ent_of = jnp.where(
            valid_e, q_entities[:, None], PAD_IDX
        ).reshape(-1)
        ids = jnp.concatenate([ent_ids, base])
        ents = jnp.concatenate([ent_of, base_ent])
    else:
        ids, ents = base, base_ent
    keep = dedup_mask(ids)
    ids = jnp.where(keep, ids, PAD_IDX)
    hops = jnp.where(ents >= 0, 0, INF_HOP)
    return ids, ents, hops


def _search_one(
    index: HybridIndex,
    qw: FusedVectors,  # weight-scaled query (single, no batch dim)
    q_raw: FusedVectors,  # UNWEIGHTED query (per-path re-scoring, modes 1-3)
    q_keywords: jax.Array,  # (Kw,) required keyword ids (PAD padded)
    q_entities: jax.Array,  # (Eq,) query entity ids (PAD padded)
    spec: FusionSpec,  # scalar-leaf spec row (mode/weights/rrf_k/stats)
    p: SearchParams,
):
    n = index.n
    P = p.pool_size
    w_kg = spec.weights.kg  # logical-path traversal bias weight
    q_b = jax.tree.map(lambda a: a[None], qw)  # add batch dim for the kernel

    def score_ids(ids):
        return ops.hybrid_scores_vs_ids(
            q_b, index.corpus, ids[None], use_kernel=p.use_kernel
        )[0]

    # ---- init pool --------------------------------------------------------
    e_ids, e_ents, e_hops = _entry_state(index, q_entities, p)
    ne = e_ids.shape[0]
    assert ne <= P, "pool_size must cover the entry set"
    e_scores = jnp.where(e_ids >= 0, score_ids(e_ids), NEG)
    if p.use_kg:
        # entity-matched entry points get the full hop-0 logical reward so the
        # traversal actually explores them (deviation from Algorithm 2 line 9,
        # which would leave chain heads with near-zero semantic score
        # unexpanded; see DESIGN.md §2)
        e_scores = jnp.where(
            (e_ents >= 0) & (e_ids >= 0), e_scores + w_kg, e_scores
        )
    pad = lambda a, fill: jnp.concatenate(
        [a, jnp.full((P - ne,) + a.shape[1:], fill, a.dtype)]
    )
    E = p.expand
    pool_ids = pad(e_ids, PAD_IDX)
    pool_scores = pad(e_scores, NEG)
    pool_visited = pad(jnp.zeros((ne,), bool), True)
    pool_ents = pad(e_ents, PAD_IDX)
    pool_hops = pad(e_hops, INF_HOP)
    ring = jnp.full((p.iters * E,), PAD_IDX, jnp.int32)
    kw_ids = jnp.full((p.kw_pool_size,), PAD_IDX, jnp.int32)
    kw_scores = jnp.full((p.kw_pool_size,), NEG, jnp.float32)
    n_expanded = jnp.int32(0)

    def body(i, state):
        (pool_ids, pool_scores, pool_visited, pool_ents, pool_hops, ring,
         kw_ids, kw_scores, n_expanded) = state

        # ---- pick the E best unvisited candidates (Algorithm 2 l.11;
        # multi-expansion per round cuts sequential merge cost — §Perf) ----
        sel = jnp.where(~pool_visited & (pool_ids >= 0), pool_scores, NEG)
        sel_top, js = jax.lax.top_k(sel, E)  # (E,)
        active = sel_top > NEG
        u = jnp.where(active, pool_ids[js], PAD_IDX)  # (E,)
        u_safe = jnp.clip(u, 0, n - 1)
        u_ent = pool_ents[js]
        u_hop = pool_hops[js]
        pool_visited = pool_visited.at[js].set(True)
        ring = jax.lax.dynamic_update_slice_in_dim(ring, u, i * E, axis=0)
        n_expanded = n_expanded + active.sum().astype(jnp.int32)

        # ---- gather neighbor lists (l.13-17, dynamic edge loading) ----
        parts_ids = [index.semantic_edges[u_safe]]  # (E, d)
        parts_ents = [jnp.full((E, index.degree), PAD_IDX, jnp.int32)]
        if p.use_keywords:
            shares = has_keyword_overlap(
                index.corpus.lexical.idx[u_safe], q_keywords[None, :]
            )  # (E,)
            kwe = jnp.where(
                shares[:, None], index.keyword_edges[u_safe], PAD_IDX
            )
            parts_ids.append(kwe)
            parts_ents.append(jnp.full(kwe.shape, PAD_IDX, jnp.int32))
        if p.use_kg:
            loge = index.logical_edges[u_safe]  # (E, L, 4)
            ok = (
                (u_ent[:, None] >= 0)
                & (u_hop[:, None] < p.kg_max_hops)
                & (loge[:, :, 1] == u_ent[:, None])
                & (loge[:, :, 0] >= 0)
            )
            parts_ids.append(jnp.where(ok, loge[:, :, 0], PAD_IDX))
            parts_ents.append(jnp.where(ok, loge[:, :, 3], PAD_IDX))
        nbr_ids2 = jnp.concatenate(parts_ids, axis=1)  # (E, W)
        nbr_log_ents2 = jnp.concatenate(parts_ents, axis=1)
        nbr_ids2 = jnp.where(active[:, None], nbr_ids2, PAD_IDX)
        src_hop2 = jnp.broadcast_to(u_hop[:, None], nbr_ids2.shape)
        src_ent2 = jnp.broadcast_to(u_ent[:, None], nbr_ids2.shape)
        nbr_ids = nbr_ids2.reshape(-1)
        nbr_log_ents = nbr_log_ents2.reshape(-1)
        src_hop = src_hop2.reshape(-1)
        src_ent = src_ent2.reshape(-1)

        # ---- dedup vs pool, visited ring, and within the list ----
        dup = (nbr_ids[:, None] == pool_ids[None, :]).any(-1)
        dup |= (nbr_ids[:, None] == ring[None, :]).any(-1)
        nbr_ids = jnp.where(dup | ~dedup_mask(nbr_ids), PAD_IDX, nbr_ids)

        # ---- entity matching for semantic expansions (l.19-20) ----
        if p.use_kg:
            cand_ents = index.doc_entities[jnp.clip(nbr_ids, 0, n - 1)]  # (W, Ed)
            src_ent_safe = jnp.clip(src_ent, 0, index.entity_adj.shape[0] - 1)
            rel = (
                index.entity_adj[
                    src_ent_safe[:, None], jnp.clip(cand_ents, 0, index.entity_adj.shape[0] - 1)
                ]
                & (cand_ents >= 0)
                & (src_ent[:, None] >= 0)
            )  # (W, Ed)
            first = jnp.argmax(rel, axis=-1)
            sem_match = jnp.where(
                rel.any(-1), jnp.take_along_axis(cand_ents, first[:, None], -1)[:, 0], PAD_IDX
            )
            o_ents = jnp.where(nbr_log_ents >= 0, nbr_log_ents, sem_match)
            o_hops = jnp.where(
                (o_ents >= 0) & (nbr_ids >= 0),
                jnp.minimum(src_hop + 1, INF_HOP),
                INF_HOP,
            )
            reward = jnp.where(
                o_hops < INF_HOP, w_kg / jnp.maximum(o_hops, 1).astype(jnp.float32), 0.0
            )
        else:
            o_ents = jnp.full(nbr_ids.shape, PAD_IDX, jnp.int32)
            o_hops = jnp.full(nbr_ids.shape, INF_HOP)
            reward = jnp.zeros(nbr_ids.shape, jnp.float32)

        # ---- fused hybrid distance + top-k over the round (l.21-25) ----
        # All E expanded nodes' neighbor lists ride the candidate axis of ONE
        # fused kernel invocation (multi-node batching: the pinned query
        # block amortizes over every node's tiles), the kg reward enters as
        # the pre-selection bias, and only the round's top-kr survivors come
        # back — the (W,) score vector never round-trips through HBM on the
        # kernel path. Pre-selecting the round is exact:
        # top_P(pool ∪ round) == top_P(pool ∪ top_kr(round)) for kr >= min(P, W),
        # and tie order is preserved (fused selection prefers low positions,
        # matching the concat order lax.top_k would have seen).
        W = nbr_ids.shape[0]
        kr = min(P, W)
        sel_scores, sel_pos = ops.fused_topk_vs_ids(
            q_b, index.corpus, nbr_ids[None], kr,
            bias=reward[None], use_kernel=p.use_kernel,
        )
        sel_scores, sel_pos = sel_scores[0], sel_pos[0]
        sel_ids = ops.take_topk_ids(nbr_ids, sel_pos)
        sel_ents = ops.take_topk(o_ents, sel_pos, PAD_IDX)
        sel_hops = ops.take_topk(o_hops, sel_pos, INF_HOP)

        all_ids = jnp.concatenate([pool_ids, sel_ids])
        all_scores = jnp.concatenate([pool_scores, sel_scores])
        all_visited = jnp.concatenate([pool_visited, jnp.zeros(sel_ids.shape, bool)])
        all_ents = jnp.concatenate([pool_ents, sel_ents])
        all_hops = jnp.concatenate([pool_hops, sel_hops])
        top, pos = jax.lax.top_k(all_scores, P)
        pool_ids = jnp.where(top > NEG, all_ids[pos], PAD_IDX)
        pool_scores = top
        pool_visited = all_visited[pos] | (top <= NEG)
        pool_ents = all_ents[pos]
        pool_hops = all_hops[pos]

        # ---- twin pool: keyword-satisfying candidates (l.26-28) ----
        # Same fused selection over the keyword-matching subset. Candidates
        # already resident in the twin pool are PAD'd out *before* selection
        # (the pre-selection dedup that makes top_kk exact), so
        # top_kwP(kw ∪ matched) == top_kwP(kw ∪ top_kk(matched \ kw)).
        if p.use_keywords:
            cand_kw = index.corpus.lexical.idx[jnp.clip(nbr_ids, 0, n - 1)]
            matches = has_keyword_overlap(cand_kw, q_keywords) & (nbr_ids >= 0)
            in_kw = (nbr_ids[:, None] == kw_ids[None, :]).any(-1)
            kw_cand = jnp.where(matches & ~in_kw, nbr_ids, PAD_IDX)
            kk = min(p.kw_pool_size, W)
            kwsel_scores, kwsel_pos = ops.fused_topk_vs_ids(
                q_b, index.corpus, kw_cand[None], kk,
                bias=reward[None], use_kernel=p.use_kernel,
            )
            kwsel_ids = ops.take_topk_ids(kw_cand, kwsel_pos[0])
            m_ids = jnp.concatenate([kw_ids, kwsel_ids])
            m_scores = jnp.concatenate([kw_scores, kwsel_scores[0]])
            kw_top, kw_pos = jax.lax.top_k(m_scores, p.kw_pool_size)
            kw_ids = jnp.where(kw_top > NEG, m_ids[kw_pos], PAD_IDX)
            kw_scores = kw_top

        return (pool_ids, pool_scores, pool_visited, pool_ents, pool_hops,
                ring, kw_ids, kw_scores, n_expanded)

    state = (pool_ids, pool_scores, pool_visited, pool_ents, pool_hops,
             ring, kw_ids, kw_scores, n_expanded)
    state = jax.lax.fori_loop(0, p.iters, body, state)
    (pool_ids, pool_scores, _, _, _, _, kw_ids, kw_scores, n_expanded) = state
    return _final_results(
        index, q_raw, q_keywords, spec, p,
        pool_ids, pool_scores, kw_ids, kw_scores, n_expanded,
    )


def _final_results(
    index: HybridIndex,
    q_raw: FusedVectors,
    q_keywords: jax.Array,
    spec: FusionSpec,
    p: SearchParams,
    pool_ids: jax.Array,  # (P,) primary pool
    pool_scores: jax.Array,  # (P,) traversal scores
    kw_ids: jax.Array,  # (kw_pool_size,) twin pool
    kw_scores: jax.Array,
    n_expanded: jax.Array,
):
    """Final results of one query (Algorithm 2 l.29-30) from its candidate
    pools, however they were filled: a graph traversal or a scan."""
    n = index.n
    # ---- final results (l.29-30): merge pools, keyword filter, alive filter
    res_ids = jnp.concatenate([pool_ids, kw_ids])
    res_scores = jnp.concatenate([pool_scores, kw_scores])
    keep = dedup_mask(res_ids)
    alive = index.alive[jnp.clip(res_ids, 0, n - 1)] & (res_ids >= 0)
    valid = keep & alive
    res_scores = jnp.where(valid, res_scores, NEG)
    if p.use_keywords:
        has_req = (q_keywords >= 0).any()
        match = has_keyword_overlap(
            index.corpus.lexical.idx[jnp.clip(res_ids, 0, n - 1)], q_keywords
        )
        valid = valid & ~(has_req & ~match)
        res_scores = jnp.where(has_req & ~match, NEG, res_scores)

    # ---- dynamic fusion (§11): re-score the final candidate pool ----------
    # Traversal always navigated with the weighted-sum score (qw); the
    # fusion mode only re-scores the merged pool. Per-path raw scores come
    # from the UNWEIGHTED query via three single-path-masked passes through
    # the same scoring op — the shape-stable analogue of keeping separate
    # per-path result lists. In weighted_sum mode the fused scores are
    # exactly ``res_scores`` (bit-compatible default). The KG logical reward
    # is a traversal bias in every mode but enters FINAL scores only through
    # the weighted-sum branch (ranks/normalized sums are score-path-only).
    zeros_like_val = lambda s: SparseVec(s.idx, jnp.zeros_like(s.val))

    def path_score(q_single):
        return ops.hybrid_scores_vs_ids(
            jax.tree.map(lambda a: a[None], q_single),
            index.corpus,
            res_ids[None],
            use_kernel=p.use_kernel,
        )[0]

    q_dense = FusedVectors(
        q_raw.dense, zeros_like_val(q_raw.learned), zeros_like_val(q_raw.lexical)
    )
    q_learned = FusedVectors(
        jnp.zeros_like(q_raw.dense), q_raw.learned, zeros_like_val(q_raw.lexical)
    )
    q_lexical = FusedVectors(
        jnp.zeros_like(q_raw.dense), zeros_like_val(q_raw.learned), q_raw.lexical
    )
    ps = jnp.stack(
        [path_score(q_dense), path_score(q_learned), path_score(q_lexical)],
        axis=-1,
    )  # (M, 3); -inf on PAD slots -> sanitize before any arithmetic
    ps = jnp.where(valid[:, None], ps, 0.0)
    fused = fuse_candidates(res_scores, ps, valid, spec, NEG)

    top, pos = jax.lax.top_k(fused, p.k)
    ok = top > NEG
    out_ids = jnp.where(ok, res_ids[pos], PAD_IDX)
    out_ps = jnp.where(ok[:, None], ps[pos], 0.0)
    return out_ids, top, out_ps, n_expanded


# incremented once per trace of search_padded or of its exact twin
# scan_padded, the grow segment's read (the Python body only runs when jit
# misses its cache) — the observable the shape-bucketing tests and the CI
# obs gate assert on: retraces == compiles for these entry points.
# Lives in the process-wide metrics registry so benches and the serving
# exposition read the same series (obs naming convention, DESIGN.md §12).
_TRACE_COUNTER = _OBS.counter(
    "allanpoe_core_search_padded_traces_total",
    "search_padded (re)traces: jit cache misses for the padded entry point",
)


def search_padded_trace_count() -> int:
    """Process-wide number of ``search_padded`` (re)traces so far."""
    return int(_TRACE_COUNTER.total())


@partial(jax.jit, static_argnames=("params",))
def search_padded(
    index: HybridIndex,
    queries: FusedVectors,
    fusion: Union[FusionSpec, PathWeights],
    keywords: jax.Array,  # (B, Kw) required keywords, PAD_IDX padded
    entities: jax.Array,  # (B, Eq) query entities, PAD_IDX padded
    params: SearchParams,
) -> SearchResult:
    """Shape-stable batched search: every operand is a concrete array with a
    static pad cap and no data-dependent Python branching, so one traced
    executable serves every request mix of a given shape bucket.

    ``fusion`` is a ``FusionSpec`` whose leaves may be scalars (whole-batch)
    or (B,)/(B, 3) arrays (per-query fusion, as micro-batched serving
    requires): mode, weights, rrf_k and stats all enter as traced data, so
    switching ANY of them never recompiles (Theorem 1 extended to the
    dynamic fusion framework, §11). A bare ``PathWeights`` still works
    (silently: jitted code is no place for a once-per-trace warning) and
    means weighted-sum. This is the entry point the serving layer
    AOT-compiles per (bucket shape, SearchParams); ``search()`` is the
    convenience wrapper that fabricates the pad arrays.
    """
    _TRACE_COUNTER.inc()
    if isinstance(fusion, PathWeights):
        fusion = FusionSpec.from_weights(fusion)
    b = queries.dense.shape[0]
    spec = broadcast_spec(fusion, b)
    qw = weighted_query(queries, spec.weights)
    ids, scores, ps, expanded = jax.vmap(
        lambda q, qr, kw, en, sp: _search_one(index, q, qr, kw, en, sp, params)
    )(qw, queries, keywords, entities, spec)
    return SearchResult(ids, scores, expanded, ps)


# retained name for callers of the private batched entry point
_search_batch = search_padded


SCAN_CHUNK = 256  # rows scored per step of a scan: bounds the row gather


def _scan_scores(
    index: HybridIndex, qw: FusedVectors, p: SearchParams
) -> jax.Array:
    """(B, n) scores of every row against every weighted query, in row
    chunks so the gathered rows stay (B, SCAN_CHUNK); dead rows NEG."""
    n, b = index.n, qw.dense.shape[0]
    c = min(SCAN_CHUNK, n)
    ids = jnp.arange(-(-n // c) * c, dtype=jnp.int32)
    ids = jnp.where(ids < n, ids, PAD_IDX).reshape(-1, c)
    scores = jax.lax.map(
        lambda blk: ops.hybrid_scores_vs_ids(
            qw, index.corpus, jnp.broadcast_to(blk, (b, c)),
            use_kernel=p.use_kernel,
        ),
        ids,
    )  # (n_chunks, B, c)
    scores = jnp.moveaxis(scores, 0, 1).reshape(b, -1)[:, :n]
    return jnp.where(index.alive[None, :], scores, NEG)


def _scan_one(
    index: HybridIndex,
    scores: jax.Array,  # (n,) this query's score of every row
    q_raw: FusedVectors,
    q_keywords: jax.Array,
    spec: FusionSpec,
    p: SearchParams,
):
    """``_search_one`` with every live row scored instead of a traversal:
    the pools hold the exact best rows, so the results are exact."""
    n = index.n

    def best(s, width):
        width_n = min(width, n)
        top, pos = jax.lax.top_k(s, width_n)
        pad = width - width_n
        top = jnp.concatenate([top, jnp.full((pad,), NEG, top.dtype)])
        pos = jnp.concatenate(
            [pos.astype(jnp.int32), jnp.full((pad,), PAD_IDX, jnp.int32)]
        )
        return jnp.where(top > NEG, pos, PAD_IDX), top

    pool_ids, pool_scores = best(scores, p.pool_size)
    kw_ids = jnp.full((p.kw_pool_size,), PAD_IDX, jnp.int32)
    kw_scores = jnp.full((p.kw_pool_size,), NEG, jnp.float32)
    if p.use_keywords:
        match = has_keyword_overlap(index.corpus.lexical.idx, q_keywords[None, :])
        kw_ids, kw_scores = best(jnp.where(match, scores, NEG), p.kw_pool_size)
    return _final_results(
        index, q_raw, q_keywords, spec, p,
        pool_ids, pool_scores, kw_ids, kw_scores, jnp.int32(n),
    )


@partial(jax.jit, static_argnames=("params",))
def scan_padded(
    index: HybridIndex,
    queries: FusedVectors,
    fusion: Union[FusionSpec, PathWeights],
    keywords: jax.Array,  # (B, Kw) required keywords, PAD_IDX padded
    entities: jax.Array,  # (B, Eq) unused: a scan walks no logical edge
    params: SearchParams,
) -> SearchResult:
    """Exact twin of ``search_padded`` for a small segment: every live row
    is scored, so a passage is always found by a query equal to its own
    vector, which a graph traversal does not promise. Same arguments and
    results; ``expanded`` counts the rows scored. Logical edges are not
    walked (``params.use_kg`` must be False)."""
    if params.use_kg:
        raise ValueError("scan_padded walks no logical edge: use search_padded")
    del entities
    _TRACE_COUNTER.inc()
    if isinstance(fusion, PathWeights):
        fusion = FusionSpec.from_weights(fusion)
    spec = broadcast_spec(fusion, queries.dense.shape[0])
    qw = weighted_query(queries, spec.weights)
    scores = _scan_scores(index, qw, params)
    ids, out_scores, ps, expanded = jax.vmap(
        lambda s, qr, kw, sp: _scan_one(index, s, qr, kw, sp, params)
    )(scores, queries, keywords, spec)
    return SearchResult(ids, out_scores, expanded, ps)


def search(
    index: HybridIndex,
    queries: FusedVectors,
    fusion: Union[FusionSpec, PathWeights],
    params: SearchParams,
    *,
    keywords: Optional[jax.Array] = None,  # (B, Kw) required keywords
    entities: Optional[jax.Array] = None,  # (B, Eq) query entities
) -> SearchResult:
    """Batched hybrid search with any path combination and fusion mode
    (public API). ``fusion`` is a ``FusionSpec``; passing ``PathWeights``
    still works via the deprecated weighted-sum shim (DeprecationWarning)."""
    spec = as_fusion_spec(fusion)
    b = queries.dense.shape[0]

    def as_padded(a):  # fabricate the PAD array only when absent/empty
        a = None if a is None else jnp.asarray(a, jnp.int32)
        if a is None or a.shape[1] == 0:
            return jnp.full((b, 1), PAD_IDX, jnp.int32)
        return a

    return search_padded(
        index, queries, spec, as_padded(keywords), as_padded(entities), params
    )
