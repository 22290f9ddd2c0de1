"""RNG-IP joint edge pruning + keyword-aware neighbor recycling
(paper §4.1 Steps 2-3, §3.3, Algorithm 1 lines 5-17).

Phase 1 (RNG, CAGRA-style): for node u with candidates sorted by hybrid
similarity, the edge u->v_j is *detourable* via v_i when
sim(u, v_i) > sim(u, v_j) and sim(v_i, v_j) > sim(u, v_j); candidates are
re-ranked by detourable-route count (fewest first).

Phase 2 (IP pruning, Tan et al. rule): walking the re-ranked list, candidate
v joins the kept set only if IP(w, v) < IP(v, v) for every already-kept w —
this removes small-norm vectors that can never win a MIPS comparison.

Keyword recycling (dual assessment): a candidate v that phase 2 prunes is
recycled as a *keyword edge* iff it contributes a keyword k in K(u) ∩ K(v)
that no kept neighbor covers — keeping keyword navigation reachable after
vector fusion. The flags are computed from the same intersection pass that
the pruning distances already need (the paper fuses this into the warp
kernel; here it is a fused batched mask computation over the same gathered
tiles).

Final edge list (paper Step 2 tail): d/4 IP-kept + d/4 reverse neighbors +
d/2 single-path neighbors (per-path re-ranking of the fused candidate pool —
the Pareto-frontier approximation that keeps any-weight queries robust).

GPU->TPU: one warp per neighbor pair becomes vmapped (K, K) score tiles; the
sequential keep-scan is a lax.scan; everything is fixed-shape and chunked.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.knn_graph import reverse_neighbors
from repro.core.usms import PAD_IDX, FusedVectors, PathWeights, weighted_query
from repro.kernels import ops, ref
from repro.runtime import dispatch

NEG = -1e30


@dataclasses.dataclass(frozen=True)
class PruneConfig:
    degree: int = 16  # final semantic degree d
    keyword_degree: int = 8  # keyword-edge slots per node
    node_chunk: int = 1024
    use_kernel: bool | None = None  # None -> backend auto (Pallas off-CPU)
    mode: str = "joint"  # joint | rng (no IP rule) | ip (no detour ordering)


def detour_counts(cand_scores: jax.Array, pair_scores: jax.Array) -> jax.Array:
    """cand_scores: (K,) sim(u, v_j) sorted desc; pair_scores: (K, K) sim(v_i, v_j).
    Returns (K,) number of detourable routes per candidate."""
    k = cand_scores.shape[0]
    i_lt_j = jnp.tril(jnp.ones((k, k), bool), k=-1).T  # [i, j] True iff i < j
    detour = i_lt_j & (pair_scores > cand_scores[None, :])
    return detour.sum(axis=0).astype(jnp.int32)


def ip_keep_scan(
    order: jax.Array,  # (K,) candidate positions in keep-priority order
    pair_scores: jax.Array,  # (K, K) sim(v_i, v_j)
    self_scores: jax.Array,  # (K,) IP(v, v)
    valid: jax.Array,  # (K,) candidate validity
    cap: int,
) -> jax.Array:
    """Sequential IP-pruning keep rule -> bool (K,) kept mask (in original
    candidate positions)."""
    k = order.shape[0]

    def body(carry, j):
        kept, n_kept = carry  # kept: (K,) bool in original positions
        v = order[j]
        ips_vs_kept = jnp.where(kept, pair_scores[:, v], NEG)  # IP(w, v)
        ok = jnp.all(ips_vs_kept < self_scores[v]) & (n_kept < cap) & valid[v]
        kept = kept.at[v].set(ok)
        return (kept, n_kept + ok.astype(jnp.int32)), ok

    (kept, _), _ = jax.lax.scan(
        body, (jnp.zeros((k,), bool), jnp.int32(0)), jnp.arange(k)
    )
    return kept


def keyword_flags(
    u_kw: jax.Array,  # (Pf,) keyword ids of node u (PAD padded)
    cand_kw: jax.Array,  # (K, Pf) keyword ids of candidates
    kept: jax.Array,  # (K,) kept mask
) -> jax.Array:
    """Dual-assessment recycle flags: candidate v (not kept) is flagged iff
    some keyword in K(u) ∩ K(v) is absent from every kept neighbor."""
    # in_u[v, p]: cand_kw[v, p] ∈ K(u)
    in_u = (cand_kw[:, :, None] == u_kw[None, None, :]).any(-1) & (cand_kw >= 0)
    # covered[v, p]: cand_kw[v, p] present in some *kept* candidate's keyword set
    eq = cand_kw[:, :, None, None] == cand_kw[None, None, :, :]  # (K, Pf, K, Pf)
    covered_by = eq.any(-1) & kept[None, None, :]  # (K, Pf, K)
    covered = covered_by.any(-1)
    return ((in_u & ~covered).any(-1)) & ~kept


def unique_take(ids: jax.Array, scores: jax.Array, width: int) -> jax.Array:
    """Stable first-occurrence unique over a priority-ordered id list, padded
    to ``width`` with PAD_IDX. O(L^2) fixed-shape."""
    l = ids.shape[0]
    earlier_same = (ids[:, None] == ids[None, :]) & (
        jnp.arange(l)[None, :] < jnp.arange(l)[:, None]
    )
    is_dup = earlier_same.any(-1) | (ids == PAD_IDX) | ~jnp.isfinite(scores)
    rank = jnp.where(is_dup, l + jnp.arange(l), jnp.arange(l))
    order = jnp.argsort(rank)
    out = jnp.where(jnp.sort(rank) < l, ids[order], PAD_IDX)
    return out[:width]


def _prune_node(
    u_query: FusedVectors,  # fused vec of node u (no batch dim handled by caller)
    u_id: jax.Array,  # () node id (self-edges masked)
    cand_ids: jax.Array,  # (K,) candidate ids sorted by fused score desc
    cand_scores: jax.Array,  # (K,) sim(u, v)
    pair_scores: jax.Array,  # (K, K)
    cand_self: jax.Array,  # (K,) IP(v, v)
    path_picks: jax.Array,  # (3, pk) single-path neighbor ids (dense/sparse/full)
    u_kw: jax.Array,  # (Pf,)
    cand_kw: jax.Array,  # (K, Pf)
    rev_ids: jax.Array,  # (R,) reverse-neighbor ids
    rev_scores: jax.Array,  # (R,)
    cfg: PruneConfig,
):
    d = cfg.degree
    d4 = max(d // 4, 1)
    cand_ids = jnp.where(cand_ids == u_id, PAD_IDX, cand_ids)
    path_picks = jnp.where(path_picks == u_id, PAD_IDX, path_picks)
    valid = cand_ids >= 0

    # --- phase 1: RNG ordering by detourable routes ---
    if cfg.mode == "ip":
        # ablation: no detour ordering, keep fused-score order
        order = jnp.argsort(jnp.where(valid, -cand_scores, jnp.inf))
    else:
        routes = detour_counts(cand_scores, pair_scores)
        routes = jnp.where(valid, routes, jnp.iinfo(jnp.int32).max)
        # stable: tie-break by original rank (already score-sorted)
        order = jnp.argsort(routes * cand_ids.shape[0] + jnp.arange(cand_ids.shape[0]))

    # --- phase 2: IP keep rule ---
    if cfg.mode == "rng":
        # ablation: accept the first d/4 candidates in detour order
        kept = jnp.zeros(cand_ids.shape, bool).at[order[:d4]].set(True) & valid
    else:
        kept = ip_keep_scan(order, pair_scores, cand_self, valid, d4)

    # --- keyword recycling flags (dual assessment) ---
    flags = keyword_flags(u_kw, cand_kw, kept) & valid

    # --- assemble final semantic edges ---
    kept_rank = jnp.where(kept, -cand_scores, jnp.inf)  # kept first, best first
    kept_order = jnp.argsort(kept_rank)
    kept_ids = jnp.where(
        jnp.sort(kept_rank) < jnp.inf, cand_ids[kept_order], PAD_IDX
    )[:d4]

    rev_top = rev_ids[:d4]

    d_rem = d - 2 * d4
    per_path = max(d_rem // 3, 1)
    # interleave per-path picks (dense, sparse, full, dense, ...) so the
    # d/2 single-path budget is shared evenly (Pareto-frontier approximation)
    picks = jnp.swapaxes(path_picks[:, :per_path], 0, 1).reshape(-1)
    picks = jnp.where(picks == jnp.int32(-2), PAD_IDX, picks)
    # priority list: IP-kept, reverse, per-path picks, then remaining by score
    priority = jnp.concatenate([kept_ids, rev_top, picks, cand_ids])
    pr_scores = jnp.zeros_like(priority, jnp.float32)  # order already encodes priority
    sem = unique_take(priority, pr_scores, d)

    # --- keyword edges from flagged pruned candidates ---
    kw_rank = jnp.where(flags, -cand_scores, jnp.inf)
    kw_order = jnp.argsort(kw_rank)
    kw = jnp.where(jnp.sort(kw_rank) < jnp.inf, cand_ids[kw_order], PAD_IDX)[
        : cfg.keyword_degree
    ]
    return sem, kw, flags


_prune_nodes_batch = jax.vmap(
    _prune_node, in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, None)
)


def _prune_chunk(
    corpus: FusedVectors,
    chunk_queries: FusedVectors,
    node_ids: jax.Array,  # (C,) ids of the nodes being pruned
    cand_ids: jax.Array,  # (C, K)
    cand_scores: jax.Array,  # (C, K)
    corpus_self: jax.Array,  # (N,) IP(v,v) for all nodes
    rev_ids: jax.Array,  # (C, R)
    path_ids: jax.Array | None,  # (C, 3, pk) per-path neighbor ids or None
    cfg: PruneConfig,
):
    c, k = cand_ids.shape
    # pairwise scores among candidates: gather each node's K rows ONCE and
    # compute the (K, K) tile in place (kernels/pairwise_tile.py) — the old
    # path re-gathered the rows K times via a (C*K, K) id matrix
    cand_rows = corpus.take(cand_ids.reshape(-1))  # (C*K, ...)
    tile = jax.tree.map(
        lambda a: a.reshape((c, k) + a.shape[1:]), cand_rows
    )
    pair = ops.pairwise_tile_scores(tile, use_kernel=cfg.use_kernel)
    # invalid candidates j score -inf, matching hybrid_scores_vs_ids masking
    pair = jnp.where(cand_ids[:, None, :] >= 0, pair, -jnp.inf)
    cand_self = jnp.where(
        cand_ids >= 0, corpus_self[jnp.clip(cand_ids, 0, corpus.n - 1)], NEG
    )
    if path_ids is None:
        # fallback (insertion path): rerank the fused candidate pool per path
        pk = max((cfg.degree - 2 * max(cfg.degree // 4, 1)) // 3, 1)
        paths = []
        for w in (
            PathWeights.make(1.0, 0.0, 0.0),
            PathWeights.make(0.0, 1.0, 0.0),
            PathWeights.make(0.0, 0.0, 1.0),
        ):
            qw = weighted_query(chunk_queries, w)
            # fused per-path top-pk: selection happens in-kernel, the (C, K)
            # per-path score matrix never leaves it
            _, pos = ops.fused_topk_vs_ids(
                qw, corpus, cand_ids, pk, use_kernel=cfg.use_kernel
            )
            paths.append(ops.take_topk_ids(cand_ids, pos))
        path_ids = jnp.stack(paths, axis=1)  # (C, 3, pk)
    u_kw = chunk_queries.lexical.idx
    cand_kw = corpus.lexical.idx[jnp.clip(cand_ids, 0, corpus.n - 1)]
    cand_kw = jnp.where(cand_ids[..., None] >= 0, cand_kw, PAD_IDX)
    rev_scores = jnp.zeros(rev_ids.shape, jnp.float32)
    return _prune_nodes_batch(
        chunk_queries,
        node_ids,
        cand_ids,
        cand_scores,
        pair,
        cand_self,
        path_ids,
        u_kw,
        cand_kw,
        rev_ids,
        rev_scores,
        cfg,
    )


# jitted wrapper for the legacy host-driven chunk loop; the device-resident
# pipeline (core/build_pipeline.py) traces the plain body inside lax.map
_prune_chunk_jit = jax.jit(_prune_chunk, static_argnames=("cfg",))


def self_scores(corpus: FusedVectors) -> jax.Array:
    """IP(v, v) — fused self-similarity (squared fused norm).

    A per-row reduction, so it runs as jnp on every backend: through the
    candidate kernel each row would be a one-candidate tile padded to
    C_TILE lanes — 128x the work, and an (N, C_TILE, Dd) gather that does
    not fit a chip's memory at corpus scale."""
    cands = jax.tree.map(lambda a: a[:, None], corpus)
    return ref.hybrid_scores_ref(corpus, cands)[:, 0]


def rng_ip_prune(
    corpus: FusedVectors,
    knn_ids: jax.Array,  # (N, K) NN-Descent output, score-sorted desc
    knn_scores: jax.Array,  # (N, K)
    cfg: PruneConfig,
    *,
    path_ids: jax.Array | None = None,  # (N, 3, pk) per-path neighbors
) -> tuple[jax.Array, jax.Array]:
    """Full pruning pass. Returns (semantic_edges (N, d), keyword_edges (N, dk))."""
    n = corpus.n
    rev = reverse_neighbors(knn_ids, max(cfg.degree // 4, 1))
    dispatch.tick()
    cself = self_scores(corpus)
    node_ids = jnp.arange(n, dtype=jnp.int32)
    sems, kws = [], []
    for s in range(0, n, cfg.node_chunk):
        e = min(s + cfg.node_chunk, n)
        dispatch.tick()
        sem, kw, _ = _prune_chunk_jit(
            corpus,
            corpus[slice(s, e)],
            node_ids[s:e],
            knn_ids[s:e],
            knn_scores[s:e],
            cself,
            rev[s:e],
            None if path_ids is None else path_ids[s:e],
            cfg,
        )
        sems.append(sem)
        kws.append(kw)
    return jnp.concatenate(sems, 0), jnp.concatenate(kws, 0)
