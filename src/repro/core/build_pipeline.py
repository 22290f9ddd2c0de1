"""Device-resident construction pipeline (paper §4.1, Algorithm 1; Table 2).

The paper's headline build numbers come from keeping the whole pipeline —
NN-Descent, RNG-IP joint pruning, keyword recycling — resident on the
accelerator. The seed reproduction drove every stage from Python chunk loops
(one jit dispatch per chunk per round, three *sequential* single-path
refinement descents, host-side concatenation of every round's (N, K)
tables). This module replaces that with a single jitted program:

  * ``BuildState`` — a pytree (neighbor ids/scores, RNG key) advanced by
    ``lax.fori_loop`` over descent rounds; node chunks stream through
    ``lax.map`` *inside* the trace, so per-round intermediates stay bounded
    by one chunk while the whole build is one host->device dispatch;
  * the three per-path refinement descents run as ONE batched descent over
    stacked single-path weight views (weights are traced data, Theorem 1):
    ``vmap`` over a leading path axis of the same round body. Note this
    trades memory for dispatch latency: the refinement stage holds the 3
    weighted corpus views and 3 (N, K) tables live at once (the legacy path
    held one at a time) — budget ~3x the fused-corpus footprint in HBM;
  * pruning chunks likewise run under ``lax.map`` in the same trace, using
    the candidate-pairwise tile kernel (kernels/pairwise_tile.py) instead of
    re-gathering candidate rows through a (C*K, K) id matrix;
  * ``insert()`` routes through the same stages (descent program + one
    fused merge/reverse/prune/back-link program).

Layering (this breaks the old index.py <-> search.py import cycle): graph
stages (knn_graph, pruning, this module's programs) sit below; assembly
(``build_index``/``insert``, which need HybridIndex and — for insert — the
search entry point) sits here at the top. ``core/index.py`` now holds only
the index structure and ``mark_deleted`` and imports neither.

Donation contract: the standalone ``nn_descent`` entry point donates the
init state buffers into the loop program (``_descent_rounds_jit``), so the
(N, K) tables are updated in place across the host boundary on accelerators
(donation is a no-op on CPU and disabled there to avoid warnings). Inside
the single-trace programs XLA reuses the fori_loop carry buffers without
any host round trip. See DESIGN.md §3.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import knn_graph, pruning
from repro.core.index import BuildConfig, HybridIndex
from repro.core.knn_graph import (
    KnnConfig,
    _chunked,
    _chunked_tree,
    _merge_topk,
    new_node_reverse,
)
from repro.core.logical_edges import LogicalEdges, build_logical_edges
from repro.core.pruning import self_scores
from repro.core.search import SearchParams, search
from repro.core.usms import (
    PAD_IDX,
    FusedVectors,
    PathWeights,
    stack_weights,
    weighted_query,
)
from repro.runtime import dispatch

# the donated loop program is built lazily at first use: donation is only
# honored on accelerator backends (on CPU it just triggers "donated buffers
# were not usable" warnings), and querying the backend at import time would
# initialize it before callers can set XLA_FLAGS / distributed topology
_descent_rounds_jit_cache = None


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["nbr_ids", "nbr_scores", "key"],
    meta_fields=[],
)
@dataclasses.dataclass
class BuildState:
    """Carry of the descent loop: the evolving k-NN tables + RNG key.

    Leaves may carry a leading path axis (3, N, K) during the batched
    per-path refinement."""

    nbr_ids: jax.Array  # (N, K) int32
    nbr_scores: jax.Array  # (N, K) f32
    key: jax.Array  # RNG key


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "knn_ids",
        "knn_scores",
        "semantic_edges",
        "keyword_edges",
        "entry_points",
        "self_ip",
    ],
    meta_fields=[],
)
@dataclasses.dataclass
class GraphArrays:
    """Device outputs of the graph stages (everything but logical edges,
    which are host-side numpy)."""

    knn_ids: jax.Array  # (N, K)
    knn_scores: jax.Array  # (N, K)
    semantic_edges: jax.Array  # (N, d)
    keyword_edges: jax.Array  # (N, dk)
    entry_points: jax.Array  # (n_entry,)
    self_ip: jax.Array  # (N,)


# ---------------------------------------------------------------------------
# Stage 1: NN-Descent, fully in-trace
# ---------------------------------------------------------------------------


def _descent_init(
    corpus: FusedVectors,
    queries: FusedVectors,
    key: jax.Array,
    init_ids: jax.Array | None,
    cfg: KnnConfig,
):
    """Initial graph + score-sorted rows (mirrors the legacy loop's prologue
    operation-for-operation so pipeline and legacy builds agree bitwise)."""
    n = corpus.n
    k = cfg.k
    key, k0 = jax.random.split(key)
    if init_ids is None:
        nbr_ids = knn_graph._init_graph(n, k, k0)
    else:
        nbr_ids = init_ids[:, :k]
        if nbr_ids.shape[1] < k:
            extra = knn_graph._init_graph(n, k - nbr_ids.shape[1], k0)
            nbr_ids = jnp.concatenate([nbr_ids, extra], axis=1)
    nbr_ids, scores = knn_graph.score_sorted_rows(queries, corpus, nbr_ids, cfg)
    return BuildState(nbr_ids=nbr_ids, nbr_scores=scores, key=key)


def _descent_rounds(
    corpus: FusedVectors,
    queries: FusedVectors,
    state: BuildState,
    cfg: KnnConfig,
    iters: int,
) -> BuildState:
    """``iters`` NN-Descent rounds as one fori_loop; each round streams node
    chunks through lax.map against the round-start neighbor table."""
    n, k = state.nbr_ids.shape
    chunk = min(cfg.node_chunk, n)
    node_ids = jnp.arange(n, dtype=jnp.int32)
    # static per-trace chunk views (node id pad value n never matches a
    # candidate id, so pad rows stay inert)
    q_chunks = _chunked_tree(queries, chunk, 0)
    node_chunks = _chunked(node_ids, chunk, n)

    def one_round(_, st: BuildState) -> BuildState:
        key, kr = jax.random.split(st.key)
        rand_ids = jax.random.randint(
            kr, (n, cfg.extra_random), 0, n, dtype=jnp.int32
        )

        def chunk_fn(x):
            qs, nid, nbrs, scs, rnd = x
            return knn_graph._descent_round_chunk(
                corpus, st.nbr_ids, qs, nid, nbrs, scs, rnd, cfg
            )

        ids_c, sc_c = jax.lax.map(
            chunk_fn,
            (
                q_chunks,
                node_chunks,
                _chunked(st.nbr_ids, chunk, PAD_IDX),
                _chunked(st.nbr_scores, chunk, -jnp.inf),
                _chunked(rand_ids, chunk, PAD_IDX),
            ),
        )
        return BuildState(
            nbr_ids=ids_c.reshape(-1, k)[:n],
            nbr_scores=sc_c.reshape(-1, k)[:n],
            key=key,
        )

    return jax.lax.fori_loop(0, iters, one_round, state)


_descent_init_jit = jax.jit(_descent_init, static_argnames=("cfg",))


def _descent_rounds_flat(corpus, queries, nbr_ids, nbr_scores, key, cfg, iters):
    state = BuildState(nbr_ids=nbr_ids, nbr_scores=nbr_scores, key=key)
    out = _descent_rounds(corpus, queries, state, cfg, iters)
    return out.nbr_ids, out.nbr_scores


def _descent_rounds_jit(*args, **kw):
    global _descent_rounds_jit_cache
    if _descent_rounds_jit_cache is None:
        donate = (2, 3) if jax.default_backend() != "cpu" else ()
        _descent_rounds_jit_cache = jax.jit(
            _descent_rounds_flat,
            static_argnames=("cfg", "iters"),
            donate_argnums=donate,
        )
    return _descent_rounds_jit_cache(*args, **kw)


def nn_descent(
    corpus: FusedVectors,
    cfg: KnnConfig,
    key: jax.Array,
    *,
    queries: FusedVectors | None = None,
    init_ids: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Device-resident NN-Descent: two dispatches total (init + donated loop
    program) instead of the legacy iters x n_chunks. Drop-in replacement for
    ``knn_graph.build_knn_graph`` — same (cfg, key) gives the same graph."""
    queries = corpus if queries is None else queries
    dispatch.tick()
    state = _descent_init_jit(corpus, queries, key, init_ids, cfg)
    dispatch.tick()
    return _descent_rounds_jit(
        corpus, queries, state.nbr_ids, state.nbr_scores, state.key, cfg, cfg.iters
    )


# ---------------------------------------------------------------------------
# Stage 1b: batched per-path refinement (one descent over stacked views)
# ---------------------------------------------------------------------------


def _single_path_views(corpus: FusedVectors) -> FusedVectors:
    """Stack the three single-path weight views of the corpus on a leading
    path axis — weights enter as traced data (Theorem 1), so one program
    refines all paths at once."""
    ws = stack_weights(
        [
            PathWeights.make(1.0, 0.0, 0.0),
            PathWeights.make(0.0, 1.0, 0.0),
            PathWeights.make(0.0, 0.0, 1.0),
        ]
    )
    return jax.vmap(lambda w: weighted_query(corpus, w))(ws)


def _path_refinement(
    corpus: FusedVectors,
    knn_ids: jax.Array,
    key: jax.Array,
    cfg: BuildConfig,
    pk: int,
) -> jax.Array:
    """The d/2 single-path neighbor slots: one *batched* descent over the
    stacked path views (vs the legacy three sequential descents). Returns
    (N, 3, pk) per-path neighbor ids."""
    pcfg = dataclasses.replace(
        cfg.knn, iters=cfg.path_refine_iters, k=max(pk, 12)
    )
    qviews = _single_path_views(corpus)  # leaves (3, N, ...)
    pkeys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(1, 4))

    def one_path(qv: FusedVectors, pkey: jax.Array) -> jax.Array:
        st = _descent_init(corpus, qv, pkey, knn_ids, pcfg)
        st = _descent_rounds(corpus, qv, st, pcfg, pcfg.iters)
        return st.nbr_ids[:, :pk]

    per_path = jax.vmap(one_path)(qviews, pkeys)  # (3, N, pk)
    return jnp.swapaxes(per_path, 0, 1)  # (N, 3, pk)


# ---------------------------------------------------------------------------
# Stages 2-3: pruning + keyword recycling, chunked in-trace
# ---------------------------------------------------------------------------


def _prune_all(
    corpus: FusedVectors,
    knn_ids: jax.Array,
    knn_scores: jax.Array,
    cself: jax.Array,
    path_ids: jax.Array | None,
    cfg,
) -> tuple[jax.Array, jax.Array]:
    """rng_ip_prune with the chunk loop inside the trace (lax.map)."""
    n = corpus.n
    chunk = min(cfg.node_chunk, n)
    rev = knn_graph.reverse_neighbors(knn_ids, max(cfg.degree // 4, 1))
    node_ids = jnp.arange(n, dtype=jnp.int32)

    xs = (
        _chunked_tree(corpus, chunk, 0),
        _chunked(node_ids, chunk, n),
        _chunked(knn_ids, chunk, PAD_IDX),
        _chunked(knn_scores, chunk, -jnp.inf),
        _chunked(rev, chunk, PAD_IDX),
    )
    if path_ids is not None:
        xs = xs + (_chunked(path_ids, chunk, PAD_IDX),)

    def chunk_fn(x):
        qs, nid, cids, cscs, rv = x[:5]
        pids = x[5] if len(x) > 5 else None
        return pruning._prune_chunk(
            corpus, qs, nid, cids, cscs, cself, rv, pids, cfg
        )

    sem, kw, _ = jax.lax.map(chunk_fn, xs)
    d = sem.shape[-1]
    dk = kw.shape[-1]
    return sem.reshape(-1, d)[:n], kw.reshape(-1, dk)[:n]


# ---------------------------------------------------------------------------
# Entry points (paper §4.2.1) — shared by pipeline (in-trace) and legacy
# ---------------------------------------------------------------------------


def _entry_points(
    corpus: FusedVectors, sip: jax.Array, n_entry: int
) -> jax.Array:
    """Union of top-norm nodes under the fused metric AND each single path,
    so entry quality holds for any query weights."""
    # ceil: the 4-part union must never be narrower than n_entry (a tiny
    # segment's n_entry = n may not divide by 4, and unique_take can only
    # return what it was given)
    per = max(-(-n_entry // 4), 1)
    entry_parts = [jax.lax.top_k(sip, per)[1]]
    for w in (
        PathWeights.make(1.0, 0.0, 0.0),
        PathWeights.make(0.0, 1.0, 0.0),
        PathWeights.make(0.0, 0.0, 1.0),
    ):
        norms = self_scores(weighted_query(corpus, w))
        entry_parts.append(jax.lax.top_k(norms, per)[1])
    cat = jnp.concatenate(entry_parts).astype(jnp.int32)
    entries = pruning.unique_take(
        cat, jnp.zeros(cat.shape, jnp.float32), n_entry
    )
    # backfill duplicates with the next-best fused-norm nodes
    fill = jax.lax.top_k(sip, n_entry)[1].astype(jnp.int32)
    return jnp.where(entries >= 0, entries, fill).astype(jnp.int32)


# ---------------------------------------------------------------------------
# The fused graph-build program: ONE dispatch for steps 1-3 + entry points
# ---------------------------------------------------------------------------


def _graph_pk(cfg: BuildConfig) -> int:
    d = cfg.prune.degree
    return max((d - 2 * max(d // 4, 1)) // 3 + 1, 2)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _build_graph_program(
    corpus: FusedVectors, key: jax.Array, cfg: BuildConfig
) -> GraphArrays:
    # Step 1: fused NN-Descent
    st = _descent_init(corpus, corpus, key, None, cfg.knn)
    st = _descent_rounds(corpus, corpus, st, cfg.knn, cfg.knn.iters)
    knn_ids, knn_scores = st.nbr_ids, st.nbr_scores

    # Step 1b: batched per-path refinement
    path_ids = None
    if cfg.path_refine_iters > 0:
        path_ids = _path_refinement(corpus, knn_ids, key, cfg, _graph_pk(cfg))

    # Steps 2-3: RNG-IP joint pruning + keyword recycling
    cself = self_scores(corpus)
    sem, kw = _prune_all(corpus, knn_ids, knn_scores, cself, path_ids, cfg.prune)

    # entry points (§4.2.1)
    n_entry = min(cfg.n_entry, corpus.n)
    entries = _entry_points(corpus, cself, n_entry)
    return GraphArrays(
        knn_ids=knn_ids,
        knn_scores=knn_scores,
        semantic_edges=sem,
        keyword_edges=kw,
        entry_points=entries,
        self_ip=cself,
    )


def build_graph(
    corpus: FusedVectors, cfg: BuildConfig, key: jax.Array
) -> GraphArrays:
    """All device-side graph stages as a single dispatch. This is the unit
    ``build_index_sharded`` replicates per segment under shard_map."""
    dispatch.tick()
    dispatch.build_rows_tick(corpus.n)
    return _build_graph_program(corpus, key, cfg)


def _build_graph_host(
    corpus: FusedVectors, cfg: BuildConfig, key: jax.Array
) -> GraphArrays:
    """Legacy host-driven path (Python chunk loops, sequential per-path
    descents). Kept for A/B benchmarking (BENCH_build.json) and as the
    reference the pipeline is validated against."""
    dispatch.build_rows_tick(corpus.n)
    knn_ids, knn_scores = knn_graph.build_knn_graph(corpus, cfg.knn, key)
    path_ids = None
    if cfg.path_refine_iters > 0:
        pk = _graph_pk(cfg)
        pcfg = dataclasses.replace(
            cfg.knn, iters=cfg.path_refine_iters, k=max(pk, 12)
        )
        per_path = []
        for i, w in enumerate(
            (
                PathWeights.make(1.0, 0.0, 0.0),
                PathWeights.make(0.0, 1.0, 0.0),
                PathWeights.make(0.0, 0.0, 1.0),
            )
        ):
            pids, _ = knn_graph.build_knn_graph(
                corpus,
                pcfg,
                jax.random.fold_in(key, i + 1),
                queries=weighted_query(corpus, w),
                init_ids=knn_ids,
            )
            per_path.append(pids[:, :pk])
        path_ids = jnp.stack(per_path, axis=1)  # (N, 3, pk)
    sem, kw = pruning.rng_ip_prune(
        corpus, knn_ids, knn_scores, cfg.prune, path_ids=path_ids
    )
    dispatch.tick()
    sip = self_scores(corpus)
    dispatch.tick(3)  # the three per-path top-norm scoring passes below
    entries = _entry_points(corpus, sip, min(cfg.n_entry, corpus.n))
    return GraphArrays(
        knn_ids=knn_ids,
        knn_scores=knn_scores,
        semantic_edges=sem,
        keyword_edges=kw,
        entry_points=entries,
        self_ip=sip,
    )


# ---------------------------------------------------------------------------
# Assembly: build_index (Algorithm 1) and insert (paper §4.1 Updates)
# ---------------------------------------------------------------------------


def build_index(
    corpus: FusedVectors,
    cfg: BuildConfig = BuildConfig(),
    *,
    key: Optional[jax.Array] = None,
    kg_triplets: Optional[np.ndarray] = None,
    doc_entities: Optional[np.ndarray] = None,
    n_entities: int = 0,
    pipeline: bool = True,
) -> HybridIndex:
    """Full construction pipeline (Algorithm 1). ``pipeline=True`` runs the
    device-resident fused program (one dispatch for all graph stages);
    ``pipeline=False`` keeps the legacy host-driven chunk loops."""
    key = key if key is not None else jax.random.key(0)
    n = corpus.n

    g = build_graph(corpus, cfg, key) if pipeline else _build_graph_host(corpus, cfg, key)

    # Step 4: logical edges (host-side numpy; no device work)
    if kg_triplets is not None and doc_entities is not None and n_entities > 0:
        log = build_logical_edges(
            kg_triplets,
            doc_entities,
            n_entities,
            l_cap=cfg.logical_cap,
            m_cap=cfg.entity_doc_cap,
        )
    else:
        log = LogicalEdges.empty(n)

    return HybridIndex(
        corpus=corpus,
        semantic_edges=g.semantic_edges,
        keyword_edges=g.keyword_edges,
        logical_edges=jnp.asarray(log.edges),
        doc_entities=jnp.asarray(log.doc_entities),
        entity_to_docs=jnp.asarray(log.entity_to_docs),
        entity_adj=jnp.asarray(log.entity_adj),
        entry_points=g.entry_points,
        alive=jnp.ones((n,), bool),
        self_ip=g.self_ip,
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def _insert_program(
    corpus_cat: FusedVectors,  # (n_old + n_new, ...) concatenated corpus
    new_docs: FusedVectors,  # (n_new, ...)
    old_self_ip: jax.Array,  # (n_old,)
    sem_old: jax.Array,  # (n_old, d)
    old_ids: jax.Array,  # (n_new, k) search results vs the existing index
    old_scores: jax.Array,  # (n_new, k)
    new_ids_local: jax.Array,  # (n_new, k) NN-Descent among the new nodes
    new_scores: jax.Array,  # (n_new, k)
    cfg: BuildConfig,
):
    """Fused merge + reverse + prune + back-link for an insert batch: one
    dispatch where the legacy path issued one per stage."""
    n_old = sem_old.shape[0]
    n_new = new_docs.n
    k = cfg.knn.k
    prune_cfg = cfg.prune

    new_ids_global = jnp.where(
        new_ids_local >= 0, new_ids_local + n_old, PAD_IDX
    )
    merged_ids, merged_scores = _merge_topk(
        old_ids, old_scores, new_ids_global, new_scores, k
    )

    cself = jnp.concatenate(
        [old_self_ip, self_scores(new_docs)]
    )
    # reverse edges among the new nodes only — merged_ids holds GLOBAL ids,
    # so old-corpus targets must not be mistaken for new-node rows
    rev = new_node_reverse(merged_ids, n_old, max(prune_cfg.degree // 4, 1))
    sem_new, kw_new, _ = pruning._prune_chunk(
        corpus_cat,
        new_docs,
        jnp.arange(n_new, dtype=jnp.int32) + n_old,
        merged_ids,
        merged_scores,
        cself,
        rev,
        None,
        prune_cfg,
    )

    # back-link: replace the weakest semantic edge of each strong old neighbor
    top_back = min(4, k)
    for j in range(top_back):
        tgt = merged_ids[:, j]  # (n_new,) target node (old or new)
        ok = (tgt >= 0) & (tgt < n_old)
        tgt_safe = jnp.clip(tgt, 0, n_old - 1)
        new_id = jnp.arange(n_new, dtype=jnp.int32) + n_old
        # weakest slot = last column (edge lists are priority-ordered)
        col = sem_old.shape[1] - 1 - (j % 2)
        sem_old = sem_old.at[tgt_safe, col].set(
            jnp.where(ok, new_id, sem_old[tgt_safe, col]), mode="drop"
        )
    return sem_old, sem_new, kw_new, cself


def insert(
    index: HybridIndex,
    new_docs: FusedVectors,
    cfg: BuildConfig,
    *,
    key: Optional[jax.Array] = None,
    new_doc_entities: Optional[np.ndarray] = None,
    search_params: Optional[SearchParams] = None,
) -> HybridIndex:
    """Insert new nodes: their k-NN = merge of (a) search of the existing
    index and (b) device-resident NN-Descent among the new nodes; then the
    standard pruning, all through the same pipeline stages as build_graph.
    Existing nodes acquire reverse edges to the new nodes (slot-replacement
    of their weakest edge) so the new region stays reachable.

    ``search_params`` bounds the step-(a) probe (the serving-layer grow
    segment trades probe breadth for insert latency); ``k`` and the edge
    paths are forced to the build's values so the candidate merge widths
    stay fixed regardless of the caller's serving params."""
    key = key if key is not None else jax.random.key(1)
    n_old = index.n
    n_new = new_docs.n
    k = cfg.knn.k
    dispatch.build_rows_tick(n_new)

    # (a) k-NN from the existing index via its own search
    if search_params is None:
        params = SearchParams(k=k, iters=max(24, 2 * k), use_kernel=cfg.knn.use_kernel)
    else:
        params = dataclasses.replace(
            search_params, k=k, use_keywords=False, use_kg=False,
            use_kernel=cfg.knn.use_kernel,
            # forcing k up must drag the pool along, or top_k(pool, k)
            # dies at trace time with an opaque XLA error
            pool_size=max(search_params.pool_size, 2 * k),
        )
    dispatch.tick()
    res = search(index, new_docs, PathWeights.three_path(), params)

    # (b) NN-Descent among the new nodes only (device-resident program)
    new_ids_local, new_scores = nn_descent(new_docs, cfg.knn, key)

    # concatenated corpus
    corpus = jax.tree.map(
        lambda a, b: jnp.concatenate([a, b], axis=0), index.corpus, new_docs
    )

    dispatch.tick()
    sem_old, sem_new, kw_new, cself = _insert_program(
        corpus,
        new_docs,
        index.self_ip,
        index.semantic_edges,
        res.ids,
        res.scores,
        new_ids_local,
        new_scores,
        cfg,
    )

    pad_rows = lambda a, rows: jnp.concatenate(
        [a, jnp.full((rows,) + a.shape[1:], PAD_IDX, a.dtype)], axis=0
    )
    if new_doc_entities is not None:
        new_ents = jnp.asarray(new_doc_entities, jnp.int32)
        if new_ents.shape[1] != index.doc_entities.shape[1]:
            raise ValueError("entity width mismatch")
        doc_entities = jnp.concatenate([index.doc_entities, new_ents], 0)
    else:
        doc_entities = pad_rows(index.doc_entities, n_new)

    return HybridIndex(
        corpus=corpus,
        semantic_edges=jnp.concatenate([sem_old, sem_new], 0),
        keyword_edges=jnp.concatenate([index.keyword_edges, kw_new], 0),
        logical_edges=pad_rows(index.logical_edges, n_new),
        doc_entities=doc_entities,
        entity_to_docs=index.entity_to_docs,
        entity_adj=index.entity_adj,
        entry_points=index.entry_points,
        alive=jnp.concatenate([index.alive, jnp.ones((n_new,), bool)]),
        self_ip=cself,
    )


# ---------------------------------------------------------------------------
# Row-axis reshaping of a built index (shape-bucketing support): shared by
# the serving grow segment and the segment pool's pow2-capacity segments.
# ---------------------------------------------------------------------------


def map_index_rows(index: HybridIndex, fn) -> HybridIndex:
    """Apply ``fn(array, pad_fill)`` to every per-row (axis-0 == N) leaf of a
    single-segment index; entity tables and entry points are N-independent."""
    from repro.core.usms import SparseVec

    return dataclasses.replace(
        index,
        corpus=FusedVectors(
            fn(index.corpus.dense, 0),
            SparseVec(
                fn(index.corpus.learned.idx, PAD_IDX),
                fn(index.corpus.learned.val, 0),
            ),
            SparseVec(
                fn(index.corpus.lexical.idx, PAD_IDX),
                fn(index.corpus.lexical.val, 0),
            ),
        ),
        semantic_edges=fn(index.semantic_edges, PAD_IDX),
        keyword_edges=fn(index.keyword_edges, PAD_IDX),
        logical_edges=fn(index.logical_edges, PAD_IDX),
        doc_entities=fn(index.doc_entities, PAD_IDX),
        alive=fn(index.alive, False),
        self_ip=fn(index.self_ip, 0.0),
    )


def pad_index_rows(index: HybridIndex, capacity: int) -> HybridIndex:
    """Pad an index's per-row arrays with DEAD rows up to ``capacity``
    (shape-bucketing). Pad rows are unreachable by construction: entry
    points and edges only reference real rows, ``alive`` is False, and no
    global-id map ever covers them."""
    n = index.n
    if capacity <= n:
        return index

    def pad(a, fill):
        return jnp.concatenate(
            [a, jnp.full((capacity - n,) + a.shape[1:], fill, a.dtype)]
        )

    return map_index_rows(index, pad)


def slice_index_rows(index: HybridIndex, n: int) -> HybridIndex:
    """Drop a padded index's dead tail (inverse of ``pad_index_rows``)."""
    if index.n == n:
        return index
    return map_index_rows(index, lambda a, _fill: a[:n])
