"""Query processing (Algorithm 2): recall vs brute force, dynamic weights,
keyword augmentation, KG multi-hop, updates."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import BuildConfig, KnnConfig, PruneConfig, build_index, insert, mark_deleted
from repro.core.fusion import FusionSpec
from repro.core.search import SearchParams, scan_padded, search
from repro.core.usms import PathWeights, has_keyword_overlap, weighted_query
from repro.data.corpus import CorpusConfig, make_corpus, ndcg_at_k, recall_at_k
from repro.kernels import ops


@pytest.fixture(scope="module")
def built():
    corpus = make_corpus(
        CorpusConfig(
            n_docs=1024, n_queries=32, n_topics=24, d_dense=48,
            nnz_sparse=16, nnz_lexical=8, seed=5,
        )
    )
    cfg = BuildConfig(
        knn=KnnConfig(k=32, iters=5, node_chunk=1024),
        prune=PruneConfig(degree=32, keyword_degree=8, node_chunk=256),
        path_refine_iters=3,
    )
    index = build_index(
        corpus.docs,
        cfg,
        kg_triplets=corpus.kg.triplets,
        doc_entities=corpus.doc_entities,
        n_entities=corpus.kg.n_entities,
    )
    return corpus, index, cfg


def vector_recall(index, corpus, weights, params, k=10):
    """Recall vs brute-force hybrid top-k under the same weights."""
    res = search(index, corpus.queries, weights, params)
    qw = weighted_query(corpus.queries, weights)
    scores = ops.pairwise_scores_chunked(qw, corpus.docs)
    _, truth = jax.lax.top_k(scores, k)
    return recall_at_k(np.asarray(res.ids[:, :k]), np.asarray(truth))


def test_three_path_recall(built):
    corpus, index, _ = built
    params = SearchParams(k=10, iters=48, pool_size=64)
    rec = vector_recall(index, corpus, PathWeights.three_path(), params)
    assert rec > 0.85, f"three-path recall {rec}"


def test_single_path_recall_dense(built):
    corpus, index, _ = built
    params = SearchParams(k=10, iters=64, pool_size=96)
    rec = vector_recall(index, corpus, PathWeights.make(1.0, 0.0, 0.0), params)
    assert rec > 0.75, f"dense-only recall {rec}"


def test_single_path_recall_sparse(built):
    corpus, index, _ = built
    params = SearchParams(k=10, iters=48, pool_size=64)
    rec = vector_recall(index, corpus, PathWeights.make(0.0, 1.0, 0.0), params)
    assert rec > 0.7, f"sparse-only recall {rec}"


def test_arbitrary_weights_no_rebuild(built):
    """Flexibility: the same index must serve any weight vector (Figure 12)."""
    corpus, index, _ = built
    params = SearchParams(k=10, iters=48, pool_size=64)
    for w in [(0.3, 0.7, 0.0), (0.7, 0.3, 0.2), (0.5, 0.5, 0.5), (0.0, 0.0, 1.0)]:
        rec = vector_recall(index, corpus, PathWeights.make(*w), params)
        assert rec > 0.5, f"weights {w}: recall {rec}"


def test_results_sorted_unique_alive(built):
    corpus, index, _ = built
    params = SearchParams(k=10, iters=32)
    res = search(index, corpus.queries, PathWeights.three_path(), params)
    ids = np.asarray(res.ids)
    scores = np.asarray(res.scores)
    assert (np.diff(scores, axis=1) <= 1e-5).all()
    for row in ids:
        v = row[row >= 0]
        assert len(set(v.tolist())) == len(v)


def test_end_to_end_hybrid_beats_single_path(built):
    """The paper's central claim: fusing paths improves end-to-end accuracy
    (planted-relevant-doc nDCG) over single-path retrieval."""
    corpus, index, _ = built
    params = SearchParams(k=10, iters=48, pool_size=64)
    truth = corpus.query_relevant

    def ndcg(w):
        res = search(index, corpus.queries, w, params)
        return ndcg_at_k(np.asarray(res.ids), truth, k=10)

    nd_dense = ndcg(PathWeights.make(1.0, 0.0, 0.0))
    nd_three = ndcg(PathWeights.three_path())
    assert nd_three >= nd_dense - 0.02, f"three {nd_three} vs dense {nd_dense}"
    assert nd_three > 0.5


def test_keyword_filter_honored(built):
    corpus, index, _ = built
    params = SearchParams(k=5, iters=48, pool_size=64, use_keywords=True)
    kw = jnp.asarray(corpus.query_keywords)
    res = search(
        index, corpus.queries, PathWeights.three_path(), params, keywords=kw
    )
    ids = np.asarray(res.ids)
    f_idx = np.asarray(corpus.docs.lexical.idx)
    q_kw = np.asarray(corpus.query_keywords)
    violations = 0
    for qi in range(len(ids)):
        req = q_kw[qi][q_kw[qi] >= 0]
        if len(req) == 0:
            continue
        for d in ids[qi][ids[qi] >= 0]:
            if not set(req.tolist()) & set(f_idx[d][f_idx[d] >= 0].tolist()):
                violations += 1
    assert violations == 0


def test_kg_multihop_improves(built):
    """Logical edges should surface chain-tail docs that pure semantic search
    misses (paper §5.5, Table 3/4)."""
    corpus, index, _ = built
    truth = corpus.query_multihop_target[:, None]

    base = search(
        index, corpus.queries, PathWeights.three_path(),
        SearchParams(k=10, iters=48, pool_size=64),
    )
    rec_base = recall_at_k(np.asarray(base.ids), truth)

    w_kg = PathWeights.make(1.0, 1.0, 1.0, kg=30.0)
    kg = search(
        index, corpus.queries, w_kg,
        SearchParams(k=10, iters=48, pool_size=64, use_kg=True),
        entities=jnp.asarray(corpus.query_entities),
    )
    rec_kg = recall_at_k(np.asarray(kg.ids), truth)
    assert rec_kg > rec_base + 0.1, f"KG {rec_kg} vs base {rec_base}"


def test_mark_deletion_filters_results(built):
    corpus, index, _ = built
    params = SearchParams(k=10, iters=32)
    res = search(index, corpus.queries, PathWeights.three_path(), params)
    victim = int(np.asarray(res.ids)[0, 0])
    index2 = mark_deleted(index, jnp.array([victim]))
    res2 = search(index2, corpus.queries, PathWeights.three_path(), params)
    assert victim not in np.asarray(res2.ids)[0].tolist()


def test_insert_preserves_quality(built):
    """Paper §5.8: inserting 20% new data keeps recall within ~a point of a
    full rebuild."""
    corpus, index, cfg = built
    n = corpus.docs.n
    n_keep = int(n * 0.8)
    base_docs = corpus.docs[slice(0, n_keep)]
    new_docs = corpus.docs[slice(n_keep, n)]

    part_index = build_index(base_docs, cfg)
    upd = insert(part_index, new_docs, cfg)
    assert upd.n == n

    params = SearchParams(k=10, iters=48, pool_size=64)
    w = PathWeights.three_path()
    res = search(upd, corpus.queries, w, params)
    qw = weighted_query(corpus.queries, w)
    scores = ops.pairwise_scores_chunked(qw, corpus.docs)
    _, truth = jax.lax.top_k(scores, 10)
    rec_upd = recall_at_k(np.asarray(res.ids), np.asarray(truth))

    res_full = search(index, corpus.queries, w, params)
    rec_full = recall_at_k(np.asarray(res_full.ids), np.asarray(truth))
    assert rec_upd > rec_full - 0.12, f"insert {rec_upd} vs rebuild {rec_full}"
    # new docs are actually reachable
    new_hit = (np.asarray(res.ids) >= n_keep).any()
    assert new_hit


@pytest.mark.parametrize("use_keywords", [False, True])
def test_scan_padded_is_exact(built, use_keywords):
    """The grow segment's read scores every live row: its top-k equals the
    brute-force top-k under the keyword rule, deleted rows never return,
    and every passage is found first by a query equal to its own vector."""
    corpus, index, _ = built
    dead = jnp.arange(0, 1024, 7)
    index = mark_deleted(index, dead)
    params = SearchParams(k=10, pool_size=64, use_keywords=use_keywords)
    spec = FusionSpec.three_path()
    q = corpus.queries
    kw = jnp.asarray(corpus.query_keywords)
    if not use_keywords:
        kw = jnp.full((q.n, 1), -1, jnp.int32)
    en = jnp.full((q.n, 1), -1, jnp.int32)
    got = np.asarray(scan_padded(index, q, spec, kw, en, params).ids)

    scores = np.array(ops.pairwise_scores_chunked(q, corpus.docs))
    scores[:, np.asarray(dead)] = -np.inf
    if use_keywords:
        ok = np.asarray(has_keyword_overlap(corpus.docs.lexical.idx[None], kw[:, None]))
        has_req = np.asarray((kw >= 0).any(1))
        scores = np.where(ok | ~has_req[:, None], scores, -np.inf)
    want = np.argsort(-scores, axis=1, kind="stable")[:, :10]
    want = np.where(np.take_along_axis(scores, want, 1) > -np.inf, want, -1)
    for g, w in zip(got, want):
        assert set(g.tolist()) == set(w.tolist())

    own = corpus.docs[slice(1, 1024, 7)]  # live rows
    res = scan_padded(
        index, own, spec, jnp.full((own.n, 1), -1, jnp.int32),
        jnp.full((own.n, 1), -1, jnp.int32), params,
    )
    np.testing.assert_array_equal(np.asarray(res.ids)[:, 0], np.arange(1, 1024, 7))


def test_scan_padded_refuses_kg_params(built):
    corpus, index, _ = built
    pad = jnp.full((2, 1), -1, jnp.int32)
    with pytest.raises(ValueError, match="logical edge"):
        scan_padded(
            index, corpus.queries[:2], FusionSpec.three_path(), pad, pad,
            SearchParams(use_kg=True),
        )
