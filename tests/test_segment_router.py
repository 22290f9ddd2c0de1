"""SegmentRouter: grow-segment streaming inserts that never evict sealed
executables, global-id deletion routing, seal-and-compact tombstone
reclamation, and the background pump thread (no lost PendingResult)."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import jax

from repro.core import BuildConfig, KnnConfig, PruneConfig, build_index
from repro.core.distributed import (
    build_segmented_index,
    place_segmented_index,
    resolve_global_ids,
)
from repro.core.search import SearchParams, search, search_padded_trace_count
from repro.core.usms import PathWeights
from repro.data.corpus import CorpusConfig, make_corpus
from repro.serving.batcher import BatcherConfig, SearchRequest
from repro.serving.hybrid_service import HybridSearchService, ServiceConfig
from repro.serving.segment_router import RouterConfig, SegmentRouter

BUILD_CFG = BuildConfig(
    knn=KnnConfig(k=12, iters=3, node_chunk=512),
    prune=PruneConfig(degree=12, keyword_degree=4, node_chunk=256),
    path_refine_iters=0,
)
PARAMS = SearchParams(k=8, iters=16, pool_size=48)
W = PathWeights.make(1.0, 1.0, 1.0)
N_SEALED = 320  # docs in the sealed segment; the rest stream in


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(
        CorpusConfig(n_docs=416, n_queries=16, n_topics=12, d_dense=24,
                     nnz_sparse=10, nnz_lexical=8, seed=31)
    )


@pytest.fixture(scope="module")
def sealed(corpus):
    return build_segmented_index(corpus.docs[:N_SEALED], 1, BUILD_CFG)


def _service(sealed, **kw):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    seg = place_segmented_index(sealed, mesh)
    svc_kw = dict(flush_size=4, max_batch=4, flush_deadline_s=60.0)
    svc_kw.update(kw.pop("batcher", {}))
    svc = HybridSearchService(
        seg, PARAMS,
        ServiceConfig(batcher=BatcherConfig(**svc_kw), **kw),
        mesh=mesh,
    )
    return svc


def _probe(corpus, i):
    """A query that IS doc i's own vector — the doc must come back first."""
    return jax.tree.map(lambda a: a[i:i + 1], corpus.docs)


def test_router_requires_segmented_service(corpus):
    index = build_index(corpus.docs[:64], BUILD_CFG)
    svc = HybridSearchService(index, PARAMS)
    with pytest.raises(ValueError):
        SegmentRouter(svc, BUILD_CFG)


def test_streaming_insert_preserves_sealed_executables(corpus, sealed):
    """The acceptance criterion: inserts land in the grow segment, searches
    see the new docs immediately, and NO sealed-segment executable is
    evicted or recompiled along the way."""
    svc = _service(sealed)
    SegmentRouter(svc, BUILD_CFG, RouterConfig(seal_threshold=10**9))

    svc.search(corpus.queries[:4], W, k=5)  # warm the sealed executable
    sealed_keys = set(svc.executable_cache)
    sealed_exes = {k: svc.executable_cache[k] for k in sealed_keys}
    assert sealed_keys  # the 4-slot bucket compiled

    v1 = svc.insert(corpus.docs[N_SEALED:N_SEALED + 32])
    assert v1 == 1
    # sealed entries still cached — the SAME objects, not recompiles
    for k in sealed_keys:
        assert svc.executable_cache[k] is sealed_exes[k]

    # the inserted docs are immediately searchable (probe = own vector)
    res = svc.search(_probe(corpus, N_SEALED + 7), W, k=5)
    assert int(np.asarray(res.ids)[0, 0]) == N_SEALED + 7

    # a second insert extends the grow segment in place
    v2 = svc.insert(corpus.docs[N_SEALED + 32:N_SEALED + 64])
    assert v2 == 2
    res = svc.search(_probe(corpus, N_SEALED + 40), W, k=5)
    assert int(np.asarray(res.ids)[0, 0]) == N_SEALED + 40

    # and the original 4-slot sealed executable is STILL the same object
    compiles = svc.stats.compiles
    svc.search(corpus.queries[:4], W, k=5)
    assert svc.stats.compiles == compiles
    for k in sealed_keys:
        assert svc.executable_cache[k] is sealed_exes[k]


def test_merged_topk_matches_reference_merge(corpus, sealed):
    """Service results over sealed+grow equal a host-side merge of direct
    searches on each part (same snapshot, global-id space)."""
    svc = _service(sealed)
    SegmentRouter(svc, BUILD_CFG, RouterConfig(seal_threshold=10**9))
    svc.insert(corpus.docs[N_SEALED:N_SEALED + 32])

    snap = svc._snap
    sealed_local = jax.tree.map(lambda a: a[0], snap.index.index)
    queries = corpus.queries[:4]
    r_sealed = search(sealed_local, queries, W, PARAMS)  # local ids == global
    r_grow = search(snap.grow, queries, W, PARAMS)
    ggids = np.asarray(snap.grow_gids)
    g_ids = np.where(np.asarray(r_grow.ids) >= 0,
                     ggids[np.clip(np.asarray(r_grow.ids), 0, len(ggids) - 1)],
                     -1)
    all_ids = np.concatenate([np.asarray(r_sealed.ids), g_ids], axis=1)
    all_sc = np.concatenate(
        [np.where(np.asarray(r_sealed.ids) >= 0, np.asarray(r_sealed.scores), -np.inf),
         np.where(g_ids >= 0, np.asarray(r_grow.scores), -np.inf)], axis=1)
    order = np.argsort(-all_sc, axis=1, kind="stable")[:, :5]
    want = np.take_along_axis(all_ids, order, axis=1)

    got = svc.search(queries, W, k=5)
    np.testing.assert_array_equal(np.asarray(got.ids), want)
    # merged rows contain no duplicate ids
    for row in np.asarray(got.ids):
        real = row[row >= 0]
        assert len(set(real.tolist())) == len(real)


def test_delete_routes_to_sealed_and_grow_tombstones(corpus, sealed):
    svc = _service(sealed)
    router = SegmentRouter(svc, BUILD_CFG, RouterConfig(seal_threshold=10**9))
    svc.insert(corpus.docs[N_SEALED:N_SEALED + 32])
    keys_before = set(svc.executable_cache)

    r0 = svc.search(corpus.queries[:4], W, k=5)
    top_sealed = int(np.asarray(r0.ids)[0, 0])
    assert top_sealed < N_SEALED
    grow_victim = N_SEALED + 3

    svc.mark_deleted([top_sealed, grow_victim, 10**6])  # one unknown id
    assert router.stats.deleted_sealed == 1
    assert router.stats.deleted_grow == 1
    assert router.stats.unknown_deletes == 1

    r1 = svc.search(corpus.queries[:4], W, k=5)
    assert top_sealed not in np.asarray(r1.ids)[0]
    res = svc.search(_probe(corpus, grow_victim), W, k=5)
    assert grow_victim not in np.asarray(res.ids)[0]
    # tombstones are shape-preserving: nothing evicted
    assert keys_before <= set(svc.executable_cache)


def test_delete_then_compact_drops_tombstoned_ids(corpus, sealed):
    """Compaction physically reclaims tombstoned rows: the new sealed index
    contains every surviving id and none of the deleted ones, and the grow
    segment is cleared."""
    svc = _service(sealed)
    router = SegmentRouter(svc, BUILD_CFG, RouterConfig(seal_threshold=10**9))
    svc.insert(corpus.docs[N_SEALED:N_SEALED + 32])

    deleted = [5, 17, N_SEALED + 1, N_SEALED + 30]
    svc.mark_deleted(deleted)
    v = router.seal_and_compact()
    assert router.stats.compactions == 1
    assert svc.grow_index is None

    gids = np.asarray(svc.index.global_ids)
    live = set(gids[gids >= 0].tolist())
    expected = set(range(N_SEALED + 32)) - set(deleted)
    assert live == expected
    assert svc.snapshot_version == v

    # compacted docs stay reachable under their ORIGINAL global ids
    res = svc.search(_probe(corpus, N_SEALED + 12), W, k=5)
    assert int(np.asarray(res.ids)[0, 0]) == N_SEALED + 12
    # deleted ids never come back
    res = svc.search(_probe(corpus, N_SEALED + 1), W, k=5)
    assert N_SEALED + 1 not in np.asarray(res.ids)[0]

    # the routing table resolves survivors and rejects the reclaimed ids
    seg, loc = resolve_global_ids(svc.index, np.asarray([6, 5, N_SEALED + 1]))
    assert seg[0] == 0 and loc[0] >= 0
    assert seg[1] == -1 and seg[2] == -1


def test_auto_compact_on_seal_threshold(corpus, sealed):
    svc = _service(sealed)
    router = SegmentRouter(
        svc, BUILD_CFG, RouterConfig(seal_threshold=48, auto_compact=True)
    )
    svc.insert(corpus.docs[N_SEALED:N_SEALED + 32])
    assert router.stats.compactions == 0  # 32 < 48: still growing
    assert router.grow_size == 32
    svc.insert(corpus.docs[N_SEALED + 32:N_SEALED + 64])
    assert router.stats.compactions == 1  # 64 >= 48: sealed + compacted
    assert svc.grow_index is None
    gids = np.asarray(svc.index.global_ids)
    assert set(gids[gids >= 0].tolist()) == set(range(N_SEALED + 64))
    # post-compaction inserts start a fresh grow segment
    svc.insert(corpus.docs[N_SEALED + 64:N_SEALED + 80])
    assert router.grow_size == 16
    res = svc.search(_probe(corpus, N_SEALED + 70), W, k=5)
    assert int(np.asarray(res.ids)[0, 0]) == N_SEALED + 70


def test_kg_survives_insert_and_compaction():
    """A KG-bearing deployment keeps its entity paths end-to-end: entity
    queries work on sealed docs, on grow docs inserted WITH entities, and
    still work after delete + seal_and_compact (logical edges are rebuilt
    over the survivors). A triplet-less router over a KG index fails fast."""
    corpus = make_corpus(
        CorpusConfig(n_docs=224, n_queries=8, n_topics=8, d_dense=16,
                     nnz_sparse=8, nnz_lexical=6, seed=13)
    )
    n0 = 192
    sealed = build_segmented_index(
        corpus.docs[:n0], 1, BUILD_CFG,
        kg_triplets=corpus.kg.triplets,
        doc_entities=corpus.doc_entities[:n0],
        n_entities=corpus.kg.n_entities,
    )
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    sealed = place_segmented_index(sealed, mesh)
    params = SearchParams(k=8, iters=16, pool_size=64, use_kg=True)
    svc = HybridSearchService(
        sealed, params,
        ServiceConfig(batcher=BatcherConfig(flush_size=2, max_batch=2)),
        mesh=mesh,
    )
    # a triplet-less router over this index would drop the KG at compaction
    with pytest.raises(ValueError, match="kg_triplets"):
        SegmentRouter(svc, BUILD_CFG)
    SegmentRouter(
        svc, BUILD_CFG, RouterConfig(seal_threshold=10**9),
        kg_triplets=corpus.kg.triplets, n_entities=corpus.kg.n_entities,
    )
    w = PathWeights.make(0.2, 0.2, 0.2, kg=2.0)

    def entity_hits(doc):
        # make_corpus gives doc i the unique rare entity i: an entity query
        # must surface that doc through the logical path
        res = svc.search(
            corpus.queries[:1], w,
            entities=np.asarray([[doc]], np.int32), k=8,
        )
        return np.asarray(res.ids)[0]

    assert 100 in entity_hits(100)  # sealed doc via entity

    # entities REQUIRE a kg-configured router; wrong shapes are rejected
    with pytest.raises(ValueError):
        svc.insert(corpus.docs[n0:n0 + 32],
                   new_doc_entities=corpus.doc_entities[:3])
    svc.insert(corpus.docs[n0:n0 + 32],
               new_doc_entities=corpus.doc_entities[n0:n0 + 32])
    assert 200 in entity_hits(200)  # grow doc via entity (birth batch)

    svc.mark_deleted([200])
    svc._router.seal_and_compact()
    assert svc.grow_index is None
    assert 210 in entity_hits(210)  # grow doc's entity path survived compact
    assert 100 in entity_hits(100)  # sealed doc's entity path survived
    assert 200 not in entity_hits(200)  # deleted doc physically gone

    # an entity-LESS insert births the next grow segment with the sealed
    # entity width, so a later entity-carrying insert into it must work
    # (and those entities land in the logical edges at the next compaction)
    svc.insert(corpus.docs[192:200])  # fresh grow, no entities (ids 224..)
    svc.insert(corpus.docs[200:208],
               new_doc_entities=corpus.doc_entities[200:208])
    svc._router.seal_and_compact()
    # the second batch's docs got ids 232..239 and carry entities 200..207
    assert 236 in entity_hits(204)


def test_grow_pow2_bucketing_limits_retraces(corpus, sealed):
    """Shape-bucketed grow segment: publishing the grow segment padded to
    power-of-two capacity means the read path's grow pass (``scan_padded``,
    counted with ``search_padded``) retraces once per CAPACITY (O(log
    growth)) between compactions, not once per insert batch — and dead pad
    rows never surface in results."""
    svc = _service(sealed)
    router = SegmentRouter(svc, BUILD_CFG, RouterConfig(seal_threshold=10**9))
    svc.search(corpus.queries[:4], W, k=5)  # warm the sealed executable
    t0 = search_padded_trace_count()

    caps = []
    for b in range(6):
        lo = N_SEALED + 8 * b
        svc.insert(corpus.docs[lo:lo + 8])
        res = svc.search(corpus.queries[:4], W, k=5)  # grow read each insert
        assert (np.asarray(res.ids) < router.grow_size + N_SEALED).all()
        caps.append(router.grow_capacity)

    # raw sizes 8..48 bucket to capacities {8, 16, 32, 64}
    assert caps == [8, 16, 32, 32, 64, 64]
    assert router.grow_size == 48  # real rows, pads excluded
    # retrace accounting: 6 grow reads hit only 4 distinct capacities, and
    # inserts 2..6 each retrace once for their raw-shape probe search.
    # Unbucketed, the same sequence costs 6 + 5 = 11 traces.
    retraces = search_padded_trace_count() - t0
    assert retraces <= 4 + 5

    # every real doc is reachable, pad rows are not (ids stay < grow_size)
    res = svc.search(_probe(corpus, N_SEALED + 44), W, k=5)
    assert int(np.asarray(res.ids)[0, 0]) == N_SEALED + 44

    # a second read at an already-seen capacity adds NO trace
    t1 = search_padded_trace_count()
    svc.search(corpus.queries[4:8], W, k=5)
    assert search_padded_trace_count() == t1

    # tombstones apply to both the published and the raw grow segment, so a
    # later insert (which extends the raw one) cannot resurrect them
    victim = N_SEALED + 10
    svc.mark_deleted([victim])
    svc.insert(corpus.docs[N_SEALED + 48:N_SEALED + 56])
    res = svc.search(_probe(corpus, victim), W, k=5)
    assert victim not in np.asarray(res.ids)[0]


def test_insert_search_override_with_small_pool(corpus, sealed):
    """A caller-tuned insert probe with a pool SMALLER than the build k must
    not die at trace time: insert() drags the pool up with the forced k."""
    svc = _service(sealed)
    SegmentRouter(
        svc, BUILD_CFG,
        RouterConfig(seal_threshold=10**9,
                     insert_search=SearchParams(k=4, iters=8, pool_size=8)),
    )
    svc.insert(corpus.docs[N_SEALED:N_SEALED + 16])  # birth (no probe)
    svc.insert(corpus.docs[N_SEALED + 16:N_SEALED + 32])  # probe runs here
    res = svc.search(_probe(corpus, N_SEALED + 20), W, k=5)
    assert int(np.asarray(res.ids)[0, 0]) == N_SEALED + 20


def test_reattached_router_never_reissues_grow_gids(corpus, sealed):
    """A new router over a service with a LIVE grow segment must continue
    the id sequence past the grow ids, not restart at sealed max + 1."""
    svc = _service(sealed)
    SegmentRouter(svc, BUILD_CFG, RouterConfig(seal_threshold=10**9))
    svc.insert(corpus.docs[N_SEALED:N_SEALED + 32])  # gids 320..351
    router2 = SegmentRouter(  # re-attach (e.g. config change)
        svc, BUILD_CFG, RouterConfig(seal_threshold=10**9))
    assert router2._next_gid == N_SEALED + 32
    svc.insert(corpus.docs[N_SEALED + 32:N_SEALED + 48])
    gids = np.asarray(svc._snap.grow_gids)
    assert len(set(gids.tolist())) == len(gids)  # unique
    assert (np.diff(gids) > 0).all()  # still sorted (delete routing relies on it)


def test_start_pump_concurrent_and_idempotent(corpus, sealed):
    svc = _service(sealed)
    threads = [threading.Thread(target=svc.start_pump, args=(0.01,))
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    alive = [t for t in threading.enumerate()
             if t.name == "hybrid-service-pump" and t.is_alive()]
    assert len(alive) == 1  # exactly one pump, no orphans
    svc.start_pump(0.01)  # idempotent while running
    assert len([t for t in threading.enumerate()
                if t.name == "hybrid-service-pump" and t.is_alive()]) == 1
    svc.stop_pump()
    time.sleep(0.05)
    assert not any(t.name == "hybrid-service-pump" and t.is_alive()
                   for t in threading.enumerate())


def test_pump_thread_no_lost_results(corpus, sealed):
    """Worker threads submit WITHOUT ever flushing; the background pump
    thread alone must deliver every PendingResult (deadline flushes no
    longer depend on the submit path)."""
    svc = _service(
        sealed,
        batcher=dict(flush_size=4, max_batch=4, flush_deadline_s=0.001,
                     max_queue=4096),
        pump_interval_s=0.002,
    )
    SegmentRouter(svc, BUILD_CFG, RouterConfig(seal_threshold=10**9))
    try:
        n_per, n_workers = 8, 3
        results = [None] * (n_per * n_workers)

        def client(base):
            for i in range(n_per):
                results[base + i] = svc.submit(SearchRequest(
                    query=corpus.queries[(base + i) % 16],
                    weights=W, k=3))

        workers = [threading.Thread(target=client, args=(b * n_per,))
                   for b in range(n_workers)]
        for t in workers:
            t.start()
        for t in workers:
            t.join()
        # wait on done flags only — result() would force a flush and mask a
        # dead pump; the pump must deliver on its own
        deadline = time.monotonic() + 60.0
        while (not all(p.done for p in results)
               and time.monotonic() < deadline):
            time.sleep(0.005)
        assert all(p.done for p in results), "pump thread lost results"
        assert svc.stats.requests == n_per * n_workers
        for p in results:
            assert p.result()[0].shape == (3,)
    finally:
        svc.stop_pump()
    assert svc._pump_thread is None


def test_pump_delivers_during_streaming_inserts(corpus, sealed):
    """Submissions racing a concurrent insert (snapshot publish) all
    deliver; results reference a consistent snapshot either side of the
    swap."""
    svc = _service(
        sealed,
        batcher=dict(flush_size=4, max_batch=4, flush_deadline_s=0.001,
                     max_queue=4096),
        pump_interval_s=0.002,
    )
    SegmentRouter(svc, BUILD_CFG, RouterConfig(seal_threshold=10**9))
    try:
        svc.insert(corpus.docs[N_SEALED:N_SEALED + 32])  # grow exists
        pendings = []
        done = threading.Event()

        def client():
            for i in range(12):
                pendings.append(svc.submit(SearchRequest(
                    query=corpus.queries[i % 16], weights=W, k=3)))
                time.sleep(0.002)
            done.set()

        t = threading.Thread(target=client)
        t.start()
        svc.insert(corpus.docs[N_SEALED + 32:N_SEALED + 48])  # racing insert
        t.join()
        assert done.wait(1.0)
        deadline = time.monotonic() + 60.0
        while (not all(p.done for p in pendings)
               and time.monotonic() < deadline):
            time.sleep(0.005)
        assert all(p.done for p in pendings)
        for p in pendings:
            ids, _ = p.result()
            assert ids.shape == (3,)
    finally:
        svc.stop_pump()
