"""Observability layer: metrics registry semantics (atomic counters under
concurrency, streaming-histogram quantiles, Prometheus/JSON exposition),
span trees through the serving stack, queue-wait attribution under
saturation, and the degraded replica-tier read audit trail. The batch
phases' profiler spans are tested in ``test_obs_profiler_spans.py``."""

from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.core import BuildConfig, KnnConfig, PruneConfig, build_index
from repro.core.fusion import as_fusion_spec
from repro.core.search import SearchParams
from repro.core.segment_pool import SegmentPool, build_pool_segment, place_pool
from repro.core.usms import PathWeights
from repro.data.corpus import CorpusConfig, make_corpus
from repro.obs.metrics import (
    GLOBAL,
    MetricsRegistry,
    merged_snapshot,
    time_buckets,
)
from repro.obs.tracer import TraceContext, Tracer
from repro.runtime import dispatch
from repro.serving.batcher import BatcherConfig, SearchRequest, _next_pow2
from repro.serving.hybrid_service import (
    HybridSearchService,
    ServiceConfig,
    ServiceStats,
)
from repro.serving.replica_router import (
    Replica,
    ReplicaRouter,
    ReplicaTierConfig,
    build_ring,
    ring_homes,
)
from repro.serving.segment_router import RouterConfig, SegmentRouter

BUILD_CFG = BuildConfig(
    knn=KnnConfig(k=8, iters=2, node_chunk=128),
    prune=PruneConfig(degree=8, keyword_degree=3, node_chunk=64),
    path_refine_iters=0,
)
PARAMS = SearchParams(k=6, iters=12, pool_size=32)
W = PathWeights.make(1.0, 1.0, 1.0)
SPEC = as_fusion_spec(W, warn=False)


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(
        CorpusConfig(n_docs=96, n_queries=8, n_topics=8, d_dense=16,
                     nnz_sparse=8, nnz_lexical=6, seed=29)
    )


@pytest.fixture(scope="module")
def index(corpus):
    return build_index(corpus.docs, BUILD_CFG)


def _service(index, **batcher_kw):
    kw = dict(flush_size=4, max_batch=4, flush_deadline_s=60.0)
    kw.update(batcher_kw)
    return HybridSearchService(
        index, PARAMS, ServiceConfig(batcher=BatcherConfig(**kw))
    )


# -- metrics registry ---------------------------------------------------------


def test_counter_labels_and_totals():
    reg = MetricsRegistry()
    c = reg.counter("x_total", "things", labels=("kind",))
    c.inc(kind="a")
    c.inc(2, kind="b")
    assert c.value(kind="a") == 1
    assert c.value(kind="b") == 2
    assert c.total() == 3
    with pytest.raises(ValueError):
        c.inc(-1, kind="a")
    with pytest.raises(ValueError):
        c.inc(bogus="a")  # undeclared label name


def test_registry_rejects_kind_and_label_mismatch():
    reg = MetricsRegistry()
    reg.counter("m", "", labels=("x",))
    assert reg.counter("m", "", labels=("x",)) is reg.get("m")  # idempotent
    with pytest.raises(ValueError):
        reg.gauge("m")
    with pytest.raises(ValueError):
        reg.counter("m", "", labels=("y",))


def test_counter_increments_are_atomic_across_8_threads():
    # the ServiceStats regression: rejected counters used to be bare ints
    # bumped from submitter threads without a lock
    reg = MetricsRegistry()
    c = reg.counter("hammer_total", "", labels=("reason",))
    n_threads, n_incs = 8, 5000

    def hammer():
        for _ in range(n_incs):
            c.inc(reason="queue_full")

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert int(c.value(reason="queue_full")) == n_threads * n_incs


def test_service_stats_facade_concurrent_rejects():
    stats = ServiceStats(MetricsRegistry())
    n_threads, n_incs = 8, 2000

    def hammer(reason):
        for _ in range(n_incs):
            stats._rejected.inc(reason=reason)

    threads = [
        threading.Thread(
            target=hammer,
            args=("queue_full" if i % 2 else "admission",),
        )
        for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert stats.rejected_queue_full == 4 * n_incs
    assert stats.rejected_admission == 4 * n_incs
    assert stats.rejected == n_threads * n_incs


def test_histogram_quantiles_close_to_numpy():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "")
    rng = np.random.default_rng(3)
    samples = rng.lognormal(mean=-4.0, sigma=1.0, size=4000)
    for s in samples:
        h.observe(float(s))
    snap = h.snapshot()
    assert snap.count == len(samples)
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(samples, q))
        est = snap.quantile(q)
        # geometric buckets at ratio 1.25: interpolation error stays within
        # one bucket width
        assert abs(est - exact) / exact < 0.15, (q, est, exact)


def test_histogram_snapshot_delta_isolates_a_window():
    reg = MetricsRegistry()
    h = reg.histogram("w_seconds", "")
    for _ in range(10):
        h.observe(1e-3)
    before = h.snapshot()
    for _ in range(5):
        h.observe(1.0)
    delta = h.snapshot().minus(before)
    assert delta.count == 5
    assert delta.quantile(0.5) > 0.5  # only the big observations remain


def test_time_buckets_monotone():
    b = time_buckets(1e-4, 60.0, ratio=1.25)
    assert all(x < y for x, y in zip(b, b[1:]))
    assert b[0] <= 1e-4 * 1.25 and b[-1] >= 60.0 / 1.25


def test_prometheus_render_and_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("allanpoe_test_requests_total", "reqs", labels=("mode",))
    g = reg.gauge("allanpoe_test_depth", "queue depth")
    h = reg.histogram("allanpoe_test_wait_seconds", "queue wait")
    c.inc(3, mode="rrf")
    g.set(7)
    h.observe(0.01)
    text = reg.render()
    assert "# TYPE allanpoe_test_requests_total counter" in text
    assert 'allanpoe_test_requests_total{mode="rrf"} 3' in text
    assert "allanpoe_test_depth 7" in text
    assert 'allanpoe_test_wait_seconds_bucket{le="+Inf"} 1' in text
    assert "allanpoe_test_wait_seconds_count 1" in text
    snap = reg.snapshot()
    assert snap["allanpoe_test_requests_total"]["series"][0]["value"] == 3
    hist = snap["allanpoe_test_wait_seconds"]["series"][0]
    assert hist["count"] == 1 and "p99" in hist
    json.dumps(snap)  # artifact must be JSON-able
    merged = merged_snapshot(reg, MetricsRegistry())
    assert "allanpoe_test_depth" in merged


def test_dispatch_counters_live_in_global_registry():
    before = GLOBAL.value("allanpoe_runtime_dispatches_total")
    with dispatch.track() as t:
        dispatch.tick(4)
    assert t.count == 4
    assert GLOBAL.value("allanpoe_runtime_dispatches_total") - before == 4


# -- tracer -------------------------------------------------------------------


def test_trace_context_tree_and_chrome_export():
    tracer = Tracer()
    with tracer.trace("query", tenant="t0") as ctx:
        with ctx.span("phase_a") as a:
            a.annotate(rows=3)
        t0 = time.perf_counter()
        ctx.add_span("phase_b", t0, t0 + 0.01, hit=True)
    assert ctx.root.t1 is not None
    names = ctx.span_names()
    assert names[0] == "query" and "phase_a" in names and "phase_b" in names
    for s in ctx.spans():
        assert s.t1 is not None and s.t1 >= s.t0
    # the finished tree is what an operator reads: kept by the tracer, its
    # children under the root with their attributes and durations
    assert tracer.finished == [ctx]
    assert ctx.root.attrs == {"tenant": "t0"}
    assert [c.name for c in ctx.root.children] == ["phase_a", "phase_b"]
    (a,), (b,) = ctx.find("phase_a"), ctx.find("phase_b")
    assert a.attrs == {"rows": 3} and b.attrs == {"hit": True}
    assert b.duration == pytest.approx(0.01)
    assert ctx.root.t0 <= a.t0 <= a.t1 <= ctx.root.t1


def test_add_span_clamps_negative_duration():
    ctx = TraceContext("q")
    s = ctx.add_span("x", 5.0, 4.0)
    assert s.t1 == s.t0 == 5.0


# -- serving integration ------------------------------------------------------


def test_service_query_span_tree_and_metrics(corpus, index):
    svc = _service(index)
    tracer = svc.tracer
    with tracer.trace("request") as ctx:
        for i in range(4):
            svc.submit(SearchRequest(
                query=corpus.queries[i], fusion=SPEC,
                k=PARAMS.k, trace=ctx,
            ))
        svc.flush()
    names = set(ctx.span_names())
    assert {"admission", "queue_wait", "batch_assembly", "executable_lookup",
            "compile", "device_dispatch", "device_readback",
            "deliver"} <= names
    for s in ctx.spans():
        assert s.t1 is not None and s.t1 >= s.t0 >= 0
    # the enqueue and the wait for the results are separate phases (each of
    # the 4 requests carries the batch's phases)
    dispatch, readback = ctx.find("device_dispatch"), ctx.find("device_readback")
    assert len(dispatch) == len(readback) == 4
    assert dispatch[0].t1 <= readback[0].t0
    lookups = ctx.find("executable_lookup")
    # first batch compiles: the lookup span records the miss
    assert lookups and lookups[0].attrs.get("hit") is False
    assert svc.stats.requests == 4 and svc.stats.batches == 1
    assert svc.metrics.value("allanpoe_serving_requests_total",
                             mode="weighted_sum") == 4
    assert svc.metrics.value("allanpoe_serving_executable_cache_total",
                             outcome="miss") == 1
    lat = svc.metrics.get("allanpoe_serving_request_latency_seconds")
    assert lat.snapshot().count == 4
    # warm second batch: cache hit recorded on both the span and the counter
    with tracer.trace("request2") as ctx2:
        for i in range(4):
            svc.submit(SearchRequest(
                query=corpus.queries[i], fusion=SPEC,
                k=PARAMS.k, trace=ctx2,
            ))
        svc.flush()
    assert ctx2.find("executable_lookup")[0].attrs.get("hit") is True
    assert svc.metrics.value("allanpoe_serving_executable_cache_total",
                             outcome="hit") == 1


def test_queue_wait_dominates_under_saturation(corpus, index):
    # saturate: requests sit queued (no size trigger) while the client
    # sleeps, then one flush runs the batch — queue wait must dominate the
    # measured end-to-end latency, and the histograms must attribute it
    svc = _service(index, flush_size=16, max_batch=16)
    # warm the measured bucket shape so compile time doesn't blur the
    # attribution
    for i in range(8):
        svc.submit(SearchRequest(query=corpus.queries[i],
                                 fusion=SPEC, k=PARAMS.k))
    svc.flush()
    wait_h = svc.metrics.get("allanpoe_serving_queue_wait_seconds")
    lat_h = svc.metrics.get("allanpoe_serving_request_latency_seconds")
    wait0, lat0 = wait_h.snapshot(), lat_h.snapshot()
    for i in range(8):
        svc.submit(SearchRequest(query=corpus.queries[i],
                                 fusion=SPEC, k=PARAMS.k))
    time.sleep(0.25)
    svc.flush()
    wait = wait_h.snapshot().minus(wait0)
    lat = lat_h.snapshot().minus(lat0)
    assert wait.count == 8 and lat.count == 8
    assert lat.mean >= 0.25
    assert wait.mean / lat.mean > 0.8, (wait.mean, lat.mean)


# -- replica tier -------------------------------------------------------------


def _make_tier(corpus, n_replicas=2):
    names = [f"replica{i}" for i in range(n_replicas)]
    homes = ring_homes(build_ring(names, 16), np.arange(corpus.docs.n))
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    reps = []
    for i, name in enumerate(names):
        rows = np.flatnonzero(homes == i)
        seg = build_pool_segment(
            jax.tree.map(lambda a: a[rows], corpus.docs),
            rows, BUILD_CFG,
            capacity=_next_pow2(int(rows.size)),
            key=jax.random.key(11 + i),
        )
        pool = place_pool(SegmentPool.from_segmented(seg), mesh)
        svc = HybridSearchService(
            pool, PARAMS,
            ServiceConfig(batcher=BatcherConfig(
                flush_size=4, max_batch=4, flush_deadline_s=60.0)),
            mesh=mesh,
        )
        router = SegmentRouter(
            svc, BUILD_CFG,
            RouterConfig(seal_threshold=10**9, compaction="incremental",
                         auto_merge=False),
        )
        reps.append(Replica(svc, router, name=name))
    return ReplicaRouter(reps, ReplicaTierConfig(virtual_nodes=16))


def test_degraded_tier_read_audit_trail(corpus):
    # 2 replicas, 1 down — the query must yield a full span tree, the down
    # replica in the result AND as a labeled counter
    tier = _make_tier(corpus, 2)
    try:
        queries = jax.tree.map(lambda a: a[:4], corpus.queries)
        healthy = tier.search(queries, W, k=PARAMS.k)
        assert healthy.down_replicas is None
        assert tier.stats.dispatched == [1, 1]

        tier.mark_down(1)
        with tier.tracer.trace("degraded_read") as ctx:
            res = tier.search(queries, W, k=PARAMS.k, trace=ctx)
        assert res.down_replicas == ("replica1",)
        assert ctx.root.attrs.get("down_replicas") == ["replica1"]
        assert tier.stats.partial_searches == 1
        assert tier.stats.degraded_reads("replica1") == 1
        assert tier.stats.degraded_reads("replica0") == 0
        assert tier.metrics.value(
            "allanpoe_replica_degraded_reads_total", replica="replica1"
        ) == 1

        names = set(ctx.span_names())
        assert {"admission", "queue_wait", "batch_assembly",
                "executable_lookup", "device_dispatch", "device_readback",
                "deliver", "replica_dispatch", "scatter_gather",
                "fusion_rescore"} <= names
        dispatches = ctx.find("replica_dispatch")
        assert [s.attrs["replica"] for s in dispatches] == ["replica0"]
        for s in ctx.spans():
            assert s.t1 is not None and s.t1 >= s.t0 >= 0

        # the audit trail lives in the tree: every span hangs off the root
        # inside its interval, and the gather names the replica it lost
        root = ctx.root
        assert all(s.t0 >= root.t0 and s.t1 <= root.t1 for s in ctx.spans())
        (gather,) = ctx.find("scatter_gather")
        assert gather.attrs == {"replicas": 1, "down": ["replica1"]}
        (merge,) = ctx.find("fusion_rescore")
        assert merge.attrs == {"parts": 1, "site": "replica_merge"}

        # recovery: marked back up, reads are whole again
        tier.mark_up(1)
        whole = tier.search(queries, W, k=PARAMS.k)
        assert whole.down_replicas is None
        assert tier.stats.partial_searches == 1
    finally:
        tier.close()
