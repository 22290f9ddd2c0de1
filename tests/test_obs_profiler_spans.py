"""Batch phases on the profiler's clock: a pool batch with two shape groups
and a grow segment, flushed under ``jax.profiler``, leaves one
``serving.<phase>`` host event per phase in the trace, in dispatch order;
the phase histogram agrees with those events, keeps counting with no
profiler running, and the traversal's work lands in the nodes-expanded
counter."""

from __future__ import annotations

import numpy as np
import pytest

import jax

from bench import trace_reduce
from repro.core import BuildConfig, KnnConfig, PruneConfig
from repro.core.fusion import FusionSpec
from repro.core.search import SearchParams
from repro.core.segment_pool import SegmentPool, append_segment, build_pool_segment
from repro.data.corpus import CorpusConfig, make_corpus
from repro.serving.batcher import BatcherConfig, SearchRequest
from repro.serving.hybrid_service import HybridSearchService, ServiceConfig
from repro.serving.segment_router import RouterConfig, SegmentRouter

BUILD_CFG = BuildConfig(
    knn=KnnConfig(k=8, iters=2, node_chunk=128),
    prune=PruneConfig(degree=8, keyword_degree=3, node_chunk=64),
    path_refine_iters=0,
)
PARAMS = SearchParams(k=6, iters=12, pool_size=32)
PREFIX = "serving."
# every phase of a batch over a two-group pool with a grow segment, in the
# order of their start on one thread: the lookup holds the (first) compile
ORDER = ["batch_assembly", "executable_lookup", "compile", "device_dispatch",
         "device_readback", "fusion_rescore", "grow_merge", "deliver"]
N_REQ = 3


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(
        CorpusConfig(n_docs=96, n_queries=8, n_topics=8, d_dense=16,
                     nnz_sparse=8, nnz_lexical=6, seed=31)
    )


@pytest.fixture(scope="module")
def service(corpus):
    # two sealed segments of different capacity: two shape groups, so the
    # batch runs two executables and merges them on the host
    pool = SegmentPool(groups=[])
    for lo, hi, cap in ((0, 64, 64), (64, 80, 16)):
        seg = build_pool_segment(
            corpus.docs[lo:hi], np.arange(lo, hi), BUILD_CFG, capacity=cap,
            key=jax.random.key(lo),
        )
        pool, _ = append_segment(pool, seg)
    assert pool.n_groups == 2
    svc = HybridSearchService(
        pool, PARAMS,
        ServiceConfig(batcher=BatcherConfig(
            flush_size=4, max_batch=4, flush_deadline_s=60.0)),
    )
    router = SegmentRouter(
        svc, BUILD_CFG,
        RouterConfig(seal_threshold=10**9, compaction="incremental",
                     auto_merge=False),
    )
    router.insert(corpus.docs[80:96])  # a grow segment: the grow_merge phase
    assert svc.grow_index is not None
    return svc


def _batch(svc, corpus):
    pend = [
        svc.submit(SearchRequest(query=corpus.queries[i],
                                 fusion=FusionSpec.three_path(), k=PARAMS.k))
        for i in range(N_REQ)
    ]
    assert svc.flush() == 1
    return pend


def _phase_stats(svc) -> dict:
    h = svc.metrics.get("allanpoe_serving_batch_phase_seconds")
    return {name: h.snapshot(phase=name) for name in ORDER}


@pytest.fixture(scope="module")
def traced(service, corpus, tmp_path_factory):
    """One batch, flushed with the profiler on: (host span events, the
    phase histogram's snapshots, the answers)."""
    trace_dir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        pend = _batch(service, corpus)
    finally:
        jax.profiler.stop_trace()
    planes = trace_reduce.load(str(trace_dir))
    spans = sorted(
        (e for p in trace_reduce.host_planes(planes) for ln in p.lines
         for e in ln.events if e.name.startswith(PREFIX)),
        key=lambda e: e.start_ns,
    )
    return spans, _phase_stats(service), pend


def test_every_phase_is_a_host_span_in_dispatch_order(traced):
    spans, _, _ = traced
    names = [e.name[len(PREFIX):] for e in spans]
    # one compile per shape group; every other phase once
    assert sorted(names) == sorted(ORDER + ["compile"])
    firsts = sorted(set(names), key=names.index)
    assert firsts == ORDER
    by = {}
    for e in spans:
        by.setdefault(e.name[len(PREFIX):], []).append(e)
    (dispatch,), (readback,) = by["device_dispatch"], by["device_readback"]
    assert dispatch.end_ns <= readback.start_ns
    (lookup,) = by["executable_lookup"]
    for c in by["compile"]:  # leaf spans inside the lookup that missed
        assert lookup.start_ns <= c.start_ns and c.end_ns <= lookup.end_ns


def test_phase_histogram_agrees_with_the_spans(traced):
    spans, stats, _ = traced
    for name in ORDER:
        events = [e for e in spans if e.name == PREFIX + name]
        assert stats[name].count == len(events), name
        span_s = sum(e.dur_ns for e in events) / 1e9
        assert stats[name].sum == pytest.approx(span_s, abs=1e-3), name


def test_nodes_expanded_counter_sums_the_real_rows(service, traced):
    _, _, pend = traced
    counter = service.metrics.get("allanpoe_serving_nodes_expanded_total")
    assert counter.total() == sum(p.expanded for p in pend) > 0


def test_histogram_counts_with_no_profiler_running(service, corpus, traced):
    before = _phase_stats(service)
    counter = service.metrics.get("allanpoe_serving_nodes_expanded_total")
    n0 = counter.total()
    pend = _batch(service, corpus)  # warm: no compile
    after = _phase_stats(service)
    for name in ORDER:
        assert after[name].count - before[name].count == (name != "compile"), name
    assert counter.total() - n0 == sum(p.expanded for p in pend)
