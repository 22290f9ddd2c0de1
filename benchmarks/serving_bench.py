"""Serving-path benchmark: sustained QPS and p50/p99 request latency of the
micro-batched ``HybridSearchService`` across bucket sizes and fusion
mixes — the online counterpart of fig8's offline batched-search numbers.

Per configuration, a closed-loop client replays a request stream (every
request a random one of several ``FusionSpec`` combinations — different
weights AND different fusion modes, so every batch is fusion-heterogeneous
and still hits ONE cached executable) and measures per-request
submit->result latency and wall-clock QPS after a warmup flush that absorbs
compilation.

``--streaming`` adds the grow-segment router bench: insert QPS and search
latency (p50/p99) measured WHILE a writer thread streams insert batches
through ``SegmentRouter`` — the mixed read/write serving scenario. Results
land in ``results/BENCH_serving.json`` (the ``--dry-run`` CI path emits the
same file, so the perf trajectory is tracked per commit as a workflow
artifact).

    PYTHONPATH=src python benchmarks/serving_bench.py [--quick] [--dry-run]
                                                      [--streaming]
"""

from __future__ import annotations

import json
import pathlib
import sys
import threading
import time

if __package__ in (None, ""):  # script mode: python benchmarks/serving_bench.py
    _root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]

import numpy as np

import jax

from repro.core import BuildConfig, FusionSpec, KnnConfig, PruneConfig, build_index
from repro.core.search import SearchParams
from repro.data.corpus import CorpusConfig, make_corpus
from repro.obs.export import write_metrics_snapshot
from repro.obs.metrics import GLOBAL
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving.batcher import BatcherConfig, SearchRequest
from repro.serving.hybrid_service import HybridSearchService, ServiceConfig

FUSION_MIXES = [
    ("dense", FusionSpec.weighted(1.0, 0.0, 0.0)),
    ("sparse+full", FusionSpec.weighted(0.0, 1.0, 1.0)),
    ("three-path", FusionSpec.three_path()),
    ("skewed", FusionSpec.weighted(0.6, 0.3, 0.1)),
    ("rrf", FusionSpec.rrf()),
    ("zscore", FusionSpec.zscore()),
]


def _drive(service, queries, n_requests, rng, k):
    """Closed-loop client: submit the stream; returns (wall_s, latency
    HistogramSnapshot). Latency percentiles come from the service's OWN
    ``allanpoe_serving_request_latency_seconds`` histogram (arrival ->
    result fulfillment) — the bench consumes the production metrics code
    path instead of keeping a second stopwatch (DESIGN.md §12), and the
    snapshot delta across the drive isolates this drive's requests from
    any earlier warmup traffic."""
    b = queries.dense.shape[0]
    hist = service.metrics.get("allanpoe_serving_request_latency_seconds")
    before = hist.snapshot()
    t0 = time.perf_counter()
    for i in range(n_requests):
        service.submit(
            SearchRequest(
                query=queries[int(rng.integers(b))],
                fusion=FUSION_MIXES[int(rng.integers(len(FUSION_MIXES)))][1],
                k=k,
            )
        )
    service.flush()
    wall = time.perf_counter() - t0
    return wall, hist.snapshot().minus(before)


def _p_ms(snap, q: float) -> float:
    """Interpolated histogram quantile, in milliseconds."""
    return float(snap.quantile(q)) * 1e3


def _update_bench_json(section: str, payload: dict, out_dir: str = "results") -> None:
    """Merge one section into results/BENCH_serving.json (steady-state and
    streaming runs each own a section, so either can run alone)."""
    out = pathlib.Path(out_dir)
    out.mkdir(exist_ok=True)
    path = out / "BENCH_serving.json"
    data: dict = {}
    if path.exists():
        try:
            data = json.loads(path.read_text())
        except ValueError:
            data = {}
    data[section] = payload
    path.write_text(json.dumps(data, indent=2) + "\n")


def run(n_docs: int = 4096, n_requests: int = 256, dry_run: bool = False):
    rows = []
    if dry_run:
        n_docs, n_requests = 512, 32
    traces0 = GLOBAL.value("allanpoe_core_search_padded_traces_total")
    services = []  # every service of this section, for the obs roll-up
    rng = np.random.default_rng(7)
    corpus = make_corpus(
        CorpusConfig(
            n_docs=n_docs, n_queries=64, n_topics=max(n_docs // 64, 8),
            d_dense=64, nnz_sparse=16, nnz_lexical=8, seed=7,
        )
    )
    index = build_index(
        corpus.docs,
        BuildConfig(
            knn=KnnConfig(k=16, iters=3, node_chunk=min(n_docs, 2048)),
            prune=PruneConfig(degree=16, keyword_degree=4, node_chunk=512),
            path_refine_iters=0,
        ),
    )
    params = SearchParams(k=10, iters=32, pool_size=64)

    steady = {
        "config": {
            "n_docs": n_docs,
            "n_requests": n_requests,
            "backend": jax.default_backend(),
        },
        "buckets": {},
    }
    for bucket in (8, 32):
        service = HybridSearchService(
            index,
            params,
            ServiceConfig(
                batcher=BatcherConfig(
                    flush_size=bucket, max_batch=bucket, flush_deadline_s=0.05
                )
            ),
        )
        services.append(service)
        # warmup: one full bucket through every shape so compile time is
        # excluded from the steady-state measurement
        _drive(service, corpus.queries, bucket, np.random.default_rng(0), params.k)
        wall, lat = _drive(service, corpus.queries, n_requests, rng, params.k)
        qps = n_requests / wall
        p50, p99 = _p_ms(lat, 0.5), _p_ms(lat, 0.99)
        steady["buckets"][str(bucket)] = {
            "qps": qps,
            "p50_ms": p50,
            "p99_ms": p99,
        }
        rows.append(
            (
                f"serving.qps_bucket{bucket}",
                wall * 1e6 / n_requests,
                f"qps={qps:.0f};p50_ms={p50:.1f};p99_ms={p99:.1f};"
                f"executables={len(service.executable_cache)};"
                f"fusion_mixes={len(FUSION_MIXES)}",
            )
        )
    _update_bench_json("steady", steady)

    # per-mix latency at the larger bucket: one homogeneous stream per fusion
    # combination, all through the SAME service (and executable)
    service = HybridSearchService(
        index,
        params,
        ServiceConfig(batcher=BatcherConfig(flush_size=32, max_batch=32)),
    )
    services.append(service)
    _drive(service, corpus.queries, 32, np.random.default_rng(0), params.k)
    for name, spec in FUSION_MIXES:
        pend = []
        t0 = time.perf_counter()
        for i in range(32):
            pend.append(
                service.submit(
                    SearchRequest(query=corpus.queries[i % 64], fusion=spec, k=params.k)
                )
            )
        service.flush()
        dt = time.perf_counter() - t0
        rows.append(
            (
                f"serving.path_{name}",
                dt * 1e6 / 32,
                f"qps={32 / dt:.0f};executables={len(service.executable_cache)}",
            )
        )
    # obs roll-up (the check_regression "obs" gate input): AOT executable
    # cache behaviour and search_padded retraces across this section, read
    # from the same registries the serving exposition renders
    hits = sum(
        int(s.metrics.value(
            "allanpoe_serving_executable_cache_total", outcome="hit"
        ))
        for s in services
    )
    misses = sum(
        int(s.metrics.value(
            "allanpoe_serving_executable_cache_total", outcome="miss"
        ))
        for s in services
    )
    obs = {
        "executable_cache": {
            "hits": hits,
            "misses": misses,
            "hit_rate": hits / max(hits + misses, 1),
        },
        "search_padded_traces": int(
            GLOBAL.value("allanpoe_core_search_padded_traces_total") - traces0
        ),
    }
    _update_bench_json("obs", obs)
    write_metrics_snapshot(
        "results/METRICS_snapshot.json",
        *[s.metrics for s in services],
        GLOBAL,
    )
    return rows


def run_streaming(
    n_docs: int = 1024,
    insert_batches: int = 8,
    insert_batch: int = 16,
    n_requests: int = 128,
    dry_run: bool = False,
):
    """Mixed read/write serving: a writer thread streams insert batches
    through the grow-segment router while the closed-loop client measures
    search latency. Reports insert docs/s, search QPS + p50/p99, and
    whether the sealed executables survived every insert (the cache-key
    invariant of the grow-segment scheme)."""
    from jax.sharding import Mesh

    from repro.core.distributed import (
        build_segmented_index,
        place_segmented_index,
    )
    from repro.serving.segment_router import RouterConfig, SegmentRouter

    if dry_run:
        n_docs, insert_batches, insert_batch, n_requests = 256, 3, 8, 24
    total = n_docs + insert_batches * insert_batch
    corpus = make_corpus(
        CorpusConfig(
            n_docs=total, n_queries=64, n_topics=max(n_docs // 64, 8),
            d_dense=64, nnz_sparse=16, nnz_lexical=8, seed=11,
        )
    )
    cfg = BuildConfig(
        knn=KnnConfig(k=16, iters=3, node_chunk=min(n_docs, 2048)),
        prune=PruneConfig(degree=16, keyword_degree=4, node_chunk=512),
        path_refine_iters=0,
    )
    seg = build_segmented_index(corpus.docs[:n_docs], 1, cfg)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    seg = place_segmented_index(seg, mesh)
    params = SearchParams(k=10, iters=32, pool_size=64)
    service = HybridSearchService(
        seg,
        params,
        ServiceConfig(
            batcher=BatcherConfig(
                flush_size=8, max_batch=8, flush_deadline_s=0.01
            ),
            pump_interval_s=0.005,
        ),
        mesh=mesh,
    )
    SegmentRouter(service, cfg, RouterConfig(seal_threshold=10**9))

    # warmup: first insert (grow-segment birth) + one bucket of searches, so
    # the steady measurement sees warm sealed executables
    service.insert(corpus.docs[n_docs:n_docs + insert_batch])
    _drive(service, corpus.queries, 8, np.random.default_rng(0), params.k)
    sealed_keys = set(service.executable_cache)

    insert_s: list[float] = []

    def writer():
        for b in range(1, insert_batches):
            lo = n_docs + b * insert_batch
            t0 = time.perf_counter()
            service.insert(corpus.docs[lo:lo + insert_batch])
            insert_s.append(time.perf_counter() - t0)

    thread = threading.Thread(target=writer)
    thread.start()
    wall, lat = _drive(
        service, corpus.queries, n_requests, np.random.default_rng(3), params.k
    )
    thread.join()
    service.stop_pump()

    sealed_stable = sealed_keys <= set(service.executable_cache)
    docs_inserted = (insert_batches - 1) * insert_batch
    insert_docs_per_s = docs_inserted / max(sum(insert_s), 1e-9)
    qps = n_requests / wall
    p50, p99 = _p_ms(lat, 0.5), _p_ms(lat, 0.99)
    _update_bench_json(
        "streaming",
        {
            "config": {
                "n_docs": n_docs,
                "insert_batches": insert_batches,
                "insert_batch": insert_batch,
                "n_requests": n_requests,
                "backend": jax.default_backend(),
            },
            "insert_docs_per_s": insert_docs_per_s,
            "search_qps": qps,
            "p50_ms": p50,
            "p99_ms": p99,
            "sealed_cache_stable": bool(sealed_stable),
            "grow_docs_final": int(service._snap.grow_gids.shape[0])
            if service._snap.grow_gids is not None
            else 0,
        },
    )
    return [
        (
            "serving.streaming",
            wall * 1e6 / n_requests,
            f"qps={qps:.0f};p50_ms={p50:.1f};p99_ms={p99:.1f};"
            f"insert_docs_per_s={insert_docs_per_s:.0f};"
            f"sealed_cache_stable={sealed_stable}",
        )
    ]


def run_compaction(
    n_docs: int = 1024,
    grow_docs: int = 48,
    n_requests: int = 96,
    dry_run: bool = False,
):
    """Compaction-concurrency bench: search p99 measured WHILE a compaction
    runs, full-rebuild vs incremental. Per mode it reports the search QPS +
    p50/p99 of a closed-loop client racing the compaction, the compaction's
    wall-clock, its ``dispatch.build_rows`` work (the O(corpus) vs O(grow)
    contrast), and whether the warm sealed executables survived — for the
    incremental path they must (the segment-pool cache-survival guarantee,
    DESIGN.md §8)."""
    from jax.sharding import Mesh

    from repro.core.distributed import (
        build_segmented_index,
        place_segmented_index,
    )
    from repro.runtime import dispatch
    from repro.serving.segment_router import RouterConfig, SegmentRouter

    if dry_run:
        n_docs, grow_docs, n_requests = 256, 16, 24
    corpus = make_corpus(
        CorpusConfig(
            n_docs=n_docs + 2 * grow_docs, n_queries=64,
            n_topics=max(n_docs // 64, 8),
            d_dense=64, nnz_sparse=16, nnz_lexical=8, seed=17,
        )
    )
    cfg = BuildConfig(
        knn=KnnConfig(k=16, iters=3, node_chunk=min(n_docs, 2048)),
        prune=PruneConfig(degree=16, keyword_degree=4, node_chunk=512),
        path_refine_iters=0,
    )
    params = SearchParams(k=10, iters=32, pool_size=64)
    rows = []
    payload = {
        "config": {
            "n_docs": n_docs,
            "grow_docs": grow_docs,
            "n_requests": n_requests,
            "backend": jax.default_backend(),
        },
    }
    for mode_i, mode in enumerate(("full", "incremental")):
        seg = build_segmented_index(corpus.docs[:n_docs], 1, cfg)
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
        seg = place_segmented_index(seg, mesh)
        service = HybridSearchService(
            seg, params,
            ServiceConfig(
                batcher=BatcherConfig(
                    flush_size=8, max_batch=8, flush_deadline_s=0.01
                ),
                pump_interval_s=0.005,
            ),
            mesh=mesh,
        )
        router = SegmentRouter(
            service, cfg,
            RouterConfig(seal_threshold=10**9, compaction=mode),
        )
        lo = n_docs + mode_i * grow_docs
        service.insert(corpus.docs[lo:lo + grow_docs])
        # warm: sealed + grow executables compiled before the measurement
        _drive(service, corpus.queries, 8, np.random.default_rng(0), params.k)
        sealed_keys = {
            k: v for k, v in service.executable_cache.items()
        }

        rows_before = dispatch.build_rows()
        compact_s = [0.0]

        def compactor():
            t0 = time.perf_counter()
            router.compact()
            compact_s[0] = time.perf_counter() - t0

        thread = threading.Thread(target=compactor)
        thread.start()
        wall, lat = _drive(
            service, corpus.queries, n_requests, np.random.default_rng(5),
            params.k,
        )
        thread.join()
        service.stop_pump()
        built = dispatch.build_rows() - rows_before
        stable = all(
            service.executable_cache.get(k) is v for k, v in sealed_keys.items()
        )
        qps = n_requests / wall
        p50, p99 = _p_ms(lat, 0.5), _p_ms(lat, 0.99)
        payload[mode] = {
            "search_qps": qps,
            "p50_ms": p50,
            "p99_ms": p99,
            "compact_s": compact_s[0],
            "built_rows": int(built),
            "sealed_cache_stable": bool(stable),
            "pool_segments": (
                router.pool.n_segments if router.pool is not None else 1
            ),
        }
        rows.append(
            (
                f"serving.compaction_{mode}",
                wall * 1e6 / n_requests,
                f"qps={qps:.0f};p50_ms={p50:.1f};p99_ms={p99:.1f};"
                f"compact_s={compact_s[0]:.2f};built_rows={built};"
                f"sealed_cache_stable={stable}",
            )
        )
    _update_bench_json("compaction", payload)
    return rows


def main() -> None:
    enable_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller corpus")
    ap.add_argument(
        "--dry-run", action="store_true", help="tiny smoke run (CI entry-point check)"
    )
    ap.add_argument(
        "--streaming",
        action="store_true",
        help="grow-segment router bench: insert QPS + p99 under concurrent inserts",
    )
    ap.add_argument(
        "--compaction",
        action="store_true",
        help="p99 during concurrent compaction, full rebuild vs incremental",
    )
    args = ap.parse_args()
    kw = {}
    if args.quick:
        kw = dict(n_docs=1024, n_requests=64)
    print("name,us_per_call,derived")
    rows = run(dry_run=args.dry_run, **kw)
    # the dry-run CI path always includes a tiny streaming pass, so
    # BENCH_serving.json tracks both sections on every commit; --quick gets
    # a reduced-but-meaningful config (dry-run scale is smoke, not signal)
    if args.streaming or args.dry_run:
        stream_kw = {}
        if args.quick and not args.dry_run:
            stream_kw = dict(
                n_docs=512, insert_batches=4, insert_batch=16, n_requests=64
            )
        rows += run_streaming(dry_run=args.dry_run, **stream_kw)
    # likewise the dry-run always exercises both compaction modes, so the
    # full-vs-incremental p99/work contrast lands in every CI artifact
    if args.compaction or args.dry_run:
        comp_kw = {}
        if args.quick and not args.dry_run:
            comp_kw = dict(n_docs=512, grow_docs=32, n_requests=64)
        rows += run_compaction(dry_run=args.dry_run, **comp_kw)
    for r in rows:
        print(f"{r[0]},{r[1]:.1f},{r[2]}")


if __name__ == "__main__":
    main()
