"""Paper Figure 14 at serving scale: build throughput and query QPS/p99 of
the data-parallel replica tier at {10k, 100k} synthetic docs × {1, 2, 4}
replicas, with the scaling-efficiency metric the nightly CI gate enforces.

Per (n_docs, R) cell, ``data.syncorpus`` streams domain-templated documents
through the fitted ``IngestPipeline`` batch by batch (the raw corpus never
materializes in host memory); every doc's home replica comes from the SAME
consistent-hash ring ``serving.replica_router`` uses online, and each
replica's shard is sealed into fixed-capacity ``SegmentPool`` segments
behind its own ``HybridSearchService`` (own snapshot, own AOT executable
cache).

Scaling metrics — measured honestly on ONE host:

  * ``iso_qps`` — each replica's QPS over the full query stream measured in
    ISOLATION. This is the share-nothing model: deployed replicas are
    separate hosts, and the tier's scatter-gather throughput is bounded by
    its slowest member, so ``model_qps = min(iso_qps)``.
  * ``scaling_efficiency = model_qps@R / (R × model_qps@1)`` — the GATED
    number. With hash placement a replica holds ~1/R of the segments, so
    per-query work shrinks ~R×; what efficiency < 1 measures is the real
    overhead the tier pays: pow2 capacity padding, per-query fixed cost,
    and consistent-hash shard imbalance.
  * ``tier_qps``/``p50``/``p99`` — the REAL in-process scatter-gather path
    (``ReplicaRouter.search`` fanning out on its thread pool). On a single
    CPU host every replica shares the same cores, so this number cannot
    scale with R; it is reported for the record and never gated.

    PYTHONPATH=src python benchmarks/fig14_scale.py [--docs 10000,100000]
        [--replicas 1,2,4] [--dry-run] [--out results/BENCH_scale.json]
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

if __package__ in (None, ""):  # script mode: python benchmarks/fig14_scale.py
    _root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]

import numpy as np

import jax

from repro.core import BuildConfig, KnnConfig, PruneConfig
from repro.core.search import SearchParams
from repro.core.segment_pool import (
    SegmentPool,
    append_segment,
    build_pool_segment,
    place_pool,
)
from repro.core.usms import PathWeights
from repro.data.syncorpus import SynCorpus, SynCorpusConfig
from repro.ingest import IngestPipeline
from repro.obs.metrics import MetricsRegistry
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving.batcher import BatcherConfig, _next_pow2
from repro.serving.hybrid_service import HybridSearchService, ServiceConfig
from repro.serving.replica_router import (
    Replica,
    ReplicaRouter,
    ReplicaTierConfig,
    build_ring,
    ring_homes,
)
from repro.serving.segment_router import RouterConfig, SegmentRouter

W = PathWeights.make(1.0, 1.0, 1.0)
SEED = 0
N_QUERIES = 64


def _build_cfg(n_docs: int) -> BuildConfig:
    return BuildConfig(
        knn=KnnConfig(k=16, iters=2, node_chunk=min(n_docs, 1024)),
        prune=PruneConfig(degree=16, keyword_degree=4, node_chunk=512),
        path_refine_iters=0,
    )


def _tree_rows(tree, rows):
    return jax.tree.map(lambda a: np.asarray(a)[rows], tree)


def _tree_concat(parts):
    return jax.tree.map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
        *parts,
    )


def build_tier(
    gen: SynCorpus,
    pipe: IngestPipeline,
    kg,
    n_docs: int,
    n_replicas: int,
    build_cfg: BuildConfig,
    params: SearchParams,
    *,
    segment_docs: int = 1024,
    encode_batch: int = 1024,
    virtual_nodes: int = 512,
) -> ReplicaRouter:
    """Stream the corpus into an R-replica tier: encode batch by batch,
    scatter rows to their consistent-hash home, seal every ``segment_docs``
    rows of a shard into one pooled segment. Peak host memory is
    O(encode_batch + R × segment_docs) encoded rows, never O(n_docs)."""
    from jax.sharding import Mesh

    names = [f"replica{i}" for i in range(n_replicas)]
    ring = build_ring(names, virtual_nodes)
    kg_kwargs = (
        dict(kg_triplets=kg.triplets, n_entities=kg.n_entities)
        if kg is not None
        else {}
    )
    pools: list[SegmentPool | None] = [None] * n_replicas
    bufs: list[list] = [[] for _ in range(n_replicas)]
    counts = [0] * n_replicas
    seg_no = 0

    def _flush(i: int, final: bool = False) -> None:
        nonlocal seg_no
        while counts[i] >= segment_docs or (final and counts[i] > 0):
            docs = _tree_concat([p[0] for p in bufs[i]])
            ents = np.concatenate([p[1] for p in bufs[i]], axis=0)
            gids = np.concatenate([p[2] for p in bufs[i]], axis=0)
            take = min(segment_docs, counts[i])
            seg_kw = dict(kg_kwargs)
            if seg_kw:
                seg_kw["doc_entities"] = ents[:take]
            seg = build_pool_segment(
                jax.tree.map(lambda a: a[:take], docs),
                gids[:take],
                build_cfg,
                capacity=_next_pow2(take),
                key=jax.random.fold_in(jax.random.key(41), seg_no),
                **seg_kw,
            )
            seg_no += 1
            pools[i] = (
                SegmentPool.from_segmented(seg)
                if pools[i] is None
                else append_segment(pools[i], seg)[0]
            )
            counts[i] -= take
            bufs[i] = (
                [(jax.tree.map(lambda a: a[take:], docs),
                  ents[take:], gids[take:])]
                if counts[i]
                else []
            )

    next_gid = 0
    for batch in gen.doc_batches(encode_batch, stop=n_docs):
        docs, ents = pipe.encode_docs([d.text for d in batch])
        gids = np.arange(next_gid, next_gid + len(batch), dtype=np.int64)
        next_gid += len(batch)
        homes = ring_homes(ring, gids)
        for i in np.unique(homes):
            rows = np.flatnonzero(homes == i)
            bufs[int(i)].append((_tree_rows(docs, rows), ents[rows], gids[rows]))
            counts[int(i)] += int(rows.size)
            if counts[int(i)] >= segment_docs:
                _flush(int(i))
    for i in range(n_replicas):
        _flush(i, final=True)

    # replica i lives on device i (round-robin when replicas outnumber
    # devices): replicas share nothing, so each gets its own chip
    devices = jax.devices()
    replicas = []
    for i, pool in enumerate(pools):
        if pool is None:
            raise RuntimeError(
                f"replica {i} received no docs — corpus too small for "
                f"{n_replicas} replicas"
            )
        mesh = Mesh(np.asarray([devices[i % len(devices)]]), ("data",))
        pool = place_pool(pool, mesh)
        svc = HybridSearchService(
            pool,
            params,
            ServiceConfig(
                batcher=BatcherConfig(
                    flush_size=32, max_batch=32, flush_deadline_s=0.05
                )
            ),
            mesh=mesh,
        )
        router = SegmentRouter(
            svc, build_cfg, RouterConfig(seal_threshold=10**9), **kg_kwargs
        )
        replicas.append(Replica(svc, router, name=names[i]))
    return ReplicaRouter(
        replicas, ReplicaTierConfig(virtual_nodes=virtual_nodes)
    )


def _measure(search_fn, query_batches, n_requests: int, batch: int):
    """Closed-loop batched client: warm one batch (compile), then drive
    ``n_requests`` requests. Per-batch wall latencies stream into the same
    fixed-bucket histogram the serving stack exposes (one local series, no
    sample array), and percentiles come from its interpolated quantiles —
    bench and production share one latency implementation."""
    np.asarray(search_fn(query_batches[0]).ids)  # warmup / compile
    hist = MetricsRegistry().histogram(
        "fig14_batch_latency_seconds", "per-batch scatter-read wall time"
    )
    done = 0
    i = 0
    t0 = time.perf_counter()
    while done < n_requests:
        t1 = time.perf_counter()
        np.asarray(search_fn(query_batches[i % len(query_batches)]).ids)
        hist.observe(time.perf_counter() - t1)
        done += batch
        i += 1
    wall = time.perf_counter() - t0
    return done / wall, hist.snapshot()


def bench_scale(
    n_docs: int,
    replicas_grid=(1, 2, 4),
    *,
    n_requests: int = 256,
    batch: int = 32,
    segment_docs: int = 256,
    encode_batch: int = 1024,
    k: int = 10,
    seed: int = SEED,
) -> dict:
    """One corpus size across the replica grid; returns the JSON payload
    for this scale (per-R build + QPS numbers, scaling efficiency)."""
    params = SearchParams(k=k, iters=32, pool_size=64)
    build_cfg = _build_cfg(n_docs)
    gen = SynCorpus(
        SynCorpusConfig(n_docs=n_docs, seed=seed, n_queries=N_QUERIES)
    )
    pipe = IngestPipeline()
    fitted = pipe.fit(gen.fit_sample(min(2048, n_docs)))
    kg = fitted.kg if len(fitted.kg.triplets) else None
    enc = pipe.encode_queries([q.text for q in gen.queries(N_QUERIES)])
    query_batches = [
        jax.tree.map(lambda a: a[lo:lo + batch], enc.vectors)
        for lo in range(0, N_QUERIES - batch + 1, batch)
    ]

    out: dict = {"replicas": {}}
    for n_rep in replicas_grid:
        t0 = time.perf_counter()
        tier = build_tier(
            gen, pipe, kg, n_docs, n_rep, build_cfg, params,
            segment_docs=segment_docs, encode_batch=encode_batch,
        )
        build_s = time.perf_counter() - t0
        try:
            iso = []
            for r in tier.replicas:
                qps, _ = _measure(
                    lambda q, s=r.service: s.search(q, W, k=k),
                    query_batches, n_requests, batch,
                )
                iso.append(qps)
            tier_qps, lats = _measure(
                lambda q: tier.search(q, W, k=k),
                query_batches, n_requests, batch,
            )
            out["replicas"][str(n_rep)] = {
                "build_s": build_s,
                "build_docs_per_s": n_docs / build_s,
                "shard_docs": tier.shard_sizes(),
                "pool_segments": [
                    r.router.pool.n_segments for r in tier.replicas
                ],
                "iso_qps": iso,
                "model_qps": min(iso),
                "tier_qps": tier_qps,
                "tier_p50_ms": float(lats.quantile(0.5)) * 1e3,
                "tier_p99_ms": float(lats.quantile(0.99)) * 1e3,
            }
        finally:
            tier.close()

    base = out["replicas"][str(replicas_grid[0])]["model_qps"]
    base_r = replicas_grid[0]
    for n_rep in replicas_grid:
        e = out["replicas"][str(n_rep)]
        e["scaling_efficiency"] = (
            (e["model_qps"] / base) * (base_r / n_rep)
        )
    out["scaling_efficiency"] = out["replicas"][str(replicas_grid[-1])][
        "scaling_efficiency"
    ]
    return out


def run(
    n_docs=10_000,
    replicas=(1, 2, 4),
    *,
    n_requests: int = 256,
    batch: int = 32,
    segment_docs: int = 256,
    encode_batch: int = 1024,
    out_path: str = "results/BENCH_scale.json",
):
    """Full bench across one or more corpus sizes; writes
    ``results/BENCH_scale.json`` and returns harness CSV rows."""
    sizes = (n_docs,) if isinstance(n_docs, int) else tuple(n_docs)
    payload = {
        "config": {
            "docs": list(sizes),
            "replicas": list(replicas),
            "n_requests": n_requests,
            "batch": batch,
            "n_queries": N_QUERIES,
            "segment_docs": segment_docs,
            "virtual_nodes": 512,
            "k": 10,
            "seed": SEED,
            "backend": jax.default_backend(),
        },
        "scales": {},
    }
    rows = []
    for n in sizes:
        scale = bench_scale(
            n, replicas, n_requests=n_requests, batch=batch,
            segment_docs=segment_docs, encode_batch=encode_batch,
        )
        payload["scales"][str(n)] = scale
        for n_rep in replicas:
            e = scale["replicas"][str(n_rep)]
            rows.append(
                (
                    f"fig14.n{n}_r{n_rep}",
                    1e6 / e["model_qps"],
                    f"build_s={e['build_s']:.1f};"
                    f"model_qps={e['model_qps']:.0f};"
                    f"tier_qps={e['tier_qps']:.0f};"
                    f"tier_p99_ms={e['tier_p99_ms']:.1f};"
                    f"eff={e['scaling_efficiency']:.2f}",
                )
            )
    out = pathlib.Path(out_path)
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(payload, indent=2) + "\n")
    return rows


def main() -> None:
    enable_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--docs", default="10000",
        help="comma list of corpus sizes (default 10000)",
    )
    ap.add_argument(
        "--replicas", default="1,2,4", help="comma list of replica counts"
    )
    ap.add_argument(
        "--dry-run", action="store_true",
        help="tiny smoke run (CI entry-point check): ~1k docs, 1-2 replicas",
    )
    ap.add_argument(
        "--segment-docs", type=int, default=256,
        help="docs sealed per pool segment (finer segmentation spreads "
        "work across replicas more evenly; must match the baseline)",
    )
    ap.add_argument("--out", default="results/BENCH_scale.json")
    args = ap.parse_args()
    kw: dict = dict(out_path=args.out, segment_docs=args.segment_docs)
    if args.dry_run:
        sizes: tuple = (1024,)
        replicas = (1, 2)
        kw.update(n_requests=64, segment_docs=128, encode_batch=512)
    else:
        sizes = tuple(int(s) for s in args.docs.split(","))
        replicas = tuple(int(r) for r in args.replicas.split(","))
    print("name,us_per_call,derived")
    for r in run(sizes, replicas, **kw):
        print(f"{r[0]},{r[1]:.1f},{r[2]}")


if __name__ == "__main__":
    main()
