"""Paper Figure 12, extended into the dynamic-fusion sweep: the same index
(and the same compiled executable) serving every fusion mode x weight mix
with zero reconstruction and zero recompiles.

``run()`` is the original synthetic-corpus weight sweep (the benchmarks.run
harness entry). ``main()`` is the fusion sweep on the bundled real-text
corpus: recall@10 per (fusion mode, weight mix) cell, plus the trace count
across the whole sweep — the shape-stability evidence (DESIGN.md §11).
Results land in ``results/BENCH_fusion.json``; the recall-floor gate in
``benchmarks/check_regression.py --only fusion`` compares them against the
committed baseline.

    PYTHONPATH=src python benchmarks/fig12_weights.py [--dry-run]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

if __package__ in (None, ""):  # script mode
    _root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]

import numpy as np

import jax

from repro.core import FusionSpec, build_index
from repro.core.fusion import FUSION_MODES, PathStats
from repro.core.search import SearchParams, search
from repro.obs.metrics import GLOBAL
from repro.runtime.compile_cache import enable_compile_cache
from repro.data.corpus import ndcg_at_k


def run(n_docs=4096, n_queries=64):
    from benchmarks.common import default_build, simple_corpus, timed

    corpus = simple_corpus(n_docs, n_queries)
    truth = corpus.query_relevant
    cfg = default_build(corpus.docs.n)
    index = build_index(corpus.docs, cfg)
    params = SearchParams(k=10, iters=40, pool_size=64)
    rows = []
    best_alpha, best_nd = 0.5, -1.0
    for alpha in (0.0, 0.1, 0.3, 0.5, 0.7, 0.9, 1.0):
        spec = FusionSpec.weighted(alpha, 1 - alpha, 0.0)
        ids, sec = timed(
            lambda s=spec: search(index, corpus.queries, s, params).ids
        )
        nd = ndcg_at_k(np.asarray(ids), truth, 10)
        if nd > best_nd:
            best_alpha, best_nd = alpha, nd
        rows.append((f"fig12.two_path.a{alpha:.1f}", sec * 1e6 / n_queries,
                     f"ndcg={nd:.3f}"))
    for alpha in (0.1, 0.5, 0.9):
        # three-path: alpha * (dense + w_opt*sparse) + (1-alpha) * full
        w_opt = best_alpha and (1 - best_alpha) / max(best_alpha, 1e-6)
        spec = FusionSpec.weighted(alpha, alpha * w_opt, 1 - alpha)
        ids, sec = timed(
            lambda s=spec: search(index, corpus.queries, s, params).ids
        )
        nd = ndcg_at_k(np.asarray(ids), truth, 10)
        rows.append((f"fig12.three_path.a{alpha:.1f}", sec * 1e6 / n_queries,
                     f"ndcg={nd:.3f}"))
    # fusion modes at equal weights on the same index — the dynamic-fusion
    # extension of the figure (rrf/normalized vs weighted-sum)
    stats = PathStats.from_corpus(index.corpus, index.alive)
    for mode in FUSION_MODES:
        spec = FusionSpec.make(mode, 1.0, 1.0, 1.0, stats=stats)
        ids, sec = timed(
            lambda s=spec: search(index, corpus.queries, s, params).ids
        )
        nd = ndcg_at_k(np.asarray(ids), truth, 10)
        rows.append((f"fig12.mode_{mode}", sec * 1e6 / n_queries,
                     f"ndcg={nd:.3f}"))
    return rows


WEIGHT_MIXES = [
    ("dense_only", (1.0, 0.0, 0.0)),
    ("hybrid", (1.0, 1.0, 1.0)),
    ("skewed", (1.0, 0.5, 0.5)),
]


def run_fusion_sweep(dry_run: bool = False) -> dict:
    """mode x mix recall@10 on the bundled ingest corpus, all cells through
    one compiled executable (the trace counter is part of the artifact)."""
    from repro.core import BuildConfig, KnnConfig, PruneConfig
    from repro.data.corpus import recall_at_k
    from repro.data.textcorpus import load_bundled_corpus, topic_truth
    from repro.ingest import IngestConfig, IngestPipeline

    corpus = load_bundled_corpus()
    pipe = IngestPipeline(IngestConfig(d_dense=64))
    ingested = pipe.fit(corpus.texts)
    cfg = BuildConfig(
        knn=KnnConfig(k=16, iters=4, node_chunk=128),
        prune=PruneConfig(degree=16, keyword_degree=4, node_chunk=128),
        path_refine_iters=1,
    )
    index = pipe.build(ingested, cfg)
    jax.block_until_ready(index.semantic_edges)

    enc = pipe.encode_queries(corpus.query_texts)
    truth = topic_truth(corpus.query_topics, corpus.topics)
    params = SearchParams(k=10, iters=48, pool_size=64)
    stats = PathStats.from_corpus(index.corpus, index.alive)

    recall = {}
    t0 = time.perf_counter()
    _trace_metric = "allanpoe_core_search_padded_traces_total"
    traces0 = GLOBAL.value(_trace_metric)
    for mode in FUSION_MODES:
        for mix_name, (wd, ws, wf) in WEIGHT_MIXES:
            spec = FusionSpec.make(mode, wd, ws, wf, stats=stats)
            res = search(index, enc.vectors, spec, params)
            recall[f"{mode}.{mix_name}"] = float(
                recall_at_k(np.asarray(res.ids), truth)
            )
    sweep_s = time.perf_counter() - t0
    # every cell after the first reuses the one compiled executable: fusion
    # mode/weights/stats are traced data, never part of the trace signature
    # (counted by the process-wide registry series the obs gate also reads)
    traces = int(GLOBAL.value(_trace_metric) - traces0)

    hybrid_best = max(
        recall[f"{m}.hybrid"] for m in FUSION_MODES
    )
    return {
        "config": {
            "n_docs": len(corpus.texts),
            "n_queries": len(corpus.query_texts),
            "d_dense": 64,
            "modes": sorted(FUSION_MODES),
            "mixes": [m for m, _ in WEIGHT_MIXES],
            "backend": jax.default_backend(),
            "dry_run": dry_run,
        },
        "recall_at_10": recall,
        "hybrid_best": hybrid_best,
        "hybrid_lift": hybrid_best - recall["weighted_sum.dense_only"],
        "sweep_s": sweep_s,
        "sweep_traces": int(traces),
    }


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--dry-run", action="store_true",
        help="CI entry-point check (same bundled corpus; flagged in config)",
    )
    ap.add_argument("--out", default="results/BENCH_fusion.json")
    args = ap.parse_args()

    out = run_fusion_sweep(dry_run=args.dry_run)
    path = pathlib.Path(args.out)
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=2) + "\n")

    rec = out["recall_at_10"]
    for key in sorted(rec):
        print(f"recall@10 {key:24s} {rec[key]:.3f}")
    print(
        f"sweep: {len(rec)} cells in {out['sweep_s']:.1f}s, "
        f"{out['sweep_traces']} trace(s)"
    )
    lift = out["hybrid_lift"]
    if lift < 0:
        print(f"FAIL: best hybrid fusion fell {-lift:.3f} BELOW dense-only")
        return 1
    print(f"PASS: best hybrid >= dense-only (lift {lift:+.3f}); wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
