"""Ingestion benchmark on the bundled real-text corpus: ingest throughput
(fit + frozen-stats encode docs/s) and end-to-end retrieval accuracy —
recall@10 of hybrid (dense+lexical+learned) vs dense-only — demonstrating
that the lexical path actually lifts accuracy on real text (paper §3.1's
full-text component; "Balancing the Blend", arXiv:2508.01405).

Ground truth: the bundled paragraphs (tests/data/paragraphs.jsonl) are
topic-clustered prose with recurring named entities; a query's relevant set
is its topic's paragraphs. Results land in ``results/BENCH_ingest.json``
(uploaded with the other CI bench artifacts). Exit code 1 if hybrid falls
below dense-only — the acceptance gate of the ingestion subsystem.

    PYTHONPATH=src python benchmarks/ingest_bench.py [--dry-run]
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

from repro.core import BuildConfig, KnnConfig, PruneConfig
from repro.core.search import SearchParams, search
from repro.core.usms import PathWeights
from repro.data.corpus import recall_at_k
from repro.data.textcorpus import load_bundled_corpus, topic_truth
from repro.ingest import IngestConfig, IngestPipeline
from repro.runtime.compile_cache import enable_compile_cache

WEIGHTS = [
    ("dense_only", PathWeights.make(1, 0, 0)),
    ("lexical_only", PathWeights.make(0, 0, 1)),
    ("learned_only", PathWeights.make(0, 1, 0)),
    ("hybrid", PathWeights.three_path()),
]


def run(dry_run: bool = False) -> dict:
    corpus = load_bundled_corpus()
    texts, topics = corpus.texts, corpus.topics
    q_texts, q_topics = corpus.query_texts, corpus.query_topics
    repeats = 1 if dry_run else 3

    pipe = IngestPipeline(IngestConfig(d_dense=64))
    t0 = time.perf_counter()
    ingested = pipe.fit(texts)
    fit_s = time.perf_counter() - t0

    # frozen-stats encode throughput (the streaming-insert hot path)
    t0 = time.perf_counter()
    for _ in range(repeats):
        pipe.encode_docs(texts)
    encode_s = (time.perf_counter() - t0) / repeats

    cfg = BuildConfig(
        knn=KnnConfig(k=16, iters=4, node_chunk=128),
        prune=PruneConfig(degree=16, keyword_degree=4, node_chunk=128),
        path_refine_iters=1,
    )
    t0 = time.perf_counter()
    index = pipe.build(ingested, cfg)
    jax.block_until_ready(index.semantic_edges)
    build_s = time.perf_counter() - t0

    enc = pipe.encode_queries(q_texts)
    truth = topic_truth(q_topics, topics)
    params = SearchParams(k=10, iters=48, pool_size=64)
    recall = {}
    for name, w in WEIGHTS:
        res = search(index, enc.vectors, w, params)
        recall[name] = float(recall_at_k(np.asarray(res.ids), truth))

    out = {
        "config": {
            "n_docs": len(texts),
            "n_queries": len(q_texts),
            "d_dense": 64,
            "backend": jax.default_backend(),
            "dry_run": dry_run,
        },
        "ingest": {
            "fit_s": fit_s,
            "fit_docs_per_s": len(texts) / max(fit_s, 1e-9),
            "encode_docs_per_s": len(texts) / max(encode_s, 1e-9),
            "build_s": build_s,
            "n_entities": len(pipe.entity_vocab),
            "n_triplets": int(len(ingested.kg.triplets)),
        },
        "recall_at_10": recall,
        "hybrid_lift": recall["hybrid"] - recall["dense_only"],
    }
    return out


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--dry-run", action="store_true",
        help="single-pass timing (CI entry-point check; same corpus/accuracy)",
    )
    ap.add_argument("--out", default="results/BENCH_ingest.json")
    args = ap.parse_args()

    out = run(dry_run=args.dry_run)
    path = pathlib.Path(args.out)
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=2) + "\n")

    ing, rec = out["ingest"], out["recall_at_10"]
    print(
        f"ingest: fit {ing['fit_docs_per_s']:.0f} docs/s, "
        f"encode {ing['encode_docs_per_s']:.0f} docs/s, "
        f"build {ing['build_s']:.2f}s, "
        f"{ing['n_entities']} entities / {ing['n_triplets']} triplets"
    )
    for name, _ in WEIGHTS:
        print(f"recall@10 {name:13s} {rec[name]:.3f}")
    lift = out["hybrid_lift"]
    if lift < 0:
        print(f"FAIL: hybrid recall fell {-lift:.3f} BELOW dense-only — the "
              "lexical path must not hurt accuracy on real text")
        return 1
    print(f"PASS: hybrid >= dense-only (lift {lift:+.3f}); wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
