"""End-to-end driver (the paper's kind: retrieval serving): build the hybrid
index over a corpus, then serve batched retrieval-augmented generation
requests — hybrid search -> context assembly -> batched decode. Retrieval
runs through ``HybridSearchService``, so RAG traffic is micro-batched into
shape-bucketed executables and would share the index snapshot with any other
search client.

    PYTHONPATH=src python examples/rag_serving.py
"""

import dataclasses
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core import BuildConfig, KnnConfig, PruneConfig, build_index
from repro.core.search import SearchParams
from repro.data.corpus import CorpusConfig, make_corpus, recall_at_k
from repro.models import transformer as tfm
from repro.serving.batcher import BatcherConfig
from repro.serving.engine import ServeConfig, ServingEngine
from repro.serving.hybrid_service import HybridSearchService, ServiceConfig
from repro.serving.rag import RagConfig, RagPipeline


def main():
    print("=== retrieval-augmented serving (end-to-end) ===")
    n_docs, n_requests = 4096, 16
    corpus = make_corpus(CorpusConfig(
        n_docs=n_docs, n_queries=n_requests, n_topics=64, d_dense=64, seed=3,
    ))

    t0 = time.perf_counter()
    index = build_index(
        corpus.docs,
        BuildConfig(
            knn=KnnConfig(k=24, iters=4, node_chunk=2048),
            prune=PruneConfig(degree=24, keyword_degree=8, node_chunk=512),
            path_refine_iters=1,
        ),
    )
    print(f"index over {n_docs} docs built in {time.perf_counter()-t0:.1f}s")

    cfg = dataclasses.replace(get_smoke_config("llama3.2-1b"), vocab=2048)
    params = tfm.init_params(jax.random.key(0), cfg)
    engine = ServingEngine(cfg, params, ServeConfig(max_len=256, batch=n_requests))

    rng = np.random.default_rng(0)
    doc_tokens = jnp.asarray(rng.integers(0, cfg.vocab, size=(n_docs, 16)), jnp.int32)
    search_params = SearchParams(k=3, iters=40, pool_size=64)
    service = HybridSearchService(
        index, search_params,
        ServiceConfig(batcher=BatcherConfig(flush_size=n_requests,
                                            max_batch=n_requests)),
    )
    rag = RagPipeline(
        engine, index, doc_tokens,
        RagConfig(top_k=3, ctx_tokens_per_doc=16, search=search_params),
        service=service,
    )

    prompts = jnp.asarray(rng.integers(0, cfg.vocab, size=(n_requests, 8)), jnp.int32)
    t0 = time.perf_counter()
    # every span of the request — admission, queue wait, batch phases,
    # context assembly, generation — lands in one trace context; the
    # profiler records the service's batch phases as serving.<phase> host
    # spans beside the device operations, on one clock
    with jax.profiler.trace("results/rag_trace"):
        with service.tracer.trace("rag_answer", requests=n_requests) as ctx:
            out, res = rag.answer(corpus.queries, prompts, n_tokens=24, trace=ctx)
    dt = time.perf_counter() - t0

    rec = recall_at_k(np.asarray(res.ids), corpus.query_relevant[:, :1])
    print(f"{n_requests} requests: retrieve(top-3) + generate(24 tok) "
          f"in {dt:.1f}s  ({n_requests * 24 / dt:.1f} tok/s)")
    print(f"retrieval recall of planted docs: {rec:.2f}")
    print(f"output shape: {out.shape} (context 3x16 + prompt 8 + 24 generated)")
    print(f"service: {service.stats.batches} batches, "
          f"{service.stats.compiles} compiled executables, "
          f"{service.stats.requests} requests")

    # observability artifacts: the request's span tree, the profiler trace
    # (TensorBoard's profile plugin or https://ui.perfetto.dev) and the
    # Prometheus view
    spans = sorted({s.name for s in ctx.spans()})
    print(f"trace: {len(ctx.spans())} spans ({', '.join(spans)})")
    print("profiler trace written under results/rag_trace/")
    print("metrics exposition (excerpt):")
    for line in service.metrics.render().splitlines():
        if line.startswith("allanpoe_serving_requests_total"):
            print(f"  {line}")


if __name__ == "__main__":
    main()
