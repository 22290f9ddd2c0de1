#!/usr/bin/env python3
"""Chip smoke: build and serve a full-width hybrid index on a TPU through the
normal entry points (``build_index`` -> ``HybridSearchService``), and check
what comes out.

    python chip_smoke.py               # one TPU chip (the default run)
    python chip_smoke.py --four-chips  # sharded search + replica tier, 4 chips
    python chip_smoke.py --rehearse    # tiny sizes; the CPU is allowed

The corpus has MS MARCO-passage widths: 768-d fp32 dense, 128-nnz learned
sparse rows over the 30,522-term SPLADE vocabulary, 64-nnz lexical rows,
and queries at 32/16 nnz, generated from a seed (``data/corpus.py``).

One chip: build the index with the Pallas kernels, serve 256 queries under
weighted three-path and RRF fusion with keywords on, and check them: the
serving executable contains the kernel, recall@10 against an exact
brute-force reference meets ``RECALL_FLOOR``, the kernel and the jnp oracle
agree on the top-10, inserted passages are found, deleted ones never
return. Four chips: the same corpus served sharded over a 4-device mesh
against a 1-device mesh, and a 4-replica ``ReplicaRouter`` tier with
replica i on device i against the same tier all on device 0.

Any failed check exits non-zero. On success the last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``. Without
``--rehearse`` a run that finds no TPU fails: there is no CPU fallback.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core import BuildConfig, KnnConfig, PruneConfig  # noqa: E402
from repro.core.distributed import (  # noqa: E402
    SegmentedIndex,
    build_index_sharded,
    place_segmented_index,
)
from repro.core.fusion import FusionSpec, stack_specs  # noqa: E402
from repro.core.search import SearchParams, search_padded  # noqa: E402
from repro.core.segment_pool import SegmentPool, build_pool_segment, place_pool  # noqa: E402
from repro.core.usms import PAD_IDX, FusedVectors, has_keyword_overlap  # noqa: E402
from repro.data.corpus import CorpusConfig, make_corpus  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving.batcher import BatcherConfig, SearchRequest  # noqa: E402
from repro.serving.hybrid_service import HybridSearchService, ServiceConfig  # noqa: E402
from repro.serving.replica_router import Replica, ReplicaRouter  # noqa: E402
from repro.serving.segment_router import RouterConfig, SegmentRouter  # noqa: E402

SEED = 0
K = 10
# Passages built on one chip. The target is 2**20; see CHANGES.md for the
# build seconds of each size tried and why this one was kept.
LOG2_DOCS = 17
LOG2_DOCS_FOUR_CHIPS = 14
N_QUERIES = 256
N_INSERT = 1024
N_DELETE = 1024
# --rehearse: a CPU run of every phase at a size that takes a minute
REHEARSE_LOG2_DOCS = 10
REHEARSE_LOG2_DOCS_FOUR_CHIPS = 9
REHEARSE_QUERIES = 32
REHEARSE_WRITES = 128
# recall@10 of the weighted three-path queries against the exact reference.
# A graph built at 2**17 with the jnp oracle (use_kernel=False) instead of
# the kernels reads 0.1609 at these search params on a TPU v5e, the same as
# the kernel-built graph: this is the graph's reach at this size, not a
# fault of the chip path. The floor sits just below it (CHANGES.md)
RECALL_FLOOR = 0.15
AGREEMENT_FLOOR = 0.99  # top-10 set overlap: kernel vs oracle, 4 vs 1 chips
# search breadth of the served queries: the library default (48 rounds,
# pool 64) reaches too little of a 2**17-passage graph (CHANGES.md)
SEARCH_ITERS = 128
SEARCH_POOL = 128
RECALL_TOLERANCE = 0.005  # sharded vs single-device recall
BATCH = 64  # one serving bucket: every request carries the same widths

WIDTHS = dict(
    d_dense=768,
    vocab_sparse=30522,
    nnz_sparse=128,
    nnz_lexical=64,
    nnz_query_sparse=32,
    nnz_query_lexical=16,
    terms_per_topic=256,  # a topic's term pool must exceed nnz_sparse
    keywords_per_topic=96,  # ... and nnz_lexical
)
REHEARSE_WIDTHS = dict(
    d_dense=32,
    nnz_sparse=16,
    nnz_lexical=8,
    nnz_query_sparse=8,
    nnz_query_lexical=4,
    n_topics=16,
)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(*parts) -> None:
    print(*parts, flush=True)


class CompileClock:
    """Seconds XLA spent compiling, from JAX's own compile events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def build_config(rehearse: bool) -> BuildConfig:
    """Library defaults (k=32, 6 descent rounds, degree 16) with node chunks
    sized so one chunk's candidate gather fits beside the corpus: the
    descent gathers node_chunk x (k*k + extra_random) rows of 4.6 KB, about
    1.4 GB at 256 nodes, where the default 2048 would need ~11 GB."""
    if rehearse:
        return BuildConfig(
            knn=KnnConfig(k=16, iters=3, node_chunk=256),
            prune=PruneConfig(degree=16, keyword_degree=8, node_chunk=256),
            path_refine_iters=1,
        )
    return BuildConfig(
        knn=KnnConfig(node_chunk=256),
        prune=PruneConfig(node_chunk=1024),
    )


def make_data(n_docs: int, n_extra: int, n_queries: int, rehearse: bool):
    widths = dict(WIDTHS, **(REHEARSE_WIDTHS if rehearse else {}))
    t0 = time.perf_counter()
    corpus = make_corpus(
        CorpusConfig(
            n_docs=n_docs + n_extra, n_queries=n_queries, seed=SEED, **widths
        )
    )
    log(
        f"data: {n_docs} passages + {n_extra} held for insert, "
        f"{n_queries} queries, dense {widths['d_dense']}, "
        f"nnz {widths['nnz_sparse']}/{widths['nnz_lexical']}, "
        f"query nnz {widths['nnz_query_sparse']}/{widths['nnz_query_lexical']}, "
        f"{corpus.kg.triplets.shape[0]} KG triplets, "
        f"generated in {time.perf_counter() - t0:.1f} s"
    )
    return corpus


def rows(tree, lo: int, hi: int):
    return jax.tree.map(lambda a: a[lo:hi], tree)


def fusion_for(i: int) -> FusionSpec:
    """Even requests: weighted three-path. Odd requests: RRF."""
    return FusionSpec.three_path() if i % 2 == 0 else FusionSpec.rrf()


def serve(service, queries: FusedVectors, keywords: np.ndarray):
    """Every query through ``submit(...).result()``; each result is read,
    so a failed batch fails here, not silently in a pump thread."""
    pending = [
        service.submit(
            SearchRequest(
                query=queries[i],
                fusion=fusion_for(i),
                k=K,
                keywords=keywords[i],
            )
        )
        for i in range(queries.n)
    ]
    ids = np.stack([p.result()[0] for p in pending])
    check(ids.shape == (queries.n, K), f"served ids shape {ids.shape}")
    return ids


def check_results(ids: np.ndarray, doc_kw: np.ndarray, keywords: np.ndarray, n: int):
    """Ids in range, no duplicate in a row, required keyword present."""
    for i, row in enumerate(ids):
        live = row[row >= 0]
        check(live.size > 0, f"query {i} returned nothing")
        check(bool((live < n).all()), f"query {i} returned an id out of range")
        check(len(set(live.tolist())) == live.size, f"query {i} repeats an id")
        need = keywords[i][keywords[i] >= 0]
        if need.size:
            ok = np.isin(doc_kw[live], need).any(axis=1)
            check(bool(ok.all()), f"query {i} returned a passage without its keyword")


def exact_top10(queries, q_kw, corpus, alive=None):
    """Exact weighted three-path top-10 over the whole corpus, by brute
    force with the jnp oracle (``ops.pairwise_scores_chunked``), under the
    service's keyword rule: with required keywords, a passage must share
    one."""
    scores = ops.pairwise_scores_chunked(queries, corpus, chunk=2048)
    has_req = (q_kw >= 0).any(axis=1)

    @jax.jit
    def mask(scores, doc_kw, q_kw, has_req):
        n = doc_kw.shape[0]
        chunk = min(n, 4096)
        pad = (-n) % chunk
        dk = jnp.concatenate([doc_kw, jnp.full((pad, doc_kw.shape[1]), PAD_IDX, doc_kw.dtype)])
        match = jax.lax.map(
            lambda blk: has_keyword_overlap(blk[None, :, :], q_kw[:, None, :]),
            dk.reshape(-1, chunk, dk.shape[1]),
        )  # (n_chunks, Q, chunk)
        match = jnp.moveaxis(match, 0, 1).reshape(q_kw.shape[0], -1)[:, :n]
        ok = match | ~has_req[:, None]
        return jnp.where(ok, scores, -jnp.inf)

    masked = mask(scores, corpus.lexical.idx, jnp.asarray(q_kw), jnp.asarray(has_req))
    if alive is not None:
        masked = jnp.where(alive[None, :], masked, -jnp.inf)
    top, ids = jax.lax.top_k(masked, K)
    return np.where(np.isfinite(np.asarray(top)), np.asarray(ids), PAD_IDX)


def recall(ids: np.ndarray, truth: np.ndarray) -> float:
    hits = total = 0
    for r, t in zip(ids, truth):
        t = t[t >= 0]
        hits += len(set(r.tolist()) & set(t.tolist()))
        total += len(t)
    return hits / max(total, 1)


def agreement(a: np.ndarray, b: np.ndarray) -> float:
    """Mean top-k set overlap; equal-score ties may order either way."""
    return float(
        np.mean([len(set(x.tolist()) & set(y.tolist())) / K for x, y in zip(a, b)])
    )


def bytes_in_use(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("bytes_in_use", -1))


def device_bytes(tree, dev) -> int:
    """Bytes of ``tree``'s shards that live on ``dev``."""
    return sum(
        s.data.nbytes
        for leaf in jax.tree.leaves(tree)
        for s in leaf.addressable_shards
        if s.device == dev
    )


def stacked_specs(n: int):
    return stack_specs([fusion_for(i) for i in range(n)])


def one_chip(rehearse: bool) -> None:
    n = 1 << (REHEARSE_LOG2_DOCS if rehearse else LOG2_DOCS)
    n_q = REHEARSE_QUERIES if rehearse else N_QUERIES
    n_ins = REHEARSE_WRITES if rehearse else N_INSERT
    n_del = REHEARSE_WRITES if rehearse else N_DELETE
    corpus = make_data(n, n_ins, n_q, rehearse)
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    cfg = build_config(rehearse)
    t0 = time.perf_counter()
    docs = jax.device_put(rows(corpus.docs, 0, n))
    queries = jax.device_put(corpus.queries)
    jax.block_until_ready((docs, queries))
    log(f"transfer: {time.perf_counter() - t0:.1f} s, bytes_in_use {bytes_in_use(dev)}")

    # --- build: build_index through the pool's sealed-segment entry point
    # (use_kernel resolves by platform: the Pallas kernels on a TPU). No
    # logical edges: the index keeps entity adjacency as a dense (E, E)
    # table, and every passage names an entity of its own, so at 2**17
    # passages it would take 17.5 GB (CHANGES.md)
    clock = CompileClock()
    t0 = time.perf_counter()
    seg = build_pool_segment(docs, np.arange(n), cfg, key=jax.random.key(SEED))
    jax.block_until_ready(seg)
    build_s = time.perf_counter() - t0
    log(
        f"build: {n} passages in {build_s:.1f} s, of which compile "
        f"{clock.seconds:.1f} s; index {seg.index.nbytes() / 2**30:.3f} GiB; "
        f"bytes_in_use {bytes_in_use(dev)}"
    )

    # --- serve: every query through submit().result(), two fusion modes;
    # writes go to the router's grow segment, as in a streaming deployment
    mesh = Mesh(np.asarray([dev]), ("data",))
    service = HybridSearchService(
        place_pool(SegmentPool.from_segmented(seg), mesh),
        SearchParams(k=K, iters=SEARCH_ITERS, pool_size=SEARCH_POOL, use_keywords=True),
        ServiceConfig(
            batcher=BatcherConfig(
                flush_size=BATCH, max_batch=BATCH, flush_deadline_s=3600.0
            )
        ),
        mesh=mesh,
    )
    SegmentRouter(service, cfg, RouterConfig(seal_threshold=10 * n_ins))
    check(
        service.params.use_kernel == on_chip,
        f"service resolved use_kernel={service.params.use_kernel} on {dev.platform}",
    )
    q_kw = corpus.query_keywords
    doc_kw = np.asarray(corpus.docs.lexical.idx)
    t0 = time.perf_counter()
    served = serve(service, corpus.queries, q_kw)
    log(
        f"serve: {n_q} queries in {time.perf_counter() - t0:.1f} s "
        f"(compile included), {service.stats.batches} batches, "
        f"{len(service.executable_cache)} compiled executable(s)"
    )
    check(len(service.executable_cache) == 1, "one bucket shape must give one executable")
    check_results(served, doc_kw, q_kw, n)
    if on_chip:
        (exe,) = service.executable_cache.values()
        n_calls = exe.as_text().count("tpu_custom_call")
        log(f"serving executable: {n_calls} tpu_custom_call")
        check(n_calls > 0, "serving executable holds no Pallas kernel")

    # --- correctness: exact reference, kernel vs oracle on this device ---
    t0 = time.perf_counter()
    truth = exact_top10(queries, q_kw, docs)
    weighted = np.arange(n_q) % 2 == 0
    rec = recall(served[weighted], truth[weighted])
    log(
        f"recall@10 (weighted three-path, exact brute force over {n}): "
        f"{rec:.4f} (floor {RECALL_FLOOR}); reference {time.perf_counter() - t0:.1f} s"
    )
    check(rec >= RECALL_FLOOR, f"recall@10 {rec:.4f} below {RECALL_FLOOR}")

    index = jax.tree.map(lambda a: a[0], seg.index)  # the one sealed segment
    specs = stacked_specs(n_q)
    kw = jnp.asarray(q_kw)
    en = jnp.full((n_q, 1), PAD_IDX, jnp.int32)
    kernel_params = dataclasses.replace(service.params, use_kernel=True)
    oracle_params = dataclasses.replace(service.params, use_kernel=False)
    got_k = np.asarray(search_padded(index, queries, specs, kw, en, kernel_params).ids)
    got_o = np.asarray(search_padded(index, queries, specs, kw, en, oracle_params).ids)
    agree = agreement(got_k, got_o)
    log(f"kernel vs oracle top-10 agreement: {agree:.4f} (floor {AGREEMENT_FLOOR})")
    check(agree >= AGREEMENT_FLOOR, f"kernel/oracle agreement {agree:.4f}")

    # --- writes: insert passages, read each back by its own vector; the
    # grow segment is read by an exact scan, so each must be found ---
    new = jax.device_put(rows(corpus.docs, n, n + n_ins))
    t0 = time.perf_counter()
    service.insert(new)
    log(
        f"insert: {n_ins} passages in {time.perf_counter() - t0:.1f} s "
        f"(compile included)"
    )
    new_ids = np.arange(n, n + n_ins)

    def self_search() -> np.ndarray:
        res = service.search(new, FusionSpec.three_path(), k=K)
        return np.asarray(res.ids)

    # the score is an inner product, not a cosine: a passage can score
    # higher against another passage than against itself, so the exact
    # reference says which inserted passages their own top-10 must hold
    everything = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), docs, new)
    exact = np.asarray(ops.topk_hybrid(new, everything, K)[1])
    must = (exact == new_ids[:, None]).any(axis=1)
    found = (self_search() == new_ids[:, None]).any(axis=1)
    stored = all(
        np.array_equal(np.asarray(a)[:n_ins], np.asarray(b))
        for a, b in zip(jax.tree.leaves(service.grow_index.corpus), jax.tree.leaves(new))
    )
    log(
        f"insert read-back: stored as sent {stored}; {int(found.sum())}/{n_ins} "
        f"found in their own top-10; the exact top-10 holds {int(must.sum())} of them"
    )
    check(stored, "the grow segment does not hold the inserted passages as sent")
    check(
        bool(found[must].all()),
        f"inserted passages not found: {new_ids[must & ~found].tolist()}",
    )

    # --- deletes: served and inserted ids among them; none may come back ---
    rng = np.random.default_rng(SEED)
    doomed = np.unique(np.concatenate([served[:, 0][served[:, 0] >= 0], new_ids[: n_ins // 2]]))
    rest = np.setdiff1d(np.arange(n + n_ins), doomed)
    doomed = np.concatenate([doomed, rng.choice(rest, n_del - doomed.size, replace=False)])
    service.mark_deleted(doomed)
    after = serve(service, corpus.queries, q_kw)
    back = np.isin(after, doomed).sum() + np.isin(self_search(), doomed).sum()
    log(f"delete: {n_del} ids marked; returned afterwards: {int(back)}")
    check(back == 0, f"{int(back)} deleted ids returned")
    check_results(after, doc_kw, q_kw, n + n_ins)
    service.stop_pump()  # joins the router's merge worker
    log(
        f"executables compiled by the service: {service.stats.compiles}; "
        f"bytes_in_use {bytes_in_use(dev)}"
    )


def four_chips(rehearse: bool) -> None:
    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    devs = devs[:4]
    # an uneven count: the last segment is padded, never cut
    n = (1 << (REHEARSE_LOG2_DOCS_FOUR_CHIPS if rehearse else LOG2_DOCS_FOUR_CHIPS)) + 3
    n_q = REHEARSE_QUERIES if rehearse else N_QUERIES
    corpus = make_data(n, 0, n_q, rehearse)
    cfg = build_config(rehearse)
    params = SearchParams(k=K, iters=SEARCH_ITERS, pool_size=SEARCH_POOL, use_keywords=True)
    docs = jax.device_put(corpus.docs)
    queries = corpus.queries
    q_kw = corpus.query_keywords
    specs = [fusion_for(i) for i in range(n_q)]
    mesh4 = Mesh(np.asarray(devs), ("data",))
    mesh1 = Mesh(np.asarray(devs[:1]), ("data",))

    clock = CompileClock()
    t0 = time.perf_counter()
    seg = build_index_sharded(docs, 4, cfg, mesh=mesh4, key=jax.random.key(SEED))
    jax.block_until_ready(seg)
    log(
        f"sharded build: {n} passages in 4 segments of "
        f"{seg.global_ids.shape[1]} rows in {time.perf_counter() - t0:.1f} s, "
        f"of which compile {clock.seconds:.1f} s"
    )
    seg = place_segmented_index(seg, mesh4)  # every leaf, not just the graph
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(seg))
    shares = [device_bytes(seg, d) for d in devs]
    log(
        "(a) per-device index bytes: "
        + ", ".join(f"{d.id}: {b} (bytes_in_use {bytes_in_use(d)})" for d, b in zip(devs, shares))
        + f"; total {total}"
    )
    check(all(b == total // 4 for b in shares), "each device must hold a quarter of the index")
    check(
        all(bytes_in_use(d) >= b for d, b in zip(devs, shares)) or rehearse,
        "a device reports fewer bytes in use than its shard",
    )

    truth = exact_top10(jax.device_put(queries), q_kw, docs)
    weighted = np.arange(n_q) % 2 == 0

    def serve_segmented(index: SegmentedIndex, mesh: Mesh) -> np.ndarray:
        svc = HybridSearchService(index, params, mesh=mesh)
        res = svc.search(queries, specs, keywords=q_kw, k=K)
        return np.asarray(res.ids)

    ids4 = serve_segmented(seg, mesh4)
    ids1 = serve_segmented(place_segmented_index(seg, mesh1), mesh1)
    rec4, rec1 = recall(ids4[weighted], truth[weighted]), recall(ids1[weighted], truth[weighted])
    agree = agreement(ids4, ids1)
    log(
        f"(a) sharded search, 4-device vs 1-device mesh: top-10 agreement "
        f"{agree:.4f}; recall@10 {rec4:.4f} vs {rec1:.4f}"
    )
    check(agree >= AGREEMENT_FLOOR, f"sharded agreement {agree:.4f}")
    check(abs(rec4 - rec1) <= RECALL_TOLERANCE, "sharded recall differs from one device")

    # (b) replica tier: replica i serves segment i of the sharded build (a
    # disjoint shard; reads merge by global id whatever the partition)
    def tier(placement):
        reps = []
        for i in range(4):
            part = SegmentedIndex(
                index=jax.tree.map(lambda a: a[i : i + 1], seg.index),
                global_ids=seg.global_ids[i : i + 1],
            )
            mesh = Mesh(np.asarray([placement[i]]), ("data",))
            pool = place_pool(SegmentPool.from_segmented(part), mesh)
            held = {d for leaf in jax.tree.leaves(pool) for d in leaf.devices()}
            check(held == {placement[i]}, f"replica {i} is on {held}")
            reps.append(Replica(HybridSearchService(pool, params, mesh=mesh), name=f"replica{i}"))
        return ReplicaRouter(reps)

    results = {}
    for name, placement in (("spread", devs), ("device0", [devs[0]] * 4)):
        with tier(placement) as router:
            res = router.search(queries, specs, keywords=q_kw, k=K)
            results[name] = np.asarray(res.ids)
    agree = agreement(results["spread"], results["device0"])
    rec_t = recall(results["spread"][weighted], truth[weighted])
    log(
        f"(b) replica tier, replica i on device i vs all on device 0: top-10 "
        f"agreement {agree:.4f}; recall@10 {rec_t:.4f}"
    )
    check(agree >= AGREEMENT_FLOOR, f"replica agreement {agree:.4f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true", help="sharded search and replica tier on 4 chips")
    ap.add_argument("--rehearse", action="store_true", help="tiny sizes; the CPU is allowed")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    cache = enable_compile_cache()
    devs = jax.devices()
    d0 = devs[0]
    log(
        f"device: platform={d0.platform} kind={d0.device_kind} count={len(devs)} "
        f"compile_cache={cache}"
    )
    if d0.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU found (platform {d0.platform})", file=sys.stderr)
        return 2
    if args.four_chips:
        four_chips(args.rehearse)
    else:
        one_chip(args.rehearse)
    log(f"all phases passed in {time.perf_counter() - t_start:.1f} s")
    print(
        json.dumps(
            {
                "ok": True,
                "device": {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)},
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
