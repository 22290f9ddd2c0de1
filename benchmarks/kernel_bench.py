"""Fused top-k kernel sweep: measured fused-vs-unfused latency + bytes model.

Sweeps the fused selection kernel over (C_TILE, K, expand) and times the two
expansion-round strategies end to end on the current backend:

  unfused : hybrid_scores_vs_ids -> (B, C) scores in HBM -> lax.top_k
  fused   : fused_topk_vs_ids    -> (B, K_pad) ids+scores, selection in VMEM

Per pair it reports µs/candidate-pair, the fused/unfused ratio, the modeled
HBM bytes for both strategies (the fused path must eliminate the (B, C) score
round-trip — gated exactly in check_regression.py), modeled selection-lane
utilization (k / k_pad), and the analytic TPU roofline of the fused kernel.

Results land in results/BENCH_kernel.json; the committed baseline is
results/BENCH_kernel_baseline.json (regenerate with --dry-run to match CI).

    PYTHONPATH=src python benchmarks/kernel_bench.py [--dry-run] [--out PATH]
"""

from __future__ import annotations

import pathlib
import sys

if __package__ in (None, ""):  # script mode: python benchmarks/kernel_bench.py
    _root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]

import argparse
import functools
import json

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.usms import quantize_corpus
from repro.kernels import ops, ref
from repro.kernels.fused_topk import k_pad
from repro.launch.hlo_analysis import MODELED_CHIP, chip_peaks
from repro.runtime.compile_cache import enable_compile_cache
from tests.helpers import random_fused

from benchmarks.common import timed

C_TILES = (128, 256)
KS = (10, 32, 64)
EXPANDS = (1, 4)

RESULTS = pathlib.Path(__file__).resolve().parents[1] / "results" / "BENCH_kernel.json"


@functools.partial(jax.jit, static_argnames=("k", "c_tile", "use_kernel"))
def _unfused(q, corpus, ids, k, c_tile, use_kernel):
    scores = ops.hybrid_scores_vs_ids(
        q, corpus, ids, c_tile=c_tile, use_kernel=use_kernel
    )
    return jax.lax.top_k(scores, k)


def _bytes_model(*, b, c, dd, ps, pf, k, c_tile, bpe):
    """Modeled HBM traffic for one expansion round, both strategies.

    Inputs (queries + gathered candidate tiles) are identical; the strategies
    differ only in what crosses HBM after scoring: unfused writes the full
    (B, C_pad) score matrix and top_k reads it back, fused writes only the
    (B, K_pad) winner lanes.
    """
    c_pad = -(-c // c_tile) * c_tile
    kp = k_pad(k)
    vec_bytes = dd * bpe + ps * 8 + pf * 8  # dense + two ELL (idx i32 + val f32)
    # quantized storage: int8 dense + 4-byte per-row scale, ELL ids stay
    # int32 but vals drop to fp16; the query side stays fp32
    vec_bytes_q = dd * 1 + 4 + ps * 6 + pf * 6
    inputs = b * vec_bytes + b * c_pad * (vec_bytes + 4)  # +4: candidate id lane
    inputs_q = b * vec_bytes + b * c_pad * (vec_bytes_q + 4)
    score_roundtrip = 2 * b * c_pad * 4  # write (B, C_pad) f32, top_k reads it back
    unfused = inputs + score_roundtrip + b * k * 8
    fused = inputs + b * kp * 8
    quantized = inputs_q + b * kp * 8  # fused selection over int8 storage
    return {
        "bytes_unfused": unfused,
        "bytes_fused": fused,
        "bytes_quantized": quantized,
        "score_roundtrip_bytes": score_roundtrip,
        "bytes_saved_ratio": round(1.0 - fused / unfused, 4),
        "quantized_saved_ratio": round(1.0 - quantized / fused, 4),
        "k_pad": kp,
        "lane_util_selection": round(k / kp, 4),
        "lane_util_candidates": round(c / c_pad, 4),
    }


def _roofline(*, b, c, dd, ps, pf, c_tile, bytes_fused):
    c_pad = -(-c // c_tile) * c_tile
    flops = b * c_pad * (2 * dd + 3 * ps * ps + 3 * pf * pf)
    peaks = chip_peaks(MODELED_CHIP)
    compute_s = flops / peaks.flops_bf16
    memory_s = bytes_fused / peaks.hbm_bw
    return {
        "model_flops": flops,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": 0.0,
        "dominant": "memory" if memory_s > compute_s else "compute",
    }


def run(dry_run: bool = False) -> dict:
    use_kernel = ops.resolve_use_kernel(None)
    if dry_run:
        b, w, dd, ps, pf, n_corpus = 2, 64, 32, 8, 4, 256
        vs, vf = 997, 251
    else:
        b, w, dd, ps, pf, n_corpus = 8, 256, 256, 64, 32, 4096
        vs, vf = 30522, 8192

    rng = np.random.default_rng(0)
    corpus = random_fused(rng, (n_corpus,), d_dense=dd, ps=ps, pf=pf, vs=vs, vf=vf)
    corpus_q = quantize_corpus(corpus)
    q = random_fused(rng, (b,), d_dense=dd, ps=ps, pf=pf, vs=vs, vf=vf)
    bpe = jnp.dtype(corpus.dense.dtype).itemsize

    sweep = {}
    for c_tile in C_TILES:
        for expand in EXPANDS:
            c = expand * w  # multi-node batching: `expand` nodes' tiles stacked
            ids = jnp.asarray(
                rng.integers(0, n_corpus, size=(b, c), dtype=np.int32)
            )
            for k in KS:
                k_eff = min(k, c)
                _, t_unfused = timed(
                    lambda: jax.block_until_ready(
                        _unfused(q, corpus, ids, k_eff, c_tile, use_kernel)
                    )
                )
                _, t_fused = timed(
                    lambda: jax.block_until_ready(
                        ops.fused_topk_vs_ids(
                            q, corpus, ids, k_eff, c_tile=c_tile, use_kernel=use_kernel
                        )
                    )
                )
                _, t_quant = timed(
                    lambda: jax.block_until_ready(
                        ops.fused_topk_vs_ids(
                            q, corpus_q, ids, k_eff, c_tile=c_tile, use_kernel=use_kernel
                        )
                    )
                )
                n_pairs = b * c
                model = _bytes_model(
                    b=b, c=c, dd=dd, ps=ps, pf=pf, k=k_eff, c_tile=c_tile, bpe=bpe
                )
                row = {
                    "c_tile": c_tile,
                    "k": k,
                    "expand": expand,
                    "n_candidates": c,
                    "unfused_us_per_pair": round(t_unfused * 1e6 / n_pairs, 4),
                    "fused_us_per_pair": round(t_fused * 1e6 / n_pairs, 4),
                    "quantized_us_per_pair": round(t_quant * 1e6 / n_pairs, 4),
                    "fused_ratio": round(t_fused / t_unfused, 4),
                    "quantized_ratio": round(t_quant / t_fused, 4),
                    "model": model,
                    "roofline": _roofline(
                        b=b, c=c, dd=dd, ps=ps, pf=pf, c_tile=c_tile,
                        bytes_fused=model["bytes_fused"],
                    ),
                }
                sweep[f"c{c_tile}_k{k}_e{expand}"] = row

    out = {
        "config": {
            "backend": jax.default_backend(),
            "use_kernel": use_kernel,
            "dry_run": dry_run,
            "b": b,
            "nbr_width": w,
            "d_dense": dd,
            "ps": ps,
            "pf": pf,
            "n_corpus": n_corpus,
        },
        "sweep": sweep,
    }

    if dry_run:
        # CI smoke: the Pallas kernel (interpret) must agree with the oracle.
        ids_s = jnp.asarray(rng.integers(0, n_corpus, size=(2, 96), dtype=np.int32))
        ks, ki = ops.fused_topk_vs_ids(
            q[:2] if b >= 2 else q, corpus, ids_s, 10,
            c_tile=32, use_kernel=True, interpret=True,
        )
        cands = jax.tree.map(
            lambda a: a.reshape((2, 96) + a.shape[1:]),
            corpus.take(ids_s.reshape(-1)),
        )
        ws, wi = ref.fused_topk_ref(q[:2] if b >= 2 else q, cands, ids_s, None, 10)
        # scores agree up to summation order (MXU dot vs oracle einsum);
        # positions agree exactly except across float-ulp ties
        np.testing.assert_allclose(
            np.asarray(ks), np.asarray(ws), rtol=1e-5, atol=1e-5,
            err_msg="fused != oracle",
        )
        flip = np.asarray(ki) != np.asarray(wi)
        assert np.all(
            np.abs(np.asarray(ks) - np.asarray(ws))[flip] < 1e-4
        ), "fused != oracle (pos beyond tie tolerance)"
        out["interpret_check"] = "ok"

        # same smoke over quantized storage: the dequant-in-tile kernel
        # (interpret) must agree with the scale-after-dot oracle
        qs, qi = ops.fused_topk_vs_ids(
            q[:2] if b >= 2 else q, corpus_q, ids_s, 10,
            c_tile=32, use_kernel=True, interpret=True,
        )
        cands_q = jax.tree.map(
            lambda a: a.reshape((2, 96) + a.shape[1:]),
            corpus_q.take(ids_s.reshape(-1)),
        )
        zs, zi = ref.fused_topk_quant_ref(
            q[:2] if b >= 2 else q, cands_q, ids_s, None, 10
        )
        np.testing.assert_allclose(
            np.asarray(qs), np.asarray(zs), rtol=1e-5, atol=1e-5,
            err_msg="quantized fused != oracle",
        )
        flip = np.asarray(qi) != np.asarray(zi)
        assert np.all(
            np.abs(np.asarray(qs) - np.asarray(zs))[flip] < 1e-4
        ), "quantized fused != oracle (pos beyond tie tolerance)"
        out["interpret_check_quantized"] = "ok"

    out["quantized"] = run_quantized_recall()
    return out


def run_quantized_recall() -> dict:
    """Recall@10 of quantized-traversal + full-precision-rescore vs the fp32
    index on the bundled ingest corpus — the committed floor the quantized
    gate enforces, plus the search_padded trace accounting (corpus dtype is
    a treedef property: one trace per storage type, zero extra on repeats)."""
    import dataclasses as _dc

    from repro.core import BuildConfig, KnnConfig, PruneConfig
    from repro.core.fusion import FusionSpec
    from repro.core.search import SearchParams, search, search_padded_trace_count
    from repro.core.usms import quantize_corpus as _quant
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
    index_q = _dc.replace(index, corpus=_quant(index.corpus))
    enc = pipe.encode_queries(corpus.query_texts)
    truth = topic_truth(corpus.query_topics, corpus.topics)
    spec = FusionSpec.weighted(1.0, 1.0, 1.0)
    params = SearchParams(k=10, iters=48, pool_size=64)
    params_q = _dc.replace(params, corpus_dtype="int8")

    traces0 = search_padded_trace_count()
    r32 = recall_at_k(
        np.asarray(search(index, enc.vectors, spec, params).ids), truth
    )
    r8 = recall_at_k(
        np.asarray(search(index_q, enc.vectors, spec, params_q).ids), truth
    )
    traces_first = search_padded_trace_count() - traces0
    # repeats on both storage types must hit the existing traces
    search(index, enc.vectors, spec, params)
    search(index_q, enc.vectors, spec, params_q)
    traces_repeat = search_padded_trace_count() - traces0 - traces_first
    return {
        "n_docs": len(corpus.texts),
        "n_queries": len(corpus.query_texts),
        "recall_at_10_fp32": float(r32),
        "recall_at_10_int8": float(r8),
        "recall_drop": float(r32 - r8),
        "sweep_traces": int(traces_first),
        "repeat_traces": int(traces_repeat),
    }


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--dry-run", action="store_true",
        help="tiny shapes + interpret-mode equality check (CI smoke)",
    )
    ap.add_argument("--out", type=pathlib.Path, default=RESULTS)
    args = ap.parse_args()

    out = run(dry_run=args.dry_run)
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(out, indent=2) + "\n")

    print("pair,unfused_us_per_pair,fused_us_per_pair,ratio,lane_util,bytes_saved")
    for name, row in out["sweep"].items():
        print(
            f"{name},{row['unfused_us_per_pair']:.3f},{row['fused_us_per_pair']:.3f},"
            f"{row['fused_ratio']:.3f},{row['model']['lane_util_selection']:.3f},"
            f"{row['model']['bytes_saved_ratio']:.3f}"
        )
    if "interpret_check" in out:
        print(f"interpret_check,{out['interpret_check']}")
    if "interpret_check_quantized" in out:
        print(f"interpret_check_quantized,{out['interpret_check_quantized']}")
    qz = out["quantized"]
    print(
        f"quantized_recall,fp32={qz['recall_at_10_fp32']:.3f},"
        f"int8={qz['recall_at_10_int8']:.3f},traces={qz['sweep_traces']},"
        f"repeat_traces={qz['repeat_traces']}"
    )
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
