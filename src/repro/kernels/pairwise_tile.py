"""Pallas TPU kernel for candidate-pairwise score tiles (paper §4.1 Step 2).

RNG-IP joint pruning needs, for every node u, the full (K, K) hybrid-score
matrix among u's K candidates: detour counting reads sim(v_i, v_j) for every
pair and the IP keep-scan reads IP(w, v) against already-kept candidates.
The GPU paper evaluates those pairs with one warp per (v_i, v_j); the naive
TPU port materialized the candidate rows K times — `corpus.take` over a
(C*K, K) id matrix gathers C*K*K fused rows per chunk.

This kernel removes the re-gather: the caller gathers each node's K candidate
rows ONCE, and every grid cell computes one node's (K, K) tile from a single
VMEM-resident copy of those rows:

  * grid = (C,), one cell per node in the chunk;
  * dense part: a (K, Dd) x (K, Dd)^T MXU matmul -> (K, K);
  * sparse parts: candidate rows are passed twice — row-major (K, P) as the
    "query side" and nnz-major (P, K) as the "candidate side" (the same
    layout trick as hybrid_distance.py). A static unroll over the P query
    slots does a vectorized (K, P, K) equality-compare + masked
    multiply-accumulate per slot, so the pair intersection needs no gathers
    and no branches;
  * the padding contract is inherited from the ELL layout: idx == PAD_IDX
    slots carry val == 0, so padded slots contribute exactly 0. Masking of
    *invalid candidates* (cand_ids < 0) stays in the caller, which knows the
    id list; the kernel only ever sees gathered rows.

Symmetry note: scores are computed for all (i, j) pairs, not just i < j —
the IP keep rule needs the full matrix, and the MXU produces it for free.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pairwise_tile_kernel(
    d_ref,  # (1, K, Dd)         candidate dense rows
    si_ref,  # (1, K, Ps) int32   learned-sparse idx (row-major)
    sv_ref,  # (1, K, Ps)         learned-sparse val (row-major)
    fi_ref,  # (1, K, Pf) int32   lexical idx (row-major)
    fv_ref,  # (1, K, Pf)         lexical val (row-major)
    tsi_ref,  # (1, Ps, K) int32   learned idx (nnz-major)
    tsv_ref,  # (1, Ps, K)
    tfi_ref,  # (1, Pf, K) int32   lexical idx (nnz-major)
    tfv_ref,  # (1, Pf, K)
    out_ref,  # (1, K, K) f32
):
    f32 = jnp.float32

    # --- dense path: (K, Dd) x (K, Dd)^T on the MXU -> (K, K) ---
    d = d_ref[0].astype(f32)
    acc = jax.lax.dot_general(
        d, d, (((1,), (1,)), ((), ())), preferred_element_type=f32
    )

    # --- sparse paths: per-slot vectorized intersection over the tile ---
    def sparse_accumulate(acc, qi_ref, qv_ref, ci_ref, cv_ref):
        qi = qi_ref[0]  # (K, P) int32  "query side" rows
        qv = qv_ref[0].astype(f32)  # (K, P)
        ci = ci_ref[0]  # (P, K) int32  same rows, nnz-major
        cv = cv_ref[0].astype(f32)  # (P, K)
        n_slots = qi.shape[-1]
        for p in range(n_slots):  # static unroll over nnz slots
            qip = qi[:, p]  # (K,)
            match = ci[None, :, :] == qip[:, None, None]  # (K, P, K)
            contrib = jnp.where(match, cv[None, :, :], 0.0)
            acc = acc + contrib.sum(axis=1) * qv[:, p][:, None]
        return acc

    acc = sparse_accumulate(acc, si_ref, sv_ref, tsi_ref, tsv_ref)
    acc = sparse_accumulate(acc, fi_ref, fv_ref, tfi_ref, tfv_ref)
    out_ref[0] = acc


def pairwise_tile_pallas(
    d: jax.Array,  # (C, K, Dd)
    si: jax.Array,  # (C, K, Ps) int32
    sv: jax.Array,  # (C, K, Ps)
    fi: jax.Array,  # (C, K, Pf) int32
    fv: jax.Array,  # (C, K, Pf)
    *,
    interpret: bool = False,
) -> jax.Array:
    """All-pairs hybrid scores within each node's candidate tile.

    Returns (C, K, K) float32 with out[c, i, j] = score(row i, row j) of
    node c's gathered candidate rows. No validity masking — callers mask.
    """
    c, k, dd = d.shape
    ps = si.shape[-1]
    pf = fi.shape[-1]
    tsi = jnp.swapaxes(si, 1, 2)  # (C, Ps, K) nnz-major views
    tsv = jnp.swapaxes(sv, 1, 2)
    tfi = jnp.swapaxes(fi, 1, 2)
    tfv = jnp.swapaxes(fv, 1, 2)

    cell = lambda i: (i, 0, 0)
    return pl.pallas_call(
        _pairwise_tile_kernel,
        grid=(c,),
        in_specs=[
            pl.BlockSpec((1, k, dd), cell),
            pl.BlockSpec((1, k, ps), cell),
            pl.BlockSpec((1, k, ps), cell),
            pl.BlockSpec((1, k, pf), cell),
            pl.BlockSpec((1, k, pf), cell),
            pl.BlockSpec((1, ps, k), cell),
            pl.BlockSpec((1, ps, k), cell),
            pl.BlockSpec((1, pf, k), cell),
            pl.BlockSpec((1, pf, k), cell),
        ],
        out_specs=pl.BlockSpec((1, k, k), cell),
        out_shape=jax.ShapeDtypeStruct((c, k, k), jnp.float32),
        interpret=interpret,
        name="pairwise_tile",
    )(d, si, sv, fi, fv, tsi, tsv, tfi, tfv)
