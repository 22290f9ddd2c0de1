"""Pallas TPU kernel for the hybrid distance computation (paper §4.1 Step 1).

The paper assigns one GPU *warp* per (query, candidate) distance: threads do
vectorized float4 loads for the dense part and per-thread binary search over
CSR for the sparse intersection, with warp-shuffle reductions.

TPU has no warps, no shuffles, and hates data-dependent scalar loads, so the
kernel is re-derived for the MXU/VPU + VMEM hierarchy:

  * one grid cell = (one query) x (one C_TILE-wide tile of its candidates);
  * dense part: a (1, Dd) x (C_TILE, Dd) MXU matvec -> (1, C_TILE);
  * sparse part: fixed-nnz ELL vectors; the candidate tile is stored
    **nnz-major** (P, C_TILE) so every per-query-term step is a vectorized
    (P, C_TILE) equality-compare + masked multiply-accumulate whose reduction
    lands on the sublane axis — no transposes, no gathers, no branches;
  * the query block (dense + sparse idx/val) is VMEM-resident across all of
    its candidate tiles (BlockSpec index_map pins it per grid row) — the TPU
    analogue of the paper's shared-memory caching of the explored node.

Padding contract: ELL slots with idx == PAD_IDX carry val == 0, so padded
slots contribute exactly 0 without validity masks (query-side -1 can only
match candidate-side -1, whose value is 0).

Path weights are folded into the query beforehand (Theorem 1), making the
kernel weight-free and therefore reusable for any path combination.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_C_TILE = 128


def _make_hybrid_distance_kernel(has_scale: bool):
    """Build the distance kernel, optionally with a per-candidate dense
    dequantization scale (int8 corpus storage). The int8 rows ride the MXU
    as-is; the fp32 scale multiplies the (1, C_TILE) matvec *output*, so
    dequantization costs one VPU multiply per candidate instead of Dd."""

    def kernel(
        qd_ref,  # (1, Dd)            query dense
        qsi_ref,  # (1, Ps) int32      query learned-sparse indices
        qsv_ref,  # (1, Ps)            query learned-sparse values
        qfi_ref,  # (1, Pf) int32      query lexical-sparse indices
        qfv_ref,  # (1, Pf)            query lexical-sparse values
        cd_ref,  # (1, C_TILE, Dd)    candidate dense tile
        csi_ref,  # (1, Ps, C_TILE)    candidate learned idx (nnz-major)
        csv_ref,  # (1, Ps, C_TILE)
        cfi_ref,  # (1, Pf, C_TILE)    candidate lexical idx (nnz-major)
        cfv_ref,  # (1, Pf, C_TILE)
        *rest,  # [cscale_ref (1, C_TILE) f32 if has_scale], out_ref (1, C_TILE)
    ):
        if has_scale:
            cscale_ref, out_ref = rest
        else:
            (out_ref,) = rest
        f32 = jnp.float32

        # --- dense path: MXU matvec (1, Dd) x (C_TILE, Dd)^T -> (1, C_TILE) ---
        qd = qd_ref[...].astype(f32)  # (1, Dd)
        cd = cd_ref[0].astype(f32)  # (C_TILE, Dd)
        acc = jax.lax.dot_general(
            qd, cd, (((1,), (1,)), ((), ())), preferred_element_type=f32
        )  # (1, C_TILE)
        if has_scale:
            acc = acc * cscale_ref[...].astype(f32)  # dequant-in-tile

        # --- sparse paths: per-query-term vectorized intersection ---
        def sparse_accumulate(acc, qi_ref, qv_ref, ci_ref, cv_ref):
            qi = qi_ref[...]  # (1, P) int32
            qv = qv_ref[...].astype(f32)  # (1, P)
            ci = ci_ref[0]  # (P, C_TILE) int32
            cv = cv_ref[0].astype(f32)  # (P, C_TILE)
            n_terms = qi.shape[-1]
            for j in range(n_terms):  # static unroll over the query's nnz slots
                match = ci == qi[0, j]  # (P, C_TILE)
                contrib = jnp.where(match, cv, 0.0)  # padded slots have val 0
                acc = acc + jnp.sum(contrib, axis=0, keepdims=True) * qv[0, j]
            return acc

        acc = sparse_accumulate(acc, qsi_ref, qsv_ref, csi_ref, csv_ref)
        acc = sparse_accumulate(acc, qfi_ref, qfv_ref, cfi_ref, cfv_ref)
        out_ref[...] = acc

    return kernel


_hybrid_distance_kernel = _make_hybrid_distance_kernel(has_scale=False)


def hybrid_distance_pallas(
    qd: jax.Array,  # (B, Dd)
    qsi: jax.Array,  # (B, Ps) int32
    qsv: jax.Array,  # (B, Ps)
    qfi: jax.Array,  # (B, Pf) int32
    qfv: jax.Array,  # (B, Pf)
    cd: jax.Array,  # (B, C, Dd)
    csi: jax.Array,  # (B, Ps, C)  nnz-major
    csv: jax.Array,  # (B, Ps, C)
    cfi: jax.Array,  # (B, Pf, C)
    cfv: jax.Array,  # (B, Pf, C)
    cscale: jax.Array | None = None,  # (B, C) f32 per-candidate dense scale
    *,
    c_tile: int = DEFAULT_C_TILE,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call wrapper. C must be a multiple of c_tile (callers pad).

    When ``cscale`` is given, ``cd`` holds int8 rows and the dense matvec is
    dequantized in-tile by the per-candidate scale.

    Returns (B, C) float32 hybrid scores (higher = more similar).
    """
    b, dd = qd.shape
    _, ps = qsi.shape  # query nnz caps
    _, pf = qfi.shape
    _, cps, c = csi.shape  # candidate nnz caps (may differ from the query's)
    cpf = cfi.shape[1]
    assert c % c_tile == 0, f"C={c} not a multiple of c_tile={c_tile}"
    grid = (b, c // c_tile)

    # Per-row operands travel as (B, 1, X) with a squeezed leading block
    # dim: the kernel sees (1, X) blocks whose last two dims equal the
    # array's, which Mosaic accepts for any B (a (1, X) block of a (B, X)
    # array is refused for B > 1). Query blocks are pinned per grid row
    # (index_map ignores the candidate tile coordinate) -> VMEM-resident
    # across candidate tiles.
    q_row = lambda i, j: (i, 0, 0)
    cand3 = lambda i, j: (i, 0, j)  # (1, P, C_TILE) tiles along last dim
    dense3 = lambda i, j: (i, j, 0)  # (1, C_TILE, Dd) tiles along middle dim
    crow = lambda i, j: (i, 0, j)
    rows = lambda a: a.reshape(a.shape[0], 1, a.shape[-1])

    has_scale = cscale is not None
    in_specs = [
        pl.BlockSpec((None, 1, dd), q_row),
        pl.BlockSpec((None, 1, ps), q_row),
        pl.BlockSpec((None, 1, ps), q_row),
        pl.BlockSpec((None, 1, pf), q_row),
        pl.BlockSpec((None, 1, pf), q_row),
        pl.BlockSpec((1, c_tile, dd), dense3),
        pl.BlockSpec((1, cps, c_tile), cand3),
        pl.BlockSpec((1, cps, c_tile), cand3),
        pl.BlockSpec((1, cpf, c_tile), cand3),
        pl.BlockSpec((1, cpf, c_tile), cand3),
    ]
    operands = [rows(qd), rows(qsi), rows(qsv), rows(qfi), rows(qfv),
                cd, csi, csv, cfi, cfv]
    if has_scale:
        in_specs.append(pl.BlockSpec((None, 1, c_tile), crow))
        operands.append(rows(cscale))

    out = pl.pallas_call(
        _make_hybrid_distance_kernel(has_scale),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, 1, c_tile), crow),
        out_shape=jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
        interpret=interpret,
        name="hybrid_distance",
    )(*operands)
    return out.reshape(b, c)
