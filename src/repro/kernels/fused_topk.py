"""Pallas TPU kernel fusing the hybrid distance with top-k selection.

The unfused hot path (kernels/hybrid_distance.py + a separate
``jax.lax.top_k``) ships the full ``(B, C)`` score matrix back to HBM between
the distance kernel and the selection — the candidate round-trip the paper's
warp-level kernel avoids by selecting in registers. This kernel keeps a
running per-query top-k *inside* the grid row:

  * grid = (B, C // C_TILE), candidate-tile axis innermost, so all of one
    query's tiles run back-to-back;
  * the distance tile is computed exactly as in ``_hybrid_distance_kernel``
    (MXU matvec for the dense path, nnz-major vectorized ELL intersection
    for the two sparse paths), then biased and validity-masked in place;
  * the ``(1, K_PAD)`` output blocks are pinned per grid row (their index
    map ignores the tile coordinate), so Mosaic keeps them VMEM-resident
    across a row's tiles — they double as the top-k accumulator: initialized
    at tile 0, merged with each tile's scores, written back to HBM only
    once per row. Nothing of size C ever leaves the kernel;
  * K is padded to ``K_PAD`` (a multiple of the 128-lane tile) so the
    accumulator is lane-aligned; only the first ``k`` slots are live, the
    rest stay at (NEG, PAD_IDX) and are sliced off by the wrapper;
  * selection payloads are candidate *positions* (j * C_TILE + lane), not
    ids: the caller holds the id list plus any per-candidate metadata
    (entity/hop state in the beam search) and gathers everything from the
    ``(B, k)`` position output — the kernel stays metadata-free;
  * multi-node batching falls out of the layout: the caller stacks an
    entire expansion round (all ``expand`` nodes' neighbor lists) into one
    candidate axis, so the pinned query block amortizes over every node's
    tiles in a single kernel invocation.

The merge itself is ``k`` unrolled max-extraction steps over the
``(1, K_PAD + C_TILE)`` concatenation of the accumulator and the current
tile: each step takes the max, records (value, position-payload) into lane
``t`` via a masked select, and retires the winning lane. That is k * O(few)
VPU ops per tile — noise next to the MXU matvec — and needs no sort network
or data-dependent control flow. Ties resolve to the lowest position (the
same preference as ``lax.top_k``), so fused and oracle agree up to the order
of equal scores.

Padding contract (shared with hybrid_distance.py): ELL slots with
idx == PAD_IDX carry val == 0; candidate slots with id PAD_IDX (and any
wrapper-added C padding) score exactly NEG and can never be selected while a
live candidate remains.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.hybrid_distance import DEFAULT_C_TILE

NEG = -1e30  # matches core.search.NEG: "no candidate" score sentinel
PAD_IDX = -1  # matches core.usms.PAD_IDX (not imported: kernels stay leaf)
K_LANE = 128  # TPU lane tile: the accumulator width granularity


def k_pad(k: int) -> int:
    """K rounded up to the 128-lane tile (the accumulator lane rule)."""
    if k <= 0:
        raise ValueError(f"top-k needs k >= 1, got {k}")
    return -(-k // K_LANE) * K_LANE


def _distance_tile(qd_ref, qsi_ref, qsv_ref, qfi_ref, qfv_ref,
                   cd_ref, csi_ref, csv_ref, cfi_ref, cfv_ref,
                   cscale_ref=None):
    """One (1, C_TILE) hybrid-distance tile — identical math to
    ``hybrid_distance._hybrid_distance_kernel``. A non-None ``cscale_ref``
    dequantizes int8 dense rows by the per-candidate scale after the MXU
    matvec (one VPU multiply per candidate)."""
    f32 = jnp.float32
    qd = qd_ref[...].astype(f32)  # (1, Dd)
    cd = cd_ref[0].astype(f32)  # (C_TILE, Dd)
    acc = jax.lax.dot_general(
        qd, cd, (((1,), (1,)), ((), ())), preferred_element_type=f32
    )  # (1, C_TILE)
    if cscale_ref is not None:
        acc = acc * cscale_ref[...].astype(f32)  # dequant-in-tile

    def sparse_accumulate(acc, qi_ref, qv_ref, ci_ref, cv_ref):
        qi = qi_ref[...]  # (1, P) int32
        qv = qv_ref[...].astype(f32)  # (1, P)
        ci = ci_ref[0]  # (P, C_TILE) int32
        cv = cv_ref[0].astype(f32)  # (P, C_TILE)
        for j in range(qi.shape[-1]):  # static unroll over query nnz slots
            match = ci == qi[0, j]
            contrib = jnp.where(match, cv, 0.0)
            acc = acc + jnp.sum(contrib, axis=0, keepdims=True) * qv[0, j]
        return acc

    acc = sparse_accumulate(acc, qsi_ref, qsv_ref, csi_ref, csv_ref)
    return sparse_accumulate(acc, qfi_ref, qfv_ref, cfi_ref, cfv_ref)


def _merge_topk_lanes(acc_s, acc_i, tile_s, tile_i, k: int):
    """Merge a (1, K_PAD) running top-k with a (1, C_TILE) tile: k unrolled
    max-extraction steps over the lane-axis concatenation. Returns the new
    (1, K_PAD) accumulator (slots >= k stay at NEG / PAD_IDX)."""
    kp = acc_s.shape[-1]
    comb_s = jnp.concatenate([acc_s, tile_s], axis=-1)  # (1, K_PAD + C_TILE)
    comb_i = jnp.concatenate([acc_i, tile_i], axis=-1)
    m_total = comb_s.shape[-1]
    miota = jax.lax.broadcasted_iota(jnp.int32, comb_s.shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, acc_s.shape, 1)
    res_s = jnp.full(acc_s.shape, NEG, jnp.float32)
    res_i = jnp.full(acc_i.shape, PAD_IDX, jnp.int32)
    for t in range(min(k, kp)):
        m = jnp.max(comb_s, axis=-1, keepdims=True)  # (1, 1)
        # lowest position achieving the max: lax.top_k's tie preference
        hit = (comb_s == m) & (comb_s > NEG)
        pos = jnp.min(jnp.where(hit, miota, m_total), axis=-1, keepdims=True)
        win = miota == pos  # at most one lane
        payload = jnp.sum(
            jnp.where(win, comb_i, 0), axis=-1, keepdims=True
        )
        res_s = jnp.where(lane == t, m, res_s)
        res_i = jnp.where((lane == t) & (m > NEG), payload, res_i)
        comb_s = jnp.where(win, NEG, comb_s)  # retire the winner
    return res_s, res_i


def _make_fused_topk_kernel(k: int, c_tile: int, has_bias: bool,
                            has_scale: bool = False):
    def kernel(*refs):
        refs = list(refs)
        qd, qsi, qsv, qfi, qfv, cd, csi, csv, cfi, cfv, cid_ref = refs[:11]
        rest = refs[11:]
        bias_ref = rest.pop(0) if has_bias else None
        cscale_ref = rest.pop(0) if has_scale else None
        out_s_ref, out_i_ref = rest
        j = pl.program_id(1)

        # the output blocks are this row's accumulator (index map pins them
        # per grid row): seed them on the row's first tile
        @pl.when(j == 0)
        def _init():
            out_s_ref[...] = jnp.full(out_s_ref.shape, NEG, jnp.float32)
            out_i_ref[...] = jnp.full(out_i_ref.shape, PAD_IDX, jnp.int32)

        scores = _distance_tile(qd, qsi, qsv, qfi, qfv, cd, csi, csv,
                                cfi, cfv, cscale_ref)
        if bias_ref is not None:
            scores = scores + bias_ref[...].astype(jnp.float32)
        cids = cid_ref[...]  # (1, C_TILE) candidate ids (validity only)
        scores = jnp.where(cids >= 0, scores, NEG)
        tile_pos = j * c_tile + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        )
        new_s, new_i = _merge_topk_lanes(
            out_s_ref[...], out_i_ref[...], scores, tile_pos, k
        )
        out_s_ref[...] = new_s
        out_i_ref[...] = new_i

    return kernel


def fused_topk_pallas(
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
    cid: jax.Array,  # (B, C) int32 candidate ids (PAD_IDX = invalid slot)
    bias: jax.Array | None,  # (B, C) f32 per-candidate score bias, or None
    cscale: jax.Array | None = None,  # (B, C) f32 per-candidate dense scale
    *,
    k: int,
    c_tile: int = DEFAULT_C_TILE,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw pallas_call wrapper. C must be a multiple of c_tile (callers pad).

    When ``cscale`` is given, ``cd`` holds int8 rows and the dense matvec is
    dequantized in-tile by the per-candidate scale.

    Returns ``(scores, positions)`` of shape (B, K_PAD): per query the top-k
    candidate scores (descending) and their positions along the C axis.
    Slots beyond k — and slots with no live candidate — hold (NEG, PAD_IDX).
    """
    b, dd = qd.shape
    _, ps = qsi.shape  # query nnz caps
    _, pf = qfi.shape
    _, cps, c = csi.shape  # candidate nnz caps (may differ from the query's)
    cpf = cfi.shape[1]
    assert c % c_tile == 0, f"C={c} not a multiple of c_tile={c_tile}"
    kp = k_pad(k)
    grid = (b, c // c_tile)

    # per-row operands as (B, 1, X) with a squeezed leading block dim — the
    # (1, X) block then equals the array's last two dims, which Mosaic
    # accepts for any B (see hybrid_distance_pallas)
    q_row = lambda i, j: (i, 0, 0)
    cand3 = lambda i, j: (i, 0, j)
    dense3 = lambda i, j: (i, j, 0)
    crow = lambda i, j: (i, 0, j)
    rows = lambda a: a.reshape(a.shape[0], 1, a.shape[-1])

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
        pl.BlockSpec((None, 1, c_tile), crow),
    ]
    args = [rows(qd), rows(qsi), rows(qsv), rows(qfi), rows(qfv),
            cd, csi, csv, cfi, cfv, rows(cid)]
    if bias is not None:
        in_specs.append(pl.BlockSpec((None, 1, c_tile), crow))
        args.append(rows(bias))
    if cscale is not None:
        in_specs.append(pl.BlockSpec((None, 1, c_tile), crow))
        args.append(rows(cscale))

    out_s, out_i = pl.pallas_call(
        _make_fused_topk_kernel(k, c_tile, bias is not None,
                                cscale is not None),
        grid=grid,
        in_specs=in_specs,
        # both outputs pinned per grid row -> VMEM-resident accumulators
        out_specs=[
            pl.BlockSpec((None, 1, kp), q_row),
            pl.BlockSpec((None, 1, kp), q_row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, kp), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, kp), jnp.int32),
        ],
        interpret=interpret,
    )(*args)
    return out_s.reshape(b, kp), out_i.reshape(b, kp)
