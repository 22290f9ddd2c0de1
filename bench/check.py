"""What decides ``correct``: the answers the client received against the
plain reference (``bench/reference.py``).

Each number compared has a limit of its own:

* ``dense_gap`` and ``sparse_gap``: the widest gap between a score the
  client received and the reference's float64 score of the same passage,
  over every answer and every returned passage. ``sparse_gap`` takes the
  learned-sparse and lexical per-path scores, which the kernels compute
  exactly in float32 on the vector unit; ``dense_gap`` takes the dense
  per-path score and the fused score (weighted-sum and z-score modes; a
  rank-fusion score depends on the whole candidate pool, not on the pair),
  which ride the matrix unit at the TPU's default precision (bfloat16
  operands, float32 accumulation). Each gap is measured against the size a
  rounding error of that score has, the Cauchy-Schwarz bound
  ``||q_p|| * ||d_p||`` of the path's inner product. An altered passage id
  shows in both: the reference scores the id the client got. Each limit
  lies between the program's widest gap on sound runs and the control's
  smallest (``bench/control.py``); ``PERF.md`` gives the readings.
* ``keyword_misses``: returned passages that hold none of the request's
  required keywords. Exact: limit 0.
* ``bad_answers``: answers with an id outside the served passages, an id
  twice, a PAD slot before a live one, or scores out of order. Exact:
  limit 0.
* ``unanswered``: requests due in the window that never got an answer, or
  got an error. Exact: limit 0.
* ``recall_at_10``: the served top-10 of the weighted-sum requests against
  the exact reference's top-10 over the whole corpus. The scores above say
  that each returned passage is scored right; this says that the search
  traversal found the passages it should, so a traversal cut short or a
  broken neighbour list fails it. The recall a sound traversal reaches
  depends on the corpus and the search breadth, so its limit, a floor, is
  the configuration's ``check.recall_at_10_min``, set from chip readings of
  sound runs and of a planted traversal fault at that configuration's size.
"""

from __future__ import annotations

import numpy as np

from bench import reference

LIMITS = {
    "dense_gap": 1e-3,
    "sparse_gap": 1e-5,
    "keyword_misses": 0,
    "bad_answers": 0,
    "unanswered": 0,
}


def _bad(ids: np.ndarray, scores: np.ndarray, lo: int, hi: int) -> np.ndarray:
    live = ids >= 0
    out_of_range = (live & ((ids < lo) | (ids >= hi))).any(axis=1)
    srt = np.sort(np.where(live, ids, -1 - np.arange(ids.shape[1])), axis=1)
    dup = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    pad_first = (~live[:, :-1] & live[:, 1:]).any(axis=1)
    s = np.where(live, scores, -np.inf)
    unordered = (s[:, 1:] > s[:, :-1]).any(axis=1) | (live & ~np.isfinite(scores)).any(axis=1)
    return out_of_range | dup | pad_first | unordered


def compare(answers: dict, corpus_host: dict, id_range: tuple, stats: dict,
            n_unanswered: int) -> dict:
    """The numbers compared. ``answers`` holds arrays over the M answers:
    ``qrow`` (M,), ``mode`` (M,) str, ``weights`` (M, 3), ``keywords``
    (M, R) PAD-padded, ``ids`` (M, k), ``scores`` (M, k), ``path`` (M, k, 3).
    ``corpus_host`` holds the plain passages and queries as numpy tuples."""
    ids = answers["ids"]
    lo, hi = id_range
    numbers = {
        "dense_gap": 0.0,
        "sparse_gap": 0.0,
        "keyword_misses": 0,
        "bad_answers": int(_bad(ids, answers["scores"], lo, hi).sum()) if len(ids) else 0,
        "unanswered": int(n_unanswered),
    }
    if not len(ids):
        return numbers
    ok_ids = np.where((ids >= lo) & (ids < hi), ids, -1)
    ref_path, scale = reference.pair_path_scores(
        corpus_host["queries"], corpus_host["passages"], answers["qrow"], ok_ids
    )
    live = ok_ids >= 0
    rel = lambda gap, size: np.where(size > 0, gap / np.maximum(size, 1e-300), gap)
    path_gap = rel(np.abs(answers["path"] - ref_path), scale)  # (M, k, 3)
    dense = [path_gap[..., 0][live]]
    for mode in (reference.WEIGHTED_SUM, reference.ZSCORE):
        rows = answers["mode"] == mode
        if not rows.any():
            continue
        fused, size = reference.fused_scores(
            ref_path[rows], scale[rows], mode, answers["weights"][rows][:, None, :], stats
        )
        dense.append(rel(np.abs(answers["scores"][rows] - fused), size)[live[rows]])
    # a served id outside the passages has no reference score: it is a bad
    # answer, and its gap counts as the largest there is
    outside = np.where((ids >= 0) & ~live, np.inf, 0.0).ravel()
    numbers["dense_gap"] = float(max(g.max(initial=0.0) for g in dense + [outside]))
    numbers["sparse_gap"] = float(max(path_gap[..., 1:][live].max(initial=0.0), outside.max(initial=0.0)))
    kw_ok = reference.keyword_ok(corpus_host["passages"][3], ok_ids, answers["keywords"])
    numbers["keyword_misses"] = int((~kw_ok & live).sum())
    return numbers


def verdict(numbers: dict, cfg_check: dict) -> tuple:
    """(correct, {name: {"value", "limit", "fails"}}) with every number
    beside its limit; ``fails`` says on which side of it a number fails:
    ``above`` for the gaps and counts, ``below`` for recall's floor (the
    configuration's ``check.recall_at_10_min``). A number that is not a
    number fails."""
    limits = {k: (v, "above") for k, v in LIMITS.items()}
    limits["recall_at_10"] = (cfg_check["recall_at_10_min"], "below")
    checks = {}
    ok = True
    for name, value in numbers.items():
        limit, fails = limits[name]
        passed = bool(np.isfinite(value)) and (value <= limit if fails == "above" else value >= limit)
        ok &= passed
        checks[name] = {"value": value, "limit": limit, "fails": fails}
    return ok, checks
