"""The comparison that decides ``correct``: answers and index state.

Every number here is "larger is worse" and is held to a limit:

  unanswered   requests or probes that never got an answer         (exact, 0)
  bad_rows     answers with fewer than k ids, unsorted scores or a
               repeated id                                          (exact, 0)
  dead_ids     returned ids that were not live when the query ran  (exact, 0)
  score_gap    largest |returned score - reference score of that
               id| over the rounding scale of the terms it sums     (limit)
  miss_rate    1 - recall@k against the exact top-k over the live
               set at the query's point (ties at the k-th score
               count as hits)                                       (limit)
  lost_inserts insert acknowledgements that are NULL, repeated, or a
               slot that still holds a live row                     (exact, 0)
  alive_diff   slots whose ``alive`` flag differs from the
               benchmark's own record of acknowledged ops           (exact, 0)
  edge_faults  graph invariants read back after the window: edges
               to non-live or own slots, repeated edges, a reverse
               graph that is not the transpose of the forward one,
               tombstones left by a hard delete                     (exact, 0)

The limits that are not exact come from the configuration's ``limits``
(set from readings of sound runs and of the control, PERF.md).
"""
from __future__ import annotations

import numpy as np

from harness import reference


def answers(corpus, queries, ids, rows, scores, live, k: int, metric: str
            ) -> tuple[dict, np.ndarray]:
    """Judge answered queries against the reference over the live set.

    ``ids`` i64[Q, k]: the ids the program returned (-1 = NULL); ``rows``
    i64[Q, k]: the corpus row each id held when the query ran, by the
    benchmark's own record (-1 where it held none); ``scores`` f32[Q, k] as
    returned; ``live`` bool[N]: the live rows at that point. Returns the
    numbers and the per-query recall."""
    q = np.asarray(queries, np.float32)
    ids = np.asarray(ids, np.int64)[:, :k]
    rows = np.asarray(rows, np.int64)[:, :k]
    scores = np.asarray(scores, np.float32)[:, :k]
    valid = ids >= 0
    ok = valid & (rows >= 0) & np.asarray(live, bool)[np.maximum(rows, 0)]
    ref = reference.scores_of(corpus, q, np.where(ok, rows, -1), metric)
    scale = reference.rounding_scale(corpus, q, rows, metric)
    gap = np.where(ok, np.abs(scores.astype(np.float64) - ref) / scale, 0.0)
    best, _ = reference.exact_topk(corpus, live, q, k, metric)
    # a returned row is a hit when its exact score reaches the k-th best;
    # the tolerance covers the two reference computations' rounding only
    hit = ok & (ref >= best[:, -1:] - 1e-6 * scale) & ~_dup_mask(rows)
    floor = np.finfo(np.float32).min   # NULL entries sort last
    unsorted = np.diff(np.where(valid, scores, floor), axis=1) > 0
    bad = (~valid).any(axis=1) | unsorted.any(axis=1) | _repeats(ids)
    return ({"bad_rows": int(bad.sum()),
             "dead_ids": int((valid & ~ok).sum()),
             "score_gap": float(gap.max(initial=0.0)),
             "hits": int(hit.sum()),
             "slots": int(k * q.shape[0])}, hit.sum(axis=1) / k)


def _dup_mask(rows: np.ndarray) -> np.ndarray:
    """True at a valid entry whose row already appeared earlier in its
    answer."""
    eq = (rows[:, :, None] == rows[:, None, :]) & (rows[:, None, :] >= 0)
    earlier = np.tril(np.ones((rows.shape[1],) * 2, bool), -1)
    return (eq & earlier[None]).any(axis=2)


def _repeats(rows: np.ndarray) -> np.ndarray:
    return _dup_mask(rows).any(axis=1)


def repeats(ids: np.ndarray) -> int:
    """How many entries of ``ids`` repeat an earlier one."""
    return int(ids.size - np.unique(ids).size)


def graph(adj: np.ndarray, radj: np.ndarray, alive: np.ndarray,
          present: np.ndarray, expect_alive: np.ndarray) -> dict:
    """Index state after the window against the benchmark's own record."""
    adj = np.asarray(adj, np.int64)
    radj = np.asarray(radj, np.int64)
    alive = np.asarray(alive, bool)
    present = np.asarray(present, bool)
    cap = alive.shape[0]
    faults = int((present != alive).sum())   # hard deletes leave no tombstone
    u = np.repeat(np.arange(cap), adj.shape[1]).reshape(adj.shape)
    e = (adj >= 0) & present[:, None]
    faults += int((adj[~present[:, None].repeat(adj.shape[1], 1)] >= 0).sum())
    faults += int((e & ~alive[np.maximum(adj, 0)]).sum())
    faults += int((e & (adj == u)).sum())
    faults += int(_dup_mask(np.where(e, adj, -1)).sum())
    fwd = np.unique(u[e] * cap + adj[e])
    v = np.repeat(np.arange(cap), radj.shape[1]).reshape(radj.shape)
    r = radj >= 0
    rev = radj[r] * cap + v[r]
    faults += int(rev.size - np.unique(rev).size)
    faults += int(np.setxor1d(fwd, np.unique(rev)).size)
    return {"alive_diff": int((alive != np.asarray(expect_alive, bool)).sum()),
            "edge_faults": faults}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) — exact numbers have limit 0."""
    out, ok = {}, True
    for name, value in numbers.items():
        lim = limits.get(name, 0)
        out[name] = {"value": value, "limit": lim}
        ok &= bool(value <= lim)
    return ok, out
