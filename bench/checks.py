"""The comparison that decides ``correct``: served answers against the reference.

Every answer a client received in the window is compared with the exact
fp32 top-K of its query (`bench.reference.exact_answers`).  The numbers,
each held to a limit of the configuration's file (``limits``):

* ``unanswered``: requests of the window that never got an answer, or got
  a refusal or failure instead (limit 0);
* ``bad_ids``: answers holding a row outside ``[0, n_valid)`` or the same
  row twice (limit 0);
* ``score_rel_err``: the largest relative gap between a served score and
  the exact mean product of the row it names.  The program states exact
  fp32 scores (``final_exact``); an answer altered after it was scored, a
  row id mapped wrongly, or a lower precision shows here;
* ``top1_miss_share``: the share of answers that do not hold the query's
  exact top-1 row.  Each query leans on one row (`bench.loadgen`), so a
  sound cascade finds it; K rows from the wrong place, or from one shard
  of four, do not;
* ``eps_share``: the share of answers whose top-K suboptimality stays
  within ``eps * value_range``, the (eps, delta) guarantee; its limit is
  the configuration's ``1 - delta`` and it has to reach it.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

#: compared numbers whose value has to stay at or above the limit
AT_LEAST = ("eps_share",)


def compare(served_ids: np.ndarray, served_scores: np.ndarray,
            top_ids: np.ndarray, top_scores: np.ndarray,
            served_exact: np.ndarray, *, n_valid: int, eps: float,
            value_range: float, unanswered: int) -> Dict[str, float]:
    """The compared numbers of one run (see the module docstring).

    ``served_*`` are (B, K) for the B answered requests; ``top_*`` the
    exact top-K of the same queries; ``served_exact`` the exact scores of
    the served ids (``nan`` where an id is out of range).
    """
    B, K = served_ids.shape
    in_range = (served_ids >= 0) & (served_ids < n_valid)
    repeated = np.array([len(set(r.tolist())) < K for r in served_ids],
                        bool) if B else np.zeros(0, bool)
    bad = int(np.sum(~in_range.all(axis=1) | repeated))
    ok = in_range.all(axis=1)
    if ok.any():
        ex = served_exact[ok].astype(np.float64)
        got = served_scores[ok].astype(np.float64)
        rel = np.abs(got - ex) / np.maximum(np.abs(ex), 1e-30)
        score_err = float(np.max(rel))
    else:
        score_err = float("inf") if B else 0.0
    miss = (float(np.mean([top_ids[i, 0] not in served_ids[i]
                           for i in range(B)])) if B else 1.0)
    # the (eps, delta) guarantee: the j-th best served row is within
    # eps * range of the j-th best row, for every j
    exact_sorted = -np.sort(-np.where(np.isnan(served_exact), -np.inf,
                                      served_exact), axis=1)
    sub = np.max(top_scores - exact_sorted, axis=1) if B else np.zeros(0)
    eps_share = float(np.mean(sub <= eps * value_range)) if B else 0.0
    return {"unanswered": int(unanswered), "bad_ids": bad,
            "score_rel_err": score_err, "top1_miss_share": miss,
            "eps_share": eps_share}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True iff every number keeps to its limit."""
    for name, value in numbers.items():
        lim = limits[name]
        if name in AT_LEAST:
            if not value >= lim:
                return False
        elif not value <= lim:
            return False
    return True


def report(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """``{name: {"value", "limit"}}`` in a fixed order, for printing."""
    return {name: {"value": numbers[name], "limit": limits[name]}
            for name in numbers}
