"""The comparison that decides ``correct``.

Served answers are held against the reference (``harness.reference``)
query by query.  Two numbers are compared, each with its limit:

* ``mi_gap``: the widest gap between a served MI and the reference's
  MI for the same (query, column) pair.  Both sides form the same
  float32 distances and the same integer ball counts, so they differ
  only in the summation order of the final means; the control (the
  reference in bfloat16) moves counts and reads far wider.
* ``wrong``: served entries the reference refuses (a join size that is
  not the exact one, a column below ``min_join``, a list out of order
  or longer than ``top_k``), lists shorter than the columns that pass
  allow, and passing columns the list misses among those whose MI the
  reference estimated (a column whose MI beats the served list's last
  entry by more than the ``mi_gap`` limit): on the answers checked in
  full, every column or the mix's seeded sample of them.  Exact: limit 0.

The reference's answers are indexed by column number.
"""

from __future__ import annotations

import numpy as np

# Limits, from the readings recorded in PERF.md (section 2).
LIMITS = {"mi_gap": 1e-3, "wrong": 0}


def column_of(meta) -> int:
    """Column number of a served entry's candidate (table ``c<number>``)."""
    return int(getattr(meta, "table", meta)[1:])


def _index(ans, c: int):
    """Position of column ``c`` in the answer's arrays (None for no
    column of the lake)."""
    return c if 0 <= c < len(ans.js) else None


def _errors(served: list, ans, *, top_k: int, min_join: int,
            tol: float) -> int:
    errors = max(0, len(served) - top_k)
    prev = np.inf
    seen = []
    for c, mi, js in served:
        i = _index(ans, c)
        if i is None:           # no column of the lake
            errors += 1
            continue
        seen.append(i)
        if ans.ambiguous[i]:
            continue
        if js != ans.js[i] or ans.js[i] < min_join:
            errors += 1
            continue
        if mi > prev + tol:
            errors += 1
        prev = mi
    sure = (ans.js >= min_join) & ~ans.ambiguous
    errors += max(0, min(top_k, int(sure.sum())) - len(served))
    # Passing columns the list misses, among those whose MI the answer
    # holds (the served ones alone, a seeded sample, or all).
    cand = sure & ~np.isnan(ans.mi)
    cand[seen] = False
    if len(served) >= top_k:
        floor = min(mi for _, mi, _ in served)
        cand &= ans.mi > floor + tol
    return errors + int(cand.sum())


def check_answer(served: list, ans, *, top_k: int,
                 min_join: int) -> tuple[float, int]:
    """(mi_gap, wrong) of one served answer: ``served`` is the list of
    (candidate, mi, join size) the service returned."""
    items = [(column_of(t), float(mi), int(js)) for t, mi, js in served]
    gap = 0.0
    for c, mi, _ in items:
        i = _index(ans, c)
        if i is not None and not ans.ambiguous[i] and not np.isnan(ans.mi[i]):
            gap = max(gap, abs(mi - float(ans.mi[i])))
    wrong = _errors(items, ans, top_k=top_k, min_join=min_join,
                    tol=LIMITS["mi_gap"])
    return gap, wrong


def verdict(mi_gap: float, wrong: int) -> tuple[bool, dict]:
    checks = {
        "mi_gap": {"value": float(mi_gap), "limit": LIMITS["mi_gap"]},
        "wrong": {"value": int(wrong), "limit": LIMITS["wrong"]},
    }
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
