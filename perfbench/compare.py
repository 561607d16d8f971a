"""The comparison that decides ``correct``: one experiment of the window,
drawn from the seed, against the plain reference (``reference.py``) on
the same requests.

Every number is a count of differences and the semantics are exact, so
every limit is 0 (the readings each was set from are in PERF.md):

* ``wrong_requests`` — requests whose finish tick, lane reassignments
  (``n_ctx``) or demotion differ from the reference, or that the program
  did not return (a shed request is left out of the program's result, so
  a shed shows here);
* ``wrong_dispatch`` — servers whose count of dispatched requests
  differs (the program's result has no per-request server; per-server
  counts are what it reports).
"""
from __future__ import annotations

import numpy as np

LIMITS = {"wrong_requests": 0, "wrong_dispatch": 0}


def answers(res) -> dict:
    """What the comparison reads from one ``ExperimentResult``."""
    return {"rids": np.asarray(res.rids, np.int64),
            "finish": np.asarray(res.finish, np.int64),
            "n_ctx": np.asarray(res.n_ctx, np.int64),
            "demoted": np.asarray(res.demoted, bool),
            "dispatch": np.asarray(res.dispatch_counts, np.int64)}


def compare(got: dict, ref: dict) -> dict:
    """``{name: {"value": n, "limit": l}}`` for every number compared."""
    n = len(ref["finish"])
    rids = got["rids"]
    ok = np.zeros(n, bool)
    valid = (rids >= 0) & (rids < n)
    r = rids[valid]
    same = ((got["finish"][valid] == ref["finish"][r])
            & (got["n_ctx"][valid] == ref["n_ctx"][r])
            & (got["demoted"][valid] == ref["demoted"][r]))
    # a rid returned twice counts once right at most
    ok[r[same]] = True
    if len(np.unique(r)) != len(r):
        dup = np.bincount(r, minlength=n) > 1
        ok[dup] = False
    d_got, d_ref = got["dispatch"], ref["dispatch"]
    if d_got.shape == d_ref.shape:
        wrong_dispatch = int((d_got != d_ref).sum())
    else:
        wrong_dispatch = len(d_ref)
    values = {"wrong_requests": int(n - ok.sum()),
              "wrong_dispatch": wrong_dispatch}
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
