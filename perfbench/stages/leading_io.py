"""Traffic stage: one leading I/O per request, as in the SFS paper's
"Handling I/O" experiment (arXiv:2209.01709, Sec. VIII-B, Fig. 11):
a share ``fraction`` of the requests starts with one blocking I/O of
``io_ms`` milliseconds, drawn uniform on ``io_ms = [low, high)``.

In ticks of ``tick_ms`` milliseconds the I/O is a stall event
``(0, ceil(ms / tick_ms))``: the request parks once its first tick has
run, before its first decode tick, and blocks for that many ticks.
Requests without I/O get ``()``.  The column is the program's
``Request.stall_events``, a numpy object array of ``n`` tuples.
"""
from __future__ import annotations

import numpy as np

PARAMS = ("fraction", "io_ms", "tick_ms")
COLUMNS = ("stall_events",)


def apply(draws: dict, params: dict, rng) -> dict:
    n = len(draws["arrival"])
    has = rng.random(n) < float(params["fraction"])
    lo, hi = params["io_ms"]
    ms = rng.uniform(float(lo), float(hi), n)
    ticks = np.ceil(ms / float(params["tick_ms"])).astype(np.int64)
    col = np.empty(n, dtype=object)
    for i, (h, k) in enumerate(zip(has.tolist(), ticks.tolist())):
        col[i] = ((0, k),) if h else ()
    draws["stall_events"] = col
    return draws
