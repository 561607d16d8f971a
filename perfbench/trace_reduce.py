"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer readers take: the device's busy time (the union of the
intervals in which an operation ran), the traced window, each op's
summed time and number of events, and the longest idle gaps labelled by
what the host was doing.

Device planes are the ``/device:TPU:<n>`` planes; their ``XLA Ops`` line
holds one event per executed HLO op.  The window is the benchmark's own
host span ``WINDOW_SPAN`` when the trace holds it, else the extent of
the device events.
"""
from __future__ import annotations

import glob
import os

WINDOW_SPAN = "perfbench.traced_experiment"
OPS_LINE = "XLA Ops"
TOP = 10


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals):
    """Total length of the union of ``(start, end)`` intervals, and the
    gaps between the merged runs as ``(start, end)``."""
    total = 0.0
    gaps = []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s > cur_e:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def _host_spans(planes):
    """``(start, end, name)`` of every host event with a duration."""
    out = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.duration_ns > 0:
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return out


def _label(spans, t):
    """Name of the shortest host span that covers time ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "no host span"


def short_name(op: str) -> str:
    """``%fusion.12 = s32[...] fusion(...)`` -> ``fusion.12``: an XLA Ops
    event is named by its whole HLO instruction."""
    head = op.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def reduce_profile(data) -> dict:
    """``data`` is a ``jax.profiler.ProfileData``.  Returns seconds:
    ``busy_s`` (mean over device planes), ``window_s``, ``chips``,
    ``ops`` ({HLO instruction: summed seconds}), ``calls``
    ({instruction: events}), ``device_ops`` and ``idle_gaps`` (the
    breakdown's two lists, longest first; ops by short name)."""
    planes = list(data.planes)
    spans = _host_spans(planes)
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    per_dev = []
    for p in devices:
        for line in p.lines:
            if line.name == OPS_LINE:
                per_dev.append([(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events])
    if not per_dev or not any(per_dev):
        return {"chips": len(devices), "busy_s": 0.0, "window_s": 0.0,
                "ops": {}, "calls": {}, "device_ops": [], "idle_gaps": []}
    if win:
        w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    else:
        w0 = min(s for evs in per_dev for s, *_ in evs)
        w1 = max(e for evs in per_dev for _, e, *_ in evs)
    busy = []
    gaps = []
    ops: dict = {}
    calls: dict = {}
    for evs in per_dev:
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in evs
                  if e > w0 and s < w1]
        total, g = _union([(s, e) for s, e, _ in inside])
        busy.append(total)
        if inside:
            first = min(s for s, *_ in inside)
            last = max(e for _, e, *_ in inside)
            g = [(w0, first)] + g + [(last, w1)]
        else:
            g = [(w0, w1)]
        gaps.extend(x for x in g if x[1] > x[0])
        for s, e, n in inside:
            ops[n] = ops.get(n, 0.0) + (e - s) * 1e-9
            calls[n] = calls.get(n, 0) + 1
    gaps.sort(key=lambda x: x[0] - x[1])
    idle = [[_label(spans, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps[:TOP]]
    by_short: dict = {}
    for n, v in ops.items():
        k = short_name(n)
        by_short[k] = by_short.get(k, 0.0) + v
    device_ops = sorted(([n, v] for n, v in by_short.items()),
                        key=lambda x: -x[1])[:TOP]
    return {"chips": len(per_dev),
            "busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (w1 - w0) * 1e-9,
            "ops": ops, "calls": calls,
            "device_ops": device_ops, "idle_gaps": idle}


def reduce_file(path: str) -> dict:
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(path))
