"""Share of its roofline the ``group_pick`` Pallas kernel reaches on the
device, in percent: the least time its calls could take, their bytes
(``kernels.group_pick_bytes`` of each call's shape) at the chip's HBM
peak, over the kernel's summed device time in the profile.  Bytes-bound:
the kernel's work is int32 compares, for which the v5e publishes no
peak.

The kernel's XLA op is the custom call the pick's jitted wrapper
(``pick_order_pallas``) or a ``pallas_call`` named after the kernel
lowers to; its result ``s32[G,kmax]`` and first operand ``s32[G,CAP]``
give the call's shape."""
import re

from perfbench.kernels import group_pick_bytes
from perfbench.trace_reduce import short_name

NAMES = ("pick_order_pallas", "group_pick", "_pick_kernel")
_SHAPE = re.compile(r" = s32\[(\d+),(\d+)\]\S* "
                    r"custom-call\(s32\[(\d+),(\d+)\]")


def calls(trace):
    """``(seconds, events, G, CAP, kmax)`` of each traced op of the
    kernel."""
    out = []
    for op, sec in trace["ops"].items():
        if not any(k in short_name(op) for k in NAMES):
            continue
        m = _SHAPE.search(op)
        if m and m.group(1) == m.group(3):
            out.append((sec, trace["calls"][op], int(m.group(3)),
                        int(m.group(4)), int(m.group(2))))
    return out


def read(run):
    tr, peaks = run["trace"], run["peaks"]
    if not tr or not peaks:
        return None
    found = calls(tr)
    seconds = sum(f[0] for f in found)
    if not found or seconds <= 0:
        return None
    nbytes = sum(n * group_pick_bytes(G, CAP, kmax)
                 for _, n, G, CAP, kmax in found)
    return nbytes / peaks["hbm_bytes_per_s"] / seconds * 100.0
