"""Plain reference of the fleet's semantics, independent of the program.

A straightforward per-server simulation, in ticks, of what a
configuration file states: N servers of ``cores`` lanes and ``slots``
cache slots, each running SFS (a FIFO queue feeding FILTER lanes with a
slice of S ticks, S = mean inter-arrival x lanes over the last 100
arrivals, bypass to the fair-share pool when a request waited 3 x S,
demotion to the pool when its slice runs out, the pool running its
smallest ``(vruntime, rid)`` on the lanes FILTER leaves free), behind
``hash`` or ``sfs-aware`` dispatch fed the oracle ETA hint.  It imports
nothing of the program: the semantics were written down from the paper
and the repo's tick object engine, and are held here so that no later
change to the program can move them.

``control`` breaks one stated guarantee, for the check that the
comparison catches such a change:

* ``batch_dispatch`` routes a tick's arrivals against the state before
  any of them is delivered (what a one-call-per-tick router on the
  device would do), where sfs-aware promises sequential dispatch;
* ``pool_ties_by_position`` breaks vruntime ties in the pool by the
  order requests joined it instead of by rid (what a top-k over pool
  positions would do).
"""
from __future__ import annotations

import hashlib
from collections import deque

import numpy as np

CONTROLS = ("batch_dispatch", "pool_ties_by_position")
_B = 1 << 20          # field width of the packed lexicographic keys


def _hash(rid: int, salt: int) -> int:
    h = hashlib.blake2s(f"{rid}:{salt}".encode(), digest_size=4)
    return int.from_bytes(h.digest(), "little")


class _Server:
    __slots__ = ("queue", "filt", "pool", "last", "minvr", "S", "iats",
                 "last_arr", "since", "free_slots", "pending")

    def __init__(self, slots: int, slice_init: int, window: int):
        self.queue = deque()        # FILTER's FIFO queue of rids
        self.filt = []              # rids on FILTER lanes, in lane order
        self.pool = {}              # fair-share pool: rid -> join order
        self.last = []              # rids the pool ran at its last pick
        self.minvr = 0
        self.S = slice_init
        self.iats = deque(maxlen=window)
        self.last_arr = None
        self.since = 0
        self.free_slots = slots
        self.pending = deque()      # delivered, waiting for a slot


def simulate(draws: dict, cfg: dict, control: str = None) -> dict:
    """Run the fleet over the drawn requests; returns rid-ordered
    ``finish``, ``n_ctx``, ``demoted`` and per-server ``dispatch``
    counts."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    if cfg["scheduler"] != "sfs" or cfg["predictor"] != "oracle":
        raise ValueError("the reference models SFS servers under the "
                         "oracle ETA hint only")
    policy = cfg["dispatch"]
    if policy not in ("hash", "sfs-aware"):
        raise ValueError(f"the reference models hash and sfs-aware "
                         f"dispatch only, not {policy!r}")
    N, L, slots = int(cfg["servers"]), int(cfg["cores"]), int(cfg["slots"])
    window, slice_init, O = 100, 32, 3.0
    arrival = draws["arrival"].tolist()
    need = (draws["n_tokens"] + 1).tolist()   # prefill + one tick a token
    hint = draws["eta_hint"].tolist()
    n = len(arrival)
    order = sorted(range(n), key=lambda i: arrival[i])   # stable: rid ties
    served = [0] * n
    slice_left = [0] * n
    vr = [0] * n
    nctx = [0] * n
    demoted = [False] * n
    finish = [-1] * n
    qenter = [0] * n
    srv = [_Server(slots, slice_init, window) for _ in range(N)]
    dispatch = np.zeros(N, np.int64)
    ties_by_pos = control == "pool_ties_by_position"
    join = [0]                  # pool join counter (control only)

    # dispatch-visible state, one column per quantity
    out = np.zeros(N, np.int64)       # slots held + pending
    ff = np.full(N, L, np.int64)      # idle FILTER lanes net of queue
    ql = np.zeros(N, np.int64)
    fl = np.zeros(N, np.int64)        # pool size
    idx = np.arange(N, dtype=np.int64)

    def refresh(s: int):
        v = srv[s]
        out[s] = slots - v.free_slots + len(v.pending)
        q = len(v.queue)
        ql[s] = q
        ff[s] = max(0, L - len(v.filt) - q)
        fl[s] = len(v.pool)

    def to_pool(v: _Server, rid: int):
        vr[rid] = v.minvr
        join[0] += 1
        v.pool[rid] = join[0]

    def on_arrival(v: _Server, rid: int, t: int):
        if v.last_arr is not None:
            v.iats.append(t - v.last_arr)
        v.last_arr = t
        v.since += 1
        if v.since >= window and len(v.iats) == window:
            v.S = max(1, int(round(sum(v.iats) / len(v.iats) * L)))
            v.since = 0
        qenter[rid] = t
        v.queue.append(rid)

    def deliver(s: int, rid: int, t: int):
        dispatch[s] += 1
        v = srv[s]
        if v.free_slots:
            v.free_slots -= 1
            on_arrival(v, rid, t)
        else:
            v.pending.append(rid)
        refresh(s)

    # sfs-aware's cluster slice: S = mean IAT x total lanes, float
    total_lanes = N * L
    cS = [32.0]
    c_iats = deque(maxlen=window)
    c_state = {"last": None, "since": 0}

    def observe(t: int):
        if c_state["last"] is not None:
            c_iats.append(t - c_state["last"])
        c_state["last"] = t
        c_state["since"] += 1
        if c_state["since"] >= window and len(c_iats) == window:
            cS[0] = max(sum(c_iats) / len(c_iats) * total_lanes, 1e-9)
            c_state["since"] = 0

    def route_sfs(rid: int, t: int) -> int:
        observe(t)
        eta = hint[rid]
        if eta < 0 or eta <= cS[0]:
            # most idle FILTER lanes, then shortest queue, then least
            # outstanding, then lowest index
            best = int(np.argmin((-ff * _B + ql) * _B + out))
            if ff[best] == 0 and (ql[best] * cS[0] / L
                                  >= O * cS[0]):
                return int(np.argmin(out))
            return best
        # long: fewest FILTER-bound requests, then least outstanding
        return int(np.argmin((out - fl) * _B + out))

    def route_hash(rid: int, snapshot) -> int:
        a = _hash(rid, 1) % N
        b = _hash(rid, 2) % N
        if b == a:
            b = (a + 1) % N
        return a if snapshot[a] <= snapshot[b] else b

    def step(s: int, t: int):
        v = srv[s]
        while v.free_slots and v.pending:
            v.free_slots -= 1
            on_arrival(v, v.pending.popleft(), t)
        # FILTER fill from the queue, with the overload bypass
        filt, pool = v.filt, v.pool
        while len(filt) < L and v.queue:
            rid = v.queue.popleft()
            if t - qenter[rid] >= O * v.S:
                demoted[rid] = True
                to_pool(v, rid)
                continue
            slice_left[rid] = v.S
            filt.append(rid)
        free = L - len(filt)
        chosen = []
        if free > 0:
            if ties_by_pos:
                ranked = sorted(pool, key=lambda r: (vr[r], pool[r]))
            else:
                ranked = sorted(pool, key=lambda r: (vr[r], r))
            chosen = ranked[:free]
            picked = set(chosen)
            for rid in v.last:      # ran at the last pick, passed over now
                if rid in pool and rid not in picked:
                    nctx[rid] += 1
            v.last = chosen
        # end of tick: FILTER lanes in lane order, then the pool's picks
        done_here = 0
        keep = []
        for rid in filt:
            served[rid] += 1
            slice_left[rid] -= 1
            if served[rid] >= need[rid]:
                finish[rid] = t + 1
                done_here += 1
            elif slice_left[rid] <= 0:
                nctx[rid] += 1
                demoted[rid] = True
                to_pool(v, rid)
            else:
                keep.append(rid)
        v.filt = keep
        if chosen:
            fin = []
            for rid in chosen:
                served[rid] += 1
                vr[rid] += 1
                if served[rid] >= need[rid]:
                    finish[rid] = t + 1
                    fin.append(rid)
            # min_vruntime is raised to the pool's minimum after each
            # charge, in pick order, and a finished pick leaves the pool
            # right after its own charge.  Those minima only rise, so the
            # last one decides: the pool without the earlier finished
            # picks, the last pick still in it.
            tail = chosen[-1]
            for rid in fin:
                if rid != tail:
                    del pool[rid]
            m = min(vr[r] for r in pool)
            if m > v.minvr:
                v.minvr = m
            if fin and fin[-1] == tail:
                del pool[tail]
            done_here += len(fin)
        v.free_slots += done_here
        return done_here

    busy = set()
    done = 0
    i = 0
    t = 0
    while done < n:
        if not busy and i < n and arrival[order[i]] > t:
            t = arrival[order[i]]      # an idle fleet changes nothing
        j = i
        while j < n and arrival[order[j]] <= t:
            j += 1
        if j > i:
            batch = order[i:j]
            if policy == "hash":
                snap = out.copy()
                targets = [route_hash(rid, snap) for rid in batch]
            elif control == "batch_dispatch":
                targets = [route_sfs(rid, t) for rid in batch]
            else:
                targets = None
            for k, rid in enumerate(batch):
                s = (targets[k] if targets is not None
                     else route_sfs(rid, t))
                deliver(s, rid, t)
                busy.add(s)
            i = j
        for s in list(busy):        # servers are independent within a tick
            done += step(s, t)
            v = srv[s]
            refresh(s)
            if not (v.filt or v.pool or v.queue or v.pending):
                busy.discard(s)
        t += 1
    return {"finish": np.array(finish, np.int64),
            "n_ctx": np.array(nctx, np.int64),
            "demoted": np.array(demoted, bool),
            "dispatch": dispatch}
