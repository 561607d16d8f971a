"""Plain reference of the fleet's semantics with I/O stalls, independent
of the program.

The fleet of ``sfs_fleet`` (N servers of ``cores`` lanes and ``slots``
cache slots running SFS behind ``hash`` or ``sfs-aware`` dispatch fed
the oracle ETA hint), simulated per server in ticks, with the I/O
handling of the SFS paper (arXiv:2209.01709, Sec. V-D) as the repo's
tick object engine states it.  A request's ``stall_events`` are
``(tokens_done, ticks)`` pairs, taken in order:

* a request that ran this tick, did not finish and has served its
  prefill and at least ``tokens_done`` decode ticks parks at the end of
  the tick (one more lane reassignment) and wakes at the start of tick
  ``t + 1 + ticks``; it keeps its slot all the while;
* parking from a FILTER lane keeps the unused slice; a request whose
  slice ran out in the same tick is demoted first (one reassignment)
  and parks from the pool (another);
* the tick's wakes come after its arrivals, in the order the requests
  took their slots: a FILTER request re-enters the FIFO queue (its
  wait for the overload bypass counting from the wake), a demoted one
  the pool at ``max(vruntime, min_vruntime)``;
* the pool's ``min_vruntime`` rises after each pick's charge, over the
  pool as it stands then: the picks before it that finished or parked
  are gone, the pick itself is still there.

It imports nothing of the program; from ``sfs_fleet`` it takes the
dispatch hash and the per-server record.  ``MODELS``, ``KNOBS`` and
``simulate(draws, cfg, control=None)`` as in ``sfs_fleet``.

``control = "io_oblivious"`` breaks the §V-D guarantee the way the
paper's Fig. 11 baseline does: the slice keeps running through the
stall, and a FILTER request whose slice ran out while it was stalled is
demoted when it wakes.
"""
from __future__ import annotations

from collections import deque

import numpy as np

from perfbench.references.sfs_fleet import _B, _Server, _hash

CONTROLS = ("io_oblivious",)
MODELS = {"scheduler": ("sfs",), "dispatch": ("hash", "sfs-aware"),
          "predictor": ("oracle",)}
KNOBS = ()


class _IOServer(_Server):
    __slots__ = ("parked", "taken")

    def __init__(self, slots: int, slice_init: int, window: int):
        super().__init__(slots, slice_init, window)
        self.parked = {}            # rid -> wake tick
        self.taken = 0              # slots handed out so far


def simulate(draws: dict, cfg: dict, control: str = None) -> dict:
    """Run the fleet over the drawn requests; returns rid-ordered
    ``finish``, ``n_ctx``, ``demoted`` and per-server ``dispatch``
    counts."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    for key, values in MODELS.items():
        if cfg[key] not in values:
            raise ValueError(f"the reference models {key} {values}, not "
                             f"{cfg[key]!r}")
    oblivious = control == "io_oblivious"
    policy = cfg["dispatch"]
    N, L, slots = int(cfg["servers"]), int(cfg["cores"]), int(cfg["slots"])
    window, slice_init, O = 100, 32, 3.0
    arrival = draws["arrival"].tolist()
    need = (draws["n_tokens"] + 1).tolist()   # prefill + one tick a token
    hint = draws["eta_hint"].tolist()
    n = len(arrival)
    events = (list(draws["stall_events"]) if "stall_events" in draws
              else [()] * n)
    order = sorted(range(n), key=lambda i: arrival[i])   # stable: rid ties
    served = [0] * n
    slice_left = [0] * n            # 0: no slice left (or none yet)
    vr = [0] * n
    nctx = [0] * n
    demoted = [False] * n
    finish = [-1] * n
    qenter = [0] * n
    sti = [0] * n                   # stall events taken
    seq = [0] * n                   # when the request took its slot
    stalled_for = [0] * n           # ticks of its last stall
    srv = [_IOServer(slots, slice_init, window) for _ in range(N)]
    dispatch = np.zeros(N, np.int64)

    # dispatch-visible state, one column per quantity
    out = np.zeros(N, np.int64)       # slots held (parked too) + pending
    ff = np.full(N, L, np.int64)      # idle FILTER lanes net of queue
    ql = np.zeros(N, np.int64)
    fl = np.zeros(N, np.int64)        # pool size, parked requests out

    def refresh(s: int):
        v = srv[s]
        out[s] = slots - v.free_slots + len(v.pending)
        q = len(v.queue)
        ql[s] = q
        ff[s] = max(0, L - len(v.filt) - q)
        fl[s] = len(v.pool)

    def to_pool(v: _IOServer, rid: int):
        vr[rid] = v.minvr
        v.pool.add(rid)

    def on_arrival(v: _IOServer, rid: int, t: int):
        seq[rid] = v.taken
        v.taken += 1
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
            best = int(np.argmin((-ff * _B + ql) * _B + out))
            if ff[best] == 0 and (ql[best] * cS[0] / L
                                  >= O * cS[0]):
                return int(np.argmin(out))
            return best
        return int(np.argmin((out - fl) * _B + out))

    def route_hash(rid: int) -> int:
        a = _hash(rid, 1) % N
        b = _hash(rid, 2) % N
        if b == a:
            b = (a + 1) % N
        return a if out[a] <= out[b] else b

    def parks(v: _IOServer, rid: int, t: int) -> bool:
        """Park ``rid`` if it reached its next stall threshold."""
        ev = events[rid]
        k = sti[rid]
        if k >= len(ev) or served[rid] - 1 < ev[k][0]:
            return False
        sti[rid] = k + 1
        nctx[rid] += 1
        stalled_for[rid] = ev[k][1]
        v.parked[rid] = t + 1 + ev[k][1]
        return True

    def step(s: int, t: int):
        v = srv[s]
        while v.free_slots and v.pending:
            v.free_slots -= 1
            on_arrival(v, v.pending.popleft(), t)
        # wakes, after the arrivals, in the order of the slots
        if v.parked:
            woke = sorted((rid for rid, w in v.parked.items() if w == t),
                          key=seq.__getitem__)
            for rid in woke:
                del v.parked[rid]
                if oblivious and not demoted[rid]:
                    slice_left[rid] -= stalled_for[rid]
                    if slice_left[rid] <= 0:
                        demoted[rid] = True
                if demoted[rid]:
                    vr[rid] = max(vr[rid], v.minvr)
                    v.pool.add(rid)
                else:
                    qenter[rid] = t
                    v.queue.append(rid)
        # FILTER fill from the queue, with the overload bypass
        filt, pool = v.filt, v.pool
        while len(filt) < L and v.queue:
            rid = v.queue.popleft()
            if t - qenter[rid] >= O * v.S:
                demoted[rid] = True
                to_pool(v, rid)
                continue
            if slice_left[rid] <= 0:
                slice_left[rid] = v.S
            filt.append(rid)
        free = L - len(filt)
        chosen = []
        if free > 0:
            chosen = sorted(pool, key=lambda r: (vr[r], r))[:free]
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
                continue
            out_of_slice = slice_left[rid] <= 0
            if out_of_slice:
                nctx[rid] += 1
                demoted[rid] = True
                to_pool(v, rid)
            if parks(v, rid, t):
                if out_of_slice:
                    pool.remove(rid)
            elif not out_of_slice:
                keep.append(rid)
        v.filt = keep
        if chosen:
            left = []
            for rid in chosen:
                served[rid] += 1
                vr[rid] += 1
                if served[rid] >= need[rid]:
                    finish[rid] = t + 1
                    left.append(rid)
                    done_here += 1
                elif parks(v, rid, t):
                    left.append(rid)
            # min_vruntime rises to the pool's minimum after each
            # charge, in pick order, and a pick that finished or parked
            # leaves the pool right after its own charge.  Those minima
            # only rise, so the last one decides: the pool without the
            # earlier picks that left, the last pick still in it.
            tail = chosen[-1]
            for rid in left:
                if rid != tail:
                    pool.remove(rid)
            m = min(vr[r] for r in pool)
            if m > v.minvr:
                v.minvr = m
            if left and left[-1] == tail:
                pool.remove(tail)
        v.free_slots += done_here
        return done_here

    def route(rid: int, t: int) -> int:
        return (route_hash(rid) if policy == "hash"
                else route_sfs(rid, t))

    sequential = policy != "hash"
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
            targets = (None if sequential
                       else [route(rid, t) for rid in batch])
            for k, rid in enumerate(batch):
                s = targets[k] if targets is not None else route(rid, t)
                deliver(s, rid, t)
                busy.add(s)
            i = j
        for s in list(busy):        # servers are independent within a tick
            done += step(s, t)
            v = srv[s]
            refresh(s)
            if not (v.filt or v.pool or v.queue or v.pending or v.parked):
                busy.discard(s)
        t += 1
    return {"finish": np.array(finish, np.int64),
            "n_ctx": np.array(nctx, np.int64),
            "demoted": np.array(demoted, bool),
            "dispatch": dispatch}
