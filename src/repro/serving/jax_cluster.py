"""JAX-compiled cluster stepping — homogeneous engine groups as jitted
array programs (``ExperimentSpec(engine="jax")``).

The numpy vector backend (:mod:`repro.serving.vector_cluster`) advances
a group with ~60 separate array kernels per tick plus Python fill/admit
loops; at 1024 engines the per-tick interpreter overhead dominates the
sweep budget.  This module ports the *stepping* — levels 2-1, the
FILTER/CFS machinery over a whole homogeneous group — into a single
jitted tick body (XLA fuses the whole step), with two multi-tick fast
paths driven by the host:

* **closed-form gap advance** — when no event can occur before the next
  arrival or completion (lanes full or queue empty per engine, and each
  fair-share pool either fits its free lanes or cannot run), ``g`` ticks
  collapse into one ``O(1)``-depth update: ``served/slice_left/vruntime
  += g`` plus the monotone ``min_vruntime`` recurrence, which telescopes
  to a max against the final pool minimum.
* **``lax.scan`` chunks** — arrival-free windows where the pool rotates
  (``pool > free lanes``) step ``CHUNK`` ticks inside one compiled scan,
  emitting per-tick completion events into a fixed small buffer; a
  buffer overflow rolls the chunk back (no donation on this path) and
  replays it tick by tick.

All device state is int32 — every quantity the scheduler tracks is an
integer below 2^31 (vruntime charges are +1 per tick, so it stays
integer-valued; the float column in ``_RequestStore`` is populated from
the integer at write-back).  Per-request state travels *with* the
request through region arrays (queue ring -> FILTER lanes -> fair-share
pool); completions emit the full field tuple, so the host never keeps
per-request device columns.

The inner fair-share pick (per-group k-smallest ``(vruntime, rid)``)
goes through :func:`repro.kernels.group_pick.pick_order`, which routes
to a Pallas kernel on TPU and a sort-free iterative argmin elsewhere
(XLA:CPU lowers ``sort`` to a scalar comparator loop);
:func:`repro.kernels.group_pick.pick_impl` names the one compiled in.

**Bit-exactness.**  The step reproduces the vector group's per-tick
semantics operation for operation, so an ``engine="jax"`` run equals
``engine="vector"`` (and therefore ``engine="tick"``) bit for bit —
asserted across backends in ``tests/test_agreement.py``.  Level 3
(dispatch, predictors, the central pull queue) is the shared
:class:`~repro.serving.cluster.ClusterFrontend`, untouched.

**Stall events** (§V-D I/O parking) run in the jitted step too, in a
variant a group compiles only once a request with stall events reaches
it (:func:`_tick_core`'s ``stl``); a fleet that never stalls lowers the
same programs as without it.  They are bit-exact with
``engine="tick"`` (the vector backend refuses them).

Not supported here (build raises): real-model decoding, per-server
object-engine pinning — pin those runs to the ``vector`` or ``tick``
backends instead.
"""
from __future__ import annotations

from collections import deque
from functools import lru_cache, partial
from itertools import repeat
from typing import Optional, Sequence

import numpy as np

from repro.core.dispatch import (BoundedTimeline, HashDispatch,
                                 ServerStateColumns, ServerView,
                                 SFSAwareDispatch, observe_instant)
from repro.core.spec import ServerSpec
from repro.serving.cluster import ClusterConfig, ClusterFrontend
from repro.serving.request import Request
from repro.serving.vector_cluster import (_SFS_KW, VECTOR_POLICIES,
                                          _RequestStore)

_IMAX = 2 ** 31 - 1

# field layouts of the region arrays (see module docstring)
_QROW, _QRID, _QNTOK, _QENT = range(4)                       # queue ring
_NQ = 4
(_LROW, _LRID, _LNTOK, _LSRV, _LSLC, _LQD, _LFS,
 _LQE) = range(8)                                            # FILTER lanes
_NL = 8
(_PROW, _PRID, _PNTOK, _PSRV, _PVR, _PNCTX, _PQD, _PFS, _PQE, _PFLG,
 _PSLC) = range(11)                                          # CFS pool
_NP = 11
(_EKEY, _EROW, _ESRV, _ENCTX, _EQD, _EFS, _EQE, _EVR, _EFLG,
 _ESLC) = range(10)                                          # events
_NE = 10
_AENG, _AKIND, _AROW, _ARID, _ANTOK, _APOS = range(6)        # arrivals
_NA = 6
# stall layout, compiled only for a group that has received a request
# with stall events: queue rows carry the lane history a woken request
# brings back, lanes its lane reassignments, and the stalled region
# holds parked rows in the pool layout plus their wake tick.  Every
# region row (and arrival row) then ends in the stall tail
# ``[sti, seq, thr_0, dur_0, ..., thr_{ME-1}, dur_{ME-1}]``: events
# consumed, slot-acquisition order (wakes re-enter in it), and the
# request's (tokens_done threshold, stall ticks) pairs, padded with
# thresholds of _IMAX.
_QSRV, _QSLC, _QQD, _QFS, _QNCTX, _QFLG = range(4, 10)       # queue
_NQS = 10
_LNCTX = 8                                                   # lanes
_NLS = 9
_SWAKE = 11                                                  # stalled
_NSS = 12
_ESTI = 10                                                   # events
_NES = 11

_SCAN_CHUNK = 64          # ticks per lax.scan dispatch
_SCAN_EVCAP_MAX = 4096    # per-tick completion buffer cap inside a chunk


def _scan_evcap(G: int, L: int, sfs: bool) -> int:
    """Per-tick completion buffer inside a scan chunk.  At fleet scale
    hundreds of engines finish in the same drain tick, and an overflow
    throws away a whole computed chunk — so size for the worst burst
    (every lane and every chosen pool slot, ``(2|1) * G * L``) up to a
    cap that keeps the buffer a few MB; past the cap the overflow/abort
    path below stays the correctness net."""
    return min((2 if sfs else 1) * G * L, _SCAN_EVCAP_MAX)

_STATE_KEYS = ("q", "qh", "qn", "lanes", "lc", "pool", "pc", "minvr",
               "last")
_STALL_KEYS = ("stl", "sc")      # stalled region and its depth


def _tail(stl) -> int:
    """Width of the stall tail for the static stall config ``stl`` =
    ``(SCAP, ME, stall_aware)`` (0 without stalls)."""
    return 0 if stl is None else 2 + 2 * stl[1]


def state_shapes(G: int, L: int, QCAP: int, CAP: int, stl=None) -> dict:
    """Shapes of one group's int32 device state, keyed like
    ``_STATE_KEYS`` (and ``_STALL_KEYS`` under a stall config)."""
    if stl is None:
        return dict(q=(G, QCAP, _NQ), qh=(G,), qn=(G,), lanes=(G, L, _NL),
                    lc=(G,), pool=(G, CAP, _NP), pc=(G,), minvr=(G,),
                    last=(G, L))
    T = _tail(stl)
    return dict(q=(G, QCAP, _NQS + T), qh=(G,), qn=(G,),
                lanes=(G, L, _NLS + T), lc=(G,), pool=(G, CAP, _NP + T),
                pc=(G,), minvr=(G,), last=(G, L),
                stl=(G, stl[0], _NSS + T), sc=(G,))


def step_arg_specs(G: int, L: int, QCAP: int, CAP: int, ACAP: int,
                   sharding=None, stl=None) -> tuple:
    """Shape/dtype structs of ``(state, arr, t, S, thr)``, the
    arguments of the jitted group step, for compiling it without arrays
    (on a described device, or to inspect the compiled program)."""
    import jax
    import jax.numpy as jnp

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    state = {k: spec(*s)
             for k, s in state_shapes(G, L, QCAP, CAP, stl).items()}
    return state, spec(ACAP, _NA + _tail(stl)), spec(), spec(G), spec(G)


def _cur_event(tail, ME: int):
    """The (threshold, ticks) of each row's next stall event, from its
    stall tail (threshold _IMAX once every event is consumed)."""
    import jax.numpy as jnp
    sti = tail[..., 0]
    thr = jnp.full(sti.shape, _IMAX, jnp.int32)
    dur = jnp.zeros(sti.shape, jnp.int32)
    for e in range(ME):
        hit = sti == e
        thr = jnp.where(hit, tail[..., 2 + 2 * e], thr)
        dur = jnp.where(hit, tail[..., 3 + 2 * e], dur)
    return thr, dur


def _park_dist(thr, srv):
    """Ticks until a row serving every tick reaches its next stall
    threshold (``tokens_done = srv - 1``), at least 1; _IMAX if none."""
    import jax.numpy as jnp
    return jnp.where(thr < _IMAX,
                     jnp.maximum(jnp.minimum(thr, _IMAX - 1) + 1 - srv, 1),
                     _IMAX)


def _tick_core(G, L, QCAP, CAP, sfs, evcap, trace, state, arr, t, S, thr,
               stl=None):
    """One tick of a G-engine homogeneous group, pure int32 array ops.

    Mirrors ``_VectorGroup.tick`` operation for operation: arrival
    scatter (positions precomputed on the host), FILTER fill with the
    ``O x S`` bypass as a cumulative-sum prefix, the batched fair-share
    pick, run/finish/demote, stable lane compaction, pool compaction,
    the monotone ``min_vruntime`` collapse, and key-sorted completion
    events (key = engine * 2L + lane for FILTER, + L + rank for CFS —
    the object cluster's replay order).

    ``trace`` (static) additionally returns the store rows touched by
    the intra-tick lifecycle transitions (FILTER admit, O x S bypass,
    slice-expiry demotion, fair-share displacement) as -1-padded masks,
    so the host can reconstruct the same lifecycle events the object
    and vector backends emit inline (core/telemetry.py) — the masks are
    captured *before* lane/pool compaction overwrites the rows.

    ``stl`` (static, ``(SCAP, ME, stall_aware)`` or None) compiles the
    object engine's stall events (§V-D) in: after the arrivals, rows
    whose wake tick is ``t`` leave the stalled region in slot order, a
    FILTER row to the queue's tail (``queue_enter = t``, its unused
    slice kept), a demoted row to the pool at ``max(vr, minvr)``; at
    tick end a row that ran, did not finish and reached its next stall
    threshold parks (``n_ctx + 1``, wake at ``t + 1 + ticks``), after
    its own slice-expiry demotion.
    """
    import jax.numpy as jnp

    from repro.kernels.group_pick import pick_order

    q, qh, qn, lanes, lc, pool, pc, minvr, last = (state[k]
                                                   for k in _STATE_KEYS)
    gi = jnp.arange(G, dtype=jnp.int32)
    il = jnp.arange(L, dtype=jnp.int32)
    ic = jnp.arange(CAP, dtype=jnp.int32)
    A = arr.shape[0]
    one32 = jnp.int32(1)
    stall = stl is not None
    if stall:
        SCAP, ME, aware = stl
        sv, sc = state["stl"], state["sc"]
        iS = jnp.arange(SCAP, dtype=jnp.int32)
        atail = arr[:, _NA:]

    # ---- arrival scatter (already classified + positioned on host) ----
    kind = arr[:, _AKIND]
    aeng = arr[:, _AENG]
    apos = arr[:, _APOS]
    tA = jnp.zeros(A, jnp.int32) + t
    zA = jnp.zeros(A, jnp.int32)
    if sfs:
        eq = jnp.where(kind == 0, aeng, G)
        qrow = jnp.stack([arr[:, _AROW], arr[:, _ARID], arr[:, _ANTOK],
                          tA], axis=-1)
        if stall:
            # fresh: nothing served, no slice, no first start yet
            qrow = jnp.concatenate([qrow, jnp.stack(
                [zA, zA, zA, zA - 1, zA, zA], axis=-1), atail], axis=-1)
        q = q.at[eq, apos].set(qrow, mode="drop")
        qn = qn + jnp.zeros(G, jnp.int32).at[eq].add(one32, mode="drop")
    ep = jnp.where(kind >= 1, aeng, G)
    avr = minvr[jnp.clip(aeng, 0, G - 1)]
    prow = jnp.stack([arr[:, _AROW], arr[:, _ARID], arr[:, _ANTOK],
                      zA, avr, zA, zA, zA - 1, tA,
                      (kind == 2).astype(jnp.int32), zA], axis=-1)
    if stall:
        prow = jnp.concatenate([prow, atail], axis=-1)
    pool = pool.at[ep, apos].set(prow, mode="drop")
    pc = pc + jnp.zeros(G, jnp.int32).at[ep].add(one32, mode="drop")

    # ---- wakes: after the arrivals, in slot-acquisition order --------
    if stall:
        svalid = iS[None, :] < sc[:, None]
        wk = svalid & (sv[..., _SWAKE] == t)
        stail = sv[..., _NSS:]
        if sfs:
            wq = wk & ((sv[..., _PFLG] & 1) == 0)      # not demoted
            wp = wk & ~wq
            seq = stail[..., 1]
            rank = jnp.sum(wq[:, None, :] & (seq[:, None, :] < seq[:, :, None]),
                           axis=-1, dtype=jnp.int32)
            qpos = jnp.where(wq, (qh[:, None] + qn[:, None] + rank) % QCAP,
                             QCAP)
            wrow = jnp.concatenate([jnp.stack(
                [sv[..., _PROW], sv[..., _PRID], sv[..., _PNTOK],
                 jnp.zeros((G, SCAP), jnp.int32) + t, sv[..., _PSRV],
                 sv[..., _PSLC], sv[..., _PQD], sv[..., _PFS],
                 sv[..., _PNCTX], sv[..., _PFLG]], axis=-1), stail], axis=-1)
            q = q.at[gi[:, None], qpos].set(wrow, mode="drop")
            qn = qn + jnp.sum(wq, axis=1, dtype=jnp.int32)
        else:
            wp = wk                                      # cfs: all pool
        pw = jnp.cumsum(wp, axis=1, dtype=jnp.int32) - wp
        ppos = jnp.where(wp, pc[:, None] + pw, CAP)
        wprow = jnp.concatenate([
            sv[..., :_NP].at[..., _PVR].set(
                jnp.maximum(sv[..., _PVR], minvr[:, None])), stail], axis=-1)
        pool = pool.at[gi[:, None], ppos].set(wprow, mode="drop")
        pc = pc + jnp.sum(wp, axis=1, dtype=jnp.int32)
        skeep = svalid & ~wk
        sdest = jnp.where(
            skeep, jnp.cumsum(skeep, axis=1, dtype=jnp.int32) - 1, SCAP)
        sv = jnp.zeros_like(sv).at[gi[:, None], sdest].set(sv, mode="drop")
        sc = jnp.sum(skeep, axis=1, dtype=jnp.int32)
        n_wake = jnp.sum(wk, axis=1, dtype=jnp.int32)

    # ---- FILTER fill: the pop loop as a cumulative-sum prefix --------
    n_byp = jnp.zeros(G, jnp.int32)
    if sfs:
        iq = jnp.arange(QCAP, dtype=jnp.int32)
        free0 = L - lc
        ring = (qh[:, None] + iq[None, :]) % QCAP
        qq = jnp.take_along_axis(q, ring[:, :, None], axis=1)
        qvalid = iq[None, :] < qn[:, None]
        delay = t - qq[..., _QENT]
        byp = qvalid & (delay >= thr[:, None])
        adm = qvalid & ~byp
        # an entry is examined iff the admitted (lane-consuming) entries
        # strictly before it have not yet filled the free lanes — the
        # loop keeps draining past bypasses
        adm_before = jnp.cumsum(adm, axis=1, dtype=jnp.int32) - adm
        examined = qvalid & (adm_before < free0[:, None])
        admit = examined & adm
        bypass = examined & byp
        if trace:
            tr_adm = jnp.where(admit, qq[..., _QROW], -1)
            tr_byp = jnp.where(bypass, qq[..., _QROW], -1)
        zQ = jnp.zeros((G, QCAP), jnp.int32)
        lane_i = jnp.where(admit, lc[:, None] + adm_before, L)
        if stall:
            # a woken row keeps what it served, its unused slice (a
            # fresh one if none is left), its first start and its
            # queue delay so far
            qfs = jnp.where(qq[..., _QFS] < 0, t, qq[..., _QFS])
            qqd = qq[..., _QQD] + delay
            qtail = qq[..., _NQS:]
            lrow = jnp.concatenate([jnp.stack(
                [qq[..., _QROW], qq[..., _QRID], qq[..., _QNTOK],
                 qq[..., _QSRV],
                 jnp.where(qq[..., _QSLC] > 0, qq[..., _QSLC], S[:, None]),
                 qqd, qfs, qq[..., _QENT], qq[..., _QNCTX]], axis=-1),
                qtail], axis=-1)
        else:
            lrow = jnp.stack([qq[..., _QROW], qq[..., _QRID],
                              qq[..., _QNTOK], zQ, zQ + S[:, None], delay,
                              zQ + t, qq[..., _QENT]], axis=-1)
        lanes = lanes.at[gi[:, None], lane_i].set(lrow, mode="drop")
        n_adm = jnp.sum(admit, axis=1, dtype=jnp.int32)
        lc = lc + n_adm
        bcum = jnp.cumsum(bypass, axis=1, dtype=jnp.int32) - bypass
        bpos = jnp.where(bypass, pc[:, None] + bcum, CAP)
        if stall:
            brow = jnp.concatenate([jnp.stack(
                [qq[..., _QROW], qq[..., _QRID], qq[..., _QNTOK],
                 qq[..., _QSRV], zQ + minvr[:, None], qq[..., _QNCTX], qqd,
                 qfs, qq[..., _QENT], qq[..., _QFLG] | 1, qq[..., _QSLC]],
                axis=-1), qtail], axis=-1)
        else:
            brow = jnp.stack([qq[..., _QROW], qq[..., _QRID],
                              qq[..., _QNTOK], zQ, zQ + minvr[:, None], zQ,
                              delay, zQ + t, qq[..., _QENT], zQ + 1, zQ],
                             axis=-1)
        pool = pool.at[gi[:, None], bpos].set(brow, mode="drop")
        n_byp = jnp.sum(bypass, axis=1, dtype=jnp.int32)
        pc = pc + n_byp
        n_ex = n_adm + n_byp
        qh = (qh + n_ex) % QCAP
        qn = qn - n_ex
        free = L - lc
    else:
        free = jnp.full(G, L, jnp.int32)

    # ---- fair-share pick + start/displacement accounting -------------
    pvalid = ic[None, :] < pc[:, None]
    vr_k = jnp.where(pvalid, pool[..., _PVR], _IMAX)
    rid_k = jnp.where(pvalid, pool[..., _PRID], _IMAX)
    cpos = pick_order(vr_k, rid_k, L)                   # [G, L] positions
    k = jnp.minimum(free, pc)
    sel = k > 0
    ch = il[None, :] < k[:, None]
    crows = jnp.take_along_axis(pool, cpos[:, :, None], axis=1)
    new = ch & (crows[..., _PFS] < 0)
    qd2 = crows[..., _PQD] + jnp.where(new, t - crows[..., _PQE], 0)
    fs2 = jnp.where(new, t, crows[..., _PFS])
    srv2 = crows[..., _PSRV] + 1                        # run (prefill/decode)
    vr2 = crows[..., _PVR] + 1                          # end-of-tick charge
    upd = (crows.at[..., _PQD].set(qd2).at[..., _PFS].set(fs2)
                .at[..., _PSRV].set(srv2).at[..., _PVR].set(vr2))
    pool = pool.at[gi[:, None], jnp.where(ch, cpos, CAP)].set(
        upd, mode="drop")
    # displaced = ran last pick, still in this pool, not re-chosen
    ch_rows = jnp.where(ch, crows[..., _PROW], -2)
    prow_ids = jnp.where(pvalid, pool[..., _PROW], -3)
    in_ch = (last[:, :, None] == ch_rows[:, None, :]).any(-1)
    eqp = last[:, :, None] == prow_ids[:, None, :]      # [G, L, CAP]
    disp = (last >= 0) & sel[:, None] & eqp.any(-1) & ~in_ch
    dpos = jnp.where(disp, jnp.argmax(eqp, -1).astype(jnp.int32), CAP)
    pool = pool.at[gi[:, None], dpos, _PNCTX].add(one32, mode="drop")
    if trace:
        tr_pre = jnp.where(disp, last, -1)
    # the pool's pick runs whenever FILTER leaves a lane free, an empty
    # pick included; only a parked row can come back to be displaced
    # against a stale pick, so only the stall variant keeps that rule
    last = jnp.where((free > 0 if stall else sel)[:, None],
                     jnp.where(ch, crows[..., _PROW], -1), last)
    nact = lc + k

    # ---- FILTER run + end of tick ------------------------------------
    if sfs:
        lact = il[None, :] < lc[:, None]
        lanes = (lanes.at[..., _LSRV].add(lact.astype(jnp.int32))
                      .at[..., _LSLC].add(-lact.astype(jnp.int32)))
        done_f = lact & (lanes[..., _LSRV] >= lanes[..., _LNTOK] + 1)
        exp_f = lact & ~done_f & (lanes[..., _LSLC] <= 0)
        fkey = jnp.where(done_f, gi[:, None] * (2 * L) + il[None, :],
                         _IMAX)
        zL = jnp.zeros((G, L), jnp.int32)
        if stall:
            ltail = lanes[..., _NLS:]
            lthr, ldur = _cur_event(ltail, ME)
            park_f = lact & ~done_f & (lanes[..., _LSRV] - 1 >= lthr)
            lnctx = lanes[..., _LNCTX]
            fev = jnp.stack([fkey, lanes[..., _LROW], lanes[..., _LSRV],
                             lnctx, lanes[..., _LQD], lanes[..., _LFS],
                             lanes[..., _LQE], zL, zL + 2,
                             lanes[..., _LSLC], ltail[..., 0]], axis=-1)
            drow = jnp.concatenate([jnp.stack(
                [lanes[..., _LROW], lanes[..., _LRID], lanes[..., _LNTOK],
                 lanes[..., _LSRV], zL + minvr[:, None], lnctx + 1,
                 lanes[..., _LQD], lanes[..., _LFS], lanes[..., _LQE],
                 zL + 3, lanes[..., _LSLC]], axis=-1), ltail], axis=-1)
            # park: a slice that ran out this tick demotes first (one
            # switch), then the stall parks it (another)
            srow_l = jnp.concatenate([jnp.stack(
                [lanes[..., _LROW], lanes[..., _LRID], lanes[..., _LNTOK],
                 lanes[..., _LSRV], jnp.where(exp_f, minvr[:, None], 0),
                 lnctx + 1 + exp_f.astype(jnp.int32), lanes[..., _LQD],
                 lanes[..., _LFS], lanes[..., _LQE],
                 jnp.where(exp_f, 3, 2),
                 lanes[..., _LSLC] if aware else zL, t + 1 + ldur], axis=-1),
                ltail.at[..., 0].add(1)], axis=-1)
        else:
            fev = jnp.stack([fkey, lanes[..., _LROW], lanes[..., _LSRV], zL,
                             lanes[..., _LQD], lanes[..., _LFS],
                             lanes[..., _LQE], zL, zL + 2,
                             lanes[..., _LSLC]], axis=-1)
            drow = jnp.stack([lanes[..., _LROW], lanes[..., _LRID],
                              lanes[..., _LNTOK], lanes[..., _LSRV],
                              zL + minvr[:, None], zL + 1, lanes[..., _LQD],
                              lanes[..., _LFS], lanes[..., _LQE], zL + 3,
                              lanes[..., _LSLC]], axis=-1)
        if trace:
            tr_dem = jnp.where(exp_f, lanes[..., _LROW], -1)

    # ---- pool compaction: drop CFS finishes, append demotes ----------
    fin_c = ch & (srv2 >= crows[..., _PNTOK] + 1)
    if stall:
        ctail = crows[..., _NP:]
        cthr, cdur = _cur_event(ctail, ME)
        park_c = ch & ~fin_c & (srv2 - 1 >= cthr)
        srow_c = jnp.concatenate([jnp.stack(
            [crows[..., _PROW], crows[..., _PRID], crows[..., _PNTOK], srv2,
             vr2, crows[..., _PNCTX] + 1, qd2, fs2, crows[..., _PQE],
             crows[..., _PFLG], crows[..., _PSLC], t + 1 + cdur], axis=-1),
            ctail.at[..., 0].add(1)], axis=-1)
        left_c = fin_c | park_c
    else:
        left_c = fin_c
    finm = jnp.zeros((G, CAP), bool).at[
        gi[:, None], jnp.where(left_c, cpos, CAP)].set(True, mode="drop")
    surv = pvalid & ~finm
    # stable compaction as a cumsum scatter (survivors keep their order;
    # dropped/tail slots zero out) — XLA:CPU sorts are comparator loops,
    # so the argsort formulation is the wrong tool at [G, CAP]
    sdest = jnp.where(surv, jnp.cumsum(surv, axis=1, dtype=jnp.int32) - 1,
                      CAP)
    pool = jnp.zeros_like(pool).at[gi[:, None], sdest].set(
        pool, mode="drop")
    pc = jnp.sum(surv, axis=1, dtype=jnp.int32)
    if sfs:
        dem = exp_f & ~park_f if stall else exp_f
        dcum = jnp.cumsum(dem, axis=1, dtype=jnp.int32) - dem
        dpos2 = jnp.where(dem, pc[:, None] + dcum, CAP)
        pool = pool.at[gi[:, None], dpos2].set(drow, mode="drop")
        pc = pc + jnp.sum(dem, axis=1, dtype=jnp.int32)
        # stable lane compaction, same cumsum-scatter trick
        lkeep = lact & ~(done_f | exp_f)
        if stall:
            lkeep = lkeep & ~park_f
        ldest = jnp.where(
            lkeep, jnp.cumsum(lkeep, axis=1, dtype=jnp.int32) - 1, L)
        lanes = jnp.zeros_like(lanes).at[gi[:, None], ldest].set(
            lanes, mode="drop")
        lc = jnp.sum(lkeep, axis=1, dtype=jnp.int32)
    if stall:
        # parked rows join the stalled region, lanes before the pool
        if sfs:
            prk = jnp.concatenate([park_f, park_c], axis=1)
            srows = jnp.concatenate([srow_l, srow_c], axis=1)
        else:
            prk, srows = park_c, srow_c
        pk = jnp.cumsum(prk, axis=1, dtype=jnp.int32) - prk
        spos = jnp.where(prk, sc[:, None] + pk, SCAP)
        sv = sv.at[gi[:, None], spos].set(srows, mode="drop")
        n_park = jnp.sum(prk, axis=1, dtype=jnp.int32)
        sc = sc + n_park

    # ---- monotone min_vruntime collapse ------------------------------
    pvalid2 = ic[None, :] < pc[:, None]
    m = jnp.where(pvalid2, pool[..., _PVR], _IMAX).min(axis=1)
    last_slot = jnp.maximum(k - 1, 0)
    # the last pick's own charge still saw it in the pool, whether it
    # then finished or parked
    lastfin = jnp.take_along_axis(left_c, last_slot[:, None], 1)[:, 0] & sel
    lastvr = jnp.take_along_axis(vr2, last_slot[:, None], 1)[:, 0]
    m = jnp.where(lastfin, jnp.minimum(m, lastvr), m)
    minvr = jnp.where(sel & (m < _IMAX), jnp.maximum(minvr, m), minvr)

    # ---- completion events, key-sorted to replay order ---------------
    ckey = jnp.where(fin_c, gi[:, None] * (2 * L) + L + il[None, :],
                     _IMAX)
    cev = [ckey, crows[..., _PROW], srv2, crows[..., _PNCTX], qd2, fs2,
           crows[..., _PQE], vr2, crows[..., _PFLG], crows[..., _PSLC]]
    if stall:
        cev.append(ctail[..., 0])
    cev = jnp.stack(cev, axis=-1)
    # interleaving per engine (FILTER lanes, then CFS ranks) makes the
    # flattened grid already ascending in event key — compacting the
    # valid rows with a cumsum scatter replaces the argsort, and rows
    # past ``evcap`` fall off exactly like the old truncation
    grid = jnp.concatenate([fev, cev], axis=1) if sfs else cev
    NE = _NES if stall else _NE
    ev = grid.reshape(-1, NE)
    evalid = ev[:, _EKEY] < _IMAX
    n_ev = jnp.sum(evalid, dtype=jnp.int32)
    edest = jnp.where(evalid, jnp.cumsum(evalid, dtype=jnp.int32) - 1,
                      ev.shape[0])
    ev = jnp.zeros((evcap, NE), jnp.int32).at[edest].set(ev, mode="drop")

    # ---- distance to the next completion/expiry (event skip) ---------
    if sfs:
        lact2 = il[None, :] < lc[:, None]
        lnext = jnp.minimum(lanes[..., _LNTOK] + 1 - lanes[..., _LSRV],
                            lanes[..., _LSLC])
        if stall:
            lnext = jnp.minimum(lnext, _park_dist(
                _cur_event(lanes[..., _NLS:], ME)[0], lanes[..., _LSRV]))
        lnext = jnp.where(lact2, lnext, _IMAX).min(axis=1)
        free2 = L - lc
    else:
        lnext = jnp.full(G, _IMAX, jnp.int32)
        free2 = jnp.full(G, L, jnp.int32)
    runnable = (free2 > 0) & (pc <= free2) & (pc > 0)
    prun = runnable[:, None] & pvalid2
    pdist = pool[..., _PNTOK] + 1 - pool[..., _PSRV]
    if stall:
        pdist = jnp.minimum(pdist, _park_dist(
            _cur_event(pool[..., _NP:], ME)[0], pool[..., _PSRV]))
    pnext = jnp.where(prun, pdist, _IMAX).min(axis=1)
    min_next = jnp.minimum(lnext, pnext).min()

    state = dict(q=q, qh=qh, qn=qn, lanes=lanes, lc=lc, pool=pool, pc=pc,
                 minvr=minvr, last=last)
    mirrors = [qn, lc, pc, nact, n_byp]
    if stall:
        state.update(stl=sv, sc=sc)
        svalid2 = iS[None, :] < sc[:, None]
        wake = jnp.where(svalid2, sv[..., _SWAKE], _IMAX)
        wmin = wake.min(axis=1)
        # a wake is an event: the gap advance stops at the earliest
        min_next = jnp.minimum(min_next, wmin.min() - t)
        mirrors += [sc, n_park, n_wake, qh, wmin,
                    jnp.sum(svalid2 & (wake == wmin[:, None]), axis=1,
                            dtype=jnp.int32)]
    out = {"events": ev,
           "scal": jnp.stack([n_ev, min_next]),
           "mirrors": jnp.stack(mirrors)}
    if trace:
        out["trace_pre"] = tr_pre
        if sfs:
            out["trace_adm"] = tr_adm
            out["trace_byp"] = tr_byp
            out["trace_dem"] = tr_dem
        if stall:
            out["trace_park"] = jnp.where(prk, srows[..., _PROW], -1)
    return state, out


def _advance_core(G, L, CAP, sfs, state, g, t0, stall=False):
    """Collapse ``g`` event-free ticks (valid only when the host proved
    no fill, no finish, no expiry, no rotation, no stall and no wake can
    occur): active lanes serve and burn slice for ``g`` ticks; pools
    that fit their free lanes run whole for ``g`` ticks (first pick at
    ``t0`` settles first-start accounting); ``min_vruntime`` telescopes
    to a max against the final pool minimum; ``last`` becomes the pool
    itself, so no displacement is ever recorded — the same no-op the
    per-tick path would compute.  The stalled region waits unchanged."""
    import jax.numpy as jnp

    q, qh, qn, lanes, lc, pool, pc, minvr, last = (state[k]
                                                   for k in _STATE_KEYS)
    il = jnp.arange(L, dtype=jnp.int32)
    ic = jnp.arange(CAP, dtype=jnp.int32)
    if sfs:
        lact = (il[None, :] < lc[:, None]).astype(jnp.int32)
        lanes = (lanes.at[..., _LSRV].add(g * lact)
                      .at[..., _LSLC].add(-g * lact))
        free = L - lc
    else:
        free = jnp.full(G, L, jnp.int32)
    run_eng = (free > 0) & (pc > 0)
    pvalid = ic[None, :] < pc[:, None]
    run = run_eng[:, None] & pvalid
    new = run & (pool[..., _PFS] < 0)
    pool = pool.at[..., _PQD].add(
        jnp.where(new, t0 - pool[..., _PQE], 0))
    pool = pool.at[..., _PFS].set(
        jnp.where(new, t0, pool[..., _PFS]))
    runi = run.astype(jnp.int32)
    pool = pool.at[..., _PSRV].add(g * runi).at[..., _PVR].add(g * runi)
    m = jnp.where(run, pool[..., _PVR], _IMAX).min(axis=1)
    minvr = jnp.where(run_eng & (m < _IMAX), jnp.maximum(minvr, m), minvr)
    rows_pad = jnp.where(pvalid, pool[..., _PROW], -1)[:, :L]
    # as in the per-tick step: with stalls an empty pick resets it too
    last = jnp.where((free > 0 if stall else run_eng)[:, None], rows_pad,
                     last)
    out = dict(q=q, qh=qh, qn=qn, lanes=lanes, lc=lc, pool=pool, pc=pc,
               minvr=minvr, last=last)
    if stall:
        out.update({k: state[k] for k in _STALL_KEYS})
    return out


@lru_cache(maxsize=None)
def _build_fns(G, L, QCAP, CAP, sfs, trace=False, stl=None):
    """Jitted (step, scan, advance) for one group shape (and stall
    config ``stl``, see :func:`_tick_core`).  Cached module-wide so
    repeated growth and multiple same-shape groups reuse
    compilations."""
    import jax
    import jax.numpy as jnp

    evfull = G * L * (2 if sfs else 1)
    if stl is None:
        step = jax.jit(partial(_tick_core, G, L, QCAP, CAP, sfs, evfull,
                               trace))
    else:
        step = jax.jit(partial(_tick_core, G, L, QCAP, CAP, sfs, evfull,
                               trace, stl=stl))

    evscan = _scan_evcap(G, L, sfs)

    def scan_fn(state, t0, S, thr):
        arr0 = jnp.full((1, _NA + _tail(stl)), -1, jnp.int32)

        def body(st, tt):
            # scan windows are only entered with telemetry traces off
            # (JaxCluster._fast_forward), so the body never traces
            return _tick_core(G, L, QCAP, CAP, sfs, evscan, False,
                              st, arr0, tt, S, thr, stl)

        ts = t0 + jnp.arange(_SCAN_CHUNK, dtype=jnp.int32)
        return jax.lax.scan(body, state, ts)

    if stl is None:
        adv = jax.jit(partial(_advance_core, G, L, CAP, sfs))
    else:
        adv = jax.jit(partial(_advance_core, G, L, CAP, sfs, stall=True))
    return step, jax.jit(scan_fn), adv


def _group_ranks(keys: np.ndarray) -> np.ndarray:
    """Each entry's rank among the entries with its key, in order: the
    grouped cumulative count, via one stable argsort."""
    o = np.argsort(keys, kind="stable")
    sk = keys[o]
    ar = np.arange(len(keys))
    first = np.r_[True, sk[1:] != sk[:-1]]
    rank = np.empty(len(keys), np.int64)
    rank[o] = ar - np.maximum.accumulate(np.where(first, ar, 0))
    return rank


def _grow_np(a: np.ndarray, axis: int, size: int, fill=0) -> np.ndarray:
    shape = list(a.shape)
    shape[axis] = size - a.shape[axis]
    return np.concatenate([a, np.full(shape, fill, a.dtype)], axis=axis)


class _JaxGroup:
    """G identical engines stepped together inside one jitted tick.

    Device state holds only *region* arrays (queue ring, lanes, pool);
    the host keeps the dispatch-visible mirrors (outstanding, free
    slots, queue/pool depths), the adaptive-slice IAT windows, and the
    pending deques — exactly the state the numpy group keeps in Python
    anyway, so routing stays identical."""

    def __init__(self, members: Sequence[int], lanes: int, n_slots: int,
                 policy: str, sched_kw: dict, store: _RequestStore):
        self.members = list(members)
        self.G = len(self.members)
        self.lanes = lanes
        self.n_slots = n_slots
        self.policy = policy
        self.store = store
        G = self.G
        self.fixed_slice = sched_kw.get("slice_ticks")
        slice_init = sched_kw.get("slice_init", 32)
        self.window = int(sched_kw.get("adaptive_window", 100))
        of = sched_kw.get("overload_factor", 3.0)
        self.overload_factor = None if of is None else float(of)
        self.hinted_demotion = bool(sched_kw.get("hinted_demotion", False))
        self.stall_aware = bool(sched_kw.get("stall_aware", True))
        init_S = (self.fixed_slice if self.fixed_slice is not None
                  else slice_init)
        self.S = np.full(G, init_S, np.int64)
        self._iats = [deque(maxlen=self.window) for _ in range(G)]
        self._last_arrival = np.full(G, -1, np.int64)
        self._since_update = np.zeros(G, np.int64)
        self.slice_timeline = [BoundedTimeline((0, int(init_S)))
                               for _ in range(G)]
        self.overload_bypasses = np.zeros(G, np.int64)
        # host mirrors of device depths (refreshed from step outputs)
        self.qh = np.zeros(G, np.int64)
        self.qlen = np.zeros(G, np.int64)
        self.filter_count = np.zeros(G, np.int64)
        self.cfs_count = np.zeros(G, np.int64)
        self.n_active = np.zeros(G, np.int64)
        self.lane_busy_ticks = np.zeros(G, np.int64)
        self.pending: list[deque] = [deque() for _ in range(G)]
        self.pending_len = np.zeros(G, np.int64)
        self.free_slots = np.full(G, n_slots, np.int64)
        self.outstanding = np.zeros(G, np.int64)
        self.min_next = 1
        # stalls (§V-D): off until a request with stall events arrives
        # (ME = the most events one of them has), then the stalled
        # region and its mirrors: parked rows, their earliest wake tick
        # and how many wake then, the tick the next step runs, and the
        # parked rows that do not wake then (what the object engine
        # leaves out of ``runnable_count`` when it routes that tick)
        self.ME = 0
        self.SCAP = 0
        self._seq = 0                   # slot acquisitions handed out
        self.parked = np.zeros(G, np.int64)
        self.wmin = np.full(G, _IMAX, np.int64)
        self.nwmin = np.zeros(G, np.int64)
        self.t_next = 0
        self.stalled = np.zeros(G, np.int64)
        # device regions
        self.QCAP = 64
        # fleet-scale runs reach pool depth ~2x lanes routinely; starting
        # at 32 avoids a mid-run _grow (each growth re-jits three fns)
        self.CAP = max(32, 2 * lanes)
        self.ACAP = 256
        # opt-in telemetry (core/telemetry.py); None = fully disabled
        self.trace = None
        self.prof = None
        self._state = self._fresh_state()
        self._batch: list = []          # (j, kind, row, rid, ntok)
        self._compile()

    # -- device plumbing ----------------------------------------------
    def _fresh_state(self):
        import jax.numpy as jnp
        shapes = state_shapes(self.G, self.lanes, self.QCAP, self.CAP)
        return {k: jnp.full(s, -1 if k == "last" else 0, jnp.int32)
                for k, s in shapes.items()}

    def _stl(self):
        """The static stall config of the compiled programs, or None."""
        return ((self.SCAP, self.ME, self.stall_aware) if self.ME
                else None)

    def _compile(self):
        self._step_fn, self._scan_fn, self._adv_fn = _build_fns(
            self.G, self.lanes, self.QCAP, self.CAP, self.policy == "sfs",
            self.trace is not None, self._stl())

    def _ensure_stalls(self, me: int):
        """Make room for requests with up to ``me`` stall events: the
        first time, switch the regions to the stall layout and add the
        stalled region (sized like the pool); later, widen every stall
        tail.  Pull, pad, push and re-jit, like :meth:`_grow`."""
        import jax.numpy as jnp
        if me <= self.ME:
            return
        prof = self.prof
        if prof is not None:
            prof.begin("jax_grow")
        host = {k: np.asarray(v) for k, v in self._state.items()}

        def tail(a, n0):
            """``a`` with its stall tail widened from ``n0`` to ``me``
            events (``n0 = None``: no tail yet), new thresholds _IMAX."""
            add = (2 + 2 * me) if n0 is None else 2 * (me - n0)
            pad = np.zeros(a.shape[:-1] + (add,), np.int32)
            first = 2 if n0 is None else 0
            pad[..., first::2] = _IMAX
            return np.concatenate([a, pad], axis=-1)

        if self.ME == 0:
            G = self.G
            q = host["q"]
            hist = np.zeros(q.shape[:-1] + (_NQS - _NQ,), np.int32)
            hist[..., _QFS - _NQ] = -1        # queued rows never ran
            host["q"] = tail(np.concatenate([q, hist], axis=-1), None)
            ln = host["lanes"]
            host["lanes"] = tail(np.concatenate(
                [ln, np.zeros(ln.shape[:-1] + (1,), np.int32)], axis=-1),
                None)
            host["pool"] = tail(host["pool"], None)
            self.SCAP = self.CAP
            host["stl"] = tail(np.zeros((G, self.SCAP, _NSS), np.int32),
                               None)
            host["sc"] = np.zeros(G, np.int32)
        else:
            for k in ("q", "lanes", "pool", "stl"):
                host[k] = tail(host[k], self.ME)
        self.ME = me
        self._state = {k: jnp.asarray(v) for k, v in host.items()}
        self._compile()
        if prof is not None:
            prof.end("jax_grow")

    def bind_telemetry(self, trace, prof):
        """Attach trace/profile collectors; tracing re-jits the step to
        the variant that also returns the lifecycle row masks."""
        retrace = (trace is not None) != (self.trace is not None)
        self.trace = trace
        self.prof = prof
        if retrace:
            self._compile()

    def _grow(self, *, qcap=None, cap=None, scap=None):
        """Resize a device region: pull, pad (unrolling the queue ring
        to head 0), push back, re-jit against the new shapes."""
        import jax.numpy as jnp
        prof = self.prof
        if prof is not None:
            prof.begin("jax_grow")
        host = {k: np.asarray(v) for k, v in self._state.items()}
        if qcap is not None and qcap > self.QCAP:
            q2 = np.zeros((self.G, qcap, host["q"].shape[2]), np.int32)
            for j in range(self.G):
                n = int(host["qn"][j])
                idx = (int(self.qh[j]) + np.arange(n)) % self.QCAP
                q2[j, :n] = host["q"][j, idx]
            host["q"] = q2
            host["qh"] = np.zeros(self.G, np.int32)
            self.qh[:] = 0
            self.QCAP = qcap
        if cap is not None and cap > self.CAP:
            host["pool"] = _grow_np(host["pool"], 1, cap)
            self.CAP = cap
        if scap is not None and scap > self.SCAP:
            host["stl"] = _grow_np(host["stl"], 1, scap)
            self.SCAP = scap
        self._state = {k: jnp.asarray(v) for k, v in host.items()}
        self._compile()
        if prof is not None:
            prof.end("jax_grow")

    # -- arrivals (host-classified, device-scattered) ------------------
    def _observe_iat(self, j: int, t: int, m: int = 1):
        """``m`` slot-taking arrivals at engine ``j``, all at ``t``,
        through its adaptive-slice window."""
        if self.fixed_slice is not None:
            return
        lj = int(self._last_arrival[j])
        self._since_update[j], fires = observe_instant(
            self._iats[j], self.window, int(self._since_update[j]),
            None if lj < 0 else t - lj, m)
        for _k, total in fires:
            self.S[j] = max(1, int(round(total / self.window * self.lanes)))
            self.slice_timeline[j].append((t, int(self.S[j])))
        self._last_arrival[j] = t

    def _observe_iats(self, ms: np.ndarray, t: int):
        """:meth:`_observe_iat` of ``ms[j]`` arrivals at every engine
        ``j``.  Engines whose count stays below the window cannot
        recompute their slice: they only append their IATs."""
        if self.fixed_slice is not None:
            return
        js = np.nonzero(ms)[0]
        quiet = self._since_update[js] + ms[js] < self.window
        jq = js[quiet]
        iats = self._iats
        for j, lj, m in zip(jq.tolist(), self._last_arrival[jq].tolist(),
                            ms[jq].tolist()):
            d = iats[j]
            if lj >= 0:
                d.append(t - lj)
            if m > 1:
                d.extend(repeat(0, m - 1))
        self._since_update[jq] += ms[jq]
        self._last_arrival[jq] = t
        for j in js[~quiet].tolist():
            self._observe_iat(j, t, int(ms[j]))

    def _classify(self, j: int, row: int, req: Request, t: int):
        """The numpy ``_on_arrival`` split, minus the region write: the
        request's first region (queue / pool / demoted pool) is decided
        here with host state; the device scatters it there."""
        if self.policy == "cfs":
            kind = 1
            self.cfs_count[j] += 1
        else:
            self._observe_iat(j, t)
            if (self.hinted_demotion and req.eta_hint is not None
                    and req.eta_hint > self.S[j]):
                kind = 2
                self.cfs_count[j] += 1
                if self.trace is not None:
                    # hinted demotion: straight to the fair-share pool
                    self.trace.emit(t, "demote", req.rid, self.members[j])
            else:
                kind = 0
                self.qlen[j] += 1
        # flat int buffer: np.array on a flat list is ~20x cheaper than
        # on a list of tuples, and step_tick converts it every tick
        self._batch.extend((j, kind, row, req.rid, req.n_tokens))

    def submit(self, j: int, req: Request, t: int):
        row = self.store.add(req)
        if req.stall_events:
            self._ensure_stalls(len(req.stall_events))
        self.outstanding[j] += 1
        if self.free_slots[j] > 0:
            self.free_slots[j] -= 1
            self._classify(j, row, req, t)
        else:
            self.pending[j].append((row, req))
            self.pending_len[j] += 1

    def submit_many(self, js: np.ndarray, rows: np.ndarray,
                    reqs: Sequence[Request], t: int):
        """:meth:`submit` of ``reqs[k]`` (already in the store at
        ``rows[k]``) to engine ``js[k]``, for each ``k`` in order: the
        same mirrors, pending deques, slice windows and arrival batch.
        Hinted demotion is not modelled (callers submit one by one)."""
        st = self.store
        if st.max_stalls > self.ME:
            self._ensure_stalls(int(st.stall_n[rows].max()))
        cnt = np.bincount(js, minlength=self.G)
        self.outstanding += cnt
        # the first free_slots arrivals at an engine take a slot, in
        # arrival order; the rest wait in its pending deque
        take = _group_ranks(js) < self.free_slots[js]
        ntake = np.minimum(cnt, self.free_slots)
        self.free_slots -= ntake
        if not take.all():
            for k in np.nonzero(~take)[0].tolist():
                self.pending[js[k]].append((int(rows[k]), reqs[k]))
            self.pending_len += cnt - ntake
            js, rows = js[take], rows[take]
        if self.policy == "cfs":
            kind = 1
            self.cfs_count += ntake
        else:
            kind = 0
            self._observe_iats(ntake, t)
            self.qlen += ntake
        b = np.empty((len(js), 5), np.int64)
        b[:, 0] = js
        b[:, 1] = kind
        b[:, 2] = rows
        b[:, 3] = self.store.rid[rows]
        b[:, 4] = self.store.n_tokens[rows]
        self._batch.extend(b.ravel().tolist())

    def _admit_pending(self, t: int):
        for j in np.nonzero((self.pending_len > 0)
                            & (self.free_slots > 0))[0]:
            pen = self.pending[j]
            while self.free_slots[j] > 0 and pen:
                self.free_slots[j] -= 1
                self.pending_len[j] -= 1
                row, req = pen.popleft()
                self._classify(int(j), row, req, t)

    # -- the per-tick step ---------------------------------------------
    def _thr32(self) -> np.ndarray:
        if self.policy != "sfs" or self.overload_factor is None:
            return np.full(self.G, _IMAX, np.int32)
        # delay >= O*S  <=>  delay >= ceil(O*S) for integer delays
        return np.minimum(
            np.ceil(self.overload_factor * self.S), _IMAX).astype(np.int32)

    def step_tick(self, t: int) -> list:
        prof = self.prof
        if prof is not None:
            prof.begin("jax_prep")
        self._admit_pending(t)
        batch, self._batch = self._batch, []
        G, L = self.G, self.lanes
        b = np.array(batch, np.int64).reshape(-1, 5)
        bj, bkind = b[:, 0], b[:, 1]
        kc = bkind != 0                       # queue vs pool region
        nq = np.bincount(bj[~kc], minlength=G)
        npl = np.bincount(bj[kc], minlength=G)
        # the mirrors already include this batch (classify is eager);
        # conservative pool headroom: every queued entry could bypass
        # into the pool this tick, and every lane could demote.  With
        # stalls any resident can come back from the stalled region,
        # inside a scan chunk too: the queue holds at most what is not
        # in the pool, pool and stalled region at most every resident
        qneed = self.qlen
        pneed = self.cfs_count + self.qlen + L
        if self.ME:
            qneed = qneed + L + self.parked
            pneed = pneed + self.parked
        if int(qneed.max(initial=0)) > self.QCAP:
            want = self.QCAP
            while int(qneed.max()) > want:
                want *= 2
            self._grow(qcap=want)
        if int(pneed.max(initial=0)) > self.CAP:
            want = self.CAP
            while int(pneed.max()) > want:
                want *= 2
            self._grow(cap=want)
        if self.ME and int(pneed.max(initial=0)) > self.SCAP:
            want = self.SCAP
            while int(pneed.max()) > want:
                want *= 2
            self._grow(scap=want)
        while len(b) > self.ACAP:
            self.ACAP *= 2
        arr = np.full((self.ACAP, _NA + _tail(self._stl())), -1, np.int32)
        if batch:
            # per-(engine, region) arrival ranks in batch order
            rank = _group_ranks(bj * 2 + kc)
            qbase = self.qlen - nq            # depth before this batch
            pbase = self.cfs_count - npl
            pos = np.where(kc, pbase[bj] + rank,
                           (self.qh[bj] + qbase[bj] + rank) % self.QCAP)
            arr[:len(b), :5] = b
            arr[:len(b), 5] = pos
            if self.ME:
                self._stall_tail(arr[:len(b)], b[:, 2])
        qn_in = self.qlen.copy()
        if prof is not None:
            prof.end("jax_prep")
            prof.begin("jax_step")
        state, out = self._step_fn(
            self._state, arr, np.int32(t),
            self.S.astype(np.int32), self._thr32())
        self._state = state
        if prof is not None:
            prof.begin("jax_sync")
        scal = np.asarray(out["scal"])
        mir = np.asarray(out["mirrors"]).astype(np.int64)
        if prof is not None:
            prof.end("jax_sync")
        n_ev = int(scal[0])
        self.min_next = int(scal[1])
        qn2, lc2, pc2, nact, nbyp = mir[:5]
        n_ex = qn_in - qn2
        self.qh = (self.qh + n_ex) % self.QCAP
        self.qlen = qn2
        self.filter_count = lc2
        self.cfs_count = pc2
        self.n_active = nact
        self.lane_busy_ticks += nact
        self.overload_bypasses += nbyp
        if prof is not None:
            prof.end("jax_step")
        if self.ME or prof is not None:
            self._stall_sync(mir[5:], mir[6:8], t + 1)
        if self.trace is not None:
            self._emit_trace(out, t)
        if n_ev == 0:
            return []
        # pull the whole buffer and slice on the host: a device-side
        # ``[:n_ev]`` is an un-jitted slice whose output shape changes
        # every tick, so XLA would recompile it per distinct n_ev
        if prof is not None:
            prof.begin("jax_events")
        ev = np.asarray(out["events"])[:n_ev].astype(np.int64)
        res = self._process_events(ev, t)
        if prof is not None:
            prof.end("jax_events")
        return res

    def _stall_tail(self, arr: np.ndarray, rows: np.ndarray):
        """Fill the stall tail of a tick's arrival rows: no event
        consumed, the next slot-acquisition numbers, and each request's
        stall schedule from the store."""
        n = len(rows)
        arr[:, _NA] = 0
        arr[:, _NA + 1] = self._seq + np.arange(n)
        self._seq += n
        arr[:, _NA + 2:] = self.store.stall_ev[rows, :2 * self.ME]

    def _stall_sync(self, mir: np.ndarray, moves: np.ndarray, t_next: int):
        """Host stall bookkeeping after a step or a scan chunk: the
        stalled-region mirrors from ``mir`` (the last tick's ``[parked,
        parks, wakes, qh, earliest wake, rows waking then]``) and the
        park and wake counters from ``moves`` (``[parks, wakes]`` of
        every tick stepped).  Profiled, a group that never stalled
        spans nothing here and counts no park or wake."""
        prof = self.prof
        if prof is not None:
            prof.begin("stall_sync")
        parks = wakes = 0
        if self.ME:
            sc, _npk, _nwk, qh, wmin, nwmin = mir
            self.parked = sc
            self.qh = qh
            self.wmin = wmin
            self.nwmin = nwmin
            self._restall(t_next)
            parks, wakes = int(moves[0].sum()), int(moves[1].sum())
        if prof is not None:
            prof.count("stall_park", parks)
            prof.count("stall_wake", wakes)
            prof.end("stall_sync")

    def _restall(self, t_next: int):
        """Parked rows not waking at ``t_next``, the tick the next step
        runs (all of them wake at or after it)."""
        self.t_next = t_next
        self.stalled = self.parked - np.where(self.wmin == t_next,
                                              self.nwmin, 0)

    def _emit_trace(self, out, t: int):
        """Reconstruct the lifecycle events the object/vector schedulers
        emit inline from the device row masks (-1 = no event).  Order
        within a tick is irrelevant — traces compare canonically sorted
        (core/telemetry.py)."""
        tr, st, mem = self.trace, self.store, self.members
        if tr is None:
            return
        keys = ([("admit", "trace_adm"), ("bypass", "trace_byp"),
                 ("demote", "trace_dem")] if self.policy == "sfs" else [])
        keys.append(("preempt", "trace_pre"))
        if self.ME:
            keys.append(("preempt", "trace_park"))    # §V-D parks
        for kind, key in keys:
            a = np.asarray(out[key])
            g, p = np.nonzero(a >= 0)
            if g.size:
                rows = a[g, p]
                tr.emit_rows(t, kind,
                             zip(st.rid[rows].tolist(),
                                 [mem[x] for x in g.tolist()]))

    def _process_events(self, ev: np.ndarray, t: int) -> list:
        """Batched store write-back of finished rows + the (member,
        order) replay tuples the frontend merges across groups."""
        st = self.store
        L2 = 2 * self.lanes
        rows = ev[:, _EROW]
        eng = ev[:, _EKEY] // L2
        st.served[rows] = ev[:, _ESRV]
        st.tokens_done[rows] = ev[:, _ESRV] - 1
        st.prefill_done[rows] = True
        st.n_ctx[rows] = ev[:, _ENCTX]
        st.queue_delay[rows] = ev[:, _EQD]
        st.first_start[rows] = ev[:, _EFS]
        st.queue_enter[rows] = ev[:, _EQE]
        st.vruntime[rows] = ev[:, _EVR]
        st.demoted[rows] = (ev[:, _EFLG] & 1).astype(bool)
        st.slice_set[rows] = (ev[:, _EFLG] >> 1).astype(bool)
        st.slice_left[rows] = ev[:, _ESLC]
        st.finish[rows] = t + 1
        if self.ME:
            st.stall_idx[rows] = ev[:, _ESTI]
        np.add.at(self.free_slots, eng, 1)
        np.add.at(self.outstanding, eng, -1)
        if self.trace is not None:
            self.trace.emit_rows(
                t + 1, "complete",
                zip(st.rid[rows].tolist(),
                    [self.members[g] for g in eng.tolist()]))
        return [(self.members[g], int(key - g * L2), int(row))
                for g, key, row in zip(eng, ev[:, _EKEY], rows)]

    # -- fleet lifecycle ----------------------------------------------
    def evict(self, j: int) -> list:
        """Remove every resident request of engine ``j`` (queue ring,
        FILTER lanes, fair-share pool, pending deque, any unflushed
        arrival batch) and zero its device regions — the jax half of
        the frontend's ``_evict_server`` hook.  Pull/patch/push: the
        array shapes are unchanged, so no re-jit."""
        import jax.numpy as jnp
        st = self.store
        rows: list = []
        if self._batch:
            # arrivals classified this tick but not yet scattered
            b = np.array(self._batch, np.int64).reshape(-1, 5)
            keep = b[:, 0] != j
            rows.extend(b[~keep, 2].tolist())
            self._batch = b[keep].reshape(-1).tolist()
        host = {k: np.asarray(v).copy() for k, v in self._state.items()}
        qn = int(host["qn"][j])
        if qn:
            idx = (int(host["qh"][j]) + np.arange(qn)) % self.QCAP
            rows.extend(host["q"][j, idx, _QROW].tolist())
        lc = int(host["lc"][j])
        if lc:
            rows.extend(host["lanes"][j, :lc, _LROW].tolist())
        pc = int(host["pc"][j])
        if pc:
            rows.extend(host["pool"][j, :pc, _PROW].tolist())
        keys = ["q", "lanes", "pool", "qh", "qn", "lc", "pc"]
        if self.ME:
            sc = int(host["sc"][j])
            if sc:                           # parked rows hold slots too
                rows.extend(host["stl"][j, :sc, _PROW].tolist())
            keys += _STALL_KEYS
            self.parked[j] = 0
            self.wmin[j] = _IMAX
            self.nwmin[j] = 0
            self.stalled[j] = 0
        evicted = [st.reqs[int(r)] for r in rows]
        evicted.extend(req for _row, req in self.pending[j])
        self.pending[j].clear()
        for k in keys:
            host[k][j] = 0
        host["last"][j] = -1
        self._state = {k: jnp.asarray(v) for k, v in host.items()}
        # host mirrors: engine j is empty from here on (the orphaned
        # store rows are never written back — resubmission adds fresh
        # rows), and the stale event-skip distance must be discarded
        self.qh[j] = 0
        self.qlen[j] = 0
        self.filter_count[j] = 0
        self.cfs_count[j] = 0
        self.n_active[j] = 0
        self.pending_len[j] = 0
        self.free_slots[j] = self.n_slots
        self.outstanding[j] = 0
        self.min_next = 1
        return evicted

    def evict_one(self, j: int, rid: int):
        """Remove the single resident request ``rid`` from engine ``j``
        (unflushed arrival batch, pending deque, queue ring, FILTER
        lane or fair-share pool) and return its Request — the jax half
        of the frontend's ``_evict_request`` hook (timeout/hedge).
        Pull/patch/push like :meth:`evict`; shapes are unchanged, so no
        re-jit, and the stale event-skip distance is discarded."""
        import jax.numpy as jnp
        st = self.store
        if self._batch:
            # classified this tick but not yet scattered: undo the
            # mirror increment _classify made for its target region
            b = np.array(self._batch, np.int64).reshape(-1, 5)
            hit = np.nonzero((b[:, 0] == j) & (b[:, 3] == rid))[0]
            if hit.size:
                k = int(hit[0])
                row, kind = int(b[k, 2]), int(b[k, 1])
                if kind == 0:
                    self.qlen[j] -= 1
                else:
                    self.cfs_count[j] -= 1
                self._batch = np.delete(b, k, axis=0).reshape(-1).tolist()
                self.free_slots[j] += 1
                self.outstanding[j] -= 1
                self.min_next = 1
                return st.reqs[row]
        for k, (row, req) in enumerate(self.pending[j]):
            if req.rid == rid:
                del self.pending[j][k]
                self.pending_len[j] -= 1
                self.outstanding[j] -= 1     # never claimed a slot
                return req
        host = {k: np.asarray(v).copy() for k, v in self._state.items()}
        row = None
        qn = int(host["qn"][j])
        if qn:
            idx = (int(host["qh"][j]) + np.arange(qn)) % self.QCAP
            ring = host["q"][j, idx]
            hit = np.nonzero(ring[:, _QRID] == rid)[0]
            if hit.size:
                p = int(hit[0])
                row = int(ring[p, _QROW])
                q2 = np.zeros_like(host["q"][j])
                q2[:qn - 1] = np.delete(ring, p, axis=0)
                host["q"][j] = q2            # unrolled to head 0
                host["qh"][j] = 0
                host["qn"][j] = qn - 1
                self.qh[j] = 0
                self.qlen[j] -= 1
        lc = int(host["lc"][j])
        if row is None and lc:
            hit = np.nonzero(host["lanes"][j, :lc, _LRID] == rid)[0]
            if hit.size:
                p = int(hit[0])
                row = int(host["lanes"][j, p, _LROW])
                # stable shift-left, like the end-of-tick compaction
                host["lanes"][j, p:lc - 1] = host["lanes"][j, p + 1:lc]
                host["lanes"][j, lc - 1] = 0
                host["lc"][j] = lc - 1
                self.filter_count[j] -= 1
        pc = int(host["pc"][j])
        if row is None and pc:
            hit = np.nonzero(host["pool"][j, :pc, _PRID] == rid)[0]
            if hit.size:
                p = int(hit[0])
                row = int(host["pool"][j, p, _PROW])
                host["pool"][j, p:pc - 1] = host["pool"][j, p + 1:pc]
                host["pool"][j, pc - 1] = 0
                host["pc"][j] = pc - 1
                self.cfs_count[j] -= 1
        sc = int(host["sc"][j]) if self.ME else 0
        if row is None and sc:
            sv = host["stl"][j]
            hit = np.nonzero(sv[:sc, _PRID] == rid)[0]
            if hit.size:
                p = int(hit[0])
                row = int(sv[p, _PROW])
                sv[p:sc - 1] = sv[p + 1:sc]
                sv[sc - 1] = 0
                host["sc"][j] = sc - 1
                self.parked[j] -= 1
                wake = sv[:sc - 1, _SWAKE]
                self.wmin[j] = wake.min(initial=_IMAX)
                self.nwmin[j] = int((wake == self.wmin[j]).sum())
                self._restall(self.t_next)
        if row is None:
            return None
        lr = host["last"][j]
        lr[lr == row] = -1                   # no phantom displacement
        self._state = {k: jnp.asarray(v) for k, v in host.items()}
        self.free_slots[j] += 1
        self.outstanding[j] -= 1
        self.min_next = 1
        return st.reqs[row]

    # -- multi-tick fast paths -----------------------------------------
    def skip_valid(self) -> bool:
        """No event before ``min_next`` ticks can change behaviour:
        fill is a no-op (lanes full or queue empty — the post-tick
        invariant), nothing rotates (each pool fits its free lanes or
        cannot run), and no pending admission could fire (pending work
        implies exhausted slots, which no completion will refill)."""
        L = self.lanes
        free = ((L - self.filter_count) if self.policy == "sfs"
                else np.full(self.G, L))
        return bool(
            np.all((self.filter_count == L) | (self.qlen == 0))
            and np.all((self.cfs_count <= free) | (free == 0))
            and np.all((self.pending_len == 0) | (self.free_slots == 0)))

    def gap_active_counts(self) -> np.ndarray:
        L = self.lanes
        free = ((L - self.filter_count) if self.policy == "sfs"
                else np.full(self.G, L))
        return self.filter_count + np.minimum(free, self.cfs_count)

    def advance(self, g: int, t0: int):
        self._state = self._adv_fn(self._state, np.int32(g), np.int32(t0))
        self.min_next -= g
        if self.ME:
            self._restall(t0 + g)
        self.lane_busy_ticks += g * self.gap_active_counts()

    def scan(self, t0: int):
        """Phase 1 of a compiled ``_SCAN_CHUNK``-tick window (no
        arrivals, no pending): run the chunk, pull the outputs, detect
        event-buffer overflow.  Nothing host-side is mutated, so an
        overflow in ANY group lets the cluster abandon the whole window
        before any group committed.  Returns ``(False, first_bad_tick)``
        or ``(True, payload)`` for :meth:`commit_scan`."""
        state, outs = self._scan_fn(
            self._state, np.int32(t0), self.S.astype(np.int32),
            self._thr32())
        scal = np.asarray(outs["scal"])
        nevs = scal[:, 0]
        evcap = _scan_evcap(self.G, self.lanes, self.policy == "sfs")
        if (nevs > evcap).any():
            return False, int(np.argmax(nevs > evcap))
        return True, (state, scal,
                      np.asarray(outs["mirrors"]).astype(np.int64),
                      np.asarray(outs["events"]))

    def commit_scan(self, t0: int, payload):
        """Phase 2: adopt the post-chunk state, update mirrors, and
        return (per-tick replay tuples, per-tick active counts)."""
        state, scal, mir, events = payload
        self._state = state
        self.min_next = int(scal[-1, 1])
        per_tick = []
        for i in range(_SCAN_CHUNK):
            n = int(scal[i, 0])
            per_tick.append(
                self._process_events(events[i, :n].astype(np.int64),
                                     t0 + i) if n else [])
        qn2, lc2, pc2, nact, _nbyp = mir[-1, :5]
        self.qh = (self.qh + (self.qlen - qn2)) % self.QCAP
        self.qlen = qn2
        self.filter_count = lc2
        self.cfs_count = pc2
        self.n_active = nact
        self.lane_busy_ticks += mir[:, 3].sum(axis=0)
        self.overload_bypasses += mir[:, 4].sum(axis=0)
        if self.ME or self.prof is not None:
            self._stall_sync(mir[-1, 5:], mir[:, 6:8].swapaxes(0, 1),
                             t0 + _SCAN_CHUNK)
        return per_tick, mir[:, 3]


class JaxServerView(ServerView):
    """``ServerView`` protocol over one engine's host mirrors — O(1)
    numpy scalar reads, same formulas as ``VectorServerView``."""

    def __init__(self, group: _JaxGroup, j: int):
        self.group = group
        self.j = j

    @property
    def lanes(self) -> int:
        return self.group.lanes

    def outstanding(self) -> int:
        return int(self.group.outstanding[self.j])

    def filter_free(self) -> int:
        g, j = self.group, self.j
        if g.policy == "sfs":
            active = int(g.filter_count[j])
        else:
            active = min(g.lanes, int(g.cfs_count[j]))
        return max(0, g.lanes - active - self.queue_len())

    def fair_load(self) -> int:
        return int(self.group.cfs_count[self.j])

    def queue_len(self) -> int:
        return (int(self.group.qlen[self.j])
                if self.group.policy == "sfs" else 0)

    def capacity(self) -> int:
        g, j = self.group, self.j
        slots = int(g.free_slots[j]) - int(g.pending_len[j])
        lanes = g.lanes - int(g.outstanding[j]) + int(g.stalled[j])
        return max(0, min(slots, lanes))


class _JaxColumns(ServerStateColumns):
    """Bulk dispatch-state refresh from the groups' host mirrors."""

    def __init__(self, views, groups):
        super().__init__(views)
        self._groups = [(g, np.asarray(g.members, np.int64))
                        for g in groups]
        self._sfs = [v.group.policy == "sfs" for v in self.views]
        self._slots: list = []

    def begin_intake(self):
        """Snapshot every server's free slots for :meth:`deliver`."""
        slots = np.empty(len(self.views), np.int64)
        for g, m in self._groups:
            slots[m] = g.free_slots
        self._slots = slots.tolist()

    def deliver(self, i: int, cols):
        """The change one ``_JaxGroup.submit`` to server ``i`` makes to
        its columns ``cols`` = ``[outstanding, filter_free, queue_len,
        fair_load]``, counting slots from :meth:`begin_intake`: one
        more outstanding; with a free slot, the request enters the
        FILTER queue (sfs) or the pool (cfs), so one idle FILTER lane
        fewer."""
        out, ff, ql, fair = cols
        out[i] += 1
        if self._slots[i] > 0:
            self._slots[i] -= 1
            if ff[i] > 0:
                ff[i] -= 1
            if self._sfs[i]:
                ql[i] += 1
            else:
                fair[i] += 1

    def _pull(self, i: int):
        # one delivery dirties one server between consecutive arrivals —
        # read the group mirrors directly instead of five view-method
        # calls (same formulas as JaxServerView, ~3x cheaper per arrival)
        v = self.views[i]
        g, j = v.group, v.j
        out = g.outstanding[j]
        fair = g.cfs_count[j]
        self.outstanding[i] = out
        self.fair_load[i] = fair
        if g.policy == "sfs":
            ql = g.qlen[j]
            ff = g.lanes - g.filter_count[j] - ql
        else:
            ql = 0
            ff = g.lanes - min(g.lanes, fair)
        self.queue_len[i] = ql
        self.filter_free[i] = ff if ff > 0 else 0
        if g.ME:
            out -= g.stalled[j]             # parked: not runnable
        cap = min(g.free_slots[j] - g.pending_len[j], g.lanes - out)
        self.capacity[i] = cap if cap > 0 else 0

    def _pull_all(self):
        for g, m in self._groups:
            self.outstanding[m] = g.outstanding
            self.fair_load[m] = g.cfs_count
            if g.policy == "sfs":
                self.queue_len[m] = g.qlen
                self.filter_free[m] = np.maximum(
                    0, g.lanes - g.filter_count - g.qlen)
            else:
                self.queue_len[m] = 0
                self.filter_free[m] = np.maximum(
                    0, g.lanes - np.minimum(g.lanes, g.cfs_count))
            self.capacity[m] = np.maximum(
                0, np.minimum(g.free_slots - g.pending_len,
                              g.lanes - g.outstanding + g.stalled))


class JaxCluster(ClusterFrontend):
    """N servers behind one dispatch policy, stepped by jitted group
    ticks with event-driven multi-tick batching.  Bit-exact with the
    ``vector`` and ``tick`` backends; reaches 1024 engines and
    million-request sweeps inside the smoke budget."""

    def __init__(self, servers: Sequence,
                 cfg: Optional[ClusterConfig] = None):
        specs = [s if isinstance(s, ServerSpec) else ServerSpec.parse(s)
                 for s in servers]
        self.store = _RequestStore()
        self.groups: list[_JaxGroup] = []
        self._backend: list = [None] * len(specs)
        by_key: dict = {}
        for i, s in enumerate(specs):
            ec = s.to_engine_config()
            ok = (ec.policy in VECTOR_POLICIES
                  and (set(ec.sched_kw) <= _SFS_KW if ec.policy == "sfs"
                       else not ec.sched_kw))
            if s.engine == "object" or not ok:
                raise ValueError(
                    f"server {i}: scheduler {ec.policy!r} with knobs "
                    f"{ec.sched_kw!r} cannot run on the jax backend; use "
                    "engine='vector' (object-engine stragglers) instead")
            key = (ec.lanes, ec.n_slots, ec.policy,
                   tuple(sorted(ec.sched_kw.items())))
            by_key.setdefault(key, []).append(i)
        for (lanes, n_slots, policy, kw), members in by_key.items():
            group = _JaxGroup(members, lanes, n_slots, policy, dict(kw),
                              self.store)
            self.groups.append(group)
            for j, idx in enumerate(members):
                self._backend[idx] = (group, j)
        views = [JaxServerView(*self._backend[i]) for i in range(len(specs))]
        super().__init__(views, cfg)
        self._cols = _JaxColumns(views, self.groups)
        self.policy.columns = self._cols
        self._group_of = np.array([self.groups.index(g)
                                   for g, _ in self._backend], np.int64)
        self._member_of = np.array([j for _, j in self._backend], np.int64)
        self._hinted = any(g.hinted_demotion for g in self.groups)
        self._done_rows: list[int] = []
        self._scan_cooldown = 0

    # -- backend hooks -------------------------------------------------
    def _bind_backend(self, tel):
        if tel.trace is not None or tel.profile is not None:
            for g in self.groups:
                g.bind_telemetry(tel.trace, tel.profile)

    def _submit(self, idx: int, req: Request):
        group, j = self._backend[idx]
        group.submit(j, req, self.t)
        self._cols.mark(idx)

    def _route_tick(self, arrivals: Sequence[Request]):
        """Batched intake: estimate, route and deliver the whole tick
        in a few calls, with the per-arrival path's results.  Taken for
        hash and sfs-aware dispatch over every server, with no warm
        set, trace, watchdog or hinted demotion; else one by one."""
        pol = self.policy
        if (not arrivals or pol.active is not None
                or not isinstance(pol, (HashDispatch, SFSAwareDispatch))
                or self._warm is not None or self._trace is not None
                or self._watchdog is not None or self._hinted):
            return super()._route_tick(arrivals)
        prof = self._prof
        if prof is not None:
            prof.begin("route_batch")
        est = self.predictor.estimate
        etas = [est(r.func_id, r.eta_hint) for r in arrivals]
        rids = [r.rid for r in arrivals]
        if isinstance(pol, HashDispatch):
            idxs = pol.route_many(rids)
        else:
            self._cols.begin_intake()
            picks = pol.route_many(etas, self.t, self._cols.deliver)
            if picks is None:
                if prof is not None:
                    prof.end("route_batch")
                return super()._route_tick(arrivals)
            idxs = np.array(picks, np.int64)
        self.eta_log.update(zip(rids, etas))
        ser = self._series
        if ser is not None:
            hits = len(etas) - etas.count(None)
            ser.counters["predictor_hits"] += hits
            ser.counters["predictor_misses"] += len(etas) - hits
        for r, eta in zip(arrivals, etas):
            if r.eta_hint is None and eta is not None:
                r.eta_hint = eta
        pol.dispatch_counts = (np.asarray(pol.dispatch_counts)
                               + np.bincount(idxs, minlength=self.n_servers)
                               ).tolist()
        rows = self.store.add_many(arrivals)
        js = self._member_of[idxs]
        if len(self.groups) == 1:
            self.groups[0].submit_many(js, rows, arrivals, self.t)
        else:
            gof = self._group_of[idxs]
            for gi, group in enumerate(self.groups):
                sel = np.nonzero(gof == gi)[0]
                if sel.size:
                    group.submit_many(js[sel], rows[sel],
                                      [arrivals[k] for k in sel.tolist()],
                                      self.t)
        self._cols.mark_all()
        if prof is not None:
            prof.end("route_batch")

    def _evict_server(self, idx: int) -> list:
        group, j = self._backend[idx]
        evicted = group.evict(j)
        self._cols.mark(idx)
        return evicted

    def _evict_request(self, idx: int, rid: int):
        group, j = self._backend[idx]
        req = group.evict_one(j, rid)
        if req is not None:
            self._cols.mark(idx)
        return req

    def _observe_finish(self, req: Request, t: int):
        # series completion counters are handled in _replay from the
        # store columns — ``req`` is only written back at collect time,
        # so its demoted/n_ctx fields are stale here
        if self._watchdog is not None:
            self._watchdog.complete(req.rid)
        self.predictor.observe(req.func_id, req.service_demand)

    def _replay(self, events: list, t: int):
        """Merge per-group completion tuples into object-cluster order
        and drive the predictor feedback loop."""
        prof = self._prof
        if prof is not None:
            prof.begin("replay")
        events.sort(key=lambda e: (e[0], e[1]))
        ser = self._series
        st = self.store
        for _member, _order, row in events:
            self._done_rows.append(row)
            if ser is not None:
                c = ser.counters
                c["completions"] += 1
                if st.demoted[row]:
                    c["demoted_done"] += 1
                c["nctx_done"] += int(st.n_ctx[row])
            self._observe_finish(st.reqs[row], t + 1)
        if prof is not None:
            prof.end("replay")

    def _step(self):
        events = []
        for group in self.groups:
            events.extend(group.step_tick(self.t))
        self._replay(events, self.t)
        self._cols.mark_all()

    def _active_counts(self) -> tuple:
        return self._per_server([g.n_active for g in self.groups])

    def _per_server(self, per_group) -> tuple:
        """One value per server from one array per group (member order),
        for the tick log."""
        counts = [0] * self.n_servers
        for group, vals in zip(self.groups, per_group):
            for j, idx in enumerate(group.members):
                counts[idx] = int(vals[j])
        return tuple(counts)

    def _finished_count(self) -> int:
        return len(self._done_rows)

    def _collect(self) -> list:
        prof = self._prof
        if prof is not None:
            prof.begin("jax_writeback")
        out = self.store.write_back_many(self._done_rows)
        if prof is not None:
            prof.end("jax_writeback")
        return out

    # -- event-driven multi-tick batching ------------------------------
    def _fast_forward(self, window: int) -> bool:
        """Advance up to ``window`` arrival-free ticks without paying
        per-tick dispatch: a closed-form gap jump when no event can
        land, else a compiled ``lax.scan`` chunk.  Returns False when
        neither applies (the caller falls back to a single tick)."""
        if window <= 0:
            return False
        gap = min(min(g.min_next for g in self.groups) - 1, window)
        if gap >= 1 and all(g.skip_valid() for g in self.groups):
            # the gap advance is trace-safe: no event of any kind can
            # occur inside the gap, so there is nothing to emit
            prof = self._prof
            if prof is not None:
                prof.begin("jax_advance")
            log = self.tick_log
            if log is not None:
                counts = self._per_server(
                    [g.gap_active_counts() for g in self.groups])
                log.extend((self.t + dt, 0, counts) for dt in range(gap))
            for group in self.groups:
                group.advance(gap, self.t)
            ser = self._series
            if ser is not None:
                for dt in range(gap):
                    if (self.t + dt) % ser.cadence == 0:
                        # gauges are frozen across an event-free gap, so
                        # the live views sample the exact per-tick values
                        ser.sample(self.t + dt, self.views,
                                   {"central_queue": len(self.central_queue)})
            self.t += gap
            self._cols.mark_all()
            if prof is not None:
                prof.end("jax_advance")
            return True
        # scan chunks skip the per-tick host loop, so they cannot emit
        # trace events or series samples — fall back to per-tick
        # stepping whenever either collector is live
        if (window >= _SCAN_CHUNK and self.t >= self._scan_cooldown
                and self._trace is None and self._series is None
                and not any(g.pending_len.any() for g in self.groups)):
            return self._scan_window()
        return False

    def _scan_window(self) -> bool:
        t0 = self.t
        prof = self._prof
        if prof is not None:
            prof.begin("jax_scan")
        payloads = []
        for group in self.groups:
            ok, res = group.scan(t0)
            if not ok:
                # a completion burst blew the per-tick event buffer:
                # nothing was committed anywhere — cool down until the
                # per-tick path has stepped past the burst tick
                self._scan_cooldown = t0 + res + 1
                if prof is not None:
                    prof.end("jax_scan")
                return False
            payloads.append(res)
        if prof is not None:
            prof.end("jax_scan")
            prof.begin("jax_commit")
        per_group = [g.commit_scan(t0, p)
                     for g, p in zip(self.groups, payloads)]
        log = self.tick_log
        for i in range(_SCAN_CHUNK):
            t = t0 + i
            events = []
            for per_tick, _ in per_group:
                events.extend(per_tick[i])
            self._replay(events, t)
            if log is not None:
                log.append((t, 0, self._per_server(
                    [nacts[i] for _, nacts in per_group])))
        self.t = t0 + _SCAN_CHUNK
        self._cols.mark_all()
        if prof is not None:
            prof.end("jax_commit")
        return True

    def run(self, workload: Sequence[Request], max_ticks: int = 1_000_000,
            prompts: Optional[dict] = None) -> list[Request]:
        prof = self._prof
        if prof is not None:
            prof.begin("intake")
        workload = sorted(workload, key=lambda r: r.arrival)
        if prof is not None:
            prof.end("intake")
        i, n = 0, len(workload)
        # shed requests never finish; they terminate the loop as their
        # own accounting, excluded from every completion metric
        while self._finished_count() + len(self._shed) < n:
            if self.t > max_ticks:
                raise RuntimeError(
                    f"cluster exceeded {max_ticks} ticks "
                    f"({self._finished_count()}/{n})")
            arrivals = []
            while i < n and workload[i].arrival <= self.t:
                r = workload[i]
                if prompts is not None and r.rid in prompts:
                    r._prompt = np.asarray(prompts[r.rid])
                arrivals.append(r)
                i += 1
            if (not arrivals and not self.central_queue):
                next_arr = workload[i].arrival if i < n else max_ticks + 2
                limit = min(next_arr, max_ticks + 2)
                horizon = self._lifecycle_horizon()
                if horizon is not None:
                    # never fast-forward past a pending failure or the
                    # next autoscale boundary: the decision must be
                    # evaluated by a real tick at exactly that time,
                    # same as the per-tick backends
                    limit = min(limit, horizon)
                if self._fast_forward(limit - self.t):
                    continue
            self.tick(arrivals)
        if prof is not None:
            prof.begin("result")        # closed by run_experiment
        return sorted(self._collect(), key=lambda r: r.rid)

    # ------------------------------------------------------------------
    def summary(self) -> dict:
        out = super().summary()
        out["backend"] = "jax"
        out["groups"] = [{"members": g.members, "lanes": g.lanes,
                          "policy": g.policy} for g in self.groups]
        out["engine_overload_bypasses"] = int(
            sum(int(g.overload_bypasses.sum()) for g in self.groups))
        return out
