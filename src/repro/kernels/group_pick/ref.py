"""Pure-jnp oracle for the per-group fair-share pick.

The CFS pick inside the jitted group step (``serving/jax_cluster.py``)
needs, per engine, the pool positions of the ``kmax`` lexicographically
smallest ``(vruntime, rid)`` candidates — the batched analogue of the
object scheduler's ``sorted(runnable, key=(vruntime, rid))[:k]`` and of
``pick_active_batched``'s lexsort on the numpy path.  Invalid slots are
passed in as ``(INT32_MAX, INT32_MAX)`` sentinels and sort last.
"""
from __future__ import annotations

import jax.numpy as jnp

_IMAX = 2**31 - 1


def pick_order_ref(vr: jnp.ndarray, rid: jnp.ndarray,
                   kmax: int) -> jnp.ndarray:
    """``[G, CAP]`` keys -> ``[G, kmax]`` pool positions, sorted by
    ``(vr, rid)`` ascending.

    Two stable argsorts emulate ``np.lexsort((rid, vr))``: sort by the
    secondary key first, then stably by the primary.  ``rid`` is unique
    per valid candidate, so the order is total; sentinel slots tie on
    ``(MAX, MAX)`` and stability leaves them position-ascending —
    exactly what the iterative-argmin kernel produces too.
    """
    o1 = jnp.argsort(rid, axis=1, stable=True)
    vr1 = jnp.take_along_axis(vr, o1, axis=1)
    o2 = jnp.argsort(vr1, axis=1, stable=True)
    return jnp.take_along_axis(o1, o2, axis=1)[:, :kmax].astype(jnp.int32)


def pick_order_argmin(vr: jnp.ndarray, rid: jnp.ndarray,
                      kmax: int) -> jnp.ndarray:
    """Sort-free equivalent of :func:`pick_order_ref` for small ``kmax``.

    XLA:CPU lowers ``sort`` to a scalar comparator loop — at
    ``[1024, CAP]`` the two stable argsorts cost more than the rest of
    the tick combined.  ``kmax`` is the lane count (single digits), so
    ``kmax`` rounds of masked min-reduction are far cheaper.  Same
    iterative two-level argmin as the Pallas kernel: min vruntime, min
    rid among its ties (``rid`` unique -> unique winner), first position
    for sentinel ties — exactly the stable-argsort order."""
    cap = vr.shape[1]
    pos = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), vr.shape)
    # positions already picked are excluded via ``avail`` (set to cap),
    # not just by masking vr: sentinel slots are _IMAX already, so a
    # vr-only mask would re-pick the first sentinel forever once the
    # valid keys run out, where the stable sort keeps advancing.  The
    # winner's rid is masked too, so a picked slot is a full (MAX, MAX)
    # sentinel and cannot win the sentinel tie on its old rid
    avail = pos
    cols = []
    for _ in range(kmax):
        m1 = jnp.min(vr, axis=1, keepdims=True)
        tie_rid = jnp.where(vr == m1, rid, _IMAX)
        m2 = jnp.min(tie_rid, axis=1, keepdims=True)
        win = (vr == m1) & (tie_rid == m2)
        p = jnp.min(jnp.where(win, avail, cap), axis=1).astype(jnp.int32)
        cols.append(p)
        taken = pos == p[:, None]
        vr = jnp.where(taken, _IMAX, vr)
        rid = jnp.where(taken, _IMAX, rid)
        avail = jnp.where(taken, cap, avail)
    return jnp.stack(cols, axis=1)
