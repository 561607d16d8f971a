"""Pallas kernel: per-group k-smallest ``(vruntime, rid)`` pick.

One grid step handles a block of ``gb`` engine groups; each group's pool
keys live in VMEM and the ``kmax`` winners are extracted by iterative
two-level argmin (min vruntime, then min rid among its ties — ``rid`` is
unique, so the winner is unique; sentinel ``INT32_MAX`` slots resolve by
first-position argmin, matching the stable-argsort reference).  ``kmax``
is the lane count — single digits — so the loop beats materializing a
full sort network for the tiny pools this serves.

TPU lowering rules the kernel keeps (Mosaic refuses the rest):

* no ``scatter``: the i-th winner lands in its output column by a select
  against a lane iota, not ``.at[:, i].set``;
* blocks respect the (8, 128) tiling: the row block ``gb`` is rounded up
  to a multiple of 8 and G is padded with all-sentinel rows to a
  multiple of it (their picks are sliced off); the pool axis and the
  ``kmax`` axis are always whole, so any ``CAP`` and ``kmax`` are legal.

Off-TPU callers go through the jnp implementation in ``ops.py`` instead
(or run this kernel in interpret mode, as ``tests/test_jax_cluster.py``
does for parity).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_IMAX = 2**31 - 1        # plain int: jnp scalars may not be captured
_SUBLANES = 8            # int32 row tile of a TPU vreg


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pick_kernel(vr_ref, rid_ref, out_ref, *, kmax: int):
    vr = vr_ref[:, :]                          # [gb, CAP] int32
    rid = rid_ref[:, :]
    cap = vr.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, vr.shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)

    def body(i, carry):
        vr_i, rid_i, avail, out = carry
        m1 = jnp.min(vr_i, axis=1, keepdims=True)          # min vruntime
        tie_rid = jnp.where(vr_i == m1, rid_i, _IMAX)
        m2 = jnp.min(tie_rid, axis=1, keepdims=True)       # min rid in tie
        win = (vr_i == m1) & (tie_rid == m2)
        # first AVAILABLE position of the winner: unique for valid keys;
        # sentinel ties advance position by position like the stable
        # sort (a vr-only mask would re-pick the first sentinel forever)
        p = jnp.min(jnp.where(win, avail, cap), axis=1, keepdims=True)
        out = jnp.where(col == i, p, out)                  # column i
        # the winner becomes a full (MAX, MAX) sentinel: with its rid
        # left in place it would win the sentinel tie once the valid
        # keys run out
        taken = pos == p
        vr_i = jnp.where(taken, _IMAX, vr_i)
        rid_i = jnp.where(taken, _IMAX, rid_i)
        avail = jnp.where(taken, cap, avail)
        return vr_i, rid_i, avail, out

    out0 = jnp.zeros(out_ref.shape, jnp.int32)
    *_, out = jax.lax.fori_loop(0, kmax, body, (vr, rid, pos, out0))
    out_ref[:, :] = out


@partial(jax.jit, static_argnames=("kmax", "gb", "interpret"))
def pick_order_pallas(vr: jnp.ndarray, rid: jnp.ndarray, kmax: int,
                      gb: int = 8, interpret: bool = False) -> jnp.ndarray:
    """``[G, CAP]`` int32 keys -> ``[G, kmax]`` winning pool positions.

    ``gb`` is the row block, rounded up to the 8-row tile and capped at
    G rounded up likewise; G is padded with sentinel rows to a multiple
    of it."""
    G, CAP = vr.shape
    gb = min(_round_up(max(gb, 1), _SUBLANES), _round_up(G, _SUBLANES))
    Gp = _round_up(G, gb)
    pad = ((0, Gp - G), (0, 0))
    vr = jnp.pad(vr.astype(jnp.int32), pad, constant_values=_IMAX)
    rid = jnp.pad(rid.astype(jnp.int32), pad, constant_values=_IMAX)
    out = pl.pallas_call(
        partial(_pick_kernel, kmax=kmax),
        grid=(Gp // gb,),
        in_specs=[pl.BlockSpec((gb, CAP), lambda g: (g, 0)),
                  pl.BlockSpec((gb, CAP), lambda g: (g, 0))],
        out_specs=pl.BlockSpec((gb, kmax), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((Gp, kmax), jnp.int32),
        interpret=interpret,
    )(vr, rid)
    return out[:G]
