"""Backend dispatcher for the per-group fair-share pick.

On TPU the Pallas kernel runs natively; everywhere else the iterative
argmin runs in plain jnp — XLA:CPU's comparator sort makes the argsort
reference the slowest option there, and interpret-mode Pallas pays a
per-op Python tax the hot loop cannot afford.  ``pick_order_ref`` stays
the oracle both are tested against.  The jitted group step in
``serving/jax_cluster.py`` calls this, so the same tick body compiles
against whichever implementation fits the platform; ``pick_impl`` says
which one that is.
"""
from __future__ import annotations

import jax

from repro.kernels.group_pick.kernel import pick_order_pallas
from repro.kernels.group_pick.ref import pick_order_argmin, pick_order_ref

__all__ = ["pick_impl", "pick_order", "pick_order_argmin", "pick_order_ref"]


def pick_impl() -> str:
    """``"pallas"`` on TPU, ``"argmin"`` elsewhere: the implementation
    :func:`pick_order` compiles into a jitted step on this backend."""
    return "pallas" if jax.default_backend() == "tpu" else "argmin"


def pick_order(vr, rid, kmax: int):
    """``[G, CAP]`` int32 ``(vruntime, rid)`` keys (sentinel INT32_MAX
    for empty slots) -> ``[G, kmax]`` pool positions, best first."""
    if pick_impl() == "pallas":
        return pick_order_pallas(vr, rid, kmax)
    return pick_order_argmin(vr, rid, kmax)
