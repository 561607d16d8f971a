from repro.kernels.group_pick.ops import (pick_impl,  # noqa: F401
                                          pick_order, pick_order_argmin,
                                          pick_order_ref)
