"""Operations and bytes of the program's kernels, computed from their
shapes here, with the benchmark, so that no change to a kernel can
change its yardstick."""


def group_pick_bytes(G: int, CAP: int, kmax: int) -> int:
    """HBM bytes one call of the ``group_pick`` Pallas pick must move:
    two ``[G, CAP]`` int32 key arrays in (vruntime, rid), one
    ``[G, kmax]`` int32 array of picked positions out.  Its work is
    integer compares (no published int32 vector peak on the v5e), so
    its roofline is the bytes bound."""
    return (2 * G * CAP + G * kmax) * 4
