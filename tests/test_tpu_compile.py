"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts (a ``scatter`` in a
Pallas kernel, a block that breaks the (8, 128) tiling), so the fleet's
kernel and one whole group step are compiled here for the chip at their
real sizes.  Nothing runs; these say nothing about results or times.
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
from functools import partial

import pytest


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler or library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("G,CAP,kmax", [(1024, 32, 8), (16, 32, 4),
                                        (12, 32, 4), (1024, 64, 8)])
def test_group_pick_kernel_compiles_for_v5e(one_chip, G, CAP, kmax):
    import jax
    import jax.numpy as jnp

    from repro.kernels.group_pick.kernel import pick_order_pallas
    keys = jax.ShapeDtypeStruct((G, CAP), jnp.int32, sharding=one_chip)
    compiled = jax.jit(partial(pick_order_pallas, kmax=kmax)).lower(
        keys, keys).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fleet1024_group_step_compiles_with_pallas_pick(one_chip,
                                                        monkeypatch):
    """The 1024-engine x 8-lane tick body as the chip runs it: the pick
    is switched to the Pallas kernel here, since ``pick_order`` chooses
    by the attached backend, which is the CPU."""
    import repro.kernels.group_pick as group_pick
    from repro.kernels.group_pick.kernel import pick_order_pallas
    from repro.serving.jax_cluster import _build_fns, step_arg_specs
    monkeypatch.setattr(group_pick, "pick_order",
                        lambda vr, rid, kmax: pick_order_pallas(vr, rid,
                                                                kmax))
    G, L, QCAP, CAP, ACAP = 1024, 8, 64, 32, 256
    # a fresh build, not the module-wide cache, so no later test can
    # reuse a step traced with the patched pick
    step = _build_fns.__wrapped__(G, L, QCAP, CAP, True)[0]
    compiled = step.lower(*step_arg_specs(G, L, QCAP, CAP, ACAP,
                                          sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16 * 2**20


@pytest.mark.parametrize("program", ["step", "scan"])
def test_fleet1024_stall_programs_compile_with_pallas_pick(one_chip,
                                                           monkeypatch,
                                                           program):
    """The stall variant of the group tick (the 1024 x 12 fleet of the
    I/O cell, one stall event a request) compiles for the chip, per
    tick and as a scan chunk."""
    import repro.kernels.group_pick as group_pick
    from repro.kernels.group_pick.kernel import pick_order_pallas
    from repro.serving.jax_cluster import _build_fns, step_arg_specs
    monkeypatch.setattr(group_pick, "pick_order",
                        lambda vr, rid, kmax: pick_order_pallas(vr, rid,
                                                                kmax))
    G, L, QCAP, CAP, ACAP = 1024, 12, 64, 64, 1024
    stl = (CAP, 1, True)
    step, scan, _ = _build_fns.__wrapped__(G, L, QCAP, CAP, True, False,
                                           stl)
    state, arr, t, S, thr = step_arg_specs(G, L, QCAP, CAP, ACAP,
                                           sharding=one_chip, stl=stl)
    if program == "step":
        compiled = step.lower(state, arr, t, S, thr).compile()
    else:
        compiled = scan.lower(state, t, S, thr).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes < 16 * 2**20
