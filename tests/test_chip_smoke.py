"""``chip_smoke.py`` on the CPU: its phases at a tiny fleet, and its
refusal to run anywhere but on a TPU."""
import importlib.util
import os

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_a_host_without_tpu(smoke, capsys):
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("this host has a TPU")
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "no TPU" in err


def test_kernel_phase_in_interpret_mode(smoke):
    smoke.check_kernel(cases=((16, 32, 8), (12, 32, 4), (3, 16, 1)),
                       interpret=True)


def test_pick_cases_exhaust_valid_keys(smoke):
    import numpy as np
    vr, rid = smoke.pick_cases(64, 32)
    valid = (vr < smoke._IMAX).sum(axis=1)
    assert valid[0] == 0
    assert (valid < 8).sum() > 1 and (valid > 8).sum() > 1
    assert len(np.unique(rid[rid < smoke._IMAX])) == valid.sum()


def test_main_path_phase_tiny_fleet(smoke):
    rows = smoke.check_main_path(engines=16, lanes=4, n=2000, load=0.9,
                                 seed=11)
    assert [(r["engine"], r["policy"]) for r in rows] == [
        ("jax", "sfs-aware"), ("jax", "hash"),
        ("vector", "sfs-aware"), ("vector", "hash")]
    for r in rows:
        assert r["wall_s"] > 0 and r["compile_s"] >= 0
        assert r["shed"] == 0 and len(r["fp"]) == 16
    assert rows[0]["compile_s"] > 0          # first jax call compiles
    assert rows[2]["compile_s"] == 0         # vector never touches jax
    assert rows[2]["compiles"] == 0


def test_main_path_phase_fails_on_a_wrong_pin(smoke):
    with pytest.raises(smoke.PhaseFailed, match="fingerprints"):
        smoke.check_main_path(engines=16, lanes=4, n=2000, load=0.9,
                              seed=11, pinned={"sfs-aware": "0" * 16,
                                               "hash": "0" * 16})


def test_pinned_fingerprints_are_the_fleet1024_baseline(smoke):
    assert smoke.pinned_fingerprints() == {"sfs-aware": "19f52ff65d862d71",
                                           "hash": "d7b157f523ffc0f9"}


def test_kernel_present_phase_fails_without_pallas(smoke):
    from repro.kernels.group_pick import pick_impl
    if pick_impl() == "pallas":
        pytest.skip("the step compiles the Pallas pick here")
    with pytest.raises(smoke.PhaseFailed, match="tpu_custom_call"):
        smoke.check_kernel_present(engines=16, lanes=4)


def test_compile_cache_leaves_a_placed_directory_alone(monkeypatch,
                                                       tmp_path):
    import jax

    from repro.launch.compile_cache import ENV, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(ENV, str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_ignored_path(monkeypatch):
    import jax

    from repro.launch.compile_cache import ENV, enable_compile_cache
    monkeypatch.delenv(ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == os.path.join(os.path.realpath(ROOT), ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
