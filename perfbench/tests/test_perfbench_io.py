"""The I/O cell's own pieces at 16 x 2 on the CPU: the ``leading_io``
stage leaves ``bimodal_dense``'s draws alone and repeats for a seed;
the ``sfs_io`` reference equals the program on the ``io_dense`` mix,
under both dispatches it models and with slots short; its
``io_oblivious`` control does not; and the two stall readers read a
profiled session and find nothing in a program without stalls."""
import importlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import compare, control, harness, system, traffic  # noqa: E402
from perfbench.references import sfs_io  # noqa: E402
from perfbench.tests.test_perfbench_harness import tiny  # noqa: E402

CELL = "fleet1024_sfs_io.io_dense"
READERS = ("stall_sync_ms_per_tick", "stall_parks_per_request")


@pytest.mark.parametrize("seed", [7, 2**31 + 3])
def test_leading_io_leaves_the_base_draw_alone(seed):
    base = traffic.draw(traffic.load("bimodal_dense"), seed, 1024 * 12,
                        n=20_000)
    io = traffic.draw(traffic.load("io_dense"), seed, 1024 * 12, n=20_000)
    for k in ("arrival", "n_tokens", "eta_hint"):
        assert np.array_equal(base[k], io[k])
    again = traffic.draw(traffic.load("io_dense"), seed, 1024 * 12,
                         n=20_000)
    col = io["stall_events"]
    assert col.dtype == object and col.shape == (20_000,)
    assert col.tolist() == again["stall_events"].tolist()
    # 75% start with one I/O of U[10, 100) ms: one or two 50 ms ticks
    has = np.array([len(e) == 1 for e in col])
    assert abs(has.mean() - 0.75) < 0.01
    assert all(e == () or (e[0][0] == 0 and e[0][1] in (1, 2)) for e in col)
    reqs = traffic.requests(io)
    assert [r.stall_events for r in reqs[:50]] == col[:50].tolist()


@pytest.mark.parametrize("dispatch,slots", [("sfs-aware", 64),
                                            ("hash", 64),
                                            ("sfs-aware", 3)])
def test_reference_equals_the_program(dispatch, slots):
    _, cfg, mix = tiny(CELL)
    cfg = dict(cfg, dispatch=dispatch, slots=slots)
    harness.check_config(cfg)
    d = traffic.draw(mix, harness.experiment_seed(2**33 + 17, 0), 32)
    ref = sfs_io.simulate(d, cfg)
    res = system.run(system.spec(cfg), traffic.requests(d),
                     max_ticks=int(d["arrival"].max()) + 20_000)
    checks = compare.compare(compare.answers(res), ref)
    assert compare.passed(checks), checks
    assert (ref["n_ctx"] > 0).mean() > 0.7        # the parks are there


def test_io_oblivious_control_is_not_correct():
    _, cfg, mix = tiny(CELL)
    for seed in (1, 2, 3):
        checks = control.readings(cfg, mix, seed)
        assert not compare.passed(checks), (seed, checks)


def test_stall_readers_read_a_profiled_session():
    _, cfg, mix = tiny(CELL)
    d = traffic.draw(mix, 5, 32)
    tel = system.profile_session()
    res = system.run(system.spec(cfg), traffic.requests(d), tel)
    e = {"n": len(d["arrival"]), "completed": int(res.n),
         "ticks": int(res.finish.max()), "phases": system.phases(tel),
         "device_profiled": False}
    ctx = {"experiments": [e], "host_experiments": [e]}
    share = np.mean([len(x) > 0 for x in d["stall_events"]])
    parks = importlib.import_module(
        "perfbench.metrics.stall_parks_per_request").read(ctx)
    assert parks == pytest.approx(share, abs=1e-12)
    ms = importlib.import_module(
        "perfbench.metrics.stall_sync_ms_per_tick").read(ctx)
    assert 0 < ms < 1e3 * e["phases"]["step"][0] / e["phases"]["step"][1]


@pytest.mark.parametrize("metric", READERS)
def test_stall_reader_finds_nothing_without_stall_spans(metric):
    """Phases as a program without the stall spans records them."""
    mod = importlib.import_module(f"perfbench.metrics.{metric}")
    older = {"route": [0.5, 40], "step": [0.9, 40], "jax_step": [0.6, 40],
             "jax_sync": [0.4, 40]}
    e = {"n": 1000, "completed": 1000, "ticks": 50, "phases": older}
    assert mod.read({"experiments": [e], "host_experiments": [e]}) is None
    assert mod.read({"experiments": [], "host_experiments": []}) is None
