"""The benchmark harness on the CPU at a tiny fleet (16 servers x 2
lanes): its traffic generators, its readers, its trace reduction on a
profile recorded on a v5e, its refusal to run without a TPU, and one
whole run of every cell in ``BENCHMARK.json`` against the reference."""
import gzip
import importlib
import io
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import (harness, kernels, peaks, system, trace_reduce,  # noqa: E402
                       traffic)

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [c["name"] for c in BENCH["workloads"]]
FIXTURE = os.path.join(ROOT, "perfbench", "fixtures", "fleet16.xplane.pb.gz")


def tiny(cell_name: str, n: int = 3000):
    """The cell's configuration and mix cut to 16 x 2 and ``n``
    requests, bursts scaled to keep 8 of them.  (Two lanes: a 16 x 4
    fleet's programs, once compiled in a test process, would spare
    ``tests/test_chip_smoke.py`` the first compile it checks for.)"""
    cell = harness.entry(BENCH["workloads"], cell_name, "workload")
    cfg = harness.config(cell["config"])
    cfg.update(servers=16, cores=2, slots=64)
    mix = traffic.load(cell["traffic"])
    mix["n"] = n
    mix["warmup"] = {"n": n // 2}
    if mix["arrivals"]["process"] == "bursts":
        mix["arrivals"]["burst_size"] = n // 8
    return cell, cfg, mix


def run_tiny(cell_name, trace=False, system_run=None, cfg=None, mix=None):
    _, cfg0, mix0 = tiny(cell_name)
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(cell_name, 2**33 + 17, 0.5, trace,
                     t_start=time.perf_counter(), require_chip=False,
                     cfg=cfg or cfg0, mix=mix or mix0,
                     system_run=system_run, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), err.getvalue()


@pytest.mark.parametrize("seed", [11, 2**31 + 3])
def test_bimodal_dense_is_tick_workload_spec(seed):
    from repro.core.spec import TickWorkloadSpec
    mix = traffic.load("bimodal_dense")
    got = traffic.requests(traffic.draw(mix, seed, 64, n=20_000))
    want = TickWorkloadSpec(n=20_000, load=mix["arrivals"]["load"],
                            seed=seed, short_frac=mix["short_frac"],
                            short_range=tuple(mix["short_range"]),
                            long_range=tuple(mix["long_range"]),
                            prompt_len=mix["prompt_len"],
                            hints=mix["hints"]).generate(64)
    assert [(r.rid, r.arrival, r.n_tokens, r.eta_hint, r.prompt_len)
            for r in got] == [(r.rid, r.arrival, r.n_tokens, r.eta_hint,
                               r.prompt_len) for r in want]


def test_timer_bursts_land_on_eight_ticks():
    mix = traffic.load("timer_bursts")
    d = traffic.draw(mix, 2**32 + 1, 8192)
    ticks, counts = np.unique(d["arrival"], return_counts=True)
    assert ticks.tolist() == [256 * k for k in range(8)]
    assert counts.tolist() == [62_500] * 8


def test_benchmark_names_files_of_its_own():
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in BENCH["workloads"]:
        harness.check_config(harness.config(w["config"]))
        traffic.check(traffic.load(w["traffic"]))
    for m in BENCH["per_layer"]:
        mod = importlib.import_module(f"perfbench.metrics.{m['name']}")
        assert callable(mod.read)


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_runs_correct_on_cpu(cell_name):
    rc, res, err = run_tiny(cell_name)
    assert rc == 0 and res["correct"], err
    assert res["failed"] == 0 and res["attempted"] >= 3000
    assert set(res["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    assert res["metrics"]["sim_requests_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")


def test_warm_up_repeats_until_no_new_program():
    """Set-up runs warm-up experiments until one builds no program, so
    nothing is built in the window.  (A fleet size of its own, so that
    no other test has built its programs in this process.)"""
    _, cfg, mix = tiny(CELLS[0])
    rc, res, err = run_tiny(CELLS[0], cfg=dict(cfg, servers=24), mix=mix)
    line = next(x for x in err.splitlines()
                if x.startswith("warm-up programs built"))
    built = json.loads(line.split(": ", 1)[1])
    assert rc == 0 and len(built) >= 2 and built[0] > 0
    assert built[-1] == 0 and all(b > 0 for b in built[:-1])
    assert "'window': 0," in err


def test_reference_refuses_an_unknown_dispatch():
    from perfbench.references import sfs_fleet
    _, cfg, mix = tiny(CELLS[0])
    d = traffic.draw(mix, 3, 32, n=100)
    with pytest.raises(ValueError):
        sfs_fleet.simulate(d, dict(cfg, dispatch="least-outstanding"))


def test_no_tpu_exits_nonzero_without_result():
    import jax
    if jax.devices()[0].platform == "tpu":
        pytest.skip("this host has a TPU")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def profile_ctx():
    """A reader context from one profile-only experiment at 16 x 2, and
    the device profile recorded on a v5e at 16 x 4."""
    _, cfg, mix = tiny(CELLS[0])
    d = traffic.draw(mix, 5, 32)
    tel = system.profile_session()
    res = system.run(system.spec(cfg), traffic.requests(d), tel)
    e = {"n": len(d["arrival"]), "completed": int(res.n),
         "ticks": int(res.finish.max()), "phases": system.phases(tel),
         "device_profiled": False}
    with gzip.open(FIXTURE) as f:
        import jax
        red = trace_reduce.reduce_profile(
            jax.profiler.ProfileData.from_serialized_xspace(f.read()))
    return {"config": dict(cfg, servers=16, cores=4), "mix": mix,
            "experiments": [e], "host_experiments": [e],
            "compiles": {"count": {"setup": 3, "window": 0},
                         "seconds": {"setup": 1.5, "window": 0.0}},
            "trace": red, "peaks": peaks.lookup("TPU v5 lite")}


@pytest.fixture(scope="module")
def ctx():
    return profile_ctx()


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_reader_returns_a_number(ctx, metric):
    mod = importlib.import_module(f"perfbench.metrics.{metric}")
    v = mod.read(ctx)
    assert v is not None and np.isfinite(v) and v >= 0
    if "%" == next(m["unit"] for m in BENCH["per_layer"]
                   if m["name"] == metric):
        assert v <= 100


def test_readers_find_nothing_without_a_trace(ctx):
    bare = dict(ctx, trace=None)
    for m in BENCH["per_layer"]:
        if m["source"] == "device_trace":
            mod = importlib.import_module(f"perfbench.metrics.{m['name']}")
            assert mod.read(bare) is None


def test_trace_reduction_of_a_recorded_v5e_profile(ctx):
    red = ctx["trace"]
    assert red["chips"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["device_ops"] and len(red["device_ops"]) <= 10
    assert len(red["idle_gaps"]) <= 10
    # ops nest (a scan's while holds its body's ops), so each one alone
    # is bounded by the busy time, not their sum
    assert all(s <= red["busy_s"] for _, s in red["device_ops"])
    from perfbench.metrics import group_pick_roofline
    found = group_pick_roofline.calls(red)
    assert found and all((G, CAP, kmax) == (16, 32, 4)
                         for _, _, G, CAP, kmax in found)


def test_union_of_intervals():
    total, gaps = trace_reduce._union([(0, 4), (2, 6), (8, 9), (9, 10)])
    assert total == 8 and gaps == [(6, 8)]


def test_group_pick_bytes():
    assert kernels.group_pick_bytes(1024, 32, 8) == (2 * 1024 * 32
                                                     + 1024 * 8) * 4


def test_peaks_refuse_an_unknown_device():
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.lookup("cpu")
