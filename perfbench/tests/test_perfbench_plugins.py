"""A configuration, a reference and a traffic stage are each found by
the name a data file gives them, and a key that no code reads is
refused before anything runs.

* Pinned digests: the draws of both mixes and the answers of the
  ``sfs_fleet`` reference, clean and under each configuration's control,
  equal the ones recorded before the reference was found by name.
* Refusals: an unknown configuration, mix, arrival or stage key, a
  dispatch that the reference does not simulate, a ``fleet`` knob that
  the reference does not model or the program's grammar rejects, a
  stage column that names no ``Request`` field: the harness exits
  non-zero and prints no result line.
* Mechanism: a configuration with ``fleet.lifecycle`` cold starts and a
  stage that adds ``func_id``, run through the whole harness at 16 x 2
  against fixture modules that this test puts in place (none of them is
  shipped): ``correct`` against a reference that models cold starts,
  not ``correct`` against one that ignores them.
"""
import copy
import hashlib
import inspect
import io
import json
import os
import shutil
import subprocess
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import harness, system, traffic  # noqa: E402
from perfbench.references import sfs_fleet  # noqa: E402
from perfbench.tests.test_perfbench_harness import (CELLS, run_tiny,  # noqa: E402
                                                    tiny)

LIFECYCLE = "lifecycle:cold=20,keep_alive=600,warm_cap=8"


def digest(arrays: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(arrays):
        v = np.asarray(arrays[k])
        h.update(k.encode())
        h.update(str(v.dtype).encode())
        h.update(str(v.shape).encode())
        h.update(np.ascontiguousarray(v).tobytes())
    return h.hexdigest()


# traffic.draw at 1024 x 12, the mix's own n
DRAWS = {
    ("bimodal_dense", 1): "3b5b544a981b3fd3dc58dec28a5b38b0f9855784053a043ba1f29c050f29f326",
    ("bimodal_dense", 2**31 + 3): "b13e6df752a2a398f9277514624beb3ecc36c851f09378f0e65a87d4fcca7c57",
    ("bimodal_dense", 2**40 + 7): "b4fe40b153747693798447278f80c53b612291894b549a4f8617358b38e5bde0",
    ("timer_bursts", 1): "f4403206d3b4ba3c56db32b00086c39a77e427f06f5e3904c1df820e894d3c01",
    ("timer_bursts", 2**31 + 3): "ee35ede24e9a70d3c7de7c971f1cc6eb9c4905610f03faae303f4117ab3d7cbb",
    ("timer_bursts", 2**40 + 7): "d4d05baad24cd7ed921df2d47179eb84e981e878eb7b9c80720f0ee868e0973f",
}

# sfs_fleet.simulate over (config, mix, servers x cores, control): the
# clean answers and sfs-aware's control as recorded before the move;
# hash's control, sequential_dispatch, as recorded when it was added
SIMS = {
    ("fleet1024_sfs_aware", "bimodal_dense", 16, 2, None): "32e4dad12661abf35b0b7ec92235a32d386ffe2f4bc3830ab49bafbe827e6f6d",
    ("fleet1024_sfs_aware", "bimodal_dense", 16, 2, "batch_dispatch"): "dbf4520700f3e08e95daf82fc1303cfb04c976f8807134ff5db6782d049b306c",
    ("fleet1024_sfs_aware", "bimodal_dense", 64, 12, None): "86577248f54eeb72605f862976a8cf564516ecbae4c4f261c02cf3f1553a13a1",
    ("fleet1024_sfs_aware", "bimodal_dense", 64, 12, "batch_dispatch"): "35d77bd3a0f543065dd7aba85017e44bcea0795e5ec76618e5ff2e9df8181eb6",
    ("fleet1024_hash", "bimodal_dense", 16, 2, None): "d6edcecbb296c71b6752091614bca13e5f92436ce44f9d8e117bd68d60e8b074",
    ("fleet1024_hash", "bimodal_dense", 16, 2, "sequential_dispatch"): "30c331a126e5a695a4e6f601117eab34b0a7ee8c1f75e14e0ef24309f74cebda",
    ("fleet1024_hash", "bimodal_dense", 64, 12, None): "5dcf7e98504499600dd983dce8c27f3b7f8cdf9b6e13cf7e7e5a621897d766ae",
    ("fleet1024_hash", "bimodal_dense", 64, 12, "sequential_dispatch"): "4b48c6981b3d008d2520b78dac114a42e213782a3e1cc95edc96d0dfd26028d7",
    ("fleet1024_sfs_aware", "timer_bursts", 16, 2, None): "e839d03886e0c756e303b35371a79a6521238f0692e99b20a05fe8c636f4bca6",
    ("fleet1024_sfs_aware", "timer_bursts", 16, 2, "batch_dispatch"): "469eb118cc1680fb61e9b62fa2a9ec9c0bcc696d7648286702fa486b2f147c37",
    ("fleet1024_sfs_aware", "timer_bursts", 64, 12, None): "219bdb8075808f0f970ade5d5522e7cf782d33a7cbc156d2b553f2809f47e83d",
    ("fleet1024_sfs_aware", "timer_bursts", 64, 12, "batch_dispatch"): "dea9bf3a82eca143de3c66187104d6364d7ea2e4e92378dec31a1786934dea74",
    ("fleet1024_hash", "timer_bursts", 16, 2, None): "5f8fba0ddb2b1c1f7a33580e0a645f067cd4a318cfe45ebbe32149505358da7b",
    ("fleet1024_hash", "timer_bursts", 16, 2, "sequential_dispatch"): "abc64beffa34a05fbbdcdde7bbabcb389345ee05a145d25069707c896b475d87",
    ("fleet1024_hash", "timer_bursts", 64, 12, None): "b51d3b938f70dcaf7e4e4cbad7af4bd92096b6b7eafb586bc83a56d9aa3a33fa",
    ("fleet1024_hash", "timer_bursts", 64, 12, "sequential_dispatch"): "575df69775f224abef105eb4d4a88053db95975271e785c7aae22930c8297162",
}


@pytest.mark.parametrize("mix_name, seed", sorted(DRAWS))
def test_draws_equal_their_pinned_digest(mix_name, seed):
    d = traffic.draw(traffic.load(mix_name), seed, 1024 * 12)
    assert digest(d) == DRAWS[mix_name, seed]


@pytest.mark.parametrize("key", sorted(SIMS, key=str), ids=str)
def test_reference_answers_equal_their_pinned_digest(key):
    cfg_name, mix_name, servers, cores, control = key
    n = 3000 if servers == 16 else 30_000
    cfg = dict(harness.config(cfg_name), servers=servers, cores=cores,
               slots=64 if servers == 16 else 192)
    assert control in (None, cfg["control"])
    mix = traffic.load(mix_name)
    if mix["arrivals"]["process"] == "bursts":
        mix["arrivals"]["burst_size"] = n // 8
    d = traffic.draw(mix, harness.experiment_seed(2**33 + 17, 0),
                     servers * cores, n=n)
    assert harness.reference(cfg) is sfs_fleet
    assert digest(sfs_fleet.simulate(d, cfg, control=control)) == SIMS[key]


# -- fixture modules, put in place by the tests that use them ------------

def zipf_stage(columns=("func_id",)):
    """A stage that gives each request a function drawn by Zipf
    popularity over ``funcs`` functions."""
    mod = types.ModuleType("perfbench.stages.zipf_fixture")
    mod.PARAMS = ("funcs", "s")
    mod.COLUMNS = columns

    def apply(draws, params, rng):
        w = np.arange(1, params["funcs"] + 1, dtype=float) ** -params["s"]
        draws["func_id"] = rng.choice(params["funcs"], len(draws["arrival"]),
                                      p=w / w.sum()).astype(np.int64)
        return draws
    mod.apply = apply
    return mod


def cold_reference(models_cold: bool):
    """``sfs_fleet`` with the lifecycle's cold starts: a delivery whose
    function is not warm on its server (never run there, idle longer
    than ``keep_alive``, or evicted as the least recently used beyond
    ``warm_cap``) costs ``cold`` more ticks of demand.  Built from
    ``sfs_fleet``'s source with one line added at delivery, so the
    shipped reference keeps no hook for it.  Without ``models_cold`` it
    claims the knob and ignores it."""
    mod = types.ModuleType("perfbench.references.cold_fixture")
    at = "        dispatch[s] += 1\n"
    src = inspect.getsource(sfs_fleet)
    assert src.count(at) == 1
    src = src.replace(at, at + "        need[rid] += _charge(s, rid, t)\n")
    exec(compile(src, "cold_fixture", "exec"), mod.__dict__)
    mod.KNOBS = ("lifecycle",)
    cold_simulate = mod.simulate

    def simulate(draws, cfg, control=None):
        if not models_cold:
            return sfs_fleet.simulate(draws, cfg, control)
        name, _, args = cfg["fleet"]["lifecycle"].partition(":")
        assert name == "lifecycle"
        knobs = {k: int(v) for k, v in (a.split("=") for a in args.split(","))}
        cold, keep, cap = knobs["cold"], knobs["keep_alive"], knobs["warm_cap"]
        warm = [{} for _ in range(int(cfg["servers"]))]
        func = draws["func_id"].tolist()

        def charge(s, rid, t):
            w, f = warm[s], func[rid]
            is_cold = f not in w or t - w[f] > keep
            w[f] = t
            if len(w) > cap:
                del w[min(w, key=lambda g: (w[g], g))]
            return cold if is_cold else 0
        mod._charge = charge
        return cold_simulate(draws, cfg, control)
    mod.simulate = simulate
    return mod


@pytest.fixture
def modules(monkeypatch):
    def put(mod):
        monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return put


def refused(cfg, mix):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(CELLS[0], 7, 0.5, False, t_start=time.perf_counter(),
                     require_chip=False, cfg=cfg, mix=mix, out=out, err=err)
    return rc, out.getvalue(), err.getvalue()


def _cold_cfg(cfg):
    return dict(cfg, reference="cold_fixture", fleet={"lifecycle": LIFECYCLE})


def _zipf_mix(mix):
    return dict(mix, stages=[{"stage": "zipf_fixture", "funcs": 64,
                              "s": 1.1}])


REFUSALS = {
    "config_key": (lambda c: dict(c, lifecycle=LIFECYCLE), None, None),
    "missing_reference": (lambda c: {k: v for k, v in c.items()
                                     if k != "reference"}, None, None),
    "unknown_reference": (lambda c: dict(c, reference="no_such_model"),
                          None, None),
    "control_of_no_reference": (lambda c: dict(c, control="no_such"),
                                None, None),
    "dispatch_the_reference_does_not_simulate": (
        lambda c: dict(c, dispatch="least-outstanding"), None, None),
    "knob_the_reference_does_not_model": (
        lambda c: dict(c, fleet={"lifecycle": LIFECYCLE}), None, None),
    "fleet_key": (lambda c: dict(_cold_cfg(c), fleet={"chaos": "x"}),
                  None, "cold"),
    "fleet_not_an_object": (lambda c: dict(_cold_cfg(c), fleet=LIFECYCLE),
                            None, "cold"),
    "knob_the_program_rejects": (
        lambda c: dict(_cold_cfg(c),
                       fleet={"lifecycle": "lifecycle:colder=20"}),
        None, "cold"),
    "mix_key": (None, lambda m: dict(m, burst=3), None),
    "arrival_key": (None, lambda m: dict(m, arrivals=dict(
        m["arrivals"], burst_size=5)), None),
    "unknown_stage": (None, lambda m: dict(m, stages=[{"stage": "nope"}]),
                      None),
    "stage_param": (None, lambda m: dict(m, stages=[
        {"stage": "zipf_fixture", "funcs": 8, "skew": 1.1}]), "zipf"),
    "stage_column_not_a_request_field": (None, _zipf_mix, "popularity"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_harness_refuses_what_nothing_reads(case, modules):
    edit_cfg, edit_mix, put = REFUSALS[case]
    if put == "cold":
        modules(cold_reference(True))
    elif put == "zipf":
        modules(zipf_stage())
    elif put == "popularity":
        modules(zipf_stage(columns=("func_id", "popularity")))
    _, cfg, mix = tiny(CELLS[0])
    cfg = edit_cfg(cfg) if edit_cfg else cfg
    mix = edit_mix(mix) if edit_mix else mix
    rc, out, err = refused(cfg, mix)
    assert rc != 0 and out == "", err
    assert "refused" in err


def test_stage_column_outside_its_columns_is_refused(modules):
    """A stage that adds a column it did not declare fails its draw."""
    stage = zipf_stage()
    inner = stage.apply

    def apply(draws, params, rng):
        out = inner(draws, params, rng)
        out["stall_events"] = np.zeros(len(out["arrival"]))
        return out
    stage.apply = apply
    modules(stage)
    _, _, mix = tiny(CELLS[0])
    with pytest.raises(ValueError, match="stall_events"):
        traffic.draw(_zipf_mix(mix), 3, 32)


def test_command_refuses_an_unknown_key_before_any_run(tmp_path):
    """``run.py`` in a checkout whose configuration has a key that no
    code reads: exit non-zero, no result line, on a host without a chip
    too (the refusal comes first)."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    path = tmp_path / "perfbench" / "configs" / "fleet1024_sfs_aware.json"
    cfg = json.loads(path.read_text())
    cfg["keep_alive"] = 600
    path.write_text(json.dumps(cfg))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                        "fleet1024_sfs_aware.bimodal_dense", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "keep_alive" in p.stderr and "refused" in p.stderr


def test_stages_leave_the_base_draw_alone(modules):
    """Stage k draws from a stream of its own: the base arrays are the
    same with or without stages, and two stages draw apart."""
    modules(zipf_stage())
    mix = traffic.load("bimodal_dense")
    base = traffic.draw(mix, 2**31 + 5, 64, n=5000)
    staged = traffic.draw(_zipf_mix(mix), 2**31 + 5, 64, n=5000)
    for k in ("arrival", "n_tokens", "eta_hint"):
        assert np.array_equal(base[k], staged[k])
    twice = copy.deepcopy(_zipf_mix(mix))
    twice["stages"] *= 2                # the second one's func_id wins
    again = traffic.draw(twice, 2**31 + 5, 64, n=5000)
    assert not np.array_equal(again["func_id"], staged["func_id"])
    reqs = traffic.requests(staged)
    assert [r.func_id for r in reqs] == staged["func_id"].tolist()


@pytest.mark.parametrize("models_cold", [True, False],
                         ids=["models_cold", "ignores_cold"])
@pytest.mark.parametrize("cell_name", ["fleet1024_sfs_aware.bimodal_dense",
                                       "fleet1024_hash.bimodal_dense"])
def test_new_config_reference_and_stage_through_the_harness(
        cell_name, models_cold, modules):
    modules(cold_reference(models_cold))
    modules(zipf_stage())
    _, cfg, mix = tiny(cell_name)
    seen = []

    def recording(spec, reqs, tel=None, max_ticks=None):
        seen.append((spec.lifecycle, len({r.func_id for r in reqs})))
        return system.run(spec, reqs, tel, max_ticks=max_ticks)

    rc, res, err = run_tiny(cell_name, cfg=_cold_cfg(cfg),
                            mix=_zipf_mix(mix), system_run=recording)
    assert rc == 0 and res["failed"] == 0, err
    from repro.core.spec import LifecycleSpec
    assert seen and all(
        lc == LifecycleSpec.parse(LIFECYCLE) and funcs > 8
        for lc, funcs in seen)
    assert res["correct"] is models_cold, err
