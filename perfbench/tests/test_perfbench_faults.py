"""The comparison that decides ``correct`` has to fail what it guards
against: the control (the reference with one stated guarantee broken)
at a size a test run holds, and whole harness runs with the timed path
broken underneath, one for each fault a one-chip fleet cell can have.
(The exchange between chips does not exist on one chip.)"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import compare, control, system  # noqa: E402
from perfbench.tests.test_perfbench_harness import (CELLS, run_tiny,  # noqa: E402
                                                    tiny)


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_is_not_correct(cell_name):
    _, cfg, mix = tiny(cell_name)
    cfg.update(servers=64, cores=12, slots=192)
    for seed in (1, 2, 3):
        checks = control.readings(cfg, mix, seed, n=30_000)
        assert not compare.passed(checks), (seed, checks)


def stuck(spec, reqs, tel=None, max_ticks=None):
    """A step that leaves its state unchanged never finishes a request:
    the program runs out of ticks."""
    return system.run(spec, reqs, tel, max_ticks=1)


def half_batch(spec, reqs, tel=None, max_ticks=None):
    """Half of the requests left out; the result covers the rest."""
    return system.run(spec, reqs[::2], tel, max_ticks=max_ticks)


def answer_altered(spec, reqs, tel=None, max_ticks=None):
    """One request's finish tick altered where the result is produced."""
    res = system.run(spec, reqs, tel, max_ticks=max_ticks)
    res.finish[len(res.finish) // 2] += 1
    return res


def departs_from_config(spec, reqs, tel=None, max_ticks=None):
    """Servers start SFS at a 16-tick slice where the configuration
    states 32."""
    import dataclasses
    servers = tuple(dataclasses.replace(s, scheduler="sfs:slice_init=16")
                    for s in spec.servers)
    return system.run(dataclasses.replace(spec, servers=servers), reqs,
                      tel, max_ticks=max_ticks)


@pytest.mark.parametrize("fault", [stuck, half_batch, answer_altered,
                                   departs_from_config],
                         ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(fault):
    rc, res, err = run_tiny(CELLS[0], system_run=fault)
    assert rc == 0
    assert res["correct"] is False, err
    assert list(res)[-1] == "checks"


def test_control_in_the_programs_place_is_not_correct():
    """The control put in the program's place, through a whole run."""
    from perfbench import reference
    _, cfg, mix = tiny(CELLS[0])

    def control_program(spec, reqs, tel=None, max_ticks=None):
        d = {"arrival": np.array([r.arrival for r in reqs]),
             "n_tokens": np.array([r.n_tokens for r in reqs]),
             "eta_hint": np.array([r.eta_hint for r in reqs]),
             "prompt_len": reqs[0].prompt_len}
        sim = reference.simulate(d, cfg, control=cfg["control"])

        class Result:
            n = len(reqs)
            rids = np.arange(len(reqs))
            finish = sim["finish"]
            n_ctx = sim["n_ctx"]
            demoted = sim["demoted"]
            dispatch_counts = list(sim["dispatch"])
        return Result()

    rc, res, err = run_tiny(CELLS[0], system_run=control_program)
    assert rc == 0 and res["correct"] is False, err
