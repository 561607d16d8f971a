#!/usr/bin/env python3
"""Readings of the control: the configuration's plain reference
(``references/<reference>.py``) with one guarantee of the configuration
broken (its ``control`` key), put in the program's place and compared
with the clean reference exactly as a run compares the program.  The
control has to come out not correct.

    python3 perfbench/control.py --workload <cell> --seed <n> [--seed ...]

Prints one JSON line per seed with every number compared; no chip is
used.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]   # a stage's Request

from perfbench import compare, harness, traffic  # noqa: E402


def as_answers(sim: dict) -> dict:
    """A reference result in the shape ``compare.answers`` gives."""
    return {"rids": np.arange(len(sim["finish"])),
            "finish": sim["finish"], "n_ctx": sim["n_ctx"],
            "demoted": sim["demoted"], "dispatch": sim["dispatch"]}


def readings(cfg: dict, mix: dict, seed: int, n: int = None) -> dict:
    lanes = int(cfg["servers"]) * int(cfg["cores"])
    d = traffic.draw(mix, harness.experiment_seed(seed, 0), lanes, n=n)
    reference = harness.reference(cfg)
    ref = reference.simulate(d, cfg)
    ctl = reference.simulate(d, cfg, control=cfg["control"])
    return compare.compare(as_answers(ctl), ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    a = ap.parse_args(argv)
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.entry(bench["workloads"], a.workload, "workload")
    cfg = harness.config(cell["config"])
    mix = traffic.load(cell["traffic"])
    harness.check_config(cfg)
    traffic.check(mix)
    for s in a.seed:
        checks = readings(cfg, mix, s)
        print(json.dumps({"workload": a.workload, "seed": s,
                          "control": cfg["control"],
                          "correct": compare.passed(checks),
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
