"""The one traffic generator: every mix is a JSON file of parameters
under ``perfbench/traffic/``, read by :func:`load` and drawn by
:func:`draw`.

A mix is a service-time law and an arrival process:

* service: ``short_frac`` of requests draw a decode demand uniform on
  ``short_range``, the rest on ``long_range`` (ticks, high end open);
  ``hints`` attaches the front end's ETA hint (demand + 1, a max-tokens
  cap), ``prompt_len`` is the prompt each request carries;
* ``arrivals.process = "poisson"``: exponential gaps scaled so that the
  offered work over the fleet's lanes is ``arrivals.load`` — the
  arithmetic of ``repro.core.spec.TickWorkloadSpec.generate``, copied
  draw for draw, so the yardstick cannot move with the program;
* ``arrivals.process = "bursts"``: ``burst_size`` requests land together
  on the first tick of every ``period``-tick period (timer triggers).

``warmup.n`` is the length of each set-up experiment (the harness
repeats them until one builds no new program).  ``assumed`` names the
parameters set here rather than taken from the mix's source.

``stages`` (optional) lists ``{"stage": <module>, <param>: ...}``
entries, applied in order to every draw: ``perfbench/stages/<module>.py``
gives ``PARAMS`` (the parameters it reads), ``COLUMNS`` (the per-request
columns it adds, each a field of the program's ``Request``) and
``apply(draws, params, rng) -> draws``.  Stage ``k`` draws from a stream
of its own, derived from the draw's seed and ``k``, so the base arrays
(``arrival``, ``n_tokens``, ``eta_hint``) are the same with or without
stages.  A key, parameter or column that nothing reads is refused by
:func:`check` before anything runs.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

from perfbench import lookup

HERE = os.path.dirname(os.path.abspath(__file__))
MIX_KEYS = {"n", "short_frac", "short_range", "long_range", "prompt_len",
            "hints", "arrivals", "warmup"}
ARRIVAL_KEYS = {"poisson": {"process", "load"},
                "bursts": {"process", "burst_size", "period"}}
BASE = ("arrival", "n_tokens", "eta_hint", "prompt_len")


def load(name: str) -> dict:
    """The mix file ``traffic/<name>.json``, as written (:func:`check`
    refuses what nothing reads)."""
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def request_fields() -> set:
    """The fields of the program's ``Request`` that a stage may set."""
    from repro.serving.request import Request
    return {f.name for f in dataclasses.fields(Request)} - {"rid", *BASE}


def check(mix: dict):
    """Refuses (``ValueError``) a mix with a key, an arrival parameter, a
    stage parameter or a stage column that nothing reads."""
    lookup.check_keys("mix", mix, MIX_KEYS, {"stages", "name"})
    arr = mix["arrivals"]
    if arr.get("process") not in ARRIVAL_KEYS:
        raise ValueError(f"unknown arrival process {arr.get('process')!r}")
    lookup.check_keys(f"{arr['process']} arrivals", arr,
                      ARRIVAL_KEYS[arr["process"]])
    lookup.check_keys("warmup", mix["warmup"], {"n"})
    for entry in mix.get("stages", ()):
        mod = lookup.module("stages", entry.get("stage"))
        lookup.check_keys(f"stage {entry['stage']}", entry, {"stage"},
                          mod.PARAMS)
        bad = sorted(set(mod.COLUMNS) - request_fields())
        if bad:
            raise ValueError(f"stage {entry['stage']}: column(s) {bad} "
                             f"name no field of the program's Request")


def draw(mix: dict, seed: int, total_lanes: int, n: int = None) -> dict:
    """Per-request arrays ``arrival``, ``n_tokens``, ``eta_hint`` (-1 = no
    hint) and each stage's columns, rid-ordered, for ``n`` requests (the
    mix's own by default).  The same seed gives the same arrays."""
    n = int(mix["n"] if n is None else n)
    rng = np.random.default_rng(seed)
    # same draw order as TickWorkloadSpec.generate: the class coin, then
    # both uniform arrays (np.where evaluates both)
    svc = np.where(rng.random(n) < mix["short_frac"],
                   rng.integers(*mix["short_range"], n),
                   rng.integers(*mix["long_range"], n))
    arr = mix["arrivals"]
    if arr["process"] == "poisson":
        span = svc.sum() / (arr["load"] * total_lanes)
        iats = rng.exponential(1.0, n)
        arrival = np.cumsum(iats * span / iats.sum()).astype(int)
    elif arr["process"] == "bursts":
        arrival = (np.arange(n) // int(arr["burst_size"])) * int(arr["period"])
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    hint = svc + 1 if mix["hints"] else np.full(n, -1)
    d = {"arrival": arrival.astype(np.int64),
         "n_tokens": svc.astype(np.int64),
         "eta_hint": hint.astype(np.int64),
         "prompt_len": int(mix["prompt_len"])}
    for k, entry in enumerate(mix.get("stages", ())):
        mod = lookup.module("stages", entry["stage"])
        params = {p: v for p, v in entry.items() if p != "stage"}
        rng_k = np.random.default_rng(
            np.random.SeedSequence(seed, spawn_key=(k,)))
        out = mod.apply(dict(d), params, rng_k)
        for c in set(out) - set(d):
            if c not in mod.COLUMNS or len(out[c]) != n:
                raise ValueError(f"stage {entry['stage']} added {c!r}, "
                                 f"not one of its COLUMNS of {n} rows")
        d = out
    return d


def requests(draws: dict) -> list:
    """The program's input: one ``Request`` per drawn row, with each
    column that names a field of ``Request`` set on that field."""
    from repro.serving.request import Request
    p = draws["prompt_len"]
    reqs = [Request(rid=i, arrival=a, prompt_len=p, n_tokens=k,
                    eta_hint=None if h < 0 else h)
            for i, (a, k, h) in enumerate(zip(draws["arrival"].tolist(),
                                              draws["n_tokens"].tolist(),
                                              draws["eta_hint"].tolist()))]
    fields = request_fields()
    for c in draws:
        if c in fields:
            for r, v in zip(reqs, draws[c].tolist()):
                setattr(r, c, v)
    return reqs
