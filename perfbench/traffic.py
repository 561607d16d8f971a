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
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def draw(mix: dict, seed: int, total_lanes: int, n: int = None) -> dict:
    """Per-request arrays ``arrival``, ``n_tokens``, ``eta_hint`` (-1 = no
    hint), rid-ordered, for ``n`` requests (the mix's own by default).
    The same seed gives the same arrays."""
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
    return {"arrival": arrival.astype(np.int64),
            "n_tokens": svc.astype(np.int64),
            "eta_hint": hint.astype(np.int64),
            "prompt_len": int(mix["prompt_len"])}


def requests(draws: dict) -> list:
    """The program's input: one ``Request`` per drawn row."""
    from repro.serving.request import Request
    p = draws["prompt_len"]
    return [Request(rid=i, arrival=a, prompt_len=p, n_tokens=k,
                    eta_hint=None if h < 0 else h)
            for i, (a, k, h) in enumerate(zip(draws["arrival"].tolist(),
                                              draws["n_tokens"].tolist(),
                                              draws["eta_hint"].tolist()))]
