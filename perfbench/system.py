"""Everything the benchmark takes from the program, in one place: the
experiment entry (``repro.run_experiment`` with ``engine="jax"``), its
spec types, the ``Request`` it consumes and the profile-only telemetry
session whose host spans the per-layer readers read.
"""
from __future__ import annotations


def spec(cfg: dict):
    """The ``ExperimentSpec`` a configuration file states."""
    import repro
    servers = tuple(repro.ServerSpec(cores=int(cfg["cores"]),
                                     slots=int(cfg["slots"]),
                                     scheduler=cfg["scheduler"])
                    for _ in range(int(cfg["servers"])))
    return repro.ExperimentSpec(engine="jax", servers=servers,
                                dispatch=cfg["dispatch"],
                                predictor=cfg["predictor"])


def profile_session():
    """Host spans only: a trace or series collector would turn the scan
    and gap fast paths off, so neither is ever attached."""
    from repro.core.telemetry import Telemetry
    return Telemetry(profile=True)


def phases(tel) -> dict:
    """``{phase: [total seconds, calls]}`` of a profile session."""
    if tel is None or tel.profile is None:
        return {}
    return {k: [float(v[0]), int(v[1])]
            for k, v in tel.profile.phases.items()}


def run(spec_, requests, telemetry=None, max_ticks: int = 20_000_000):
    import repro
    return repro.run_experiment(spec_, requests, max_ticks=max_ticks,
                                telemetry=telemetry)
