"""Everything the benchmark takes from the program, in one place: the
experiment entry (``repro.run_experiment`` with ``engine="jax"``), its
spec types, the ``Request`` it consumes (built in ``traffic.py``) and
the profile-only telemetry session whose host spans the per-layer
readers read.
"""
from __future__ import annotations

# a configuration's ``fleet`` knobs: ``ExperimentSpec`` fields, each
# given as a string of the program's own spec grammar, which refuses a
# knob it does not know
FLEET = ("lifecycle", "scaling", "faults", "retry")


def check_fleet(fleet):
    """Refuses (``ValueError``) a ``fleet`` block that is not an object
    of spec strings keyed by ``FLEET`` fields."""
    if not isinstance(fleet, dict):
        raise ValueError(f"fleet: {fleet!r} is not an object of knobs")
    unknown = sorted(set(fleet) - set(FLEET))
    if unknown:
        raise ValueError(f"unknown fleet knob(s) {unknown}; expected some "
                         f"of {FLEET}")
    for k, v in fleet.items():
        if not isinstance(v, str):
            raise ValueError(f"fleet knob {k}: {v!r} is not a spec string")


def spec(cfg: dict):
    """The ``ExperimentSpec`` a configuration file states (checked by
    ``harness.check_config``); the program's grammar parses each
    ``fleet`` knob."""
    import repro
    servers = tuple(repro.ServerSpec(cores=int(cfg["cores"]),
                                     slots=int(cfg["slots"]),
                                     scheduler=cfg["scheduler"])
                    for _ in range(int(cfg["servers"])))
    return repro.ExperimentSpec(engine="jax", servers=servers,
                                dispatch=cfg["dispatch"],
                                predictor=cfg["predictor"],
                                **cfg.get("fleet", {}))


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
