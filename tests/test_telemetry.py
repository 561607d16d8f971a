"""Telemetry subsystem: recorder semantics, exporters, zero-overhead
disabled path, fingerprint invariance, spec provenance round-trip, and
the NaN-safe empty-array metrics fix (docs/OBSERVABILITY.md)."""
import json
import math
import tracemalloc

import numpy as np
import pytest

from repro.core import telemetry
from repro.core.metrics import cdf, percentiles
from repro.core.spec import (ExperimentSpec, ServerSpec, TickWorkloadSpec,
                             run_experiment)
from repro.core.telemetry import (KINDS, FleetSeries, HostProfile, Telemetry,
                                  TelemetryConfig, TraceRecorder,
                                  save_chrome_trace)
from repro.core.workload import FaaSBenchConfig

# ---------------------------------------------------------------------------
# TraceRecorder
# ---------------------------------------------------------------------------


def test_canonical_order_is_t_kind_rid_server():
    tr = TraceRecorder()
    tr.emit(5, "complete", 2, 1)
    tr.emit(5, "arrival", 3)
    tr.emit(1, "dispatch", 0, 0, aux=2.5)
    tr.emit_rows(5, "admit", [(1, 0), (0, 1)])
    kinds = [e[1] for e in tr.canonical()]
    assert kinds == ["dispatch", "arrival", "admit", "admit", "complete"]
    # within one (t, kind) block, rid ascending
    admits = [e for e in tr.canonical() if e[1] == "admit"]
    assert [e[2] for e in admits] == [0, 1]
    assert tr.counts()["admit"] == 2 and tr.counts()["bypass"] == 0
    assert tr.by_rid(0) == [(1, "dispatch", 0, 0, 2.5),
                            (5, "admit", 0, 1, None)]


def test_digest_is_emission_order_insensitive():
    a, b = TraceRecorder(), TraceRecorder()
    events = [(3, "admit", 1, 0), (1, "arrival", 1, -1),
              (3, "complete", 0, 2), (2, "dispatch", 0, 2)]
    for t, k, rid, s in events:
        a.emit(t, k, rid, s)
    for t, k, rid, s in reversed(events):
        b.emit(t, k, rid, s)
    assert a.digest() == b.digest()
    b.emit(9, "preempt", 1, 0)
    assert a.digest() != b.digest()


def test_chrome_trace_export(tmp_path):
    tr = TraceRecorder()
    tr.emit(0, "arrival", 7)
    tr.emit(2, "dispatch", 7, 1, aux=4.0)
    tr.emit(3, "admit", 7, 1)
    tr.emit(9, "complete", 7, 1)
    path = save_chrome_trace(str(tmp_path / "t.json"), {"demo": tr})
    data = json.load(open(path))
    ev = data["traceEvents"]
    spans = [e for e in ev if e["ph"] == "X"]
    assert len(spans) == 1
    assert spans[0]["ts"] == 2 and spans[0]["dur"] == 7
    assert spans[0]["args"]["eta"] == 4.0
    meta = {e["args"]["name"] for e in ev if e["ph"] == "M"}
    assert {"demo", "server 1"} <= meta
    assert any(e["ph"] == "i" and e["name"] == "admit" for e in ev)


# ---------------------------------------------------------------------------
# FleetSeries / HostProfile / Telemetry.ensure
# ---------------------------------------------------------------------------


class _FakeView:
    lanes = 4

    def queue_len(self):
        return 3

    def filter_free(self):
        return 1

    def fair_load(self):
        return 2

    def outstanding(self):
        return 6


def test_fleet_series_sample_and_summary():
    ser = FleetSeries(cadence=10)
    ser.count("completions", 5)
    ser.sample(0, [_FakeView(), _FakeView()], {"central_queue": 4})
    s = ser.summary()
    assert s["n_samples"] == 1 and s["cadence"] == 10
    assert s["peak_queue_len"] == 6 and s["mean_filter_active"] == 6
    assert s["counters"]["completions"] == 5
    assert ser.samples[0]["central_queue"] == 4
    assert ser.to_dict()["samples"] is ser.samples


def test_host_profile_accumulates_and_formats():
    prof = HostProfile()
    prof.add("step", 0.5)
    prof.add("step", 0.25)
    prof.add("route", 0.1)
    s = prof.summary()
    assert list(s) == ["step", "route"]          # sorted by total desc
    assert s["step"]["calls"] == 2 and s["step"]["total_s"] == 0.75
    assert "step" in prof.format() and "%" in prof.format()


def test_host_profile_spans_nest_and_total():
    prof = HostProfile()
    prof.begin("step")
    for _ in range(3):
        prof.begin("jax_step")
        prof.begin("jax_sync")
        prof.end("jax_sync")
        prof.end("jax_step")
    prof.end("step")
    assert {k: v[1] for k, v in prof.phases.items()} == {
        "step": 1, "jax_step": 3, "jax_sync": 3}
    assert prof.phases["step"][0] >= prof.phases["jax_step"][0] \
        >= prof.phases["jax_sync"][0] >= 0
    prof.begin("route")
    prof.begin("replay")
    with pytest.raises(ValueError):
        prof.end("route")           # only the innermost span may close


def test_spans_write_no_annotation_without_a_profiler_session():
    """With no jax profiler session recording, a span is host-clock
    bookkeeping only."""
    assert telemetry._annotation("route") is None


def test_telemetry_ensure_normalizes():
    assert Telemetry.ensure(None) is None
    tel = Telemetry(trace=True)
    assert Telemetry.ensure(tel) is tel
    t2 = Telemetry.ensure(True)
    assert t2.trace is not None and t2.series is None and t2.profile is None
    t3 = Telemetry.ensure(TelemetryConfig(series_cadence=5, profile=True))
    assert t3.trace is None and t3.series.cadence == 5
    assert t3.profile is not None
    with pytest.raises(TypeError):
        Telemetry.ensure("yes")
    assert set(t2.summary()) == {"trace"}


# ---------------------------------------------------------------------------
# Satellite: NaN-safe metrics on empty arrays
# ---------------------------------------------------------------------------


def test_percentiles_empty_returns_nans():
    out = percentiles(np.array([]))
    assert set(out) == {50, 90, 99, 99.9}
    assert all(math.isnan(v) for v in out.values())
    # and stays correct on the non-empty path
    assert percentiles(np.array([1.0, 2.0, 3.0]))[50] == 2.0


def test_cdf_empty_returns_empty():
    xs, ys = cdf(np.array([]))
    assert xs.size == 0 and ys.size == 0
    xs, ys = cdf(np.array([3.0, 1.0, 2.0]), n=3)
    assert list(xs) == [1.0, 2.0, 3.0] and ys[-1] == 1.0


# ---------------------------------------------------------------------------
# Engine integration: fingerprints invariant, disabled path zero-cost
# ---------------------------------------------------------------------------

_SERVERS = tuple(ServerSpec(cores=4) for _ in range(4))
_WL = TickWorkloadSpec(n=250, load=1.0, seed=23)


def _spec(engine):
    if engine == "des":
        return ExperimentSpec(
            engine="des", servers=_SERVERS, dispatch="sfs-aware",
            workload=FaaSBenchConfig(n_requests=800, cores=16, load=1.0,
                                     seed=7))
    return ExperimentSpec(engine=engine, servers=_SERVERS,
                          dispatch="sfs-aware", predictor="history",
                          workload=_WL)


@pytest.mark.parametrize("engine", ["tick", "vector", "jax", "des"])
def test_enabling_telemetry_keeps_fingerprints_bit_exact(engine):
    """Full telemetry (trace + series + profile) must be observation
    only: the result fingerprint equals the telemetry-off run — even on
    the jax backend, where tracing disables the scan fast path."""
    base = run_experiment(_spec(engine), max_ticks=2_000_000)
    tel = Telemetry(trace=True, series_cadence=50, profile=True)
    res = run_experiment(_spec(engine), max_ticks=2_000_000, telemetry=tel)
    assert base.fingerprint() == res.fingerprint()
    assert res.telemetry is tel and base.telemetry is None
    assert len(tel.trace) > 0 and len(tel.series.samples) > 0
    counts = tel.trace.counts()
    n = base.n
    assert counts["arrival"] == counts["dispatch"] == n
    assert counts["complete"] == n
    assert tel.series.counters["completions"] == n
    if engine != "des":     # host-path phase timers are tick-backend side
        assert tel.profile.phases


def _telemetry_allocations(engine):
    """Bytes tracemalloc attributes to telemetry.py over one run with
    telemetry off (caches warmed by a first run)."""
    run_experiment(_spec(engine), max_ticks=2_000_000)
    tracemalloc.start()
    res = run_experiment(_spec(engine), max_ticks=2_000_000)
    snap = tracemalloc.take_snapshot()
    tracemalloc.stop()
    leaked = [s for s in snap.statistics("filename")
              if s.traceback[0].filename == telemetry.__file__]
    assert res.telemetry is None
    return leaked


def test_disabled_telemetry_adds_zero_allocations_to_vector_step():
    """With telemetry off, the hot loop must never touch telemetry.py:
    every emission site is a single `is not None` attribute check, so
    tracemalloc attributes zero allocations to the module."""
    leaked = _telemetry_allocations("vector")
    assert sum(s.size for s in leaked) == 0, leaked


def test_disabled_telemetry_adds_zero_allocations_to_jax_step():
    """The same on the jitted backend: no span is opened, so no
    ``repro.*`` annotation is created and nothing is allocated."""
    leaked = _telemetry_allocations("jax")
    assert sum(s.size for s in leaked) == 0, leaked


#: the spans that tile one jax experiment
TOP_SPANS = ("build", "intake", "route", "step", "jax_advance", "jax_scan",
             "jax_commit", "result")


def _jax_fleet(n=3000):
    servers = tuple(ServerSpec(cores=2) for _ in range(16))
    return ExperimentSpec(engine="jax", servers=servers,
                          dispatch="sfs-aware",
                          workload=TickWorkloadSpec(n=n, load=0.9, seed=5))


def test_jax_profile_spans_every_phase_once_or_per_step():
    """A profile-only jax run: the experiment-level spans once each,
    one prep and one device wait per jitted step, the replay recorded,
    and results bit-identical to an untraced run."""
    base = run_experiment(_jax_fleet())
    tel = Telemetry(profile=True)
    res = run_experiment(_jax_fleet(), telemetry=tel)
    assert res.fingerprint() == base.fingerprint()
    ph = tel.profile.phases
    assert {k: ph[k][1] for k in ("build", "intake", "result")} == {
        "build": 1, "intake": 1, "result": 1}
    assert ph["jax_prep"][1] == ph["jax_sync"][1] == ph["jax_step"][1] \
        == ph["step"][1]
    assert ph["replay"][1] >= ph["step"][1]
    assert ph["jax_writeback"][1] == 1
    # every span closed; the experiment-level ones tile the wall time,
    # so together they cannot exceed it
    assert not tel.profile._open
    assert sum(ph[k][0] for k in TOP_SPANS if k in ph) <= res.wall_s
    for inner, outer in (("jax_sync", "jax_step"), ("jax_step", "step"),
                         ("jax_prep", "step"), ("jax_writeback", "result")):
        assert ph[inner][0] <= ph[outer][0]


def test_spans_are_profiler_annotations_on_the_host_timeline(tmp_path):
    """Under a live jax profiler session every span is also a
    ``repro.<phase>`` host event, nested as the spans nest; a counter
    (the stall parks and wakes) is none."""
    import jax
    run_experiment(_jax_fleet(600))                 # compile off the trace
    tel = Telemetry(profile=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_experiment(_jax_fleet(600), telemetry=tel)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name[len("repro."):])
             for p in data.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events
             if e.name.startswith("repro.")]
    counts = {}
    for _, _, n in spans:
        counts[n] = counts.get(n, 0) + 1
    prof = tel.profile
    assert prof.counters == {"stall_park", "stall_wake"}
    assert counts == {k: v[1] for k, v in prof.phases.items()
                      if k not in prof.counters}

    def inside(inner, *outer):
        outs = [(s, e) for s, e, n in spans if n in outer]
        return all(any(s0 <= s and e <= e0 for s0, e0 in outs)
                   for s, e, n in spans if n == inner)
    assert inside("jax_sync", "jax_step") and inside("jax_prep", "step")
    assert inside("stall_sync", "step", "jax_commit")
    assert inside("jax_writeback", "result")


# ---------------------------------------------------------------------------
# Satellite: spec provenance round-trip
# ---------------------------------------------------------------------------


def test_spec_json_round_trip_tick():
    spec = ExperimentSpec(
        engine="vector",
        servers=(ServerSpec(cores=6),
                 ServerSpec(cores=2, scheduler="cfs")),
        dispatch="sfs-aware",
        predictor="class:margin=1.5,boundary=0.6",
        workload=TickWorkloadSpec(n=100, load=0.8, seed=3))
    d = json.loads(json.dumps(spec.to_json()))      # through real JSON
    assert ExperimentSpec.from_json(d) == spec


def test_spec_json_round_trip_faas():
    spec = ExperimentSpec(
        engine="des", servers=(ServerSpec(cores=4),) * 2,
        dispatch="least-outstanding", predictor="history",
        workload=FaaSBenchConfig(n_requests=500, cores=8, load=1.1,
                                 seed=13, iat="trace"))
    d = json.loads(json.dumps(spec.to_json()))
    back = ExperimentSpec.from_json(d)
    assert back == spec
    # nested tuples (duration_table rows, io_ms_range) must re-tuple
    assert back.workload.duration_table == spec.workload.duration_table


def test_all_kinds_have_an_order():
    assert len(KINDS) == 15 and KINDS[0] == "arrival"
    assert KINDS[-1] == "complete"
    # the PR 9 lifecycle kinds are first-class members of the canonical
    # order (docs/OBSERVABILITY.md): cold_start sits between dispatch
    # and admit (charged at delivery), fail/requeue/scale after preempt
    assert {"cold_start", "fail", "requeue", "scale"} <= set(KINDS)
    assert KINDS.index("dispatch") < KINDS.index("cold_start") \
        < KINDS.index("admit")
    assert KINDS.index("fail") < KINDS.index("requeue")
    # the chaos kinds (docs/OBSERVABILITY.md): shed/retry precede
    # dispatch (admission + re-entry decisions), timeout sits with the
    # other eviction causes, recover follows requeue
    assert {"shed", "retry", "timeout", "recover"} <= set(KINDS)
    assert KINDS.index("shed") < KINDS.index("retry") \
        < KINDS.index("dispatch")
    assert KINDS.index("timeout") < KINDS.index("fail")
    assert KINDS.index("requeue") < KINDS.index("recover")
