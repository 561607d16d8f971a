"""Scheduler/simulator agreement: the paper's headline claim — SFS
improves short-function turnaround over CFS — must hold in BOTH
execution models (tick-engine serving scheduler and discrete-event
simulator), as a cross-layer regression test; and the vectorized
cluster stepping backend must reproduce the object-engine cluster
bit for bit on shared seeds."""
import numpy as np
import pytest

from repro.core import FaaSBenchConfig, SimConfig, generate, simulate
from repro.core.metrics import result_bucket_stats
from repro.core.simulator import Simulator
from repro.core.spec import (ExperimentSpec, ServerSpec, TickWorkloadSpec,
                             run_experiment)
from repro.core.telemetry import Telemetry, TraceRecorder
from repro.serving import Engine, EngineConfig, Request

SHORT_TICKS = 10          # tick-engine short bucket (tokens)
SHORT_S = 0.1             # DES short bucket (seconds, Azure Table I)


def tick_workload(n=150, lanes=4, load=1.0, seed=5, short_frac=0.8):
    rng = np.random.default_rng(seed)
    svc = np.where(rng.random(n) < short_frac,
                   rng.integers(2, 8, n), rng.integers(30, 80, n))
    span = svc.sum() / (load * lanes)
    iats = rng.exponential(1.0, n)
    arr = np.cumsum(iats * span / iats.sum()).astype(int)
    return [Request(rid=i, arrival=int(arr[i]), prompt_len=4,
                    n_tokens=int(svc[i])) for i in range(n)]


def _short_p50_engine(policy, seed):
    eng = Engine(EngineConfig(lanes=4, n_slots=256, policy=policy))
    done = eng.run(tick_workload(seed=seed), max_ticks=2_000_000)
    ta = np.array([r.turnaround for r in done
                   if r.service_demand < SHORT_TICKS])
    return float(np.median(ta))


def _short_p50_des(policy, seed):
    reqs = generate(FaaSBenchConfig(n_requests=2000, cores=12, load=1.0,
                                    seed=seed))
    res = simulate(reqs, SimConfig(cores=12, policy=policy))
    ta = np.array([s.turnaround for s in res.stats
                   if s.service < SHORT_S])
    return float(np.median(ta))


def test_sfs_improves_short_p50_in_both_layers():
    for seed in (5, 6):
        engine_sfs = _short_p50_engine("sfs", seed)
        engine_cfs = _short_p50_engine("cfs", seed)
        assert engine_sfs <= engine_cfs, (seed, engine_sfs, engine_cfs)
    for seed in (5, 6):
        des_sfs = _short_p50_des("sfs", seed)
        des_cfs = _short_p50_des("cfs", seed)
        assert des_sfs < des_cfs, (seed, des_sfs, des_cfs)


def test_sfs_improves_short_p99_in_des_bucket_stats():
    """Same claim through the shared bucket-stats helper (what the
    cluster sweep reports), at the paper's 100% load point."""
    reqs = generate(FaaSBenchConfig(n_requests=2000, cores=12, load=1.0,
                                    seed=9))
    out = {}
    for policy in ("sfs", "cfs"):
        res = simulate(reqs, SimConfig(cores=12, policy=policy))
        out[policy] = result_bucket_stats(res)
    short = f"<{SHORT_S:g}s"
    assert out["sfs"][short]["p99"] < out["cfs"][short]["p99"]
    assert out["sfs"][short]["mean_rte"] > out["cfs"][short]["mean_rte"]


# ---------------------------------------------------------------------------
# Vector backend: bit-exact vs the object engines, cross-checked vs DES
# ---------------------------------------------------------------------------


def _full_fingerprint(reqs):
    """Every per-request field the engines mutate — stricter than the
    (rid, finish, n_ctx, demoted) golden currency."""
    return [(r.rid, r.finish, r.served_ticks, r.n_ctx, r.demoted,
             r.first_start, r.queue_delay, r.queue_enter, r.vruntime,
             r.slice_left, r.tokens_done, r.prefill_done, r.slot)
            for r in reqs]


def _run_backend(engine, servers, dispatch, predictor, wl):
    return run_experiment(ExperimentSpec(
        engine=engine, servers=servers, dispatch=dispatch,
        predictor=predictor, workload=wl), max_ticks=2_000_000)


@pytest.mark.parametrize("n_engines", [1, 4, 8])
@pytest.mark.parametrize("dispatch", ["hash", "least-outstanding", "pull",
                                      "sfs-aware"])
def test_vector_backend_bit_exact_vs_object(n_engines, dispatch):
    """engine="vector" == engine="tick", field for field, on shared
    seeds — including the learned-predictor feedback loop, whose
    observation ORDER the vector backend must replay exactly."""
    servers = tuple(ServerSpec(cores=4) for _ in range(n_engines))
    wl = TickWorkloadSpec(n=250, load=1.0, seed=23)
    obj = _run_backend("tick", servers, dispatch, "history", wl)
    vec = _run_backend("vector", servers, dispatch, "history", wl)
    assert _full_fingerprint(obj.raw) == _full_fingerprint(vec.raw)
    assert obj.dispatch_counts == vec.dispatch_counts
    assert obj.eta_log == vec.eta_log
    assert obj.overload_bypasses == vec.overload_bypasses
    assert obj.fingerprint() == vec.fingerprint()


def test_vector_backend_bit_exact_on_mixed_pool():
    """Heterogeneous spec: two sfs groups of different shapes plus cfs
    servers — multiple vector groups in one cluster, still bit-exact."""
    servers = (ServerSpec(cores=6), ServerSpec(cores=6),
               ServerSpec(cores=4), ServerSpec(cores=2, scheduler="cfs"),
               ServerSpec(cores=2, scheduler="cfs"))
    wl = TickWorkloadSpec(n=400, load=1.0, seed=11)
    obj = _run_backend("tick", servers, "sfs-aware", "oracle", wl)
    vec = _run_backend("vector", servers, "sfs-aware", "oracle", wl)
    assert _full_fingerprint(obj.raw) == _full_fingerprint(vec.raw)
    assert obj.dispatch_counts == vec.dispatch_counts


def test_vector_backend_matches_object_with_stragglers():
    """A server pinned to engine="object" rides inside a vector cluster
    and the whole run still equals the all-object cluster."""
    servers = (ServerSpec(cores=4), ServerSpec(cores=4),
               ServerSpec(cores=4, engine="object"),
               ServerSpec(cores=4, scheduler="srtf"))   # srtf -> fallback
    wl = TickWorkloadSpec(n=300, load=0.9, seed=3)
    obj = _run_backend("tick", servers, "least-outstanding", "oracle", wl)
    vec = _run_backend("vector", servers, "least-outstanding", "oracle", wl)
    assert _full_fingerprint(obj.raw) == _full_fingerprint(vec.raw)


# ---------------------------------------------------------------------------
# JAX backend: bit-exact vs the numpy vector backend (and therefore the
# object engines, by the tests above)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_engines", [4, 64])
@pytest.mark.parametrize("dispatch", ["hash", "least-outstanding", "pull",
                                      "sfs-aware"])
def test_jax_backend_bit_exact_vs_vector(n_engines, dispatch):
    """engine="jax" == engine="vector", field for field, on shared seeds
    — the numpy vector backend is the bit-exactness reference for the
    jitted group stepping (docs/CLUSTER.md "Scaling past 64 engines").
    Includes the learned-predictor feedback loop: the jitted step must
    emit completions in the object cluster's replay order or the
    history predictor's observation stream (and every later dispatch
    decision) diverges."""
    servers = tuple(ServerSpec(cores=4) for _ in range(n_engines))
    wl = TickWorkloadSpec(n=250, load=1.0, seed=23)
    vec = _run_backend("vector", servers, dispatch, "history", wl)
    jx = _run_backend("jax", servers, dispatch, "history", wl)
    assert _full_fingerprint(vec.raw) == _full_fingerprint(jx.raw)
    assert vec.dispatch_counts == jx.dispatch_counts
    assert vec.eta_log == jx.eta_log
    assert vec.overload_bypasses == jx.overload_bypasses
    assert vec.fingerprint() == jx.fingerprint()


def test_jax_backend_bit_exact_on_cfs_group():
    """Pure-CFS groups take the sfs=False tick body (no FILTER event
    lanes, single event grid) — exactness must hold there too."""
    servers = tuple(ServerSpec(cores=4, scheduler="cfs") for _ in range(8))
    wl = TickWorkloadSpec(n=300, load=1.0, seed=17)
    vec = _run_backend("vector", servers, "least-outstanding", "oracle", wl)
    jx = _run_backend("jax", servers, "least-outstanding", "oracle", wl)
    assert _full_fingerprint(vec.raw) == _full_fingerprint(jx.raw)
    assert vec.dispatch_counts == jx.dispatch_counts


# ---------------------------------------------------------------------------
# Telemetry trace agreement: equal-trace is strictly stronger than the
# end-state fingerprints above — every intermediate scheduling decision
# (route target + ETA, FILTER admit, demotion, preemption, completion
# tick) must match, not just the final per-request fields.
# ---------------------------------------------------------------------------


def _run_traced(engine, servers, dispatch, predictor, wl,
                lifecycle=None, scaling=None, faults=None, retry=None):
    tel = Telemetry(trace=True)
    res = run_experiment(ExperimentSpec(
        engine=engine, servers=servers, dispatch=dispatch,
        predictor=predictor, workload=wl, lifecycle=lifecycle,
        scaling=scaling, faults=faults, retry=retry),
        max_ticks=2_000_000, telemetry=tel)
    return res, tel.trace


@pytest.mark.parametrize("n_engines", [4, 64])
def test_trace_agreement_tick_vector_jax(n_engines):
    """The three tick-semantics backends emit the SAME canonical
    lifecycle event stream, event for event, at n=4 and n=64."""
    servers = tuple(ServerSpec(cores=4) for _ in range(n_engines))
    wl = TickWorkloadSpec(n=400, load=1.0, seed=23)
    canon, res0 = {}, None
    for engine in ("tick", "vector", "jax"):
        res, tr = _run_traced(engine, servers, "sfs-aware", "history", wl)
        canon[engine] = tr.canonical()
        res0 = res0 or res
    assert canon["tick"] == canon["vector"]
    assert canon["tick"] == canon["jax"]
    counts = {}
    for t, kind, rid, server, aux in canon["tick"]:
        counts[kind] = counts.get(kind, 0) + 1
    assert counts["arrival"] == counts["dispatch"] == res0.n
    assert counts["complete"] == res0.n
    assert counts["admit"] > 0                  # FILTER actually engaged


def _with_stalls(reqs, seed, p=0.7):
    """``reqs`` with the paper's leading I/O on a share ``p``: one stall
    event of 1-2 ticks after the first tick (Sec. VIII-B, at ~50 ms a
    tick), and a later one of 1-4 ticks on every fifth of them."""
    rng = np.random.default_rng(seed)
    for r in reqs:
        if rng.random() < p:
            ev = ((0, int(rng.integers(1, 3))),)
            if rng.random() < 0.2:
                ev += ((int(rng.integers(1, 20)), int(rng.integers(1, 5))),)
            r.stall_events = ev
    return reqs


@pytest.mark.parametrize("n_engines", [4, 64])
def test_trace_agreement_tick_jax_with_stalls(n_engines):
    """Stall events on the jax backend (the vector backend refuses
    them): the same canonical lifecycle stream and fingerprint as the
    tick engine, parks among the preempts, with the history predictor's
    feedback loop in the routing."""
    servers = tuple(ServerSpec(cores=4) for _ in range(n_engines))
    n = 100 * n_engines if n_engines < 64 else 1600
    canon, fps, counts = {}, {}, None
    for engine in ("tick", "jax"):
        reqs = _with_stalls(tick_workload(n=n, lanes=4 * n_engines,
                                          seed=29), 31)
        tel = Telemetry(trace=True)
        res = run_experiment(ExperimentSpec(
            engine=engine, servers=servers, dispatch="sfs-aware",
            predictor="history"), reqs, max_ticks=2_000_000,
            telemetry=tel)
        canon[engine] = tel.trace.canonical()
        fps[engine] = (_full_fingerprint(res.raw), res.dispatch_counts,
                       [r.stall_idx for r in res.raw])
        counts = counts or tel.trace.counts()
    assert canon["tick"] == canon["jax"]
    assert fps["tick"] == fps["jax"]
    assert counts["preempt"] > 0.6 * n      # at least every park


def test_trace_agreement_covers_demote_and_preempt():
    """Contention scenario (high load, hinted demotion) so the rarer
    demote/preempt/bypass kinds are exercised — still equal-trace."""
    servers = tuple(ServerSpec(cores=2, scheduler="sfs:hinted_demotion=True")
                    for _ in range(4))
    wl = TickWorkloadSpec(n=300, load=1.5, seed=11)
    canon, counts = {}, None
    for engine in ("tick", "vector", "jax"):
        _, tr = _run_traced(engine, servers, "sfs-aware", "oracle", wl)
        canon[engine] = tr.canonical()
        counts = counts or tr.counts()
    assert canon["tick"] == canon["vector"] == canon["jax"]
    assert counts["demote"] > 0 and counts["preempt"] > 0


def test_des_cluster_trace_matches_single_simulator():
    """DES leg of the trace cross-check: a 1-server ClusterSimulator's
    server-side events equal a bare Simulator fed the same requests —
    the frontend adds arrival/dispatch but must not perturb the
    per-server scheduling event stream."""
    reqs = generate(FaaSBenchConfig(n_requests=1200, cores=4, load=1.0,
                                    seed=7))
    tel = Telemetry(trace=True)
    res = run_experiment(ExperimentSpec(
        engine="des", servers=(ServerSpec(cores=4),), dispatch="hash",
        predictor="none"), requests=reqs, telemetry=tel)
    server_kinds = {"admit", "bypass", "demote", "preempt", "complete"}
    cluster_ev = [e for e in tel.trace.canonical()
                  if e[1] in server_kinds]
    tr = TraceRecorder()
    sim = Simulator(reqs, SimConfig(cores=4, policy="sfs"))
    sim.bind_trace(tr, 0)
    sim.run()
    assert cluster_ev == tr.canonical()
    counts = tel.trace.counts()
    assert counts["arrival"] == counts["dispatch"] == res.n
    assert counts["complete"] == res.n


# ---------------------------------------------------------------------------
# Lifecycle agreement (docs/CLUSTER.md "Production realism"): cold
# starts, failure/drain and autoscaling are frontend-side decisions, so
# all three tick backends must emit the SAME canonical event stream with
# them enabled; and the DES cluster's cold-start charge must equal a
# bare Simulator fed the pre-inflated workload at n=1.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dispatch", ["hash", "sfs-aware"])
def test_trace_agreement_cold_start_keep_alive(dispatch):
    servers = tuple(ServerSpec(cores=2) for _ in range(4))
    wl = "bimodal:n=250,seed=23|zipf:funcs=8,s=1.2"
    canon, fp, counts = {}, set(), None
    for engine in ("tick", "vector", "jax"):
        res, tr = _run_traced(engine, servers, dispatch, "history", wl,
                              lifecycle="lifecycle:cold=3,ttl=60,cap=4")
        canon[engine] = tr.canonical()
        fp.add(res.fingerprint())
        counts = counts or tr.counts()
    assert canon["tick"] == canon["vector"] == canon["jax"]
    assert len(fp) == 1
    assert counts["cold_start"] > 0
    assert counts["fail"] == counts["requeue"] == counts["scale"] == 0


def test_trace_agreement_failure_drain_and_scaling():
    """The full lifecycle stack at once — keep-alive cold starts, a
    mid-run server failure with drain/requeue, and an autoscaler — still
    equal-trace across tick/vector/jax, with every request finishing."""
    servers = tuple(ServerSpec(cores=2) for _ in range(4))
    wl = "bimodal:n=250,seed=5,load=1.2|flash:at=150,x=4,dur=200"
    canon, fp, counts = {}, set(), None
    for engine in ("tick", "vector", "jax"):
        res, tr = _run_traced(
            engine, servers, "sfs-aware", "history", wl,
            lifecycle="lifecycle:cold=3,ttl=60,cap=4,fail=40,fail_server=1",
            scaling="scale:min=2,T=25,up=0.5,down=0.1")
        canon[engine] = tr.canonical()
        fp.add(res.fingerprint())
        counts = counts or tr.counts()
        assert res.n == 250                     # drained work is re-run
    assert canon["tick"] == canon["vector"] == canon["jax"]
    assert len(fp) == 1
    assert counts["fail"] == 1 and counts["requeue"] > 0
    assert counts["scale"] > 0 and counts["cold_start"] > 0


def test_trace_agreement_under_chaos_schedule():
    """The full chaos stack — correlated fault episodes with recovery,
    per-dispatch timeouts with backoff retries, and admission shedding
    — still equal-trace across tick/vector/jax (docs/CLUSTER.md "Chaos
    and graceful degradation").  This is the acceptance gate for the
    chaos subsystem: the jax gap/scan fast paths must stop at every
    fault, recovery, deadline, and backoff-release boundary."""
    servers = tuple(ServerSpec(cores=2) for _ in range(4))
    wl = "bimodal:n=250,seed=5,load=1.2|zipf:funcs=8,s=1.2"
    canon, fp, counts, res0 = {}, set(), None, None
    for engine in ("tick", "vector", "jax"):
        res, tr = _run_traced(
            engine, servers, "sfs-aware", "history", wl,
            lifecycle="lifecycle:cold=3,ttl=60,cap=4",
            faults="faults:mttf=150,mttr=60,blast=2,episodes=2,seed=9",
            retry="retry:timeout=120,retries=2,backoff=8,shed=10")
        canon[engine] = tr.canonical()
        fp.add(res.fingerprint())
        counts = counts or tr.counts()
        res0 = res0 or res
    assert canon["tick"] == canon["vector"]
    assert canon["tick"] == canon["jax"]
    assert len(fp) == 1
    # every chaos kind is actually exercised by this schedule
    assert counts["fail"] > 0 and counts["recover"] > 0
    assert counts["timeout"] > 0 and counts["retry"] > 0
    assert counts["shed"] > 0
    # conservation: every arrival either completes or sheds, and shed
    # requests are excluded from the completion arrays
    assert res0.n + res0.shed == 250
    assert counts["complete"] == res0.n
    assert res0.timeouts == counts["timeout"]
    assert res0.retries == counts["retry"]


def test_des_cluster_cold_start_parity_at_n1():
    """DES leg of the cold-start cross-check: a 1-server cluster with a
    cold penalty (no keep-alive expiry, unbounded warm cap — each
    function is cold exactly once) equals a bare Simulator fed the same
    workload with that first-invocation inflation applied by hand."""
    import dataclasses
    reqs = generate(FaaSBenchConfig(n_requests=800, cores=4, load=1.0,
                                    seed=7, n_functions=8))
    pen = 0.05
    res = run_experiment(ExperimentSpec(
        engine="des", servers=(ServerSpec(cores=4),), dispatch="hash",
        predictor="none", lifecycle=f"lifecycle:cold={pen}"),
        requests=reqs)
    seen, inflated = set(), []
    for r in sorted(reqs, key=lambda r: (r.arrival, r.rid)):
        if r.func_id not in seen:
            seen.add(r.func_id)
            r = dataclasses.replace(r, service=r.service + pen)
        inflated.append(r)
    ref = simulate(inflated, SimConfig(cores=4, policy="sfs"))
    key = lambda s: (s.rid, s.finish, s.n_ctx, s.demoted)
    assert sorted(map(key, res.raw.merged.stats)) == \
        sorted(map(key, ref.stats))


def test_vector_and_des_agree_on_sfs_aware_headline():
    """Three-way cross-validation on shared seeds: the cluster claim
    (sfs-aware <= hash on short P99 under load) holds in the DES and in
    BOTH tick stepping backends — and the two tick backends agree
    exactly.  The DES leg pools seeds (7, 11) like the cluster sweep
    does: single-seed p99 at n=2000 is tie-noise territory."""
    seeds = (7, 11)
    servers = tuple(ServerSpec(cores=4) for _ in range(4))
    # tick semantics: vector vs object, and the headline itself
    out = {}
    for dispatch in ("hash", "sfs-aware"):
        wl = TickWorkloadSpec(n=800, load=1.0, seed=seeds[0])
        vec = _run_backend("vector", servers, dispatch, "oracle", wl)
        obj = _run_backend("tick", servers, dispatch, "oracle", wl)
        assert vec.fingerprint() == obj.fingerprint()
        out[dispatch] = vec.buckets()
    short_t = list(out["sfs-aware"])[0]
    assert (out["sfs-aware"][short_t]["p99"]
            <= out["hash"][short_t]["p99"] * 1.05)
    # DES, same seeds, same shape, seed-pooled turnarounds
    des = {}
    for dispatch in ("hash", "sfs-aware"):
        svc, ta = [], []
        for seed in seeds:
            res = run_experiment(ExperimentSpec(
                engine="des", servers=servers, dispatch=dispatch,
                workload=FaaSBenchConfig(n_requests=2000, cores=16,
                                         load=1.0, seed=seed)))
            svc.append(res.service)
            ta.append(res.turnaround)
        svc, ta = np.concatenate(svc), np.concatenate(ta)
        des[dispatch] = float(np.percentile(ta[svc < SHORT_S], 99))
    assert des["sfs-aware"] <= des["hash"] * 1.05
