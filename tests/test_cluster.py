"""Cluster dispatch layer: routing invariants, pull work conservation,
golden parity with the single engine, DES cross-validation."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (ClusterSimConfig, FaaSBenchConfig, SimConfig,
                        generate, simulate, simulate_cluster)
from repro.core.dispatch import POLICIES
from repro.serving import (Cluster, ClusterConfig, Engine, EngineConfig,
                           Request)


def workload(n=60, lanes=4, load=1.0, seed=0, short_frac=0.8,
             stalls=False, hints=True):
    rng = np.random.default_rng(seed)
    svc = np.where(rng.random(n) < short_frac,
                   rng.integers(2, 8, n), rng.integers(30, 80, n))
    span = svc.sum() / (load * lanes)
    iats = rng.exponential(1.0, n)
    arr = np.cumsum(iats * span / iats.sum()).astype(int)
    reqs = []
    for i in range(n):
        ev = ((1, int(rng.integers(2, 8))),) if stalls and \
            rng.random() < 0.4 and svc[i] > 3 else ()
        reqs.append(Request(rid=i, arrival=int(arr[i]), prompt_len=4,
                            n_tokens=int(svc[i]), stall_events=ev,
                            eta_hint=int(svc[i]) + 1 if hints else None))
    return reqs


def make_cluster(policy, n_engines, lanes=2, n_slots=64):
    engines = [Engine(EngineConfig(lanes=lanes, n_slots=n_slots,
                                   policy="sfs"))
               for _ in range(n_engines)]
    return Cluster(engines, ClusterConfig(policy=policy))


# ---------------------------------------------------------------------------
# Invariants: nothing lost, nothing duplicated
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 100), policy=st.sampled_from(POLICIES),
       n_engines=st.integers(1, 4), stalls=st.booleans())
def test_no_request_lost_or_duplicated(seed, policy, n_engines, stalls):
    n = 50
    cluster = make_cluster(policy, n_engines)
    done = cluster.run(workload(n=n, lanes=2 * n_engines, seed=seed,
                                stalls=stalls),
                       max_ticks=2_000_000)
    assert [r.rid for r in done] == list(range(n))
    # each request finished on exactly one engine
    per_engine = [sorted(r.rid for r in e.finished)
                  for e in cluster.engines]
    all_rids = sorted(rid for rids in per_engine for rid in rids)
    assert all_rids == list(range(n))
    assert sum(cluster.dispatch_counts) == n
    assert not cluster.central_queue


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), n_engines=st.integers(1, 4),
       lanes=st.integers(1, 4))
def test_pull_work_conservation(seed, n_engines, lanes):
    """Under pull dispatch no engine idles while the central queue is
    non-empty (slots are ample and the workload never stalls, so an
    engine that runs < lanes requests could have pulled)."""
    cluster = make_cluster("pull", n_engines, lanes=lanes, n_slots=128)
    cluster.tick_log = []
    cluster.run(workload(n=40, lanes=lanes * n_engines, seed=seed),
                max_ticks=2_000_000)
    assert len(cluster.tick_log) == cluster.t
    for t, central_qlen, actives in cluster.tick_log:
        if central_qlen > 0:
            assert all(a == lanes for a in actives), \
                (t, central_qlen, actives)


def test_overload_bypass_fires_under_burst():
    reqs = [Request(rid=i, arrival=0, prompt_len=4, n_tokens=4,
                    eta_hint=5) for i in range(300)]
    cluster = make_cluster("sfs-aware", 2, lanes=2, n_slots=256)
    cluster.run(reqs, max_ticks=1_000_000)
    assert cluster.summary()["overload_bypasses"] > 0


def test_sfs_aware_separates_eta_classes():
    """With idle engines, long-ETA requests avoid the engine that is
    busy with FILTER work, while a short request goes to it only if it
    is the most FILTER-free."""
    cluster = make_cluster("sfs-aware", 2, lanes=2, n_slots=64)
    e0, e1 = cluster.engines
    # occupy engine 0's FILTER lanes
    for i in range(2):
        e0.submit(Request(rid=100 + i, arrival=0, prompt_len=4,
                          n_tokens=50))
    long_req = Request(rid=0, arrival=0, prompt_len=4, n_tokens=1000,
                       eta_hint=1000)
    short_req = Request(rid=1, arrival=0, prompt_len=4, n_tokens=2,
                        eta_hint=2)
    assert cluster.route(long_req) == 1
    assert cluster.route(short_req) == 1   # e1 is the FILTER-free engine


# ---------------------------------------------------------------------------
# Golden parity: hash over 1 engine == the engine alone
# ---------------------------------------------------------------------------


def _fingerprint(reqs):
    return [(r.rid, r.finish, r.served_ticks, r.n_ctx, r.demoted)
            for r in reqs]


def test_hash_batch_routes_same_tick_against_pre_delivery_state():
    """Legacy Router parity: all of a tick's arrivals are routed before
    any is delivered, so two same-tick requests that p2c-hash to the
    same engine both land there (the first delivery must not divert the
    second)."""
    cluster = make_cluster("hash", 2, lanes=2, n_slots=64)
    # find two rids whose p2c choice agrees while both engines are empty
    probe = [Request(rid=i, arrival=0, prompt_len=4, n_tokens=4)
             for i in range(20)]
    picks = {r.rid: cluster.route(r) for r in probe}
    target = picks[probe[0].rid]
    pair = [r for r in probe if picks[r.rid] == target][:2]
    assert len(pair) == 2
    cluster.tick(pair)
    assert all(r.rid in {q.rid for q in
                         cluster.engines[target].by_slot.values()}
               for r in pair)


def test_hash_single_engine_matches_engine_run():
    kw = dict(n=80, lanes=4, seed=11, stalls=True)
    solo = Engine(EngineConfig(lanes=4, n_slots=64, policy="sfs"))
    ref = solo.run(workload(**kw), max_ticks=2_000_000)
    cluster = make_cluster("hash", 1, lanes=4, n_slots=64)
    got = cluster.run(workload(**kw), max_ticks=2_000_000)
    assert _fingerprint(got) == _fingerprint(ref)


# ---------------------------------------------------------------------------
# DES multi-server mode
# ---------------------------------------------------------------------------


def test_des_single_server_hash_matches_simulate():
    reqs = generate(FaaSBenchConfig(n_requests=800, cores=4, load=0.9,
                                    seed=1))
    single = simulate(reqs, SimConfig(cores=4, policy="sfs"))
    clus = simulate_cluster(reqs, ClusterSimConfig(
        n_servers=1, dispatch="hash",
        server=SimConfig(cores=4, policy="sfs")))
    a = [(s.rid, s.finish, s.n_ctx, s.demoted) for s in single.stats]
    b = [(s.rid, s.finish, s.n_ctx, s.demoted)
         for s in clus.merged.stats]
    assert a == b


@pytest.mark.parametrize("policy", POLICIES)
def test_des_cluster_completes_all(policy):
    n = 1000
    reqs = generate(FaaSBenchConfig(n_requests=n, cores=12, load=0.9,
                                    seed=2, io_fraction=0.2))
    res = simulate_cluster(reqs, ClusterSimConfig(
        n_servers=3, dispatch=policy,
        server=SimConfig(cores=4, policy="sfs")))
    assert [s.rid for s in res.merged.stats] == list(range(n))
    assert sum(res.dispatch_counts) == n
    per_server = sum(len(r.stats) for r in res.per_server)
    assert per_server == n
    for s in res.merged.stats:
        assert s.turnaround > 0


def test_des_pull_prefers_idle_servers():
    """Two far-apart arrivals: with pull dispatch the second lands on an
    idle server immediately (no central wait), so its turnaround equals
    the single-server run-to-completion time."""
    from repro.core.workload import Request as CoreRequest
    reqs = [CoreRequest(rid=0, arrival=0.0, service=0.05),
            CoreRequest(rid=1, arrival=1.0, service=0.05)]
    res = simulate_cluster(reqs, ClusterSimConfig(
        n_servers=2, dispatch="pull",
        server=SimConfig(cores=1, policy="sfs")))
    for s in res.merged.stats:
        assert s.turnaround == pytest.approx(0.05 + 100e-6, abs=1e-9)


# ---------------------------------------------------------------------------
# Dispatch latency (router -> server network delay)
# ---------------------------------------------------------------------------


def test_dispatch_latency_adds_to_turnaround_exactly():
    """An uncontended request pays service + switch-in + latency, and
    turnaround is still measured from the *cluster* arrival."""
    from repro.core.workload import Request as CoreRequest
    lat = 0.01
    reqs = [CoreRequest(rid=0, arrival=0.0, service=0.05),
            CoreRequest(rid=1, arrival=1.0, service=0.05)]
    res = simulate_cluster(reqs, ClusterSimConfig(
        n_servers=2, dispatch="least-outstanding", dispatch_latency_s=lat,
        server=SimConfig(cores=1, policy="sfs")))
    for s in res.merged.stats:
        assert s.turnaround == pytest.approx(0.05 + 100e-6 + lat, abs=1e-9)


@pytest.mark.parametrize("policy", POLICIES)
def test_des_cluster_completes_under_latency(policy):
    n = 600
    reqs = generate(FaaSBenchConfig(n_requests=n, cores=8, load=1.0,
                                    seed=6))
    res = simulate_cluster(reqs, ClusterSimConfig(
        n_servers=2, dispatch=policy, dispatch_latency_s=0.002,
        server=SimConfig(cores=4, policy="sfs")))
    assert [s.rid for s in res.merged.stats] == list(range(n))
    assert all(s.turnaround >= 0.002 for s in res.merged.stats)


def test_overload_bypass_fires_under_dispatch_latency():
    """O x S re-validation (ROADMAP): with nonzero latency the router's
    view of each server is stale, but its own in-flight sends spill into
    the estimated FILTER queue, so a same-instant burst still trips the
    est-wait >= O x S bypass."""
    from repro.core.workload import Request as CoreRequest
    reqs = [CoreRequest(rid=i, arrival=0.0, service=0.05, func_id=0)
            for i in range(300)]
    res = simulate_cluster(reqs, ClusterSimConfig(
        n_servers=2, dispatch="sfs-aware", dispatch_latency_s=0.005,
        slice_init_s=0.05,
        server=SimConfig(cores=2, policy="sfs")))
    assert res.overload_bypasses > 0
    assert [s.rid for s in res.merged.stats] == list(range(300))


# ---------------------------------------------------------------------------
# Multi-server slice-timeline merge (was silently dropped)
# ---------------------------------------------------------------------------


def test_merged_slice_timeline_tagged_per_server():
    reqs = generate(FaaSBenchConfig(n_requests=800, cores=8, load=1.0,
                                    seed=3))
    res = simulate_cluster(reqs, ClusterSimConfig(
        n_servers=2, dispatch="least-outstanding",
        server=SimConfig(cores=4, policy="sfs")))
    tl = res.merged.slice_timeline
    assert tl, "multi-server merge must not drop slice timelines"
    assert all(len(e) == 3 for e in tl)          # (time, S, server)
    assert [e[0] for e in tl] == sorted(e[0] for e in tl)
    assert {e[2] for e in tl} <= {0, 1}
    # each server's own trace is recoverable from the merged one
    for i, r in enumerate(res.per_server):
        assert [(t, s) for (t, s, j) in tl if j == i] == r.slice_timeline


def test_merged_slice_timeline_single_server_keeps_legacy_shape():
    reqs = generate(FaaSBenchConfig(n_requests=400, cores=4, load=1.0,
                                    seed=4))
    single = simulate(reqs, SimConfig(cores=4, policy="sfs"))
    clus = simulate_cluster(reqs, ClusterSimConfig(
        n_servers=1, dispatch="hash",
        server=SimConfig(cores=4, policy="sfs")))
    assert clus.merged.slice_timeline == single.slice_timeline
    assert all(len(e) == 2 for e in clus.merged.slice_timeline)


# ---------------------------------------------------------------------------
# Bounded slice timelines (regression: unbounded growth on long runs)


def test_slice_timeline_bounded_on_long_runs():
    """Regression: SFSAwareDispatch.slice_timeline grew one entry per
    adaptive window forever.  Feed enough arrivals for ~20k window
    updates and check the trace stays capped (decimated, first and
    latest entries preserved)."""
    from repro.core.dispatch import BoundedTimeline, SFSAwareDispatch

    class _V:
        lanes = 2

    pol = SFSAwareDispatch([_V(), _V()], adaptive_window=1)
    for t in range(20_000):
        pol._observe(float(t))
    tl = pol.slice_timeline
    assert isinstance(tl, BoundedTimeline)
    assert 2 <= len(tl) <= tl.cap
    assert tl[0] == (0.0, 32.0)                    # first entry survives
    assert tl[-1][0] == 19_999.0                   # latest entry survives
    ts = [t for t, _ in tl]
    assert ts == sorted(ts)


@pytest.mark.parametrize("window,since,filled,first,m", [
    (4, 0, 0, None, 1), (4, 3, 4, 5, 1), (4, 0, 2, 7, 3),
    (4, 2, 4, 1, 11), (3, 2, 1, None, 9), (100, 99, 100, 2, 250),
    (1, 0, 1, 3, 5)])
def test_observe_instant_equals_one_arrival_at_a_time(window, since, filled,
                                                      first, m):
    """m arrivals at one instant through an IAT window: the same window,
    count and slice updates (with their arrival index and window sum) as
    m single observations, the first with its IAT and the rest with 0."""
    from collections import deque

    from repro.core.dispatch import observe_instant
    seed = list(range(1, filled + 1))
    d_many = deque(seed, maxlen=window)
    since_many, fires = observe_instant(d_many, window, since, first, m)
    d_one, s_one, want = deque(seed, maxlen=window), since, []
    for k in range(m):
        s_one, f = observe_instant(d_one, window, s_one,
                                   first if k == 0 else 0, 1)
        want.extend((k, total) for _, total in f)
    assert (list(d_many), since_many, fires) == (list(d_one), s_one, want)


def test_hash_many_equals_hash():
    from repro.core.dispatch import _hash, _hash_many
    rids = [0, 1, 7, 99, 12345, 2**31 + 5, -3]
    for salt in (1, 2):
        assert _hash_many(rids, salt).tolist() == [_hash(r, salt)
                                                   for r in rids]


def test_bounded_timeline_decimation_semantics():
    from repro.core.dispatch import BoundedTimeline
    tl = BoundedTimeline(cap=8)
    for i in range(100):
        tl.append((i, i))
    assert len(tl) <= 8
    assert tl[-1] == (99, 99)
    assert tl[0] == (0, 0)
    assert list(tl) == sorted(tl)
    # list/equality interop used by the simulator merge path
    assert tl == list(tl)


def test_engine_and_vector_timelines_bounded():
    """The per-engine scheduler and the vector-group mirrors share the
    same bounded container."""
    from repro.core.dispatch import BoundedTimeline
    eng = Engine(EngineConfig(lanes=2, n_slots=16, policy="sfs"))
    assert isinstance(eng.scheduler.slice_timeline, BoundedTimeline)
