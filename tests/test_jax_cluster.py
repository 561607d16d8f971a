"""JAX cluster backend edges: empty-tick and event-skip fast paths,
``lax.scan`` chunking (commit, overflow, cooldown), device-region
growth, the unsupported-feature gates, stall events against the tick
engine, and the group_pick kernel parity promises (Pallas interpret
mode vs both jnp implementations).

Bit-exactness against the numpy vector backend across dispatch
policies and fleet sizes is asserted in ``tests/test_agreement.py``;
these are the structural edges that suite cannot reach cheaply."""
import numpy as np
import pytest

import repro.serving.jax_cluster as jc_mod
from repro.core.spec import ExperimentSpec, ServerSpec, run_experiment
from repro.core.telemetry import Telemetry
from repro.serving import ClusterConfig, Request, VectorCluster
from repro.serving.cluster import ClusterFrontend
from repro.serving.jax_cluster import _SCAN_CHUNK, JaxCluster


def fingerprint(reqs):
    """Every per-request field the engines mutate (the
    test_agreement.py currency)."""
    return [(r.rid, r.finish, r.served_ticks, r.n_ctx, r.demoted,
             r.first_start, r.queue_delay, r.queue_enter, r.vruntime,
             r.slice_left, r.tokens_done, r.prefill_done, r.slot)
            for r in reqs]


def per_tick_run(cluster, workload, max_ticks=200_000):
    """cluster.run() minus the multi-tick fast paths: the per-tick
    reference the batched stepping must match."""
    workload = sorted(workload, key=lambda r: r.arrival)
    i, n = 0, len(workload)
    while cluster._finished_count() < n:
        assert cluster.t <= max_ticks, "per-tick reference ran away"
        arrivals = []
        while i < n and workload[i].arrival <= cluster.t:
            arrivals.append(workload[i])
            i += 1
        cluster.tick(arrivals)
    return sorted(cluster._collect(), key=lambda r: r.rid)


# ---------------------------------------------------------------------------
# Grouping and the unsupported-feature gates
# ---------------------------------------------------------------------------


def test_homogeneous_servers_form_one_group():
    jc = JaxCluster([ServerSpec(cores=4)] * 8, ClusterConfig())
    s = jc.summary()
    assert s["backend"] == "jax"
    assert len(s["groups"]) == 1
    assert s["groups"][0]["members"] == list(range(8))


def test_unvectorizable_scheduler_raises():
    with pytest.raises(ValueError, match="jax backend"):
        JaxCluster([ServerSpec(cores=4, scheduler="srtf")], ClusterConfig())


def test_object_pinned_server_raises():
    # no straggler path here: the whole point of this backend is one
    # jitted step, so object-engine riders go to engine="vector"
    with pytest.raises(ValueError, match="jax backend"):
        JaxCluster([ServerSpec(cores=4, engine="object")], ClusterConfig())


# ---------------------------------------------------------------------------
# Empty ticks and the event-skip (gap advance) fast path
# ---------------------------------------------------------------------------


def test_empty_ticks_are_inert():
    jc = JaxCluster([ServerSpec(cores=2, slots=8)] * 3, ClusterConfig())
    for _ in range(50):
        jc.tick(())
    assert jc.t == 50
    assert jc._finished_count() == 0
    g = jc.groups[0]
    assert g.filter_count.sum() == 0 and g.cfs_count.sum() == 0
    assert g.outstanding.sum() == 0
    assert (g.free_slots == 8).all()
    # a request arriving after the idle stretch completes normally
    jc.tick([Request(rid=0, arrival=jc.t, prompt_len=4, n_tokens=3)])
    for _ in range(10):
        jc.tick(())
    assert jc._finished_count() == 1


def _sparse_workload():
    """Arrival gaps far wider than any service demand: every request
    leaves long idle/drain windows the fast paths must skip over."""
    rng = np.random.default_rng(41)
    out = []
    for i in range(24):
        ntok = int(rng.integers(2, 8) if rng.random() < 0.7
                   else rng.integers(30, 60))
        out.append(Request(rid=i, arrival=i * 120, prompt_len=4,
                           n_tokens=ntok))
    return out


@pytest.mark.parametrize("policy", ["least-outstanding", "sfs-aware"])
def test_fast_paths_match_per_tick_stepping(policy):
    """run() (gap advance + scan chunks) == the per-tick reference,
    field for field — and the fast paths actually fired."""
    specs = [ServerSpec(cores=2)] * 3
    fired = []

    class Spy(JaxCluster):
        def _fast_forward(self, window):
            took = super()._fast_forward(window)
            fired.append(took)
            return took

    fast = Spy(specs, ClusterConfig(policy=policy))
    fast.tick_log = []
    got = fast.run(_sparse_workload(), max_ticks=200_000)
    ref = JaxCluster(specs, ClusterConfig(policy=policy))
    ref.tick_log = []
    want = per_tick_run(ref, _sparse_workload())
    assert any(fired), "sparse workload never engaged a fast path"
    assert fingerprint(got) == fingerprint(want)
    # the final completion can land mid-chunk, so run() may overshoot
    # the per-tick stop point by up to a chunk of idle ticks — but the
    # shared prefix must match tick for tick
    n = len(ref.tick_log)
    assert fast.t - ref.t < _SCAN_CHUNK
    assert fast.tick_log[:n] == ref.tick_log
    assert all(c == (0,) * len(specs) for _, _, c in fast.tick_log[n:])


@pytest.mark.parametrize("cls", [JaxCluster, VectorCluster])
def test_tick_log_is_opt_in(cls):
    """No tick log unless a caller sets a list before run(); keeping one
    changes no result, and the gap advance and scan chunks log every
    tick they carry."""
    specs = [ServerSpec(cores=2)] * 3
    for wl in (_sparse_workload, _burst_workload):
        off = cls(specs, ClusterConfig(policy="least-outstanding"))
        got = off.run(wl(), max_ticks=200_000)
        assert off.tick_log is None
        on = cls(specs, ClusterConfig(policy="least-outstanding"))
        on.tick_log = []
        want = on.run(wl(), max_ticks=200_000)
        assert fingerprint(got) == fingerprint(want)
        assert [t for t, _, _ in on.tick_log] == list(range(on.t))


def test_gap_advance_skips_pure_drain():
    """One long request then silence: skip_valid() holds (lanes busy,
    queue empty, nothing rotates), so the drain collapses into gap
    jumps rather than per-tick device calls."""
    jc = JaxCluster([ServerSpec(cores=2)], ClusterConfig())
    steps = []
    g = jc.groups[0]
    orig = type(g).step_tick

    def counting(self, t):
        steps.append(t)
        return orig(self, t)

    type(g).step_tick = counting
    try:
        done = jc.run([Request(rid=0, arrival=0, prompt_len=4,
                               n_tokens=400)], max_ticks=10_000)
    finally:
        type(g).step_tick = orig
    assert len(done) == 1 and done[0].finish is not None
    # 400+ ticks of wall time, but only a handful of real device steps
    assert jc.t >= 400
    assert len(steps) < 50


# ---------------------------------------------------------------------------
# lax.scan chunks: commit, overflow, cooldown
# ---------------------------------------------------------------------------


def _burst_workload():
    """16 identical long requests at t=0: pools rotate (scan territory)
    and completions land in same-tick bursts (overflow territory)."""
    return [Request(rid=i, arrival=0, prompt_len=4, n_tokens=90)
            for i in range(16)]


def test_scan_chunks_commit_and_match_vector():
    specs = [ServerSpec(cores=2)] * 4
    committed = []

    class Spy(JaxCluster):
        def _scan_window(self):
            took = super()._scan_window()
            committed.append(took)
            return took

    jx = Spy(specs, ClusterConfig(policy="least-outstanding"))
    got = jx.run(_burst_workload(), max_ticks=50_000)
    vec = VectorCluster(specs, ClusterConfig(policy="least-outstanding"))
    want = vec.run(_burst_workload(), max_ticks=50_000)
    assert any(committed), "burst drain never committed a scan chunk"
    assert fingerprint(got) == fingerprint(want)


def test_scan_overflow_cooldown_still_exact():
    """A blown per-tick event buffer must roll the whole chunk back and
    replay per tick — shrink the buffer to one event so every burst
    overflows, and the run must still equal the vector backend."""
    specs = [ServerSpec(cores=2)] * 4
    orig = jc_mod._scan_evcap
    jc_mod._scan_evcap = lambda G, L, sfs: 1
    jc_mod._build_fns.cache_clear()
    try:
        jx = JaxCluster(specs, ClusterConfig(policy="least-outstanding"))
        got = jx.run(_burst_workload(), max_ticks=50_000)
        assert jx._scan_cooldown > 0, "no overflow with a 1-event buffer"
    finally:
        jc_mod._scan_evcap = orig
        jc_mod._build_fns.cache_clear()
    vec = VectorCluster(specs, ClusterConfig(policy="least-outstanding"))
    want = vec.run(_burst_workload(), max_ticks=50_000)
    assert fingerprint(got) == fingerprint(want)


def test_scan_evcap_sizing():
    """Burst-sized: every FILTER lane plus every chosen pool slot can
    complete in one tick, capped to keep the chunk buffer small."""
    assert jc_mod._scan_evcap(4, 2, False) == 8
    assert jc_mod._scan_evcap(4, 2, True) == 16
    assert jc_mod._scan_evcap(1024, 8, True) == jc_mod._SCAN_EVCAP_MAX
    assert _SCAN_CHUNK <= jc_mod._SCAN_EVCAP_MAX


# ---------------------------------------------------------------------------
# Device-region growth (queue ring / pool / arrival buffer)
# ---------------------------------------------------------------------------


def _flood_workload():
    rng = np.random.default_rng(13)
    return [Request(rid=i, arrival=0, prompt_len=4,
                    n_tokens=int(rng.integers(2, 30)))
            for i in range(300)]


def test_region_growth_under_single_tick_flood():
    """300 simultaneous arrivals on one 2-lane engine blow all three
    device regions past their initial sizes in the first step; the
    grow/re-jit path must preserve exactness vs the vector backend."""
    specs = [ServerSpec(cores=2, slots=2048)]
    cfg = ClusterConfig(policy="hash")
    jx = JaxCluster(specs, cfg)
    g = jx.groups[0]
    qcap0, cap0, acap0 = g.QCAP, g.CAP, g.ACAP
    got = jx.run(_flood_workload(), max_ticks=200_000)
    assert g.QCAP > qcap0 and g.CAP > cap0 and g.ACAP > acap0
    vec = VectorCluster(specs, ClusterConfig(policy="hash"))
    want = vec.run(_flood_workload(), max_ticks=200_000)
    assert fingerprint(got) == fingerprint(want)


# ---------------------------------------------------------------------------
# Batched intake: one routing call and one submit per group a tick
# ---------------------------------------------------------------------------


class PerArrival(JaxCluster):
    """The jax backend with the frontend's one-by-one intake: the
    reference the batched intake must match."""
    _route_tick = ClusterFrontend._route_tick


class Witness(JaxCluster):
    """The batched intake, recording the most arrivals one server took
    in a tick and the deepest pending deque after an intake."""

    def __init__(self, *args):
        super().__init__(*args)
        self.seen = {"burst": 0, "pending": 0}

    def _route_tick(self, arrivals):
        before = np.array(self.policy.dispatch_counts)
        super()._route_tick(arrivals)
        seen = self.seen
        seen["burst"] = max(seen["burst"], int(
            (np.array(self.policy.dispatch_counts) - before).max()))
        seen["pending"] = max(seen["pending"], max(
            int(g.pending_len.max()) for g in self.groups))


def _intake_workload(n, per_tick, seed, every=1, funcs=6):
    """``per_tick`` arrivals every ``every`` ticks; each function has
    its own duration, so the history predictor learns distinct ETAs."""
    rng = np.random.default_rng(seed)
    dur = rng.integers(2, 60, funcs)
    out = []
    for i in range(n):
        f = int(rng.integers(funcs))
        ntok = max(1, int(dur[f] + rng.integers(-1, 2)))
        out.append(Request(rid=i, arrival=(i // per_tick) * every,
                           prompt_len=4, n_tokens=ntok, eta_hint=ntok + 1,
                           func_id=f))
    return out


def _intake_state(c):
    """Every result and piece of routing state the intake writes."""
    p = c.policy
    return dict(
        dispatch_counts=c.dispatch_counts, eta_log=c.eta_log,
        summary=c.summary(), S=getattr(p, "S", None),
        slice_timeline=list(getattr(p, "slice_timeline", ())),
        iats=list(getattr(p, "_iats", ())),
        groups=[(g.S.tolist(), [list(x) for x in g.slice_timeline],
                 [list(d) for d in g._iats], g._since_update.tolist(),
                 g._last_arrival.tolist()) for g in c.groups])


_SFS4 = (ServerSpec(cores=2, slots=4),) * 4
_MIXED = ((ServerSpec(cores=4),) * 3
          + (ServerSpec(cores=2, scheduler="cfs"),) * 2)
_WIDE = (ServerSpec(cores=4, slots=512),) * 2

INTAKE_CASES = {
    # 20 arrivals a tick on 4 x 4 slots: most wait in pending deques
    "sfs-aware-slots-exhausted": (_SFS4, "sfs-aware", "history",
                                  (600, 20, 1),
                                  lambda c, s: s["pending"] > 0),
    "hash-slots-exhausted": (_SFS4, "hash", "history", (600, 20, 1),
                             lambda c, s: s["pending"] > 0),
    # 400 arrivals a tick on two servers: the dispatcher's bypass fires
    # and each server takes more than adaptive_window (100) in a tick
    "sfs-aware-bypass-wide-burst": (
        _WIDE, "sfs-aware", "oracle", (1200, 400, 3, 300),
        lambda c, s: c.policy.overload_bypasses > 0 and s["burst"] > 100),
    "hash-wide-burst": (_WIDE, "hash", "oracle", (1200, 400, 3, 300),
                        lambda c, s: s["burst"] > 100),
    # an sfs group and a cfs group behind one dispatcher
    "sfs-aware-mixed-groups": (_MIXED, "sfs-aware", "history",
                               (800, 12, 2),
                               lambda c, s: len(c.groups) == 2),
    "hash-mixed-groups": (_MIXED, "hash", "oracle", (800, 12, 2),
                          lambda c, s: len(c.groups) == 2),
}


@pytest.mark.parametrize("case", sorted(INTAKE_CASES))
def test_batched_intake_matches_per_arrival(case):
    """The batched intake (route_batch engaged on every tick with
    arrivals) == the one-by-one intake, field for field, with the same
    routing state: dispatch counts, ETA log, bypasses, both levels of
    adaptive slice."""
    specs, policy, predictor, wl, witness = INTAKE_CASES[case]
    cfg = ClusterConfig(policy=policy, predictor=predictor)
    runs = []
    for cls in (Witness, PerArrival):
        tel = Telemetry(profile=True)
        c = cls(specs, cfg)
        c.attach_telemetry(tel)
        got = c.run(_intake_workload(*wl), max_ticks=200_000)
        runs.append((c, tel.profile.phases, got))
    (bc, bph, bgot), (rc, rph, rgot) = runs
    assert witness(bc, bc.seen), case
    arrival_ticks = len({r.arrival for r in _intake_workload(*wl)})
    assert bph["route_batch"][1] == arrival_ticks
    assert "route_batch" not in rph
    assert fingerprint(bgot) == fingerprint(rgot)
    assert _intake_state(bc) == _intake_state(rc)


def test_batched_intake_falls_back_when_keys_cannot_pack(monkeypatch):
    """A tick whose counters could outgrow the packed key fields routes
    one by one over np.lexsort, with the same results."""
    import repro.core.dispatch as dispatch_mod
    monkeypatch.setattr(dispatch_mod, "_PACK", 24)
    batched = []
    submit_many = jc_mod._JaxGroup.submit_many
    monkeypatch.setattr(jc_mod._JaxGroup, "submit_many",
                        lambda g, *a: batched.append(1) or submit_many(g, *a))
    specs, wl = (ServerSpec(cores=2, slots=64),) * 3, (400, 8, 4)
    cfg = ClusterConfig(policy="sfs-aware")
    runs = []
    for cls in (Witness, PerArrival):
        c = cls(specs, cfg)
        runs.append((c, c.run(_intake_workload(*wl), max_ticks=200_000)))
    (bc, bgot), (rc, rgot) = runs
    # some ticks were batched, the rest routed over np.lexsort
    assert 0 < len(batched) < len({r.arrival for r in _intake_workload(*wl)})
    assert bc.policy._keys is None
    assert fingerprint(bgot) == fingerprint(rgot)
    assert _intake_state(bc) == _intake_state(rc)


@pytest.mark.parametrize("fallback", [None, "masked", "warm_set", "trace"])
def test_route_batch_span_counts_ticks_with_arrivals(fallback):
    """One route_batch call per tick that had arrivals, nested in
    route; masked routing, a warm set and a trace collector take the
    one-by-one intake, so the span is absent there."""
    wl = _intake_workload(300, 10, 5, every=3)
    cfg = ClusterConfig(
        policy="sfs-aware",
        scaling="scale:min=2,T=50" if fallback == "masked" else None,
        lifecycle="lifecycle:cold=3" if fallback == "warm_set" else None)
    tel = Telemetry(profile=True, trace=fallback == "trace")
    c = JaxCluster([ServerSpec(cores=2)] * 4, cfg)
    c.attach_telemetry(tel)
    c.run(wl, max_ticks=200_000)
    ph = tel.profile.phases
    if fallback is None:
        assert ph["route_batch"][1] == len({r.arrival for r in wl})
        assert ph["route_batch"][0] <= ph["route"][0]
    else:
        if fallback == "masked":
            assert c.policy.active is not None
        assert "route_batch" not in ph


@pytest.mark.parametrize("scheduler", ["sfs", "cfs"])
def test_columns_delivery_model_matches_pull(scheduler):
    """_JaxColumns.deliver, the backend's model of one delivery, equals
    the columns pulled after a real submit: with free slots and after
    they run out, with idle FILTER lanes and after they are gone."""
    specs = [ServerSpec(cores=2, slots=3, scheduler=scheduler),
             ServerSpec(cores=2, slots=3)]
    c = JaxCluster(specs, ClusterConfig(policy="sfs-aware"))
    cols = c._cols
    for k in range(6):
        for i in (0, 1):
            cols.refresh()
            model = [a.tolist() for a in (cols.outstanding, cols.filter_free,
                                          cols.queue_len, cols.fair_load)]
            cols.begin_intake()
            cols.deliver(i, model)
            c._submit(i, Request(rid=2 * k + i, arrival=0, prompt_len=4,
                                 n_tokens=5))
            cols.refresh()
            assert model == [a.tolist() for a in (
                cols.outstanding, cols.filter_free, cols.queue_len,
                cols.fair_load)], (scheduler, k, i)
    g = c.groups[0]
    assert g.pending_len.max() > 0 and (g.free_slots == 0).all()


# ---------------------------------------------------------------------------
# Stall events (§V-D parking): jax against the tick engine
# ---------------------------------------------------------------------------


def _stall_workload(n, lanes, seed, load=1.0, p=0.6, thr=(0, 3), dur=(1, 4),
                    events=1, gap=None, n_tok=None):
    """Bimodal demands with Poisson arrivals at ``load`` over ``lanes``
    (or one arrival every ``gap`` ticks); a share ``p`` of the requests
    carries ``events`` stall events, thresholds drawn on ``thr`` (each
    after the last) and durations on ``dur``."""
    rng = np.random.default_rng(seed)
    svc = (np.full(n, n_tok) if n_tok is not None else
           np.where(rng.random(n) < 0.8, rng.integers(2, 8, n),
                    rng.integers(30, 80, n)))
    if gap is None:
        iats = rng.exponential(1.0, n)
        arr = np.cumsum(iats * svc.sum() / (load * lanes) / iats.sum())
    else:
        arr = np.arange(n) * gap
    out = []
    for i in range(n):
        ev, at = [], 0
        if rng.random() < p:
            for _ in range(events):
                at += int(rng.integers(*thr))
                ev.append((at, int(rng.integers(*dur))))
        out.append(Request(rid=i, arrival=int(arr[i]), prompt_len=4,
                           n_tokens=int(svc[i]), eta_hint=int(svc[i]) + 1,
                           stall_events=tuple(ev)))
    return out


def _stall_fingerprint(reqs):
    return fingerprint(reqs) + [r.stall_idx for r in reqs]


class _StallWitness:
    """What the jax groups did with stalls during a run: parks from
    FILTER lanes and from the pool, wakes inside committed scan chunks,
    gap advances that end on a wake tick, steps with both a pending
    deque and parked rows, and every program built."""

    def __init__(self, monkeypatch):
        self.seen = dict(filter_park=0, pool_park=0, scan_wakes=0,
                         gap_to_wake=0, pending_and_parked=0)
        self.builds = []
        G = jc_mod._JaxGroup
        seen, builds = self.seen, self.builds
        emit, commit = G._emit_trace, G.commit_scan
        advance, step = G.advance, G.step_tick
        build = jc_mod._build_fns

        def _emit_trace(g, out, t):
            if "trace_park" in out:
                # sfs: FILTER lanes, then the pool's picks; cfs: picks
                hit = np.asarray(out["trace_park"]) >= 0
                lanes = g.lanes if g.policy == "sfs" else 0
                seen["filter_park"] += int(hit[:, :lanes].sum())
                seen["pool_park"] += int(hit[:, lanes:].sum())
            return emit(g, out, t)

        def commit_scan(g, t0, payload):
            if g.ME:
                seen["scan_wakes"] += int(payload[2][:, 7].sum())
            return commit(g, t0, payload)

        def advance_(g, n, t0):
            if g.ME and g.parked.any() and g.wmin.min() == t0 + n:
                seen["gap_to_wake"] += 1
            return advance(g, n, t0)

        def step_tick(g, t):
            res = step(g, t)
            if g.pending_len.any() and g.parked.any():
                seen["pending_and_parked"] += 1
            return res

        def build_fns(*a):
            builds.append(a)
            return build(*a)

        monkeypatch.setattr(G, "_emit_trace", _emit_trace)
        monkeypatch.setattr(G, "commit_scan", commit_scan)
        monkeypatch.setattr(G, "advance", advance_)
        monkeypatch.setattr(G, "step_tick", step_tick)
        monkeypatch.setattr(jc_mod, "_build_fns", build_fns)


def _parked_then(trace, kind):
    """Requests that a ``preempt`` (park) precedes a later ``kind``."""
    first = {}
    hits = set()
    for t, k, rid, _srv, _aux in trace:
        if k == "preempt":
            first.setdefault(rid, t)
        elif k == kind and rid in first and first[rid] < t:
            hits.add(rid)
    return hits


_SFS2 = ServerSpec(cores=2)
STALL_CASES = {
    # (servers, dispatch, workload, witness(seen, results, trace))
    "filter-lane-sfs-aware": (
        (_SFS2,) * 4, "sfs-aware", dict(n=240, lanes=8, seed=3),
        lambda s, r, tr: s["filter_park"] > 0),
    "pool-hash": (
        (ServerSpec(cores=2, scheduler="sfs:slice=3"),) * 3, "hash",
        dict(n=200, lanes=6, seed=4, thr=(4, 40)),
        lambda s, r, tr: s["pool_park"] > 0),
    "wake-bypass": (
        (ServerSpec(cores=2, scheduler="sfs:slice=4,overload_factor=0.5"),)
        * 2, "hash", dict(n=200, lanes=4, seed=5, load=1.3),
        lambda s, r, tr: len(_parked_then(tr, "bypass")) > 0),
    "slots-full": (
        (ServerSpec(cores=2, slots=3),) * 3, "sfs-aware",
        dict(n=300, lanes=6, seed=6, load=1.4),
        lambda s, r, tr: s["pending_and_parked"] > 0),
    "wake-in-scan": (
        (_SFS2,) * 2, "least-outstanding",
        dict(n=16, lanes=4, seed=7, gap=0, n_tok=90, p=1.0, thr=(20, 60),
             dur=(2, 9)),
        lambda s, r, tr: s["scan_wakes"] > 0),
    "wake-ends-gap": (
        (_SFS2,) * 3, "sfs-aware",
        dict(n=24, lanes=6, seed=8, gap=120, p=0.8, dur=(20, 60)),
        lambda s, r, tr: s["gap_to_wake"] > 0),
    "two-events-sfs-aware": (
        (_SFS2,) * 4, "sfs-aware", dict(n=240, lanes=8, seed=9, events=2),
        lambda s, r, tr: any(x.stall_idx == 2 and x.n_ctx >= 2 for x in r)),
    "two-events-cfs-hash": (
        (ServerSpec(cores=2, scheduler="cfs"),) * 3, "hash",
        dict(n=200, lanes=6, seed=10, events=2, load=1.2),
        lambda s, r, tr: s["pool_park"] > 0
        and any(x.stall_idx == 2 for x in r)),
    "slice-burns-while-stalled": (
        (ServerSpec(cores=2, scheduler="sfs:stall_aware=False"),) * 3,
        "sfs-aware", dict(n=240, lanes=6, seed=11, load=1.2),
        lambda s, r, tr: s["filter_park"] > 0),
    # pull reads capacity: parked requests are not runnable, unless
    # they wake at the tick being routed
    "pull-capacity": (
        (_SFS2,) * 4, "pull", dict(n=240, lanes=8, seed=13, load=1.2),
        lambda s, r, tr: s["filter_park"] > 0),
    # two pool picks park, the pool runs empty (an empty pick clears
    # what it ran last), both wake together onto one lane: the one
    # passed over was not displaced
    "empty-pick-while-parked": (
        (ServerSpec(cores=1, scheduler="cfs"),), "hash",
        lambda: [Request(rid=i, arrival=0, prompt_len=4, n_tokens=5,
                         stall_events=((0, 3 - i),)) for i in range(2)],
        lambda s, r, tr: s["pool_park"] == 2),
    "never-stalls": (
        (_SFS2,) * 4, "sfs-aware", dict(n=240, lanes=8, seed=12, p=0.0),
        lambda s, r, tr: s == dict(filter_park=0, pool_park=0,
                                   scan_wakes=0, gap_to_wake=0,
                                   pending_and_parked=0)),
}


@pytest.mark.parametrize("case", sorted(STALL_CASES))
def test_stalls_match_tick_engine(case, monkeypatch):
    """engine="jax" with stall events == engine="tick", field for field
    (stall_idx too), with the same dispatch counts; traced (the
    per-tick path), the same lifecycle events, a park a ``preempt``;
    untraced, through the scan and gap fast paths, the same results
    again.  Each case's witness shows its path was taken.  A fleet that
    never stalls keeps the stall region off: its groups build only the
    programs without it."""
    servers, dispatch, wl, witness = STALL_CASES[case]
    workload = wl if callable(wl) else (lambda: _stall_workload(**wl))
    spec = ExperimentSpec(engine="tick", servers=servers, dispatch=dispatch,
                          predictor="oracle")
    tick_tel = Telemetry(trace=True)
    want = run_experiment(spec, workload(), max_ticks=200_000,
                          telemetry=tick_tel)
    spy = _StallWitness(monkeypatch)
    jax_spec = ExperimentSpec(engine="jax", servers=servers,
                              dispatch=dispatch, predictor="oracle")
    tel = Telemetry(trace=True)
    traced = run_experiment(jax_spec, workload(), max_ticks=200_000,
                            telemetry=tel)
    fast = run_experiment(jax_spec, workload(), max_ticks=200_000)
    ref = _stall_fingerprint(want.raw)
    assert _stall_fingerprint(traced.raw) == ref
    assert _stall_fingerprint(fast.raw) == ref
    assert traced.dispatch_counts == want.dispatch_counts
    assert fast.dispatch_counts == want.dispatch_counts
    assert tel.trace.canonical() == tick_tel.trace.canonical()
    assert witness(spy.seen, want.raw, tick_tel.trace.canonical()), spy.seen
    stalled = any(r.stall_events for r in workload())
    configs = [b[6] for b in spy.builds]        # _build_fns(..., stl)
    assert configs and any(c is not None for c in configs) == stalled


# ---------------------------------------------------------------------------
# group_pick kernel parity (the kernel.py docstring promise)
# ---------------------------------------------------------------------------


def _pick_cases(G=8, CAP=16, seed=3):
    import jax.numpy as jnp
    from repro.kernels.group_pick.ref import _IMAX
    rng = np.random.default_rng(seed)
    # heavy vruntime ties + unique rids, ~30% sentinel slots
    vr = rng.integers(0, 6, (G, CAP)).astype(np.int32)
    rid = rng.permutation(G * CAP).reshape(G, CAP).astype(np.int32)
    hole = rng.random((G, CAP)) < 0.3
    hole[1:3, 3:] = True        # pools with fewer valid keys than kmax
    vr = np.where(hole, _IMAX, vr)
    rid = np.where(hole, _IMAX, rid)
    vr[0, :] = _IMAX            # one fully-empty pool
    rid[0, :] = _IMAX
    return jnp.asarray(vr), jnp.asarray(rid)


def test_pick_order_argmin_matches_ref():
    from repro.kernels.group_pick import pick_order_argmin, pick_order_ref
    for G in (8, 12):
        vr, rid = _pick_cases(G)
        for kmax in (1, 4, 8):
            ref = np.asarray(pick_order_ref(vr, rid, kmax))
            got = np.asarray(pick_order_argmin(vr, rid, kmax))
            assert (ref == got).all(), (G, kmax)


def test_pick_order_keeps_stable_sort_order_after_valid_keys_run_out():
    """Once a pool's valid keys are picked, the remaining picks are its
    sentinel slots in position order, as the stable sort gives them —
    a picked slot keeps no rid that could win the sentinel tie."""
    import jax.numpy as jnp

    from repro.kernels.group_pick import pick_order_argmin, pick_order_ref
    from repro.kernels.group_pick.kernel import pick_order_pallas
    from repro.kernels.group_pick.ref import _IMAX
    vr = jnp.asarray([[5, _IMAX, 3, _IMAX]], jnp.int32)
    rid = jnp.asarray([[7, _IMAX, 2, _IMAX]], jnp.int32)
    want = [[2, 0, 1, 3]]
    assert np.asarray(pick_order_ref(vr, rid, 4)).tolist() == want
    assert np.asarray(pick_order_argmin(vr, rid, 4)).tolist() == want
    assert np.asarray(pick_order_pallas(vr, rid, 4,
                                        interpret=True)).tolist() == want


def test_pick_order_pallas_interpret_matches_ref():
    from repro.kernels.group_pick.kernel import pick_order_pallas
    from repro.kernels.group_pick.ref import pick_order_ref
    vr, rid = _pick_cases()
    for kmax, gb in ((1, 8), (4, 8), (4, 3), (8, 1)):
        ref = np.asarray(pick_order_ref(vr, rid, kmax))
        got = np.asarray(pick_order_pallas(vr, rid, kmax, gb=gb,
                                           interpret=True))
        assert (ref == got).all(), (kmax, gb)


@pytest.mark.parametrize("G,gb", [(12, 8), (12, 3), (1, 8), (20, 16),
                                  (13, 1)])
def test_pick_order_pallas_interpret_pads_ragged_groups(G, gb):
    """G not a multiple of the 8-row tile: the kernel pads with
    sentinel rows (any requested ``gb`` rounds up to the tile) and
    slices them off."""
    from repro.kernels.group_pick.kernel import pick_order_pallas
    from repro.kernels.group_pick.ref import pick_order_ref
    vr, rid = _pick_cases(G, seed=G)
    for kmax in (1, 4, 8):
        ref = np.asarray(pick_order_ref(vr, rid, kmax))
        got = np.asarray(pick_order_pallas(vr, rid, kmax, gb=gb,
                                           interpret=True))
        assert got.shape == (G, kmax)
        assert (ref == got).all(), (G, gb, kmax)


def test_pick_order_dispatcher_off_tpu():
    import jax

    from repro.kernels.group_pick import (pick_impl, pick_order,
                                         pick_order_ref)
    if jax.default_backend() == "tpu":
        pytest.skip("dispatcher routes to the Pallas kernel on TPU")
    vr, rid = _pick_cases()
    assert pick_impl() == "argmin"
    assert (np.asarray(pick_order(vr, rid, 4))
            == np.asarray(pick_order_ref(vr, rid, 4))).all()
