"""Cluster dispatch sweep: policy x engine-count x load (+ mixed pools).

Sweeps the four dispatch policies (hash, least-outstanding, pull,
sfs-aware) over both execution models of the cluster layer, every cell
declared as a :class:`repro.ExperimentSpec` and run through the single
``repro.run_experiment`` entry point:

* the tick-engine serving cluster (``engine="tick"``, synthetic mode —
  no JAX), reporting P50/P99 turnaround and mean RTE per service-demand
  bucket (short / medium / long, in ticks);
* the discrete-event multi-server simulator (``engine="des"``, FaaSBench
  workload, seconds) — in ``--smoke``/``--des`` runs, for
  cross-validation.

A **mixed-pool** scenario exercises heterogeneous clusters (first-class
in the spec layer): two FILTER-rich SFS servers (6 lanes) next to two
small fair-share-only CFS servers (2 lanes).  ``sfs-aware`` exploits the
shape — shorts to the FILTER-rich servers, longs concentrated on the
fair-share pool — where shape-blind ``hash`` cannot.

A **fleet** scenario runs 64 engines x 4 lanes through the vectorized
stepping backend (``engine="vector"``, docs/CLUSTER.md "Scaling past 8
engines") — consolidation scale the per-object tick loop cannot reach
inside the smoke budget — and checks that sfs-aware still protects
short functions against hash and least-outstanding under the bimodal
(Azure-shaped) workload at load >= 0.8.

A **fleet1024** scenario (``--fleet1024``, its own invocation so it
gets its own <60 s budget) pushes consolidation to 1024 engines x 8
lanes at load 0.9 through the jitted JAX backend (``engine="jax"``,
docs/CLUSTER.md "Scaling past 64 engines") — a million requests total
across the sfs-aware/hash pair, scale where even the vectorized numpy
stepping pays minutes of per-tick interpreter overhead.  Its rows land
in the same artifact family and are gated in ``BENCH_cluster.json``
alongside the rest of the sweep (see ``benchmarks/run.py``).

``--smoke`` runs a <60 s configuration suitable as a CI check and
verifies the headline cluster claims: sfs-aware short-function P99 <=
hash at load >= 0.8, in the uniform sweep, the mixed pool AND the
64-engine fleet.  The ``--fleet1024`` invocation applies the same check
to the 1024-engine cells.

A **chaos** scenario (``--chaos``, own invocation) runs 16 engines x 4
lanes under correlated fault episodes with recovery, request timeouts
retried with backoff, and admission shedding — graceful degradation
under faults, gated in ``BENCH_cluster.json`` like the rest.

Usage:
  PYTHONPATH=src python benchmarks/cluster_sweep.py [--smoke] [--des]
  PYTHONPATH=src python benchmarks/cluster_sweep.py --fleet1024
  PYTHONPATH=src python benchmarks/cluster_sweep.py --chaos
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

if __package__ in (None, ""):          # `python benchmarks/cluster_sweep.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.common import save
from repro.core import FaaSBenchConfig
from repro.core.dispatch import POLICIES
from repro.core.metrics import DEFAULT_BUCKET_EDGES_T, bucket_stats
from repro.core.spec import (ExperimentSpec, ServerSpec, TickWorkloadSpec,
                             run_experiment)
from repro.launch.compile_cache import enable_compile_cache

SHORT_LABEL = f"<{DEFAULT_BUCKET_EDGES_T[0]:g}t"
SHORT_LABEL_S = "<0.1s"


def uniform_servers(n: int, lanes: int) -> tuple:
    return tuple(ServerSpec(cores=lanes) for _ in range(n))


# the heterogeneous pool: FILTER-rich SFS servers + small fair-share-only
# CFS servers (16 lanes total, like 4x4 uniform); same spec in both
# engines (the DES ignores tick cache slots)
MIXED_SERVERS = (ServerSpec(cores=6), ServerSpec(cores=6),
                 ServerSpec(cores=2, scheduler="cfs"),
                 ServerSpec(cores=2, scheduler="cfs"))


def run_tick(policy: str, servers: tuple, load: float, *, n: int,
             seed: int, scenario: str = "uniform",
             backend: str = "tick", workload: str = None,
             lifecycle: str = None, scaling: str = None,
             faults: str = None, retry: str = None) -> dict:
    from repro.core.telemetry import Telemetry
    spec = ExperimentSpec(
        engine=backend, servers=servers, dispatch=policy,
        workload=(workload if workload is not None
                  else TickWorkloadSpec(n=n, load=load, seed=seed)),
        lifecycle=lifecycle, scaling=scaling, faults=faults, retry=retry)
    # profile-only telemetry keeps every fast path (gap advance + scan
    # windows) live, so the phase breakdown rides along at no perf cost
    tel = Telemetry(profile=True)
    res = run_experiment(spec, max_ticks=50_000_000, telemetry=tel)
    return {
        "layer": "tick-engine", "scenario": scenario, "policy": policy,
        "backend": backend,
        "engines": len(servers), "lanes": [s.cores for s in servers],
        # n is row identity in the perf gate, so report the SUBMITTED
        # count: chaos rows shed a policy-dependent share of arrivals,
        # and completions alone would desync baseline matching the
        # moment a shed count moves
        "load": load, "n": res.n + res.shed, "wall_s": res.wall_s,
        # shed requests are their own metric: excluded from the
        # completion arrays behind the percentiles, reported per row
        "shed": res.shed,
        "dispatch_counts": res.dispatch_counts,
        "overload_bypasses": res.overload_bypasses,
        "buckets": res.buckets(),
        "provenance": {"spec": spec.to_json(), "seed": seed,
                       "result_fp": res.fingerprint()[:16]},
        "phases": tel.profile.summary(),
    }


def run_des(policy: str, servers: tuple, load: float, *, n: int,
            seeds=(7, 11), scenario: str = "uniform") -> dict:
    """DES sweep cell; pools a couple of seeds so p99 is stable."""
    total = sum(s.cores for s in servers)
    svc, ta, rte, counts, bypasses, wall = [], [], [], None, 0, 0.0
    prov, fps = None, []
    for seed in seeds:
        spec = ExperimentSpec(
            engine="des", servers=servers, dispatch=policy,
            workload=FaaSBenchConfig(n_requests=n, cores=total, load=load,
                                     seed=seed))
        if prov is None:      # seeds differ only in the workload seed
            prov = spec.to_json()
        res = run_experiment(spec)
        fps.append(res.fingerprint()[:16])
        svc.append(res.service)
        ta.append(res.turnaround)
        rte.append(res.rte)
        counts = (res.dispatch_counts if counts is None else
                  [a + b for a, b in zip(counts, res.dispatch_counts)])
        bypasses += res.overload_bypasses
        wall += res.wall_s
    return {
        "layer": "des", "scenario": scenario, "policy": policy,
        "engines": len(servers), "cores": [s.cores for s in servers],
        "load": load, "n": sum(len(x) for x in svc), "wall_s": wall,
        "dispatch_counts": counts, "overload_bypasses": bypasses,
        "buckets": bucket_stats(np.concatenate(svc), np.concatenate(ta),
                                np.concatenate(rte)),
        "provenance": {"spec": prov, "seed": list(seeds),
                       "result_fp": fps},
    }


def print_row(r: dict, short_key: str):
    b = r["buckets"]
    short, keys = b[short_key], list(b)
    long_ = b[keys[-1]]
    print(f"  {r['policy']:18s} short p50={short['p50']:9.2f} "
          f"p99={short['p99']:9.2f} rte={short.get('mean_rte', 0):.3f} | "
          f"long p99={long_['p99']:10.2f} | {r['wall_s']:5.1f}s")


def check_headline(rows: list, *, hard: bool) -> int:
    """sfs-aware must not lose to hash on short-function P99 at load >=
    0.8 (small tolerance for tie noise) — in the uniform sweep and in
    the mixed pool, where exploiting the FILTER-rich servers is the
    whole point.  Hard-enforced (non-zero exit) in the smoke/fleet1024
    configs only: the full sweep includes deliberately unstable cells
    (2 engines at load 1.0) where both policies are in queue-explosion
    territory and p99 is backlog noise."""
    failures = []
    by_key = {(r["layer"], r["scenario"], r["engines"], r["load"],
               r["policy"]): r for r in rows}
    for (layer, scenario, m, load, pol), r in by_key.items():
        if pol != "sfs-aware" or load < 0.8:
            continue
        h = by_key[(layer, scenario, m, load, "hash")]
        skey = SHORT_LABEL if layer == "tick-engine" else SHORT_LABEL_S
        sfs_p99 = r["buckets"][skey]["p99"]
        hash_p99 = h["buckets"][skey]["p99"]
        ok = sfs_p99 <= hash_p99 * 1.05
        print(f"[{layer} {scenario} m={m} load={load}] sfs-aware short "
              f"p99 {sfs_p99:.2f} vs hash {hash_p99:.2f} -> "
              f"{'OK' if ok else 'FAIL'}")
        if not ok:
            failures.append((layer, scenario, m, load))
    if failures:
        print("headline check failures:", failures)
        return 1 if hard else 0
    print("cluster sweep: all headline checks passed")
    return 0


def run_fleet1024(n: int) -> list:
    """1024 engines x 8 lanes at load 0.9 through ``engine="jax"`` —
    sfs-aware vs hash, ``n`` requests each (1M total at the default).
    8 lanes rather than 4: doubling lane capacity halves the tick span
    for the same request count, which is what keeps the pair inside the
    invocation's <60 s budget on one core."""
    from repro.kernels.group_pick import pick_impl
    servers = uniform_servers(1024, 8)
    rows = []
    print(f"tick-engine FLEET1024 (jax backend): engines=1024 lanes=8 "
          f"load=0.9 n={n} pick={pick_impl()}")
    for pol in ("sfs-aware", "hash"):
        r = run_tick(pol, servers, 0.9, n=n, seed=11,
                     scenario="fleet1024", backend="jax")
        rows.append(r)
        print_row(r, SHORT_LABEL)
    return rows


def run_elastic(n: int) -> list:
    """``--elastic``: the production-realism scenario (docs/CLUSTER.md
    "Production realism") — 16 engines x 4 lanes through the vector
    backend with the full lifecycle stack on: Zipf function popularity
    feeding per-function cold starts under keep-alive/cap, a flash
    crowd compressing the middle of the arrival stream 2x, one server
    failing (drain + requeue) after the crowd passes, and an autoscaler
    growing the active set from ``min=12`` into the spike and shrinking
    back out of it.  sfs-aware vs hash, loads 0.6 / 0.8; its rows join
    the gated BENCH_cluster.json family and the headline check applies
    at 0.8 — short P99 must survive elasticity, not just the steady
    state.  The failure lands after the flash drains: a server loss
    *inside* a 2x crowd puts the 0.8 cell in queue-explosion territory
    where p99 is backlog noise for both policies (same reason the full
    sweep's 2-engine load-1.0 cells are not hard-gated)."""
    servers = uniform_servers(16, 4)
    rows = []
    for load in (0.6, 0.8):
        wl = (f"bimodal:n={n},seed=7,load={load}|zipf:funcs=16,s=1.1"
              f"|flash:at=1000,x=2,dur=1000")
        print(f"tick-engine ELASTIC (vector backend): engines=16 lanes=4 "
              f"load={load} n={n}")
        for pol in ("sfs-aware", "hash"):
            r = run_tick(
                pol, servers, load, n=n, seed=7, scenario="elastic",
                backend="vector", workload=wl,
                lifecycle="lifecycle:cold=2,ttl=400,cap=8,"
                          "fail=2600,fail_server=3",
                scaling="scale:min=12,T=25,up=0.6,down=0.15,step=2")
            rows.append(r)
            print_row(r, SHORT_LABEL)
    return rows


def run_chaos(n: int) -> list:
    """``--chaos``: the graceful-degradation scenario (docs/CLUSTER.md
    "Chaos and graceful degradation") — 16 engines x 4 lanes through
    the vector backend under the full chaos stack: Zipf popularity
    feeding keep-alive cold starts, correlated failure episodes (blast
    radius 4) with recovery re-entering dispatch cold, per-request
    timeouts retried with exponential backoff under a budget, and an
    admission watermark shedding arrivals when outstanding work per
    lane crosses it.  sfs-aware vs hash, loads 0.6 / 0.8; rows join the
    gated BENCH_cluster.json family and the headline check applies at
    0.8 — short P99 must survive faults, not just steady state.  The
    two loads pin the two regimes: at 0.6 the fleet absorbs a blast-4
    outage outright (zero shed, no timeouts), while at 0.8 the same
    outage forces degradation — requests time out, retry, and shed —
    and the policy under test decides whether short functions drown
    in the backlog (hash) or stay protected (sfs-aware).  Shed
    requests are excluded from the completion percentiles and reported
    as their own ``shed`` column (a metric, never row identity — the
    gate in check_regression.py treats it like wall_s)."""
    servers = uniform_servers(16, 4)
    rows = []
    for load in (0.6, 0.8):
        wl = f"bimodal:n={n},seed=7,load={load}|zipf:funcs=16,s=1.1"
        print(f"tick-engine CHAOS (vector backend): engines=16 lanes=4 "
              f"load={load} n={n}")
        for pol in ("sfs-aware", "hash"):
            r = run_tick(
                pol, servers, load, n=n, seed=7, scenario="chaos",
                backend="vector", workload=wl,
                lifecycle="lifecycle:cold=2,ttl=400,cap=8",
                faults="faults:mttf=1200,mttr=250,blast=4,episodes=3,"
                       "seed=13,first=800",
                retry="retry:timeout=400,retries=2,backoff=16,shed=10")
            rows.append(r)
            print_row(r, SHORT_LABEL)
            print(f"    shed={r['shed']}")
    return rows


def run_trace_demo(out_path: str, n: int) -> int:
    """``--trace``: render one sfs-aware-vs-hash lifecycle trace of the
    fleet64 smoke scenario (64 engines x 4 lanes, vector backend, load
    1.0) as a Chrome-trace JSON loadable in Perfetto / chrome://tracing.
    Each policy becomes its own process row (``make trace-demo``)."""
    from repro.core.telemetry import Telemetry, save_chrome_trace
    servers = uniform_servers(64, 4)
    traces = {}
    for pol in ("sfs-aware", "hash"):
        spec = ExperimentSpec(
            engine="vector", servers=servers, dispatch=pol,
            workload=TickWorkloadSpec(n=n, load=1.0, seed=7))
        tel = Telemetry(trace=True, series_cadence=100)
        res = run_experiment(spec, max_ticks=50_000_000, telemetry=tel)
        traces[pol] = tel.trace
        print(f"  {pol:12s} events={len(tel.trace):7d} "
              f"digest={tel.trace.digest()[:16]} wall={res.wall_s:.1f}s")
    save_chrome_trace(out_path, traces)
    print("wrote", out_path)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI config: <60 s, asserts the headline claims")
    ap.add_argument("--des", action="store_true",
                    help="also sweep the discrete-event multi-server sim")
    ap.add_argument("--fleet1024", action="store_true",
                    help="run ONLY the 1024-engine jax-backend scenario "
                         "(own <60 s budget; asserts its headline claim)")
    ap.add_argument("--elastic", action="store_true",
                    help="run ONLY the lifecycle scenario (cold starts + "
                         "flash crowd + failure + autoscaling; own <60 s "
                         "budget; asserts its headline claim)")
    ap.add_argument("--chaos", action="store_true",
                    help="run ONLY the chaos scenario (correlated fault "
                         "episodes with recovery + timeouts/retries + "
                         "shedding; own <60 s budget; asserts its "
                         "headline claim)")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="write ONE sfs-aware-vs-hash Perfetto trace of "
                         "the fleet64 smoke scenario and exit")
    ap.add_argument("--n", type=int, default=None, help="requests per run")
    # parse_known_args: tolerate suite names when driven by benchmarks.run
    args, _ = ap.parse_known_args(argv)
    enable_compile_cache()

    if args.trace:
        return run_trace_demo(args.trace, args.n or 10_000)

    if args.fleet1024:
        rows = run_fleet1024(args.n or 500_000)
        path = save("cluster_fleet1024", {"rows": rows})
        print("saved", path)
        return check_headline(rows, hard=True)

    if args.elastic:
        rows = run_elastic(args.n or 20_000)
        path = save("cluster_elastic", {"rows": rows})
        print("saved", path)
        return check_headline(rows, hard=True)

    if args.chaos:
        rows = run_chaos(args.n or 20_000)
        path = save("cluster_chaos", {"rows": rows})
        print("saved", path)
        return check_headline(rows, hard=True)

    if args.smoke:
        engine_counts, loads = [4], [0.8, 1.0]
        n_tick, n_des, lanes = args.n or 1000, args.n or 2000, 4
        n_fleet = args.n or 40_000
    else:
        engine_counts, loads = [2, 4, 8], [0.6, 0.8, 1.0]
        n_tick, n_des, lanes = args.n or 3000, args.n or 4000, 4
        n_fleet = args.n or 64_000

    rows = []
    for m in engine_counts:
        for load in loads:
            print(f"tick-engine cluster: engines={m} lanes={lanes} "
                  f"load={load}")
            for pol in POLICIES:
                r = run_tick(pol, uniform_servers(m, lanes), load,
                             n=n_tick, seed=7)
                rows.append(r)
                print_row(r, SHORT_LABEL)
    if args.des or args.smoke:
        for m in engine_counts:
            for load in loads:
                print(f"DES cluster: servers={m} cores={lanes} load={load}")
                for pol in POLICIES:
                    r = run_des(pol, uniform_servers(m, lanes), load,
                                n=n_des)
                    rows.append(r)
                    print_row(r, SHORT_LABEL_S)

    # mixed-pool scenario: heterogeneous shapes, declared purely via spec
    mixed_loads = [0.8, 1.0] if args.smoke else loads
    for load in mixed_loads:
        print(f"tick-engine MIXED pool (6+6 sfs / 2+2 cfs): load={load}")
        for pol in POLICIES:
            r = run_tick(pol, MIXED_SERVERS, load, n=n_tick,
                         seed=7, scenario="mixed")
            rows.append(r)
            print_row(r, SHORT_LABEL)
    if args.des or args.smoke:
        for load in mixed_loads:
            print(f"DES MIXED pool (6+6 sfs / 2+2 cfs): load={load}")
            for pol in POLICIES:
                r = run_des(pol, MIXED_SERVERS, load, n=n_des,
                            scenario="mixed")
                rows.append(r)
                print_row(r, SHORT_LABEL_S)

    # fleet scenario: 64 engines through the vectorized stepping backend
    # (the object path pays O(engines) Python per tick plus O(engines)
    # dispatch scans per arrival and cannot cover this grid in smoke
    # time; the vector backend is bit-exact with it, pinned in
    # tests/test_agreement.py)
    fleet_servers = uniform_servers(64, lanes)
    fleet_loads = [0.8, 1.0] if args.smoke else [0.6, 0.8, 1.0]
    for load in fleet_loads:
        print(f"tick-engine FLEET (vector backend): engines=64 "
              f"lanes={lanes} load={load} n={n_fleet}")
        for pol in ("sfs-aware", "hash", "least-outstanding"):
            r = run_tick(pol, fleet_servers, load, n=n_fleet, seed=7,
                         scenario="fleet64", backend="vector")
            rows.append(r)
            print_row(r, SHORT_LABEL)

    path = save("cluster_sweep", {"rows": rows})
    print("saved", path)

    return check_headline(rows, hard=args.smoke)


if __name__ == "__main__":
    sys.exit(main())
