#!/usr/bin/env python3
"""Chip smoke test: the 1024-engine jax fleet on one TPU, end to end.

    python3 chip_smoke.py          # from the repo root, on a TPU host

One process, no children.  Phases, in order; the first that fails stops
the run with a non-zero exit and no result line:

1. device — JAX must see a TPU (never falls back to the CPU); prints the
   jax version, device kind and count, and the compile-cache directory
   (``repro.launch.compile_cache``);
2. kernel — ``pick_order_pallas`` on the chip equals ``pick_order_ref``
   on pools heavy in sentinels and ties, at G=1024 and at a G that is
   not a multiple of 8;
3. main path — ``run_experiment`` on the fleet1024 cell of
   ``benchmarks/cluster_sweep.py`` (1024 engines x 8 lanes, load 0.9,
   500k requests, seed 11) for sfs-aware and hash, with ``engine="jax"``
   and then ``engine="vector"`` (host-only numpy); the fingerprints must
   agree with each other and with the pinned ``result_fp`` in
   ``benchmarks/baselines/BENCH_cluster.json``;
4. kernel present — the compiled group step contains
   ``tpu_custom_call``, i.e. the Pallas pick is compiled into it;
5. memory — the device's ``peak_bytes_in_use``.

The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.spec import (ExperimentSpec, ServerSpec,  # noqa: E402
                             TickWorkloadSpec, run_experiment)
from repro.kernels.group_pick import pick_impl, pick_order_ref  # noqa: E402
from repro.kernels.group_pick.kernel import pick_order_pallas  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

BASELINE = os.path.join(ROOT, "benchmarks", "baselines",
                        "BENCH_cluster.json")
# the fleet1024 cell of benchmarks/cluster_sweep.py (run_fleet1024)
FLEET = dict(engines=1024, lanes=8, n=500_000, load=0.9, seed=11)
POLICIES = ("sfs-aware", "hash")
# (G, CAP, kmax): the fleet's shape at every lane count the pick serves,
# and a G the kernel must pad to its 8-row tile
KERNEL_CASES = ((1024, 32, 1), (1024, 32, 4), (1024, 32, 8), (12, 32, 4))
_IMAX = int(np.iinfo(np.int32).max)     # empty pool slot
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/jaxpr_trace_duration")


class PhaseFailed(RuntimeError):
    pass


def log(*args):
    print(*args, flush=True)


def check_device() -> dict:
    """The device JAX uses, as the result line reports it; raises unless
    it is a TPU."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise PhaseFailed(f"no TPU: jax found {d.platform!r} "
                          f"({d.device_kind}); this check never runs on "
                          "the CPU")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def pick_cases(G: int, CAP: int, seed: int = 3):
    """``[G, CAP]`` int32 pool keys heavy in vruntime ties and sentinel
    slots: each row has its own hole share, so some rows run out of
    valid keys before ``kmax`` picks, and row 0 is empty."""
    rng = np.random.default_rng(seed)
    vr = rng.integers(0, 6, (G, CAP)).astype(np.int32)
    rid = rng.permutation(G * CAP).reshape(G, CAP).astype(np.int32)
    hole = rng.random((G, CAP)) < rng.random((G, 1))
    hole[0] = True
    return np.where(hole, _IMAX, vr), np.where(hole, _IMAX, rid)


def check_kernel(cases=KERNEL_CASES, interpret: bool = False):
    for G, CAP, kmax in cases:
        vr, rid = pick_cases(G, CAP)
        want = np.asarray(pick_order_ref(vr, rid, kmax))
        got = np.asarray(pick_order_pallas(vr, rid, kmax,
                                           interpret=interpret))
        bad = int((want != got).any(axis=1).sum())
        log(f"  pick_order_pallas G={G} CAP={CAP} kmax={kmax}: "
            f"{G - bad}/{G} rows equal pick_order_ref")
        if bad:
            raise PhaseFailed(f"kernel differs from reference in {bad} "
                              f"rows at {(G, CAP, kmax)}")


def pinned_fingerprints(path: str = BASELINE) -> dict:
    """``{policy: result_fp}`` of the fleet1024 rows pinned in the
    cluster baseline."""
    with open(path) as f:
        rows = json.load(f)["rows"]
    return {r["policy"]: r["provenance"]["result_fp"] for r in rows
            if r.get("scenario") == "fleet1024" and r["n"] == FLEET["n"]}


def run_cell(engine: str, policy: str, *, engines: int, lanes: int,
             n: int, load: float, seed: int) -> dict:
    """One ``run_experiment`` call; ``compile_s`` is the time JAX spent
    tracing, lowering and compiling inside it (set-up, not steady
    state), ``compiles`` the programs the backend compiled (persistent
    cache hits excluded)."""
    spent = []

    def on_event(event, duration, **_):
        if event in _COMPILE_EVENTS:
            spent.append((event, duration))

    spec = ExperimentSpec(
        engine=engine, servers=tuple(ServerSpec(cores=lanes)
                                     for _ in range(engines)),
        dispatch=policy, workload=TickWorkloadSpec(n=n, load=load,
                                                   seed=seed))
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        res = run_experiment(spec, max_ticks=50_000_000)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    b = res.buckets()
    keys = list(b)
    return {"engine": engine, "policy": policy, "wall_s": res.wall_s,
            "compile_s": sum(d for _, d in spent),
            "compiles": sum(e == _COMPILE_EVENTS[0] for e, _ in spent),
            "short_p99": b[keys[0]]["p99"],
            "long_p99": b[keys[-1]]["p99"], "shed": res.shed,
            "fp": res.fingerprint()[:16]}


def check_main_path(*, engines: int, lanes: int, n: int, load: float,
                    seed: int, pinned: dict = None) -> list:
    """Each policy on the jax backend, then on the vector backend; the
    fingerprints must agree, and match ``pinned`` where it has one."""
    log(f"  fleet {engines}x{lanes} load={load} n={n} seed={seed} "
        f"pick={pick_impl()}")
    rows = [run_cell(engine, pol, engines=engines, lanes=lanes, n=n,
                     load=load, seed=seed)
            for engine in ("jax", "vector") for pol in POLICIES]
    for r in rows:
        log("  " + json.dumps(r))
    for pol in POLICIES:
        fps = {r["engine"]: r["fp"] for r in rows if r["policy"] == pol}
        want = fps["vector"] if pinned is None else pinned.get(pol)
        if not fps["jax"] == fps["vector"] == want:
            raise PhaseFailed(f"{pol}: fingerprints jax={fps['jax']} "
                              f"vector={fps['vector']} pinned={want}")
    return rows


def check_kernel_present(*, engines: int, lanes: int):
    """The fleet's jitted group step, as compiled for this device, must
    contain the Pallas pick."""
    from repro.serving.jax_cluster import _build_fns, step_arg_specs
    qcap, cap, acap = 64, max(32, 2 * lanes), 256   # _JaxGroup's start
    step = _build_fns(engines, lanes, qcap, cap, True)[0]
    t0 = time.perf_counter()
    hlo = step.lower(*step_arg_specs(engines, lanes, qcap, cap,
                                     acap)).compile().as_text()
    found = hlo.count("tpu_custom_call")
    log(f"  group step G={engines} L={lanes} CAP={cap}: "
        f"{found} tpu_custom_call ({time.perf_counter() - t0:.3f}s)")
    if not found:
        raise PhaseFailed("the compiled group step has no "
                          "tpu_custom_call: the Pallas pick is not in it")


def report_memory():
    stats = jax.devices()[0].memory_stats() or {}
    log(f"  peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}"
        f" bytes_limit={stats.get('bytes_limit', 'not reported')}")


def main(argv=None) -> int:
    try:
        device = check_device()
    except PhaseFailed as e:
        print(f"FAIL device: {e}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    log(f"[device] jax {jax.__version__} kind={device['kind']} "
        f"count={device['count']} compile_cache={cache}")
    phases = (("kernel", check_kernel),
              ("main path", lambda: check_main_path(
                  **FLEET, pinned=pinned_fingerprints())),
              ("kernel present", lambda: check_kernel_present(
                  engines=FLEET["engines"], lanes=FLEET["lanes"])),
              ("memory", report_memory))
    for name, fn in phases:
        log(f"[{name}]")
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:     # report which phase failed, then stop
            traceback.print_exc()
            print(f"FAIL {name}", file=sys.stderr)
            return 1
        log(f"[{name}] ok ({time.perf_counter() - t0:.3f}s)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
