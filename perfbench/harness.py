"""One run of one benchmark cell (see ``run.py`` for the command line).

A cell names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); ``BENCHMARK.json`` lists the cells and the
metrics, and each per-layer metric is read by ``metrics/<name>.py``.
A configuration names its plain reference (``references/<name>.py``)
and may carry ``fleet`` knobs, which the program's spec grammar parses
and the reference has to model; a mix may list traffic stages
(``traffic.py``).  Nothing here knows a cell, a mix, a reference or a
metric by name.

A run:

0. refusal — a configuration or mix with a key that no code reads, a
   reference or stage that does not exist, a scheduler, dispatch or
   predictor that the reference does not simulate, a ``fleet`` knob
   that the reference does not model or that the program's grammar
   rejects: exit 2 with no result, before anything is run;
1. set-up — finds the chips (none: exit 2, no result), builds the spec,
   and runs warm-up experiments of the cell's own configuration and
   traffic (the mix's ``warmup`` gives their ``n``; their seeds lie
   outside the timed ones) until one of them builds no program that the
   ones before it had not, so that every program the window needs is
   compiled or loaded from the persistent cache.  The program grows its
   device regions, and re-jits, as deep as an experiment's queues go;
   the warm-up does not guess how deep that is, it looks;
2. window — whole experiments back to back, each on a request list
   drawn off the clock from ``(seed, i)``, until ``seconds`` have passed;
   the experiment running at the deadline is finished and counted.
   ``--trace 1`` attaches a profile-only telemetry session to every
   experiment and takes a device profile of the first;
3. check — once the window has closed and peak memory is read, one
   experiment drawn from the seed is recomputed by the configuration's
   plain reference and compared (``compare.py``).
"""
from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

from perfbench import compare, lookup, system, trace_reduce, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WARMUP_SEED = 20_220_904
WARMUP_MAX = 5              # warm-up experiments at most
DRAIN_TICKS = 20_000        # ticks past the last arrival before giving up
# JAX's compile events: tracing, lowering (once for every program built
# anew, whether the backend compiles it or the persistent cache loads
# it) and the backend step, which wraps the cache read on a hit
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_SPANS = ("/jax/core/compile/jaxpr_trace_duration", _LOWER,
          "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


# configuration keys that code reads (``control``: ``control.py``)
CONFIG_KEYS = {"servers", "cores", "slots", "scheduler", "dispatch",
               "predictor", "reference"}
CONFIG_OPTIONAL = {"fleet", "name"}


def config(name: str) -> dict:
    """The configuration file ``configs/<name>.json``, as written
    (:func:`check_config` refuses what nothing reads)."""
    cfg = load_json(os.path.join(HERE, "configs", name + ".json"))
    cfg["name"] = name
    return cfg


def reference(cfg: dict):
    """The configuration's plain reference module."""
    return lookup.module("references", cfg["reference"])


def check_config(cfg: dict):
    """Refuses (``ValueError``) a configuration with a key that no code
    reads, a scheduler, dispatch or predictor that its reference does
    not simulate, a control its reference does not have, or a ``fleet``
    knob that its reference does not model; returns the reference
    module."""
    lookup.check_keys("configuration", cfg, CONFIG_KEYS, CONFIG_OPTIONAL)
    system.check_fleet(cfg.get("fleet", {}))
    ref = reference(cfg)
    for key, values in ref.MODELS.items():
        if cfg[key] not in values:
            raise ValueError(f"reference {cfg['reference']!r} simulates "
                             f"{key} {values}, not {cfg[key]!r}")
    if "control" in cfg and cfg["control"] not in ref.CONTROLS:
        raise ValueError(f"reference {cfg['reference']!r} has no control "
                         f"{cfg['control']!r}; it has {ref.CONTROLS}")
    blind = sorted(set(cfg.get("fleet", {})) - set(ref.KNOBS))
    if blind:
        raise ValueError(f"reference {cfg['reference']!r} does not model "
                         f"the fleet knob(s) {blind}; it models "
                         f"{ref.KNOBS or 'none'}")
    return ref


def experiment_seed(seed: int, i: int) -> int:
    """Workload seed of experiment ``i`` of a run with ``--seed seed``."""
    return int(np.random.SeedSequence([seed % 2**64, i]).generate_state(
        1)[0])


class CompileLog:
    """Programs built, and the seconds spent tracing, lowering,
    compiling or loading them, in each phase of the run, from JAX's
    monitoring events.  A program counts when it is lowered: the backend
    then compiles it or the persistent cache loads it."""

    def __init__(self):
        self.phase = "setup"
        self.count = {"setup": 0, "window": 0, "check": 0}
        self.seconds = {"setup": 0.0, "window": 0.0, "check": 0.0}

    def __call__(self, event, duration, **_):
        if event in _SPANS:
            self.seconds[self.phase] += duration
        if event == _LOWER:
            self.count[self.phase] += 1


def find_chips(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s) "
                     f"({devs[0].device_kind})")
    return devs[:chips]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def readers(bench: dict, cell: dict, trace: bool) -> list:
    """The metrics this run reports: the cell's end-to-end metrics
    untraced, its per-layer metrics traced."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def read_per_layer(metrics: list, ctx: dict) -> dict:
    out = {}
    for m in metrics:
        mod = importlib.import_module(f"perfbench.metrics.{m['name']}")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def _profile_options():
    import jax
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0        # every Python call would be an event
    po.host_tracer_level = 2
    return po


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, cfg: dict = None,
        mix: dict = None, system_run=None,
        out=None, err=None) -> int:
    """One run; prints the result line and returns the exit code.
    ``cfg``, ``mix`` and ``system_run`` replace the cell's configuration,
    mix and program entry (tests run the harness at a small size and
    with faults planted underneath)."""
    out = out or sys.stdout
    err = err or sys.stderr
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = entry(bench["workloads"], workload, "workload")
    try:
        cfg = cfg or config(cell["config"])
        mix = mix or traffic.load(cell["traffic"])
        ref = check_config(cfg)
        traffic.check(mix)
        spec = system.spec(cfg)
    except (ValueError, TypeError) as e:
        print(f"no result: refused: {e}", file=err)
        return 2
    system_run = system_run or system.run
    import jax
    if require_chip:
        try:
            devs = find_chips(int(cell["chips"]))
        except NoChip as e:
            print(f"no result: {e}", file=err)
            return 2
    else:
        devs = jax.devices()[:1]
    peaks = None
    if trace and require_chip:
        from perfbench import peaks as peak_table
        peaks = peak_table.lookup(devs[0].device_kind)
    # every program goes to the persistent cache, however fast it built
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    try:
        return _run(cell, cfg, mix, spec, ref, seed, seconds, trace,
                    t_start, devs, peaks, bench, log, system_run, out, err)
    finally:
        jax.monitoring.unregister_event_duration_listener(log)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_s)


def _run(cell, cfg, mix, spec, ref_module, seed, seconds, trace, t_start,
         devs, peaks, bench, log, system_run, out, err) -> int:
    import jax
    lanes = int(cfg["servers"]) * int(cfg["cores"])

    # -- set-up: warm-up experiments of this cell's own traffic, until
    # one builds no new program -------------------------------------
    failure = None
    warmups = []
    for k in range(WARMUP_MAX):
        before = log.count["setup"]
        wd = traffic.draw(mix, experiment_seed(WARMUP_SEED, k), lanes,
                          n=mix["warmup"]["n"])
        try:
            system_run(spec, traffic.requests(wd),
                       max_ticks=int(wd["arrival"].max()) + DRAIN_TICKS)
        except Exception:           # the program failed: report, no window
            failure = traceback.format_exc()
            break
        finally:
            del wd
        warmups.append(log.count["setup"] - before)
        if warmups[-1] == 0:
            break
    gc.collect()

    # -- window ------------------------------------------------------
    exps = []
    setup_s = None
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    t_window = time.perf_counter()
    deadline = t_window + seconds
    try:
        while failure is None:
            i = len(exps)
            s_i = experiment_seed(seed, i)
            d = traffic.draw(mix, s_i, lanes)
            reqs = traffic.requests(d)
            n = len(reqs)
            max_ticks = int(d["arrival"].max()) + DRAIN_TICKS
            del d
            tel = system.profile_session() if trace else None
            profiled = trace and i == 0
            if setup_s is None:
                setup_s = time.perf_counter() - t_start
            if profiled:
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=_profile_options())
            log.phase = "window"
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(
                        trace_reduce.WINDOW_SPAN if profiled
                        else "perfbench.experiment"):
                    res = system_run(spec, reqs, tel, max_ticks=max_ticks)
            except Exception:       # the program failed: report, stop
                failure = traceback.format_exc()
                exps.append({"seed": s_i, "n": n, "completed": 0,
                             "seconds": time.perf_counter() - t0,
                             "failed": True})
                break
            finally:
                dt = time.perf_counter() - t0
                if profiled:
                    jax.profiler.stop_trace()
            exps.append({"seed": s_i, "n": n, "completed": int(res.n),
                         "seconds": dt, "failed": False,
                         "ticks": int(res.finish.max()) if res.n else 0,
                         "phases": system.phases(tel),
                         "device_profiled": profiled,
                         "answers": compare.answers(res)})
            del res, reqs, tel
            if time.perf_counter() >= deadline:
                break
    finally:
        log.phase = "check"
    t_window_end = time.perf_counter()
    dev = device_info(devs)
    gc.collect()

    reduced = None
    if trace and exps and not exps[0]["failed"]:
        reduced = trace_reduce.reduce_file(trace_reduce.find_xplane(trace_dir))
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)

    # -- check: one experiment of the window against the reference ------
    t_check = time.perf_counter()
    if failure is None:
        k = int(np.random.default_rng([seed % 2**64, 1]).integers(len(exps)))
        d = traffic.draw(mix, exps[k]["seed"], lanes)
        ref = ref_module.simulate(d, cfg)
        checks = compare.compare(exps[k]["answers"], ref)
        correct = compare.passed(checks)
        del d, ref
    else:
        print(failure, file=err)
        checks = {name: {"value": None, "limit": lim}
                  for name, lim in compare.LIMITS.items()}
        correct = False

    # -- result ------------------------------------------------------
    attempted = sum(e["n"] for e in exps)
    completed = sum(e["completed"] for e in exps)
    for i, e in enumerate(exps):
        ph = e.get("phases") or {}
        print(f"experiment {i}: seed={e['seed']} n={e['n']} "
              f"completed={e['completed']} seconds={e['seconds']!r} "
              f"ticks={e.get('ticks')} "
              + " ".join(f"{k}={v[1]}x{v[0]!r}s" for k, v in
                         sorted(ph.items())), file=err)
    print(f"window_wall_s={t_window_end - t_window!r} "
          f"check_s={time.perf_counter() - t_check!r}", file=err)
    print(f"warm-up programs built, by experiment: {warmups}", file=err)
    print(f"setup_s={setup_s!r} compiles={log.count} "
          f"compile_s={log.seconds} memory_peak_bytes="
          f"{dev['memory_peak_bytes']}", file=err)
    ok = [e for e in exps if not e["failed"]]
    if trace:
        host = [e for e in ok if not e["device_profiled"]] or ok
        ctx = {"cell": cell, "config": cfg, "mix": mix, "experiments": ok,
               "host_experiments": host, "setup_s": setup_s,
               "compiles": {"count": dict(log.count),
                            "seconds": dict(log.seconds)},
               "trace": reduced, "peaks": peaks}
        metrics = read_per_layer(readers(bench, cell, True), ctx)
        if reduced is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
    else:
        metrics = {}
        call_s = sum(e["seconds"] for e in ok)
        for m in readers(bench, cell, False):
            if m["name"] == "setup_s" and setup_s is not None:
                metrics["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            elif m["name"] == "sim_requests_per_s" and call_s > 0:
                metrics[m["name"]] = {"value": completed / call_s,
                                      "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": attempted - completed, "metrics": metrics,
              "device": dev}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
    return 0


def main(argv, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    return run(a.workload, a.seed, a.seconds, bool(a.trace),
               t_start=t_start)
