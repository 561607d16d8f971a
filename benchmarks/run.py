"""Benchmark aggregator: one harness per paper table/figure + the serving
engine e2e + the roofline table (from dry-run artifacts, if present).

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig2 fig6  # subset
  PYTHONPATH=src python -m benchmarks.run --smoke cluster predict
  REPRO_BENCH_N=49712 ... runs at the paper's request count.

Exit status is non-zero when any suite raises or returns a failing
return code, so CI can catch benchmark regressions.  ``--smoke`` is
passed through to suites that take CLI args (cluster, predict).

``--json`` additionally distills each suite's artifact into a
machine-readable ``BENCH_<suite>.json`` in the working directory
(wall-clock + headline short/long P99 per scenario row) — the perf
trajectory CI uploads as build artifacts and gates against the
checked-in ``benchmarks/baselines/`` via ``check_regression.py``.
"""
from __future__ import annotations

import json
import os
import sys
import time

from benchmarks import (cluster_sweep, fig1_duration_cdf, fig2_policies,
                        fig6_7_load_sweep, fig9_10_timeslice, fig11_io,
                        fig12_overload, predict_sweep, roofline,
                        serving_e2e, table2_overhead)
from benchmarks.common import OUT_DIR
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "fig1": fig1_duration_cdf,
    "fig2": fig2_policies,
    "fig6": fig6_7_load_sweep,
    "fig9": fig9_10_timeslice,
    "fig11": fig11_io,
    "fig12": fig12_overload,
    "table2": table2_overhead,
    "serving": serving_e2e,
    "roofline": roofline,
    "fleet1024": cluster_sweep,     # before "cluster": their artifacts
    "elastic": cluster_sweep,       # must be fresh when cluster distills
    "chaos": cluster_sweep,
    "cluster": cluster_sweep,
    "predict": predict_sweep,
}


# suites whose main(argv) takes CLI flags (--smoke pass-through)
ARGV_SUITES = {"cluster", "fleet1024", "elastic", "chaos", "predict"}

# per-suite forced flags: "fleet1024" / "elastic" / "chaos" are
# cluster_sweep's standalone invocations (each with its own <60 s
# budget) — the 1024-engine jax-backend fleet, the lifecycle scenario,
# and the fault/timeout/shedding scenario
SUITE_FLAGS = {"fleet1024": ["--fleet1024"], "elastic": ["--elastic"],
               "chaos": ["--chaos"]}

# --json distillation: suite -> (artifact names, row key fields).  "n"
# is part of a row's identity: smoke and full runs sweep the same cells
# at different request counts, and the gate must never compare (or pin)
# one against the other silently.  "cluster" distills from two
# artifacts — the main sweep plus the standalone fleet1024 invocation —
# so both land in the one gated BENCH_cluster.json; run the fleet1024
# suite FIRST so its artifact is fresh when cluster distills (a missing
# artifact is skipped here and surfaces as dropped baseline rows in the
# gate).
BENCH_JSON = {
    "cluster": (("cluster_sweep", "cluster_fleet1024", "cluster_elastic",
                 "cluster_chaos"),
                ("layer", "scenario", "backend", "policy",
                 "engines", "load", "n")),
    "predict": (("predict_sweep",), ("predictor", "dispatch", "load", "iat",
                                     "hinted_demotion", "n")),
}


def write_bench_json(name: str, out_dir: str = ".") -> str:
    """Distill a suite's saved artifact into BENCH_<name>.json: one flat
    row per sweep cell (identity keys + short/long P99 + wall-clock),
    stable enough to diff across commits and gate in CI."""
    artifacts, key_fields = BENCH_JSON[name]
    rows = []
    for artifact in artifacts:
        path = os.path.join(OUT_DIR, artifact + ".json")
        if not os.path.exists(path):
            print(f"  note: artifact {artifact}.json not found, skipping "
                  "(its baseline rows will show as dropped in the gate)")
            continue
        with open(path) as f:
            data = json.load(f)
        for r in data["rows"]:
            buckets = r["buckets"]
            keys = list(buckets)
            row = {k: r[k] for k in key_fields if k in r}
            row["short_p99"] = buckets[keys[0]]["p99"]
            row["long_p99"] = buckets[keys[-1]]["p99"]
            row["wall_s"] = r["wall_s"]
            # run provenance (spec JSON + seed + result fingerprint) and
            # host-path phase breakdown ride along as non-identity
            # metadata — check_regression warns on provenance drift but
            # never keys or fails on either (docs/OBSERVABILITY.md)
            if "provenance" in r:
                row["provenance"] = r["provenance"]
            if "phases" in r:
                row["phases"] = r["phases"]
            # chaos rows: shed requests are excluded from the
            # percentiles above, so carry the count as its own metric
            if "shed" in r:
                row["shed"] = r["shed"]
            rows.append(row)
    payload = {
        "suite": name,
        "n_rows": len(rows),
        "total_wall_s": round(sum(r["wall_s"] for r in rows), 3),
        "rows": rows,
    }
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def _run_suite(name: str, mod, flags: list) -> int:
    argv = SUITE_FLAGS.get(name, []) + (flags if name in ARGV_SUITES else [])
    rc = mod.main(argv) if argv else mod.main()
    # some suites return their result dict (fig1) rather than an exit
    # code; only an int counts as a failing/passing status
    return rc if isinstance(rc, int) else 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    enable_compile_cache()
    flags = [a for a in argv if a.startswith("-")]
    json_mode = "--json" in flags
    flags = [f for f in flags if f != "--json"]
    names = [a for a in argv if not a.startswith("-")] or list(SUITES)
    if "-h" in flags or "--help" in flags:
        print(__doc__)
        print("suites:", ", ".join(SUITES))
        return 0
    unknown = [n for n in names if n not in SUITES]
    if unknown:
        print(f"unknown suite(s): {', '.join(unknown)}; "
              f"valid: {', '.join(SUITES)}")
        print("(flags that take a value, e.g. --n 500, are not supported "
              "here — use REPRO_BENCH_N or run the suite directly)")
        return 1
    failures = []
    for name in names:
        mod = SUITES[name]
        print(f"\n===== {name}: {mod.__doc__.splitlines()[0]}")
        t0 = time.time()
        rc = None
        try:
            rc = _run_suite(name, mod, flags)
        except SystemExit as e:      # argparse exits (e.g. --help) must
            rc = (e.code if isinstance(e.code, int)   # not abort the rest;
                  else 0 if e.code is None else 1)    # sys.exit("msg") == 1
        except Exception as e:                     # keep the run going
            print(f"  !! {name} failed: {e!r}")
            failures.append(name)
        if rc not in (None, 0):
            print(f"  !! {name} exited {rc}")
            failures.append(name)
        if json_mode and name in BENCH_JSON and name not in failures:
            print("  bench json:", write_bench_json(name))
        print(f"  ({time.time() - t0:.1f}s)")
    if failures:
        print(f"\n{len(failures)}/{len(names)} suite(s) failed: "
              + ", ".join(failures))
        return 1
    print(f"\nall {len(names)} suite(s) passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
