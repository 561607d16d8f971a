#!/usr/bin/env python3
"""The benchmark's command: one run of one cell, from the checkout's root.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cells, metrics and bounds are in ``BENCHMARK.json``; ``harness.py``
says what a run does.  The last line of stdout is the result as one JSON
object; the numbers compared with the reference are the last lines of
stderr.  Exits 2, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.

JAX's persistent compilation cache is kept at ``<checkout>/.jax_cache``,
a fixed path inside the checkout, whatever the environment says: the
program's own cache helper takes the directory from
``JAX_COMPILATION_CACHE_DIR``, and so does JAX, at import.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from perfbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
