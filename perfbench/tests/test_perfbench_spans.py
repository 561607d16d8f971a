"""The readers of the program's experiment-level and device-wait spans
find nothing, and raise nothing, in the phases of a program that writes
no such span."""
import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# phases as a program without the ``build``/``intake``/``result`` and
# ``jax_sync`` spans records them
OLDER = {"route": [0.5, 40], "step": [0.9, 40], "jax_step": [0.6, 40],
         "jax_events": [0.1, 30], "jax_writeback": [0.05, 1]}


@pytest.mark.parametrize("metric", ["entry_s", "step_sync_ms_per_tick"])
def test_span_reader_finds_nothing_in_an_older_program(metric):
    mod = importlib.import_module(f"perfbench.metrics.{metric}")
    e = {"n": 1000, "ticks": 50, "phases": OLDER}
    assert mod.read({"experiments": [e], "host_experiments": [e]}) is None
    assert mod.read({"experiments": [], "host_experiments": []}) is None
