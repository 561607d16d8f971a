"""The table of device peaks (``peaks.json``), keyed by JAX's
``device_kind``.  A device that is not in the table is an error."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def lookup(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; "
                       f"known: {sorted(table['devices'])}")
    return dict(table["devices"][kind], source=table["source"])
