"""Whether the timed calls answered right: the comparison's common parts.

After the window, each kind of call (``kinds/<kind>.py``) works a sample
of the window's calls, drawn from the seed, again with the plain
reference in ``reference/`` (float64, the planner's own random numbers)
and gives its numbers; here each is set beside its limit from
``limits/<cell>.json``.

The control is the reference computed in bfloat16, the nearest precision
below the configurations' float32; the tests and ``tools/limits.py``
show that it fails.
"""

from __future__ import annotations

import json
import pathlib

import ml_dtypes
import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
# the nearest precision below the configurations' float32
CONTROL_DTYPE = ml_dtypes.bfloat16


def max_rel(a, b) -> float:
    """Largest |a - b| / |b|; inf where only one of the two is finite."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    m = np.isfinite(b)
    if (np.isfinite(a) != m).any():
        return float("inf")
    if not m.any():
        return 0.0
    return float(np.max(np.abs(a[m] - b[m])
                        / np.maximum(np.abs(b[m]), 1e-30)))


def limits(cell: str) -> dict:
    return json.loads((HERE / "limits" / f"{cell}.json").read_text())


def judge(numbers: dict, lims: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}); a missing limit fails."""
    table = {k: {"value": v, "limit": lims.get(k)} for k, v in
             numbers.items()}
    ok = all(t["limit"] is not None and np.isfinite(t["value"])
             and t["value"] <= t["limit"] for t in table.values())
    return ok, table
