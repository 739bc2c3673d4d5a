"""Device time of the XLA modules whose name matches ``args["pattern"]``,
from the traced window: ``"value": "share_of_busy"`` (per cent of the
device's busy time) or ``"median_ms"`` (median duration of one run). No
trace, or no module of that name in it, returns nothing."""

from __future__ import annotations

import numpy as np

from benchmarks import reduce


def read(result: dict, args: dict):
    trace = result.get("trace")
    if trace is None:
        return None
    try:
        runs = reduce.module_durations(trace, args["pattern"])
    except ValueError:  # no device operation at all: nothing to read
        return None
    if not runs:
        return None
    if args["value"] == "median_ms":
        return float(np.median(runs)) * 1e3
    if args["value"] == "share_of_busy":
        chips = reduce.busy(trace)
        return 100.0 * sum(runs) / (chips["busy_s"] * chips["chips"])
    raise SystemExit(f"trace_modules: unknown value {args['value']!r}")
