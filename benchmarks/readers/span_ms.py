"""Median length in ms of the host annotations named ``args["span"]`` that
lie inside the traced window (any thread). No trace, no device plane (a
phase of the loop that drives a device means nothing where none ran) or no
such annotation: nothing."""

from __future__ import annotations

import numpy as np

from benchmarks import reduce


def read(result: dict, args: dict):
    trace = result.get("trace")
    if trace is None or next(reduce._lines(
            trace, reduce.DEVICE_PLANE, reduce.OPS_LINE), None) is None:
        return None
    lo, hi = reduce.window(trace)
    runs = [end - start
            for name, start, end in reduce.annotations(trace, args["span"])
            if name == args["span"] and start >= lo and end <= hi]
    if not runs:
        return None
    return float(np.median(runs)) / 1e6
