"""Share of the traced window in which no operation ran on the device."""

from __future__ import annotations

from benchmarks import reduce


def read(result: dict, args: dict):
    trace = result.get("trace")
    if trace is None:
        return None
    try:
        b = reduce.busy(trace)
    except ValueError:  # no device plane: nothing to read
        return None
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])
