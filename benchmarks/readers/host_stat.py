"""A number the runner took with its own clock or read from a handle:
``args = {"stat": key of result["stats"], "reduce": "value" | "mean" |
"median" | "p<NN>"}``. Nothing to read (no such key, an empty list) returns
nothing."""

from __future__ import annotations

import numpy as np

from benchmarks import traffic


def read(result: dict, args: dict):
    values = result["stats"].get(args["stat"])
    how = args.get("reduce", "value")
    if values is None or (how != "value" and len(values) == 0):
        return None
    if how == "value":
        return float(values)
    if how == "mean":
        return float(np.mean(values))
    if how == "median":
        return float(np.median(values))
    if how.startswith("p"):
        p = float(how[1:])
        print(f"[bench] {args['stat']} {how}: {len(values)} samples "
              f"(ten beyond the percentile need {traffic.samples_needed(p)})",
              flush=True)
        return traffic.percentile(values, p)
    raise SystemExit(f"host_stat: unknown reduce {how!r}")
