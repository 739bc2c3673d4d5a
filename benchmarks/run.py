"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` and finds everything else by the names it gives:
the cell's configuration (``configs/``), its traffic mix (``traffic/``),
the runner the mix names (``runners/``) and, after the window, the reader
of every metric that lists the cell (``metrics/`` -> ``readers/``). Prints
earlier lines as it likes and, last, one JSON object.

Never falls back: without a TPU (or with fewer chips than the cell asks
for) it exits non-zero and prints no result. ``main(platform="cpu")`` is
the tests' rehearsal at tiny sizes: the command line cannot reach it, and
it reports nothing that comes from a device trace or a device's peaks.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import common  # noqa: E402


def _metrics(bench: dict, group: str, cell: str) -> list[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def evaluate(root: str, bench: dict, cell: str, traced: bool,
             result: dict) -> dict:
    """Every metric of the cell's group through the reader its file names.
    A reader that finds nothing to read returns nothing, and the metric is
    left out of the line. Off the TPU (the tests' rehearsal) nothing that
    comes from the device trace is read at all."""
    out = {}
    on_chip = result["device"]["platform"] == "tpu"
    for m in _metrics(bench, "per_layer" if traced else "end_to_end", cell):
        if m["source"] == "device_trace" and not on_chip:
            continue
        spec = common.metric_spec(root, m["name"])
        value = common.plugin(root, "readers", spec["reader"]).read(
            result, spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, platform: str = "tpu", root: str = common.ROOT) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    bench, cell, config, mix, runner = common.load_cell(root, args.workload)
    devices = common.start_jax(platform, cell["chips"])

    workdir = os.path.join(root, "_work", "bench", args.workload)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    ctx = common.Context(
        cell=cell, config=config, traffic=mix, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), workdir=workdir,
        t0=_T0, platform=platform)
    ctx.log("start", cell=cell["name"], seed=args.seed,
            device=devices[0].device_kind, count=len(devices))
    result = runner.run(ctx)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": result["memory_peak_bytes"]}
    result["device"] = device
    result["stats"]["setup_s"] = result["setup_s"]
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"]}
    if args.trace and result.get("trace_dir"):
        from benchmarks import reduce

        trace = reduce.load(reduce.find_xplane(result["trace_dir"]))
        result["trace"] = trace
        try:
            device.update({k: v for k, v in reduce.busy(trace).items()
                           if k in ("busy_s", "window_s")})
        except ValueError:
            if platform == "tpu":  # no operation ran on the device
                raise
        line["breakdown"] = {"device_ops": reduce.top_ops(trace),
                             "idle_gaps": reduce.idle_gaps(trace)}
    line["metrics"] = evaluate(root, bench, cell["name"], bool(args.trace), result)
    line["device"] = device
    shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
