"""Shares of a peak, from ``benchmarks/roofline.py`` (shapes), ``peaks.json`` and a
time from the trace or the runner's clock. ``args["kind"]``:

- ``decode``: least bytes of one decode step at the traced window's mean
  live rows and K/V positions, over the HBM peak, over the median device
  time of the module matching ``args["pattern"]``;
- ``flash``: the flash forward and backward kernels' (``args["forward"]``,
  ``args["backward"]`` patterns on the operations line;
  ``backward_calls_per_layer`` kernels make one backward) summed device
  time against the larger of operations/peak and bytes/peak, per call;
- ``mfu``: required operations per token x tokens per second (the stat
  ``args["stat"]`` names: the whole-window rate, in a traced run less the
  profiler's own stop) over the bf16 peak.

None clamps: above 100 a count is wrong."""

from __future__ import annotations

import numpy as np

from benchmarks import roofline, reduce


def read(result: dict, args: dict):
    shapes = result["shapes"]
    model = shapes["model"]
    kind = args["kind"]
    if kind == "mfu":
        if result["device"]["platform"] != "tpu":
            return None  # a share of a TPU's peak: nothing to read elsewhere
        rate = result["stats"].get(args["stat"])
        if rate is None:
            return None
        peak = roofline.peaks(result["device"]["kind"])
        flops = roofline.train_flops_per_token(model, shapes["seq"])
        return 100.0 * flops * rate / (
            peak["flops_per_s_bf16"] * result["device"]["count"])
    trace = result.get("trace")
    if trace is None:
        return None
    if kind == "decode":
        runs = reduce.module_durations(trace, args["pattern"])
        if not runs or "live_rows" not in shapes:
            return None
        peak = roofline.peaks(result["device"]["kind"])
        least = roofline.decode_step_min_bytes(
            model, shapes["live_rows"], shapes["live_kv_tokens"])
        print(f"[bench] decode roofline: {least / 1e9:.3f} GB least at "
              f"{shapes['live_rows']:.1f} live rows, "
              f"{shapes['live_kv_tokens']:.0f} live K/V positions; "
              f"bound: memory", flush=True)
        return 100.0 * least / peak["hbm_bytes_per_s"] / float(np.median(runs))
    if kind == "flash":
        # One forward kernel and ``backward_calls_per_layer`` backward
        # kernels make one layer's attention in one step.
        fwd = reduce.op_durations(trace, args["forward"])
        bwd = reduce.op_durations(trace, args["backward"])
        if not fwd or not bwd:
            return None
        peak = roofline.peaks(result["device"]["kind"])
        total_t = total_least = 0.0
        for which, runs, calls, work in (
                ("forward", fwd, len(fwd), roofline.flash_forward),
                ("backward", bwd,
                 len(bwd) / int(args["backward_calls_per_layer"]),
                 roofline.flash_backward)):
            least, bound = roofline.roofline_seconds(
                work(model, shapes["batch"], shapes["seq"]), peak)
            print(f"[bench] flash {which}: {calls:.1f} layer calls, "
                  f"{sum(runs) / calls * 1e3:.3f} ms each, least "
                  f"{least * 1e3:.3f} ms, bound: {bound}", flush=True)
            total_t += sum(runs)
            total_least += least * calls
        return 100.0 * total_least / total_t
    raise SystemExit(f"roofline: unknown kind {kind!r}")
