"""Roofline shares of what the latent-attention family adds, from
``benchmarks/roofline_latent.py`` (shapes), ``peaks.json`` and device times
of single operations in the trace. Operations are told apart by the text of
their HLO line (``args["op"]``, a regular expression) and by the module run
that holds them (``args["step"]`` and ``args["prefill"]``, patterns on the
modules line): the profiler's operation names are HLO lines, the
``jax.named_scope`` of an operation (mla_decode, moe_gmm) is not in them.
``args["kind"]``:

- ``attention``: the decode step's latent attention. Summed device time of
  the matching operations inside the step module's runs, against the larger
  of bytes/peak and operations/peak of ``latent_decode_attention`` at the
  traced window's mean live rows and positions, once a run.
- ``gmm``: the grouped expert products (three a layer). A product inside a
  decode step is counted at the window's measured ``experts_touched``; one
  inside a prefill at the experts its rows reach under uniform routing;
  rows are read off the operation's own output shape.

No trace, no such module or operation, or a program without the counters:
nothing. None clamps."""

from __future__ import annotations

import re

from benchmarks import reduce, roofline, roofline_latent


def _runs(trace, pattern):
    lo, hi = reduce.window(trace)
    rx = re.compile(pattern)
    return [(s, s + d)
            for _, line in reduce._lines(trace, reduce.DEVICE_PLANE,
                                         reduce.MODULES_LINE)
            for name, s, d in line["events"]
            if rx.search(name) and s >= lo and s + d <= hi]


def _ops_inside(trace, pattern, runs):
    """(name, seconds) of the operations matching ``pattern`` that start
    inside one of ``runs``."""
    rx = re.compile(pattern)
    out = []
    for _, line in reduce._lines(trace, reduce.DEVICE_PLANE, reduce.OPS_LINE):
        for name, s, d in line["events"]:
            if rx.search(name) and any(a <= s < b for a, b in runs):
                out.append((name, d / 1e9))
    return out


def read(result: dict, args: dict):
    trace = result.get("trace")
    shapes = result["shapes"]
    if trace is None or "live_rows" not in shapes:
        return None
    try:
        steps = _runs(trace, args["step"])
    except ValueError:  # no device operation at all
        return None
    if not steps:
        return None
    model = shapes["model"]
    peak = roofline.peaks(result["device"]["kind"])
    if args["kind"] == "attention":
        ops = _ops_inside(trace, args["op"], steps)
        if not ops:
            return None
        least, bound = roofline.roofline_seconds(
            roofline_latent.latent_decode_attention(
                model, shapes["live_rows"], shapes["live_kv_tokens"]), peak)
        total = sum(t for _, t in ops)
        print(f"[bench] latent attention: {len(ops)} operations in "
              f"{len(steps)} steps, {total / len(steps) * 1e3:.3f} ms a step, "
              f"least {least * 1e3:.3f} ms at {shapes['live_rows']:.1f} rows, "
              f"{shapes['live_kv_tokens']:.0f} positions, bound: {bound}",
              flush=True)
        return 100.0 * least * len(steps) / total
    if args["kind"] == "gmm":
        touched = result["stats"].get("experts_touched")
        if touched is None:
            return None
        total_t = total_least = 0.0
        for where, runs, reached in (
                ("decode", steps, lambda rows: touched),
                ("prefill", _runs(trace, args["prefill"]),
                 lambda rows: roofline_latent.expected_experts_touched(
                     model, rows / model["moe_top_k"]))):
            ops = _ops_inside(trace, args["op"], runs)
            t = least_sum = 0.0
            bounds = set()
            for name, seconds in ops:
                rows = int(re.search(args["rows"], name).group(1))
                least, bound = roofline.roofline_seconds(
                    roofline_latent.grouped_product(model, rows, reached(rows)),
                    peak)
                t, least_sum = t + seconds, least_sum + least
                bounds.add(bound)
            if ops:
                print(f"[bench] grouped products in {where}: {len(ops)} in "
                      f"{len(runs)} runs, {t * 1e3:.1f} ms, least "
                      f"{least_sum * 1e3:.1f} ms, bound: {sorted(bounds)}",
                      flush=True)
            total_t, total_least = total_t + t, total_least + least_sum
        return 100.0 * total_least / total_t if total_t else None
    raise SystemExit(f"latent_roofline: unknown kind {args['kind']!r}")
