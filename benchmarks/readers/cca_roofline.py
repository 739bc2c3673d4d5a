"""Roofline shares of what the zaya-like family adds, from
``benchmarks/roofline_cca.py`` (shapes), ``peaks.json`` and device times in
the trace; the trace's modules, scopes and operations are found by
``readers/hybrid_roofline.py``'s own functions (an operation's
``jax.named_scope`` path, read off the ``.xplane.pb``). ``args["kind"]``:

- ``cca``: the compressed-convolutional-attention sublayers inside the decode
  module: summed device time, a step, of the operations traced under the
  scopes ``args["scopes"]`` (the mixing ``cca_mix``, the paged kernel and the
  scatter under ``blk_attn``, ``W_o`` and the scaled residual under
  ``blk_out``), against the larger of bytes/peak and operations/peak of
  ``roofline_cca.cca_step`` at the window's mean live rows and positions.
- ``decode``: least bytes of one whole decode step (that, the router, the
  experts the window's steps touched, the table as head) over the HBM peak,
  over the median device time of the module matching ``args["module"]``: the
  cell's share of the whole step's peak.
- ``expert_ffn``: the top-1 experts' THREE products a layer, in the decode
  module and in the prefill module: the operations under the scope
  ``args["scope"]`` (the batched products at a capacity) and, where a
  slice's rows spill past it, the grouped products, which the compiler
  renames and strips of their path (``args["op"]`` tells them by the text of
  their HLO line; alone that text would miss every batched product). Decode
  against the bytes of the experts the window's steps touched; a slice (its
  length read off the shapes under ``args["slice_scope"]``) against the larger
  of its operations and the bytes of the experts its rows reach under
  uniform routing.

Every kind prints the device time it divided by and its bound. No trace, no
such module, scope or operation, or a cell of another family: nothing. None
clamps."""

from __future__ import annotations

import re

import numpy as np

from benchmarks import roofline, roofline_cca
from benchmarks.readers import hybrid_roofline as found
from benchmarks.readers.kda_roofline import _slice_lengths


def _under(ops, runs, tags, rx=None):
    """Seconds of the operations whose scope path holds one of ``tags`` (or
    whose HLO line matches ``rx``) and that start inside one of ``runs``."""
    starts = np.array([a for a, _ in runs])
    ends = np.array([b for _, b in runs])
    total = 0.0
    for s, seconds, name, path in ops:
        if not (any(t in path for t in tags) or (rx and rx.search(name))):
            continue
        i = int(np.searchsorted(starts, s, side="right")) - 1
        if i >= 0 and s < ends[i]:
            total += seconds
    return total


def read(result: dict, args: dict):
    trace = result.get("trace")
    shapes = result["shapes"]
    model = shapes["model"]
    if trace is None or "live_rows" not in shapes \
            or not result.get("trace_dir") or "router_dim" not in model:
        return None
    try:
        runs = found._runs(trace, args["module"])
    except ValueError:  # no device operation at all
        return None
    if not runs:
        return None
    kind = args["kind"]
    peak = roofline.peaks(result["device"]["kind"])
    rows, positions = shapes["live_rows"], shapes["live_kv_tokens"]
    touched = result["stats"].get("experts_touched")
    if kind == "decode":
        if touched is None:
            return None
        least = roofline_cca.decode_step_min_bytes(
            model, rows, positions, touched)
        median = float(np.median([(b - a) / 1e9 for a, b in runs]))
        print(f"[bench] cca decode roofline: {least / 1e9:.3f} GB least at "
              f"{rows:.1f} live rows, {positions:.0f} positions, "
              f"{touched:.2f} experts touched; {median * 1e3:.3f} ms a step; "
              "bound: memory", flush=True)
        return 100.0 * least / peak["hbm_bytes_per_s"] / median
    ops = found._scoped_ops(result)
    if kind == "cca":
        total = _under(ops, runs, [f"/{s}/" for s in args["scopes"]])
        if not total:
            return None
        least, bound = roofline.roofline_seconds(
            roofline_cca.cca_step(model, rows, positions), peak)
        print(f"[bench] cca sublayers in decode: {len(runs)} steps, "
              f"{total / len(runs) * 1e3:.3f} ms a step, least "
              f"{least * 1e3:.3f} ms at {rows:.1f} rows and {positions:.0f} "
              f"positions, bound: {bound}", flush=True)
        return 100.0 * least * len(runs) / total
    if kind != "expert_ffn":
        raise SystemExit(f"cca_roofline: unknown kind {kind!r}")
    if touched is None:
        return None
    k, layers = model["moe_top_k"], model["n_layers"]
    tags, rx = [f"/{args['scope']}/"], re.compile(args["op"])

    def block(tokens, reached):
        seconds, bound = roofline.roofline_seconds(
            roofline_cca.expert_product(model, tokens * k, reached), peak)
        return 3 * layers * seconds, bound

    t = _under(ops, runs, tags, rx)
    per_step, bound = block(rows, touched)
    least = len(runs) * per_step if t else 0.0
    if t:
        print(f"[bench] expert products in decode: {len(runs)} steps, "
              f"{t / len(runs) * 1e3:.3f} ms a step, least "
              f"{per_step * 1e3:.3f} ms, bound: {bound}", flush=True)
    try:
        slices = found._runs(trace, args["prefill"])
    except ValueError:
        slices = []
    lengths = _slice_lengths(
        found._inside(ops, slices, args["slice_scope"]), args["tokens"])
    inside = [slices[i] for i in sorted(lengths)]
    t_prefill = _under(ops, inside, tags, rx) if inside else 0.0
    bounds: dict[str, int] = {}
    least_prefill = 0.0
    for tokens in lengths.values():
        seconds, bound = block(
            tokens, roofline_cca.expected_touched(model, tokens))
        least_prefill += seconds
        bounds[bound] = bounds.get(bound, 0) + 1
    if t_prefill:
        print(f"[bench] expert products in prefill: {len(inside)} slices, "
              f"{t_prefill * 1e3:.1f} ms, least {least_prefill * 1e3:.1f} "
              f"ms, bound: {sorted(bounds.items())}", flush=True)
        t, least = t + t_prefill, least + least_prefill
    return 100.0 * least / t if t else None
