"""Roofline shares of what the solar_open2-like family adds, from
``benchmarks/roofline_kda.py`` (shapes), ``peaks.json`` and device times in
the trace; the trace's modules, scopes and operations are found by
``readers/hybrid_roofline.py``'s own functions. ``args["kind"]``:

- ``decode``: least bytes of one whole decode step (recurrent state read and
  written, weights, the experts the window's steps touched, live keys and
  values) over the HBM peak, over the median device time of the module
  matching ``args["module"]``: the cell's share of the whole step.
- ``kda_step``: the KDA mixers inside the decode module: summed device time
  of the operations traced under the scope ``args["scope"]``, a step,
  against the larger of bytes/peak and operations/peak of
  ``roofline_kda.kda_step`` at the window's mean live rows.
- ``kda_scan``: the KDA mixers inside the prefill module, run by run: a
  run's slice length is read off the shape of one of its scoped operations
  (``args["tokens"]``, a pattern whose group is T), its least is
  ``roofline_kda.kda_scan`` at that length.
- ``expert_ffn``: the routed experts' THREE products a block (SwiGLU) of a
  held share, in the decode module AND in the prefill module: the operations
  under the scope ``args["scope"]`` (the dense form at few tokens, the
  bounded rungs' batched products) or matching ``args["op"]`` (the last
  rung's grouped products, told by the text of their HLO line). Decode:
  against three products a block over the live rows' held assignments at
  the window's measured ``experts_touched``. Prefill: a run's slice length
  as above (from the scope ``args["slice_scope"]``), three products a block
  over its held assignments at what uniform routing reaches.

The bound of each share is printed. No trace, no such module, scope or
operation: nothing. None clamps."""

from __future__ import annotations

import re

import numpy as np

from benchmarks import roofline, roofline_kda
from benchmarks.readers import hybrid_roofline as found


def _slice_lengths(scoped: dict, pattern: str) -> dict:
    """{run index: slice length T} read off the scoped operations' shapes;
    a run whose length cannot be read is left out."""
    rx = re.compile(pattern)
    out = {}
    for i, ops in scoped.items():
        sizes = [int(m.group(1)) for _, name in ops
                 if (m := rx.search(name)) is not None]
        if sizes:
            out[i] = max(sizes)
    return out


def read(result: dict, args: dict):
    trace = result.get("trace")
    shapes = result["shapes"]
    if trace is None or "live_rows" not in shapes \
            or not result.get("trace_dir"):
        return None
    kind = args["kind"]
    try:
        runs = found._runs(trace, args["module"])
    except ValueError:  # no device operation at all
        return None
    if not runs:
        return None
    model = shapes["model"]
    if "kda_heads" not in model:
        return None
    peak = roofline.peaks(result["device"]["kind"])
    touched = result["stats"].get("experts_touched")
    rows = shapes["live_rows"]
    if kind == "decode":
        if touched is None:
            return None
        least = roofline_kda.decode_step_min_bytes(
            model, rows, shapes["live_kv_tokens"], touched)
        median = float(np.median([(b - a) / 1e9 for a, b in runs]))
        print(f"[bench] kda decode roofline: {least / 1e9:.3f} GB least at "
              f"{rows:.1f} live rows, {shapes['live_kv_tokens']:.0f} "
              f"positions, {touched:.2f} experts touched; "
              f"{median * 1e3:.3f} ms a step; bound: memory", flush=True)
        return 100.0 * least / peak["hbm_bytes_per_s"] / median
    ops = found._scoped_ops(result)
    if kind == "expert_ffn":
        if touched is None:
            return None
        held = model["experts_held"] / model["n_experts"]
        k, blocks = model["moe_top_k"], roofline_kda.layers(model)["E"]
        rx = re.compile(args["op"])
        tag = f"/{args['scope']}/"

        def block(tokens, reached):
            seconds, bound = roofline.roofline_seconds(
                roofline_kda.expert_product(model, tokens * k * held, reached),
                peak)
            return 3 * blocks * seconds, bound

        def spent(inside):
            return sum(d for s, d, name, path in ops
                       if (tag in path or rx.search(name))
                       and any(a <= s < b for a, b in inside))

        t = spent(runs)
        per_step, bound = block(rows, touched)
        least = len(runs) * per_step if t else 0.0
        if t:
            print(f"[bench] expert products in decode: {len(runs)} steps, "
                  f"{t * 1e3:.1f} ms, least {least * 1e3:.1f} ms, bound: "
                  f"{bound}", flush=True)
        try:
            slices = found._runs(trace, args["prefill"])
        except ValueError:
            slices = []
        lengths = _slice_lengths(
            found._inside(ops, slices, args["slice_scope"]), args["tokens"])
        inside = [slices[i] for i in lengths]
        t_prefill = spent(inside)
        bounds: dict[str, int] = {}
        least_prefill = 0.0
        for tokens in lengths.values():
            seconds, bound = block(
                tokens, roofline_kda.expected_held_touched(model, tokens))
            least_prefill += seconds
            bounds[bound] = bounds.get(bound, 0) + 1
        if t_prefill:
            print(f"[bench] expert products in prefill: {len(inside)} "
                  f"slices, {t_prefill * 1e3:.1f} ms, least "
                  f"{least_prefill * 1e3:.1f} ms, bound: "
                  f"{sorted(bounds.items())}", flush=True)
            t, least = t + t_prefill, least + least_prefill
        return 100.0 * least / t if t else None
    if kind not in ("kda_step", "kda_scan"):
        raise SystemExit(f"kda_roofline: unknown kind {kind!r}")
    scoped = found._inside(ops, runs, args["scope"])
    if not scoped:
        return None
    if kind == "kda_step":
        total = sum(t for run in scoped.values() for t, _ in run)
        least, bound = roofline.roofline_seconds(
            roofline_kda.kda_step(model, rows), peak)
        print(f"[bench] kda step: {sum(map(len, scoped.values()))} operations "
              f"in {len(scoped)} steps, {total / len(scoped) * 1e3:.3f} ms a "
              f"step, least {least * 1e3:.3f} ms at {rows:.1f} rows, bound: "
              f"{bound}", flush=True)
        return 100.0 * least * len(scoped) / total
    total = total_least = 0.0
    by_length: dict[int, int] = {}
    bounds = {}
    for i, tokens in _slice_lengths(scoped, args["tokens"]).items():
        least, bound = roofline.roofline_seconds(
            roofline_kda.kda_scan(model, tokens), peak)
        total += sum(t for t, _ in scoped[i])
        total_least += least
        by_length[tokens] = by_length.get(tokens, 0) + 1
        bounds[bound] = bounds.get(bound, 0) + 1
    if not total:
        return None
    print(f"[bench] kda scan: slices by length {sorted(by_length.items())}, "
          f"{total * 1e3:.1f} ms, least {total_least * 1e3:.1f} ms, bound: "
          f"{sorted(bounds.items())}", flush=True)
    return 100.0 * total_least / total
