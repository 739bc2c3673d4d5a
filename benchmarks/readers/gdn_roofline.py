"""Roofline shares of what the gigachat3_5-like family adds, from
``benchmarks/roofline_gdn.py`` (shapes), ``peaks.json`` and device times in
the trace; the trace's modules, scopes and operations are found by
``readers/hybrid_roofline.py``'s own functions. ``args["kind"]``:

- ``decode``: least bytes of one whole decode step (recurrent state read and
  written, weights, the experts the window's steps touched, live latent
  entries) over the HBM peak, over the median device time of the module
  matching ``args["module"]``: the cell's share of the whole step.
- ``gdn_step``: the GatedDeltaNet mixers inside the decode module: summed
  device time of the operations traced under the scope ``args["scope"]``, a
  step, against the larger of bytes/peak and operations/peak of
  ``roofline_gdn.gdn_step`` at the window's mean live rows.
- ``gdn_scan``: the GatedDeltaNet mixers inside the prefill module, run by
  run: a run's slice length is read off the shape of one of its scoped
  operations (``args["tokens"]``, a pattern whose group is T), its least is
  ``roofline_gdn.gdn_scan`` at that length.
- ``latent``: the latent decode kernel, as ``readers/latent_roofline.py``
  reads it (its ``attention`` kind), over the LATENT layers held: that
  reader counts a latent layer a layer of the model, which holds for its own
  family and not for a pattern with one latent layer in five.
- ``expert_ffn``: the routed experts' three products a block of a held
  share, as ``readers/kda_roofline.py`` reads them (the same SwiGLU experts,
  the same dispatch): that reader asks for its own family's model, so it is
  handed this one under the key it looks for.

The bound of each share is printed. No trace, no such module, scope or
operation, or a cell of another family: nothing. None clamps."""

from __future__ import annotations

import numpy as np

from benchmarks import roofline, roofline_gdn
from benchmarks.readers import hybrid_roofline as found
from benchmarks.readers import kda_roofline, latent_roofline


def _with_model(result: dict, **keys) -> dict:
    shapes = result["shapes"]
    return {**result, "shapes": {**shapes,
                                 "model": {**shapes["model"], **keys}}}


def read(result: dict, args: dict):
    trace = result.get("trace")
    shapes = result["shapes"]
    model = shapes["model"]
    if trace is None or "live_rows" not in shapes \
            or not result.get("trace_dir") or "gdn_v_heads" not in model:
        return None
    kind = args["kind"]
    if kind == "latent":
        return latent_roofline.read(
            _with_model(result, n_layers=roofline_gdn.layers(model)["*"]),
            {**args, "kind": "attention"})
    if kind == "expert_ffn":
        found._scoped_ops(result)  # read once, kept on the result
        return kda_roofline.read(_with_model(result, kda_heads=0), args)
    try:
        runs = found._runs(trace, args["module"])
    except ValueError:  # no device operation at all
        return None
    if not runs:
        return None
    peak = roofline.peaks(result["device"]["kind"])
    rows = shapes["live_rows"]
    if kind == "decode":
        touched = result["stats"].get("experts_touched")
        if touched is None:
            return None
        least = roofline_gdn.decode_step_min_bytes(
            model, rows, shapes["live_kv_tokens"], touched)
        median = float(np.median([(b - a) / 1e9 for a, b in runs]))
        print(f"[bench] gdn decode roofline: {least / 1e9:.3f} GB least at "
              f"{rows:.1f} live rows, {shapes['live_kv_tokens']:.0f} "
              f"positions, {touched:.2f} experts touched; "
              f"{median * 1e3:.3f} ms a step; bound: memory", flush=True)
        return 100.0 * least / peak["hbm_bytes_per_s"] / median
    if kind not in ("gdn_step", "gdn_scan"):
        raise SystemExit(f"gdn_roofline: unknown kind {kind!r}")
    scoped = found._inside(found._scoped_ops(result), runs, args["scope"])
    if not scoped:
        return None
    if kind == "gdn_step":
        total = sum(t for run in scoped.values() for t, _ in run)
        least, bound = roofline.roofline_seconds(
            roofline_gdn.gdn_step(model, rows), peak)
        print(f"[bench] gdn step: {sum(map(len, scoped.values()))} operations "
              f"in {len(scoped)} steps, {total / len(scoped) * 1e3:.3f} ms a "
              f"step, least {least * 1e3:.3f} ms at {rows:.1f} rows, bound: "
              f"{bound}", flush=True)
        return 100.0 * least * len(scoped) / total
    total = total_least = 0.0
    by_length: dict[int, int] = {}
    bounds: dict[str, int] = {}
    for i, tokens in kda_roofline._slice_lengths(
            scoped, args["tokens"]).items():
        least, bound = roofline.roofline_seconds(
            roofline_gdn.gdn_scan(model, tokens), peak)
        total += sum(t for t, _ in scoped[i])
        total_least += least
        by_length[tokens] = by_length.get(tokens, 0) + 1
        bounds[bound] = bounds.get(bound, 0) + 1
    if not total:
        return None
    print(f"[bench] gdn scan: slices by length {sorted(by_length.items())}, "
          f"{total * 1e3:.1f} ms, least {total_least * 1e3:.1f} ms, bound: "
          f"{sorted(bounds.items())}", flush=True)
    return 100.0 * total_least / total
