"""Roofline shares of what the nemotron_h-like family adds, from
``benchmarks/roofline_hybrid.py`` (shapes), ``peaks.json`` and device times
in the trace. ``args["kind"]``:

- ``decode``: least bytes of one whole decode step (recurrent state read
  and written, weights, the experts the window's steps touched, live keys
  and values) over the HBM peak, over the median device time of the module
  matching ``args["step"]``.
- ``ssm_step``: the Mamba mixers inside the decode module: summed device
  time of the operations traced under the scope ``args["scope"]``, a step,
  against the larger of bytes/peak and operations/peak of
  ``roofline_hybrid.ssm_step`` at the window's mean live rows.
- ``ssm_scan``: the Mamba mixers inside the prefill module, run by run: a
  run's slice length is read off the shape of one of its scoped operations
  (``args["tokens"]``, a pattern whose group is T), its least is
  ``roofline_hybrid.ssm_scan`` at that length.
- ``expert_ffn``: the routed experts' two products a layer (the
  squared-ReLU expert has no gate). In the decode module: the operations
  under the scope ``args["scope"]`` (the batched form a held share runs
  at few tokens) or matching ``args["op"]`` (the grouped products, told
  by the text of their HLO line), against two products a layer over the
  live rows' held assignments at the window's measured
  ``experts_touched``. In the prefill module: the grouped products, rows
  off the output shape (``args["rows"]``), of which the share of the
  experts held is work (``roofline_hybrid.expert_product``), at what
  uniform routing reaches.

The profiler names an operation by its HLO line; the ``jax.named_scope`` it
was traced under is in the ``.xplane.pb`` as a statistic of the event's
metadata (the operation's ``op_name``), which ``reduce.load`` does not
keep: the scoped kinds read the file itself (``scopes_by_operation``),
once a result. No trace, no such module, scope
or operation: nothing. None clamps."""

from __future__ import annotations

import re

import numpy as np

from benchmarks import reduce, roofline, roofline_hybrid


def _runs(trace, pattern):
    lo, hi = reduce.window(trace)
    rx = re.compile(pattern)
    return sorted((s, s + d)
                  for _, line in reduce._lines(trace, reduce.DEVICE_PLANE,
                                               reduce.MODULES_LINE)
                  for name, s, d in line["events"]
                  if rx.search(name) and s >= lo and s + d <= hi)


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")


def scopes_by_operation(path: str) -> dict:
    """{HLO line: set of scope paths ("jit(step)/while/body/.../ssm_step/
    mul")} of the device planes' operations. The profiler keeps an
    operation's ``op_name`` as a statistic of its EVENT METADATA, which
    ``jax.profiler.ProfileData`` does not hand out: the few fields needed
    are read off the wire format (XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, a map whose values are XEventMetadata with
    .name = 2 and .stats = 5; XStat.str_value = 5)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    found: dict[str, set] = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in fields if k == 2), "")
        if not re.search(reduce.DEVICE_PLANE, name):
            continue
        for k, entry in fields:
            if k != 4:
                continue
            meta = next((v for n, v in _fields(entry) if n == 2), None)
            if meta is None:
                continue
            line, paths = "", []
            for n, v in _fields(meta):
                if n == 2:
                    line = bytes(v).decode(errors="replace")
                elif n == 5:
                    for m, text in _fields(v):
                        if m == 5 and bytes(text[:4]) == b"jit(":
                            paths.append(bytes(text).decode(errors="replace"))
            if line and paths:
                found.setdefault(line, set()).update(paths)
    return found


def _scoped_ops(result: dict) -> list:
    """[(start_ns, seconds, HLO line, its scope paths joined or "")] of
    every device operation, once a result."""
    if "_scoped_ops" not in result:
        scopes = scopes_by_operation(reduce.find_xplane(result["trace_dir"]))
        result["_scoped_ops"] = [
            (s, d / 1e9, name, " ".join(sorted(scopes.get(name, ()))))
            for _, line in reduce._lines(result["trace"], reduce.DEVICE_PLANE,
                                         reduce.OPS_LINE)
            for name, s, d in line["events"]]
    return result["_scoped_ops"]


def _inside(ops, runs, scope: str):
    """{run index: [(seconds, HLO line)]} of the operations under
    ``scope`` that start inside one of ``runs``."""
    starts = np.array([a for a, _ in runs])
    ends = np.array([b for _, b in runs])
    tag = f"/{scope}/"
    found: dict[int, list] = {}
    for s, seconds, name, path in ops:
        if tag not in path:
            continue
        i = int(np.searchsorted(starts, s, side="right")) - 1
        if i >= 0 and s < ends[i]:
            found.setdefault(i, []).append((seconds, name))
    return found


def read(result: dict, args: dict):
    trace = result.get("trace")
    shapes = result["shapes"]
    if trace is None or "live_rows" not in shapes:
        return None
    kind = args["kind"]
    try:
        runs = _runs(trace, args["module"])
    except ValueError:  # no device operation at all
        return None
    if not runs:
        return None
    model = shapes["model"]
    peak = roofline.peaks(result["device"]["kind"])
    touched = result["stats"].get("experts_touched")
    if kind == "decode":
        if touched is None:
            return None
        least = roofline_hybrid.decode_step_min_bytes(
            model, shapes["live_rows"], shapes["live_kv_tokens"], touched)
        median = float(np.median([(b - a) / 1e9 for a, b in runs]))
        print(f"[bench] hybrid decode roofline: {least / 1e9:.3f} GB least "
              f"at {shapes['live_rows']:.1f} live rows, "
              f"{shapes['live_kv_tokens']:.0f} positions, {touched:.2f} "
              f"experts touched; {median * 1e3:.3f} ms a step; bound: memory",
              flush=True)
        return 100.0 * least / peak["hbm_bytes_per_s"] / median
    if kind == "expert_ffn":
        if touched is None:
            return None
        held = model["experts_held"] / model["n_experts"]
        k, layers = model["moe_top_k"], roofline_hybrid.layers(model)["E"]
        rx = re.compile(args["op"])
        scoped = (_scoped_ops(result) if result.get("trace_dir") else [])
        tag = f"/{args['scope']}/"

        def product(tokens, reached):
            return roofline.roofline_seconds(roofline_hybrid.expert_product(
                model, tokens * k * held, reached), peak)[0]

        # Decode: a step's products under the scope (the batched form) or by
        # their HLO line (the grouped form), against two products a layer
        # over the window's live rows at the experts its steps touched.
        t = sum(d for s, d, name, path in scoped
                if (tag in path or rx.search(name))
                and any(a <= s < b for a, b in runs))
        least = len(runs) * layers * 2 * product(shapes["live_rows"], touched)
        if t:
            print(f"[bench] expert products in decode: {len(runs)} steps, "
                  f"{t * 1e3:.1f} ms, least {least * 1e3:.1f} ms", flush=True)
        total_t, total_least = t, least if t else 0.0
        # Prefill: the grouped products by their HLO line, rows off the
        # output shape, at the experts those rows reach under uniform
        # routing (slices short enough for the batched form are left out).
        try:
            inside = _runs(trace, args["prefill"])
        except ValueError:
            inside = []
        t = least = 0.0
        n = 0
        for _, line in reduce._lines(trace, reduce.DEVICE_PLANE,
                                     reduce.OPS_LINE):
            for name, s, d in line["events"]:
                if rx.search(name) and any(a <= s < b for a, b in inside):
                    tokens = int(re.search(args["rows"], name).group(1)) / k
                    least += product(tokens, roofline_hybrid
                                     .expected_held_touched(model, tokens))
                    t, n = t + d / 1e9, n + 1
        if n:
            print(f"[bench] expert products in prefill: {n} in "
                  f"{len(inside)} runs, {t * 1e3:.1f} ms, least "
                  f"{least * 1e3:.1f} ms", flush=True)
        total_t, total_least = total_t + t, total_least + least
        return 100.0 * total_least / total_t if total_t else None
    if kind not in ("ssm_step", "ssm_scan"):
        raise SystemExit(f"hybrid_roofline: unknown kind {kind!r}")
    if not result.get("trace_dir"):
        return None
    found = _inside(_scoped_ops(result), runs, args["scope"])
    if not found:
        return None
    total = sum(t for ops in found.values() for t, _ in ops)
    if kind == "ssm_step":
        least, bound = roofline.roofline_seconds(
            roofline_hybrid.ssm_step(model, shapes["live_rows"]), peak)
        print(f"[bench] ssm step: {sum(map(len, found.values()))} operations "
              f"in {len(found)} steps, {total / len(found) * 1e3:.3f} ms a "
              f"step, least {least * 1e3:.3f} ms at "
              f"{shapes['live_rows']:.1f} rows, bound: {bound}", flush=True)
        return 100.0 * least * len(found) / total
    rx = re.compile(args["tokens"])
    total = total_least = 0.0
    lengths: dict[int, int] = {}
    for ops in found.values():
        sizes = [int(m.group(1)) for _, name in ops
                 if (m := rx.search(name)) is not None]
        if not sizes:
            continue  # a run whose slice length cannot be read is left out
        tokens = max(sizes)
        least, _ = roofline.roofline_seconds(
            roofline_hybrid.ssm_scan(model, tokens), peak)
        total += sum(t for t, _ in ops)
        total_least += least
        lengths[tokens] = lengths.get(tokens, 0) + 1
    if not total:
        return None
    print(f"[bench] ssm scan: slices by length {sorted(lengths.items())}, "
          f"{total * 1e3:.1f} ms, least {total_least * 1e3:.1f} ms",
          flush=True)
    return 100.0 * total_least / total
