"""From a profiler trace to numbers: device busy and idle, time per XLA
module, the operations that took most time, and the longest idle gaps with
what the host was doing in them.

Works on a plain structure, so that the arithmetic can be tested on a small
recorded trace kept as JSON (``fixtures/``):

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

``load`` makes it from the ``.xplane.pb`` the JAX profiler writes. Which
planes are devices and which line holds what is data (regular expressions
with defaults that fit a TPU trace), never a constant buried in a reader.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"
MODULES_LINE = r"^XLA Modules$"
HOST_PLANE = r"^/host:"
WINDOW_ANNOTATION = "bench.window"
ANNOTATION_PREFIX = "oim."


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, keep_host: str = r"^(bench\.|oim\.)") -> dict:
    """Read an .xplane.pb. Device planes are kept whole; of the host planes
    only the events whose name matches ``keep_host`` (annotations), since
    the rest is the Python tracer's noise and by far the most of the file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    keep = re.compile(keep_host)
    planes = []
    for plane in data.planes:
        host = re.search(HOST_PLANE, plane.name) is not None
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if not host or keep.search(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _lines(trace: dict, plane_re: str, line_re: str):
    for plane in trace["planes"]:
        if re.search(plane_re, plane["name"]):
            for line in plane["lines"]:
                if re.search(line_re, line["name"]):
                    yield plane["name"], line


def _merge(intervals):
    """Union of [start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def annotations(trace: dict, prefix: str):
    """(name, start, end) of every host event whose name starts with
    ``prefix``."""
    out = []
    for _, line in _lines(trace, HOST_PLANE, r""):
        for name, start, dur in line["events"]:
            if name.startswith(prefix):
                out.append((name, start, start + dur))
    return out


def window(trace: dict, device_plane: str = DEVICE_PLANE,
           ops_line: str = OPS_LINE):
    """[start_ns, end_ns] of the traced window: the harness's own
    ``bench.window`` annotation where the trace has it (idle time at the
    edges then counts), else from the first to the last device operation."""
    marks = annotations(trace, WINDOW_ANNOTATION)
    if marks:
        return min(m[1] for m in marks), max(m[2] for m in marks)
    spans = [(s, s + d) for _, line in _lines(trace, device_plane, ops_line)
             for _, s, d in line["events"]]
    if not spans:
        raise ValueError("no device operation in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def busy(trace: dict, device_plane: str = DEVICE_PLANE,
         ops_line: str = OPS_LINE) -> dict:
    """Seconds in which an operation ran on the device (union of the
    operation intervals inside the window, averaged over the device
    planes) and the window's length."""
    lo, hi = window(trace, device_plane, ops_line)
    per_chip = []
    for _, line in _lines(trace, device_plane, ops_line):
        merged = _clip(_merge((s, s + d) for _, s, d in line["events"]), lo, hi)
        per_chip.append(sum(e - s for s, e in merged))
    if not per_chip:
        raise ValueError("no device plane in the trace")
    return {"busy_s": sum(per_chip) / len(per_chip) / 1e9,
            "window_s": (hi - lo) / 1e9, "chips": len(per_chip)}


def module_durations(trace: dict, pattern: str,
                     device_plane: str = DEVICE_PLANE,
                     modules_line: str = MODULES_LINE) -> list[float]:
    """Device seconds of every run, inside the window, of the XLA modules
    whose name matches ``pattern`` (all device planes together)."""
    lo, hi = window(trace)
    rx = re.compile(pattern)
    return [d / 1e9 for _, line in _lines(trace, device_plane, modules_line)
            for name, s, d in line["events"]
            if rx.search(name) and s >= lo and s + d <= hi]


def op_durations(trace: dict, pattern: str, device_plane: str = DEVICE_PLANE,
                 ops_line: str = OPS_LINE) -> list[float]:
    """The same for single operations (kernels) on the operations line."""
    return module_durations(trace, pattern, device_plane, ops_line)


NAME_CHARS = 140  # an operation's name is its whole HLO line: keep the head


def top_ops(trace: dict, n: int = 10, device_plane: str = DEVICE_PLANE,
            ops_line: str = OPS_LINE) -> list[list]:
    """[[name, seconds], ...]: the device operations that took most time
    (a ``while`` holds the operations of its body, which are listed too)."""
    total: dict[str, float] = {}
    for _, line in _lines(trace, device_plane, ops_line):
        for name, _, d in line["events"]:
            name = name[:NAME_CHARS]
            total[name] = total.get(name, 0.0) + d / 1e9
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, n: int = 10, device_plane: str = DEVICE_PLANE,
              ops_line: str = OPS_LINE) -> list[list]:
    """[[what the host was doing, seconds], ...]: idle time of the first
    device plane inside the window, each gap charged to the innermost host
    annotation whose name starts with ``oim.`` and which covers the whole
    gap, and to ``unannotated`` otherwise; summed by name, longest first."""
    lo, hi = window(trace, device_plane, ops_line)
    first = next(_lines(trace, device_plane, ops_line), None)
    if first is None:
        return []
    merged = _clip(_merge((s, s + d) for _, s, d in first[1]["events"]), lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    notes = annotations(trace, ANNOTATION_PREFIX)
    total: dict[str, float] = {}
    for s, e in gaps:
        covering = [(ae - as_, name) for name, as_, ae in notes
                    if as_ <= s and ae >= e]
        name = min(covering)[1] if covering else "unannotated"
        total[name] = total.get(name, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def summary(trace: dict, n: int = 25) -> dict:
    """What a person looks at before writing a pattern: every plane and
    line with its event count and its commonest names."""
    out = {}
    for plane in trace["planes"]:
        for line in plane["lines"]:
            names: dict[str, list] = {}
            for name, _, d in line["events"]:
                ent = names.setdefault(name, [0, 0.0])
                ent[0] += 1
                ent[1] += d / 1e9
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:n]
            out[f"{plane['name']} | {line['name']}"] = {
                "events": len(line["events"]),
                "top": [[k, c, s] for k, (c, s) in top]}
    return out


def cut(trace: dict, seconds: float) -> dict:
    """The first ``seconds`` of the window, names shortened: small enough
    to keep as a fixture."""
    lo, _ = window(trace)
    hi = lo + int(seconds * 1e9)
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            if re.search(DEVICE_PLANE, plane["name"]):
                ev = [[n[:60], s, d] for n, s, d in line["events"]
                      if s >= lo and s + d <= hi]
            else:
                ev = [[n, max(s, lo), min(s + d, hi) - max(s, lo)]
                      for n, s, d in line["events"] if s < hi and s + d > lo]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


if __name__ == "__main__":
    # python3 benchmarks/reduce.py <file.xplane.pb>            -> summary
    # python3 benchmarks/reduce.py <file.xplane.pb> <seconds>  -> the cut a
    #     fixture such as reduce_recorded.json is made from
    import json
    import sys

    loaded = load(sys.argv[1])
    if len(sys.argv) > 2:
        print(json.dumps(cut(loaded, float(sys.argv[2]))))
    else:
        print(json.dumps(summary(loaded), indent=1))
