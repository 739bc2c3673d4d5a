"""Device time of the programs' runs by the ``jax.named_scope`` their
operations were traced under: per cent of the SELF time of every operation
of the runs of the modules matching ``args["module"]``, inside the traced
window, that stands under the scopes ``args["scopes"]`` names (``"unnamed"``
is what stands under none). A share of the MODULE's busy device time, not of
the window: it does not follow how many slices a window happens to hold, and
the shares of one module sum to 100.

The program writes ONE vocabulary (``VOCABULARY``; tests/test_scopes.py
holds it to the names the program's sources set): the decoder block's
``blk_*``, the ``tok_*`` around it, and the names the kernels' families had
before (``moe_*``, ``ssm_*``, ``kda_*``, ``mla_*``).

- **Self time**: an event of the ``XLA Ops`` line is charged the part of its
  interval that no later-started event covers, so a ``while`` is charged its
  loop's own overhead and not its body's operations a second time
  (``reduce.top_ops`` lists both). Every instant in which an operation of
  the runs ran is charged to exactly one operation: the classes sum to the
  runs' busy device time (the union of their operations' intervals), and
  the reader asserts it.
- **Innermost name wins**: an operation is charged to the deepest component
  of its path that is in the vocabulary (``blk_kv_write`` inside
  ``blk_attn`` is ``blk_kv_write``), to ``unnamed`` where there is none. A
  transformation's wrapper (``transpose(jvp(blk_attn))``) counts as the name
  it wraps.
- **Per program**: the profiler keeps an operation's path (its ``op_name``)
  in the EVENT METADATA, one entry an operation of a PROGRAM, and an event
  names its entry by id. Two programs' ``fusion.177`` with the same HLO text
  are two entries with two paths, which ``hybrid_roofline.scopes_by_operation``
  (keyed by that text) merges: this reader reads the events themselves off
  the wire format, with that file's helpers, and keys by the id.
- One line a traced run and module that a metric asks for, every name with
  its seconds and share, the seconds charged by instruction name and not by
  path (``REWRITTEN``:
  part of the share they stand in, printed apart because they are inferred)
  and the three longest unnamed operations:
  ``[bench] device by scope jit_prefill: runs 23, 1.384 s; blk_attn 0.410
  (29.6 %) ...; by instruction name: moe_gmm 0.345; longest unnamed: ...``.

No trace, no ``trace_dir``, no device plane, no run of such a module:
nothing. A program that carries no name (the parent of PR 39; an executable
loaded from a compile cache filled before it: JAX's cache key leaves the
names out) reads ``unnamed`` near 100: the check on the coverage says so."""

from __future__ import annotations

import bisect
import re

from benchmarks import reduce
from benchmarks.readers.hybrid_roofline import _fields

# In the order a program runs them.
VOCABULARY = ("tok_embed", "blk_loop", "blk_qkv", "blk_attn", "blk_kv_write",
              "mla_prefill", "mla_decode", "blk_out", "blk_ffn", "moe_route",
              "moe_gmm", "ssm_scan", "ssm_step", "kda_scan", "kda_step",
              "tok_head")
# What the compiler renames: XLA expands ``lax.ragged_dot`` into custom calls
# (``%ragged-dot-none.N``, ``%ragged-dot-metadata``) whose ``op_name`` is that
# name and no longer the path it was traced under. The program's one call is
# ``moe.grouped_ffn``'s, under ``moe_gmm`` (tests/test_scopes.py holds it
# there): an operation with NO path whose name starts so is charged there.
REWRITTEN = {"ragged-dot": "moe_gmm"}
UNNAMED = "unnamed"
LONGEST_UNNAMED = 3


def rewritten(path: str, line: str):
    """What ``REWRITTEN`` says of the HLO line of an operation with no path."""
    if not path:
        for prefix, scope in REWRITTEN.items():
            if _short(line).startswith(prefix):
                return scope
    return None


def classify(path: str, line: str = "") -> str:
    """The innermost vocabulary name of an operation's scope path; of an
    operation with no path, what ``REWRITTEN`` says of its HLO line."""
    for part in reversed(path.rstrip(":").split("/")):
        name = part.rsplit("(", 1)[-1].rstrip(")")
        if name in VOCABULARY:
            return name
    return rewritten(path, line) or UNNAMED


def _text(buf) -> str:
    return bytes(buf).decode(errors="replace")


def _metadata(entry):
    """(id, HLO line, scope path or "") of one entry of XPlane.event_metadata
    (map key = 1, value = 2: XEventMetadata with .name = 2 and .stats = 5,
    of which the str_value = 5 that starts with "jit(" is the path)."""
    key, line, path = 0, "", ""
    for number, value in _fields(entry):
        if number == 1:
            key = value
        elif number == 2:
            for n, v in _fields(value):
                if n == 2:
                    line = _text(v)
                elif n == 5:
                    for m, text in _fields(v):
                        if m == 5 and bytes(text[:4]) == b"jit(":
                            path = _text(text)
    return key, line, path


def _events(line, wanted: tuple):
    """(which of the ``wanted`` patterns the line's name matches or None,
    [(start_ps, end_ps, metadata id)]) of one XLine (.name = 2,
    .timestamp_ns = 3, .events = 4: XEvent with .metadata_id = 1,
    .offset_ps = 2, .duration_ps = 3, then its stats, which are skipped);
    the events of a line nobody wants are not parsed."""
    name, base, raw = "", 0, []
    for number, value in _fields(line):
        if number == 2:
            name = _text(value)
        elif number == 3:
            base = value * 1000
        elif number == 4:
            raw.append(value)
    which = next((rx for rx in wanted if re.search(rx, name)), None)
    events = []
    for event in raw if which else ():
        key = offset = duration = 0
        for number, value in _fields(event):
            if number == 1:
                key = value
            elif number == 2:
                offset = value
            elif number == 3:
                duration = value
            else:
                break
        events.append((base + offset, base + offset + duration, key))
    return which, events


def device_planes(path: str) -> list:
    """[(metadata {id: (HLO line, path)}, module runs, operations)] of the
    device planes of an ``.xplane.pb`` (XSpace.planes = 1; XPlane.name = 2,
    .lines = 3, .event_metadata = 4), events as ``_events`` gives them."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for number, plane in _fields(space):
        if number != 1:
            continue
        fields = list(_fields(plane))
        name = next((_text(v) for k, v in fields if k == 2), "")
        if not re.search(reduce.DEVICE_PLANE, name):
            continue
        metadata = {key: (line, scope) for key, line, scope in
                    (_metadata(v) for k, v in fields if k == 4)}
        events: dict = {reduce.MODULES_LINE: [], reduce.OPS_LINE: []}
        for k, v in fields:
            if k == 3:
                which, found = _events(v, tuple(events))
                if which:
                    events[which] += found
        planes.append((metadata, events[reduce.MODULES_LINE],
                       events[reduce.OPS_LINE]))
    return planes


def self_times(events) -> dict:
    """{key: ps}: every instant of the union of ``events`` [(start, end,
    key)] charged to the event that started last among those over it."""
    total: dict = {}
    open_, cursor = [], 0  # open_: (end, key), the innermost last

    def close_until(t):
        nonlocal cursor
        while open_ and open_[-1][0] <= t:
            end, key = open_.pop()
            if end > cursor:
                total[key] = total.get(key, 0) + end - cursor
                cursor = end

    for start, end, key in sorted(events, key=lambda e: (e[0], -e[1])):
        close_until(start)
        if open_ and start > cursor:
            total[open_[-1][1]] = total.get(open_[-1][1], 0) + start - cursor
        cursor = max(cursor, start)
        open_.append((end, key))
    close_until(float("inf"))
    return total


def _union(events) -> int:
    return sum(e - s for s, e in reduce._merge((s, e) for s, e, _ in events))


def split(path: str, lo_ns: int, hi_ns: int) -> dict:
    """{module (``jit_step``, its fingerprint left off: a bucket's program
    is a program of the same name): [runs, {scope: ps}, {unnamed HLO line:
    ps}, {scope: ps of it charged by ``REWRITTEN``}]} of the module runs
    inside [lo, hi], all device planes together."""
    out: dict = {}
    for metadata, modules, ops in device_planes(path):
        runs = sorted((s, e, metadata.get(key, ("?", ""))[0].split("(", 1)[0])
                      for s, e, key in modules
                      if s >= lo_ns * 1000 and e <= hi_ns * 1000)
        starts = [s for s, _, _ in runs]
        inside: list[list] = [[] for _ in runs]
        for op in ops:
            i = bisect.bisect_right(starts, op[0]) - 1
            if i >= 0 and op[0] < runs[i][1]:
                inside[i].append(op)
        scope_of = {key: classify(scope, line)
                    for key, (line, scope) in metadata.items()}
        by_name = {key for key, (line, scope) in metadata.items()
                   if rewritten(scope, line)}
        for (_, _, module), events in zip(runs, inside):
            by_key = self_times(events)
            assert sum(by_key.values()) == _union(events), module
            entry = out.setdefault(module, [0, {}, {}, {}])
            entry[0] += 1
            for key, ps in by_key.items():
                scope = scope_of.get(key, UNNAMED)
                entry[1][scope] = entry[1].get(scope, 0) + ps
                if scope == UNNAMED:
                    line = metadata.get(key, ("?", ""))[0]
                    entry[2][line] = entry[2].get(line, 0) + ps
                elif key in by_name:
                    entry[3][scope] = entry[3].get(scope, 0) + ps
    return out


def _short(line: str) -> str:
    """``%fusion.177 = bf16[...] fusion(...)`` -> ``fusion.177``."""
    return line.split(" ", 1)[0].lstrip("%") or "?"


def _line_of(module: str, runs, scopes, unnamed, by_name) -> str:
    total = sum(scopes.values())
    parts = [f"{name} {scopes.get(name, 0) / 1e12:.4f} "
             f"({100 * scopes.get(name, 0) / total:.1f} %)"
             for name in VOCABULARY + (UNNAMED,)]
    longest = sorted(unnamed.items(), key=lambda kv: -kv[1])
    return (f"[bench] device by scope {module}: runs {runs}, "
            f"{total / 1e12:.4f} s; " + " ".join(parts)
            + "; by instruction name: "
            + (", ".join(f"{name} {ps / 1e12:.4f}"
                         for name, ps in sorted(by_name.items())) or "none")
            + "; longest unnamed: "
            + ", ".join(f"{_short(line)} {ps / 1e12:.4f}"
                        for line, ps in longest[:LONGEST_UNNAMED]))


def _splits(result: dict) -> dict:
    """``split`` of the result's trace, once a result."""
    if "_scope_split" not in result:
        lo, hi = reduce.window(result["trace"])
        result["_scope_split"] = split(
            reduce.find_xplane(result["trace_dir"]), lo, hi)
        result["_scope_printed"] = set()
    return result["_scope_split"]


def read(result: dict, args: dict):
    if result.get("trace") is None or not result.get("trace_dir"):
        return None
    try:
        found = _splits(result)
    except (ValueError, FileNotFoundError):  # no device operation, no file
        return None
    rx = re.compile(args["module"])
    scopes: dict = {}
    for module, entry in sorted(found.items()):
        if rx.search(module) and sum(entry[1].values()):
            if module not in result["_scope_printed"]:  # once a module
                result["_scope_printed"].add(module)
                print(_line_of(module, *entry), flush=True)
            for name, ps in entry[1].items():
                scopes[name] = scopes.get(name, 0) + ps
    if not scopes:
        return None
    return (100.0 * sum(scopes.get(name, 0) for name in args["scopes"])
            / sum(scopes.values()))
