"""Idle time of the device by what the host was doing in it: per cent of
the traced window in which no operation ran on the first device plane (the
window and the gaps exactly as ``reduce.idle_gaps`` takes them) while the
thread that feeds the device was under one of the ``oim.*`` annotations
``args["spans"]`` names, or under none (``"spans": null``).

Unlike ``reduce.idle_gaps``, which wants one annotation to cover a whole
gap, each gap is CUT at the annotations' edges and each piece is charged to
the innermost annotation over it: a gap between two decode steps runs
through the tail of the token fetch, the emit loop, the admission and the
next dispatch, and no one of them covers it.

**The profiler's two clocks disagree.** The device plane's timestamps lay
1.4 to 4.6 ms EARLIER than the host plane's in PR 26's traced runs, another
amount in every process (a decode program seemed to start 2.9 ms before the
call that launches it opened), which is as long as the gaps to be split.
So the annotations are first moved onto the device's clock by what cause
and effect allow (``ALIGN``): no ``jit_step`` run may start before the
``oim.serve.dispatch`` nearest to it opens (the least shift), nor end after
the ``oim.serve.fetch`` nearest to it closes (the most). The shift used is the middle of the two, both are
printed, and half their distance is what each step's split between the
launch and the wake-up is uncertain by: only the two together are a metric.
Without such runs, or where the pairs contradict one another, nothing is
moved and the line says ``not aligned``.

Nesting means something on one thread only, so the annotations are those
of ONE host line: the thread that dispatches the step (of several, the one
that opened ``oim.serve.dispatch`` most often). Another thread's spans (a
request's ``oim.serve.generate``, an RPC handler's) neither cover nor cut,
however many they are. With several engines in one process the pieces
would want their own device planes; the cells here have one.

The split is made once a run and printed whole, every name with its
seconds, so the finer names are in every traced log. No trace, no device
plane, a program whose step no thread dispatches under a name (the parent,
the trainer) or that opens none of the annotations asked for: nothing."""

from __future__ import annotations

import bisect
import re
import statistics
from typing import NamedTuple

from benchmarks import reduce

UNANNOTATED = "unannotated"
# The serve engine's step: launched in one annotation, its tokens awaited
# in the next.
ALIGN = {"module": r"^jit_step(\(|$)", "launched_in": "oim.serve.dispatch",
         "awaited_in": "oim.serve.fetch"}


class Split(NamedTuple):
    window: int             # ns
    idle: dict[str, int]    # name -> idle ns under it
    opened: set[str]        # the names the feeding thread opened
    shift: tuple | None     # (least, most) ns the device clock is behind


def feeding_thread(trace: dict) -> list[tuple]:
    """(name, start, end) of the ``oim.*`` events of the host line that
    launched the step (most often, of several); none where no line did."""
    best, launches = [], 0
    for _, line in reduce._lines(trace, reduce.HOST_PLANE, r""):
        n = sum(name == ALIGN["launched_in"] for name, _, _ in line["events"])
        if n > launches:
            launches = n
            best = [(name, start, start + dur)
                    for name, start, dur in line["events"]
                    if name.startswith(reduce.ANNOTATION_PREFIX)]
    return best


def _nearest(sorted_values: list[int], x: int) -> int:
    i = bisect.bisect_left(sorted_values, x)
    return min(sorted_values[max(i - 1, 0):i + 1], key=lambda v: abs(v - x))


def clock_shift(trace: dict, notes: list[tuple]):
    """(least, most) ns by which the device plane's clock is behind the
    host plane's, from cause and effect; nothing where the trace has no
    such run or annotation, or no pair is left. Every run inside the
    window (one cut by the profile's edge has a false start or end) is
    paired with the nearest opening of ``launched_in`` and the nearest
    closing of ``awaited_in``: the clock is behind by at least the largest
    launch pair (opening - run start) and at most the smallest await pair
    (closing - run end). A pair that contradicts the other side's MEDIAN
    pair is a wrong pair (the run's own annotation is not in the profile
    and a neighbour's, a step away, was taken; or a hiccup of the profile)
    and is left out."""
    lo, hi = reduce.window(trace)
    runs = [(s, s + d) for _, line in reduce._lines(
        trace, reduce.DEVICE_PLANE, reduce.MODULES_LINE)
        for name, s, d in line["events"]
        if re.search(ALIGN["module"], name) and s >= lo and s + d <= hi]
    opened = sorted(s for n, s, _ in notes if n == ALIGN["launched_in"])
    closed = sorted(e for n, _, e in notes if n == ALIGN["awaited_in"])
    if not runs or not opened or not closed:
        return None
    launch = [_nearest(opened, s) - s for s, _ in runs]
    await_ = [_nearest(closed, e) - e for _, e in runs]
    least = [d for d in launch if d <= statistics.median(await_)]
    most = [d for d in await_ if d >= statistics.median(launch)]
    if not least or not most:
        return None
    return max(least), min(most)


def innermost(notes: list[tuple]) -> list[tuple]:
    """One thread's annotations as sorted, disjoint (start, end, name)
    segments: every instant under the innermost annotation over it."""
    out, stack = [], []  # stack: (end, name) of the open ones
    at = float("-inf")  # segments are out up to here

    def close(until):
        nonlocal at
        while stack and stack[-1][0] <= until:
            end, name = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end

    for name, start, end in sorted(notes, key=lambda n: (n[1], -n[2])):
        close(start)
        if stack and start > at:
            out.append((at, start, stack[-1][1]))
        at = max(at, start)
        stack.append((end, name))
    close(float("inf"))
    return out


def split(trace: dict) -> Split | None:
    """Nothing without a device plane or without a feeding thread."""
    first = next(reduce._lines(trace, reduce.DEVICE_PLANE, reduce.OPS_LINE),
                 None)
    notes = feeding_thread(trace)
    if first is None or not notes:
        return None
    lo, hi = reduce.window(trace)
    busy = reduce._clip(reduce._merge(
        (s, s + d) for _, s, d in first[1]["events"]), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    bounds = clock_shift(trace, notes)
    if bounds is not None:  # the annotations onto the device's clock
        shift = sum(bounds) // 2
        notes = [(name, s - shift, e - shift) for name, s, e in notes]
    segments = innermost(notes)
    starts = [s for s, _, _ in segments]
    total: dict[str, int] = {}

    def charge(name, ns):
        if ns > 0:
            total[name] = total.get(name, 0) + ns

    for s, e in zip(edges[0::2], edges[1::2]):
        at, i = s, max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(segments) and segments[i][0] < e:
            a, b, name = segments[i]
            a, b = max(a, at), min(b, e)
            if b > a:
                charge(UNANNOTATED, a - at)
                charge(name, b - a)
                at = b
            i += 1
        charge(UNANNOTATED, e - at)
    return Split(hi - lo, total, {name for name, _, _ in notes}, bounds)


def _report(got: Split) -> None:
    moved = "not aligned" if got.shift is None else (
        "device clock behind the host's by {:.3f} to {:.3f} ms, annotations "
        "moved {:.3f}".format(got.shift[0] / 1e6, got.shift[1] / 1e6,
                              sum(got.shift) // 2 / 1e6))
    by_name = sorted(got.idle.items(), key=lambda kv: -kv[1])
    print(f"[bench] idle by span: window {got.window / 1e9:.6f} s, idle "
          f"{sum(got.idle.values()) / 1e9:.6f} s ({moved}): "
          + ", ".join(f"{name} {ns / 1e9:.6f}" for name, ns in by_name),
          flush=True)


def read(result: dict, args: dict):
    trace = result.get("trace")
    if trace is None:
        return None
    if "idle_by_span" not in result:  # four metrics, one split
        result["idle_by_span"] = split(trace)
        if result["idle_by_span"] is not None:
            _report(result["idle_by_span"])
    got = result["idle_by_span"]
    if got is None:
        return None
    names = args["spans"]
    if names is None:
        names = [UNANNOTATED]
    elif not got.opened & set(names):
        return None
    return 100.0 * sum(got.idle.get(name, 0) for name in names) / got.window
