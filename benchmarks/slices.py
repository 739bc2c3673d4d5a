"""The steady rate beside the end-to-end one: the median of ten slices.

The whole-window rates (``train_tokens_per_s``, ``out_tokens_per_s.batch``) are
all the work of the window over all of its time: what a user gets, stalls and
all. A second of standstill in a 50 s window lowers them by 2 %, whatever
caused it. To tell a stall from a slower program, the window is also cut
into ``SLICES`` consecutive slices, each slice gets its own rate, and the
median of those is reported per layer (``slice_rate.<mix>``): a stall that
falls into one or two slices cannot move it; a cost that recurs in most
slices (a feed wait per step, a slower kernel, a host sync per token) moves
it in full. ``stall_share`` = 1 - whole / median says how much of the
window went to what the median leaves out.

Two cuts:

- work that comes in whole steps (training): ``steps`` steps are cut into
  runs of whole steps as equal in count as can be (147 -> seven of 15, three
  of 14, the longer runs first); a slice's rate is its work over the host
  time from the boundary that opens its first step to the boundary that
  closes its last;
- work that is stamped as it arrives (served tokens): ten equal spans of the
  window; a slice's rate is the stamps inside it over its length.
"""

from __future__ import annotations

import bisect
import statistics

SLICES = 10


def cut_steps(steps: int, slices: int = SLICES) -> list[int]:
    """Step counts of the slices: as equal as can be, the longer first.
    Fewer steps than slices give one slice a step."""
    slices = max(1, min(slices, steps))
    base, extra = divmod(steps, slices)
    return [base + 1] * extra + [base] * (slices - extra)


def step_slice_rates(boundaries, work_per_step: float,
                     slices: int = SLICES) -> list[float]:
    """``boundaries`` = host times b_0 .. b_n of n steps (b_i closes step i
    and opens step i + 1). One rate per slice of whole steps."""
    steps = len(boundaries) - 1
    if steps < 1:
        return []
    rates, first = [], 0
    for count in cut_steps(steps, slices):
        span = boundaries[first + count] - boundaries[first]
        rates.append(count * work_per_step / span)
        first += count
    return rates


def span_slice_rates(stamps, start: float, end: float,
                     slices: int = SLICES) -> list[float]:
    """``stamps`` = host times of the units of work (tokens). One rate per
    equal span of [start, end]; a stamp on an inner edge counts once, in the
    later span, and one at ``end`` in the last."""
    stamps = sorted(t for t in stamps if start <= t <= end)
    length = (end - start) / slices
    edges = [start + i * length for i in range(slices)]
    cuts = [bisect.bisect_left(stamps, e) for e in edges] + [len(stamps)]
    return [(cuts[i + 1] - cuts[i]) / length for i in range(slices)]


def median(rates) -> float:
    return float(statistics.median(rates))


def stall_share(whole_rate: float, slice_median: float) -> float:
    """Per cent of the window's work-time that the slice median leaves out:
    100 x (1 - whole-window rate / slice median). About 0 in a window
    without a stall (a little under it where the slower slices are the
    shorter ones); s / window for one stall of s seconds."""
    return 100.0 * (1.0 - whole_rate / slice_median)
