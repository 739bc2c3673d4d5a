"""The slice estimator on synthetic stamps: a stall that falls into one
slice leaves the median where it was, a cost that recurs in every slice
moves it by its full share, and the cut of whole steps is as equal as can
be."""

import numpy as np
import pytest

from benchmarks import slices

STEP_S, TOKENS = 0.3436, 8192  # the train cell's step on the v5e (PR 24)


def _bounds(steps, step_s=STEP_S, stall_at=None, stall_s=0.0, every=0.0):
    gaps = np.full(steps, step_s + every)
    if stall_at is not None:
        gaps[stall_at] += stall_s
    return np.concatenate([[100.0], 100.0 + np.cumsum(gaps)])


@pytest.mark.parametrize("steps,want", [
    (147, [15] * 7 + [14] * 3), (150, [15] * 10), (86, [9] * 6 + [8] * 4),
    (10, [1] * 10), (3, [1, 1, 1]), (1, [1])])
def test_steps_are_cut_as_equally_as_can_be(steps, want):
    assert slices.cut_steps(steps) == want
    assert sum(slices.cut_steps(steps)) == steps


def test_a_steady_window_reads_the_step_rate_in_every_slice():
    rates = slices.step_slice_rates(_bounds(147), TOKENS)
    assert len(rates) == 10
    assert rates == pytest.approx([TOKENS / STEP_S] * 10)
    assert slices.median(rates) == pytest.approx(23841.68, abs=0.01)


@pytest.mark.parametrize("stall_at", [0, 20, 73, 146])
@pytest.mark.parametrize("stall_s", [0.4, 1.0, 1.5])
def test_a_stall_in_one_slice_leaves_the_median_where_it_was(stall_at, stall_s):
    steady = slices.median(slices.step_slice_rates(_bounds(147), TOKENS))
    bounds = _bounds(147, stall_at=stall_at, stall_s=stall_s)
    rates = slices.step_slice_rates(bounds, TOKENS)
    assert slices.median(rates) == pytest.approx(steady, rel=1e-9)
    whole = 147 * TOKENS / (bounds[-1] - bounds[0])
    # the whole-window rate feels it in full: s / window
    assert 1 - whole / steady == pytest.approx(
        stall_s / (147 * STEP_S + stall_s), rel=1e-6)
    assert slices.stall_share(whole, slices.median(rates)) == pytest.approx(
        100 * stall_s / (147 * STEP_S + stall_s), rel=1e-6)


def test_stalls_in_two_slices_still_leave_it():
    bounds = _bounds(147, stall_at=5, stall_s=1.0)
    bounds[100:] += 0.7  # a second stall, in another slice
    rates = slices.step_slice_rates(bounds, TOKENS)
    assert slices.median(rates) == pytest.approx(TOKENS / STEP_S, rel=1e-9)


@pytest.mark.parametrize("every_ms", [1.0, 3.436, 10.0])
def test_a_cost_in_every_step_moves_the_median_by_its_full_share(every_ms):
    rates = slices.step_slice_rates(_bounds(147, every=every_ms / 1e3), TOKENS)
    want = TOKENS / (STEP_S + every_ms / 1e3)
    assert slices.median(rates) == pytest.approx(want, rel=1e-9)
    whole = 147 * TOKENS / (147 * (STEP_S + every_ms / 1e3))
    assert slices.stall_share(whole, slices.median(rates)) == pytest.approx(0, abs=1e-9)


def test_too_few_steps_give_fewer_slices_and_none_gives_nothing():
    assert len(slices.step_slice_rates(_bounds(4), TOKENS)) == 4
    assert slices.step_slice_rates([5.0], TOKENS) == []


def _token_stamps(rate, start, end, freeze=None):
    """Tokens at an even rate; inside ``freeze`` = (from, to) none arrives
    and the held tokens all arrive at its end."""
    t = np.arange(start, end, 1.0 / rate)
    if freeze:
        lo, hi = freeze
        t = np.where((t >= lo) & (t < hi), hi, t)
    return t.tolist()


def test_spans_count_each_token_once():
    stamps = _token_stamps(800.0, 10.0, 60.0)
    rates = slices.span_slice_rates(stamps, 10.0, 60.0)
    assert len(rates) == 10 and sum(r * 5.0 for r in rates) == len(stamps)
    assert rates == pytest.approx([800.0] * 10, rel=2e-3)
    # stamps outside the window are not counted
    assert slices.span_slice_rates([1.0, 9.99, 60.01] + stamps, 10.0, 60.0) == rates


@pytest.mark.parametrize("freeze", [(11.0, 12.0), (33.0, 34.5), (58.0, 59.0)])
def test_a_freeze_inside_one_span_leaves_the_serving_median(freeze):
    steady = slices.median(slices.span_slice_rates(
        _token_stamps(800.0, 10.0, 60.0), 10.0, 60.0))
    rates = slices.span_slice_rates(
        _token_stamps(800.0, 10.0, 60.0, freeze), 10.0, 60.0)
    assert slices.median(rates) == pytest.approx(steady, rel=2e-3)


def test_a_freeze_that_loses_tokens_is_seen_by_the_whole_window_rate_only():
    stamps = [t for t in _token_stamps(800.0, 10.0, 60.0)
              if not 33.0 <= t < 34.5]  # 1.5 s in which nothing was made
    rates = slices.span_slice_rates(stamps, 10.0, 60.0)
    assert slices.median(rates) == pytest.approx(800.0, rel=2e-3)
    assert len(stamps) / 50.0 == pytest.approx(800.0 * (1 - 1.5 / 50), rel=2e-3)


def test_a_slower_token_rate_moves_the_serving_median_in_full():
    rates = slices.span_slice_rates(_token_stamps(760.0, 0.0, 50.0), 0.0, 50.0)
    assert slices.median(rates) == pytest.approx(760.0, rel=2e-3)
