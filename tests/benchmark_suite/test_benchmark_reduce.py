"""The trace reduction on small traces: busy/idle union, per-module sums,
top operations, and idle gaps charged to the innermost ``oim.`` annotation
that covers them (``unannotated`` otherwise)."""

import json
import os

import pytest

from benchmarks import reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "reduce_recorded.json")
MS = 1_000_000


def hand_trace(annotated: bool):
    """Window 0-100 ms. Ops: [10,30) [25,40) overlap -> busy 30 ms;
    [60,70) -> 10 ms. Idle: 0-10, 40-60, 70-100."""
    host = [["bench.window", 0, 100 * MS]]
    if annotated:
        host += [["oim.admit", 38 * MS, 30 * MS],     # covers gap 40-60
                 ["oim.admit.prefill", 39 * MS, 22 * MS],  # innermost cover
                 ["oim.fetch", 75 * MS, 5 * MS]]       # inside gap 70-100 only
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", 10 * MS, 20 * MS], ["copy.2", 25 * MS, 15 * MS],
                ["fusion.1", 60 * MS, 10 * MS]]},
            {"name": "XLA Modules", "events": [
                ["jit_step(123)", 10 * MS, 30 * MS],
                ["jit_prefill(9)", 60 * MS, 10 * MS]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]}]}


def test_busy_is_the_union_inside_the_window():
    b = tr.busy(hand_trace(False))
    assert b == {"busy_s": pytest.approx(0.040), "window_s": pytest.approx(0.100),
                 "chips": 1}


def test_window_falls_back_to_the_device_extent():
    trace = hand_trace(False)
    trace["planes"][1]["lines"][0]["events"] = []
    assert tr.window(trace) == (10 * MS, 70 * MS)
    assert tr.busy(trace)["window_s"] == pytest.approx(0.060)


@pytest.mark.parametrize("pattern,total,count", [
    (r"^jit_step(\(|$)", 0.030, 1), (r"^jit_prefill(\(|$)", 0.010, 1),
    (r"^jit_", 0.040, 2), (r"^jit_step_fn", 0.0, 0)])
def test_module_sums(pattern, total, count):
    runs = tr.module_durations(hand_trace(False), pattern)
    assert len(runs) == count and sum(runs) == pytest.approx(total)


def test_top_ops_sums_by_name():
    assert tr.top_ops(hand_trace(False)) == [
        ["fusion.1", pytest.approx(0.030)], ["copy.2", pytest.approx(0.015)]]


def test_gaps_without_annotations_are_unannotated():
    assert tr.idle_gaps(hand_trace(False)) == [
        ["unannotated", pytest.approx(0.060)]]


def test_gaps_go_to_the_innermost_covering_annotation():
    gaps = dict(tr.idle_gaps(hand_trace(True)))
    assert gaps == {"oim.admit.prefill": pytest.approx(0.020),
                    "unannotated": pytest.approx(0.040)}


def test_no_device_plane_is_an_error():
    trace = hand_trace(False)
    trace["planes"] = trace["planes"][1:]
    with pytest.raises(ValueError):
        tr.busy(trace)


def test_recorded_trace_reduces_consistently():
    """A cut of a real v5e trace of the chat cell (PR 24): the numbers the
    harness would print from it are pinned, so a change to the reduction
    shows."""
    with open(RECORDED) as f:
        fixture = json.load(f)
    trace, want = fixture["trace"], fixture["expect"]
    b = tr.busy(trace)
    assert b["busy_s"] == pytest.approx(want["busy_s"])
    assert b["window_s"] == pytest.approx(want["window_s"])
    assert 0 < b["busy_s"] <= b["window_s"]
    decode = tr.module_durations(trace, r"^jit_step(\(|$)")
    assert len(decode) == want["decode_runs"]
    assert sum(decode) == pytest.approx(want["decode_s"])
    idle = sum(s for _, s in tr.idle_gaps(trace, n=1000))
    assert idle == pytest.approx(b["window_s"] - b["busy_s"])
    assert [n for n, _ in tr.top_ops(trace, 3)] == want["top_ops"]
