"""The program's ``oim.*`` annotations as the benchmark reads them:
``readers/idle_by_span.py`` (idle gaps cut at the annotations' edges, each
piece to the innermost annotation of the thread that feeds the device) and
``readers/span_ms.py`` on hand-made traces, the metric files against the
names the engine really opens, and a traced tiny serving cell whose host
plane must hold the engine's phases."""

import json
import os
import re

import pytest

import benchmark_tiny as tiny
from benchmarks import common, reduce, run
from benchmarks.readers import idle_by_span, span_ms, trace_idle

REPO = tiny.REPO
MS = 1_000_000
IDLE_METRICS = ["idle_step_roundtrip", "idle_emit", "idle_admit",
                "idle_unannotated"]


def _spec(name):
    with open(os.path.join(REPO, "benchmarks", "metrics", f"{name}.json")) as f:
        return json.load(f)


def hand_trace(*threads, device=True, window=True):
    """Ops [10,30) [25,40) [60,70) ms in a window of 0-100 ms: idle 0-10,
    40-60, 70-100. Each of ``threads`` is one host line's events in ms."""
    lines = [{"name": "python3", "events": [
        ["bench.window", 0, 100 * MS]] if window else []}]
    lines += [{"name": "python3",
               "events": [[n, round(s * MS), round(d * MS)]
                          for n, s, d in events]}
              for events in threads]
    planes = [{"name": "/host:CPU", "lines": [ln for ln in lines if ln["events"]]}]
    if device:
        planes.insert(0, {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", 10 * MS, 20 * MS], ["copy.2", 25 * MS, 15 * MS],
                ["fusion.1", 60 * MS, 10 * MS]]}]})
    return {"planes": planes}


def shares(trace, *names):
    """Per cent of the window idle under each of ``names`` (None: under no
    annotation), each through ``read`` on one shared result."""
    result = {"trace": trace}
    return [idle_by_span.read(
        result, {"spans": None if n is None else [n]}) for n in names]


# The thread whose annotations count is the one that dispatches the step:
# a launch while the device is busy (12-13 ms) marks it and charges nothing.
LAUNCH = ("oim.serve.dispatch", 12, 1)
ENGINE = [LAUNCH, ("oim.serve.admit", 38, 30), ("oim.serve.prefill", 39, 22),
          ("oim.serve.fetch", 75, 5)]


@pytest.mark.parametrize("threads,names,want", [
    # one annotation over a whole gap, and over part of another
    ([[LAUNCH, ("oim.serve.fetch", 38, 24), ("oim.serve.fetch", 75, 5)]],
     ["oim.serve.fetch", "oim.serve.dispatch", None], [25.0, 0.0, 35.0]),
    # a gap (40-60) across three in a row: cut at their edges
    ([[("oim.serve.fetch", 35, 8), ("oim.serve.emit", 43, 9),
       ("oim.serve.dispatch", 52, 10)]],
     ["oim.serve.fetch", "oim.serve.emit", "oim.serve.dispatch", None],
     [3.0, 9.0, 8.0, 40.0]),
    # ... where reduce.idle_gaps, which wants one cover, sees none of it
    # nested: the innermost takes its part, the outer one the rest
    ([ENGINE], ["oim.serve.prefill", "oim.serve.admit", "oim.serve.fetch", None],
     [20.0, 0.0, 5.0, 35.0]),
    ([[LAUNCH, ("oim.serve.admit", 38, 30), ("oim.serve.prefill", 45, 5)]],
     ["oim.serve.prefill", "oim.serve.admit", None], [5.0, 15.0, 40.0]),
    # another thread's long span covers everything: it neither covers ...
    ([ENGINE, [("oim.serve.generate", 0, 100)]],
     ["oim.serve.prefill", "oim.serve.fetch", "oim.serve.generate", None],
     [20.0, 5.0, None, 35.0]),
    # ... nor do its short ones cut the engine's phase, however many
    ([ENGINE, [("oim.server:Heartbeat", 45 + i, 0.5) for i in range(9)]],
     ["oim.serve.prefill", "oim.server:Heartbeat", None], [20.0, None, 35.0]),
    # an annotation that starts before the window counts inside it only
    ([[("oim.serve.wait", -50, 58), ("oim.serve.fetch", 8, 1), LAUNCH]],
     ["oim.serve.wait", "oim.serve.fetch", None], [8.0, 1.0, 51.0]),
    # annotations there, but none of those asked for: nothing to read
    ([ENGINE], ["oim.serve.emit"], [None]),
    # no thread launched a step under a name: no thread's nesting to go by
    ([[("oim.serve.fetch", 38, 24)], [("oim.feeder.window", 0, 100)]],
     ["oim.serve.fetch", "oim.feeder.window", None], [None, None, None]),
], ids=["one-cover", "three-in-a-row", "nested-prefill", "nested-inside-gap",
        "other-thread-long", "other-thread-many-short", "clipped-to-window",
        "name-not-opened", "no-launching-thread"])
def test_idle_is_cut_at_the_edges_and_charged_to_the_innermost(
        threads, names, want):
    got = shares(hand_trace(*threads), *names)
    assert got == [pytest.approx(w) if w is not None else None for w in want]


def test_a_gap_across_three_annotations_is_unannotated_to_the_ledgers_reader():
    """Why this reader exists: ``reduce.idle_gaps`` charges a gap to an
    annotation that covers the WHOLE gap, so flat phases in a row leave it
    ``unannotated``; ``idle_by_span`` names all of it."""
    trace = hand_trace([("oim.serve.fetch", 35, 8), ("oim.serve.emit", 43, 9),
                        ("oim.serve.dispatch", 52, 10)])
    assert dict(reduce.idle_gaps(trace)) == {"unannotated": pytest.approx(0.060)}
    assert sum(shares(trace, "oim.serve.fetch", "oim.serve.emit",
                      "oim.serve.dispatch")) == pytest.approx(20.0)


@pytest.mark.parametrize("trace", [
    None,                                            # untraced run
    hand_trace(ENGINE, device=False),                # the CPU rehearsal
    hand_trace(),                                    # the parent: no oim.*
    hand_trace([("bench.other", 0, 50)]),
    hand_trace([("oim.feeder.window", 0, 50), ("oim.stage", 50, 20)]),
], ids=["no-trace", "no-device-plane", "no-annotation", "only-bench-events",
        "no-launching-thread"])
@pytest.mark.parametrize("metric", IDLE_METRICS + ["emit_ms"])
def test_nothing_to_read_returns_nothing(trace, metric, capsys):
    spec = _spec(metric)
    reader = common.plugin(REPO, "readers", spec["reader"])
    result = {} if trace is None else {"trace": trace}
    assert reader.read(result, spec["args"]) is None
    assert "idle by span" not in capsys.readouterr().out


def test_the_shares_add_up_to_the_idle_share_and_are_printed_once(capsys):
    """The four metrics' files through their reader on one result: with
    what lies under other names (here ``oim.serve.wait``) they are
    ``device_idle`` exactly, and the split is one line of the log."""
    trace = hand_trace([
        ("oim.serve.wait", 0, 4), ("oim.serve.admit", 4, 5),
        ("oim.serve.map", 5, 1), ("oim.serve.prefill", 6, 2),
        ("oim.serve.sync", 8, 0.5), ("oim.serve.upload", 9.2, 0.3),
        ("oim.serve.dispatch", 9.5, 1), ("oim.serve.fetch", 10.5, 30.5),
        ("oim.serve.emit", 41.5, 8), ("oim.serve.admit", 50, 1),
        ("oim.serve.upload", 51, 3), ("oim.serve.dispatch", 54, 7),
        ("oim.serve.fetch", 61, 11), ("oim.serve.emit", 72.5, 20)])
    result = {"trace": trace}
    got = {}
    for metric in IDLE_METRICS:
        spec = _spec(metric)
        assert spec["reader"] == "idle_by_span"
        got[metric] = idle_by_span.read(result, spec["args"])
    assert got == {
        # dispatch 9.5-10, 54-60 and fetch 40-41, 70-72
        "idle_step_roundtrip": pytest.approx(0.5 + 6.0 + 1.0 + 2.0),
        "idle_emit": pytest.approx(8.0 + 20.0),       # 41.5-49.5, 72.5-92.5
        "idle_admit": pytest.approx(5.0 + 0.3 + 1.0 + 3.0),
        "idle_unannotated": pytest.approx(0.2 + 0.5 + 0.5 + 0.5 + 7.5)}
    wait = idle_by_span.read(result, {"spans": ["oim.serve.wait"]})
    assert wait == pytest.approx(4.0)
    assert sum(got.values()) + wait == pytest.approx(
        trace_idle.read(result, {}))
    out = capsys.readouterr().out
    assert out.count("[bench] idle by span") == 1
    line = next(ln for ln in out.splitlines() if "idle by span" in ln)
    assert "window 0.100000 s, idle 0.060000 s" in line
    # the finer split, the two sides of the step among it, is in the line
    for name, seconds in (("oim.serve.emit", 0.028), ("oim.serve.prefill", 0.002),
                          ("oim.serve.sync", 0.0005), ("oim.serve.fetch", 0.003),
                          ("oim.serve.dispatch", 0.0065), ("unannotated", 0.0092)):
        assert f"{name} {seconds:.6f}" in line


@pytest.mark.parametrize("notes,want", [
    ([("a", 0, 10)], [(0, 10, "a")]),
    ([("a", 0, 10), ("b", 10, 20)], [(0, 10, "a"), (10, 20, "b")]),
    ([("a", 0, 10), ("b", 12, 20)], [(0, 10, "a"), (12, 20, "b")]),
    ([("a", 0, 10), ("b", 2, 5)], [(0, 2, "a"), (2, 5, "b"), (5, 10, "a")]),
    ([("a", 0, 10), ("b", 2, 8), ("c", 3, 4), ("d", 4, 6)],
     [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "d"), (6, 8, "b"),
      (8, 10, "a")]),
    ([("a", 0, 10), ("b", 0, 10)], [(0, 10, "b")]),     # same extent: the later
    ([("a", 0, 10), ("b", 5, 5)], [(0, 5, "a"), (5, 10, "a")]),  # empty span
    ([("b", 5, 12), ("a", 0, 10)], [(0, 5, "a"), (5, 12, "b")]),  # not nested
    ([("a", -10, -2), ("b", -8, -4)],                   # moved before zero
     [(-10, -8, "a"), (-8, -4, "b"), (-4, -2, "a")]),
    ([], []),
], ids=["one", "adjacent", "apart", "nested", "deep", "same-extent", "empty",
        "improper", "negative", "none"])
def test_innermost_segments(notes, want):
    got = idle_by_span.innermost(notes)
    assert got == want
    assert all(a < b for a, b, _ in got)
    assert all(x[1] <= y[0] for x, y in zip(got, got[1:]))


def stepping_trace(skew_ms, first_launch=True):
    """Three decode steps of 20 ms as they really lie (host clock, ms):
    launched 0.5, 0.9 and 0.4 ms into their dispatch, their tokens on the
    host 0.5, 0.6 and 0.9 ms after they end; the device plane written
    ``skew_ms`` EARLIER than that, as the profiler did on the chip."""
    host, ops, at = [], [], 8.0
    for launch, wake in ((0.5, 0.5), (0.9, 0.6), (0.4, 0.9)):
        start = at + launch
        host += [("oim.serve.dispatch", at, 1.0),
                 ("oim.serve.fetch", at + 1.0, start + 20 + wake - at - 1.0),
                 ("oim.serve.emit", start + 20 + wake, 2.0),
                 ("oim.serve.admit", start + 23 + wake, 0.1)]
        ops.append(["jit_step(7)", round((start - skew_ms) * MS), 20 * MS])
        at = start + 23.1 + wake
    if not first_launch:  # the profile opened after the first launch
        host = host[1:]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["fusion", s, d] for _, s, d in ops]},
            {"name": "XLA Modules", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [["bench.window", -20 * MS, 140 * MS]]},
            {"name": "engine", "events": [
                [n, round(s * MS), round(d * MS)] for n, s, d in host]}]}]}


@pytest.mark.parametrize("skew_ms", [3.0, 0.0, -5.0, 9.5])
def test_the_planes_are_aligned_by_cause_and_effect(skew_ms, capsys):
    """Whatever the profiler's two clocks differ by, the split is the same:
    the annotations are moved by the middle of what cause (no step before
    its launch opens: the tightest 0.4 ms) and effect (no token before its
    step ends: 0.5 ms) allow, 0.05 ms from the truth here."""
    trace = stepping_trace(skew_ms)
    notes = idle_by_span.feeding_thread(trace)
    least, most = idle_by_span.clock_shift(trace, notes)
    assert (least / MS, most / MS) == (pytest.approx(skew_ms - 0.4),
                                       pytest.approx(skew_ms + 0.5))
    got = shares(trace, "oim.serve.dispatch", "oim.serve.fetch",
                 "oim.serve.emit", "oim.serve.admit", None)
    # per step: launch + 0.05 under dispatch, wake - 0.05 under fetch, all
    # of emit, the 1 ms after it under no name, all of admit; the window's
    # 140 ms less 3 x 20 of steps is idle, the rest of it at the edges
    want = [0.5 + 0.9 + 0.4 + 0.15, 0.5 + 0.6 + 0.9 - 0.15, 6.0, 0.3]
    assert got[:4] == [pytest.approx(100 * w / 140) for w in want]
    assert sum(got) == pytest.approx(100 * (140 - 60) / 140)
    assert (f"device clock behind the host's by {skew_ms - 0.4:.3f} to "
            f"{skew_ms + 0.5:.3f} ms") in capsys.readouterr().out


def test_a_step_whose_launch_the_profile_cut_does_not_set_the_shift():
    """Its nearest ``dispatch`` is the next step's, 24 ms on: a clock that
    far behind contradicts the median await pair (3.6 ms), so the pair is
    left out."""
    trace = stepping_trace(3.0, first_launch=False)
    notes = idle_by_span.feeding_thread(trace)
    least, most = idle_by_span.clock_shift(trace, notes)
    assert (least / MS, most / MS) == (pytest.approx(2.6), pytest.approx(3.5))


def test_an_await_pair_below_the_median_launch_pair_is_left_out():
    """Chip call D1 had one: a fetch that seemed to close 10 ms BEFORE its
    step ended, where every launch pair said the clock is 1 ms behind.
    Here the second step's own fetch is gone and one that closes 10 ms
    before its end stands in its place."""
    trace = stepping_trace(3.0)
    events = trace["planes"][1]["lines"][1]["events"]
    fetches = [e for e in events if e[0] == "oim.serve.fetch"]
    start, dur = fetches[1][1:]
    fetches[1][1:] = [start, dur - round(10.6 * MS)]
    notes = idle_by_span.feeding_thread(trace)
    least, most = idle_by_span.clock_shift(trace, notes)
    assert (least / MS, most / MS) == (pytest.approx(2.6), pytest.approx(3.5))


def _engine_events(trace):
    return trace["planes"][1]["lines"][1]["events"]


def _no_such_run(trace):
    for event in trace["planes"][0]["lines"][1]["events"]:
        event[0] = "jit_other(7)"


def _no_await_annotation(trace):
    _engine_events(trace)[:] = [e for e in _engine_events(trace)
                                if e[0] != "oim.serve.fetch"]


def _every_pair_contradicts(trace):
    """Every launch opens 8 ms late: 10 ms after its step seems to start,
    where the await pairs say the clock is 3.6 ms behind at most."""
    for event in _engine_events(trace):
        if event[0] == "oim.serve.dispatch":
            event[1] += 8 * MS


@pytest.mark.parametrize("spoil", [
    _no_such_run, _no_await_annotation, _every_pair_contradicts])
def test_without_runs_annotations_or_a_pair_left_nothing_is_moved(spoil, capsys):
    """A profile that cannot be aligned is read as the profiler wrote it
    and says so; it must not stop a traced run."""
    trace = stepping_trace(3.0)
    spoil(trace)
    notes = idle_by_span.feeding_thread(trace)
    assert idle_by_span.clock_shift(trace, notes) is None
    unmoved = idle_by_span.read({"trace": trace}, {"spans": ["oim.serve.emit"]})
    assert "not aligned" in capsys.readouterr().out
    # as written the next step seems to start 3 ms early, its launch + 0.1
    # ms into the emit before it: idle under emit 1.0, 0.5 and the last's 2
    assert unmoved == pytest.approx(100 * 3.5 / 140)


def test_no_launch_annotation_no_shift():
    trace = stepping_trace(3.0)
    notes = [n for n in idle_by_span.feeding_thread(trace)
             if n[0] != "oim.serve.dispatch"]
    assert notes and idle_by_span.clock_shift(trace, notes) is None


def test_the_feeding_thread_is_the_one_that_launches_the_step():
    """Not the one with the most ``oim.*`` events: every span of the
    program is one since the bridge, and a busy server thread has more."""
    busy = [("oim.server:Generate", i, 0.5) for i in range(12)]
    trace = hand_trace([("oim.serve.generate", 0, 100)], busy, ENGINE,
                       [("bench.x", 0, 1), ("oim.router.generate", 0, 100)])
    assert idle_by_span.feeding_thread(trace) == [
        (n, s * MS, (s + d) * MS) for n, s, d in ENGINE]
    assert idle_by_span.feeding_thread(hand_trace(busy)) == []


@pytest.mark.parametrize("threads,span,want", [
    ([[("oim.serve.emit", 41, 1), ("oim.serve.emit", 72, 2),
       ("oim.serve.emit", 93, 6)]], "oim.serve.emit", 2.0),
    # any thread; a name that only starts alike is another name
    ([[("oim.serve.emit", 41, 1)], [("oim.serve.emit", 50, 3),
                                    ("oim.serve.emitted", 60, 30)]],
     "oim.serve.emit", 2.0),
    # only those that lie inside the window
    ([[("oim.serve.emit", -5, 10), ("oim.serve.emit", 95, 10),
       ("oim.serve.emit", 50, 0.25)]], "oim.serve.emit", 0.25),
    ([[("oim.serve.fetch", 41, 1)]], "oim.serve.emit", None),
], ids=["median", "exact-name-any-thread", "inside-the-window", "absent"])
def test_span_ms_is_the_median_length_inside_the_window(threads, span, want):
    got = span_ms.read({"trace": hand_trace(*threads)}, {"span": span})
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_recorded_chip_trace_with_phases_laid_over_it():
    """A cut of a real v5e trace (PR 24, no annotation in it): nothing to
    read as it is; with phases laid back to back over its window every
    idle nanosecond is charged, and the pieces sum to ``trace_idle``."""
    with open(os.path.join(REPO, "benchmarks", "reduce_recorded.json")) as f:
        trace = json.load(f)["trace"]
    assert shares(trace, None, "oim.serve.fetch") == [None, None]
    lo, hi = reduce.window(trace)
    # launches and no fetch: nothing to align by, the phases stay where put
    names = ["oim.serve.dispatch", "oim.serve.emit", "oim.serve.sync"]
    step = (hi - lo) // 300
    trace["planes"].append({"name": "/host:CPU", "lines": [
        {"name": "engine", "events": [
            [names[i % 3], lo + i * step, step] for i in range(300)]}]})
    got = shares(trace, *names, None)
    covered = 300 * step
    assert sum(got) == pytest.approx(trace_idle.read({"trace": trace}, {}))
    assert got[3] <= 100.0 * (hi - lo - covered) / (hi - lo) + 1e-9
    assert all(g > 0 for g in got[:3])


def _engine_names():
    with open(os.path.join(REPO, "oim_tpu", "serve", "engine.py")) as f:
        src = f.read()
    return {"oim." + n for n in re.findall(
        r'tracing\.(?:annotate|start_span)\(\s*"([a-z_.]+)"', src)}


@pytest.mark.parametrize("name", [
    "oim.serve.wait", "oim.serve.admit", "oim.serve.map", "oim.serve.prefill",
    "oim.serve.draft_prefill", "oim.serve.sync", "oim.serve.upload",
    "oim.serve.dispatch", "oim.serve.fetch", "oim.serve.emit"])
def test_the_engine_opens_every_phase_and_all_but_wait_are_read(name):
    """The names are the yardstick's as much as the program's: a phase
    renamed in ``serve/engine.py`` alone would read as 0 idle under it."""
    assert name in _engine_names()
    read = {n for m in IDLE_METRICS for n in (_spec(m)["args"]["spans"] or ())}
    assert (name in read) is (name != "oim.serve.wait")


def test_no_metric_reads_a_name_the_engine_does_not_open():
    read = {n for m in IDLE_METRICS for n in (_spec(m)["args"]["spans"] or ())}
    assert read | {_spec("emit_ms")["args"]["span"]} <= _engine_names()
    assert _spec("idle_unannotated")["args"] == {"spans": None}


# -- a traced tiny cell: the engine's phases in the host plane ---------------


CELL = "tiny-moe.backlog-long"


@pytest.fixture(scope="module")
def traced_batch(tmp_path_factory):
    """One traced pass of the tiny expert cell on the CPU; the trace as
    ``reduce.load`` handed it to the readers, and the result line. Its
    backlog is a new mix, configuration and cell of 768 requests on a
    queue that holds them, several times what the pass can serve however
    fast this box is: the queue must not drain inside the profile."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench")))
    deep = {**tiny.CONFIGS["tiny-moe"],
            "serve": {**tiny.CONFIGS["tiny-moe"]["serve"], "queue_depth": 1024}}
    for kind, name, body in (
            ("configs", "tiny-moe-deep", deep),
            ("traffic", "tiny-backlog-long",
             {**tiny.TRAFFIC["tiny-backlog"], "requests": 768})):
        with open(os.path.join(root, "benchmarks", kind, f"{name}.json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    bench = common.load_json(path)
    bench["configs"].append({
        "name": "tiny-moe-deep", "source": "tests", "reduced": [],
        "why": "tiny", "file": "benchmarks/configs/tiny-moe-deep.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-moe-deep", "traffic": "tiny-backlog-long",
        "chips": 1, "why": "tiny"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "tiny-moe.batch" in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    kept = {}
    load = reduce.load

    def keeping(path, *a, **k):
        kept["trace"] = load(path, *a, **k)
        return kept["trace"]

    reduce.load = keeping
    try:
        import contextlib
        import io

        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            assert run.main(["--workload", CELL, "--seed", "9",
                             "--seconds", "1.5", "--trace", "1"],
                            platform="cpu", root=root) == 0
    finally:
        reduce.load = load
    kept["line"] = json.loads(out.getvalue().strip().splitlines()[-1])
    kept["root"] = root
    return kept


def _engine_line(trace):
    notes = idle_by_span.feeding_thread(trace)
    return {name: sorted((s, e) for n, s, e in notes if n == name)
            for name in {n for n, _, _ in notes}}


@pytest.mark.parametrize("name", [
    "oim.serve.admit", "oim.serve.map", "oim.serve.prefill", "oim.serve.sync",
    "oim.serve.upload", "oim.serve.dispatch", "oim.serve.fetch",
    "oim.serve.emit"])
def test_traced_tiny_cell_holds_the_phase(traced_batch, name):
    """A backlog on four slots: every phase but ``wait`` (the queue never
    empties) and the draft's (no draft model) is reached, all on one host
    line, the engine's thread."""
    assert traced_batch["line"]["correct"] is True
    assert len(_engine_line(traced_batch["trace"])[name]) >= 2


def test_traced_tiny_cell_nests_prefill_in_admit_and_keeps_the_step_flat(
        traced_batch):
    line = _engine_line(traced_batch["trace"])
    assert "oim.serve.wait" not in line and "oim.serve.draft_prefill" not in line

    # (an annotation open when the profile starts or stops is not in it,
    # so an admission cut by an edge shows its inner phases alone)
    admits = line["oim.serve.admit"]
    for inner in ("oim.serve.map", "oim.serve.prefill", "oim.serve.sync"):
        whole = [(s, e) for s, e in line[inner]
                 if admits[0][0] <= s and e <= admits[-1][1]]
        assert whole and all(any(a <= s and e <= b for a, b in admits)
                             for s, e in whole), inner
    # the decode round's phases follow one another and never overlap
    flat = sorted((s, e, n) for n in (
        "oim.serve.upload", "oim.serve.dispatch", "oim.serve.fetch",
        "oim.serve.emit") for s, e in line[n])
    assert all(x[1] <= y[0] for x, y in zip(flat, flat[1:]))
    order = [n.rsplit(".", 1)[1] for _, _, n in flat]
    first = order.index("upload")
    assert order[first:first + 5] == ["upload", "dispatch", "fetch", "emit",
                                      "upload"]
    # and none of them inside an admission (no chunked prefill here)
    assert not any(a <= s and e <= b for s, e, _ in flat
                   for a, b in line["oim.serve.admit"])


def test_traced_tiny_cell_reads_nothing_without_a_device_and_all_with_one(
        traced_batch, capsys):
    """On the CPU there is no device plane, so the line leaves the new
    metrics out. Given one (an operation inside every token fetch, as a
    decode step lies on the chip) every new metric of the cell reads, and
    with ``oim.serve.wait`` they are the idle share."""
    new = {f"{m}.batch" for m in IDLE_METRICS + ["emit_ms"]}
    assert "itl_p50_ms.batch" in traced_batch["line"]["metrics"]
    assert not new & set(traced_batch["line"]["metrics"])
    line = _engine_line(traced_batch["trace"])
    ops = [["step", s + (e - s) // 4, (e - s) // 2]
           for s, e in line["oim.serve.fetch"]]
    # The window: from the first to the last phase the profile holds (the
    # tiny backlog may drain inside the profile, and the wait that follows
    # is still open when it stops: not in it).
    lo = min(s for spans in line.values() for s, _ in spans)
    hi = max(e for spans in line.values() for _, e in spans)
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
        *({"name": p["name"], "lines": [
            {"name": ln["name"], "events": [
                [n, lo, hi - lo] if n == reduce.WINDOW_ANNOTATION else [n, s, d]
                for n, s, d in ln["events"]]} for ln in p["lines"]]}
          for p in traced_batch["trace"]["planes"])]}
    bench = common.load_json(os.path.join(traced_batch["root"], "BENCHMARK.json"))
    result = {"trace": trace, "stats": {}, "device": {"platform": "cpu"}}
    got = {}
    for name in sorted(new):
        spec = common.metric_spec(traced_batch["root"], name)
        got[name] = common.plugin(traced_batch["root"], "readers",
                                  spec["reader"]).read(result, spec["args"])
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m["workloads"]} >= new
    assert got["emit_ms.batch"] > 0
    assert got["idle_step_roundtrip.batch"] > 0 and got["idle_emit.batch"] > 0
    idle = trace_idle.read(result, {})
    other = sum(idle_by_span.read(result, {"spans": [n]}) or 0.0
                for n in ("oim.serve.wait",))
    assert sum(v for k, v in got.items() if k != "emit_ms.batch") + other \
        == pytest.approx(idle)
    # the loop's top and two statements a step are all that no name covers
    assert got["idle_unannotated.batch"] < 0.25 * idle
    assert capsys.readouterr().out.count("idle by span") == 1
