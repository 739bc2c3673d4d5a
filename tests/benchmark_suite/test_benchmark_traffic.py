"""The traffic generator: same seed, same schedule; every seed the same
multiset of sizes and gaps; clipping; open-loop due times; percentiles."""

import json
import os

import numpy as np
import pytest

from benchmarks import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _mix(name):
    with open(os.path.join(REPO, "benchmarks", "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_same_seed_same_schedule(seed):
    mix = _mix("chat")
    a = traffic.open_loop(mix, seed, 20.0, 32768)
    b = traffic.open_loop(mix, seed, 20.0, 32768)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))


def test_seeds_turn_one_cycle():
    """Every seed sends the mix's own cycle, started at another request:
    same sizes, same gaps, same neighbours; only the tokens are new."""
    mix = _mix("chat")
    a = [r for r in traffic.open_loop(mix, 1, 30.0, 32768) if r.due >= 0]
    b = [r for r in traffic.open_loop(mix, 2, 30.0, 32768) if r.due >= 0]
    assert len(a) == len(b) == round(mix["rate_per_s"] * 30.0)
    la, lb = [len(r.prompt) for r in a], [len(r.prompt) for r in b]
    assert la != lb and any(la[k:] + la[:k] == lb for k in range(len(la)))
    oa, ob = [r.max_new for r in a], [r.max_new for r in b]
    k = next(k for k in range(len(la)) if la[k:] + la[:k] == lb)
    assert oa[k:] + oa[:k] == ob
    assert not np.array_equal(a[k].prompt, b[0].prompt)  # tokens of their own


def test_pre_roll_is_the_cycles_tail_before_the_window():
    mix = _mix("chat")
    reqs = traffic.open_loop(mix, 9, 30.0, 32768)
    pre = [r for r in reqs if r.due < 0]
    win = [r for r in reqs if r.due >= 0]
    assert pre and min(r.due for r in pre) >= -mix["pre_roll_s"]
    assert [len(r.prompt) for r in pre] == [len(r.prompt) for r in win[-len(pre):]]
    # but never the same tokens: the prefix cache must not know them
    assert not any(np.array_equal(p.prompt, w.prompt)
                   for p, w in zip(pre, win[-len(pre):]))
    assert [r.due for r in reqs] == sorted(r.due for r in reqs)


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_lengths_are_clipped_and_fit(name):
    mix = _mix(name)
    grid = traffic.lognormal_grid(500, mix["prompt_tokens"])
    assert grid.min() == mix["prompt_tokens"]["min"]
    assert grid.max() == mix["prompt_tokens"]["max"]
    assert abs(np.median(grid) - mix["prompt_tokens"]["median"]) <= 2
    out = traffic.lognormal_grid(500, mix["output_tokens"])
    assert out.min() >= mix["output_tokens"]["min"]
    assert out.max() <= mix["output_tokens"]["max"]


def test_open_loop_due_times():
    reqs = traffic.open_loop(_mix("chat"), 5, 40.0, 32768)
    due = np.array([r.due for r in reqs if r.due >= 0])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 40.0
    assert due[-1] > 35.0  # the schedule fills the window


def test_backlog_is_one_cycle_repeated():
    mix = _mix("batch")
    pool = traffic.backlog(mix, 3, 32000)
    assert len(pool) == mix["requests"] and all(r.due == 0.0 for r in pool)
    assert max(len(r.prompt) + r.max_new for r in pool) <= 2048
    pairs = [(len(r.prompt), r.max_new) for r in pool]
    block = mix["block"]
    assert all(pairs[i:i + block] == pairs[:block]
               for i in range(0, len(pairs), block))  # the same work, in order
    assert len({p for p, _ in pairs[:block]}) > block // 2  # a spread of sizes
    # but never the same tokens twice
    assert not np.array_equal(pool[0].prompt, pool[block].prompt)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_backlog_same_seed_same_requests_other_seed_turns_the_cycle(seed):
    mix = _mix("batch")
    a, b = traffic.backlog(mix, seed, 32000), traffic.backlog(mix, seed, 32000)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
    block = mix["block"]
    la = [(len(r.prompt), r.max_new) for r in a[:block]]
    others = [[(len(r.prompt), r.max_new) for r in
               traffic.backlog(mix, seed + k, 32000)[:block]] for k in (1, 2, 3)]
    assert any(o != la for o in others)  # another start of the cycle
    assert all(any(la[k:] + la[:k] == o for k in range(block)) for o in others)
    assert not np.array_equal(
        a[0].prompt[:8], traffic.backlog(mix, seed + 1, 32000)[0].prompt[:8])


def test_backlog_refuses_a_pool_that_is_not_whole_blocks():
    with pytest.raises(SystemExit):
        traffic.backlog(dict(_mix("batch"), requests=100), 1, 32000)


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_train_records_from_the_seed(seed):
    mix = _mix("pretrain-fed")
    rec = traffic.train_records(mix, seed, 32768)
    assert rec.shape == (4095, 4097) and rec.dtype == np.int32
    assert len({r.tobytes() for r in rec[:64]}) == 64
    assert np.array_equal(rec[:4], traffic.train_records(mix, seed, 32768)[:4])
    assert not np.array_equal(rec[:4], traffic.train_records(mix, seed + 1, 32768)[:4])


def test_another_seed_gives_other_tokens_in_the_open_loop():
    mix = _mix("chat")
    a, b = traffic.open_loop(mix, 1, 20.0, 32768), traffic.open_loop(mix, 2, 20.0, 32768)
    assert not any(len(x.prompt) == len(y.prompt) and np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, b))


@pytest.mark.parametrize("p,need", [(50, 20), (90, 100), (95, 200), (99, 1000)])
def test_percentile_sample_rule(p, need):
    assert traffic.samples_needed(p) == need
    values = np.arange(1, 101)
    assert traffic.percentile(values, 50) == 50.5
    assert traffic.percentile([1.0], p) == 1.0
