"""One general traffic generator: a mix is a data file of parameters
(``traffic/<mix>.json``), this file turns it and a seed into the requests
or the records of a run.

Every seed gets the SAME multiset of sizes and of gaps between arrivals —
the quantile grid of the mix's distributions — in another order, so that
two seeds differ in order and content, not in the amount of work. Same
seed, same schedule, token for token.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    due: float            # seconds after the window opens (open loop); 0 otherwise
    # (a backlog is offered whole, ``pre_roll_s`` before the window)
    prompt: np.ndarray    # int32 token ids
    max_new: int


def lognormal_grid(n: int, spec: dict) -> np.ndarray:
    """The n-point quantile grid of a clipped log-normal, as whole numbers:
    spec = {"median", "sigma", "min", "max"}."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def exponential_grid(n: int, rate: float) -> np.ndarray:
    """The n-point quantile grid of the gaps of a Poisson process."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def _cycle(n: int, mix: dict):
    """The mix's own base cycle: n (gap, prompt length, output length)
    triples in the order the mix's ``schedule_seed`` fixes."""
    s = int(mix["schedule_seed"])
    prompts = _rng(s, 1).permutation(lognormal_grid(n, mix["prompt_tokens"]))
    outputs = _rng(s, 2).permutation(lognormal_grid(n, mix["output_tokens"]))
    return prompts, outputs


def _make(lengths, outs, due, seed: int, stream: int, vocab: int):
    toks = _rng(seed, stream)
    return [Request(float(d), toks.integers(0, vocab, int(p), dtype=np.int32),
                    int(o)) for p, o, d in zip(lengths, outs, due)]


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list[Request]:
    """Arrivals on a schedule whatever the system does. The schedule is a
    CYCLE of n = rate x seconds requests lasting ``seconds``: gaps from the
    exponential grid, lengths from the log-normal grids, in the order the
    mix fixes. The run's seed turns the cycle (it starts at another request)
    and draws every prompt's tokens; the last ``pre_roll_s`` seconds of the
    turned cycle are also sent BEFORE the window opens (due < 0, tokens of
    their own), so that the window is one whole turn of a cycle already in
    steady state and every request meets the same predecessors whatever
    the seed."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    prompts, outputs = _cycle(n, mix)
    gaps = _rng(int(mix["schedule_seed"]), 0).permutation(
        exponential_grid(n, rate))
    gaps = gaps * (seconds / gaps.sum())
    turn = (np.arange(n) + int(_rng(seed, 5).integers(n))) % n
    prompts, outputs, gaps = prompts[turn], outputs[turn], gaps[turn]
    due = np.cumsum(gaps) - gaps  # request i leaves gaps[i] before i + 1
    early = due - seconds >= -float(mix.get("pre_roll_s", 0.0))
    return (_make(prompts[early], outputs[early], due[early] - seconds,
                  seed, 6, vocab)
            + _make(prompts, outputs, due, seed, 3, vocab))


def backlog(mix: dict, seed: int, vocab: int) -> list[Request]:
    """``requests`` requests all offered at once (more than a window
    finishes), served in this order: a CYCLE of ``block`` requests — the
    quantile grids of the mix's distributions, paired and ordered as the
    mix's ``schedule_seed`` fixes — repeated, and started at the request
    the run's seed picks. A block is a few seconds of work, so every slice
    of every window holds about the same work whatever the seed; the seed
    changes where the cycle starts and every token."""
    n, block = int(mix["requests"]), int(mix["block"])
    if n % block:
        raise SystemExit(f"backlog: {n} requests are not whole blocks of {block}")
    prompts, outputs = _cycle(block, mix)
    turn = (np.arange(n) + int(_rng(seed, 5).integers(block))) % block
    return _make(prompts[turn], outputs[turn], np.zeros(n), seed, 3, vocab)


def train_records(mix: dict, seed: int, vocab: int) -> np.ndarray:
    """[records, seq_len + 1] int32, every row its own random tokens."""
    span = int(mix["seq_len"]) + 1
    n = int(mix["volume_bytes"]) // (span * 4)
    return _rng(seed, 4).integers(0, vocab, (n, span), dtype=np.int32)


def percentile(values, p: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), p))


def samples_needed(p: float) -> int:
    """A percentile is reported where at least ten samples lie beyond it."""
    return int(math.ceil(round(10.0 / (1.0 - p / 100.0), 6)))
