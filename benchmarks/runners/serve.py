"""Serving runner: drives the program's ``ServeEngine`` in this process
through its public ``submit()`` / ``GenHandle.tokens()`` — the layer
``ServeService`` wraps — under an open-loop mix or a backlog offered whole.

The window and the clocks are the harness's: a request is timed from when
it was DUE (open loop), a token when this process received it. From the
program come only the engine, ``GenHandle.stats`` and, in a traced run, the
names of its XLA modules.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmarks import common, slices, traffic, weights

FIRST_TOKEN_GRACE_S = 20.0  # after the window: a request without a first
# token by then has failed. Streams still running then are cancelled.


class _Stream:
    """One request's life as the client saw it."""

    def __init__(self, index: int, req: traffic.Request):
        self.index, self.req = index, req
        self.sent = 0.0
        self.stamps: list[float] = []
        self.tokens: list[int] = []
        self.refused = ""
        self.handle = None
        self.done = threading.Event()

    def consume(self):
        try:
            for tok in self.handle.tokens(timeout=120.0):
                self.stamps.append(time.monotonic())
                self.tokens.append(tok)
        finally:
            self.done.set()

    @property
    def finished(self) -> bool:
        return (self.done.is_set() and self.handle is not None
                and self.handle.finish_reason == "length"
                and len(self.tokens) == self.req.max_new)


def _bucket(n: int, max_seq: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return min(b, max_seq)


def _submit(engine, stream: _Stream) -> bool:
    from oim_tpu.serve.engine import Draining, QueueFull

    stream.sent = time.monotonic()
    try:
        stream.handle = engine.submit(
            stream.req.prompt, max_new=stream.req.max_new, temperature=0.0,
            seed=0, eos=-1)
    except (QueueFull, Draining, ValueError) as err:
        stream.refused = type(err).__name__
        stream.done.set()
        return False
    threading.Thread(target=stream.consume, daemon=True,
                     name=f"bench-stream-{stream.index}").start()
    return True


def _warm_up(ctx, engine, requests, vocab: int, max_seq: int) -> None:
    """Every prefill bucket this cell's prompts fall into, and the decode
    program, once — with tokens of their own, so that the prefix store
    learns nothing about the window's prompts."""
    longest: dict[int, int] = {}
    for r in requests:
        b = _bucket(len(r.prompt), max_seq)
        longest[b] = max(longest.get(b, 0), len(r.prompt))
    rng = np.random.default_rng([int(ctx.seed), 9])
    for b, n in sorted(longest.items()):
        s = _Stream(-1, traffic.Request(
            0.0, rng.integers(0, vocab, n, dtype=np.int32), 2))
        if not _submit(engine, s) or not s.done.wait(1100.0) or not s.finished:
            raise SystemExit(f"warm-up of prefill bucket {b} failed: "
                             f"{s.refused or s.handle.finish_reason}")
        ctx.log("warmed", bucket=b, prompt=n)


def _open_loop(ctx, engine, requests, t_start: float) -> list[_Stream]:
    streams = []
    for i, req in enumerate(requests):  # due < 0: the pre-roll
        wait = t_start + req.due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        s = _Stream(i, req)
        _submit(engine, s)
        streams.append(s)
    left = t_start + ctx.seconds - time.monotonic()
    if left > 0:
        time.sleep(left)
    # Every request of the window gets the chance of a first token; what
    # still streams after that is cancelled (its gaps inside the window
    # are already counted).
    deadline = t_start + ctx.seconds + FIRST_TOKEN_GRACE_S
    for s in streams:
        while not s.stamps and not s.done.is_set() \
                and time.monotonic() < deadline:
            time.sleep(0.01)
    return streams


def _backlog(ctx, engine, requests, t_start: float) -> list[_Stream]:
    """Every request offered at once, ``pre_roll_s`` before the window
    opens: more than the window finishes, so the queue never empties."""
    streams = [_Stream(i, req) for i, req in enumerate(requests)]
    for s in streams:
        _submit(engine, s)
    left = t_start + ctx.seconds - time.monotonic()
    if left > 0:
        time.sleep(left)
    return streams


def _live_load(streams, lo: float, hi: float) -> tuple[float, float]:
    """Mean number of live rows and of live K/V positions (prompt plus
    tokens emitted so far) over [lo, hi], sampled 200 times."""
    rows, kv = [], []
    for t in np.linspace(lo, hi, 200):
        r = k = 0
        for s in streams:
            if s.stamps and s.stamps[0] <= t and not (
                    s.done.is_set() and s.stamps[-1] < t):
                r += 1
                k += len(s.req.prompt) + int(np.searchsorted(s.stamps, t))
        rows.append(r)
        kv.append(k)
    return float(np.mean(rows)), float(np.mean(kv))


def _check(ctx, model: dict, finished: list[_Stream], limits: dict) -> dict:
    """Served tokens against the float32 reference, after the engine is
    gone: the longest finished request and a seeded sample of the others;
    the numbers compared are the widest and the mean gap by which a served
    token's reference logit lies below the reference's best."""
    from benchmarks.reference import llama_like as ref

    if not finished:
        return {"ok": False, "numbers": {}, "sample": []}
    n_sample = int(ctx.traffic["check_requests"])
    by_len = sorted(finished, key=lambda s: -(len(s.req.prompt) + s.req.max_new))
    rest = by_len[1:]
    pick = np.random.default_rng([int(ctx.seed), 7]).permutation(len(rest))
    sample = [by_len[0]] + [rest[i] for i in pick[: n_sample - 1]]
    t = time.monotonic()
    gaps = np.concatenate([
        ref.served_gaps(ctx.seed, model, s.req.prompt.tolist(), s.tokens)
        for s in sample])
    ctx.log("reference ran", seconds=f"{time.monotonic() - t:.1f}",
            positions=[len(s.req.prompt) + s.req.max_new for s in sample])
    numbers = {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean())}
    ok = True
    for name, limit in limits.items():  # a number without a limit is only shown
        ctx.log("correct?", number=name, value=f"{numbers[name]:.6g}",
                limit=limit, tokens=len(gaps), requests=len(sample))
        ok = ok and numbers[name] <= limit
    ctx.log("also read", **{k: f"{v:.6g}" for k, v in numbers.items()
                            if k not in limits})
    return {"ok": ok, "numbers": numbers,
            "sample": [(s.req.prompt.tolist(), s.tokens) for s in sample]}


def run(ctx: common.Context) -> dict:
    import jax

    from oim_tpu.models import llama
    from oim_tpu.serve.engine import ServeEngine

    sizes = ctx.config["serve"]
    model = common.model_dict(ctx.config, "serve")
    pcfg = common.program_config(model)
    weights.check_against_program(model, jax.eval_shape(
        lambda k: llama.init(k, pcfg), jax.random.PRNGKey(0)))

    kind = ctx.traffic["kind"]
    if kind == "open_loop":
        requests = traffic.open_loop(ctx.traffic, ctx.seed, ctx.seconds,
                                     model["vocab"])
    elif kind == "backlog":
        requests = traffic.backlog(ctx.traffic, ctx.seed, model["vocab"])
    else:
        raise SystemExit(f"runners/serve.py cannot run traffic kind {kind!r}")
    worst = max(len(r.prompt) + r.max_new for r in requests)
    if worst > model["max_seq"]:
        raise SystemExit(f"a request of {worst} positions exceeds max_seq")

    params = weights.make_on_device(ctx.seed, model)
    jax.block_until_ready(params)
    ctx.log("weights on device", params=f"{sum(x.size for x in jax.tree.leaves(params)):,}")
    compiles = common.CompileCounter()
    engine = ServeEngine(
        params, pcfg, max_batch=sizes["max_batch"], max_seq=model["max_seq"],
        queue_depth=sizes["queue_depth"],
        kv_pool_tokens=sizes["kv_pool_tokens"], name="bench")
    del params
    _warm_up(ctx, engine, requests, model["vocab"], model["max_seq"])

    # The window opens ``pre_roll_s`` from now: until then the same traffic
    # runs unmeasured, so that the window starts in steady state (set-up).
    t_start = time.monotonic() + float(ctx.traffic.get("pre_roll_s", 0.0))
    setup_s = t_start - ctx.t0
    ctx.log("pre-roll starts; window opens at", setup_s=f"{setup_s:.2f}",
            requests=len(requests))
    compiles.start()
    trace_dir, trace_span = None, None
    tracer = None
    if ctx.trace:
        def profile():
            nonlocal trace_dir, trace_span
            trace_at, trace_for = common.trace_span(ctx)
            time.sleep(max(t_start + trace_at - time.monotonic(), 0))
            with common.traced(ctx) as d:
                lo = time.monotonic()
                time.sleep(trace_for)
                trace_span = (lo, time.monotonic())
            trace_dir = d

        tracer = threading.Thread(target=profile, name="bench-tracer")
        tracer.start()
    loop = _open_loop if kind == "open_loop" else _backlog
    streams = loop(ctx, engine, requests, t_start)
    t_end = t_start + ctx.seconds
    n_compiles = compiles.stop()
    if tracer is not None:
        tracer.join()

    for s in streams:  # cancel what still streams; then every stream ends
        if s.handle is not None and not s.done.is_set():
            s.handle.cancel()
    for s in streams:
        s.done.wait(60.0)
    pool = engine.pool_stats()
    peak = common.memory_peak_bytes()
    engine.stop(drain=False, timeout=60.0)

    # Offered and never admitted by the window's end (a backlog's tail) is
    # neither attempted nor failed; everything else that was sent is.
    sent = [s for s in streams if s.sent
            and (kind == "open_loop" or s.stamps or s.refused
                 or s.handle.finish_reason not in ("", "cancelled"))]
    finished = [s for s in sent if s.finished]
    # Failed: refused, ended for another reason than its length or the
    # harness's own cancel, or (open loop) no first token within the grace.
    failed = [s for s in sent if s.refused
              or (kind == "open_loop" and not s.stamps)
              or (s.handle is not None and s.done.is_set()
                  and s.handle.finish_reason not in ("length", "cancelled"))]
    timed = [s for s in sent if s.req.due >= 0]  # not the pre-roll
    ttft = [(s.stamps[0] - (t_start + s.req.due)) * 1e3
            for s in timed if s.stamps] if kind == "open_loop" else []
    # Gaps between one stream's tokens that END inside the window (the
    # pre-roll's own gaps are not measured), with the instant they ended.
    gaps = [(b - a, b) for s in sent for a, b in zip(s.stamps, s.stamps[1:])
            if t_start <= b <= t_end]
    itl = [g * 1e3 for g, _ in gaps]
    stamps = [t for s in sent for t in s.stamps if t_start <= t <= t_end]
    # The rate a user gets: every token of the window over all of its
    # time. The median of its ten slices stands beside it, per layer.
    rates = slices.span_slice_rates(stamps, t_start, t_end)
    whole = len(stamps) / ctx.seconds
    steady = slices.median(rates)
    stats = {
        "ttft_ms": ttft, "itl_ms": itl,
        "out_tokens_per_s": whole,
        "slice_median_tokens_per_s": steady,
        "stall_share": slices.stall_share(whole, steady) if steady else None,
        "gen_lateness_ms": [(s.sent - t_start - s.req.due) * 1e3
                            for s in timed] if kind == "open_loop" else [],
        "queue_wait_ms": [s.handle.stats["queue_wait_s"] * 1e3
                          for s in timed if s.handle is not None and s.stamps]
        if kind == "open_loop" else [],
    }
    if ttft:
        ctx.log("ttft raw", ms=[round(x, 1) for x in ttft])
        half = len(ttft) // 2
        ctx.log("backlog?",
                ttft_p50_first_half=f"{np.median(ttft[:half] or [0]):.0f}",
                ttft_p50_second_half=f"{np.median(ttft[half:] or [0]):.0f}",
                queue_wait_max=f"{max(stats['queue_wait_ms'] or [0]):.0f}",
                streaming_at_window_end=sum(
                    1 for s in sent if s.stamps and s.stamps[-1] > t_end - 0.5))
    ctx.log("longest token gaps", gaps=[
        (f"{g * 1e3:.0f}ms", f"at {at - ctx.t0:.2f}s")
        for g, at in sorted(gaps, reverse=True)[:5]])
    if itl:
        ctx.log("token gap percentiles, ms", **{
            f"p{p}": f"{np.percentile(itl, p):.1f}"
            for p in (50, 80, 90, 93, 94, 95, 96, 97, 99)})
    ctx.log("slices", tokens_per_s=[round(r, 1) for r in rates])
    ctx.log("window closed", sent=len(sent), finished=len(finished),
            failed=len(failed), first_tokens=len(ttft), gaps=len(itl),
            out_tokens=len(stamps),
            whole_window_tokens_per_s=f"{whole:.1f}",
            slice_median_tokens_per_s=f"{steady:.1f}",
            compiles_in_window=n_compiles, kv_pages=pool)
    shapes = {"model": model}
    if trace_span is not None:
        shapes["live_rows"], shapes["live_kv_tokens"] = _live_load(
            sent, *trace_span)

    del engine
    gc.collect()
    verdict = _check(ctx, model, finished, sizes["limits"])
    if n_compiles:
        ctx.log("NOT correct: programs were built inside the window",
                count=n_compiles)
    return {
        "correct": bool(verdict["ok"] and not failed and n_compiles == 0),
        "attempted": len(sent), "failed": len(failed),
        "setup_s": setup_s, "stats": stats, "shapes": shapes,
        "trace_dir": trace_dir, "memory_peak_bytes": peak,
        "compared": verdict["numbers"], "check_sample": verdict["sample"],
    }
