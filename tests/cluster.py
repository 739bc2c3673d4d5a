"""What the smoke modules share: ``oim_tpu.chaos.sim.ClusterSim`` IS the
tests' in-process serving cluster (registries, malloc controllers,
``ServeEngine`` replicas behind the real ``oim.v1.Serve`` servers with
heartbeating ``serve/<id>`` rows, an ``oim-router`` in front, everything
on ``localhost:0`` ports, ``routed_load()`` / ``warm()`` / ``feeder()``
and the fault levers). ``cluster()`` boots one from the engine's keyword
arguments, so a test says only how its engines differ from the default;
the rest here is a wait that names what never came, the request
generator, and the two things every module asks of a sim: one stream's
tokens and what the pools hold after a drain.
"""

from __future__ import annotations

import contextlib
import random
import time

from oim_tpu.chaos.ladder import _reqs
from oim_tpu.chaos.sim import ClusterSim, model, solo_tokens  # noqa: F401


def wait_until(predicate, what: str, timeout: float = 30.0,
               interval: float = 0.02):
    """Poll ``predicate`` until it is truthy and return its value; an
    AssertionError naming ``what`` when ``timeout`` seconds pass first."""
    deadline = time.monotonic() + timeout
    while True:
        value = predicate()
        if value:
            return value
        if time.monotonic() > deadline:
            raise AssertionError(f"{what} (waited {timeout:.0f}s)")
        time.sleep(interval)


def mixed_requests(seed: int, n: int, **kw):
    """``n`` requests (prompt, n_new, temperature, seed) from ``seed``:
    the chaos ladder's generator (random tokens, lengths uniform in
    ``prompt_len`` / ``max_new``, greedy and sampled alternating)."""
    return _reqs(random.Random(seed), n, **kw)


@contextlib.contextmanager
def cluster(replicas: int = 1, *, engine_kwargs: list[dict] | None = None,
            **engine_defaults):
    """A :class:`ClusterSim` of ``replicas`` engines behind the router
    and one malloc controller (``host-0``, reached with ``sim.feeder()``).
    ``engine_defaults`` go to every ``ServeEngine``, ``engine_kwargs[i]``
    on top of them to replica i's."""
    sized = {key: engine_defaults.pop(key)
             for key in ("max_batch", "max_seq", "queue_depth")
             if key in engine_defaults}
    extra = engine_kwargs or []
    per_replica = [dict(engine_defaults, **(extra[i] if i < len(extra) else {}))
                   for i in range(replicas)]
    with ClusterSim(replicas=replicas, controllers=1,
                    engine_kwargs=per_replica, **sized) as sim:
        yield sim


def engines(sim) -> list:
    return [replica.engine for replica in sim.replicas]


def solo(sim, prompt, n_new, temperature=0.0, seed=0):
    """What a solo ``generate()`` emits for this request at the sim's
    ``max_seq``: the reference every served stream is held to."""
    return solo_tokens(prompt, n_new, temperature, seed,
                       sim.engine_defaults["max_seq"])


def stream(sim, prompt, n_new, temperature=0.0, seed=0,
           timeout: float = 120.0) -> list[int]:
    """One routed ``Generate`` stream's tokens, on the caller's thread
    (so the caller's open span is the stream's parent)."""
    from oim_tpu.spec import pb

    tokens: list[int] = []
    for delta in sim.router_stub.Generate(
            pb.GenerateRequest(prompt=prompt, max_new_tokens=n_new,
                               temperature=temperature, seed=seed),
            timeout=timeout):
        tokens.extend(delta.tokens)
    return tokens


def drain(sim) -> list[dict]:
    """Finish every resident stream and let the prefix stores go, so
    that what a pool still holds is a leak: each engine's
    ``pool_stats()`` afterwards. Call it last in a fixture: the engines
    serve nothing after it."""
    for engine in engines(sim):
        engine.stop(drain=True, timeout=60)
        engine.evict_prefix_store()
    return [engine.pool_stats() for engine in engines(sim)]
