"""Two serve replicas heartbeating ``serve/<id>`` rows behind an
``oim-router``: the least-loaded pick spreads, and the router changes no
token."""

import pytest

from tests import cluster as C


@pytest.fixture(scope="module")
def routed():
    facts = {}
    with C.cluster(replicas=2) as sim:
        sim.warm()
        before = [e.finished_total for e in C.engines(sim)]
        facts["reqs"] = C.mixed_requests(5, 16, prompt_len=(2, 7),
                                         max_new=(3, 8))
        facts["results"], facts["errors"] = sim.routed_load(
            facts["reqs"], concurrency=8)
        facts["served"] = [e.finished_total - b
                           for e, b in zip(C.engines(sim), before)]
        facts["solo"] = [C.solo(sim, *req) for req in facts["reqs"]]
    return facts


def test_every_replica_serves(routed):
    errors, served = routed["errors"], routed["served"]
    assert not errors, f"{len(errors)} routed streams failed: {errors[0]!r}"
    assert sum(served) == len(routed["reqs"])
    assert min(served) >= 1, f"routing did not spread: {served}"


def test_router_smoke_spread_and_byte_identity(routed):
    for req, tokens, solo in zip(routed["reqs"], routed["results"],
                                 routed["solo"]):
        assert tokens == solo, f"routed {req} diverged from solo"
