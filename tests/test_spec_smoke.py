"""Speculative decoding with the target as its own draft, four
proposals a verify round: greedy streams stay solo ``generate()``'s,
proposals are accepted, a target dispatch yields more than one token,
and neither pool keeps a page. Sampled streams are distribution-exact,
not byte-identical (tests/test_spec.py holds the ratio test), so only
greedy rows are compared here."""

import pytest

from tests import cluster as C


def _spec():
    """The engine's keyword arguments for a self-draft of four tokens."""
    params, cfg = C.model()
    return dict(draft_params=params, draft_cfg=cfg, spec_tokens=4)


def _greedy_match_solo(reqs, results, solo):
    for req, tokens, want in zip(reqs, results, solo):
        if req[2] == 0.0:
            assert tokens == want, f"greedy {req} diverged from solo"


@pytest.fixture(scope="module")
def served():
    facts = {}
    with C.cluster(max_batch=4, **_spec()) as sim:
        sim.warm()
        reqs = C.mixed_requests(42, 12)
        results, errors = sim.routed_load(reqs, concurrency=6)
        assert not errors, f"streams failed: {errors[0]!r}"
        engine = C.engines(sim)[0]
        facts.update(reqs=reqs, results=results, stats=engine.stats(),
                     solo=[C.solo(sim, *req) for req in reqs],
                     drained=C.drain(sim),
                     draft_pages=engine.spec_stats()["draft_used_pages"])
    return facts


def test_spec_smoke_identity_and_acceptance(served):
    assert ([len(r) for r in served["results"]]
            == [n for _, n, _, _ in served["reqs"]])
    _greedy_match_solo(served["reqs"], served["results"], served["solo"])
    assert served["stats"]["spec_accept_rate"] > 0


def test_a_target_dispatch_yields_more_than_one_token(served):
    stats = served["stats"]
    assert stats["decode_tokens"] > stats["target_steps"] > 0


def test_drain_leaves_no_page_in_either_pool(served):
    assert [pool["used_pages"] for pool in served["drained"]] == [0]
    assert served["draft_pages"] == 0


def test_mixed_fleet_behind_the_router():
    """A rolling rollout's shape: one speculating replica, one plain.
    Wherever the pick lands the greedy stream is solo's, both replicas
    serve, and the speculating one keeps no draft page."""
    with C.cluster(replicas=2, engine_kwargs=[_spec(), {}]) as sim:
        sim.warm()
        speculating, plain = C.engines(sim)
        rounds = speculating.stats()["spec_rounds"]
        before = [e.finished_total for e in (speculating, plain)]
        reqs = [([11 + i, 3, 5], 6, 0.0, i) for i in range(6)]
        results, errors = sim.routed_load(reqs, concurrency=6)
        assert not errors, f"routed streams failed: {errors[0]!r}"
        _greedy_match_solo(reqs, results, [C.solo(sim, *r) for r in reqs])
        assert speculating.stats()["spec_rounds"] > rounds
        assert min(e.finished_total - b
                   for e, b in zip((speculating, plain), before)) >= 1
        assert speculating.spec_stats()["draft_used_pages"] == 0
