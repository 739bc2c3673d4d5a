"""The prefix KV cache: half the requests open on one shared system
prompt. Hits are counted, their prefill is skipped, no token changes;
behind a router, same-prefix requests herd to the replica that holds
the prefix."""

import numpy as np
import pytest

from tests import cluster as C

BLOCK = 16


@pytest.fixture(scope="module")
def served():
    from oim_tpu.common import metrics as M

    facts = {}
    with C.cluster(max_batch=4, prefix_block=BLOCK) as sim:
        sim.warm()
        # Two whole blocks and one token: the reusable prefix is 2 blocks.
        system = np.random.RandomState(42).randint(
            1, 64, size=2 * BLOCK + 1).tolist()
        C.engines(sim)[0].submit(system + [1], max_new=2).result(timeout=300)
        reqs = [((system if i % 2 else []) + p, n, t, s)
                for i, (p, n, t, s) in enumerate(C.mixed_requests(7, 12))]
        hits = M.SERVE_PREFIX_HITS.value
        saved = M.SERVE_PREFILL_TOKENS.labels(source="cache").value
        results, errors = sim.routed_load(reqs, concurrency=6)
        assert not errors, f"streams failed: {errors[0]!r}"
        facts.update(
            reqs=reqs, results=results,
            hits=M.SERVE_PREFIX_HITS.value - hits,
            saved=M.SERVE_PREFILL_TOKENS.labels(source="cache").value - saved,
            solo=[C.solo(sim, *req) for req in reqs])
    return facts


def test_prefix_smoke_hits_and_savings(served):
    shared = sum(len(p) > 2 * BLOCK for p, _, _, _ in served["reqs"])
    assert served["hits"] == shared == 6
    assert served["saved"] == shared * 2 * BLOCK


def test_hits_and_misses_match_solo_generate(served):
    for req, tokens, solo in zip(served["reqs"], served["results"],
                                 served["solo"]):
        assert tokens == solo, f"request {req} diverged from solo"


@pytest.fixture(scope="module")
def herded():
    from oim_tpu.common import metrics as M

    facts = {}
    with C.cluster(replicas=2) as sim:
        sim.warm()
        shared = np.random.RandomState(11).randint(1, 64, size=20).tolist()
        picks = M.ROUTER_AFFINITY_PICKS.value
        outs = []
        for i in range(6):
            req = (shared + [10 + i], 4, 0.0 if i % 2 else 0.6, i)
            outs.append((req, C.stream(sim, *req), C.solo(sim, *req)))
            # The holder's next beat carries the retained prefix to the
            # routing table before the next pick.
            for replica in sim.replicas:
                replica.registration.beat_once()
            holders = sum(bool(e.prefix_stats()["entries"])
                          for e in C.engines(sim))
            C.wait_until(
                lambda: sum(bool(r.prefix_hashes)
                            for r in sim.table.replicas()) >= holders,
                "the routing table never learnt who holds the prefix",
                timeout=10)
        facts.update(
            outs=outs, picks=M.ROUTER_AFFINITY_PICKS.value - picks,
            stores=[e.prefix_stats()["entries"] for e in C.engines(sim)])
    return facts


def test_same_prefix_requests_herd_to_the_holder(herded):
    picks, stores = herded["picks"], herded["stores"]
    assert picks >= 1, f"the router took no affinity pick (stores {stores})"
    assert max(stores) >= 1


def test_herded_streams_match_solo_generate(herded):
    for req, tokens, solo in herded["outs"]:
        assert tokens == solo, f"herded {req} diverged from solo"
