"""The paged KV cache under a bimodal short/long prompt mix with the
page pool at HALF the dense ``max_batch x max_seq`` reservation: the
mix is served whole and unchanged, exhaustion waits in the queue, and
more slots are live than dense slots of the same HBM could be."""

import numpy as np
import pytest

from tests import cluster as C

MAX_BATCH, MAX_SEQ, MAX_NEW = 4, 64, 8


@pytest.fixture(scope="module")
def served():
    facts = {}
    with C.cluster(max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                   kv_pool_tokens=MAX_BATCH * MAX_SEQ // 2) as sim:
        sim.warm()
        rng = np.random.RandomState(42)
        short = C.mixed_requests(1, 6, max_new=(4, MAX_NEW))
        long = C.mixed_requests(
            2, 6, prompt_len=(MAX_SEQ // 2, MAX_SEQ - MAX_NEW - 1),
            max_new=(4, MAX_NEW))
        reqs = short + long
        rng.shuffle(reqs)
        facts["reqs"] = reqs
        facts["results"], facts["errors"] = sim.routed_load(
            reqs, concurrency=8)
        facts["solo"] = [C.solo(sim, *req) for req in reqs]
        facts["pages"] = C.engines(sim)[0].pool_stats()
    return facts


def test_pool_exhaustion_waits_instead_of_failing(served):
    """Eight streams at once want more pages than the half pool has:
    none is refused, none fails, each gets every token it asked for."""
    errors = served["errors"]
    assert not errors, f"{len(errors)} streams failed; first: {errors[0]!r}"
    assert ([len(r) for r in served["results"]]
            == [n for _, n, _, _ in served["reqs"]])


def test_paged_smoke_identity_and_hbm_saving(served):
    for req, tokens, solo in zip(served["reqs"], served["results"],
                                 served["solo"]):
        assert tokens == solo, f"request {req} diverged from solo"
    pages = served["pages"]
    assert pages["total_pages"] * 2 == pages["dense_equiv_pages"]
    assert 0 < pages["peak_used_pages"] <= pages["total_pages"]


def test_more_slots_live_than_dense_slots_of_equal_hbm():
    """A pool of 128 tokens is two dense slots of 64. Four requests of
    33 positions each are admitted together: a reservation of
    ``max_seq`` a slot would block the third on pages."""
    from oim_tpu.serve import ServeEngine

    params, cfg = C.model()
    eng = ServeEngine(params, cfg, max_batch=4, max_seq=MAX_SEQ,
                      queue_depth=8, prefix_cache_bytes=0,
                      kv_pool_tokens=128)
    try:
        reqs = [([3 + i, 4, 5], 30, 0.0 if i % 2 else 0.9, i)
                for i in range(4)]
        handles = [eng.submit(p, max_new=n, temperature=t, seed=s)
                   for p, n, t, s in reqs]
        C.wait_until(lambda: eng.active_slots == 4,
                     "four slots were never live together on the HBM of "
                     "two dense slots", timeout=120, interval=0.002)
        for req, handle in zip(reqs, handles):
            assert handle.result(timeout=300) == C.solo_tokens(*req)
    finally:
        eng.stop(drain=False, timeout=30)
