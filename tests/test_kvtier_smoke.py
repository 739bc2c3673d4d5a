"""KV tiering and fleet-wide prefix sharing: replica A exports a
finished 28-block prefix chain as a content-addressed KV-page volume
through a controller; replica B, whose store never held the prefix,
adopts the pages over the data path. Every trial is a real peer fetch,
no token changes, and every tier drains to nothing."""

import numpy as np
import pytest

from tests import cluster as C

BLOCK, N_BLOCKS, MAX_NEW, TRIALS = 16, 28, 4, 3


@pytest.fixture(scope="module")
def shared_prefix():
    from oim_tpu.common import metrics as M
    from oim_tpu.serve.kvvolume import (
        PeerPrefixFetcher,
        config_fingerprint,
        export_chain,
    )

    facts = {}
    with C.cluster(replicas=2, max_seq=512, queue_depth=8,
                   prefix_block=BLOCK,
                   engine_kwargs=[dict(kv_host_bytes=4 << 20), {}]) as sim:
        holder, adopter = C.engines(sim)
        feeder = sim.feeder()
        rng = np.random.RandomState(7)
        shared = rng.randint(1, 64, size=BLOCK * N_BLOCKS).tolist()
        holder.submit(shared + [60], max_new=MAX_NEW).result(timeout=300)
        chain = holder.hot_chains()[0]
        assert len(chain) == N_BLOCKS
        volume_id = export_chain(holder, feeder, list(chain))
        assert volume_id.startswith("kvchain-")
        adopter.set_kv_fetch(PeerPrefixFetcher(
            feeder, config_fingerprint(C.model()[1], BLOCK)))
        hit = M.SERVE_PREFIX_PEER_FETCHES.labels(outcome="hit")
        hits, tokens = hit.value, M.SERVE_PREFIX_PEER_TOKENS.value
        outs = []
        for i in range(TRIALS):
            req = (shared + [10 + i], MAX_NEW, 0.0 if i % 2 else 0.6, i)
            # Evicted first, so no trial re-hits the one before it locally.
            adopter.evict_prefix_store()
            outs.append((req, adopter.submit(
                req[0], max_new=req[1], temperature=req[2],
                seed=req[3]).result(timeout=300), C.solo(sim, *req)))
        facts.update(outs=outs, hits=hit.value - hits,
                     tokens=M.SERVE_PREFIX_PEER_TOKENS.value - tokens)

        holder_pool, adopter_pool = C.drain(sim)
        # The holder's store-only pages were demoted to the host on
        # eviction; the host tier empties on its own call.
        facts["demoted"] = holder.host_stats()
        holder.evict_host_tier()
        facts["host"] = holder.host_stats()
        facts["used_pages"] = (holder.pool_stats()["used_pages"],
                               adopter_pool["used_pages"])
        feeder.unpublish(volume_id)
        try:
            feeder.fetch_window(volume_id, 0, 16)
            facts["after_unpublish"] = "still served"
        except Exception as err:  # noqa: BLE001 - the test reads it
            facts["after_unpublish"] = str(err)
    return facts


def test_every_trial_adopts_the_whole_prefix_from_the_peer(shared_prefix):
    assert shared_prefix["hits"] == TRIALS
    assert shared_prefix["tokens"] == TRIALS * N_BLOCKS * BLOCK


def test_peer_adopted_streams_match_solo_generate(shared_prefix):
    for req, tokens, solo in shared_prefix["outs"]:
        assert tokens == solo, f"adopted {req} diverged from solo"


def test_every_tier_drains_and_the_volume_unpublishes(shared_prefix):
    demoted, host = shared_prefix["demoted"], shared_prefix["host"]
    assert demoted["demotions"] > 0 and demoted["entries"] > 0
    assert (host["entries"], host["bytes"]) == (0, 0)
    assert shared_prefix["used_pages"] == (0, 0)
    assert "NOT_FOUND" in shared_prefix["after_unpublish"]
