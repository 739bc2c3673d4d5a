"""The serving plane end to end, small: weights published once through
the control plane and restored from the staged bytes, then concurrent
gRPC streams through the continuous batch (more streams than slots, so
requests are admitted while others decode). The fixture drives it; one
gate a test."""

import jax
import numpy as np
import pytest

from tests import cluster as C


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from oim_tpu.common import metrics as M
    from oim_tpu.serve.weights import (
        publish_weights,
        restore_weights,
        save_packed,
    )

    facts = {}
    with C.cluster(max_batch=4) as sim:
        # The weights the way a fleet's reach it: one packed file, one
        # volume, the tree rebuilt from the staged bytes.
        params, _ = C.model()
        path = str(tmp_path_factory.mktemp("weights") / "w.oimw")
        save_packed(params, path)
        feeder = sim.feeder()
        publish_weights(feeder, "weights", path, timeout=60)
        restored = restore_weights(feeder, "weights", timeout=60)
        facts["restored_equal"] = [
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(restored))]
        hits, misses = M.STAGE_CACHE_HITS.value, M.STAGE_CACHE_MISSES.value
        feeder.unpublish("weights")
        publish_weights(feeder, "weights", path, timeout=60)
        facts["republish"] = (M.STAGE_CACHE_HITS.value - hits,
                              M.STAGE_CACHE_MISSES.value - misses)

        sim.warm()
        facts["reqs"] = C.mixed_requests(42, 12)
        facts["results"], facts["errors"] = sim.routed_load(
            facts["reqs"], concurrency=6)
        facts["solo"] = [C.solo(sim, *req) for req in facts["reqs"]]
        facts["drained"] = C.drain(sim)
    return facts


def test_weights_republish_is_a_cache_hit_that_reads_no_source(served):
    hits, misses = served["republish"]
    assert hits == 1, \
        "the identical republish was not served by the stage cache"
    assert misses == 0, "the identical republish staged the source again"


def test_no_request_dropped(served):
    errors = served["errors"]
    assert not errors, f"{len(errors)} streams failed; first: {errors[0]!r}"
    assert ([len(r) for r in served["results"]]
            == [n for _, n, _, _ in served["reqs"]])


def test_serve_smoke_weights_and_batching(served):
    """The restored tree is the published one, leaf for leaf, and every
    stream, greedy and sampled, is its solo ``generate()`` run: the batch
    it shared changed no token."""
    assert served["restored_equal"] and all(served["restored_equal"])
    for req, tokens, solo in zip(served["reqs"], served["results"],
                                 served["solo"]):
        assert tokens == solo, f"request {req} diverged from solo"


def test_drain_leaves_no_page(served):
    assert [pool["used_pages"] for pool in served["drained"]] == [0]
