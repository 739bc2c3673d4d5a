"""The fast rungs of the chaos ladder, one a case: each builds a fresh
in-process cluster, runs its seeded fault schedule and must CONVERGE:
the declared heal events on ``/debug/events`` in their declared order,
no client-visible error where the retry contract promises none,
byte-identical routed outputs (the rung's own asserts) and a zero-leak
page / prefix / channel census. The compound rung, leader kill under
load and the rest run under ``make chaos`` / ``pytest -m slow``
(tests/test_chaos.py)."""

import pytest

SERVE_FREE = {"quorum_partition", "registry_rolling_restart"}


def teardown_module(_module):
    # Eight rungs x several sim replicas leave a pile of compiled
    # executables in XLA's in-process cache, each live LLVM mappings
    # against vm.max_map_count: crossing it segfaults a later compile.
    import jax

    jax.clear_caches()


HEALED = {
    "replica_kill": ["router_mark_failed", "router_retry"],
    "channel_blackhole": ["router_mark_failed", "router_retry"],
    "pool_exhaustion": ["page_pool_exhausted"],
    "quorum_partition": ["registry_election", "registry_promotion",
                         "registry_stepdown"],
    "registry_rolling_restart": ["registry_election", "registry_promotion"],
    "kv_peer_fetch": ["kv_peer_fetch", "kv_fetch_fallback"],
    "prefill_replica_kill": ["kv_peer_fetch", "router_mark_failed",
                             "kv_fetch_fallback"],
    "shard_member_kill": ["shard_member_lost", "shard_member_healed"],
}


def test_the_smoke_rungs_are_the_fast_ones():
    from oim_tpu import chaos

    assert set(chaos.SMOKE_RUNGS) == set(HEALED)
    assert not [r.name for r in chaos.RUNGS
                if r.slow and r.name in chaos.SMOKE_RUNGS]


@pytest.mark.parametrize("rung", list(HEALED))
def test_chaos_smoke_rung_converges(rung):
    from oim_tpu import chaos

    report = chaos.run_ladder(names=[rung])
    assert report["event_signature"] == [[rung, *HEALED[rung]]]
    census = report["rungs"][0]["census"]
    if rung in SERVE_FREE:
        # No engine to audit; the channel pool's census still ran.
        assert "pooled_channels" in census
    else:
        assert census["replicas"], "the census audited no replica"
