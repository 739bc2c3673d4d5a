"""The control plane at a small scale: ONE in-process quorum registry of
three members carrying 50 LiteReplica rows (real registration, heartbeat,
telemetry and Watch clients, decode stubbed) with eight Watch consumers
attached. Every consumer converges on every publisher's latest row
through three full-fleet bursts, no stream is shed, the fan-out and
commit paths are instrumented, the incremental fleet fold equals the
from-scratch one on the point's own telemetry rows, and after the leader
is killed a write commits again. The fixture drives the point; each test
holds one gate."""

import json

import pytest

from tests.cluster import wait_until

N, CONSUMERS, BURSTS = 50, 8, 3


@pytest.fixture(scope="module")
def point():
    from oim_tpu.chaos.sim import ClusterSim
    from oim_tpu.common import metrics as M
    from oim_tpu.obs import merge
    from oim_tpu.router.table import ReplicaTable

    facts: dict = {}
    # A long natural cadence: the fan-in is this test's own bursts. One
    # box hosts three registries, the publishers and the consumers, so
    # the election timeout is a deployment's, not the default 0.4 s.
    with ClusterSim(replicas=0, registry_quorum=3, lite_replicas=N,
                    lite_interval_s=120.0, lite_volume_keys=2,
                    election_timeout_s=2.0) as sim:
        watchers = [sim.registry_watcher("serve") for _ in range(CONSUMERS)]
        for i, w in enumerate(watchers):
            wait_until(lambda: len(w.rows) >= N,
                       f"consumer {i} never saw all {N} serve/ rows",
                       timeout=60)
        fanout = M.WATCH_FANOUT_SECONDS.merged_snapshot()
        commit = M.REGISTRY_COMMIT_SECONDS.merged_snapshot({"phase": "total"})
        sheds = M.WATCH_SHED_STREAMS.value

        def whole_burst():
            """One full-fleet burst; True when every beat committed. A
            beat that lands in an election fails, is counted and is the
            next burst's (LiteFleet's contract): six busy workers can
            hold a leader's beats past even a 2 s election timeout."""
            errors = sim.lite.beat_errors
            sim.lite.beat_all()
            return sim.lite.beat_errors == errors

        for i in range(BURSTS):
            wait_until(whole_burst, f"full-fleet burst {i} never committed "
                       "whole", timeout=120, interval=0.5)
        # beat_all returns after every SetValue committed and its apply
        # fanned out, so the deltas are complete here.
        facts["fanout"] = merge.total(
            M.WATCH_FANOUT_SECONDS.merged_snapshot()) - merge.total(fanout)
        facts["commits"] = merge.total(
            M.REGISTRY_COMMIT_SECONDS.merged_snapshot({"phase": "total"})
        ) - merge.total(commit)

        def latest(w):
            with w.lock:
                return {p: json.loads(v).get("beat") for p, v in w.rows.items()
                        if "/lite-" in p and ".member." not in p}

        def published():
            """The beat stamped into each publisher's last committed row
            (the background drivers still beat, every two minutes)."""
            return {replica.row.key: replica.row._last_snapshot["beat"]
                    for replica in sim.lite.replicas}

        facts["views"] = wait_until(
            lambda: [latest(w) for w in watchers]
            if all(latest(w) == published() for w in watchers) else None,
            "the consumers never held every publisher's latest row",
            timeout=30)
        facts["sheds"] = M.WATCH_SHED_STREAMS.value - sheds

        tele = sim.registry_watcher("telemetry")
        wait_until(lambda: len(tele.rows) >= N,
                   f"the telemetry view never held {N} rows", timeout=60)
        snaps = [hist["first_token"] for hist in (
            json.loads(v).get("hist", {}) for v in list(tele.rows.values()))
            if "first_token" in hist]
        fleet = merge.FleetHistogram()
        for i, snap in enumerate(snaps):
            fleet.update(f"lite-{i:04d}", snap)
        facts["folds"] = (len(snaps), fleet.merged(), fleet.merged_scratch())

        table = ReplicaTable(sim.registry_address, interval=5.0)
        table.start()
        try:
            wait_until(lambda: len(table.replicas()) >= N,
                       f"the routing table never held {N} rows", timeout=60)
        finally:
            table.stop()

        # A quiet-window step-down can leave the quorum leaderless for a
        # moment: kill a SEATED leader, so that this is a real failover.
        wait_until(lambda: sim.registry_leader() is not None,
                   "the quorum has no leader to kill", timeout=30)
        sim.kill_registry_leader()
        facts["recommitted"] = wait_until(lambda: sim.registry_write(
            "test/converged", "x", lease_seconds=30.0),
            "no write committed again after the leader was killed",
            timeout=15, interval=0.1)
    return facts


def test_every_consumer_holds_every_publishers_latest_row(point):
    assert len(point["views"]) == CONSUMERS
    assert all(len(view) == N for view in point["views"])


def test_no_watch_stream_is_shed_under_the_bursts(point):
    assert point["sheds"] == 0


def test_fanout_and_commit_paths_are_instrumented(point):
    """The series ``oimctl --top`` reads exist and count: a quorum commit
    a heartbeat write, fan-outs for every publisher's changed rows."""
    assert point["fanout"] >= N
    assert point["commits"] >= N * BURSTS


def test_incremental_fold_equals_scratch_on_real_rows(point):
    rows, incremental, scratch = point["folds"]
    assert rows > 0
    assert incremental["counts"] == scratch["counts"]


def test_scalesim_smoke_write_commits_again_after_leader_kill(point):
    """A seated leader killed, a write accepted by the survivors' new
    leader within the fixture's 15 s deadline."""
    assert point["recommitted"]
