"""Ring-1 tests for the raft-style quorum registry
(registry/quorum.py): election restriction, single-vote-per-term,
majority-gated commit, leader step-down, split-brain write census, and
the CLI flag matrix. The end-to-end failover contract runs in tier-1
via tests/test_quorum_smoke.py and under load in the chaos ladder."""

import time

import grpc
import pytest

from oim_tpu.common import tlsutil
from oim_tpu.registry import MemRegistryDB, RegistryService
from oim_tpu.registry.quorum import (
    FOLLOWER,
    LEADER,
    NotLeader,
    QuorumManager,
    QuorumUnavailable,
)
from oim_tpu.registry.registry import registry_server
from oim_tpu.spec import RegistryStub, pb


def wait_for(predicate, timeout=20.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


def make_cluster(n=3, election_timeout_s=0.4, commit_timeout_s=2.0):
    services, servers = [], []
    for _ in range(n):
        svc = RegistryService(db=MemRegistryDB())
        servers.append(registry_server("tcp://127.0.0.1:0", svc))
        services.append(svc)
    addrs = [srv.addr for srv in servers]
    managers = [
        QuorumManager(services[i], node_id=addrs[i],
                      peers=[a for a in addrs if a != addrs[i]],
                      election_timeout_s=election_timeout_s,
                      commit_timeout_s=commit_timeout_s)
        for i in range(n)
    ]
    return services, servers, managers, addrs


class Cluster:
    def __init__(self, n=3, **kwargs):
        (self.services, self.servers, self.managers,
         self.addrs) = make_cluster(n, **kwargs)
        for mgr in self.managers:
            mgr.start()
        self.channels = [tlsutil.dial(a, None) for a in self.addrs]
        self.stubs = [RegistryStub(ch) for ch in self.channels]

    def leader_index(self):
        leaders = [i for i, m in enumerate(self.managers)
                   if m.role == LEADER]
        return leaders[0] if len(leaders) == 1 else None

    def await_leader(self):
        assert wait_for(lambda: self.leader_index() is not None), \
            "no leader elected"
        return self.leader_index()

    def close(self):
        for mgr in self.managers:
            mgr.stop()
        for ch in self.channels:
            ch.close()
        for srv in self.servers:
            srv.force_stop()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class TestElection:
    def test_exactly_one_leader_and_terms_agree(self):
        with Cluster() as c:
            li = c.await_leader()
            assert wait_for(lambda: len({m.term for m in c.managers}) == 1)
            assert sum(1 for m in c.managers if m.role == LEADER) == 1
            assert c.managers[li].leader_hint() == c.addrs[li]

    def test_vote_once_per_term(self):
        with Cluster() as c:
            li = c.await_leader()
            voter = c.managers[(li + 1) % 3]
            term = voter.term + 10

            class Req:
                pass

            def vote(candidate, last_term, offset=0, log_id="x"):
                return voter.on_vote(pb.VoteRequest(
                    term=term, candidate_id=candidate,
                    last_log_term=last_term, last_log_offset=offset,
                    log_id=log_id), None)

            first = vote("cand-a", last_term=99)
            assert first.granted
            second = vote("cand-b", last_term=99)
            assert not second.granted, \
                "two candidates granted in one term"
            # Re-asking by the SAME candidate is idempotent.
            again = vote("cand-a", last_term=99)
            assert again.granted

    def test_vote_refused_to_stale_log(self):
        with Cluster() as c:
            li = c.await_leader()
            # Commit something so the cluster's log position advances.
            c.stubs[li].SetValue(pb.SetValueRequest(value=pb.Value(
                path="q/x", value="1")), timeout=10)
            voter = c.managers[(li + 1) % 3]
            assert wait_for(lambda: voter._log_position()[1] > 0)
            reply = voter.on_vote(pb.VoteRequest(
                term=voter.term + 1, candidate_id="empty-node",
                last_log_term=0, last_log_offset=0, log_id="fresh"),
                None)
            assert not reply.granted, \
                "a voter with data endorsed an empty-log candidate"

    def test_stale_term_vote_refused(self):
        with Cluster() as c:
            li = c.await_leader()
            voter = c.managers[(li + 1) % 3]
            reply = voter.on_vote(pb.VoteRequest(
                term=0, candidate_id="old", last_log_term=99,
                last_log_offset=99, log_id="z"), None)
            assert not reply.granted
            assert reply.term == voter.term


class TestCommit:
    def test_write_visible_only_after_commit_everywhere(self):
        with Cluster() as c:
            li = c.await_leader()
            c.stubs[li].SetValue(pb.SetValueRequest(value=pb.Value(
                path="q/committed", value="v", lease_seconds=60)),
                timeout=10)
            # The leader applied at commit; every follower converges.
            for i in range(3):
                assert wait_for(
                    lambda i=i: c.services[i].db.get("q/committed") == "v"
                ), f"member {i} never applied the committed write"

    def test_partitioned_leader_cannot_acknowledge(self):
        with Cluster(commit_timeout_s=1.0) as c:
            li = c.await_leader()
            leader = c.managers[li]
            others = [a for i, a in enumerate(c.addrs) if i != li]
            leader.set_unreachable(others)
            with pytest.raises(grpc.RpcError) as err:
                c.stubs[li].SetValue(pb.SetValueRequest(value=pb.Value(
                    path="q/split", value="x")), timeout=10)
            assert err.value.code() in (
                grpc.StatusCode.UNAVAILABLE,
                grpc.StatusCode.FAILED_PRECONDITION)
            # Never applied anywhere — not even on the leader itself.
            assert c.services[li].db.get("q/split") == ""
            leader.set_unreachable([])

    def test_propose_on_follower_raises_not_leader(self):
        with Cluster() as c:
            li = c.await_leader()
            follower = c.managers[(li + 1) % 3]
            # Elected is not yet heard of: the hint is the leader's first
            # contact with this follower, which six workers can delay.
            assert wait_for(lambda: follower.leader_hint() == c.addrs[li])
            with pytest.raises(NotLeader) as err:
                follower.propose_kv("q/y", "1", 0.0)
            assert err.value.hint == c.addrs[li]

    def test_heartbeat_renewal_rides_the_quorum(self):
        with Cluster() as c:
            li = c.await_leader()
            c.stubs[li].SetValue(pb.SetValueRequest(value=pb.Value(
                path="serve/r0", value="{}", lease_seconds=0.5)),
                timeout=10)
            fi = (li + 1) % 3
            assert wait_for(
                lambda: c.services[fi].leases.has_lease("serve/r0"))
            reply = c.stubs[li].Heartbeat(pb.HeartbeatRequest(
                keys=["serve/r0"], lease_seconds=60), timeout=10)
            assert list(reply.keys_known) == [True]
            # The RENEW record committed: the follower's lease got the
            # new TTL, re-based on ITS clock.
            assert wait_for(
                lambda: (c.services[fi].leases.remaining("serve/r0")
                         or 0) > 10)


class TestYieldToData:
    def test_position_ahead_comparison(self):
        """``_position_ahead``: the term-first comparison a candidate
        runs over EVERY vote reply — a voter strictly ahead makes the
        candidate yield the election instead of seating itself and
        erasing the voter's committed records on resync. Same term in
        DIFFERENT journals compares equal (offsets are journal-local),
        so cold boots — all positions (0,0) — are unaffected."""
        from oim_tpu.registry.quorum import _position_ahead

        def req(term, off, log_id="L"):
            return pb.VoteRequest(last_log_term=term,
                                  last_log_offset=off, log_id=log_id)

        def rep(term, off, log_id="L"):
            return pb.VoteReply(last_log_term=term,
                                last_log_offset=off, log_id=log_id)

        assert _position_ahead(rep(2, 1), req(1, 99))
        assert _position_ahead(rep(1, 5), req(1, 3))
        assert not _position_ahead(rep(1, 3), req(1, 5))
        assert not _position_ahead(rep(1, 9, "other"), req(1, 1))
        assert not _position_ahead(rep(0, 0), req(0, 0))

    def test_vote_reply_advertises_voter_position(self):
        """Every vote reply — granted or DENIED — carries the voter's
        own log position: the deny from a data-holding voter is the
        evidence a wiped-rejoining candidate yields to."""
        with Cluster() as c:
            li = c.await_leader()
            c.stubs[li].SetValue(pb.SetValueRequest(value=pb.Value(
                path="q/evidence", value="1")), timeout=10)
            voter = c.managers[(li + 1) % 3]
            assert wait_for(lambda: voter._log_position()[1] > 0)
            reply = voter.on_vote(pb.VoteRequest(
                term=voter.term + 1, candidate_id="wiped-node",
                last_log_term=0, last_log_offset=0, log_id="fresh"),
                None)
            assert not reply.granted
            term, offset, log_id = voter._log_position()
            assert (reply.last_log_term, reply.last_log_offset,
                    reply.log_id) == (term, offset, log_id)


class TestFollowerReadLag:
    def test_follower_reads_trail_commit_by_one_ack_round_trip(self):
        """Follower GetValues serves LOCAL applied state — no
        read-index round-trip — so a committed write is invisible
        there until the next leader contact advertises the commit;
        oim_registry_read_lag_records counts that gap. Gate the
        follower's apply step to hold the window open (records still
        arrive and ack, so the leader's majority math is untouched),
        observe the stale read and the non-zero lag, then release and
        watch it drain to zero."""
        with Cluster() as c:
            li = c.await_leader()
            fi = (li + 1) % 3
            follower = c.managers[fi]
            # An elected leader does not mean THIS follower's stream is
            # attached: the write below commits on the leader and the
            # other follower alone, and a follower that attaches after it
            # gets the record by resync, applied with nothing pending:
            # no lag ever shows. Hold the window open only on a follower
            # that is seen applying the leader's writes.
            c.stubs[li].SetValue(pb.SetValueRequest(value=pb.Value(
                path="q/attached", value="1", lease_seconds=60)),
                timeout=10)
            assert wait_for(
                lambda: c.services[fi].db.get("q/attached") == "1"), \
                "the follower never applied a write of the elected leader"
            real_flush = follower._flush_pending
            follower._flush_pending = lambda: None
            try:
                c.stubs[li].SetValue(pb.SetValueRequest(value=pb.Value(
                    path="q/lag", value="v", lease_seconds=60)),
                    timeout=10)
                # Committed (SetValue returned): the leader serves it...
                assert c.services[li].db.get("q/lag") == "v"
                # ...while the gated follower's GetValues misses it.
                got = {v.path for v in c.stubs[fi].GetValues(
                    pb.GetValuesRequest(path="q"), timeout=5).values}
                assert "q/lag" not in got, \
                    "follower applied through the gate?"

                def lag():
                    with follower._lock:
                        return follower._read_lag_locked()

                assert wait_for(lambda: lag() > 0), \
                    "read-lag never surfaced the held-open gap"
            finally:
                follower._flush_pending = real_flush
            assert wait_for(
                lambda: c.services[fi].db.get("q/lag") == "v"), \
                "released follower never applied the committed write"
            assert wait_for(lambda: lag() == 0), \
                "read-lag never drained after release"


class TestStepDown:
    def test_leader_without_majority_steps_down_and_in_flight_fails(self):
        with Cluster(commit_timeout_s=5.0) as c:
            li = c.await_leader()
            leader = c.managers[li]
            leader.set_unreachable(
                [a for i, a in enumerate(c.addrs) if i != li])
            assert wait_for(lambda: leader.role == FOLLOWER, timeout=10), \
                "partitioned leader never stepped down"
            with pytest.raises((NotLeader, QuorumUnavailable)):
                leader.propose_kv("q/after-stepdown", "1", 0.0)
            leader.set_unreachable([])
            # The cluster re-converges to one leader after heal.
            assert wait_for(lambda: c.leader_index() is not None)

    def test_rejoining_old_leader_resyncs_majority_state(self):
        with Cluster() as c:
            li = c.await_leader()
            old = c.managers[li]
            old.set_unreachable(
                [a for i, a in enumerate(c.addrs) if i != li])
            for i, m in enumerate(c.managers):
                if i != li:
                    m.set_unreachable([c.addrs[li]])
            majority = [m for i, m in enumerate(c.managers) if i != li]
            assert wait_for(lambda: sum(
                1 for m in majority if m.role == LEADER) == 1)
            ni = next(i for i, m in enumerate(c.managers)
                      if m in majority and m.role == LEADER)
            c.stubs[ni].SetValue(pb.SetValueRequest(value=pb.Value(
                path="q/majority-write", value="M")), timeout=10)
            for m in c.managers:
                m.set_unreachable([])
            assert wait_for(
                lambda: old.role == FOLLOWER
                and old.db.get("q/majority-write") == "M", timeout=20), \
                "old leader never resynced after heal"


class TestStatusAndCli:
    def test_status_entries_expose_term_and_commit(self):
        with Cluster() as c:
            li = c.await_leader()
            c.stubs[li].SetValue(pb.SetValueRequest(value=pb.Value(
                path="q/s", value="1")), timeout=10)
            entries = {
                v.path: v.value
                for v in c.stubs[li].GetValues(
                    pb.GetValuesRequest(path="registry"),
                    timeout=5).values}
            assert entries["registry/role"] == LEADER
            assert int(entries["registry/term"]) >= 1
            assert int(
                entries["registry/replication/commit_offset"]) >= 1
            assert entries["registry/leader"] == c.addrs[li]
            assert entries["registry/members"] == "3"

    @pytest.mark.parametrize("argv,message", [
        (["--quorum", "a:1,b:2", "--advertise", "a:1"], "3+ members"),
        (["--quorum", "a:1,b:2,c:3"], "--advertise"),
        (["--quorum", "a:1,b:2,c:3", "--advertise", "d:4"],
         "not in the"),
        (["--quorum", "a:1,b:2,c:3", "--advertise", "a:1",
          "--peer", "b:2"], "mutually exclusive"),
    ])
    def test_cli_flag_validation(self, argv, message):
        from oim_tpu.cli.oim_registry import main

        with pytest.raises(SystemExit) as err:
            main(argv)
        assert message in str(err.value)
