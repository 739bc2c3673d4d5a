"""Ring-1 tests for Watch streams (registry/watch.py), the batched
Heartbeat (registry.py / telemetry.py), and the router table's
watch-mode (router/table.py): resume-token replay after a stream drop,
watch-across-failover on the replicated pair, lease expiry delivered as
a deletion, slow-consumer backpressure (stream closed, registry never
blocked), instant mark_failed re-admission, and the poll fallback
against a pre-Watch registry."""

import json
import queue
import threading
import time

import grpc
import pytest

from oim_tpu.common import tlsutil
from oim_tpu.registry import MemRegistryDB, RegistryService
from oim_tpu.registry import watch as W
from oim_tpu.registry.registry import registry_server
from oim_tpu.spec import RegistryStub, RegistryServicer, pb
from oim_tpu.spec.services import add_registry_to_server


def wait_for(predicate, timeout=15.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


@pytest.fixture
def registry():
    service = RegistryService(db=MemRegistryDB())
    service.watch.sweep_interval = 0.05
    server = registry_server("tcp://127.0.0.1:0", service)
    channel = tlsutil.dial(server.addr, None)
    try:
        yield service, server, RegistryStub(channel)
    finally:
        channel.close()
        server.force_stop()


def put(stub, path, value, lease=0.0):
    stub.SetValue(pb.SetValueRequest(value=pb.Value(
        path=path, value=value, lease_seconds=lease)), timeout=5)


def collect_until_sync(call):
    """Events up to (and including) the first SYNC."""
    out = []
    for ev in call:
        out.append(ev)
        if ev.kind == W.KIND_SYNC:
            return out
    raise AssertionError("stream ended before SYNC")


class TestWatchStream:
    def test_snapshot_then_live_deltas(self, registry):
        _, _, stub = registry
        put(stub, "serve/r0", "v0")
        call = stub.Watch(pb.WatchRequest(path="serve"))
        initial = collect_until_sync(call)
        kinds = [e.kind for e in initial]
        assert kinds[0] == W.KIND_RESET and kinds[-1] == W.KIND_SYNC
        assert [(e.value.path, e.value.value) for e in initial
                if e.kind == W.KIND_PUT] == [("serve/r0", "v0")]
        put(stub, "serve/r1", "v1")
        ev = next(iter(call))
        assert (ev.kind, ev.value.path, ev.value.value) == \
            (W.KIND_PUT, "serve/r1", "v1")
        put(stub, "serve/r1", "")  # the delete idiom
        ev = next(iter(call))
        assert (ev.kind, ev.value.path) == (W.KIND_DELETE, "serve/r1")
        # Out-of-scope keys never reach a prefix-scoped stream.
        put(stub, "other/x", "y")
        put(stub, "serve/r2", "v2")
        ev = next(iter(call))
        assert ev.value.path == "serve/r2"
        call.cancel()

    def test_resume_token_replays_exact_deltas(self, registry):
        _, _, stub = registry
        put(stub, "serve/r0", "v0")
        call = stub.Watch(pb.WatchRequest(path="serve"))
        token = collect_until_sync(call)[-1].resume_token
        call.cancel()  # the stream drop
        # Mutations while disconnected: one put, one delete.
        put(stub, "serve/r1", "v1")
        put(stub, "serve/r0", "")
        call = stub.Watch(pb.WatchRequest(path="serve",
                                          resume_token=token))
        events = collect_until_sync(call)
        call.cancel()
        # A replay, not a snapshot: no RESET, exactly the missed deltas
        # in commit order.
        assert all(e.kind != W.KIND_RESET for e in events)
        assert [(e.kind, e.value.path) for e in events[:-1]] == [
            (W.KIND_PUT, "serve/r1"), (W.KIND_DELETE, "serve/r0")]

    def test_bogus_token_degrades_to_snapshot(self, registry):
        _, _, stub = registry
        put(stub, "serve/r0", "v0")
        call = stub.Watch(pb.WatchRequest(path="serve",
                                          resume_token="not:real"))
        events = collect_until_sync(call)
        call.cancel()
        assert events[0].kind == W.KIND_RESET
        assert [e.value.path for e in events
                if e.kind == W.KIND_PUT] == ["serve/r0"]

    def test_lease_expiry_delivered_as_deletion(self, registry):
        _, _, stub = registry
        put(stub, "serve/r0", "v0", lease=0.3)
        call = stub.Watch(pb.WatchRequest(path="serve"))
        collect_until_sync(call)
        got = queue.Queue()

        def consume():
            try:
                for ev in call:
                    got.put(ev)
            except grpc.RpcError:
                pass  # the test's final cancel

        threading.Thread(target=consume, daemon=True).start()
        deadline = time.monotonic() + 10
        while True:
            ev = got.get(timeout=max(0.1, deadline - time.monotonic()))
            if ev.kind == W.KIND_EXPIRED:
                break
        assert ev.value.path == "serve/r0"
        # A bare renewal resurrects the row as a PUT (the value never
        # changed, so only the sweeper can re-announce it).
        stub.Heartbeat(pb.HeartbeatRequest(
            keys=["serve/r0"], lease_seconds=60), timeout=5)
        while True:
            ev = got.get(timeout=max(0.1, deadline - time.monotonic()))
            if ev.kind == W.KIND_PUT:
                break
        assert (ev.value.path, ev.value.value) == ("serve/r0", "v0")
        call.cancel()

    def test_slow_consumer_closed_not_blocked(self, registry):
        """Driven at the hub level, where "slow" is precise: the
        serving generator is simply never advanced while writes flood
        in (over gRPC the transport's own buffering would mask the
        queue until flow-control kicked in at ~64KB)."""
        service, _, stub = registry
        hub = service.watch
        hub.queue_max = 8

        class Abort(Exception):
            def __init__(self, code, details):
                super().__init__(details)
                self.code = code

        class Ctx:
            @staticmethod
            def is_active():
                return True

            @staticmethod
            def abort(code, details):
                raise Abort(code, details)

        gen = hub.serve(pb.WatchRequest(path="serve"), Ctx())
        for ev in gen:
            if ev.kind == W.KIND_SYNC:
                break
        # Flood without advancing the generator: the registry write
        # path must never block, and the stream must be CLOSED.
        t0 = time.monotonic()
        for i in range(64):
            put(stub, "serve/r0", f"v{i}")
        write_wall = time.monotonic() - t0
        assert write_wall < 5.0, \
            f"writes blocked on a slow watcher ({write_wall:.1f}s)"
        with pytest.raises(Abort) as err:
            for _ in range(256):
                next(gen)
        assert err.value.code == grpc.StatusCode.RESOURCE_EXHAUSTED
        # Other streams keep working: the registry only shed the slow
        # one.
        call = stub.Watch(pb.WatchRequest(path="serve"))
        events = collect_until_sync(call)
        call.cancel()
        assert any(e.value.path == "serve/r0" for e in events
                   if e.kind == W.KIND_PUT)

    def test_watch_across_pair_failover(self, registry):
        """Pair mode: a watcher that loses the primary re-targets the
        (promoted) standby and converges with no missed rows — the
        standby's hub was fed by the replication apply path."""
        from oim_tpu.registry.replication import (
            PRIMARY,
            STANDBY,
            ReplicationManager,
        )

        p_svc, p_srv, p_stub = registry
        s_svc = RegistryService(db=MemRegistryDB())
        s_srv = registry_server("tcp://127.0.0.1:0", s_svc)
        p_mgr = ReplicationManager(p_svc, peer=s_srv.addr, role=PRIMARY,
                                   primary_lease_seconds=0.5)
        s_mgr = ReplicationManager(s_svc, peer=p_srv.addr, role=STANDBY,
                                   primary_lease_seconds=0.5)
        s_channel = tlsutil.dial(s_srv.addr, None)
        s_stub = RegistryStub(s_channel)
        try:
            p_mgr.start(initial_probe=False)
            s_mgr.start(initial_probe=False)
            assert wait_for(s_mgr._may_auto_promote)
            put(p_stub, "serve/r0", "v0")
            call = p_stub.Watch(pb.WatchRequest(path="serve"))
            assert [e.value.path for e in collect_until_sync(call)
                    if e.kind == W.KIND_PUT] == ["serve/r0"]
            # The standby's own hub already holds the replicated row.
            assert wait_for(
                lambda: s_svc.db.get("serve/r0") == "v0")
            call.cancel()
            s_call = s_stub.Watch(pb.WatchRequest(path="serve"))
            events = collect_until_sync(s_call)
            s_call.cancel()
            assert [(e.value.path, e.value.value) for e in events
                    if e.kind == W.KIND_PUT] == [("serve/r0", "v0")]
        finally:
            s_channel.close()
            p_mgr.stop()
            s_mgr.stop()
            s_srv.force_stop()


class TestWatchConsumer:
    """The shared client state machine (registry/watch.py
    WatchConsumer): resume tokens commit only once the view they
    describe is installed."""

    @staticmethod
    def _event(kind, path="", value="", token=""):
        ev = pb.WatchEvent(kind=kind, resume_token=token)
        if path:
            ev.value.path = path
            ev.value.value = value
        return ev

    def test_token_not_committed_during_interrupted_snapshot(self):
        from oim_tpu.registry.watch import WatchConsumer

        consumer = WatchConsumer()
        consumer.resume_token = "hub:1"

        class Dies(Exception):
            pass

        def stream():
            yield self._event(W.KIND_RESET, token="hub:9")
            yield self._event(W.KIND_PUT, "serve/r0", "v", token="hub:9")
            raise Dies()  # the stream drops BEFORE the SYNC

        installed = []
        with pytest.raises(Dies):
            consumer.run(stream(), install=installed.append,
                         put=lambda *a: installed.append(("put", a)),
                         delete=lambda *a: None)
        # Nothing was installed, so the pre-snapshot token must stand:
        # resuming with "hub:9" would replay deltas onto a view that
        # was never built (a deleted row would ghost forever).
        assert consumer.resume_token == "hub:1"
        assert installed == []

    def test_snapshot_commits_token_at_sync(self):
        from oim_tpu.registry.watch import WatchConsumer

        consumer = WatchConsumer()

        def stream():
            yield self._event(W.KIND_RESET, token="hub:9")
            yield self._event(W.KIND_PUT, "serve/r0", "v", token="hub:9")
            yield self._event(W.KIND_SYNC, token="hub:9")
            yield self._event(W.KIND_PUT, "serve/r1", "w", token="hub:10")

        views, puts = [], []
        consumer.run(stream(), install=views.append,
                     put=lambda p, v: puts.append((p, v)),
                     delete=lambda *a: None)
        assert views == [{"serve/r0": "v"}]  # atomic rebuild at SYNC
        assert puts == [("serve/r1", "w")]   # live delta after
        assert consumer.resume_token == "hub:10"


class TestBatchHeartbeat:
    def test_keys_renew_and_report(self, registry):
        _, _, stub = registry
        put(stub, "serve/r0", "{}", lease=0.5)
        put(stub, "telemetry/h0", "{}", lease=0.5)
        reply = stub.Heartbeat(pb.HeartbeatRequest(
            keys=["serve/r0", "telemetry/h0", "serve/ghost"],
            lease_seconds=60), timeout=5)
        assert list(reply.keys_known) == [True, True, False]
        assert not reply.known  # no controller_id in the request

    def test_reserved_keys_rejected(self, registry):
        _, _, stub = registry
        with pytest.raises(grpc.RpcError) as err:
            stub.Heartbeat(pb.HeartbeatRequest(
                keys=["registry/role"]), timeout=5)
        assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT

    def test_empty_request_rejected(self, registry):
        _, _, stub = registry
        with pytest.raises(grpc.RpcError) as err:
            stub.Heartbeat(pb.HeartbeatRequest(), timeout=5)
        assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT


class _PreBatchRegistry(RegistryService):
    """A registry from before the batch-heartbeat era: it parses the
    request but ignores `keys` entirely (and so returns an empty
    keys_known)."""

    def Heartbeat(self, request, context):
        stripped = pb.HeartbeatRequest(
            controller_id=request.controller_id,
            lease_seconds=request.lease_seconds)
        reply = super().Heartbeat(stripped, context)
        return pb.HeartbeatReply(known=reply.known)


class TestPublisherDegrade:
    def _publisher(self, addr, republish_every=4):
        from oim_tpu.common.telemetry import RegistryRowPublisher

        class P(RegistryRowPublisher):
            def snapshot(self) -> dict:
                return {"static": "row"}

        return P("telemetry/t0", addr, interval=10.0, lease_seconds=60,
                 republish_every=republish_every)

    def test_renews_between_republishes(self, registry):
        service, server, stub = registry
        publisher = self._publisher(server.addr)
        publisher.beat_once()  # publish (first)
        first = service.db.get("telemetry/t0")
        for _ in range(3):
            publisher.beat_once()  # renew: value unchanged
        assert service.db.get("telemetry/t0") == first
        assert publisher._beats == 1
        publisher.beat_once()  # the republish bound: every 4th beat
        assert service.db.get("telemetry/t0") != first
        assert publisher._beats == 2

    def test_degrades_against_pre_batch_registry(self):
        service = _PreBatchRegistry(db=MemRegistryDB())
        server = registry_server("tcp://127.0.0.1:0", service)
        try:
            publisher = self._publisher(server.addr)
            publisher.beat_once()
            first = service.db.get("telemetry/t0")
            publisher.beat_once()  # renewal attempt -> empty keys_known
            assert publisher._batch_supported is False
            assert service.db.get("telemetry/t0") != first, \
                "publisher skipped the republish against a pre-batch " \
                "registry"
        finally:
            server.force_stop()

    def test_lost_row_republishes_immediately(self, registry):
        service, server, stub = registry
        publisher = self._publisher(server.addr)
        publisher.beat_once()
        # The registry loses the row (restart-shaped sweep).
        with service._write_lock:
            service.apply_kv("telemetry/t0", "", 0.0)
        publisher.beat_once()  # renewal says known=False -> republish
        assert service.db.get("telemetry/t0") != ""


class TestTableWatchMode:
    def _row(self, endpoint="1.2.3.4:9", beat=1, ready=True):
        return json.dumps({"endpoint": endpoint, "free_slots": 1,
                           "max_batch": 2, "queue_depth": 0,
                           "ready": ready, "beat": beat},
                          sort_keys=True)

    def test_delta_lands_without_waiting_a_poll(self, registry):
        from oim_tpu.router.table import ReplicaTable

        _, server, stub = registry
        put(stub, "serve/r0", self._row(), lease=60)
        table = ReplicaTable(server.addr, interval=3600.0, watch=True)
        table.start()
        try:
            assert wait_for(lambda: len(table.replicas()) == 1, timeout=10)
            # A new replica appears push-fast despite the 1h poll.
            put(stub, "serve/r1", self._row("5.6.7.8:9"), lease=60)
            assert wait_for(lambda: len(table.replicas()) == 2,
                            timeout=5), \
                "watch delta waited on the poll interval"
            # Drain (ready:false) disappears push-fast too.
            put(stub, "serve/r1", self._row("5.6.7.8:9", ready=False),
                lease=60)
            assert wait_for(lambda: len(table.replicas()) == 1,
                            timeout=5)
        finally:
            table.stop()

    def test_synced_watch_table_issues_no_getvalues(self, registry):
        """The count behind the retired `getvalues_drop_x` timing gate:
        once its stream is synced, a watch-mode table follows every row
        change with ZERO GetValues reads of the registry."""
        from oim_tpu.common import metrics as M
        from oim_tpu.router.table import ReplicaTable

        _, server, stub = registry
        put(stub, "serve/r0", self._row(), lease=60)
        table = ReplicaTable(server.addr, interval=3600.0, watch=True)
        table.start()
        try:
            assert wait_for(lambda: len(table.replicas()) == 1, timeout=10)
            reads = M.REGISTRY_GETVALUES.value
            for i in range(1, 4):
                put(stub, f"serve/r{i}", self._row(f"5.6.7.{i}:9"), lease=60)
            assert wait_for(lambda: len(table.replicas()) == 4, timeout=5), \
                "the pushed rows never reached the table"
            assert M.REGISTRY_GETVALUES.value == reads, \
                "a synced watch-mode table still polls GetValues"
        finally:
            table.stop()

    def test_mark_failed_readmits_on_row_change(self, registry):
        from oim_tpu.router.table import ReplicaTable

        _, server, stub = registry
        put(stub, "serve/r0", self._row(beat=1), lease=60)
        table = ReplicaTable(server.addr, interval=3600.0, watch=True)
        table.start()
        try:
            assert wait_for(lambda: len(table.replicas()) == 1)
            table.mark_failed("r0")
            assert len(table.replicas()) == 0
            # The frozen row proves nothing; a CHANGED row re-admits
            # the moment it lands — no poll tick involved.
            put(stub, "serve/r0", self._row(beat=2), lease=60)
            assert wait_for(lambda: len(table.replicas()) == 1,
                            timeout=5), \
                "changed row did not re-admit the failed replica"
        finally:
            table.stop()

    def test_falls_back_to_polling_on_pre_watch_registry(self):
        """Against a registry with no Watch RPC the table degrades to
        the original GetValues poll, transparently."""
        from oim_tpu.common.server import NonBlockingGRPCServer
        from oim_tpu.router.table import ReplicaTable

        class PreWatchRegistry(RegistryServicer):
            def GetValues(self, request, context):
                return pb.GetValuesReply(values=[pb.Value(
                    path="serve/r0",
                    value=json.dumps({"endpoint": "1.2.3.4:9",
                                      "ready": True}))])

        server = NonBlockingGRPCServer("tcp://127.0.0.1:0")
        server.start(lambda s: add_registry_to_server(
            PreWatchRegistry(), s))
        try:
            table = ReplicaTable(server.addr, interval=0.1, watch=True)
            table.start()
            assert wait_for(lambda: len(table.replicas()) == 1,
                            timeout=10), \
                "table never fell back to polling"
            table.stop()
        finally:
            server.force_stop()


class TestSerializeOnceFanout:
    """The hub's write-path contract at scale: one committed delta is
    serialized ONCE and every attached stream's frame is the same bytes
    object: the count behind the retired `serialize_once_x` timing
    gate is one serialisation a committed delta."""

    def _hub(self, **kwargs):
        return W.WatchHub(service=None, **kwargs)

    def test_fanout_shares_one_wire_frame(self):
        hub = self._hub()
        streams = [W._Stream(["serve"], maxsize=8) for _ in range(3)]
        hub._streams.extend(streams)
        hub.publish_kv("serve/r0", "v0", 5.0)
        deltas = [s.queue.get_nowait() for s in streams]
        assert deltas[0] is deltas[1] is deltas[2], \
            "streams queued distinct delta copies"
        wire = deltas[0].wire
        assert wire is not None, "fan-out did not eager-serialize"
        assert wire == hub._proto(deltas[0]).SerializeToString(), \
            "cached frame diverges from a fresh serialization"
        # Delivery serves the SAME bytes object — no re-serialization.
        assert hub._wire(deltas[0]) is wire

    def test_no_matching_stream_skips_serialization(self):
        """A delta no attached stream wants stays unserialized until a
        resuming watcher actually replays it from the ring."""
        hub = self._hub()
        hub._streams.append(W._Stream(["serve"], maxsize=8))
        hub.publish_kv("other/x", "v", 5.0)
        assert hub._ring[-1].wire is None

    def test_shed_lands_flight_recorder_event_with_high_water(self):
        """A shed must be diagnosable at scale: the stream dies, the
        counter moves, and a watch_stream_shed event records WHICH
        prefix and how deep the queue ran."""
        from oim_tpu.common import events as E
        from oim_tpu.common import metrics as M

        hub = self._hub(queue_max=2)
        stream = W._Stream(["serve"], maxsize=2)
        hub._streams.append(stream)
        rec = E.recorder()
        shed_before = len(rec.events(type_=E.WATCH_STREAM_SHED))
        metric_before = M.WATCH_SHED_STREAMS.value
        for i in range(3):
            hub.publish_kv(f"serve/r{i}", "v", 5.0)
        assert stream.dead.is_set(), "overflowed stream not shed"
        assert M.WATCH_SHED_STREAMS.value == metric_before + 1
        shed = rec.events(type_=E.WATCH_STREAM_SHED)
        assert len(shed) == shed_before + 1
        attrs = shed[-1].attrs
        assert attrs["prefix"] == "serve"
        assert attrs["queue_high_water"] == 2
        assert attrs["queue_max"] == 2
