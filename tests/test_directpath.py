"""Direct data path (ISSUE 5): proxy-free windows over pooled channels.

The reference's core rule is that the control plane stays off the data
path (README.md:39-40) — these tests pin the consume half: a feeder
resolves the owning controller's registered endpoint and streams
ReadVolume straight to it over ONE pooled channel; the registry's
transparent proxy remains the always-correct fallback. Pinned here:

* byte identity: direct ≡ proxy ≡ source, for windows and whole volumes;
* fallback: a blackholed direct endpoint degrades to the proxy inside
  one call, with identical bytes;
* pooling: N windows dial the controller exactly once (spy on
  tlsutil.dial), and a controller restart evicts the stale channel while
  the healed window still completes;
* zero-copy: the window path assembles into one preallocated buffer —
  no b"".join anywhere in the driver (source-pinned).
"""

from __future__ import annotations

import contextlib
import socket
import threading

import grpc
import numpy as np
import pytest

from oim_tpu.common import metrics as M, tlsutil
from oim_tpu.common.channelpool import ChannelPool
from oim_tpu.controller import ControllerService, MallocBackend
from oim_tpu.controller.controller import controller_server
from oim_tpu.feeder import Feeder
from oim_tpu.feeder.driver import PublishError
from oim_tpu.registry import MemRegistryDB, RegistryService
from oim_tpu.registry.registry import registry_server
from oim_tpu.spec import pb


def _publish_file(feeder, volume_id, tmp_path, nbytes=100_000, seed=5):
    data = np.random.RandomState(seed).bytes(nbytes)
    path = tmp_path / f"{volume_id}.bin"
    path.write_bytes(data)
    feeder.publish(pb.MapVolumeRequest(
        volume_id=volume_id,
        file=pb.FileParams(path=str(path), format="raw"),
    ))
    return data


def _read_all(feeder, volume_id, window=33_000):
    got = bytearray()
    offset = 0
    while True:
        w, total, spec = feeder.fetch_window(volume_id, offset, window)
        assert spec is not None
        got += w.tobytes()
        offset += w.size
        if offset >= total:
            return bytes(got)


@contextlib.contextmanager
def dead_endpoint():
    """An address that refuses connections for as long as the block runs:
    bound and never listening. (Bound then CLOSED, the port could be handed
    to another xdist worker's ``localhost:0`` server before the dial, and
    the "dead" endpoint would answer.)"""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        yield f"127.0.0.1:{s.getsockname()[1]}"


class TestChannelPool:
    def test_get_memoizes_per_target_and_peer(self):
        dialed = []

        def spy(address, tls, peer_name):
            dialed.append((address, peer_name))
            return grpc.insecure_channel(address)

        pool = ChannelPool(dial=spy)
        a = pool.get("localhost:1", None, "component.registry")
        assert pool.get("localhost:1", None, "component.registry") is a
        b = pool.get("localhost:1", None, "controller.host-0")
        assert b is not a  # distinct pinned peer = distinct channel
        pool.get("localhost:2", None, "component.registry")
        assert len(dialed) == 3
        assert len(pool) == 3
        assert pool.stats()[("localhost:1", "component.registry")] == 1
        pool.close()

    def test_evict_closes_and_redial_counts(self):
        pool = ChannelPool(
            dial=lambda a, t, p: grpc.insecure_channel(a))
        pool.get("localhost:1", None, "x")
        pool.get("localhost:1", None, "y")
        before = M.CHANNEL_POOL_SIZE.value
        assert pool.evict("localhost:1") == 2
        assert M.CHANNEL_POOL_SIZE.value == before - 2
        assert len(pool) == 0
        pool.get("localhost:1", None, "x")
        assert pool.stats()[("localhost:1", "x")] == 2  # re-dialed
        pool.close()

    def test_maybe_evict_only_on_transport_codes(self):
        """Answered statuses keep the channel; transport-class ones
        (refused AND black-holed — DEADLINE_EXCEEDED is how a dead
        established flow presents) drop it so the next get re-dials."""
        pool = ChannelPool(
            dial=lambda a, t, p: grpc.insecure_channel(a))

        class Err(grpc.RpcError):
            def __init__(self, code):
                self._code = code

            def code(self):
                return self._code

        pool.get("localhost:1")
        assert not pool.maybe_evict(
            Err(grpc.StatusCode.NOT_FOUND), "localhost:1")
        assert len(pool) == 1
        assert pool.maybe_evict(
            Err(grpc.StatusCode.UNAVAILABLE), "localhost:1")
        assert len(pool) == 0
        pool.get("localhost:1")
        assert pool.maybe_evict(
            Err(grpc.StatusCode.DEADLINE_EXCEEDED), "localhost:1")
        assert len(pool) == 0
        pool.close()

    def test_concurrent_get_dials_once(self):
        dials = []
        gate = threading.Barrier(8)

        def spy(address, tls, peer_name):
            dials.append(address)
            return grpc.insecure_channel(address)

        pool = ChannelPool(dial=spy)
        results = []

        def run():
            gate.wait()
            results.append(pool.get("localhost:9", None, "p"))

        threads = [threading.Thread(target=run) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(dials) == 1
        assert len({id(c) for c in results}) == 1
        pool.close()


class TestDirectWindows:
    @pytest.fixture(autouse=True)
    def _close_pools(self):
        # Tests create private pools (the process-wide shared() pool
        # would leak channels across tests); close them so no channel is
        # garbage-collected with gRPC machinery still attached.
        self._pools: list[ChannelPool] = []
        yield
        for pool in self._pools:
            pool.close()

    @pytest.fixture
    def cluster(self):
        db = MemRegistryDB()
        registry = registry_server("tcp://localhost:0", RegistryService(db=db))
        service = ControllerService(MallocBackend())
        controller = controller_server("tcp://localhost:0", service)
        db.set("host-0/address", controller.addr)
        db.set("host-0/mesh", "1,2,3")
        yield db, registry, controller
        registry.force_stop()
        controller.force_stop()

    def feeder_for(self, registry, **kw):
        pool = kw.setdefault("pool", ChannelPool())
        self._pools.append(pool)
        return Feeder(registry_address=registry.addr, controller_id="host-0",
                      **kw)

    def test_direct_and_proxy_windows_byte_identical(self, cluster, tmp_path):
        _, registry, _ = cluster
        direct = self.feeder_for(registry)
        data = _publish_file(direct, "vol-d", tmp_path)
        proxy = self.feeder_for(registry, direct_data=False)
        d_before = M.WINDOW_PATH_TOTAL.labels(path="direct").value
        p_before = M.WINDOW_PATH_TOTAL.labels(path="proxy").value
        assert _read_all(direct, "vol-d") == data
        assert _read_all(proxy, "vol-d") == data
        assert M.WINDOW_PATH_TOTAL.labels(path="direct").value > d_before
        assert M.WINDOW_PATH_TOTAL.labels(path="proxy").value > p_before
        # Whole-volume fetch rides the same machinery on both paths.
        assert direct.fetch("vol-d").tobytes() == data
        assert proxy.fetch("vol-d").tobytes() == data

    def test_n_windows_reuse_exactly_one_controller_channel(
            self, cluster, tmp_path, monkeypatch):
        _, registry, controller = cluster
        dialed: list[str] = []
        real_dial = tlsutil.dial

        def spy(address, tls, peer_name=""):
            dialed.append(address)
            return real_dial(address, tls, peer_name)

        monkeypatch.setattr(tlsutil, "dial", spy)
        feeder = self.feeder_for(registry)
        data = _publish_file(feeder, "vol-n", tmp_path)
        dialed.clear()
        for i in range(8):
            w, total, _ = feeder.fetch_window("vol-n", i * 10_000, 10_000)
            assert w.tobytes() == data[i * 10_000:(i + 1) * 10_000]
        # 8 windows: ONE direct channel to the controller, and at most
        # one (pre-pooled) registry channel for endpoint resolution —
        # never a dial per window.
        assert dialed.count(controller.addr) == 1
        assert len(dialed) <= 2

    def test_blackholed_direct_endpoint_falls_back_to_proxy(
            self, cluster, tmp_path):
        _, registry, _ = cluster
        feeder = self.feeder_for(registry)
        data = _publish_file(feeder, "vol-b", tmp_path)
        # Blackhole ONLY the direct path: seed the resolver cache with an
        # address nothing serves (the registry still routes the proxy to
        # the live controller).
        import time as _time

        p_before = M.WINDOW_PATH_TOTAL.labels(path="proxy").value
        with dead_endpoint() as dead:
            feeder._direct_addr = (dead, _time.monotonic())
            w, total, _ = feeder.fetch_window("vol-b", 0, 10_000)
        assert w.tobytes() == data[:10_000] and total == len(data)
        assert M.WINDOW_PATH_TOTAL.labels(path="proxy").value == p_before + 1
        # The dead endpoint was invalidated: the next window re-resolves
        # the real one and goes direct again.
        d_before = M.WINDOW_PATH_TOTAL.labels(path="direct").value
        w2, _, _ = feeder.fetch_window("vol-b", 10_000, 10_000)
        assert w2.tobytes() == data[10_000:20_000]
        assert M.WINDOW_PATH_TOTAL.labels(path="direct").value == d_before + 1

    def test_hanging_direct_endpoint_falls_back_and_backs_off(
            self, cluster, tmp_path):
        """A registered-but-unroutable endpoint HANGS instead of refusing
        (firewalled pod IP): the unverified channel's 1-byte first-
        contact probe — bounded at min(5s, half the budget) — eats the
        hang instead of the window read burning the caller's whole
        deadline. The same call must still complete via the proxy, and
        the direct path backs off so the NEXT window doesn't stall
        again."""
        _, registry, _ = cluster
        feeder = self.feeder_for(registry)
        data = _publish_file(feeder, "vol-hang", tmp_path)
        # A listener that accepts TCP but never speaks HTTP/2: the RPC
        # hangs until its deadline.
        listener = socket.socket()
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        hang_addr = f"127.0.0.1:{listener.getsockname()[1]}"
        try:
            import time as _time

            feeder._direct_addr = (hang_addr, _time.monotonic())
            p_before = M.WINDOW_PATH_TOTAL.labels(path="proxy").value
            probes = []
            probe = feeder._direct_channel_usable
            feeder._direct_channel_usable = (
                lambda *a: probes.append(probe(*a)) or probes[-1])
            # The probe takes half of this budget (2 s) and the proxy read
            # has the rest: the window coming back at all is the deadline
            # kept, whatever six busy workers make of the proxy's half.
            w, total, _ = feeder.fetch_window("vol-hang", 0, 10_000,
                                              timeout=4.0)
            assert w.tobytes() == data[:10_000] and total == len(data)
            assert probes == [False], "the hang was not eaten by ONE probe"
            assert (M.WINDOW_PATH_TOTAL.labels(path="proxy").value
                    == p_before + 1)
            # Back-off armed: the next window goes straight to the proxy,
            # with no second probe whose deadline it would wait out.
            assert feeder._direct_endpoint() is None
            w2, _, _ = feeder.fetch_window("vol-hang", 10_000, 10_000,
                                           timeout=4.0)
            assert w2.tobytes() == data[10_000:20_000]
            assert probes == [False], "the second window probed again"
            assert (M.WINDOW_PATH_TOTAL.labels(path="proxy").value
                    == p_before + 2)
        finally:
            listener.close()

    def test_negative_chunk_bytes_rejected_client_and_server(
            self, cluster, tmp_path):
        """A negative chunk request must not clamp to 1-byte messages:
        the Feeder rejects it at construction, and a raw stub sending one
        anyway gets the server DEFAULT, not millions of tiny chunks."""
        _, registry, controller = cluster
        with pytest.raises(ValueError, match="window_chunk_bytes"):
            Feeder(registry_address=registry.addr, controller_id="host-0",
                   window_chunk_bytes=-1, pool=ChannelPool())
        feeder = self.feeder_for(registry)
        data = _publish_file(feeder, "vol-neg", tmp_path)
        channel = tlsutil.dial(controller.addr, None)
        try:
            from oim_tpu.spec import ControllerStub

            chunks = list(ControllerStub(channel).ReadVolume(
                pb.ReadVolumeRequest(volume_id="vol-neg", chunk_bytes=-5),
                timeout=30,
            ))
        finally:
            channel.close()
        assert len(chunks) == 1  # 100 KB under the 3 MiB default chunk
        assert chunks[0].data == data

    def test_direct_not_found_is_not_masked_by_fallback(self, cluster):
        _, registry, _ = cluster
        feeder = self.feeder_for(registry)
        with pytest.raises(PublishError, match="NOT_FOUND"):
            feeder.fetch_window("ghost", 0, 100)

    def test_controller_restart_evicts_pooled_channel_and_heals(
            self, cluster, tmp_path):
        db, registry, controller = cluster
        feeder = self.feeder_for(registry)
        data = _publish_file(feeder, "vol-r", tmp_path)
        w, _, _ = feeder.fetch_window("vol-r", 0, 10_000)
        assert w.tobytes() == data[:10_000]
        old_addr = controller.addr
        assert old_addr in feeder._pool.targets()  # direct channel pooled
        # Controller dies; a replacement with empty soft state registers
        # at a NEW address (the restart story of test_feeder, now with a
        # pooled direct channel pointing at the corpse).
        controller.force_stop()
        svc2 = ControllerService(MallocBackend())
        ctrl2 = controller_server("tcp://localhost:0", svc2)
        db.set("host-0/address", ctrl2.addr)
        try:
            w2, total2, _ = feeder.fetch_window(
                "vol-r", 10_000, 10_000, timeout=30, heal=True)
            assert w2.tobytes() == data[10_000:20_000]
            assert total2 == len(data)
            assert svc2.get_volume("vol-r") is not None  # restaged
            # The dead endpoint's channel is gone from the pool; the new
            # one is in (no half-dead channels accumulate across heals).
            assert old_addr not in feeder._pool.targets()
            assert ctrl2.addr in feeder._pool.targets()
        finally:
            ctrl2.force_stop()

    def test_address_watch_pushes_moves_without_ttl_wait(self, cluster):
        """PR 14's named follow-up: the direct-path resolver rides a
        Watch stream on the one address key — an address re-registered
        through the WRITE path (apply_kv, what a real re-registration
        does) reaches _direct_endpoint the moment it commits, not one
        DIRECT_TTL_S later; a pushed lease expiry turns the direct path
        off the same way."""
        import time as time_mod

        db, _, controller = cluster
        service = RegistryService(db=db)
        registry = registry_server("tcp://localhost:0", service)
        try:
            feeder = self.feeder_for(registry)
            assert feeder._direct_endpoint() == controller.addr
            watch = feeder._address_watch
            assert watch is not None
            deadline = time_mod.monotonic() + 5
            while watch.value() is None:  # wait for the stream to sync
                assert time_mod.monotonic() < deadline, \
                    "watch never synced"
                time_mod.sleep(0.02)
            # The address moves through the committed-write path; the
            # stale TTL cache would have served the old value for 30s —
            # the push must override it.
            service.apply_kv("host-0/address", "10.9.9.9:1", 0.0)
            deadline = time_mod.monotonic() + 5
            while feeder._direct_endpoint() != "10.9.9.9:1":
                assert time_mod.monotonic() < deadline, \
                    "pushed address move never reached the resolver"
                time_mod.sleep(0.02)
            # Delete (the lease-expiry/deregistration shape): the
            # stream PROVES no live row — direct path off, no poll.
            service.apply_kv("host-0/address", "", 0.0)
            deadline = time_mod.monotonic() + 5
            while feeder._direct_endpoint() is not None:
                assert time_mod.monotonic() < deadline, \
                    "pushed delete never disabled the direct path"
                time_mod.sleep(0.02)
            feeder.close()
            assert feeder._address_watch is None
        finally:
            registry.force_stop()

    def test_address_watch_falls_back_to_poll_pre_watch(self, cluster):
        """Against a registry with no Watch RPC the resolver degrades to
        the original GetValues poll permanently (UNIMPLEMENTED retires
        the stream — the mixed-version stance)."""
        import time as time_mod

        class _NoWatch(RegistryService):
            def Watch(self, request, context):
                context.abort(grpc.StatusCode.UNIMPLEMENTED, "pre-watch")

        db, _, controller = cluster
        old_registry = registry_server(
            "tcp://localhost:0", _NoWatch(db=db))
        try:
            feeder = self.feeder_for(old_registry)
            assert feeder._direct_endpoint() == controller.addr
            deadline = time_mod.monotonic() + 5
            while not feeder._address_watch._unsupported:
                assert time_mod.monotonic() < deadline
                time_mod.sleep(0.02)
            # Poll keeps answering (and honors its TTL cache).
            assert feeder._direct_endpoint() == controller.addr
            assert feeder._address_watch.value() is None
            feeder.close()
        finally:
            old_registry.force_stop()

    def test_direct_disabled_never_dials_controller(
            self, cluster, tmp_path, monkeypatch):
        _, registry, controller = cluster
        dialed: list[str] = []
        real_dial = tlsutil.dial

        def spy(address, tls, peer_name=""):
            dialed.append(address)
            return real_dial(address, tls, peer_name)

        monkeypatch.setattr(tlsutil, "dial", spy)
        feeder = self.feeder_for(registry, direct_data=False)
        data = _publish_file(feeder, "vol-p", tmp_path)
        dialed.clear()
        w, _, _ = feeder.fetch_window("vol-p", 0, 10_000)
        assert w.tobytes() == data[:10_000]
        assert controller.addr not in dialed

    def test_big_window_streams_in_large_chunks(self, cluster, tmp_path):
        """A >4 MiB window must cross in few messages (the raised server
        cap + requested chunk_bytes), not in 3 MiB shards — and arrive
        byte-identical."""
        _, registry, controller = cluster
        feeder = self.feeder_for(registry)
        data = _publish_file(feeder, "vol-big", tmp_path, nbytes=12 << 20,
                             seed=11)
        fetched = feeder.fetch("vol-big")
        assert fetched.tobytes() == data
        # Raw stub with a big requested chunk: the server honors it now
        # that MAX_READ_CHUNK > DEFAULT_READ_CHUNK.
        channel = tlsutil.dial(controller.addr, None)
        try:
            from oim_tpu.spec import ControllerStub

            chunks = list(ControllerStub(channel).ReadVolume(
                pb.ReadVolumeRequest(volume_id="vol-big",
                                     chunk_bytes=16 << 20),
                timeout=30,
            ))
        finally:
            channel.close()
        assert len(chunks) == 1  # 12 MiB in ONE message
        assert chunks[0].data == data


class TestHeartbeatPooling:
    def test_heartbeat_loop_reuses_one_channel(self, monkeypatch):
        from oim_tpu.controller.controller import Controller

        db = MemRegistryDB()
        registry = registry_server("tcp://localhost:0", RegistryService(db=db))
        dialed: list[str] = []
        real_dial = tlsutil.dial

        def spy(address, tls, peer_name=""):
            dialed.append(address)
            return real_dial(address, tls, peer_name)

        monkeypatch.setattr(tlsutil, "dial", spy)
        try:
            ctl = Controller(
                "host-hb", backend=MallocBackend(),
                controller_address="localhost:1",
                registry_address=registry.addr,
                pool=ChannelPool(),
            )
            ctl.register_once()
            for _ in range(3):
                assert ctl.heartbeat_once() is True
            assert dialed.count(registry.addr) == 1
        finally:
            registry.force_stop()


class TestZeroCopyAssembly:
    def test_no_join_copy_on_the_window_path(self):
        """The acceptance criterion 'no b"".join remains on the window
        path', pinned at the source level like the metrics drift test."""
        from pathlib import Path

        import oim_tpu.feeder.driver as driver_mod

        source = Path(driver_mod.__file__).read_text()
        assert 'b"".join' not in source and "b''.join" not in source

    def test_window_lands_in_one_preallocated_buffer(self, tmp_path):
        """Multi-chunk windows must come back as ONE contiguous buffer
        (np.frombuffer over the preallocated bytearray), not a
        concatenation result."""
        db = MemRegistryDB()
        registry = registry_server("tcp://localhost:0", RegistryService(db=db))
        service = ControllerService(MallocBackend())
        controller = controller_server("tcp://localhost:0", service)
        db.set("host-0/address", controller.addr)
        pool = ChannelPool()
        try:
            feeder = Feeder(registry_address=registry.addr,
                            controller_id="host-0", pool=pool,
                            window_chunk_bytes=4 << 10)  # force many chunks
            data = _publish_file(feeder, "vol-z", tmp_path, nbytes=64 << 10)
            w, total, _ = feeder.fetch_window("vol-z", 1_000, 50_000)
            assert w.tobytes() == data[1_000:51_000]
            assert total == len(data)
            assert w.base is not None  # a view over the landing buffer
            assert isinstance(w.base, (bytearray, memoryview, np.ndarray))
        finally:
            pool.close()
            registry.force_stop()
            controller.force_stop()


class TestDirectPathAuthz:
    """Controller-side peer-CN check: the host.<id> -> <id> rule, bound
    on the DIRECT path (doc/architecture.md's security note, closed).
    cryptography-free seam: the servicer reads the verified CN through
    context.auth_context(), so a fake context exercises every branch."""

    class _Ctx:
        def __init__(self, cn=None):
            self._cn = cn

        def auth_context(self):
            return {"x509_common_name": [self._cn.encode()]} if self._cn \
                else {}

        def abort(self, code, details):
            raise AssertionError(f"{code.name}: {details}")

    @pytest.fixture
    def service(self):
        return ControllerService(MallocBackend(), controller_id="host-0")

    def _read(self, service, ctx):
        list(service.ReadVolume(pb.ReadVolumeRequest(volume_id="none"), ctx))

    def test_assigned_host_proxy_and_admin_pass(self, service):
        # Authorized peers fall through the gate to the volume lookup.
        for cn in ("host.host-0", "component.registry", "user.admin"):
            with pytest.raises(AssertionError, match="NOT_FOUND"):
                self._read(service, self._Ctx(cn))

    def test_foreign_host_denied_before_any_lookup(self, service):
        for cn in ("host.host-1", "controller.host-1", "component.feeder"):
            with pytest.raises(AssertionError, match="PERMISSION_DENIED"):
                self._read(service, self._Ctx(cn))
            with pytest.raises(AssertionError, match="PERMISSION_DENIED"):
                service.PrestageVolume(
                    pb.MapVolumeRequest(volume_id="v"), self._Ctx(cn))

    def test_every_controller_rpc_guarded(self, service):
        # The rule covers the mutating control RPCs too — a direct
        # UnmapVolume would be worse than a direct read.
        ctx = self._Ctx("host.host-1")
        calls = [
            lambda: service.MapVolume(
                pb.MapVolumeRequest(volume_id="v"), ctx),
            lambda: service.UnmapVolume(
                pb.UnmapVolumeRequest(volume_id="v"), ctx),
            lambda: service.ProvisionMallocBDev(
                pb.ProvisionMallocBDevRequest(bdev_name="b", size=1), ctx),
            lambda: service.CheckMallocBDev(
                pb.CheckMallocBDevRequest(bdev_name="b"), ctx),
            lambda: service.StageStatus(
                pb.StageStatusRequest(volume_id="v"), ctx),
        ]
        for call in calls:
            with pytest.raises(AssertionError, match="PERMISSION_DENIED"):
                call()

    def test_unauthenticated_transport_unenforced(self, service):
        # Insecure transport verifies no CN: nothing to bind on (the
        # same condition under which the proxy skips its check).
        with pytest.raises(AssertionError, match="NOT_FOUND"):
            self._read(service, self._Ctx(None))

    def test_bare_service_unenforced(self):
        # A service that doesn't know its own id (tests, local mode)
        # keeps the open behavior.
        bare = ControllerService(MallocBackend())
        with pytest.raises(AssertionError, match="NOT_FOUND"):
            self._read(bare, self._Ctx("host.host-9"))


class TestProxyPooling:
    """The transparent proxy pools its controller channels (the last
    per-call dialer on the serving path): N proxied calls ride ONE
    dial, a transport failure evicts, and the next call re-dials."""

    def test_n_proxied_calls_one_dial_and_heal(self, tmp_path):
        from oim_tpu.spec import ControllerStub

        db = MemRegistryDB()
        dialed: list[str] = []

        def counting_dial(address, peer_name):
            dialed.append(address)
            return grpc.insecure_channel(address)

        registry = registry_server(
            "tcp://localhost:0", RegistryService(db=db), dial=counting_dial)
        service = ControllerService(MallocBackend())
        controller = controller_server("tcp://localhost:0", service)
        db.set("host-0/address", controller.addr)
        channel = grpc.insecure_channel(registry.addr)
        stub = ControllerStub(channel)
        meta = [("controllerid", "host-0")]

        def status(volume_id="ghost"):
            stub.StageStatus(
                pb.StageStatusRequest(volume_id=volume_id),
                metadata=meta, timeout=10)

        try:
            for _ in range(5):
                with pytest.raises(grpc.RpcError) as err:
                    status()
                # NOT_FOUND = the far end ANSWERED: healthy channel.
                assert err.value.code() == grpc.StatusCode.NOT_FOUND
            assert dialed == [controller.addr], \
                "5 proxied calls must reuse one pooled channel"

            # Controller dies: the proxied call surfaces a transport
            # failure and the proxy evicts its pooled channel ...
            controller.force_stop()
            with pytest.raises(grpc.RpcError) as err:
                status()
            assert err.value.code() == grpc.StatusCode.UNAVAILABLE
            # ... so the replacement (new address, same id) is reached
            # with a fresh dial on the very next call.
            svc2 = ControllerService(MallocBackend())
            ctrl2 = controller_server("tcp://localhost:0", svc2)
            db.set("host-0/address", ctrl2.addr)
            try:
                with pytest.raises(grpc.RpcError) as err:
                    status()
                assert err.value.code() == grpc.StatusCode.NOT_FOUND
                assert dialed[-1] == ctrl2.addr
            finally:
                ctrl2.force_stop()
        finally:
            channel.close()
            registry.force_stop()
            controller.force_stop()


class TestCrossControllerPrestage:
    """The mTLS prestage exemption (registry.py TransparentProxy
    _may_prestage): the strict ``host.<id>`` -> ``<id>`` proxy rule
    blocks warm-standby and serve weight fan-out, both of which
    PrestageVolume a PEER controller — so PrestageVolume (and ONLY it)
    is open to any live mesh member: a host whose own controller is
    registered with an unexpired lease. Driven through the proxy's
    ``_forward`` with a fake TLS context (same cryptography-free seam as
    TestDirectPathAuthz)."""

    class _Abort(Exception):
        def __init__(self, code, details):
            self.code = code
            self.details = details
            super().__init__(f"{code.name}: {details}")

    class _Ctx:
        def __init__(self, cn):
            self._cn = cn

        def auth_context(self):
            return {"x509_common_name": [self._cn.encode()]} if self._cn \
                else {}

        def abort(self, code, details):
            raise TestCrossControllerPrestage._Abort(code, details)

        def time_remaining(self):
            return 30.0

    @pytest.fixture
    def mesh(self):
        """Registry service with FAKE tls (authz enforced) + a real
        insecure controller B the proxy can dial; host A is a live
        lease-holding mesh member, host C is unregistered."""
        from oim_tpu.common.tlsutil import TLSConfig
        from oim_tpu.registry.leases import LeaseTable
        from oim_tpu.registry.registry import TransparentProxy

        now = [1000.0]
        db = MemRegistryDB()
        service = RegistryService(
            db=db, tls=TLSConfig(ca_pem=b"x", key_pem=b"x", cert_pem=b"x"),
            leases=LeaseTable(clock=lambda: now[0]))
        controller = controller_server(
            "tcp://localhost:0", ControllerService(MallocBackend()))
        db.set("B/address", controller.addr)
        db.set("A/address", "somewhere:1")
        service.leases.grant("A/address", 30.0)
        proxy = TransparentProxy(
            service, dial=lambda addr, peer: grpc.insecure_channel(addr))
        try:
            yield proxy, now
        finally:
            proxy.close()
            controller.force_stop()

    PRESTAGE = "/oim.v1.Controller/PrestageVolume"
    READ = "/oim.v1.Controller/ReadVolume"

    def _call(self, proxy, method, cn, target="B"):
        request = pb.MapVolumeRequest(volume_id="warm").SerializeToString()
        return list(proxy._forward(
            method, (("controllerid", target),), iter([request]),
            self._Ctx(cn)))

    def test_live_host_may_prestage_foreign_controller(self, mesh):
        proxy, _ = mesh
        # host.A reaches controller B THROUGH the authz gate: the abort
        # seen is the controller's own INVALID_ARGUMENT for the empty
        # volume params, not the proxy's PERMISSION_DENIED.
        with pytest.raises(self._Abort) as err:
            self._call(proxy, self.PRESTAGE, "host.A")
        assert err.value.code is grpc.StatusCode.INVALID_ARGUMENT
        assert "no volume params" in err.value.details

    def test_only_the_prestage_rpc_is_exempt(self, mesh):
        proxy, _ = mesh
        with pytest.raises(self._Abort) as err:
            self._call(proxy, self.READ, "host.A")
        assert err.value.code is grpc.StatusCode.PERMISSION_DENIED

    def test_unregistered_host_stays_locked_out(self, mesh):
        proxy, _ = mesh
        with pytest.raises(self._Abort) as err:
            self._call(proxy, self.PRESTAGE, "host.C")
        assert err.value.code is grpc.StatusCode.PERMISSION_DENIED

    def test_expired_lease_revokes_the_exemption(self, mesh):
        proxy, now = mesh
        now[0] += 31.0  # host A's own lease lapses: not a live member
        with pytest.raises(self._Abort) as err:
            self._call(proxy, self.PRESTAGE, "host.A")
        assert err.value.code is grpc.StatusCode.PERMISSION_DENIED

    def test_non_host_identities_not_exempt(self, mesh):
        proxy, _ = mesh
        for cn in ("component.feeder", "controller.A", None):
            with pytest.raises(self._Abort) as err:
                self._call(proxy, self.PRESTAGE, cn)
            assert err.value.code is grpc.StatusCode.PERMISSION_DENIED, cn

    def test_own_host_rule_untouched(self, mesh):
        proxy, _ = mesh
        # host.B keeps full access to its own controller (ReadVolume
        # reaches the volume lookup -> NOT_FOUND, not PERMISSION_DENIED).
        with pytest.raises(self._Abort) as err:
            self._call(proxy, self.READ, "host.B")
        assert err.value.code is grpc.StatusCode.NOT_FOUND


class TestWindowCompression:
    """Opt-in wire compression for ReadVolume windows (ISSUE 17,
    --window-compress): negotiated PER STREAM — the request declares
    the client can decompress, the server compresses a chunk only when
    that actually shrinks it — so every mixed-version pairing interops:
    an old client never receives compressed bytes, an old server's raw
    chunks (compressed absent = False) read fine on a new client, and
    offsets/total_bytes stay in uncompressed space throughout."""

    @pytest.fixture
    def cluster(self):
        db = MemRegistryDB()
        registry = registry_server("tcp://localhost:0",
                                   RegistryService(db=db))
        controller = controller_server(
            "tcp://localhost:0", ControllerService(MallocBackend()))
        db.set("host-0/address", controller.addr)
        db.set("host-0/mesh", "1,2,3")
        pool = ChannelPool()
        yield registry, controller, pool
        pool.close()
        registry.force_stop()
        controller.force_stop()

    def _publish(self, registry, pool, tmp_path, volume_id, data):
        feeder = Feeder(registry_address=registry.addr,
                        controller_id="host-0", pool=pool)
        path = tmp_path / f"{volume_id}.bin"
        path.write_bytes(data)
        feeder.publish(pb.MapVolumeRequest(
            volume_id=volume_id,
            file=pb.FileParams(path=str(path), format="raw")))
        return feeder

    def _chunks(self, controller, volume_id, accept: bool,
                chunk_bytes: int = 16_384):
        from oim_tpu.spec import ControllerStub

        channel = tlsutil.dial(controller.addr, None)
        try:
            return list(ControllerStub(channel).ReadVolume(
                pb.ReadVolumeRequest(volume_id=volume_id,
                                     chunk_bytes=chunk_bytes,
                                     accept_compressed=accept),
                timeout=30))
        finally:
            channel.close()

    def test_negotiated_stream_compresses_cold_extents(
            self, cluster, tmp_path):
        import zlib

        registry, controller, pool = cluster
        data = b"oim-kv-page " * 8_000  # squeezes like a cold KV extent
        self._publish(registry, pool, tmp_path, "vol-z", data)
        chunks = self._chunks(controller, "vol-z", accept=True)
        assert len(chunks) > 1
        assert all(c.compressed for c in chunks)
        # Offsets stay in UNCOMPRESSED space: each chunk covers the
        # window math's 16 KiB stride no matter what shipped.
        assert [c.offset for c in chunks] == \
            [i * 16_384 for i in range(len(chunks))]
        assert chunks[0].total_bytes == len(data)
        rebuilt = b"".join(zlib.decompress(c.data) for c in chunks)
        assert rebuilt == data
        wire = sum(len(c.data) for c in chunks)
        assert wire < len(data) // 2  # the point of the flag

    def test_old_client_never_receives_compressed_bytes(
            self, cluster, tmp_path):
        registry, controller, pool = cluster
        data = b"oim-kv-page " * 8_000
        self._publish(registry, pool, tmp_path, "vol-old", data)
        chunks = self._chunks(controller, "vol-old", accept=False)
        assert not any(c.compressed for c in chunks)
        assert b"".join(c.data for c in chunks) == data

    def test_incompressible_chunks_ship_raw_even_when_negotiated(
            self, cluster, tmp_path):
        registry, controller, pool = cluster
        data = np.random.RandomState(11).bytes(80_000)  # won't shrink
        self._publish(registry, pool, tmp_path, "vol-rand", data)
        chunks = self._chunks(controller, "vol-rand", accept=True)
        # compressed=False chunks are exactly what an OLD server sends
        # (field absent reads False) — the raw path IS the old-server
        # interop path, and the new client must take it per chunk.
        assert not any(c.compressed for c in chunks)
        assert b"".join(c.data for c in chunks) == data

    def test_feeder_window_compress_end_to_end_byte_identical(
            self, cluster, tmp_path):
        registry, _, pool = cluster
        data = b"shared system prompt kv " * 5_000
        self._publish(registry, pool, tmp_path, "vol-e2e", data)
        on = Feeder(registry_address=registry.addr, controller_id="host-0",
                    pool=pool, window_compress=True)
        off = Feeder(registry_address=registry.addr, controller_id="host-0",
                     pool=pool)
        assert _read_all(on, "vol-e2e") == data
        assert _read_all(off, "vol-e2e") == data
        w, total, _ = on.fetch_window("vol-e2e", 7_000, 9_000)
        assert w.tobytes() == data[7_000:16_000] and total == len(data)
