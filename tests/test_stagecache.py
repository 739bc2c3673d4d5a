"""Ring-1 tests for the content-addressed stage cache
(oim_tpu/controller/stagecache.py) and its controller/feeder wiring: an
identical re-publish returns the resident array in O(1) WITHOUT re-reading
the source; changed sources miss; idle entries evict under capacity
pressure; PrestageVolume warms a controller's cache ahead of MapVolume
(the warm-standby failover path)."""

import threading
import time

import numpy as np
import pytest

import grpc

from oim_tpu.common import metrics as M
from oim_tpu.common.meshcoord import MeshCoord
from oim_tpu.controller import malloc_backend, stagecache
from oim_tpu.controller.backend import StageState
from oim_tpu.controller.controller import (
    Controller,
    ControllerService,
    controller_server,
)
from oim_tpu.controller.malloc_backend import MallocBackend
from oim_tpu.controller.tpu_backend import TPUBackend
from oim_tpu.data import plane
from oim_tpu.feeder import Feeder
from oim_tpu.registry.db import MemRegistryDB
from oim_tpu.registry.registry import RegistryService, registry_server
from oim_tpu.spec import RegistryStub, pb


def wait_for(predicate, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


class _Ctx:
    """grpc context adapter for in-process servicer calls."""

    def abort(self, code, details):
        raise AssertionError(f"{code.name}: {details}")


def _file_request(path, volume_id="vol", shape=None, dtype="uint8"):
    spec = pb.ArraySpec(dtype=dtype)
    if shape:
        spec.shape.extend(shape)
    return pb.MapVolumeRequest(
        volume_id=volume_id, spec=spec,
        file=pb.FileParams(path=str(path), format="raw"),
    )


@pytest.fixture
def counted_reads(monkeypatch):
    """Counts plane-path file reads (TPUBackend) AND whole-read loads
    (MallocBackend fallback), so "no source re-read" is provable."""
    counts = {"reads": 0}
    orig_reader = plane.READERS["file"]

    def counting_reader(*args, **kwargs):
        counts["reads"] += 1
        return orig_reader(*args, **kwargs)

    orig_load = malloc_backend.load_source

    def counting_load(*args, **kwargs):
        counts["reads"] += 1
        return orig_load(*args, **kwargs)

    monkeypatch.setitem(plane.READERS, "file", counting_reader)
    monkeypatch.setattr(malloc_backend, "load_source", counting_load)
    return counts


class TestStageCacheUnit:
    def _entry_bytes(self, cache):
        return cache.stats()["bytes"]

    def test_lookup_miss_then_insert_hit(self):
        cache = stagecache.StageCache(capacity_bytes=1 << 20)
        assert cache.lookup("k1") is None
        arr = np.arange(10, dtype=np.uint8)
        entry = cache.insert("k1", arr, arr.nbytes, ("/a",))
        cache.release(entry)
        hit = cache.lookup("k1")
        assert hit is entry and hit.pins == 1
        np.testing.assert_array_equal(hit.array, arr)

    def test_lru_eviction_under_capacity(self):
        cache = stagecache.StageCache(capacity_bytes=120)
        e1 = cache.insert("k1", np.zeros(60, np.uint8), 60, ("/a",))
        cache.release(e1)
        e2 = cache.insert("k2", np.zeros(30, np.uint8), 30, ("/b",))
        cache.release(e2)
        # Touch k1 so k2 becomes LRU; a 50-byte insert must evict only k2
        # (60 + 50 fits in 120 once the 30 is gone).
        cache.release(cache.lookup("k1"))
        e3 = cache.insert("k3", np.zeros(50, np.uint8), 50, ("/c",))
        cache.release(e3)
        assert cache.lookup("k2") is None
        assert cache.lookup("k1") is not None

    def test_pinned_entries_never_evicted(self):
        cache = stagecache.StageCache(capacity_bytes=100)
        pinned = cache.insert("k1", np.zeros(80, np.uint8), 80, ("/a",))
        # k1 stays pinned: the new insert cannot fit and stays uncached.
        e2 = cache.insert("k2", np.zeros(80, np.uint8), 80, ("/b",))
        assert cache.lookup("k1") is not None
        assert cache.lookup("k2") is None  # never indexed
        cache.release(e2)  # uncached entry: release just frees it
        assert pinned.pins >= 1

    def test_stale_locator_invalidated_on_insert(self):
        cache = stagecache.StageCache(capacity_bytes=1 << 20)
        old = cache.insert("old", np.zeros(10, np.uint8), 10, ("/same",),
                           source_sig="content-v1")
        cache.release(old)
        new = cache.insert("new", np.ones(10, np.uint8), 10, ("/same",),
                           source_sig="content-v2")
        cache.release(new)
        # The source changed on disk (new source signature, same
        # locator): the stale bytes can never match again and must go.
        assert cache.lookup("old") is None
        assert cache.lookup("new") is not None

    def test_same_content_different_specs_coexist(self):
        """Two specs/placements of the SAME unchanged file (same source
        signature, different cache keys) must not evict each other."""
        cache = stagecache.StageCache(capacity_bytes=1 << 20)
        a = cache.insert("spec-a", np.zeros(10, np.uint8), 10, ("/f",),
                         source_sig="content-v1")
        cache.release(a)
        b = cache.insert("spec-b", np.ones(10, np.uint8), 10, ("/f",),
                         source_sig="content-v1")
        cache.release(b)
        assert cache.lookup("spec-a") is not None
        assert cache.lookup("spec-b") is not None

    def test_capacity_zero_disables(self):
        cache = stagecache.StageCache(capacity_bytes=0)
        e = cache.insert("k", np.zeros(4, np.uint8), 4, ("/a",))
        cache.release(e)
        assert cache.lookup("k") is None

    def test_release_keep_false_frees_idle(self):
        cache = stagecache.StageCache(capacity_bytes=1 << 20)
        e = cache.insert("k", np.zeros(4, np.uint8), 4, ("/a",))
        cache.release(e, keep=False)
        assert cache.lookup("k") is None
        assert self._entry_bytes(cache) == 0

    def test_evict_idle_frees_everything_idle(self):
        cache = stagecache.StageCache(capacity_bytes=1 << 20)
        idle = cache.insert("a", np.zeros(10, np.uint8), 10, ("/a",))
        cache.release(idle)
        cache.insert("b", np.zeros(20, np.uint8), 20, ("/b",))  # pinned
        assert cache.evict_idle() == 10
        assert cache.lookup("a") is None
        assert cache.lookup("b") is not None


class TestObjectFingerprint:
    """Object-store sources are cacheable only when the store provides a
    freshness validator (ETag / Last-Modified): a same-size re-upload
    must change the key, and a validator-less store must not cache at
    all — a silent stale hit is worse than a restage."""

    def _serve(self, with_etag):
        import hashlib
        import http.server

        test_objectstore = pytest.importorskip("test_objectstore")

        class Handler(test_objectstore._RangeHandler):
            def do_HEAD(self):
                data = self._object()
                if data is None:
                    return
                self.send_response(200)
                self.send_header("Content-Length", str(len(data)))
                if with_etag:
                    self.send_header(
                        "ETag", hashlib.sha1(data).hexdigest()[:16])
                self.end_headers()

        server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        server.objects = {"/o": b"v1" * 500}
        server.auth = None
        threading.Thread(target=server.serve_forever, daemon=True).start()
        return server

    def _src(self, server):
        url = f"http://127.0.0.1:{server.server_address[1]}/o"
        return plane.ExtentSource(
            [plane.Extent("object", url, 0, 1000, object_size=1000)])

    def test_no_validator_means_uncacheable(self):
        server = self._serve(with_etag=False)
        try:
            assert stagecache.fingerprint_source(self._src(server)) is None
        finally:
            server.shutdown()
            server.server_close()

    def test_same_size_reupload_changes_fingerprint(self):
        server = self._serve(with_etag=True)
        try:
            fp1 = stagecache.fingerprint_source(self._src(server))
            assert fp1 is not None
            server.objects["/o"] = b"v2" * 500  # same size, new content
            fp2 = stagecache.fingerprint_source(self._src(server))
            assert fp2 is not None and fp2 != fp1
        finally:
            server.shutdown()
            server.server_close()


class TestControllerCache:
    """MapVolume-level behavior on both backends."""

    def _publish(self, service, request):
        feeder = Feeder(controller=service)
        return feeder, feeder.publish(request, timeout=60.0)

    @pytest.mark.parametrize("backend_cls", [MallocBackend, TPUBackend])
    def test_republish_after_unmap_hits_without_reread(
            self, tmp_path, counted_reads, backend_cls):
        data = np.random.RandomState(0).bytes(50_000)
        path = tmp_path / "v.bin"
        path.write_bytes(data)
        service = ControllerService(backend_cls())
        request = _file_request(path)
        feeder, pub = self._publish(service, request)
        assert bytes(np.asarray(pub.array).reshape(-1)) == data
        reads_after_first = counted_reads["reads"]
        assert reads_after_first > 0
        feeder.unpublish("vol")
        feeder2, pub2 = self._publish(service, request)
        assert counted_reads["reads"] == reads_after_first, \
            "cache hit must not re-read the source file"
        assert bytes(np.asarray(pub2.array).reshape(-1)) == data
        assert M.STAGE_CACHE_HITS.value > 0

    def test_changed_source_misses(self, tmp_path, counted_reads):
        path = tmp_path / "v.bin"
        path.write_bytes(b"a" * 10_000)
        service = ControllerService(TPUBackend())
        feeder, _ = self._publish(service, _file_request(path))
        feeder.unpublish("vol")
        before = counted_reads["reads"]
        path.write_bytes(b"b" * 10_000)  # same size, new mtime/content
        _, pub = self._publish(service, _file_request(path))
        assert counted_reads["reads"] > before, "changed file must restage"
        assert bytes(np.asarray(pub.array)) == b"b" * 10_000

    def test_keep_cached_false_frees_on_unmap(self, tmp_path, counted_reads):
        path = tmp_path / "v.bin"
        path.write_bytes(b"x" * 4_000)
        service = ControllerService(TPUBackend(keep_cached=False))
        feeder, _ = self._publish(service, _file_request(path))
        before = counted_reads["reads"]
        feeder.unpublish("vol")
        _, pub = self._publish(service, _file_request(path))
        assert counted_reads["reads"] > before, \
            "keep_cached=False must free the entry on last unmap"
        assert bytes(np.asarray(pub.array)) == b"x" * 4_000

    def test_two_volume_ids_same_content_share_entry(
            self, tmp_path, counted_reads):
        data = b"z" * 20_000
        path = tmp_path / "v.bin"
        path.write_bytes(data)
        service = ControllerService(TPUBackend())
        _, pub1 = self._publish(service, _file_request(path, "vol-a"))
        before = counted_reads["reads"]
        _, pub2 = self._publish(service, _file_request(path, "vol-b"))
        assert counted_reads["reads"] == before
        assert bytes(np.asarray(pub2.array)) == data
        # Unmapping one must not free the other's array.
        service.UnmapVolume(pb.UnmapVolumeRequest(volume_id="vol-a"),
                            Feeder._LocalContext())
        assert bytes(np.asarray(pub2.array)) == data

    def test_capacity_pressure_evicts_idle(self, tmp_path, counted_reads):
        service = ControllerService(TPUBackend(cache_bytes=25_000))
        pa, pc = tmp_path / "a.bin", tmp_path / "b.bin"
        pa.write_bytes(b"a" * 20_000)
        pc.write_bytes(b"b" * 20_000)
        feeder, _ = self._publish(service, _file_request(pa, "vol-a"))
        feeder.unpublish("vol-a")  # entry idle
        self._publish(service, _file_request(pc, "vol-b"))  # evicts vol-a's
        before = counted_reads["reads"]
        _, pub = self._publish(service, _file_request(pa, "vol-a"))
        assert counted_reads["reads"] > before, "evicted entry must restage"
        assert bytes(np.asarray(pub.array)) == b"a" * 20_000
        assert M.STAGE_CACHE_EVICTIONS.value > 0

    def test_malloc_buffers_never_cached(self):
        service = ControllerService(MallocBackend())
        service.ProvisionMallocBDev(
            pb.ProvisionMallocBDevRequest(bdev_name="buf", size=1024), _Ctx())
        service.MapVolume(pb.MapVolumeRequest(
            volume_id="buf", malloc=pb.MallocParams()), _Ctx())
        vol = service.get_volume("buf")
        assert vol.wait(timeout=30) and vol.state == StageState.READY
        assert len(service.backend.cache) == 0

    def test_prestage_warms_then_mapvolume_hits(self, tmp_path,
                                                counted_reads):
        data = np.random.RandomState(1).bytes(30_000)
        path = tmp_path / "v.bin"
        path.write_bytes(data)
        backend = TPUBackend()
        service = ControllerService(backend)
        request = _file_request(path)
        reply = service.PrestageVolume(request, _Ctx())
        assert reply.already_cached is False
        assert wait_for(lambda: len(backend.cache) == 1)
        # No volume was created — prestage is cache-only.
        assert service.get_volume("vol") is None
        reads = counted_reads["reads"]
        _, pub = self._publish(service, request)
        assert counted_reads["reads"] == reads, \
            "MapVolume after prestage must hit the warmed cache"
        assert bytes(np.asarray(pub.array).reshape(-1)) == data
        # A second prestage is a resident no-op.
        assert service.PrestageVolume(request, _Ctx()).already_cached is True

    def test_unmap_during_staging_leaves_no_pins(self, tmp_path):
        """Cancel mid-stage: the stager must release its own cache pin so
        the entry (if inserted) is not leaked as permanently pinned."""
        path = tmp_path / "v.bin"
        path.write_bytes(b"q" * (1 << 20))
        backend = TPUBackend(chunk_bytes=32 << 10)
        service = ControllerService(backend)
        gate = threading.Event()
        orig = plane.READERS["file"]

        def slow_reader(*args, **kwargs):
            gate.set()
            time.sleep(0.02)
            return orig(*args, **kwargs)

        plane.READERS["file"] = slow_reader
        try:
            service.MapVolume(_file_request(path), _Ctx())
            gate.wait(timeout=10)
            service.UnmapVolume(
                pb.UnmapVolumeRequest(volume_id="vol"), _Ctx())
            vol_gone = wait_for(lambda: service.get_volume("vol") is None)
            assert vol_gone
            # Whatever ended up in the cache must be idle (pins == 0) so
            # it can be evicted/reused; nothing may stay pinned forever.
            assert wait_for(
                lambda: backend.cache.stats()["pinned"] == 0, timeout=15)
        finally:
            plane.READERS["file"] = orig


class TestWarmStandby:
    """The ROADMAP warm-standby item: a feeder prestages the replica at
    the same mesh coordinate after each publish, so controller failover
    re-publishes in O(1) from the replica's cache instead of re-staging
    O(volume) from source."""

    def test_publish_warms_replica_and_failover_skips_restage(
            self, tmp_path, counted_reads):
        db = MemRegistryDB()
        registry = registry_server("tcp://localhost:0",
                                   RegistryService(db=db))
        backends = [MallocBackend(), MallocBackend()]
        controllers = [
            Controller(
                controller_id=f"host-{i}", backend=backends[i],
                controller_address="pending",
                registry_address=registry.addr,
                registry_delay=0.1,
                # The default lease is 2.5 beats = 0.25 s: one late beat
                # under six workers and the replica is STALE when the
                # one-shot warm (or the failover) looks for a LIVE one.
                lease_seconds=10.0,
                mesh_coord=MeshCoord.parse("4,5,6"),
            )
            for i in range(2)
        ]
        svcs = [c.service for c in controllers]
        servers = [controller_server("tcp://localhost:0", s) for s in svcs]
        for c, s in zip(controllers, servers):
            c.controller_address = s.addr
        try:
            for c in controllers:
                c.start()
            with grpc.insecure_channel(registry.addr) as ch:
                stub = RegistryStub(ch)
                # A beat writes <id>/address, then <id>/mesh, in two
                # RPCs, and the one-shot warm finds its replica by the
                # MESH rows: wait for those (an address row alone lets
                # the publish race host-1's second write under load).
                assert wait_for(lambda: len([
                    v for v in stub.GetValues(
                        pb.GetValuesRequest(path=""), timeout=10.0).values
                    if v.path.endswith("/mesh")]) == 2, timeout=30), \
                    "the two controllers never registered their mesh rows"

            data = np.random.RandomState(9).bytes(40_000)
            path = tmp_path / "warm.bin"
            path.write_bytes(data)
            feeder = Feeder(registry_address=registry.addr,
                            controller_id="host-0", warm_standby=True)
            feeder.publish(_file_request(path, "vol-w"))
            # The background warm thread prestages host-1's cache.
            assert wait_for(lambda: len(backends[1].cache) == 1, timeout=30), \
                "the publish's background warm never reached host-1's cache"
            assert svcs[1].get_volume("vol-w") is None  # cache-only warm

            # KILL host-0; the healed window must fail over AND be served
            # from host-1's warmed cache without re-reading the source.
            controllers[0].stop()
            servers[0].force_stop()
            reads_before = counted_reads["reads"]
            w, total, _ = feeder.fetch_window("vol-w", 0, 10_000,
                                              timeout=30, heal=True)
            assert w.tobytes() == data[:10_000] and total == len(data)
            assert feeder.controller_id == "host-1"
            assert counted_reads["reads"] == reads_before, \
                "failover re-publish must hit the replica's warmed cache"
            assert svcs[1].get_volume("vol-w") is not None
        finally:
            for c in controllers:
                c.stop()
            for s in servers:
                s.force_stop()
            registry.force_stop()
