"""Ring-1 tests for the uniform data plane (oim_tpu/data/plane.py).

The reference's design rule under test: EVERY source kind sits behind the
same data plane, off the control path (reference README.md:153-170 — the
SPDK stance), and every placement — single device, NamedSharding scatter,
replication — is fed by the same chunked read-ahead -> DMA pipeline with
peak device memory bounded by shard + chunk (VERDICT r3 #1).
"""

import threading
import time

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from oim_tpu.data import plane, readers
from oim_tpu.spec import pb


def _file_params(path):
    return pb.FileParams(path=str(path), format="raw")


def _write(tmp_path, name, data: bytes):
    p = tmp_path / name
    p.write_bytes(data)
    return p


@pytest.fixture
def mesh8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the virtual 8-device CPU mesh")
    return Mesh(np.array(devs[:8]).reshape(4, 2), ("data", "model"))


class TestLowerSource:
    def test_raw_file_is_one_extent(self, tmp_path):
        p = _write(tmp_path, "v.bin", b"x" * 1000)
        src = plane.lower_source("file", _file_params(p))
        assert src.total_bytes == 1000
        assert [e.kind for e in src.extents] == ["file"]

    def test_npy_lifts_dtype_and_shape(self, tmp_path):
        arr = np.arange(24, dtype=np.float32).reshape(4, 6)
        p = tmp_path / "a.npy"
        np.save(p, arr)
        src = plane.lower_source("file", pb.FileParams(path=str(p), format="npy"))
        assert src is not None
        assert src.src_dtype == np.float32
        assert src.src_shape == (4, 6)
        assert src.total_bytes == arr.nbytes  # header excluded
        out = np.empty(arr.nbytes, np.uint8)
        plane.read_range(src, 0, out)
        np.testing.assert_array_equal(out.view(np.float32).reshape(4, 6), arr)

    def test_fortran_npy_falls_back(self, tmp_path):
        arr = np.asfortranarray(np.arange(12, dtype=np.int32).reshape(3, 4))
        p = tmp_path / "f.npy"
        np.save(p, arr)
        assert plane.lower_source(
            "file", pb.FileParams(path=str(p), format="npy")) is None

    def test_tfrecord_paths_lay_back_to_back(self, tmp_path):
        recs_a, recs_b = [b"aaaa", b"bb"], [b"cccccc"]
        pa, pb_ = tmp_path / "a.tfrecord", tmp_path / "b.tfrecord"
        readers.write_tfrecords(pa, recs_a)
        readers.write_tfrecords(pb_, recs_b)
        src = plane.lower_source(
            "tfrecord", pb.TFRecordParams(paths=[str(pa), str(pb_)]))
        assert src.total_bytes == pa.stat().st_size + pb_.stat().st_size
        out = np.empty(src.total_bytes, np.uint8)
        plane.read_range(src, 0, out)
        # Framing survives staging: record boundaries recoverable from the
        # staged bytes themselves (the readers.py contract).
        assert list(readers.iter_tfrecord_bytes(out)) == recs_a + recs_b

    def test_missing_file_raises_for_stage_status(self, tmp_path):
        with pytest.raises(OSError):
            plane.lower_source(
                "file", _file_params(tmp_path / "nope.bin"))

    def test_malloc_is_not_lowerable(self):
        assert plane.lower_source("malloc", pb.MallocParams()) is None


class TestReadRange:
    def test_crosses_extent_boundaries(self, tmp_path):
        pa = _write(tmp_path, "a", bytes(range(100)))
        pb_ = _write(tmp_path, "b", bytes(range(100, 200)))
        src = plane.ExtentSource([
            plane.Extent("file", str(pa), 0, 100),
            plane.Extent("file", str(pb_), 0, 100),
        ])
        whole = bytes(range(200))
        for off, n in [(0, 200), (90, 20), (99, 2), (100, 100), (150, 1)]:
            dst = np.empty(n, np.uint8)
            plane.read_range(src, off, dst)
            assert bytes(dst) == whole[off:off + n]

    def test_extent_inner_offsets(self, tmp_path):
        p = _write(tmp_path, "a", bytes(range(256)))
        src = plane.ExtentSource([
            plane.Extent("file", str(p), 10, 20),
            plane.Extent("file", str(p), 100, 5),
        ])
        dst = np.empty(25, np.uint8)
        plane.read_range(src, 0, dst)
        assert bytes(dst) == bytes(range(10, 30)) + bytes(range(100, 105))

    def test_out_of_range_raises(self, tmp_path):
        p = _write(tmp_path, "a", b"abc")
        src = plane.ExtentSource([plane.Extent("file", str(p), 0, 3)])
        with pytest.raises(ValueError):
            plane.read_range(src, 2, np.empty(2, np.uint8))


class TestSliceRuns:
    """Runs must concatenate to exactly the slice's row-major bytes."""

    @pytest.mark.parametrize("shape,index", [
        ((8, 4), (slice(2, 4), slice(None))),       # row block
        ((8, 4), (slice(None), slice(1, 3))),       # column block
        ((8, 4), (slice(2, 6), slice(0, 2))),       # both
        ((6, 5, 4), (slice(1, 3), slice(2, 5), slice(None))),
        ((6, 5, 4), (slice(None), slice(None), slice(1, 2))),
        ((10, 3), (slice(8, 10), slice(None))),     # uneven tail shard
        ((7,), (slice(3, 7),)),
        ((4, 4), ()),                               # replicated: whole array
    ])
    def test_concatenation_is_the_slice(self, shape, index):
        arr = np.arange(np.prod(shape), dtype=np.int32).reshape(shape)
        runs, slice_shape = plane.slice_runs(shape, index, arr.itemsize)
        flat = arr.reshape(-1).view(np.uint8)
        got = np.concatenate([flat[o:o + n] for o, n in runs])
        idx = tuple(index) + (slice(None),) * (len(shape) - len(index))
        want = arr[idx]
        assert slice_shape == want.shape
        np.testing.assert_array_equal(
            got.view(np.int32).reshape(slice_shape), want)

    def test_run_explosion_returns_none(self):
        shape = (plane.MAX_RUNS + 1, 2, 2)
        assert plane.slice_runs(
            shape, (slice(None), slice(None), slice(0, 1)), 4) is None


class TestStageSource:
    def _roundtrip(self, tmp_path, data: np.ndarray, sharding, shape, dtype,
                   chunk=10_000, max_workers=None):
        path = _write(tmp_path, "vol.bin", data.tobytes())
        src = plane.lower_source("file", _file_params(path))
        arr = plane.stage_source(
            src, dtype=dtype, shape=shape, sharding=sharding,
            chunk_bytes=chunk, max_workers=max_workers)
        np.testing.assert_array_equal(
            np.asarray(arr), data.view(dtype).reshape(shape))
        return arr

    def test_sharded_both_axes(self, mesh8, tmp_path):
        data = np.arange(64 * 16, dtype=np.float32)
        sh = NamedSharding(mesh8, P("data", "model"))
        arr = self._roundtrip(tmp_path, data, sh, (64, 16), np.float32)
        assert len(arr.sharding.device_set) == 8

    def test_replicated_axis(self, mesh8, tmp_path):
        data = np.arange(32 * 8, dtype=np.int32)
        sh = NamedSharding(mesh8, P(None, "model"))
        arr = self._roundtrip(tmp_path, data, sh, (32, 8), np.int32)
        assert len(arr.sharding.device_set) == 8

    def test_uneven_shards(self, mesh8, tmp_path):
        # 10 rows over 4 'data' shards: jax pads the last shard's indices
        # map to ceil-div blocks; the plane must follow it exactly.
        data = np.arange(10 * 4, dtype=np.float32)
        sh = NamedSharding(mesh8, P("data",))
        try:
            arr = self._roundtrip(tmp_path, data, sh, (10, 4), np.float32,
                                  chunk=64)
        except ValueError as e:
            pytest.skip(f"jax rejects uneven sharding here: {e}")
        assert np.asarray(arr).shape == (10, 4)

    def test_multi_extent_source_sharded(self, mesh8, tmp_path):
        """A 2-shard webdataset-style source scattered over the mesh: the
        chunk stream crosses extent boundaries AND run boundaries."""
        a = np.arange(0, 512, dtype=np.float32)
        b = np.arange(512, 1024, dtype=np.float32)
        pa = _write(tmp_path, "s0", a.tobytes())
        pb_ = _write(tmp_path, "s1", b.tobytes())
        src = plane.ExtentSource([
            plane.Extent("file", str(pa), 0, a.nbytes),
            plane.Extent("file", str(pb_), 0, b.nbytes),
        ])
        sh = NamedSharding(mesh8, P("data", None))
        arr = plane.stage_source(
            src, dtype=np.float32, shape=(64, 16), sharding=sh,
            chunk_bytes=1000)
        np.testing.assert_array_equal(
            np.asarray(arr),
            np.concatenate([a, b]).reshape(64, 16))

    def test_memory_bound_shard_plus_chunk(self, mesh8, tmp_path):
        """The round-3 failure mode: a volume larger than HALF the budget
        must stage (the old on-device concatenate finish peaked at 2x
        volume). With the parallel pipeline, transients scale with the
        pool width (2 chunks per in-flight group): the plane's accounting
        asserts peak <= physical placement + 2 * chunk * workers — the
        knob that bounds transient memory on a tight chip; chip_smoke.py's
        stage phase checks device.memory_stats() for real on TPU."""
        volume_bytes = 1 << 20
        budget = int(1.5 * volume_bytes)  # old path needed 2x > budget
        chunk = 64 << 10
        workers = 2
        data = np.arange(volume_bytes // 4, dtype=np.float32)
        sh = NamedSharding(mesh8, P("data", "model"))
        self._roundtrip(tmp_path, data, sh, (512, 512), np.float32,
                        chunk=chunk, max_workers=workers)
        placement = plane.placement_bytes((512, 512), np.float32, sh)
        assert placement == volume_bytes  # fully sharded: no replication
        assert plane.LAST_STAGE_CONCURRENCY <= workers
        assert plane.LAST_STAGE_PEAK <= placement + 2 * chunk * workers \
            < budget

    def test_single_device_peak_volume_plus_chunk(self, tmp_path):
        data = np.arange(1 << 18, dtype=np.float32)
        chunk = 32 << 10
        self._roundtrip(tmp_path, data, SingleDeviceSharding(jax.devices()[0]),
                        (data.size,), np.float32, chunk=chunk)
        assert plane.LAST_STAGE_PEAK <= data.nbytes + 2 * chunk

    def test_int64_offset_path(self, tmp_path, monkeypatch):
        """Buffers past int32 indexing land chunks under scoped x64 (the
        >2 GiB shard case, exercised here by lowering the threshold)."""
        monkeypatch.setattr(plane, "_X64_THRESHOLD", 1000)
        data = np.arange(5000, dtype=np.uint8)
        self._roundtrip(tmp_path, data, SingleDeviceSharding(jax.devices()[0]),
                        (5000,), np.uint8, chunk=1024)

    def test_progress_abort_frees_buffers(self, mesh8, tmp_path):
        data = np.zeros(1 << 20, np.uint8)
        path = _write(tmp_path, "vol.bin", data.tobytes())
        src = plane.lower_source("file", _file_params(path))
        calls = []

        def progress(done):
            calls.append(done)
            return len(calls) < 3

        sh = NamedSharding(mesh8, P("data",))
        # max_workers=1: serial group order makes the call count exact
        # (the parallel-abort twin lives in TestConcurrentGroups).
        out = plane.stage_source(
            src, dtype=np.uint8, shape=(1 << 20,), sharding=sh,
            chunk_bytes=64 << 10, progress=progress, max_workers=1)
        assert out is None
        assert len(calls) == 3

    def test_empty_volume(self, tmp_path):
        path = _write(tmp_path, "empty.bin", b"")
        src = plane.lower_source("file", _file_params(path))
        arr = plane.stage_source(
            src, dtype=np.uint8, shape=(0,),
            sharding=SingleDeviceSharding(jax.devices()[0]))
        assert np.asarray(arr).size == 0


class TestControllerOnThePlane:
    """MapVolume-level proof that every source kind rides the plane."""

    def _backend(self, mesh=None, chunk=4096):
        from oim_tpu.controller.tpu_backend import TPUBackend

        return TPUBackend(mesh=mesh, chunk_bytes=chunk)

    def _stage(self, backend, params_kind, params, spec):
        from oim_tpu.controller.backend import StagedVolume, StageState

        vol = StagedVolume(volume_id="v", params_key=b"", spec=spec)
        before = plane.STAGE_CALLS
        backend.stage(vol, params_kind, params)
        assert vol.wait(timeout=60)
        assert vol.state == StageState.READY, vol.error
        assert plane.STAGE_CALLS == before + 1, "plane bypassed"
        return vol

    def test_tfrecord_volume_rides_the_plane(self, tmp_path):
        recs = [readers.encode_example({"x": np.arange(4)}) for _ in range(8)]
        pa, pb_ = tmp_path / "a.tfrecord", tmp_path / "b.tfrecord"
        readers.write_tfrecords(pa, recs[:5])
        readers.write_tfrecords(pb_, recs[5:])
        vol = self._stage(
            self._backend(), "tfrecord",
            pb.TFRecordParams(paths=[str(pa), str(pb_)]), pb.ArraySpec())
        staged = np.asarray(vol.array)
        assert list(readers.iter_tfrecord_bytes(staged)) == recs

    def test_two_shard_webdataset_sharded_readback(self, tmp_path, mesh8):
        """VERDICT r4 #1 done-criterion: a 2-shard webdataset staged
        through the chunked path under a NamedSharding, exact readback."""
        pad0 = np.random.RandomState(0).bytes(3 * 512)
        pad1 = np.random.RandomState(1).bytes(5 * 512)
        s0 = _write(tmp_path, "shard0.tar", pad0)
        s1 = _write(tmp_path, "shard1.tar", pad1)
        spec = pb.ArraySpec(shape=[8, 512], dtype="uint8",
                            sharding_axes=["data", ""])
        vol = self._stage(
            self._backend(mesh=mesh8, chunk=700), "webdataset",
            pb.WebDatasetParams(shard_urls=[str(s0), str(s1)]), spec)
        staged = np.asarray(vol.array)
        assert bytes(staged.reshape(-1)) == pad0 + pad1
        # data axis sharded, model axis replicated: all 8 devices hold it
        assert len(vol.array.sharding.device_set) == 8

    def test_npy_volume_keeps_source_dtype(self, tmp_path):
        arr = np.linspace(0, 1, 60, dtype=np.float32).reshape(3, 20)
        p = tmp_path / "w.npy"
        np.save(p, arr)
        vol = self._stage(
            self._backend(), "file",
            pb.FileParams(path=str(p), format="npy"), pb.ArraySpec())
        out = np.asarray(vol.array)
        assert out.dtype == np.float32 and out.shape == (3, 20)
        np.testing.assert_array_equal(out, arr)

    def test_npy_with_dtype_override_stages_flat(self, tmp_path):
        """A spec dtype override reinterprets the bytes: the source's
        element geometry must be dropped, not combined with the new dtype
        (which would fail resolve_shape)."""
        arr = np.arange(24, dtype=np.float32).reshape(4, 6)
        p = tmp_path / "o.npy"
        np.save(p, arr)
        vol = self._stage(
            self._backend(), "file",
            pb.FileParams(path=str(p), format="npy"),
            pb.ArraySpec(dtype="uint8"))
        out = np.asarray(vol.array)
        assert out.dtype == np.uint8 and out.shape == (arr.nbytes,)
        np.testing.assert_array_equal(out.view(np.float32), arr.reshape(-1))

    def test_object_changed_mid_stage_fails_loudly(self, tmp_path):
        """The extent map sized the object; a Content-Range total that
        disagrees must fail the stage, never mix versions silently."""
        test_objectstore = pytest.importorskip("test_objectstore")
        import http.server

        from oim_tpu.data import objectstore

        server = http.server.ThreadingHTTPServer(
            ("127.0.0.1", 0), test_objectstore._RangeHandler)
        server.objects = {"/o": b"x" * 10_000}
        server.auth = None
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            url = f"http://127.0.0.1:{server.server_address[1]}/o"
            dst = np.empty(5_000, np.uint8)
            with pytest.raises(objectstore.ObjectStoreError, match="mid-stage"):
                objectstore.read_range(url, 0, 5_000, dst,
                                       expected_total=20_000)
        finally:
            server.shutdown()
            server.server_close()

    def test_f64_npy_falls_back_to_value_conversion(self, tmp_path):
        """With x64 off, a 64-bit on-device bitcast would mangle bit
        patterns; the backend must route f64 through the whole-read path,
        where device_put VALUE-converts to f32 (the old semantics)."""
        from oim_tpu.controller.backend import StagedVolume, StageState

        arr = np.linspace(0, 1, 60, dtype=np.float64).reshape(3, 20)
        p = tmp_path / "w64.npy"
        np.save(p, arr)
        backend = self._backend()
        vol = StagedVolume(volume_id="v", params_key=b"", spec=pb.ArraySpec())
        before = plane.STAGE_CALLS
        backend.stage(vol, "file", pb.FileParams(path=str(p), format="npy"))
        assert vol.wait(timeout=60)
        assert vol.state == StageState.READY, vol.error
        assert plane.STAGE_CALLS == before  # plane refused pre-stage
        out = np.asarray(vol.array)
        assert out.dtype == np.float32
        np.testing.assert_allclose(out, arr, rtol=1e-6)

    def test_object_store_volume_rides_the_plane(self, tmp_path):
        test_objectstore = pytest.importorskip("test_objectstore")
        import http.server

        server = http.server.ThreadingHTTPServer(
            ("127.0.0.1", 0), test_objectstore._RangeHandler)
        data = np.random.RandomState(3).bytes(50_000)
        server.objects = {"/pool/img": data}
        server.auth = None
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            params = pb.CephParams(
                monitors=f"127.0.0.1:{server.server_address[1]}",
                pool="pool", image="img")
            vol = self._stage(self._backend(chunk=9_000), "ceph", params,
                              pb.ArraySpec())
            assert bytes(np.asarray(vol.array)) == data
        finally:
            server.shutdown()
            server.server_close()


class TestOverlapTiming:
    """The design property SPDK exists for, asserted instead of believed
    (VERDICT r3 weak #7): with a slow reader AND a slow consumer, chunked
    staging wall ~= max(read, consume) + epsilon, not their sum — because
    the filler reads chunk N+1 while the consumer works on chunk N."""

    N_CHUNKS = 8
    READ_S = 0.04
    CONSUME_S = 0.04

    def _timed_stage(self, tmp_path, monkeypatch):
        chunk = 10_000
        data = np.random.RandomState(5).bytes(chunk * self.N_CHUNKS)
        path = _write(tmp_path, "slow.bin", data)
        src = plane.ExtentSource(
            [plane.Extent("slowfile", str(path), 0, len(data))])
        reads = []  # (start, end) per reader call

        def slow_read(locator, offset, length, dst, headers):
            t0 = time.monotonic()
            time.sleep(self.READ_S)
            plane.READERS["file"](locator, offset, length, dst, headers)
            reads.append((t0, time.monotonic()))

        monkeypatch.setitem(plane.READERS, "slowfile", slow_read)
        consumes = []

        def progress(done):
            t0 = time.monotonic()
            time.sleep(self.CONSUME_S)
            consumes.append((t0, time.monotonic()))
            return True

        t0 = time.monotonic()
        arr = plane.stage_source(
            src, dtype=np.uint8, shape=(len(data),),
            sharding=SingleDeviceSharding(jax.devices()[0]),
            chunk_bytes=chunk, progress=progress)
        wall = time.monotonic() - t0
        assert bytes(np.asarray(arr)) == data
        return wall, reads, consumes

    def test_wall_is_max_not_sum(self, tmp_path, monkeypatch):
        wall, reads, consumes = self._timed_stage(tmp_path, monkeypatch)
        serial = self.N_CHUNKS * (self.READ_S + self.CONSUME_S)
        # Structural read-ahead proof: some later read began before an
        # earlier consume finished, i.e. the halves interleave.
        overlapped = sum(
            1 for (rs, _), (_, ce) in zip(reads[1:], consumes)
            if rs < ce
        )
        assert overlapped >= self.N_CHUNKS // 2, (
            f"filler never ran ahead: reads={reads} consumes={consumes}")
        # Concurrency proof from the timestamps themselves: the summed
        # interval intersection between read windows and consume windows
        # must cover several chunks' worth. (A serialized pipeline has
        # ~zero intersection.) Timestamps are immune to suite-load
        # slowdowns that make absolute wall-clock comparisons flaky —
        # a loaded machine delays intervals but cannot fabricate
        # concurrency between them.
        concurrent = sum(
            max(0.0, min(re, ce) - max(rs, cs))
            for rs, re in reads
            for cs, ce in consumes
        )
        assert concurrent > 2.5 * min(self.READ_S, self.CONSUME_S), (
            f"reads and consumes barely overlap ({concurrent:.3f}s "
            f"concurrent vs wall {wall:.3f}s, serialized {serial:.3f}s)")


class TestConcurrentGroups:
    """The parallel staging pipeline (ISSUE 4 tentpole): distinct shard
    groups stage on a thread pool — concurrently, byte-identically, and
    abortable with nothing leaked."""

    def _source(self, tmp_path, nbytes, name="par.bin", seed=11):
        data = np.random.RandomState(seed).bytes(nbytes)
        path = _write(tmp_path, name, data)
        return data, plane.lower_source("file", _file_params(path))

    @pytest.mark.parametrize("shape", [
        (16, 16),  # even shards + 2-way replication
        (10, 16),  # uneven tail shard (skipped where jax rejects it)
    ])
    def test_parallel_byte_identical_to_serial(self, mesh8, tmp_path,
                                               shape):
        """Sharded + replicated placements staged serially and in
        parallel: identical bytes, identical placement. Chunk size chosen
        so every group streams multiple chunks with an uneven tail."""
        data, src = self._source(tmp_path, shape[0] * shape[1] * 4)
        sh = NamedSharding(mesh8, P("data", None))  # 4-way + 2 replicas
        try:
            serial = plane.stage_source(
                src, dtype=np.float32, shape=shape, sharding=sh,
                chunk_bytes=600, max_workers=1)
        except ValueError as e:
            pytest.skip(f"jax rejects uneven sharding here: {e}")
        parallel = plane.stage_source(
            src, dtype=np.float32, shape=shape, sharding=sh,
            chunk_bytes=600, max_workers=8)
        np.testing.assert_array_equal(np.asarray(serial),
                                      np.asarray(parallel))
        assert np.asarray(parallel).tobytes() == data
        assert len(parallel.sharding.device_set) == 8

    def test_observes_two_groups_in_flight(self, mesh8, tmp_path,
                                           monkeypatch):
        """Direct observation (not just our own counter): slow per-group
        reads from DIFFERENT volume quarters must overlap in time."""
        nbytes = 64 << 10
        data, base_src = self._source(tmp_path, nbytes)
        src = plane.ExtentSource(
            [plane.Extent("slowpar", base_src.extents[0].locator, 0, nbytes)])
        windows = []  # (t_start, t_end, volume_offset)
        lock = threading.Lock()

        def slow_read(locator, offset, length, dst, headers):
            t0 = time.monotonic()
            time.sleep(0.05)
            plane.READERS["file"](locator, offset, length, dst, headers)
            with lock:
                windows.append((t0, time.monotonic(), offset))

        monkeypatch.setitem(plane.READERS, "slowpar", slow_read)
        sh = NamedSharding(mesh8, P("data",))  # 4 groups, quarter each
        arr = plane.stage_source(
            src, dtype=np.uint8, shape=(nbytes,), sharding=sh,
            chunk_bytes=8 << 10, max_workers=4)
        assert bytes(np.asarray(arr)) == data
        assert plane.LAST_STAGE_CONCURRENCY >= 2
        quarter = nbytes // 4
        overlapped = any(
            max(s1, s2) < min(e1, e2) and o1 // quarter != o2 // quarter
            for s1, e1, o1 in windows
            for s2, e2, o2 in windows
        )
        assert overlapped, (
            f"no reads from distinct groups overlapped: {windows}")

    def test_parallel_abort_frees_every_groups_buffers(self, mesh8,
                                                       tmp_path):
        """Mid-stage cancellation (the unmap-during-staging hook) with
        groups in flight concurrently: stage_source returns None and NO
        device array survives — donated buffers, staged chunks, and
        completed groups all freed."""
        import jax

        _, src = self._source(tmp_path, 1 << 20)
        sh = NamedSharding(mesh8, P("data",))
        before = len(jax.live_arrays())
        calls = []

        def progress(done):
            calls.append(done)
            return len(calls) < 5

        out = plane.stage_source(
            src, dtype=np.uint8, shape=(1 << 20,), sharding=sh,
            chunk_bytes=64 << 10, progress=progress, max_workers=4)
        assert out is None
        assert len(calls) >= 5
        assert len(jax.live_arrays()) == before, "leaked device arrays"

    def test_reader_error_in_one_group_aborts_all_and_raises(
            self, mesh8, tmp_path, monkeypatch):
        nbytes = 32 << 10
        _, base_src = self._source(tmp_path, nbytes)
        src = plane.ExtentSource(
            [plane.Extent("failpar", base_src.extents[0].locator, 0, nbytes)])

        def failing_read(locator, offset, length, dst, headers):
            if offset >= nbytes // 2:
                raise OSError("disk gone")
            plane.READERS["file"](locator, offset, length, dst, headers)

        monkeypatch.setitem(plane.READERS, "failpar", failing_read)
        import jax

        before = len(jax.live_arrays())
        sh = NamedSharding(mesh8, P("data",))
        with pytest.raises(OSError, match="disk gone"):
            plane.stage_source(
                src, dtype=np.uint8, shape=(nbytes,), sharding=sh,
                chunk_bytes=4 << 10, max_workers=4)
        assert len(jax.live_arrays()) == before, "leaked device arrays"

    def test_padded_tail_reuses_one_updater_program(self, tmp_path):
        """A multi-chunk view with an uneven tail must land through ONE
        jitted updater program shape: the tail chunk is re-aligned to
        full size (identical overlap bytes re-landed), so per-volume
        compiles don't double."""
        nbytes = 10_000  # chunk 4096 -> chunks at 0, 4096, 5904 (padded)
        data, src = self._source(tmp_path, nbytes)
        seen = []
        runs = [(0, nbytes)]
        starts = [0]
        for off, chunk in plane.iter_view_chunks(
                src, runs, chunk_bytes=4096, pad_tail=True):
            seen.append((off, chunk.size, bytes(chunk)))
        assert [s[1] for s in seen] == [4096, 4096, 4096]
        assert seen[-1][0] == nbytes - 4096
        # Reassembly in offset order reproduces the volume exactly.
        out = bytearray(nbytes)
        for off, n, blob in seen:
            out[off:off + n] = blob
        assert bytes(out) == data
        del starts


class TestSteppedSliceGuard:
    def test_stepped_slice_falls_back_to_whole_read(self):
        """A stepped per-dim slice cannot lower to contiguous byte runs;
        slice_runs must return None (whole-read fallback) instead of
        staging wrong bytes silently (advisor r4)."""
        from oim_tpu.data import plane

        assert plane.slice_runs(
            (8, 4), (slice(0, 8, 2), slice(None)), 4) is None
        # step=1 / None stay lowerable.
        assert plane.slice_runs(
            (8, 4), (slice(0, 4, 1), slice(None)), 4) is not None
