"""Ring-0 unit tests for the common layer (model: reference pkg/oim-common
path_test.go, pci_test.go, server_test.go, cmdmonitor_test.go and pkg/log
tests)."""

import io
import os
import subprocess
import sys
import time

import grpc
import pytest

from oim_tpu.common import (
    KeyMutex,
    Logger,
    MeshCoord,
    NonBlockingGRPCServer,
    from_context,
    join_registry_path,
    parse_endpoint,
    split_registry_path,
    with_logger,
)
from oim_tpu.common import logging as oim_logging
from oim_tpu.common.cmdmonitor import monitored_popen
from oim_tpu.common.meshcoord import UNSET
from oim_tpu.spec import pb, RegistryServicer, RegistryStub, add_registry_to_server


class TestRegistryPath:
    def test_roundtrip(self):
        assert split_registry_path("host-0/address") == ["host-0", "address"]
        assert join_registry_path(["host-0", "mesh"]) == "host-0/mesh"

    @pytest.mark.parametrize("bad", ["", "a//b", "a/./b", "../a", "a/.."])
    def test_rejects_traversal(self, bad):
        with pytest.raises(ValueError):
            split_registry_path(bad)


class TestMeshCoord:
    def test_parse_format(self):
        c = MeshCoord.parse("1,2,3")
        assert (c.x, c.y, c.z, c.core) == (1, 2, 3, UNSET)
        assert c.format() == "1,2,3"
        assert MeshCoord.parse("1,2,3,0").format() == "1,2,3,0"
        assert MeshCoord.parse("*,2,*").format() == "*,2,*"

    @pytest.mark.parametrize("bad", ["1,2", "1,2,3,4,5", "a,b,c", "-2,1,1"])
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            MeshCoord.parse(bad)

    def test_complete_merges_wildcards(self):
        # The reference's CompletePCIAddress semantics (pci.go:51-65).
        got = MeshCoord.parse("*,2,*").complete(MeshCoord.parse("7,8,9,1"))
        assert got == MeshCoord(7, 2, 9, 1)
        assert got.is_complete()
        assert not MeshCoord.parse("*,2,3").is_complete()

    def test_proto_roundtrip(self):
        c = MeshCoord(1, 2, 3, 0)
        assert MeshCoord.from_proto(c.to_proto()) == c


class TestLogging:
    def test_context_attachment(self):
        buf = io.StringIO()
        logger = Logger(output=buf).with_fields(component="test")
        assert from_context() is oim_logging.get_global()
        with with_logger(logger):
            assert from_context() is logger
            from_context().info("hello", n=1)
        assert from_context() is oim_logging.get_global()
        line = buf.getvalue()
        assert "hello" in line and "component: 'test'" in line and "n: 1" in line

    def test_level_threshold(self):
        buf = io.StringIO()
        logger = Logger(output=buf, level=oim_logging.WARNING)
        logger.info("quiet")
        logger.warning("loud")
        assert "quiet" not in buf.getvalue()
        assert "loud" in buf.getvalue()

    def test_parse_level(self):
        assert oim_logging.parse_level("debug") == oim_logging.DEBUG
        with pytest.raises(ValueError):
            oim_logging.parse_level("bogus")


class TestParseEndpoint:
    def test_forms(self):
        assert parse_endpoint("unix:///tmp/x.sock") == ("unix", "/tmp/x.sock")
        assert parse_endpoint("unix://rel.sock") == ("unix", "rel.sock")
        assert parse_endpoint("tcp://1.2.3.4:5") == ("tcp", "1.2.3.4:5")
        assert parse_endpoint("localhost:0") == ("tcp", "localhost:0")

    @pytest.mark.parametrize("bad", ["", "unix://", "http://x", "tcp://"])
    def test_rejects(self, bad):
        with pytest.raises(ValueError):
            parse_endpoint(bad)


class _EchoRegistry(RegistryServicer):
    def GetValues(self, request, context):
        return pb.GetValuesReply(values=[pb.Value(path=request.path, value="v")])


class TestServer:
    def test_tcp_port_discovery_and_stop(self):
        srv = NonBlockingGRPCServer("tcp://localhost:0")
        srv.start(lambda s: add_registry_to_server(_EchoRegistry(), s))
        assert not srv.addr.endswith(":0")
        with grpc.insecure_channel(srv.addr) as ch:
            reply = RegistryStub(ch).GetValues(pb.GetValuesRequest(path="k"))
        assert reply.values[0].path == "k"
        srv.stop()

    def test_unix_socket_cleanup(self, tmp_path):
        sock = tmp_path / "srv.sock"
        sock.write_text("stale")  # stale socket from a "previous run"
        srv = NonBlockingGRPCServer(f"unix://{sock}")
        srv.start(lambda s: add_registry_to_server(_EchoRegistry(), s))
        with grpc.insecure_channel(srv.addr) as ch:
            RegistryStub(ch).GetValues(pb.GetValuesRequest(path="k"))
        srv.stop()
        assert not sock.exists()


class TestKeyMutex:
    def test_serializes_same_key(self):
        import threading

        km = KeyMutex()
        order = []

        def worker(tag, delay):
            with km.locked("vol-1"):
                order.append(("start", tag))
                time.sleep(delay)
                order.append(("end", tag))

        t1 = threading.Thread(target=worker, args=("a", 0.05))
        t1.start()
        time.sleep(0.01)
        t2 = threading.Thread(target=worker, args=("b", 0))
        t2.start()
        t1.join()
        t2.join()
        # b must not start until a ended
        assert order.index(("end", "a")) < order.index(("start", "b"))


class TestCmdMonitor:
    def test_detects_death(self):
        proc, mon = monitored_popen([sys.executable, "-c", "import time; time.sleep(0.2)"])
        assert not mon.died.is_set()
        assert mon.died.wait(5.0)
        proc.wait()

    def test_survives_while_running(self):
        proc, mon = monitored_popen(
            [sys.executable, "-c", "import time; time.sleep(10)"],
            stdout=subprocess.DEVNULL,
        )
        assert not mon.died.wait(0.3)
        proc.kill()
        assert mon.died.wait(5.0)
        proc.wait()


class TestSpecDrift:
    def test_proto_matches_spec_md(self):
        # CI drift check, reference Makefile:78-103 discipline.
        import scripts.gen_proto as gen

        assert gen.main(check=True) == 0

    def test_pb2_matches_proto(self):
        """The committed oim_pb2.py descriptor must be exactly what the
        builtin compiler produces from the committed oim.proto — the
        generated-code half of the drift gate (`make proto` keeps both in
        lockstep). Serialized-descriptor equality also pins the builtin
        compiler to protoc's byte-for-byte output format."""
        import scripts.gen_proto as gen
        from oim_tpu.spec import pb

        compiled = gen.compile_proto(gen.PROTO.read_text())
        assert pb.DESCRIPTOR.serialized_pb == compiled.SerializeToString(), (
            "oim_pb2.py drifted from oim.proto; run scripts/gen_proto.py "
            "(or `make proto`)"
        )


class TestProfiling:
    def test_profile_trace_writes_a_trace(self, tmp_path):
        """SURVEY §5.1: jax.profiler trace is the Jaeger replacement; the
        context manager must produce a loadable trace dir around real work."""
        import jax.numpy as jnp

        from oim_tpu.common.profiling import profile_trace

        d = tmp_path / "trace"
        with profile_trace(str(d)):
            float(jnp.arange(256.0).sum())
        files = list(d.rglob("*")) if d.exists() else []
        assert any(f.is_file() for f in files), "no trace artifacts written"

    def test_profile_trace_noop_on_empty(self):
        from oim_tpu.common.profiling import profile_trace

        with profile_trace(""):
            pass


class TestDependencyManifest:
    """pyproject.toml is the bill of materials — the reference's
    Gopkg.lock + vendor-bom.csv discipline, where CI fails on drift
    (reference test/test.make:118-149). Two invariants:

    1. every third-party module imported anywhere in oim_tpu/ (plus
       __graft_entry__.py) is declared in the manifest;
    2. every pinned version matches the installed one — the manifest
       names the exact environment the green suite and the BASELINE.md
       perf rows were produced on.
    """

    # import name -> distribution name where they differ
    _DIST = {"PIL": "pillow", "google": "protobuf", "grpc": "grpcio",
             "orbax": "orbax-checkpoint", "jax": "jax"}
    # imported only under `if TYPE_CHECKING` / optional probes, or
    # first-party: never required in the manifest
    _IGNORE = {"oim_tpu", "scripts", "tests", "conftest"}

    @staticmethod
    def _manifest():
        """(required pins, optional pins). Optional extras (tpu/test) are
        NOT required to be installed — a CPU-only host without libtpu must
        still run the suite — but when one IS installed its version must
        match the pin."""
        import tomllib
        from pathlib import Path

        root = Path(__file__).resolve().parent.parent
        with open(root / "pyproject.toml", "rb") as f:
            data = tomllib.load(f)

        def pin_map(deps):
            pins = {}
            for dep in deps:
                name, _, version = dep.partition("==")
                pins[name.strip().lower().replace("_", "-")] = version.strip()
            return pins

        optional = []
        for extra in data["project"].get("optional-dependencies", {}).values():
            optional += extra
        return pin_map(data["project"]["dependencies"]), pin_map(optional)

    @staticmethod
    def _imports():
        import ast
        import sys
        from pathlib import Path

        root = Path(__file__).resolve().parent.parent
        files = list((root / "oim_tpu").rglob("*.py"))
        files.append(root / "__graft_entry__.py")
        mods: set[str] = set()
        for p in files:
            for node in ast.walk(ast.parse(p.read_text())):
                if isinstance(node, ast.Import):
                    mods.update(a.name.split(".")[0] for a in node.names)
                elif (isinstance(node, ast.ImportFrom)
                      and node.module and node.level == 0):
                    mods.add(node.module.split(".")[0])
        return {m for m in mods if m not in sys.stdlib_module_names}

    def test_every_import_is_declared(self):
        required, _ = self._manifest()
        missing = []
        for mod in sorted(self._imports() - self._IGNORE):
            dist = self._DIST.get(mod, mod).lower().replace("_", "-")
            if dist not in required:
                missing.append(f"{mod} (distribution {dist})")
        assert not missing, (
            "imports with no pyproject.toml pin (add them — the manifest "
            f"is the BOM): {missing}"
        )

    def test_pins_match_installed_versions(self):
        import importlib.metadata as im

        required, optional = self._manifest()
        drift = []
        for dist, pinned in {**required, **optional}.items():
            try:
                installed = im.version(dist)
            except im.PackageNotFoundError:
                if dist in required:
                    drift.append(f"{dist}: pinned {pinned} but not installed")
                continue  # optional extra absent on this host: fine
            if installed != pinned:
                drift.append(f"{dist}: pinned {pinned}, installed {installed}")
        assert not drift, (
            "pyproject.toml pins drifted from the running environment "
            f"(update the manifest to the verified set): {drift}"
        )


class TestChipOwnership:
    """A chip belongs to one process at a time, so everything that runs
    NEXT TO a chip-owning trainer or server must stay off JAX entirely:
    the daemons (a `--backend malloc` controller serves MapVolume and
    ReadVolume windows from host buffers) and chip_smoke.py itself, whose
    children hold the chip in turn."""

    @pytest.mark.parametrize("module", [
        "oim_tpu.cli.oim_registry", "oim_tpu.cli.oim_controller",
        "oim_tpu.cli.oim_router", "oim_tpu.cli.oim_monitor",
        "oim_tpu.cli.oim_autoscaler", "chip_smoke",
    ])
    def test_import_stays_off_jax(self, module):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run(
            [sys.executable, "-c",
             f"import sys; import {module}; print('jax' in sys.modules)"],
            cwd=root, capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip() == "False", f"{module} imports jax"

    def test_malloc_controller_data_path_stays_off_jax(self, tmp_path):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = f"""
import sys
import numpy as np
from oim_tpu.controller import MallocBackend
from oim_tpu.controller.controller import ControllerService
from oim_tpu.feeder import Feeder
from oim_tpu.spec import pb
np.save({str(tmp_path / 'v.npy')!r}, np.arange(1000, dtype=np.int32))
feeder = Feeder(controller=ControllerService(MallocBackend()))
req = pb.MapVolumeRequest(volume_id="v")
req.file.path, req.file.format = {str(tmp_path / 'v.npy')!r}, "npy"
assert feeder.publish(req, timeout=30).bytes == 4000
assert feeder.fetch_window("v", 0, 1024, timeout=10)[1] == 4000
print('jax' in sys.modules)
"""
        out = subprocess.run(
            [sys.executable, "-c", script], cwd=root, capture_output=True,
            text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == "False"
