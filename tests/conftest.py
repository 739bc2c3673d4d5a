"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the analog of the reference's
QEMU multi-VM rig, SURVEY.md section 4.3): JAX_PLATFORMS=cpu +
xla_force_host_platform_device_count=8 must be set before jax initializes,
so this conftest sets them at import time. What only a chip can show is
chip_smoke.py's business (run through the chip tool), never a test's;
tests/test_chip_compile.py compiles for a DESCRIBED chip, no device needed.
"""

import os
import sys

# Force CPU even when the environment preselects a TPU platform: tests
# always run on the virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
# Compile effort, not correctness: the tier-1 box is a single vCPU and the
# suite's wall clock is dominated by XLA compiles of the same small models
# (measured: test_train 185s -> 143s, chaos+shard smoke 90s -> 45s). Byte-
# identity pins compare runs within one process, so they see the same
# executable either way. What runs on the chip (benchmarks/run.py,
# chip_smoke.py) never imports this conftest and is unaffected.
if "xla_backend_optimization_level" not in _flags:
    _flags = (_flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = _flags

# grpc's C core logs INFO-level GOAWAY/teardown chatter (absl "I0000 ...
# chttp2_transport.cc") straight to stderr, which splices into pytest's
# progress lines and corrupts the tier-1 log. Only errors are signal here;
# must be set before the first grpc import initializes the C core.
os.environ.setdefault("GRPC_VERBOSITY", "ERROR")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The persistent compilation cache stays OFF under tests, for in-process
# code and (through the inherited variable) every CLI child: PR 19 found a
# cached compile poisoning that day's CPU jaxlib for later orbax/trainer
# runs, and a compile for a described chip (test_chip_compile.py) is
# written to the cache but can never be read back here.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

# In-process daemons (registry replication, feeder drivers, serve engines)
# log INFO/WARNING chatter to stderr from background threads, which lands
# mid-line in pytest's progress output — the tier-1 log's dot lines must
# stay machine-parseable. Errors still print. CLI assertions in the suite
# read stdout, never these stderr lines.
from oim_tpu.common import logging as _oim_logging  # noqa: E402

_oim_logging.get_global().level = _oim_logging.ERROR
# In-process CLI mains (setup_logging) and subprocess daemons re-create the
# global logger from --log-level's default; the env override keeps them at
# ERROR too.
os.environ.setdefault("OIM_LOG_LEVEL", "error")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def ca():
    """A real CA shared by the TLS test suite."""
    from oim_tpu.common.ca import CertAuthority

    return CertAuthority("oim-test-ca")


@pytest.fixture(scope="session")
def evil_ca():
    """A deliberately untrusted CA for MITM tests (reference _work/evil-ca,
    README.md:558-563)."""
    from oim_tpu.common.ca import CertAuthority

    return CertAuthority("oim-evil-ca")
