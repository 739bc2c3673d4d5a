"""Profiling: jax.profiler traces as the step-timing observability layer.

The reference scaffolds OpenTracing/Jaeger for its control plane but ships
it disabled (pkg/oim-common/tracing.go:232-246); its active layer is gRPC
call logging. This framework keeps the call-logging interceptors
(oim_tpu/common/interceptors.py) for the control plane and uses JAX's
native profiler for the data plane, per SURVEY.md §5.1: a TensorBoard-
loadable trace of device compute, XLA ops, and host<->device transfers is
the TPU analog of a Jaeger span tree.

Usage: ``with profile_trace(dir):`` around the hot region, or the
``--profile DIR`` flag on oim-trainer. Empty dir = no-op.
"""

from __future__ import annotations

import contextlib

from oim_tpu.common.logging import from_context


@contextlib.contextmanager
def profile_trace(trace_dir: str | None):
    """jax.profiler.trace wrapper; no-op when trace_dir is falsy. A
    profiler that cannot start or finalize raises: a run asked to trace
    that silently traced nothing is worse than one that stops."""
    if not trace_dir:
        yield
        return
    import jax

    from_context().info("profiling", dir=trace_dir)
    with jax.profiler.trace(trace_dir):
        yield
