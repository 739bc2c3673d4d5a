"""Shared flag plumbing for the CLIs (the reference's InitSimpleFlags +
LoadTLSConfig pattern, cmd/*/main.go)."""

from __future__ import annotations

import argparse
import os

from oim_tpu.common import logging as oim_logging
from oim_tpu.common.tlsutil import TLSConfig, load_tls


def add_registry_flag(
    parser: argparse.ArgumentParser,
    default: str = "",
    required: bool = False,
    help_suffix: str = "",
) -> None:
    """The shared ``--registry`` flag: one endpoint, or a comma-separated
    list (``primary:9421,standby:9421``) with a replicated registry —
    clients fail over to the next endpoint on UNAVAILABLE /
    FAILED_PRECONDITION (common/endpoints.py)."""
    parser.add_argument(
        "--registry",
        default=default,
        required=required,
        help="registry endpoint, or comma-separated list primary,standby "
             "(clients fail over on UNAVAILABLE/FAILED_PRECONDITION)"
             + (f"; {help_suffix}" if help_suffix else ""),
    )


def add_model_override_flag(parser: argparse.ArgumentParser) -> None:
    """``--model-override KEY=VALUE`` (repeatable): the one way every
    model-taking CLI (trainer, serve, infer) cuts a named config — e.g.
    ``--model llama3-8b --model-override n_layers=2`` is the published
    widths at a depth one chip holds."""
    parser.add_argument("--model-override", action="append", default=[],
                        metavar="KEY=VALUE",
                        help="override a model-config field (repeatable), "
                             "e.g. --model-override n_layers=4; ints/"
                             "floats parsed, anything else kept as string")


def parse_model_overrides(items: list[str]) -> dict:
    overrides = {}
    for item in items:
        key, sep, raw = item.partition("=")
        if not sep or not key:
            raise SystemExit(f"--model-override {item!r}: expected KEY=VALUE")
        low = raw.lower()
        if low in ("true", "false"):
            # A string "false" would be truthy in a bool field — parse
            # booleans explicitly.
            val = low == "true"
        else:
            try:
                val = int(raw)
            except ValueError:
                try:
                    val = float(raw)
                except ValueError:
                    val = raw
        overrides[key] = val
    return overrides


# The persistent compilation cache of a checkout: ONE fixed path (the
# path is part of the cache key, so a directory that moves never hits),
# shared by every chip-owning entry point so the trainer, the packer and
# the server of one chain — and the next run — reuse each other's
# programs. JAX_COMPILATION_CACHE_DIR, when set, wins and nothing is set
# in code.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def init_jax(platform: str = "") -> None:
    """The one place a chip-owning entry point (oim-trainer, oim-serve,
    oim-infer) configures JAX before its first backend touch:
    ``platform`` (the ``--platform`` flag) overrides whatever
    JAX_PLATFORMS the environment exported — an explicit choice never
    falls back to another backend — and the compilation cache lands in
    JAX_COMPILATION_CACHE_DIR or, unset, in the checkout's
    ``.jax_cache/``."""
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)


def restore_checkpoint_params(directory: str, mcfg, verb: str):
    """(params, step) of the latest trainer checkpoint for oim-serve /
    oim-infer: params only — serving needs no optimizer state in HBM. No
    checkpoint is a refusal, never a random init."""
    from oim_tpu.train.checkpoint import restore_llama_params

    try:
        return restore_llama_params(directory, mcfg)
    except FileNotFoundError:
        raise SystemExit(
            f"no checkpoint found in {directory!r} "
            f"(refusing to {verb} from random init)") from None


def device_memory() -> dict:
    """Log fields for the allocator's view of every local device, in
    device order — ``bytes_in_use`` shows a lopsided placement,
    ``peak_bytes_in_use`` what the process needed at most. Empty on a
    backend that keeps no allocator stats (the CPU)."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    if not all(stats):
        return {}
    return {
        key: [int(s[key]) for s in stats]
        for key in ("bytes_in_use", "peak_bytes_in_use")
    }


def add_common_flags(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "--log-level",
        default=os.environ.get("OIM_LOG_LEVEL", "info"),
        help="debug|info|warning|error (reference -log.level flag; "
             "OIM_LOG_LEVEL env overrides the default — fleet operators "
             "and the test harness quiet every daemon without threading "
             "the flag through each spawn site)",
    )
    parser.add_argument(
        "--log-format",
        default="text",
        choices=oim_logging.FORMATS,
        help="text = '<time> <level> <msg> | k: v'; json = one JSON object "
             "per line with fields flattened (log aggregators); trace_id "
             "appears as a field in both when telemetry binds it",
    )
    parser.add_argument("--ca", default="", help="CA certificate file (mTLS)")
    parser.add_argument(
        "--key",
        default="",
        help="path prefix for <prefix>.key/.crt (reference .key/.crt convention)",
    )


def add_observability_flags(parser: argparse.ArgumentParser) -> None:
    """--metrics-port / --metrics-host / --trace-dir / the trace-ring and
    tail-sampling knobs, shared by every daemon."""
    parser.add_argument(
        "--metrics-port", type=int, default=-1,
        help=">=0 serves GET /metrics (Prometheus text + OpenMetrics "
             "exemplars), GET /debug/spans (span ring buffer, Chrome "
             "trace JSON) and GET /debug/events (flight recorder); "
             "0 = ephemeral port",
    )
    parser.add_argument(
        "--metrics-host", default="127.0.0.1",
        help="bind address for the metrics server; 0.0.0.0 lets Prometheus "
             "scrape from another pod (default loopback)",
    )
    parser.add_argument(
        "--trace-dir", default="",
        help="stream finished spans into <dir>/<service>-<pid>.trace.json "
             "(Chrome trace-event JSON: open in Perfetto / chrome://tracing; "
             "merge processes with scripts/trace_demo.py); the flight "
             "recorder dumps <service>-<pid>.events.json here on SIGQUIT, "
             "crash, and shutdown",
    )
    parser.add_argument(
        "--trace-ring", type=int, default=4096,
        help="span ring-buffer capacity behind /debug/spans: a busy serve "
             "replica evicts router/feeder hops from a small ring before "
             "an operator can read it — raise this on hot daemons",
    )
    parser.add_argument(
        "--trace-sample", type=float, default=1.0,
        help="tail-sampling keep probability for the --trace-dir stream: "
             "error spans and spans slower than --trace-slow-ms ALWAYS "
             "export; the rest export with this probability, decided per "
             "trace_id so a kept trace keeps every hop (1.0 = keep all)",
    )
    parser.add_argument(
        "--trace-slow-ms", type=float, default=100.0,
        help="latency threshold above which a span always exports to "
             "--trace-dir regardless of --trace-sample (the tail worth "
             "keeping); 0 disables the slow-keep rule",
    )
    parser.add_argument(
        "--events-ring", type=int, default=2048,
        help="flight-recorder ring capacity behind /debug/events "
             "(typed control-plane events stamped with trace ids); "
             "0 disables event recording",
    )
    parser.add_argument(
        "--telemetry-id", default="",
        help="id for this daemon's TTL-leased telemetry/<id> registry "
             "row (metrics endpoint + role; the `oimctl --top` "
             "discovery row). Default: derived from the daemon's own "
             "identity; 'none' disables. Published only when both a "
             "metrics server and a registry are configured; under mTLS "
             "the id must match the dialing identity's own id (or be a "
             "dot-suffixed variant)",
    )


class Observability:
    """Started telemetry for one daemon: span recorder + flight recorder
    + metrics server (+ the telemetry registry row, when wired)."""

    def __init__(self, server, recorder, service: str = "",
                 trace_dir: str = ""):
        self.server = server  # MetricsServer | None
        self.recorder = recorder
        self.service = service
        self.trace_dir = trace_dir
        self.telemetry = None  # TelemetryRegistration | None

    def dump_events(self) -> str | None:
        """Flight-recorder post-mortem dump into --trace-dir (SIGQUIT /
        crash / shutdown). Best-effort: a full disk must not mask the
        original failure."""
        if not self.trace_dir:
            return None
        from oim_tpu.common import events

        try:
            return events.dump_to(self.trace_dir, self.service or "oim")
        except OSError:
            return None

    def stop(self) -> None:
        if self.telemetry is not None:
            self.telemetry.stop(deregister=True)
            self.telemetry = None
        self.dump_events()
        self.recorder.flush()
        self.recorder.close()
        if self.server is not None:
            self.server.stop()


def start_observability(args: argparse.Namespace, service: str) -> Observability:
    """Configure the process-global span + event recorders (service names
    the Perfetto process and the dump files) and start the metrics server
    when requested. With a --trace-dir, SIGQUIT and an unhandled crash
    dump the flight recorder next to the span stream."""
    import signal
    import sys

    from oim_tpu.common import events, tracing
    from oim_tpu.common.logging import from_context

    trace_dir = getattr(args, "trace_dir", "")
    recorder = tracing.configure(
        service, trace_dir=trace_dir,
        capacity=getattr(args, "trace_ring", 4096),
        sample=getattr(args, "trace_sample", 1.0),
        slow_threshold_s=getattr(args, "trace_slow_ms", 100.0) / 1000.0)
    events.configure(capacity=getattr(args, "events_ring", 2048))
    server = None
    if getattr(args, "metrics_port", -1) >= 0:
        from oim_tpu.common.metrics import MetricsServer

        server = MetricsServer(
            port=args.metrics_port, host=args.metrics_host).start()
        from_context().info(
            "metrics", host=server.host, port=server.port)
    obs = Observability(server, recorder, service, trace_dir)
    if trace_dir:
        def _dump_on_signal(signum, frame):  # noqa: ARG001 - signal API
            path = obs.dump_events()
            recorder.flush()
            from_context().info("flight recorder dumped", path=path,
                                signal=signum)

        try:
            signal.signal(signal.SIGQUIT, _dump_on_signal)
        except (ValueError, AttributeError):
            pass  # non-main thread (tests) or no SIGQUIT (non-POSIX)

        prev_hook = sys.excepthook

        def _dump_on_crash(exc_type, exc, tb):
            obs.dump_events()
            recorder.flush()
            prev_hook(exc_type, exc, tb)

        sys.excepthook = _dump_on_crash
    return obs


def start_telemetry_row(
    obs: Observability,
    telemetry_id: str,
    role: str,
    registry_address: str,
    tls=None,
    interval: float = 10.0,
):
    """Self-publish this daemon's TTL-leased ``telemetry/<id>`` registry
    row (metrics endpoint + role) so ``oimctl --top`` discovers it. A
    no-op without a metrics server or registry — the row's whole value
    is a scrapeable endpoint. Pass ``--telemetry-id none`` to disable.
    Stops with ``obs.stop()``."""
    if (obs.server is None or not registry_address or not telemetry_id
            or telemetry_id == "none"):
        return None
    from oim_tpu.common.logging import from_context
    from oim_tpu.common.telemetry import TelemetryRegistration

    registration = TelemetryRegistration(
        telemetry_id, role,
        f"{obs.server.host}:{obs.server.port}",
        registry_address, interval=interval, tls=tls)
    registration.start()
    obs.telemetry = registration
    from_context().info("telemetry row published", row=registration.key,
                        role=role, metrics=registration.metrics_endpoint)
    return registration


def setup_logging(args: argparse.Namespace) -> None:
    oim_logging.set_global(
        oim_logging.Logger(
            level=oim_logging.parse_level(args.log_level),
            fmt=getattr(args, "log_format", "text"),
        )
    )


def load_tls_flags(args: argparse.Namespace, peer_name: str = "") -> TLSConfig | None:
    if not args.ca and not args.key:
        return None
    if not (args.ca and args.key):
        raise SystemExit("--ca and --key must be given together")
    return load_tls(args.ca, args.key, peer_name)
