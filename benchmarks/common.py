"""What the entry and the runners share: the files a cell names, the
configuration as the program wants it, the run's context."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find(root: str, kind: str, name: str) -> str:
    """``<root>/benchmarks/<kind>/<name>.json`` — everything is found by
    the name ``BENCHMARK.json`` gives it."""
    path = os.path.join(root, "benchmarks", kind, f"{name}.json")
    if not os.path.exists(path):
        raise SystemExit(f"no {kind} file {path}")
    return path


def metric_spec(root: str, name: str) -> dict:
    """The reader and its arguments for a metric: ``metrics/<name>.json``
    or, where a quantity is split by what it moves (``device_idle.chat``,
    ``device_idle.train``), the one file of its stem, ``metrics/<name
    without its last .suffix>.json``. Unit, layer, ``moves`` and cells are
    ``BENCHMARK.json``'s alone."""
    stem = name.rsplit(".", 1)[0]
    for candidate in (name, stem):
        path = os.path.join(root, "benchmarks", "metrics", f"{candidate}.json")
        if os.path.exists(path):
            return load_json(path)
    raise SystemExit(f"no metrics file for {name!r} "
                     f"(looked for {name}.json and {stem}.json)")


def plugin(root: str, kind: str, name: str):
    """``<root>/benchmarks/<kind>/<name>.py`` as a module (loaded by its
    path, so that a copy of the tree with a file added finds it)."""
    path = os.path.join(root, "benchmarks", kind, f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def model_dict(config: dict, runner: str) -> dict:
    """The configuration file's published keys under the names the
    program's ``llama.Config`` (and the benchmark's own arithmetic) use,
    at the depth and context length this runner runs."""
    sizes = config[runner]
    out = {
        "vocab": config["vocab_size"],
        "dim": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "mlp_dim": config["intermediate_size"],
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "dtype": config["torch_dtype"],
        "n_layers": sizes["num_hidden_layers"],
        "max_seq": sizes["max_position_embeddings"],
        "n_experts": config.get("num_local_experts", 0),
        "moe_top_k": config.get("num_experts_per_tok", 0),
    }
    return out


def program_config(model: dict, **extra):
    """The program's own Config for ``model``. Its RMSNorm epsilon is fixed
    in ``ops/norms.py`` (1e-6) and is not a field, so a configuration file
    that states another value cannot be run as stated."""
    import jax.numpy as jnp

    from oim_tpu.models import llama

    if model["rms_norm_eps"] != 1e-6:
        raise SystemExit("the program's rmsnorm epsilon is fixed at 1e-6; "
                         f"the configuration states {model['rms_norm_eps']}")
    fields = dict(
        vocab=model["vocab"], dim=model["dim"], n_layers=model["n_layers"],
        n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
        head_dim=model["head_dim"], mlp_dim=model["mlp_dim"],
        max_seq=model["max_seq"], rope_theta=model["rope_theta"],
        dtype=jnp.dtype(model["dtype"]), n_experts=model["n_experts"])
    if model["n_experts"]:
        fields["moe_top_k"] = model["moe_top_k"]
    fields.update(extra)
    return llama.Config(**fields)


@dataclasses.dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    workdir: str       # inside the checkout, listed in .gitignore
    t0: float          # time.monotonic() at process start
    platform: str      # "tpu"; "cpu" only from the tests' entry

    def log(self, msg: str, **fields) -> None:
        extra = " ".join(f"{k}={v}" for k, v in fields.items())
        print(f"[bench {time.monotonic() - self.t0:8.2f}s] {msg} {extra}",
              flush=True)


class CompileCounter:
    """Counts backend compilations (a program built, or loaded from the
    persistent cache) between ``start`` and ``stop``: inside a measured
    window there must be none."""

    def __init__(self):
        import jax.monitoring

        self.counting = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if self.counting and "backend_compile" in event:
            self.count += 1

    def start(self):
        self.count, self.counting = 0, True

    def stop(self) -> int:
        self.counting = False
        return self.count


def memory_peak_bytes() -> int:
    """Peak on the fullest chip, as the allocator reports it (0 where the
    backend keeps no statistics)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


TRACE_AT, TRACE_S = 0.35, 4.0  # a traced run profiles TRACE_S seconds (at
# most two fifths of the window) from this share of the window on: steady
# state, and a trace that stays small


def trace_span(ctx: Context) -> tuple[float, float]:
    """(seconds into the window at which the profile starts, its length)."""
    return TRACE_AT * ctx.seconds, min(TRACE_S, 0.4 * ctx.seconds)


def traced(ctx: Context):
    """Context manager around a profile of the caller's choosing: names
    the directory and brackets the trace with the ``bench.window``
    annotation the reduction takes the window from."""
    import contextlib

    import jax

    @contextlib.contextmanager
    def cm():
        trace_dir = os.path.join(ctx.workdir, "trace")
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield trace_dir
        finally:
            jax.profiler.stop_trace()

    return cm()


def load_cell(root: str, workload: str):
    """(benchmark, cell, configuration, mix, runner module) of a cell."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = cells[workload]
    config = load_json(find(root, "configs", cell["config"]))
    mix = load_json(find(root, "traffic", cell["traffic"]))
    return bench, cell, config, mix, plugin(root, "runners", mix["runner"])


def start_jax(platform: str, chips: int):
    """Name the platform (never inherit it), keep the compile cache in the
    checkout, and refuse to go on without the chips: no fallback."""
    from oim_tpu.cli.common import init_jax

    init_jax(platform)  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    import jax

    # Every program goes to the persistent cache, however quick its
    # compile: the second run of a cell then builds nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()  # raises where the platform is not there
    if devices[0].platform != platform or len(devices) < chips:
        raise SystemExit(f"need {chips} {platform} device(s), found "
                         f"{len(devices)} x {devices[0].platform}")
    return devices
