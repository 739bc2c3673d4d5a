"""Training runner: the program's ``Trainer`` step, fed through the control
plane (the README's remote-trainer topology): ``oim_registry`` and
``oim_controller --backend malloc`` run as children that never import JAX,
a token volume made from the seed is staged on the controller, and
``data/feeds.py`` reads it back in windows through the registry.

Set-up builds ONE object — the compiled step with its state — drives it
through its first three steps on the feed's first three batches (that is
also the warm-up), and hands the same object to the window. The reference
follows those three steps after the window, when the state is freed.
"""

from __future__ import annotations

import argparse
import collections
import gc
import math
import os
import socket
import subprocess
import sys
import time

import numpy as np

from benchmarks import common, slices, traffic, weights

CHECK_STEPS = 3


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _Plane:
    """Registry and controller children; ``close`` ends them and waits."""

    def __init__(self, ctx):
        self.children: list[subprocess.Popen] = []
        self.logs = []
        self.ctx = ctx
        self.registry = f"127.0.0.1:{_free_port()}"
        self.controller = f"127.0.0.1:{_free_port()}"

    def spawn(self, name: str, module: str, *argv: str) -> None:
        env = dict(os.environ, PYTHONPATH=common.ROOT, JAX_PLATFORMS="cpu")
        log = open(os.path.join(self.ctx.workdir, f"{name}.log"), "w")
        self.logs.append(log)
        self.children.append(subprocess.Popen(
            [sys.executable, "-m", f"oim_tpu.cli.{module}", *argv],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=common.ROOT))

    def start(self) -> None:
        import grpc

        from oim_tpu.spec import RegistryStub, pb

        self.spawn("registry", "oim_registry",
                   "--endpoint", f"tcp://{self.registry}")
        # Retry the connection every half second at most: gRPC's default
        # back-off (1 s growing to 120 s) would sleep through a registry
        # that a busy host starts late.
        stub = RegistryStub(grpc.insecure_channel(self.registry, options=[
            ("grpc.initial_reconnect_backoff_ms", 100),
            ("grpc.min_reconnect_backoff_ms", 100),
            ("grpc.max_reconnect_backoff_ms", 500)]))

        def paths():
            try:
                reply = stub.GetValues(pb.GetValuesRequest(path=""), timeout=2)
                return {v.path for v in reply.values}
            except grpc.RpcError:
                return None

        self._wait(lambda: paths() is not None, "registry never answered")
        self.spawn("controller", "oim_controller",
                   "--endpoint", f"tcp://{self.controller}",
                   "--controller-id", "host-0",
                   "--controller-address", self.controller,
                   "--registry", self.registry, "--registry-delay", "1",
                   "--backend", "malloc", "--mesh-coord", "0,0,0")
        self._wait(lambda: "host-0/address" in (paths() or ()),
                   "controller never registered")

    def _wait(self, ready, why: str) -> None:
        deadline = time.monotonic() + 90
        while not ready():
            if any(c.poll() is not None for c in self.children):
                raise SystemExit(f"control plane: a child died ({why})")
            if time.monotonic() > deadline:
                raise SystemExit(f"control plane: {why}")
            time.sleep(0.1)

    def close(self) -> None:
        for c in self.children:
            c.terminate()
        for c in self.children:
            try:
                c.wait(30)
            except subprocess.TimeoutExpired:
                c.kill()
                c.wait()
        for log in self.logs:
            log.close()


def _leaf_norms(tree) -> dict:
    import jax.numpy as jnp

    return {name: math.sqrt(float(jnp.sum(jnp.square(leaf.astype(jnp.float32)))))
            for name, leaf in weights.leaf_paths(tree)}


def _adam_mu(opt_state):
    """The first-moment tree inside the optimizer's state."""
    import jax

    found = [x for x in jax.tree.leaves(
        opt_state, is_leaf=lambda n: hasattr(n, "mu") and hasattr(n, "nu"))
        if hasattr(x, "mu")]
    if len(found) != 1:
        raise SystemExit("cannot find the Adam moments in the optimizer state")
    return found[0].mu


def _delta_norms(seed: int, model: dict, params) -> dict:
    """Per-leaf norm of (parameters now) - (parameters from the seed), one
    leaf at a time so that no second copy of the model exists."""
    import jax
    import jax.numpy as jnp

    root = weights.root_key(seed)
    spec = weights.tree_spec(model)
    out = {}
    for name, leaf in weights.leaf_paths(params):
        shape, dt, scale = spec[name]

        def diff(root, leaf, name=name, shape=shape, dt=dt, scale=scale):
            p0 = weights._leaf(root, name, shape, dt, scale,
                               weights._lead(name, shape))
            return jnp.sum(jnp.square(
                leaf.astype(jnp.float32) - p0.astype(jnp.float32)))

        out[name] = math.sqrt(float(jax.jit(diff)(root, leaf)))
    return out


def _check(ctx, model: dict, opt: dict, seen: dict, limits: dict) -> dict:
    from benchmarks.reference import llama_like as ref

    want = ref.train_reference(ctx.seed, model, opt, seen["batches"])
    numbers = {
        "loss_gap": max(abs(a - b) for a, b in zip(seen["loss"], want["loss"])),
        "grad1_gap": ref.worst_leaf_gap(seen["grad1"], want["grad1"]),
        "delta_gap": ref.worst_leaf_gap(seen["delta"], want["delta"]),
        "grad1_diff": ref.worst_leaf_difference(
            seen["grad1_sample"], want["grad1_sample"]),
    }
    ctx.log("reference", loss=[f"{x:.5f}" for x in want["loss"]],
            program=[f"{x:.5f}" for x in seen["loss"]])
    ok = True
    for name, value in numbers.items():
        ctx.log("correct?", number=name, value=f"{value:.6g}",
                limit=limits[name])
        ok = ok and value <= limits[name]
    return {"ok": ok, "numbers": numbers, "reference": want}


def run(ctx: common.Context) -> dict:
    import jax

    from oim_tpu.data import feeds
    from oim_tpu.train.state import TrainState
    from oim_tpu.train.trainer import TrainConfig, Trainer

    sizes, mix = ctx.config["train"], ctx.traffic
    model = common.model_dict(ctx.config, "train")
    opt = sizes["optimizer"]
    batch, seq = int(mix["batch_size"]), int(mix["seq_len"])

    plane = _Plane(ctx)
    try:
        plane.start()
        volume_file = os.path.join(ctx.workdir, "tokens.npy")
        np.save(volume_file, traffic.train_records(mix, ctx.seed, model["vocab"]))
        ctx.log("control plane up; volume written",
                bytes=os.path.getsize(volume_file))

        tcfg = TrainConfig(
            model=sizes["program_model"], batch_size=batch, seq_len=seq,
            lr=opt["lr"], warmup_steps=opt["warmup_steps"],
            total_steps=opt["total_steps"], weight_decay=opt["weight_decay"],
            seed=ctx.seed & 0x7FFFFFFF,
            model_overrides={
                "vocab": model["vocab"], "rope_theta": model["rope_theta"],
                "n_layers": model["n_layers"], "max_seq": model["max_seq"],
                **sizes.get("program_overrides", {})})
        pcfg = tcfg.model_config()
        for key in ("dim", "n_heads", "n_kv_heads", "head_dim", "mlp_dim",
                    "vocab", "n_layers"):
            if getattr(pcfg, key) != model[key]:
                raise SystemExit(f"the program's {key} is {getattr(pcfg, key)}"
                                 f", the configuration's {model[key]}")
        common.program_config(model)  # epsilon check
        from jax.sharding import Mesh

        trainer = Trainer(tcfg, mesh=Mesh(
            np.asarray(jax.devices()[:ctx.cell["chips"]]), ("data",)))
        make_state = jax.jit(
            lambda root: TrainState.create(
                weights.make(root, model), trainer.tx, {}),
            out_shardings=trainer.state_shardings)
        weights.check_against_program(
            model, jax.eval_shape(make_state, weights.root_key(0)).params)
        state = make_state(weights.root_key(ctx.seed))
        feed = feeds.feeder_batches(
            argparse.Namespace(
                registry=plane.registry, controller_id="host-0",
                volume="bench-tokens", volume_file=volume_file,
                feed_window_bytes=int(mix["feed_window_bytes"]),
                publish_timeout=60.0, direct_data=True),
            tcfg, tls=None)

        def step(state):
            t = time.monotonic()
            host = next(feed)
            waited = time.monotonic() - t
            new_state, out = trainer.step_fn(state, trainer.place_batch(host))
            return new_state, out, host, waited

        if ctx.platform == "tpu":
            host = next(feed)
            text = str(jax.make_jaxpr(trainer.step_fn)(
                state, trainer.place_batch(host)))
            if "pallas_call" not in text:
                raise SystemExit("the train step does not hold the Pallas "
                                 "flash kernel")
            feed = _chain(host, feed)

        seen = {"batches": [], "loss": []}
        for i in range(CHECK_STEPS):
            state, out, host, _ = step(state)
            seen["batches"].append(np.array(host["tokens"]))
            seen["loss"].append(float(out["loss"]))
            if i == 0:
                from benchmarks.reference.llama_like import GRAD_SAMPLE

                mu = _adam_mu(state.opt_state)
                seen["grad1"] = {k: v / (1.0 - opt["b1"])
                                 for k, v in _leaf_norms(mu).items()}
                seen["grad1_sample"] = {
                    f"layers/{k}": np.asarray(mu["layers"][k]).astype(
                        np.float32) / (1.0 - opt["b1"]) for k in GRAD_SAMPLE}
        seen["delta"] = _delta_norms(ctx.seed, model, state.params)
        rows = np.concatenate(seen["batches"])
        if len({r.tobytes() for r in rows}) != len(rows):
            raise SystemExit("the checked steps' rows do not all differ")
        ctx.log("first steps", loss=[f"{x:.5f}" for x in seen["loss"]])

        compiles = common.CompileCounter()
        jax.block_until_ready(state)  # the window opens on a drained device
        setup_s = time.monotonic() - ctx.t0
        ctx.log("window opens", setup_s=f"{setup_s:.2f}")
        compiles.start()
        t_start = time.monotonic()
        # The loop keeps ``run_ahead_steps`` steps dispatched ahead of the
        # loss it reads, as ``Trainer.run`` does between two of its log
        # lines: a pause of the host shorter than the queue costs the
        # device nothing. bounds[i] = host time at which step i's loss
        # reached the host (bounds[0]: the window opens).
        lag = max(1, int(mix.get("run_ahead_steps", 1)))
        bounds, feed_wait, losses = [t_start], [], []
        queued = collections.deque()
        trace_dir, tracing, traced_from, profiler_s = None, None, 0.0, 0.0
        trace_at, trace_for = common.trace_span(ctx)

        def reap():
            losses.append(float(queued.popleft()["loss"]))
            bounds.append(time.monotonic())

        while True:
            if ctx.trace and tracing is None and trace_dir is None \
                    and bounds[-1] - t_start >= trace_at:
                tracing = common.traced(ctx)
                trace_dir = tracing.__enter__()
                traced_from = time.monotonic()
            state, out, _, waited = step(state)
            feed_wait.append(waited * 1e3)
            queued.append(out)
            if len(queued) > lag:
                reap()
            if tracing is not None \
                    and time.monotonic() - traced_from >= trace_for:
                # The profiler's stop holds the host for about a second:
                # the queue is drained first, so that this time is the
                # harness's alone and can be told from the program's.
                while queued:
                    reap()
                t = time.monotonic()
                tracing.__exit__(None, None, None)
                tracing = None
                profiler_s += time.monotonic() - t
            # Stop dispatching once the steps already queued carry the
            # device to the window's end.
            typical = float(np.median(np.diff(bounds[-32:]))) \
                if len(bounds) > 1 else 0.0
            if time.monotonic() + len(queued) * typical \
                    >= t_start + ctx.seconds:
                break
        while queued:
            reap()
        jax.block_until_ready(state)
        bounds[-1] = time.monotonic()
        if tracing is not None:
            tracing.__exit__(None, None, None)
        n_compiles = compiles.stop()
        peak = common.memory_peak_bytes()
        steps, window_s = len(bounds) - 1, bounds[-1] - t_start
        bad = sum(1 for x in losses if not math.isfinite(x))
        per_step = batch * seq
        # The rate a user gets: every step of the window over all of its
        # time. Beside it, per layer: the median of ten slices, and the
        # whole-window rate less the profiler's own stop (the harness's
        # doing, only in a traced run), which ``mfu`` and ``stall_share``
        # are taken from.
        whole = steps * per_step / window_s
        rates = slices.step_slice_rates(bounds, per_step)
        steady = slices.median(rates)
        unprofiled = steps * per_step / (window_s - profiler_s)
        stats = {
            "train_tokens_per_s": whole,
            "steady_tokens_per_s": unprofiled,
            "slice_median_tokens_per_s": steady,
            "stall_share": slices.stall_share(unprofiled, steady),
            "feed_wait_ms": feed_wait,
        }
        step_s = np.diff(bounds)
        ctx.log("slices", steps=slices.cut_steps(steps),
                tokens_per_s=[round(r, 1) for r in rates])
        ctx.log("step times", median_ms=f"{np.median(step_s) * 1e3:.3f}",
                longest=[(f"{step_s[i] * 1e3:.1f}ms", f"step {i + 1}",
                          f"at {bounds[i + 1] - ctx.t0:.2f}s")
                         for i in np.argsort(-step_s)[:5]])
        ctx.log("window closed", steps=steps, window_s=f"{window_s:.3f}",
                whole_window_tokens_per_s=f"{whole:.1f}",
                slice_median_tokens_per_s=f"{steady:.1f}",
                run_ahead_steps=lag, profiler_stop_s=f"{profiler_s:.3f}",
                loss=f"{losses[0]:.4f}->{losses[-1]:.4f}",
                compiles_in_window=n_compiles)
        shapes = {"model": model, "batch": batch, "seq": seq}
        del state, trainer, make_state, out, queued
    finally:
        plane.close()
    gc.collect()
    verdict = _check(ctx, model, opt, seen, sizes["limits"])
    return {
        "correct": bool(verdict["ok"] and bad == 0 and n_compiles == 0),
        "attempted": steps, "failed": bad, "setup_s": setup_s,
        "stats": stats, "shapes": shapes, "trace_dir": trace_dir,
        "memory_peak_bytes": peak, "compared": verdict["numbers"],
        "check_sample": seen, "reference": verdict["reference"],
    }


def _chain(first, rest):
    yield first
    yield from rest
