#!/usr/bin/env python3
"""chip_smoke.py: the system's main path on the TPU, through its own CLIs.

    python chip_smoke.py               # one chip (what the driver runs)
    python chip_smoke.py --chips 4     # the cross-chip paths, nothing else
    python chip_smoke.py --rehearse-cpu [--chips 4]   # same code, CPU, tiny

Default chain (three chip-owning processes IN TURN — a chip belongs to one
process at a time, and this script itself never imports JAX):

1. control plane (``oim_registry`` + ``oim_controller --backend malloc``,
   neither touches JAX) and a token volume made from ``--seed``; then
   ``oim_trainer --model llama3-8b --model-override n_layers=N`` takes a
   few steps at seq 2048 fed by publish + ReadVolume windows and writes
   one checkpoint;
2. ``oim_serve --checkpoint-dir ... --pack-to ...`` restores the params,
   writes the one packed weights file and answers one greedy prompt;
3. a staging check (MapVolume file -> HBM of that file, bytes hashed back,
   allocator peak bounded, flash kernel against the reference) and then
   ``oim_serve --weights-file ... --backend tpu``: the blob staged into
   HBM by the in-process controller, a handful of concurrent ``Generate``
   streams, same prompt twice -> same tokens, step 2's prompt -> step 2's
   tokens.

Every phase prints one JSON line when it ends. A failure prints the
phase, the child's command line and the tail of its log, and exits
non-zero; every child has its own time limit. The last stdout line of a
passing run is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
Model widths are the named model's own; only ``n_layers`` is cut.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# Sizes. llama3-8b widths (dim 4096, 32q/8kv x 128, ffn 14336, vocab
# 128256) are never touched; depth is what one 16 GB chip holds next to
# Adam (6 B a parameter resident + 2 B of gradients: the two untied
# tables alone are 6.3 GB — tests/test_chip_compile.py compiles these
# exact shapes for a described v5e).
ONE_CHIP = dict(
    model="llama3-8b", vocab=128256, n_layers=2, batch=1, seq=2048,
    steps=6, max_batch=4, max_seq=2048, prompt_lens=(12, 100, 1900),
    max_new=8,
)
FOUR_CHIPS = dict(
    ONE_CHIP, batch=4, steps=3,
    # A depth one chip cannot hold (its compiler refuses n_layers=4 at
    # batch 1); fsdp=4 holds a quarter of every leaf per chip.
    deep_layers=8,
    # |loss(fsdp=4) - loss(1 device)| per step, same seed/batch/depth:
    # identical math up to bf16 reduction order.
    loss_atol=0.05,
    shard=4, max_new=4,
)
# --rehearse-cpu: the same code on the CPU backend at llama-tiny size.
TINY = dict(model="llama-tiny", vocab=256, n_layers=2, batch=4, seq=32,
            max_batch=4, max_seq=64, prompt_lens=(5, 12, 40), max_new=6,
            deep_layers=4,
            shard=2,  # llama-tiny has 2 kv heads: no 4-way head split
            # llama-tiny is f32; the rehearsal takes the real model's
            # dtype so that bf16-only faults show here, not on the chip.
            overrides=("dtype=bfloat16",))

GLOBAL_LIMIT_S = 1150.0  # the contract allows 1200 s, compilation included


class PhaseError(Exception):
    def __init__(self, phase: str, why: str, proc: "Child | None" = None):
        super().__init__(why)
        self.phase, self.why, self.proc = phase, why, proc


class Child:
    """One started process: its command line, its log file, its limit."""

    def __init__(self, run: "Run", name: str, cmd: list[str], limit: float,
                 env: dict | None = None):
        self.name, self.cmd = name, cmd
        self.log_path = os.path.join(run.logs, f"{name}.log")
        self.deadline = time.monotonic() + min(limit, run.remaining())
        self._log = open(self.log_path, "wb")
        self.popen = subprocess.Popen(
            cmd, cwd=REPO, env=env or run.env, stdin=subprocess.DEVNULL,
            stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True)
        run.children.append(self)

    def lines(self) -> list[str]:
        with open(self.log_path, errors="replace") as f:
            return f.read().splitlines()

    def records(self) -> list[dict]:
        """The child's structured log lines (--log-format json)."""
        out = []
        for line in self.lines():
            if line.startswith("{"):
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass
        return out

    def record(self, msg: str) -> dict | None:
        found = [r for r in self.records() if r.get("msg") == msg]
        return found[-1] if found else None

    def wait(self, phase: str) -> None:
        """Run to its end inside its own limit; a hang becomes a
        message, not a kill from outside."""
        try:
            rc = self.popen.wait(max(0.0, self.deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            self.end()
            raise PhaseError(phase, f"{self.name} exceeded its time limit",
                             self) from None
        if rc != 0:
            raise PhaseError(phase, f"{self.name} exited with code {rc}", self)

    def check_alive(self, phase: str) -> None:
        rc = self.popen.poll()
        if rc is not None:
            raise PhaseError(
                phase, f"{self.name} exited early with code {rc}", self)
        if time.monotonic() > self.deadline:
            self.end()
            raise PhaseError(
                phase, f"{self.name} exceeded its time limit", self)

    def end(self, grace: float = 30.0) -> int | None:
        """SIGTERM by pid (never a pattern kill), SIGKILL the group
        after the grace period."""
        if self.popen.poll() is None:
            try:
                os.kill(self.popen.pid, signal.SIGTERM)
                self.popen.wait(grace)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(self.popen.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                self.popen.wait(10)
            except ProcessLookupError:
                pass
        self._log.close()
        return self.popen.returncode


class Run:
    def __init__(self, args):
        self.args = args
        self.platform = "cpu" if args.rehearse_cpu else "tpu"
        self.chips = args.chips
        sizes = dict(FOUR_CHIPS if args.chips == 4 else ONE_CHIP)
        if args.rehearse_cpu:
            sizes.update(TINY)
        self.sizes = sizes
        self.out = os.path.abspath(args.out)
        shutil.rmtree(self.out, ignore_errors=True)
        self.logs = os.path.join(self.out, "logs")
        os.makedirs(self.logs)
        self.children: list[Child] = []
        self.t0 = time.monotonic()
        self.device: dict = {}
        # Children get the platform named here, whatever was inherited
        # (this sandbox exports JAX_PLATFORMS=cpu; the chip host may or
        # may not) — and the repo on their path, whatever the cwd.
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = self.platform
        env["PYTHONPATH"] = REPO + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        env["OIM_LOG_LEVEL"] = "info"
        env.setdefault("GRPC_VERBOSITY", "ERROR")
        env.pop("XLA_FLAGS", None)
        if args.rehearse_cpu:
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={args.chips}")
        self.env = env

    def remaining(self) -> float:
        return max(1.0, GLOBAL_LIMIT_S - (time.monotonic() - self.t0))

    def py(self, *argv: str) -> list[str]:
        return [sys.executable, *argv]

    def model_flags(self, n_layers: int) -> list[str]:
        flags = ["--model", self.sizes["model"],
                 "--model-override", f"n_layers={n_layers}"]
        for item in self.sizes.get("overrides", ()):
            flags += ["--model-override", item]
        return flags

    def cli(self, module: str, *argv) -> list[str]:
        return self.py("-m", f"oim_tpu.cli.{module}", *map(str, argv),
                       "--log-format", "json")

    def emit(self, phase: str, t_start: float, **fields) -> None:
        print(json.dumps({
            "phase": phase, "ok": True,
            "seconds": round(time.monotonic() - t_start, 2), **fields,
        }), flush=True)

    def end_all(self) -> None:
        for child in reversed(self.children):
            child.end(grace=10.0)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(16 << 20), b""):
            h.update(block)
    return h.hexdigest()


NOT_REPORTED = "not reported by this backend"  # the CPU keeps no stats


def memory_of(record: dict | None, key: str = "peak_bytes_in_use"):
    """An allocator figure per device from a CLI's log line."""
    return (record or {}).get(key, NOT_REPORTED)


# -- phases ------------------------------------------------------------------


def phase_device(run: Run) -> None:
    """Ask JAX for the device in a child (the parent stays off JAX). A
    missing accelerator ends the run here, by name."""
    t = time.monotonic()
    probe = (
        "import json, jax; d = jax.devices(); "
        "print(json.dumps({'platform': d[0].platform, "
        "'kind': d[0].device_kind, 'count': len(d)}))")
    child = Child(run, "device", run.py("-c", probe), limit=180)
    try:
        child.wait("device")
    except PhaseError as err:
        raise PhaseError(
            "device",
            f"JAX found no {run.platform.upper()} device "
            f"(JAX_PLATFORMS={run.platform} was set for the probe): "
            f"{err.why}", child) from None
    found = [ln for ln in child.lines() if ln.startswith("{")]
    run.device = json.loads(found[-1])
    if run.device["platform"] != run.platform:
        raise PhaseError("device", f"asked for {run.platform}, JAX gave "
                         f"{run.device}", child)
    if run.device["count"] != run.chips:
        raise PhaseError(
            "device", f"this run needs {run.chips} device(s), JAX reports "
            f"{run.device['count']}", child)
    run.emit("device", t, **run.device)


def phase_build(run: Run) -> None:
    """The native staging engine is a build product git does not carry:
    build it from native/staging.cc. A failed build is printed."""
    t = time.monotonic()
    lib = os.path.join(REPO, "native", "libstaging.so")
    built = not os.path.exists(lib)
    if built:
        child = Child(run, "make-native",
                      ["make", "-C", os.path.join(REPO, "native")], limit=180)
        child.wait("build")
    if not os.path.exists(lib):
        raise PhaseError("build", f"make left no {lib}")
    run.emit("build", t, native_lib=os.path.relpath(lib, REPO),
             built_now=built)


def phase_control_plane(run: Run) -> dict:
    """Certificates, registry, controller (malloc: no JAX, no chip), a
    token volume from the seed. Readiness is an RPC that answers."""
    t = time.monotonic()
    from oim_tpu.common.ca import CertAuthority
    from oim_tpu.common.tlsutil import load_tls, secure_channel
    from oim_tpu.spec import RegistryStub, pb

    import numpy as np

    ca_dir = os.path.join(run.out, "ca")
    ca = CertAuthority("oim-chip-smoke-ca")
    for cn in ("component.registry", "controller.host-0", "host.host-0",
               "user.admin"):
        ca.write_files(ca_dir, cn)
    reg_port, ctl_port = free_port(), free_port()
    registry = Child(run, "registry", run.cli(
        "oim_registry", "--endpoint", f"tcp://127.0.0.1:{reg_port}",
        "--ca", f"{ca_dir}/ca.crt", "--key", f"{ca_dir}/component.registry",
    ), limit=GLOBAL_LIMIT_S)
    deadline = time.monotonic() + 90
    # Dial only once the port is bound: a channel whose first connect is
    # refused sits out gRPC's reconnect backoff (seconds, growing).
    while registry.record("server listening") is None:
        registry.check_alive("control-plane")
        if time.monotonic() > deadline:
            raise PhaseError("control-plane", "registry never listened",
                             registry)
        time.sleep(0.05)
    stub = RegistryStub(secure_channel(
        f"127.0.0.1:{reg_port}",
        load_tls(f"{ca_dir}/ca.crt", f"{ca_dir}/user.admin",
                 "component.registry")))

    def registered() -> set:
        try:
            reply = stub.GetValues(pb.GetValuesRequest(path=""), timeout=2)
            return {v.path for v in reply.values}
        except Exception:  # noqa: BLE001 - not up yet; the deadline decides
            return None

    while registered() is None:  # the controller dials a registry that answers
        registry.check_alive("control-plane")
        if time.monotonic() > deadline:
            raise PhaseError("control-plane", "registry never answered",
                             registry)
        time.sleep(0.1)
    controller = Child(run, "controller", run.cli(
        "oim_controller", "--endpoint", f"tcp://127.0.0.1:{ctl_port}",
        "--controller-id", "host-0",
        "--controller-address", f"127.0.0.1:{ctl_port}",
        "--registry", f"127.0.0.1:{reg_port}", "--registry-delay", "1",
        "--backend", "malloc", "--mesh-coord", "0,0,0",
        "--ca", f"{ca_dir}/ca.crt", "--key", f"{ca_dir}/controller.host-0",
    ), limit=GLOBAL_LIMIT_S)
    while "host-0/address" not in (registered() or ()):
        registry.check_alive("control-plane")
        controller.check_alive("control-plane")
        if time.monotonic() > deadline:
            raise PhaseError("control-plane",
                             "controller never registered", controller)
        time.sleep(0.1)
    # Two batches of records: the windowed feed wraps, so every record
    # comes round again and a falling loss means the step learns.
    s = run.sizes
    n_records = 2 * s["batch"]
    tokens = np.random.default_rng(run.args.seed).integers(
        0, s["vocab"], size=n_records * (s["seq"] + 1), dtype=np.int32)
    volume_file = os.path.join(run.out, "tokens.npy")
    np.save(volume_file, tokens)
    run.emit("control-plane", t, registry_port=reg_port,
             controller_port=ctl_port, volume_bytes=int(tokens.nbytes),
             volume_records=n_records)
    return {"registry": f"127.0.0.1:{reg_port}", "ca_dir": ca_dir,
            "volume_file": volume_file}


def run_trainer(run: Run, plane: dict, name: str, n_layers: int,
                extra: list[str], checkpoint_dir: str = "") -> dict:
    """One oim_trainer process to its end; returns what its log says."""
    t = time.monotonic()
    s = run.sizes
    cmd = run.cli(
        "oim_trainer", "--platform", run.platform,
        *run.model_flags(n_layers), "--steps", s["steps"], "--batch-size", s["batch"],
        "--seq-len", s["seq"], "--log-every", 1, "--warmup-steps", 1,
        "--registry", plane["registry"], "--controller-id", "host-0",
        "--volume", "tokens", "--volume-file", plane["volume_file"],
        "--ca", f"{plane['ca_dir']}/ca.crt",
        "--key", f"{plane['ca_dir']}/host.host-0", *extra)
    if checkpoint_dir:
        cmd += ["--checkpoint-dir", checkpoint_dir]
    child = Child(run, name, cmd, limit=700)
    child.wait(name)
    records = child.records()
    losses = [r["loss"] for r in records if r.get("msg") == "step"]
    done = child.record("done")
    kernels = sorted({r["kernel"] for r in records
                      if r.get("msg") == "attention dispatch"})
    if len(losses) != s["steps"] or done is None:
        raise PhaseError(name, f"expected {s['steps']} step lines and a "
                         f"'done' line, got {len(losses)}", child)
    if not all(isinstance(x, float) and x == x and abs(x) < 1e9
               for x in losses):
        raise PhaseError(name, f"non-finite loss: {losses}", child)
    # Step k and step k + 2 see the same records (the volume holds two
    # batches): the later visit must be cheaper.
    if not (losses[-1] < losses[-3] and losses[-2] < losses[-4]
            if len(losses) >= 4 else losses[-1] < losses[0]):
        raise PhaseError(name, f"loss is not falling: {losses}", child)
    want = "pallas_flash" if run.platform == "tpu" else "jnp_reference"
    if kernels != [want]:
        raise PhaseError(name, f"attention took {kernels}, expected "
                         f"[{want!r}] on {run.platform}", child)
    windowed = child.record("volume published (windowed feed)")
    if windowed is None:
        raise PhaseError(name, "the feed never went through the control "
                         "plane's ReadVolume window", child)
    out = dict(
        model=s["model"], n_layers=n_layers, batch=s["batch"],
        seq=s["seq"], steps=s["steps"], losses=losses,
        attention_kernel=kernels[0],
        fed_bytes=windowed["total_bytes"],
        peak_bytes_in_use=memory_of(done))
    run.emit(name, t, **out)
    return out


def start_serve(run: Run, name: str, n_layers: int, source: list[str],
                shard: int = 1) -> tuple[Child, str]:
    s = run.sizes
    port = free_port()
    child = Child(run, name, run.cli(
        "oim_serve", "--platform", run.platform,
        *run.model_flags(n_layers), "--endpoint", f"tcp://127.0.0.1:{port}",
        "--max-batch", s["max_batch"], "--max-seq", s["max_seq"],
        "--shard", shard, *source), limit=600)
    return child, f"127.0.0.1:{port}"


def wait_serving(child: Child, addr: str, phase: str):
    """Readiness = the Identity Probe answers ready."""
    import grpc

    from oim_tpu.spec import IdentityStub, ServeStub, pb

    # Dial once the server says it listens (see phase_control_plane on
    # gRPC's reconnect backoff); readiness itself is the Probe RPC.
    while child.record("oim-serve serving") is None:
        child.check_alive(phase)
        time.sleep(0.2)
    channel = grpc.insecure_channel(addr)
    probe = IdentityStub(channel)
    while True:
        child.check_alive(phase)
        try:
            if probe.Probe(pb.ProbeRequest(), timeout=2).ready:
                return ServeStub(channel)
        except grpc.RpcError:
            pass
        time.sleep(0.5)


def generate(stub, prompt: list[int], max_new: int, limit: float) -> dict:
    from oim_tpu.spec import pb

    tokens, reason = [], ""
    for delta in stub.Generate(pb.GenerateRequest(
            prompt=prompt, max_new_tokens=max_new, temperature=0.0, seed=0),
            timeout=limit):
        tokens.extend(delta.tokens)
        if delta.done:
            reason = delta.finish_reason
    return {"tokens": tokens, "finish_reason": reason}


def make_prompts(run: Run) -> list[list[int]]:
    import numpy as np

    rng = np.random.default_rng(run.args.seed + 1)
    return [rng.integers(0, run.sizes["vocab"], size=n).tolist()
            for n in run.sizes["prompt_lens"]]


def stop_serve(child: Child, phase: str) -> dict:
    """SIGTERM drains and exits 0; the 'stopped' line carries the
    allocator's peak."""
    rc = child.end(grace=90.0)
    if rc != 0:
        raise PhaseError(phase, f"{child.name} exited with code {rc} "
                         "after SIGTERM (a clean drain exits 0)", child)
    return child.record("stopped") or {}


def phase_pack(run: Run, ckpt: str, blob: str, n_layers: int) -> list[int]:
    """Checkpoint -> params only -> the one packed weights file; one
    greedy prompt answered from the restored params."""
    t = time.monotonic()
    child, addr = start_serve(run, "pack", n_layers, [
        "--checkpoint-dir", ckpt, "--pack-to", blob])
    stub = wait_serving(child, addr, "pack")
    prompt = make_prompts(run)[0]
    answer = generate(stub, prompt, run.sizes["max_new"], 300)
    if (len(answer["tokens"]) != run.sizes["max_new"]
            or answer["finish_reason"] != "length"):
        raise PhaseError("pack", f"stream did not finish: {answer}", child)
    packed = child.record("packed weights")
    stopped = stop_serve(child, "pack")
    if packed is None or packed["bytes"] != os.path.getsize(blob):
        raise PhaseError("pack", "no 'packed weights' line matching the "
                         "file", child)
    run.emit("pack", t, n_layers=n_layers, blob_bytes=packed["bytes"],
             blob_sha256=sha256_file(blob), tokens=answer["tokens"],
             peak_bytes_in_use=memory_of(stopped))
    return answer["tokens"]


def phase_stage(run: Run, blob: str) -> None:
    """MapVolume(file) -> HBM of the blob in a process of its own, with
    the checks the chip alone can make (see child_stage)."""
    t = time.monotonic()
    child = Child(run, "stage", run.py(
        os.path.join(REPO, "chip_smoke.py"), "--child-stage", blob,
        "--platform", run.platform), limit=420)
    child.wait("stage")
    found = [ln for ln in child.lines() if ln.startswith('{"stage"')]
    if not found:
        raise PhaseError("stage", "the staging child printed no result",
                         child)
    result = json.loads(found[-1])["stage"]
    if result["blob"]["sha256"] != sha256_file(blob):
        raise PhaseError("stage", "staged bytes do not hash to the file's",
                         child)
    run.emit("stage", t, **result)


def phase_serve(run: Run, blob: str, n_layers: int,
                packed_tokens: list[int]) -> None:
    """The blob staged into HBM by the in-process controller, then
    Generate streams over gRPC."""
    t = time.monotonic()
    child, addr = start_serve(run, "serve", n_layers, [
        "--weights-file", blob, "--backend", "tpu"])
    stub = wait_serving(child, addr, "serve")
    answers = serve_round(run, child, stub, "serve")
    if answers[0]["tokens"] != packed_tokens:
        raise PhaseError(
            "serve", "checkpoint-restored and blob-staged weights answer "
            f"differently: {packed_tokens} vs {answers[0]['tokens']}",
            child)
    published = child.record("published weights volume") or {}
    serving = child.record("oim-serve serving") or {}
    stopped = stop_serve(child, "serve")
    if published.get("bytes") != os.path.getsize(blob):
        raise PhaseError("serve", "staged volume size differs from the "
                         "file's", child)
    run.emit("serve", t, n_layers=n_layers, staged_bytes=published["bytes"],
             read_path=published.get("read_path"),
             requests=len(answers), streams_finished=len(answers),
             new_tokens=[len(a["tokens"]) for a in answers],
             prompt_lens=list(run.sizes["prompt_lens"]),
             decode_attention=serving.get("decode_attention"),
             prefill_attention=serving.get("prefill_attention"),
             bytes_in_use_after_load=memory_of(serving, "bytes_in_use"),
             peak_bytes_in_use=memory_of(stopped))


def serve_round(run: Run, child: Child, stub, phase: str) -> list[dict]:
    """All prompts IN FLIGHT TOGETHER (one per prefill bucket), then the
    first one again alone: every stream ends with its tokens and
    finish_reason 'length', and the repeat gives the same tokens."""
    prompts = make_prompts(run)
    max_new = run.sizes["max_new"]
    answers: list = [None] * len(prompts)

    def ask(i):
        try:
            answers[i] = generate(stub, prompts[i], max_new, 420)
        except Exception as err:  # noqa: BLE001 - reported below, not dropped
            answers[i] = {"tokens": [], "finish_reason": repr(err)}

    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(len(prompts))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    child.check_alive(phase)
    again = generate(stub, prompts[0], max_new, 420)
    for answer in answers + [again]:
        if (len(answer["tokens"]) != max_new
                or answer["finish_reason"] != "length"):
            raise PhaseError(phase, f"stream did not finish: {answer}",
                             child)
        if not all(0 <= tok < run.sizes["vocab"] for tok in answer["tokens"]):
            raise PhaseError(phase, f"token outside the vocabulary: "
                             f"{answer}", child)
    if again["tokens"] != answers[0]["tokens"]:
        raise PhaseError(
            phase, "the same greedy prompt answered differently: "
            f"{answers[0]['tokens']} vs {again['tokens']}", child)
    return answers + [again]


# -- four chips --------------------------------------------------------------


def phase_fsdp(run: Run, plane: dict) -> None:
    """oim-trainer --rules fsdp --mesh data=1,fsdp=4 against the same seed,
    batch and depth on ONE device of the same host, then once at a
    depth only four chips hold."""
    s = run.sizes
    one = run_trainer(run, plane, "train-1dev", s["n_layers"],
                      ["--mesh", "data=1"])
    four = run_trainer(run, plane, "train-fsdp4", s["n_layers"],
                       ["--rules", "fsdp", "--mesh", "data=1,fsdp=4"])
    t = time.monotonic()
    diffs = [abs(a - b) for a, b in zip(one["losses"], four["losses"])]
    if max(diffs) > s["loss_atol"]:
        raise PhaseError(
            "fsdp-compare", f"fsdp=4 losses {four['losses']} leave the "
            f"one-device losses {one['losses']} by more than "
            f"{s['loss_atol']}")
    run.emit("fsdp-compare", t, one_device=one["losses"],
             fsdp4=four["losses"], max_abs_diff=round(max(diffs), 4),
             tolerance=s["loss_atol"])
    run_trainer(run, plane, "train-fsdp4-deep", s["deep_layers"],
                ["--rules", "fsdp", "--mesh", "data=1,fsdp=4"])


def phase_blob_from_seed(run: Run, blob: str, n_layers: int) -> None:
    """Random weights from the seed, packed on the host CPU (named: this
    child makes data, it is not a chip phase)."""
    t = time.monotonic()
    child = Child(run, "blob", run.py(
        os.path.join(REPO, "chip_smoke.py"), "--child-blob", blob,
        *run.model_flags(n_layers), "--seed", str(run.args.seed)),
        limit=420, env=dict(run.env, JAX_PLATFORMS="cpu"))
    child.wait("blob")
    run.emit("blob", t, n_layers=n_layers,
             blob_bytes=os.path.getsize(blob), made_on="host cpu")


def phase_shard(run: Run, blob: str) -> None:
    """oim-serve --shard 4 against --shard 1: same blob, same prompts,
    greedy tokens identical (the repo's own claim)."""
    s = run.sizes
    results = {}
    for shard in (1, s["shard"]):
        t = time.monotonic()
        name = f"serve-shard{shard}"
        child, addr = start_serve(run, name, s["n_layers"], [
            "--weights-file", blob, "--backend", "tpu"], shard=shard)
        stub = wait_serving(child, addr, name)
        answers = serve_round(run, child, stub, name)
        serving = child.record("oim-serve serving") or {}
        stopped = stop_serve(child, name)
        results[shard] = [a["tokens"] for a in answers]
        run.emit(name, t, shard=shard, n_layers=s["n_layers"],
                 tokens=results[shard],
                 decode_attention=serving.get("decode_attention"),
                 prefill_attention=serving.get("prefill_attention"),
                 bytes_in_use_after_load=memory_of(serving, "bytes_in_use"),
                 peak_bytes_in_use=memory_of(stopped))
    t = time.monotonic()
    if results[1] != results[s["shard"]]:
        raise PhaseError(
            "shard-compare", f"--shard {s['shard']} tokens differ from "
            f"--shard 1: {results[s['shard']]} vs {results[1]}")
    run.emit("shard-compare", t, identical_streams=len(results[1]))


# -- children that are this script's own -------------------------------------


def check(ok: bool, what: str) -> None:
    """A result check in a child (not an assert: those vanish under -O)."""
    if not ok:
        raise SystemExit(f"check failed: {what}")


def child_stage(blob: str, platform: str) -> int:
    """What only the attached chip can show about the data plane: the
    blob staged file -> device through TPUBackend, read back and hashed;
    a volume int32 can index staged chunk by chunk with monotone
    progress and the allocator's peak under volume + 4 chunks (a donated
    landing buffer, not a concatenate); the flash kernels against the
    reference math at the trainer's geometry."""
    sys.path.insert(0, REPO)
    from oim_tpu.cli.common import init_jax

    init_jax(platform)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from oim_tpu.controller.backend import StagedVolume, StageState
    from oim_tpu.controller.tpu_backend import TPUBackend
    from oim_tpu.data import plane, staging
    from oim_tpu.spec import pb

    dev = jax.devices()[0]
    check(dev.platform == platform, f"device {dev} is not {platform}")

    def stage(path: str, chunk: int) -> dict:
        """One MapVolume-shaped stage of ``path`` through TPUBackend."""
        stats0 = dev.memory_stats()
        breakdown0 = plane.LAST_STAGE_BREAKDOWN
        size = os.path.getsize(path)
        backend = TPUBackend(chunk_bytes=chunk, keep_cached=False)
        vol = StagedVolume(volume_id=os.path.basename(path), params_key=b"",
                           spec=pb.ArraySpec())
        backend.stage(vol, "file", pb.FileParams(path=path, format="raw"))
        seen = []
        while not vol.wait(timeout=0.02):
            seen.append(vol.bytes_staged)
        check(vol.state == StageState.READY, f"stage failed: {vol.error}")
        check(seen == sorted(seen), "staging progress went backwards")
        check(vol.total_bytes == size,
              f"staged {vol.total_bytes} bytes of {size}")
        out = {
            "bytes": size, "chunk_bytes": chunk,
            # The plane lands chunk by chunk; past int32 byte indexing a
            # TPU takes the whole-read path (plane.stage_source).
            # (the plane rebinds its breakdown only when it staged.)
            "path": "chunked plane"
            if plane.LAST_STAGE_BREAKDOWN is not breakdown0
            else "whole read",
            "read_path": staging.read_path(),
            "sha256": hashlib.sha256(np.asarray(vol.array)).hexdigest(),
            "progress_polls_mid_stage": len(
                [b for b in seen if 0 < b < size]),
        }
        stats1 = dev.memory_stats()
        if stats0 and stats1:
            out["staging_peak_bytes"] = int(
                stats1["peak_bytes_in_use"] - stats0["bytes_in_use"])
        backend.unstage(vol)
        return out

    # First, while the allocator's high-water mark is still low: the
    # chunked path on a volume int32 can index — many chunks, monotone
    # progress, bytes equal, and the peak under volume + 4 chunks (a
    # donated landing buffer, not a 2x concatenate).
    small = os.path.join(os.path.dirname(blob), "chunked.bin")
    size = (256 << 20) + 777 if platform == "tpu" else (1 << 20) + 777
    chunk = size // 8
    with open(small, "wb") as f:
        f.write(np.random.default_rng(11).bytes(size))
    chunked = stage(small, chunk)
    check(chunked["sha256"] == sha256_file(small),
          "chunked staging changed the bytes")
    check(chunked["path"] == "chunked plane",
          f"the small volume missed the chunked plane: {chunked}")
    chunked["stage_breakdown_s"] = {
        k: round(v, 3) for k, v in plane.LAST_STAGE_BREAKDOWN.items()}
    if "staging_peak_bytes" in chunked:
        peak = chunked["staging_peak_bytes"]
        check(peak < size + 4 * chunk,
              f"staging peak {peak} >= volume {size} + 4 x {chunk}")
    # Then the blob itself, as oim-serve --backend tpu will stage it.
    result = {"chunked": chunked, "blob": stage(blob, 64 << 20)}

    # Flash fwd + bwd against the reference math, through the SAME
    # dispatch the trainer's step takes, at its geometry.
    from oim_tpu.ops.attention import attention, mha_reference

    t_len = 2048 if platform == "tpu" else 128
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (1, t_len, 32, 128), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, t_len, 8, 128), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, t_len, 8, 128), jnp.bfloat16)
    g = jax.random.normal(keys[3], (1, t_len, 32, 128), jnp.bfloat16)
    out, vjp = jax.vjp(lambda q, k, v: attention(q, k, v, True), q, k, v)
    ref, vjp_ref = jax.vjp(
        lambda q, k, v: mha_reference(q, k, v, True), q, k, v)
    errs = {"out": float(jnp.max(jnp.abs(
        out.astype(jnp.float32) - ref.astype(jnp.float32))))}
    for a, b, name in zip(vjp(g), vjp_ref(g), ("dq", "dk", "dv")):
        errs[name] = float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
    check(errs["out"] <= 3e-2 and max(errs.values()) <= 1e-1,
          f"flash kernels leave the reference: {errs}")
    result["flash_vs_reference_max_abs_err"] = errs
    stats2 = dev.memory_stats()
    if stats2:
        result["peak_bytes_in_use"] = [int(stats2["peak_bytes_in_use"])]
    print(json.dumps({"stage": result}), flush=True)
    return 0


def child_blob(blob: str, model: str, overrides: list[str], seed: int) -> int:
    sys.path.insert(0, REPO)
    import jax

    from oim_tpu.cli.common import parse_model_overrides
    from oim_tpu.models import llama
    from oim_tpu.serve.weights import save_packed
    from oim_tpu.train import TrainConfig

    mcfg = TrainConfig(
        model=model, model_overrides=parse_model_overrides(overrides),
    ).model_config()
    save_packed(llama.init(jax.random.PRNGKey(seed), mcfg), blob)
    return 0


# -- entry -------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser("chip_smoke")
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4 = the cross-chip paths (fsdp trainer, "
                             "--shard 4 serve) and what each is compared "
                             "with; none of the default phases")
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="the same chain on the CPU backend at "
                             "llama-tiny size (a rehearsal asked for by "
                             "name; without it a missing TPU is an error)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=os.path.join(
        REPO, "_work", "chip_smoke"),
        help="everything the run makes (certs, volume, checkpoint, "
             "blob, logs); emptied first")
    parser.add_argument("--child-stage", default="", help=argparse.SUPPRESS)
    parser.add_argument("--child-blob", default="", help=argparse.SUPPRESS)
    parser.add_argument("--platform", default="", help=argparse.SUPPRESS)
    parser.add_argument("--model", default="", help=argparse.SUPPRESS)
    parser.add_argument("--model-override", action="append", default=[],
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child_stage:
        return child_stage(args.child_stage, args.platform)
    if args.child_blob:
        return child_blob(args.child_blob, args.model, args.model_override,
                          args.seed)

    sys.path.insert(0, REPO)
    run = Run(args)
    s = run.sizes
    try:
        phase_device(run)
        phase_build(run)
        plane = phase_control_plane(run)
        blob = os.path.join(run.out, "weights.oimw")
        if args.chips == 4:
            phase_fsdp(run, plane)
            phase_blob_from_seed(run, blob, s["n_layers"])
            phase_shard(run, blob)
        else:
            ckpt = os.path.join(run.out, "ckpt")
            run_trainer(run, plane, "train", s["n_layers"],
                        ["--mesh", "data=1"], checkpoint_dir=ckpt)
            packed_tokens = phase_pack(run, ckpt, blob, s["n_layers"])
            phase_stage(run, blob)
            phase_serve(run, blob, s["n_layers"], packed_tokens)
    except PhaseError as err:
        print(json.dumps({"phase": err.phase, "ok": False,
                          "error": err.why}), flush=True)
        if err.proc is not None:
            print("command: " + " ".join(err.proc.cmd), flush=True)
            print(f"--- last lines of {err.proc.log_path} ---", flush=True)
            print("\n".join(err.proc.lines()[-60:]), flush=True)
        return 1
    finally:
        run.end_all()
    if "jax" in sys.modules:  # a parent on JAX would hold the chip
        print(json.dumps({"phase": "total", "ok": False,
                          "error": "chip_smoke.py itself imported JAX"}))
        return 1
    print(json.dumps({"phase": "total", "ok": True,
                      "seconds": round(time.monotonic() - run.t0, 2)}),
          flush=True)
    print(json.dumps({"ok": True, "device": run.device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
