"""A copy of the benchmark with tiny configurations, mixes and cells ADDED
as new files and new entries (nothing that is there is edited): what a
later PR does, and what the CPU tests drive through ``run.main``."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_WIDTHS = {
    "hidden_size": 64, "intermediate_size": 192, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "rope_theta": 1000000.0, "torch_dtype": "float32", "rms_norm_eps": 1e-06,
}
_SERVE = {"num_hidden_layers": 2, "max_position_embeddings": 128,
          "max_batch": 4, "queue_depth": 128, "kv_pool_tokens": 512,
          "limits": {"gap_max": 1e-3, "gap_mean": 1e-4}}
_PROGRAM = {"dim": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
            "mlp_dim": 192, "vocab_chunk": 0}
CONFIGS = {
    "tiny-dense": {
        **_WIDTHS, "serve": _SERVE,
        "train": {
            "num_hidden_layers": 2, "max_position_embeddings": 128,
            "program_model": "llama-tiny", "program_overrides": _PROGRAM,
            "optimizer": {"lr": 0.0003, "warmup_steps": 1, "total_steps": 1000,
                          "weight_decay": 0.1, "b1": 0.9, "b2": 0.95,
                          "eps": 1e-08, "grad_clip": 1.0},
            "limits": {"loss_gap": 1e-4, "grad1_gap": 1e-3, "delta_gap": 1e-2,
                       "grad1_diff": 1e-3}}},
    "tiny-moe": {**_WIDTHS, "num_local_experts": 4, "num_experts_per_tok": 2,
                 "serve": _SERVE},
}
_LENGTHS = {"prompt_tokens": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
            "output_tokens": {"median": 8, "sigma": 0.4, "min": 4, "max": 16},
            "check_requests": 3, "schedule_seed": 1}
TRAFFIC = {
    "tiny-open": {"runner": "serve", "kind": "open_loop", "rate_per_s": 12.0, "pre_roll_s": 0.5,
                  **_LENGTHS},
    "tiny-backlog": {"runner": "serve", "kind": "backlog", "requests": 96,
                     "block": 8, "pre_roll_s": 0.3, **_LENGTHS},
    "tiny-train": {"runner": "train", "kind": "train_job", "batch_size": 2,
                   "seq_len": 128, "volume_bytes": 65536,
                   "feed_window_bytes": 16384, "run_ahead_steps": 3},
}
CELLS = [("tiny-dense.chat", "tiny-dense", "tiny-open", "mistral-7b.chat"),
         ("tiny-moe.batch", "tiny-moe", "tiny-backlog", "mixtral-8x7b.batch"),
         ("tiny-dense.train", "tiny-dense", "tiny-train", "mistral-7b.train")]


def make_root(tmp: str) -> str:
    """``tmp`` becomes a checkout holding BENCHMARK.json and benchmarks/
    plus the tiny files and entries; returns it."""
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for kind, files in (("configs", CONFIGS), ("traffic", TRAFFIC)):
        for name, body in files.items():
            with open(os.path.join(tmp, "benchmarks", kind, f"{name}.json"), "w") as f:
                json.dump(body, f)
    for name, config, mix, like in CELLS:
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": 1, "why": "tiny"})
        for group in ("end_to_end", "per_layer"):
            for m in bench[group]:
                if like in m.get("workloads", ()):
                    m["workloads"].append(name)
    for name in CONFIGS:
        bench["configs"].append({
            "name": name, "source": "tests", "reduced": [], "why": "tiny",
            "file": f"benchmarks/configs/{name}.json"})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


def run_cell(root: str, cell: str, seed: int, seconds: float, trace: int,
             capsys, earlier: bool = False):
    """One pass of ``run.main`` on the CPU; returns the last line parsed
    (with ``earlier``: and the text of the lines before it)."""
    from benchmarks import run

    assert run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", str(trace)],
                    platform="cpu", root=root) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    return (last, "\n".join(lines[:-1])) if earlier else last
