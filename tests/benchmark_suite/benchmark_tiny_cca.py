"""A tiny cell of the zaya-like family ADDED to ``benchmark_tiny``'s temporary
copy of the benchmark: a configuration (three layers of compressed
convolutional attention and top-1 of 8 experts behind the router network, a
tied table), a chunk-prefilled backlog and a cell, as new files and
entries."""

from __future__ import annotations

import json
import os

import benchmark_tiny as tiny

CELL = "tiny-cca.reasonbatch64-cca"
REAL = "zaya1-8b.reasonbatch64-cca"
CONFIG = {
    "serve_cca": "zaya_like", "model_type": "zaya", "attention_bias": False,
    "cca_time0": 2, "cca_time1": 2, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 64, "layer_types": ["hybrid"] * 3, "lm_head_bias": False,
    "max_position_embeddings": 256, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 1,
    "num_hidden_layers": 3, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-5,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                   "rope_theta": 5000000,
                                   "rope_type": "default"},
                        "rope_type": "default"},
    "router_hidden_size": 16, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 512,
    "torch_dtype": "float32", "published": {},
    "serve": {"num_hidden_layers": 3, "max_position_embeddings": 256,
              "max_batch": 4, "queue_depth": 128, "kv_pool_tokens": 1024,
              "prefill_chunk": 32,
              "limits": {"gap_max": 1e-3, "gap_mean": 1e-4}},
}
TRAFFIC = {
    "runner": "serve_cca", "kind": "backlog", "requests": 96, "block": 8,
    "pre_roll_s": 0.5,
    "prompt_tokens": {"median": 40, "sigma": 0.5, "min": 12, "max": 100},
    "output_tokens": {"median": 10, "sigma": 0.4, "min": 5, "max": 20},
    "check_requests": 3, "schedule_seed": 1,
}
# 4 slots x 3 layers x (2 x 96 + 16) float32
TAIL_BYTES = 4 * 3 * (2 * 96 + 16) * 4


def make_root(tmp: str) -> str:
    root = tiny.make_root(tmp)
    for kind, name, body in (("configs", "tiny-cca", CONFIG),
                             ("traffic", "tiny-reasonbatch64-cca", TRAFFIC)):
        with open(os.path.join(root, "benchmarks", kind, f"{name}.json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-cca", "source": "tests", "reduced": [], "why": "tiny",
        "file": "benchmarks/configs/tiny-cca.json"})
    bench["workloads"].append({"name": CELL, "config": "tiny-cca",
                               "traffic": "tiny-reasonbatch64-cca", "chips": 1,
                               "why": "tiny"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if REAL in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
