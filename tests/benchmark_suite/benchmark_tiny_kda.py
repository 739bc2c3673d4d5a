"""A tiny cell of the solar_open2-like family ADDED to ``benchmark_tiny``'s
temporary copy of the benchmark: a configuration (one rank of four over one
period: gated attention and three KDA layers, an expert block behind each),
a chunk-prefilled backlog and a cell, as new files and entries."""

from __future__ import annotations

import json
import os

import benchmark_tiny as tiny

CELL = "tiny-kda.agentbatch64"
REAL = "solar-open2-250b.agentbatch64"
CONFIG = {
    "serve_kda": "solar_open2_like", "model_type": "solar_open2",
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
    "head_dim": 16, "num_key_value_heads": 2, "vocab_size": 512,
    "intermediate_size": 160, "moe_intermediate_size": 32,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "tie_word_embeddings": False,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8], "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 4,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 4, "torch_dtype": "float32",
    "published": {"n_routed_experts": 16},
    "assumed_sizes": {"kda_gate_rank": 16, "time_step_min": 1e-3,
                      "time_step_max": 0.1},
    "serve": {"num_hidden_layers": 4, "max_position_embeddings": 256,
              "max_batch": 4, "queue_depth": 128, "kv_pool_tokens": 1024,
              "prefill_chunk": 32,
              "limits": {"gap_max": 1e-3, "gap_mean": 1e-4}},
}
TRAFFIC = {
    "runner": "serve_kda", "kind": "backlog", "requests": 96, "block": 8,
    "pre_roll_s": 0.5,
    "prompt_tokens": {"median": 60, "sigma": 0.5, "min": 20, "max": 150},
    "output_tokens": {"median": 8, "sigma": 0.4, "min": 4, "max": 16},
    "check_requests": 3, "schedule_seed": 1,
}
# 4 slots x 3 KDA layers x (S [4, 16, 16] float32 + a window [3, 192] float32)
STATE_BYTES = 4 * 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)


def make_root(tmp: str) -> str:
    root = tiny.make_root(tmp)
    for kind, name, body in (("configs", "tiny-kda", CONFIG),
                             ("traffic", "tiny-agentbatch64", TRAFFIC)):
        with open(os.path.join(root, "benchmarks", kind, f"{name}.json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-kda", "source": "tests", "reduced": [], "why": "tiny",
        "file": "benchmarks/configs/tiny-kda.json"})
    bench["workloads"].append({"name": CELL, "config": "tiny-kda",
                               "traffic": "tiny-agentbatch64", "chips": 1,
                               "why": "tiny"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if REAL in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
