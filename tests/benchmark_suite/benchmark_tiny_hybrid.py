"""A tiny cell of the nemotron_h-like family ADDED to ``benchmark_tiny``'s
temporary copy of the benchmark: a configuration (one rank of four, all
three kinds of mixer), a chunk-prefilled backlog and a cell, as new files
and entries."""

from __future__ import annotations

import json
import os

import benchmark_tiny as tiny

CELL = "tiny-hybrid.agentbatch"
REAL = "nemotron-3-nano-30b.agentbatch"
CONFIG = {
    "serve_hybrid": "nemotron_h_like", "attention_bias": False,
    "attention_rotary_embedding": False, "chunk_size": 8, "conv_kernel": 4,
    "head_dim": 16, "hidden_size": 64,
    "hybrid_override_pattern": "MEM*EMEME", "intermediate_size": 48,
    "layer_norm_epsilon": 1e-5, "mamba_head_dim": 8,
    "mamba_hidden_act": "silu", "mamba_num_heads": 8,
    "mamba_proj_bias": False, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 96,
    "n_group": 1, "n_groups": 2, "n_routed_experts": 4,
    "n_shared_experts": 1, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_key_value_heads": 2, "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "ssm_state_size": 16, "time_step_floor": 1e-4, "time_step_max": 0.1,
    "time_step_min": 1e-3, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "vocab_size": 512, "torch_dtype": "float32",
    "published": {"n_routed_experts": 16},
    "serve": {"num_hidden_layers": 9, "max_position_embeddings": 256,
              "max_batch": 4, "queue_depth": 128, "kv_pool_tokens": 1024,
              "prefill_chunk": 32,
              "limits": {"gap_max": 1e-3, "gap_mean": 1e-4}},
}
TRAFFIC = {
    "runner": "serve_hybrid", "kind": "backlog", "requests": 96, "block": 8,
    "pre_roll_s": 0.5,
    "prompt_tokens": {"median": 60, "sigma": 0.5, "min": 20, "max": 150},
    "output_tokens": {"median": 8, "sigma": 0.4, "min": 4, "max": 16},
    "check_requests": 3, "schedule_seed": 1,
}


def make_root(tmp: str) -> str:
    root = tiny.make_root(tmp)
    for kind, name, body in (("configs", "tiny-hybrid", CONFIG),
                             ("traffic", "tiny-agentbatch", TRAFFIC)):
        with open(os.path.join(root, "benchmarks", kind, f"{name}.json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-hybrid", "source": "tests", "reduced": [], "why": "tiny",
        "file": "benchmarks/configs/tiny-hybrid.json"})
    bench["workloads"].append({"name": CELL, "config": "tiny-hybrid",
                               "traffic": "tiny-agentbatch", "chips": 1,
                               "why": "tiny"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if REAL in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
