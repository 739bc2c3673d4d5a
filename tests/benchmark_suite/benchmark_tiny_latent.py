"""A tiny cell of the latent-attention family ADDED to ``benchmark_tiny``'s
temporary copy of the benchmark: a configuration, a chunk-prefilled open
loop and a cell, as new files and entries."""

from __future__ import annotations

import json
import os

import benchmark_tiny as tiny

CELL = "tiny-latent.longctx"
CONFIG = {
    "serve_family": "deepseek_like", "attention_bias": False,
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 192,
    "kv_lora_rank": 16, "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 16, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "q_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6, "rope_scaling": None,
    "rope_theta": 32000000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_group": 1, "v_head_dim": 16,
    "vocab_size": 512, "torch_dtype": "float32",
    "serve": {"num_hidden_layers": 3, "max_position_embeddings": 256,
              "max_batch": 4, "queue_depth": 128, "kv_pool_tokens": 2048,
              "prefill_chunk": 32,
              "limits": {"gap_max": 1e-3, "gap_mean": 1e-4}},
}
TRAFFIC = {
    "runner": "serve_family", "kind": "open_loop", "rate_per_s": 6.0,
    "pre_roll_s": 0.5,
    "prompt_tokens": {"median": 60, "sigma": 0.5, "min": 20, "max": 150},
    "output_tokens": {"median": 8, "sigma": 0.4, "min": 4, "max": 16},
    "check_requests": 3, "schedule_seed": 1,
}


def make_root(tmp: str) -> str:
    root = tiny.make_root(tmp)
    for kind, name, body in (("configs", "tiny-latent", CONFIG),
                             ("traffic", "tiny-longctx", TRAFFIC)):
        with open(os.path.join(root, "benchmarks", kind, f"{name}.json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-latent", "source": "tests", "reduced": [], "why": "tiny",
        "file": "benchmarks/configs/tiny-latent.json"})
    bench["workloads"].append({"name": CELL, "config": "tiny-latent",
                               "traffic": "tiny-longctx", "chips": 1,
                               "why": "tiny"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "joyai-llm-flash.longctx" in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
