"""A tiny cell of the gigachat3_5-like family ADDED to ``benchmark_tiny``'s
temporary copy of the benchmark: a configuration (one rank of four over a
leading dense layer and one period: gated latent attention and three
GatedDeltaNet layers, an expert block behind each), a chunk-prefilled
backlog and a cell, as new files and entries."""

from __future__ import annotations

import json
import os

import benchmark_tiny as tiny

CELL = "tiny-gdn.reasonbatch64"
REAL = "gigachat35-432b-a28b.reasonbatch64"
CONFIG = {
    "serve_gdn": "gigachat3_5_like", "model_type": "gigachat3_5",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "n_shared_experts": 1, "n_routed_experts": 4,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 16, "q_lora_rank": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "qk_nope_head_dim": 16,
    "qk_head_dim": 24, "n_group": 1, "topk_group": 1,
    "num_experts_per_tok": 4, "first_k_dense_replace": 3,
    "norm_topk_prob": True, "rope_interleave": True, "hidden_act": "silu",
    "rms_norm_eps": 1e-6, "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "attention_bias": False, "norm_type": "ZeroCenteredGatedNorm",
    "layernorm_type": "pre_post", "layernorm_gating_weight": 2,
    "gated_attention": True, "use_shared_expert_sigmoid": False,
    "use_mla_scaling_factor": True,
    "linear_attention_type": "GigaChat35GatedDeltaNet",
    "full_attention_layers": [3, 7], "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-6,
    "swiglu_limit": 1, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "published": {"n_routed_experts": 16},
    "assumed_sizes": {"time_step_min": 1e-3, "time_step_max": 0.1},
    "serve": {"num_hidden_layers": 5, "layers_held": [0, 3, 4, 5, 6],
              "max_position_embeddings": 256, "max_batch": 4,
              "queue_depth": 128, "kv_pool_tokens": 1024,
              "prefill_chunk": 32,
              "limits": {"gap_max": 1e-3, "gap_mean": 1e-4}},
}
TRAFFIC = {
    "runner": "serve_gdn", "kind": "backlog", "requests": 96, "block": 8,
    "pre_roll_s": 0.5,
    "prompt_tokens": {"median": 40, "sigma": 0.5, "min": 12, "max": 100},
    "output_tokens": {"median": 10, "sigma": 0.4, "min": 5, "max": 20},
    "check_requests": 3, "schedule_seed": 1,
}
# 4 slots x 4 GatedDeltaNet layers x (S [4, 16, 16] float32 + a window
# [3, 128] float32)
STATE_BYTES = 4 * 4 * (4 * 16 * 16 * 4 + 3 * 128 * 4)


def make_root(tmp: str) -> str:
    root = tiny.make_root(tmp)
    for kind, name, body in (("configs", "tiny-gdn", CONFIG),
                             ("traffic", "tiny-reasonbatch64", TRAFFIC)):
        with open(os.path.join(root, "benchmarks", kind, f"{name}.json"), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-gdn", "source": "tests", "reduced": [], "why": "tiny",
        "file": "benchmarks/configs/tiny-gdn.json"})
    bench["workloads"].append({"name": CELL, "config": "tiny-gdn",
                               "traffic": "tiny-reasonbatch64", "chips": 1,
                               "why": "tiny"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if REAL in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
