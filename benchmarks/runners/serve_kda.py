"""Serving runner for a hybrid of KDA mixers and gated GQA attention with an
expert block behind each (the solar_open2-like family): the configuration
names its family (``"serve_kda": "solar_open2_like"``), and with it the
plain reference (``reference/solar_open2_like.py``) and the weights
(``weights_solar_open2.py``) of its own tree.

Everything else IS ``runners/serve_hybrid.py`` (which is
``serve_family.py``, which is ``serve.py``): this file loads that module
afresh and calls its ``run`` with its family table, its key, its model
description and its program configuration exchanged. The engine without a
prefix store, the window's mean of ``pool_stats()["state_bytes"]``, streams,
backlog, window, clocks, warm-up of the chunk's buckets and the check
against the reference are those files' own code.

The runner's first act is to build the program's ``Config``: a commit whose
program cannot express the family (its ``Config`` has no KDA field) ends
with one line and a non-zero exit, before any weights, engine or compile.
"""

from __future__ import annotations

import os

from benchmarks import common

FAMILIES = {"solar_open2_like": "weights_solar_open2"}
KEY = "serve_kda"


def pattern(n_layers: int, gqa_layers) -> str:
    """One character a block: published layer i is its mixer ("*" gated
    GQA where i is in ``gqa_layers``, else "K") then an expert block."""
    return "".join(("*" if i in gqa_layers else "K") + "E"
                   for i in range(n_layers))


def model_dict(config: dict, runner: str = "serve") -> dict:
    """The published keys under the names the reference, the weights and
    the byte counts use, at the depth and context length this cell runs.
    ``n_routed_experts`` and ``vocab_size`` are what this rank holds; the
    router keeps the published width."""
    sizes = config[runner]
    linear = config["linear_attn_config"]
    if not config["norm_topk_prob"] or config["use_rope"] \
            or config["kda_use_full_proj"] or config["first_k_dense_replace"] \
            or config["tie_word_embeddings"] \
            or config["n_shared_experts"] != 1 \
            or linear["num_kv_heads"] not in (None, linear["num_heads"]):
        raise SystemExit(
            "the solar_open2_like family runs SwiGLU experts with a "
            "renormalised sigmoid top-k beside one shared expert in every "
            "layer, attention that rotates nothing, low-rank KDA gates and "
            "as many KDA value heads as key heads, untied tables")
    layers = sizes["num_hidden_layers"]
    routed = config["published"]["n_routed_experts"]
    held = config["n_routed_experts"]
    if routed % held:
        raise SystemExit(f"{held} experts held do not divide {routed}")
    steps = config["assumed_sizes"]
    return {
        "family": config[KEY],
        "vocab": config["vocab_size"], "dim": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "attn_rope": bool(config["use_rope"]),
        "gqa_gate": bool(config["use_gqa_gate"]),
        "rope_theta": float(config["rope_theta"]),
        "pattern": pattern(layers, config["gqa_layers"]),
        "kda_heads": linear["num_heads"], "kda_head_dim": linear["head_dim"],
        "kda_conv": linear["short_conv_kernel_size"],
        "kda_rank": steps["kda_gate_rank"],
        "neg_eigval": bool(config["kda_allow_neg_eigval"]),
        "time_step_min": float(steps["time_step_min"]),
        "time_step_max": float(steps["time_step_max"]),
        "moe_dim": config["moe_intermediate_size"],
        "shared_dim": (config["n_shared_experts"]
                       * config["moe_intermediate_size"]),
        "n_experts": routed, "experts_held": held, "expert_first": 0,
        "moe_top_k": config["num_experts_per_tok"],
        "routed_scale": float(config["routed_scaling_factor"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "dtype": config["torch_dtype"],
        "n_layers": layers,
        "max_seq": sizes["max_position_embeddings"],
    }


def program_config(model: dict, **extra):
    """The program's own Config for ``model``, or one line and a non-zero
    exit where the program cannot express it (a commit before the KDA
    mixer and the gated attention: its Config has no such field)."""
    import jax.numpy as jnp

    from oim_tpu.models import llama

    ranks = model["n_experts"] // model["experts_held"]
    rank = model["expert_first"] // model["experts_held"]
    if model["kda_rank"] != model["kda_head_dim"]:
        raise SystemExit("the program's KDA gates are low-rank pairs of "
                         f"rank head_dim, not {model['kda_rank']}")
    blocks = model["pattern"]
    fields = dict(
        vocab=model["vocab"], dim=model["dim"], n_layers=model["n_layers"],
        n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
        head_dim=model["head_dim"], mlp_dim=model["moe_dim"],
        max_seq=model["max_seq"], dtype=jnp.dtype(model["dtype"]),
        rope_theta=model["rope_theta"], attn_rope=model["attn_rope"],
        norm_eps=model["rms_norm_eps"],
        gqa_layers=tuple(i for i in range(model["n_layers"])
                         if blocks[2 * i] == "*"),
        kda_num_heads=model["kda_heads"], kda_head_dim=model["kda_head_dim"],
        kda_conv_kernel=model["kda_conv"], use_gqa_gate=model["gqa_gate"],
        kda_allow_neg_eigval=model["neg_eigval"],
        n_experts=model["n_experts"], moe_top_k=model["moe_top_k"],
        moe_dispatch="ragged", moe_intermediate_size=model["moe_dim"],
        n_shared_experts=1, scoring_func="sigmoid",
        routed_scaling_factor=model["routed_scale"],
        expert_rank=f"{rank}/{ranks}" if ranks > 1 else "")
    fields.update(extra)
    try:
        cfg = llama.Config(**fields)
    except (TypeError, ValueError) as err:
        raise SystemExit(f"the program cannot express the {model['family']} "
                         f"family: {err}") from None
    if cfg.pattern != blocks:
        raise SystemExit(f"the program runs {cfg.pattern!r}, the "
                         f"configuration states {blocks!r}")
    return cfg


def _hybrid(root: str | None = None):
    """This checkout's own runners/serve_hybrid.py, loaded afresh, with
    this family's collaborators in the place of its own."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    hybrid = common.plugin(root, "runners", "serve_hybrid")
    hybrid.FAMILIES, hybrid.KEY = FAMILIES, KEY
    hybrid.model_dict, hybrid.program_config = model_dict, program_config
    return hybrid


def run(ctx: common.Context) -> dict:
    # First: can the program express this configuration at all?
    program_config(model_dict(ctx.config, "serve"))
    return _hybrid().run(ctx)


def control_check(ctx, sample) -> dict:
    """The float8 control on the sample a run judged
    (``check_limits_family.py``), by ``serve_family``'s own comparison."""
    return _hybrid().control_check(ctx, sample)
