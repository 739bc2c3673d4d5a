"""Serving runner for a hybrid of gated latent attention and GatedDeltaNet
mixers with a dense FFN or an expert block behind each (the gigachat3_5-like
family): the configuration names its family (``"serve_gdn":
"gigachat3_5_like"``), and with it the plain reference
(``reference/gigachat3_5_like.py``) and the weights
(``weights_gigachat35.py``) of its own tree.

Everything else IS ``runners/serve_hybrid.py`` (which is
``serve_family.py``, which is ``serve.py``): this file loads that module
afresh and calls its ``run`` with its family table, its key, its model
description and its program configuration exchanged, as
``runners/serve_kda.py`` does. The engine without a prefix store, the
window's mean of ``pool_stats()["state_bytes"]``, streams, backlog, window,
clocks, warm-up of the chunk's buckets and the check against the reference
are those files' own code. One thing is kept that ``serve_hybrid`` drops:
the latent pool's fill (``latent_pool_fill``), which this family has.

The runner's first act is to build the program's ``Config``: a commit whose
program cannot express the family (its ``Config`` has no GatedDeltaNet
field, or refuses latent attention in a pattern) ends with one line and a
non-zero exit, before any weights, engine or compile.
"""

from __future__ import annotations

import os

from benchmarks import common

FAMILIES = {"gigachat3_5_like": "weights_gigachat35"}
KEY = "serve_gdn"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def pattern(layers, full_attention_layers, first_k_dense: int) -> str:
    """Two characters a PUBLISHED layer of ``layers``: its mixer ("*" latent
    attention where it is in ``full_attention_layers``, else "G") then its
    FFN ("D" dense under ``first_k_dense``, else "E")."""
    return "".join(("*" if i in full_attention_layers else "G")
                   + ("D" if i < first_k_dense else "E") for i in layers)


def model_dict(config: dict, runner: str = "serve") -> dict:
    """The published keys under the names the reference, the weights and
    the byte counts use, at the layers and context length this cell runs.
    ``n_routed_experts`` and ``vocab_size`` are what this rank holds; the
    router keeps the published width. Scalars only (the reference keys its
    compiled programs by them)."""
    sizes = config[runner]
    scaling = config["rope_scaling"]
    for key in ("n_group", "topk_group"):
        if config[key] != 1:
            raise SystemExit(f"{key}={config[key]}: group-limited routing "
                             "is not implemented (program or reference)")
    if not config["norm_topk_prob"] or config["attention_bias"] \
            or config["tie_word_embeddings"] \
            or config["use_shared_expert_sigmoid"] \
            or config["n_shared_experts"] != 1 or config["hidden_act"] != "silu" \
            or not config["rope_interleave"] or scaling["type"] != "yarn" \
            or scaling["mscale"] != scaling["mscale_all_dim"] \
            or config["norm_type"] != "ZeroCenteredGatedNorm" \
            or config["layernorm_type"] != "pre_post" \
            or config["linear_attn_o_norm_eps"] != config["rms_norm_eps"] \
            or config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                         + config["qk_rope_head_dim"]):
        raise SystemExit(
            "the gigachat3_5_like family runs SwiGLU experts with a "
            "renormalised sigmoid top-k beside one ungated shared expert, "
            "interleaved rope under YaRN with mscale = mscale_all_dim, "
            "zero-centred gated norms before and after every sublayer at "
            "one epsilon, no bias, untied tables")
    layers = sizes["layers_held"]
    if len(layers) != sizes["num_hidden_layers"]:
        raise SystemExit(f"{len(layers)} layers held, the {runner} group "
                         f"runs {sizes['num_hidden_layers']}")
    routed = config["published"]["n_routed_experts"]
    held = config["n_routed_experts"]
    if routed % held:
        raise SystemExit(f"{held} experts held do not divide {routed}")
    steps = config["assumed_sizes"]
    return {
        "family": config[KEY],
        "vocab": config["vocab_size"], "dim": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "q_lora_rank": config["q_lora_rank"],
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope_head_dim": config["qk_nope_head_dim"],
        "qk_rope_head_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "attn_gate": bool(config["gated_attention"]),
        "rope_theta": float(config["rope_theta"]),
        "yarn_factor": float(scaling["factor"]),
        "yarn_original_max": int(scaling["original_max_position_embeddings"]),
        "yarn_beta_fast": float(scaling["beta_fast"]),
        "yarn_beta_slow": float(scaling["beta_slow"]),
        "mla_scaling": bool(config["use_mla_scaling_factor"]),
        "pattern": pattern(layers, config["full_attention_layers"],
                           config["first_k_dense_replace"]),
        "gdn_k_heads": config["linear_num_key_heads"],
        "gdn_v_heads": config["linear_num_value_heads"],
        "gdn_k_dim": config["linear_key_head_dim"],
        "gdn_v_dim": config["linear_value_head_dim"],
        "gdn_conv": config["linear_conv_kernel_dim"],
        "gdn_gate_scale": float(config["linear_sigmoid_gate_scale"]),
        "time_step_min": float(steps["time_step_min"]),
        "time_step_max": float(steps["time_step_max"]),
        "mlp_dim": config["intermediate_size"],
        "moe_dim": config["moe_intermediate_size"],
        "shared_dim": (config["n_shared_experts"]
                       * config["moe_intermediate_size"]),
        "n_experts": routed, "experts_held": held, "expert_first": 0,
        "moe_top_k": config["num_experts_per_tok"],
        "routed_scale": float(config["routed_scaling_factor"]),
        "swiglu_limit": float(config["swiglu_limit"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "dtype": config["torch_dtype"],
        "n_layers": len(layers),
        "max_seq": sizes["max_position_embeddings"],
    }


def program_config(model: dict, **extra):
    """The program's own Config for ``model``, or one line and a non-zero
    exit where the program cannot express it (a commit before the
    GatedDeltaNet mixer, or one that refuses latent attention and a dense
    FFN block in a pattern)."""
    import jax.numpy as jnp

    from oim_tpu.models import llama

    ranks = model["n_experts"] // model["experts_held"]
    rank = model["expert_first"] // model["experts_held"]
    blocks = model["pattern"]
    fields = dict(
        vocab=model["vocab"], dim=model["dim"], n_layers=len(blocks),
        n_heads=model["n_heads"], n_kv_heads=model["n_heads"],
        head_dim=model["dim"] // model["n_heads"], mlp_dim=model["mlp_dim"],
        max_seq=model["max_seq"], dtype=jnp.dtype(model["dtype"]),
        rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"],
        rope_yarn=(model["yarn_factor"], model["yarn_original_max"],
                   model["yarn_beta_fast"], model["yarn_beta_slow"]),
        use_mla_scaling_factor=model["mla_scaling"],
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], gated_attention=model["attn_gate"],
        hybrid_override_pattern=blocks,
        linear_num_key_heads=model["gdn_k_heads"],
        linear_num_value_heads=model["gdn_v_heads"],
        linear_key_head_dim=model["gdn_k_dim"],
        linear_value_head_dim=model["gdn_v_dim"],
        linear_conv_kernel_dim=model["gdn_conv"],
        linear_sigmoid_gate_scale=model["gdn_gate_scale"],
        norm_type="zero_centered_gated", layernorm_type="pre_post",
        swiglu_limit=model["swiglu_limit"],
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


def _hybrid():
    """This checkout's own runners/serve_hybrid.py, loaded afresh, with
    this family's collaborators in the place of its own; the base it makes
    drops the latent pool's fill from the window's means (its families have
    no latent pool), so it is put back as ``serve_family`` reports it."""
    hybrid = common.plugin(ROOT, "runners", "serve_hybrid")
    hybrid.FAMILIES, hybrid.KEY = FAMILIES, KEY
    hybrid.model_dict, hybrid.program_config = model_dict, program_config
    make_base = hybrid._base

    def base():
        made = make_base()
        without_fill = made._window_means
        with_fill = common.plugin(ROOT, "runners", "serve_family")._window_means

        def window_means(samples, lo, hi):
            out = without_fill(samples, lo, hi)
            fill = with_fill(samples, lo, hi).get("latent_pool_fill")
            return out if fill is None else {**out, "latent_pool_fill": fill}

        made._window_means = window_means
        return made

    hybrid._base = base
    return hybrid


def run(ctx: common.Context) -> dict:
    # First: can the program express this configuration at all?
    program_config(model_dict(ctx.config, "serve"))
    return _hybrid().run(ctx)


def control_check(ctx, sample) -> dict:
    """The float8 control on the sample a run judged
    (``check_limits_family.py``), by ``serve_family``'s own comparison."""
    return _hybrid().control_check(ctx, sample)
