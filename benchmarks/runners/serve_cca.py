"""Serving runner for compressed convolutional attention over experts behind
a router with memory (the zaya-like family): the configuration names its
family (``"serve_cca": "zaya_like"``), and with it the plain reference
(``reference/zaya_like.py``) and the weights (``weights_zaya.py``) of its own
tree.

Everything else IS ``runners/serve_hybrid.py`` (which is
``serve_family.py``, which is ``serve.py``): this file loads that module
afresh and calls its ``run`` with its family table, its key, its model
description and its program configuration exchanged, as
``runners/serve_kda.py`` does. The engine without a prefix store (the engine
refuses one beside a slot's tail as beside recurrent state), the window's
mean of ``pool_stats()["state_bytes"]`` (here the tails), streams, backlog,
window, clocks, warm-up of the chunk's buckets and the check against the
reference are those files' own code.

The runner's first act is to build the program's ``Config``: a commit whose
program cannot express the family (its ``Config`` has no field for the
convolutional attention, the router network or the tied table) ends with one
line and a non-zero exit, before any weights, engine or compile.
"""

from __future__ import annotations

import os

from benchmarks import common

FAMILIES = {"zaya_like": "weights_zaya"}
KEY = "serve_cca"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def model_dict(config: dict, runner: str = "serve") -> dict:
    """The published keys under the names the reference, the weights and the
    byte counts use, at the depth and context length this cell runs. Scalars
    only (the reference keys its compiled programs by them)."""
    sizes = config[runner]
    rope = config["rope_parameters"]["hybrid"]
    depth = sizes["num_hidden_layers"]
    if set(config["layer_types"][:depth]) != {"hybrid"} \
            or config["sliding_window"] is not None:
        raise SystemExit("the zaya_like family runs 'hybrid' layers (full "
                         "attention, one rotary base): window layers are not "
                         "implemented (program or reference)")
    if (config["cca_time0"], config["cca_time1"]) != (2, 2) \
            or config["attention_bias"] or config["lm_head_bias"] \
            or not config["tie_word_embeddings"] \
            or config["hidden_act"] != "silu" \
            or rope["rope_type"] != "default" \
            or rope["partial_rotary_factor"] != config["partial_rotary_factor"]:
        raise SystemExit(
            "the zaya_like family runs two causal convolutions of two taps, "
            "SwiGLU experts, a default rotary over partial_rotary_factor of "
            "a head, no bias in attention or head, one tied table")
    routed = config["published"].get("num_experts", config["num_experts"])
    held = config["num_experts"]
    if routed % held:
        raise SystemExit(f"{held} experts held do not divide {routed}")
    return {
        "family": config[KEY],
        "vocab": config["vocab_size"], "dim": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "rope_dim": int(config["head_dim"] * config["partial_rotary_factor"]),
        "rope_theta": float(rope["rope_theta"]),
        "moe_dim": config["moe_intermediate_size"],
        "n_experts": routed, "experts_held": held, "expert_first": 0,
        "moe_top_k": config["num_experts_per_tok"],
        "router_dim": config["router_hidden_size"],
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "dtype": config["torch_dtype"],
        "n_layers": depth,
        "max_seq": sizes["max_position_embeddings"],
    }


def program_config(model: dict, **extra):
    """The program's own Config for ``model``, or one line and a non-zero
    exit where the program cannot express it (a commit before compressed
    convolutional attention, the router network and the tied table)."""
    import jax.numpy as jnp

    from oim_tpu.models import llama

    ranks = model["n_experts"] // model["experts_held"]
    rank = model["expert_first"] // model["experts_held"]
    fields = dict(
        vocab=model["vocab"], dim=model["dim"], n_layers=model["n_layers"],
        n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
        head_dim=model["head_dim"], mlp_dim=model["moe_dim"],
        max_seq=model["max_seq"], dtype=jnp.dtype(model["dtype"]),
        rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"],
        cca_time0=2, cca_time1=2,
        partial_rotary_factor=model["rope_dim"] / model["head_dim"],
        n_experts=model["n_experts"], moe_top_k=model["moe_top_k"],
        moe_dispatch="ragged", moe_intermediate_size=model["moe_dim"],
        scoring_func="mlp", router_hidden_size=model["router_dim"],
        residual_scaling=True, tie_word_embeddings=True,
        expert_rank=f"{rank}/{ranks}" if ranks > 1 else "")
    fields.update(extra)
    try:
        cfg = llama.Config(**fields)
    except (TypeError, ValueError) as err:
        raise SystemExit(f"the program cannot express the {model['family']} "
                         f"family: {err}") from None
    if cfg.pattern != "CE" * model["n_layers"] \
            or cfg.rope_dim != model["rope_dim"]:
        raise SystemExit(
            f"the program runs {cfg.pattern!r} rotating {cfg.rope_dim} dims, "
            f"the configuration states {model['n_layers']} layers of CCA then "
            f"experts rotating {model['rope_dim']}")
    return cfg


def _hybrid(root: str | None = None):
    """This checkout's own runners/serve_hybrid.py, loaded afresh, with this
    family's collaborators in the place of its own."""
    hybrid = common.plugin(root or ROOT, "runners", "serve_hybrid")
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
