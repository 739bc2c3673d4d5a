"""Serving runner for a hybrid of mixers with recurrent state (the
nemotron_h-like family): the configuration names its family
(``"serve_hybrid": "nemotron_h_like"``), and with it the plain reference
(``reference/nemotron_h_like.py``) and the weights
(``weights_nemotron_h.py``) of its own tree.

Everything else IS ``runners/serve_family.py``, which in turn is
``runners/serve.py``: this file loads that module afresh and calls its
``run`` with its family table, its model description and its program
configuration exchanged, as that file does with ``serve.py``. Streams,
backlog, window, clocks, warm-up of the chunk's buckets, the engine's
``prefill_chunk``, the sampler and the check against the reference are
those files' own code. Two things are added for the length of the call:

- the engine is built with ``prefix_cache_bytes=0`` (``ServeEngine``
  refuses a prefix store beside recurrent state: a page hit brings no
  state; ``runners/serve.py`` builds its engine with the default store);
- the window's mean of ``pool_stats()["state_bytes"]`` joins the stats
  (``state_pool_bytes``), from the samples ``serve_family`` takes.

The runner's first act is to build the program's ``Config``: a commit
whose program cannot express the family ends with one line and a non-zero
exit, before any weights, engine or compile.
"""

from __future__ import annotations

import importlib
import os

import numpy as np

from benchmarks import common

FAMILIES = {"nemotron_h_like": "weights_nemotron_h"}
KEY = "serve_hybrid"


def model_dict(config: dict, runner: str = "serve") -> dict:
    """The published keys under the names the reference, the weights and
    the byte counts use, at the depth and context length this cell runs.
    ``n_routed_experts`` and ``vocab_size`` are what this rank holds;
    the router keeps the published width."""
    sizes = config[runner]
    for key in ("n_group", "topk_group"):
        if config[key] != 1:
            raise SystemExit(f"{key}={config[key]}: group-limited routing "
                             "is not implemented (program or reference)")
    if not config["norm_topk_prob"] or config["mlp_hidden_act"] != "relu2" \
            or config["mamba_hidden_act"] != "silu" or config["use_bias"] \
            or config["mamba_proj_bias"] or config["mlp_bias"] \
            or config["attention_bias"] or not config["use_conv_bias"] \
            or config["n_shared_experts"] != 1 \
            or config["norm_eps"] != config["layer_norm_epsilon"]:
        raise SystemExit(
            "the nemotron_h_like family runs squared-ReLU experts with a "
            "renormalised sigmoid top-k beside one shared expert, silu in "
            "the Mamba mixer, a conv bias and no other bias")
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != sizes["num_hidden_layers"]:
        raise SystemExit(f"the pattern has {len(pattern)} layers, the "
                         f"{runner} group runs {sizes['num_hidden_layers']}")
    routed = config["published"]["n_routed_experts"]
    held = config["n_routed_experts"]
    if routed % held:
        raise SystemExit(f"{held} experts held do not divide {routed}")
    return {
        "family": config[KEY],
        "vocab": config["vocab_size"], "dim": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "attn_rope": bool(config["attention_rotary_embedding"]),
        "rope_theta": float(config["rope_theta"]),
        "pattern": pattern,
        "mamba_heads": config["mamba_num_heads"],
        "mamba_head_dim": config["mamba_head_dim"],
        "ssm_groups": config["n_groups"],
        "ssm_state": config["ssm_state_size"],
        "conv_kernel": config["conv_kernel"],
        "chunk": config["chunk_size"],
        "time_step_min": float(config["time_step_min"]),
        "time_step_max": float(config["time_step_max"]),
        "time_step_floor": float(config["time_step_floor"]),
        "moe_dim": config["moe_intermediate_size"],
        "shared_dim": config["moe_shared_expert_intermediate_size"],
        "n_experts": routed, "experts_held": held, "expert_first": 0,
        "moe_top_k": config["num_experts_per_tok"],
        "routed_scale": float(config["routed_scaling_factor"]),
        "rms_norm_eps": float(config["norm_eps"]),
        "dtype": config["torch_dtype"],
        "n_layers": sizes["num_hidden_layers"],
        "max_seq": sizes["max_position_embeddings"],
    }


def program_config(model: dict, **extra):
    """The program's own Config for ``model``, or one line and a non-zero
    exit where the program cannot express it (a commit before the hybrid
    pattern, the state pool and the held share: its Config has no such
    field)."""
    import jax.numpy as jnp

    from oim_tpu.models import llama

    ranks = model["n_experts"] // model["experts_held"]
    rank = model["expert_first"] // model["experts_held"]
    fields = dict(
        vocab=model["vocab"], dim=model["dim"], n_layers=model["n_layers"],
        n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
        head_dim=model["head_dim"], mlp_dim=model["moe_dim"],
        max_seq=model["max_seq"], dtype=jnp.dtype(model["dtype"]),
        rope_theta=model["rope_theta"], attn_rope=model["attn_rope"],
        norm_eps=model["rms_norm_eps"],
        hybrid_override_pattern=model["pattern"],
        mamba_num_heads=model["mamba_heads"],
        mamba_head_dim=model["mamba_head_dim"], n_groups=model["ssm_groups"],
        ssm_state_size=model["ssm_state"], conv_kernel=model["conv_kernel"],
        chunk_size=model["chunk"], n_experts=model["n_experts"],
        moe_top_k=model["moe_top_k"], moe_dispatch="ragged",
        moe_intermediate_size=model["moe_dim"], n_shared_experts=1,
        moe_shared_expert_intermediate_size=model["shared_dim"],
        mlp_hidden_act="relu2", scoring_func="sigmoid",
        routed_scaling_factor=model["routed_scale"],
        expert_rank=f"{rank}/{ranks}" if ranks > 1 else "")
    fields.update(extra)
    try:
        return llama.Config(**fields)
    except (TypeError, ValueError) as err:
        raise SystemExit(f"the program cannot express the {model['family']} "
                         f"family: {err}") from None


def _family(config: dict):
    """(reference module, weights module) of the configuration's family."""
    name = config.get(KEY)
    if name not in FAMILIES:
        raise SystemExit(f"runners/serve_hybrid.py: no family {name!r} "
                         f"(have: {sorted(FAMILIES)})")
    return (importlib.import_module(f"benchmarks.reference.{name}"),
            importlib.import_module(f"benchmarks.{FAMILIES[name]}"))


def _base(root: str | None = None):
    """This checkout's own runners/serve_family.py, loaded afresh, with
    this family's collaborators in the place of its own."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    base = common.plugin(root, "runners", "serve_family")
    base.FAMILIES = FAMILIES
    base.model_dict = model_dict
    base.program_config = program_config
    base._family = _family
    means = base._window_means

    def window_means(samples, lo, hi):
        out = means(samples, lo, hi)
        out.pop("latent_pool_fill", None)  # no latent pool here
        inside = [p for t, _, p in samples if lo <= t <= hi]
        if inside and "state_bytes" in inside[0]:
            out["state_pool_bytes"] = float(np.mean(
                [p["state_bytes"] for p in inside]))
        return out

    base._window_means = window_means
    return base


def run(ctx: common.Context) -> dict:
    # First: can the program express this configuration at all?
    program_config(model_dict(ctx.config, "serve"))
    import oim_tpu.serve.engine as engine_module

    base = _base()
    real = engine_module.ServeEngine

    class NoStoreEngine(real):
        """The program's engine without a prefix store."""

        def __init__(self, *args, **kwargs):
            super().__init__(*args, prefix_cache_bytes=0, **kwargs)

    engine_module.ServeEngine = NoStoreEngine
    try:
        return base.run(ctx)
    finally:
        engine_module.ServeEngine = real


def control_check(ctx, sample) -> dict:
    """The float8 control on the sample a run judged
    (``check_limits_family.py``), by ``serve_family``'s own comparison."""
    return _base().control_check(ctx, sample)
