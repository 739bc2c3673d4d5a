"""Weights from a seed for the gigachat3_5-like family (gated latent attention
and GatedDeltaNet mixers, a dense SwiGLU or an expert block of SwiGLU experts
beside a shared one behind each, a gated norm before and after every
sublayer), on the device, in the type they are served in: the sibling of
``weights_solar_open2.py``, on ``weights.py``'s drawing machinery (one key a
slice, no float32 copy of a stacked leaf).

``cfg`` is the "model" group ``runners/serve_gdn.py`` makes of a
configuration file. Parameters are stacked a KIND of block (``GROUPS``), in
the order each kind's blocks appear in the pattern. Leaves are DRAWN in the
published layout: the rope dims of ``wq_b`` and ``wkv_a`` in interleaved
pairs (2i, 2i+1), which is how the reference rotates them; ``make`` hands
the program the same values with those columns in the split-half order it
rotates in (``weights_deepseek.to_program_layout``). Only the experts HELD
here are drawn (``cfg["experts_held"]`` of ``cfg["n_experts"]``, from
``cfg["expert_first"]`` on); router and bias keep every expert's column.

What decides the numerics: every norm's weight ``w`` (through ``2
sigmoid(w)``) is drawn at 0.5, so that the sigmoid changes the result (a
fresh model's 0 multiplies by 1); ``dt_bias`` (one a value head) is the
inverse softplus of a step drawn log-uniform in [time_step_min,
time_step_max], ``A_log`` the log of a uniform [1, 16], as
``weights_solar_open2.py`` draws them: with ``W_a`` at the fan-in of its
contraction a position's decay a head ranges from 1 - 3e-4 down to e^-8, and
beta = sigmoid(N(0, 1)) over (0.1, 0.9). Matrices: fan-in of the
contraction; the conv's weights at fan-in K; embedding 0.02; the router's
bias 0.01, so that it changes choices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import weights as base
from benchmarks.weights_deepseek import to_program_layout
from benchmarks.weights_solar_open2 import _special

root_key = base.root_key
leaf_paths = base.leaf_paths

GROUPS = {"G": "gdn_layers", "D": "ffn_layers", "E": "expert_layers",
          "*": "attn_layers"}
NORM = 0.5  # the draw of a norm's weight, before its sigmoid


def group_sizes(cfg: dict) -> dict:
    """Blocks in each stacked group."""
    return {name: cfg["pattern"].count(kind) for kind, name in GROUPS.items()}


def tree_spec(cfg: dict) -> dict:
    """{path: (shape, dtype name, scale)}: ``scale`` a float (a normal draw
    at that scale) or the name of a special draw ("dt_bias", "A_log")."""
    D, V, H = cfg["dim"], cfg["vocab"], cfg["n_heads"]
    ql, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    Hv, dv, K = cfg["gdn_v_heads"], cfg["gdn_v_dim"], cfg["gdn_conv"]
    key_dim = cfg["gdn_k_heads"] * cfg["gdn_k_dim"]
    conv_dim, value_dim = 2 * key_dim + Hv * dv, Hv * dv
    E, Eh, Fe = cfg["n_experts"], cfg["experts_held"], cfg["moe_dim"]
    F, Fs = cfg["mlp_dim"], cfg["shared_dim"]
    wd = cfg["dtype"]
    fan = D ** -0.5
    n = group_sizes(cfg)
    spec = {
        "embed": ((V, D), wd, 0.02),
        "final_norm": ((D,), "float32", NORM),
        "lm_head": ((D, V), wd, fan),
    }
    for g, L in n.items():
        if L:  # every sublayer stands between two norms
            spec[f"{g}/norm"] = ((L, D), "float32", NORM)
            spec[f"{g}/post_norm"] = ((L, D), "float32", NORM)
    L = n["gdn_layers"]
    if L:
        g = "gdn_layers"
        spec.update({
            f"{g}/w_qkv": ((L, D, conv_dim), wd, fan),
            f"{g}/conv_w": ((L, K, conv_dim), wd, K ** -0.5),
            f"{g}/w_a": ((L, D, Hv), wd, fan),
            f"{g}/dt_bias": ((L, Hv), "float32", "dt_bias"),
            f"{g}/A_log": ((L, Hv), "float32", "A_log"),
            f"{g}/w_b": ((L, D, Hv), wd, fan),
            f"{g}/w_z": ((L, D, value_dim), wd, fan),
            f"{g}/o_norm": ((L, dv), "float32", NORM),
            f"{g}/w_out": ((L, value_dim, D), wd, value_dim ** -0.5),
        })
    L = n["ffn_layers"]
    if L:
        g = "ffn_layers"
        spec.update({
            f"{g}/w_gate": ((L, D, F), wd, fan),
            f"{g}/w_up": ((L, D, F), wd, fan),
            f"{g}/w_down": ((L, F, D), wd, F ** -0.5),
        })
    L = n["expert_layers"]
    if L:
        g = "expert_layers"
        spec.update({
            f"{g}/moe/router": ((L, D, E), "float32", fan),
            f"{g}/moe/bias": ((L, E), "float32", 0.01),
            f"{g}/moe/w_gate": ((L, Eh, D, Fe), wd, fan),
            f"{g}/moe/w_up": ((L, Eh, D, Fe), wd, fan),
            f"{g}/moe/w_down": ((L, Eh, Fe, D), wd, Fe ** -0.5),
            f"{g}/moe/shared/w_gate": ((L, D, Fs), wd, fan),
            f"{g}/moe/shared/w_up": ((L, D, Fs), wd, fan),
            f"{g}/moe/shared/w_down": ((L, Fs, D), wd, Fs ** -0.5),
        })
    L = n["attn_layers"]
    if L:
        g = "attn_layers"
        spec.update({
            f"{g}/q_norm": ((L, ql), "float32", NORM),
            f"{g}/kv_norm": ((L, r), "float32", NORM),
            f"{g}/wq_a": ((L, D, ql), wd, fan),
            f"{g}/wq_b": ((L, ql, H * (nope + rope)), wd, ql ** -0.5),
            f"{g}/wkv_a": ((L, D, r + rope), wd, fan),
            f"{g}/wkv_b": ((L, r, H * (nope + v)), wd, r ** -0.5),
            f"{g}/wo": ((L, H * v, D), wd, (H * v) ** -0.5),
        })
        if cfg["attn_gate"]:
            spec[f"{g}/wg"] = ((L, D, H * v), wd, fan)
    return spec


def _lead(path: str, shape) -> int:
    # As weights._lead: slice over every axis but the last two of a
    # stacked leaf (layer, and expert where there is one).
    return (max(len(shape) - 2, 0)
            if path.split("/")[0] in GROUPS.values() else 0)


def _draw(root, path: str, spec, cfg: dict, layer=None):
    """One leaf AS PUBLISHED: whole, or its layer ``layer`` alone, equal bit
    for bit."""
    shape, dt, scale = spec
    if isinstance(scale, str):
        leaf = _special(root, path, shape, scale, cfg)
        return leaf if layer is None else leaf[layer]
    lead = _lead(path, shape)
    if layer is None:
        return base._leaf(root, path, shape, dt, scale, lead)
    if lead:
        return base._leaf(root, path, shape, dt, scale, lead, index=layer)
    return base._leaf(root, path, shape, dt, scale, 0)[layer]


def make(root, cfg: dict) -> dict:
    """The whole parameter tree AS THE PROGRAM HOLDS IT (trace this under
    one ``jax.jit``)."""
    return base._nest({
        path: to_program_layout(path, _draw(root, path, spec, cfg), cfg)
        for path, spec in tree_spec(cfg).items()})


def make_on_device(seed: int, cfg: dict, out_shardings=None):
    """One jitted call from the seed; nothing is drawn on the host."""
    fn = jax.jit(lambda root: make(root, cfg), out_shardings=out_shardings)
    return fn(root_key(seed))


def layer_slice(root, cfg: dict, group: str, layer) -> dict:
    """Block ``layer`` of group ``group``, every leaf AS PUBLISHED (rope
    dims interleaved), equal bit for bit to the values ``make`` permutes."""
    flat = {path[len(group) + 1:]: _draw(root, path, spec, cfg, layer)
            for path, spec in tree_spec(cfg).items()
            if path.startswith(group + "/")}
    return base._nest(flat)


def tables(root, cfg: dict) -> dict:
    """The leaves outside the layer stacks (embed, final_norm, lm_head)."""
    return {path: _draw(root, path, spec, cfg)
            for path, spec in tree_spec(cfg).items()
            if path.split("/")[0] not in GROUPS.values()}


def check_against_program(cfg: dict, program_shapes) -> None:
    """The tree this file hands over must be the tree the program
    initialises: same paths, shapes and types."""
    want = {name: (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
            for name, leaf in leaf_paths(program_shapes)}
    have = {path: (tuple(shape), jnp.dtype(dt).name)
            for path, (shape, dt, _) in tree_spec(cfg).items()}
    if want != have:
        diff = sorted(set(want.items()) ^ set(have.items()))
        raise SystemExit(f"benchmark weights do not match the program's "
                         f"parameter tree: {diff[:6]}")
