"""Weights from a seed for the solar_open2-like family (KDA mixers and gated
GQA attention that rotates nothing, an expert block of SwiGLU experts beside
a shared one behind each), on the device, in the type they are served in:
the sibling of ``weights_nemotron_h.py``, on ``weights.py``'s drawing
machinery (one key a slice, no float32 copy of a stacked leaf).

``cfg`` is the "model" group ``runners/serve_kda.py`` makes of a
configuration file. Parameters are stacked a KIND of block (``GROUPS``), in
the order each kind's blocks appear in the pattern. The program holds every
leaf as drawn (an expert's width, 1280, is whole lanes): ``make`` is the
tree both sides read. Only the experts HELD here are drawn
(``cfg["experts_held"]`` of ``cfg["n_experts"]``, from ``cfg["expert_first"]``
on); router and bias keep every expert's column.

What decides the numerics: ``dt_bias`` (one a channel) is the inverse
softplus of a step drawn log-uniform in [time_step_min, time_step_max],
``A_log`` (one a head) the log of a uniform [1, 16], as the family's
published implementation initialises them; with the low-rank decay gate at
the fan-in of its contractions (about N(0, 1) before the softplus) a
position's decay a channel ranges from 1 - 3e-4 down to e^-8, and beta = 2
sigmoid(N(0, 1)) over (0.3, 1.7): both sides of 1. Matrices: fan-in of the
contraction; the conv's weights at fan-in K; embedding 0.02; the output
gate's bias 0.1 and the router's bias 0.01, so that each does something.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import weights as base

root_key = base.root_key
leaf_paths = base.leaf_paths

GROUPS = {"K": "kda_layers", "E": "expert_layers", "*": "attn_layers"}


def group_sizes(cfg: dict) -> dict:
    """Blocks in each stacked group."""
    return {name: cfg["pattern"].count(kind) for kind, name in GROUPS.items()}


def tree_spec(cfg: dict) -> dict:
    """{path: (shape, dtype name, scale)}: ``scale`` a float (a normal draw
    at that scale), None (ones) or the name of a special draw ("dt_bias",
    "A_log")."""
    D, V = cfg["dim"], cfg["vocab"]
    q = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    H, d, K = cfg["kda_heads"], cfg["kda_head_dim"], cfg["kda_conv"]
    inner, r = H * d, cfg["kda_rank"]
    E, Eh, F = cfg["n_experts"], cfg["experts_held"], cfg["moe_dim"]
    Fs = cfg["shared_dim"]
    wd = cfg["dtype"]
    fan = D ** -0.5
    n = group_sizes(cfg)
    spec = {
        "embed": ((V, D), wd, 0.02),
        "final_norm": ((D,), "float32", None),
        "lm_head": ((D, V), wd, fan),
    }
    L = n["kda_layers"]
    if L:
        g = "kda_layers"
        spec.update({
            f"{g}/norm": ((L, D), "float32", None),
            f"{g}/w_qkv": ((L, D, 3 * inner), wd, fan),
            f"{g}/conv_w": ((L, K, 3 * inner), wd, K ** -0.5),
            f"{g}/w_f1": ((L, D, r), wd, fan),
            f"{g}/w_f2": ((L, r, inner), wd, r ** -0.5),
            f"{g}/dt_bias": ((L, inner), "float32", "dt_bias"),
            f"{g}/A_log": ((L, H), "float32", "A_log"),
            f"{g}/w_beta": ((L, D, H), wd, fan),
            f"{g}/w_g1": ((L, D, r), wd, fan),
            f"{g}/w_g2": ((L, r, inner), wd, r ** -0.5),
            f"{g}/g_bias": ((L, inner), "float32", 0.1),
            f"{g}/o_norm": ((L, d), "float32", None),
            f"{g}/w_out": ((L, inner, D), wd, inner ** -0.5),
        })
    L = n["expert_layers"]
    if L:
        g = "expert_layers"
        spec.update({
            f"{g}/norm": ((L, D), "float32", None),
            f"{g}/moe/router": ((L, D, E), "float32", fan),
            f"{g}/moe/bias": ((L, E), "float32", 0.01),
            f"{g}/moe/w_gate": ((L, Eh, D, F), wd, fan),
            f"{g}/moe/w_up": ((L, Eh, D, F), wd, fan),
            f"{g}/moe/w_down": ((L, Eh, F, D), wd, F ** -0.5),
            f"{g}/moe/shared/w_gate": ((L, D, Fs), wd, fan),
            f"{g}/moe/shared/w_up": ((L, D, Fs), wd, fan),
            f"{g}/moe/shared/w_down": ((L, Fs, D), wd, Fs ** -0.5),
        })
    L = n["attn_layers"]
    if L:
        g = "attn_layers"
        spec.update({
            f"{g}/norm": ((L, D), "float32", None),
            f"{g}/wq": ((L, D, q), wd, fan),
            f"{g}/wk": ((L, D, kv), wd, fan),
            f"{g}/wv": ((L, D, kv), wd, fan),
            f"{g}/wo": ((L, q, D), wd, q ** -0.5),
        })
        if cfg["gqa_gate"]:
            spec[f"{g}/wg"] = ((L, D, q), wd, fan)
    return spec


def _lead(path: str, shape) -> int:
    # As weights._lead: slice over every axis but the last two of a
    # stacked leaf (layer, and expert where there is one).
    return (max(len(shape) - 2, 0)
            if path.split("/")[0] in GROUPS.values() else 0)


def _special(root, path: str, shape, kind: str, cfg: dict):
    """The two vectors that no normal draw makes: ``A_log`` [L, H] and
    ``dt_bias`` [L, H d]."""
    u = jax.random.uniform(base._leaf_key(root, path), shape, jnp.float32)
    if kind == "A_log":
        return jnp.log(1.0 + 15.0 * u)
    lo, hi = cfg["time_step_min"], cfg["time_step_max"]
    step = jnp.exp(u * (jnp.log(hi) - jnp.log(lo)) + jnp.log(lo))
    return step + jnp.log(-jnp.expm1(-step))  # softplus^-1(step)


def _draw(root, path: str, spec, cfg: dict, layer=None):
    """One leaf: whole, or its layer ``layer`` alone, equal bit for bit."""
    shape, dt, scale = spec
    if isinstance(scale, str):
        leaf = _special(root, path, shape, scale, cfg)
        return leaf if layer is None else leaf[layer]
    lead = _lead(path, shape)
    if layer is None:
        return base._leaf(root, path, shape, dt, scale, lead)
    if lead or scale is None:
        return base._leaf(root, path, shape, dt, scale, lead, index=layer)
    return base._leaf(root, path, shape, dt, scale, 0)[layer]


def make(root, cfg: dict) -> dict:
    """The whole parameter tree as the program holds it (trace this under
    one ``jax.jit``)."""
    return base._nest({path: _draw(root, path, spec, cfg)
                       for path, spec in tree_spec(cfg).items()})


def make_on_device(seed: int, cfg: dict, out_shardings=None):
    """One jitted call from the seed; nothing is drawn on the host."""
    fn = jax.jit(lambda root: make(root, cfg), out_shardings=out_shardings)
    return fn(root_key(seed))


def layer_slice(root, cfg: dict, group: str, layer) -> dict:
    """Block ``layer`` of group ``group``, equal bit for bit to the values
    ``make`` stacks."""
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
