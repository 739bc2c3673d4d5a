"""Weights from a seed for the nemotron_h-like family (a hybrid of Mamba-2
mixers, squared-ReLU experts beside a shared one, and GQA attention that
rotates nothing), on the device, in the type they are served in: the
sibling of ``weights_deepseek.py``, on ``weights.py``'s drawing machinery
(one key a slice, no float32 copy of a stacked leaf).

``cfg`` is the "model" group ``runners/serve_hybrid.py`` makes of a
configuration file. Parameters are stacked a KIND of mixer (``GROUPS``), in
the order each kind's layers appear in the pattern. Leaves are DRAWN at the
published widths; ``make`` hands the program the same values in the layout
it holds them in: the routed experts' leaves padded with zero columns
(``w_up``) and zero rows (``w_down``) to whole lanes (``stored_width``:
1856 -> 1920; a zero column adds an exact zero), written out here, not
imported from the program. Only the experts HELD here are drawn
(``cfg["experts_held"]`` of ``cfg["n_experts"]``, from ``cfg["expert_first"]``
on); router and bias keep every expert's column.

What decides the numerics follows the family's published initialisation:
``dt_bias`` is the inverse softplus of a step drawn log-uniform in
[time_step_min, time_step_max] and floored at time_step_floor, ``A_log`` the
log of a uniform [1, 16], ``D`` one, the conv's weights at fan-in K (its bias
at 0.1, so that it does something). Matrices: fan-in of the contraction;
embedding 0.02; the router's bias 0.01 so that it changes choices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import weights as base

root_key = base.root_key
leaf_paths = base.leaf_paths

GROUPS = {"M": "mamba_layers", "E": "expert_layers", "*": "attn_layers"}
LANES = 128


def group_sizes(cfg: dict) -> dict:
    """Layers in each stacked group."""
    return {name: cfg["pattern"].count(kind) for kind, name in GROUPS.items()}


def stored_width(width: int) -> int:
    """The width the program holds a routed expert's leaves at."""
    if width <= LANES or width % LANES == 0:
        return width
    return -(-width // LANES) * LANES


def mamba_sizes(cfg: dict) -> dict:
    H, P = cfg["mamba_heads"], cfg["mamba_head_dim"]
    conv_dim = H * P + 2 * cfg["ssm_groups"] * cfg["ssm_state"]
    return {"inner": H * P, "conv_dim": conv_dim,
            "proj": H * P + conv_dim + H}


def tree_spec(cfg: dict) -> dict:
    """{path: (shape AS DRAWN, dtype name, scale)}: ``scale`` a float (a
    normal draw at that scale), None (ones) or the name of a special draw
    ("dt_bias", "A_log")."""
    D, V = cfg["dim"], cfg["vocab"]
    q = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    m = mamba_sizes(cfg)
    H, K = cfg["mamba_heads"], cfg["conv_kernel"]
    E, Eh, F, Fs = (cfg["n_experts"], cfg["experts_held"], cfg["moe_dim"],
                    cfg["shared_dim"])
    wd = cfg["dtype"]
    fan = D ** -0.5
    n = group_sizes(cfg)
    spec = {
        "embed": ((V, D), wd, 0.02),
        "final_norm": ((D,), "float32", None),
        "lm_head": ((D, V), wd, fan),
    }
    L = n["mamba_layers"]
    if L:
        g = "mamba_layers"
        spec.update({
            f"{g}/norm": ((L, D), "float32", None),
            f"{g}/w_in": ((L, D, m["proj"]), wd, fan),
            f"{g}/conv_w": ((L, K, m["conv_dim"]), wd, K ** -0.5),
            f"{g}/conv_b": ((L, m["conv_dim"]), wd, 0.1),
            f"{g}/dt_bias": ((L, H), "float32", "dt_bias"),
            f"{g}/A_log": ((L, H), "float32", "A_log"),
            f"{g}/D": ((L, H), "float32", None),
            f"{g}/gate_norm": ((L, m["inner"]), "float32", None),
            f"{g}/w_out": ((L, m["inner"], D), wd, m["inner"] ** -0.5),
        })
    L = n["expert_layers"]
    if L:
        g = "expert_layers"
        spec.update({
            f"{g}/norm": ((L, D), "float32", None),
            f"{g}/moe/router": ((L, D, E), "float32", fan),
            f"{g}/moe/bias": ((L, E), "float32", 0.01),
            f"{g}/moe/w_up": ((L, Eh, D, F), wd, fan),
            f"{g}/moe/w_down": ((L, Eh, F, D), wd, F ** -0.5),
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
    return spec


def _lead(path: str, shape) -> int:
    # As weights._lead: slice over every axis but the last two of a
    # stacked leaf (layer, and expert where there is one).
    return (max(len(shape) - 2, 0)
            if path.split("/")[0] in GROUPS.values() else 0)


def _special(root, path: str, shape, kind: str, cfg: dict):
    """The two per-head vectors [L, H] that no normal draw makes."""
    u = jax.random.uniform(base._leaf_key(root, path), shape, jnp.float32)
    if kind == "A_log":
        return jnp.log(1.0 + 15.0 * u)
    lo, hi = cfg["time_step_min"], cfg["time_step_max"]
    step = jnp.exp(u * (jnp.log(hi) - jnp.log(lo)) + jnp.log(lo))
    step = jnp.maximum(step, cfg["time_step_floor"])
    return step + jnp.log(-jnp.expm1(-step))  # softplus^-1(step)


def _draw(root, path: str, spec, cfg: dict, layer=None):
    """One leaf as published: whole, or its layer ``layer`` alone, equal
    bit for bit."""
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


def to_program_layout(path: str, leaf, cfg: dict):
    """The routed experts' leaves padded with zeros to the width the
    program holds them at; every other leaf as drawn."""
    pad = stored_width(cfg["moe_dim"]) - cfg["moe_dim"]
    if not pad or "/moe/" not in path or "/shared/" in path:
        return leaf
    name = path.rsplit("/", 1)[-1]
    if name == "w_up":
        return jnp.pad(leaf, ((0, 0),) * (leaf.ndim - 1) + ((0, pad),))
    if name == "w_down":
        return jnp.pad(leaf, ((0, 0),) * (leaf.ndim - 2) + ((0, pad), (0, 0)))
    return leaf


def program_spec(cfg: dict) -> dict:
    """{path: (shape, dtype name)} of the tree ``make`` gives."""
    out = {}
    for path, (shape, dt, _) in tree_spec(cfg).items():
        leaf = jax.eval_shape(lambda s=shape, d=dt, p=path: to_program_layout(
            p, jnp.zeros(s, d), cfg))
        out[path] = (tuple(leaf.shape), jnp.dtype(dt).name)
    return out


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
    """Layer ``layer`` of group ``group``, every leaf AS PUBLISHED (no
    padding), equal bit for bit to the values ``make`` pads."""
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
    have = program_spec(cfg)
    if want != have:
        diff = sorted(set(want.items()) ^ set(have.items()))
        raise SystemExit(f"benchmark weights do not match the program's "
                         f"parameter tree: {diff[:6]}")
