"""Weights from a seed for the zaya-like family (compressed convolutional
attention, then top-1 SwiGLU experts behind a router network with memory
across layers; learned scales on the residual stream; one tied table), on the
device, in the type they are served in: the sibling of
``weights_gigachat35.py``, on ``weights.py``'s drawing machinery (one key a
slice, no float32 copy of a stacked leaf).

``cfg`` is the "model" group ``runners/serve_cca.py`` makes of a
configuration file. Parameters are stacked a KIND of block (``GROUPS``): the
CCA sublayers and the expert sublayers of the layers held, in order. There is
NO ``lm_head``: the head is the embedding's transpose.

What decides the numerics, and is not a plain fan-in draw (every choice
stands in the configuration's ``assumed.weights``): the residual stream's
scales ``res_a`` / ``res_c`` at 1 +- 0.1 and biases ``res_b`` / ``res_e`` at
0 +- 0.02, so that they change the result; the keys' temperature ``tau`` at 1
+- 0.1; the router's carry ``g`` uniform in [0.3, 0.9]; its selection bias at
0.01, so that it changes choices; its last layer ``w_c`` at FOUR times the
fan-in scale, so that the chosen probability of a seeded router is 0.2-0.6 as
a trained top-1 router's is and not 1/16 (at the plain scale every expert's
part would be weighed by 0.06-0.08 and a wrong expert could not be told);
conv and router biases at 0.02; both convolutions at the fan-in of their
taps. Norm weights are ones. Router leaves are float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import weights as base

root_key = base.root_key
leaf_paths = base.leaf_paths

GROUPS = {"C": "cca_layers", "E": "expert_layers"}
TAPS = 2
RESIDUAL = {"res_a": "near_one", "res_b": 0.02, "res_c": "near_one",
            "res_e": 0.02}
ROUTER_OUT_SCALE = 4.0  # w_c, over its fan-in scale (see the docstring)


def tree_spec(cfg: dict) -> dict:
    """{path: (shape, dtype name, scale)}: ``scale`` a float (a normal draw
    at that scale), None (ones) or the name of a special draw ("near_one": 1
    + 0.1 N(0, 1); "carry": uniform [0.3, 0.9])."""
    D, V, L = cfg["dim"], cfg["vocab"], cfg["n_layers"]
    H, Hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    q, kv = H * hd, Hkv * hd
    E, Eh, F, R = (cfg["n_experts"], cfg["experts_held"], cfg["moe_dim"],
                   cfg["router_dim"])
    wd = cfg["dtype"]
    fan = D ** -0.5
    spec = {
        "embed": ((V, D), wd, 0.02),
        "final_norm": ((D,), "float32", None),
    }
    for g in GROUPS.values():
        spec[f"{g}/norm"] = ((L, D), "float32", None)
        for leaf, scale in RESIDUAL.items():
            spec[f"{g}/{leaf}"] = ((L, D), "float32", scale)
    g = "cca_layers"
    spec.update({
        f"{g}/w_qk": ((L, D, q + kv), wd, fan),
        f"{g}/w_v": ((L, D, kv), wd, fan),
        f"{g}/wo": ((L, q, D), wd, q ** -0.5),
        f"{g}/conv0_w": ((L, TAPS, q + kv), "float32", TAPS ** -0.5),
        f"{g}/conv0_b": ((L, q + kv), "float32", 0.02),
        f"{g}/conv1_w": ((L, TAPS, H + Hkv, hd, hd), wd,
                         (TAPS * hd) ** -0.5),
        f"{g}/conv1_b": ((L, q + kv), "float32", 0.02),
        f"{g}/tau": ((L, Hkv), "float32", "near_one"),
    })
    g, m = "expert_layers", "expert_layers/moe/router_mlp"
    spec.update({
        f"{m}/w_down": ((L, D, R), "float32", fan),
        f"{m}/b_down": ((L, R), "float32", 0.02),
        f"{m}/carry": ((L, R), "float32", "carry"),
        f"{m}/norm": ((L, R), "float32", None),
        f"{m}/w_a": ((L, R, R), "float32", R ** -0.5),
        f"{m}/b_a": ((L, R), "float32", 0.02),
        f"{m}/w_b": ((L, R, R), "float32", R ** -0.5),
        f"{m}/b_b": ((L, R), "float32", 0.02),
        f"{m}/w_c": ((L, R, E), "float32", ROUTER_OUT_SCALE * R ** -0.5),
        f"{m}/b_c": ((L, E), "float32", 0.02),
        f"{g}/moe/bias": ((L, E), "float32", 0.01),
        f"{g}/moe/w_gate": ((L, Eh, D, F), wd, fan),
        f"{g}/moe/w_up": ((L, Eh, D, F), wd, fan),
        f"{g}/moe/w_down": ((L, Eh, F, D), wd, F ** -0.5),
    })
    return spec


def _special(root, path: str, shape, kind: str):
    key = base._leaf_key(root, path)
    if kind == "near_one":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "carry":
        return jax.random.uniform(key, shape, jnp.float32, 0.3, 0.9)
    raise ValueError(kind)


def _lead(path: str, shape) -> int:
    # As weights._lead: slice over every axis but the last two of a stacked
    # leaf (layer; tap and head, or expert, where there are any).
    return (max(len(shape) - 2, 0)
            if path.split("/")[0] in GROUPS.values() else 0)


def _draw(root, path: str, spec, layer=None):
    """One leaf: whole, or its layer ``layer`` alone, equal bit for bit."""
    shape, dt, scale = spec
    if isinstance(scale, str):
        leaf = _special(root, path, shape, scale)
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
    return base._nest({path: _draw(root, path, spec)
                       for path, spec in tree_spec(cfg).items()})


def make_on_device(seed: int, cfg: dict, out_shardings=None):
    """One jitted call from the seed; nothing is drawn on the host."""
    fn = jax.jit(lambda root: make(root, cfg), out_shardings=out_shardings)
    return fn(root_key(seed))


def layer_slice(root, cfg: dict, group: str, layer) -> dict:
    """Block ``layer`` of group ``group``, equal bit for bit to the values
    ``make`` stacks."""
    flat = {path[len(group) + 1:]: _draw(root, path, spec, layer)
            for path, spec in tree_spec(cfg).items()
            if path.startswith(group + "/")}
    return base._nest(flat)


def tables(root, cfg: dict) -> dict:
    """The leaves outside the layer stacks (embed, final_norm)."""
    return {path: _draw(root, path, spec)
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
