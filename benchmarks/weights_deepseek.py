"""Weights from a seed for the DeepSeek-V3-like family (latent attention,
leading dense layers, sigmoid-routed experts beside a shared one), on the
device, in the type they are served in: the sibling of ``weights.py``,
whose drawing machinery (one key a slice, no float32 copy of a stacked
leaf) it uses unchanged.

``cfg`` is the "model" group ``runners/serve_family.py`` makes of a
configuration file. Leaves are DRAWN in the published layout: the rope dims
of ``wq_b`` and ``wkv_a`` in interleaved pairs (2i, 2i+1), which is how the
reference rotates them. ``make`` hands the program the same values with
those columns permuted to the split-half order the program rotates in; the
dot product of a rotated query with a rotated key is the same either way.
The permutation is written out here, not imported from the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import weights as base

root_key = base.root_key
leaf_paths = base.leaf_paths

GROUPS = ("dense_layers", "layers")


def group_sizes(cfg: dict) -> dict:
    """Layers in each stacked group, in the order they run."""
    lead = min(cfg["n_dense_layers"], cfg["n_layers"])
    return {"dense_layers": lead, "layers": cfg["n_layers"] - lead}


def tree_spec(cfg: dict) -> dict:
    """{path: (shape, dtype name, scale or None)}; ``scale`` None is a leaf
    of ones (the norms). Scales are fan-in of the contraction; embedding
    0.02; the router's bias 0.01 so that it changes choices."""
    D, V, H = cfg["dim"], cfg["vocab"], cfg["n_heads"]
    ql, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    F, Fe, E = cfg["mlp_dim"], cfg["moe_dim"], cfg["n_experts"]
    Fs = cfg["n_shared"] * Fe
    wd = cfg["dtype"]
    fan = D ** -0.5
    spec = {
        "embed": ((V, D), wd, 0.02),
        "final_norm": ((D,), "float32", None),
        "lm_head": ((D, V), wd, fan),
    }
    for g, L in group_sizes(cfg).items():
        if not L:
            continue
        spec.update({
            f"{g}/attn_norm": ((L, D), "float32", None),
            f"{g}/mlp_norm": ((L, D), "float32", None),
            f"{g}/q_norm": ((L, ql), "float32", None),
            f"{g}/kv_norm": ((L, r), "float32", None),
            f"{g}/wq_a": ((L, D, ql), wd, fan),
            f"{g}/wq_b": ((L, ql, H * (nope + rope)), wd, ql ** -0.5),
            f"{g}/wkv_a": ((L, D, r + rope), wd, fan),
            f"{g}/wkv_b": ((L, r, H * (nope + v)), wd, r ** -0.5),
            f"{g}/wo": ((L, H * v, D), wd, (H * v) ** -0.5),
        })
        if g == "dense_layers":
            spec.update({
                f"{g}/w_gate": ((L, D, F), wd, fan),
                f"{g}/w_up": ((L, D, F), wd, fan),
                f"{g}/w_down": ((L, F, D), wd, F ** -0.5),
            })
        else:
            spec.update({
                f"{g}/moe/router": ((L, D, E), "float32", fan),
                f"{g}/moe/bias": ((L, E), "float32", 0.01),
                f"{g}/moe/w_gate": ((L, E, D, Fe), wd, fan),
                f"{g}/moe/w_up": ((L, E, D, Fe), wd, fan),
                f"{g}/moe/w_down": ((L, E, Fe, D), wd, Fe ** -0.5),
                f"{g}/moe/shared/w_gate": ((L, D, Fs), wd, fan),
                f"{g}/moe/shared/w_up": ((L, D, Fs), wd, fan),
                f"{g}/moe/shared/w_down": ((L, Fs, D), wd, Fs ** -0.5),
            })
    return spec


def _lead(path: str, shape) -> int:
    # As weights._lead: slice over every axis but the last two of a
    # stacked leaf (layer, and expert where there is one).
    return max(len(shape) - 2, 0) if path.split("/")[0] in GROUPS else 0


def _split_half(n: int):
    """Interleaved pairs (2i, 2i+1) -> split-half order [evens | odds]."""
    return jnp.concatenate([jnp.arange(0, n, 2), jnp.arange(1, n, 2)])


def to_program_layout(path: str, leaf, cfg: dict):
    """The rope columns of ``wq_b`` (per head) and ``wkv_a`` permuted from
    the published interleaved order to split-half; every other leaf as
    drawn."""
    name = path.rsplit("/", 1)[-1]
    nope, rope, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["kv_lora_rank"])
    if name == "wq_b":
        head = jnp.concatenate([jnp.arange(nope), nope + _split_half(rope)])
        cols = (jnp.arange(cfg["n_heads"])[:, None] * (nope + rope)
                + head[None, :]).reshape(-1)
        return leaf[..., cols]
    if name == "wkv_a":
        return leaf[..., jnp.concatenate([jnp.arange(r), r + _split_half(rope)])]
    return leaf


def make(root, cfg: dict) -> dict:
    """The whole parameter tree AS THE PROGRAM HOLDS IT (trace this under
    one ``jax.jit``)."""
    return base._nest({
        path: to_program_layout(
            path, base._leaf(root, path, shape, dt, scale, _lead(path, shape)),
            cfg)
        for path, (shape, dt, scale) in tree_spec(cfg).items()})


def make_on_device(seed: int, cfg: dict, out_shardings=None):
    """One jitted call from the seed; nothing is drawn on the host."""
    fn = jax.jit(lambda root: make(root, cfg), out_shardings=out_shardings)
    return fn(root_key(seed))


def layer_slice(root, cfg: dict, group: str, layer) -> dict:
    """Layer ``layer`` of group ``group``, every leaf AS PUBLISHED (rope
    dims interleaved), equal bit for bit to the values ``make`` permutes."""
    flat = {}
    for path, (shape, dt, scale) in tree_spec(cfg).items():
        if path.startswith(group + "/"):
            lead = _lead(path, shape)
            leaf = (base._leaf(root, path, shape, dt, scale, lead, index=layer)
                    if lead or scale is None
                    else base._leaf(root, path, shape, dt, scale, 0)[layer])
            flat[path[len(group) + 1:]] = leaf
    return base._nest(flat)


def tables(root, cfg: dict) -> dict:
    """The leaves outside the layer stack (embed, final_norm, lm_head)."""
    return {path: base._leaf(root, path, shape, dt, scale, 0)
            for path, (shape, dt, scale) in tree_spec(cfg).items()
            if path.split("/")[0] not in GROUPS}


def check_against_program(cfg: dict, program_shapes) -> None:
    """The tree this file draws must be the tree the program initialises:
    same paths, shapes and types."""
    want = {name: (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
            for name, leaf in leaf_paths(program_shapes)}
    have = {p: (tuple(s), jnp.dtype(d).name)
            for p, (s, d, _) in tree_spec(cfg).items()}
    if want != have:
        diff = sorted(set(want.items()) ^ set(have.items()))
        raise SystemExit(f"benchmark weights do not match the program's "
                         f"parameter tree: {diff[:6]}")
