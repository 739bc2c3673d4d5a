"""Weights from a seed, on the device, in the type they are served in.

The benchmark owns the weights: the program (engine or trainer) is handed
them, and the plain reference makes the same values again from the same
seed, one layer at a time, without ever touching an array the program held.

Every leaf is drawn slice by slice over its leading (layer, expert) axes,
each slice from its own key, so that (a) no float32 copy of a stacked
[L, 4096, 14336] or [L, 8, 4096, 14336] leaf ever exists — the draw of one
[4096, 14336] slice is the largest temporary — and (b) ``layer_slice`` can
rebuild layer ``l`` alone, bit for bit. Scales are those of
``oim_tpu.models.llama.init`` / ``moe.init`` (fan-in of the contraction).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
from jax import lax


def tree_spec(cfg: dict) -> dict:
    """{path: (shape, dtype name, scale or None)} for a llama-like config
    (``cfg`` = the "model" group of a configuration file). ``scale`` None
    means a leaf of ones (the norms). Paths are '/'-joined tree keys."""
    L, D = cfg["n_layers"], cfg["dim"]
    q = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    F, V, E = cfg["mlp_dim"], cfg["vocab"], cfg.get("n_experts", 0)
    wd = cfg["dtype"]
    fan = D ** -0.5
    spec = {
        "embed": ((V, D), wd, 0.02),
        "final_norm": ((D,), "float32", None),
        "lm_head": ((D, V), wd, fan),
        "layers/attn_norm": ((L, D), "float32", None),
        "layers/mlp_norm": ((L, D), "float32", None),
        "layers/wq": ((L, D, q), wd, fan),
        "layers/wk": ((L, D, kv), wd, fan),
        "layers/wv": ((L, D, kv), wd, fan),
        "layers/wo": ((L, q, D), wd, q ** -0.5),
    }
    if E:
        spec.update({
            "layers/moe/router": ((L, D, E), "float32", fan),
            "layers/moe/w_gate": ((L, E, D, F), wd, fan),
            "layers/moe/w_up": ((L, E, D, F), wd, fan),
            "layers/moe/w_down": ((L, E, F, D), wd, F ** -0.5),
        })
    else:
        spec.update({
            "layers/w_gate": ((L, D, F), wd, fan),
            "layers/w_up": ((L, D, F), wd, fan),
            "layers/w_down": ((L, F, D), wd, F ** -0.5),
        })
    return spec


def root_key(seed: int):
    """The key everything is drawn from. Any whole number up to a little
    over 2**31 is a legal seed: fold it in two 31-bit halves. Functions
    below take this KEY (an array), so one compiled program serves every
    seed."""
    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    return jax.random.fold_in(key, (int(seed) >> 31) & 0x7FFFFFFF)


def _leaf_key(root, path: str):
    # The path is folded by a stable hash (not Python's salted one).
    return jax.random.fold_in(root, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(key, shape, dtype, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _leaf(root, path: str, shape, dtype, scale, lead: int, index=None):
    """One leaf; ``lead`` leading axes are sliced (one key per slice).
    ``index`` picks slice(s) of the FIRST axis only (a layer)."""
    dtype = jnp.dtype(dtype)
    if scale is None:
        full = jnp.ones(shape, dtype)
        return full if index is None else full[index]
    key = _leaf_key(root, path)
    n = 1
    for d in shape[:lead]:
        n *= d
    if lead == 0:
        return _draw(key, shape, dtype, scale)
    per = n // shape[0]  # slices per first-axis entry
    ids = jnp.arange(n) if index is None else index * per + jnp.arange(per)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(ids)
    rest = tuple(shape[lead:])
    out = lax.map(lambda k: _draw(k, rest, dtype, scale), keys)
    lead_shape = shape[:lead] if index is None else shape[1:lead]
    return out.reshape(tuple(lead_shape) + rest)


def _lead(path: str, shape) -> int:
    # Slice over every axis but the last two (the matrix itself): layer,
    # and expert where there is one. Tables and vectors are drawn whole.
    return max(len(shape) - 2, 0) if path.startswith("layers/") else 0


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def make(root, cfg: dict) -> dict:
    """The whole parameter tree (trace this under one ``jax.jit``)."""
    return _nest({
        path: _leaf(root, path, shape, dt, scale, _lead(path, shape))
        for path, (shape, dt, scale) in tree_spec(cfg).items()})


def make_on_device(seed: int, cfg: dict, out_shardings=None):
    """One jitted call from the seed; nothing is drawn on the host."""
    fn = jax.jit(lambda root: make(root, cfg), out_shardings=out_shardings)
    return fn(root_key(seed))


def layer_slice(root, cfg: dict, layer) -> dict:
    """Layer ``layer`` (traced or static int) of every stacked leaf, equal
    bit for bit to ``make(...)["layers"][...][layer]``."""
    flat = {}
    for path, (shape, dt, scale) in tree_spec(cfg).items():
        if path.startswith("layers/"):
            flat[path[len("layers/"):]] = _leaf(
                root, path, shape, dt, scale, _lead(path, shape), index=layer)
    return _nest(flat)


def tables(root, cfg: dict) -> dict:
    """The leaves outside the layer stack (embed, final_norm, lm_head)."""
    return {
        path: _leaf(root, path, shape, dt, scale, 0)
        for path, (shape, dt, scale) in tree_spec(cfg).items()
        if not path.startswith("layers/")}


def leaf_paths(tree) -> list:
    """[('/'-joined path, leaf), ...] of a parameter tree."""
    return [("/".join(str(getattr(k, "key", k)) for k in path), leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


def check_against_program(cfg: dict, program_shapes) -> None:
    """The tree this file draws must be the tree the program initialises
    (``jax.eval_shape(llama.init, ...)``): same paths, shapes and types. A
    refactor of the model's parameters then fails here, loudly, and not as
    a silent mismatch inside the engine."""
    want = {name: (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
            for name, leaf in leaf_paths(program_shapes)}
    have = {p: (tuple(s), jnp.dtype(d).name)
            for p, (s, d, _) in tree_spec(cfg).items()}
    if want != have:
        diff = sorted(set(want.items()) ^ set(have.items()))
        raise SystemExit(f"benchmark weights do not match the program's "
                         f"parameter tree: {diff[:6]}")
