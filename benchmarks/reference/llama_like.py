"""Plain float32 reference for the llama-like family (Mistral-7B dense,
Mixtral-8x7B experts): forward, next-token loss, gradients and AdamW, in
straightforward jax.numpy under ``precision=HIGHEST``.

It imports nothing of the program and takes nothing the program made: the
weights are drawn again from the seed by ``benchmarks/weights.py``, one
layer at a time, and cast to float32 inside the layer loop (a 16-layer
model never exists in float32 at once). No kernels, no cache, no batching
tricks: causal attention is the full masked softmax, computed in blocks of
query rows only so that the score matrix fits.

Follows the published architecture (RMSNorm eps from the config file, RoPE
in the split-half convention of the HF implementation, SwiGLU, GQA;
Mixtral: softmax over 8 router logits, top-2, renormalised). Stated
storage types are the configuration's: parameters and AdamW moments are
STORED in the dtype the configuration states (bfloat16), every operation
between two stores is float32.

``quant=True`` is the CONTROL of the correctness check, never the
reference: every matmul of the linear layers runs after the usual float8
recipe (operands rounded to e4m3 forward, the gradient to e5m2 backward,
per-tensor absmax scaling) — the nearest precision below the bfloat16 the
configurations state.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks import weights

HI = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_BLOCK = 512


def _round8(x, fmt, top):
    """Round to a float8 format with per-tensor absmax scaling."""
    s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(fmt).astype(jnp.float32) / s


@jax.custom_vjp
def _mm8(x, w):
    """The control's matmul, after the usual float8 recipe: operands in
    e4m3 forward, the incoming gradient in e5m2 backward, float32 sums."""
    return jnp.matmul(_round8(x, F8, F8_MAX), _round8(w, F8, F8_MAX),
                      precision=HI)


def _mm8_fwd(x, w):
    x8, w8 = _round8(x, F8, F8_MAX), _round8(w, F8, F8_MAX)
    return jnp.matmul(x8, w8, precision=HI), (x8, w8)


def _mm8_bwd(res, g):
    x8, w8 = res
    g8 = _round8(g, jnp.float8_e5m2, 57344.0)
    dx = jnp.matmul(g8, w8.T, precision=HI)
    dw = jnp.matmul(x8.reshape(-1, x8.shape[-1]).T,
                    g8.reshape(-1, g8.shape[-1]), precision=HI)
    return dx, dw


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def _mm(x, w, quant: bool):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if quant:
        return _mm8(x, w)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x [B, T, H, hd] at positions 0..T-1, split-half pairs."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Full causal softmax attention, [B, T, H, hd] with K/V already
    repeated to H heads; query rows in blocks so scores stay [.., blk, T]."""
    B, T, H, hd = q.shape
    blk = min(Q_BLOCK, T)
    assert T % blk == 0, (T, blk)
    cols = jnp.arange(T)

    @jax.checkpoint
    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HI) * hd ** -0.5
        rows = i * blk + jnp.arange(blk)
        s = jnp.where(rows[:, None] >= cols[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)

    out = lax.map(block, jnp.arange(T // blk))  # [n, B, blk, H, hd]
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, hd)


def _ffn(h, w, cfg, quant):
    if not cfg.get("n_experts"):
        g = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
        return _mm(g, w["w_down"], quant)
    m, k = w["moe"], cfg["moe_top_k"]
    probs = jax.nn.softmax(_mm(h, m["router"], False), axis=-1)  # [B,T,E]
    top, idx = lax.top_k(probs, k)
    gate = top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.sum(
        jax.nn.one_hot(idx, cfg["n_experts"], dtype=jnp.float32)
        * gate[..., None], axis=-2)  # [B,T,E], zero off the top-k

    def expert(carry, ew):
        wg, wu, wdn, col = ew
        y = _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wdn, quant)
        return carry + y * col[..., None], None

    out, _ = lax.scan(
        expert, jnp.zeros_like(h),
        (m["w_gate"], m["w_up"], m["w_down"], jnp.moveaxis(weight, -1, 0)))
    return out


def layer_forward(x, w, cfg, quant=False):
    """One decoder block on x [B, T, D] float32 at positions 0..T-1."""
    B, T, _ = x.shape
    H, KV, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    h = _rms(x, w["attn_norm"], eps)
    q = _rope(_mm(h, w["wq"], quant).reshape(B, T, H, hd), cfg["rope_theta"])
    k = _rope(_mm(h, w["wk"], quant).reshape(B, T, KV, hd), cfg["rope_theta"])
    v = _mm(h, w["wv"], quant).reshape(B, T, KV, hd)
    k, v = (jnp.repeat(a, H // KV, axis=2) for a in (k, v))
    attn = _attention(q, k, v).reshape(B, T, H * hd)
    x = x + _mm(attn, w["wo"], quant)
    return x + _ffn(_rms(x, w["mlp_norm"], eps), w, cfg, quant)


def _hashable(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


@functools.lru_cache(maxsize=None)
def _serve_programs(cfg_items, quant: bool):
    cfg = dict(cfg_items)

    @jax.jit
    def embed(root, tokens):
        return weights.tables(root, cfg)["embed"][tokens].astype(jnp.float32)

    @jax.jit
    def layer(root, x, l):
        return layer_forward(x, weights.layer_slice(root, cfg, l), cfg, quant)

    @jax.jit
    def head(root, x, rows):
        t = weights.tables(root, cfg)
        h = _rms(x[0, rows], t["final_norm"], cfg["rms_norm_eps"])
        return _mm(h, t["lm_head"], quant)

    return embed, layer, head


def serve_logits(seed: int, cfg: dict, tokens, rows, quant: bool = False):
    """Float32 logits [ROWS, vocab] of ONE sequence ``tokens`` at the
    positions ``rows`` (each row's logits predict the NEXT token; the
    caller keeps the first len(rows) of them). The sequence is padded with
    zeros to the configuration's ``max_seq`` and the rows to a multiple of
    the block, so that one compiled program serves every request of a
    cell; causal attention keeps the pad out of every real row."""
    embed, layer, head = _serve_programs(_hashable(cfg), quant)
    root = weights.root_key(seed)
    T = len(tokens)
    pad = -(-max(T, cfg["max_seq"]) // Q_BLOCK) * Q_BLOCK
    padded = np.zeros((1, pad), np.int32)
    padded[0, :T] = tokens
    n_rows = -(-len(rows) // Q_BLOCK) * Q_BLOCK
    row_ids = np.zeros((n_rows,), np.int32)
    row_ids[: len(rows)] = rows
    x = embed(root, jnp.asarray(padded))
    for l in range(cfg["n_layers"]):
        x = layer(root, x, jnp.int32(l))
    return head(root, x, jnp.asarray(row_ids))[: len(rows)]


def served_gaps(seed: int, cfg: dict, prompt, served, control: bool = False):
    """For one finished request: how far each SERVED token's reference
    logit lies below the reference's best at that position (>= 0; 0 where
    the program served the reference's own arg-max). With ``control`` the
    judged token is instead the one the float8 control puts first at the
    same positions of the same sequence (it need not decode)."""
    n = len(served)
    seq = list(prompt) + list(served[:-1])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    ref = serve_logits(seed, cfg, seq, rows, quant=False)
    if control:
        judged = jnp.argmax(serve_logits(seed, cfg, seq, rows, quant=True), -1)
    else:
        judged = jnp.asarray(np.asarray(served, np.int32))
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, judged[:, None], axis=-1)[:, 0]
    return np.asarray(best - got, np.float64)


# -- training ---------------------------------------------------------------


def lr_at(opt: dict, count: int) -> float:
    """optax.warmup_cosine_decay_schedule(0, lr, warmup, decay_steps,
    end=0.1*lr) at update number ``count`` (0 for the first)."""
    peak, warm = opt["lr"], opt["warmup_steps"]
    decay = max(opt["total_steps"], warm + 1)
    if count < warm:
        return peak * count / warm
    frac = min((count - warm) / (decay - warm), 1.0)
    end = 0.1 * peak
    return end + (peak - end) * 0.5 * (1.0 + math.cos(math.pi * frac))


def _adamw(p, g, mu, nu, scale, lr, t, opt):
    """One AdamW update of one leaf in float32; stores in the leaf's own
    dtype. ``g`` is the raw gradient, ``scale`` the global clip factor."""
    b1, b2 = opt["b1"], opt["b2"]
    pf, g = p.astype(jnp.float32), g * scale
    mu_n = b1 * mu.astype(jnp.float32) + (1 - b1) * g
    nu_n = b2 * nu.astype(jnp.float32) + (1 - b2) * g * g
    upd = (mu_n / (1 - b1 ** t)) / (jnp.sqrt(nu_n / (1 - b2 ** t)) + opt["eps"])
    new = pf - lr * (upd + opt["weight_decay"] * pf)
    return new.astype(p.dtype), mu_n.astype(p.dtype), nu_n.astype(p.dtype)


def _sq(tree):
    return jax.tree.map(lambda a: jnp.sum(jnp.square(a.astype(jnp.float32))),
                        tree)


def _flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


GRAD_SAMPLE = ("wk", "wv")  # the layer leaves whose first gradient is kept
# whole (the two smallest matrices; both lie behind the attention kernel)


def train_reference(seed: int, cfg: dict, opt: dict, batches,
                    quant: bool = False) -> dict:
    """Follow the first ``len(batches)`` training steps ([B, T+1] int32
    each) from the seeded weights. Returns per-step ``loss``, the per-leaf
    norm of the first gradient as the optimizer gets it (after the global
    clip) ``grad1``, and the per-leaf norm of the parameters' change after
    all steps ``delta``, and that first gradient itself for the leaves of
    ``GRAD_SAMPLE`` (``grad1_sample``, stacked over layers). Dense FFN only
    (the MoE auxiliary loss is not reproduced here)."""
    if cfg.get("n_experts"):
        raise NotImplementedError("train_reference covers the dense family")
    L, eps = cfg["n_layers"], cfg["rms_norm_eps"]
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa: E731

    fwd = jax.jit(lambda x, w: layer_forward(x, f32(w), cfg, quant))

    def head_loss(x, fn, lm, labels):
        logits = _mm(_rms(x, fn, eps), lm, quant)
        logz = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - lab)

    @jax.jit
    def head_vjp(x, fn, lm, labels):
        loss, vjp = jax.vjp(
            lambda x, fn, lm: head_loss(x, fn, lm, labels), x, f32(fn), f32(lm))
        return (loss,) + vjp(jnp.float32(1.0))

    @jax.jit
    def bwd_sq(x, w, dy):
        _, vjp = jax.vjp(lambda x, w: layer_forward(x, w, cfg, quant), x, f32(w))
        dx, dw = vjp(dy)
        return dx, _sq(dw)

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def bwd_update(x, w, mu, nu, dy, scale, lr, t):
        _, vjp = jax.vjp(lambda x, w: layer_forward(x, w, cfg, quant), x, f32(w))
        dx, dw = vjp(dy)
        new = jax.tree.map(
            lambda p, g, m, n: _adamw(p, g, m, n, scale, lr, t, opt),
            w, dw, mu, nu)
        pick = lambda i: jax.tree.map(  # noqa: E731
            lambda p, tup: tup[i], w, new)
        kept = {k: dw[k] * scale for k in GRAD_SAMPLE}
        return dx, pick(0), pick(1), pick(2), kept

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def leaf_update(p, g, mu, nu, scale, lr, t):
        return _adamw(p, g, mu, nu, scale, lr, t, opt)

    @jax.jit
    def embed_grad(dx, inputs):
        V, D = cfg["vocab"], cfg["dim"]
        return jnp.zeros((V, D), jnp.float32).at[inputs.reshape(-1)].add(
            dx.reshape(-1, D))

    root = weights.root_key(seed)
    tables_fn = jax.jit(lambda: weights.tables(root, cfg))
    tabs = tables_fn()
    slice_fn = jax.jit(lambda l: weights.layer_slice(root, cfg, l))
    layers = [slice_fn(jnp.int32(l)) for l in range(L)]
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)  # noqa: E731
    mu_l, nu_l = [zeros(w) for w in layers], [zeros(w) for w in layers]
    mu_t, nu_t = zeros(tabs), zeros(tabs)

    losses, grad1, sample = [], {}, {}
    for step, tokens in enumerate(batches):
        tokens = jnp.asarray(np.asarray(tokens, np.int32))
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        xs = [tabs["embed"][inputs].astype(jnp.float32)]
        for w in layers:
            xs.append(fwd(xs[-1], w))
        loss, dx_top, d_fn, d_lm = head_vjp(
            xs[-1], tabs["final_norm"], tabs["lm_head"], labels)
        losses.append(float(loss))
        # Sweep 1: the global norm the clip needs (gradients not kept).
        sq = {"final_norm": _sq(d_fn), "lm_head": _sq(d_lm)}
        dx = dx_top
        for l in reversed(range(L)):
            dx, s = bwd_sq(xs[l], layers[l], dx)
            for k, v in _flat(s).items():
                sq[f"layers/{k}"] = sq.get(f"layers/{k}", 0.0) + v
        d_embed = embed_grad(dx, inputs)
        sq["embed"] = _sq(d_embed)
        sq = {k: float(v) for k, v in sq.items()}
        gnorm = math.sqrt(sum(sq.values()))
        scale = min(1.0, opt["grad_clip"] / gnorm) if gnorm > 0 else 1.0
        if step == 0:
            grad1 = {k: math.sqrt(v) * scale for k, v in sq.items()}
        # Sweep 2: the same backward again, each layer updated in place.
        lr, t = lr_at(opt, step), step + 1
        args = (jnp.float32(scale), jnp.float32(lr), jnp.float32(t))
        dx = dx_top
        for l in reversed(range(L)):
            dx, layers[l], mu_l[l], nu_l[l], kept = bwd_update(
                xs[l], layers[l], mu_l[l], nu_l[l], dx, *args)
            if step == 0:
                sample[l] = {k: np.asarray(v) for k, v in kept.items()}
        for name, g in (("final_norm", d_fn), ("lm_head", d_lm),
                        ("embed", d_embed)):
            tabs[name], mu_t[name], nu_t[name] = leaf_update(
                tabs[name], g, mu_t[name], nu_t[name], *args)
        del xs, dx, dx_top, d_fn, d_lm, d_embed

    diff_sq = jax.jit(lambda a, b: _sq(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
    delta = {k: float(v) for k, v in diff_sq(
        tabs, tables_fn()).items()}
    for l in range(L):
        for k, v in _flat(diff_sq(layers[l], slice_fn(jnp.int32(l)))).items():
            delta[f"layers/{k}"] = delta.get(f"layers/{k}", 0.0) + float(v)
    return {"loss": losses, "grad1": grad1,
            "delta": {k: math.sqrt(v) for k, v in delta.items()},
            "grad1_sample": {f"layers/{k}": np.stack(
                [sample[l][k] for l in range(L)]) for k in GRAD_SAMPLE}}


def worst_leaf_difference(program: dict, reference: dict) -> float:
    """Worst sampled leaf of ||program - reference|| / ||reference||: unlike
    a gap between norms, this sees noise that leaves the norm alone."""
    return max(float(np.linalg.norm(program[k].astype(np.float64)
                                    - reference[k].astype(np.float64))
                     / np.linalg.norm(reference[k].astype(np.float64)))
               for k in reference)


def worst_leaf_gap(program: dict, reference: dict) -> float:
    """Worst leaf of |program norm - reference norm| over the larger of
    the reference's norm of that leaf and of the median leaf (some
    gradients are all but zero)."""
    floor = float(np.median(list(reference.values())))
    return max(abs(program[k] - reference[k]) / max(reference[k], floor, 1e-30)
               for k in reference)
