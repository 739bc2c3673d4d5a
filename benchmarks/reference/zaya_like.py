"""Plain float32 reference for the zaya-like family (ZAYA1-8B): a layer is a
compressed-convolutional-attention (CCA) sublayer then an expert sublayer,
each joined to the stream by learned scales,

    x <- (a1 * x + b1) + (c1 * CCA(N(x; w1)) + e1)
    x <- (a2 * x + b2) + (c2 * MoE(N(x; w2), r_{l-1}) + e2)

with ``N(x; w) = x * rsqrt(mean(x^2) + eps) * w``; the head is the embedding
itself, ``logits = N(x; w_f) E^T``. Straightforward jax.numpy under
``jax.default_matmul_precision("highest")`` (and ``precision=HIGHEST`` on
every product); no cache, no pages, no tail, no batching, no grouped
product: a whole sequence at a time, the two convolutions written as their
sums over two positions of a sequence padded with one row of zeros, the
router's state handed from one layer's call to the next by the loop.

It imports nothing of the program and takes nothing the program made: the
weights are drawn again from the seed by ``benchmarks/weights_zaya.py`` at
the published widths, one sublayer at a time (every sampled request goes
through a sublayer before the next is drawn), and cast to float32 inside the
operations.

The equations (``cfg`` = the "model" group of a configuration file; ``h_t``
the normed input at position t, ``h_{-1}`` = 0; H query heads, Hkv key-value
heads of d, G = H / Hkv):

- CCA: ``p_t = [q~_t | k~_t] = h_t [W_q | W_k]`` (H d | Hkv d); ``u_t[j] =
  w0[0, j] p_{t-1}[j] + w0[1, j] p_t[j] + b0[j]`` (depthwise; ``p_{-1}`` =
  0); over the H + Hkv heads as groups of d channels ``z_t[g] = u_{t-1}[g]
  W1[0, g] + u_t[g] W1[1, g] + b1[g]`` (``u_{-1}`` = 0); the q-k mean from
  the PRE-conv latents: ``q_t[i] = z_t[i] + (q~_t[i] + k~_t[i // G]) / 2``,
  ``k_t[j] = z_t[H + j] + (mean_{i // G = j} q~_t[i] + k~_t[j]) / 2``; a
  head: ``q^ = q d^1/2 / sqrt(|q|^2 + eps)``, ``k^ = tau_j k d^1/2 /
  sqrt(|k|^2 + eps)``; rotary on the first ``rope_dim`` of a head's d in
  split-half pairs (i, i + rope_dim / 2), ``f_i = theta^(-2i / rope_dim)``,
  the rest untouched; ``v_t = [h_t W_v1 | h_{t-1} W_v2]``: the first half of
  the key-value heads holds this position's values, the second half those of
  the position before; causal softmax of ``q^_t[i] . k^_s[i // G] d^-1/2``
  over s <= t; ``out = o W_o``.
- Router (float32 whatever ``quant``): ``r_l = h W_d + b_d + g_l * r_{l-1}``
  (``r_{-1}`` = 0; ``r_l``, not ``h``, is what the next layer receives); ``s
  = W_c gelu(W_b gelu(W_a N(r_l; w_r) + b_a) + b_b) + b_c`` (tanh GELU); ``P
  = softmax(s)``; the expert is ``argmax(P + beta)`` (the k largest where k >
  1), its weight ``P`` of the chosen expert, NOT renormalised.
- Experts: ``P_e * (silu(h W1_e) * (h W3_e)) W2_e``, no shared expert. Of a
  held share (``experts_held`` from ``expert_first`` on) only the chosen
  experts that are held add their part. Each expert's tokens are picked BY
  INDEX on the host and go through that expert alone.

``quant=True`` is the CONTROL of the correctness check, never the reference:
every matmul of the linear layers (W_q | W_k, W_v, W_o, the experts' three,
the head) after the usual float8 recipe (``llama_like._mm8``); the
convolutions and the router stay float32. ``tail_dtype`` (tests) rounds what
a slot's tail would hold (``p``, ``u`` and ``h W_v2`` of every position, as
the next position reads them) to that type: float32 is the reference;
bfloat16 is what a program that kept its tail in the model's type computes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks import weights_zaya as weights
from benchmarks.reference.deepseek_like import Q_BLOCK, SEQ_BLOCK, _attention
from benchmarks.reference.llama_like import HI, _hashable, _mm

RESIDUAL = ("res_a", "res_b", "res_c", "res_e")


def _norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _join(x, out, w):
    """(a x + b) + (c out + e): the scaled residual."""
    a, b, c, e = (w[k].astype(jnp.float32) for k in RESIDUAL)
    return (a * x + b) + (c * out + e)


def _before(x):
    """x [T, ...] one position later, zeros before the sequence."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def _rope_first(x, rope_dim: int, theta: float):
    """x [T, H, d] at positions 0..T-1: the first ``rope_dim`` dims rotated
    in split-half pairs (i, i + rope_dim / 2), the rest as they are."""
    half = rope_dim // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rope_dim)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rope_dim], x[..., rope_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _unit(x, eps, hd):
    return x * hd ** 0.5 / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def cca_qkv(h, w, cfg, quant=False, tail_dtype=jnp.float32):
    """Steps 1-6 on the normed h [T, D]: (q^ [T, H, d], k^ [T, Hkv, d], v
    [T, Hkv, d]), rotated, before the softmax."""
    T = h.shape[0]
    H, Hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    G, eps = H // Hkv, cfg["rms_norm_eps"]
    kept = jnp.finfo(tail_dtype)

    def held(x):  # what the NEXT position reads of this one
        if kept.bits < 32:
            return lax.reduce_precision(x, kept.nexp, kept.nmant)
        return x

    p = _mm(h, w["w_qk"], quant)                                  # [T, 1280]
    w0 = w["conv0_w"].astype(jnp.float32)
    u = w0[0] * _before(held(p)) + w0[1] * p + w["conv0_b"]
    w1 = w["conv1_w"].astype(jnp.float32)                   # [2, H+Hkv, d, d]
    ug, ub = u.reshape(T, H + Hkv, hd), _before(held(u)).reshape(T, H + Hkv, hd)
    z = (jnp.einsum("tgc,gce->tge", ub, w1[0], precision=HI)
         + jnp.einsum("tgc,gce->tge", ug, w1[1], precision=HI)
         + w["conv1_b"].reshape(H + Hkv, hd))
    q0 = p[:, :H * hd].reshape(T, H, hd)
    k0 = p[:, H * hd:].reshape(T, Hkv, hd)
    mq = 0.5 * (q0 + jnp.repeat(k0, G, axis=1))
    mk = 0.5 * (q0.reshape(T, Hkv, G, hd).sum(axis=2) / G + k0)
    q = _unit(z[:, :H] + mq, eps, hd)
    k = _unit(z[:, H:] + mk, eps, hd) * w["tau"].astype(jnp.float32)[:, None]
    q = _rope_first(q, cfg["rope_dim"], cfg["rope_theta"])
    k = _rope_first(k, cfg["rope_dim"], cfg["rope_theta"])
    s = _mm(h, w["w_v"], quant)                                   # [T, 2 x 128]
    half = Hkv * hd // 2
    v = jnp.concatenate([s[:, :half], _before(held(s[:, half:]))], axis=-1)
    return q, k, v.reshape(T, Hkv, hd)


def cca_forward(x, w, cfg, quant=False, tail_dtype=jnp.float32):
    """x [T, D] float32 at positions 0..T-1 -> the stream after the CCA
    sublayer."""
    T = x.shape[0]
    H, Hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    h = _norm(x, w["norm"], cfg["rms_norm_eps"])
    q, k, v = cca_qkv(h, w, cfg, quant, tail_dtype)
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
    o = _attention(q, k, v, hd ** -0.5).reshape(T, H * hd)
    return _join(x, _mm(o, w["wo"], quant), w)


def route(h, m, bias, prev, cfg):
    """(chosen [T, k], weights [T, k], r_l [T, R]) from the normed h [T, D]
    and the state ``prev`` the layer before left; float32 throughout."""
    f32 = jnp.float32
    r = jnp.matmul(h, m["w_down"].astype(f32), precision=HI) + m["b_down"] \
        + m["carry"] * prev
    x = _norm(r, m["norm"], cfg["rms_norm_eps"])
    for wk, bk in (("w_a", "b_a"), ("w_b", "b_b")):
        x = jax.nn.gelu(jnp.matmul(x, m[wk].astype(f32), precision=HI)
                        + m[bk], approximate=True)
    s = jnp.matmul(x, m["w_c"].astype(f32), precision=HI) + m["b_c"]
    probs = jax.nn.softmax(s, axis=-1)
    _, chosen = lax.top_k(probs + bias, cfg["moe_top_k"])
    return chosen, jnp.take_along_axis(probs, chosen, axis=-1), r


def _swiglu(h, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, quant: bool, tail_dtype: str = "float32"):
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]

    @jax.jit
    def embed(root, tokens):
        return weights.tables(root, cfg)["embed"][tokens].astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=(1,))
    def draw(root, group, l):
        return weights.layer_slice(root, cfg, group, l)

    @jax.jit
    def attend(x, w):
        return cca_forward(x, w, cfg, quant, jnp.dtype(tail_dtype))

    @jax.jit
    def open_experts(x, w, prev):
        """The normed rows, their routing and the router's new state."""
        h = _norm(x, w["norm"], eps)
        chosen, weight, r = route(
            h, w["moe"]["router_mlp"], w["moe"]["bias"], prev, cfg)
        return h, chosen, weight, r

    @jax.jit
    def one_expert(out, h, ids, weight, wg, wu, wd):
        """``out[ids] += weight * E(h[ids])``; ``ids`` padded with T (read
        as zeros, dropped at the add)."""
        rows = jnp.take(h, ids, axis=0, mode="fill", fill_value=0.0)
        y = _swiglu(rows, wg, wu, wd, quant) * weight[:, None]
        return out.at[ids].add(y, mode="drop")

    @jax.jit
    def close_experts(x, out, w):
        return _join(x, out, w)

    @jax.jit
    def head(root, x, rows):
        t = weights.tables(root, cfg)
        return _mm(_norm(x[rows], t["final_norm"], eps), t["embed"].T, quant)

    return embed, draw, attend, open_experts, one_expert, close_experts, head


def _expert_ffn(x, w, prev, cfg, programs):
    """An expert sublayer on x [T, D] with the router's state ``prev`` [T,
    R]: (the stream after it, the state this layer leaves). A loop over the
    experts held, each given the rows that chose it, picked by index on the
    host."""
    _, _, _, open_experts, one_expert, close_experts, _ = programs
    h, chosen, weight, r = open_experts(x, w, prev)
    chosen, weight = np.asarray(chosen), np.asarray(weight)
    T = x.shape[0]
    m, out = w["moe"], jnp.zeros_like(x)
    first = cfg["expert_first"]
    for e in range(cfg["experts_held"]):
        rows, slot = np.nonzero(chosen == first + e)
        if not len(rows):
            continue
        n = 1 << max(int(len(rows) - 1).bit_length(), 3)  # few shapes
        ids = np.full((n,), T, np.int32)
        ids[: len(rows)] = rows
        wt = np.zeros((n,), np.float32)
        wt[: len(rows)] = weight[rows, slot]
        out = one_expert(out, h, jnp.asarray(ids), jnp.asarray(wt),
                         m["w_gate"][e], m["w_up"][e], m["w_down"][e])
    return close_experts(x, out, w), r


def _streams(seed: int, cfg: dict, sequences, quant: bool, tail_dtype: str):
    """The stream [T padded, D] of each sequence after the last layer.
    Sublayer-major: a sublayer's weights are drawn once and every sequence
    goes through it, each with the router state its own layer before left.
    A sequence is padded to whole blocks of positions; every sublayer is
    causal, so the pad moves no real position."""
    programs = _programs(_hashable(cfg), quant, tail_dtype)
    embed, draw, attend = programs[:3]
    root = weights.root_key(seed)
    block = min(SEQ_BLOCK, cfg["max_seq"])
    xs = []
    for tokens in sequences:
        pad = -(-len(tokens) // block) * block
        padded = np.zeros((pad,), np.int32)
        padded[: len(tokens)] = tokens
        xs.append(embed(root, jnp.asarray(padded)))
    states = [jnp.zeros((x.shape[0], cfg["router_dim"]), jnp.float32)
              for x in xs]
    for l in range(cfg["n_layers"]):
        w = draw(root, "cca_layers", l)
        for i, x in enumerate(xs):
            xs[i] = attend(x, w)
        w = draw(root, "expert_layers", l)
        for i, x in enumerate(xs):
            xs[i], states[i] = _expert_ffn(x, w, states[i], cfg, programs)
        del w
    return xs


def _head_blocks(seed: int, cfg: dict, x, rows, quant: bool):
    """The logits [Q_BLOCK, vocab] of ``x`` at ``rows``, a block of rows at a
    time with how many of them are real: at 262 272 rows of vocabulary the
    logits of a 12 288-token answer would be 12.9 GB at once."""
    head = _programs(_hashable(cfg), quant)[-1]
    root = weights.root_key(seed)
    for at in range(0, len(rows), Q_BLOCK):
        part = rows[at:at + Q_BLOCK]
        ids = np.zeros((Q_BLOCK,), np.int32)
        ids[: len(part)] = part
        yield head(root, x, jnp.asarray(ids)), len(part)


def logits_many(seed: int, cfg: dict, sequences, rows, quant: bool = False,
                tail_dtype: str = "float32"):
    """Float32 logits [len(rows[i]), vocab] of each sequence ``sequences[i]``
    (a list of token ids) at its positions ``rows[i]`` (each row's logits
    predict the NEXT token): for tests and scripts, a few rows."""
    with jax.default_matmul_precision("highest"):
        xs = _streams(seed, cfg, sequences, quant, tail_dtype)
        return [jnp.concatenate([lg[:n] for lg, n in _head_blocks(
            seed, cfg, x, np.asarray(r), quant)]) for x, r in zip(xs, rows)]


def served_gaps_many(seed: int, cfg: dict, sample, control: bool = False):
    """For each finished request (prompt, served) of ``sample``: how far
    each SERVED token's reference logit lies below the reference's best at
    that position (>= 0; 0 where the program served the reference's own
    arg-max). With ``control`` the judged token is instead the one the
    float8 control puts first at the same positions of the same sequence."""
    seqs = [list(p) + list(s[:-1]) for p, s in sample]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(s)) for p, s in sample]
    with jax.default_matmul_precision("highest"):
        judged = [np.asarray(s, np.int32) for _, s in sample]
        if control:
            judged = [
                np.concatenate([np.asarray(jnp.argmax(lg[:n], -1))
                                for lg, n in _head_blocks(seed, cfg, x, r, True)])
                for x, r in zip(_streams(seed, cfg, seqs, True, "float32"),
                                rows)]
        gaps = []
        for x, r, tok in zip(_streams(seed, cfg, seqs, False, "float32"),
                             rows, judged):
            part, at = [], 0
            for lg, n in _head_blocks(seed, cfg, x, r, False):
                got = jnp.take_along_axis(
                    lg[:n], jnp.asarray(tok[at:at + n])[:, None], axis=-1)[:, 0]
                part.append(np.asarray(jnp.max(lg[:n], axis=-1) - got,
                                       np.float64))
                at += n
            gaps.append(np.concatenate(part))
    return gaps
