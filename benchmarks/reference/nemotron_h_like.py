"""Plain float32 reference for the nemotron_h-like family
(NVIDIA-Nemotron-3-Nano-30B-A3B): a pattern of Mamba-2 mixers ("M"),
sigmoid-routed squared-ReLU experts beside a shared one ("E") and GQA
attention without a rotary embedding ("*"), each block ``x + mixer(RMSNorm
(x))``. Straightforward jax.numpy under ``precision=HIGHEST``; no cache, no
batching, no grouped product, and the state-space recurrence as a
SEQUENTIAL ``lax.scan`` over time: the definition, not the chunked
algorithm the program runs.

It imports nothing of the program and takes nothing the program made: the
weights are drawn again from the seed by ``benchmarks/weights_nemotron_h.py``
at the published widths, one layer at a time (every sampled request goes
through a layer before the next is drawn), and cast to float32 inside the
operations.

The equations (``cfg`` = the "model" group of a configuration file; eps =
``rms_norm_eps``):

- Mamba-2 mixer: ``[z | xBC | dt] = h W_in``; ``xBC_t <- silu(b + sum_j w_j
  xBC_{t-(K-1)+j})`` (depthwise, causal, zeros before the sequence); split
  into x [H, P], B [G, N], C [G, N] (head h uses group h // (H/G));
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``s_t = exp(dt_t A)
  s_{t-1} + dt_t x_t (outer) B_t`` from ``s = 0``; ``y_t = s_t C_t + D
  x_t``; ``y <- RMSNorm over each of the G groups of (y * silu(z))`` with
  one weight of width H x P; out = ``y W_out``.
- experts: ``s = sigmoid(h W_r)`` in float32 over ALL ``n_experts``; chosen
  = top-k of ``s + b``; ``w = s[chosen] / (sum s[chosen] + 1e-20) *
  routed_scale``; expert e: ``relu(h U_e)^2 V_e``. Of a held share
  (``experts_held`` from ``expert_first`` on) only the chosen experts that
  are held add their part; what the absent ranks would add is left out,
  as in the program. Plus the shared expert, of the same form at its own
  width, for every token. Each expert's tokens are picked BY INDEX on the
  host and go through that expert alone.
- attention: GQA, ``n_kv_heads`` key/value heads repeated to ``n_heads``,
  causal softmax at scale head_dim^-0.5, NO rotary embedding (the
  configuration's ``assumed`` says why), no bias.

``quant=True`` is the CONTROL of the correctness check, never the
reference: every matmul of the linear layers after the usual float8 recipe
(``llama_like._mm8``); the router and the recurrence stay float32, as in
the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks import weights_nemotron_h as weights
from benchmarks.reference.deepseek_like import Q_BLOCK, SEQ_BLOCK, _attention
from benchmarks.reference.llama_like import _hashable, _mm, _rms


def mamba_forward(x, w, cfg, quant=False):
    """x [T, D] float32 from an empty state -> x + mixer."""
    T = x.shape[0]
    H, P, G, N, K = (cfg["mamba_heads"], cfg["mamba_head_dim"],
                     cfg["ssm_groups"], cfg["ssm_state"], cfg["conv_kernel"])
    inner, eps = H * P, cfg["rms_norm_eps"]
    conv_dim = inner + 2 * G * N
    p = _mm(_rms(x, w["norm"], eps), w["w_in"], quant)
    z, xbc, dt = (p[:, :inner], p[:, inner:inner + conv_dim],
                  p[:, inner + conv_dim:])
    padded = jnp.concatenate([jnp.zeros((K - 1, conv_dim), jnp.float32), xbc])
    cw = w["conv_w"].astype(jnp.float32)
    xbc = jax.nn.silu(w["conv_b"].astype(jnp.float32) + sum(
        cw[j] * padded[j:j + T] for j in range(K)))
    xs = xbc[:, :inner].reshape(T, H, P)
    bm = jnp.repeat(xbc[:, inner:inner + G * N].reshape(T, G, N), H // G, axis=1)
    cm = jnp.repeat(xbc[:, inner + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])             # [T, H]
    a = -jnp.exp(w["A_log"])                             # [H]

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    # (unroll: the same steps in the same order, fewer trips of the loop)
    _, y = lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (xs, bm, cm, dt),
                    unroll=8)
    y = (y + w["D"][None, :, None] * xs).reshape(T, inner) * jax.nn.silu(z)
    y = y.reshape(T, G, inner // G)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(T, inner) * w["gate_norm"].astype(jnp.float32)
    return x + _mm(y, w["w_out"], quant)


def attention_forward(x, w, cfg, quant=False):
    """x [T, D] float32 at positions 0..T-1 -> x + attention."""
    T = x.shape[0]
    H, KV, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    if cfg["attn_rope"]:
        raise SystemExit("the nemotron_h-like reference rotates nothing")
    h = _rms(x, w["norm"], cfg["rms_norm_eps"])
    q = _mm(h, w["wq"], quant).reshape(T, H, hd)
    k = jnp.repeat(_mm(h, w["wk"], quant).reshape(T, KV, hd), H // KV, axis=1)
    v = jnp.repeat(_mm(h, w["wv"], quant).reshape(T, KV, hd), H // KV, axis=1)
    o = _attention(q, k, v, hd ** -0.5)
    return x + _mm(o.reshape(T, H * hd), w["wo"], quant)


def _relu2(h, wu, wd, quant):
    return _mm(jnp.square(jax.nn.relu(_mm(h, wu, quant))), wd, quant)


def route(h, m, cfg):
    """(experts [T, k] int32, weights [T, k] f32) of the normed rows h,
    over every expert of the layer."""
    s = jax.nn.sigmoid(_mm(h, m["router"], False))
    _, chosen = lax.top_k(s + m["bias"].astype(jnp.float32), cfg["moe_top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg["routed_scale"]


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, quant: bool):
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]

    @jax.jit
    def embed(root, tokens):
        return weights.tables(root, cfg)["embed"][tokens].astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=(1,))
    def draw(root, group, l):
        return weights.layer_slice(root, cfg, group, l)

    @jax.jit
    def mamba(x, w):
        return mamba_forward(x, w, cfg, quant)

    @jax.jit
    def attend(x, w):
        return attention_forward(x, w, cfg, quant)

    @jax.jit
    def open_experts(x, w):
        """The normed rows, their routing, and the shared expert's part."""
        h = _rms(x, w["norm"], eps)
        chosen, weight = route(h, w["moe"], cfg)
        s = w["moe"]["shared"]
        return h, chosen, weight, x + _relu2(h, s["w_up"], s["w_down"], quant)

    @jax.jit
    def one_expert(out, h, ids, weight, wu, wd):
        """``out[ids] += weight * E(h[ids])``; ``ids`` padded with T (read
        as zeros, dropped at the add)."""
        rows = jnp.take(h, ids, axis=0, mode="fill", fill_value=0.0)
        y = _relu2(rows, wu, wd, quant) * weight[:, None]
        return out.at[ids].add(y, mode="drop")

    @jax.jit
    def head(root, x, rows):
        t = weights.tables(root, cfg)
        return _mm(_rms(x[rows], t["final_norm"], eps), t["lm_head"], quant)

    return embed, draw, mamba, attend, open_experts, one_expert, head


def _expert_ffn(x, w, cfg, open_experts, one_expert):
    """An expert layer on x [T, D]: a loop over the experts held, each
    given the rows that chose it, picked by index on the host."""
    h, chosen, weight, out = open_experts(x, w)
    chosen, weight = np.asarray(chosen), np.asarray(weight)
    T = x.shape[0]
    m = w["moe"]
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
                         m["w_up"][e], m["w_down"][e])
    return out


def _layers(cfg: dict):
    """(kind, group, index within the group) of each layer, in order."""
    at = dict.fromkeys(weights.GROUPS, 0)
    for kind in cfg["pattern"]:
        yield kind, weights.GROUPS[kind], at[kind]
        at[kind] += 1


def logits_many(seed: int, cfg: dict, sequences, rows, quant: bool = False):
    """Float32 logits [len(rows[i]), vocab] of each sequence ``sequences[i]``
    (a list of token ids) at its positions ``rows[i]`` (each row's logits
    predict the NEXT token). Layer-major: a layer's weights are drawn once
    and every sequence goes through it. A sequence is padded to whole
    blocks; every mixer is causal, so the pad moves no real position."""
    embed, draw, mamba, attend, open_experts, one_expert, head = \
        _programs(_hashable(cfg), quant)
    root = weights.root_key(seed)
    block = min(SEQ_BLOCK, cfg["max_seq"])
    xs = []
    for tokens in sequences:
        pad = -(-len(tokens) // block) * block
        padded = np.zeros((pad,), np.int32)
        padded[: len(tokens)] = tokens
        xs.append(embed(root, jnp.asarray(padded)))
    for kind, group, l in _layers(cfg):
        w = draw(root, group, l)
        for i, x in enumerate(xs):
            if kind == "M":
                xs[i] = mamba(x, w)
            elif kind == "*":
                xs[i] = attend(x, w)
            else:
                xs[i] = _expert_ffn(x, w, cfg, open_experts, one_expert)
        del w
    out = []
    for x, r in zip(xs, rows):
        n_rows = -(-len(r) // Q_BLOCK) * Q_BLOCK
        ids = np.zeros((n_rows,), np.int32)
        ids[: len(r)] = r
        out.append(head(root, x, jnp.asarray(ids))[: len(r)])
    return out


def layer_forward(x, w, cfg, kind: str):
    """One block of ``kind`` on x [T, D] float32 (tests)."""
    _, _, mamba, attend, open_experts, one_expert, _ = _programs(
        _hashable(cfg), False)
    if kind == "M":
        return mamba(x, w)
    if kind == "*":
        return attend(x, w)
    return _expert_ffn(x, w, cfg, open_experts, one_expert)


def served_gaps_many(seed: int, cfg: dict, sample, control: bool = False):
    """For each finished request (prompt, served) of ``sample``: how far
    each SERVED token's reference logit lies below the reference's best at
    that position (>= 0; 0 where the program served the reference's own
    arg-max). With ``control`` the judged token is instead the one the
    float8 control puts first at the same positions of the same sequence."""
    seqs = [list(p) + list(s[:-1]) for p, s in sample]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(s)) for p, s in sample]
    ref = logits_many(seed, cfg, seqs, rows, quant=False)
    if control:
        judged = [jnp.argmax(lg, -1)
                  for lg in logits_many(seed, cfg, seqs, rows, quant=True)]
    else:
        judged = [jnp.asarray(np.asarray(s, np.int32)) for _, s in sample]
    gaps = []
    for lg, tok in zip(ref, judged):
        got = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(jnp.max(lg, axis=-1) - got, np.float64))
    return gaps
