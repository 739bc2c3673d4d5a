"""Plain float32 reference for the solar_open2-like family
(Solar-Open2-250B): published layer i is ``x + mixer_i(RMSNorm(x))`` then
``x + experts(RMSNorm(x))``; the mixer is gated GQA attention without a
rotary embedding ("*") or a KDA mixer ("K": a gated delta rule with one
decay a channel), the expert block ("E") sigmoid-routed SwiGLU experts
beside a shared one. Straightforward jax.numpy under ``precision=HIGHEST``;
no cache, no batching, no chunks, no grouped product, and the recurrence as
a SEQUENTIAL ``lax.scan`` over positions: the definition, not the chunked
algorithm the program runs.

It imports nothing of the program and takes nothing the program made: the
weights are drawn again from the seed by ``benchmarks/weights_solar_open2.py``
at the published widths, one block at a time (every sampled request goes
through a block before the next is drawn), and cast to float32 inside the
operations.

The equations (``cfg`` = the "model" group of a configuration file; eps =
``rms_norm_eps``; H heads of width d):

- KDA: ``[q | k | v] = h W_qkv``; each ``<- silu(sum_j w_j [.]_{t-(K-1)+j})``
  (depthwise, causal, zeros before the sequence, no bias); a head:
  ``q <- q / sqrt(|q|^2 + 1e-6) * d^-1/2``, ``k <- k / sqrt(|k|^2 + 1e-6)``;
  ``g_t = -exp(A_log_h) softplus(W_f2 (W_f1 h_t) + dt_bias)`` [H, d];
  ``beta_t = 2 sigmoid(h_t W_beta)`` [H] (1 x without negative
  eigenvalues); from ``S = 0``: ``S <- exp(g_t) * S`` (a row a channel),
  ``S <- S + beta_t k_t (v_t - S^T k_t)^T``, ``o_t = S^T q_t``; out =
  ``[RMSNorm_head(o_t) * sigmoid(W_g2 (W_g1 h_t) + b_g)] W_out``.
- attention: GQA, ``n_kv_heads`` key/value heads repeated to ``n_heads``,
  causal softmax at scale head_dim^-0.5, NO rotary embedding, no q/k norm,
  no bias; ``out = [attn * sigmoid(h W_g)] W_o`` (one gate an element).
- experts: ``s = sigmoid(h W_r)`` in float32 over ALL ``n_experts``; chosen
  = top-k of ``s + b``; ``w = s[chosen] / (sum s[chosen] + 1e-20) *
  routed_scale``; expert e: ``(silu(h G_e) * h U_e) V_e``. Of a held share
  (``experts_held`` from ``expert_first`` on) only the chosen experts that
  are held add their part; what the absent ranks would add is left out, as
  in the program. Plus the shared expert, of the same form, for every
  token. Each expert's tokens are picked BY INDEX on the host and go through
  that expert alone.

``quant=True`` is the CONTROL of the correctness check, never the
reference: every matmul of the linear layers after the usual float8 recipe
(``llama_like._mm8``); the router, the low-rank gates' second halves'
nonlinearities and the recurrence stay float32. ``state_dtype`` (tests) is
the type the recurrent state is ROUNDED to after every position: float32 is
the reference; bfloat16 is what a program that kept its state in the
model's type would compute.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks import weights_solar_open2 as weights
from benchmarks.reference.deepseek_like import Q_BLOCK, SEQ_BLOCK, _attention
from benchmarks.reference.llama_like import _hashable, _mm, _rms
from benchmarks.reference.nemotron_h_like import route  # the same router

QK_EPS = 1e-6


def kda_forward(x, w, cfg, quant=False, state_dtype=jnp.float32):
    """x [T, D] float32 from an empty state -> x + mixer."""
    T = x.shape[0]
    H, d, K = cfg["kda_heads"], cfg["kda_head_dim"], cfg["kda_conv"]
    inner, eps = H * d, cfg["rms_norm_eps"]
    h = _rms(x, w["norm"], eps)
    qkv = _mm(h, w["w_qkv"], quant)
    padded = jnp.concatenate([jnp.zeros((K - 1, 3 * inner), jnp.float32), qkv])
    cw = w["conv_w"].astype(jnp.float32)
    qkv = jax.nn.silu(sum(cw[j] * padded[j:j + T] for j in range(K)))
    q, k, v = (a.reshape(T, H, d) for a in jnp.split(qkv, 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + QK_EPS) * d ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + QK_EPS)
    f = _mm(_mm(h, w["w_f1"], quant), w["w_f2"], quant) + w["dt_bias"]
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(f).reshape(T, H, d)
    beta = jax.nn.sigmoid(_mm(h, w["w_beta"], quant))            # [T, H]
    if cfg["neg_eigval"]:
        beta = 2.0 * beta
    gate = (_mm(_mm(h, w["w_g1"], quant), w["w_g2"], quant)
            + w["g_bias"]).reshape(T, H, d)

    # (reduce_precision, not a pair of casts: a TPU's compiler folds a cast
    # to bfloat16 and back into nothing)
    kept = jnp.finfo(state_dtype)

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, :, None] * s                         # [H, d, d]
        u = b_t[:, None] * (v_t - jnp.sum(k_t[:, :, None] * s, axis=1))
        s = s + k_t[:, :, None] * u[:, None, :]
        if kept.bits < 32:  # float32: the reference, nothing to round
            s = lax.reduce_precision(s, kept.nexp, kept.nmant)
        return s, jnp.sum(q_t[:, :, None] * s, axis=1)

    # (unroll: the same steps in the same order, fewer trips of the loop)
    _, o = lax.scan(step, jnp.zeros((H, d, d), jnp.float32),
                    (q, k, v, g, beta), unroll=8)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * w["o_norm"].astype(jnp.float32) * jax.nn.sigmoid(gate)
    return x + _mm(o.reshape(T, inner), w["w_out"], quant)


def attention_forward(x, w, cfg, quant=False):
    """x [T, D] float32 at positions 0..T-1 -> x + gated attention."""
    T = x.shape[0]
    H, KV, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    if cfg["attn_rope"]:
        raise SystemExit("the solar_open2-like reference rotates nothing")
    h = _rms(x, w["norm"], cfg["rms_norm_eps"])
    q = _mm(h, w["wq"], quant).reshape(T, H, hd)
    k = jnp.repeat(_mm(h, w["wk"], quant).reshape(T, KV, hd), H // KV, axis=1)
    v = jnp.repeat(_mm(h, w["wv"], quant).reshape(T, KV, hd), H // KV, axis=1)
    o = _attention(q, k, v, hd ** -0.5).reshape(T, H * hd)
    if cfg["gqa_gate"]:
        o = o * jax.nn.sigmoid(_mm(h, w["wg"], quant))
    return x + _mm(o, w["wo"], quant)


def _swiglu(h, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, quant: bool, state_dtype: str = "float32"):
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]

    @jax.jit
    def embed(root, tokens):
        return weights.tables(root, cfg)["embed"][tokens].astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=(1,))
    def draw(root, group, l):
        return weights.layer_slice(root, cfg, group, l)

    @jax.jit
    def kda(x, w):
        return kda_forward(x, w, cfg, quant, jnp.dtype(state_dtype))

    @jax.jit
    def attend(x, w):
        return attention_forward(x, w, cfg, quant)

    @jax.jit
    def open_experts(x, w):
        """The normed rows, their routing, and the shared expert's part."""
        h = _rms(x, w["norm"], eps)
        chosen, weight = route(h, w["moe"], cfg)
        s = w["moe"]["shared"]
        return h, chosen, weight, x + _swiglu(
            h, s["w_gate"], s["w_up"], s["w_down"], quant)

    @jax.jit
    def one_expert(out, h, ids, weight, wg, wu, wd):
        """``out[ids] += weight * E(h[ids])``; ``ids`` padded with T (read
        as zeros, dropped at the add)."""
        rows = jnp.take(h, ids, axis=0, mode="fill", fill_value=0.0)
        y = _swiglu(rows, wg, wu, wd, quant) * weight[:, None]
        return out.at[ids].add(y, mode="drop")

    @jax.jit
    def head(root, x, rows):
        t = weights.tables(root, cfg)
        return _mm(_rms(x[rows], t["final_norm"], eps), t["lm_head"], quant)

    return embed, draw, kda, attend, open_experts, one_expert, head


def _expert_ffn(x, w, cfg, open_experts, one_expert):
    """An expert block on x [T, D]: a loop over the experts held, each
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
                         m["w_gate"][e], m["w_up"][e], m["w_down"][e])
    return out


def _blocks(cfg: dict):
    """(kind, group, index within the group) of each block, in order."""
    at = dict.fromkeys(weights.GROUPS, 0)
    for kind in cfg["pattern"]:
        yield kind, weights.GROUPS[kind], at[kind]
        at[kind] += 1


def logits_many(seed: int, cfg: dict, sequences, rows, quant: bool = False,
                state_dtype: str = "float32"):
    """Float32 logits [len(rows[i]), vocab] of each sequence ``sequences[i]``
    (a list of token ids) at its positions ``rows[i]`` (each row's logits
    predict the NEXT token). Block-major: a block's weights are drawn once
    and every sequence goes through it. A sequence is padded to whole
    blocks of positions; every mixer is causal, so the pad moves no real
    position."""
    embed, draw, kda, attend, open_experts, one_expert, head = \
        _programs(_hashable(cfg), quant, state_dtype)
    root = weights.root_key(seed)
    block = min(SEQ_BLOCK, cfg["max_seq"])
    xs = []
    for tokens in sequences:
        pad = -(-len(tokens) // block) * block
        padded = np.zeros((pad,), np.int32)
        padded[: len(tokens)] = tokens
        xs.append(embed(root, jnp.asarray(padded)))
    for kind, group, l in _blocks(cfg):
        w = draw(root, group, l)
        for i, x in enumerate(xs):
            if kind == "K":
                xs[i] = kda(x, w)
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
    _, _, kda, attend, open_experts, one_expert, _ = _programs(
        _hashable(cfg), False)
    if kind == "K":
        return kda(x, w)
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
