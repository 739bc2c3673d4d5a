"""Plain float32 reference for the gigachat3_5-like family
(GigaChat3.5-432B-A28B): published layer i is ``x + N(mixer_i(N(x)))`` then
``x + N(ffn_i(N(x)))``, four norms a layer, every norm of the model ``N(x;
w) = x * rsqrt(mean(x^2) + eps) * 2 sigmoid(w)``; the mixer is gated latent
attention under YaRN ("*") or a GatedDeltaNet mixer ("G": a gated delta rule
with one decay a head, fewer key heads than value heads), the FFN a dense
SwiGLU ("D") or sigmoid-routed SwiGLU experts beside a shared one ("E"),
every SwiGLU cut at ``swiglu_limit``. Straightforward jax.numpy under
``precision=HIGHEST``; no cache, no batching, no chunks, no grouped product,
no absorbed attention, and the recurrence as a SEQUENTIAL ``lax.scan`` over
positions: the definition, not the chunked algorithm the program runs.

It imports nothing of the program and takes nothing the program made: the
weights are drawn again from the seed by ``benchmarks/weights_gigachat35.py``
at the published widths and in the PUBLISHED layout, one block at a time
(every sampled request goes through a block before the next is drawn), and
cast to float32 inside the operations.

The equations (``cfg`` = the "model" group of a configuration file; eps =
``rms_norm_eps``; ``h`` the pre-normed input of a sublayer):

- latent attention: ``q = N(h W_qa) W_qb`` -> H heads of ``q_n | q_r``;
  ``[c | k_r] = h W_kva``, ``c <- N(c)``; ``q_r`` and ``k_r`` (one for all
  heads) rotated in INTERLEAVED pairs (2i, 2i+1) by YaRN's frequencies:
  ``f_i = theta^(-2i/d)``, ``low = floor(d ln(L / (beta_fast 2 pi)) / (2 ln
  theta))``, ``high = ceil(d ln(L / (beta_slow 2 pi)) / (2 ln theta))``,
  ``r_i = clip((i - low) / (high - low), 0, 1)``, ``f'_i = r_i f_i / factor
  + (1 - r_i) f_i``, cos and sin unscaled; ``[k_n | v] = c W_kvb`` a head;
  scores ``(q_n . k_n + q_r . k_r) (nope + rope)^-1/2 m^2`` with ``m = 0.1
  ln(factor) + 1``, causal softmax, ``o = P v``; ``out = [o * sigmoid(h
  W_g)] W_o`` (one gate an output element). Every key and value is expanded
  a head from its latent: the program's absorbed decode is thereby checked
  against other arithmetic.
- GatedDeltaNet: ``[q | k | v] = h W_qkv`` (Hk dk | Hk dk | Hv dv); each
  ``<- silu(sum_j w_j [.]_{t-(K-1)+j})`` (depthwise, causal, zeros before
  the sequence, no bias); a key head: ``q <- q / sqrt(|q|^2 + 1e-6) *
  dk^-1/2``, ``k <- k / sqrt(|k|^2 + 1e-6)``; value head j reads key head
  ``j // (Hv / Hk)``; ``beta_t = sigmoid(h_t W_b)`` [Hv]; ``g_t =
  -exp(A_log) softplus(h_t W_a + dt_bias)`` [Hv], one a head; from ``S =
  0`` [Hv, dk, dv]: ``S <- e^{g_t} S``, ``S <- S + beta_t k_t (v_t - S^T
  k_t)^T``, ``o_t = S^T q_t``; ``out = [N_head(o_t; w_o) * gate_scale
  sigmoid(h_t W_z)] W_out``, ``N_head`` over a head's dv.
- SwiGLU (dense, shared, routed): ``g = min(h W_1, limit)``, ``u = clip(h
  W_3, -limit, limit)``, ``(silu(g) * u) W_2``.
- experts: ``s = sigmoid(h W_r)`` in float32 over ALL ``n_experts``; chosen
  = top-k of ``s + b``; ``w = s[chosen] / (sum s[chosen] + 1e-20) *
  routed_scale``. Of a held share (``experts_held`` from ``expert_first``
  on) only the chosen experts that are held add their part; what the absent
  ranks would add is left out, as in the program. Plus the shared expert,
  ungated, for every token. Each expert's tokens are picked BY INDEX on the
  host and go through that expert alone.

``quant=True`` is the CONTROL of the correctness check, never the
reference: every matmul of the linear layers after the usual float8 recipe
(``llama_like._mm8``); the router and the recurrence stay float32.
``state_dtype`` (tests, scripts/gdn_on_chip.py) is the type the recurrent
state is ROUNDED to after every position: float32 is the reference; bfloat16
is what a program that kept its state in the model's type would compute.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks import weights_gigachat35 as weights
from benchmarks.reference.deepseek_like import Q_BLOCK, SEQ_BLOCK, _attention
from benchmarks.reference.llama_like import _hashable, _mm
from benchmarks.reference.nemotron_h_like import route  # the same router

QK_EPS = 1e-6


def _norm(x, w, eps):
    """N(x; w): RMSNorm whose weight passes 2 sigmoid(.)."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (
        2.0 * jax.nn.sigmoid(w.astype(jnp.float32)))


def yarn_inv_freq(cfg: dict):
    """The rotary frequencies a pair, [rope / 2] float32."""
    d, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if not cfg["yarn_factor"]:
        return inv

    def pair(turns):
        return (d * math.log(cfg["yarn_original_max"] / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = math.floor(pair(cfg["yarn_beta_fast"]))
    high = math.ceil(pair(cfg["yarn_beta_slow"]))
    r = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                 / max(high - low, 1e-3), 0.0, 1.0)
    return r * inv / cfg["yarn_factor"] + (1.0 - r) * inv


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if cfg["mla_scaling"]:
        scale *= (0.1 * math.log(cfg["yarn_factor"]) + 1.0) ** 2
    return scale


def _rope_interleaved(x, inv):
    """x [T, H, d] at positions 0..T-1, pairs (2i, 2i+1)."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape)


def attention_forward(x, w, cfg, quant=False):
    """x [T, D] float32 at positions 0..T-1 -> x + N(gated latent
    attention)."""
    T = x.shape[0]
    H, r = cfg["n_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, inv = cfg["rms_norm_eps"], yarn_inv_freq(cfg)
    h = _norm(x, w["norm"], eps)
    c_q = _norm(_mm(h, w["wq_a"], quant), w["q_norm"], eps)
    q = _mm(c_q, w["wq_b"], quant).reshape(T, H, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], _rope_interleaved(q[..., nope:], inv)], axis=-1)
    ckv = _mm(h, w["wkv_a"], quant)
    c_kv = _norm(ckv[:, :r], w["kv_norm"], eps)
    k_r = _rope_interleaved(ckv[:, None, r:], inv)  # [T, 1, rope]
    kv = _mm(c_kv, w["wkv_b"], quant).reshape(T, H, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (T, H, rope))], axis=-1)
    o = _attention(q, k, kv[..., nope:], softmax_scale(cfg)).reshape(T, H * dv)
    if cfg["attn_gate"]:
        o = o * jax.nn.sigmoid(_mm(h, w["wg"], quant))
    return x + _norm(_mm(o, w["wo"], quant), w["post_norm"], eps)


def gdn_forward(x, w, cfg, quant=False, state_dtype=jnp.float32):
    """x [T, D] float32 from an empty state -> x + N(mixer)."""
    T = x.shape[0]
    Hk, Hv = cfg["gdn_k_heads"], cfg["gdn_v_heads"]
    dk, dv, K = cfg["gdn_k_dim"], cfg["gdn_v_dim"], cfg["gdn_conv"]
    eps = cfg["rms_norm_eps"]
    h = _norm(x, w["norm"], eps)
    qkv = _mm(h, w["w_qkv"], quant)
    padded = jnp.concatenate(
        [jnp.zeros((K - 1, qkv.shape[-1]), jnp.float32), qkv])
    cw = w["conv_w"].astype(jnp.float32)
    qkv = jax.nn.silu(sum(cw[j] * padded[j:j + T] for j in range(K)))
    q = qkv[:, :Hk * dk].reshape(T, Hk, dk)
    k = qkv[:, Hk * dk:2 * Hk * dk].reshape(T, Hk, dk)
    v = qkv[:, 2 * Hk * dk:].reshape(T, Hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + QK_EPS) * dk ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + QK_EPS)
    # value head j reads key head j // (Hv / Hk)
    q, k = (jnp.repeat(a, Hv // Hk, axis=1) for a in (q, k))
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(
        _mm(h, w["w_a"], quant) + w["dt_bias"])                  # [T, Hv]
    beta = jax.nn.sigmoid(_mm(h, w["w_b"], quant))               # [T, Hv]
    gate = _mm(h, w["w_z"], quant).reshape(T, Hv, dv)

    # (reduce_precision, not a pair of casts: a TPU's compiler folds a cast
    # to bfloat16 and back into nothing)
    kept = jnp.finfo(state_dtype)

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, None, None] * s                      # [Hv, dk, dv]
        u = b_t[:, None] * (v_t - jnp.sum(k_t[:, :, None] * s, axis=1))
        s = s + k_t[:, :, None] * u[:, None, :]
        if kept.bits < 32:  # float32: the reference, nothing to round
            s = lax.reduce_precision(s, kept.nexp, kept.nmant)
        return s, jnp.sum(q_t[:, :, None] * s, axis=1)

    # (unroll: the same steps in the same order, fewer trips of the loop)
    _, o = lax.scan(step, jnp.zeros((Hv, dk, dv), jnp.float32),
                    (q, k, v, g, beta), unroll=8)
    o = _norm(o, w["o_norm"], eps) * (
        cfg["gdn_gate_scale"] * jax.nn.sigmoid(gate))
    out = _mm(o.reshape(T, Hv * dv), w["w_out"], quant)
    return x + _norm(out, w["post_norm"], eps)


def _swiglu(h, wg, wu, wd, limit, quant):
    g, u = _mm(h, wg, quant), _mm(h, wu, quant)
    if limit:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return _mm(jax.nn.silu(g) * u, wd, quant)


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, quant: bool, state_dtype: str = "float32"):
    cfg = dict(cfg_items)
    eps, limit = cfg["rms_norm_eps"], cfg["swiglu_limit"]

    @jax.jit
    def embed(root, tokens):
        return weights.tables(root, cfg)["embed"][tokens].astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=(1,))
    def draw(root, group, l):
        return weights.layer_slice(root, cfg, group, l)

    @jax.jit
    def gdn(x, w):
        return gdn_forward(x, w, cfg, quant, jnp.dtype(state_dtype))

    @jax.jit
    def attend(x, w):
        return attention_forward(x, w, cfg, quant)

    @jax.jit
    def dense_ffn(x, w):
        h = _norm(x, w["norm"], eps)
        out = _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], limit, quant)
        return x + _norm(out, w["post_norm"], eps)

    @jax.jit
    def open_experts(x, w):
        """The normed rows, their routing, and the shared expert's part."""
        h = _norm(x, w["norm"], eps)
        chosen, weight = route(h, w["moe"], cfg)
        s = w["moe"]["shared"]
        return h, chosen, weight, _swiglu(
            h, s["w_gate"], s["w_up"], s["w_down"], limit, quant)

    @jax.jit
    def one_expert(out, h, ids, weight, wg, wu, wd):
        """``out[ids] += weight * E(h[ids])``; ``ids`` padded with T (read
        as zeros, dropped at the add)."""
        rows = jnp.take(h, ids, axis=0, mode="fill", fill_value=0.0)
        y = _swiglu(rows, wg, wu, wd, limit, quant) * weight[:, None]
        return out.at[ids].add(y, mode="drop")

    @jax.jit
    def close_experts(x, out, post_norm):
        return x + _norm(out, post_norm, eps)

    @jax.jit
    def head(root, x, rows):
        t = weights.tables(root, cfg)
        return _mm(_norm(x[rows], t["final_norm"], eps), t["lm_head"], quant)

    return (embed, draw, gdn, attend, dense_ffn, open_experts, one_expert,
            close_experts, head)


def _expert_ffn(x, w, cfg, open_experts, one_expert, close_experts):
    """An expert block on x [T, D]: a loop over the experts held, each
    given the rows that chose it, picked by index on the host; the block's
    sum is normed once more before it joins the stream."""
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
    return close_experts(x, out, w["post_norm"])


def _blocks(cfg: dict):
    """(kind, group, index within the group) of each block, in order."""
    at = dict.fromkeys(weights.GROUPS, 0)
    for kind in cfg["pattern"]:
        yield kind, weights.GROUPS[kind], at[kind]
        at[kind] += 1


def _block(x, w, cfg, kind, programs):
    _, _, gdn, attend, dense_ffn, open_experts, one_expert, close_experts, _ \
        = programs
    if kind == "G":
        return gdn(x, w)
    if kind == "*":
        return attend(x, w)
    if kind == "D":
        return dense_ffn(x, w)
    return _expert_ffn(x, w, cfg, open_experts, one_expert, close_experts)


def logits_many(seed: int, cfg: dict, sequences, rows, quant: bool = False,
                state_dtype: str = "float32"):
    """Float32 logits [len(rows[i]), vocab] of each sequence ``sequences[i]``
    (a list of token ids) at its positions ``rows[i]`` (each row's logits
    predict the NEXT token). Block-major: a block's weights are drawn once
    and every sequence goes through it. A sequence is padded to whole
    blocks of positions; every mixer is causal, so the pad moves no real
    position."""
    programs = _programs(_hashable(cfg), quant, state_dtype)
    embed, draw, head = programs[0], programs[1], programs[-1]
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
            xs[i] = _block(x, w, cfg, kind, programs)
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
    return _block(x, w, cfg, kind, _programs(_hashable(cfg), False))


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
