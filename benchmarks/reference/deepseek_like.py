"""Plain float32 reference for the DeepSeek-V3-like family (JoyAI-LLM-Flash):
latent attention in the EXPANDED form only, a leading dense layer, then
sigmoid-routed experts beside a shared one. Straightforward jax.numpy under
``precision=HIGHEST``; no cache, no batching, no grouped product.

It imports nothing of the program and takes nothing the program made: the
weights are drawn again from the seed by ``benchmarks/weights_deepseek.py``
in the PUBLISHED layout, one layer at a time (an expert layer is 4.96 GB in
float32, so a layer is drawn once and every sampled request goes through it
before the next is drawn), and cast to float32 inside the operations.

The equations (``cfg`` = the "model" group of a configuration file):

- attention: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> H heads of
  ``q_n | q_r``; ``[c_kv | k_r] = x W_kva``; ``c_kv = RMSNorm(c_kv)``;
  RoPE(theta) on INTERLEAVED pairs (2i, 2i+1) of ``q_r`` and of ``k_r``,
  ``k_r`` shared by all heads; ``[k_n | v] = c_kv W_kvb`` a head;
  ``o = softmax(q k^T / sqrt(nope + rope) + causal) v``; ``x += o W_o``.
  Every key and value is expanded per head from its latent: the program's
  absorbed decode is thereby checked against other arithmetic.
- FFN of the leading dense layers: SwiGLU at ``mlp_dim``. Of the others:
  ``s = sigmoid(h W_r)`` in float32; chosen = top-k of ``s + b``;
  ``w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scale``;
  ``y = sum_i w_i E_i(h) + E_shared(h)``. Each expert's tokens are picked
  BY INDEX on the host and go through that expert alone (a loop over the
  experts): nothing of a sort, a capacity or a grouped product.

``quant=True`` is the CONTROL of the correctness check, never the
reference: every matmul of the linear layers after the usual float8 recipe
(``llama_like._mm8``); the router stays float32, as in the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks import weights_deepseek as weights
from benchmarks.reference.llama_like import HI, _hashable, _mm, _rms

Q_BLOCK = 512    # query rows a block of the attention: scores [H, blk, T]
SEQ_BLOCK = 2048  # a request is padded to whole blocks of positions: one
# compiled program a padded length, and causal attention keeps the pad out


def _rope_interleaved(x, theta):
    """x [T, H, d] at positions 0..T-1, pairs (2i, 2i+1)."""
    T, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape)


def _attention(q, k, v, scale):
    """Full causal softmax attention: q, k [T, H, dq], v [T, H, dv]; query
    rows in blocks so that the scores stay [H, blk, T]."""
    T = q.shape[0]
    blk = min(Q_BLOCK, T)
    assert T % blk == 0, (T, blk)
    cols = jnp.arange(T)

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * blk, blk, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
        rows = i * blk + jnp.arange(blk)
        s = jnp.where(rows[:, None] >= cols[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    out = lax.map(block, jnp.arange(T // blk))  # [n, blk, H, dv]
    return out.reshape((T,) + out.shape[2:])


def attention_forward(x, w, cfg, quant=False):
    """x [T, D] float32 at positions 0..T-1 -> x + attention."""
    T = x.shape[0]
    H, r = cfg["n_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms(x, w["attn_norm"], eps)
    c_q = _rms(_mm(h, w["wq_a"], quant), w["q_norm"], eps)
    q = _mm(c_q, w["wq_b"], quant).reshape(T, H, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], _rope_interleaved(q[..., nope:], theta)], axis=-1)
    ckv = _mm(h, w["wkv_a"], quant)
    c_kv = _rms(ckv[:, :r], w["kv_norm"], eps)
    k_r = _rope_interleaved(ckv[:, None, r:], theta)  # [T, 1, rope]
    kv = _mm(c_kv, w["wkv_b"], quant).reshape(T, H, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (T, H, rope))], axis=-1)
    o = _attention(q, k, kv[..., nope:], (nope + rope) ** -0.5)
    return x + _mm(o.reshape(T, H * dv), w["wo"], quant)


def _swiglu(h, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)


def route(h, m, cfg):
    """(experts [T, k] int32, weights [T, k] f32) of the normed rows h."""
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
    def attend(x, w):
        return attention_forward(x, w, cfg, quant)

    @jax.jit
    def dense_ffn(x, w):
        h = _rms(x, w["mlp_norm"], eps)
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant)

    @jax.jit
    def open_experts(x, w):
        """The normed rows, their routing, and the shared expert's part."""
        h = _rms(x, w["mlp_norm"], eps)
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

    return embed, draw, attend, dense_ffn, open_experts, one_expert, head


def _expert_ffn(x, w, cfg, open_experts, one_expert):
    """The expert layers' FFN on x [T, D]: a loop over the experts, each
    given the rows that chose it, picked by index on the host."""
    h, chosen, weight, out = open_experts(x, w)
    chosen, weight = np.asarray(chosen), np.asarray(weight)
    T = x.shape[0]
    m = w["moe"]
    for e in range(cfg["n_experts"]):
        rows, slot = np.nonzero(chosen == e)
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


def logits_many(seed: int, cfg: dict, sequences, rows, quant: bool = False):
    """Float32 logits [len(rows[i]), vocab] of each sequence ``sequences[i]``
    (a list of token ids) at its positions ``rows[i]`` (each row's logits
    predict the NEXT token). Layer-major: a layer's weights are drawn once
    and every sequence goes through it."""
    embed, draw, attend, dense_ffn, open_experts, one_expert, head = \
        _programs(_hashable(cfg), quant)
    root = weights.root_key(seed)
    xs = []
    for tokens in sequences:
        pad = -(-len(tokens) // SEQ_BLOCK) * SEQ_BLOCK
        padded = np.zeros((pad,), np.int32)
        padded[: len(tokens)] = tokens
        xs.append(embed(root, jnp.asarray(padded)))
    for group, n in weights.group_sizes(cfg).items():
        for l in range(n):
            w = draw(root, group, l)
            for i, x in enumerate(xs):
                x = attend(x, w)
                xs[i] = (dense_ffn(x, w) if group == "dense_layers" else
                         _expert_ffn(x, w, cfg, open_experts, one_expert))
            del w
    out = []
    for x, r in zip(xs, rows):
        n_rows = -(-len(r) // Q_BLOCK) * Q_BLOCK
        ids = np.zeros((n_rows,), np.int32)
        ids[: len(r)] = r
        out.append(head(root, x, jnp.asarray(ids))[: len(r)])
    return out


def layer_forward(x, w, cfg, group: str = "layers"):
    """One block on x [T, D] float32 (tests: against a layer written out
    by hand)."""
    _, _, attend, dense_ffn, open_experts, one_expert, _ = _programs(
        _hashable(cfg), False)
    x = attend(x, w)
    if group == "dense_layers":
        return dense_ffn(x, w)
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
