"""Operations and least bytes of the programs the benchmark judges, from
shapes alone. No ``cost_analysis()``, nothing read from the program: a
share of a roofline is this file's count over ``peaks.json`` over a time
from the device trace. A share above 100 % means a count here is too high
or the time leaves work out — fix the count, never clamp.

``cfg`` is the "model" group of a configuration file.
"""

from __future__ import annotations

import json
import os

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> dict:
    """Published peaks of the device; an unknown kind is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {path} (known: {sorted(table)})")
    return table[device_kind]


def layer_params(cfg: dict, experts: int | None = None) -> int:
    """Parameters of one decoder layer; ``experts`` counts that many
    expert FFNs (default: all of them)."""
    D, F = cfg["dim"], cfg["mlp_dim"]
    q, kv = cfg["n_heads"] * cfg["head_dim"], cfg["n_kv_heads"] * cfg["head_dim"]
    attn = D * q + 2 * D * kv + q * D
    E = cfg.get("n_experts", 0)
    if E:
        e = E if experts is None else experts
        ffn = D * E + 3 * e * D * F
    else:
        ffn = 3 * D * F
    return attn + ffn + 2 * D


def total_params(cfg: dict) -> int:
    """Every parameter held: layers, both tables, the final norm."""
    return (cfg["n_layers"] * layer_params(cfg)
            + 2 * cfg["vocab"] * cfg["dim"] + cfg["dim"])


def matmul_params_per_token(cfg: dict) -> int:
    """Parameters a token multiplies against: its top-k experts, the
    attention projections and the output head — not the embedding (a
    lookup) and not the norms."""
    k = min(cfg.get("moe_top_k", 0), cfg.get("n_experts", 0)) or None
    per_layer = layer_params(cfg, experts=k) - 2 * cfg["dim"]
    return cfg["n_layers"] * per_layer + cfg["dim"] * cfg["vocab"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations one trained token REQUIRES: 6 per
    matmul parameter, plus causal attention — QK^T and PV are 4*T*q_dim a
    token a layer in full, the causal mask halves it, times 3 for forward
    and backward. Recomputation (remat, the flash backward's second QK^T)
    is not counted."""
    q = cfg["n_heads"] * cfg["head_dim"]
    attn = 3 * (4 * seq_len * q) / 2 * cfg["n_layers"]
    return 6.0 * matmul_params_per_token(cfg) + attn


def expected_experts_touched(cfg: dict, rows: float) -> float:
    """Distinct experts a decode step's ``rows`` live rows reach, each row
    choosing top-k of E uniformly: E * (1 - (1 - k/E)^rows). With 32 rows,
    top-2 of 8: 7.999 — the whole layer."""
    E, k = cfg.get("n_experts", 0), cfg.get("moe_top_k", 0)
    if not E:
        return 0.0
    return E * (1.0 - (1.0 - k / E) ** rows)


def decode_step_min_bytes(cfg: dict, live_rows: float, live_kv_tokens: float) -> float:
    """Least bytes one lockstep decode step must move through HBM: every
    weight it touches read once (attention, the experts its rows reach or
    the dense FFN, the norms, the output head), the LIVE keys and values
    read once (``live_kv_tokens`` = sum of the live rows' context lengths),
    and each row's new K/V written. Page tables, the gathered copy of the
    cache, scores and logits are the implementation's, not the least."""
    w = _BYTES[cfg["dtype"]]
    D, F = cfg["dim"], cfg["mlp_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    q = cfg["n_heads"] * cfg["head_dim"]
    attn = (D * q + 2 * D * kv + q * D) * w
    if cfg.get("n_experts"):
        ffn = (cfg["n_experts"] * D * 4
               + expected_experts_touched(cfg, live_rows) * 3 * D * F * w)
    else:
        ffn = 3 * D * F * w
    per_layer = attn + ffn + 2 * D * 4
    kv_bytes = 2 * kv * w  # K and V of one position in one layer
    return (cfg["n_layers"] * (per_layer + (live_kv_tokens + live_rows) * kv_bytes)
            + D * cfg["vocab"] * w + D * 4)


def flash_forward(cfg: dict, batch: int, seq: int) -> dict:
    """Causal flash-attention forward over [batch, seq] in one layer:
    operations (QK^T and PV, halved by the mask) and least bytes (q, k, v
    read, o written, the row log-sum-exp written in float32)."""
    H, KV, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    w = _BYTES[cfg["dtype"]]
    flops = 2 * (2 * batch * H * seq * seq * hd) / 2
    nbytes = batch * seq * hd * w * (2 * H + 2 * KV) + batch * seq * H * 4
    return {"flops": float(flops), "bytes": float(nbytes)}


def flash_backward(cfg: dict, batch: int, seq: int) -> dict:
    """Its backward: dV, dP, dQ and dK are four matmuls of the forward's
    size (the kernel's recomputed QK^T is not counted); reads q, k, v, o,
    do and the log-sum-exp, writes dq, dk, dv."""
    H, KV, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    w = _BYTES[cfg["dtype"]]
    flops = 4 * (2 * batch * H * seq * seq * hd) / 2
    nbytes = (batch * seq * hd * w * (4 * H + 4 * KV)
              + batch * seq * H * 4)
    return {"flops": float(flops), "bytes": float(nbytes)}


def roofline_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip could take and which peak bounds it."""
    t_flops = work.get("flops", 0.0) / peak["flops_per_s_bf16"]
    t_bytes = work.get("bytes", 0.0) / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
