"""Operations and least bytes of what the gigachat3_5-like family adds, from
shapes alone (the sibling of ``roofline_kda.py``, whose rule holds here too:
a share above 100 % means a count here is too high or the time leaves work
out — fix the count, never clamp).

``cfg`` is the "model" group ``runners/serve_gdn.py`` makes of a
configuration file: the published widths, the layers and the experts HELD
here (``experts_held`` of ``n_experts``) and the vocabulary rows held. An
expert block's products are ``roofline_kda``'s (the same SwiGLU experts of a
held share: ``expert_product``, ``expected_held_touched``).
"""

from __future__ import annotations

from benchmarks.roofline_kda import expert_params
from benchmarks.roofline_latent import latent_entry_bytes

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
# Positions a chunk of the counted scan: the published algorithm's, whatever
# chunk the program runs (a count follows the mathematics, not the code).
CHUNK = 64


def layers(cfg: dict) -> dict:
    """Blocks of each kind: {"G": ..., "D": ..., "E": ..., "*": ...}."""
    return {k: cfg["pattern"].count(k) for k in "GDE*"}


def gdn_sizes(cfg: dict) -> dict:
    Hk, Hv = cfg["gdn_k_heads"], cfg["gdn_v_heads"]
    dk, dv = cfg["gdn_k_dim"], cfg["gdn_v_dim"]
    return {"value": Hv * dv, "conv": 2 * Hk * dk + Hv * dv,
            "state": Hv * dk * dv,
            # columns the normed input is multiplied into: q | k | v, the
            # two head-wide gates, the output gate
            "in": 2 * Hk * dk + 2 * Hv * dv + 2 * Hv}


def gdn_layer_params(cfg: dict) -> int:
    """W_qkv, W_a, W_b, W_z, W_out, the conv's weight, dt_bias, A_log, the
    head norm's weight, and the block's two norms."""
    D, s = cfg["dim"], gdn_sizes(cfg)
    return (D * (s["in"] + s["value"]) + cfg["gdn_conv"] * s["conv"]
            + 2 * cfg["gdn_v_heads"] + cfg["gdn_v_dim"] + 2 * D)


def latent_layer_params(cfg: dict) -> int:
    """wq_a, wq_b, wkv_a, wkv_b, wo, the output gate where the family has
    one, the two inner norms and the block's two."""
    D, H = cfg["dim"], cfg["n_heads"]
    ql, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (D * ql + ql + ql * H * (nope + rope) + D * (r + rope) + r
            + r * H * (nope + v) + H * v * D * (2 if cfg["attn_gate"] else 1)
            + 2 * D)


def dense_layer_params(cfg: dict) -> int:
    return 3 * cfg["dim"] * cfg["mlp_dim"] + 2 * cfg["dim"]


def expert_layer_params(cfg: dict, experts: float | None = None) -> float:
    """Router (over every expert), its bias, ``experts`` routed experts
    (default: those held), the shared expert, the block's two norms."""
    D = cfg["dim"]
    e = cfg["experts_held"] if experts is None else experts
    return (D * cfg["n_experts"] + cfg["n_experts"] + e * expert_params(cfg)
            + 3 * D * cfg["shared_dim"] + 2 * D)


def held_params(cfg: dict) -> float:
    """Every parameter this rank holds: at the published sizes with layer 0
    and layers 3-6, 16 of 256 experts and 16032 of 128256 rows, 4.73 B (9.46
    GB in bfloat16); with all of all three, the model's 430.55 B (431.87 B
    with the two multi-token-prediction modules that are not run)."""
    n = layers(cfg)
    return (n["G"] * gdn_layer_params(cfg) + n["*"] * latent_layer_params(cfg)
            + n["D"] * dense_layer_params(cfg)
            + n["E"] * expert_layer_params(cfg)
            + 2 * cfg["vocab"] * cfg["dim"] + cfg["dim"])


def weight_bytes(cfg: dict) -> float:
    return held_params(cfg) * _BYTES[cfg["dtype"]]


def slot_state_bytes(cfg: dict) -> int:
    """What one slot keeps over all GatedDeltaNet layers: the state S [Hv,
    dk, dv] in float32 and the conv's last K - 1 inputs [K - 1, conv] in the
    model's type. 4.29 MB a layer, 17.17 MB a slot at the sizes held."""
    s = gdn_sizes(cfg)
    per_layer = (s["state"] * 4 + (cfg["gdn_conv"] - 1) * s["conv"]
                 * _BYTES[cfg["dtype"]])
    return layers(cfg)["G"] * per_layer


def position_bytes(cfg: dict) -> int:
    """What one position keeps over the latent layers: the normed latent and
    the shared rotated key dims (the program's padding to whole lanes is
    its own, not the least). 1152 B at the sizes held."""
    return layers(cfg)["*"] * latent_entry_bytes(cfg)


def gdn_step(cfg: dict, rows: float) -> dict:
    """The GatedDeltaNet mixers of ONE decode step over ``rows`` live rows:
    each row's state and conv window read and written once, the mixers'
    weights read once; operations: the projections and the state update
    (the two read-outs, the decay, the rank-one update: 7 a state element)."""
    w = _BYTES[cfg["dtype"]]
    n, s = layers(cfg)["G"], gdn_sizes(cfg)
    nbytes = 2 * rows * slot_state_bytes(cfg) + n * gdn_layer_params(cfg) * w
    flops = n * rows * (2 * cfg["dim"] * (s["in"] + s["value"])
                        + 7 * s["state"])
    return {"flops": float(flops), "bytes": float(nbytes)}


def gdn_scan(cfg: dict, tokens: int) -> dict:
    """The GatedDeltaNet mixers of ONE prompt slice of ``tokens`` positions
    (one slot): the projections, and the chunked delta rule's products —
    three with the carried state a position a value head (2 dk dv each)
    and, inside a chunk of ``CHUNK`` positions, the causal halves of K K^T
    and Q K^T a KEY head (CHUNK dk each) and of the triangular system's two
    right-hand sides and the output's product a value head (CHUNK (2 dv +
    dk)); bytes: the weights once, the slice in and out, the slot's state
    read and written."""
    w = _BYTES[cfg["dtype"]]
    n, s = layers(cfg)["G"], gdn_sizes(cfg)
    Hk, Hv = cfg["gdn_k_heads"], cfg["gdn_v_heads"]
    dk, dv = cfg["gdn_k_dim"], cfg["gdn_v_dim"]
    C = min(CHUNK, tokens)
    flops = n * tokens * (2 * cfg["dim"] * (s["in"] + s["value"])
                          + Hv * (6 * dk * dv + C * (2 * dv + dk))
                          + Hk * 2 * C * dk)
    nbytes = (n * (gdn_layer_params(cfg) * w + 2 * tokens * cfg["dim"] * w)
              + 2 * slot_state_bytes(cfg))
    return {"flops": float(flops), "bytes": float(nbytes)}


def decode_step_min_bytes(cfg: dict, live_rows: float, live_kv_tokens: float,
                          experts_touched: float) -> float:
    """Least bytes one lockstep decode step must move through HBM: the live
    rows' recurrent state read and written, the GatedDeltaNet, latent and
    dense-FFN weights, the router, the shared expert and the
    ``experts_touched`` routed experts of each expert block read once, the
    live latent entries read once and each row's new one written, the head.
    At 64 rows, 14 experts and 260 k positions: 10.9 GB, of which the state
    is 2.2."""
    w = _BYTES[cfg["dtype"]]
    n = layers(cfg)
    return (2 * live_rows * slot_state_bytes(cfg)
            + n["G"] * gdn_layer_params(cfg) * w
            + n["*"] * latent_layer_params(cfg) * w
            + n["D"] * dense_layer_params(cfg) * w
            + (live_kv_tokens + live_rows) * position_bytes(cfg)
            + n["E"] * expert_layer_params(cfg, experts_touched) * w
            + cfg["dim"] * cfg["vocab"] * w + cfg["dim"] * 4)
