"""Operations and least bytes of what the solar_open2-like family adds, from
shapes alone (the sibling of ``roofline_hybrid.py``, whose rule holds here
too: a share above 100 % means a count here is too high or the time leaves
work out — fix the count, never clamp).

``cfg`` is the "model" group ``runners/serve_kda.py`` makes of a
configuration file: the published widths, the experts HELD here
(``experts_held`` of ``n_experts``) and the vocabulary rows held.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
# Positions a chunk of the counted scan: the published algorithm's, whatever
# chunk the program runs (a count follows the mathematics, not the code).
CHUNK = 64


def layers(cfg: dict) -> dict:
    """Blocks of each kind: {"K": ..., "E": ..., "*": ...}."""
    return {k: cfg["pattern"].count(k) for k in "KE*"}


def kda_sizes(cfg: dict) -> dict:
    H, d = cfg["kda_heads"], cfg["kda_head_dim"]
    return {"inner": H * d, "state": H * d * d,
            # columns the normed input is multiplied into: q | k | v, the
            # two low-rank pairs' first halves, beta
            "in": 3 * H * d + 2 * cfg["kda_rank"] + H}


def kda_layer_params(cfg: dict) -> int:
    """W_qkv, the conv's weight, the two low-rank pairs, dt_bias, A_log,
    W_beta, the output gate's bias, the head norm's weight, W_out, and the
    block's norm."""
    D, H, d, r = cfg["dim"], cfg["kda_heads"], cfg["kda_head_dim"], cfg["kda_rank"]
    inner = H * d
    return (D * 3 * inner + cfg["kda_conv"] * 3 * inner
            + 2 * (D * r + r * inner) + inner + H + D * H + inner + d
            + inner * D + D)


def attention_layer_params(cfg: dict) -> int:
    """wq, wk, wv, wo, the output gate where the family has one, the norm."""
    D = cfg["dim"]
    q = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    return D * q * (3 if cfg["gqa_gate"] else 2) + 2 * D * kv + D


def expert_params(cfg: dict) -> int:
    """One routed expert: three matrices (SwiGLU)."""
    return 3 * cfg["dim"] * cfg["moe_dim"]


def expert_layer_params(cfg: dict, experts: float | None = None) -> float:
    """Router (over every expert), its bias, ``experts`` routed experts
    (default: those held), the shared expert, the block's norm."""
    D = cfg["dim"]
    e = cfg["experts_held"] if experts is None else experts
    return (D * cfg["n_experts"] + cfg["n_experts"] + e * expert_params(cfg)
            + 3 * D * cfg["shared_dim"] + D)


def held_params(cfg: dict) -> float:
    """Every parameter this rank holds: at the published sizes with 4 of 48
    layers, 40 of 320 experts and 24576 of 196608 rows, 3.308 B (6.62 GB in
    bfloat16); with all of all three, the model's 250.29 B."""
    n = layers(cfg)
    return (n["K"] * kda_layer_params(cfg)
            + n["*"] * attention_layer_params(cfg)
            + n["E"] * expert_layer_params(cfg)
            + 2 * cfg["vocab"] * cfg["dim"] + cfg["dim"])


def weight_bytes(cfg: dict) -> float:
    return held_params(cfg) * _BYTES[cfg["dtype"]]


def slot_state_bytes(cfg: dict) -> int:
    """What one slot keeps over all KDA layers: the state S [H, d, d] in
    float32 and the conv's last K - 1 inputs [K - 1, 3 H d] in the model's
    type. 4.34 MB a layer, 13.03 MB a slot at the published sizes held."""
    per_layer = (kda_sizes(cfg)["state"] * 4
                 + (cfg["kda_conv"] - 1) * 3 * kda_sizes(cfg)["inner"]
                 * _BYTES[cfg["dtype"]])
    return layers(cfg)["K"] * per_layer


def position_bytes(cfg: dict) -> int:
    """What one position keeps over the attention layers: K and V of
    ``n_kv_heads`` heads a layer. 4 KB at the published sizes held."""
    return (layers(cfg)["*"] * 2 * cfg["n_kv_heads"] * cfg["head_dim"]
            * _BYTES[cfg["dtype"]])


def kda_step(cfg: dict, rows: float) -> dict:
    """The KDA mixers of ONE decode step over ``rows`` live rows: each row's
    state and conv window read and written once, the mixers' weights read
    once; operations: the projections and the state update (decay, the
    delta's read-out, the rank-one update, the output's read-out: 7 a state
    element)."""
    w = _BYTES[cfg["dtype"]]
    n, s = layers(cfg)["K"], kda_sizes(cfg)
    r = cfg["kda_rank"]
    nbytes = 2 * rows * slot_state_bytes(cfg) + n * kda_layer_params(cfg) * w
    flops = n * rows * (2 * cfg["dim"] * (s["in"] + s["inner"])
                        + 2 * 2 * r * s["inner"] + 7 * s["state"])
    return {"flops": float(flops), "bytes": float(nbytes)}


def kda_scan(cfg: dict, tokens: int) -> dict:
    """The KDA mixers of ONE prompt slice of ``tokens`` positions (one
    slot): the projections, and the chunked delta rule's products — three
    with the carried state a position (its read-outs for the delta and the
    output, the chunk's addition to it: 2 d^2 each a head) and, inside a
    chunk of ``CHUNK`` positions, the causal halves of K K^T, Q K^T, the
    triangular system's two right-hand sides and the output's product (5
    CHUNK d a position a head); bytes: the weights once, the slice in and
    out, the slot's state read and written."""
    w = _BYTES[cfg["dtype"]]
    n, s = layers(cfg)["K"], kda_sizes(cfg)
    H, d, r = cfg["kda_heads"], cfg["kda_head_dim"], cfg["kda_rank"]
    C = min(CHUNK, tokens)
    flops = n * tokens * (2 * cfg["dim"] * (s["in"] + s["inner"])
                          + 2 * 2 * r * s["inner"]
                          + H * (6 * d * d + 5 * C * d))
    nbytes = (n * (kda_layer_params(cfg) * w + 2 * tokens * cfg["dim"] * w)
              + 2 * slot_state_bytes(cfg))
    return {"flops": float(flops), "bytes": float(nbytes)}


def expert_product(cfg: dict, rows: float, experts_touched: float) -> dict:
    """ONE product of an expert block over ``rows`` assignment rows of the
    experts held (any of the three: ``w_gate``, ``w_up`` or ``w_down``): 2 x
    rows x dim x moe_dim operations, and least bytes = the touched experts'
    matrix read once plus the rows in and out."""
    w = _BYTES[cfg["dtype"]]
    D, F = cfg["dim"], cfg["moe_dim"]
    return {"flops": float(2 * rows * D * F),
            "bytes": float(experts_touched * D * F * w + rows * (D + F) * w)}


def expected_held_touched(cfg: dict, tokens: float) -> float:
    """Distinct held experts that ``tokens`` tokens reach, each choosing
    top-k of all E uniformly: held x (1 - (1 - k/E)^tokens). At 64 tokens,
    top-8 of 320, 40 held: 32.1."""
    E, k = cfg["n_experts"], cfg["moe_top_k"]
    return cfg["experts_held"] * (1.0 - (1.0 - k / E) ** tokens)


def decode_step_min_bytes(cfg: dict, live_rows: float, live_kv_tokens: float,
                          experts_touched: float) -> float:
    """Least bytes one lockstep decode step must move through HBM: the live
    rows' recurrent state read and written, the KDA and attention weights,
    the router, the shared expert and the ``experts_touched`` routed experts
    of each expert block read once, the live keys and values read once and
    each row's new ones written, the head. At 64 rows, 32 experts and 260 k
    positions: 8.3 GB, of which the state is 1.7."""
    w = _BYTES[cfg["dtype"]]
    n = layers(cfg)
    return (2 * live_rows * slot_state_bytes(cfg)
            + n["K"] * kda_layer_params(cfg) * w
            + n["*"] * attention_layer_params(cfg) * w
            + (live_kv_tokens + live_rows) * position_bytes(cfg)
            + n["E"] * expert_layer_params(cfg, experts_touched) * w
            + cfg["dim"] * cfg["vocab"] * w + cfg["dim"] * 4)
