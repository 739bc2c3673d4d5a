"""Operations and least bytes of what the nemotron_h-like family adds, from
shapes alone (the sibling of ``roofline.py`` and ``roofline_latent.py``,
whose rule holds here too: a share above 100 % means a count here is too
high or the time leaves work out — fix the count, never clamp).

``cfg`` is the "model" group ``runners/serve_hybrid.py`` makes of a
configuration file: the published widths, the experts HELD here
(``experts_held`` of ``n_experts``) and the vocabulary rows held. Counts
are of the published widths: the zero columns the program pads an expert's
leaves with (1856 -> 1920) are the implementation's, not the least.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layers(cfg: dict) -> dict:
    """Layers of each kind: {"M": ..., "E": ..., "*": ...}."""
    return {k: cfg["pattern"].count(k) for k in "ME*"}


def mamba_sizes(cfg: dict) -> dict:
    H, P = cfg["mamba_heads"], cfg["mamba_head_dim"]
    inner = H * P
    conv_dim = inner + 2 * cfg["ssm_groups"] * cfg["ssm_state"]
    return {"inner": inner, "conv_dim": conv_dim,
            "proj": inner + conv_dim + H}


def mamba_layer_params(cfg: dict) -> int:
    """w_in, the conv's weight and bias, dt_bias, A_log, D, the gated
    norm's weight, w_out, and the block's norm."""
    D, m = cfg["dim"], mamba_sizes(cfg)
    return (D * m["proj"] + (cfg["conv_kernel"] + 1) * m["conv_dim"]
            + 3 * cfg["mamba_heads"] + m["inner"] + m["inner"] * D + D)


def attention_layer_params(cfg: dict) -> int:
    D = cfg["dim"]
    q = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    return D * q + 2 * D * kv + q * D + D


def expert_params(cfg: dict) -> int:
    """One routed expert: two matrices (the squared-ReLU FFN has no gate)."""
    return 2 * cfg["dim"] * cfg["moe_dim"]


def expert_layer_params(cfg: dict, experts: float | None = None) -> float:
    """Router (over every expert), its bias, ``experts`` routed experts
    (default: those held), the shared expert, the block's norm."""
    D = cfg["dim"]
    e = cfg["experts_held"] if experts is None else experts
    return (D * cfg["n_experts"] + cfg["n_experts"] + e * expert_params(cfg)
            + 2 * D * cfg["shared_dim"] + D)


def held_params(cfg: dict) -> float:
    """Every parameter this rank holds: at the published sizes with 16 of
    128 experts and 16384 of 131072 rows, 5.26 B (10.52 GB in bfloat16);
    with all of both, the model's 31.58 B."""
    n = layers(cfg)
    return (n["M"] * mamba_layer_params(cfg)
            + n["*"] * attention_layer_params(cfg)
            + n["E"] * expert_layer_params(cfg)
            + 2 * cfg["vocab"] * cfg["dim"] + cfg["dim"])


def weight_bytes(cfg: dict) -> float:
    return held_params(cfg) * _BYTES[cfg["dtype"]]


def slot_state_bytes(cfg: dict) -> int:
    """What one slot keeps over all Mamba layers: the state h [H, P, N] in
    float32 and the conv's last K - 1 inputs [K - 1, conv_dim] in the
    model's type. 2.13 MB a layer, 49.1 MB a slot at the published sizes."""
    per_layer = (cfg["mamba_heads"] * cfg["mamba_head_dim"] * cfg["ssm_state"]
                 * 4 + (cfg["conv_kernel"] - 1) * mamba_sizes(cfg)["conv_dim"]
                 * _BYTES[cfg["dtype"]])
    return layers(cfg)["M"] * per_layer


def position_bytes(cfg: dict) -> int:
    """What one position keeps over the attention layers: K and V of
    ``n_kv_heads`` heads a layer. 6 KB at the published sizes."""
    return (layers(cfg)["*"] * 2 * cfg["n_kv_heads"] * cfg["head_dim"]
            * _BYTES[cfg["dtype"]])


def ssm_step(cfg: dict, rows: float) -> dict:
    """The Mamba mixers of ONE decode step over ``rows`` live rows: each
    row's state and conv window read and written, the mixers' weights read
    once; operations: the two projections and the state update (multiply,
    add and read-out a state element)."""
    w = _BYTES[cfg["dtype"]]
    n, m = layers(cfg)["M"], mamba_sizes(cfg)
    state = cfg["mamba_heads"] * cfg["mamba_head_dim"] * cfg["ssm_state"]
    nbytes = (2 * rows * slot_state_bytes(cfg)
              + n * mamba_layer_params(cfg) * w)
    flops = n * rows * (2 * cfg["dim"] * (m["proj"] + m["inner"]) + 6 * state)
    return {"flops": float(flops), "bytes": float(nbytes)}


def ssm_scan(cfg: dict, tokens: int) -> dict:
    """The Mamba mixers of ONE prompt slice of ``tokens`` positions (one
    slot): the two projections, and the chunked scan's four products a
    chunk (C B^T, the masked product with x, the chunk's state, the
    read-out of the carried state); bytes: the weights once, the slice in
    and out, the slot's state read and written."""
    w = _BYTES[cfg["dtype"]]
    n, m = layers(cfg)["M"], mamba_sizes(cfg)
    H, P, N, G = (cfg["mamba_heads"], cfg["mamba_head_dim"], cfg["ssm_state"],
                  cfg["ssm_groups"])
    Q = min(cfg["chunk"], tokens)
    chunks = -(-tokens // Q)
    per_chunk = 2 * Q * Q * N * G + 2 * Q * Q * P * H + 4 * Q * P * N * H
    flops = n * (tokens * 2 * cfg["dim"] * (m["proj"] + m["inner"])
                 + chunks * per_chunk)
    nbytes = (n * (mamba_layer_params(cfg) * w + 2 * tokens * cfg["dim"] * w)
              + 2 * slot_state_bytes(cfg))
    return {"flops": float(flops), "bytes": float(nbytes)}


def expert_product(cfg: dict, rows: int, experts_touched: float) -> dict:
    """ONE grouped product of an expert layer over ``rows`` assignment rows
    of the experts held (either of the two: ``w_up`` or ``w_down``): 2 x
    rows x dim x moe_dim operations, and least bytes = the touched experts'
    matrix read once plus the rows in and out."""
    w = _BYTES[cfg["dtype"]]
    D, F = cfg["dim"], cfg["moe_dim"]
    return {"flops": float(2 * rows * D * F),
            "bytes": float(experts_touched * D * F * w + rows * (D + F) * w)}


def expected_held_touched(cfg: dict, tokens: float) -> float:
    """Distinct held experts that ``tokens`` tokens reach, each choosing
    top-k of all E uniformly: held x (1 - (1 - k/E)^tokens). At 48 tokens,
    top-6 of 128, 16 held: 14.4."""
    E, k = cfg["n_experts"], cfg["moe_top_k"]
    return cfg["experts_held"] * (1.0 - (1.0 - k / E) ** tokens)


def decode_step_min_bytes(cfg: dict, live_rows: float, live_kv_tokens: float,
                          experts_touched: float) -> float:
    """Least bytes one lockstep decode step must move through HBM: the
    live rows' recurrent state read and written, the Mamba and attention
    weights, the router, the shared expert and the ``experts_touched``
    routed experts of each expert layer read once, the live keys and
    values read once and each row's new ones written, the head. At 48
    rows, 14.4 experts and 150 k positions: 15.3 GB, of which the state is
    4.7."""
    w = _BYTES[cfg["dtype"]]
    n = layers(cfg)
    return (2 * live_rows * slot_state_bytes(cfg)
            + n["M"] * mamba_layer_params(cfg) * w
            + n["*"] * attention_layer_params(cfg) * w
            + (live_kv_tokens + live_rows) * position_bytes(cfg)
            + n["E"] * expert_layer_params(cfg, experts_touched) * w
            + cfg["dim"] * cfg["vocab"] * w + cfg["dim"] * 4)
