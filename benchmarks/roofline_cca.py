"""Operations and least bytes of what the zaya-like family adds, from shapes
alone (the sibling of ``roofline_gdn.py``, whose rule holds here too: a share
above 100 % means a count here is too high or the time leaves work out — fix
the count, never clamp).

``cfg`` is the "model" group ``runners/serve_cca.py`` makes of a
configuration file: the published widths, the layers and the experts HELD
here (``experts_held`` of ``n_experts``: all of them in the benchmark's cell)
and the whole vocabulary, one tied table.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
TAPS = 2


def cca_sizes(cfg: dict) -> dict:
    H, Hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    return {"q": H * hd, "kv": Hkv * hd, "latent": (H + Hkv) * hd,
            "groups": H + Hkv,
            # what a slot keeps a layer: p, u and the shifted values' half
            "tail": 2 * (H + Hkv) * hd + Hkv * hd // 2}


def cca_matrix_params(cfg: dict) -> int:
    """The five matrices (W_q | W_k, W_v1 | W_v2, W_o) and the grouped
    convolution's two taps: what is held in the model's type."""
    D, s = cfg["dim"], cca_sizes(cfg)
    return (D * (s["latent"] + s["kv"]) + s["q"] * D
            + TAPS * s["groups"] * cfg["head_dim"] ** 2)


def cca_vector_params(cfg: dict) -> int:
    """float32: the depthwise convolution and both biases, the keys'
    temperature, the block's norm and its four residual vectors."""
    s = cca_sizes(cfg)
    return ((TAPS + 2) * s["latent"] + cfg["n_kv_heads"] + 5 * cfg["dim"])


def router_params(cfg: dict) -> int:
    """float32: the projection down, the carry, the norm, three layers with
    their biases, the selection bias."""
    D, R, E = cfg["dim"], cfg["router_dim"], cfg["n_experts"]
    return D * R + 3 * R + 2 * (R * R + R) + R * E + 2 * E


def expert_params(cfg: dict) -> int:
    """One routed expert: three matrices (SwiGLU)."""
    return 3 * cfg["dim"] * cfg["moe_dim"]


def layer_bytes(cfg: dict, experts: float | None = None) -> float:
    """One layer's bytes with ``experts`` routed experts (default: those
    held): CCA, router, experts, the expert block's norm and residual
    vectors."""
    w = _BYTES[cfg["dtype"]]
    e = cfg["experts_held"] if experts is None else experts
    return ((cca_matrix_params(cfg) + e * expert_params(cfg)) * w
            + (cca_vector_params(cfg) + router_params(cfg)
               + 5 * cfg["dim"]) * 4)


def held_params(cfg: dict) -> float:
    """Every parameter held: at the published sizes with 14 of 40 layers,
    3.44 B (6.89 GB in bfloat16); with all 40, the model's 8.84 B."""
    per_layer = (cca_matrix_params(cfg) + cca_vector_params(cfg)
                 + router_params(cfg) + 5 * cfg["dim"]
                 + cfg["experts_held"] * expert_params(cfg))
    return (cfg["n_layers"] * per_layer + cfg["vocab"] * cfg["dim"]
            + cfg["dim"])


def weight_bytes(cfg: dict) -> float:
    return (cfg["n_layers"] * layer_bytes(cfg)
            + cfg["vocab"] * cfg["dim"] * _BYTES[cfg["dtype"]]
            + cfg["dim"] * 4)


def slot_tail_bytes(cfg: dict) -> int:
    """What one slot keeps over all layers: the tail, float32. 10.5 KiB a
    layer at the published widths."""
    return cfg["n_layers"] * cca_sizes(cfg)["tail"] * 4


def position_bytes(cfg: dict) -> int:
    """What one position keeps over all layers: rotated keys and values of
    the key-value heads. 1 KiB a layer at the published widths."""
    return (cfg["n_layers"] * 2 * cca_sizes(cfg)["kv"]
            * _BYTES[cfg["dtype"]])


def cca_step(cfg: dict, rows: float, live_kv_tokens: float) -> dict:
    """(a) The CCA sublayers of ONE decode step over ``rows`` live rows at
    ``live_kv_tokens`` live positions: their matrices and vectors read once,
    the live positions' K and V read once and each row's new ones written,
    each row's tail read and written; operations: the five matrices and the
    grouped convolution a row, the scores and the weighed values a live
    position a query head."""
    w = _BYTES[cfg["dtype"]]
    L, s = cfg["n_layers"], cca_sizes(cfg)
    nbytes = (L * (cca_matrix_params(cfg) * w + cca_vector_params(cfg) * 4)
              + (live_kv_tokens + rows) * position_bytes(cfg)
              + 2 * rows * slot_tail_bytes(cfg))
    flops = L * (rows * 2 * cca_matrix_params(cfg)
                 + live_kv_tokens * 4 * s["q"])
    return {"flops": float(flops), "bytes": float(nbytes)}


def decode_step_min_bytes(cfg: dict, live_rows: float, live_kv_tokens: float,
                          experts_touched: float) -> float:
    """(b) Least bytes one lockstep decode step must move through HBM: (a)'s
    bytes, the router, norms and scales and the ``experts_touched`` routed
    experts of each layer read once, the table read once as the head (the
    rows it gives as the embedding are a row a live row). At 64 rows, 15.7
    experts and 224 k positions: 10.0 GB, of which the experts are 5.5, the
    live pages 3.2 and the table 1.07."""
    w = _BYTES[cfg["dtype"]]
    return (cfg["n_layers"] * layer_bytes(cfg, experts_touched)
            + (live_kv_tokens + live_rows) * position_bytes(cfg)
            + 2 * live_rows * slot_tail_bytes(cfg)
            + cfg["vocab"] * cfg["dim"] * w + live_rows * cfg["dim"] * w
            + cfg["dim"] * 4)


def expert_product(cfg: dict, rows: float, experts_touched: float) -> dict:
    """(c) ONE product of an expert block over ``rows`` assignment rows (any
    of the three: ``w_gate``, ``w_up`` or ``w_down``): 2 x rows x dim x
    moe_dim operations, and least bytes = the touched experts' matrix read
    once plus the rows in and out."""
    w = _BYTES[cfg["dtype"]]
    D, F = cfg["dim"], cfg["moe_dim"]
    return {"flops": float(2 * rows * D * F),
            "bytes": float(experts_touched * D * F * w + rows * (D + F) * w)}


def expected_touched(cfg: dict, tokens: float) -> float:
    """Distinct held experts that ``tokens`` tokens reach, each choosing
    top-k of all E uniformly: held x (1 - (1 - k/E)^tokens). At 64 tokens,
    top-1 of 16: 15.7."""
    E, k = cfg["n_experts"], cfg["moe_top_k"]
    return cfg["experts_held"] * (1.0 - (1.0 - k / E) ** tokens)
