"""Operations and least bytes of what the latent-attention family adds,
from shapes alone (the sibling of ``roofline.py``, whose rule holds here
too: a share above 100 % means a count here is too high or the time leaves
work out — fix the count, never clamp).

``cfg`` is the "model" group ``runners/serve_family.py`` makes of a
configuration file.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def latent_entry_bytes(cfg: dict) -> int:
    """What one position keeps in one layer: the normed latent and the
    shared rotated key dims (the program's padding of the entry to whole
    lanes is its own, not the least)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * _BYTES[cfg["dtype"]]


def latent_decode_attention(cfg: dict, live_rows: float,
                            live_kv_tokens: float) -> dict:
    """The absorbed latent attention of ONE decode step over all layers:
    least bytes (every live position's cache entry read once, each row's
    new entry written, ``wkv_b`` read once a layer for the two
    absorptions) and operations (a row's query taken into the latent
    space, scored against and summed over its positions, taken out
    again). At 32 rows of 10 k positions and 5 layers: 1.85 GB (2.3 ms at
    the HBM peak) against 0.11 TFLOP (0.6 ms): memory bound; the reader
    prints which."""
    w = _BYTES[cfg["dtype"]]
    H, r = cfg["n_heads"], cfg["kv_lora_rank"]
    rope, nope, v = (cfg["qk_rope_head_dim"], cfg["qk_nope_head_dim"],
                     cfg["v_head_dim"])
    L = cfg["n_layers"]
    nbytes = L * ((live_kv_tokens + live_rows) * latent_entry_bytes(cfg)
                  + r * H * (nope + v) * w)
    flops = L * (2 * live_kv_tokens * H * ((r + rope) + r)
                 + 2 * live_rows * H * r * (nope + v))
    return {"flops": float(flops), "bytes": float(nbytes)}


def expected_experts_touched(cfg: dict, rows: float) -> float:
    """Distinct experts that ``rows`` rows reach, each choosing top-k of E
    uniformly: E * (1 - (1 - k/E)^rows)."""
    E, k = cfg["n_experts"], cfg["moe_top_k"]
    return E * (1.0 - (1.0 - k / E) ** rows)


def grouped_product(cfg: dict, rows: int, experts_touched: float) -> dict:
    """ONE grouped product of an expert layer over ``rows`` assignment
    rows (tokens x top-k): 2 * rows * dim * moe_dim operations whichever of
    the three projections it is, and least bytes = the touched experts'
    matrices read once plus the rows in and out."""
    w = _BYTES[cfg["dtype"]]
    D, F = cfg["dim"], cfg["moe_dim"]
    return {"flops": float(2 * rows * D * F),
            "bytes": float(experts_touched * D * F * w + rows * (D + F) * w)}
