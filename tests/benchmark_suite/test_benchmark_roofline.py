"""roofline.py against numbers worked by hand for both configurations."""

import json
import os

import pytest

from benchmarks import common, roofline as ob

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _model(name, runner="serve"):
    return common.model_dict(common.load_json(
        os.path.join(REPO, "benchmarks", "configs", f"{name}.json")), runner)


ATTN = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096  # 41,943,040
FFN = 3 * 4096 * 14336                                # 176,160,768


def test_mistral_layer_and_totals():
    m = _model("mistral-7b")
    assert ob.layer_params(m) == ATTN + FFN + 2 * 4096 == 218_112_000
    L = m["n_layers"]
    assert ob.total_params(m) == L * 218_112_000 + 2 * 32768 * 4096 + 4096
    assert ob.matmul_params_per_token(m) == L * (ATTN + FFN) + 4096 * 32768


def test_mixtral_layer_and_active():
    m = _model("mixtral-8x7b")
    assert ob.layer_params(m) == ATTN + 4096 * 8 + 8 * FFN + 2 * 4096 == 1_451_270_144
    assert ob.layer_params(m, experts=2) == ATTN + 4096 * 8 + 2 * FFN + 2 * 4096
    assert ob.matmul_params_per_token(m) == m["n_layers"] * (
        ATTN + 4096 * 8 + 2 * FFN) + 4096 * 32000
    assert ob.expected_experts_touched(m, 1) == pytest.approx(2.0)
    assert ob.expected_experts_touched(m, 32) == pytest.approx(8 * (1 - 0.75 ** 32))


def test_train_flops_count_causal_attention_at_half():
    m = _model("mistral-7b", "train")
    L = m["n_layers"]
    matmul = 6 * (L * (ATTN + FFN) + 4096 * 32768)
    attn = L * 3 * (4 * 4096 * 4096) / 2
    assert ob.train_flops_per_token(m, 4096) == matmul + attn
    # the program's own count credits the masked half too
    assert ob.train_flops_per_token(m, 4096) < matmul + 2 * attn


def test_decode_bytes_weights_once_plus_live_kv():
    m = _model("mistral-7b")
    L = m["n_layers"]
    per_layer = (ATTN + FFN) * 2 + 2 * 4096 * 4
    kv_pos = 2 * 1024 * 2
    want = L * (per_layer + (10_000 + 32) * kv_pos) + 4096 * 32768 * 2 + 4096 * 4
    assert ob.decode_step_min_bytes(m, 32, 10_000) == want
    x = _model("mixtral-8x7b")
    touched = ob.expected_experts_touched(x, 32)
    per_layer = ATTN * 2 + 8 * 4096 * 4 + touched * FFN * 2 + 2 * 4096 * 4
    want = x["n_layers"] * (per_layer + (5_000 + 32) * kv_pos) \
        + 4096 * 32000 * 2 + 4096 * 4
    assert ob.decode_step_min_bytes(x, 32, 5_000) == pytest.approx(want)


def test_flash_counts_and_bound():
    m = _model("mistral-7b", "train")
    fwd, bwd = ob.flash_forward(m, 2, 4096), ob.flash_backward(m, 2, 4096)
    assert fwd["flops"] == 2 * 2 * 2 * 32 * 4096 * 4096 * 128 / 2
    assert bwd["flops"] == 2 * fwd["flops"]
    assert fwd["bytes"] == 2 * 4096 * 128 * 2 * (64 + 16) + 2 * 4096 * 32 * 4
    peak = ob.peaks("TPU v5 lite")
    t, bound = ob.roofline_seconds(fwd, peak)
    assert bound == "compute" and t == fwd["flops"] / 197e12


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        ob.peaks("cpu")
    assert json.dumps(ob.peaks("TPU v5 lite")["hbm_bytes_per_s"]) == "819000000000.0"
