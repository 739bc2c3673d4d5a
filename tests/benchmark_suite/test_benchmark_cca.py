"""What PR 45 adds to the benchmark, on the CPU: the new configuration's
entry, the zaya_like reference against sublayers written out by hand, the
runner on a tiny cell of ``benchmark_tiny``'s temporary copy (and a broken
tail carry coming out not ``correct``), the new reader on recorded input, and
the byte and operation counts against hand counts. Nothing here counts the
benchmark's cells or names another cell's entries."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny_cca as tiny_cca
from benchmarks import common, roofline, roofline_cca
from benchmarks import weights_zaya as weights
from benchmarks.reference import zaya_like as ref
from benchmarks.runners import serve_cca

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELL = "zaya1-8b.reasonbatch64-cca"
# The catalog row's ``config`` (model-configs/architectures.jsonl, row
# ZAYA1-8B), copied here: every number must be in the file under the same key
# unless ``reduced`` names the key.
CATALOG = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048,
    "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272,
}
REDUCED = ["num_hidden_layers", "max_position_embeddings"]
NEW = ("cca_roofline", "decode_roofline", "expert_ffn_roofline")
SHARED = ("prefill_attn_share", "prefill_ffn_share", "step_attn_share",
          "step_ffn_share", "scope_unnamed_share")
ASSUMED = ("residual_scaling", "cca_convolutions", "qk_mean", "qk_norm",
           "rotary", "value_shift", "tail", "router_carry", "router_mlp",
           "experts", "head", "weights", "serve", "limits")


def config_file():
    return common.load_json(os.path.join(
        REPO, "benchmarks", "configs", "zaya1-8b.json"))


def model():
    return serve_cca.model_dict(config_file(), "serve")


def tiny_model():
    return serve_cca.model_dict(tiny_cca.CONFIG, "serve")


def test_the_new_configuration_entry():
    entry = {c["name"]: c for c in BENCH["configs"]}["zaya1-8b"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["source"] == \
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    assert entry["file"] == "benchmarks/configs/zaya1-8b.json"
    assert entry["reduced"] == REDUCED
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    body = config_file()
    assert body["source"] == entry["source"] and body["reduced"] == REDUCED
    for key, value in CATALOG.items():
        if key not in REDUCED:
            assert body[key] == value, key
    # the cut: a pipeline stage by depth with the table; every width, head
    # count, expert count and vocabulary row as published
    assert {k: body[k] for k in REDUCED} == {
        "num_hidden_layers": 14, "max_position_embeddings": 16384}
    assert body["published"] == {k: CATALOG[k] for k in REDUCED}
    assert 12 <= body["num_hidden_layers"] <= 24  # four at least; 24 fit
    for key in REDUCED:
        assert not key.endswith(("_dim", "_rank", "_size"))
    assert all(isinstance(v, str) and len(v) > 40
               for v in body["assumed"].values())
    for reading in ASSUMED:
        assert reading in body["assumed"], reading
    assert "2.9 times" in body["deployment"]
    assert body["serve_cca"] == "zaya_like"
    assert 0 < body["serve"]["limits"]["gap_mean"] < 1
    assert set(body["serve"]["limits"]) == {"gap_mean"}


def test_the_cell_lists_what_the_issue_names():
    """THIS cell's configuration, traffic, chips, end-to-end list exactly and
    its per-layer list with >=; nothing about how many cells there are or
    about any other cell (a later PR adds to both)."""
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "zaya1-8b", "reasonbatch64-cca", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    own = {f"{stem}.reasonbatch64-cca" for stem in NEW}
    assert listed >= own | set(SHARED) and len(own) <= 3
    # no recurrent mixer: not of the two mixer shares, nor of a sibling's splits
    assert not {"prefill_mixer_share", "step_mixer_share"} & listed
    assert not any(name.endswith(".reasonbatch64") for name in listed)
    for m in BENCH["per_layer"]:
        if m["name"] in own:
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
            assert (m["unit"], m["better"], m["source"], m["layer"]) == (
                "%", "higher", "device_trace", "kernels")
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]]
    assert e2e == ["itl_p95_ms", "setup_s"]
    for name in NEW:  # the new files are found under their full names
        assert common.metric_spec(REPO, f"{name}.reasonbatch64-cca")["reader"] \
            == "cca_roofline"
    for name in SHARED:
        assert common.metric_spec(REPO, name)["reader"] == "scope_share"
    mix = common.load_json(os.path.join(REPO, "benchmarks", "traffic",
                                        "reasonbatch64-cca.json"))
    sibling = common.load_json(os.path.join(REPO, "benchmarks", "traffic",
                                            "reasonbatch64.json"))
    assert (mix["runner"], mix["kind"]) == ("serve_cca", "backlog")
    for key in ("prompt_tokens", "output_tokens", "requests", "block",
                "check_requests", "schedule_seed", "pre_roll_s"):
        assert mix[key] == sibling[key], key  # the sibling's lengths on purpose
    assert mix["prompt_tokens"] == {"median": 1024, "sigma": 0.6, "min": 256,
                                    "max": 4096}
    assert mix["output_tokens"] == {"median": 4096, "sigma": 0.5, "min": 1024,
                                    "max": 12288}
    assert (mix["requests"], mix["block"]) == (256, 64)
    assert (mix["check_requests"], mix["schedule_seed"], mix["pre_roll_s"]) \
        == (3, 20260927, 30.0)
    sizes = config_file()["serve"]
    assert (sizes["max_batch"], sizes["max_position_embeddings"],
            sizes["prefill_chunk"], sizes["kv_pool_tokens"],
            sizes["queue_depth"]) == (64, 16384, 1024, 425984, 320)
    assert sizes["kv_pool_tokens"] == 64 * 6656 >= 393216
    assert mix["runner"] in config_file()
    assert len(BENCH["per_layer"]) <= 128
    assert len(json.dumps(BENCH, indent=1)) < 64 << 10


def test_the_longest_request_fits_the_configuration():
    from benchmarks import traffic
    from benchmarks.runners import serve_family

    mix = common.load_json(os.path.join(REPO, "benchmarks", "traffic",
                                        "reasonbatch64-cca.json"))
    m, sizes = model(), config_file()["serve"]
    reqs = traffic.backlog(mix, 2**31 + 3, m["vocab"])
    assert len(reqs) == 256 <= sizes["queue_depth"]
    assert max(len(r.prompt) + r.max_new for r in reqs) <= m["max_seq"] == 16384
    assert min(len(r.prompt) for r in reqs) >= 256
    assert min(r.max_new for r in reqs) >= 1024
    # ids from all 262 272 rows
    assert 250_000 < max(int(r.prompt.max()) for r in reqs[:8]) < 262272
    pieces = serve_family._piece_buckets(
        reqs, sizes["prefill_chunk"], m["max_seq"],
        lambda n, top: min(max(8, 1 << (n - 1).bit_length()), top))
    assert max(pieces) == 1024 and all(b <= 1024 for b in pieces)
    # a whole cycle's positions at once (what 64 admissions reserve) fit
    cycle = reqs[:mix["block"]]
    reserved = sum(len(r.prompt) + r.max_new for r in cycle)
    assert 350_000 < reserved <= sizes["kv_pool_tokens"]
    slices = sum(-(-len(r.prompt) // 1024) for r in cycle)
    assert slices / sum(r.max_new for r in cycle) * 64 < 0.035


def test_model_dict_and_the_programs_tree():
    from oim_tpu.models import llama

    m = model()
    assert (m["dim"], m["n_heads"], m["n_kv_heads"], m["head_dim"]) == (
        2048, 8, 2, 128)
    assert (m["n_experts"], m["experts_held"], m["moe_top_k"], m["moe_dim"],
            m["router_dim"]) == (16, 16, 1, 2048, 256)
    assert (m["rope_dim"], m["rope_theta"], m["n_layers"], m["vocab"]) == (
        64, 5e6, 14, 262272)
    assert all(isinstance(v, (int, float, str)) for v in m.values())
    cfg = serve_cca.program_config(m)
    assert cfg.pattern == "CE" * 14 and cfg.tie_word_embeddings
    weights.check_against_program(m, jax.eval_shape(
        lambda: llama.init(jax.random.PRNGKey(0), cfg)))
    spec = weights.tree_spec(m)
    assert "lm_head" not in spec
    assert spec["cca_layers/conv1_w"][0] == (14, 2, 10, 128, 128)
    assert spec["expert_layers/moe/router_mlp/w_c"][0] == (14, 256, 16)
    t = tiny_model()
    tcfg = serve_cca.program_config(t)
    weights.check_against_program(t, jax.eval_shape(
        lambda: llama.init(jax.random.PRNGKey(0), tcfg)))


def test_the_special_draws_are_what_assumed_says():
    t = tiny_model()
    tree = weights.make_on_device(3, t)
    g = np.asarray(tree["expert_layers"]["moe"]["router_mlp"]["carry"])
    assert 0.3 <= g.min() and g.max() <= 0.9 and g.std() > 0.1
    for leaf in ("res_a", "res_c"):
        a = np.asarray(tree["cca_layers"][leaf])
        assert abs(a.mean() - 1) < 0.03 and 0.07 < a.std() < 0.13
    for leaf in ("res_b", "res_e"):
        a = np.asarray(tree["expert_layers"][leaf])
        assert abs(a.mean()) < 0.01 and 0.01 < a.std() < 0.03
    tau = np.asarray(tree["cca_layers"]["tau"])
    assert 0.6 < tau.min() and tau.max() < 1.4 and tau.std() > 0
    assert np.asarray(tree["final_norm"]).tolist() == [1.0] * 64
    # a layer drawn alone (as the reference draws it) is the layer of the
    # stack, bit for bit
    root = weights.root_key(3)
    for group in weights.GROUPS.values():
        alone = jax.jit(lambda r, g=group: weights.layer_slice(r, t, g, 2))(root)
        whole = jax.tree.map(lambda a: a[2], tree[group])
        for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(whole)):
            np.testing.assert_array_equal(a, b)


def test_a_program_without_the_family_is_refused_in_one_line(monkeypatch):
    from oim_tpu.models import llama

    class Before(llama.Config):
        def __init__(self, **fields):
            if "cca_time0" in fields:
                raise TypeError("Config.__init__() got an unexpected keyword "
                                "argument 'cca_time0'")

    monkeypatch.setattr(llama, "Config", Before)
    with pytest.raises(SystemExit, match="cannot express the zaya_like"):
        serve_cca.program_config(model())
    ctx = common.Context(cell={}, config=config_file(), traffic={}, seed=1,
                         seconds=1.0, trace=False, workdir="", t0=0.0,
                         platform="cpu")
    with pytest.raises(SystemExit, match="cannot express"):
        serve_cca.run(ctx)


# -- the reference against sublayers written out by hand (float64) ------------

def _f64(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)


def _n(x, w, eps=1e-5):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def hand_cca(x, w, m):
    """The CCA sublayer position by position, every shift an index t - 1."""
    T = x.shape[0]
    H, Hkv, d, G = m["n_heads"], m["n_kv_heads"], m["head_dim"], 2
    R, theta = m["rope_dim"], m["rope_theta"]
    h = _n(x, w["norm"])
    p = h @ w["w_qk"]
    u = np.zeros_like(p)
    for t in range(T):
        before = p[t - 1] if t else 0.0
        u[t] = w["conv0_w"][0] * before + w["conv0_w"][1] * p[t] + w["conv0_b"]
    z = np.zeros((T, H + Hkv, d))
    for t in range(T):
        for g in range(H + Hkv):
            now = u[t, g * d:(g + 1) * d]
            before = u[t - 1, g * d:(g + 1) * d] if t else np.zeros(d)
            z[t, g] = before @ w["conv1_w"][0, g] + now @ w["conv1_w"][1, g]
    z += w["conv1_b"].reshape(H + Hkv, d)
    q0, k0 = p[:, :H * d].reshape(T, H, d), p[:, H * d:].reshape(T, Hkv, d)
    q, k = np.zeros((T, H, d)), np.zeros((T, Hkv, d))
    for i in range(H):
        q[:, i] = z[:, i] + 0.5 * (q0[:, i] + k0[:, i // G])
    for j in range(Hkv):
        k[:, j] = z[:, H + j] + 0.5 * (q0[:, j * G:(j + 1) * G].mean(1)
                                       + k0[:, j])
    q = q * d ** 0.5 / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-5)
    k = k * d ** 0.5 / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-5)
    k = k * w["tau"][:, None]

    def rotate(a):
        out = a.copy()
        for t in range(T):
            for i in range(R // 2):
                ang = t * theta ** (-2.0 * i / R)
                a1, a2 = a[t, :, i], a[t, :, i + R // 2]
                out[t, :, i] = a1 * np.cos(ang) - a2 * np.sin(ang)
                out[t, :, i + R // 2] = a2 * np.cos(ang) + a1 * np.sin(ang)
        return out

    q, k = rotate(q), rotate(k)
    s = h @ w["w_v"]
    v = np.zeros((T, Hkv, d))
    v[:, 0] = s[:, :d]
    v[1:, 1] = s[:-1, d:]
    o = np.zeros((T, H, d))
    for i in range(H):
        score = q[:, i] @ k[:, i // G].T * d ** -0.5
        score = np.where(np.tril(np.ones((T, T), bool)), score, -np.inf)
        prob = np.exp(score - score.max(-1, keepdims=True))
        o[:, i] = prob / prob.sum(-1, keepdims=True) @ v[:, i // G]
    out = o.reshape(T, H * d) @ w["wo"]
    return (w["res_a"] * x + w["res_b"]) + (w["res_c"] * out + w["res_e"])


def hand_experts(x, w, prev, m):
    def gelu(a):
        return 0.5 * a * (1 + np.tanh(np.sqrt(2 / np.pi)
                                      * (a + 0.044715 * a ** 3)))

    h, r = _n(x, w["norm"]), w["moe"]["router_mlp"]
    state = h @ r["w_down"] + r["b_down"] + r["carry"] * prev
    a = gelu(_n(state, r["norm"]) @ r["w_a"] + r["b_a"])
    s = gelu(a @ r["w_b"] + r["b_b"]) @ r["w_c"] + r["b_c"]
    prob = np.exp(s - s.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    chosen = (prob + w["moe"]["bias"]).argmax(-1)
    out = np.zeros_like(x)
    for t, e in enumerate(chosen):
        g, up = h[t] @ w["moe"]["w_gate"][e], h[t] @ w["moe"]["w_up"][e]
        out[t] = prob[t, e] * ((g / (1 + np.exp(-g)) * up)
                               @ w["moe"]["w_down"][e])
    return ((w["res_a"] * x + w["res_b"]) + (w["res_c"] * out + w["res_e"]),
            state)


@pytest.mark.parametrize("layer", [0, 2])
def test_reference_cca_against_a_hand_written_one(layer):
    m = {**tiny_model(), "n_heads": 4}
    w = weights.layer_slice(weights.root_key(5), m, "cca_layers", layer)
    x = jax.random.normal(jax.random.PRNGKey(layer), (19, 64))
    with jax.default_matmul_precision("highest"):
        got = ref.cca_forward(x, w, m)
    np.testing.assert_allclose(
        got, hand_cca(np.asarray(x, np.float64), _f64(w), m), atol=2e-5)


@pytest.mark.parametrize("layer", [0, 1])
def test_reference_experts_against_hand_written_ones(layer):
    m = tiny_model()
    w = weights.layer_slice(weights.root_key(5), m, "expert_layers", layer)
    x = jax.random.normal(jax.random.PRNGKey(10 + layer), (23, 64))
    prev = jax.random.normal(jax.random.PRNGKey(20 + layer), (23, 16))
    with jax.default_matmul_precision("highest"):
        got, state = ref._expert_ffn(
            x, w, prev, m, ref._programs(ref._hashable(m), False))
    want, want_state = hand_experts(
        np.asarray(x, np.float64), _f64(w), np.asarray(prev, np.float64), m)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5)


def test_the_control_reads_worse_than_the_reference_reads_itself():
    m = tiny_model()
    prompt = list(range(3, 40))
    lg = ref.logits_many(7, m, [prompt], [np.arange(36, 37)])[0]
    served = [int(jnp.argmax(lg[0]))]
    for _ in range(7):
        seq = prompt + served
        lg = ref.logits_many(7, m, [seq], [np.arange(len(seq) - 1, len(seq))])[0]
        served.append(int(jnp.argmax(lg[0])))
    sample = [(prompt, served)]
    assert ref.served_gaps_many(7, m, sample)[0].max() == 0.0
    control = ref.served_gaps_many(7, m, sample, control=True)[0]
    assert control.mean() > 1e-3


# -- the runner on a tiny cell -------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_cca.make_root(str(tmp_path_factory.mktemp("bench-cca")))


def test_the_cca_runner_runs_a_tiny_cell(root, capsys):
    import benchmark_tiny as tiny

    line, text = tiny.run_cell(root, tiny_cca.CELL, 2**31 + 11, 2.5, 0,
                               capsys, earlier=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 12
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert "warmed bucket=32" in text and "compiles_in_window=0" in text
    assert "correct? number=gap_mean" in text
    assert f"'state_bytes': {tiny_cca.TAIL_BYTES}" in text
    assert f"'state_bytes_by_kind': {{'cca': {tiny_cca.TAIL_BYTES}}}" in text


def test_the_cca_runner_traced_reports_nothing_from_a_device(root, capsys):
    import benchmark_tiny as tiny

    line = tiny.run_cell(root, tiny_cca.CELL, 7, 2.5, 1, capsys)
    assert line["correct"] is True
    # every per-layer entry of this cell reads the device trace: none on the CPU
    assert line["metrics"] == {}


def test_a_broken_tail_carry_is_not_correct(root, capsys, monkeypatch):
    """A mixing that hands out an empty tail: every slice after a prompt's
    first, and every decode step, reads zeros where the position before it
    stood. The run serves, fails no request, and is not ``correct``."""
    import benchmark_tiny as tiny
    from oim_tpu.ops import cca
    from oim_tpu.serve import engine

    real = cca.mix

    def forgetful(*args, **kwargs):
        q, k, v, tail = real(*args, **kwargs)
        return q, k, v, jnp.zeros_like(tail)

    monkeypatch.setattr(cca, "mix", forgetful)
    engine._target_programs.cache_clear()
    try:
        line, text = tiny.run_cell(root, tiny_cca.CELL, 11, 2.0, 0, capsys,
                                   earlier=True)
    finally:
        monkeypatch.undo()
        engine._target_programs.cache_clear()
    assert line["correct"] is False and line["failed"] == 0
    assert "compiles_in_window=0" in text


def test_check_limits_family_reads_sound_and_control(root, capsys):
    from benchmarks import check_limits_family

    assert check_limits_family.main(
        ["--workload", tiny_cca.CELL, "--seeds", "5", "--seconds", "1.5"],
        platform="cpu", root=root) == 0
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("LIMITS ")][-1]
    got = json.loads(line[len("LIMITS "):])
    assert got["correct"] is True and got["sound"]["gap_mean"] <= 1e-4
    assert got["control"]["gap_mean"] > 10 * max(got["sound"]["gap_mean"], 1e-4)
    assert got["control_correct"] is False


# -- counts against hand arithmetic at the published sizes --------------------

def test_what_the_stage_holds():
    m = model()
    assert roofline_cca.cca_matrix_params(m) + roofline_cca.cca_vector_params(m) \
        - 5 * 2048 == 5_575_682
    assert roofline_cca.router_params(m) == 660_768
    assert roofline_cca.expert_params(m) == 12_582_912
    assert 3.44e9 < roofline_cca.held_params(m) < 3.45e9
    assert 6.88e9 < roofline_cca.weight_bytes(m) < 6.92e9
    assert 8.83e9 < roofline_cca.held_params({**m, "n_layers": 40}) < 8.85e9
    assert roofline_cca.slot_tail_bytes(m) == 14 * 2688 * 4
    assert roofline_cca.position_bytes(m) == 14 * 1024
    sizes = config_file()["serve"]
    pool = sizes["kv_pool_tokens"] * roofline_cca.position_bytes(m)
    assert 6.10e9 < pool < 6.12e9
    resident = (roofline_cca.weight_bytes(m) + pool
                + sizes["max_batch"] * roofline_cca.slot_tail_bytes(m))
    assert 0.60 < resident / 17179869184 < 0.85  # 13.0 of 16 GB


def test_decode_step_counts():
    m = model()
    least = roofline_cca.decode_step_min_bytes(m, 64, 224_000, 15.7)
    assert 9.9e9 < least < 10.2e9
    experts = 14 * 15.7 * roofline_cca.expert_params(m) * 2
    pages = 224_064 * 14 * 1024
    table = 262272 * 2048 * 2
    assert 0.53 < experts / least < 0.57 and 0.31 < pages / least < 0.33
    assert 0.10 < table / least < 0.12
    # one more live position costs its 14 KiB, one more expert its 25 MB
    assert roofline_cca.decode_step_min_bytes(m, 64, 224_001, 15.7) - least \
        == pytest.approx(14 * 1024)
    assert roofline_cca.decode_step_min_bytes(m, 64, 224_000, 16.7) - least \
        == pytest.approx(14 * roofline_cca.expert_params(m) * 2)
    step = roofline_cca.cca_step(m, 64, 224_000)
    assert step["bytes"] == pytest.approx(
        14 * (5_570_560 * 2 + 15_362 * 4)
        + 224_064 * 14 * 1024 + 2 * 64 * 14 * 2688 * 4)
    assert roofline.roofline_seconds(
        step, roofline.peaks("TPU v5 lite"))[1] == "memory"
    assert step["flops"] == pytest.approx(
        14 * (64 * 2 * 5_570_560 + 224_000 * 4 * 1024))


def test_expert_product_counts():
    m, peak = model(), roofline.peaks("TPU v5 lite")
    assert roofline_cca.expected_touched(m, 64) == pytest.approx(15.743, abs=1e-3)
    assert roofline_cca.expected_touched(m, 1024) == pytest.approx(16.0)
    step = roofline_cca.expert_product(m, 64, 15.7)
    assert step["bytes"] == pytest.approx(15.7 * 2048 * 2048 * 2
                                          + 64 * 4096 * 2)
    assert roofline.roofline_seconds(step, peak)[1] == "memory"
    chunk = roofline_cca.expert_product(m, 1024, 16.0)
    assert chunk["flops"] == 2 * 1024 * 2048 * 2048
    assert roofline.roofline_seconds(chunk, peak)[1] == "memory"  # 64 rows an expert


# -- the new reader on recorded input ------------------------------------------

def recorded(step_ops, prefill_ops):
    """A trace of two decode steps and one prefill: [name, start, ns]."""
    ops, mods = [], []
    t = 1000
    for run, names in (("jit_step(1)", step_ops), ("jit_step(1)", step_ops),
                       ("jit_prefill(2)", prefill_ops)):
        start = t
        for name, ns in names:
            ops.append([name, t, ns])
            t += ns + 10
        mods.append([run, start, t - start])
        t += 1000
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.window", 0, t + 1000]]}]}]}


GMM = ("%ragged-dot-none{} = bf16[{},2048]{{1,0:T(8,128)(2,1)S(1)}} "
       "custom-call(s32[1]{{0:T(128)}} %a, s32[225]{{0:T(512)S(1)}} %b)")
MIX = ("%fusion.{} = f32[{},{},1280]{{2,1,0:T(8,128)}} fusion("
       "bf16[14,2048,1280]{{2,1,0:T(8,128)(2,1)}} %get-tuple-element.4)")
KERNEL = ("%blk_attn.3 = bf16[64,8,128]{2,1,0:T(8,128)(2,1)} custom-call("
          "s32[64]{0} %a, bf16[14,26625,16,2,128]{4,3,2,1,0} %pool)")
OUT = "%fusion.77 = bf16[64,1,2048]{2,1,0:T(8,128)(2,1)} fusion(bf16[64,1024] %o)"
BATCHED = ("%fusion.90 = bf16[16,{},2048]{{2,1,0:T(8,128)(2,1)S(1)}} fusion("
           "bf16[14,16,2048,2048]{{3,2,1,0:T(8,128)(2,1)}} %get-tuple-element.7)")
OTHER = "%fusion.5 = f32[64,2048]{1,0:T(8,128)} fusion(f32[64,2048] %p)"
STEP_MIX, SLICE_MIX = MIX.format(11, 64, 1), MIX.format(12, 1, 1024)
SCOPES = {
    STEP_MIX: "jit(step)/while/body/closed_call/blk_qkv/cca_mix/dot_general",
    SLICE_MIX: "jit(prefill)/while/body/closed_call/blk_qkv/cca_mix/dot_general",
    KERNEL: "jit(step)/while/body/closed_call/blk_attn/pallas_call",
    OUT: "jit(step)/while/body/closed_call/blk_out/dot_general",
    BATCHED.format(64):
        "jit(step)/while/body/closed_call/blk_ffn/moe_gmm/dot_general",
    BATCHED.format(128):
        "jit(prefill)/while/body/closed_call/blk_ffn/moe_gmm/dot_general",
}


def metric_args(name):
    return common.load_json(os.path.join(
        REPO, "benchmarks", "metrics", f"{name}.reasonbatch64-cca.json"))["args"]


def reader_result(trace, scopes=None, shapes_model=None, **stats):
    result = {"trace": trace, "stats": stats, "trace_dir": "recorded",
              "device": {"kind": "TPU v5 lite", "platform": "tpu"},
              "shapes": {"model": shapes_model or model(), "live_rows": 64.0,
                         "live_kv_tokens": 224_000.0}}
    if trace is not None:  # what scopes_by_operation would read off the file
        scopes = scopes or {}
        result["_scoped_ops"] = [
            (s, d / 1e9, name, scopes.get(name, ""))
            for name, s, d in trace["planes"][0]["lines"][1]["events"]]
    return result


def test_cca_reader_the_step_and_the_sublayer(capsys):
    reader = common.plugin(REPO, "readers", "cca_roofline")
    m, peak = model(), roofline.peaks("TPU v5 lite")
    trace = recorded(
        [(STEP_MIX, 1_000_000), (KERNEL, 4_000_000), (OUT, 500_000),
         (BATCHED.format(64), 8_000_000), (OTHER, 1_500_000)],
        [(SLICE_MIX, 3_000_000), (OTHER, 5_000_000)])
    result = reader_result(trace, SCOPES, experts_touched=15.7)
    got = reader.read(result, metric_args("decode_roofline"))
    least = roofline_cca.decode_step_min_bytes(m, 64.0, 224_000.0, 15.7)
    assert got == pytest.approx(100 * least / 819e9 / 15.00004e-3)
    assert 0 < got < 100
    got = reader.read(result, metric_args("cca_roofline"))
    least = roofline.roofline_seconds(
        roofline_cca.cca_step(m, 64.0, 224_000.0), peak)[0]
    assert got == pytest.approx(100 * least / 5.5e-3) and 0 < got < 100
    text = capsys.readouterr().out
    assert "15.000 ms a step" in text and "5.500 ms a step" in text
    assert text.count("bound: memory") == 2
    # a program without the scopes or the counter (the parent), a cell of
    # another family, a run without a trace: nothing, and no raise
    bare = reader_result(trace, {}, experts_touched=15.7)
    assert reader.read(bare, metric_args("cca_roofline")) is None
    assert reader.read(reader_result(trace, SCOPES),
                       metric_args("decode_roofline")) is None
    for name in ("cca_roofline", "decode_roofline", "expert_ffn_roofline"):
        assert reader.read(reader_result(None), metric_args(name)) is None
        other = reader_result(trace, SCOPES, {"pattern": "MEM"},
                              experts_touched=3)
        assert reader.read(other, metric_args(name)) is None
    idle = reader_result(recorded([], []), {}, experts_touched=1.0)
    idle["trace"]["planes"][0]["lines"][0]["events"] = []  # no module ran
    for name in ("cca_roofline", "decode_roofline", "expert_ffn_roofline"):
        assert reader.read(idle, metric_args(name)) is None


def test_cca_reader_expert_products(capsys):
    """Decode: the batched products under the scope. Prefill: the batched
    products at the capacity (by scope) AND the spilled rows' grouped
    products (by their HLO line: the compiler strips their path), against
    THREE products a layer at the slice's own length, read off the cca_mix
    scope."""
    reader = common.plugin(REPO, "readers", "cca_roofline")
    m, peak = model(), roofline.peaks("TPU v5 lite")
    args = metric_args("expert_ffn_roofline")

    def least(tokens, touched):
        return 3 * 14 * roofline.roofline_seconds(
            roofline_cca.expert_product(m, tokens, touched), peak)[0]

    step = [(STEP_MIX, 100), (BATCHED.format(64), 5_000_000),
            (BATCHED.format(64), 4_000_000), (OTHER, 900)]
    chunk = [(SLICE_MIX, 1_000_000), (BATCHED.format(128), 6_000_000),
             (GMM.format("", 1024), 3_000_000),
             (GMM.format(".1", 1024), 2_000_000), (OTHER, 7_000_000)]
    got = reader.read(reader_result(recorded(step, chunk), SCOPES,
                                    experts_touched=15.7), args)
    want = 2 * least(64.0, 15.7) + least(1024, 16.0)
    assert got == pytest.approx(100 * want / (2 * 9e-3 + 11e-3))
    assert 0 < got < 100
    text = capsys.readouterr().out
    assert "expert products in prefill: 1 slices, 11.0 ms" in text
    assert "expert products in decode: 2 steps, 9.000 ms a step" in text
    assert reader.read(reader_result(recorded(step, chunk), SCOPES), args) is None
    # by the grouped products' text alone every batched product is missed
    by_text = reader.read(reader_result(recorded(step, chunk), {
        SLICE_MIX: SCOPES[SLICE_MIX]}, experts_touched=15.7), args)
    assert by_text == pytest.approx(100 * least(1024, 16.0) / 5e-3)
