"""What PR 42 adds to the benchmark, on the CPU: the new configuration's
entry, the gigachat3_5_like reference against blocks written out by hand,
the runner on a tiny cell of ``benchmark_tiny``'s temporary copy (and a
broken state carry coming out not ``correct``), the new reader on recorded
input, and the byte and operation counts against hand counts. Nothing here
counts the benchmark's cells or names another cell's entries."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny_gdn as tiny_gdn
from benchmarks import common, roofline, roofline_gdn, roofline_kda
from benchmarks import weights_gigachat35 as weights
from benchmarks.reference import gigachat3_5_like as ref
from benchmarks.runners import serve_gdn

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELL = "gigachat35-432b-a28b.reasonbatch64"
# The catalog row's ``config`` (model-configs/architectures.jsonl, row
# GigaChat3.5-432B-A28B), copied here: every number must be in the file under
# the same key unless ``reduced`` names the key.
CATALOG = {
    "vocab_size": 128256, "max_position_embeddings": 262144,
    "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_hidden_layers": 40,
    "nextn_is_sparse": False, "num_attention_heads": 64,
    "n_shared_experts": 1, "n_routed_experts": 256,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "qk_head_dim": 192, "n_group": 1, "topk_group": 1,
    "num_experts_per_tok": 8, "first_k_dense_replace": 3,
    "norm_topk_prob": True, "rope_interleave": True,
    "num_key_value_heads": 64, "hidden_act": "silu", "rms_norm_eps": 1e-06,
    "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32768,
                     "type": "yarn"},
    "attention_bias": False, "norm_type": "ZeroCenteredGatedNorm",
    "layernorm_type": "pre_post", "layernorm_gating_weight": 2,
    "gated_attention": True, "use_shared_expert_sigmoid": False,
    "use_mla_scaling_factor": True,
    "linear_attention_type": "GigaChat35GatedDeltaNet",
    "full_attention_layers": [3, 7, 11, 15, 19, 23, 27, 31, 35, 39],
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "linear_num_key_heads": 32,
    "linear_num_value_heads": 64,
    "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
    "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-06,
    "swiglu_limit": 10, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 2, "model_type": "gigachat3_5",
    "tf_legacy_loss": False,
}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "max_position_embeddings", "num_nextn_predict_layers"]
SPLITS = (
    "itl_p50_ms", "itl_p97_ms", "out_tokens_per_s", "decode_step_ms",
    "prefill_chunk_ms", "prefill_share", "device_idle", "idle_step_roundtrip",
    "idle_unannotated", "experts_touched", "state_pool_bytes",
    "latent_pool_fill", "decode_roofline", "expert_ffn_roofline",
    "gdn_step_roofline", "gdn_scan_roofline", "latent_kernel_roofline")
SHARED = ("prefill_attn_share", "prefill_ffn_share", "prefill_mixer_share",
          "step_attn_share", "step_ffn_share", "step_mixer_share",
          "scope_unnamed_share")


def config_file():
    return common.load_json(os.path.join(
        REPO, "benchmarks", "configs", "gigachat35-432b-a28b.json"))


def model():
    return serve_gdn.model_dict(config_file(), "serve")


def tiny_model():
    return serve_gdn.model_dict(tiny_gdn.CONFIG, "serve")


def test_the_new_configuration_entry():
    entry = {c["name"]: c for c in BENCH["configs"]}["gigachat35-432b-a28b"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["source"] == ("https://huggingface.co/ai-sage/"
                               "GigaChat3.5-432B-A28B/blob/main/config.json")
    assert entry["file"] == "benchmarks/configs/gigachat35-432b-a28b.json"
    assert entry["reduced"] == REDUCED
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    body = config_file()
    assert body["source"] == entry["source"] and body["reduced"] == REDUCED
    for key, value in CATALOG.items():
        if key not in REDUCED:
            assert body[key] == value, key
    # the cut: the leading layer and one period, one rank of sixteen, an
    # eighth of the rows
    assert {k: body[k] for k in REDUCED} == {
        "num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 16032,
        "max_position_embeddings": 16384, "num_nextn_predict_layers": 0}
    assert body["published"] == {k: CATALOG[k] for k in REDUCED}
    assert body["serve"]["layers_held"] == [0, 3, 4, 5, 6]
    # floors: a whole period and four layers behind the leading dense ones,
    # >= 8 experts, >= 1/8 of the rows
    assert body["n_routed_experts"] >= 8
    assert body["vocab_size"] * 8 >= CATALOG["vocab_size"]
    # no width is named as cut, and every assumption carries its reason
    for key in REDUCED:
        assert not key.endswith(("_dim", "_rank", "_size")) or key == "vocab_size"
    assert all(isinstance(v, str) and len(v) > 40
               for v in body["assumed"].values())
    for reading in ("norm", "layernorm_type", "gated_attention",
                    "gated_delta_net", "swiglu_limit", "rope_scaling",
                    "limits"):
        assert reading in body["assumed"]
    assert "sixteen v5e chips" in body["deployment"]
    assert body["serve_gdn"] == "gigachat3_5_like"
    assert 0 < body["serve"]["limits"]["gap_mean"] < 1


def test_the_cell_lists_what_the_issue_names():
    """THIS cell's configuration, traffic, chips, end-to-end list exactly and
    its per-layer list with >=; nothing about how many cells there are or
    about any other cell (a later PR adds to both)."""
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gigachat35-432b-a28b", "reasonbatch64", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    own = {f"{stem}.reasonbatch64" for stem in SPLITS}
    assert listed >= own | set(SHARED) and len(own) <= 18
    for m in BENCH["per_layer"]:
        if m["name"] in own:
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]]
    assert e2e == ["itl_p95_ms", "setup_s"]
    # the new files take precedence over their stems' (common.metric_spec)
    for name in ("gdn_step_roofline", "gdn_scan_roofline",
                 "expert_ffn_roofline", "decode_roofline",
                 "latent_kernel_roofline"):
        assert common.metric_spec(REPO, f"{name}.reasonbatch64")["reader"] \
            == "gdn_roofline"
    for name, reader in (("prefill_share", "trace_modules"),
                         ("latent_pool_fill", "host_stat"),
                         ("state_pool_bytes", "host_stat")):
        assert common.metric_spec(REPO, f"{name}.reasonbatch64")["reader"] \
            == reader
    mix = common.load_json(os.path.join(REPO, "benchmarks", "traffic",
                                        "reasonbatch64.json"))
    assert (mix["runner"], mix["kind"]) == ("serve_gdn", "backlog")
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
            sizes["queue_depth"]) == (64, 16384, 1024, 1048576, 320)
    assert mix["runner"] in config_file()
    assert len(json.dumps(BENCH)) < 64 << 10


def test_the_longest_request_fits_the_configuration():
    from benchmarks import traffic
    from benchmarks.runners import serve_family

    mix = common.load_json(os.path.join(REPO, "benchmarks", "traffic",
                                        "reasonbatch64.json"))
    m, sizes = model(), config_file()["serve"]
    reqs = traffic.backlog(mix, 2**31 + 3, m["vocab"])
    assert len(reqs) == 256 <= sizes["queue_depth"]
    assert max(len(r.prompt) + r.max_new for r in reqs) <= m["max_seq"] == 16384
    assert min(len(r.prompt) for r in reqs) >= 256
    assert min(r.max_new for r in reqs) >= 1024
    assert 15500 < max(int(r.prompt.max()) for r in reqs[:8]) < 16032
    pieces = serve_family._piece_buckets(
        reqs, sizes["prefill_chunk"], m["max_seq"],
        lambda n, top: min(max(8, 1 << (n - 1).bit_length()), top))
    assert max(pieces) == 1024 and all(b <= 1024 for b in pieces)
    # every position of a block's requests at once fits the latent pool
    cycle = reqs[:mix["block"]]
    assert sum(len(r.prompt) + r.max_new for r in cycle) \
        <= sizes["kv_pool_tokens"]
    # slices a token gap in steady state: well under 5 %
    slices = sum(-(-len(r.prompt) // 1024) for r in cycle)
    assert slices / sum(r.max_new for r in cycle) * 64 < 0.035


def test_model_dict_and_the_programs_tree():
    from oim_tpu.models import generate as gen
    from oim_tpu.models import llama

    m = model()
    assert m["pattern"] == "GD*EGEGEGE" and m["n_layers"] == 5
    assert (m["n_experts"], m["experts_held"], m["moe_top_k"]) == (256, 16, 8)
    assert (m["gdn_k_heads"], m["gdn_v_heads"], m["gdn_k_dim"], m["gdn_v_dim"],
            m["gdn_conv"]) == (32, 64, 128, 128, 4)
    assert all(isinstance(v, (int, float, str)) for v in m.values())
    cfg = serve_gdn.program_config(m)
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    weights.check_against_program(m, shapes)
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == roofline_gdn.held_params(m) == llama.num_params(cfg)
    assert gen.state_bytes(cfg) == roofline_gdn.slot_state_bytes(m)
    # the least of a position is its 576 values; the pool pads them to 640
    assert roofline_gdn.position_bytes(m) == 576 * 2
    assert gen.page_bytes(cfg, 1) == 640 * 2
    # the whole model, by the same arithmetic: 430.55 B
    whole = {**m, "experts_held": 256, "vocab": 128256,
             "pattern": serve_gdn.pattern(
                 range(40), CATALOG["full_attention_layers"], 3)}
    assert roofline_gdn.held_params(whole) == llama.num_params(
        llama.GIGACHAT35_432B)
    assert abs(roofline_gdn.held_params(whole) / 430.55e9 - 1) < 1e-4


def test_the_special_draws_follow_the_familys_initialisation():
    m = tiny_model()
    root = weights.root_key(3)
    w = weights.layer_slice(root, m, "gdn_layers", 1)
    step = jax.nn.softplus(w["dt_bias"])
    assert 1e-3 * 0.999 <= float(step.min()) and float(step.max()) <= 0.1 * 1.001
    assert 0.0 <= float(w["A_log"].min()) and float(w["A_log"].max()) <= np.log(16)
    assert w["dt_bias"].shape == (4,) and w["A_log"].shape == (4,)  # a HEAD
    # every norm's weight is drawn, so that its sigmoid does something
    for name in ("norm", "post_norm", "o_norm"):
        assert 0.2 < float(jnp.std(w[name])) < 0.8
    # a layer drawn alone is the layer of the whole tree, bit for bit; the
    # latent block's rope columns are handed over in the program's order
    tree = weights.make(root, m)
    for name, leaf in w.items():
        np.testing.assert_array_equal(leaf, tree["gdn_layers"][name][1])
    for group in ("expert_layers", "ffn_layers"):
        one = weights.layer_slice(root, m, group, 0)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b[0]),
                     one, tree[group])
    one = weights.layer_slice(root, m, "attn_layers", 0)
    r, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    order = np.concatenate([np.arange(r), r + np.arange(0, rope, 2),
                            r + np.arange(1, rope, 2)])
    np.testing.assert_array_equal(tree["attn_layers"]["wkv_a"][0],
                                  one["wkv_a"][:, order])
    np.testing.assert_array_equal(tree["attn_layers"]["wkv_b"][0], one["wkv_b"])


def test_a_program_without_the_family_is_refused_in_one_line(monkeypatch):
    """The parent's ``Config`` has no GatedDeltaNet field: the runner's
    first act ends the run with one line before any weights."""
    import dataclasses

    from oim_tpu.models import llama

    fields = [f for f in dataclasses.fields(llama.Config)
              if not f.name.startswith(("linear_", "full_attention",
                                        "gated_attention", "rope_yarn",
                                        "norm_type", "layernorm_type",
                                        "swiglu", "use_mla"))]
    Parent = dataclasses.make_dataclass(
        "Config", [(f.name, f.type, f) for f in fields], frozen=True)
    monkeypatch.setattr(llama, "Config", Parent)
    with pytest.raises(SystemExit) as err:
        serve_gdn.program_config(model())
    assert "cannot express the gigachat3_5_like family" in str(err.value)
    assert "\n" not in str(err.value)


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", False), ("attention_bias", True),
    ("use_shared_expert_sigmoid", True), ("n_shared_experts", 2),
    ("tie_word_embeddings", True), ("rope_interleave", False),
    ("norm_type", "RMSNorm"), ("layernorm_type", "pre"),
    ("linear_attn_o_norm_eps", 1e-5)])
def test_what_the_family_does_not_implement_is_refused(key, value):
    with pytest.raises(SystemExit, match="gigachat3_5_like family runs"):
        serve_gdn.model_dict({**config_file(), key: value}, "serve")


def test_a_share_or_a_depth_that_does_not_add_up_is_refused():
    with pytest.raises(SystemExit, match="do not divide"):
        serve_gdn.model_dict({**config_file(), "n_routed_experts": 48}, "serve")
    with pytest.raises(SystemExit, match="layers held"):
        serve_gdn.model_dict({**config_file(), "serve": {
            **config_file()["serve"], "layers_held": [0, 3]}}, "serve")
    with pytest.raises(SystemExit, match="group-limited"):
        serve_gdn.model_dict({**config_file(), "n_group": 2}, "serve")


# -- the reference against blocks written out by hand --------------------------

def _n(x, w):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) \
        * 2 / (1 + np.exp(-w))


def _f64(x, w):
    return (np.asarray(x, np.float64),
            jax.tree.map(lambda a: np.asarray(a, np.float64), w))


def hand_gdn(x, w, m):
    """One GatedDeltaNet block in float64 numpy, a position and a head at a
    time."""
    Hk, Hv, dk, dv, K = (m["gdn_k_heads"], m["gdn_v_heads"], m["gdn_k_dim"],
                         m["gdn_v_dim"], m["gdn_conv"])
    x, w = _f64(x, w)
    T = x.shape[0]
    h = _n(x, w["norm"])
    qkv = h @ w["w_qkv"]
    padded = np.concatenate([np.zeros((K - 1, qkv.shape[1])), qkv])
    conv = sum(w["conv_w"][j] * padded[j:j + T] for j in range(K))
    conv = conv / (1 + np.exp(-conv))
    g = -np.exp(w["A_log"]) * np.log1p(np.exp(h @ w["w_a"] + w["dt_bias"]))
    beta = 1 / (1 + np.exp(-(h @ w["w_b"])))
    gate = 2 / (1 + np.exp(-(h @ w["w_z"])))
    out = np.zeros((T, Hv * dv))
    for head in range(Hv):
        kh = head // (Hv // Hk)
        q = conv[:, kh * dk:(kh + 1) * dk]
        k = conv[:, Hk * dk + kh * dk:Hk * dk + (kh + 1) * dk]
        v = conv[:, 2 * Hk * dk + head * dv:2 * Hk * dk + (head + 1) * dv]
        S = np.zeros((dk, dv))
        for t in range(T):
            qt = q[t] / np.sqrt(q[t] @ q[t] + 1e-6) / np.sqrt(dk)
            kt = k[t] / np.sqrt(k[t] @ k[t] + 1e-6)
            S = np.exp(g[t, head]) * S
            S = S + beta[t, head] * np.outer(kt, v[t] - S.T @ kt)
            o = _n(S.T @ qt, w["o_norm"])
            out[t, head * dv:(head + 1) * dv] = \
                o * gate[t, head * dv:(head + 1) * dv]
    return x + _n(out @ w["w_out"], w["post_norm"])


def _hand_swiglu(rows, e, limit):
    g = np.minimum(rows @ e["w_gate"], limit)
    u = np.clip(rows @ e["w_up"], -limit, limit)
    return (g / (1 + np.exp(-g)) * u) @ e["w_down"]


def hand_dense(x, w, m):
    x, w = _f64(x, w)
    out = _hand_swiglu(_n(x, w["norm"]), w, m["swiglu_limit"])
    return x + _n(out, w["post_norm"])


def hand_experts(x, w, m):
    x, w = _f64(x, w)
    h = _n(x, w["norm"])
    mo, limit = w["moe"], m["swiglu_limit"]
    out = _hand_swiglu(h, mo["shared"], limit)
    s = 1 / (1 + np.exp(-(h @ mo["router"])))
    for t in range(x.shape[0]):
        chosen = np.argsort(-(s[t] + mo["bias"]), kind="stable")[:m["moe_top_k"]]
        total = s[t, chosen].sum()
        for e in chosen:
            if m["expert_first"] <= e < m["expert_first"] + m["experts_held"]:
                i = e - m["expert_first"]
                out[t] += s[t, e] / total * m["routed_scale"] * _hand_swiglu(
                    h[t], {k: mo[k][i] for k in ("w_gate", "w_up", "w_down")},
                    limit)
    return x + _n(out, w["post_norm"])


def hand_attention(x, w, m):
    H, r = m["n_heads"], m["kv_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    x, w = _f64(x, w)
    T = x.shape[0]
    h = _n(x, w["norm"])
    # YaRN, written out: low and high from the two turn counts
    d, theta, L = rope, m["rope_theta"], m["yarn_original_max"]
    f = theta ** (-np.arange(0, d, 2) / d)
    low = np.floor(d * np.log(L / (m["yarn_beta_fast"] * 2 * np.pi))
                   / (2 * np.log(theta)))
    high = np.ceil(d * np.log(L / (m["yarn_beta_slow"] * 2 * np.pi))
                   / (2 * np.log(theta)))
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    f = ramp * f / m["yarn_factor"] + (1 - ramp) * f
    scale = (nope + rope) ** -0.5 * (0.1 * np.log(m["yarn_factor"]) + 1) ** 2

    def rotate(v, t):  # pairs (2i, 2i + 1)
        a, b = v[0::2], v[1::2]
        out = np.empty_like(v)
        out[0::2] = a * np.cos(t * f) - b * np.sin(t * f)
        out[1::2] = b * np.cos(t * f) + a * np.sin(t * f)
        return out

    q = (_n(h @ w["wq_a"], w["q_norm"]) @ w["wq_b"]).reshape(T, H, nope + rope)
    ckv = h @ w["wkv_a"]
    c = _n(ckv[:, :r], w["kv_norm"])
    k_r = np.stack([rotate(ckv[t, r:], t) for t in range(T)])
    kv = (c @ w["wkv_b"]).reshape(T, H, nope + dv)
    out = np.zeros((T, H, dv))
    for head in range(H):
        for t in range(T):
            qt = np.concatenate([q[t, head, :nope],
                                 rotate(q[t, head, nope:], t)])
            keys = np.concatenate([kv[:t + 1, head, :nope], k_r[:t + 1]], 1)
            s = keys @ qt * scale
            p = np.exp(s - s.max())
            out[t, head] = (p / p.sum()) @ kv[:t + 1, head, nope:]
    gated = out.reshape(T, H * dv) / (1 + np.exp(-(h @ w["wg"])))
    return x + _n(gated @ w["wo"], w["post_norm"])


@pytest.mark.parametrize("kind,group,hand", [
    ("G", "gdn_layers", hand_gdn), ("D", "ffn_layers", hand_dense),
    ("E", "expert_layers", hand_experts), ("*", "attn_layers", hand_attention)])
def test_reference_block_against_a_hand_written_one(kind, group, hand):
    m = tiny_model()
    w = weights.layer_slice(weights.root_key(2), m, group, 0)
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (19, m["dim"]))
    np.testing.assert_allclose(ref.layer_forward(x, w, m, kind),
                               hand(x, w, m), atol=3e-5)


def test_the_control_reads_worse_than_the_reference_reads_itself():
    m = tiny_model()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 512, 30).tolist()
    rows = [np.arange(29, 39)]
    seq = [prompt + rng.integers(0, 512, 9).tolist()]
    sound = ref.logits_many(4, m, seq, rows)[0]
    control = ref.logits_many(4, m, seq, rows, quant=True)[0]
    assert 1e-3 < float(jnp.abs(sound - control).max())
    served = [int(t) for t in np.asarray(jnp.argmax(sound, -1))]
    gaps = ref.served_gaps_many(4, m, [(seq[0][:30], served)])[0]
    assert gaps.shape == (10,) and gaps[0] == 0.0


# -- the runner on a tiny cell -------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_gdn.make_root(str(tmp_path_factory.mktemp("bench-gdn")))


def test_the_gdn_runner_runs_a_tiny_cell(root, capsys):
    import benchmark_tiny as tiny

    line, text = tiny.run_cell(root, tiny_gdn.CELL, 2**31 + 11, 2.5, 0,
                               capsys, earlier=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 12
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert "warmed bucket=32" in text and "compiles_in_window=0" in text
    assert "correct? number=gap_mean" in text
    assert f"'state_bytes': {tiny_gdn.STATE_BYTES}" in text
    assert f"'state_bytes_by_kind': {{'gdn': {tiny_gdn.STATE_BYTES}}}" in text


def test_the_gdn_runner_traced_reports_the_engines_counters(root, capsys):
    import benchmark_tiny as tiny

    line = tiny.run_cell(root, tiny_gdn.CELL, 7, 2.5, 1, capsys)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # host and counter metrics only: no device plane on the CPU
    assert set(got) == {f"{s}.reasonbatch64" for s in (
        "itl_p50_ms", "itl_p97_ms", "out_tokens_per_s", "experts_touched",
        "state_pool_bytes", "latent_pool_fill")}
    assert 1 <= got["experts_touched.reasonbatch64"] <= 4  # of the 4 held
    assert got["state_pool_bytes.reasonbatch64"] == tiny_gdn.STATE_BYTES
    assert 0 < got["latent_pool_fill.reasonbatch64"] <= 100


def test_a_broken_state_carry_is_not_correct(root, capsys, monkeypatch):
    """A scan that hands out an empty state: every slice after a prompt's
    first starts from nothing and every decode step from the last slice's
    own tokens. The run serves, fails no request, and is not ``correct``."""
    import benchmark_tiny as tiny
    from oim_tpu.ops import gdn
    from oim_tpu.serve import engine

    real = gdn.scan

    def forgetful(layer, x, state, conv, n_tokens, dims, eps):
        out, state, conv = real(layer, x, state, conv, n_tokens, dims, eps)
        return out, jnp.zeros_like(state), conv

    monkeypatch.setattr(gdn, "scan", forgetful)
    engine._target_programs.cache_clear()
    try:
        line, text = tiny.run_cell(root, tiny_gdn.CELL, 11, 2.0, 0, capsys,
                                   earlier=True)
    finally:
        monkeypatch.undo()
        engine._target_programs.cache_clear()
    assert line["correct"] is False and line["failed"] == 0
    assert "compiles_in_window=0" in text


def test_check_limits_family_reads_sound_and_control(root, capsys):
    from benchmarks import check_limits_family

    assert check_limits_family.main(
        ["--workload", tiny_gdn.CELL, "--seeds", "5", "--seconds", "1.5"],
        platform="cpu", root=root) == 0
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("LIMITS ")][-1]
    got = json.loads(line[len("LIMITS "):])
    assert got["correct"] is True and got["sound"]["gap_mean"] <= 1e-4
    assert got["control"]["gap_mean"] > 10 * max(got["sound"]["gap_mean"], 1e-4)
    assert got["control_correct"] is False


# -- counts against hand arithmetic at the published sizes --------------------

def test_what_this_rank_holds():
    m = model()
    D = 7168
    # ISSUE 42's table: a GatedDeltaNet mixer 235.8 M, a latent mixer 159.8
    # M (its gate 58.7 M), a dense FFN 396.4 M, an expert 44.04 M, an expert
    # block with 16 held 750.5 M (+ the mixer: 986.3 M)
    assert roofline_gdn.gdn_layer_params(m) - 2 * D == 235_864_320
    assert abs((roofline_gdn.latent_layer_params(m) - 2 * D) / 159.8e6 - 1) < 1e-3
    assert 64 * 128 * D == 58_720_256
    assert roofline_gdn.dense_layer_params(m) - 2 * D == 3 * D * 18432
    assert abs(roofline_gdn.dense_layer_params(m) / 396.4e6 - 1) < 1e-3
    assert roofline_kda.expert_params(m) == 3 * D * 2048 == 44_040_192
    block = roofline_gdn.expert_layer_params(m)
    assert abs((block + roofline_gdn.gdn_layer_params(m)) / 986.3e6 - 1) < 1e-3
    assert abs((block + roofline_gdn.latent_layer_params(m)) / 910.3e6 - 1) < 1e-3
    assert abs(roofline_gdn.expert_layer_params(m, 256) / 11.32e9 - 1) < 1e-3
    assert abs(roofline_gdn.held_params(m) / 4.73e9 - 1) < 2e-3
    assert abs(roofline_gdn.weight_bytes(m) / 9.46e9 - 1) < 2e-3
    assert roofline_gdn.slot_state_bytes(m) == 4 * (64 * 128 * 128 * 4
                                                    + 3 * 16384 * 2)
    assert abs(64 * roofline_gdn.slot_state_bytes(m) / 1.10e9 - 1) < 0.01
    assert 1048576 * 640 * 2 == 1_342_177_280      # the latent pool, padded
    assert roofline_kda.expected_held_touched(m, 64) == pytest.approx(
        16 * (1 - (1 - 8 / 256) ** 64))
    assert 13.5 < roofline_kda.expected_held_touched(m, 64) < 14.5


def test_decode_step_counts():
    m = model()
    state = 2 * 64 * roofline_gdn.slot_state_bytes(m)
    step = roofline_gdn.gdn_step(m, 64)
    assert step["bytes"] == state + 4 * roofline_gdn.gdn_layer_params(m) * 2
    assert step["bytes"] / 819e9 > step["flops"] / 197e12  # memory bound
    assert abs(state / 2.2e9 - 1) < 0.01           # ISSUE: 2.2 GB of state
    # ISSUE 42's round at 64 rows, about 14 of 16 experts a layer and 4k
    # positions a row: about 11 GB
    least = roofline_gdn.decode_step_min_bytes(m, 64, 64 * 4096, 14)
    assert 10.5e9 < least < 11.5e9
    kv = 64 * 4096 * roofline_gdn.position_bytes(m)
    assert abs(kv / 0.30e9 - 1) < 0.01
    fewer = roofline_gdn.decode_step_min_bytes(m, 64, 64 * 4096, 12)
    assert least - fewer == pytest.approx(4 * 2 * 44_040_192 * 2)
    # without experts, state or pages: the 3.6 GB of mixer, dense, shared
    # and head weights ISSUE 42 counts
    bare = roofline_gdn.decode_step_min_bytes(m, 0, 0, 0)
    assert abs(bare / 3.6e9 - 1) < 0.03


def test_scan_counts():
    m, peak = model(), roofline.peaks("TPU v5 lite")
    scan = roofline_gdn.gdn_scan(m, 1024)
    projections = 4 * 1024 * 2 * 7168 * (16384 + 128 + 8192 + 8192)
    assert scan["flops"] == pytest.approx(
        projections + 4 * 1024 * (64 * (6 * 128 * 128 + 64 * 3 * 128)
                                  + 32 * 2 * 64 * 128))
    # a head-scalar decay: the chunk products are under a fifth of the
    # projections (the per-channel form's were a third)
    assert scan["flops"] < 1.2 * projections
    assert roofline.roofline_seconds(scan, peak)[1] == "compute"
    assert roofline.roofline_seconds(
        roofline_gdn.gdn_scan(m, 16), peak)[1] == "memory"
    assert roofline_gdn.gdn_scan(m, 16)["bytes"] > 4 * 235.8e6 * 2


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
       "custom-call(s32[1]{{0:T(128)}} %a, s32[65]{{0:T(512)S(1)}} %b)")
UPDATE = ("%fusion.71 = f32[4,64,64,128,128]{4,3,2,1,0:T(8,128)} fusion("
          "f32[4,64,64,128,128]{4,3,2,1,0:T(8,128)} %get-tuple-element.9)")
QKV = ("%fusion.1681 = bf16[1,{},16384]{{2,1,0:T(8,128)(2,1)S(1)}} fusion("
       "bf16[4,7168,16384]{{2,1,0:T(8,128)(2,1)}} %get-tuple-element.4457)")
DENSE = ("%fusion.90 = bf16[16,64,2048]{2,1,0:T(8,128)(2,1)S(1)} fusion("
         "bf16[4,16,7168,2048]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.7)")
KERNEL = ("%mla_decode.3 = f32[64,64,640]{2,1,0:T(8,128)} custom-call("
          "s32[1]{0} %a, bf16[1,65537,16,640]{3,2,1,0} %pool)")
OTHER = "%fusion.5 = f32[64,7168]{1,0:T(8,128)} fusion(f32[64,7168] %p)"
SCOPES = {
    UPDATE: "jit(step)/while/body/closed_call/kda_step/gdn_step/mul",
    QKV.format(1024):
        "jit(prefill)/while/body/kda_scan/gdn_scan/kda_scan/gdn_scan/dot_general",
    DENSE: "jit(step)/while/body/closed_call/blk_ffn/moe_gmm/dot_general",
    GMM.format("", 8192): "jit(prefill)/cond/branch_2_fun/moe_gmm/ragged_dot",
}


def metric_args(name):
    return common.load_json(os.path.join(
        REPO, "benchmarks", "metrics", f"{name}.reasonbatch64.json"))["args"]


def reader_result(trace, scopes=None, shapes_model=None, **stats):
    result = {"trace": trace, "stats": stats, "trace_dir": "recorded",
              "device": {"kind": "TPU v5 lite", "platform": "tpu"},
              "shapes": {"model": shapes_model or model(), "live_rows": 64.0,
                         "live_kv_tokens": 260_000.0}}
    if trace is not None:  # what scopes_by_operation would read off the file
        scopes = scopes or {}
        result["_scoped_ops"] = [
            (s, d / 1e9, name, scopes.get(name, ""))
            for name, s, d in trace["planes"][0]["lines"][1]["events"]]
    return result


def test_gdn_reader_decode_mixers_and_the_latent_kernel(capsys):
    reader = common.plugin(REPO, "readers", "gdn_roofline")
    m, peak = model(), roofline.peaks("TPU v5 lite")
    trace = recorded(
        [(UPDATE, 6_000_000), (KERNEL, 1_000_000), (OTHER, 13_000_000)],
        [(QKV.format(1024), 40_000_000), (OTHER, 5_000_000)])
    result = reader_result(trace, SCOPES, experts_touched=14.0)
    got = reader.read(result, metric_args("decode_roofline"))
    least = roofline_gdn.decode_step_min_bytes(m, 64.0, 260_000.0, 14.0)
    assert got == pytest.approx(100 * least / 819e9 / 20.00003e-3)
    assert 0 < got < 100
    got = reader.read(result, metric_args("gdn_step_roofline"))
    least = roofline_gdn.gdn_step(m, 64.0)["bytes"] / 819e9
    assert got == pytest.approx(100 * least / 6e-3) and 0 < got < 100
    got = reader.read(result, metric_args("gdn_scan_roofline"))
    least = roofline.roofline_seconds(roofline_gdn.gdn_scan(m, 1024), peak)[0]
    assert got == pytest.approx(100 * least / 40e-3) and 0 < got < 100
    # the latent kernel over ONE latent layer: the stem's reader would count
    # five and read five times the share
    got = reader.read(result, metric_args("latent_kernel_roofline"))
    one = (260_064 * 576 * 2 + 512 * 64 * 256 * 2) / 819e9
    assert got == pytest.approx(100 * one / 1e-3) and 0 < got < 100
    stem = common.plugin(REPO, "readers", "latent_roofline").read(
        result, {**metric_args("latent_kernel_roofline"), "kind": "attention"})
    assert stem == pytest.approx(5 * got)
    text = capsys.readouterr().out
    assert "bound: memory" in text and "bound: [('compute', 1)]" in text
    # a program without the scopes or the counter (the parent), a cell of
    # another family, a run without a trace: nothing, and no raise
    bare = reader_result(trace, {}, experts_touched=14.0)
    assert reader.read(bare, metric_args("gdn_step_roofline")) is None
    assert reader.read(bare, metric_args("gdn_scan_roofline")) is None
    assert reader.read(reader_result(trace, SCOPES),
                       metric_args("decode_roofline")) is None
    for name in ("decode_roofline", "latent_kernel_roofline",
                 "expert_ffn_roofline"):
        assert reader.read(reader_result(None), metric_args(name)) is None
        other = reader_result(trace, SCOPES, {"pattern": "MEM"},
                              experts_touched=3)
        assert reader.read(other, metric_args(name)) is None
    no_kernel = reader_result(recorded([(OTHER, 5)], [(OTHER, 5)]), SCOPES)
    assert reader.read(no_kernel, metric_args("latent_kernel_roofline")) is None


def test_gdn_reader_expert_products(capsys):
    """Decode: the dense form under the scope. Prefill: the last rung's
    grouped products (found by scope or by their HLO line), against THREE
    products a block at the slice's own length, read off the gdn_scan
    scope."""
    reader = common.plugin(REPO, "readers", "gdn_roofline")
    m, peak = model(), roofline.peaks("TPU v5 lite")
    args = metric_args("expert_ffn_roofline")

    def least(tokens, touched):
        return 3 * 4 * roofline.roofline_seconds(roofline_kda.expert_product(
            m, tokens * 8 / 16, touched), peak)[0]

    step = [(DENSE, 5_000_000), (DENSE, 4_000_000), (OTHER, 900)]
    chunk = [(QKV.format(1024), 1_000_000), (GMM.format("", 8192), 6_000_000),
             (GMM.format(".1", 8192), 5_000_000), (OTHER, 7_000_000)]
    got = reader.read(reader_result(recorded(step, chunk), SCOPES,
                                    experts_touched=14.0), args)
    want = 2 * least(64.0, 14.0) + least(
        1024, roofline_kda.expected_held_touched(m, 1024))
    assert got == pytest.approx(100 * want / (2 * 9e-3 + 11e-3))
    assert 0 < got < 100
    assert "expert products in prefill: 1 slices" in capsys.readouterr().out
    assert reader.read(reader_result(recorded(step, chunk), SCOPES), args) is None
