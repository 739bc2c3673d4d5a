"""What PR 33 adds to the benchmark, on the CPU: the new configuration's
entry, the nemotron_h_like reference against layers written out by hand,
the hybrid runner on a tiny cell of ``benchmark_tiny``'s temporary copy
(and a broken state carry coming out not ``correct``), the new reader on
recorded input, and the byte and operation counts against hand counts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny_hybrid as tiny_hybrid
from benchmarks import common, roofline, roofline_hybrid
from benchmarks import weights_nemotron_h as weights
from benchmarks.reference import nemotron_h_like as ref
from benchmarks.runners import serve_hybrid

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELL = "nemotron-3-nano-30b.agentbatch"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# The catalog row's ``config`` (model-configs/architectures.jsonl, row
# NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), copied here: every number must be
# in the file under the same key unless ``reduced`` names the key.
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688, "hybrid_override_pattern": PATTERN,
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}


def config_file():
    return common.load_json(os.path.join(
        REPO, "benchmarks", "configs", "nemotron-3-nano-30b.json"))


def model():
    return serve_hybrid.model_dict(config_file(), "serve")


def tiny_model():
    return serve_hybrid.model_dict(tiny_hybrid.CONFIG, "serve")


def test_the_new_configuration_entry():
    """test_benchmark_contract.test_configuration_entry's rules, with this
    family's published widths in place of the Mistral family's (that
    pinned case fails for this configuration as for joyai-llm-flash: it
    holds every configuration to Mistral's widths and dislikes a reduced
    key that ends in ``_size``, which ``vocab_size`` does)."""
    entry = {c["name"]: c for c in BENCH["configs"]}["nemotron-3-nano-30b"]
    body = config_file()
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert body["source"] == entry["source"] and body["reduced"] == entry["reduced"]
    assert entry["reduced"] == ["n_routed_experts", "vocab_size",
                                "max_position_embeddings"]
    for key, value in CATALOG.items():
        if key in entry["reduced"]:
            assert body["published"][key] == value
        else:
            assert body[key] == value, key
    assert (body["n_routed_experts"], body["vocab_size"]) == (16, 16384)
    assert body["serve"]["num_hidden_layers"] == 52 == len(PATTERN)
    assert body["serve"]["max_position_embeddings"] == 8192
    assert (body["serve"]["queue_depth"], body["serve"]["kv_pool_tokens"],
            body["serve"]["prefill_chunk"]) == (1100, 196608, 1024)
    assert body["serve"]["max_batch"] in (48, 32)
    assert "eight v5e chips" in body["deployment"] \
        and "no layer is left out" in body["deployment"] \
        and "1/8 of the rows" in body["deployment"]
    for key in ("attention_rotary_embedding", "state", "torch_dtype", "rms",
                "weights", "serve", "limits"):
        assert key in body["assumed"], key
    assert body["attention_rotary_embedding"] is False
    assert body[serve_hybrid.KEY] == "nemotron_h_like"


def test_the_cell_lists_what_the_issue_names():
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-nano-30b", "agentbatch", 1)
    assert len(BENCH["workloads"]) == 5
    assert not any(w["chips"] == 4 for w in BENCH["workloads"])
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {f"{stem}.agentbatch" for stem in (
        "prefill_share", "itl_p50_ms", "itl_p90_ms", "itl_p97_ms",
        "out_tokens_per_s", "slice_rate", "stall_share", "decode_step_ms",
        "device_idle", "idle_step_roundtrip", "idle_emit", "idle_admit",
        "idle_unannotated", "idle_prefill_chunk", "emit_ms",
        "prefill_chunk_ms", "experts_touched", "expert_load_max_over_mean",
        "ssm_step_roofline", "ssm_scan_roofline", "expert_ffn_roofline",
        "decode_roofline", "state_pool_bytes")}
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]]
    assert e2e == ["itl_p95_ms", "setup_s"]
    # the five new files take precedence over their stems' (common.metric_spec)
    for name in ("ssm_step_roofline", "ssm_scan_roofline",
                 "expert_ffn_roofline", "decode_roofline"):
        assert common.metric_spec(REPO, f"{name}.agentbatch")["reader"] \
            == "hybrid_roofline"
    assert common.metric_spec(REPO, "decode_roofline.batch")["reader"] \
        == "roofline_share"
    mix = common.load_json(os.path.join(REPO, "benchmarks", "traffic",
                                        "agentbatch.json"))
    assert (mix["runner"], mix["kind"]) == ("serve_hybrid", "backlog")
    assert mix["prompt_tokens"] == {"median": 2048, "sigma": 0.6, "min": 512,
                                    "max": 6144}
    assert mix["output_tokens"] == {"median": 512, "sigma": 0.5, "min": 128,
                                    "max": 2048}
    # ISSUE 33 named 1024 requests in blocks of 48; traffic.backlog wants
    # whole blocks, so 22 of them
    assert (mix["requests"], mix["block"]) == (1056, 48)
    assert (mix["check_requests"], mix["schedule_seed"], mix["pre_roll_s"]) \
        == (3, 20260927, 30.0)


def test_the_longest_request_fits_the_configuration():
    from benchmarks import traffic
    from benchmarks.runners import serve_family

    mix = common.load_json(os.path.join(REPO, "benchmarks", "traffic",
                                        "agentbatch.json"))
    m, sizes = model(), config_file()["serve"]
    reqs = traffic.backlog(mix, 2**31 + 3, m["vocab"])
    assert len(reqs) <= sizes["queue_depth"]
    assert max(len(r.prompt) + r.max_new for r in reqs) <= m["max_seq"]
    assert min(len(r.prompt) for r in reqs) >= 512
    assert 16000 < max(int(r.prompt.max()) for r in reqs[:8]) < 16384
    pieces = serve_family._piece_buckets(
        reqs, sizes["prefill_chunk"], m["max_seq"],
        lambda n, top: min(max(8, 1 << (n - 1).bit_length()), top))
    assert max(pieces) == 1024 and all(b <= 1024 for b in pieces)
    # every position of the block's requests at once fits the page pool
    cycle = reqs[:mix["block"]]
    assert sum(len(r.prompt) + r.max_new for r in cycle) \
        <= sizes["kv_pool_tokens"]


def test_model_dict_and_the_programs_tree():
    from oim_tpu.models import llama

    m = model()
    cfg = serve_hybrid.program_config(m)
    assert cfg == llama.dataclasses.replace(
        llama.NEMOTRON_3_NANO_30B, expert_rank="0/8", vocab=16384,
        max_seq=8192)
    weights.check_against_program(m, jax.eval_shape(
        lambda k: llama.init(k, cfg), jax.random.PRNGKey(0)))
    spec = weights.tree_spec(m)
    drawn = sum(int(np.prod(s)) for s, _, _ in spec.values())
    assert drawn == roofline_hybrid.held_params(m) == llama.num_params(cfg)
    assert spec["expert_layers/moe/w_up"][0] == (23, 16, 2688, 1856)
    assert spec["expert_layers/moe/router"][0] == (23, 2688, 128)
    assert spec["mamba_layers/w_in"][0] == (23, 2688, 10304)
    assert spec["attn_layers/wk"][0] == (6, 2688, 256)
    held = weights.program_spec(m)
    assert held["expert_layers/moe/w_up"][0] == (23, 16, 2688, 1920)
    assert held["expert_layers/moe/w_down"][0] == (23, 16, 1920, 2688)
    assert held["expert_layers/moe/shared/w_up"][0] == (23, 2688, 3712)


def test_the_programs_layout_pads_with_exact_zeros():
    m = tiny_model() | {"moe_dim": 192}
    root = weights.root_key(3)
    made = weights.make(root, m)["expert_layers"]["moe"]
    drawn = weights.layer_slice(root, m, "expert_layers", 2)["moe"]
    assert made["w_up"].shape[-1] == 256 and drawn["w_up"].shape[-1] == 192
    np.testing.assert_array_equal(made["w_up"][2][..., :192], drawn["w_up"])
    np.testing.assert_array_equal(made["w_down"][2][:, :192], drawn["w_down"])
    assert not np.any(made["w_up"][..., 192:]) \
        and not np.any(made["w_down"][:, :, 192:])
    np.testing.assert_array_equal(made["router"][2], drawn["router"])


def test_the_special_draws_follow_the_familys_initialisation():
    m = tiny_model()
    layer = weights.layer_slice(weights.root_key(9), m, "mamba_layers", 1)
    step = np.log1p(np.exp(np.asarray(layer["dt_bias"], np.float64)))
    assert np.all(step >= 1e-3 * 0.999) and np.all(step <= 0.1 * 1.001)
    a = np.exp(np.asarray(layer["A_log"]))
    assert np.all(a >= 1.0) and np.all(a <= 16.0) and a.std() > 0.5
    assert np.all(np.asarray(layer["D"]) == 1.0)
    assert 0.3 < float(jnp.std(layer["conv_w"])) < 0.7  # fan-in 4
    whole = weights.make(weights.root_key(9), m)["mamba_layers"]
    for leaf, value in layer.items():
        np.testing.assert_array_equal(whole[leaf][1], value)


def test_a_program_without_the_family_is_refused_in_one_line(monkeypatch):
    """What the parent commit does: its Config has no such field."""
    from oim_tpu.models import llama

    class Old:
        def __init__(self, **fields):
            raise TypeError("Config.__init__() got an unexpected keyword "
                            "argument 'attn_rope'")

    monkeypatch.setattr(llama, "Config", Old)
    with pytest.raises(SystemExit, match="cannot express the nemotron_h_like"):
        serve_hybrid.program_config(model())


@pytest.mark.parametrize("key,value", [
    ("n_group", 8), ("topk_group", 4), ("norm_topk_prob", False),
    ("mlp_hidden_act", "silu"), ("use_conv_bias", False),
    ("mamba_proj_bias", True), ("n_shared_experts", 2), ("norm_eps", 1e-6),
    ("hybrid_override_pattern", "ME"), ("n_routed_experts", 15)])
def test_what_the_family_does_not_implement_is_refused(key, value):
    with pytest.raises(SystemExit):
        serve_hybrid.model_dict({**config_file(), key: value}, "serve")


# -- the reference against layers written out by hand ------------------------

def hand_mamba(x, w, m):
    """The mixer with numpy loops over time and heads, in float64."""
    w = jax.tree.map(lambda a: np.asarray(a, np.float64), w)
    x = np.asarray(x, np.float64)
    T = x.shape[0]
    H, P, G, N, K = (m["mamba_heads"], m["mamba_head_dim"], m["ssm_groups"],
                     m["ssm_state"], m["conv_kernel"])
    inner, conv_dim = H * P, H * P + 2 * G * N
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w["norm"]
    p = h @ w["w_in"]
    z, xbc, dt = p[:, :inner], p[:, inner:inner + conv_dim], p[:, inner + conv_dim:]
    conv = np.zeros_like(xbc)
    for t in range(T):
        for j in range(K):
            if t - (K - 1) + j >= 0:
                conv[t] += w["conv_w"][j] * xbc[t - (K - 1) + j]
    conv = conv + w["conv_b"]
    conv = conv / (1 + np.exp(-conv))
    xs = conv[:, :inner].reshape(T, H, P)
    bm = conv[:, inner:inner + G * N].reshape(T, G, N)
    cm = conv[:, inner + G * N:].reshape(T, G, N)
    dt = np.log1p(np.exp(dt + w["dt_bias"]))
    state = np.zeros((H, P, N))
    y = np.zeros((T, H, P))
    for t in range(T):
        for head in range(H):
            g = head // (H // G)
            state[head] = (np.exp(-np.exp(w["A_log"][head]) * dt[t, head])
                           * state[head]
                           + dt[t, head] * np.outer(xs[t, head], bm[t, g]))
            y[t, head] = state[head] @ cm[t, g] + w["D"][head] * xs[t, head]
    y = y.reshape(T, inner) * (z / (1 + np.exp(-z)))
    y = y.reshape(T, G, -1)
    y = (y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5)).reshape(T, inner)
    return x + (y * w["gate_norm"]) @ w["w_out"]


def hand_experts(x, w, m):
    w = jax.tree.map(lambda a: np.asarray(a, np.float64), w)
    x = np.asarray(x, np.float64)
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w["norm"]
    e = w["moe"]
    out = x + np.maximum(h @ e["shared"]["w_up"], 0) ** 2 @ e["shared"]["w_down"]
    for t in range(x.shape[0]):
        s = 1 / (1 + np.exp(-(h[t] @ e["router"])))
        chosen = np.argsort(-(s + e["bias"]), kind="stable")[: m["moe_top_k"]]
        weight = s[chosen] / s[chosen].sum() * m["routed_scale"]
        for j, c in zip(weight, chosen):
            c -= m["expert_first"]
            if 0 <= c < m["experts_held"]:
                out[t] += j * (np.maximum(h[t] @ e["w_up"][c], 0) ** 2
                               @ e["w_down"][c])
    return out


def hand_attention(x, w, m):
    w = jax.tree.map(lambda a: np.asarray(a, np.float64), w)
    x = np.asarray(x, np.float64)
    T, H, KV, hd = x.shape[0], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w["norm"]
    q = (h @ w["wq"]).reshape(T, H, hd)
    k = (h @ w["wk"]).reshape(T, KV, hd)
    v = (h @ w["wv"]).reshape(T, KV, hd)
    o = np.zeros((T, H, hd))
    for head in range(H):
        kv = head // (H // KV)
        s = q[:, head] @ k[:, kv].T / np.sqrt(hd)  # no rotary embedding
        s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        o[:, head] = p / p.sum(-1, keepdims=True) @ v[:, kv]
    return x + o.reshape(T, H * hd) @ w["wo"]


@pytest.mark.parametrize("kind,group,hand", [
    ("M", "mamba_layers", hand_mamba), ("E", "expert_layers", hand_experts),
    ("*", "attn_layers", hand_attention)])
def test_reference_layer_against_a_hand_written_one(kind, group, hand):
    m = tiny_model()
    w = weights.layer_slice(weights.root_key(4), m, group, 0)
    x = jax.random.normal(jax.random.PRNGKey(2), (32, m["dim"]), jnp.float32)
    got = np.asarray(ref.layer_forward(x, w, m, kind))
    assert np.abs(got - hand(x, w, m)).max() < 2e-5


def test_the_control_reads_worse_than_the_reference_reads_itself():
    m = tiny_model()
    prompt = np.random.default_rng(1).integers(0, 512, 40).tolist()
    lg = ref.logits_many(5, m, [prompt], [np.arange(39, 40)])[0]
    served = [int(jnp.argmax(lg[0]))]
    assert ref.served_gaps_many(5, m, [(prompt, served)])[0].max() == 0.0
    long = np.random.default_rng(2).integers(0, 512, 200).tolist()
    control = ref.served_gaps_many(5, m, [(long[:100], long[100:])],
                                   control=True)[0]
    assert control.mean() > 0.01


# -- the hybrid runner on a tiny cell -----------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_hybrid.make_root(str(tmp_path_factory.mktemp("bench-hybrid")))


def test_the_hybrid_runner_runs_a_tiny_cell(root, capsys):
    import benchmark_tiny as tiny

    line, text = tiny.run_cell(root, tiny_hybrid.CELL, 2**31 + 11, 2.5, 0,
                               capsys, earlier=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 12
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert "warmed bucket=32" in text and "compiles_in_window=0" in text
    assert "correct? number=gap_mean" in text
    assert "'state_bytes': 90112" in text  # 4 slots x 4 layers x (4096 + 1536)


def test_the_hybrid_runner_traced_reports_the_engines_counters(root, capsys):
    import benchmark_tiny as tiny

    line = tiny.run_cell(root, tiny_hybrid.CELL, 7, 2.5, 1, capsys)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # host and counter metrics only: no device plane on the CPU
    assert set(got) == {f"{s}.agentbatch" for s in (
        "itl_p50_ms", "itl_p90_ms", "itl_p97_ms", "out_tokens_per_s",
        "slice_rate", "stall_share", "experts_touched",
        "expert_load_max_over_mean", "state_pool_bytes")}
    assert 1 <= got["experts_touched.agentbatch"] <= 4  # of the 4 held
    assert got["expert_load_max_over_mean.agentbatch"] >= 1
    assert got["state_pool_bytes.agentbatch"] == 90112


def test_a_broken_state_carry_is_not_correct(root, capsys, monkeypatch):
    """A scan that hands out an empty state: every slice after a prompt's
    first starts from nothing and every decode step from the last slice's
    own tokens. The run serves, fails no request, and is not ``correct``."""
    import benchmark_tiny as tiny
    from oim_tpu.ops import ssm
    from oim_tpu.serve import engine

    real = ssm.scan

    def forgetful(layer, x, state, conv, n_tokens, dims, eps):
        out, state, conv = real(layer, x, state, conv, n_tokens, dims, eps)
        return out, jnp.zeros_like(state), conv

    monkeypatch.setattr(ssm, "scan", forgetful)
    engine._target_programs.cache_clear()
    try:
        line, text = tiny.run_cell(root, tiny_hybrid.CELL, 11, 2.0, 0, capsys,
                                   earlier=True)
    finally:
        monkeypatch.undo()
        engine._target_programs.cache_clear()
    assert line["correct"] is False and line["failed"] == 0
    assert "compiles_in_window=0" in text


def test_window_means_add_the_state_pool():
    def sample(t, steps, touched, fullest, state):
        return (t, {"expert_load_steps": steps, "experts_touched_sum": touched,
                    "expert_load_max_over_mean_sum": fullest},
                {"used_pages": 5, "total_pages": 10, "state_bytes": state})

    samples = [sample(0.0, 0, 0.0, 0.0, 7), sample(1.0, 10, 100.0, 30.0, 7),
               sample(2.0, 30, 300.0, 100.0, 7), sample(9.0, 99, 9e9, 9e9, 7)]
    means = serve_hybrid._base()._window_means(samples, 0.5, 2.5)
    assert means == {"experts_touched": 10.0,
                     "expert_load_max_over_mean": 3.5, "state_pool_bytes": 7.0}
    # a program without the counter: no such stat, no raise
    bare = [(t, s, {"used_pages": 5, "total_pages": 10}) for t, s, _ in samples]
    assert "state_pool_bytes" not in serve_hybrid._base()._window_means(
        bare, 0.5, 2.5)


def test_check_limits_family_reads_sound_and_control(root, capsys):
    from benchmarks import check_limits_family

    assert check_limits_family.main(
        ["--workload", tiny_hybrid.CELL, "--seeds", "5", "--seconds", "1.5"],
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
    mamba = (2688 * 10304 + 5 * 6144 + 3 * 64 + 4096 + 4096 * 2688 + 2688)
    attn = 2688 * 4096 * 2 + 2 * 2688 * 256 + 2688
    expert = 2 * 2688 * 1856
    layer = 2688 * 128 + 128 + 16 * expert + 2 * 2688 * 3712 + 2688
    assert roofline_hybrid.mamba_layer_params(m) == mamba          # 38.74 M
    assert roofline_hybrid.attention_layer_params(m) == attn       # 23.40 M
    assert roofline_hybrid.expert_params(m) == expert              # 9.98 M
    held = 23 * mamba + 6 * attn + 23 * layer + 2 * 16384 * 2688 + 2688
    assert roofline_hybrid.held_params(m) == held
    assert abs(held - 5.26e9) < 0.005e9
    assert abs(roofline_hybrid.weight_bytes(m) - 10.52e9) < 0.005e9
    whole = dict(m, experts_held=128, vocab=131072)
    assert abs(roofline_hybrid.held_params(whole) - 31.58e9) < 0.005e9
    assert roofline_hybrid.slot_state_bytes(m) == 23 * (64 * 64 * 128 * 4
                                                        + 3 * 6144 * 2)
    assert abs(roofline_hybrid.slot_state_bytes(m) - 49.1e6) < 0.05e6
    assert roofline_hybrid.position_bytes(m) == 6144               # 6 KB


def test_decode_step_counts():
    m = model()
    touched = roofline_hybrid.expected_held_touched(m, 48)
    assert touched == pytest.approx(16 * (1 - (1 - 6 / 128) ** 48))
    assert touched == pytest.approx(14.4, abs=0.01)
    least = roofline_hybrid.decode_step_min_bytes(m, 48, 150_000, touched)
    state = 2 * 48 * roofline_hybrid.slot_state_bytes(m)
    assert state == pytest.approx(4.71e9, rel=2e-3)
    assert least == pytest.approx(15.3e9, rel=5e-3)   # 18.7 ms at 819 GB/s
    assert state / least == pytest.approx(0.31, abs=0.01)
    step = roofline_hybrid.ssm_step(m, 48)
    assert step["bytes"] == state + 23 * roofline_hybrid.mamba_layer_params(m) * 2
    assert step["bytes"] / least == pytest.approx(0.42, abs=0.01)
    peak = roofline.peaks("TPU v5 lite")
    assert roofline.roofline_seconds(step, peak)[1] == "memory"


def test_scan_and_product_counts():
    m, peak = model(), roofline.peaks("TPU v5 lite")
    scan = roofline_hybrid.ssm_scan(m, 1024)
    projections = 1024 * 2 * 2688 * (10304 + 4096)
    chunk = (2 * 128 * 128 * 128 * 8 + 2 * 128 * 128 * 64 * 64
             + 4 * 128 * 64 * 128 * 64)
    assert scan["flops"] == 23 * (projections + 8 * chunk)
    assert projections / 1024 == pytest.approx(77.4e6, rel=1e-3)  # 77 MFLOP a token
    assert roofline.roofline_seconds(scan, peak)[1] == "compute"
    assert roofline.roofline_seconds(
        roofline_hybrid.ssm_scan(m, 32), peak)[1] == "memory"
    product = roofline_hybrid.expert_product(m, 36, 14.4)
    assert product["flops"] == 2 * 36 * 2688 * 1856
    assert product["bytes"] == 14.4 * 2688 * 1856 * 2 + 36 * (2688 + 1856) * 2
    assert roofline.roofline_seconds(product, peak)[1] == "memory"


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


# Operation names as a v5e trace of the cell has them (my chip runs, PR 33).
GMM = ("%ragged-dot-none{} = bf16[{},1920]{{1,0:T(8,128)(2,1)S(1)}} "
       "custom-call(s32[1]{{0:T(128)}} %a, s32[369]{{0:T(512)S(1)}} %b)")
UPDATE = ("%fusion.71 = f32[23,48,64,64,128]{4,3,2,1,0:T(8,128)} fusion("
          "f32[23,48,64,64,128]{4,3,2,1,0:T(8,128)} %get-tuple-element.9)")
IN_PROJ = ("%fusion.1681 = bf16[1,{},10304]{{1,2,0:T(8,128)(2,1)S(1)}} fusion("
           "bf16[23,2688,10304]{{1,2,0:T(8,128)(2,1)}} %get-tuple-element.4457)")
DENSE = ("%fusion.90 = bf16[16,48,1920]{2,1,0:T(8,128)(2,1)S(1)} fusion("
         "bf16[23,16,2688,1920]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.7)")
OTHER = "%fusion.5 = f32[48,2688]{1,0:T(8,128)} fusion(f32[48,2688] %p)"


def metric_args(name):
    return common.load_json(os.path.join(
        REPO, "benchmarks", "metrics", f"{name}.agentbatch.json"))["args"]


def reader_result(trace, scopes=None, **stats):
    result = {"trace": trace, "stats": stats, "trace_dir": "recorded",
              "device": {"kind": "TPU v5 lite", "platform": "tpu"},
              "shapes": {"model": model(), "live_rows": 48.0,
                         "live_kv_tokens": 150_000.0}}
    if trace is not None:  # what scopes_by_operation would read off the file
        scopes = scopes or {}
        result["_scoped_ops"] = [
            (s, d / 1e9, name, scopes.get(name, ""))
            for name, s, d in trace["planes"][0]["lines"][1]["events"]]
    return result


def test_hybrid_reader_decode_and_mixers():
    reader = common.plugin(REPO, "readers", "hybrid_roofline")
    m, peak = model(), roofline.peaks("TPU v5 lite")
    scopes = {UPDATE: "jit(step)/while/body/closed_call/ssm_step/mul",
              IN_PROJ.format(1024): "jit(prefill)/while/body/ssm_scan/dot_general",
              IN_PROJ.format(256): "jit(prefill)/ssm_scan/ssm_scan/dot_general"}
    trace = recorded(
        [(UPDATE, 9_000_000), (OTHER, 11_000_000)],
        [(IN_PROJ.format(1024), 20_000_000), (OTHER, 5_000_000)])
    result = reader_result(trace, scopes, experts_touched=14.4)
    got = reader.read(result, metric_args("decode_roofline"))
    least = roofline_hybrid.decode_step_min_bytes(m, 48.0, 150_000.0, 14.4)
    assert got == pytest.approx(100 * least / 819e9 / 20.00002e-3)
    got = reader.read(result, metric_args("ssm_step_roofline"))
    least = roofline_hybrid.ssm_step(m, 48.0)["bytes"] / 819e9
    assert got == pytest.approx(100 * least / 9e-3) and 0 < got < 100
    got = reader.read(result, metric_args("ssm_scan_roofline"))
    least = roofline.roofline_seconds(roofline_hybrid.ssm_scan(m, 1024), peak)[0]
    assert got == pytest.approx(100 * least / 20e-3) and 0 < got < 100
    # a program without the scopes or the counter (the parent): nothing
    bare = reader_result(trace, {}, experts_touched=14.4)
    assert reader.read(bare, metric_args("ssm_step_roofline")) is None
    assert reader.read(bare, metric_args("ssm_scan_roofline")) is None
    assert reader.read(reader_result(trace, scopes),
                       metric_args("decode_roofline")) is None
    assert reader.read(reader_result(None), metric_args("decode_roofline")) is None


def test_hybrid_reader_expert_products_in_both_forms():
    reader = common.plugin(REPO, "readers", "hybrid_roofline")
    m, peak = model(), roofline.peaks("TPU v5 lite")
    args = metric_args("expert_ffn_roofline")
    chunk = [(GMM.format(i, 6144), 2_000_000) for i in ("", ".1")]
    scopes = {DENSE: "jit(step)/while/body/closed_call/moe_gmm/dot_general"}

    def least(tokens, touched):
        return roofline.roofline_seconds(roofline_hybrid.expert_product(
            m, tokens * 6 / 8, touched), peak)[0]

    slices = 2 * least(1024, roofline_hybrid.expected_held_touched(m, 1024))
    for step, spent in (
            ([(DENSE, 9_000_000), (DENSE, 8_000_000), (OTHER, 900)], 2 * 17e-3),
            ([(GMM.format(i, 288), 20_000_000) for i in ("", ".1")], 2 * 40e-3)):
        got = reader.read(reader_result(recorded(step, chunk), scopes,
                                        experts_touched=14.4), args)
        want = 2 * 23 * 2 * least(48.0, 14.4) + slices
        assert got == pytest.approx(100 * want / (spent + 4e-3))
        assert 0 < got < 100
    assert reader.read(reader_result(recorded(step, chunk), scopes), args) is None


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def test_scopes_are_read_off_the_xplane_wire_format(tmp_path):
    """An .xplane.pb written by hand: two planes, the device's with two
    operations' metadata (name = 2, stats = 5 of which str_value = 5 that
    starts with "jit(" is the scope path), and lines that are skipped."""
    reader = common.plugin(REPO, "readers", "hybrid_roofline")

    def metadata(key, name, *stats):
        body = _field(1, key) + _field(2, name.encode())
        for stat in stats:
            body += _field(5, _field(1, 26) + (
                _field(5, stat.encode()) if isinstance(stat, str)
                else _field(3, stat)))
        return _field(4, _field(1, key) + _field(2, body))

    device = (_field(1, 7) + _field(2, b"/device:TPU:0")
              + _field(3, _field(2, b"XLA Ops") + _field(4, _field(1, 1)))
              + metadata(1, UPDATE, 12345,
                         "jit(step)/while/body/closed_call/ssm_step/mul",
                         "/root/repo/oim_tpu/ops/ssm.py:143")
              + metadata(2, OTHER, "jit(step)/add")
              + metadata(3, "%copy.1 = f32[8] copy(%p)"))
    host = (_field(1, 8) + _field(2, b"/host:CPU")
            + metadata(1, UPDATE, "jit(nothing)/of/the/device"))
    path = tmp_path / "a.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host))
    assert reader.scopes_by_operation(str(path)) == {
        UPDATE: {"jit(step)/while/body/closed_call/ssm_step/mul"},
        OTHER: {"jit(step)/add"}}
