"""What PR 37 adds to the benchmark, on the CPU: the new configuration's
entry, the solar_open2_like reference against blocks written out by hand,
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

import benchmark_tiny_kda as tiny_kda
from benchmarks import common, roofline, roofline_kda
from benchmarks import weights_solar_open2 as weights
from benchmarks.reference import solar_open2_like as ref
from benchmarks.runners import serve_kda

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELL = "solar-open2-250b.agentbatch64"
# The catalog row's ``config`` (model-configs/architectures.jsonl, row
# Solar-Open2-250B), copied here: every number must be in the file under the
# same key unless ``reduced`` names the key.
CATALOG = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8,
}
SPLITS = (
    "prefill_share", "itl_p50_ms", "itl_p90_ms", "itl_p97_ms",
    "out_tokens_per_s", "slice_rate", "decode_step_ms", "device_idle",
    "idle_step_roundtrip", "idle_emit", "idle_admit", "idle_unannotated",
    "idle_prefill_chunk", "emit_ms", "prefill_chunk_ms", "experts_touched",
    "expert_load_max_over_mean", "kda_step_roofline", "kda_scan_roofline",
    "expert_ffn_roofline", "decode_roofline", "state_pool_bytes")


def config_file():
    return common.load_json(os.path.join(
        REPO, "benchmarks", "configs", "solar-open2-250b.json"))


def model():
    return serve_kda.model_dict(config_file(), "serve")


def tiny_model():
    return serve_kda.model_dict(tiny_kda.CONFIG, "serve")


def test_the_new_configuration_entry():
    entry = {c["name"]: c for c in BENCH["configs"]}["solar-open2-250b"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["source"] == \
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"
    assert entry["file"] == "benchmarks/configs/solar-open2-250b.json"
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size", "max_position_embeddings"]
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    body = config_file()
    assert body["source"] == entry["source"] and body["reduced"] == entry["reduced"]
    for key, value in CATALOG.items():
        if key not in entry["reduced"]:
            assert body[key] == value, key
    # the cut: one period of layers, one rank of eight, an eighth of the rows
    assert {k: body[k] for k in entry["reduced"]} == {
        "num_hidden_layers": 4, "n_routed_experts": 40, "vocab_size": 24576,
        "max_position_embeddings": 8192}
    assert body["published"] == {k: CATALOG[k] for k in entry["reduced"]}
    # floors: a whole period and four layers, >= 8 experts, >= 1/8 of the rows
    assert body["num_hidden_layers"] >= 4 and body["n_routed_experts"] >= 8
    assert body["vocab_size"] * 8 >= CATALOG["vocab_size"]
    # no width is named as cut, and every assumption carries its reason
    for key in entry["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) or key == "vocab_size"
    assert all(isinstance(v, str) and len(v) > 40
               for v in body["assumed"].values())
    assert "eight v5e chips" in body["deployment"] \
        and "12 stages" in body["deployment"]
    assert body["serve_kda"] == "solar_open2_like"
    assert 0 < body["serve"]["limits"]["gap_mean"] < 1


def test_the_cell_lists_what_the_issue_names():
    """THIS cell's configuration, traffic, chips, end-to-end list exactly and
    its per-layer list with >=; nothing about how many cells there are or
    about any other cell (a later PR adds to both)."""
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b", "agentbatch64", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= {f"{stem}.agentbatch64" for stem in SPLITS}
    for m in BENCH["per_layer"]:
        if m["name"] in {f"{stem}.agentbatch64" for stem in SPLITS}:
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]]
    assert e2e == ["itl_p95_ms", "setup_s"]
    # the new files take precedence over their stems' (common.metric_spec)
    for name in ("kda_step_roofline", "kda_scan_roofline",
                 "expert_ffn_roofline", "decode_roofline"):
        assert common.metric_spec(REPO, f"{name}.agentbatch64")["reader"] \
            == "kda_roofline"
    assert common.metric_spec(REPO, "prefill_share.agentbatch64")["reader"] \
        == "trace_modules"
    mix = common.load_json(os.path.join(REPO, "benchmarks", "traffic",
                                        "agentbatch64.json"))
    assert (mix["runner"], mix["kind"]) == ("serve_kda", "backlog")
    assert mix["prompt_tokens"] == {"median": 2048, "sigma": 0.6, "min": 512,
                                    "max": 6144}
    assert mix["output_tokens"] == {"median": 512, "sigma": 0.5, "min": 128,
                                    "max": 2048}
    assert (mix["requests"], mix["block"]) == (1024, 64)
    assert (mix["check_requests"], mix["schedule_seed"], mix["pre_roll_s"]) \
        == (3, 20260927, 30.0)
    sizes = config_file()["serve"]
    assert (sizes["max_batch"], sizes["max_position_embeddings"],
            sizes["prefill_chunk"]) == (64, 8192, 1024)
    assert mix["runner"] in config_file()


def test_the_longest_request_fits_the_configuration():
    from benchmarks import traffic
    from benchmarks.runners import serve_family

    mix = common.load_json(os.path.join(REPO, "benchmarks", "traffic",
                                        "agentbatch64.json"))
    m, sizes = model(), config_file()["serve"]
    reqs = traffic.backlog(mix, 2**31 + 3, m["vocab"])
    assert len(reqs) == 1024 <= sizes["queue_depth"]
    assert max(len(r.prompt) + r.max_new for r in reqs) <= m["max_seq"]
    assert min(len(r.prompt) for r in reqs) >= 512
    assert 24000 < max(int(r.prompt.max()) for r in reqs[:8]) < 24576
    pieces = serve_family._piece_buckets(
        reqs, sizes["prefill_chunk"], m["max_seq"],
        lambda n, top: min(max(8, 1 << (n - 1).bit_length()), top))
    assert max(pieces) == 1024 and all(b <= 1024 for b in pieces)
    # every position of the block's requests at once fits the page pool
    cycle = reqs[:mix["block"]]
    assert sum(len(r.prompt) + r.max_new for r in cycle) \
        <= sizes["kv_pool_tokens"]


def test_model_dict_and_the_programs_tree():
    from oim_tpu.models import generate as gen
    from oim_tpu.models import llama

    m = model()
    assert m["pattern"] == "*EKEKEKE" and m["n_layers"] == 4
    assert (m["n_experts"], m["experts_held"], m["moe_top_k"]) == (320, 40, 8)
    assert (m["kda_heads"], m["kda_head_dim"], m["kda_conv"], m["kda_rank"]) \
        == (64, 128, 4, 128)
    cfg = serve_kda.program_config(m)
    import dataclasses
    assert dataclasses.replace(
        cfg, n_layers=48, gqa_layers=tuple(range(0, 48, 4)), expert_rank="",
        vocab=196608, max_seq=1048576) == llama.SOLAR_OPEN2_250B
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    weights.check_against_program(m, shapes)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 3_308_377_920 \
        == roofline_kda.held_params(m) == llama.num_params(cfg)
    assert gen.state_bytes(cfg) == roofline_kda.slot_state_bytes(m)
    assert gen.page_bytes(cfg, 1) == roofline_kda.position_bytes(m) == 4096
    # the whole model, by the same arithmetic: 250.29 B
    whole = {**m, "experts_held": 320, "vocab": 196608,
             "pattern": serve_kda.pattern(48, CATALOG["gqa_layers"])}
    assert roofline_kda.held_params(whole) == llama.num_params(
        llama.SOLAR_OPEN2_250B)
    assert abs(roofline_kda.held_params(whole) / 250.29e9 - 1) < 1e-4


def test_the_special_draws_follow_the_familys_initialisation():
    m = tiny_model()
    root = weights.root_key(3)
    w = weights.layer_slice(root, m, "kda_layers", 1)
    step = jax.nn.softplus(w["dt_bias"])
    assert 1e-3 * 0.999 <= float(step.min()) and float(step.max()) <= 0.1 * 1.001
    assert 0.0 <= float(w["A_log"].min()) and float(w["A_log"].max()) <= np.log(16)
    assert w["dt_bias"].shape == (64,) and w["A_log"].shape == (4,)
    # a layer drawn alone is the layer of the whole tree, bit for bit
    tree = weights.make(root, m)
    for name, leaf in w.items():
        np.testing.assert_array_equal(leaf, tree["kda_layers"][name][1])
    for group in ("expert_layers", "attn_layers"):
        one = weights.layer_slice(root, m, group, 0)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b[0]),
                     one, tree[group])


def test_a_program_without_the_family_is_refused_in_one_line(monkeypatch):
    """The parent's ``Config`` has no KDA field: the runner's first act ends
    the run with one line before any weights."""
    import dataclasses

    from oim_tpu.models import llama

    fields = [f for f in dataclasses.fields(llama.Config)
              if not f.name.startswith(("kda_", "gqa_", "use_gqa"))]
    Parent = dataclasses.make_dataclass(
        "Config", [(f.name, f.type, f) for f in fields], frozen=True)
    monkeypatch.setattr(llama, "Config", Parent)
    with pytest.raises(SystemExit) as err:
        serve_kda.program_config(model())
    assert "cannot express the solar_open2_like family" in str(err.value)
    assert "\n" not in str(err.value)


@pytest.mark.parametrize("key,value", [
    ("use_rope", True), ("kda_use_full_proj", True), ("norm_topk_prob", False),
    ("first_k_dense_replace", 1), ("n_shared_experts", 2),
    ("tie_word_embeddings", True)])
def test_what_the_family_does_not_implement_is_refused(key, value):
    with pytest.raises(SystemExit, match="solar_open2_like family runs"):
        serve_kda.model_dict({**config_file(), key: value}, "serve")


def test_a_rank_the_program_does_not_hold_is_refused():
    with pytest.raises(SystemExit, match="rank head_dim"):
        serve_kda.program_config({**tiny_model(), "kda_rank": 8})
    with pytest.raises(SystemExit, match="do not divide"):
        serve_kda.model_dict({**config_file(), "n_routed_experts": 48}, "serve")


# -- the reference against blocks written out by hand --------------------------

def hand_kda(x, w, m):
    """One KDA block in float64 numpy, a position and a head at a time."""
    H, d, K = m["kda_heads"], m["kda_head_dim"], m["kda_conv"]
    x = np.asarray(x, np.float64)
    w = jax.tree.map(lambda a: np.asarray(a, np.float64), w)
    T = x.shape[0]
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w["norm"]
    qkv = h @ w["w_qkv"]
    padded = np.concatenate([np.zeros((K - 1, 3 * H * d)), qkv])
    conv = sum(w["conv_w"][j] * padded[j:j + T] for j in range(K))
    conv = conv / (1 + np.exp(-conv))
    f = (h @ w["w_f1"]) @ w["w_f2"] + w["dt_bias"]
    gate = (h @ w["w_g1"]) @ w["w_g2"] + w["g_bias"]
    beta = 2 / (1 + np.exp(-(h @ w["w_beta"])))
    out = np.zeros((T, H * d))
    for head in range(H):
        q, k, v = (conv[:, part * H * d + head * d:][:, :d] for part in range(3))
        S = np.zeros((d, d))
        for t in range(T):
            qt = q[t] / np.sqrt(q[t] @ q[t] + 1e-6) / np.sqrt(d)
            kt = k[t] / np.sqrt(k[t] @ k[t] + 1e-6)
            g = -np.exp(w["A_log"][head]) * np.log1p(
                np.exp(f[t, head * d:(head + 1) * d]))
            S = (np.eye(d) - beta[t, head] * np.outer(kt, kt)) \
                @ (np.exp(g)[:, None] * S) + beta[t, head] * np.outer(kt, v[t])
            o = S.T @ qt
            o = o / np.sqrt((o * o).mean() + 1e-5) * w["o_norm"]
            out[t, head * d:(head + 1) * d] = o / (
                1 + np.exp(-gate[t, head * d:(head + 1) * d]))
    return x + out @ w["w_out"]


def hand_experts(x, w, m):
    x = np.asarray(x, np.float64)
    w = jax.tree.map(lambda a: np.asarray(a, np.float64), w)
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w["norm"]
    mo = w["moe"]
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731

    def ffn(rows, e):
        return (silu(rows @ e["w_gate"]) * (rows @ e["w_up"])) @ e["w_down"]

    out = x + ffn(h, mo["shared"])
    s = 1 / (1 + np.exp(-(h @ mo["router"])))
    for t in range(x.shape[0]):
        chosen = np.argsort(-(s[t] + mo["bias"]), kind="stable")[:m["moe_top_k"]]
        total = s[t, chosen].sum()
        for e in chosen:
            if m["expert_first"] <= e < m["expert_first"] + m["experts_held"]:
                i = e - m["expert_first"]
                out[t] += s[t, e] / total * m["routed_scale"] * ffn(
                    h[t], {k: mo[k][i] for k in ("w_gate", "w_up", "w_down")})
    return out


def hand_attention(x, w, m):
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    x = np.asarray(x, np.float64)
    w = jax.tree.map(lambda a: np.asarray(a, np.float64), w)
    T = x.shape[0]
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w["norm"]
    q = (h @ w["wq"]).reshape(T, H, hd)
    k = (h @ w["wk"]).reshape(T, KV, hd)
    v = (h @ w["wv"]).reshape(T, KV, hd)
    out = np.zeros((T, H, hd))
    for head in range(H):
        kv = head // (H // KV)
        for t in range(T):
            s = q[t, head] @ k[:t + 1, kv].T / np.sqrt(hd)
            p = np.exp(s - s.max())
            out[t, head] = (p / p.sum()) @ v[:t + 1, kv]
    gated = out.reshape(T, H * hd) / (1 + np.exp(-(h @ w["wg"])))
    return x + gated @ w["wo"]


@pytest.mark.parametrize("kind,group,hand", [
    ("K", "kda_layers", hand_kda), ("E", "expert_layers", hand_experts),
    ("*", "attn_layers", hand_attention)])
def test_reference_block_against_a_hand_written_one(kind, group, hand):
    m = tiny_model()
    w = weights.layer_slice(weights.root_key(2), m, group, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (19, m["dim"]))
    np.testing.assert_allclose(ref.layer_forward(x, w, m, kind),
                               hand(x, w, m), atol=2e-5)


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
    return tiny_kda.make_root(str(tmp_path_factory.mktemp("bench-kda")))


def test_the_kda_runner_runs_a_tiny_cell(root, capsys):
    import benchmark_tiny as tiny

    line, text = tiny.run_cell(root, tiny_kda.CELL, 2**31 + 11, 2.5, 0,
                               capsys, earlier=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 12
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert "warmed bucket=32" in text and "compiles_in_window=0" in text
    assert "correct? number=gap_mean" in text
    assert f"'state_bytes': {tiny_kda.STATE_BYTES}" in text
    assert f"'state_bytes_by_kind': {{'kda': {tiny_kda.STATE_BYTES}}}" in text


def test_the_kda_runner_traced_reports_the_engines_counters(root, capsys):
    import benchmark_tiny as tiny

    line = tiny.run_cell(root, tiny_kda.CELL, 7, 2.5, 1, capsys)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # host and counter metrics only: no device plane on the CPU
    assert set(got) == {f"{s}.agentbatch64" for s in (
        "itl_p50_ms", "itl_p90_ms", "itl_p97_ms", "out_tokens_per_s",
        "slice_rate", "experts_touched", "expert_load_max_over_mean",
        "state_pool_bytes")}
    assert 1 <= got["experts_touched.agentbatch64"] <= 4  # of the 4 held
    assert got["expert_load_max_over_mean.agentbatch64"] >= 1
    assert got["state_pool_bytes.agentbatch64"] == tiny_kda.STATE_BYTES


def test_a_broken_state_carry_is_not_correct(root, capsys, monkeypatch):
    """A scan that hands out an empty state: every slice after a prompt's
    first starts from nothing and every decode step from the last slice's
    own tokens. The run serves, fails no request, and is not ``correct``."""
    import benchmark_tiny as tiny
    from oim_tpu.ops import kda
    from oim_tpu.serve import engine

    real = kda.scan

    def forgetful(layer, x, state, conv, n_tokens, dims, eps):
        out, state, conv = real(layer, x, state, conv, n_tokens, dims, eps)
        return out, jnp.zeros_like(state), conv

    monkeypatch.setattr(kda, "scan", forgetful)
    engine._target_programs.cache_clear()
    try:
        line, text = tiny.run_cell(root, tiny_kda.CELL, 11, 2.0, 0, capsys,
                                   earlier=True)
    finally:
        monkeypatch.undo()
        engine._target_programs.cache_clear()
    assert line["correct"] is False and line["failed"] == 0
    assert "compiles_in_window=0" in text


def test_check_limits_family_reads_sound_and_control(root, capsys):
    from benchmarks import check_limits_family

    assert check_limits_family.main(
        ["--workload", tiny_kda.CELL, "--seeds", "5", "--seconds", "1.5"],
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
    # ISSUE 37's figures: a KDA mixer 137.74 M, a gated GQA mixer 109.05 M,
    # an expert 15.73 M, an expert block with 40 held 646.2 M
    D = 4096
    assert roofline_kda.kda_layer_params(m) - D == 137_740_480
    assert roofline_kda.attention_layer_params(m) - D == 3 * D * 8192 + 2 * D * 1024
    assert abs(roofline_kda.attention_layer_params(m) / 109.05e6 - 1) < 1e-3
    assert roofline_kda.expert_params(m) == 3 * 4096 * 1280 == 15_728_640
    assert abs(roofline_kda.expert_layer_params(m) / 646.2e6 - 1) < 1e-3
    assert abs(roofline_kda.expert_layer_params(m, 320) / 5050e6 - 1) < 1e-3
    assert abs(roofline_kda.weight_bytes(m) / 6.62e9 - 1) < 2e-3
    assert roofline_kda.slot_state_bytes(m) == 3 * (64 * 128 * 128 * 4
                                                    + 3 * 24576 * 2)
    assert abs(64 * roofline_kda.slot_state_bytes(m) / 0.83e9 - 1) < 0.01
    assert 64 * 8192 * roofline_kda.position_bytes(m) == 2_147_483_648
    assert roofline_kda.expected_held_touched(m, 64) == pytest.approx(
        40 * (1 - (1 - 8 / 320) ** 64))


def test_decode_step_counts():
    m = model()
    state = 2 * 64 * roofline_kda.slot_state_bytes(m)
    step = roofline_kda.kda_step(m, 64)
    assert step["bytes"] == state + 3 * roofline_kda.kda_layer_params(m) * 2
    assert step["bytes"] / 819e9 > step["flops"] / 197e12  # memory bound
    # ISSUE 37's round at 64 rows and about 4k positions a row, all 40 held
    # experts touched: 9.1 GB
    least = roofline_kda.decode_step_min_bytes(m, 64, 64 * 4096, 40)
    assert abs(least / 9.1e9 - 1) < 0.02
    assert abs(state / 1.61e9 - 1) < 0.05          # ISSUE: 1.61 GB of state
    kv = 64 * 4096 * roofline_kda.position_bytes(m)
    assert abs(kv / 1.07e9 - 1) < 0.01             # ISSUE: 1.07 GB of K/V
    fewer = roofline_kda.decode_step_min_bytes(m, 64, 64 * 4096, 32)
    assert least - fewer == pytest.approx(4 * 8 * 15_728_640 * 2)


def test_scan_and_product_counts():
    m, peak = model(), roofline.peaks("TPU v5 lite")
    scan = roofline_kda.kda_scan(m, 1024)
    projections = 3 * 1024 * 2 * 4096 * (3 * 8192 + 2 * 128 + 64 + 8192)
    assert scan["flops"] > projections
    assert scan["flops"] == pytest.approx(
        projections + 3 * 1024 * (4 * 128 * 8192
                                  + 64 * (6 * 128 * 128 + 5 * 64 * 128)))
    assert roofline.roofline_seconds(scan, peak)[1] == "compute"
    # a short slice is bound by the mixers' weights
    assert roofline.roofline_seconds(
        roofline_kda.kda_scan(m, 16), peak)[1] == "memory"
    assert roofline_kda.kda_scan(m, 16)["bytes"] > 3 * 137.7e6 * 2
    one = roofline_kda.expert_product(m, 1024 * 8 * 40 / 320, 40)
    assert one["flops"] == 2 * 1024 * 4096 * 1280
    assert one["bytes"] == 40 * 4096 * 1280 * 2 + 1024 * (4096 + 1280) * 2
    assert roofline.roofline_seconds(one, peak)[1] == "memory"


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


GMM = ("%ragged-dot-none{} = bf16[{},1280]{{1,0:T(8,128)(2,1)S(1)}} "
       "custom-call(s32[1]{{0:T(128)}} %a, s32[161]{{0:T(512)S(1)}} %b)")
UPDATE = ("%fusion.71 = f32[3,64,64,128,128]{4,3,2,1,0:T(8,128)} fusion("
          "f32[3,64,64,128,128]{4,3,2,1,0:T(8,128)} %get-tuple-element.9)")
QKV = ("%fusion.1681 = bf16[1,{},24576]{{2,1,0:T(8,128)(2,1)S(1)}} fusion("
       "bf16[3,4096,24576]{{2,1,0:T(8,128)(2,1)}} %get-tuple-element.4457)")
DENSE = ("%fusion.90 = bf16[40,64,1280]{2,1,0:T(8,128)(2,1)S(1)} fusion("
         "bf16[4,40,4096,1280]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.7)")
RUNG = ("%fusion.91 = bf16[40,256,1280]{2,1,0:T(8,128)(2,1)S(1)} fusion("
        "bf16[4,40,4096,1280]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.8)")
OTHER = "%fusion.5 = f32[64,4096]{1,0:T(8,128)} fusion(f32[64,4096] %p)"
SCOPES = {
    UPDATE: "jit(step)/while/body/closed_call/kda_step/mul",
    QKV.format(1024): "jit(prefill)/while/body/kda_scan/kda_scan/dot_general",
    QKV.format(256): "jit(prefill)/while/body/kda_scan/kda_scan/dot_general",
    DENSE: "jit(step)/while/body/closed_call/moe_gmm/dot_general",
    RUNG: "jit(prefill)/while/body/cond/branch_0_fun/moe_gmm/dot_general",
    GMM.format("", 8192): "jit(prefill)/cond/branch_2_fun/moe_gmm/ragged_dot",
}


def metric_args(name):
    return common.load_json(os.path.join(
        REPO, "benchmarks", "metrics", f"{name}.agentbatch64.json"))["args"]


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


def test_kda_reader_decode_and_mixers(capsys):
    reader = common.plugin(REPO, "readers", "kda_roofline")
    m, peak = model(), roofline.peaks("TPU v5 lite")
    trace = recorded(
        [(UPDATE, 5_000_000), (OTHER, 11_000_000)],
        [(QKV.format(1024), 40_000_000), (OTHER, 5_000_000)])
    result = reader_result(trace, SCOPES, experts_touched=32.0)
    got = reader.read(result, metric_args("decode_roofline"))
    least = roofline_kda.decode_step_min_bytes(m, 64.0, 260_000.0, 32.0)
    assert got == pytest.approx(100 * least / 819e9 / 16.00002e-3)
    assert 0 < got < 100
    got = reader.read(result, metric_args("kda_step_roofline"))
    least = roofline_kda.kda_step(m, 64.0)["bytes"] / 819e9
    assert got == pytest.approx(100 * least / 5e-3) and 0 < got < 100
    got = reader.read(result, metric_args("kda_scan_roofline"))
    least = roofline.roofline_seconds(roofline_kda.kda_scan(m, 1024), peak)[0]
    assert got == pytest.approx(100 * least / 40e-3) and 0 < got < 100
    text = capsys.readouterr().out
    assert "bound: memory" in text and "bound: [('compute', 1)]" in text
    # a program without the scopes or the counter (the parent), a cell of
    # another family, a run without a trace: nothing, and no raise
    bare = reader_result(trace, {}, experts_touched=32.0)
    assert reader.read(bare, metric_args("kda_step_roofline")) is None
    assert reader.read(bare, metric_args("kda_scan_roofline")) is None
    assert reader.read(reader_result(trace, SCOPES),
                       metric_args("decode_roofline")) is None
    assert reader.read(reader_result(None), metric_args("decode_roofline")) is None
    other = reader_result(trace, SCOPES, {"pattern": "MEM"}, experts_touched=3)
    assert reader.read(other, metric_args("decode_roofline")) is None


def test_kda_reader_expert_products_in_every_form(capsys):
    """Decode: the dense form under the scope. Prefill: a bounded rung's
    batched products under the scope AND the last rung's grouped products
    (found by scope or by their HLO line), against THREE products a block
    at the slice's own length."""
    reader = common.plugin(REPO, "readers", "kda_roofline")
    m, peak = model(), roofline.peaks("TPU v5 lite")
    args = metric_args("expert_ffn_roofline")

    def least(tokens, touched):
        return 3 * 4 * roofline.roofline_seconds(roofline_kda.expert_product(
            m, tokens * 8 / 8, touched), peak)[0]

    step = [(DENSE, 3_000_000), (DENSE, 2_000_000), (OTHER, 900)]
    gmm_unscoped = GMM.format(".1", 8192)
    chunk = [(QKV.format(1024), 1_000_000), (RUNG, 2_000_000),
             (GMM.format("", 8192), 3_000_000), (gmm_unscoped, 4_000_000),
             (OTHER, 7_000_000)]
    got = reader.read(reader_result(recorded(step, chunk), SCOPES,
                                    experts_touched=32.0), args)
    want = 2 * least(64.0, 32.0) + least(
        1024, roofline_kda.expected_held_touched(m, 1024))
    assert got == pytest.approx(100 * want / (2 * 5e-3 + 9e-3))
    assert 0 < got < 100
    assert "expert products in prefill: 1 slices" in capsys.readouterr().out
    # a prefill whose slice length cannot be read is left out on both sides
    blind = [(RUNG, 2_000_000), (OTHER, 7_000_000)]
    got = reader.read(reader_result(recorded(step, blind), SCOPES,
                                    experts_touched=32.0), args)
    assert got == pytest.approx(100 * 2 * least(64.0, 32.0) / 10e-3)
    assert reader.read(reader_result(recorded(step, chunk), SCOPES), args) is None
