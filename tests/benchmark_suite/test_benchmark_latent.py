"""What PR 28 adds to the benchmark, on the CPU: the new configuration's
entry, the deepseek_like reference against a layer written out by hand,
the family runner on a tiny cell of ``benchmark_tiny``'s temporary copy, the
new readers on recorded input, and the byte and operation counts against
hand counts."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny_latent as tiny_latent
from benchmarks import common, roofline, roofline_latent
from benchmarks.runners import serve_family

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELL = "joyai-llm-flash.longctx"
# The catalog row's ``config`` (model-configs/architectures.jsonl, row
# JoyAI-LLM-Flash), copied here: every number must be in the file under
# the same key unless ``reduced`` names the key.
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280,
}


def config_file():
    return common.load_json(os.path.join(
        REPO, "benchmarks", "configs", "joyai-llm-flash.json"))


def model():
    return serve_family.model_dict(config_file(), "serve")


def test_the_new_configuration_entry():
    """test_benchmark_contract.test_configuration_entry's rules, with this
    family's published widths in place of the Mistral family's."""
    entry = {c["name"]: c for c in BENCH["configs"]}["joyai-llm-flash"]
    body = config_file()
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert body["source"] == entry["source"] and body["reduced"] == entry["reduced"]
    assert entry["reduced"] == ["num_hidden_layers", "max_position_embeddings",
                                "num_nextn_predict_layers"]
    for key in entry["reduced"]:
        assert not re.search(r"(_dim|_rank|_size|head_dim|per_tok)$", key)
    for key, value in CATALOG.items():
        if key in entry["reduced"]:
            assert body["published"][key] == value
        else:
            assert body[key] == value, key
    assert body["serve"]["num_hidden_layers"] == 5  # dense + the floor of 4
    assert body["serve"]["max_position_embeddings"] == 32768
    assert "seven further chips as pipeline stages" in body["deployment"]


def test_the_cell_lists_what_the_issue_names():
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai-llm-flash", "longctx", 1)
    assert len(BENCH["workloads"]) == 4
    assert not any(w["chips"] == 4 for w in BENCH["workloads"])
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {f"{stem}.longctx" for stem in (
        "gen_lateness_p95_ms", "queue_wait_p95_ms", "ttft_p50_ms",
        "prefill_share", "itl_p90_ms", "itl_p97_ms", "decode_step_ms",
        "device_idle", "idle_step_roundtrip", "idle_emit", "idle_admit",
        "idle_unannotated", "emit_ms", "latent_attn_roofline",
        "expert_gmm_roofline", "experts_touched",
        "expert_load_max_over_mean", "latent_pool_fill", "prefill_chunk_ms",
        "idle_prefill_chunk")}
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]]
    assert e2e == ["itl_p95_ms", "setup_s"]
    mix = common.load_json(os.path.join(REPO, "benchmarks", "traffic", "longctx.json"))
    assert mix["kind"] == "open_loop" and mix["schedule_seed"] == 20260927
    assert mix["prompt_tokens"] == {"median": 8192, "sigma": 0.6, "min": 2048,
                                    "max": 24576}
    assert mix["output_tokens"] == {"median": 192, "sigma": 0.5, "min": 64,
                                    "max": 512}
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"])
    # ISSUE 28 named 10 s; one whole turn of the cycle is what made the
    # window's work the same whatever the seed (PERF.md section 4)
    assert (mix["check_requests"], mix["pre_roll_s"]) == (3, 50.0)


def test_the_longest_request_fits_the_configuration():
    from benchmarks import traffic

    mix = common.load_json(os.path.join(REPO, "benchmarks", "traffic", "longctx.json"))
    m = model()
    reqs = traffic.open_loop(mix, 2**31 + 3, 50.0, m["vocab"])
    assert max(len(r.prompt) + r.max_new for r in reqs) <= m["max_seq"]
    assert min(len(r.prompt) for r in reqs) >= 2048
    assert max(int(r.prompt.max()) for r in reqs[:8]) > 100_000  # whole vocabulary
    sizes = config_file()["serve"]
    chunk = sizes["prefill_chunk"]
    pieces = serve_family._piece_buckets(
        reqs, chunk, m["max_seq"], lambda n, top: min(max(8, 1 << (n - 1).bit_length()), top))
    assert max(pieces) == chunk and all(b <= chunk for b in pieces)


def test_model_dict_and_the_programs_tree():
    from benchmarks import weights_deepseek
    from oim_tpu.models import llama

    m = model()
    cfg = serve_family.program_config(m)
    weights_deepseek.check_against_program(m, jax.eval_shape(
        lambda k: llama.init(k, cfg), jax.random.PRNGKey(0)))
    spec = weights_deepseek.tree_spec(m)
    assert sum(int(np.prod(s)) for s, _, _ in spec.values()) == 5_558_141_952
    assert llama.num_params(cfg) == 5_558_141_952
    assert spec["layers/moe/w_gate"][0] == (4, 256, 2048, 768)
    assert spec["dense_layers/w_gate"][0] == (1, 2048, 7168)
    assert spec["layers/wkv_a"][0] == (4, 2048, 576)


def test_a_program_without_the_family_is_refused_in_one_line(monkeypatch):
    """What the parent commit does: its Config has no such field."""
    from oim_tpu.models import llama

    class Old:
        def __init__(self, **fields):
            if "kv_lora_rank" in fields:
                raise TypeError("Config.__init__() got an unexpected keyword "
                                "argument 'kv_lora_rank'")

    monkeypatch.setattr(llama, "Config", Old)
    with pytest.raises(SystemExit, match="cannot express the deepseek_like"):
        serve_family.program_config(model())


@pytest.mark.parametrize("key,value", [("n_group", 8), ("topk_group", 4),
                                       ("norm_topk_prob", False),
                                       ("scoring_func", "softmax"),
                                       ("rope_scaling", {"type": "yarn"})])
def test_what_the_family_does_not_implement_is_refused(key, value):
    with pytest.raises(SystemExit):
        serve_family.model_dict({**config_file(), key: value}, "serve")


# -- the reference against a layer written out by hand ----------------------


def hand_layer(x, w, m, dense):
    """One block in float64 numpy, loops and all: no jax, no shared code."""
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    T, H = x.shape[0], m["n_heads"]
    r, nope, rope, dv = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"])

    def rms(a, g):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-6) * f(g)

    def rot(vec, pos):  # interleaved pairs (2i, 2i + 1)
        out = vec.copy()
        for i in range(rope // 2):
            ang = pos / (m["rope_theta"] ** (2 * i / rope))
            a, b = vec[2 * i], vec[2 * i + 1]
            out[2 * i] = a * np.cos(ang) - b * np.sin(ang)
            out[2 * i + 1] = b * np.cos(ang) + a * np.sin(ang)
        return out

    h = rms(x, w["attn_norm"])
    q = (rms(h @ f(w["wq_a"]), w["q_norm"]) @ f(w["wq_b"])).reshape(T, H, nope + rope)
    ckv = h @ f(w["wkv_a"])
    c = rms(ckv[:, :r], w["kv_norm"])
    kv = (c @ f(w["wkv_b"])).reshape(T, H, nope + dv)
    k_r = np.stack([rot(ckv[t, r:], t) for t in range(T)])
    o = np.zeros((T, H, dv))
    for t in range(T):
        for hd in range(H):
            qt = np.concatenate([q[t, hd, :nope], rot(q[t, hd, nope:], t)])
            s = np.array([qt @ np.concatenate([kv[u, hd, :nope], k_r[u]])
                          for u in range(t + 1)]) / np.sqrt(nope + rope)
            p = np.exp(s - s.max())
            o[t, hd] = (p / p.sum()) @ kv[:t + 1, hd, nope:]
    x = x + o.reshape(T, H * dv) @ f(w["wo"])
    h = rms(x, w["mlp_norm"])

    def swiglu(a, g, u, d):
        gate = a @ f(g)
        return (gate / (1 + np.exp(-gate)) * (a @ f(u))) @ f(d)

    if dense:
        return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
    e = w["moe"]
    out = x + swiglu(h, e["shared"]["w_gate"], e["shared"]["w_up"],
                     e["shared"]["w_down"])
    for t in range(T):
        s = 1 / (1 + np.exp(-(h[t] @ f(e["router"]))))
        top = np.argsort(-(s + f(e["bias"])), kind="stable")[: m["moe_top_k"]]
        wt = s[top] / (s[top].sum() + 1e-20) * m["routed_scale"]
        for j, wj in zip(top, wt):
            out[t] += wj * swiglu(h[t], e["w_gate"][j], e["w_up"][j], e["w_down"][j])
    return out


@pytest.mark.parametrize("group", ["dense_layers", "layers"])
def test_reference_layer_against_a_hand_written_one(group):
    from benchmarks import weights_deepseek
    from benchmarks.reference import deepseek_like as ref

    m = serve_family.model_dict(tiny_latent.CONFIG, "serve")
    root = weights_deepseek.root_key(3)
    w = jax.jit(lambda r: weights_deepseek.layer_slice(r, m, group, 0))(root)
    x = np.random.default_rng(0).normal(size=(2048, 64)).astype(np.float32)
    got = np.asarray(ref.layer_forward(jnp.asarray(x), w, m, group))[:12]
    want = hand_layer(x[:12].astype(np.float64), w, m, group == "dense_layers")
    # float32 at HIGHEST against float64, values of order 3: 2e-5
    assert np.abs(got - want).max() < 2e-5


def test_the_control_reads_worse_than_the_reference_reads_itself():
    from benchmarks.reference import deepseek_like as ref

    m = serve_family.model_dict(tiny_latent.CONFIG, "serve")
    prompt = np.random.default_rng(1).integers(0, 512, 40).tolist()
    lg = ref.logits_many(5, m, [prompt], [np.arange(39, 40)])[0]
    served = [int(jnp.argmax(lg[0]))]
    assert ref.served_gaps_many(5, m, [(prompt, served)])[0].max() == 0.0
    long = np.random.default_rng(2).integers(0, 512, 200).tolist()
    control = ref.served_gaps_many(5, m, [(long[:100], long[100:])], control=True)[0]
    assert control.mean() > 0.01


# -- the family runner on a tiny cell ---------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_latent.make_root(str(tmp_path_factory.mktemp("bench-latent")))


def test_the_family_runner_runs_a_tiny_cell(root, capsys):
    import benchmark_tiny as tiny

    line, text = tiny.run_cell(root, tiny_latent.CELL, 2**31 + 11, 2.5, 0,
                               capsys, earlier=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 12
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert "warmed bucket=32" in text and "compiles_in_window=0" in text
    assert "correct? number=gap_mean" in text


def test_the_family_runner_traced_reports_the_engines_counters(root, capsys):
    import benchmark_tiny as tiny

    line = tiny.run_cell(root, tiny_latent.CELL, 7, 2.5, 1, capsys)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # host and counter metrics only: no device plane on the CPU
    assert set(got) == {f"{s}.longctx" for s in (
        "gen_lateness_p95_ms", "queue_wait_p95_ms", "ttft_p50_ms",
        "itl_p90_ms", "itl_p97_ms", "experts_touched",
        "expert_load_max_over_mean", "latent_pool_fill")}
    assert 4 <= got["experts_touched.longctx"] <= 16
    assert got["expert_load_max_over_mean.longctx"] >= 1
    assert 0 < got["latent_pool_fill.longctx"] <= 100


def test_window_means_come_from_the_samples_inside_the_window():
    def sample(t, steps, touched, fullest, used):
        return (t, {"expert_load_steps": steps, "experts_touched_sum": touched,
                    "expert_load_max_over_mean_sum": fullest},
                {"used_pages": used, "total_pages": 200})

    samples = [sample(0.0, 0, 0.0, 0.0, 0), sample(1.0, 10, 1600.0, 30.0, 50),
               sample(2.0, 30, 4800.0, 100.0, 150), sample(9.0, 99, 9e9, 9e9, 200)]
    means = serve_family._window_means(samples, 0.5, 2.5)
    assert means == {"latent_pool_fill": 50.0, "experts_touched": 160.0,
                     "expert_load_max_over_mean": 3.5}
    assert serve_family._window_means(samples[:1], 0.0, 5.0) == {}
    # a program without the counters: the fill alone
    bare = [(t, {}, p) for t, _, p in samples]
    assert serve_family._window_means(bare, 0.5, 2.5) == {"latent_pool_fill": 50.0}


# -- byte and operation counts against hand counts ---------------------------


def test_latent_decode_attention_counts():
    m = model()
    assert roofline_latent.latent_entry_bytes(m) == 1152  # 576 bfloat16
    work = roofline_latent.latent_decode_attention(m, 32, 320_000)
    # 5 layers x (320 032 entries of 1152 B + wkv_b 512 x 32 x 256 x 2 B)
    assert work["bytes"] == 5 * (320_032 * 1152 + 8_388_608) == 1_885_327_360
    # 5 x (2 x 320 000 x 32 x (576 + 512) + 2 x 32 x 32 x 512 x 256)
    assert work["flops"] == 5 * (22_282_240_000 + 268_435_456)
    least, bound = roofline.roofline_seconds(work, roofline.peaks("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(1_885_327_360 / 819e9)


def test_grouped_product_counts():
    m = model()
    assert roofline_latent.expected_experts_touched(m, 32) == pytest.approx(
        256 * (1 - (1 - 8 / 256) ** 32)) == pytest.approx(163.4, abs=0.1)
    decode = roofline_latent.grouped_product(m, 256, 163.4)
    assert decode["flops"] == 2 * 256 * 2048 * 768 == 805_306_368
    assert decode["bytes"] == pytest.approx(
        163.4 * 2048 * 768 * 2 + 256 * (2048 + 768) * 2)
    peak = roofline.peaks("TPU v5 lite")
    assert roofline.roofline_seconds(decode, peak)[1] == "memory"
    chunk = roofline_latent.grouped_product(m, 16384, 256)
    assert chunk["flops"] == 2 * 16384 * 2048 * 768 == 51_539_607_552
    # 64 rows an expert: 0.26 ms of operations against 1.1 ms of bytes
    assert roofline.roofline_seconds(chunk, peak)[1] == "memory"
    assert roofline.roofline_seconds(
        roofline_latent.grouped_product(m, 16384 * 8, 256), peak)[1] == "compute"


# -- the new reader on recorded input ----------------------------------------


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


# Operation names as a v5e trace of the cell has them (my chip runs, PR 28).
ATTN = ("%while.82 = (s32[]{:T(128)}, f32[32,32]{1,0:T(8,128)S(1)}, "
        "f32[32,32]{1,0:T(8,128)S(1)}, f32[32,32,512]{2,1,0:T(8,128)S(1)}, "
        "s32[]{:T(128)}, /*index=5*/s32[32,2048]{0,1:T(8,128)}) while(%tuple.9)")
LAYERS = ("%while.81 = (s32[]{:T(128)}, bf16[32,1,2048]{2,0,1:T(8,128)(2,1)S(1)}, "
          "bf16[5,18433,16,640]{3,2,1,0:T(8,128)(2,1)}, f32[4,2]{1,0:T(4,128)}) "
          "while(%tuple.8)")
GMM = ("%ragged-dot-none{} = bf16[{},768]{{1,0:T(8,128)(2,1)}} custom-call("
       "s32[1]{{0:T(128)}} %a, s32[1025]{{0:T(1024)S(1)}} %b)")
METADATA = "%ragged-dot-metadata = (s32[1025]{0:T(1024)S(1)}) custom-call(%c)"


def metric_args(name):
    return common.load_json(os.path.join(
        REPO, "benchmarks", "metrics", f"{name}.json"))["args"]


def reader_result(trace, **stats):
    return {"trace": trace, "stats": stats,
            "device": {"kind": "TPU v5 lite", "platform": "tpu"},
            "shapes": {"model": model(), "live_rows": 32.0,
                       "live_kv_tokens": 320_000.0}}


def test_latent_roofline_reader_attention():
    reader = common.plugin(REPO, "readers", "latent_roofline")
    least = 1_885_327_360 / 819e9  # test_latent_decode_attention_counts
    trace = recorded([(ATTN, 2_000_000), ("%fusion.1 = f32[8] fusion()", 500),
                      (LAYERS, 900), (ATTN, 2_604_000)], [(ATTN, 9_000_000)])
    args = metric_args("latent_attn_roofline")  # the committed patterns
    got = reader.read(reader_result(trace), args)
    # two steps of 4.604 ms of attention each; the layer scan's while is not
    # the attention's, and the prefill's while is not a step's
    assert got == pytest.approx(100 * least / 4.604e-3)
    assert reader.read(reader_result(trace), {**args, "op": "nothing"}) is None
    assert reader.read(reader_result(None), args) is None
    no_rows = reader_result(trace)
    del no_rows["shapes"]["live_rows"]
    assert reader.read(no_rows, args) is None


def test_latent_roofline_reader_gmm():
    reader = common.plugin(REPO, "readers", "latent_roofline")
    m, peak = model(), roofline.peaks("TPU v5 lite")
    step = [(GMM.format(i, 256), 1_000_000) for i in ("", ".1", ".2")]
    step.append((METADATA, 40_000))  # not a product
    chunk = [(GMM.format(i, 16384), 2_000_000) for i in ("", ".1", ".2")]
    args = metric_args("expert_gmm_roofline")  # the committed patterns
    got = reader.read(reader_result(recorded(step, chunk), experts_touched=160.0), args)
    d = roofline.roofline_seconds(roofline_latent.grouped_product(m, 256, 160.0), peak)[0]
    p = roofline.roofline_seconds(roofline_latent.grouped_product(
        m, 16384, roofline_latent.expected_experts_touched(m, 2048)), peak)[0]
    assert got == pytest.approx(100 * (6 * d + 3 * p) / (6 * 1e-3 + 3 * 2e-3))
    assert 0 < got < 100
    # a program without the counter (the parent): nothing, and no raise
    assert reader.read(reader_result(recorded(step, chunk)), args) is None


def test_check_limits_family_reads_sound_and_control(root, capsys):
    from benchmarks import check_limits_family

    assert check_limits_family.main(
        ["--workload", tiny_latent.CELL, "--seeds", "5", "--seconds", "1.5"],
        platform="cpu", root=root) == 0
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("LIMITS ")][-1]
    got = json.loads(line[len("LIMITS "):])
    assert got["correct"] is True and got["sound"]["gap_mean"] <= 1e-4
    # float32 program, float8 control: the control must read worse, and
    # the run's own comparison against the cell's limits must refuse it
    assert got["control"]["gap_mean"] > 10 * max(got["sound"]["gap_mean"], 1e-4)
    assert got["control_correct"] is False
    with pytest.raises(SystemExit, match="brings no control"):
        check_limits_family.main(
            ["--workload", "tiny-dense.chat", "--seeds", "5"],
            platform="cpu", root=root)
