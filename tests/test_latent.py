"""The DeepSeek-V3 family's block (latent attention over a latent page
pool, a leading dense layer, sigmoid-routed experts beside a shared one,
computed dropless) at test scale on the CPU, seeded random weights, against
the benchmark's plain float32 reference (benchmarks/reference/
deepseek_like.py: expanded attention, interleaved rope, a loop over the
experts with rows picked by index — other arithmetic throughout).

Tolerances, each with its reason, are beside the comparison they hold.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_deepseek as wd
from benchmarks.reference import deepseek_like as ref
from benchmarks.runners import serve_family
from oim_tpu.models import generate as gen
from oim_tpu.models import llama, moe
from oim_tpu.ops import latent_attention
from oim_tpu.ops.rope import (
    apply_rope,
    interleaved_to_split_half,
    rope_frequencies,
)
from oim_tpu.serve.engine import ServeEngine

PAGE = 8
# The satellite's sizes: dim 64, 4 heads, ranks 24/16, nope 16 / rope 8 /
# v 16, 16 experts top-4 + 1 shared, 1 dense + 2 expert layers, vocab 512.
CONFIG = {
    "serve_family": "deepseek_like", "attention_bias": False,
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 192,
    "kv_lora_rank": 16, "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 16, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "q_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6, "rope_scaling": None,
    "rope_theta": 32000000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_group": 1, "v_head_dim": 16,
    "vocab_size": 512, "torch_dtype": "float32",
    "serve": {"num_hidden_layers": 3, "max_position_embeddings": 256},
}
# float32 program against the float32 reference: the two differ in the
# order of their sums only (absorbed against expanded attention, a grouped
# product against one expert at a time, split-half against interleaved
# rope). Logits are of order 5; 5e-5 is ten times what such reorderings
# read here (6e-6) and a hundredth of what bfloat16 anywhere would (5e-3).
F32_TOL = 5e-5
SEED = 2**31 + 5


def family(dtype="float32", seed=SEED):
    config = {**CONFIG, "torch_dtype": dtype}
    model = serve_family.model_dict(config)
    cfg = serve_family.program_config(model)
    return model, cfg, wd.make_on_device(seed, model)


@pytest.fixture(scope="module")
def f32():
    return family()


def reference_logits(model, tokens, seed=SEED, quant=False):
    return np.asarray(ref.logits_many(
        seed, model, [list(tokens)], [np.arange(len(tokens))], quant)[0])


def prompt_tokens(n, stream=0):
    return np.random.default_rng([11, stream]).integers(0, 512, n).tolist()


def test_the_tree_the_benchmark_draws_is_the_programs(f32):
    model, cfg, _ = f32
    wd.check_against_program(model, jax.eval_shape(
        lambda k: llama.init(k, cfg), jax.random.PRNGKey(0)))
    assert set(llama.init(jax.random.PRNGKey(0), cfg)) == {
        "embed", "dense_layers", "layers", "final_norm", "lm_head"}
    assert cfg.cache_leaves == {"kv": (128,)}  # 16 + 8, padded to lanes


@pytest.mark.parametrize("seed", [SEED, 7])
def test_a_whole_forward_gives_the_reference_logits(seed):
    """(a) llama.apply: the one block body over a whole sequence."""
    model, cfg, params = family(seed=seed)
    tokens = prompt_tokens(100)
    got = llama.apply(params, jnp.asarray([tokens]), cfg)[0]
    want = reference_logits(model, tokens, seed)
    assert np.abs(np.asarray(got) - want).max() < F32_TOL


def test_the_dense_cache_path_gives_the_reference_logits(f32):
    """generate()'s cached_forward: prefill, then one token at a time."""
    model, cfg, params = f32
    tokens = prompt_tokens(40)
    want = reference_logits(model, tokens)
    cache = gen.init_cache(cfg, 1, 64)
    got, cache = gen.cached_forward(
        params, jnp.asarray([tokens[:30]]), cache, 0, cfg)
    assert np.abs(np.asarray(got[0]) - want[:30]).max() < F32_TOL
    for t in range(30, 40):
        got, cache = gen.cached_forward(
            params, jnp.asarray([[tokens[t]]]), cache, t, cfg)
        assert np.abs(np.asarray(got[0, 0]) - want[t]).max() < F32_TOL


def test_chunked_prefill_then_paged_decode_gives_the_reference_logits(f32):
    """(b), logits: the serving programs' own functions over a latent pool
    laid out as the engine lays it out (scattered pages, rows at their own
    depths, an idle row), a prompt prefilled in three chunks and 24 decode
    steps: every position's logits against the reference's full forward."""
    model, cfg, params = f32
    tokens = prompt_tokens(70 + 24)
    want = reference_logits(model, tokens)
    pool = gen.init_page_pool(cfg, 40, PAGE)
    table = jnp.asarray([31, 4, 17, 9, 22, 2, 38, 13, 27, 6, 11, 35, 0, 0, 0, 0],
                        jnp.int32)
    prefill = jax.jit(lambda p, t, n, c, tb, st: gen.prefill_into_pages(
        p, t, n, c, tb, st, cfg, PAGE))
    at = 0
    for piece in (32, 32, 6):  # the last chunk padded to its bucket of 8
        padded = np.zeros((1, max(piece, 8) if piece == 6 else piece), np.int32)
        padded[0, :piece] = tokens[at:at + piece]
        last, pool = prefill(params, jnp.asarray(padded), piece, pool, table, at)
        at += piece
        assert np.abs(np.asarray(last) - want[at - 1]).max() < F32_TOL
    tables = jnp.zeros((3, 16), jnp.int32).at[1].set(table)
    step = jax.jit(lambda p, t, c, tb, ps: gen.decode_step(
        p, t, c, tb, ps, cfg, PAGE, with_load=True))
    for t in range(70, 94):
        got, pool, load = step(
            params, jnp.asarray([3, tokens[t], 5], jnp.int32), pool, tables,
            jnp.asarray([256, t, 9], jnp.int32))  # rows 0, 2 idle
        assert np.abs(np.asarray(got[1]) - want[t]).max() < F32_TOL
    touched, fullest = np.asarray(load)
    assert 4 <= touched <= 12 and fullest >= 1.0  # 3 rows x top-4 of 16


def serve(cfg, params, prompts, max_new, **kw):
    eng = ServeEngine(params, cfg, max_batch=4, max_seq=256,
                      prefix_block=PAGE, kv_pool_tokens=2048, **kw)
    try:
        handles = [eng.submit(p, max_new=max_new, temperature=0.0, eos=-1)
                   for p in prompts]
        return [h.result(timeout=300) for h in handles], eng.stats()
    finally:
        eng.stop(timeout=30)


def gaps(model, prompt, served, seed=SEED, control=False):
    return ref.served_gaps_many(seed, model, [(prompt, served)], control)[0]


def test_the_engine_serves_the_references_tokens(f32):
    """(b), through ServeEngine: three requests at once, prompts prefilled
    in chunks of 32 between decode steps, 24 tokens each, greedy. Every
    served token's reference logit lies within 1e-4 of the reference's
    best: with float32 on both sides a served token is the reference's
    arg-max or, where two logits lie closer than the reordering of sums
    (6e-6), its twin."""
    model, cfg, params = f32
    prompts = [prompt_tokens(n, i) for i, n in enumerate((70, 45, 101))]
    outs, stats = serve(cfg, params, prompts, 24, prefill_chunk=32)
    assert stats["decode_attention"] == "jnp_latent_absorbed"
    assert stats["prefill_attention"] == "jnp_latent_expanded"
    assert stats["cache_kind"] == "latent"
    assert stats["expert_load_steps"] > 0
    assert 4 <= stats["experts_touched_sum"] / stats["expert_load_steps"] <= 16
    for prompt, served in zip(prompts, outs):
        assert len(served) == 24
        assert gaps(model, prompt, served).max() < 1e-4


def test_absorbed_decode_equals_expanded_attention():
    """(c) at the operation: the same latents through both forms."""
    d = latent_attention.Dims(heads=4, rank=16, nope=16, rope=8, v=16)
    rng = np.random.default_rng(3)
    B, nb = 3, 8
    pool = jnp.asarray(rng.normal(size=(2, 30, PAGE, d.width)), jnp.float32)
    wkv_b = jnp.asarray(rng.normal(size=(d.rank, 4 * 32)) * 0.25, jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, 30))[:B * nb].reshape(B, nb),
                         jnp.int32)
    pos = jnp.asarray([5, 63, 40], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 1, 4, 24)), jnp.float32)
    got = latent_attention.paged_attention(q, pool, 1, tables, pos, wkv_b, d)
    logical = pool[1, tables].reshape(B, nb * PAGE, d.width)
    for b in range(B):
        want = latent_attention.full_attention(
            q[b:b + 1], logical[b:b + 1], wkv_b, d, int(pos[b]))
        # float32, sums reordered: 1e-5 of values of order 1
        assert np.abs(np.asarray(got[b] - want[0])).max() < 1e-5


def test_an_idle_row_reads_nothing_and_gets_zeros():
    d = latent_attention.Dims(heads=4, rank=16, nope=16, rope=8, v=16)
    pool = jnp.full((1, 4, PAGE, d.width), jnp.nan, jnp.float32)
    got = latent_attention.paged_attention(
        jnp.ones((2, 1, 4, 24)), pool, 0, jnp.zeros((2, 4), jnp.int32),
        jnp.asarray([256, 7], jnp.int32), jnp.ones((16, 4 * 32)), d)
    assert np.array_equal(np.asarray(got), np.zeros((2, 1, 4, 16)))


def latent_shapes(t=1, rank=512, page=16, dtype=jnp.bfloat16, nb=2048):
    """(q, pool, tables, d) of a decode (t = 1) or prefill program, as
    shapes: the cell's own by default."""
    d = latent_attention.Dims(heads=32, rank=rank, nope=128, rope=64, v=128)
    return (jax.ShapeDtypeStruct((32, t, 32, 192), dtype),
            jax.ShapeDtypeStruct((5, 18433, page, d.width), dtype),
            jax.ShapeDtypeStruct((32, nb), jnp.int32), d)


@pytest.mark.parametrize("backend,kw,name,pages", [
    ("tpu", {}, "pallas_latent", latent_attention.BLOCK_TOKENS // 16),
    ("tpu", {"nb": 24}, "pallas_latent", 8),  # a block never past the table
    ("tpu", {"dtype": jnp.float32, "page": 8}, "pallas_latent",
     latent_attention.BLOCK_TOKENS // 8),
    ("cpu", {}, "jnp_latent_absorbed", None),
    ("tpu", {"rank": 448}, "jnp_latent_absorbed", None),  # values off the lanes
    ("tpu", {"page": 8}, "jnp_latent_absorbed", None),  # half a bf16 tile
    ("tpu", {"t": 4}, "jnp_latent_expanded", None),
    ("cpu", {"t": 2048}, "jnp_latent_expanded", None),
])
def test_latent_dispatch_reads_shapes_and_backend_only(
        monkeypatch, backend, kw, name, pages):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    shapes = latent_shapes(**kw)
    assert latent_attention.kernel_name(*shapes) == name
    assert latent_attention._latent_plan(*shapes) == pages


def test_stats_say_which_attention_the_latent_decode_program_takes(
        monkeypatch):
    """``stats()["decode_attention"]`` is the rule's word on the engine's
    own shapes: the kernel where a TPU finds the entry's value part whole
    lanes (rank 128 here), the ``jax.numpy`` form for the toy's rank 16 and
    on the CPU. No option selects it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for rank, want in ((128, "pallas_latent"), (16, "jnp_latent_absorbed")):
        model = serve_family.model_dict({**CONFIG, "kv_lora_rank": rank})
        cfg = serve_family.program_config(model)
        eng = ServeEngine(wd.make_on_device(SEED, model), cfg, max_batch=2,
                          max_seq=64, prefix_block=16, queue_depth=4)
        try:
            assert eng.stats()["decode_attention"] == want
            assert eng.stats()["cache_kind"] == "latent"
        finally:
            eng.stop(drain=False, timeout=30)


def test_the_decode_step_takes_the_kernel_where_the_rule_says_so(monkeypatch):
    """``paged_attention`` at T = 1 under a TPU's rule, the kernel run in
    interpret mode: the absorbed form's numbers, both absorptions around
    the kernel included."""
    import functools

    d = latent_attention.Dims(heads=4, rank=128, nope=16, rope=8, v=16)
    rng = np.random.default_rng(5)
    B, nb, page = 3, 4, 16
    pool = rng.normal(size=(2, 14, page, d.width)).astype(np.float32)
    pool[..., d.rank + d.rope:] = 0.0
    pool = jnp.asarray(pool)
    wkv_b = jnp.asarray(rng.normal(size=(d.rank, 4 * 32)) * 0.1, jnp.float32)
    tables = jnp.asarray([[3, 7, 1, 9], [0, 0, 0, 0], [12, 5, 0, 0]], jnp.int32)
    pos = jnp.asarray([57, 3, 20], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 1, 4, 24)), jnp.float32)
    args = (q, pool, 1, tables, pos, wkv_b, d)
    want = latent_attention.paged_attention(*args)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        latent_attention, "_latent_decode", functools.partial(
            latent_attention._latent_decode, interpret=True))
    assert latent_attention.kernel_name(q, pool, tables, d) == "pallas_latent"
    got = latent_attention.paged_attention(*args)
    assert not np.asarray(got[1]).any()  # the idle row
    # float32, sums reordered: 1e-5 of values of order 1
    assert np.abs(np.asarray(got - want)).max() < 1e-5


def skewed_experts(bias_scale):
    """A router that sends nearly every row's first choice to expert 3 and
    never reaches experts 12..15; ``bias_scale`` sizes the bias."""
    cfg = moe.MoEConfig(n_experts=16, top_k=4, dispatch="ragged",
                        scoring="sigmoid", routed_scale=2.5, n_shared=1)
    params = moe.init(jax.random.PRNGKey(2), 64, 32, cfg, jnp.float32)
    router = np.asarray(params["router"]) * 0.3
    router[:, 3] = 0.4  # with x >= 0 below: the largest score of most rows
    router[:, 12:] = -1.0
    params["router"] = jnp.asarray(router)
    params["bias"] = params["bias"] * bias_scale / 0.01
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (2, 40, 64)))
    return cfg, params, x


def test_the_dropless_dispatch_is_the_per_token_sum_over_chosen_experts():
    """(d): against the definition, token by token and expert by expert,
    under a routing so skewed that one expert gets most rows and several
    get none."""
    cfg, params, x = skewed_experts(bias_scale=0.2)
    out, load = moe.apply(params, x, cfg, with_load=True)
    want, counts = per_token_sum(params, x, cfg)
    assert counts[3] >= 70 and (counts == 0).sum() >= 4  # of 80 rows
    # float32 against float64 sums of ~100 terms of order 1: 1e-4
    assert np.abs(np.asarray(out).reshape(-1, 64) - want).max() < 1e-4
    assert load[2] == (counts > 0).sum()
    assert load[3] == pytest.approx(counts.max() * 16 / (80 * 4))


def per_token_sum(params, x, cfg):
    """The definition in float64: each token through its own k experts,
    weighed, and through the shared expert."""
    d = x.shape[-1]
    tokens = np.asarray(x.reshape(-1, d), np.float64)
    chosen, weight = (np.asarray(a) for a in moe.route(
        params, x.reshape(-1, d), cfg))

    def swiglu(h, wg, wu, wd):
        g = h @ np.asarray(wg, np.float64)
        return (g / (1 + np.exp(-g)) * (h @ np.asarray(wu, np.float64))
                ) @ np.asarray(wd, np.float64)

    want = np.zeros_like(tokens)
    for n, h in enumerate(tokens):
        for e, w in zip(chosen[n], weight[n]):
            want[n] += w * swiglu(h, params["w_gate"][e], params["w_up"][e],
                                  params["w_down"][e])
        s = params["shared"]
        want[n] += swiglu(h, s["w_gate"], s["w_up"], s["w_down"])
    return want, np.bincount(chosen.reshape(-1), minlength=cfg.n_experts)


# 96 tokens, top-2 of 16, every expert held: uniform routing sends an expert
# 12 rows; with row tiles of 8 the capacity is 24 rows an expert, and behind
# it the same capacity with the rows past it through the grouped product. The
# bias leans towards expert 3 by this much: its rows are 20, 42, 82 and 96.
WHOLE_RUNGS = {"first": 0.0, "a-few-rows-past": 0.15, "most-rows-past": 0.5,
               "every-token": 1.0}


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("case", sorted(WHOLE_RUNGS))
def test_a_whole_sets_rungs_are_the_per_token_sum_over_chosen_experts(
        monkeypatch, case, stacked):
    """A whole set's bounded rung, and the rung behind it (routing forced
    past the capacity, up to every token on one expert), against the
    definition in float64: the same top-k sum, no token dropped, whichever
    products ran."""
    monkeypatch.setattr(moe, "ROW_TILE", 8)
    cfg = moe.MoEConfig(n_experts=16, top_k=2, dispatch="ragged",
                        scoring="sigmoid", routed_scale=2.5, n_shared=1)
    assert moe.capacity_ladder(96, cfg) == (24,)
    params = moe.init(jax.random.PRNGKey(2), 64, 32, cfg, jnp.float32,
                      n_layers=3)
    params["bias"] = params["bias"].at[1, 3].add(WHOLE_RUNGS[case])
    layer = jax.tree.map(lambda a: a[1], params)
    run = layer
    if stacked:  # as the layer loop hands a layer over (moe.keep_stacked)
        sliced, whole = moe.keep_stacked({"moe": params})
        run = moe.at_layer(jax.tree.map(lambda a: a[1], sliced), whole,
                           jnp.int32(1))["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 96, 64))
    out, load = jax.jit(lambda p, x: moe.apply(p, x, cfg, with_load=True))(
        run, x)
    want, counts = per_token_sum(layer, x, cfg)
    fullest = counts.max()
    rung = "first" if case == "first" else "whole"
    assert {"first": fullest <= 24, "a-few-rows-past": 24 < fullest <= 48,
            "most-rows-past": 48 < fullest < 96,
            "every-token": fullest == 96}[case], counts
    assert dict(zip(moe.RUNG_NAMES, load[4:]))[rung] == 1 and load[4:].sum() == 1
    # float32 against float64 sums of ~100 terms of order 1: 1e-4
    assert np.abs(np.asarray(out).reshape(-1, 64) - want).max() < 1e-4
    assert load[2] == (counts > 0).sum()
    assert load[3] == pytest.approx(fullest * 16 / (96 * 2))


@pytest.mark.parametrize("lean", [0.0, 0.15, 1.0])
def test_a_whole_sets_unowned_rows_never_reach_the_sum(monkeypatch, lean):
    """Rows no assignment owns are whatever the products left there (a
    capacity's slots past an expert's count, the grouped product's rows past
    the groups' sum): non-finite values planted in every one of them reach
    no token's sum, on either rung."""
    monkeypatch.setattr(moe, "ROW_TILE", 8)
    cfg = moe.MoEConfig(n_experts=16, top_k=2, dispatch="ragged",
                        scoring="sigmoid", routed_scale=2.5, n_shared=1)
    params = moe.init(jax.random.PRNGKey(2), 64, 32, cfg, jnp.float32)
    params["bias"] = params["bias"].at[3].add(lean)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 96, 64))

    def run():
        return jax.jit(lambda p, x: moe.apply(p, x, cfg, with_load=True))(
            params, x)
    clean, load = run()
    chosen, _ = moe.route(params, x.reshape(-1, 64), cfg)
    counts = jnp.bincount(chosen.reshape(-1), length=16)
    grouped, batched = moe.grouped_ffn, moe._batched_ffn

    def bad(rows):
        return jnp.where(jnp.arange(rows) % 2 == 0, jnp.nan, jnp.inf)

    def planted_grouped(p, rows, group_sizes, *limit):
        y = grouped(p, rows, group_sizes, *limit)
        live = jnp.arange(y.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(live[:, None], y, bad(y.shape[0])[:, None])

    def planted_batched(leaves, x, *limit):
        y = batched(leaves, x, *limit)
        live = jnp.arange(y.shape[1]) < counts[:, None]
        return jnp.where(live[..., None], y, bad(y.shape[1])[None, :, None])

    monkeypatch.setattr(moe, "grouped_ffn", planted_grouped)
    monkeypatch.setattr(moe, "_batched_ffn", planted_batched)
    planted, planted_load = run()
    np.testing.assert_array_equal(planted_load, load)
    assert load[4 if lean == 0.0 else 6] == 1
    assert np.isfinite(planted).all()
    np.testing.assert_array_equal(planted, clean)


def test_a_decode_sized_call_keeps_the_grouped_product():
    """One row an expert (32 rows of a decode step, top-8 of 256): a
    batched product would read every expert's bytes where the grouped one
    reads the touched experts'; the call traces the grouped products alone,
    no capacity, no branch, and its load carries no rung."""
    cfg = moe.MoEConfig(n_experts=256, top_k=8, dispatch="ragged",
                        scoring="sigmoid", routed_scale=2.5, n_shared=1)
    params = jax.eval_shape(lambda: moe.init(
        jax.random.PRNGKey(0), 64, 32, cfg, jnp.float32))

    def traced(tokens):
        x = jax.ShapeDtypeStruct((tokens, 1, 64), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda p, x: moe.apply(
            p, x, cfg, with_load=True))(params, x)
        return str(jaxpr), jaxpr.out_avals[1].shape
    for tokens in (32, 64, 96):  # a step, and verify steps of 2 and 3 tokens
        text, load = traced(tokens)
        assert text.count("ragged_dot_general[") == 3
        assert "cond[" not in text and load == (4,)
    text, load = traced(128)  # 4 rows an expert: a token's worth holds
    assert "ragged_dot_general[" not in text and "cond[" not in text
    assert load == (7,)
    text, load = traced(256)  # a capacity under the tokens: the rows past it
    assert text.count("ragged_dot_general[") == 3 and "cond[" in text


def test_the_bias_changes_choices_and_not_weights():
    cfg, params, x = skewed_experts(bias_scale=0.2)
    rows = x.reshape(-1, 64)
    with_bias, w = moe.route(params, rows, cfg)
    without, _ = moe.route({**params, "bias": params["bias"] * 0}, rows, cfg)
    assert (np.sort(with_bias, -1) != np.sort(without, -1)).any()
    scores = jax.nn.sigmoid(rows @ params["router"])
    picked = jnp.take_along_axis(scores, with_bias, axis=-1)
    want = picked / picked.sum(-1, keepdims=True) * 2.5  # unbiased scores
    assert np.allclose(np.asarray(w), np.asarray(want), rtol=1e-6)


def test_sigmoid_scoring_refuses_a_capacity_dispatch():
    with pytest.raises(ValueError, match="ragged"):
        dataclasses.replace(llama.tiny_latent(), moe_dispatch="gather")
    cfg = moe.MoEConfig(n_experts=4, top_k=2, scoring="sigmoid")
    params = moe.init(jax.random.PRNGKey(0), 8, 8, cfg, jnp.float32)
    with pytest.raises(ValueError, match="ragged"):
        moe.apply(params, jnp.ones((1, 2, 8)), cfg)


def test_softmax_routing_runs_dropless_too():
    """The third dispatch under the old router: what no capacity drops,
    the gather dispatch with room for every row computes too."""
    cfg = moe.MoEConfig(n_experts=4, top_k=2, capacity_factor=2.0)
    params = moe.init(jax.random.PRNGKey(0), 16, 24, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 16))
    want, _ = moe.apply(params, x, cfg)
    got, _ = moe.apply(params, x, dataclasses.replace(cfg, dispatch="ragged"))
    assert np.abs(np.asarray(got - want)).max() < 1e-5


def test_interleaved_rope_is_split_half_rope_under_the_permutation():
    """(e): rotate interleaved pairs as published (the reference's own
    rotation), or permute the columns and rotate split-half as the program
    does — the rotated vectors are the same up to that order, so every
    query-key product is the same."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(9, 3, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(9, 1, 8)), jnp.float32)
    cos, sin = rope_frequencies(8, 64, 32e6)
    pos = jnp.arange(9)[None]
    perm = interleaved_to_split_half(8)
    assert perm.tolist() == [0, 2, 4, 6, 1, 3, 5, 7]
    qi, ki = (ref._rope_interleaved(a, 32e6) for a in (q, k))
    qs, ks = (apply_rope(a[None][..., perm], cos, sin, pos)[0] for a in (q, k))
    assert np.allclose(np.asarray(qi[..., perm]), np.asarray(qs), atol=1e-6)
    assert np.allclose(np.asarray(jnp.einsum("thd,sgd->hts", qi, ki)),
                       np.asarray(jnp.einsum("thd,sgd->hts", qs, ks)),
                       atol=1e-5)


def test_a_published_layout_tree_loads_into_the_programs(f32):
    """(e) at the loader: the tree as published (rope columns interleaved,
    an MTP module beside it) through serve/weights.py gives the tree the
    benchmark hands the program."""
    from oim_tpu.serve import weights

    model, cfg, params = f32
    root = wd.root_key(SEED)
    published = {**jax.jit(lambda r: wd.tables(r, model))(root)}
    for group, n in wd.group_sizes(model).items():
        draw = jax.jit(lambda r, l, g=group: wd.layer_slice(r, model, g, l))
        layers = [draw(root, l) for l in range(n)]
        published[group] = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    published["mtp_layers"] = {"wq_a": jnp.ones((1, 64, 24))}
    loaded = weights.rope_split_half(
        weights.unpack_params(weights.pack_params(published)), cfg)
    assert "mtp_layers" not in loaded
    want = dict(wd.leaf_paths(params))
    got = dict(wd.leaf_paths(loaded))
    assert set(got) == set(want)
    for path in want:
        assert np.array_equal(np.asarray(got[path]), np.asarray(want[path])), path


def test_a_prefix_hit_and_a_promoted_block_serve_what_a_cold_prefill_serves(f32):
    """(f): latent pages shared by reference, spilled to the host tier and
    staged back give the tokens of a cold prefill, greedy and sampled."""
    model, cfg, params = f32
    shared = prompt_tokens(43)
    eng = ServeEngine(params, cfg, max_batch=2, max_seq=128, prefix_block=PAGE,
                      kv_pool_tokens=1024, kv_host_bytes=1 << 20)
    try:
        def run(tail, temperature=0.0, seed=0):
            h = eng.submit(shared + tail, max_new=6, temperature=temperature,
                           seed=seed, eos=-1)
            return h.result(timeout=300), h.stats["prefix_tokens"]

        cold, reused = run([7])
        assert reused == 0
        hit, reused = run([7])
        assert hit == cold and reused == 40  # five whole pages of 8
        sampled_hot, _ = run([9], 0.8, 3)
        assert eng.evict_prefix_store() >= 5
        host = eng.host_stats()
        assert host["demotions"] >= 5 and host["bytes"] == host["entries"] * (
            3 * PAGE * 128 * 4)  # one leaf: [L, page, width] float32
        promoted, reused = run([7])
        assert promoted == cold and reused == 40
        assert eng.host_stats()["promotions"] >= 5
        sampled_promoted, _ = run([9], 0.8, 3)
        assert sampled_promoted == sampled_hot
        assert eng.pool_stats()["page_bytes"] == 3 * PAGE * 128 * 4
    finally:
        eng.stop(timeout=30)
    assert gaps(model, shared + [7], cold).max() < 1e-4


def test_a_latent_volume_and_a_gqa_engine_refuse_each_other(f32):
    """(g): the fingerprint carries the cache's kind."""
    from oim_tpu.serve.kvvolume import config_fingerprint, pack_chain, unpack_chain

    _, cfg, _ = f32
    gqa = llama.tiny(n_layers=3)
    fp_latent, fp_gqa = config_fingerprint(cfg, PAGE), config_fingerprint(gqa, PAGE)
    assert fp_latent["cache"] == "latent" and fp_gqa["cache"] == "gqa"
    assert fp_latent["leaves"] == {"kv": [128]}
    assert fp_gqa["leaves"] == {"k": [2, 16], "v": [2, 16]}
    latent_block = (np.ones((3, PAGE, 128), np.float32),)
    gqa_block = (np.ones((3, PAGE, 2, 16), np.float32),) * 2
    latent_blob = pack_chain(["h0"], [latent_block], PAGE, fp_latent)
    gqa_blob = pack_chain(["h0"], [gqa_block], PAGE, fp_gqa)
    hashes, blocks, _ = unpack_chain(latent_blob, fp_latent)
    assert hashes == ["h0"] and blocks[0][0].shape == (3, PAGE, 128)
    with pytest.raises(ValueError, match="fingerprint"):
        unpack_chain(latent_blob, fp_gqa)
    with pytest.raises(ValueError, match="fingerprint"):
        unpack_chain(gqa_blob, fp_latent)


@pytest.mark.parametrize("kwargs,match", [
    ({"shard": 2}, "latent attention"),
    ({"role": "prefill"}, "latent attention"),
    ({"role": "decode"}, "latent attention"),
    ({"draft": True}, "latent attention"),
])
def test_what_latent_attention_cannot_do_yet_is_refused_at_construction(
        f32, kwargs, match):
    """(h): one line each, before anything is built."""
    _, cfg, params = f32
    if kwargs.pop("draft", False):
        draft = llama.tiny(vocab=512)
        kwargs.update(draft_params=llama.init(jax.random.PRNGKey(0), draft),
                      draft_cfg=draft, spec_tokens=2)
    with pytest.raises(ValueError, match=match):
        ServeEngine(params, cfg, max_batch=2, max_seq=64, **kwargs)


def test_bfloat16_passes_a_limit_that_a_float8_reference_fails():
    """(j): the program in bfloat16 (weights, activations, the latent
    pool) against the float32 reference drawn from the same seed, through
    the engine as in (b). The number is the benchmark's: the mean gap by
    which a served token's reference logit lies below the reference's
    best. Sound bfloat16 reads 0.0015 here (a token served is now and
    then the reference's second choice, a few hundredths below); the
    float8 control — the reference itself with every linear layer's
    operands rounded to e4m3 — reads 0.19. The limit 0.04 stands
    between, with room on both sides."""
    model, cfg, params = family("bfloat16")
    prompts = [prompt_tokens(n, i) for i, n in enumerate((70, 45, 101))]
    outs, _ = serve(cfg, params, prompts, 24, prefill_chunk=32)
    sample = list(zip(prompts, outs))
    sound = np.concatenate(ref.served_gaps_many(SEED, model, sample)).mean()
    control = np.concatenate(
        ref.served_gaps_many(SEED, model, sample, control=True)).mean()
    print(f"gap_mean: bfloat16 program {sound:.4f}, float8 control {control:.4f}")
    assert sound < 0.04 < control


def test_oim_serve_names_the_model_and_the_trainer_does_not(monkeypatch):
    """``oim-serve --model joyai-llm-flash`` with the trainer's
    --model-override; the trainer has no such model until it can train it."""
    import argparse

    from oim_tpu.cli import oim_serve
    from oim_tpu.train import TrainConfig

    monkeypatch.setattr(oim_serve, "restore_checkpoint_params",
                        lambda path, mcfg, who: ({}, 0))
    args = argparse.Namespace(
        model="joyai-llm-flash", model_override=["n_layers=5"],
        checkpoint_dir="somewhere", pack_to="")
    _, mcfg, _ = oim_serve._load_params(args, oim_serve.from_context())
    assert mcfg == dataclasses.replace(llama.JOYAI_LLM_FLASH, n_layers=5)
    with pytest.raises(ValueError, match="unknown model"):
        TrainConfig(model="joyai-llm-flash").model_config()


def test_parameter_counts_count_the_new_tree(f32):
    _, cfg, params = f32
    assert llama.num_params(cfg) == sum(x.size for x in jax.tree.leaves(params))
    # active: 4 of 16 routed experts, the shared one, the dense layer whole
    inactive = 2 * 12 * 3 * 64 * 32
    assert llama.num_active_params(cfg) == llama.num_params(cfg) - inactive
    full = llama.JOYAI_LLM_FLASH
    assert 48.5e9 < llama.num_params(full) < 49.5e9  # "48B-A2.7B"
    five = dataclasses.replace(full, n_layers=5)
    assert llama.num_params(five) == 5_558_141_952  # ISSUE 28's 5558 M
    assert gen.page_bytes(five, 16) == 5 * 16 * 640 * 2  # 576 in whole lanes
