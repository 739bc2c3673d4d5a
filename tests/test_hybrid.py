"""A hybrid of mixers with recurrent state (PR 33), small, on the CPU, in
float32: the Mamba-2 mixer's two forms against each other and against the
sequential definition, the pattern's layer loop, the state pool beside the
page pool through the serving programs and ``ServeEngine``, the held share
of an expert layer, and program against the benchmark's plain reference on
seeded weights. Self-contained: no cluster, no port."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_nemotron_h as bench_weights
from benchmarks.reference import nemotron_h_like as ref
from oim_tpu.models import generate as gen
from oim_tpu.models import llama, moe
from oim_tpu.ops import ssm
from oim_tpu.ops.norms import rmsnorm
from oim_tpu.serve.engine import ServeEngine

PAGE = 16
D = ssm.Dims(heads=8, head_dim=4, groups=2, state=16, conv=4, chunk=8)
DIM = 32


def mixer_layer(seed=0):
    layer = jax.tree.map(lambda a: a[0], ssm.init(
        jax.random.PRNGKey(seed), DIM, D, jnp.float32, 1))
    layer["conv_b"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), layer["conv_b"].shape)
    return layer


def empty_state(batch):
    return (jnp.zeros((batch, D.heads, D.head_dim, D.state)),
            jnp.zeros((batch, D.conv - 1, D.conv_dim)))


def sequential(layer, x, s, c):
    """The definition: one token after another through ``ssm.step``."""
    outs = []
    for t in range(x.shape[1]):
        o, s, c = ssm.step(layer, x[:, t], s, c, D, 1e-5)
        outs.append(o)
    return jnp.stack(outs, 1), s, c


def by_hand(layer, x):
    """The recurrence written out with numpy loops, one row from zeros."""
    x = np.asarray(x, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in layer.items()}
    T = x.shape[0]
    p = x @ w["w_in"]
    z, xbc, dt = (p[:, :D.inner], p[:, D.inner:D.inner + D.conv_dim],
                  p[:, D.inner + D.conv_dim:])
    padded = np.concatenate([np.zeros((D.conv - 1, D.conv_dim)), xbc])
    conv = w["conv_b"] + sum(w["conv_w"][j] * padded[j:j + T]
                             for j in range(D.conv))
    conv = conv / (1 + np.exp(-conv))
    gn = D.groups * D.state
    xs = conv[:, :D.inner].reshape(T, D.heads, D.head_dim)
    bm = conv[:, D.inner:D.inner + gn].reshape(T, D.groups, D.state)
    cm = conv[:, D.inner + gn:].reshape(T, D.groups, D.state)
    dt = np.log1p(np.exp(dt + w["dt_bias"]))
    a = -np.exp(w["A_log"])
    h = np.zeros((D.heads, D.head_dim, D.state))
    y = np.zeros((T, D.heads, D.head_dim))
    for t in range(T):
        for head in range(D.heads):
            g = head // (D.heads // D.groups)
            h[head] = (np.exp(dt[t, head] * a[head]) * h[head]
                       + dt[t, head] * np.outer(xs[t, head], bm[t, g]))
            y[t, head] = h[head] @ cm[t, g] + w["D"][head] * xs[t, head]
    y = y.reshape(T, D.inner) * (z / (1 + np.exp(-z)))
    y = y.reshape(T, D.groups, -1)
    y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5)
    return (y.reshape(T, D.inner) * w["gate_norm"]) @ w["w_out"], h


# -- the mixer's two forms ----------------------------------------------------

def test_the_one_token_update_is_the_definition():
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 11, DIM))
    out, s, _ = sequential(layer, x, *empty_state(1))
    want, h = by_hand(layer, x[0])
    np.testing.assert_allclose(out[0], want, atol=2e-5)
    np.testing.assert_allclose(s[0], h, atol=2e-6)


@pytest.mark.parametrize("length", [8, 16, 5, 21, 1])
@pytest.mark.parametrize("carried", [False, True])
def test_chunked_scan_against_the_sequential_recurrence(length, carried):
    """Lengths that are and are not multiples of the chunk (8), from zero
    and from a carried state: outputs, state and conv window agree. Both
    are float32 sums of the same terms in another order: 1e-5."""
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, length, DIM))
    s0, c0 = empty_state(2)
    if carried:
        _, s0, c0 = sequential(layer, jax.random.normal(
            jax.random.PRNGKey(4), (2, 6, DIM)), s0, c0)
    want, s1, c1 = sequential(layer, x, s0, c0)
    out, s2, c2 = ssm.scan(layer, x, s0, c0, length, D, 1e-5)
    np.testing.assert_allclose(out, want, atol=1e-5)
    np.testing.assert_allclose(s2, s1, atol=1e-5)
    np.testing.assert_allclose(c2, c1, atol=1e-6)


@pytest.mark.parametrize("real,padded", [(5, 8), (13, 32), (1, 8), (16, 16)])
def test_padding_leaves_state_and_window_at_the_last_real_token(real, padded):
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(5), (1, padded, DIM))
    s0, c0 = empty_state(1)
    want, s1, c1 = sequential(layer, x[:, :real], s0, c0)
    out, s2, c2 = ssm.scan(layer, x, s0, c0, real, D, 1e-5)
    np.testing.assert_allclose(out[:, :real], want, atol=1e-5)
    np.testing.assert_allclose(s2, s1, atol=1e-5)
    np.testing.assert_allclose(c2, c1, atol=1e-6)


def test_slices_carry_what_a_whole_scan_computes():
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 29, DIM))
    whole, s1, c1 = ssm.scan(layer, x, *empty_state(1), 29, D, 1e-5)
    s, c = empty_state(1)
    outs = []
    for lo, hi, bucket in ((0, 16, 16), (16, 24, 8), (24, 29, 8)):
        piece = jnp.pad(x[:, lo:hi], ((0, 0), (0, bucket - (hi - lo)), (0, 0)))
        o, s, c = ssm.scan(layer, piece, s, c, hi - lo, D, 1e-5)
        outs.append(o[:, :hi - lo])
    np.testing.assert_allclose(jnp.concatenate(outs, 1), whole, atol=1e-5)
    np.testing.assert_allclose(s, s1, atol=1e-5)
    np.testing.assert_allclose(c, c1, atol=1e-6)


# -- the configuration and the layer loop --------------------------------------

def test_the_published_constant():
    cfg = llama.NEMOTRON_3_NANO_30B
    assert (cfg.n_of("M"), cfg.n_of("E"), cfg.n_of("*")) == (23, 23, 6)
    assert cfg.n_layers == 52 and cfg.n_cache_layers == 6
    m = cfg.mamba
    assert (m.inner, m.conv_dim, m.proj_dim) == (4096, 6144, 10304)
    assert abs(llama.num_params(cfg) - 31.58e9) < 0.01e9
    rank = dataclasses.replace(cfg, expert_rank="0/8", vocab=16384)
    assert abs(llama.num_params(rank) - 5.26e9) < 0.01e9
    assert rank.moe.held == (0, 16) and rank.moe.n_held == 16
    assert gen.state_bytes(cfg) == 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert gen.page_bytes(cfg, 1) == 6 * 2 * 2 * 128 * 2  # 6 KB a position


def test_pattern_runs_scan_the_repeats_and_unroll_the_rest():
    runs = llama.pattern_runs(llama.NEMOTRON_3_NANO_30B.hybrid_override_pattern)
    assert "".join(unit * n for unit, n in runs) == \
        llama.NEMOTRON_3_NANO_30B.hybrid_override_pattern
    assert sum(1 for _, n in runs if n > 1) == 7
    assert [u for u, n in runs if n == 1] == ["M"] + ["*"] * 6 + ["E"]
    assert llama.pattern_runs("MEM*EMEME") == (
        ("M", 1), ("E", 1), ("M", 1), ("*", 1), ("EM", 2), ("E", 1))
    assert llama.pattern_runs("MMEE") == tuple((k, 1) for k in "MMEE")


def test_tiny_hybrid_has_all_three_kinds_and_counts_its_parameters():
    cfg = llama.tiny_hybrid()
    assert set(cfg.hybrid_override_pattern) == {"M", "E", "*"}
    params = llama.init(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"embed", "final_norm", "lm_head", "mamba_layers",
                           "expert_layers", "attn_layers"}
    assert "w_gate" not in params["expert_layers"]["moe"]
    assert params["expert_layers"]["moe"]["shared"]["w_up"].shape == (4, 64, 96)
    assert sum(x.size for x in jax.tree.leaves(params)) == llama.num_params(cfg)


@pytest.mark.parametrize("fields,match", [
    (dict(hybrid_override_pattern="MEX"), "characters of"),
    (dict(hybrid_override_pattern="ME", n_layers=3), "characters of"),
    (dict(mamba_num_heads=0), "needs mamba_num_heads"),
    (dict(moe_dispatch="gather", scoring_func="softmax"), "needs n_experts"),
    (dict(expert_rank="3/5"), "rank/ranks"),
    (dict(expert_rank="8/8"), "rank/ranks"),
])
def test_a_malformed_hybrid_is_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(llama.tiny_hybrid(), **fields)


def test_no_sharding_rules_and_no_dense_cache_for_a_hybrid():
    cfg = llama.tiny_hybrid()
    with pytest.raises(ValueError, match="no sharding rules yet for latent"):
        llama.param_logical_axes(cfg)
    with pytest.raises(ValueError, match="no sharding rules yet"):
        llama.param_logical_axes(dataclasses.replace(
            llama.tiny(n_experts=4), expert_rank="0/2"))
    with pytest.raises(ValueError, match="dense cache"):
        gen.init_cache(cfg, 1, 32)
    with pytest.raises(ValueError, match="hybrid pattern"):
        gen.shard_config(cfg, 2)


# -- every existing preset as the parent commit computed it -------------------
# Sums over the parameters and the logits of llama.init / llama.apply at
# PRNGKey(3) / tokens PRNGKey(4) [2, 16], read from the parent of PR 33
# (commit a42ccba) on the CPU: the new Config fields at their defaults move
# nothing (float32 sums: relative 1e-6).
PARENT = {
    "tiny": (131392, 11012.689453125, 6568.65185546875,
             [-0.4001116454601288, -0.6437100172042847, 0.11766046285629272]),
    "tiny_moe": (353088, 30038.798828125, 6580.09765625,
                 [-0.24080954492092133, 0.5945478677749634,
                  -0.6052486300468445]),
    "tiny_latent": (348504, 35954.1171875, 12993.5791015625,
                    [-0.33129096031188965, -1.5882389545440674,
                     0.545111894607544]),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_existing_presets_are_the_parents(name):
    cfg = {"tiny": llama.tiny(), "tiny_moe": llama.tiny(n_experts=4),
           "tiny_latent": llama.tiny_latent()}[name]
    assert (cfg.norm_eps, cfg.attn_rope, cfg.hybrid_override_pattern,
            cfg.expert_rank, cfg.mlp_hidden_act) == (1e-6, True, "", "", "silu")
    params = llama.init(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, cfg.vocab)
    logits = llama.apply(params, tokens, cfg)
    leaves = jax.tree.leaves(params)
    n, total, logit_sum, first = PARENT[name]
    assert sum(x.size for x in leaves) == n
    np.testing.assert_allclose(
        sum(float(jnp.abs(x.astype(jnp.float32)).sum()) for x in leaves),
        total, rtol=1e-6)
    np.testing.assert_allclose(float(jnp.abs(logits).sum()), logit_sum,
                               rtol=1e-6)
    np.testing.assert_allclose(logits[0, -1, :3], first, rtol=1e-5, atol=1e-6)


def test_the_norms_epsilon_is_a_field():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8)) * 1e-3
    w = jnp.ones((8,))
    assert not np.allclose(rmsnorm(x, w, 1e-5), rmsnorm(x, w, 1e-6))
    np.testing.assert_array_equal(rmsnorm(x, w), rmsnorm(x, w, 1e-6))


# -- the expert layer: squared ReLU, a shared width, a held share -------------

def test_stored_width_pads_whole_lanes_and_adds_exact_zeros():
    assert [moe.stored_width(w) for w in (48, 128, 192, 768, 1856, 14336)] \
        == [48, 128, 256, 768, 1920, 14336]
    cfg = moe.MoEConfig(n_experts=4, top_k=2, dispatch="ragged",
                        scoring="sigmoid", act="relu2")
    wide = moe.init(jax.random.PRNGKey(0), 16, 192, cfg, jnp.float32)
    assert wide["w_up"].shape == (4, 16, 256) \
        and wide["w_down"].shape == (4, 256, 16)
    assert not np.any(wide["w_up"][..., 192:]) \
        and not np.any(wide["w_down"][:, 192:])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 16))
    cut = {**wide, "w_up": wide["w_up"][..., :192],
           "w_down": wide["w_down"][:, :192]}
    np.testing.assert_array_equal(moe.apply(wide, x, cfg)[0],
                                  moe.apply(cut, x, cfg)[0])
    # a capacity-padded configuration's leaves stay as they were
    gather = moe.init(jax.random.PRNGKey(0), 16, 192,
                      moe.MoEConfig(n_experts=4), jnp.float32)
    assert gather["w_up"].shape == (4, 16, 192)


@pytest.mark.parametrize("stacked", [False, True])
def test_a_held_share_runs_dense_at_few_tokens_and_grouped_above(
        monkeypatch, stacked):
    """The two forms of a held share compute the same sum (float32, another
    order: 2e-5) and count the same load; which one runs follows the tokens
    in the call."""
    cfg = dataclasses.replace(llama.tiny_hybrid(), expert_rank="1/4").moe
    params = moe.init(jax.random.PRNGKey(0), 64, 48, cfg, jnp.float32,
                      n_layers=3)
    if stacked:  # as the layer loop hands a layer over (moe.keep_stacked)
        sliced, whole = moe.keep_stacked({"moe": params})
        layer = moe.at_layer(jax.tree.map(lambda a: a[1], sliced), whole,
                             jnp.int32(1))["moe"]
    else:
        layer = jax.tree.map(lambda a: a[1], params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64))
    assert x.shape[0] * x.shape[1] <= moe.DENSE_UP_TO_TOKENS
    dense, dense_load = jax.jit(
        lambda p, x: moe.apply(p, x, cfg, with_load=True))(layer, x)
    monkeypatch.setattr(moe, "DENSE_UP_TO_TOKENS", 0)
    grouped, grouped_load = jax.jit(
        lambda p, x: moe.apply(p, x, cfg, with_load=True))(layer, x)
    np.testing.assert_allclose(dense, grouped, atol=2e-5)
    np.testing.assert_allclose(dense_load, grouped_load, rtol=1e-6)
    assert "ragged_dot" in str(jax.make_jaxpr(
        lambda p, x: moe.apply(p, x, cfg))(layer, x))
    monkeypatch.undo()
    assert "ragged_dot" not in str(jax.make_jaxpr(
        lambda p, x: moe.apply(p, x, cfg))(layer, x))
    long = jnp.zeros((1, moe.DENSE_UP_TO_TOKENS + 1, 64))
    assert "ragged_dot" in str(jax.make_jaxpr(
        lambda p, x: moe.apply(p, x, cfg))(layer, long))
    # every expert held: the grouped form whatever the tokens
    whole_cfg = llama.tiny_hybrid().moe
    assert "ragged_dot" in str(jax.make_jaxpr(lambda x: moe.apply(
        moe.init(jax.random.PRNGKey(0), 64, 48, whole_cfg, jnp.float32), x,
        whole_cfg))(x))


@pytest.mark.parametrize("tokens", [24, 160])
def test_the_shares_add_up_to_the_uncut_layer_of_the_reference(tokens):
    """8 ranks of 2 experts each, in the dense form (24 tokens) and the
    grouped one (160): the routed parts of all shares, plus the shared
    expert once, are the reference's uncut expert layer (the same float32
    terms; the reference sums an expert at a time: 2e-5)."""
    model = tiny_model(experts_held=16)
    root = bench_weights.root_key(5)
    w = bench_weights.layer_slice(root, model, "expert_layers", 1)
    x = jax.random.normal(jax.random.PRNGKey(7), (tokens, model["dim"]))
    want = ref.layer_forward(x, w, model, "E") - x
    h = rmsnorm(x, w["norm"], 1e-5)[None]
    m = w["moe"]
    shared_only = None
    routed = jnp.zeros_like(x)
    for rank in range(8):
        cfg = dataclasses.replace(program_config(model),
                                  expert_rank=f"{rank}/8").moe
        first, count = cfg.held
        share = {**m, "w_up": m["w_up"][first:first + count],
                 "w_down": m["w_down"][first:first + count]}
        out, load = moe.apply(share, h, cfg, with_load=True)
        none = moe.apply({**share, "w_down": jnp.zeros_like(share["w_down"])},
                         h, cfg)[0]
        shared_only = none if shared_only is None else shared_only
        np.testing.assert_allclose(none, shared_only, atol=1e-6)
        routed = routed + (out - none)[0]
        assert 0 <= load[2] <= count  # experts touched, of those held
    np.testing.assert_allclose(routed + shared_only[0], want, atol=2e-5)
    # one share alone is NOT the layer: what the absent ranks add is left out
    assert float(jnp.abs(out[0] - want).max()) > 1e-3


def test_expert_rows_count_the_held_share():
    cfg = llama.tiny_hybrid()
    assert gen.expert_rows(cfg, 10) == ("dropless", 4 * 4 * 10)
    assert gen.expert_rows(dataclasses.replace(cfg, expert_rank="1/4"), 10) \
        == ("dropless", 4 * 4 * 10 // 4)
    assert gen.expert_rows(llama.tiny_latent(), 10) == ("dropless", 2 * 4 * 10)


# -- program against the benchmark's reference, on the benchmark's weights ----

def tiny_model(experts_held=4, pattern="MEM*EMEME"):
    return {
        "family": "nemotron_h_like", "vocab": 512, "dim": 64, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "attn_rope": False, "rope_theta": 1e4,
        "pattern": pattern, "mamba_heads": 8, "mamba_head_dim": 8,
        "ssm_groups": 2, "ssm_state": 16, "conv_kernel": 4, "chunk": 8,
        "time_step_min": 1e-3, "time_step_max": 0.1, "time_step_floor": 1e-4,
        "moe_dim": 48, "shared_dim": 96, "n_experts": 16,
        "experts_held": experts_held, "expert_first": 0, "moe_top_k": 4,
        "routed_scale": 2.5, "rms_norm_eps": 1e-5, "dtype": "float32",
        "n_layers": len(pattern), "max_seq": 128}


def program_config(model):
    from benchmarks.runners import serve_hybrid

    return serve_hybrid.program_config(model)


@pytest.fixture(scope="module")
def served():
    model = tiny_model()
    cfg = program_config(model)
    params = bench_weights.make_on_device(11, model)
    bench_weights.check_against_program(model, jax.eval_shape(
        lambda k: llama.init(k, cfg), jax.random.PRNGKey(0)))
    return model, cfg, params


def test_the_config_the_runner_builds_is_the_tiny_preset(served):
    _, cfg, _ = served
    assert cfg == dataclasses.replace(
        llama.tiny_hybrid(expert_rank="0/4"), max_seq=128, rope_theta=1e4)


def test_full_forward_against_the_reference(served):
    """llama.apply (the chunked scan from zeros, the grouped products) and
    the reference (sequential recurrence, an expert at a time) in float32
    on the same seeded weights: the same terms summed in another order
    through 9 layers, logits of magnitude 1: 5e-4."""
    model, cfg, params = served
    tokens = np.random.default_rng(0).integers(0, 512, 45)
    want = ref.logits_many(11, model, [tokens.tolist()], [np.arange(45)])[0]
    got = llama.apply(params, jnp.asarray(tokens)[None], cfg)[0]
    np.testing.assert_allclose(got, want, atol=5e-4)


def pools(cfg, slots, n_pages=24):
    return {**gen.init_page_pool(cfg, n_pages, PAGE),
            **gen.init_state_pool(cfg, slots)}


@functools.lru_cache(maxsize=None)
def programs(cfg):
    """(prefill, decode) jitted once a configuration (a trace a bucket)."""
    return (jax.jit(lambda p, t, n, pool, table, start, slot:
                    gen.prefill_into_pages(p, t, n, pool, table, start, cfg,
                                           PAGE, None, slot)),
            jax.jit(lambda p, t, pool, tables, pos:
                    gen.decode_step(p, t, pool, tables, pos, cfg, PAGE)))


def prefill(params, cfg, pool, table, tokens, start, slot, bucket):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(tokens)] = tokens
    return programs(cfg)[0](
        params, jnp.asarray(padded), jnp.int32(len(tokens)), pool,
        jnp.asarray(table), jnp.int32(start), jnp.int32(slot))


@pytest.mark.parametrize("pieces", [
    [(32, 32), (8, 8)],               # a full chunk and an exact rest
    [(16, 16), (16, 16), (8, 32)],    # a padded last slice
    [(8, 8)] * 5,                     # the smallest bucket
    [(32, 32), (5, 8), (3, 8)],       # slices that end off a chunk of 8
])
def test_a_prompt_in_slices_of_any_bucket_is_the_prompt_whole(served, pieces):
    """State, conv window, pages and the last row's logits after a chunked
    prefill of 40 tokens, against the prompt in one call (padded to its
    bucket, 64), and the logits against the reference's."""
    model, cfg, params = served
    tokens = np.random.default_rng(1).integers(0, 512, 40)
    table = np.arange(1, 9, dtype=np.int32)
    want_logits, want = prefill(params, cfg, pools(cfg, 3), table, tokens,
                                0, 1, 64)
    pool, at = pools(cfg, 3), 0
    for n, bucket in pieces:
        logits, pool = prefill(params, cfg, pool, table, tokens[at:at + n],
                               at, 1, bucket)
        at += n
    np.testing.assert_allclose(logits, want_logits, atol=2e-4)
    for leaf in ("ssm", "conv", "k", "v"):
        np.testing.assert_allclose(pool[leaf], want[leaf], atol=2e-5)
    # only slot 1's rows moved
    assert not np.any(pool["ssm"][:, [0, 2]]) \
        and not np.any(pool["conv"][:, [0, 2]])
    ref_logits = ref.logits_many(11, model, [tokens.tolist()], [[39]])[0][0]
    np.testing.assert_allclose(logits, ref_logits, atol=5e-4)


def test_decode_moves_live_rows_only_and_a_successor_starts_from_zeros(served):
    model, cfg, params = served
    rng = np.random.default_rng(2)
    first, second = rng.integers(0, 512, 20), rng.integers(0, 512, 12)
    tables = np.zeros((3, 8), np.int32)
    tables[1, :2] = [1, 2]
    _, pool = prefill(params, cfg, pools(cfg, 3), tables[1], first, 0, 1, 32)
    before = jax.tree.map(np.asarray, pool)
    # Row 0 is idle (its table maps nothing), row 2 is mid-prefill (its
    # table row is zeroed while its slices run): neither row's state moves.
    logits, pool = programs(cfg)[1](
        params, jnp.asarray([5, 7, 9], jnp.int32), pool, jnp.asarray(tables),
        jnp.asarray([3, 20, 11], jnp.int32))
    for leaf in ("ssm", "conv"):
        np.testing.assert_array_equal(pool[leaf][:, [0, 2]],
                                      before[leaf][:, [0, 2]])
        assert np.abs(np.asarray(pool[leaf][:, 1]) - before[leaf][:, 1]).max() > 0
    want = ref.logits_many(11, model, [first.tolist() + [7]], [[20]])[0][0]
    np.testing.assert_allclose(logits[1], want, atol=5e-4)
    # The slot's next request starts at position 0 over the state the
    # first one left: it reads none of it.
    tables[1, :2] = [3, 4]
    got, reused = prefill(params, cfg, pool, tables[1], second, 0, 1, 16)
    fresh_logits, fresh = prefill(params, cfg, pools(cfg, 3), tables[1],
                                  second, 0, 1, 16)
    np.testing.assert_array_equal(got, fresh_logits)
    for leaf in ("ssm", "conv"):
        np.testing.assert_array_equal(reused[leaf][:, 1], fresh[leaf][:, 1])


def test_verify_step_refuses_recurrent_state(served):
    _, cfg, params = served
    with pytest.raises(ValueError, match="roll the state back"):
        gen.verify_step(params, jnp.zeros((3, 2), jnp.int32), pools(cfg, 3),
                        jnp.zeros((3, 8), jnp.int32),
                        jnp.zeros((3,), jnp.int32), cfg, PAGE)


# -- through ServeEngine -------------------------------------------------------

@pytest.mark.parametrize("chunk", [0, 16])
def test_engine_prefill_then_decode_against_the_references_full_forward(
        served, chunk):
    """More requests than slots, so slots are reused mid-flight: every
    served token's reference logit against the reference's best at its
    position (the benchmark's comparison, logits and not tokens). Float32
    on both sides; the gap is 0 wherever the program's arg-max is the
    reference's, and a near-tie may flip under the reordered sums: 1e-3."""
    model, cfg, params = served
    engine = ServeEngine(params, cfg, max_batch=3, max_seq=128,
                         prefix_cache_bytes=0, kv_page_tokens=PAGE,
                         prefill_chunk=chunk)
    try:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 512, n).tolist()
                   for n in (37, 9, 50, 21, 64, 5, 33)]
        handles = [engine.submit(p, max_new=10) for p in prompts]
        outs = [h.result(timeout=300) for h in handles]
        stats, pool = engine.stats(), engine.pool_stats()
    finally:
        engine.stop()
    gaps = np.concatenate(ref.served_gaps_many(11, model, list(zip(prompts, outs))))
    assert gaps.max() <= 1e-3, gaps.max()
    assert stats["state_resets"] == len(prompts)
    assert stats["state_bytes"] == pool["state_bytes"] == gen.state_bytes(cfg, 3)
    assert pool["state_slots_live"] == 0 and stats["cache_kind"] == "gqa"
    assert stats["expert_load_steps"] > 0
    assert 0 < stats["experts_touched_sum"] / stats["expert_load_steps"] <= 4


@pytest.mark.parametrize("kwargs,match", [
    (dict(), "prefix store"),
    (dict(prefix_cache_bytes=0, kv_host_bytes=1 << 20), "host tier"),
    (dict(prefix_cache_bytes=0, spec_tokens=2, draft="self"), "speculative"),
    (dict(prefix_cache_bytes=0, shard=2), "shard > 1"),
    (dict(prefix_cache_bytes=0, role="prefill"), "role 'prefill'"),
])
def test_what_cannot_be_right_beside_recurrent_state_is_refused(
        served, kwargs, match):
    _, cfg, params = served
    if kwargs.pop("draft", None):
        kwargs.update(draft_params=params, draft_cfg=cfg)
    with pytest.raises(ValueError, match=match + ".*recurrent state"):
        ServeEngine(params, cfg, max_batch=2, max_seq=64, **kwargs)


def test_oim_serve_names_the_model():
    from oim_tpu.cli import oim_serve

    assert getattr(llama, oim_serve.SERVED_ONLY["nemotron-3-nano-30b"]) \
        is llama.NEMOTRON_3_NANO_30B
    with pytest.raises(SystemExit):
        oim_serve.main(["--model", "no-such-model"])
