"""A hybrid of KDA mixers (a gated delta rule with one decay a channel) and
gated GQA attention, an expert block behind each (PR 37), small, on the
CPU, in float32: the mixer's two forms against each other and against the
reference's sequential recurrence, the derived pattern, the state pool a
kind of recurrent layer, the serving programs and ``ServeEngine`` against
the benchmark's plain reference on seeded weights, and the eight held
shares of an expert layer against the uncut one. Self-contained: no
cluster, no port."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_solar_open2 as bench_weights
from benchmarks.reference import solar_open2_like as ref
from oim_tpu.models import generate as gen
from oim_tpu.models import llama, moe
from oim_tpu.ops import kda
from oim_tpu.ops.norms import rmsnorm
from oim_tpu.serve.engine import ServeEngine

PAGE = 16
D = kda.Dims(heads=4, head_dim=8, conv=4, chunk=4, neg_eigval=True)
DIM = 32
EPS = 1e-5


def mixer_layer(seed=0, fast=5):
    """A mixer whose first ``fast`` channels decay by e^-6 or more a
    position (a chunk of 4 takes them under e^-24, a slice of 21 under
    float32's smallest number), a gate bias that does something, and a
    beta projection wide enough that beta passes 1.5."""
    layer = jax.tree.map(lambda a: a[0], kda.init(
        jax.random.PRNGKey(seed), DIM, D, jnp.float32, 1))
    layer["dt_bias"] = layer["dt_bias"].at[:fast].set(6.0)
    layer["A_log"] = layer["A_log"].at[0].set(jnp.log(16.0))
    layer["g_bias"] = 0.5 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), layer["g_bias"].shape)
    layer["w_beta"] = 3.0 * layer["w_beta"]
    return layer


def some_state(batch, seed=2):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (batch, D.heads, D.head_dim, D.head_dim)),
            jax.random.normal(k2, (batch, D.conv - 1, D.conv_dim)))


def token_by_token(layer, x, s, c):
    outs = []
    for t in range(x.shape[1]):
        o, s, c = kda.step(layer, x[:, t], s, c, D, EPS)
        outs.append(o)
    return jnp.stack(outs, 1), s, c


def mixer_model():
    """The reference's description of ``mixer_layer``'s one block."""
    return {"kda_heads": D.heads, "kda_head_dim": D.head_dim,
            "kda_conv": D.conv, "neg_eigval": True, "rms_norm_eps": EPS}


# -- the mixer: scan = step = the sequential definition ------------------------

def test_the_one_token_update_is_the_references_recurrence():
    """From an empty state ``kda.step`` token by token IS the reference's
    sequential scan (which knows no chunk and no carried window)."""
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (21, DIM))
    w = {**layer, "norm": jnp.ones((DIM,))}
    h = rmsnorm(x, w["norm"], EPS)
    want = ref.kda_forward(x, w, mixer_model()) - x
    got, s, _ = token_by_token(
        layer, h[None], jnp.zeros((1, D.heads, D.head_dim, D.head_dim)),
        jnp.zeros((1, D.conv - 1, D.conv_dim)))
    np.testing.assert_allclose(got[0], want, atol=2e-6)
    g, beta, _ = kda._gates(layer, h, D)
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5
    assert float(g.min()) < -80 and float(g.max()) > -0.1  # both ends


@pytest.mark.parametrize("length", [8, 16, 5, 21, 1])
@pytest.mark.parametrize("carried", [False, True])
def test_chunked_scan_against_the_one_token_update(length, carried):
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, length, DIM))
    s, c = some_state(2) if carried else jax.tree.map(
        jnp.zeros_like, some_state(2))
    want, ws, wc = token_by_token(layer, x, s, c)
    got, gs, gc = kda.scan(layer, x, s, c, length, D, EPS)
    np.testing.assert_allclose(got, want, atol=3e-6)
    np.testing.assert_allclose(gs, ws, atol=3e-6)
    np.testing.assert_array_equal(gc, wc)


@pytest.mark.parametrize("real,padded", [(5, 8), (13, 32), (1, 8), (16, 16)])
def test_padding_leaves_state_and_window_at_the_last_real_token(real, padded):
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(4), (1, padded, DIM))
    s, c = some_state(1)
    want, ws, wc = token_by_token(layer, x[:, :real], s, c)
    got, gs, gc = kda.scan(layer, x, s, c, real, D, EPS)
    np.testing.assert_allclose(got[:, :real], want, atol=3e-6)
    np.testing.assert_allclose(gs, ws, atol=3e-6)
    np.testing.assert_allclose(gc, wc, atol=1e-6)  # a product of another shape


def test_slices_carry_what_a_whole_scan_computes():
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 27, DIM))
    s, c = some_state(1)
    want, ws, wc = kda.scan(layer, x, s, c, 27, D, EPS)
    a, s1, c1 = kda.scan(layer, x[:, :16], s, c, 16, D, EPS)
    tail = jnp.pad(x[:, 16:], ((0, 0), (0, 5), (0, 0)))
    b, s2, c2 = kda.scan(layer, tail, s1, c1, 11, D, EPS)
    np.testing.assert_allclose(
        jnp.concatenate([a, b[:, :11]], 1), want, atol=3e-6)
    np.testing.assert_allclose(s2, ws, atol=3e-6)
    np.testing.assert_array_equal(c2, wc)


def test_a_decay_that_passes_float32_inside_a_chunk_is_exact():
    """One chunk of 16 positions, channels that lose e^-96 a position: the
    cumulative log-decay reaches -1500, so ``exp(G_t) * exp(-G_j)`` is 0 x
    inf. The scan takes differences first and stays the recurrence."""
    d16 = dataclasses.replace(D, chunk=16)
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 16, DIM))
    s, c = some_state(1)
    g, _, _ = kda._gates(layer, x, D)
    cum = jnp.cumsum(g, axis=1)
    assert float(cum.min()) < -1000
    with np.errstate(over="ignore", invalid="ignore"):
        naive = np.exp(np.asarray(cum)[0, -1]) * np.exp(-np.asarray(cum)[0, 3])
    assert not np.all(np.isfinite(naive))
    want, ws, _ = token_by_token(layer, x, s, c)
    got, gs, _ = kda.scan(layer, x, s, c, 16, d16, EPS)
    assert np.all(np.isfinite(got)) and np.all(np.isfinite(gs))
    np.testing.assert_allclose(got, want, atol=3e-6)
    np.testing.assert_allclose(gs, ws, atol=3e-6)


def test_without_negative_eigenvalues_beta_stays_under_one():
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(7), (9, DIM))
    _, two, _ = kda._gates(layer, x, D)
    _, one, _ = kda._gates(layer, x, dataclasses.replace(D, neg_eigval=False))
    np.testing.assert_allclose(two, 2 * one, rtol=1e-6)
    assert float(one.max()) < 1 < float(two.max())


def test_the_unit_lower_inverse_is_the_inverse():
    m = jnp.tril(jax.random.normal(jax.random.PRNGKey(8), (3, 7, 7)), -1)
    inv = kda._unit_lower_inverse(m)
    np.testing.assert_allclose(
        jnp.einsum("bij,bjk->bik", jnp.eye(7) + m, inv, precision="highest"),
        jnp.broadcast_to(jnp.eye(7), m.shape), atol=1e-5)


# -- the configuration: published keys, derived pattern, counts ----------------

def test_the_published_constant():
    cfg = llama.SOLAR_OPEN2_250B
    assert cfg.pattern == "*EKEKEKE" * 12 and cfg.n_layers == 48
    assert (cfg.n_of("*"), cfg.n_of("K"), cfg.n_of("E")) == (12, 36, 48)
    assert cfg.kda == kda.Dims(heads=64, head_dim=128, conv=4, chunk=16,
                               neg_eigval=True)
    # the scan's chunk is the program's constant: no key of the model
    assert not [f.name for f in dataclasses.fields(llama.Config)
                if "kda" in f.name and "chunk" in f.name]
    assert cfg.moe.n_experts == 320 and cfg.moe.top_k == 8 \
        and cfg.moe.act == "silu" and cfg.moe.routed_scale == 1.0
    assert moe.stored_width(cfg.expert_dim) == 1280  # ten whole lanes
    # 250.29 B from the equations against the published 250 B
    assert abs(llama.num_params(cfg) / 250e9 - 1) < 0.005
    # one rank of eight over one period: what the benchmark's cell holds
    held = dataclasses.replace(cfg, n_layers=4, expert_rank="0/8", vocab=24576)
    assert held.pattern == "*EKEKEKE"
    assert llama.pattern_runs(held.pattern) == (("*", 1), ("EK", 3), ("E", 1))
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), held))
    assert llama.num_params(held) == 3_308_377_920 == sum(
        x.size for x in jax.tree.leaves(shapes))
    # a slot: S [64, 128, 128] float32 and [3, 24576] bfloat16, 3 layers
    assert gen.state_bytes(held) == 3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    assert gen.state_bytes_by_kind(held, 64) == {"kda": 833_617_920}
    assert held.cache_leaves == {"k": (8, 128), "v": (8, 128)} \
        and held.n_cache_layers == 1


def test_tiny_kda_counts_its_parameters_and_keeps_a_period():
    cfg = llama.tiny_kda()
    assert cfg.pattern == "*EKEKEKE"
    params = llama.init(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"embed", "final_norm", "lm_head", "kda_layers",
                           "expert_layers", "attn_layers"}
    assert params["kda_layers"]["w_qkv"].shape == (3, 64, 3 * 64)
    assert params["attn_layers"]["wg"].shape == (1, 64, 64)
    assert sum(x.size for x in jax.tree.leaves(params)) == llama.num_params(cfg)
    assert set(gen.init_state_pool(cfg, 3)) == {"kda", "kda_conv"}
    assert gen.init_state_pool(cfg, 3)["kda"].shape == (3, 3, 4, 16, 16)
    eight = llama.tiny_kda(n_layers=8)
    assert eight.pattern == "*EKEKEKE" * 2 and eight.n_of("K") == 6


@pytest.mark.parametrize("fields,match", [
    (dict(kda_use_full_proj=True), "kda_use_full_proj"),
    (dict(kda_head_dim=0), "kda_head_dim"),
    (dict(hybrid_override_pattern="*EK"), "n_layers"),
    (dict(moe_dispatch="gather", scoring_func="softmax"), "ragged"),
    (dict(gqa_layers=(), use_gqa_gate=True, kda_num_heads=0), "use_gqa_gate"),
])
def test_a_malformed_kda_hybrid_is_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(llama.tiny_kda(), **fields)


def test_a_given_pattern_may_name_the_kind():
    """``K`` is a kind of the pattern language as ``M`` is: a given pattern
    wins over the one ``gqa_layers`` derive, and both recurrent kinds may
    stand in one model, each with its own leaves of the state pool."""
    with pytest.raises(ValueError, match="'K' needs kda_num_heads"):
        llama.tiny_hybrid(pattern="KEKE")
    cfg = dataclasses.replace(
        llama.tiny_hybrid(), hybrid_override_pattern="MEK*E", n_layers=5,
        kda_num_heads=4, kda_head_dim=8)
    assert cfg.pattern == "MEK*E" and set(cfg.recurrent) == {"M", "K"}
    assert set(gen.init_state_pool(cfg, 2)) == {"ssm", "conv", "kda", "kda_conv"}
    assert set(gen.state_bytes_by_kind(cfg)) == {"mamba", "kda"}
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 40), 0, cfg.vocab)
    assert np.all(np.isfinite(llama.apply(params, tokens, cfg)))


def test_no_sharding_rules_and_no_dense_cache():
    cfg = llama.tiny_kda()
    with pytest.raises(ValueError, match="KDA"):
        llama.param_logical_axes(cfg)
    with pytest.raises(ValueError, match="hybrid pattern"):
        gen.init_cache(cfg, 1, 32)
    with pytest.raises(ValueError, match="hybrid pattern"):
        gen.shard_config(cfg, 2)


# What the parent (6cdc048) initialises and computes for the hybrid preset
# PR 33 added, beside tests/test_hybrid.py's three: this PR moved its layer
# loop and its state pool onto the interface KDA shares.
def test_the_hybrid_preset_is_the_parents():
    cfg = llama.tiny_hybrid()
    assert (cfg.pattern, cfg.kda, cfg.use_gqa_gate) == ("MEM*EMEME", None, False)
    params = llama.init(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, cfg.vocab)
    logits = llama.apply(params, tokens, cfg)
    leaves = jax.tree.leaves(params)
    assert sum(x.size for x in leaves) == 595488
    np.testing.assert_allclose(
        sum(float(jnp.abs(x.astype(jnp.float32)).sum()) for x in leaves),
        60869.30617454648, rtol=1e-6)
    np.testing.assert_allclose(float(jnp.abs(logits).sum()),
                               13041.8525390625, rtol=1e-6)
    np.testing.assert_allclose(
        logits[0, -1, :3],
        [-0.6080976128578186, -1.0865082740783691, 0.9535605311393738],
        rtol=1e-5, atol=1e-6)
    pool = gen.init_state_pool(cfg, 3)
    assert {k: v.shape for k, v in pool.items()} == {
        "ssm": (4, 3, 8, 8, 16), "conv": (4, 3, 3 * 128)}
    assert gen.state_bytes_by_kind(cfg, 3) == {"mamba": gen.state_bytes(cfg, 3)}


# -- the expert block: eight held shares of 320 SwiGLU experts -----------------

def tiny_model(experts_held=4, n_experts=16, n_layers=4):
    from benchmarks.runners import serve_kda

    return {
        "family": "solar_open2_like", "vocab": 512, "dim": 64, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "attn_rope": False, "gqa_gate": True,
        "rope_theta": 1e4,
        "pattern": serve_kda.pattern(n_layers, range(0, n_layers, 4)),
        "kda_heads": 4, "kda_head_dim": 16, "kda_conv": 4, "kda_rank": 16,
        "neg_eigval": True, "time_step_min": 1e-3, "time_step_max": 0.1,
        "moe_dim": 32, "shared_dim": 32, "n_experts": n_experts,
        "experts_held": experts_held, "expert_first": 0, "moe_top_k": 4,
        "routed_scale": 1.0, "rms_norm_eps": 1e-5, "dtype": "float32",
        "n_layers": n_layers, "max_seq": 128}


def program_config(model, **extra):
    from benchmarks.runners import serve_kda

    return serve_kda.program_config(model, **extra)


@pytest.mark.parametrize("tokens", [24, 160])
def test_the_eight_shares_add_up_to_the_uncut_layer_of_the_reference(tokens):
    """8 ranks of 40 experts each of 320, top-8, three products an expert,
    in the dense form (24 tokens) and the bounded or grouped one (160): the
    routed parts of all shares, plus the shared expert ONCE, are the
    reference's uncut expert block (the same float32 terms; the reference
    sums an expert at a time: 2e-5)."""
    model = {**tiny_model(experts_held=320, n_experts=320), "moe_top_k": 8}
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
        assert count == 40 and cfg.top_k == 8 and cfg.act == "silu"
        share = {**m, **{k: m[k][first:first + count]
                         for k in moe.EXPERT_LEAVES}}
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


# -- program against the benchmark's reference, on the benchmark's weights ----

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
        llama.tiny_kda(expert_rank="0/4"), max_seq=128, rope_theta=1e4)
    assert cfg.moe.held == (0, 4)


def test_full_forward_against_the_reference(served):
    """llama.apply (the chunked scan from zeros, the grouped products) and
    the reference (sequential recurrence, an expert at a time) in float32
    on the same seeded weights: the same terms summed in another order
    through 8 blocks, logits of magnitude 1: 5e-4."""
    model, cfg, params = served
    tokens = np.random.default_rng(0).integers(0, 512, 45)
    want = ref.logits_many(11, model, [tokens.tolist()], [np.arange(45)])[0]
    got = llama.apply(params, jnp.asarray(tokens)[None], cfg)[0]
    np.testing.assert_allclose(got, want, atol=5e-4)


def test_the_attention_layer_is_gated_and_rotates_nothing(served):
    """The one attention block of the period against the reference's, which
    has no rotary embedding to leave out; without the gate, or with a
    rotation, the program's block is another function."""
    model, cfg, params = served
    w = bench_weights.layer_slice(
        bench_weights.root_key(11), model, "attn_layers", 0)
    x = jax.random.normal(jax.random.PRNGKey(9), (24, 64))
    want = ref.attention_forward(x, w, model)

    def block(cfg):
        cos, sin = llama.rope_frequencies(cfg.rope_dim, 24, cfg.rope_theta)
        return llama._attn_mixer(
            x[None], w, cfg, cos, sin, None,
            lambda _, q, k, v: (llama.default_attention(
                q, k, v, causal=True), None), None)[0][0]

    np.testing.assert_allclose(block(cfg), want, atol=2e-5)
    for other in (dict(use_gqa_gate=False), dict(attn_rope=True)):
        got = block(dataclasses.replace(cfg, **other))
        assert float(jnp.abs(got - want).max()) > 1e-2


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
    [(32, 32), (5, 8), (3, 8)],       # slices that end off a chunk of 16
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
    for leaf in ("kda", "kda_conv", "k", "v"):
        np.testing.assert_allclose(pool[leaf], want[leaf], atol=2e-5)
    # only slot 1's rows moved
    assert not np.any(pool["kda"][:, [0, 2]]) \
        and not np.any(pool["kda_conv"][:, [0, 2]])
    ref_logits = ref.logits_many(11, model, [tokens.tolist()], [[39]])[0][0]
    np.testing.assert_allclose(logits, ref_logits, atol=5e-4)


def test_decode_moves_live_rows_only_and_a_successor_starts_from_zeros(served):
    """A retired slot's row is dead where it lies: the decode step leaves
    the rows of idle and mid-prefill slots as they were, and the slot's
    next request reads none of what its predecessor left."""
    model, cfg, params = served
    rng = np.random.default_rng(2)
    first, second = rng.integers(0, 512, 20), rng.integers(0, 512, 12)
    tables = np.zeros((3, 8), np.int32)
    tables[1, :2] = [1, 2]
    _, pool = prefill(params, cfg, pools(cfg, 3), tables[1], first, 0, 1, 32)
    # row 0 holds a retired request's state, row 2 is mid-prefill
    pool = {**pool, "kda": pool["kda"].at[:, 0].set(1.5),
            "kda_conv": pool["kda_conv"].at[:, 2].set(-0.5)}
    before = jax.tree.map(np.asarray, pool)
    logits, pool = programs(cfg)[1](
        params, jnp.asarray([5, 7, 9], jnp.int32), pool, jnp.asarray(tables),
        jnp.asarray([3, 20, 11], jnp.int32))
    for leaf in ("kda", "kda_conv"):
        np.testing.assert_array_equal(pool[leaf][:, [0, 2]],
                                      before[leaf][:, [0, 2]])
        assert np.abs(np.asarray(pool[leaf][:, 1]) - before[leaf][:, 1]).max() > 0
    want = ref.logits_many(11, model, [first.tolist() + [7]], [[20]])[0][0]
    np.testing.assert_allclose(logits[1], want, atol=5e-4)
    tables[1, :2] = [3, 4]
    got, reused = prefill(params, cfg, pool, tables[1], second, 0, 1, 16)
    fresh_logits, fresh = prefill(params, cfg, pools(cfg, 3), tables[1],
                                  second, 0, 1, 16)
    np.testing.assert_array_equal(got, fresh_logits)
    for leaf in ("kda", "kda_conv"):
        np.testing.assert_array_equal(reused[leaf][:, 1], fresh[leaf][:, 1])


def test_verify_step_refuses_recurrent_state(served):
    _, cfg, params = served
    with pytest.raises(ValueError, match="roll the state back"):
        gen.verify_step(params, jnp.zeros((3, 2), jnp.int32), pools(cfg, 3),
                        jnp.zeros((3, 8), jnp.int32),
                        jnp.zeros((3,), jnp.int32), cfg, PAGE)


# -- through ServeEngine -------------------------------------------------------

PROMPT_LENGTHS = (37, 9, 50, 21, 64, 5, 33)


def serve(params, cfg, chunk):
    engine = ServeEngine(params, cfg, max_batch=3, max_seq=128,
                         prefix_cache_bytes=0, kv_page_tokens=PAGE,
                         prefill_chunk=chunk)
    try:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 512, n).tolist() for n in PROMPT_LENGTHS]
        handles = [engine.submit(p, max_new=10) for p in prompts]
        outs = [h.result(timeout=300) for h in handles]
        return prompts, outs, engine.stats(), engine.pool_stats()
    finally:
        engine.stop()


@pytest.mark.parametrize("chunk", [0, 16])
def test_engine_prefill_then_decode_against_the_references_full_forward(
        served, chunk):
    """More requests than slots, so slots are reused mid-flight: every
    served token's reference logit against the reference's best at its
    position (the benchmark's comparison, logits and not tokens). Float32
    on both sides; the gap is 0 wherever the program's arg-max is the
    reference's, and a near-tie may flip under the reordered sums: 1e-3."""
    model, cfg, params = served
    prompts, outs, stats, pool = serve(params, cfg, chunk)
    gaps = np.concatenate(
        ref.served_gaps_many(11, model, list(zip(prompts, outs))))
    assert gaps.max() <= 1e-3, gaps.max()
    assert stats["state_resets"] == len(prompts)
    assert stats["state_bytes"] == pool["state_bytes"] == gen.state_bytes(cfg, 3)
    assert pool["state_bytes_by_kind"] == {"kda": gen.state_bytes(cfg, 3)}
    assert pool["state_slots_live"] == 0 and stats["cache_kind"] == "gqa"
    assert stats["expert_load_steps"] > 0
    assert 0 < stats["experts_touched_sum"] / stats["expert_load_steps"] <= 4


def decode_logits(params, cfg, prompt, steps):
    """Logits of ``steps`` paged decode steps after a prefill of ``prompt``
    in slot 1: [steps, vocab], and the tokens fed (the prompt's own
    continuation is fixed, not sampled)."""
    tables = np.zeros((3, 8), np.int32)
    tables[1, :4] = [1, 2, 3, 4]
    fed = np.random.default_rng(8).integers(0, 512, steps)
    _, pool = prefill(params, cfg, pools(cfg, 3), tables[1], prompt, 0, 1, 32)
    out = []
    for i, tok in enumerate(fed):
        logits, pool = programs(cfg)[1](
            params, jnp.asarray([0, tok, 0], jnp.int32), pool,
            jnp.asarray(tables),
            jnp.asarray([0, len(prompt) + i, 0], jnp.int32))
        out.append(logits[1])
    return jnp.stack(out), fed


@pytest.mark.parametrize("broken", [
    "", "state in bfloat16", "no decay in the step", "beta under 1"])
def test_a_broken_path_fails_the_tiny_comparison(served, monkeypatch, broken):
    """What the comparison is for. Prefill then 8 paged decode steps against
    the reference's full forward, logits: the sound program within 5e-4; a
    program that keeps its state in the model's type where the configuration
    states float32, leaves the decay out of the one-token update, or halves
    beta, ten times outside it."""
    model, cfg, params = served
    real_step, real_scan = kda.step, kda.scan

    def rounded(fn):
        def run(*args):
            out, state, conv = fn(*args)
            return out, jax.lax.reduce_precision(state, 8, 7), conv
        return run

    if broken == "state in bfloat16":
        monkeypatch.setattr(kda, "step", rounded(real_step))
        monkeypatch.setattr(kda, "scan", rounded(real_scan))
    elif broken == "no decay in the step":
        monkeypatch.setattr(kda, "step", lambda layer, *a: real_step(
            {**layer, "A_log": jnp.full_like(layer["A_log"], -30.0)}, *a))
    elif broken:
        cfg = dataclasses.replace(cfg, kda_allow_neg_eigval=False)
    prompt = np.random.default_rng(5).integers(0, 512, 20)
    programs.cache_clear()  # traced again, over what stands in kda now
    try:
        got, fed = decode_logits(params, cfg, prompt, 8)
    finally:
        programs.cache_clear()
    tokens = prompt.tolist() + fed.tolist()
    want = ref.logits_many(11, model, [tokens], [np.arange(20, 28)])[0]
    worst = float(jnp.abs(got - want).max())
    assert worst < 5e-4 if not broken else worst > 5e-3, worst


def test_the_references_own_broken_state_reads_worse_than_itself(served):
    """The same from the reference's side: its logits with the state
    rounded to bfloat16 after every position are not its float32 logits."""
    model, _, _ = served
    tokens = np.random.default_rng(4).integers(0, 512, 60).tolist()
    rows = [np.arange(60)]
    sound = ref.logits_many(11, model, [tokens], rows)[0]
    rounded = ref.logits_many(11, model, [tokens], rows,
                              state_dtype="bfloat16")[0]
    assert 1e-3 < float(jnp.abs(sound - rounded).max()) < 1.0


@pytest.mark.parametrize("kwargs,match", [
    (dict(prefix_cache_bytes=1 << 20), "prefix store"),
    (dict(prefix_cache_bytes=0, kv_host_bytes=1 << 20), "host tier"),
    (dict(prefix_cache_bytes=0, shard=2), "shard > 1"),
    (dict(prefix_cache_bytes=0, role="prefill"), "role"),
    (dict(prefix_cache_bytes=0, draft=True), "speculative decoding"),
])
def test_what_cannot_be_right_beside_recurrent_state_is_refused(
        served, kwargs, match):
    _, cfg, params = served
    if kwargs.pop("draft", False):
        kwargs.update(draft_params=params, draft_cfg=cfg)
    with pytest.raises(ValueError, match=match):
        ServeEngine(params, cfg, max_batch=2, max_seq=128,
                    kv_page_tokens=PAGE, **kwargs)


def test_oim_serve_names_the_model():
    from oim_tpu.cli import oim_serve

    assert getattr(llama, oim_serve.SERVED_ONLY["solar-open2-250b"]) \
        is llama.SOLAR_OPEN2_250B
