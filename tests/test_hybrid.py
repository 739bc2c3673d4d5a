"""A hybrid of mixers with recurrent state (PR 33), small, on the CPU, in
float32: the Mamba-2 mixer's two forms against each other and against the
sequential definition, the pattern's layer loop, the state pool beside the
page pool through the serving programs and ``ServeEngine``, the held share
of an expert layer, and program against the benchmark's plain reference on
seeded weights. Self-contained: no cluster, no port."""

import dataclasses
import functools
import os

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
    """The forms of a held share compute the same sum (float32, another
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
    np.testing.assert_allclose(dense_load[:4], grouped_load[:4], rtol=1e-6)
    # above the crossing a call says which rung it ran on (24 tokens: one
    # capacity, a token's worth, which always holds); the dense form ran on
    # none
    assert dense_load.shape == grouped_load.shape == (moe.load_width(cfg, 24),)
    assert not dense_load[4:].any() and list(grouped_load[4:]) == [1, 0, 0]

    def traced(x):
        return str(jax.make_jaxpr(lambda p, x: moe.apply(p, x, cfg))(layer, x))

    # above the crossing each held expert's own rows are sorted out and
    # gathered to a capacity: of few tokens a token's worth, which always
    # holds, so no grouped product stands behind it
    assert "argsort" in traced(x) and "ragged_dot" not in traced(x)
    monkeypatch.undo()
    assert "argsort" not in traced(x)
    long = jnp.zeros((1, moe.DENSE_UP_TO_TOKENS + 1, 64))
    assert "argsort" in traced(long) and "ragged_dot" not in traced(long)
    # capacities under the call's tokens: the grouped product behind them
    monkeypatch.setattr(moe, "MIN_CAPACITY", 8)
    monkeypatch.setattr(moe, "ROW_TILE", 8)
    monkeypatch.setattr(moe, "CAPACITY_MULTIPLE", 1)
    assert moe.capacity_ladder(65, cfg) == (16, 32)
    assert "ragged_dot" in traced(long) and "cond" in traced(long)
    # every expert held: the grouped form alone while uniform routing
    # sends an expert fewer than WHOLE_FROM_ROWS rows (a decode step), a
    # ladder of its own above (top-4 of 16: 8 tokens send 2 rows, 24 send 6)
    whole_cfg = llama.tiny_hybrid().moe
    monkeypatch.undo()

    def whole_traced(x):
        return str(jax.make_jaxpr(lambda x: moe.apply(
            moe.init(jax.random.PRNGKey(0), 64, 48, whole_cfg, jnp.float32),
            x, whole_cfg))(x))
    assert "ragged_dot" in whole_traced(x[:, :4])
    assert moe.capacity_ladder(8, whole_cfg) == ()
    assert moe.capacity_ladder(24, whole_cfg) == (24,)
    assert "ragged_dot" not in whole_traced(x) and "argsort" in whole_traced(x)


def held_layer(rank, stacked):
    """(cfg, one layer's params as the layer loop hands them over) of a held
    share of the tiny hybrid's 16 experts."""
    cfg = dataclasses.replace(llama.tiny_hybrid(), expert_rank=rank).moe
    params = moe.init(jax.random.PRNGKey(0), 64, 48, cfg, jnp.float32,
                      n_layers=3)
    if stacked:
        sliced, whole = moe.keep_stacked({"moe": params})
        return cfg, moe.at_layer(jax.tree.map(lambda a: a[1], sliced), whole,
                                 jnp.int32(1))["moe"]
    return cfg, jax.tree.map(lambda a: a[1], params)


def routed_to(cfg, tokens, held_rows):
    """A router that sends exactly the first ``held_rows`` assignments of
    the call to the experts held here, in turn, and every other one
    elsewhere."""
    first, count = cfg.held
    a = np.arange(tokens * cfg.top_k)
    elsewhere = np.array([e for e in range(cfg.n_experts)
                          if not first <= e < first + count])
    experts = np.where(a < held_rows, first + a % count,
                       elsewhere[a % len(elsewhere)])
    w = np.random.default_rng(held_rows).uniform(0.1, 1.0, a.shape)

    def route(params, x, cfg):
        return (jnp.asarray(experts.reshape(tokens, -1), jnp.int32),
                jnp.asarray(w.reshape(tokens, -1), jnp.float32))
    return route


def both_forms(monkeypatch, layer, x, cfg):
    """(bounded out, its load, whole out): the ladder's form, and the
    grouped product over every assignment row alone (today's ``_dropless``,
    the ladder's last rung)."""
    def run():
        return jax.jit(lambda p, x: moe.apply(p, x, cfg, with_load=True))(
            layer, x)
    bounded, load = run()
    with monkeypatch.context() as m:
        m.setattr(moe, "capacity_ladder", lambda n_tokens, cfg: ())
        whole, whole_load = run()
    assert list(whole_load[4:]) == [0, 0, 1]
    np.testing.assert_array_equal(load[:4], whole_load[:4])
    return bounded, load, whole


@pytest.fixture()
def toy_ladder(monkeypatch):
    """96 tokens, top-4 of 16: an expert expects 24 rows; capacities of
    24 and 48 rows an expert (row tiles of 8), then the grouped product."""
    monkeypatch.setattr(moe, "ROW_TILE", 8)
    monkeypatch.setattr(moe, "MIN_CAPACITY", 8)
    monkeypatch.setattr(moe, "CAPACITY_MULTIPLE", 1)


# One rank of eight holds 2 experts; the router hands them the first
# ``held rows`` assignments in turn, so the fuller gets half, rounded up.
HELD_ROWS = {
    "none-held": (0, "first"), "under-the-first-rung": (40, "first"),
    "on-the-first-rung": (48, "first"), "over-the-first-rung": (49, "second"),
    "on-the-second-rung": (96, "second"),
    "over-every-rung-but-the-last": (97, "whole"),
    "every-row-held": (384, "whole")}


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("case", sorted(HELD_ROWS))
def test_the_bounded_products_are_the_whole_product(
        monkeypatch, toy_ladder, case, stacked):
    """A held share's products over the smallest capacity that holds its
    fullest expert give what the grouped product over every assignment row
    gives (the last rung, the fallback): the same float32 terms, summed by
    another product (2e-5, as the dense form's)."""
    held_rows, rung = HELD_ROWS[case]
    cfg, layer = held_layer("1/8", stacked)
    assert moe.capacity_ladder(96, cfg) == (24, 48)
    monkeypatch.setattr(moe, "route", routed_to(cfg, 96, held_rows))
    x = jax.random.normal(jax.random.PRNGKey(held_rows), (1, 96, 64))
    bounded, load, whole = both_forms(monkeypatch, layer, x, cfg)
    assert dict(zip(moe.RUNG_NAMES, load[4:]))[rung] == 1 and load[4:].sum() == 1
    np.testing.assert_allclose(bounded, whole, atol=2e-5)
    if rung == "whole":  # the same program ran
        np.testing.assert_array_equal(bounded, whole)
    if held_rows:  # and the held experts did add something
        shared = moe._shared_ffn(layer["shared"], x[0])
        assert float(jnp.abs(whole[0] - shared).max()) > 1e-3


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("biased", [False, True])
def test_the_routers_own_choices_pick_the_rung(
        monkeypatch, toy_ladder, biased, stacked):
    """The layer's own router: unbiased, a quarter of the assignments fall
    to the 4 experts held of 16 and a bounded rung holds the fullest;
    biased onto the held range, every token picks all 4 (a token's worth
    of rows an expert, which no capacity under N holds) and the grouped
    product runs. Either way the sum is the whole product's."""
    cfg, layer = held_layer("1/4", stacked)
    if biased:
        first, count = cfg.held
        layer = {**layer, "bias": layer["bias"].at[first:first + count].add(10.0)}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 96, 64))
    assert moe.capacity_ladder(96, cfg) == (24, 48)
    bounded, load, whole = both_forms(monkeypatch, layer, x, cfg)
    if biased:
        assert list(load[4:]) == [0, 0, 1] and load[2] == 4
    else:
        assert load[6] == 0 and load[4] + load[5] == 1
    np.testing.assert_allclose(bounded, whole, atol=2e-5)


@pytest.mark.parametrize("held_rows", [40, 60, 300])
def test_rows_of_no_assignment_never_reach_the_sum(
        monkeypatch, toy_ladder, held_rows):
    """Rows no assignment owns are whatever the products left there:
    non-finite values planted in every one of them (a bounded rung's slots
    past an expert's count, the grouped product's rows past the groups'
    sum) reach no token's sum, not as a NaN times zero either."""
    cfg, layer = held_layer("1/8", True)
    monkeypatch.setattr(moe, "route", routed_to(cfg, 96, held_rows))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 96, 64))
    clean, load, clean_whole = both_forms(monkeypatch, layer, x, cfg)
    counts = jnp.asarray([-(-held_rows // 2), held_rows // 2])
    grouped, batched = moe.grouped_ffn, moe._batched_ffn

    def bad(rows):
        return jnp.where(jnp.arange(rows) % 2 == 0, jnp.nan, jnp.inf)

    def planted_grouped(params, rows, group_sizes, *limit):
        y = grouped(params, rows, group_sizes, *limit)
        live = jnp.arange(y.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(live[:, None], y, bad(y.shape[0])[:, None])

    def planted_batched(leaves, x, *limit):
        y = batched(leaves, x, *limit)
        live = jnp.arange(y.shape[1]) < counts[:, None]
        return jnp.where(live[..., None], y, bad(y.shape[1])[None, :, None])

    monkeypatch.setattr(moe, "grouped_ffn", planted_grouped)
    monkeypatch.setattr(moe, "_batched_ffn", planted_batched)
    bounded, planted_load, whole = both_forms(monkeypatch, layer, x, cfg)
    np.testing.assert_array_equal(planted_load, load)
    assert np.isfinite(bounded).all() and np.isfinite(whole).all()
    np.testing.assert_array_equal(bounded, clean)
    np.testing.assert_array_equal(whole, clean_whole)


WHOLE_LADDERS = {
    # joyai-llm-flash, top-8 of 256: a decode or verify step (one row an
    # expert at 32 tokens, two at 64) keeps the grouped product alone; a
    # slice takes one capacity of twice the uniform rows in whole row tiles
    "joyai-decode-32": ("joyai", 32, ()), "joyai-64": ("joyai", 64, ()),
    "joyai-128": ("joyai", 128, (128,)), "joyai-512": ("joyai", 512, (128,)),
    "joyai-2048": ("joyai", 2048, (128,)), "joyai-4096": ("joyai", 4096, (256,)),
    # mixtral-8x7b, top-2 of 8, the programs generate._no_drop runs dropless
    "mixtral-640": ("mixtral", 640, (384,)),
    "mixtral-1024": ("mixtral", 1024, (512,)),
    # past 256 uniform rows an expert the grouped product's row tiles are
    # full: it runs alone, as the parent ran it
    "mixtral-2048": ("mixtral", 2048, ()), "joyai-16384": ("joyai", 16384, ()),
}


@pytest.mark.parametrize("case", sorted(WHOLE_LADDERS))
def test_a_whole_sets_ladder_follows_the_calls_shapes(case):
    """``held == ()``: the ladder of a model that holds every expert, from
    E, k and N alone (the table beside ``moe.WHOLE_MULTIPLE``), and the
    load vector carries a rung exactly where there is a ladder."""
    from benchmarks import common

    name, tokens, ladder = WHOLE_LADDERS[case]
    cfg = {"joyai": llama.JOYAI_LLM_FLASH,
           "mixtral": dataclasses.replace(common.program_config(
               common.model_dict(common.load_json(os.path.join(
                   common.ROOT, "benchmarks", "configs",
                   "mixtral-8x7b.json")), "serve")),
               moe_dispatch="ragged")}[name].moe
    assert not cfg.held and moe.capacity_ladder(tokens, cfg) == ladder
    assert moe.load_width(cfg, tokens) == 4 + (
        len(moe.RUNG_NAMES) if ladder else 0)
    # the configuration's own dispatch below the crossing carries none
    assert moe.load_width(dataclasses.replace(cfg, dispatch="gather"),
                          tokens) == 4


def test_a_held_shares_ladder_is_what_it_was():
    """The ladder a slice of the published model's share is compiled for:
    its constants were set from ITS skew (PR 35), and a whole set's do not
    move them."""
    share = dataclasses.replace(llama.NEMOTRON_3_NANO_30B, expert_rank="0/8").moe
    assert moe.capacity_ladder(1024, share) == LADDER_1024
    # a short last piece: one capacity, a token's worth, which always holds
    assert moe.capacity_ladder(128, share) == (128,)
    assert moe.capacity_ladder(512, share) == (256, 512)
    assert moe.capacity_ladder(2048, share) == (512, 1024)
    for tokens in (32, 64, 1024):  # the dense form's zeros included
        assert moe.load_width(share, tokens) == 4 + len(moe.RUNG_NAMES)
    solar = dataclasses.replace(llama.SOLAR_OPEN2_250B, expert_rank="0/8").moe
    assert moe.capacity_ladder(1024, solar) == (256, 512)


LADDER_1024 = (256, 512)


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


@pytest.mark.parametrize("biased", [False, True])
def test_the_engine_counts_expert_calls_by_rung(
        monkeypatch, served, biased):
    """A held share's prefill programs tally on the device which rung each
    expert layer's products ran on; the engine fetches a prompt's tallies
    in the one wait it already makes for the prompt's first token (no
    slice is waited for), and stats() and the exported counter show the
    sums: a bounded rung under the seeded router, the grouped product over
    every row under a router biased onto the held range. Slices of 64
    tokens or fewer run dense and count on no rung."""
    from oim_tpu.common import metrics as M
    from oim_tpu.serve.engine import _target_programs

    # 128-token slices, top-4 of 16: an expert expects 32 rows
    monkeypatch.setattr(moe, "ROW_TILE", 8)
    monkeypatch.setattr(moe, "MIN_CAPACITY", 8)
    monkeypatch.setattr(moe, "CAPACITY_MULTIPLE", 1.5)
    _target_programs.cache_clear()  # traced under this ladder, and dropped

    model, cfg, params = served
    cfg = dataclasses.replace(cfg, max_seq=512)
    layers = cfg.n_expert_layers
    assert cfg.moe.held == (0, 4) and layers == 4
    if biased:
        experts = params["expert_layers"]
        params = {**params, "expert_layers": {**experts, "moe": {
            **experts["moe"],
            "bias": experts["moe"]["bias"].at[:, :4].add(10.0)}}}
    engine = ServeEngine(params, cfg, max_batch=2, max_seq=512,
                         prefix_cache_bytes=0, kv_page_tokens=PAGE,
                         prefill_chunk=128)
    fetches = []
    fetch = engine._jax.device_get
    engine._jax = type("jax", (), {"__getattr__": lambda _, k: getattr(jax, k)})()
    engine._jax.device_get = lambda x: (fetches.append(x), fetch(x))[1]
    def exported():
        return {name: M.SERVE_EXPERT_CALLS.labels(rung=name).value
                for name in moe.RUNG_NAMES}
    at_start = exported()
    try:
        rng = np.random.default_rng(4)
        assert engine.stats()["expert_calls_first_rung"] == 0
        # 300 tokens: slices of 128, 128 and 44 (dense); 100: one of 128
        for n, slices in ((300, 2), (100, 1)):
            before = engine.stats()
            engine.submit(rng.integers(0, 512, n).tolist(), max_new=3,
                          temperature=0.0, eos=-1).result(timeout=300)
            after = engine.stats()
            moved = {name: after[f"expert_calls_{name}_rung"]
                     - before[f"expert_calls_{name}_rung"]
                     for name in moe.RUNG_NAMES}
            assert sum(moved.values()) == layers * slices
            assert moved["whole"] == (layers * slices if biased else 0)
        # one fetch a prompt carried its slices' tallies with the token
        # (and its RNG carry: (token, carry, tallies))
        tallies = [len(x[-1]) for x in fetches
                   if isinstance(x, tuple) and isinstance(x[-1], list)]
        assert tallies == [3, 1]
        final = engine.stats()
        assert {name: exported()[name] - at_start[name]
                for name in moe.RUNG_NAMES} == {
            name: final[f"expert_calls_{name}_rung"]
            for name in moe.RUNG_NAMES}
    finally:
        engine.stop()
        _target_programs.cache_clear()


def test_a_model_that_holds_every_expert_counts_its_prefills_rungs():
    """The tiny latent model (top-4 of 16, every expert held): a 32-token
    bucket sends an expert 8 rows and compiles a ladder, so its prefill
    tallies its two expert layers' calls; an 8-token bucket (two rows an
    expert) runs the grouped product alone and returns no tally; decode
    rounds count on no rung."""
    cfg = llama.tiny_latent()
    layers = cfg.n_expert_layers
    assert moe.capacity_ladder(32, cfg.moe) == (32,) and layers == 2
    engine = ServeEngine(llama.init(jax.random.PRNGKey(0), cfg), cfg,
                         max_batch=2, max_seq=64)
    try:
        assert engine._bucket(3) == 8 and engine._bucket(20) == 32
        assert moe.capacity_ladder(8, cfg.moe) == ()
        stats = engine.stats()
        assert {stats[f"expert_calls_{name}_rung"]
                for name in moe.RUNG_NAMES} == {0}
        for n, calls in ((3, 0), (20, layers)):
            engine.submit(list(range(1, n + 1)), max_new=4, temperature=0.0,
                          eos=-1).result(timeout=300)
            stats = engine.stats()
            assert "expert_rows_dropless" in stats
            # a capacity of a token's worth always holds: the first rung
            assert [stats[f"expert_calls_{name}_rung"]
                    for name in moe.RUNG_NAMES] == [calls, 0, 0]
    finally:
        engine.stop()


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
