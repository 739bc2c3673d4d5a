"""Compressed convolutional attention over experts behind a router with
memory (the zaya family, PR 45), at test scale on the CPU: ``ops/cca.py``'s
``step`` against its ``scan`` against the plain reference's sums over two
positions; the router's carry, bias and weight; residual scaling; the tied
table; the whole tiny model prefilled in slices and decoded through pages and
tails against the reference's full forward; the engine's accounting of the
tail; what the configuration refuses."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_zaya as weights
from benchmarks.reference import zaya_like as ref
from benchmarks.runners import serve_cca
from oim_tpu.models import generate as gen
from oim_tpu.models import llama, moe
from oim_tpu.ops import cca
from oim_tpu.ops.rope import apply_rope, rope_frequencies

SEED = 45
MODEL = {
    "family": "zaya_like", "vocab": 512, "dim": 64, "n_heads": 8,
    "n_kv_heads": 2, "head_dim": 16, "rope_dim": 8,
    "rope_theta": 5e6, "moe_dim": 32, "n_experts": 8, "experts_held": 8,
    "expert_first": 0, "moe_top_k": 1, "router_dim": 16,
    "rms_norm_eps": 1e-5, "dtype": "float32", "n_layers": 3, "max_seq": 2048,
}
DIMS = cca.Dims(heads=8, kv_heads=2, head_dim=16)
ROPE_DIM = 8
PAGE = 8


@pytest.fixture(scope="module")
def cfg():
    return serve_cca.program_config(MODEL)


@pytest.fixture(scope="module")
def params():
    return weights.make_on_device(SEED, MODEL)


@pytest.fixture(scope="module")
def layer(params):
    return jax.tree.map(lambda a: a[1], params["cca_layers"])


def _hidden(t, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, t, MODEL["dim"]))


def _tables(t):
    return rope_frequencies(ROPE_DIM, t, MODEL["rope_theta"])


def _zeros():
    return jnp.zeros((1, DIMS.tail), jnp.float32)


def _whole(layer, h):
    t = h.shape[1]
    cos, sin = _tables(t)
    return cca.scan(layer, h, _zeros(), t, DIMS, cos, sin,
                    jnp.arange(t)[None])


# -- the mixing: step, scan, the reference -------------------------------------

def test_scan_is_the_references_sums(layer):
    h = _hidden(37)
    q, k, v, _ = _whole(layer, h)
    want = ref.cca_qkv(h[0], layer, MODEL)
    for got, wanted in zip((q, k, v), want):
        np.testing.assert_allclose(got[0], wanted, atol=2e-5)


@pytest.mark.parametrize("pad", [0, 3])
@pytest.mark.parametrize("boundary", [1, 2, 5, 1024])
def test_a_slice_resumed_from_the_tail_is_the_whole(layer, boundary, pad):
    """Positions [0, b) then [b, T) from the tail the first slice left, each
    slice padded with rows that move nothing, against one scan of all T."""
    t = boundary + 6
    h = _hidden(t, seed=boundary)
    cos, sin = _tables(t + pad)
    want = _whole(layer, h)

    def run(lo, hi, tail):
        n = hi - lo
        padded = jnp.concatenate(
            [h[:, lo:hi], 7.0 * jnp.ones((1, pad, MODEL["dim"]))], axis=1)
        q, k, v, tail = cca.scan(
            layer, padded, tail, n, DIMS, cos, sin,
            (lo + jnp.arange(n + pad))[None])
        return (q[:, :n], k[:, :n], v[:, :n]), tail

    first, tail = run(0, boundary, _zeros())
    rest, tail = run(boundary, t, tail)
    for a, b, w in zip(first, rest, want[:3]):
        np.testing.assert_allclose(
            jnp.concatenate([a, b], axis=1), w, atol=1e-5)
    np.testing.assert_allclose(tail, want[3], atol=1e-5)


def test_step_from_the_tail_is_the_scan(layer):
    t = 9
    h = _hidden(t, seed=3)
    cos, sin = _tables(t)
    want = _whole(layer, h)
    tail = _zeros()
    for i in range(t):
        q, k, v, tail = cca.step(layer, h[:, i], tail, DIMS, cos, sin,
                                 jnp.array([i]))
        for got, w in zip((q, k, v), want[:3]):
            np.testing.assert_allclose(got[:, 0], w[:, i], atol=1e-5)
    np.testing.assert_allclose(tail, want[3], atol=1e-5)


def test_position_zero_reads_zeros_before_it(layer):
    """``h_{-1}`` = ``p_{-1}`` = ``u_{-1}`` = 0: at position 0 the shifted
    values are zero and the first convolution is its second tap alone."""
    h = _hidden(4, seed=5)
    _, _, v, tail = _whole(layer, h[:, :1])
    assert float(jnp.abs(v[0, 0, 1]).max()) == 0.0
    assert float(jnp.abs(v[0, 0, 0]).max()) > 0.0
    p, u, _ = cca.split_tail(tail, DIMS)
    np.testing.assert_allclose(
        u, layer["conv0_w"][1] * p + layer["conv0_b"], atol=1e-6)


def test_which_head_holds_the_shifted_values(layer):
    h = _hidden(6, seed=6)
    _, _, v, tail = _whole(layer, h)
    s = h[0] @ layer["w_v"]
    np.testing.assert_allclose(v[0, :, 0], s[:, :16], atol=1e-5)
    np.testing.assert_allclose(v[0, 1:, 1], s[:-1, 16:], atol=1e-5)
    np.testing.assert_allclose(cca.split_tail(tail, DIMS)[2][0], s[-1, 16:],
                               atol=1e-5)


def test_the_qk_mean_is_over_four_query_heads_a_key_head(layer):
    """With the second convolution silenced q and k are the mean alone
    (before the normalisation, which keeps a head's direction)."""
    quiet = {**layer, "conv1_w": jnp.zeros_like(layer["conv1_w"]),
             "conv1_b": jnp.zeros_like(layer["conv1_b"]),
             "tau": jnp.ones_like(layer["tau"])}
    h = _hidden(1, seed=7)
    cos, sin = _tables(1)
    q, k, _, _ = cca.scan(quiet, h, _zeros(), 1, DIMS, cos, sin,
                          jnp.zeros((1, 1), jnp.int32))  # position 0: no turn
    p = (h[0, 0] @ layer["w_qk"]).reshape(10, 16)
    q0, k0 = p[:8], p[8:]
    for i in range(8):
        want = 0.5 * (q0[i] + k0[i // 4])
        want = want * 4.0 / jnp.sqrt(jnp.sum(want * want) + 1e-5)
        np.testing.assert_allclose(q[0, 0, i], want, atol=1e-5)
    for j in range(2):
        want = 0.5 * (jnp.mean(q0[4 * j:4 * j + 4], axis=0) + k0[j])
        want = want * 4.0 / jnp.sqrt(jnp.sum(want * want) + 1e-5)
        np.testing.assert_allclose(k[0, 0, j], want, atol=1e-5)


def test_tau_scales_a_key_heads_keys_and_nothing_else(layer):
    h = _hidden(5, seed=8)
    base = _whole(layer, h)
    twice = _whole({**layer, "tau": layer["tau"] * jnp.array([2.0, 1.0])}, h)
    np.testing.assert_allclose(twice[1][:, :, 0], 2 * base[1][:, :, 0],
                               rtol=1e-5)
    np.testing.assert_allclose(twice[1][:, :, 1], base[1][:, :, 1])
    np.testing.assert_allclose(twice[0], base[0])
    # a head's norm is tau x d^1/2 whatever went in
    norms = jnp.sqrt(jnp.sum(base[1] ** 2, axis=-1))
    np.testing.assert_allclose(norms, jnp.broadcast_to(
        4.0 * layer["tau"], norms.shape), rtol=1e-4)


@pytest.mark.parametrize("width,head", [(64, 128), (8, 16), (16, 16)])
def test_the_half_rotary_is_ropes_at_that_width(width, head):
    """Tables of width 64 rotate the first 64 of a head's 128 in split-half
    pairs (i, i + 32) and pass the last 64."""
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 5, 3, head))
    cos, sin = rope_frequencies(width, 32, 5e6)
    pos = jnp.array([[3, 4, 5, 6, 7], [0, 9, 2, 30, 1]])
    got = apply_rope(x, cos, sin, pos)
    np.testing.assert_array_equal(got[..., width:], x[..., width:])
    half = width // 2
    inv = 5e6 ** (-2.0 * np.arange(half) / width)
    ang = np.asarray(pos)[..., None, None] * inv
    x1, x2 = np.asarray(x[..., :half]), np.asarray(x[..., half:width])
    np.testing.assert_allclose(got[..., :half],
                               x1 * np.cos(ang) - x2 * np.sin(ang), atol=1e-5)
    np.testing.assert_allclose(got[..., half:width],
                               x2 * np.cos(ang) + x1 * np.sin(ang), atol=1e-5)


def test_a_tail_is_2688_values_at_the_published_widths():
    d = llama.ZAYA1_8B.cca
    assert (d.latent, d.shifted, d.tail) == (1280, 128, 2688)
    assert d.slot_leaves(jnp.bfloat16) == {
        "cca_tail": ((2688,), jnp.float32)}
    assert cca.n_params(2048, d) == 5_575_682  # 5.57 M, convs 0.33 M
    assert cca.NAME == "cca" and set(cca.SCOPES) == {"blk_qkv/cca_mix"}


# -- the router: a network with memory ----------------------------------------

def _expert_layer(params, l):
    return jax.tree.map(lambda a: a[l], params["expert_layers"]["moe"])


def test_the_routers_state_crosses_three_layers_as_the_references(params, cfg):
    h = [jax.random.normal(jax.random.PRNGKey(20 + l), (11, 64))
         for l in range(3)]
    state, want_state = None, jnp.zeros((11, 16))
    for l in range(3):
        m = _expert_layer(params, l)
        state = moe.router_state(m, h[l], state, cfg.moe)
        experts, w = moe.route_from_state(m, state, cfg.moe)
        chosen, weight, want_state = ref.route(
            h[l], m["router_mlp"], m["bias"], want_state, MODEL)
        np.testing.assert_allclose(state, want_state, atol=1e-5)
        np.testing.assert_array_equal(experts, chosen)
        np.testing.assert_allclose(w, weight, atol=1e-6)
        assert state.dtype == w.dtype == jnp.float32
    # the carry matters: layer 2's state is not what it makes of h alone
    alone = moe.router_state(_expert_layer(params, 2), h[2], None, cfg.moe)
    assert float(jnp.abs(alone - state).max()) > 0.1


def test_beta_flips_a_choice_and_not_the_weight(params, cfg):
    m = _expert_layer(params, 0)
    h = jax.random.normal(jax.random.PRNGKey(30), (6, 64))
    state = moe.router_state(m, h, None, cfg.moe)
    experts, w = moe.route_from_state(m, state, cfg.moe)
    runner_up = int(jnp.argsort(jax.nn.softmax(moe._mlp_logits(
        m["router_mlp"], state, 1e-5))[0])[-2])
    flipped = {**m, "bias": m["bias"].at[runner_up].add(1.0)}
    experts2, w2 = moe.route_from_state(flipped, state, cfg.moe)
    assert int(experts2[0, 0]) == runner_up != int(experts[0, 0])
    probs = jax.nn.softmax(moe._mlp_logits(m["router_mlp"], state, 1e-5))
    np.testing.assert_allclose(w2[0, 0], probs[0, runner_up], rtol=1e-6)


def test_the_top1_weight_is_not_renormalised(params, cfg):
    m = _expert_layer(params, 1)
    h = jax.random.normal(jax.random.PRNGKey(31), (50, 64))
    _, w = moe.route_from_state(
        m, moe.router_state(m, h, None, cfg.moe), cfg.moe)
    assert w.shape == (50, 1)
    assert float(w.max()) < 1.0 and float(w.min()) > 1.0 / 8 - 0.05


@pytest.mark.parametrize("tokens", [5, 70])
def test_an_expert_block_is_the_references(params, cfg, tokens):
    """A dropless call under the router network (few tokens: the grouped
    product alone; 70: a capacity with the rest behind it) against the
    reference's expert at a time."""
    w = jax.tree.map(lambda a: a[0], params["expert_layers"])
    x = jax.random.normal(jax.random.PRNGKey(32), (1, tokens, 64))
    prev = jax.random.normal(jax.random.PRNGKey(33), (1, tokens, 16))
    got, _, state = llama._ffn_mixer(x, w, cfg, router=prev)
    want, want_state = ref._expert_ffn(
        x[0], w, prev[0], MODEL, ref._programs(ref._hashable(MODEL), False))
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    np.testing.assert_allclose(state[0], want_state, atol=1e-5)


@pytest.mark.parametrize("rows", [1, 4, 7, 8, 9, 15])
def test_the_grouped_product_takes_any_row_count(params, rows):
    """Rows are padded to whole tiles of 8 for the product and cut off again
    (on the chip a float32 product at the highest precision was wrong at
    every other count): the sums are a product a row whatever the count."""
    m = _expert_layer(params, 0)
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, 64))
    chosen = np.sort(np.random.default_rng(rows).integers(0, 8, rows))
    sizes = jnp.asarray(np.bincount(chosen, minlength=8), jnp.int32)
    got = moe.grouped_ffn(m, x, sizes)
    assert got.shape == (rows, 64)
    for i, e in enumerate(chosen):
        want = (jax.nn.silu(x[i] @ m["w_gate"][e]) * (x[i] @ m["w_up"][e])
                ) @ m["w_down"][e]
        np.testing.assert_allclose(got[i], want, atol=1e-5)


def test_two_ranks_shares_add_up_to_the_uncut_layer(params, cfg):
    w = jax.tree.map(lambda a: a[2], params["expert_layers"])
    h = jax.random.normal(jax.random.PRNGKey(34), (1, 40, 64))
    state = moe.router_state(w["moe"], h, None, cfg.moe)
    whole, _ = moe.apply(w["moe"], h, cfg.moe, state=state)
    parts = []
    for rank in range(2):
        held = dataclasses.replace(cfg, expert_rank=f"{rank}/2")
        assert held.moe.held == (4 * rank, 4)
        share = {**w["moe"], **{k: w["moe"][k][4 * rank:4 * rank + 4]
                                for k in moe.EXPERT_LEAVES}}
        parts.append(moe.apply(share, h, held.moe, state=state)[0])
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=1e-5)
    assert float(jnp.abs(parts[0]).max()) > 0 < float(jnp.abs(parts[1]).max())


# -- residual scaling, the tied table ------------------------------------------

def test_residual_scaling_is_a_scale_and_a_bias_on_both(cfg):
    x = jax.random.normal(jax.random.PRNGKey(40), (2, 3, 64))
    out = jax.random.normal(jax.random.PRNGKey(41), (2, 3, 64))
    leaves = {k: jax.random.normal(jax.random.PRNGKey(42 + i), (64,))
              for i, k in enumerate(llama.RESIDUAL_LEAVES)}
    got = llama._residual(x, out, leaves, cfg)
    np.testing.assert_allclose(
        got, (leaves["res_a"] * x + leaves["res_b"])
        + (leaves["res_c"] * out + leaves["res_e"]), atol=1e-6)
    plain = {"res_a": jnp.ones(64), "res_b": jnp.zeros(64),
             "res_c": jnp.ones(64), "res_e": jnp.zeros(64)}
    np.testing.assert_allclose(llama._residual(x, out, plain, cfg), x + out,
                               atol=1e-6)
    np.testing.assert_allclose(
        ref._join(x, out, leaves), got, atol=1e-6)


def test_the_table_is_one_leaf_and_the_head_is_its_transpose(cfg, params):
    fresh = llama.init(jax.random.PRNGKey(0), cfg)
    assert "lm_head" not in fresh and "lm_head" not in params
    assert llama.head(params).shape == (64, 512)
    tokens = jnp.arange(12)[None]
    x, _ = llama.hidden_states(params, tokens, cfg)
    np.testing.assert_allclose(
        llama.apply(params, tokens, cfg), x @ params["embed"].T, atol=1e-6)
    untied = {"lm_head": jnp.ones((3, 5)), "embed": jnp.zeros((5, 3))}
    assert llama.head(untied) is untied["lm_head"]
    assert llama.num_params(cfg) == sum(
        x.size for x in jax.tree.leaves(fresh))
    weights.check_against_program(MODEL, jax.eval_shape(lambda: fresh))


def test_the_published_model_counts_what_was_published():
    c = llama.ZAYA1_8B
    assert c.pattern == "CE" * 40 and c.rope_dim == 64
    assert 8.83e9 < llama.num_params(c) < 8.85e9
    active = llama.num_active_params(c) - c.vocab * c.dim  # without the table
    assert 0.74e9 < active < 0.77e9
    assert c.n_cache_layers == 40 and c.cache_leaves == {
        "k": (2, 128), "v": (2, 128)}
    assert gen.page_bytes(dataclasses.replace(c, n_layers=1), 1) == 1024
    assert gen.state_bytes_by_kind(c, 1) == {"cca": 40 * 2688 * 4}


# -- the whole tiny model through pages and tails -----------------------------

def _serve(params, cfg, tokens, slices, steps, round_tail=None, dirty=False):
    """Prefill ``tokens`` in ``slices`` through slot 1's pages and tail, then
    ``steps`` paged decode steps: the logits of the last prompt row and of
    every step."""
    slots, blocks = 3, 16
    pool = {**gen.init_page_pool(cfg, slots * blocks + 1, PAGE),
            **gen.init_state_pool(cfg, slots)}
    if dirty:  # a slot that served a request before: its tail is not zeros
        pool["cca_tail"] = pool["cca_tail"] + 3.0
    tables = np.zeros((slots, blocks), np.int32)
    tables[1] = 1 + blocks + np.arange(blocks)

    def rounded(pool):
        if round_tail is None:
            return pool
        return {**pool, "cca_tail": pool["cca_tail"].astype(round_tail)
                .astype(jnp.float32)}

    out, at = [], 0
    with jax.default_matmul_precision("highest"):
        for n in slices:
            bucket = 1 << (n - 1).bit_length()
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = tokens[at:at + n]
            logits, pool = gen.prefill_into_pages(
                params, jnp.asarray(padded), n, pool, jnp.asarray(tables[1]),
                at, cfg, PAGE, slot=1)
            pool = rounded(pool)
            at += n
        out.append(logits)
        for i in range(steps):
            fed = np.zeros((slots,), np.int32)
            fed[1] = tokens[at + i]
            pos = np.zeros((slots,), np.int32)
            pos[1] = at + i
            logits, pool = gen.decode_step(
                params, jnp.asarray(fed), pool, jnp.asarray(tables),
                jnp.asarray(pos), cfg, PAGE)
            pool = rounded(pool)
            out.append(logits[1])
    return np.asarray(jnp.stack(out)), pool


@pytest.fixture(scope="module")
def sequence():
    return np.random.default_rng(SEED).integers(0, 512, 60)


@pytest.fixture(scope="module")
def reference_logits(sequence):
    return np.asarray(ref.logits_many(
        SEED, MODEL, [sequence.tolist()], [np.arange(60)])[0])


@pytest.mark.parametrize("slices", [(50,), (32, 18), (1, 2, 47), (16, 16, 18)])
def test_prefill_in_slices_then_decode_is_the_references_forward(
        params, cfg, sequence, reference_logits, slices):
    """Float32 on both sides at the highest precision: what is left is the
    order of the sums (a paged online softmax against a full one), 1e-6 of
    logits of rms 0.16; 2e-5 is twenty times that."""
    got, _ = _serve(params, cfg, sequence, slices, 10)
    np.testing.assert_allclose(got, reference_logits[49:], atol=2e-5)


def test_a_readmitted_slot_starts_from_zeros(params, cfg, sequence,
                                             reference_logits):
    got, _ = _serve(params, cfg, sequence, (32, 18), 4, dirty=True)
    np.testing.assert_allclose(got, reference_logits[49:54], atol=2e-5)


def test_a_bfloat16_tail_is_told_apart(params, cfg, sequence, reference_logits):
    """The tail is float32 by the configuration's ``assumed``: rounded to
    bfloat16 after every call the same comparison reads a hundred times the
    sound one, and the reference told to round what the next position reads
    agrees with THAT program instead."""
    sound, _ = _serve(params, cfg, sequence, (32, 18), 10)
    rough, _ = _serve(params, cfg, sequence, (32, 18), 10, jnp.bfloat16)
    want = reference_logits[49:]
    assert np.abs(sound - want).max() < 2e-5
    assert np.abs(rough - want).max() > 2e-4
    rounded = np.asarray(ref.logits_many(
        SEED, MODEL, [sequence.tolist()], [np.arange(49, 60)],
        tail_dtype="bfloat16")[0])
    assert np.abs(rounded - want).max() > 2e-4


def test_an_idle_rows_tail_stays(params, cfg, sequence):
    _, pool = _serve(params, cfg, sequence, (20,), 3)
    tails = np.asarray(pool["cca_tail"])
    assert np.abs(tails[:, 1]).max() > 0
    assert np.abs(tails[:, 0]).max() == 0 == np.abs(tails[:, 2]).max()


def test_the_full_forward_is_the_references(params, cfg, sequence,
                                            reference_logits):
    with jax.default_matmul_precision("highest"):
        got = llama.apply(params, jnp.asarray(sequence)[None], cfg)[0]
    np.testing.assert_allclose(got, reference_logits, atol=2e-5)


def test_the_layers_are_one_scanned_run_that_carries_the_router(cfg):
    assert llama.pattern_runs(cfg.pattern) == (("CE", 3),)
    assert llama.router_carry(cfg, 2, 5).shape == (2, 5, 16)
    assert llama.router_carry(llama.tiny_kda(), 2, 5) is None


# -- the engine ----------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(params, cfg):
    from oim_tpu.serve.engine import ServeEngine

    eng = ServeEngine(params, cfg, max_batch=2, max_seq=128,
                      prefix_cache_bytes=0, prefill_chunk=16,
                      kv_pool_tokens=512, name="cca-test")
    yield eng
    eng.stop(drain=False, timeout=30.0)


def test_the_engine_holds_a_pool_and_a_tail(engine, cfg):
    tail = 2 * 3 * DIMS.tail * 4  # 2 slots x 3 layers, float32
    assert engine.cache_kind == "gqa"
    assert set(engine._cache) == {"k", "v", "cca_tail"}
    assert engine._cache["cca_tail"].shape == (3, 2, DIMS.tail)
    assert engine.state_bytes_by_kind == {"cca": tail}
    stats = engine.pool_stats()
    assert stats["state_bytes"] == tail
    assert stats["state_bytes_by_kind"] == {"cca": tail}
    assert engine.stats()["state_bytes"] == tail


def test_the_engine_serves_the_references_tokens(engine, params, cfg):
    prompt = np.random.default_rng(1).integers(0, 512, 41).astype(np.int32)
    handle = engine.submit(prompt, max_new=6, temperature=0.0, seed=0, eos=-1)
    served = list(handle.tokens(timeout=120.0))
    assert len(served) == 6 and handle.finish_reason == "length"
    gaps = ref.served_gaps_many(SEED, MODEL, [(prompt.tolist(), served)])[0]
    assert gaps.max() < 1e-4
    assert engine.stats()["state_resets"] >= 1
    assert engine.stats()["experts_touched_sum"] > 0


@pytest.mark.parametrize("kwargs,what", [
    ({}, "a prefix store"),
    ({"prefix_cache_bytes": 0, "kv_host_bytes": 1 << 20}, "a host tier"),
    ({"prefix_cache_bytes": 0, "shard": 2}, "shard > 1"),
    ({"prefix_cache_bytes": 0, "role": "prefill"}, "role 'prefill'"),
    ({"prefix_cache_bytes": 0, "draft": True}, "speculative decoding"),
])
def test_what_the_engine_refuses_beside_a_tail(params, cfg, kwargs, what):
    from oim_tpu.serve.engine import ServeEngine

    kwargs = dict(kwargs)
    if kwargs.pop("draft", False):
        kwargs.update(draft_params=params, draft_cfg=cfg)
    with pytest.raises(ValueError, match=what):
        ServeEngine(params, cfg, max_batch=2, max_seq=64, **kwargs)


def test_verify_step_refuses_a_tail(params, cfg):
    with pytest.raises(ValueError, match="recurrent state"):
        gen.verify_step(params, jnp.zeros((1, 2), jnp.int32), {},
                        jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32),
                        cfg, PAGE)


# -- what the configuration refuses --------------------------------------------

@pytest.mark.parametrize("change,message", [
    ({"kv_lora_rank": 16, "q_lora_rank": 24, "qk_nope_head_dim": 16,
      "qk_rope_head_dim": 8, "v_head_dim": 16}, "beside latent attention"),
    ({"cca_time1": 4}, "both convolutions have 2 taps"),
    ({"cca_time0": 0, "cca_time1": 0,
      "hybrid_override_pattern": "CEC"}, "needs cca_time0"),
    ({"n_heads": 3, "n_kv_heads": 3}, "even n_kv_heads"),
    ({"n_heads": 6, "n_kv_heads": 4}, "dividing n_heads"),
    ({"router_hidden_size": 0}, "go together"),
    ({"scoring_func": "sigmoid"}, "go together"),
    ({"moe_dispatch": "gather"}, "routes dropless"),
    ({"partial_rotary_factor": 0.3}, "rotates whole pairs"),
    ({"partial_rotary_factor": 1.5}, "rotates whole pairs"),
    ({"hybrid_override_pattern": "C*E"}, "beside '\\*'"),
    ({"expert_rank": "3/3"}, "expert_rank"),
])
def test_what_the_configuration_refuses(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(cfg, **change)


def test_residual_scaling_needs_a_pattern():
    with pytest.raises(ValueError, match="residual_scaling"):
        dataclasses.replace(llama.tiny(), residual_scaling=True)
    with pytest.raises(ValueError, match="sharding rules"):
        llama.param_logical_axes(llama.tiny_cca())


def test_the_runner_refuses_what_the_family_does_not_implement():
    from benchmarks import common

    config = common.load_json(
        common.find(common.ROOT, "configs", "zaya1-8b"))
    assert serve_cca.model_dict(config)["rope_dim"] == 64
    for key, value in (("cca_time0", 4), ("tie_word_embeddings", False),
                       ("sliding_window", 4096), ("attention_bias", True)):
        with pytest.raises(SystemExit):
            serve_cca.model_dict({**config, key: value})
    windowed = ["hybrid_sliding"] + config["layer_types"][1:]
    with pytest.raises(SystemExit, match="window layers"):
        serve_cca.model_dict({**config, "layer_types": windowed})
