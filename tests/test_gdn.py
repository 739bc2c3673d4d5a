"""A hybrid of gated latent attention and GatedDeltaNet mixers (a gated delta
rule with one decay a head, fewer key heads than value heads) behind a
leading dense layer, a gated norm before and after every sublayer (PR 42),
small, on the CPU, in float32: the mixer's two forms against each other and
against the reference's sequential recurrence, YaRN and the softmax's
temperature, the gated norm, the cut SwiGLU, the gated latent block, the
state pool beside a latent page pool, the serving programs and
``ServeEngine`` against the benchmark's plain reference on seeded weights,
and the sixteen held shares of an expert layer against the uncut one.
Self-contained: no cluster, no port."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_gigachat35 as bench_weights
from benchmarks.reference import gigachat3_5_like as ref
from benchmarks.runners import serve_gdn
from oim_tpu.models import generate as gen
from oim_tpu.models import llama, moe
from oim_tpu.ops import gdn, kda
from oim_tpu.ops.norms import gated_rmsnorm, rmsnorm
from oim_tpu.ops.rope import rope_frequencies, yarn_ramp
from oim_tpu.serve.engine import ServeEngine

PAGE = 16
D = gdn.Dims(k_heads=2, v_heads=4, k_dim=8, v_dim=8, conv=4, gate_scale=2.0,
             gated_norm=True, chunk=4)
DIM = 32
EPS = 1e-6


def mixer_layer(seed=0):
    """A mixer whose heads' decays a position span the family's range: head
    0 near e^-8 (a chunk of 4 takes it under e^-32), head 3 near 1 - 3e-4;
    a head norm whose sigmoid does something, a beta over most of (0, 1)."""
    layer = jax.tree.map(lambda a: a[0], gdn.init(
        jax.random.PRNGKey(seed), DIM, D, jnp.float32, 1))
    layer["A_log"] = jnp.log(jnp.asarray([16.0, 4.0, 2.0, 1.0]))
    layer["dt_bias"] = jnp.asarray([-0.43, 0.0, -2.0, -8.1])
    layer["w_a"] = 0.3 * layer["w_a"]
    layer["w_b"] = 2.0 * layer["w_b"]
    layer["o_norm"] = 0.5 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), layer["o_norm"].shape)
    return layer


def some_state(batch, seed=2):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (batch, D.v_heads, D.k_dim, D.v_dim)),
            jax.random.normal(k2, (batch, D.conv - 1, D.conv_dim)))


def empty_state(batch):
    return jax.tree.map(jnp.zeros_like, some_state(batch))


@functools.partial(jax.jit, static_argnums=(4,))
def token_by_token(layer, x, s, c, d=D):
    def one(carry, x_t):
        o, s, c = gdn.step(layer, x_t, *carry, d, EPS)
        return (s, c), o

    (s, c), outs = jax.lax.scan(one, (s, c), jnp.moveaxis(x, 1, 0))
    return jnp.moveaxis(outs, 0, 1), s, c


def mixer_model():
    """The reference's description of ``mixer_layer``'s one block."""
    return {"gdn_k_heads": D.k_heads, "gdn_v_heads": D.v_heads,
            "gdn_k_dim": D.k_dim, "gdn_v_dim": D.v_dim, "gdn_conv": D.conv,
            "gdn_gate_scale": D.gate_scale, "rms_norm_eps": EPS}


# -- the mixer: scan = step = the sequential definition ------------------------

def test_the_one_token_update_is_the_references_recurrence():
    """From an empty state ``gdn.step`` token by token IS the reference's
    sequential scan (which knows no chunk, no carried window and no
    ``a S^T q + (q . k) u``), over decays from 1 - 3e-4 down to e^-8."""
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (21, DIM))
    w = {**layer, "norm": 0.3 * jnp.ones((DIM,)), "post_norm": jnp.zeros((DIM,))}
    h = gated_rmsnorm(x, w["norm"], EPS)
    want = ref.gdn_forward(x, w, mixer_model())
    got, _, _ = token_by_token(layer, h[None], *empty_state(1))
    np.testing.assert_allclose(
        x + gated_rmsnorm(got[0], w["post_norm"], EPS), want, atol=3e-6)
    g, beta, _ = gdn._gates(layer, h, D)
    assert g.shape == (21, 4) and beta.shape == (21, 4)  # one a HEAD
    assert float(g.min()) < -8 and float(g.max()) > -1e-3  # both ends
    assert 0 < float(beta.min()) < 0.2 and 0.8 < float(beta.max()) < 1


@pytest.mark.parametrize("length", [8, 16, 5, 21, 1])
@pytest.mark.parametrize("carried", [False, True])
def test_chunked_scan_against_the_one_token_update(length, carried):
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, length, DIM))
    s, c = some_state(2) if carried else empty_state(2)
    want, ws, wc = token_by_token(layer, x, s, c)
    got, gs, gc = gdn.scan(layer, x, s, c, length, D, EPS)
    np.testing.assert_allclose(got, want, atol=3e-6)
    np.testing.assert_allclose(gs, ws, atol=3e-6)
    np.testing.assert_array_equal(gc, wc)


@pytest.mark.parametrize("length", [64, 130, 200])
def test_the_programs_own_chunk_crosses_its_boundaries(length):
    """At ``Dims.chunk`` as the program runs it (64): one whole chunk, two
    and a rest, three and a rest."""
    d = dataclasses.replace(D, chunk=gdn.Dims.chunk)
    assert d.chunk == 64
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(13), (1, length, DIM))
    s, c = some_state(1)
    want, ws, wc = token_by_token(layer, x, s, c, d)
    got, gs, gc = gdn.scan(layer, x, s, c, length, d, EPS)
    # (a chunk's triangular solve sums 64 terms where the update adds one)
    np.testing.assert_allclose(got, want, atol=3e-5)
    np.testing.assert_allclose(gs, ws, atol=3e-5)
    np.testing.assert_allclose(gc, wc, atol=1e-6)  # a product of another shape


@pytest.mark.parametrize("real,padded", [(5, 8), (13, 32), (1, 8), (16, 16)])
def test_padding_leaves_state_and_window_at_the_last_real_token(real, padded):
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(4), (1, padded, DIM))
    s, c = some_state(1)
    want, ws, wc = token_by_token(layer, x[:, :real], s, c)
    got, gs, gc = gdn.scan(layer, x, s, c, real, D, EPS)
    np.testing.assert_allclose(got[:, :real], want, atol=3e-6)
    np.testing.assert_allclose(gs, ws, atol=3e-6)
    np.testing.assert_allclose(gc, wc, atol=1e-6)  # a product of another shape


def test_a_slice_resumed_from_a_slots_state_is_the_scan_whole():
    layer = mixer_layer()
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 27, DIM))
    s, c = some_state(1)
    want, ws, wc = gdn.scan(layer, x, s, c, 27, D, EPS)
    a, s1, c1 = gdn.scan(layer, x[:, :16], s, c, 16, D, EPS)
    tail = jnp.pad(x[:, 16:], ((0, 0), (0, 5), (0, 0)))
    b, s2, c2 = gdn.scan(layer, tail, s1, c1, 11, D, EPS)
    np.testing.assert_allclose(
        jnp.concatenate([a, b[:, :11]], 1), want, atol=3e-6)
    np.testing.assert_allclose(s2, ws, atol=3e-6)
    np.testing.assert_array_equal(c2, wc)


def test_a_decay_that_passes_float32_inside_a_chunk_is_exact():
    """One chunk of 64 positions, heads that lose e^-96 a position: the
    cumulative log-decay reaches -6000, so ``exp(G_t) * exp(-G_j)`` is 0 x
    inf. The scan takes differences first and stays the recurrence."""
    d64 = dataclasses.replace(D, chunk=64)
    layer = {**mixer_layer(), "dt_bias": jnp.full((4,), 6.0),
             "A_log": jnp.full((4,), jnp.log(16.0))}
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 64, DIM))
    s, c = some_state(1)
    g, _, _ = gdn._gates(layer, x, D)
    cum = jnp.cumsum(g, axis=1)
    assert float(cum.min()) < -3000
    with np.errstate(over="ignore", invalid="ignore"):
        naive = np.exp(np.asarray(cum)[0, -1]) * np.exp(-np.asarray(cum)[0, 3])
    assert not np.all(np.isfinite(naive))
    want, ws, _ = token_by_token(layer, x, s, c)
    got, gs, _ = gdn.scan(layer, x, s, c, 64, d64, EPS)
    assert np.all(np.isfinite(got)) and np.all(np.isfinite(gs))
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(gs, ws, atol=2e-5)


@pytest.mark.parametrize("form", ["step", "scan"])
def test_value_head_j_reads_key_head_j_over_two(form):
    """Key head 1's columns of W_qkv (queries and keys) moved: value heads
    2 and 3 change, value heads 0 and 1 and their states do not."""
    layer = {**mixer_layer(), "w_out": jnp.eye(D.value_dim, D.value_dim)}
    cols = jnp.concatenate([
        D.k_dim + jnp.arange(D.k_dim), D.key_dim + D.k_dim + jnp.arange(D.k_dim)])
    moved = {**layer, "w_qkv": layer["w_qkv"].at[:, cols].multiply(-1.7)}
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 9, DIM))

    def run(w):
        if form == "scan":
            out, s, _ = gdn.scan(w, x, *empty_state(1), 9, D, EPS)
        else:
            out, s, _ = token_by_token(w, x, *empty_state(1))
        return out.reshape(9, D.v_heads, D.v_dim), s[0]

    (a, sa), (b, sb) = run(layer), run(moved)
    np.testing.assert_array_equal(a[:, :2], b[:, :2])
    np.testing.assert_array_equal(sa[:2], sb[:2])
    assert float(jnp.abs(a[:, 2:] - b[:, 2:]).min(axis=(0, 2)).max()) > 0
    assert float(jnp.abs(sa[2:] - sb[2:]).max()) > 1e-3


def test_a_head_scalar_decay_pays_no_per_channel_chunk_product():
    """The scan's chunk products are [C, C] a head with the decay an
    element-wise factor: no array of the traced program has the per-channel
    form's [.., C, C, d] (``ops/kda.py`` builds it and sums it)."""
    d = dataclasses.replace(D, chunk=16)
    layer = mixer_layer()
    x = jnp.zeros((1, 32, DIM))
    C = d.chunk

    def shapes(fn, *args):
        jaxpr = jax.make_jaxpr(fn)(*args)
        found = set()

        def walk(j):
            for eqn in j.eqns:
                found.update(tuple(v.aval.shape) for v in eqn.outvars)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        return found

    ours = shapes(lambda x: gdn.scan(layer, x, *empty_state(1), 32, d, EPS), x)
    assert (1, 2, D.v_heads, C, C) in ours           # the decay, a head
    assert not [s for s in ours if len(s) >= 6 and s[-3:-1] == (C, C)]
    kd = kda.Dims(heads=4, head_dim=8, conv=4, chunk=C)
    kl = jax.tree.map(lambda a: a[0], kda.init(
        jax.random.PRNGKey(0), DIM, kd, jnp.float32, 1))
    theirs = shapes(lambda x: kda.scan(
        kl, x, jnp.zeros((1, 4, 8, 8)), jnp.zeros((1, 3, kd.conv_dim)), 32,
        kd, EPS), x)
    assert (1, 2, 4, C, C, 8) in theirs


# -- YaRN, the temperature, the norm, the cut SwiGLU ---------------------------

def published_model():
    from benchmarks import common
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return serve_gdn.model_dict(common.load_json(os.path.join(
        repo, "benchmarks", "configs", "gigachat35-432b-a28b.json")), "serve")


def tiny_model(experts_held=4, n_experts=16, pattern="GD*EGEGEGE"):
    return {
        "family": "gigachat3_5_like", "vocab": 512, "dim": 64, "n_heads": 4,
        "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "attn_gate": True,
        "rope_theta": 1e5, "yarn_factor": 8.0, "yarn_original_max": 64,
        "yarn_beta_fast": 32.0, "yarn_beta_slow": 1.0, "mla_scaling": True,
        "pattern": pattern, "gdn_k_heads": 2, "gdn_v_heads": 4,
        "gdn_k_dim": 16, "gdn_v_dim": 16, "gdn_conv": 4,
        "gdn_gate_scale": 2.0, "time_step_min": 1e-3, "time_step_max": 0.1,
        "mlp_dim": 96, "moe_dim": 32, "shared_dim": 32,
        "n_experts": n_experts, "experts_held": experts_held,
        "expert_first": 0, "moe_top_k": 4, "routed_scale": 2.5,
        "swiglu_limit": 1.0, "rms_norm_eps": 1e-6, "dtype": "float32",
        "n_layers": len(pattern) // 2, "max_seq": 128}


@pytest.mark.parametrize("which", ["published", "tiny"])
def test_yarn_frequencies_and_the_temperature_are_the_references(which):
    model = published_model() if which == "published" else tiny_model()
    cfg = serve_gdn.program_config(model)
    dim, theta = cfg.rope_dim, cfg.rope_theta
    want = ref.yarn_inv_freq(model)
    cos, sin = rope_frequencies(dim, 50, theta, cfg.rope_yarn)
    at = jnp.arange(50, dtype=jnp.float32)[:, None]
    np.testing.assert_allclose(cos, jnp.cos(at * want), atol=1e-6)
    np.testing.assert_allclose(sin, jnp.sin(at * want), atol=1e-6)
    plain = 1.0 / theta ** (jnp.arange(0, dim, 2) / dim)
    ramp = yarn_ramp(dim, theta, *cfg.rope_yarn[1:])
    np.testing.assert_allclose(want, ramp * plain / 8 + (1 - ramp) * plain,
                               rtol=1e-6)
    if which == "published":  # low 14, high 24 of the 32 pairs
        assert float(ramp[14]) == 0.0 and float(ramp[24]) == 1.0
        assert 0 < float(ramp[15]) < float(ramp[23]) < 1
        np.testing.assert_allclose(want[:15], plain[:15], rtol=1e-6)
        np.testing.assert_allclose(want[24:], plain[24:] / 8, rtol=1e-6)
    m = 0.1 * np.log(8.0) + 1.0
    assert cfg.latent.mscale == pytest.approx(m)
    assert cfg.latent.scale == pytest.approx(ref.softmax_scale(model)) \
        == pytest.approx((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
                         * m * m)
    # without the key the scale is the head size's alone, as before
    plain_cfg = dataclasses.replace(cfg, use_mla_scaling_factor=False)
    assert plain_cfg.latent.scale == (cfg.qk_nope_head_dim
                                      + cfg.qk_rope_head_dim) ** -0.5


def test_no_yarn_leaves_the_tables_as_they_were():
    a = rope_frequencies(16, 40, 1e4)
    b = rope_frequencies(16, 40, 1e4, ())
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    c = rope_frequencies(16, 40, 1e4, (8.0, 64, 32.0, 1.0))
    assert float(jnp.abs(a[1] - c[1]).max()) > 0.1


def test_the_gated_norm_is_n():
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (5, 32))
    w = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    want = ref._norm(x, w, EPS)
    np.testing.assert_allclose(gated_rmsnorm(x, w, EPS), want, rtol=1e-6)
    np.testing.assert_allclose(
        want, x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
        * 2 / (1 + jnp.exp(-w)), rtol=1e-5)
    # a fresh model's weight multiplies by 1; the sigmoid keeps it in (0, 2)
    np.testing.assert_allclose(gated_rmsnorm(x, jnp.zeros((32,)), EPS),
                               rmsnorm(x, jnp.ones((32,)), EPS), rtol=1e-6)
    big = gated_rmsnorm(x, jnp.full((32,), 30.0), EPS)
    np.testing.assert_allclose(big, 2 * rmsnorm(x, jnp.ones((32,)), EPS),
                               rtol=1e-6)
    bf = gated_rmsnorm(x.astype(jnp.bfloat16), w, EPS)
    assert bf.dtype == jnp.bfloat16


def test_the_cut_fires_and_zero_cuts_nothing():
    gate = jnp.asarray([-30.0, -2.0, 0.5, 9.0, 11.0, 40.0])
    up = jnp.asarray([-50.0, -10.5, 3.0, 10.5, -0.1, 12.0])
    got = moe.swiglu(gate, up, 10.0)
    want = jax.nn.silu(jnp.minimum(gate, 10.0)) * jnp.clip(up, -10.0, 10.0)
    np.testing.assert_array_equal(got, want)
    # the gate is cut from above only: -30 stays -30
    assert float(got[0]) == float(jax.nn.silu(-30.0) * -10.0)
    np.testing.assert_array_equal(moe.swiglu(gate, up),
                                  jax.nn.silu(gate) * up)
    assert float(jnp.abs(got - moe.swiglu(gate, up)).max()) > 1


# -- the blocks against the reference's, on the benchmark's weights ------------

@pytest.fixture(scope="module")
def served():
    model = tiny_model()
    cfg = serve_gdn.program_config(model)
    params = bench_weights.make_on_device(11, model)
    bench_weights.check_against_program(model, jax.eval_shape(
        lambda k: llama.init(k, cfg), jax.random.PRNGKey(0)))
    return model, cfg, params


def block_weights(served, group, index=0):
    """(the reference's leaves as published, the program's as it holds
    them) of one block of ``served``'s tree."""
    model, _, params = served
    return (bench_weights.layer_slice(bench_weights.root_key(11), model,
                                      group, index),
            jax.tree.map(lambda a: a[index], params[group]))


def program_block(x, layer, cfg, kind):
    if kind in "DE":
        return llama._ffn_mixer(x[None], layer, cfg)[0][0]
    cos, sin = rope_frequencies(cfg.rope_dim, x.shape[0], cfg.rope_theta,
                                cfg.rope_yarn)
    return llama._attn_mixer(x[None], layer, cfg, cos, sin, None,
                             llama._full_attend(cfg, None), None)[0][0]


@pytest.mark.parametrize("kind,group,scale", [
    ("D", "ffn_layers", 1.0), ("D", "ffn_layers", 6.0),
    ("E", "expert_layers", 1.0), ("E", "expert_layers", 6.0)])
def test_the_ffn_blocks_with_inputs_that_reach_the_cut(served, kind, group,
                                                        scale):
    """Dense, shared and routed SwiGLU between their two norms against the
    reference's; the norms' weights are scaled so that the products pass the
    limit (1 at test scale), and a program without the cut is another
    function."""
    model, cfg, _ = served
    w, layer = block_weights(served, group)
    w, layer = ({**t, "norm": t["norm"] + np.log(scale)} for t in (w, layer))
    x = jax.random.normal(jax.random.PRNGKey(9), (24, 64))
    want = ref.layer_forward(x, w, model, kind)
    np.testing.assert_allclose(program_block(x, layer, cfg, kind), want,
                               atol=2e-5)
    h = gated_rmsnorm(x, layer["norm"], EPS)
    leaves = layer if kind == "D" else layer["moe"]["shared"]
    assert float(jnp.abs(h @ leaves["w_up"]).max()) > model["swiglu_limit"]
    if kind == "E":
        routed = jnp.einsum("nd,edf->enf", h, layer["moe"]["w_gate"])
        assert float(routed.max()) > model["swiglu_limit"]
    uncut = program_block(x, layer, dataclasses.replace(cfg, swiglu_limit=0.0),
                          kind)
    assert float(jnp.abs(uncut - want).max()) > 1e-3


def test_the_latent_block_is_gated_rotated_by_yarn_and_tempered(served):
    model, cfg, _ = served
    w, layer = block_weights(served, "attn_layers")
    x = jax.random.normal(jax.random.PRNGKey(9), (24, 64))
    want = ref.attention_forward(x, w, model)
    np.testing.assert_allclose(program_block(x, layer, cfg, "*"), want,
                               atol=2e-5)
    for other in (dict(gated_attention=False), dict(rope_yarn=(),
                  use_mla_scaling_factor=False),
                  dict(use_mla_scaling_factor=False),
                  dict(layernorm_type="pre")):
        got = program_block(x, layer, dataclasses.replace(cfg, **other), "*")
        assert float(jnp.abs(got - want).max()) > 1e-3, other


def test_the_mixer_block_stands_between_two_norms(served):
    model, cfg, _ = served
    w, layer = block_weights(served, "gdn_layers", 2)
    x = jax.random.normal(jax.random.PRNGKey(9), (24, 64))
    want = ref.layer_forward(x, w, model, "G")
    d = cfg.gdn
    y, _, _ = gdn.scan(layer, llama._norm(x[None], layer["norm"], cfg),
                       jnp.zeros((1, 4, 16, 16)),
                       jnp.zeros((1, 3, d.conv_dim)), 24, d, cfg.norm_eps)
    np.testing.assert_allclose(llama._residual(x[None], y, layer, cfg)[0],
                               want, atol=2e-5)
    pre = dataclasses.replace(cfg, layernorm_type="pre")
    assert float(jnp.abs(
        llama._residual(x[None], y, layer, pre)[0] - want).max()) > 1e-3


# -- the configuration: published keys, derived pattern, counts ----------------

def test_the_published_constant():
    cfg = llama.GIGACHAT35_432B
    assert cfg.pattern == "GD" * 3 + "*EGEGEGE" * 9 + "*E" and cfg.n_layers == 40
    assert (cfg.n_of("*"), cfg.n_of("G"), cfg.n_of("D"), cfg.n_of("E")) \
        == (10, 30, 3, 37)
    assert cfg.gdn == gdn.Dims(k_heads=32, v_heads=64, k_dim=128, v_dim=128,
                               conv=4, gate_scale=2.0, gated_norm=True,
                               chunk=64)
    assert cfg.gdn.conv_dim == 4096 + 4096 + 8192
    # the scan's chunk is the program's constant: no key of the model
    assert not [f.name for f in dataclasses.fields(llama.Config)
                if "linear" in f.name and "chunk" in f.name]
    assert cfg.moe.n_experts == 256 and cfg.moe.top_k == 8 \
        and cfg.moe.swiglu_limit == 10.0 and cfg.moe.routed_scale == 2.5
    assert moe.stored_width(cfg.expert_dim) == 2048  # sixteen whole lanes
    assert gdn.n_params(7168, cfg.gdn) == 235_864_320
    # 430.55 B from the equations; the two modules that are not run 1.32 B
    assert abs(llama.num_params(cfg) / 430.55e9 - 1) < 1e-4
    # one rank of sixteen over the leading layer and one period
    held = dataclasses.replace(
        cfg, hybrid_override_pattern="GD*EGEGEGE", n_layers=10,
        first_k_dense_replace=0, expert_rank="0/16", vocab=16032,
        max_seq=16384)
    assert llama.pattern_runs(held.pattern) == (
        ("G", 1), ("D", 1), ("*", 1), ("EG", 3), ("E", 1))
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), held))
    assert llama.num_params(held) == sum(
        x.size for x in jax.tree.leaves(shapes))
    assert abs(llama.num_params(held) / 4.73e9 - 1) < 2e-3
    # a slot: S [64, 128, 128] float32 and [3, 16384] bfloat16, 4 layers
    assert gen.state_bytes(held) == 4 * (64 * 128 * 128 * 4 + 3 * 16384 * 2)
    assert gen.state_bytes_by_kind(held, 64) == {"gdn": 1_098_907_648}
    assert held.cache_leaves == {"kv": (640,)} and held.n_cache_layers == 1
    assert gen.page_bytes(held, 16) * 65536 == 1_342_177_280


def test_tiny_gdn_counts_its_parameters_and_keeps_a_period():
    cfg = llama.tiny_gdn()
    assert cfg.pattern == "GD*EGEGEGE"
    params = llama.init(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"embed", "final_norm", "lm_head", "gdn_layers",
                           "ffn_layers", "expert_layers", "attn_layers"}
    assert params["gdn_layers"]["w_qkv"].shape == (4, 64, 32 + 32 + 64)
    assert params["attn_layers"]["wg"].shape == (1, 64, 64)
    assert params["attn_layers"]["wkv_a"].shape == (1, 64, 16 + 8)
    for group in ("gdn_layers", "ffn_layers", "expert_layers", "attn_layers"):
        # a fresh model's gated norms multiply by 1
        assert not np.any(params[group]["norm"]) \
            and not np.any(params[group]["post_norm"])
    assert sum(x.size for x in jax.tree.leaves(params)) == llama.num_params(cfg)
    pool = gen.init_state_pool(cfg, 3)
    assert {k: v.shape for k, v in pool.items()} == {
        "gdn": (4, 3, 4, 16, 16), "gdn_conv": (4, 3, 3 * 128)}
    assert gen.init_page_pool(cfg, 5, PAGE)["kv"].shape == (1, 5, PAGE, 128)
    derived = dataclasses.replace(
        cfg, hybrid_override_pattern="", n_layers=5, first_k_dense_replace=1,
        full_attention_layers=(1,))
    assert derived.pattern == "GD*EGEGEGE"


@pytest.mark.parametrize("fields,match", [
    (dict(linear_num_key_heads=3), "multiple of linear_num_key_heads"),
    (dict(linear_value_head_dim=0), "linear_value_head_dim"),
    (dict(hybrid_override_pattern="GD*"), "n_layers"),
    (dict(first_k_dense_replace=1), "names its dense FFN blocks itself"),
    (dict(norm_type="layer"), "norm_type"),
    (dict(layernorm_type="post"), "layernorm_type"),
    (dict(rope_yarn=()), "use_mla_scaling_factor"),
    (dict(hybrid_override_pattern="GDGEGEGEGE"), "gated_attention"),
    (dict(q_lora_rank=0), "q_lora_rank"),
])
def test_a_malformed_gdn_hybrid_is_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(llama.tiny_gdn(), **fields)


def test_gated_norms_and_a_second_norm_are_a_patterns():
    with pytest.raises(ValueError, match="hybrid pattern"):
        dataclasses.replace(llama.tiny_latent(), layernorm_type="pre_post")
    with pytest.raises(ValueError, match="hybrid pattern"):
        dataclasses.replace(llama.tiny(), norm_type="zero_centered_gated")


def test_a_pattern_may_hold_latent_attention_and_every_kind_of_state():
    """What the parent refused ("a hybrid pattern runs GQA attention and no
    leading dense layers"): latent attention and a dense FFN block as kinds
    of a pattern, all three kinds of recurrent state in one model."""
    with pytest.raises(ValueError, match="'G' needs linear_num_value_heads"):
        llama.tiny_hybrid(pattern="GEGE")
    cfg = dataclasses.replace(
        llama.tiny_gdn(), hybrid_override_pattern="MDK*EG", n_layers=6,
        mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        chunk_size=8, kda_num_heads=4, kda_head_dim=8)
    assert set(cfg.recurrent) == {"M", "K", "G"}
    assert set(gen.init_state_pool(cfg, 2)) == {
        "ssm", "conv", "kda", "kda_conv", "gdn", "gdn_conv"}
    assert set(gen.state_bytes_by_kind(cfg)) == {"mamba", "kda", "gdn"}
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, cfg.vocab)
    assert np.all(np.isfinite(llama.apply(params, tokens, cfg)))


def test_no_sharding_rules_and_no_dense_cache():
    cfg = llama.tiny_gdn()
    with pytest.raises(ValueError, match="GatedDeltaNet"):
        llama.param_logical_axes(cfg)
    with pytest.raises(ValueError, match="hybrid pattern"):
        gen.init_cache(cfg, 1, 32)
    with pytest.raises(ValueError, match="hybrid pattern"):
        gen.shard_config(cfg, 2)


# -- the expert block: sixteen held shares of 256 cut-SwiGLU experts -----------

@pytest.mark.parametrize("tokens", [24, 160])
def test_the_sixteen_shares_add_up_to_the_uncut_layer_of_the_reference(tokens):
    """16 ranks of 16 experts each of 256, top-8, three products an expert,
    in the dense form (24 tokens) and the bounded or grouped one (160): the
    routed parts of all shares, plus the shared expert ONCE, are the
    reference's uncut expert block before its second norm (the same float32
    terms; the reference sums an expert at a time: 2e-5)."""
    model = {**tiny_model(experts_held=256, n_experts=256), "moe_top_k": 8}
    root = bench_weights.root_key(5)
    w = bench_weights.layer_slice(root, model, "expert_layers", 1)
    x = jax.random.normal(jax.random.PRNGKey(7), (tokens, model["dim"]))
    # the reference's block with a second norm that can be taken off again:
    # N(out; w) / (2 sigmoid(w)) * rms(out) is out
    programs = ref._programs(ref._hashable(model), False)
    h, chosen, weight, shared = programs[5](x, w)
    want = np.array(shared)
    for e in range(256):
        rows, slot = np.nonzero(np.asarray(chosen) == e)
        m = w["moe"]
        y = ref._swiglu(h[rows], m["w_gate"][e], m["w_up"][e], m["w_down"][e],
                        model["swiglu_limit"], False)
        want[rows] += np.asarray(y * np.asarray(weight)[rows, slot][:, None])
    hp = gated_rmsnorm(x, w["norm"], EPS)[None]
    np.testing.assert_allclose(hp[0], h, atol=1e-6)
    m = w["moe"]
    shared_only = None
    routed = jnp.zeros_like(x)
    for rank in range(16):
        cfg = dataclasses.replace(serve_gdn.program_config(model),
                                  expert_rank=f"{rank}/16").moe
        first, count = cfg.held
        assert count == 16 and cfg.top_k == 8 and cfg.swiglu_limit == 1.0
        share = {**m, **{k: m[k][first:first + count]
                         for k in moe.EXPERT_LEAVES}}
        out, load = moe.apply(share, hp, cfg, with_load=True)
        none = moe.apply({**share, "w_down": jnp.zeros_like(share["w_down"])},
                         hp, cfg)[0]
        shared_only = none if shared_only is None else shared_only
        np.testing.assert_allclose(none, shared_only, atol=1e-6)
        routed = routed + (out - none)[0]
        assert 0 <= load[2] <= count  # experts touched, of those held
    np.testing.assert_allclose(shared_only[0], shared, atol=2e-6)
    np.testing.assert_allclose(routed + shared_only[0], want, atol=2e-5)
    # one share alone is NOT the layer: what the absent ranks add is left out
    assert float(jnp.abs(out[0] - want).max()) > 1e-3


# -- program against the benchmark's reference, on the benchmark's weights ----

def test_the_config_the_runner_builds_is_the_tiny_preset(served):
    _, cfg, _ = served
    assert cfg == dataclasses.replace(
        llama.tiny_gdn(expert_rank="0/4"), max_seq=128)
    assert cfg.moe.held == (0, 4) and cfg.latent.heads == 4


def test_full_forward_against_the_reference(served):
    """llama.apply (the chunked scan from zeros, the grouped products, the
    blockwise latent attention on split-half rope) and the reference
    (sequential recurrence, an expert at a time, interleaved rope) in
    float32 on the same seeded weights: the same terms summed in another
    order through 10 blocks, logits of magnitude 1: 5e-4."""
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
    [(32, 32), (5, 8), (3, 8)],       # slices that end off a page of 16
])
def test_a_prompt_in_slices_of_any_bucket_is_the_prompt_whole(served, pieces):
    """State, conv window, latent pages and the last row's logits after a
    chunked prefill of 40 tokens, against the prompt in one call (padded to
    its bucket, 64), and the logits against the reference's."""
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
    assert set(pool) == {"gdn", "gdn_conv", "kv"}  # both pools, one dict
    for leaf in ("gdn", "gdn_conv", "kv"):
        np.testing.assert_allclose(pool[leaf], want[leaf], atol=2e-5)
    # only slot 1's rows moved
    assert not np.any(pool["gdn"][:, [0, 2]]) \
        and not np.any(pool["gdn_conv"][:, [0, 2]])
    ref_logits = ref.logits_many(11, model, [tokens.tolist()], [[39]])[0][0]
    np.testing.assert_allclose(logits, ref_logits, atol=5e-4)


def test_decode_moves_live_rows_only_and_a_successor_starts_from_zeros(served):
    model, cfg, params = served
    rng = np.random.default_rng(2)
    first, second = rng.integers(0, 512, 20), rng.integers(0, 512, 12)
    tables = np.zeros((3, 8), np.int32)
    tables[1, :2] = [1, 2]
    _, pool = prefill(params, cfg, pools(cfg, 3), tables[1], first, 0, 1, 32)
    # row 0 holds a retired request's state, row 2 is mid-prefill
    pool = {**pool, "gdn": pool["gdn"].at[:, 0].set(1.5),
            "gdn_conv": pool["gdn_conv"].at[:, 2].set(-0.5)}
    before = jax.tree.map(np.asarray, pool)
    logits, pool = programs(cfg)[1](
        params, jnp.asarray([5, 7, 9], jnp.int32), pool, jnp.asarray(tables),
        jnp.asarray([3, 20, 11], jnp.int32))
    for leaf in ("gdn", "gdn_conv"):
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
    for leaf in ("gdn", "gdn_conv"):
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
        return (prompts, outs, engine.stats(), engine.pool_stats(),
                set(engine._cache))
    finally:
        engine.stop()


@pytest.mark.parametrize("chunk", [0, 16])
def test_engine_prefill_then_decode_against_the_references_full_forward(
        served, chunk):
    """More requests than slots, so slots are reused mid-flight: every
    served token's reference logit against the reference's best at its
    position (the benchmark's comparison, logits and not tokens). Float32
    on both sides: 1e-3. The engine holds a latent page pool and a state
    pool in one dict, and its counters name both."""
    model, cfg, params = served
    prompts, outs, stats, pool, leaves = serve(params, cfg, chunk)
    gaps = np.concatenate(
        ref.served_gaps_many(11, model, list(zip(prompts, outs))))
    assert gaps.max() <= 1e-3, gaps.max()
    assert leaves == {"kv", "gdn", "gdn_conv"}
    assert stats["state_resets"] == len(prompts)
    assert stats["state_bytes"] == pool["state_bytes"] == gen.state_bytes(cfg, 3)
    assert pool["state_bytes_by_kind"] == {"gdn": gen.state_bytes(cfg, 3)}
    assert pool["state_slots_live"] == 0
    assert stats["cache_kind"] == "latent"
    assert stats["decode_attention"] == "jnp_latent_absorbed"
    assert pool["total_pages"] > 0 and "used_pages" in pool
    assert stats["expert_load_steps"] > 0
    assert 0 < stats["experts_touched_sum"] / stats["expert_load_steps"] <= 4


def decode_logits(params, cfg, prompt, steps):
    """Logits of ``steps`` paged decode steps after a prefill of ``prompt``
    in slot 1: [steps, vocab], and the tokens fed."""
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
    "", "state in bfloat16", "no decay in the step", "no cut",
    "no yarn", "one norm a sublayer"])
def test_a_broken_path_fails_the_tiny_comparison(served, monkeypatch, broken):
    """What the comparison is for. Prefill then 8 paged decode steps against
    the reference's full forward, logits: the sound program within 5e-4; a
    program that keeps its state in the model's type where the configuration
    states float32, leaves the decay out of the one-token update, the cut
    out of the SwiGLU, YaRN out of the tables or the second norm out of a
    sublayer, ten times outside it."""
    model, cfg, params = served
    real_step, real_scan = gdn.step, gdn.scan

    def rounded(fn):
        def run(*args):
            out, state, conv = fn(*args)
            return out, jax.lax.reduce_precision(state, 8, 7), conv
        return run

    if broken == "state in bfloat16":
        monkeypatch.setattr(gdn, "step", rounded(real_step))
        monkeypatch.setattr(gdn, "scan", rounded(real_scan))
    elif broken == "no decay in the step":
        monkeypatch.setattr(gdn, "step", lambda layer, *a: real_step(
            {**layer, "A_log": jnp.full_like(layer["A_log"], -30.0)}, *a))
    elif broken == "no cut":
        cfg = dataclasses.replace(cfg, swiglu_limit=0.0)
    elif broken == "no yarn":
        cfg = dataclasses.replace(cfg, rope_yarn=(),
                                  use_mla_scaling_factor=False)
    elif broken:
        cfg = dataclasses.replace(cfg, layernorm_type="pre")
    prompt = np.random.default_rng(5).integers(0, 512, 20)
    programs.cache_clear()  # traced again, over what stands in gdn now
    try:
        got, fed = decode_logits(params, cfg, prompt, 8)
    finally:
        programs.cache_clear()
    tokens = prompt.tolist() + fed.tolist()
    want = ref.logits_many(11, model, [tokens], [np.arange(20, 28)])[0]
    worst = float(jnp.abs(got - want).max())
    assert worst < 5e-4 if not broken else worst > 5e-3, worst


def test_the_references_own_broken_state_reads_worse_than_itself(served):
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
def test_what_cannot_be_right_beside_both_pools_is_refused(
        served, kwargs, match):
    _, cfg, params = served
    if kwargs.pop("draft", False):
        kwargs.update(draft_params=params, draft_cfg=cfg)
    with pytest.raises(ValueError, match=match):
        ServeEngine(params, cfg, max_batch=2, max_seq=128,
                    kv_page_tokens=PAGE, **kwargs)


def test_oim_serve_names_the_model():
    from oim_tpu.cli import oim_serve

    assert getattr(llama, oim_serve.SERVED_ONLY["gigachat35-432b-a28b"]) \
        is llama.GIGACHAT35_432B
