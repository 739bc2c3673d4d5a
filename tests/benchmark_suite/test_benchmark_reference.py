"""The plain float32 reference against the program at ``llama.tiny`` size,
dense and with experts, and the seeded weights it is built on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny as tiny
from benchmarks import common, weights
from benchmarks.reference import llama_like as ref
from oim_tpu.models import generate as gen
from oim_tpu.models import llama

SEED = 2**31 + 5


def _model(name):
    return common.model_dict(tiny.CONFIGS[name], "serve")


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-moe"])
def test_weights_match_the_programs_tree_and_slice(name):
    model = _model(name)
    pcfg = common.program_config(model)
    weights.check_against_program(model, jax.eval_shape(
        lambda k: llama.init(k, pcfg), jax.random.PRNGKey(0)))
    params = weights.make_on_device(SEED, model)
    again = weights.make_on_device(SEED, model)
    other = weights.make_on_device(SEED + 1, model)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(again)))
    assert not np.array_equal(params["lm_head"], other["lm_head"])
    one = jax.jit(lambda l: weights.layer_slice(
        weights.root_key(SEED), model, l))(jnp.int32(1))
    want = jax.tree.map(lambda a: a[1], params["layers"])
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(one), jax.tree.leaves(want)))
    std = float(jnp.std(params["layers"]["wq"]))
    assert std == pytest.approx(model["dim"] ** -0.5, rel=0.05)


def test_a_mismatched_tree_is_refused():
    model = dict(_model("tiny-dense"), mlp_dim=128)
    pcfg = common.program_config(_model("tiny-dense"))
    with pytest.raises(SystemExit):
        weights.check_against_program(model, jax.eval_shape(
            lambda k: llama.init(k, pcfg), jax.random.PRNGKey(0)))


def test_forward_equals_llama_apply_dense():
    model = _model("tiny-dense")
    pcfg = common.program_config(model)
    params = weights.make_on_device(SEED, model)
    toks = np.random.default_rng(0).integers(0, 256, 56).tolist()
    want = llama.apply(params, jnp.asarray([toks], jnp.int32), pcfg)[0]
    got = ref.serve_logits(SEED, model, toks, np.arange(56))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-moe"])
def test_generate_serves_the_references_argmax_and_the_control_fails(name):
    """Prefill then decode through the cache agrees with the reference's
    full forward (gap 0 in float32); the float8 control does not."""
    model = _model(name)
    pcfg = common.program_config(model)
    params = weights.make_on_device(SEED, model)
    prompt = np.random.default_rng(1).integers(0, 256, 37).tolist()
    out = np.asarray(jax.jit(lambda p, t: gen.generate(p, t, 24, pcfg))(
        params, jnp.asarray([prompt], jnp.int32)))[0, 37:].tolist()
    sound = ref.served_gaps(SEED, model, prompt, out)
    control = ref.served_gaps(SEED, model, prompt, out, control=True)
    limits = tiny.CONFIGS[name]["serve"]["limits"]
    assert sound.max() <= limits["gap_max"] and sound.mean() <= limits["gap_mean"]
    assert control.mean() > limits["gap_mean"] * 3


def test_worst_leaf_gap_uses_the_median_floor():
    want = {"a": 1.0, "b": 2.0, "c": 1e-9}
    assert ref.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 1e-9}, want) == pytest.approx(0.1)
    # an all-but-zero leaf is held against the median leaf, not itself
    assert ref.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 0.1}, want) == pytest.approx(0.1)


@pytest.mark.parametrize("count,lr", [(0, 0.0), (1, 3e-4), (1000, 3e-5)])
def test_schedule_is_optax_warmup_cosine(count, lr):
    import optax

    opt = tiny.CONFIGS["tiny-dense"]["train"]["optimizer"]
    sched = optax.warmup_cosine_decay_schedule(
        0.0, opt["lr"], opt["warmup_steps"],
        max(opt["total_steps"], opt["warmup_steps"] + 1), opt["lr"] * 0.1)
    assert ref.lr_at(opt, count) == pytest.approx(float(sched(count)), rel=1e-5)
    assert ref.lr_at(opt, count) == pytest.approx(lr, rel=1e-3, abs=1e-12)
