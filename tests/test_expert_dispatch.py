"""Inference picks the expert dispatch from the tokens in the call
(``generate._no_drop``): at ``DROPLESS_FROM_TOKENS`` tokens or more a
capacity-padded expert configuration runs dropless, below it padded with
capacity = tokens. Both compute every token's top-k sum; dense and
dropless-by-configuration models never see the choice; training keeps the
configuration's own dispatch. All on the CPU, in-process: no cluster, no
port."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oim_tpu.common import metrics as M
from oim_tpu.models import generate as gen
from oim_tpu.models import llama, moe
from oim_tpu.serve.engine import ServeEngine, _target_programs

X = gen.DROPLESS_FROM_TOKENS
PAGE = 16
CONFIGS = {
    "dense": llama.tiny(),
    "softmax-gather": llama.tiny(n_experts=4),
    "softmax-einsum": dataclasses.replace(
        llama.tiny(n_experts=4), moe_dispatch="einsum"),
    "sigmoid-ragged": llama.tiny_latent(),
}


def test_the_crossing_is_the_measured_one():
    """One constant in tokens, between the last size at which the padded
    products won on the chip and the first at which the dropless ones did
    (the table beside the constant; PERF.md section 6, PR 31)."""
    assert X == 640


@pytest.mark.parametrize("n", [1, 8, 32, 64, 128, 256, 512, 1024, 4096])
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_rule(name, n):
    """Which dispatch and capacity a configuration leaves ``_no_drop``
    with, and the expert rows the engine counts for such a call."""
    cfg = CONFIGS[name]
    run = gen._no_drop(cfg, n)
    if name in ("dense", "sigmoid-ragged"):
        assert run is cfg
        layers = cfg.n_layers - cfg.n_dense_layers
        assert gen.expert_rows(cfg, n) == (
            ("", 0) if name == "dense"
            else ("dropless", layers * cfg.moe_top_k * n))
        return
    k, e = cfg.moe_top_k, cfg.n_experts
    if n >= X:
        assert run == dataclasses.replace(cfg, moe_dispatch="ragged")
        assert gen.expert_rows(cfg, n) == ("dropless", cfg.n_layers * k * n)
    else:
        assert run.moe_dispatch == cfg.moe_dispatch
        assert moe.capacity(n, run.moe) == n  # room for every token
        assert dataclasses.replace(
            run, moe_capacity_factor=cfg.moe_capacity_factor) == cfg
        assert gen.expert_rows(cfg, n) == ("padded", cfg.n_layers * e * n)


def test_a_roomier_capacity_is_kept_below_the_crossing():
    cfg = dataclasses.replace(llama.tiny(n_experts=4), moe_capacity_factor=3.0)
    assert gen._no_drop(cfg, X - 1) is cfg
    assert gen._no_drop(cfg, X).moe_dispatch == "ragged"


# -- (2) the same prefill under both dispatches ------------------------------

# 8 experts, top-2: Mixtral's ratio, at which a whole set's ladder is one
# capacity of twice the uniform rows with the grouped products behind it
# (``moe.capacity_ladder``; at 4 experts twice the uniform rows is a token's
# worth, which always holds, and no grouped product is compiled).
EXPERT = dataclasses.replace(
    llama.tiny(vocab=251, dim=32, n_experts=8), max_seq=4 * X + 64)


@pytest.fixture(scope="module")
def expert_params():
    return llama.init(jax.random.PRNGKey(3), EXPERT)


def prefill(params, tokens, n_tokens, pool, table, start, crossing):
    """``prefill_into_pages`` traced with the crossing at ``crossing``: the
    rule's own, or out of reach (the padded dispatch forced)."""
    before = gen.DROPLESS_FROM_TOKENS
    gen.DROPLESS_FROM_TOKENS = crossing
    try:
        return jax.jit(lambda p, t, c: gen.prefill_into_pages(
            p, t, n_tokens, c, table, start, EXPERT, PAGE))(
                params, tokens, pool)
    finally:
        gen.DROPLESS_FROM_TOKENS = before


def ragged_dots(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("ragged_dot_general[")


@pytest.mark.parametrize("bucket,n_tokens,start", [
    (X, X - 9, 0), (2 * X, 2 * X - 3, 0), (4 * X, 3 * X + 5, 0),
    (X, X - 40, 48)], ids=["x", "2x", "4x", "prefix-hit-tail"])
def test_prefill_under_the_rule_is_the_padded_prefill(
        expert_params, bucket, n_tokens, start):
    """Logits and the pool's written pages within 1e-5 (float32, tiny
    widths): three buckets at and above the crossing, and a prefix-hit
    tail (``start`` > 0, its prefix prefilled first)."""
    nb = EXPERT.max_seq // PAGE
    rng = np.random.default_rng(bucket + start)
    table = jnp.asarray(1 + rng.permutation(nb), jnp.int32)
    pool = gen.init_page_pool(EXPERT, nb + 1, PAGE)
    if start:
        head = jnp.asarray(rng.integers(0, 251, (1, 64)), jnp.int32)
        _, pool = prefill(expert_params, head, start, pool, table, 0, X)
    tokens = jnp.asarray(rng.integers(0, 251, (1, bucket)), jnp.int32)
    assert ragged_dots(lambda t: gen.prefill_into_pages(
        expert_params, t, n_tokens, pool, table, start, EXPERT, PAGE),
        tokens) == 3
    got, got_pool = prefill(
        expert_params, tokens, n_tokens, pool, table, start, X)
    want, want_pool = prefill(
        expert_params, tokens, n_tokens, pool, table, start, 1 << 30)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    written = np.asarray(table)[:-(-(start + n_tokens) // PAGE)]
    for leaf in ("k", "v"):
        g, w = np.asarray(got_pool[leaf]), np.asarray(want_pool[leaf])
        assert np.abs(w[:, written]).max() > 0.1
        assert np.abs(g - w).max() < 1e-5
    # Pads were dropped by both: nothing landed past the last real token.
    after = np.asarray(table)[-(-(start + n_tokens) // PAGE):]
    assert not np.asarray(got_pool["k"])[:, after].any()


def test_a_decode_step_and_a_small_prefill_stay_padded(expert_params):
    nb = EXPERT.max_seq // PAGE
    pool = gen.init_page_pool(EXPERT, nb + 1, PAGE)
    tables = jnp.ones((4, nb), jnp.int32)
    assert ragged_dots(lambda t: gen.decode_step(
        expert_params, t, pool, tables, jnp.arange(4), EXPERT, PAGE),
        jnp.zeros((4,), jnp.int32)) == 0
    assert ragged_dots(lambda t: gen.prefill_into_pages(
        expert_params, t, 5, pool, tables[0], 0, EXPERT, PAGE),
        jnp.zeros((1, X // 2), jnp.int32)) == 0
    # verify_step falls on the side its B x T does.
    for t, dots in ((X // 4 - 1, 0), (X // 4, 3)):
        assert ragged_dots(lambda s: gen.verify_step(
            expert_params, s, pool, tables, jnp.arange(4), EXPERT, PAGE),
            jnp.zeros((4, t), jnp.int32)) == dots


def test_solo_generate_follows_the_same_rule(expert_params):
    for t, dots in ((X - 1, 0), (X, 3)):
        cache = gen.init_cache(EXPERT, 1, t)
        assert ragged_dots(lambda p: gen.cached_forward(
            expert_params, p, cache, 0, EXPERT),
            jnp.zeros((1, t), jnp.int32)) == dots


# -- (3), (4) through the engine ---------------------------------------------

def solo(params, prompt, n_new):
    out = gen.generate(params, jnp.asarray([prompt], jnp.int32), n_new, EXPERT)
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


@pytest.fixture(scope="module")
def engine(expert_params):
    eng = ServeEngine(expert_params, EXPERT, max_batch=2, max_seq=2 * X + 64,
                      prefix_block=PAGE, prefix_cache_bytes=0)
    yield eng
    eng.stop(timeout=30)
    _target_programs.cache_clear()
    jax.clear_caches()


LENGTHS = {"far-below": 12, "below": X // 2 - 8, "bucket-crosses": X - 40,
           "above": X + 4, "far-above": X + 44}


@pytest.mark.parametrize("case", sorted(LENGTHS))
def test_the_engine_streams_what_solo_generate_does(
        expert_params, engine, case):
    """Prompts on both sides of the crossing, token for token (greedy,
    float32). In ``bucket-crosses`` the engine's bucket runs dropless where
    solo's own length runs padded: the two dispatches pick the same
    tokens."""
    n = LENGTHS[case]
    if case == "bucket-crosses":
        assert n < X <= engine._bucket(n)
    prompt = [int(t) for t in
              np.random.default_rng(n).integers(0, 251, (n,))]
    before = dict(engine._expert_rows)
    served = engine.submit(
        prompt, max_new=6, temperature=0.0, eos=-1).result(timeout=300)
    assert served == solo(expert_params, prompt, 6)
    side = "dropless" if engine._bucket(n) >= X else "padded"
    assert engine._expert_rows[side] > before[side]
    if side == "padded":
        assert engine._expert_rows["dropless"] == before["dropless"]


def test_the_counter_counts_rows_by_dispatch(expert_params, engine):
    """A known sequence: one admission above the crossing, one below, and
    the decode rounds between; rows over the two expert layers, from the
    programs' shapes. Every round DISPATCHED counts, the one that steps a
    row past its last token too (the engine keeps a round in flight)."""
    e, k, layers, b = (EXPERT.n_experts, EXPERT.moe_top_k, EXPERT.n_layers,
                       engine.max_batch)

    def snapshot():
        s = engine.stats()
        return (s["expert_rows_dropless"], s["expert_rows_padded"],
                s["decode_rounds_ahead"] + s["decode_rounds_drained"],
                M.SERVE_EXPERT_ROWS.labels(dispatch="dropless").value,
                M.SERVE_EXPERT_ROWS.labels(dispatch="padded").value)

    def run(n, max_new):
        prompt = [int(t) for t in
                  np.random.default_rng(7 * n).integers(0, 251, (n,))]
        engine.submit(prompt, max_new=max_new, temperature=0.0,
                      eos=-1).result(timeout=300)

    at = snapshot()
    run(X + 20, 5)
    mid = snapshot()
    rounds = mid[2] - at[2]
    assert rounds >= 4  # the first token is the prefill's
    assert mid[0] - at[0] == layers * k * engine._bucket(X + 20)
    assert mid[1] - at[1] == layers * e * b * rounds
    run(20, 3)
    end = snapshot()
    rounds = end[2] - mid[2]
    assert end[0] == mid[0]
    assert end[1] - mid[1] == layers * e * (32 + b * rounds)
    # The exported counter moved by the same rows.
    assert end[3] - at[3] == end[0] - at[0]
    assert end[4] - at[4] == end[1] - at[1]


def test_the_engine_tallies_rungs_where_a_program_has_a_ladder(
        expert_params, engine):
    """A capacity-padded configuration's engine-level dispatch is ``gather``
    and its decode step reports no expert load, yet its buckets above the
    crossing run dropless on a ladder: those prefills tally their expert
    layers' rungs (``oim_serve_expert_calls_total``), the padded ones below
    return no tally, and decode rounds count on no rung."""
    layers = EXPERT.n_layers

    def calls():
        s = engine.stats()
        return sum(s[f"expert_calls_{name}_rung"] for name in moe.RUNG_NAMES)

    def run(n):
        prompt = [int(t) for t in
                  np.random.default_rng(11 * n).integers(0, 251, (n,))]
        engine.submit(prompt, max_new=4, temperature=0.0,
                      eos=-1).result(timeout=300)

    assert moe.capacity_ladder(
        engine._bucket(X + 20), gen._no_drop(EXPERT, X + 20).moe)
    at = calls()
    run(X + 20)
    assert calls() - at == layers
    run(20)
    assert calls() - at == layers


def test_a_dense_engine_counts_no_expert_rows():
    cfg = llama.tiny(vocab=253)
    eng = ServeEngine(llama.init(jax.random.PRNGKey(0), cfg), cfg,
                      max_batch=2, max_seq=64)
    try:
        eng.submit([1, 2, 3], max_new=3, temperature=0.0,
                   eos=-1).result(timeout=300)
        assert "expert_rows_dropless" not in eng.stats()
        assert eng._expert_rows == {"dropless": 0, "padded": 0}
    finally:
        eng.stop(timeout=30)


# -- (5) training keeps the configuration's dispatch -------------------------

def test_the_trainers_expert_step_still_traces_gather():
    """8 x 256 = 2048 tokens a step, over the crossing: the train step
    reaches ``moe.apply`` with the configuration's own ``gather`` and
    capacity factor (``ragged`` is not differentiated)."""
    from oim_tpu.train import TrainConfig, Trainer

    trainer = Trainer(TrainConfig(
        model="llama-tiny-moe", batch_size=8, seq_len=256, warmup_steps=1,
        total_steps=1))
    state = jax.eval_shape(trainer.init_fn, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((8, 257), jnp.int32)}
    assert ragged_dots(trainer.step_fn, state, batch) == 0
    assert "scatter" in str(jax.make_jaxpr(trainer.step_fn)(state, batch))
    cfg = trainer.cfg.model_config()
    assert cfg.moe_dispatch == "gather"
    # The same model's serving prefill over as many tokens does run
    # dropless (here at Mixtral's 8 experts: the grouped products alone,
    # behind a capacity in the crossing's own bucket).
    cfg = dataclasses.replace(cfg, n_experts=8)
    ragged = gen._no_drop(cfg, 2 * X).moe
    assert moe.capacity_ladder(X, ragged) == (384,)
    assert moe.capacity_ladder(2 * X, ragged) == ()  # 320 rows an expert
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: gen.init_page_pool(cfg, 2 * X // PAGE + 1,
                                                     PAGE))
    assert ragged_dots(
        lambda p, t, c, tb: gen.prefill_into_pages(
            p, t, 2 * X - 5, c, tb, 0, cfg, PAGE),
        params, jax.ShapeDtypeStruct((1, 2 * X), jnp.int32), pool,
        jax.ShapeDtypeStruct((2 * X // PAGE,), jnp.int32)) == 3
