"""The device-side scopes of the serving programs (PR 39): every family's
prefill, decode step and (where it has one) verify program carries the one
vocabulary of ``jax.named_scope`` names in its operations' ``op_name``s,
under the program's own ``jit(...)``, nested as the reader of
``benchmarks/readers/scope_share.py`` expects them (innermost name wins).
Compiled here on the CPU at tiny sizes: the names are trace-time metadata
and the same on every backend."""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks import common
from oim_tpu.models import generate as gen
from oim_tpu.models import llama, moe
from oim_tpu.ops import cca, gdn, kda, ssm
from oim_tpu.serve import engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, MAX_SEQ, SLOTS, SPEC = 16, 64, 2, 3
# a prompt slice of a few tokens, and one long enough for a capacity-padded
# expert model to run dropless (the only form of it that has scopes of its own)
BUCKETS = {"prefill": 32, "prefill_dropless": gen.DROPLESS_FROM_TOKENS}
BLOCK = {"tok_embed", "blk_loop", "blk_qkv", "blk_attn", "blk_kv_write",
         "blk_out", "blk_ffn", "tok_head"}
EXPERTS = {"moe_route", "moe_gmm"}
# family -> (configuration, what the family adds to the block's names in
# each program; no entry: the family has no such program)
FAMILIES = {
    "tiny": (llama.tiny, {"prefill": set(), "step": set(), "verify": set()}),
    # 8 experts, top-2: the dropless bucket's ladder is one capacity with the
    # grouped products behind it (at 4 a token's worth, which always holds)
    "tiny_experts": (lambda: llama.tiny(n_experts=8),
                     {"prefill": set(), "prefill_dropless": EXPERTS,
                      "step": set(), "verify": set()}),
    "tiny_latent": (llama.tiny_latent,
                    {"prefill": EXPERTS | {"mla_prefill"},
                     "step": EXPERTS | {"mla_decode"},
                     "verify": EXPERTS | {"mla_prefill"}}),
    "tiny_hybrid": (llama.tiny_hybrid, {"prefill": EXPERTS | {"ssm_scan"},
                                        "step": EXPERTS | {"ssm_step"}}),
    "tiny_kda": (llama.tiny_kda, {"prefill": EXPERTS | {"kda_scan"},
                                  "step": EXPERTS | {"kda_step"}}),
    # the GatedDeltaNet mixer's own names stand INSIDE the delta-rule
    # family's (``inside`` below); its latent layer has the latent kernels'
    "tiny_gdn": (llama.tiny_gdn,
                 {"prefill": EXPERTS | {"kda_scan", "mla_prefill"},
                  "step": EXPERTS | {"kda_step", "mla_decode"}}),
    # compressed convolutional attention adds no name to the vocabulary: its
    # mixing stands INSIDE ``blk_qkv`` (``inside`` below), its pages are GQA's
    "tiny_cca": (llama.tiny_cca, {"prefill": EXPERTS, "step": EXPERTS}),
}
CASES = [(family, program) for family, (_, programs) in FAMILIES.items()
         for program in programs]


def _tokens(program: str) -> int:
    return BUCKETS.get(program, SLOTS * (SPEC if program == "verify" else 1))


def _has_grouped_products(cfg, program: str) -> bool:
    """Whether the program's expert layers compile ``lax.ragged_dot``: the
    dropless dispatch does unless a capacity of a token's worth closes its
    ladder (``moe.capacity_ladder``) or a held share runs dense."""
    n = _tokens(program)
    run = gen._no_drop(cfg, n)
    if not run.n_experts or run.moe_dispatch != "ragged":
        return False
    if run.moe.held and n <= moe.DENSE_UP_TO_TOKENS:
        return False
    ladder = moe.capacity_ladder(n, run.moe)
    return not ladder or ladder[-1] < n


def _lowered(cfg, program: str):
    """``program`` lowered at tiny sizes: the engine's own jitted step and
    prefill (sampling included), and ``verify_step`` under a jit of that
    name."""
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    blocks = MAX_SEQ // PAGE
    pool = jax.eval_shape(lambda: {
        **gen.init_page_pool(cfg, SLOTS * blocks + 1, PAGE),
        **gen.init_state_pool(cfg, SLOTS)})
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    step, prefill = engine._target_programs(cfg, PAGE, MAX_SEQ)
    if program == "step":
        lowered = step.lower(
            params, pool, i32(SLOTS), i32(SLOTS),
            jax.ShapeDtypeStruct((SLOTS,) + key.shape, key.dtype),
            f32(SLOTS), i32(SLOTS, blocks))
    elif program in BUCKETS:
        slot = (i32(),) if cfg.state_leaves else ()
        lowered = prefill.lower(params, pool, i32(1, BUCKETS[program]), i32(),
                                i32(blocks), i32(), key, f32(), *slot)
    else:
        def verify(params, tokens, pool, tables, pos):
            return gen.verify_step(params, tokens, pool, tables, pos, cfg,
                                   PAGE)

        lowered = jax.jit(verify).lower(
            params, i32(SLOTS, SPEC), pool, i32(SLOTS, blocks), i32(SLOTS))
    return lowered


@pytest.mark.parametrize("family,program", CASES,
                         ids=[f"{f}-{p}" for f, p in CASES])
def test_program_carries_the_vocabulary(family, program):
    make, programs = FAMILIES[family]
    jitted = program.split("_")[0]
    lowered = _lowered(make(), program)
    # the compiled program's own operations: called computations (a
    # scatter's combiner, a reduction's body) keep a relative path
    paths = [name.split("/") for name in re.findall(
        r'op_name="([^"]*)"', lowered.compile().as_text())
        if name.startswith(f"jit({jitted})/")]
    found = {part for path in paths for part in path}
    want = BLOCK | programs[program]
    assert want <= found, sorted(want - found)

    def inside(inner, outer):
        return any(inner in path and outer in path[:path.index(inner)]
                   for path in paths)

    assert inside("blk_kv_write", "blk_attn")
    # the layer loop's name stands outside the block's: the block's win
    assert inside("blk_qkv", "blk_loop") or inside("blk_ffn", "blk_loop")
    if "moe_gmm" in want:
        assert inside("moe_gmm", "blk_ffn") and inside("moe_route", "blk_ffn")
    # the TPU's compiler renames a grouped product and drops its path: the
    # reader charges one to the scope every one of them is traced under
    # (read before compiling: the CPU's compiler expands them away)
    reader = common.plugin(REPO, "readers", "scope_share")
    grouped = set(re.findall(r'loc\("([^"]*ragged_dot[^"]*)"',
                             lowered.as_text(debug_info=True)))
    assert bool(grouped) == _has_grouped_products(make(), program)
    if program in ("step", "prefill_dropless"):  # both forms are held
        assert bool(grouped) == ("moe_gmm" in want)
    assert all("moe_gmm/" in name for name in grouped)
    assert set(reader.REWRITTEN.values()) == {"moe_gmm"}
    for kernel in want & {"mla_decode", "mla_prefill"}:
        assert inside(kernel, "blk_attn")
    if family == "tiny_gdn":
        # the mixer's own scope under the vocabulary's: the benchmark's
        # reader charges the innermost name it KNOWS (kda_*), the mixer's
        # roofline reads its own (gdn_*), and neither name stands alone
        own = "gdn_scan" if jitted == "prefill" else "gdn_step"
        family_scope = own.replace("gdn", "kda")
        assert inside(own, family_scope)
        assert all(family_scope in path for path in paths if own in path)
        assert reader.classify("/".join(
            next(p for p in paths if own in p))) == family_scope
    if family == "tiny_cca":
        # the mixing's own scope under the block's: the benchmark's reader
        # charges it to ``blk_qkv``, the sublayer's roofline reads ``cca_mix``
        outer, own = cca.SCOPES[jitted == "prefill"].split("/")
        assert (outer, own) == ("blk_qkv", "cca_mix") and inside(own, outer)
        assert all(outer in path for path in paths if own in path)
        assert reader.classify("/".join(
            next(p for p in paths if own in p))) == outer
        # the tail's read and write stand under it, the router's carry under
        # the router's name
        assert any("cca_mix" in p and "dynamic_update_slice" in p[-1]
                   or "cca_mix" in p and "scatter" in p[-1] for p in paths)
        assert inside("moe_route", "blk_ffn")
    # nothing of the vocabulary that the family should not have: a
    # recurrent mixer's name in a model without one would be a wrong ``with``
    assert found & set(reader.VOCABULARY) == want


def test_the_reader_knows_every_scope_the_program_sets():
    """``scope_share.VOCABULARY`` is the benchmark's copy of the names: one
    name more or less in the program's sources fails here."""
    named = set(ssm.SCOPES) | set(kda.SCOPES)
    # a mixer's OWN names inside a vocabulary name ("kda_step/gdn_step") are
    # not the vocabulary's: the outer name is what the reader charges
    assert [s.split("/")[0] for s in gdn.SCOPES] == list(kda.SCOPES)
    for folder, _, files in os.walk(os.path.join(REPO, "oim_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    named |= set(re.findall(
                        r'named_scope\("([A-Za-z_0-9]+)"\)', f.read()))
    reader = common.plugin(REPO, "readers", "scope_share")
    assert named == set(reader.VOCABULARY)
    assert BLOCK <= named
