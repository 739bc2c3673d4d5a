"""The main path's programs COMPILED for a TPU v5e that is described, not
attached: the chip's own compiler refuses here what it would refuse on
the machine — a kernel slice off the tiling, a program over HBM, a
Mosaic call XLA is asked to partition — at no chip time. Shapes are
chip_smoke.py's (llama3-8b widths, its depths, its --max-batch/--max-seq),
so the smoke's first chip call is never spent on a compile error.

A compile that passes is not a run: nothing here says a result is right
or fast. Everything that touches the topology lives in fixtures of THIS
file (one xdist worker loads the TPU library; nothing at import time).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

import chip_smoke
from oim_tpu.models import generate as gen
from oim_tpu.models import llama, moe
from oim_tpu.ops import latent_attention
from oim_tpu.ops.attention import _flash_plan, attention
from oim_tpu.train import TrainConfig

ONE = chip_smoke.ONE_CHIP
FOUR = chip_smoke.FOUR_CHIPS
PAGE = 16  # oim-serve's default --prefix-block == --kv-page-tokens


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture()
def as_tpu(monkeypatch, no_compile_cache):
    """Code that asks jax.default_backend() still sees the CPU here and
    would take its CPU branch; steer the attention dispatch from the
    test, not through an option of the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def shaped(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def model_config(n_layers: int) -> llama.Config:
    return TrainConfig(
        model=ONE["model"], model_overrides={"n_layers": n_layers},
    ).model_config()


def test_widths_are_the_published_ones():
    cfg = model_config(ONE["n_layers"])
    assert dataclasses.replace(cfg, n_layers=32) == llama.LLAMA3_8B
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.mlp_dim,
            cfg.vocab) == (4096, 32, 8, 128, 14336, ONE["vocab"])


@pytest.mark.parametrize("seq,block", [(2048, 1024), (1536, 512), (640, 128)])
def test_flash_fwd_bwd(topo, as_tpu, seq, block):
    """The Pallas flash kernels, forward and backward, at the model's
    head geometry and each block size the plan can pick."""
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = llama.LLAMA3_8B
    q = jax.ShapeDtypeStruct((1, seq, cfg.n_heads, cfg.head_dim),
                             jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, seq, cfg.n_kv_heads, cfg.head_dim),
                              jnp.bfloat16, sharding=chip)
    assert _flash_plan(q, kv) == (block, block)

    def loss(q, k, v):
        return attention(q, k, v, True).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert "tpu_custom_call" in text


def serve_shapes(cfg, sharding):
    n_pages = ONE["max_batch"] * ONE["max_seq"] // PAGE + 1
    params = shaped(jax.eval_shape(
        lambda: llama.init(jax.random.PRNGKey(0), cfg)), sharding)
    pool = shaped(jax.eval_shape(
        lambda: gen.init_page_pool(cfg, n_pages, PAGE)), sharding)
    return params, pool


def step_operands(sharding, b=ONE["max_batch"], seq=ONE["max_seq"]):
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (s((b,), jnp.int32), s((b,), jnp.int32),
            s((b,) + key.shape, key.dtype), s((b,), jnp.float32),
            s((b, seq // PAGE), jnp.int32))


def prefill_operands(sharding, bucket, seq=ONE["max_seq"]):
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (s((1, bucket), jnp.int32), s((), jnp.int32),
            s((seq // PAGE,), jnp.int32), s((), jnp.int32),
            s(key.shape, key.dtype), s((), jnp.float32))


def test_decode_step(topo, as_tpu):
    from oim_tpu.serve.engine import _target_programs

    chip = SingleDeviceSharding(topo.devices[0])
    cfg = model_config(ONE["n_layers"])
    step, _ = _target_programs(cfg, PAGE, ONE["max_seq"])
    params, pool = serve_shapes(cfg, chip)
    mem = step.lower(params, pool, *step_operands(chip)
                     ).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 4 << 30


@pytest.mark.parametrize("bucket", [16, ONE["max_seq"]])
def test_prefill_bucket(topo, as_tpu, bucket):
    """The smallest bucket and the full --max-seq one (f32 scores and
    full-sequence logits materialize there)."""
    from oim_tpu.serve.engine import _target_programs

    chip = SingleDeviceSharding(topo.devices[0])
    cfg = model_config(ONE["n_layers"])
    _, prefill = _target_programs(cfg, PAGE, ONE["max_seq"])
    params, pool = serve_shapes(cfg, chip)
    prefill.lower(params, pool, *prefill_operands(chip, bucket)).compile()


# -- the page pool is read and written where it lies ------------------------
# The benchmark's two serving cells at their own sizes (benchmarks/configs).

CELLS = {"chat": ("mistral-7b", 1024), "batch": ("mixtral-8x7b", 512)}
# sha256 (first 16 digits) of each cell's decode kernel, see mosaic_kernels.
PAGED_KERNEL = {"chat": "98b1c7d9bae56d91", "batch": "da7dd844297494c1"}
MOVES = ("copy", "copy-start", "dynamic-update-slice", "gather")
_INSTRUCTION = re.compile(
    r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")


def moves_of(text: str, shape, tail=()):
    """Instructions of the optimized HLO (fused bodies too) that copy,
    restack or gather an array of ``shape``'s element count — whatever
    way the compiler folded its leading dims — and, with ``tail``, of
    those trailing dims."""
    count, found = math.prod(shape), []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or m.group(3) not in MOVES:
            continue
        for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(2)):
            dims = tuple(int(d) for d in dims.split(","))
            if math.prod(dims) == count and dims[len(dims) - len(tail):] \
                    == tuple(tail):
                found.append((m.group(3), m.group(1), dims))
    return found


def mosaic_kernels(text: str) -> list:
    """(kernel function's name, the HLO line of its call, its Mosaic module
    as text WITHOUT debug locations) of each Pallas call in an optimized
    HLO. The serialized module carries the checkout's path and every source
    line; without them the text is the kernel's body alone, the same in
    any checkout until the kernel itself (or the shapes it is built for)
    changes."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    found = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        config = json.loads(line[line.index("backend_config=") + 15:])
        body = config["custom_call_config"].get("body")
        if not body:
            continue
        ctx = mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True  # stable_mosaic.*
        with ctx:
            module = ir.Module.parse(base64.b64decode(body))
            attrs = module.operation.attributes
            if "sym_name" in attrs:  # XLA's own (ragged dot) have no name
                found.append((
                    ir.StringAttr(attrs["sym_name"]).value, line.strip(),
                    module.operation.get_asm(enable_debug_info=False)))
    return found


def program_text(text: str) -> str:
    """An optimized HLO without what names the checkout or a source line:
    the header's tables and every ``metadata={...}`` go, and each Pallas
    call's serialized body gives way to its Mosaic text without debug
    locations. What is left is the program: the same in any checkout until
    the code it is traced from computes something else."""
    kernels = {line: body for _, line, body in mosaic_kernels(text)}
    out, table = [], False
    for line in text.splitlines():
        # The header's tables of files, functions, lines and stack frames.
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            table = True
        elif not line:
            table = False
        if table:
            continue
        body = kernels.get(line.strip())
        if body is not None:
            line = line[:line.index("backend_config=")] + body
        out.append(re.sub(r",? ?metadata=\{[^{}]*\}", "", line))
    return "\n".join(out)


def text_hash(text: str) -> str:
    return hashlib.sha256(program_text(text).encode()).hexdigest()[:16]


def test_moves_of_reads_the_hlo():
    text = """
  %copy.94 = bf16[14,2561,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} copy(%p)
  %dynamic-update-slice.3 = bf16[573664,8,128]{2,1,0} dynamic-update-slice(%a, %b, %c)
  ROOT %gather.1 = bf16[32,256,16,8,128]{4,3,2,1,0} gather(%a, %b)
  %copy.1 = bf16[32768,4096]{1,0} copy(%embed)
  %bitcast.2 = bf16[14,2561,128,128]{3,2,1,0} bitcast(%fusion.176)
  %fusion.9 = bf16[573664,8,128]{2,1,0} fusion(%a), kind=kCustom"""
    assert [m[1] for m in moves_of(text, (14, 2561, 16, 8, 128))] == [
        "copy.94", "dynamic-update-slice.3"]
    assert [m[1] for m in moves_of(text, (32, 4096, 8, 128), (8, 128))] == [
        "gather.1"]


def cell_shapes(cell, sharding):
    from benchmarks import common

    name, bucket = CELLS[cell]
    with open(os.path.join(common.ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    model = common.model_dict(config, "serve")
    cfg = common.program_config(model)
    n_pages = config["serve"]["kv_pool_tokens"] // PAGE + 1
    b, seq = config["serve"]["max_batch"], model["max_seq"]
    params = shaped(jax.eval_shape(
        lambda: llama.init(jax.random.PRNGKey(0), cfg)), sharding)
    pool = shaped(jax.eval_shape(
        lambda: gen.init_page_pool(cfg, n_pages, PAGE)), sharding)
    return cfg, params, pool, b, seq, bucket


_COMPILED = {}


def serving_program(chip, cell, program):
    """(compiled, cfg, pool, max_batch, max_seq) of one serving program of a
    benchmark cell at the cell's own sizes: ``step``, or
    ``prefill-<bucket>``. Compiled once a module, whichever test asks."""
    from oim_tpu.serve.engine import _target_programs

    if (cell, program) not in _COMPILED:
        if cell == "longctx":
            cfg, params, pool, sizes, seq = latent_cell(chip)
            b = sizes["max_batch"]
        else:
            cfg, params, pool, b, seq, _ = cell_shapes(cell, chip)
        step, prefill = _target_programs(cfg, PAGE, seq)
        if program == "step":
            lowered = step.lower(params, pool, *step_operands(chip, b, seq))
        else:
            lowered = prefill.lower(params, pool, *prefill_operands(
                chip, int(program.split("-")[1]), seq))
        _COMPILED[cell, program] = lowered.compile(), cfg, pool, b, seq
    return _COMPILED[cell, program]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_decode_reads_live_pages_in_place(topo, as_tpu, cell):
    """The decode program of each serving cell: the Pallas kernel is in
    it, and nothing copies, restacks or gathers an array of the pool's
    shape or of [B, S, kvh, hd]."""
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, pool, b, seq = serving_program(chip, cell, "step")
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The GQA kernel as PR 27 left it (commit 518e4ec; PR 29 gave the
    # latent pool a kernel of its own and held these two programs to their
    # text): an edit of ops/paged_attention.py that moves this moves both
    # cells' decode programs; measure them, then record the new text's hash.
    assert {(name, hashlib.sha256(body.encode()).hexdigest()[:16])
            for name, _, body in mosaic_kernels(text)} == {
        ("_paged_kernel", PAGED_KERNEL[cell])}
    assert not moves_of(text, pool["k"].shape)
    tail = (cfg.n_kv_heads, cfg.head_dim)
    assert not moves_of(text, (b, seq) + tail, tail)
    # No gathered view, no f32 scores over all S: what is left is small.
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def scores_of(text: str, t: int, seq: int) -> list:
    """float32 arrays [.., t, seq] of an optimized HLO with more than one
    row of leading dims (a head's, a group's): attention scores over a
    whole table. [1, T, D] activations of a model whose width is ``seq``
    are none."""
    found = set()
    for dims in re.findall(rf"f32\[([\d,]+),{t},{seq}\]", text):
        if math.prod(int(d) for d in dims.split(",")) > 1:
            found.add(f"f32[{dims},{t},{seq}]")
    return sorted(found)


def test_scores_of_reads_the_hlo():
    text = """
  %fusion.1 = f32[1,2,16,1024,8192]{4,3,2,1,0} fusion(%a), kind=kOutput
  %fusion.2 = f32[64,1024,8192]{2,1,0} fusion(%a), kind=kOutput
  %fusion.3 = f32[1,1024,8192]{2,1,0} fusion(%a), kind=kLoop
  %fusion.4 = bf16[64,1024,8192]{2,1,0} fusion(%a), kind=kLoop
  %fusion.5 = f32[1024,8192]{1,0} fusion(%a), kind=kLoop"""
    assert scores_of(text, 1024, 8192) == [
        "f32[1,2,16,1024,8192]", "f32[64,1024,8192]"]


def reads_pages_in_place(text: str, cfg, pool, bucket: int, seq: int,
                         calls: int) -> bool:
    """Whether a prefill program attends through the Pallas prefill kernel,
    ``calls`` times (once a layer loop, or a layer), and nothing copies,
    restacks, gathers or re-lays an array of the pool's shape or a slot's
    gathered [1, S, kvh, hd] view, and no [.., T, S] float32 scores exist."""
    tail = (cfg.n_kv_heads, cfg.head_dim)
    return ([name for name, _, _ in mosaic_kernels(text)]
            == ["_prefill_kernel"] * calls
            and not moves_of(text, pool["k"].shape)
            and not moves_of(text, (1, seq) + tail, tail)
            and not scores_of(text, bucket, seq))


# Temporaries of each cell's commonest prefill bucket, bytes: the parent
# held 521.7 MB (chat: the gathered view and f32[1, 8, 4, 1024, 4096]
# scores) and 129.8 MB (batch); what is left, 1.3 and 117.9 MB, is the
# bucket's activations and the expert dispatch.
CELL_PREFILL_TEMP = {"chat": 64 << 20, "batch": 128 << 20}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_prefill_carries_the_pool(topo, as_tpu, cell):
    """The cell's commonest prefill bucket holds what the decode program
    holds (PR 43): the pool is scattered into in place and attended where
    it lies by the Pallas prefill kernel; no gather of the slot's table,
    no copy or re-layout of the pool, no f32 scores over all S."""
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, pool, _, seq = serving_program(
        chip, cell, f"prefill-{CELLS[cell][1]}")
    assert reads_pages_in_place(
        compiled.as_text(), cfg, pool, CELLS[cell][1], seq, calls=1)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < CELL_PREFILL_TEMP[cell]


def test_verify_carries_the_pool(topo, as_tpu):
    """The speculative verify program (T = K + 1 > 1: the gather path on
    the carried pool) on the chat cell's target and pool."""
    from oim_tpu.serve.engine import _spec_programs

    chip = SingleDeviceSharding(topo.devices[0])
    cfg, params, pool, b, seq, _ = cell_shapes("chat", chip)
    k = 3
    dcfg = dataclasses.replace(cfg, n_layers=1)
    _, _, verify = _spec_programs(cfg, dcfg, PAGE, seq, k)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    text = verify.lower(
        params, pool, *step_operands(chip, b, seq), s((b, k), jnp.int32),
        s((b, k, cfg.vocab), jnp.float32), s((b,), jnp.bool_),
    ).compile().as_text()
    assert not moves_of(text, pool["k"].shape)


def compile_train_step(topo, rules, axes, n_layers, batch):
    from oim_tpu.parallel.sharding import BATCH, logical_sharding
    from oim_tpu.train.state import make_optimizer
    from oim_tpu.train.trainer import RULES, make_train_step

    n = int(np.prod([size for _, size in axes]))
    mesh = Mesh(np.asarray(topo.devices[:n]).reshape(
        [size for _, size in axes]), tuple(name for name, _ in axes))
    cfg = TrainConfig(model=ONE["model"], rules=rules, batch_size=batch,
                      seq_len=ONE["seq"],
                      model_overrides={"n_layers": n_layers})
    step, shardings, init_fn, _ = make_train_step(cfg, mesh, make_optimizer())
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)), shardings)
    tokens = jax.ShapeDtypeStruct(
        (batch, ONE["seq"] + 1), jnp.int32,
        sharding=logical_sharding(mesh, RULES[rules], (BATCH, None)))
    return step.lower(state, {"tokens": tokens}).compile()


def test_train_step_one_chip(topo, as_tpu):
    """The smoke's trainer step fits one chip with the kernel in it, and
    the chunked loss keeps the [B, T, vocab] logits out of the program."""
    compiled = compile_train_step(
        topo, "dp", [("data", 1)], ONE["n_layers"], ONE["batch"])
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    logits = f"[{ONE['batch']},{ONE['seq']},{ONE['vocab']}]"
    assert logits not in text.replace(" ", "")


def test_train_step_fsdp_four_chips(topo, as_tpu):
    """--rules fsdp over four chips: XLA cannot partition a Mosaic kernel,
    so the step compiles only with attention under shard_map."""
    compiled = compile_train_step(
        topo, "fsdp", [("data", 1), ("fsdp", 4)], FOUR["deep_layers"],
        FOUR["batch"])
    assert "tpu_custom_call" in compiled.as_text()


def shard4_program(topo, monkeypatch, program: str):
    """(optimized HLO, cfg, member-local cfg, n_pages, b, seq) of one
    --shard 4 serving program on a mesh of the described devices, at the
    chat cell's 32 slots x 4096 positions (the smoke's pool of a few MB the
    compiler parks in VMEM and back, which is no finding): ``step``, or
    ``prefill-<bucket>``."""
    from jax.sharding import PartitionSpec as P

    from oim_tpu.serve import shard as shardlib
    from oim_tpu.serve.engine import _target_programs

    mesh = Mesh(np.asarray(topo.devices[:4]), ("tp",))
    monkeypatch.setattr(shardlib, "tp_mesh", lambda n: mesh)
    cfg = model_config(ONE["n_layers"])
    b, seq = 32, 4096
    n_pages = b * seq // PAGE + 1
    _target_programs.cache_clear()
    try:
        step, prefill = _target_programs(cfg, PAGE, seq, 4)
        params = jax.tree_util.tree_map_with_path(
            lambda path, s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(
                    mesh, shardlib.leaf_spec(path[-1].key))),
            jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg)))
        pool = {
            k: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(
                    mesh, shardlib.pool_specs()[k]))
            for k, s in jax.eval_shape(
                lambda: gen.init_page_pool(cfg, n_pages, PAGE)).items()}
        whole = NamedSharding(mesh, P())
        if program == "step":
            lowered = step.lower(params, pool, *step_operands(whole, b, seq))
        else:
            lowered = prefill.lower(params, pool, *prefill_operands(
                whole, int(program.split("-")[1]), seq))
        text = lowered.compile().as_text()
    finally:
        _target_programs.cache_clear()  # never leak the described mesh
    return text, cfg, gen.shard_config(cfg, 4), n_pages, b, seq


def test_decode_step_shard4(topo, as_tpu, monkeypatch):
    """One --shard 4 decode step. The member's view (kv heads 8 / 4 = 2)
    takes the kernel inside the shard_map and moves neither its slice of
    the pool nor a gathered view."""
    text, cfg, lcfg, n_pages, b, seq = shard4_program(
        topo, monkeypatch, "step")
    assert "all-reduce" in text
    assert "tpu_custom_call" in text
    tail = (lcfg.n_kv_heads, cfg.head_dim)
    assert not moves_of(text, (cfg.n_layers, n_pages, PAGE) + tail)
    assert not moves_of(text, (b, seq) + tail, tail)


def test_prefill_bucket_shard4(topo, as_tpu, monkeypatch):
    """One --shard 4 prefill bucket (PR 43): the member's view (8 query
    heads over 2 kv heads) takes the prefill kernel inside the shard_map,
    the plan read from the member-local shapes, and moves neither its
    slice of the pool nor a gathered view of the slot's table."""
    text, cfg, lcfg, n_pages, _, seq = shard4_program(
        topo, monkeypatch, "prefill-1024")
    assert "all-reduce" in text
    assert [name for name, _, _ in mosaic_kernels(text)] == ["_prefill_kernel"]
    tail = (lcfg.n_kv_heads, cfg.head_dim)
    assert not moves_of(text, (cfg.n_layers, n_pages, PAGE) + tail)
    assert not moves_of(text, (1, seq) + tail, tail)
    assert not scores_of(text, 1024, seq)


def test_byte_buffer_past_int32_is_refused(topo, no_compile_cache):
    """Why plane.stage_source sends a >2 GiB byte view on a TPU to the
    whole-read path: the chip's compiler refuses the chunk-landing
    dynamic-update-slice once its indices need 64 bits. If this ever
    compiles, the refusal in stage_source can go."""
    from oim_tpu.data import plane

    chip = SingleDeviceSharding(topo.devices[0])
    buf = jax.ShapeDtypeStruct((3 << 30,), jnp.uint8, sharding=chip)
    chunk = jax.ShapeDtypeStruct((64 << 20,), jnp.uint8, sharding=chip)
    with jax.enable_x64(True):
        off = jax.ShapeDtypeStruct((), jnp.int64, sharding=chip)
        with pytest.raises(Exception, match="exceed 32-bits"):
            plane._updater(True).lower(buf, chunk, off).compile()
    # Under int32 the same program is fine.
    small = jax.ShapeDtypeStruct((1 << 30,), jnp.uint8, sharding=chip)
    off = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    plane._updater(False).lower(small, chunk, off).compile()


# -- the latent pool of joyai-llm-flash.longctx, at its own sizes ------------


def latent_cell(sharding):
    from benchmarks import common
    from benchmarks.runners import serve_family

    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs", "joyai-llm-flash.json"))
    model = serve_family.model_dict(config, "serve")
    cfg = serve_family.program_config(model)
    sizes = config["serve"]
    params = shaped(jax.eval_shape(
        lambda: llama.init(jax.random.PRNGKey(0), cfg)), sharding)
    pool = shaped(jax.eval_shape(lambda: gen.init_page_pool(
        cfg, sizes["kv_pool_tokens"] // PAGE + 1, PAGE)), sharding)
    return cfg, params, pool, sizes, model["max_seq"]


def test_latent_widths_are_the_published_ones(topo):
    cfg, params, pool, sizes, seq = latent_cell(
        SingleDeviceSharding(topo.devices[0]))
    assert dataclasses.replace(cfg, n_layers=40, max_seq=131072) \
        == llama.JOYAI_LLM_FLASH
    assert pool["kv"].shape == (5, 18433, 16, 640) and set(pool) == {"kv"}
    held = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves((params, pool)))
    assert 12.9e9 < held < 13.1e9  # 11.12 GB of weights + 1.89 GB of pool


def test_latent_decode_updates_the_pool_in_place(topo, as_tpu):
    """The decode program of joyai-llm-flash.longctx: the latent kernel is
    in it (once a layer group) and reads the pool where it lies; nothing
    copies, restacks or gathers an array of the pool's shape (the scatter
    of the step's 32 entries aliases the donated buffer), no gathered view
    exists ([B, S, width], or a block [B * pages, 16, width] of every
    row's pages), no f32 [B, H, block] scores at the kernel's block, and
    arguments + temporaries fit the chip."""
    from benchmarks import common

    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, pool, b, seq = serving_program(chip, "longctx", "step")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    kernels = mosaic_kernels(text)
    assert [name for name, _, _ in kernels] == ["_latent_kernel"] * 2, \
        "one call in the dense layer's loop, one in the expert layers'"
    # The benchmark's readers tell operations by the text of their HLO
    # line: latent_kernel_roofline's pattern finds the two calls and no
    # other instruction; latent_attn_roofline's (a while whose carry is
    # the online softmax's) finds the two loops that hold them, as it
    # found the jax.numpy form's: the metric must not lose its operation.
    lines = [re.sub(r"^\s*(ROOT )?", "", line) for line in text.splitlines()]
    found = {}
    for metric in ("latent_kernel_roofline", "latent_attn_roofline"):
        op = re.compile(common.metric_spec(common.ROOT, metric)["args"]["op"])
        found[metric] = [line for line in lines if op.search(line)]
    assert sorted(found["latent_kernel_roofline"]) == sorted(
        line for _, line, _ in kernels)
    assert len(found["latent_attn_roofline"]) == 2
    for line in found["latent_attn_roofline"]:  # each holds one call
        body = re.search(r"body=(%[\w.\-]+)", line).group(1)
        held = text[text.index(f"\n{body} "):]
        assert held[:held.index("\n}")].count("tpu_custom_call") == 1
    assert not moves_of(text, pool["kv"].shape)
    assert not moves_of(text, (b, seq, 640), (640,))
    # Positions a block: the kernel's, and the jax.numpy form's.
    for block in (latent_attention.BLOCK_TOKENS, latent_attention.KEY_BLOCK):
        assert not moves_of(
            text, (b * block // PAGE, PAGE, 640), (PAGE, 640))
        if block != cfg.kv_lora_rank:  # [B, H, rank] f32 is the carried sum
            assert f"f32[{b},{cfg.n_heads},{block}]" not in text
    assert mem.alias_size_in_bytes >= math.prod(pool["kv"].shape) * 2
    assert mem.temp_size_in_bytes < 64 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


@pytest.mark.parametrize("bucket", [2048, 64])
def test_latent_prefill_chunk_carries_the_pool(topo, as_tpu, bucket):
    """A prefill chunk (the configuration's, and a last piece's small
    bucket): the pool in place, no [H, T, S] score array (blockwise over
    key blocks: 8.6 GB in float32 at 32 heads, 2048 x 32768), one row of
    logits, and arguments + temporaries inside 15.75 GB."""
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, pool, _, seq = serving_program(
        chip, "longctx", f"prefill-{bucket}")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert not moves_of(text, pool["kv"].shape)
    assert f"f32[{cfg.n_heads},{bucket},{seq}]" not in text
    assert f"f32[1,{cfg.n_heads},{bucket},{seq}]" not in text
    if bucket != cfg.dim:  # [dim, vocab] is the head itself
        assert f"f32[{bucket},{cfg.vocab}]" not in text  # the last row only
        assert f"f32[1,{bucket},{cfg.vocab}]" not in text
    assert mem.temp_size_in_bytes < 1.25 * (1 << 30)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


LATENT_LADDERS = {2048: (128,), 1024: (128,), 128: (128,)}


@pytest.mark.parametrize("bucket", sorted(LATENT_LADDERS))
def test_latent_slice_sizes_its_expert_products_to_the_rows(
        topo, as_tpu, bucket):
    """PR 41: a slice of joyai-llm-flash.longctx (the chunk, and two last
    pieces) runs its 256 experts' products batched at a capacity,
    [256, C, .] a rung of ``moe.capacity_ladder``, over this layer's leaves
    read where they lie in the stack: no copy, slice or re-layout of an
    expert leaf is written (0.8 GB, 1 ms a leaf; PR 28 paid 29 ms a step
    for such a slice), the grouped products (over the rows past the
    capacity) stand in the last rung only (absent where the capacity is a
    token's worth), and the rungs' temporaries fit beside the pool."""
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, pool, _, seq = serving_program(
        chip, "longctx", f"prefill-{bucket}")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    L, E, D, F, k = (cfg.n_expert_layers, cfg.n_experts, cfg.dim,
                     cfg.moe_intermediate_size, cfg.moe_top_k)
    ladder = moe.capacity_ladder(bucket, cfg.moe)
    assert ladder == LATENT_LADDERS[bucket] and (L, E, D, F) == (
        4, 256, 2048, 768)
    assert not materialized(text, [(E, D, F), (E, F, D), (1, E, D, F),
                                   (1, E, F, D)])
    for leaf in ((L, E, D, F), (E, D, F), (L, E, F, D), (E, F, D)):
        assert not moves_of(text, leaf)
    for capacity in ladder:  # a bounded rung: three batched products
        for width in (F, D):
            assert re.search(rf"= bf16\[{E},{capacity},{width}\]\S* "
                             r"convolution\(", text)
    calls = re.findall(r"%ragged-dot[\w.\-]* = bf16\[(\d+),(\d+)\]", text)
    if ladder[-1] < bucket:
        assert sorted(calls) == sorted(
            [(str(k * bucket), str(F))] * 2 + [(str(k * bucket), str(D))])
        assert last_rung_only(text)
    else:
        assert not calls
    assert text.count(" conditional(") == (len(ladder) > 1 or bool(calls))
    assert not moves_of(text, pool["kv"].shape)
    assert mem.temp_size_in_bytes < 1.25 * (1 << 30)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


# -- which expert dispatch an inference program runs (generate._no_drop) -----
# PR 31: a capacity-padded expert configuration runs dropless in a call of
# DROPLESS_FROM_TOKENS tokens or more. Dense and dropless-by-configuration
# models leave the rule at its first line, and Mixtral's decode step and
# 512-token bucket lie under the crossing: text_hash of each such program
# of the benchmark's serving cells as the parent of PR 31 (commit 9de7509)
# compiled it. An edit that moves
# one of these moves that cell's program: measure the cell, then record the
# new text's hash. (PR 39 recorded five anew: its scopes renumber
# %broadcast_in_dim.N and name GQA's decode kernel %blk_attn.N, %closed_call.N
# before; 3 to 37 lines a text, names only. (longctx, step) did not move: it
# holds the name %mla_decode.N, which latent_kernel_roofline.longctx reads.)
# PR 43 recorded the five GQA prefill entries anew after measuring their
# cells (PERF.md section 6): the Pallas prefill kernel stands where the
# gather and its scores stood. The six step / longctx entries did not move.
PARENT_TEXT = {
    ("chat", "step"): "345dae5792673393",
    ("chat", "prefill-1024"): "0f3e2564b357de8f",
    ("batch", "step"): "11283d27517818e9",
    ("batch", "prefill-512"): "7b107c1cbf9f6a54",
    ("longctx", "step"): "0deb004e1b62525a",
    # PR 41: a whole set's slice takes a capacity ladder from 4 rows an expert
    # (moe.WHOLE_FROM_ROWS: joyai's 128-token bucket and up, so its 2048
    # bucket left this table); its decode step and its 64-token bucket (one
    # and two rows an expert) are the parent's, and so are the held shares'
    # programs, whose ladder did not move (parent 364d93e).
    ("longctx", "prefill-64"): "729e256973e5a89c",
    ("agentbatch", "step"): "7fb8a848123f98d9",
    ("agentbatch", "prefill-1024"): "10e6ff5e597902cd",
    ("agentbatch64", "step"): "75d8955b9c14301f",
    ("agentbatch64", "prefill-1024"): "67bab9fc0548b031",
    # Mixtral's largest bucket sends an expert 512 rows, past
    # moe.WHOLE_UP_TO_ROWS: the grouped products alone, the parent's text.
    ("batch", "prefill-2048"): "64243e9ff7ec0d2b",
}
def test_program_text_drops_what_names_a_checkout():
    text = """HloModule jit_step, is_scheduled=true

FileNames
1 "/root/repo/oim_tpu/models/generate.py"

StackFrames
1 {file_location_id=1 parent_frame_id=1}

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0), metadata={op_name="p" stack_frame_id=7}
  ROOT %add.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(step)/add" \
source_file="/root/repo/x.py" source_line=3}
}"""
    got = program_text(text)
    assert "/root/repo" not in got and "metadata" not in got
    assert "ROOT %add.1 = f32[4]{0} add(%p, %p)" in got
    assert got == program_text(text.replace("/root/repo", "/tmp/other")
                               .replace("source_line=3", "source_line=99"))
    assert got != program_text(text.replace("add(%p, %p)", "multiply(%p, %p)"))


@pytest.mark.parametrize("cell,program", sorted(PARENT_TEXT))
def test_programs_outside_the_choice_are_the_parents(
        topo, as_tpu, cell, program):
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, *_ = {"agentbatch": hybrid_program,
                         "agentbatch64": kda_program}.get(
        cell, functools.partial(serving_program, cell=cell))(
            chip, program=program)
    text = compiled.as_text()
    tokens = int(program.partition("-")[2] or 0)  # a step: under any crossing
    if cfg.moe_dispatch != "ragged" and tokens < gen.DROPLESS_FROM_TOKENS:
        assert "ragged-dot" not in text  # Mixtral under the crossing, Mistral
    assert text_hash(text) == PARENT_TEXT[cell, program]


def last_rung_only(text: str) -> bool:
    """Whether every grouped product of the program stands in the LAST
    branch of a conditional (the ladder's fallback), and nowhere else."""
    last = {m.group(1) for m in re.finditer(
        r"branch_computations=\{[^}]*?(%[\w.\-]+)\}", text)}
    inside, ok = None, True
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):
            inside = line.split(" ", 1)[0]
        elif re.match(r"\s*(ROOT )?%ragged-dot[\w.\-]* = ", line):
            ok = ok and inside in last
    return ok and bool(last)


def test_last_rung_only_reads_the_hlo():
    text = """
%rung.1 (a: f32[4]) -> f32[4] {
  ROOT %convolution.1 = f32[4]{0} convolution(%a, %a)
}
%rung.2 (a: f32[4]) -> f32[4] {
  ROOT %ragged-dot-none.1 = bf16[8,4]{1,0} custom-call(%a)
}
ENTRY %main (p: f32[4]) -> f32[4] {
  ROOT %conditional.1 = f32[4]{0} conditional(%i, %p, %p), \
branch_computations={%rung.1, %rung.2}
}"""
    assert last_rung_only(text)
    assert not last_rung_only(text.replace("{%rung.1, %rung.2}",
                                           "{%rung.2, %rung.1}"))
    assert not last_rung_only(text.replace(
        "ROOT %conditional", "%ragged-dot-none.2 = bf16[8,4]{1,0} "
        "custom-call(%p)\n  ROOT %conditional"))


@pytest.mark.parametrize("bucket", [1024, 2048])
def test_mixtral_prefill_runs_dropless(topo, as_tpu, bucket):
    """The batch cell's largest prefill bucket (a gap that holds one is its
    itl_p95_ms) and the configuration's largest, since PR 41. The 1024
    bucket: one capacity of twice the rows uniform routing sends an expert
    (512: three batched products [8, 512, .], half the padded form), and
    behind it the same with the rows past the capacity through three
    grouped products a layer (handed k x N rows, of which they compute the
    groups'). The 2048 bucket sends an expert 512 rows, past
    ``moe.WHOLE_UP_TO_ROWS``: three grouped products over k x N rows alone,
    as before. Neither holds an [E, N, F] or [E, N, D] operand, and the
    expert leaves [L, E, D, F] go into every product where they lie: none
    is copied, none sliced a layer at a time (a slice handed to a custom
    call is a copy: PR 28's 29 ms a step)."""
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, *_ = serving_program(chip, "batch", f"prefill-{bucket}")
    assert bucket >= gen.DROPLESS_FROM_TOKENS and cfg.moe_dispatch == "gather"
    text, mem = compiled.as_text(), compiled.memory_analysis()
    L, E, D, F, k = (cfg.n_layers, cfg.n_experts, cfg.dim, cfg.mlp_dim,
                     cfg.moe_top_k)
    ladder = moe.capacity_ladder(bucket, gen._no_drop(cfg, bucket).moe)
    assert ladder == {1024: (512,), 2048: ()}[bucket]
    calls = re.findall(r"%ragged-dot[\w.\-]* = bf16\[(\d+),(\d+)\]", text)
    assert sorted(calls) == sorted(
        [(str(k * bucket), str(F))] * 2 + [(str(k * bucket), str(D))])
    assert text.count(" conditional(") == len(ladder)
    assert last_rung_only(text) == bool(ladder)
    for width in (F, D):
        assert f"[{E},{bucket},{width}]" not in text
        for capacity in ladder:
            assert re.search(rf"= bf16\[{E},{capacity},{width}\]\S* "
                             r"convolution\(", text)
    assert not materialized(text, [(E, D, F), (E, F, D), (1, E, D, F),
                                   (1, E, F, D)])
    for leaf in ((L, E, D, F), (E, D, F), (L, E, F, D), (E, F, D)):
        assert not moves_of(text, leaf)
    if bucket == 1024:  # the parent's padded form held 288 MB here
        assert mem.temp_size_in_bytes < 288 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


# -- a hybrid of mixers with recurrent state beside the pages (PR 33) --------
# nemotron-3-nano-30b.agentbatch at the cell's own sizes: 52 layers, one rank
# of eight, 48 slots of state (2.36 GB) beside 12 289 pages (1.21 GB).

def hybrid_cell(sharding):
    from benchmarks import common
    from benchmarks.runners import serve_hybrid

    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs", "nemotron-3-nano-30b.json"))
    model = serve_hybrid.model_dict(config, "serve")
    cfg = serve_hybrid.program_config(model)
    sizes = config["serve"]
    params = shaped(jax.eval_shape(
        lambda: llama.init(jax.random.PRNGKey(0), cfg)), sharding)
    pool = shaped(jax.eval_shape(lambda: {
        **gen.init_page_pool(cfg, sizes["kv_pool_tokens"] // PAGE + 1, PAGE),
        **gen.init_state_pool(cfg, sizes["max_batch"])}), sharding)
    return cfg, params, pool, sizes, model["max_seq"]


def hybrid_program(chip, program):
    from oim_tpu.serve.engine import _target_programs

    if ("hybrid", program) not in _COMPILED:
        cfg, params, pool, sizes, seq = hybrid_cell(chip)
        step, prefill = _target_programs(cfg, PAGE, seq)
        if program == "step":
            lowered = step.lower(params, pool, *step_operands(
                chip, sizes["max_batch"], seq))
        else:
            lowered = prefill.lower(
                params, pool,
                *prefill_operands(chip, int(program.split("-")[1]), seq),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=chip))  # the slot
        _COMPILED["hybrid", program] = lowered.compile(), cfg, pool, sizes
    return _COMPILED["hybrid", program]


def copies_of(text, shape):
    return [m for m in moves_of(text, shape) if m[0].startswith("copy")]


def test_hybrid_widths_are_the_published_ones(topo):
    cfg, params, pool, sizes, seq = hybrid_cell(
        SingleDeviceSharding(topo.devices[0]))
    assert dataclasses.replace(cfg, expert_rank="", vocab=131072,
                               max_seq=262144) == llama.NEMOTRON_3_NANO_30B
    assert set(pool) == {"k", "v", "ssm", "conv"}
    assert pool["k"].shape == (6, 12289, 16, 2, 128)
    assert pool["ssm"].shape == (23, 48, 64, 64, 128) \
        and pool["ssm"].dtype == jnp.float32
    assert pool["conv"].shape == (23, 48, 3 * 6144)
    held = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves((params, pool)))
    assert 14.2e9 < held < 14.5e9  # 10.78 of weights + 2.36 of state + 1.21


def test_hybrid_decode_updates_state_and_pool_in_place(topo, as_tpu):
    """The decode program: the Pallas kernel in each of the six attention
    layers (a group of 16 query heads a key/value head), state and pages
    aliased to the donated buffers and neither copied, no copy of an expert
    leaf (held at whole lanes: moe.stored_width), the held share's products
    batched (no grouped product at 48 tokens), seven scanned runs, and
    arguments + temporaries inside the chip."""
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, pool, sizes = hybrid_program(chip, "step")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert [name for name, _, _ in mosaic_kernels(text)] == ["_paged_kernel"] * 6
    for leaf in pool.values():
        assert not copies_of(text, leaf.shape)
    assert not copies_of(text, (23, 16, 2688, 1920))
    assert "ragged-dot" not in text
    assert text.count(" while(") == 7
    state = sum(math.prod(pool[k].shape) * pool[k].dtype.itemsize
                for k in pool)
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < 128 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def materialized(text: str, shapes) -> list:
    """Instructions OUTSIDE fused computations that produce an array of one
    of ``shapes`` by other means than naming it (a parameter, a tuple's
    element, a bitcast): each is a buffer of that size written. A slice
    inside the fusion of the product that reads it is read where it lies."""
    wanted = {",".join(str(d) for d in shape) for shape in shapes}
    found, fused = [], False
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):  # a computation's header
            fused = line.startswith(("%fused_computation", "%bitcast_fusion"))
        m = _INSTRUCTION.match(line)
        if fused or not m:
            continue
        dims = re.match(r"\w+\[([\d,]+)\]", m.group(2))
        if dims and dims.group(1) in wanted and m.group(3) not in (
                "parameter", "get-tuple-element", "bitcast"):
            found.append((m.group(3), m.group(1)))
    return found


HYBRID_PREFILL_TEMP = 256 << 20  # bytes; the 1024 bucket: 127.0 MB, the parent's 1111.2


@pytest.mark.parametrize("bucket", [1024, 512, 64, 32])
def test_hybrid_prefill_slice_carries_state_and_pool(topo, as_tpu, bucket):
    """A prefill slice (the configuration's chunk, and a short last piece):
    the slot's rows of the state cut out and written back in place, the
    whole state never copied, one row of logits, the expert leaves whole,
    and arguments + temporaries inside 15.75 GB at 48 slots (what the
    configuration's max_batch rests on). The six attention layers attend
    the pages where they lie through the Pallas prefill kernel (PR 43: no
    gather of the slot's table, no re-layout of the two-head pool around
    it, which PR 33 compiled as ten copies of the pool in the 1024 bucket,
    no f32[1, 2, 16, T, 8192] scores: the 1024 bucket's temporaries were
    1.16 GB of the compiled 15.51). The held share's products (PR 35) are
    compiled once a rung: batched
    products [16, C, .] a capacity of ``moe.capacity_ladder``, which read
    this layer's leaves where they lie in the stack (no buffer of a
    layer's leaves is written), and LAST the grouped products over every
    assignment row, k x N, unless a capacity of N, which always holds,
    stands before them (the 512 bucket: a prompt's shorter last piece)."""
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, pool, sizes = hybrid_program(chip, f"prefill-{bucket}")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    for leaf in ("ssm", "conv"):
        assert not copies_of(text, pool[leaf].shape)
    assert reads_pages_in_place(text, cfg, pool, bucket, 8192, calls=6)
    assert not copies_of(text, (23, 16, 2688, 1920))
    assert not materialized(text, [(16, 2688, 1920), (16, 1920, 2688),
                                   (1, 16, 2688, 1920), (1, 16, 1920, 2688)])
    assert f"f32[{bucket},{cfg.vocab}]" not in text
    calls = re.findall(r"%ragged-dot[\w.\-]* = bf16\[(\d+),(\d+)\]", text)
    ladder = moe.capacity_ladder(bucket, cfg.moe)
    if bucket > 64:
        assert ladder == (256, 512)
        whole = {(str(6 * bucket), "1920"), (str(6 * bucket), "2688")}
        assert set(calls) == (whole if ladder[-1] < bucket else set())
        for capacity in ladder:  # a bounded rung: two batched products
            for width in (1920, 2688):
                assert re.search(rf"= bf16\[16,{capacity},{width}\]\S* "
                                 r"convolution\(", text)
        bodies = text.count(" conditional(")  # scanned runs + single layers
        assert 0 < bodies <= 23 and len(calls) in (0, 2 * bodies)
    else:             # few tokens: every held expert over every token
        assert not calls and " conditional(" not in text
    assert mem.alias_size_in_bytes >= 3.5e9
    assert mem.temp_size_in_bytes < HYBRID_PREFILL_TEMP
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


# -- a second kind of recurrent state: KDA beside gated attention (PR 37) ------
# solar-open2-250b.agentbatch64 at the cell's own sizes: one period of four
# layers, one rank of eight, 64 slots of state (0.83 GB) beside 32 769 pages
# (2.15 GB) and 6.63 GB of weights.

def kda_cell(sharding):
    from benchmarks import common
    from benchmarks.runners import serve_kda

    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs", "solar-open2-250b.json"))
    model = serve_kda.model_dict(config, "serve")
    cfg = serve_kda.program_config(model)
    sizes = config["serve"]
    params = shaped(jax.eval_shape(
        lambda: llama.init(jax.random.PRNGKey(0), cfg)), sharding)
    pool = shaped(jax.eval_shape(lambda: {
        **gen.init_page_pool(cfg, sizes["kv_pool_tokens"] // PAGE + 1, PAGE),
        **gen.init_state_pool(cfg, sizes["max_batch"])}), sharding)
    return cfg, params, pool, sizes, model["max_seq"]


def kda_program(chip, program):
    from oim_tpu.serve.engine import _target_programs

    if ("kda", program) not in _COMPILED:
        cfg, params, pool, sizes, seq = kda_cell(chip)
        step, prefill = _target_programs(cfg, PAGE, seq)
        if program == "step":
            lowered = step.lower(params, pool, *step_operands(
                chip, sizes["max_batch"], seq))
        else:
            lowered = prefill.lower(
                params, pool,
                *prefill_operands(chip, int(program.split("-")[1]), seq),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=chip))  # the slot
        _COMPILED["kda", program] = lowered.compile(), cfg, pool, sizes
    return _COMPILED["kda", program]


def test_kda_widths_are_the_published_ones(topo):
    cfg, params, pool, sizes, seq = kda_cell(
        SingleDeviceSharding(topo.devices[0]))
    assert dataclasses.replace(
        cfg, n_layers=48, gqa_layers=tuple(range(0, 48, 4)), expert_rank="",
        vocab=196608, max_seq=1048576) == llama.SOLAR_OPEN2_250B
    assert set(pool) == {"k", "v", "kda", "kda_conv"}
    assert pool["k"].shape == (1, 32769, 16, 8, 128)
    assert pool["kda"].shape == (3, 64, 64, 128, 128) \
        and pool["kda"].dtype == jnp.float32
    assert pool["kda_conv"].shape == (3, 64, 3 * 24576)
    weights = sum(math.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    held = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(pool))
    assert 6.62e9 < weights < 6.64e9       # 3.308 B parameters, 8 K in float32
    assert 2.97e9 < held < 2.99e9          # 0.83 of state + 2.15 of pages


def test_kda_decode_updates_state_and_pool_in_place(topo, as_tpu):
    """The decode program at 64 slots: the Pallas kernel in the one
    attention layer (a group of 8 query heads a key/value head, 64 heads),
    state and pages aliased to the donated buffers and neither copied, no
    copy of an expert leaf (1280 is ten whole lanes), the held share's three
    products batched (no grouped product at 64 tokens), the period's three
    KDA + expert blocks one scanned run, and arguments + temporaries inside
    the chip."""
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, pool, sizes = kda_program(chip, "step")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert [name for name, _, _ in mosaic_kernels(text)] == ["_paged_kernel"]
    for name, leaf in pool.items():
        moved = copies_of(text, leaf.shape)
        if name == "kda_conv":
            # The 28 MB of windows are updated in the chip's nearer memory
            # (layout S(1)) and moved out once, asynchronously: a
            # copy-start / copy-done pair, no copy the step waits on.
            moved = [m for m in moved if m[0] != "copy-start"]
        assert not moved
    assert not copies_of(text, (4, 40, 4096, 1280))
    assert "ragged-dot" not in text
    assert text.count(" while(") == 1
    state = sum(math.prod(pool[k].shape) * pool[k].dtype.itemsize
                for k in pool)
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < 128 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


@pytest.mark.parametrize("bucket", [1024, 64])
def test_kda_prefill_slice_carries_state_and_pool(topo, as_tpu, bucket):
    """A prefill slice (the configuration's chunk, and a short last piece):
    the slot's rows of the state cut out and written back in place, the
    whole state never copied, one row of logits, the expert leaves whole,
    the pairwise decay of the chunked delta rule ([chunks, heads, C, C, d]
    float32: 0.5 GB at 1024 tokens) inside the temporaries, and arguments +
    temporaries inside 15.75 GB at 64 slots (what the configuration's
    max_batch rests on). The one attention layer attends the pages where
    they lie through the Pallas prefill kernel (PR 43): the 1024 bucket's
    largest temporary, its scores f32[64, 1024, 8192] = 2.15 GB, is gone
    (2090.0 MB of temporaries -> 335.5), and what is left is the KDA
    layers' own."""
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, pool, sizes = kda_program(chip, f"prefill-{bucket}")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert reads_pages_in_place(text, cfg, pool, bucket, 8192, calls=1)
    for leaf in ("kda", "kda_conv"):
        assert not copies_of(text, pool[leaf].shape)
    assert not copies_of(text, (4, 40, 4096, 1280))
    assert not materialized(text, [(40, 4096, 1280), (40, 1280, 4096),
                                   (1, 40, 4096, 1280), (1, 40, 1280, 4096)])
    assert f"f32[{bucket},{cfg.vocab}]" not in text
    calls = re.findall(r"%ragged-dot[\w.\-]* = bf16\[(\d+),(\d+)\]", text)
    ladder = moe.capacity_ladder(bucket, cfg.moe)
    if bucket > 64:
        assert ladder == (256, 512)
        assert set(calls) == {(str(8 * bucket), "1280"), (str(8 * bucket), "4096")}
        for capacity in ladder:  # a bounded rung: three batched products
            for width in (1280, 4096):
                assert re.search(rf"= bf16\[40,{capacity},{width}\]\S* "
                                 r"convolution\(", text)
    else:             # few tokens: every held expert over every token
        assert not calls and " conditional(" not in text
    assert mem.alias_size_in_bytes >= 2.9e9
    assert mem.temp_size_in_bytes < 0.5 * (1 << 30)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


HYBRID_ARGUMENT_BYTES = {"step": 14_350_592_512, "prefill-1024": 14_350_500_352}


def test_the_hybrid_cells_arguments_are_the_parents(topo, as_tpu):
    """nemotron-3-nano-30b's programs take the bytes they took before the
    state pool carried a second kind (the parent's compile, 6cdc048: 14.35
    GB of arguments, the pool aliased whole)."""
    chip = SingleDeviceSharding(topo.devices[0])
    for program in ("step", "prefill-1024"):
        compiled, cfg, pool, sizes = hybrid_program(chip, program)
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes == HYBRID_ARGUMENT_BYTES[program]
        assert mem.alias_size_in_bytes == 3_564_011_520


# -- a third kind of recurrent state beside a LATENT pool (PR 42) --------------
# gigachat35-432b-a28b.reasonbatch64 at the cell's own sizes: the leading
# dense layer and one period of four, one rank of sixteen, 64 slots of
# GatedDeltaNet state (1.10 GB) beside 65 537 latent pages (1.34 GB) and
# 9.46 GB of weights.

def gdn_cell(sharding):
    from benchmarks import common
    from benchmarks.runners import serve_gdn

    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs", "gigachat35-432b-a28b.json"))
    model = serve_gdn.model_dict(config, "serve")
    cfg = serve_gdn.program_config(model)
    sizes = config["serve"]
    params = shaped(jax.eval_shape(
        lambda: llama.init(jax.random.PRNGKey(0), cfg)), sharding)
    pool = shaped(jax.eval_shape(lambda: {
        **gen.init_page_pool(cfg, sizes["kv_pool_tokens"] // PAGE + 1, PAGE),
        **gen.init_state_pool(cfg, sizes["max_batch"])}), sharding)
    return cfg, params, pool, sizes, model["max_seq"]


def gdn_program(chip, program):
    from oim_tpu.serve.engine import _target_programs

    if ("gdn", program) not in _COMPILED:
        cfg, params, pool, sizes, seq = gdn_cell(chip)
        step, prefill = _target_programs(cfg, PAGE, seq)
        if program == "step":
            lowered = step.lower(params, pool, *step_operands(
                chip, sizes["max_batch"], seq))
        else:
            lowered = prefill.lower(
                params, pool,
                *prefill_operands(chip, int(program.split("-")[1]), seq),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=chip))  # the slot
        _COMPILED["gdn", program] = lowered.compile(), cfg, pool, sizes
    return _COMPILED["gdn", program]


def test_gdn_widths_are_the_published_ones(topo):
    cfg, params, pool, sizes, seq = gdn_cell(
        SingleDeviceSharding(topo.devices[0]))
    assert dataclasses.replace(
        cfg, hybrid_override_pattern="", n_layers=40, first_k_dense_replace=3,
        full_attention_layers=tuple(range(3, 40, 4)), expert_rank="",
        vocab=128256, max_seq=262144, head_dim=112) == llama.GIGACHAT35_432B
    assert cfg.pattern == "GD*EGEGEGE"
    assert set(pool) == {"kv", "gdn", "gdn_conv"}
    assert pool["kv"].shape == (1, 65537, 16, 640)
    assert pool["gdn"].shape == (4, 64, 64, 128, 128) \
        and pool["gdn"].dtype == jnp.float32
    assert pool["gdn_conv"].shape == (4, 64, 3 * 16384)
    weights = sum(math.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    held = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(pool))
    assert 9.45e9 < weights < 9.48e9       # 4.73 B parameters
    assert 2.43e9 < held < 2.45e9          # 1.10 of state + 1.34 of pages


def test_gdn_decode_updates_state_and_pool_in_place(topo, as_tpu):
    """The decode program at 64 slots: the latent Pallas kernel in the one
    latent layer (64 heads), state and pages aliased to the donated buffers
    and neither copied, no copy of an expert leaf (2048 is sixteen whole
    lanes) nor of the head (16 032 rows are not whole lanes), the held
    share's three products batched (no grouped product at 64 tokens), the
    period's three expert + GatedDeltaNet blocks one scanned run, and
    arguments + temporaries inside the chip."""
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, pool, sizes = gdn_program(chip, "step")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert [name for name, _, _ in mosaic_kernels(text)] == ["_latent_kernel"]
    for name, leaf in pool.items():
        moved = copies_of(text, leaf.shape)
        if name == "gdn_conv":  # as kda_conv: moved out once, asynchronously
            moved = [m for m in moved if m[0] != "copy-start"]
        assert not moved, (name, moved)
    assert not copies_of(text, (4, 16, 7168, 2048))
    assert not copies_of(text, (7168, 16032))
    assert "ragged-dot" not in text
    assert text.count(" while(") == 2  # the scanned run, the kernel's spans
    state = sum(math.prod(pool[k].shape) * pool[k].dtype.itemsize
                for k in pool)
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < 256 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


@pytest.mark.parametrize("bucket", [1024, 64])
def test_gdn_prefill_slice_carries_state_and_pool(topo, as_tpu, bucket):
    """A prefill slice (the configuration's chunk, and a short last piece):
    the slot's rows of the state cut out and written back in place, the
    whole state never copied, one row of logits, the expert leaves whole,
    the chunked delta rule's [chunks, heads, C, C] decays (no per-channel
    [.., C, C, d]) inside the temporaries, and arguments + temporaries
    inside 15.75 GB at 64 slots (what the configuration's max_batch rests
    on)."""
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, pool, sizes = gdn_program(chip, f"prefill-{bucket}")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    for leaf in ("gdn", "gdn_conv"):
        assert not copies_of(text, pool[leaf].shape)
    assert not copies_of(text, (4, 16, 7168, 2048))
    assert not materialized(text, [(16, 7168, 2048), (16, 2048, 7168),
                                   (1, 16, 7168, 2048), (1, 16, 2048, 7168)])
    assert f"f32[{bucket},{cfg.vocab}]" not in text
    assert not re.search(r"f32\[\d+,\d+,64,64,64,128\]", text)
    calls = re.findall(r"%ragged-dot[\w.\-]* = bf16\[(\d+),(\d+)\]", text)
    ladder = moe.capacity_ladder(bucket, cfg.moe)
    if bucket > 64:
        assert ladder == (256, 512)
        assert set(calls) == {(str(8 * bucket), "2048"), (str(8 * bucket), "7168")}
        for capacity in ladder:  # a bounded rung: three batched products
            for width in (2048, 7168):
                assert re.search(rf"= bf16\[16,{capacity},{width}\]\S* "
                                 r"convolution\(", text)
    else:             # few tokens: every held expert over every token
        assert not calls and " conditional(" not in text
    assert mem.alias_size_in_bytes >= 2.4e9
    assert mem.temp_size_in_bytes < (2.5 if bucket == 1024 else 0.5) * (1 << 30)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


# -- compressed convolutional attention: a TAIL beside the GQA pool (PR 45) ----
# zaya1-8b.reasonbatch64-cca at the cell's own sizes: 14 of 40 layers, all 16
# experts, the whole tied 262 272-row table (6.89 GB), 26 625 pages of 16
# positions over 2 key-value heads of 128 (6.11 GB) and 64 slots' tails (9.6
# MB) beside them.

def cca_cell(sharding):
    from benchmarks import common
    from benchmarks.runners import serve_cca

    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs", "zaya1-8b.json"))
    model = serve_cca.model_dict(config, "serve")
    cfg = serve_cca.program_config(model)
    sizes = config["serve"]
    params = shaped(jax.eval_shape(
        lambda: llama.init(jax.random.PRNGKey(0), cfg)), sharding)
    pool = shaped(jax.eval_shape(lambda: {
        **gen.init_page_pool(cfg, sizes["kv_pool_tokens"] // PAGE + 1, PAGE),
        **gen.init_state_pool(cfg, sizes["max_batch"])}), sharding)
    return cfg, params, pool, sizes, model["max_seq"]


def cca_program(chip, program):
    from oim_tpu.serve.engine import _target_programs

    if ("cca", program) not in _COMPILED:
        cfg, params, pool, sizes, seq = cca_cell(chip)
        step, prefill = _target_programs(cfg, PAGE, seq)
        if program == "step":
            lowered = step.lower(params, pool, *step_operands(
                chip, sizes["max_batch"], seq))
        else:
            lowered = prefill.lower(
                params, pool,
                *prefill_operands(chip, int(program.split("-")[1]), seq),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=chip))  # the slot
        _COMPILED["cca", program] = lowered.compile(), cfg, pool, sizes
    return _COMPILED["cca", program]


def test_cca_widths_are_the_published_ones(topo):
    cfg, params, pool, sizes, seq = cca_cell(
        SingleDeviceSharding(topo.devices[0]))
    assert dataclasses.replace(
        cfg, n_layers=40, max_seq=131072) == llama.ZAYA1_8B
    assert cfg.pattern == "CE" * 14 and cfg.rope_dim == 64
    assert set(pool) == {"k", "v", "cca_tail"}
    assert pool["k"].shape == pool["v"].shape == (14, 26625, 16, 2, 128)
    assert pool["cca_tail"].shape == (14, 64, 2688) \
        and pool["cca_tail"].dtype == jnp.float32
    assert "lm_head" not in params and params["embed"].shape == (262272, 2048)
    weights = sum(math.prod(x.shape) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    held = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(pool))
    assert 6.88e9 < weights < 6.92e9       # 3.44 B parameters, one table
    assert 6.11e9 < held < 6.13e9          # 6.11 of pages + 9.6 MB of tails
    assert (weights + held) / 16e9 > 0.8   # resident: 13.0 of 16 GB


def test_cca_decode_updates_tail_and_pool_in_place(topo, as_tpu):
    """The decode program at 64 slots: GQA's paged Pallas kernel in every
    layer (8 query over 2 key-value heads), pages and tails aliased to the
    donated buffers and neither copied, the 14 layers ONE scanned run that
    carries the router's state, no copy of an expert leaf nor of the table
    (the head contracts the embedding's minor dim where it lies: no second
    1.07 GB), the top-1 products batched at a capacity of a step's 64 rows
    (no grouped product), and arguments + temporaries inside the chip."""
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, pool, sizes = cca_program(chip, "step")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    print("CCA step", mem.temp_size_in_bytes, mem.argument_size_in_bytes,
          mem.alias_size_in_bytes,
          [name for name, _, _ in mosaic_kernels(text)],
          text.count(" while("))
    assert [name for name, _, _ in mosaic_kernels(text)] \
        == ["_paged_kernel"]
    for name, leaf in pool.items():
        assert not copies_of(text, leaf.shape), (name, copies_of(text, leaf.shape))
    assert not copies_of(text, (262272, 2048))
    assert not materialized(text, [(2048, 262272), (262272, 2048)])
    assert not copies_of(text, (14, 16, 2048, 2048))
    assert not materialized(text, [(16, 2048, 2048), (1, 16, 2048, 2048)])
    assert "ragged-dot" not in text
    assert moe.capacity_ladder(64, cfg.moe) == (64,)
    state = sum(math.prod(pool[k].shape) * pool[k].dtype.itemsize
                for k in pool)
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < 512 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


@pytest.mark.parametrize("bucket", [1024, 256])
def test_cca_prefill_slice_carries_tail_and_pool(topo, as_tpu, bucket):
    """A prefill slice (the configuration's chunk, and a short last piece):
    the Pallas flash prefill over the slot's pages, the slot's tail cut out
    and written back in place, pool and tails never copied, one row of
    logits against the table where it lies, the expert leaves whole, the
    top-1 products at ONE capacity with the rows past it through the grouped
    product, and arguments + temporaries inside 15.75 GB at 64 slots and
    425 984 positions (what the configuration's pool rests on)."""
    chip = SingleDeviceSharding(topo.devices[0])
    compiled, cfg, pool, sizes = cca_program(chip, f"prefill-{bucket}")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    print("CCA prefill", bucket, mem.temp_size_in_bytes,
          mem.argument_size_in_bytes, mem.alias_size_in_bytes,
          [name for name, _, _ in mosaic_kernels(text)])
    assert {name for name, _, _ in mosaic_kernels(text)} \
        == {"_prefill_kernel"}
    for name, leaf in pool.items():
        assert not copies_of(text, leaf.shape), (name, copies_of(text, leaf.shape))
    assert not copies_of(text, (262272, 2048))
    assert not materialized(text, [(2048, 262272), (262272, 2048)])
    assert not copies_of(text, (14, 16, 2048, 2048))
    assert f"f32[{bucket},{cfg.vocab}]" not in text
    ladder = moe.capacity_ladder(bucket, cfg.moe)
    assert ladder == (128,)
    assert re.search(r"= bf16\[16,128,2048\]\S* convolution\(", text)
    assert set(re.findall(r"%ragged-dot[\w.\-]* = bf16\[(\d+),(\d+)\]", text)) \
        == {(str(bucket), "2048")}
    assert mem.alias_size_in_bytes >= 6.11e9
    assert mem.temp_size_in_bytes < 512 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
