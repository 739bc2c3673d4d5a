"""Autoregressive decoding for the Llama family: KV cache + sampled/greedy
generation.

New scope relative to the reference (a storage control plane has no
inference path); this completes the model-family API so a checkpoint
trained by oim-trainer is directly servable. TPU-first shape:

- The cache is one [L, B, S, ...] array a leaf of ``Config.cache_leaves``
  (K and V by kv head for GQA, one latent vector a position for latent
  attention) scanned in lockstep with the stacked layer params — one trace per layer regardless
  of depth, like the training path.
- Decode attends over the FULL fixed-size cache with a position mask
  (static shapes; no growing arrays inside jit). Prefill and decode are the
  same function at different T, so there is exactly one cached-forward
  implementation to keep correct.
- The decode loop is a ``lax.scan`` over steps: one compiled program
  generates any number of tokens.

Sharding: the cache dims follow the attention heads, so under TP_SP_RULES
the kv_heads axis shards over "model" exactly like wk/wv; generate() works
unchanged under jit with sharded params.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from oim_tpu.models.llama import (
    RECURRENT_KINDS,
    STATE_KINDS,
    Config,
    _attn_mixer,
    _block,
    _cca_mixer,
    _ffn_mixer,
    _norm,
    _residual,
    head,
    layer_groups,
    router_carry,
    run_pattern,
)
from oim_tpu.ops import cca, latent_attention
from oim_tpu.ops.norms import rmsnorm
from oim_tpu.ops.paged_attention import cache_attention, paged_attention
from oim_tpu.ops.rope import rope_frequencies


def _reduce(x, axis: str | None):
    """Sum a partial projection product over the tensor-parallel mesh
    axis (no-op when unsharded). The ONLY point activations cross ICI
    in the sharded decode path: with wq/wk/wv column-split and
    wo/w_down row-split, every other tensor in a layer is either fully
    local (per-head attention, gated MLP halves) or replicated (the
    residual stream), so one psum after the attention-out projection
    and one after the FFN-down projection reassemble the exact sums
    the unsharded matmuls compute — same terms, reassociated — which
    is why greedy decode stays token-identical under sharding (see
    doc/architecture.md "Sharded decode")."""
    if axis is None:
        return x
    from oim_tpu.parallel.collectives import psum

    return psum(x, axis)


def shard_config(cfg: Config, n: int) -> Config:
    """The PER-MEMBER view of ``cfg`` on an ``n``-way tensor-parallel
    mesh: 1/n of the query and KV heads (the GQA group size g =
    n_heads/n_kv_heads is preserved, so contiguous head slices keep
    every query head aligned with its own KV head). The returned cfg is
    what the shard_map BODY runs with — reshapes inside
    ``decode_step``/``prefill_into_pages``/``verify_step`` must match
    the member-local array slices, not the global shapes."""
    import dataclasses

    if n < 1:
        raise ValueError(f"shard count must be >= 1, got {n}")
    if n == 1:
        return cfg
    if cfg.pattern:
        raise ValueError(
            "tensor-parallel decode does not support a hybrid pattern yet "
            "(the recurrent state and the mixers have no sharding rules; "
            f"pattern={cfg.pattern!r})")
    if cfg.kv_lora_rank:
        raise ValueError(
            "tensor-parallel decode does not support latent attention yet "
            "(one latent is shared by all heads: the cache cannot split by "
            f"head; kv_lora_rank={cfg.kv_lora_rank})")
    if cfg.n_experts:
        raise ValueError(
            "tensor-parallel decode does not support MoE configs yet "
            f"(n_experts={cfg.n_experts})")
    if cfg.n_heads % n or cfg.n_kv_heads % n:
        raise ValueError(
            f"shard count {n} must divide n_heads ({cfg.n_heads}) and "
            f"n_kv_heads ({cfg.n_kv_heads})")
    return dataclasses.replace(
        cfg, n_heads=cfg.n_heads // n, n_kv_heads=cfg.n_kv_heads // n)


# Tokens in a call (B x T, static at trace time) from which a capacity-padded
# expert configuration runs dropless at inference. One rule on shapes, no
# option (as ``ops/paged_attention._paged_plan``). The expert FFN alone at
# Mixtral-8x7B's widths (E 8, k 2, D 4096, F 14336, 4 layers scanned, bf16)
# on one v5e, ms a layer (the weights' bytes / 819 GB/s are 3.44 ms, the
# needed operations / 197 TFLOP/s 3.66 and 7.33 ms at 1024 and 2048 tokens;
# scripts/expert_dispatch_crossing.py, PERF.md section 6, PR 31):
#
#   tokens     32     64     128    256    512    640    768    896    1024   2048
#   padded     3.98   4.02   4.02   4.56   8.60   10.46  12.79  14.87  16.68  34.48
#   dropless   4.51   5.03   6.05   9.19   10.50  9.18   11.66  10.64  12.97  18.29
#
# Padded computes E x N rows: bound by the weights' bytes while those rows
# are few (86 % of that roofline to 128 tokens), and at 85-88 % of the MXU's
# peak for the rows it computes beyond that. Dropless computes k x N rows,
# but ``lax.ragged_dot`` reaches a third of its roofline or less at 64-256
# rows an expert (28 % at 1024 tokens), so it wins only where padded does
# four times its work at full speed: from 640 tokens, the first size
# measured at which it did. A faster grouped product (a row tile,
# ``megablox.gmm``: ROADMAP S8 (a)) moves this down. Since PR 41 the dropless
# form of a whole set runs batched products at a capacity of N / 2 rows an
# expert here and only the rows past it through the grouped product
# (``moe.capacity_ladder``), and reads (``moe.py``'s tables, two layers
# scanned, the same chip; no expert over the capacity / the fullest at 2.5
# times the uniform rows, as the batch cell's seeded router fills it):
#
#   tokens     256    512           640    1024           2048 (no capacity)
#   dropless   4.33   4.95 / 6.70   6.79   8.93 / 10.82   18.84
#
# which is under the padded row from 256 tokens on (8.89 -> 4.95-6.70 at 512,
# the bucket that is ``itl_p90_ms.batch``): the crossing would move to 256 or
# below. It stays at 640 in PR 41, so that PR's before and after differ by
# one thing; the next issue moves it (ROADMAP S2 (c)).
DROPLESS_FROM_TOKENS = 640


def _no_drop(cfg: Config, n_tokens: int) -> Config:
    """The one rule of expert dispatch at inference, for a call of
    ``n_tokens`` = B x T tokens. MoE inference must not drop tokens:
    training groups tokens per call and caps expert capacity, but an
    inference call's cap would route trained tokens to nothing. Dense
    configurations and dropless ones (``moe_dispatch="ragged"``) pass
    through untouched. A capacity-padded one (``gather``, ``einsum``; its
    router is softmax: sigmoid is dropless by configuration)

    - at ``DROPLESS_FROM_TOKENS`` tokens or more runs ``dispatch="ragged"``:
      every token through its k experts, k x N rows of expert work
      (``moe._dropless``; ``_scan_groups`` keeps the expert leaves whole);
    - below it keeps its dispatch with a capacity factor of
      n_experts/top_k, which makes capacity == n_tokens (mathematically
      no drop) at E x N rows: a decode step's few rows are bound by the
      expert weights' stream either way, and the padded products stream
      them nearer the roofline (the table above: 3.98 against 4.51 ms a
      layer at 32 tokens).

    Both compute the same top-k sum (``tests/test_expert_dispatch.py``)."""
    if not cfg.n_experts or cfg.moe_dispatch == "ragged":
        return cfg  # dense, or dropless by construction
    import dataclasses

    if n_tokens >= DROPLESS_FROM_TOKENS:
        return dataclasses.replace(cfg, moe_dispatch="ragged")
    factor = cfg.n_experts / cfg.moe_top_k
    if cfg.moe_capacity_factor >= factor:
        return cfg
    return dataclasses.replace(cfg, moe_capacity_factor=factor)


@functools.lru_cache(maxsize=256)
def expert_rows(cfg: Config, n_tokens: int) -> tuple[str, int]:
    """(dispatch, rows of expert FFN work over all expert layers) of one
    inference program over ``n_tokens`` tokens, from its shapes alone:
    ("dropless", k x N a layer), ("padded", E x capacity = E x N a layer)
    or ("", 0) for a dense configuration. Of a held share
    (``Config.expert_rank``) the rows of the experts held: k x N x held / E
    a layer, what uniform routing sends them (the rows themselves are the
    data's). What the serving engine counts at each dispatch
    (``oim_serve_expert_rows_total``): cached, a decode round asks every
    time."""
    if not cfg.n_experts:
        return "", 0
    from oim_tpu.models import moe

    run = _no_drop(cfg, n_tokens)
    layers = cfg.n_expert_layers
    if run.moe_dispatch == "ragged":
        return "dropless", (layers * run.moe_top_k * n_tokens
                            * run.moe.n_held // run.n_experts)
    return "padded", layers * run.n_experts * moe.capacity(n_tokens, run.moe)


def init_cache(cfg: Config, batch: int, max_seq: int):
    """Zeroed dense cache: one [L, B, max_seq, ...] array a leaf of
    ``cfg.cache_leaves`` ({"k","v"} [.., kv_heads, head_dim] for GQA,
    {"kv"} [.., kv_lora_rank + qk_rope_head_dim] for latent attention)."""
    if cfg.pattern:
        raise ValueError(
            "the dense cache runs the attention-then-FFN block and holds no "
            "recurrent state: a hybrid pattern is served through the page "
            "pool and the state pool (ServeEngine), or run whole "
            "(llama.apply)")
    return {name: jnp.zeros((cfg.n_cache_layers, batch, max_seq) + tail,
                            cfg.dtype)
            for name, tail in cfg.cache_leaves.items()}


def _scan_groups(body, carry, params, cfg: Config, xs=None):
    """``lax.scan`` of the block over each stacked layer group in turn
    (models/llama.py LAYER_GROUPS); ``xs`` are per-layer arrays [L, ...]
    scanned beside the layers. Returns (carry, stacked ys)."""
    from oim_tpu.models import moe

    ys, at = [], 0
    for group in layer_groups(params):
        n = jax.tree.leaves(group)[0].shape[0]
        part = None if xs is None else jax.tree.map(
            lambda a: a[at:at + n], xs)
        # A dropless expert group's expert leaves stay whole in the scan
        # (moe.keep_stacked: a slice handed to a grouped product is a copy).
        sliced, whole = (moe.keep_stacked(group)
                         if cfg.moe_dispatch == "ragged" else (group, {}))

        def step(carry, inp, whole=whole):
            layer, part, i = inp
            return body(carry, (moe.at_layer(layer, whole, i), part))

        with jax.named_scope("blk_loop"):  # the scan's own cut of a layer
            carry, y = lax.scan(step, carry, (sliced, part, jnp.arange(n)))
        ys.append(y)
        at += n
    return carry, jax.tree.map(lambda *a: jnp.concatenate(a), *ys)


def cached_forward(params, tokens, cache, pos, cfg: Config,
                   axis: str | None = None):
    """Forward ``tokens`` [B,T] occupying absolute positions pos..pos+T-1.

    Returns (logits [B,T,vocab] f32, updated cache). Serves both prefill
    (T = prompt length, pos = 0) and decode (T = 1). Under ``axis`` the
    body runs inside a shard_map over that tensor-parallel mesh axis:
    ``cfg`` must be the member-local view (:func:`shard_config`) and
    params/cache the member-local slices — two psums per layer
    reassemble the projections (see :func:`_reduce`).
    """
    B, T = tokens.shape
    S = _page_leaf(cache, cfg).shape[2]
    cfg = _no_drop(cfg, B * T)
    # Host-numpy weight trees (a freshly restored checkpoint) must work:
    # numpy arrays can't be indexed by traced token ids inside the decode
    # scan, so lift everything to jax arrays first (no-op when already on
    # device).
    params = jax.tree.map(jnp.asarray, params)
    with jax.named_scope("tok_embed"):
        cos, sin = rope_frequencies(cfg.rope_dim, S, cfg.rope_theta,
                                    cfg.rope_yarn)
        positions = jnp.broadcast_to(pos + jnp.arange(T), (B, T))
        x = params["embed"][tokens].astype(cfg.dtype)

    def attend(c, q, *new):
        if cfg.kv_lora_rank:
            latent, wkv_b = new
            kv = lax.dynamic_update_slice_in_dim(c["kv"], latent, pos, axis=1)
            return latent_attention.full_attention(
                q, kv, wkv_b, cfg.latent, pos), {"kv": kv}
        k, v = new
        ck = lax.dynamic_update_slice_in_dim(c["k"], k, pos, axis=1)
        cv = lax.dynamic_update_slice_in_dim(c["v"], v, pos, axis=1)
        return cache_attention(q, ck, cv, pos), {"k": ck, "v": cv}

    def body(x, inp):
        layer, c = inp
        x, _, c = _block(x, layer, cfg, cos, sin, positions, attend, c,
                         lambda y: _reduce(y, axis))
        return x, c

    x, cache = _scan_groups(body, x, params, cfg, cache)
    with jax.named_scope("tok_head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = (x @ head(params)).astype(jnp.float32)
    return logits, cache


# -- serving entry points (oim_tpu/serve: continuous batching) ------------
#
# The serving engine's KV storage is PAGED: one pool of fixed-size pages,
# an array [L, n_pages, page_tokens, ...] a leaf of ``Config.cache_leaves``
# ({"k","v"} [.., kv_heads, head_dim] for GQA; for latent attention ONE
# array {"kv"} [.., kv_lora_rank + qk_rope_head_dim]), shared by every
# live request, addressed through per-slot page tables (logical position
# s of slot b lives at pool[:, table[b, s // page], s % page]). Capacity
# stops being a per-slot [max_seq] reservation — short and long prompts
# share one pool, and a cached prompt prefix is SHARED by pointing two
# slots' tables at the same physical pages (vLLM's paged-attention idea
# re-expressed on this repo's primitives). The engine's operations —
# insert a new request's prefill into a slot mid-flight, advance the
# whole batch one token with per-row positions, verify a draft's
# candidates — are ONE layer loop (``_forward_paged``) that carries the
# pool, scatters this call's K/V through the table in place, and attends
# through ``ops.paged_attention.paged_attention``. That function has three
# paths and one rule (``_paged_plan``: shapes and backend only, no
# option):
#
# * the REFERENCE path, everywhere and for every T: gather the slot's
#   logical cache from the table, then the SAME ``cache_attention`` the
#   solo path uses. The gathered logical cache holds exactly the values
#   the dense cache held at every position the causal mask admits, and
#   masked positions (unwritten pads, stale bytes in a freshly mapped
#   page) contribute EXACT zeros through the softmax (-inf score -> 0
#   probability -> 0 * finite = 0), so the attention sums are
#   term-for-term identical: served tokens are BYTE-IDENTICAL to solo
#   generate(). Tier-1 (CPU) runs and guards this path: tests/test_spec.py,
#   the engine's paged-vs-solo identity tests. On a TPU it is what is left
#   for the shapes the kernels do not take: the speculative verify's
#   T = K + 1 rows a slot, a head_dim or a page off the tiling.
# * the two KERNEL paths on a TPU when head_dim and the page fit the
#   tiling: a decode step (T == 1) and a prompt slice (T whole query
#   blocks: every prefill bucket from 16 rows). Each is a Pallas kernel that
#   reads a row's live pages where they lie, with an online softmax (bf16
#   K/V and probabilities, f32 scores, statistics and accumulators, every
#   live position attended; only the order of the f32 sums differs from
#   the reference). The slice's kernel reads this call's own keys from the
#   pool too (the scatter lands first), walks no further than a query
#   block's last real position (``n_tokens``: pad rows cost nothing and
#   their results are discarded) and gathers, copies or re-lays nothing of
#   the pool's or the table's size. Neither is byte-identical to solo
#   generate(): they are held to the benchmark's limits against the
#   float32 reference on the chip (benchmarks/configs/*.json) and to the
#   reference path in interpret mode (tests/test_ops.py);
#   tests/test_chip_compile.py holds the compiled programs to "no copy,
#   restack or gather of the pool".
#
# The program logs ``attention dispatch kernel=pallas_paged|
# pallas_paged_prefill|jnp_gather`` once per trace, and
# ``ServeEngine.stats()["decode_attention"]`` / ``["prefill_attention"]``
# show the same words on the replica's serve/<id> row.


def init_page_pool(cfg: Config, n_pages: int, page_tokens: int):
    """Zeroed page pool: one [L, n_pages, page_tokens, ...] array a leaf
    of ``cfg.cache_leaves`` — {"k","v"} [.., kv_heads, head_dim] for GQA,
    ONE array {"kv"} [.., kv_lora_rank + qk_rope_head_dim] for latent
    attention (no head axis, no separate value). Physical page 0 is the
    engine's scratch/null page: every unmapped page-table entry points at
    it, and idle decode rows write their discarded K/V into it — its
    content is garbage by design and is only ever read through the
    causal mask's exact-zero branch."""
    return {name: jnp.zeros((cfg.n_cache_layers, n_pages, page_tokens) + tail,
                            cfg.dtype)
            for name, tail in cfg.cache_leaves.items()}


def page_bytes(cfg: Config, page_tokens: int) -> int:
    """Device bytes of one page over all layers and all leaves of the
    pool: the unit of the engine's pool and prefix-store accounting."""
    import math

    per_position = sum(math.prod(t) for t in cfg.cache_leaves.values())
    return (cfg.n_cache_layers * page_tokens * per_position
            * jnp.dtype(cfg.dtype).itemsize)


# Beside the pages a hybrid keeps RECURRENT STATE: what a slot's recurrent
# layers hold whatever its position (``Config.state_leaves``: a matrix state
# in float32 and a conv window, a KIND of recurrent layer), a row a slot of
# the engine's batch: a Mamba-2 model's {"ssm": [Lm, slots, H, P, N], "conv":
# [Lm, slots, (K - 1) * conv_dim]}, a KDA model's {"kda": [Lk, slots, H, d,
# d], "kda_conv": [Lk, slots, (K - 1) * 3 H d]} (ops/ssm.py says why a slot's
# conv window is kept flat), a GatedDeltaNet model's {"gdn": [Lg, slots, Hv,
# dk, dv], "gdn_conv": ...}. A new kind of state is one more entry of
# ``llama.RECURRENT_KINDS`` whose module has ``Dims`` (``slot_leaves``,
# ``state_leaf``, ``window_leaf``), ``step`` and ``scan``. A layer of
# compressed convolutional attention keeps pages AND, a slot, the TAIL its
# convolutions and its value shift read at the next position ({"cca_tail":
# [Lc, slots, tail]} float32, ops/cca.py): the same rows, the same rules. The
# leaves ride in the SAME dict as the page leaves, so the serving programs donate and
# update them with the pool. A prefill at
# ``start`` = 0 begins from zeros (no call zeroes a row: a retired slot's
# state is dead where it lies), a later slice of a chunked prefill from the
# row its predecessor left, a decode step updates the rows of live slots
# only — a row whose first table entry is the scratch page is idle, as in
# ``ops/paged_attention._paged_decode`` — and pad positions move nothing.


def init_state_pool(cfg: Config, slots: int) -> dict:
    """Zeroed recurrent state for ``slots`` slots, every kind's leaves in
    one dict ({} without recurrent layers)."""
    for kind, dims in cfg.recurrent.items():
        # ``_hybrid_paged`` slices a slot's row of a [L, slots, H, a, b] leaf
        shape, _ = cfg.state_leaves[kind][dims.state_leaf]
        assert len(shape) == 3, (kind, shape)
    return {name: jnp.zeros((cfg.n_of(kind), slots) + shape, dtype)
            for kind, leaves in cfg.state_leaves.items()
            for name, (shape, dtype) in leaves.items()}


def state_bytes_by_kind(cfg: Config, slots: int = 1) -> dict:
    """{kind's name ("mamba", "kda", "gdn", "cca"): device bytes of ``slots``
    slots' recurrent state (or tail) over all layers of that kind}."""
    import math

    return {STATE_KINDS[kind].NAME: slots * cfg.n_of(kind) * sum(
        math.prod(shape) * jnp.dtype(dtype).itemsize
        for shape, dtype in leaves.values())
        for kind, leaves in cfg.state_leaves.items()}


def state_bytes(cfg: Config, slots: int = 1) -> int:
    """Device bytes of ``slots`` slots' recurrent state over all recurrent
    layers."""
    return sum(state_bytes_by_kind(cfg, slots).values())


def _page_leaf(pool, cfg: Config):
    """One page leaf of ``pool`` (all share [L, n_pages, page_tokens])."""
    return pool[next(iter(cfg.cache_leaves))]


def _forward_paged(params, tokens, pool, tables, pos, phys, off,
                   cfg: Config, axis: str | None, slot=None, n_tokens=None):
    """The one layer loop of the three serving programs: forward
    ``tokens`` [B, T] at absolute positions pos[b] + t (``pos`` a scalar
    or [B]), writing position (b, t)'s cache entry at pool[l, phys[b, t],
    off[b, t]] (an out-of-range ``phys`` DROPS the write) and attending
    through ``tables`` [B, n_blocks]. Returns (hidden [B, T, D] after the
    final norm, updated pool, load): ``load`` f32 is the mean over the
    expert layers of [experts that got a row, rows of the fullest expert
    over the mean] (zeros without a dropless expert layer) and, where the
    call's routed products have a ladder (``moe.load_width``), how many
    expert layers' products ran on each rung (``moe.RUNG_NAMES``,
    ``moe.capacity_ladder``).

    The pool is part of the scan's CARRY, with the layer index beside it:
    each layer scatters its rows into pool[l] and reads pool[l] where it
    lies. As the scan's xs/ys the pool was sliced per layer and restacked
    every call — two copies of the whole pool a program, whatever the
    callers' donation. Carried, the caller's donated buffer is the one
    the scatter updates in place (tests/test_chip_compile.py holds the
    compiled programs to it). The layer index runs on through the layer
    groups (an expert model's leading dense layers, then the rest).

    A hybrid pattern's layers run in pattern order (``llama.run_pattern``)
    with the recurrent state of ``pool`` beside the pages: ``slot`` None is
    a decode step (row b of the state is batch row b's), else the one
    slot a prefill of ``n_tokens`` real positions reads and writes."""
    B, T = tokens.shape
    page = _page_leaf(pool, cfg).shape[2]
    S = tables.shape[1] * page
    cfg = _no_drop(cfg, B * T)
    params = jax.tree.map(jnp.asarray, params)
    with jax.named_scope("tok_embed"):
        cos, sin = rope_frequencies(cfg.rope_dim, S, cfg.rope_theta,
                                    cfg.rope_yarn)
        positions = jnp.broadcast_to(pos, (B,))[:, None] + jnp.arange(T)
        x = params["embed"][tokens].astype(cfg.dtype)

    def attend_at(l):
        """Layer l's attention over the pool it is handed."""
        def attend(pool, q, *new):
            if cfg.kv_lora_rank:
                latent, wkv_b = new
                with jax.named_scope("blk_kv_write"):
                    kv = pool["kv"].at[l, phys, off].set(latent, mode="drop")
                return latent_attention.paged_attention(
                    q, kv, l, tables, pos, wkv_b, cfg.latent), {**pool, "kv": kv}
            k, v = new
            with jax.named_scope("blk_kv_write"):
                pk = pool["k"].at[l, phys, off].set(k, mode="drop")
                pv = pool["v"].at[l, phys, off].set(v, mode="drop")
            return (paged_attention(q, pk, pv, l, tables, pos, n_tokens),
                    {**pool, "k": pk, "v": pv})
        return attend

    n_moe = max(cfg.n_expert_layers, 1)
    if cfg.pattern:
        x, pool, load = _hybrid_paged(
            params, x, pool, cfg, cos, sin, positions, attend_at,
            tables[:, 0] != 0, slot, n_tokens, pos)
        with jax.named_scope("tok_head"):
            x = _norm(x, params["final_norm"], cfg)
        return x, pool, _mean_load(load, n_moe)

    def body(carry, inp):
        x, pool, l = carry  # pool leaves: [L, n_pages, page, ...]
        x, aux, pool = _block(x, inp[0], cfg, cos, sin, positions,
                              attend_at(l), pool, lambda y: _reduce(y, axis),
                              load=True)
        return (x, pool, l + 1), aux[2:]

    (x, pool, _), load = _scan_groups(
        body, (x, pool, jnp.int32(0)), params, cfg)
    with jax.named_scope("tok_head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, pool, _mean_load(jnp.sum(load, axis=0), n_moe)


def _mean_load(load, n_moe: int):
    """The expert layers' summed load: its two means, and a held share's
    counts of calls a rung as they are."""
    if load.shape[0] == 2:
        return load / n_moe
    return jnp.concatenate([load[:2] / n_moe, load[2:]])


def _hybrid_paged(params, x, pool, cfg: Config, cos, sin, positions,
                  attend_at, live, slot, n_tokens, start):
    """``_forward_paged``'s layer loop for a hybrid pattern: (x, pool,
    summed expert load [2]). ``pool`` carries pages and recurrent state;
    each recurrent layer reads its rows of its kind's state where they lie
    and writes them back in place."""
    eps = cfg.norm_eps

    def recurrent(kind):
        module, dims = RECURRENT_KINDS[kind], cfg.recurrent[kind]
        state, conv = dims.state_leaf, dims.window_leaf
        step_scope, scan_scope = module.SCOPES

        def mixer(carry, layer, i):
            x, pool, load, router = carry
            h = _norm(x, layer["norm"], cfg)
            sp, cp = pool[state], pool[conv]
            # The state's read and write-back stand under the mixer's scope
            # too: the compiler fuses them with its update, and the profile
            # names a fusion after one of its operations.
            if slot is None:  # a decode step: every row, live rows kept
                with jax.named_scope(step_scope):
                    s, c = sp[i], cp[i]
                    y, s2, c2 = module.step(
                        layer, h[:, 0], s, c.reshape((-1,) + dims.window),
                        dims, eps)
                    s2 = jnp.where(live[:, None, None, None], s2, s)
                    c2 = jnp.where(live[:, None], c2.reshape(c.shape), c)
                    pool = {**pool, state: sp.at[i].set(s2),
                            conv: cp.at[i].set(c2)}
                return (_residual(x, y[:, None], layer, cfg), pool, load,
                        router)
            # A prompt slice of one slot, from zeros at position 0.
            with jax.named_scope(scan_scope):
                s = lax.dynamic_slice(sp, (i, slot, 0, 0, 0),
                                      (1, 1) + sp.shape[2:])[0]
                c = lax.dynamic_slice(cp, (i, slot, 0),
                                      (1, 1, cp.shape[2]))[0]
                s = jnp.where(start == 0, jnp.zeros_like(s), s)
                c = jnp.where(start == 0, jnp.zeros_like(c), c)
                y, s2, c2 = module.scan(
                    layer, h, s, c.reshape((1,) + dims.window), n_tokens,
                    dims, eps)
                pool = {**pool,
                        state: lax.dynamic_update_slice(
                            sp, s2[None], (i, slot, 0, 0, 0)),
                        conv: lax.dynamic_update_slice(
                            cp, c2.reshape(1, 1, -1), (i, slot, 0))}
            return (_residual(x, y, layer, cfg), pool, load, router)

        return mixer

    def experts(carry, layer, _):
        x, pool, load, router = carry
        x, aux, router = _ffn_mixer(x, layer, cfg, load=True, router=router)
        return (x, pool, load + aux[2:], router)

    def attention(carry, layer, i):
        x, pool, load, router = carry
        x, pool = _attn_mixer(x, layer, cfg, cos, sin, positions,
                              attend_at(i), pool)
        return (x, pool, load, router)

    def conv_attention(carry, layer, i):
        """Compressed convolutional attention: layer i's pages as GQA's, and
        beside them the rows' tails, read and written where they lie (under
        the mixing's scope, as a recurrent layer's state)."""
        x, pool, load, router = carry
        leaf = cfg.cca.tail_leaf
        tp = pool[leaf]
        with jax.named_scope(cca.SCOPE):
            if slot is None:  # a decode step: every row's own tail
                tail = tp[i]
            else:  # a prompt slice of one slot, from zeros at position 0
                tail = lax.dynamic_slice(tp, (i, slot, 0),
                                         (1, 1, tp.shape[2]))[0]
                tail = jnp.where(start == 0, jnp.zeros_like(tail), tail)
        x, pool, new = _cca_mixer(x, layer, cfg, cos, sin, positions,
                                  attend_at(i), pool, tail, n_tokens)
        with jax.named_scope(cca.SCOPE):
            if slot is None:  # an idle row keeps what it had
                tp = tp.at[i].set(jnp.where(live[:, None], new, tail))
            else:
                tp = lax.dynamic_update_slice(tp, new[None], (i, slot, 0))
        return (x, {**pool, leaf: tp}, load, router)

    from oim_tpu.models import moe

    B, T = x.shape[:2]
    x, pool, load, _ = run_pattern(
        params, cfg,
        (x, pool, jnp.zeros(
            (moe.load_width(cfg.moe, B * T) - 2,), jnp.float32),
         router_carry(cfg, B, T)),
        {"E": experts, "D": experts, "*": attention, "C": conv_attention,
         **{kind: recurrent(kind) for kind in cfg.recurrent}})
    return x, pool, load


def prefill_into_pages(params, tokens, n_tokens, pool, page_table,
                       start, cfg: Config, page_tokens: int,
                       axis: str | None = None, slot=0,
                       with_rungs: bool = False):
    """Prefill ``tokens`` [1, T] (first ``n_tokens`` real, rest pad — the
    engine buckets prompt lengths so one compiled program serves many)
    through the slot's ``page_table`` [n_blocks] into the page pool,
    occupying logical positions [start, start + n_tokens).

    Returns (last real token's logits [vocab] f32, updated pool) and,
    ``with_rungs`` in a program whose routed products have a ladder (a held
    share's, a whole set's from ``moe.WHOLE_FROM_ROWS`` rows an expert), how
    many expert layers' products ran on each rung ([len(moe.RUNG_NAMES)]
    int32, see ``_forward_paged``). This is
    BOTH prefill paths in one program: the full path is start=0 with the
    whole prompt as ``tokens``; the prefix-cache hit passes only the
    UNCACHED TAIL with ``start`` = the cached depth as a traced scalar —
    the cached prefix K/V is never copied anywhere, the slot's page
    table simply references the store's pages and the attention reads
    them in place (zero-copy sharing; K/V at a prompt position is a pure
    function of the tokens at and before it — causal attention,
    absolute-position RoPE from 0 — so shared bytes are exactly what a
    full prefill would recompute). Because ``start`` is traced and the
    page-table shape is fixed, the compiled-program count is one per
    TAIL bucket — strictly fewer than the dense resume path's
    (tail buckets x prefix buckets).

    Pad positions (t >= n_tokens, or logical positions past the table)
    are DROPPED at the scatter instead of written-then-zeroed: the
    causal mask already keeps them out of every real query's softmax
    with exact-zero weight, and never writing them is what keeps a
    SHARED page immutable — a slot may only write pages it privately
    owns (its tail and decode blocks), which is the copy-on-write
    contract the prefix store relies on.

    ``slot`` is the engine slot being filled: the row of the recurrent
    state a hybrid's recurrent layers carry from slice to slice (zeros at
    ``start`` = 0); other configurations do not read it.
    """
    T = tokens.shape[1]  # tokens [1, T]: admission is per-slot
    nb = page_table.shape[0]
    S = nb * page_tokens
    n_pages = _page_leaf(pool, cfg).shape[1]
    logical = start + jnp.arange(T)
    blk = jnp.minimum(logical // page_tokens, nb - 1)
    keep = (jnp.arange(T) < n_tokens) & (logical < S)
    # Out-of-range physical index: pad K/V never lands.
    phys = jnp.where(keep, page_table[blk], n_pages)
    x, pool, load = _forward_paged(
        params, tokens, pool, page_table[None], start, phys[None],
        (logical % page_tokens)[None], cfg, axis, slot, n_tokens)
    # The last real row is taken BEFORE the head: one row of logits is
    # kept, so one row is computed (at 129 280 rows of vocabulary a
    # 2048-token chunk's float32 logits would be 1 GB for nothing).
    with jax.named_scope("tok_head"):
        last = lax.dynamic_slice_in_dim(x[0], n_tokens - 1, 1, axis=0)
        logits = (last @ head(params)).astype(jnp.float32)[0]
    if with_rungs and load.shape[0] > 2:
        return logits, pool, load[2:].astype(jnp.int32)
    return logits, pool


def decode_step(params, tokens, pool, page_tables, pos, cfg: Config,
                page_tokens: int, axis: str | None = None,
                with_load: bool = False):
    """One lockstep decode step over the whole slot batch: ``tokens`` [B]
    int32 (each slot's previous token) at absolute positions ``pos`` [B],
    written and attended through ``page_tables`` [B, n_blocks]. Returns
    (logits [B, vocab] f32, updated pool) and, ``with_load``, the expert
    layers' load (see ``_forward_paged``).

    Mid-flight admission leaves every slot at its own depth, so the K/V
    write is a per-row scatter at (table[b, pos // page], pos % page)
    and the attention is per-row (``paged_attention`` takes the [B]
    position vector directly). Idle slots decode a garbage row the
    engine discards; their page tables are all-zero, so their writes
    land in scratch page 0, never in a page a live request owns. A live
    row only ever writes the private page covering its own position —
    shared prefix pages sit strictly below ``pos`` and are read-only by
    construction.
    """
    B = tokens.shape[0]
    nb = page_tables.shape[1]
    # Positions past the table (an idle row's clamped position, or a
    # draft model speculating past a request's final position) write
    # scratch page 0 — never the clamped LAST page, which a live row
    # may own. In-range positions of an idle row land in scratch via
    # its all-zero table either way.
    blk = jnp.minimum(pos // page_tokens, nb - 1)
    phys = jnp.where(pos < nb * page_tokens,
                     page_tables[jnp.arange(B), blk], 0)  # [B]
    x, pool, load = _forward_paged(
        params, tokens[:, None], pool, page_tables, pos, phys[:, None],
        (pos % page_tokens)[:, None], cfg, axis)
    with jax.named_scope("tok_head"):
        logits = (x @ head(params)).astype(jnp.float32)
    if with_load:
        return logits[:, 0], pool, load[:2]
    return logits[:, 0], pool


def verify_step(params, tokens, pool, page_tables, pos, cfg: Config,
                page_tokens: int, axis: str | None = None):
    """The multi-token sibling of ``decode_step``: forward ``tokens``
    [B, T] (each row's previous token followed by T-1 speculated
    candidates) at absolute positions pos..pos+T-1 (``pos`` [B]),
    scattering every position's K/V through the slot page tables and
    gathering the logical cache for attention. Returns (logits
    [B, T, vocab] f32, updated pool) — per-row logits for ALL T
    positions in ONE program, so a draft model's K proposals verify in
    a single target forward (compiled once per T).

    Write discipline matches ``prefill_into_pages``: positions past the
    table (t >= S) DROP at the scatter, and a row's unmapped table
    entries (an idle row's whole table, or positions past a live row's
    reserved pages) route to scratch page 0 — a verify can therefore
    never touch a page it does not privately own. Within the program a
    query at position p attends exactly the positions <= p a sequential
    decode would have written (this round's candidates included — the
    scatter lands before the gather), so row logits are the ones T
    single-token decode_steps would have produced.

    Rejected-suffix discipline (the speculative-decoding contract): the
    engine advances ``pos`` only past ACCEPTED tokens. K/V written for
    rejected candidates stays in place but is logically dead — the next
    round's scatter overwrites positions pos'..pos'+T-1 before its
    gather, and anything beyond that horizon is masked by ``pos`` with
    exact-zero softmax weight (the same argument that makes paged
    attention byte-identical)."""
    if cfg.state_leaves:
        raise ValueError(
            "verify_step does not support recurrent state yet: a rejected "
            "candidate would have to roll the state back (serve/spec.py)")
    B, T = tokens.shape
    nb = page_tables.shape[1]
    n_pages = _page_leaf(pool, cfg).shape[1]
    positions = pos[:, None] + jnp.arange(T)[None, :]  # [B, T]
    blk = jnp.minimum(positions // page_tokens, nb - 1)
    # Out-of-range physical index: past-the-table K/V never lands (same
    # stance as prefill_into_pages' pad positions).
    phys = jnp.where(positions < nb * page_tokens,
                     page_tables[jnp.arange(B)[:, None], blk], n_pages)
    x, pool, _ = _forward_paged(
        params, tokens, pool, page_tables, pos, phys,
        positions % page_tokens, cfg, axis)
    with jax.named_scope("tok_head"):
        return (x @ head(params)).astype(jnp.float32), pool


def generate(params, prompt, n_new: int, cfg: Config,
             temperature: float = 0.0, rng=None, max_seq: int | None = None):
    """prompt [B,T0] int32 -> [B, T0+n_new]: prefill once, then one
    compiled lax.scan decode loop. temperature 0 = greedy, else categorical
    sampling. Wrap in jax.jit(..., static_argnums=...) for repeated use.
    """
    B, t0 = prompt.shape
    if n_new < 0:
        raise ValueError(f"n_new must be >= 0, got {n_new}")
    if n_new == 0:
        return prompt
    s = max_seq or (t0 + n_new)
    if s < t0 + n_new:
        raise ValueError(f"max_seq {s} < prompt {t0} + n_new {n_new}")
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def sample(logits, key):
        with jax.named_scope("tok_head"):
            if temperature > 0:
                return jax.random.categorical(
                    key, logits / temperature).astype(prompt.dtype)
            return jnp.argmax(logits, axis=-1).astype(prompt.dtype)

    cache = init_cache(cfg, B, s)
    logits, cache = cached_forward(params, prompt, cache, 0, cfg)
    rng, sub = jax.random.split(rng)
    tok = sample(logits[:, -1], sub)

    def step(carry, _):
        cache, tok, pos, key = carry
        key, sub = jax.random.split(key)
        logits, cache = cached_forward(params, tok[:, None], cache, pos, cfg)
        nxt = sample(logits[:, -1], sub)
        return (cache, nxt, pos + 1, key), nxt

    (cache, _, _, _), rest = lax.scan(
        step, (cache, tok, jnp.int32(t0), rng), None, length=n_new - 1
    )
    new_tokens = jnp.concatenate(
        [tok[:, None]] + ([rest.T] if n_new > 1 else []), axis=1
    )
    return jnp.concatenate([prompt, new_tokens], axis=1)
