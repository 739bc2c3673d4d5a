"""Mixture-of-Experts FFN with expert parallelism.

GShard/Switch-style dense dispatch: top-k routing with a capacity limit,
dispatch/combine expressed as einsums so the whole layer is MXU work and
XLA inserts the expert all-to-alls from the shardings (expert-major
tensors carry the "expert" mesh axis via the logical-axis tables; no
hand-written collectives).

Router math in float32 (softmax over experts is precision-sensitive);
expert FFNs in the model dtype.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.lax import stop_gradient as lax_stop_gradient

from oim_tpu.ops.norms import rmsnorm
from oim_tpu.parallel.sharding import EMBED, EXPERT, LAYER, MLP


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    # "einsum": GShard dense dispatch/combine — one-hot einsums, pure MXU
    #   work, but O(N^2 * D) FLOPs (capacity C ~ N/E makes N*E*C*D
    #   quadratic in tokens): the measured dispatch tax behind the r3
    #   38%-MFU MoE row.
    # "gather": index-based — scatter token ids into the [E, C] buffer,
    #   gather tokens into expert_in, gather expert outputs back per
    #   routing round. O(k * N * D) data movement, no quadratic matmul.
    #   Default since the r4 measurement: +13% step speed at cf=1.25 on
    #   the MoE flagship (BASELINE.md), numerics identical to einsum
    #   (tested incl. gradients and capacity drops).
    # "ragged": DROPLESS — the N * k assignments sorted by expert and one
    #   grouped product a projection over the rows each expert got
    #   (``lax.ragged_dot``): no capacity, no [E, C, D] buffer, exactly
    #   k * N rows of expert work. What inference of a many-expert model
    #   needs (at 256 experts a capacity-padded [E, N, D] with room for
    #   every token is 32 times the work); not differentiated.
    # This field is what TRAINING runs. At inference ``generate._no_drop``
    # picks the dispatch of each program from the tokens in its call: a
    # "gather" or "einsum" configuration runs "ragged" from
    # ``generate.DROPLESS_FROM_TOKENS`` tokens up (a long prompt's bucket) and its
    # own dispatch with capacity = tokens below (a decode step, whose few
    # rows the expert weights' stream bounds either way); "ragged" stays.
    dispatch: str = "gather"
    # Router scores: "softmax" over the experts (GShard / Mixtral) or
    # "sigmoid" per expert (DeepSeek-V3's ``noaux_tc`` with one group:
    # top-k of score + a per-expert bias, weighed by the UNBIASED scores
    # renormalised and scaled). Sigmoid routing is dropless by
    # definition: it runs under ``dispatch="ragged"`` only.
    # "mlp": the router is a small network with memory across layers, not
    # ``tokens @ router`` (``router_state`` / ``route``): the tokens are
    # projected down to ``router_dim``, the state the expert block before
    # left is added a learned coefficient a channel, and a three-layer MLP
    # (RMSNorm first, tanh GELU) gives the logits; softmax in float32, top-k
    # of probability + a selection bias, the chosen probabilities the weights
    # as they are (not renormalised: a renormalised top-1 is the constant
    # 1). Dropless too.
    scoring: str = "softmax"
    router_dim: int = 0
    norm_eps: float = 1e-6  # of the "mlp" router's norm
    routed_scale: float = 1.0
    # Shared experts: a dense FFN of width ``shared_dim`` (0: n_shared *
    # mlp_dim) that every token takes beside its routed experts.
    n_shared: int = 0
    shared_dim: int = 0
    # The experts' FFN: "silu" is the gated SwiGLU ``w_down(silu(w_gate x)
    # * w_up x)``, "relu2" the non-gated ``w_down(relu(w_up x)^2)`` (two
    # products an expert, no ``w_gate`` leaf).
    act: str = "silu"
    # One rank's share of an expert-parallel layer: ``held`` = (first,
    # count) are the experts whose weights this tree holds. The router
    # keeps its full width and top-k; the layer computes its own experts'
    # part of each token's sum (and the shared expert whole) and leaves
    # out what the absent ranks would add. () holds every expert.
    held: tuple = ()
    # ``swiglu_limit``: a gated expert's ``w_gate`` product is cut at it from
    # above and its ``w_up`` product to [-limit, limit] before the
    # activation (``swiglu``); 0 cuts nothing.
    swiglu_limit: float = 0.0

    @property
    def n_held(self) -> int:
        return self.held[1] if self.held else self.n_experts


LANES = 128


def stored_width(mlp_dim: int) -> int:
    """The width a dropless configuration (``dispatch="ragged"``: every
    call of it runs the grouped product) HOLDS its routed experts' leaves
    at: ``mlp_dim``, or the next multiple of 128 where it is wider than
    that and not one (1856 -> 1920), the added columns of ``w_up`` / ``w_gate`` and rows of
    ``w_down`` zero, so that they add exact zeros. A TPU keeps an array
    whose minor dim is not whole lanes in another layout than a grouped
    product reads, and the whole stack was then copied at every call (3.4
    GB a decode step at 23 layers of 16 experts of 2688 x 1856, compiled
    for a described v5e: PERF.md section 6, PR 33)."""
    if mlp_dim <= LANES or mlp_dim % LANES == 0:
        return mlp_dim
    return -(-mlp_dim // LANES) * LANES


def init(rng, dim: int, mlp_dim: int, cfg: MoEConfig, dtype, n_layers: int | None = None):
    """Expert FFN params; with n_layers, stacked [L, ...] for scan."""
    lead = () if n_layers is None else (n_layers,)
    pad = stored_width(mlp_dim) - mlp_dim if cfg.dispatch == "ragged" else 0
    tail = ((0, 0),) * (len(lead) + 1)
    ks = jax.random.split(rng, 4)
    # Later leaves fold their own keys in: the four draws above stay what
    # they were for every tree that has no such leaf.
    ks = list(ks) + [jax.random.fold_in(rng, i) for i in range(4, 8)]
    e, held = cfg.n_experts, cfg.n_held
    fan = dim**-0.5
    gated = _gated(cfg.act)
    params = {
        "w_up": (jax.random.normal(ks[2], lead + (held, dim, mlp_dim)) * fan
                 ).astype(dtype),
        "w_down": (jax.random.normal(ks[3], lead + (held, mlp_dim, dim))
                   * mlp_dim**-0.5).astype(dtype),
    }
    if cfg.scoring == "mlp":
        params["router_mlp"] = _init_router_mlp(ks[0], lead, dim, cfg)
    else:
        params["router"] = (jax.random.normal(ks[0], lead + (dim, e)) * fan
                            ).astype(jnp.float32)
    if gated:
        params["w_gate"] = (jax.random.normal(
            ks[1], lead + (held, dim, mlp_dim)) * fan).astype(dtype)
    if pad:  # see stored_width
        for k in EXPERT_LEAVES:
            if k in params:
                params[k] = jnp.pad(params[k], tail + (
                    ((0, pad), (0, 0)) if k == "w_down" else ((0, 0), (0, pad))))
    if cfg.scoring in ("sigmoid", "mlp"):
        # ``e_score_correction_bias``: zero in a fresh model and moved by
        # the aux-free balancing rule in training; drawn small here so
        # that a seeded tree has a bias that changes choices.
        params["bias"] = (jax.random.normal(ks[4], lead + (e,)) * 0.01
                          ).astype(jnp.float32)
    if cfg.n_shared:
        f = cfg.shared_dim or cfg.n_shared * mlp_dim
        params["shared"] = {
            "w_up": (jax.random.normal(ks[6], lead + (dim, f)) * fan
                     ).astype(dtype),
            "w_down": (jax.random.normal(ks[7], lead + (f, dim))
                       * f**-0.5).astype(dtype),
        }
        if gated:
            params["shared"]["w_gate"] = (jax.random.normal(
                ks[5], lead + (dim, f)) * fan).astype(dtype)
    return params


def _init_router_mlp(rng, lead: tuple, dim: int, cfg: MoEConfig) -> dict:
    """The "mlp" router's leaves, float32: the projection down, the
    coefficient on the state carried in (0.6: between forgetting and
    keeping), the norm and the three layers with their biases."""
    r, e = cfg.router_dim, cfg.n_experts
    ks = jax.random.split(rng, 4)

    def dense(key, shape):
        return jax.random.normal(key, lead + shape, jnp.float32) \
            * shape[0] ** -0.5

    def zeros(n):
        return jnp.zeros(lead + (n,), jnp.float32)

    return {
        "w_down": dense(ks[0], (dim, r)), "b_down": zeros(r),
        "carry": jnp.full(lead + (r,), 0.6, jnp.float32),
        "norm": jnp.ones(lead + (r,), jnp.float32),
        "w_a": dense(ks[1], (r, r)), "b_a": zeros(r),
        "w_b": dense(ks[2], (r, r)), "b_b": zeros(r),
        "w_c": dense(ks[3], (r, e)), "b_c": zeros(e),
    }


def _gated(act: str) -> bool:
    if act not in ("silu", "relu2"):
        raise ValueError(f"unknown expert activation {act!r} "
                         "(valid: 'silu' gated, 'relu2' non-gated)")
    return act == "silu"


def swiglu(gate, up, limit: float = 0.0):
    """``silu(gate) * up``, the inside of every gated FFN of the model
    (routed, shared, dense); with ``limit`` (``swiglu_limit``) of
    ``min(gate, limit)`` and ``clip(up, -limit, limit)``."""
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def _shared_ffn(s, tokens, limit: float = 0.0):
    """The shared expert on tokens [N, D]; which form is read from its
    leaves, as ``grouped_ffn``."""
    if "w_gate" in s:
        return swiglu(tokens @ s["w_gate"], tokens @ s["w_up"], limit
                      ) @ s["w_down"]
    return jnp.square(jax.nn.relu(tokens @ s["w_up"])) @ s["w_down"]


def param_logical_axes(stacked: bool = False):
    lead = (LAYER,) if stacked else ()
    return {
        "router": lead + (EMBED, EXPERT),
        "w_gate": lead + (EXPERT, EMBED, MLP),
        "w_up": lead + (EXPERT, EMBED, MLP),
        "w_down": lead + (EXPERT, MLP, EMBED),
    }


def capacity(n_tokens: int, cfg: MoEConfig) -> int:
    return max(1, int(cfg.top_k * n_tokens * cfg.capacity_factor / cfg.n_experts))


_HI = jax.lax.Precision.HIGHEST  # the "mlp" router's products: a top-1
# choice has no second expert to soften a flipped one


def router_state(params, tokens, prev, cfg: MoEConfig):
    """The "mlp" router's state of this expert block, float32 [..., R]:
    ``tokens W_d + b_d + g * prev`` with ``prev`` the state the expert block
    before left (None in the first: zeros). What the next block is handed."""
    m = params["router_mlp"]
    r = jnp.matmul(tokens.astype(jnp.float32), m["w_down"],
                   precision=_HI) + m["b_down"]
    return r if prev is None else r + m["carry"] * prev


def _mlp_logits(m, state, eps: float):
    """``W_c gelu(W_b gelu(W_a N(state))))``, each layer with its bias."""
    x = rmsnorm(state, m["norm"], eps)
    for w, b in (("w_a", "b_a"), ("w_b", "b_b")):
        x = jax.nn.gelu(jnp.matmul(x, m[w], precision=_HI) + m[b],
                        approximate=True)
    return jnp.matmul(x, m["w_c"], precision=_HI) + m["b_c"]


def route_from_state(params, state, cfg: MoEConfig):
    """The "mlp" router: state [N, R] (``router_state``), not the tokens, ->
    (experts [N, k] int32, weights [N, k] f32): top-k of
    ``softmax(MLP(state)) + bias``, weighed by the probabilities of the
    chosen as they are (not renormalised)."""
    probs = jax.nn.softmax(
        _mlp_logits(params["router_mlp"], state, cfg.norm_eps), axis=-1)
    _, experts = jax.lax.top_k(probs + params["bias"], cfg.top_k)
    w = jnp.take_along_axis(probs, experts, axis=-1)
    return experts, w * cfg.routed_scale


def _route(params, tokens, cfg: MoEConfig, state):
    """A call's routing: from the router's carried state where the router
    is a network, from the tokens otherwise."""
    if cfg.scoring == "mlp":
        return route_from_state(params, state, cfg)
    return route(params, tokens, cfg)


def route(params, tokens, cfg: MoEConfig):
    """tokens [N, D] -> (experts [N, k] int32, weights [N, k] f32): each
    token's k experts and the weight of each in its output, in float32.

    - softmax: top-k of the probabilities, renormalised over the chosen
      (k > 1; the raw probability for k == 1);
    - sigmoid: top-k of ``sigmoid(logits) + bias``, weighed by the
      unbiased scores of the chosen, renormalised and scaled.

    The "mlp" router reads its carried state: ``route_from_state``."""
    logits = tokens.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    if cfg.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(scores + params["bias"], cfg.top_k)
        w = jnp.take_along_axis(scores, experts, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return experts, w * cfg.routed_scale
    if cfg.scoring != "softmax":
        raise ValueError(f"unknown MoE scoring {cfg.scoring!r} "
                         "(valid: 'softmax', 'sigmoid'; 'mlp' routes from "
                         "its state: route_from_state)")
    w, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    if cfg.top_k > 1:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return experts, w * cfg.routed_scale


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
SUBLANES = 8  # rows of a float32 tile: see ``grouped_ffn``


def keep_stacked(group: dict) -> tuple[dict, dict]:
    """Split a stacked layer group for a ``lax.scan`` over its layers:
    (what the scan slices a layer at a time, the expert leaves kept WHOLE).
    A scan hands its body a slice of every stacked leaf, and a slice that
    feeds a grouped product (a custom call, nothing fuses into it) is
    materialised: 2.4 GB of expert weights copied a layer a step at 256
    experts of 2048 x 768 — 29 of a 53 ms decode step on a v5e (PERF.md
    section 6, PR 28). ``at_layer`` puts the whole stack back beside the
    layer's index, and ``grouped_ffn`` folds the layer into the group axis."""
    if "moe" not in group:
        return group, {}
    experts = group["moe"]
    whole = {k: experts[k] for k in EXPERT_LEAVES if k in experts}
    sliced = {**group, "moe": {k: v for k, v in experts.items()
                               if k not in EXPERT_LEAVES}}
    return sliced, whole


def at_layer(layer: dict, whole: dict, index) -> dict:
    """``keep_stacked``'s other half, inside the scan's body."""
    if not whole:
        return layer
    return {**layer, "moe": {**layer["moe"], "stack": (whole, index)}}


def grouped_ffn(params, rows, group_sizes, limit: float = 0.0):
    """The expert FFN over ``rows`` [M, D] sorted by expert, expert e
    owning the next ``group_sizes[e]`` of them: three grouped products for
    the gated SwiGLU, two for the non-gated squared ReLU (a tree without
    ``w_gate``). Rows past the groups' sum belong to no expert held here
    and are no part of any product. With ``params["stack"]`` = (the expert leaves of ALL L layers
    [L, E, ...], this layer's index) the products run over L * E groups, of
    which only this layer's E have rows: an empty group costs the product
    nothing, and no layer's weights are cut out of the stack. The rows are
    handed over in whole sublane tiles of 8 (zero rows past the groups' sum,
    cut off again): on a v5e the grouped product in float32 at the highest
    matmul precision gave wrong sums for EVERY row whenever the row count was
    not a multiple of 8 (1, 2, 4, 7, 9, 15 rows: off by 4 of an rms of 1; 8,
    16, 32 rows, and 8 or more rows of which 1 or 4 are assigned: 1.5e-6;
    bfloat16 and float32 at the default precision are right at every count;
    PERF.md section 6, PR 45). No serving program has such a count but a
    decode step of fewer than 8 assignment rows."""
    n_rows = rows.shape[0]
    if n_rows % SUBLANES:
        rows = jnp.pad(rows, ((0, -n_rows % SUBLANES), (0, 0)))
        return grouped_ffn(params, rows, group_sizes, limit)[:n_rows]
    if "stack" in params:
        whole, index = params["stack"]
        n_layers, e = whole["w_up"].shape[:2]
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * e,), group_sizes.dtype), group_sizes,
            (index * e,))
        params = {k: v.reshape((n_layers * e,) + v.shape[2:])
                  for k, v in whole.items()}
    with jax.named_scope("moe_gmm"):
        up = jax.lax.ragged_dot(rows, params["w_up"], group_sizes)
        if "w_gate" in params:
            gate = jax.lax.ragged_dot(rows, params["w_gate"], group_sizes)
            hidden = swiglu(gate, up, limit)
        else:
            hidden = jnp.square(jax.nn.relu(up))
        return jax.lax.ragged_dot(hidden, params["w_down"], group_sizes)


# Tokens in a call (B x T, static at trace time) up to which a HELD SHARE of
# the experts (``MoEConfig.held``: one rank of an expert-parallel layer, a
# few experts) runs DENSE: every held expert over every token, two batched
# products [e, N, D] x [e, D, F], each token's sum weighed by its router
# weights (zero for an expert it did not choose). One rule on shapes, no
# option (as ``generate.DROPLESS_FROM_TOKENS``). Few tokens are bound by the
# experts' bytes either way (N operations a byte, against the chip's 240),
# nearly every held expert is touched anyway (48 tokens, top-6 of 128, 16
# held: 14.4), and the batched product streams the weights near the
# roofline where ``lax.ragged_dot`` over 36 live rows in 368 groups took
# 1.0 ms a product: 47 of a 69 ms decode step on a v5e (PERF.md section 6,
# PR 33). Above it each held expert's own rows are gathered to a capacity
# (``capacity_ladder``, below): dense would do held x N rows of work for the
# k x N x held / E that are needed, and from 128 rows on the compiler wants
# the stacked leaf in another layout for this product of every token with
# every expert and copies it whole (3.5 GB; tests/test_chip_compile.py holds
# the 64-token bucket and the decode step to "no copy of an expert leaf").
DENSE_UP_TO_TOKENS = 64


def _dense_held(params, x, cfg: MoEConfig, state=None):
    """``_dropless`` for a held share and few tokens: the same sum, every
    held expert computed for every token and weighed (see
    ``DENSE_UP_TO_TOKENS``). Returns (out, load) as ``_dropless``."""
    b, t, d = x.shape
    n, e = b * t, cfg.n_held
    tokens = x.reshape(n, d)
    with jax.named_scope("moe_route"):
        experts, w = _route(params, tokens, cfg, state)            # [N, k]
        chosen = (experts - cfg.held[0])[..., None] == jnp.arange(e)
        weight = jnp.sum(jnp.where(chosen, w[..., None], 0.0), axis=1)  # [N, e]
        counts = jnp.sum(chosen, axis=(0, 1))
    leaves = _layer_leaves(params)
    with jax.named_scope("moe_gmm"):
        up = jnp.einsum("nd,edf->enf", tokens, leaves["w_up"])
        if "w_gate" in leaves:
            hidden = swiglu(jnp.einsum(
                "nd,edf->enf", tokens, leaves["w_gate"]), up, cfg.swiglu_limit)
        else:
            hidden = jnp.square(jax.nn.relu(up))
        y = jnp.einsum("enf,efd->end", hidden, leaves["w_down"])
    out = jnp.einsum("end,ne->nd", y.astype(jnp.float32), weight)
    if cfg.n_shared:
        out = out + _shared_ffn(params["shared"], tokens,
                                cfg.swiglu_limit).astype(jnp.float32)
    load = jnp.stack([
        jnp.sum(counts > 0).astype(jnp.float32),
        jnp.max(counts).astype(jnp.float32) * e
        / jnp.maximum(jnp.sum(counts), 1), *(0.0,) * len(RUNG_NAMES)])
    return out.astype(x.dtype).reshape(b, t, d), load


# What a HELD SHARE's routed products are sized to above ``DENSE_UP_TO_TOKENS``
# (one rule on shapes, no option). On a v5e ``lax.ragged_dot`` is a grouped
# kernel of 512 x 128 x 128 tiles wherever a width is not a multiple of 256
# (1920 = 15 x 128, 2688 = 21 x 128): 315 grid steps of 0.37 us for every
# group that has rows, WHATEVER the rows and the groups it is handed.
# nemotron-3-nano-30b's held share, a 1024-token slice (768 live rows of 6144
# in 16 groups of 368, bf16), ms a product (scripts/expert_dispatch_crossing.py
# --sweep held; PERF.md section 6, PR 35):
#
#   rows handed     6144    3072    1536    768
#   368 groups      2.05    2.05    2.05    1.99
#   16 groups       2.03    2.03    2.03    1.97   (and 1.10 ms to cut a
#                                   layer's 330 MB of leaves from the stack)
#
# so neither fewer rows nor fewer groups buy anything there. Each held
# expert's rows gathered to a capacity C and two batched products [16, C, D]
# x [16, D, F] x [16, F, D] over the layer's leaves, read where they lie in
# the stack, ms a layer (both products, where the grouped ones take 4.0):
#
#   C         64     128    256    384    512    768    1024
#   ms        0.49   0.48   0.53   0.72   1.02   1.42   1.91
#
# bound by the experts' bytes (330 MB: 0.40 ms) up to the chip's 240
# operations a byte, by the MXU beyond. Hence capacities of 256 rows an expert
# at least, and of 5 times the rows uniform routing sends one: on the
# agentbatch cell's own traffic a slice's fullest held expert got up to 5.3
# times its expected 48 rows in 82 % of 4692 expert-layer calls and up to
# 10.7 times in 98.9 % (seeded routers are skewed), so the rungs of a
# 1024-token slice, 256 and 512, take 76-92 % and 8-23 % of the calls and the
# grouped product behind them 0.4-1 % (nine seeds).
MIN_CAPACITY = 256     # rows an expert: under it the experts' bytes bound
CAPACITY_MULTIPLE = 5  # of the rows uniform routing sends an expert
ROW_TILE = 128
RUNG_NAMES = ("first", "second", "whole")

# What a WHOLE SET's routed products (``held == ()``: every expert's weights
# are here) are sized to: ONE capacity, twice the rows uniform routing sends
# an expert, and behind it not the grouped product over every row but the
# same capacity with the rows PAST it through the grouped product
# (``_routed_products.spilled``). On a v5e, bf16, two stacked layers, ms a
# layer, router to weighed sum (scripts/expert_dispatch_crossing.py --sweep
# whole; PERF.md section 6, PR 41):
#
#   joyai-llm-flash (E 256, k 8, D 2048, F 768: a layer's experts 2.42 GB =
#   2.95 ms at the HBM peak; uniform routing sends k x N / E rows an expert)
#   tokens N         32     64     128    256    512    1024   2048
#   rows an expert   1      2      4      8      16     32     64
#   grouped          4.33   7.91   9.15   9.41   9.70   10.29  11.33
#   capacity 128     3.82*  4.16*  4.84   5.04   5.22   5.60   6.02
#   capacity 256                          6.67   6.93   7.30   7.53
#   capacity 512                                 11.58  11.99  11.85
#   (* a capacity of N.) The three batched products alone: 3.52 / 3.93 / 5.02
#   / 8.50 / 15.48 ms at C = 64 / 128 / 256 / 512 / 1024 (75-84 % of the
#   bytes' roofline to 128 rows, the MXU beyond). The three grouped products
#   alone cost what the groups that HAVE rows cost, about 9-12 us a group a
#   product whatever its rows: 4.11 ms at 165 groups (32 distinct tokens),
#   8.62 at 249 (128 tokens), 10.21 at 256 (2048 tokens); the longctx cell's
#   decode step touches 51-63 (its idle slots' rows are one row) and pays 1.5
#   ms a layer where a batched set would pay 3.8: hence no capacity under 4
#   uniform rows an expert (249 of 256 experts touched at 128 tokens).
#
#   mixtral-8x7b (E 8, k 2, D 4096, F 14336: 2.82 GB = 3.44 ms; N / 4 rows)
#   tokens N         256    512    640    1024   2048
#   grouped          9.45   10.69  9.40   13.17  18.84
#   capacity N / 2   4.33   4.95   6.79+  8.93   17.08   (+ 384 = 3 row tiles)
#   capacity N       4.76   8.71   10.58  16.31  33.61
#   padded, room N   4.75   8.89   10.67  16.87  -
#   The batched products alone run at 94 % of the MXU from 512 rows an expert,
#   the grouped ones at 29 % at 1024 tokens and 42 % at 2048.
#
# TABLE-SKEW. The seeded models route far from uniformly (--sweep skew: the
# fullest expert's rows over the uniform rows, per expert-layer call of a
# run of the cell): joyai-llm-flash.longctx's 2048-token slices 2.8 / 4.6 /
# 7.6 / 13.8 times at p5 / p50 / p90 / p99 of 748 calls (none within 2
# times, 35 % within 4, 92 % within 8); mixtral-8x7b.batch's 1024 bucket
# 1.6 / 2.2 / 3.0 / 3.3 times of 296 (35 % within 2, 90 % within 3). A ladder
# of capacities with the grouped product over EVERY row behind it (a held
# share's form) would need 512 rows an expert to hold 92 % of longctx's
# slices, which costs what the grouped product costs; at 128 / 256 it left
# 64 % of the calls on the grouped product and a slice gap at 77 ms for the
# parent's 82. With the rows past the capacity alone through the grouped
# product, which then has few groups with rows, under tokens that share a
# direction (--lean: fullest expert 22 times the uniform rows of joyai, 2.4
# times of mixtral; the grouped product over every row / this form):
#
#   joyai    128 tokens 5.68 / 4.83   512: 7.60 / 6.04   2048: 10.15 / 9.19
#   mixtral  512 tokens 10.67 / 6.70  1024: 13.30 / 10.82  2048: 18.86 / 19.64
#
# and at a fullest expert of 31 times (joyai; a few experts take nearly every
# row, the grouped product has few groups and the capacity still reads every
# expert) 7.37 / 9.29 at 2048 tokens: past the cells' own skew (their p100 is
# 15.5 and 3.6) the form loses and nothing chooses it away; the counters say
# how often the rows spill. Mixtral's 2048 bucket loses under its own skew:
# past 512 rows an expert the grouped product's row tiles are full, hence no
# capacity over ``WHOLE_UP_TO_ROWS`` uniform rows (a capacity of 512).
WHOLE_MULTIPLE = 2      # of the rows uniform routing sends an expert
WHOLE_FROM_ROWS = 4     # uniform rows an expert from which there is a capacity
WHOLE_UP_TO_ROWS = 256  # and up to which: over it the grouped product alone


def capacity_ladder(n_tokens: int, cfg: MoEConfig) -> tuple:
    """The static capacities (rows an expert) the routed products of a call
    of ``n_tokens`` are compiled for, of which a call takes the smallest
    that holds its fullest expert: a multiple of the rows uniform routing
    sends an expert (k x N / E) in whole row tiles, never over N (no expert
    gets a token twice: a capacity of N always holds). Behind a last
    capacity under N stands a grouped product, so any routing whatever is
    computed in full. () is the grouped product over every row alone.

    - A held share: ``CAPACITY_MULTIPLE`` times, not under ``MIN_CAPACITY``
      (fewer rows cost the same), and twice that; behind them the grouped
      product over every assignment row.
    - A whole set, from ``WHOLE_FROM_ROWS`` to ``WHOLE_UP_TO_ROWS`` uniform
      rows an expert: one capacity, ``WHOLE_MULTIPLE`` times; behind it the
      same capacity with the rows past it through the grouped product
      (``_routed_products``)."""
    expected = cfg.top_k * n_tokens / cfg.n_experts
    if cfg.held:
        multiple, least = CAPACITY_MULTIPLE, MIN_CAPACITY
    elif not WHOLE_FROM_ROWS <= expected <= WHOLE_UP_TO_ROWS:
        return ()
    else:
        multiple, least = WHOLE_MULTIPLE, ROW_TILE
    first = -(-int(multiple * expected) // ROW_TILE) * ROW_TILE
    first = min(max(first, least), n_tokens)
    if not cfg.held:
        return (first,)
    return (first, min(2 * first, n_tokens))[:1 + (first < n_tokens)]


def load_width(cfg: MoEConfig, n_tokens: int) -> int:
    """Entries of ``apply``'s ``with_load`` vector in a call of ``n_tokens``:
    [aux, dropped, experts that got a row, fullest over mean] and, where the
    call's products have a ladder (and in every call of a held share), the
    call's rung (one of ``RUNG_NAMES`` set to 1; zeros in the dense form)."""
    rungs = cfg.dispatch == "ragged" and (
        cfg.held or capacity_ladder(n_tokens, cfg))
    return 4 + (len(RUNG_NAMES) if rungs else 0)


def _layer_leaves(params):
    """This layer's expert leaves, read where they lie in the stack."""
    if "stack" not in params:
        return params
    whole, index = params["stack"]
    return {k: v[index] for k, v in whole.items()}


def _batched_ffn(leaves, x, limit: float = 0.0):
    """The expert FFN over x [e, C, D], expert e's rows in x[e]: batched
    products [e, C, D] x [e, D, F] (which form is read from the leaves, as
    ``grouped_ffn``)."""
    with jax.named_scope("moe_gmm"):
        up = jnp.einsum("ecd,edf->ecf", x, leaves["w_up"])
        if "w_gate" in leaves:
            hidden = swiglu(jnp.einsum(
                "ecd,edf->ecf", x, leaves["w_gate"]), up, limit)
        else:
            hidden = jnp.square(jax.nn.relu(up))
        return jnp.einsum("ecf,efd->ecd", hidden, leaves["w_down"])


def _routed_products(params, tokens, flat, order, counts, cfg: MoEConfig):
    """The routed products of a call, sized to the rows each expert got: (y
    [k x N, D] in assignment order, the rung taken). Sorted, expert e's
    assignments are the ``counts[e]`` rows from ``starts[e]`` (a held
    share's bin e, held elsewhere, sorts last). A bounded rung gathers each
    expert's rows into [e, C, D], C the smallest capacity of
    ``capacity_ladder`` that holds the fullest expert (chosen on the
    device), and runs batched products over this layer's leaves; rows past
    an expert's count repeat token 0 and no assignment reads them. The last
    rung, unless a capacity of N stands before it, holds the grouped
    product: over every assignment row for a held share (PR 35: 0.4-1 % of
    its calls), over the rows past the last capacity for a whole set, whose
    router overfills a few experts in most calls (``spilled``). Without a
    ladder the grouped product over every row is the call. An assignment of
    an absent rank reads some row of the result and the caller masks it
    (``mine``)."""
    e, k, rows = cfg.n_held, cfg.top_k, order.shape[0]
    d = tokens.shape[-1]

    def whole(back=None):
        with jax.named_scope("moe_route"):
            x = jnp.take(tokens, order // k, axis=0)  # [N * k, D]
        y = grouped_ffn(params, x, counts, cfg.swiglu_limit)
        # Alone, the un-sort is traced behind the products, where it always
        # stood: a decode step's compiled text is held to its hash (tier-1).
        return jnp.take(y, jnp.argsort(order) if back is None else back, axis=0)

    ladder = capacity_ladder(rows // k, cfg)
    if not ladder:
        return whole(), len(RUNG_NAMES) - 1
    back = jnp.argsort(order)  # where each assignment sorted to
    starts = jnp.cumsum(counts) - counts
    held = jnp.minimum(flat, e - 1)
    rank = back - starts[held]  # an assignment's place among its expert's

    def batched(c):
        """Each expert's first ``c`` rows through the batched products, and
        back to assignment order: y [k x N, D], right where ``rank < c``."""
        with jax.named_scope("moe_route"):
            slot = jnp.arange(c)
            at = jnp.minimum(starts[:, None] + slot, rows - 1)
            source = jnp.where(slot < counts[:, None], order[at] // k, 0)
            x = jnp.take(tokens, source.reshape(-1), axis=0)
        y = _batched_ffn(_layer_leaves(params), x.reshape(e, c, d),
                         cfg.swiglu_limit)
        return jnp.take(y.reshape(e * c, d),
                        held * c + jnp.clip(rank, 0, c - 1), axis=0)

    def spilled(c):
        """``batched(c)``, and the rows past ``c`` of the experts that got
        more through the grouped product: sorted, they move to the front in
        expert order, expert e owning ``counts[e] - c`` of them. The grouped
        product pays for the groups that have rows (few: the experts over
        the capacity), whatever rows it is handed."""
        with jax.named_scope("moe_route"):
            past = jnp.arange(rows) - starts[held[order]] >= c  # sorted order
            first = jnp.argsort(~past, stable=True)
            x = jnp.take(tokens, order[first] // k, axis=0)  # [N * k, D]
        y = grouped_ffn(params, x, jnp.maximum(counts - c, 0),
                        cfg.swiglu_limit)
        place = jnp.cumsum(past) - 1  # of a sorted row among those past c
        return jnp.where((rank < c)[:, None], batched(c),
                         jnp.take(y, place[back], axis=0))

    runs = [functools.partial(batched, c) for c in ladder]
    if ladder[-1] < rows // k:  # a capacity of N always holds
        runs.append(functools.partial(whole, back) if cfg.held
                    else functools.partial(spilled, ladder[-1]))
    if len(runs) == 1:
        return runs[0](), jnp.int32(0)
    rung = jnp.sum(jnp.max(counts) > jnp.asarray(ladder, jnp.int32))
    y = jax.lax.switch(jnp.minimum(rung, len(runs) - 1), runs)
    return y, jnp.where(rung == len(ladder), len(RUNG_NAMES) - 1, rung)


def _dropless(params, x, cfg: MoEConfig, state=None):
    """x [B, T, D] -> (out, load f32): every token through all k of
    its experts, or through those of them that are held here (``cfg.held``:
    the others' assignments sort behind the last group and take part in no
    product; few tokens run ``_dense_held``). The products are
    ``_routed_products``: one body for a held share and for a whole set,
    which is the share whose range is every expert. ``load`` = [experts
    that got a row, rows of the fullest expert over the mean], over the
    experts held: what the serving engine counts; it is followed by the
    call's rung where ``load_width`` says so."""
    b, t, d = x.shape
    n, e, k = b * t, cfg.n_held, cfg.top_k
    if state is not None:  # the "mlp" router's [B, T, R], a row a token
        state = state.reshape(n, -1)
    if cfg.held and n <= DENSE_UP_TO_TOKENS:
        return _dense_held(params, x, cfg, state)
    tokens = x.reshape(n, d)
    with jax.named_scope("moe_route"):
        experts, w = _route(params, tokens, cfg, state)
        flat = experts.reshape(-1)                 # assignment a = token a // k
        if cfg.held:
            local = flat - cfg.held[0]
            mine = (local >= 0) & (local < e)
            flat = jnp.where(mine, local, e)       # bin e: held elsewhere
        order = jnp.argsort(flat, stable=True)     # sorted by expert
        counts = jnp.zeros((e + bool(cfg.held),), jnp.int32).at[flat].add(1)[:e]
    # Back in assignment order, each token's k rows weighed and summed in
    # float32: a gather, no scatter-add, so the sum's order is fixed.
    y, rung = _routed_products(params, tokens, flat, order, counts, cfg)
    y = y.reshape(n, k, d)
    if cfg.held:  # rows of no group are whatever the product left there
        mine = mine.reshape(n, k)
        y, w = jnp.where(mine[..., None], y, 0), jnp.where(mine, w, 0.0)
    out = jnp.sum(y.astype(jnp.float32) * w[..., None], axis=1)
    if cfg.n_shared:
        out = out + _shared_ffn(params["shared"], tokens,
                                cfg.swiglu_limit).astype(jnp.float32)
    total = jnp.maximum(jnp.sum(counts), 1) if cfg.held else n * k
    load = jnp.stack([
        jnp.sum(counts > 0).astype(jnp.float32),
        jnp.max(counts).astype(jnp.float32) * e / total])
    if load_width(cfg, n) > 4:
        load = jnp.concatenate([load, jax.nn.one_hot(
            rung, len(RUNG_NAMES), dtype=jnp.float32)])
    return out.astype(x.dtype).reshape(b, t, d), load


def apply(params, x, cfg: MoEConfig, with_stats: bool = False,
          with_load: bool = False, state=None):
    """x: [B, T, D] -> (out [B, T, D], aux_loss scalar f32).

    ``state`` [B, T, R]: what the "mlp" router chooses from
    (``router_state()`` of this block, made by the caller, who carries it to
    the next expert block); the other routers read the tokens.

    ``with_load`` (the serving programs): the second return is the f32
    vector [aux_loss, dropped_fraction, experts that got a row, rows of the
    fullest expert over the mean]; the capacity-padded dispatches leave
    the last two at zero, and a held share adds the rung its routed
    products ran on (``load_width``).

    Tokens over capacity for their chosen expert are dropped (contribute
    zero; the residual stream carries them), the standard capacity
    trade-off that keeps every shape static for XLA.

    ``with_stats``: the second return becomes the f32 vector
    [aux_loss, dropped_fraction] — dropped_fraction is the share of the
    N*k routing assignments this group rejected for capacity, the
    telemetry that makes the capacity_factor quality knob observable
    (VERDICT r4 weak #4; rides the aux channel so the pipelined paths'
    masked accumulators carry it unchanged).
    """
    if cfg.dispatch == "ragged":
        # No capacity, so nothing dropped; no balance loss either (the
        # sigmoid router is balanced by its bias, outside the loss).
        out, load = _dropless(params, x, cfg, state)
        zeros = jnp.zeros((2,), jnp.float32)
        if with_load:  # ``load_width`` entries
            return out, jnp.concatenate([zeros, load])
        return out, (zeros if with_stats else zeros[0])
    if (cfg.scoring != "softmax" or cfg.n_shared or cfg.routed_scale != 1.0
            or cfg.held or not _gated(cfg.act)):
        raise ValueError(
            f"MoE dispatch {cfg.dispatch!r} runs the softmax router over "
            "gated experts all held here, without shared experts; sigmoid "
            "or mlp scoring, a routed scale, shared experts, the squared-ReLU "
            "expert and a held share need dispatch='ragged'")
    b, t, d = x.shape
    n = b * t
    e, k = cfg.n_experts, cfg.top_k
    cap = capacity(n, cfg)
    tokens = x.reshape(n, d)

    logits = tokens.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # [N, E]

    # Top-k assignment, capacity-limited per expert. Per round we keep the
    # (expert, pos, keep, gate) routing coordinates; the two dispatch
    # modes consume them differently below.
    remaining = probs
    fill = jnp.zeros((e,), jnp.int32)  # accepted per expert across rounds
    rounds = []
    for _ in range(k):
        gate = jnp.max(remaining, axis=-1)  # [N]
        expert = jnp.argmax(remaining, axis=-1)  # [N]
        onehot = jax.nn.one_hot(expert, e, dtype=jnp.int32)  # [N, E]
        # Position of each token in its expert's buffer this round.
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1) + fill[None, :]
        pos = jnp.sum(pos_in_expert * onehot, axis=-1)  # [N]
        keep = pos < cap
        fill = fill + jnp.sum(onehot * keep[:, None].astype(jnp.int32), axis=0)
        pos = jnp.clip(pos, 0, cap - 1)
        rounds.append((gate, expert, pos, keep))
        remaining = remaining * (1.0 - onehot.astype(jnp.float32))

    # Gate renormalization over the experts actually used (GShard). For
    # k == 1 keep the RAW router prob (Switch): normalizing would make
    # the gate identically 1 and kill the router's task-loss gradient.
    if k > 1:
        denom = sum(
            jnp.where(keep, gate, 0.0) for gate, _, _, keep in rounds)
        rounds = [
            (gate / jnp.maximum(denom, 1e-9), expert, pos, keep)
            for gate, expert, pos, keep in rounds
        ]

    def expert_ffn(expert_in):
        """[E, C, D] -> [E, C, D]: the expert SwiGLU, shared by both
        dispatch modes (they must never diverge — TestMoEDispatchModes
        asserts numerical identity)."""
        h = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"])
        ) * jnp.einsum("ecd,edf->ecf", expert_in, params["w_up"])
        return jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    if cfg.dispatch == "gather":
        # Index-based dispatch: token ids scatter into the [E, C] buffer
        # (each (expert, pos) pair is written at most once across rounds
        # by construction), tokens gather into expert_in, and each round
        # gathers its expert outputs straight back to token positions —
        # O(k*N*D) movement instead of the O(N^2*D) one-hot matmuls.
        idx_buf = jnp.zeros((e, cap), jnp.int32)
        valid = jnp.zeros((e, cap), bool)
        for _, expert, pos, keep in rounds:
            # Dropped tokens redirect to the out-of-range slot `cap` and
            # fall off via mode="drop" — they must never overwrite the
            # legitimate occupant of slot cap-1.
            pos_w = jnp.where(keep, pos, cap)
            idx_buf = idx_buf.at[expert, pos_w].set(
                jnp.arange(n, dtype=jnp.int32), mode="drop")
            valid = valid.at[expert, pos_w].set(True, mode="drop")
        expert_in = jnp.take(tokens, idx_buf.reshape(-1), axis=0)
        expert_in = (expert_in.reshape(e, cap, d)
                     * valid[..., None].astype(x.dtype))
        flat_out = expert_ffn(expert_in).reshape(e * cap, d)
        out = jnp.zeros((n, d), x.dtype)
        for gate, expert, pos, keep in rounds:
            picked = jnp.take(flat_out, expert * cap + pos, axis=0)  # [N, D]
            w = (gate * keep).astype(x.dtype)
            out = out + picked * w[:, None]
        out = out.reshape(b, t, d)
    elif cfg.dispatch == "einsum":
        # GShard dense dispatch/combine (einsums; "expert" axis rides E).
        combine = jnp.zeros((n, e, cap), jnp.float32)
        dispatch = jnp.zeros((n, e, cap), bool)
        for gate, expert, pos, keep in rounds:
            onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)
            slot = jax.nn.one_hot(pos, cap, dtype=jnp.float32)  # [N, C]
            contrib = (
                onehot[:, :, None] * slot[:, None, :] * keep[:, None, None]
            )
            combine = combine + gate[:, None, None] * contrib
            dispatch = jnp.logical_or(dispatch, contrib > 0)
        expert_in = jnp.einsum(
            "nec,nd->ecd", dispatch.astype(x.dtype), tokens
        )  # [E, C, D]
        expert_out = expert_ffn(expert_in)
        out = jnp.einsum(
            "nec,ecd->nd", combine.astype(x.dtype), expert_out
        ).reshape(b, t, d)
    else:
        raise ValueError(
            f"unknown MoE dispatch mode {cfg.dispatch!r} "
            "(valid: 'gather', 'einsum', 'ragged')"
        )

    # Load-balance auxiliary loss (Switch Transformer eq. 4): E * sum_e
    # (fraction of tokens routed to e) * (mean router prob for e).
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    if not (with_stats or with_load):
        return out, aux
    # Dropped share of the N*k routing assignments (gradient-free: a
    # count, not a differentiable quantity).
    kept = sum(jnp.sum(keep.astype(jnp.float32)) for _, _, _, keep in rounds)
    dropped = lax_stop_gradient(1.0 - kept / (n * k))
    if with_load:
        return out, jnp.stack([aux, dropped, 0.0, 0.0])
    return out, jnp.stack([aux, dropped])
