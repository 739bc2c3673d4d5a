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

import jax
import jax.numpy as jnp
from jax.lax import stop_gradient as lax_stop_gradient

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
    scoring: str = "softmax"
    routed_scale: float = 1.0
    # Shared experts: a dense SwiGLU of width n_shared * mlp_dim that
    # every token takes beside its routed experts.
    n_shared: int = 0


def init(rng, dim: int, mlp_dim: int, cfg: MoEConfig, dtype, n_layers: int | None = None):
    """Expert FFN params; with n_layers, stacked [L, ...] for scan."""
    lead = () if n_layers is None else (n_layers,)
    ks = jax.random.split(rng, 4)
    # Later leaves fold their own keys in: the four draws above stay what
    # they were for every tree that has no such leaf.
    ks = list(ks) + [jax.random.fold_in(rng, i) for i in range(4, 8)]
    e = cfg.n_experts
    fan = dim**-0.5
    params = {
        "router": (jax.random.normal(ks[0], lead + (dim, e)) * fan
                   ).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], lead + (e, dim, mlp_dim)) * fan
                   ).astype(dtype),
        "w_up": (jax.random.normal(ks[2], lead + (e, dim, mlp_dim)) * fan
                 ).astype(dtype),
        "w_down": (jax.random.normal(ks[3], lead + (e, mlp_dim, dim))
                   * mlp_dim**-0.5).astype(dtype),
    }
    if cfg.scoring == "sigmoid":
        # ``e_score_correction_bias``: zero in a fresh model and moved by
        # the aux-free balancing rule in training; drawn small here so
        # that a seeded tree has a bias that changes choices.
        params["bias"] = (jax.random.normal(ks[4], lead + (e,)) * 0.01
                          ).astype(jnp.float32)
    if cfg.n_shared:
        f = cfg.n_shared * mlp_dim
        params["shared"] = {
            "w_gate": (jax.random.normal(ks[5], lead + (dim, f)) * fan
                       ).astype(dtype),
            "w_up": (jax.random.normal(ks[6], lead + (dim, f)) * fan
                     ).astype(dtype),
            "w_down": (jax.random.normal(ks[7], lead + (f, dim))
                       * f**-0.5).astype(dtype),
        }
    return params


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


def route(params, tokens, cfg: MoEConfig):
    """tokens [N, D] -> (experts [N, k] int32, weights [N, k] f32): each
    token's k experts and the weight of each in its output, in float32.

    - softmax: top-k of the probabilities, renormalised over the chosen
      (k > 1; the raw probability for k == 1);
    - sigmoid: top-k of ``sigmoid(logits) + bias``, weighed by the
      unbiased scores of the chosen, renormalised and scaled."""
    logits = tokens.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    if cfg.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(scores + params["bias"], cfg.top_k)
        w = jnp.take_along_axis(scores, experts, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return experts, w * cfg.routed_scale
    if cfg.scoring != "softmax":
        raise ValueError(f"unknown MoE scoring {cfg.scoring!r} "
                         "(valid: 'softmax', 'sigmoid')")
    w, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    if cfg.top_k > 1:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return experts, w * cfg.routed_scale


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


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
    whole = {k: experts[k] for k in EXPERT_LEAVES}
    sliced = {**group, "moe": {k: v for k, v in experts.items()
                               if k not in EXPERT_LEAVES}}
    return sliced, whole


def at_layer(layer: dict, whole: dict, index) -> dict:
    """``keep_stacked``'s other half, inside the scan's body."""
    if not whole:
        return layer
    return {**layer, "moe": {**layer["moe"], "stack": (whole, index)}}


def grouped_ffn(params, rows, group_sizes):
    """The expert SwiGLU over ``rows`` [M, D] sorted by expert, expert e
    owning the next ``group_sizes[e]`` of them: three grouped products.
    With ``params["stack"]`` = (the expert leaves of ALL L layers
    [L, E, ...], this layer's index) the products run over L * E groups, of
    which only this layer's E have rows: an empty group costs the product
    nothing, and no layer's weights are cut out of the stack."""
    if "stack" in params:
        whole, index = params["stack"]
        n_layers, e = whole["w_gate"].shape[:2]
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * e,), group_sizes.dtype), group_sizes,
            (index * e,))
        params = {k: whole[k].reshape((n_layers * e,) + whole[k].shape[2:])
                  for k in EXPERT_LEAVES}
    with jax.named_scope("moe_gmm"):
        gate = jax.lax.ragged_dot(rows, params["w_gate"], group_sizes)
        up = jax.lax.ragged_dot(rows, params["w_up"], group_sizes)
        return jax.lax.ragged_dot(
            jax.nn.silu(gate) * up, params["w_down"], group_sizes)


def _dropless(params, x, cfg: MoEConfig):
    """x [B, T, D] -> (out, load [2] f32): every token through all k of
    its experts. ``load`` = [experts that got a row, rows of the fullest
    expert over the mean]: what the serving engine counts."""
    b, t, d = x.shape
    n, e, k = b * t, cfg.n_experts, cfg.top_k
    tokens = x.reshape(n, d)
    with jax.named_scope("moe_route"):
        experts, w = route(params, tokens, cfg)
        flat = experts.reshape(-1)                 # assignment a = token a // k
        order = jnp.argsort(flat, stable=True)     # sorted by expert
        counts = jnp.zeros((e,), jnp.int32).at[flat].add(1)
        rows = jnp.take(tokens, order // k, axis=0)  # [N * k, D]
    y = grouped_ffn(params, rows, counts)
    # Back in assignment order, each token's k rows weighed and summed in
    # float32: a gather, no scatter-add, so the sum's order is fixed.
    y = jnp.take(y, jnp.argsort(order), axis=0).reshape(n, k, d)
    out = jnp.sum(y.astype(jnp.float32) * w[..., None], axis=1)
    if cfg.n_shared:
        s = params["shared"]
        out = out + ((jax.nn.silu(tokens @ s["w_gate"]) * (tokens @ s["w_up"]))
                     @ s["w_down"]).astype(jnp.float32)
    load = jnp.stack([
        jnp.sum(counts > 0).astype(jnp.float32),
        jnp.max(counts).astype(jnp.float32) * e / (n * k)])
    return out.astype(x.dtype).reshape(b, t, d), load


def apply(params, x, cfg: MoEConfig, with_stats: bool = False,
          with_load: bool = False):
    """x: [B, T, D] -> (out [B, T, D], aux_loss scalar f32).

    ``with_load`` (the serving programs): the second return is the f32
    vector [aux_loss, dropped_fraction, experts that got a row, rows of the
    fullest expert over the mean]; the capacity-padded dispatches leave
    the last two at zero.

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
        out, load = _dropless(params, x, cfg)
        zeros = jnp.zeros((2,), jnp.float32)
        if with_load:
            return out, jnp.concatenate([zeros, load])
        return out, (zeros if with_stats else zeros[0])
    if cfg.scoring != "softmax" or cfg.n_shared or cfg.routed_scale != 1.0:
        raise ValueError(
            f"MoE dispatch {cfg.dispatch!r} runs the softmax router without "
            "shared experts only; sigmoid scoring, a routed scale and shared "
            "experts need dispatch='ragged'")
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
