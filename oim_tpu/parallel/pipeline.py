"""Pipeline parallelism: GPipe-style microbatched execution over a "pipe"
mesh axis.

Layers are already STACKED along a leading axis (models/llama.py scans over
them); pipelining shards that axis across stages — each device holds
n_layers/P contiguous layers — and streams M microbatches through, handing
activations to the next stage with ``ppermute`` each tick. SPMD-friendly:
every stage executes the same code; stage identity only selects which data
is real (``jnp.where`` on ``axis_index``), so the whole schedule jits as one
program with no data-dependent control flow.

Schedule: plain GPipe — M + P - 1 ticks, bubble fraction (P-1)/(M+P-1).
Choose M >= 4*P to keep the bubble under ~20%.

The backward pass needs no special handling: jax differentiates through
ppermute (transpose = reverse permute), so one ``jax.grad`` over the whole
pipelined apply reproduces the reverse communication pattern. MEMORY is
GPipe's law, though: jax.grad keeps every microbatch's stage activations
live until the backward — O(M) per stage — so the M you need to tame the
bubble is the M you pay for in activation residency. At config-5 scale
(P=8, long context, M>=32) that is the regime 1F1B exists for: see
``parallel/pipeline_1f1b.py`` for the PipeDream-flush schedule with live
activations bounded by P (stashes stage INPUTS only, recomputes in the
backward), at the cost of one extra forward per microbatch. Use GPipe for
simplicity and MoE aux-loss support; use 1F1B when M activations don't
fit.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from oim_tpu.parallel.collectives import ppermute_ring


def pipeline_apply(
    layer_fn: Callable[[Any, Any], Any],
    stage_params: Any,
    x: Any,
    n_microbatches: int,
    axis: str = "pipe",
    with_aux: bool = False,
    aux_reduce_axes: tuple[str, ...] = (),
):
    """Run microbatched pipeline over the ``axis`` mesh axis.

    Must be called inside shard_map with ``axis`` bound.

    layer_fn(carry, layer_params) -> carry (or (carry, aux) when
        ``with_aux``; aux a scalar or f32 vector — llama uses
        [load_balance_loss, drop_fraction]): one layer (the same body the
        sequential model scans with). Aux values (MoE load balance +
        telemetry) are summed over a stage's layers, masked to REAL
        microbatch ticks (bubble ticks compute garbage activations whose
        aux must not leak into the loss), and reduced across stages.
    stage_params: THIS stage's layer stack [L/P, ...] pytree (the "pipe"
        axis of the global [L, ...] stack, sharded by shard_map).
    x: [M, mb, ...] microbatched input (real data on every stage; only
        stage 0's is consumed).
    Returns [M, mb, ...] outputs (valid on every stage — the last stage's
    results are rotated forward so stage 0 holds them too; see below), or
    (outputs, aux_mean) when ``with_aux`` — aux_mean is the per-microbatch
    mean of the summed layer aux, matching the sequential scan's value.
    """
    p = lax.psum(1, axis)  # concrete under shard_map
    idx = lax.axis_index(axis)
    m = n_microbatches
    if x.shape[0] != m:
        raise ValueError(f"x leading dim {x.shape[0]} != n_microbatches {m}")
    mb_shape = x.shape[1:]

    def run_stage(h):
        def body(carry, layer):
            out = layer_fn(carry, layer)
            if with_aux:
                return out[0], out[1]
            return out, jnp.zeros((), jnp.float32)

        out, aux = lax.scan(body, h, stage_params)
        return out, jnp.sum(aux, axis=0)  # sum layers, keep aux vector

    outputs = jnp.zeros((m,) + mb_shape, x.dtype)
    h = jnp.zeros(mb_shape, x.dtype)  # activation arriving from the left
    aux_total = jnp.zeros((), jnp.float32)

    for t in range(m + p - 1):
        # Stage 0 injects microbatch t; other stages consume what arrived.
        mb_idx = jnp.clip(t, 0, m - 1)
        inject = lax.dynamic_index_in_dim(x, mb_idx, keepdims=False)
        h_in = jnp.where(idx == 0, inject, h)
        out, aux = run_stage(h_in)
        # Stage s processes microbatch t-s at tick t: real iff 0 <= t-s < m.
        real = jnp.logical_and(idx <= t, t < idx + m)
        aux_total = aux_total + jnp.where(real, aux, 0.0)
        # The last stage banks its result for microbatch t - (p - 1).
        out_idx = jnp.clip(t - (p - 1), 0, m - 1)
        bank = jnp.logical_and(idx == p - 1, t >= p - 1)
        outputs = jnp.where(
            bank,
            lax.dynamic_update_index_in_dim(outputs, out, out_idx, axis=0),
            outputs,
        )
        # Hand activations to the next stage (last stage's hand-off wraps to
        # stage 0 and is ignored there — stage 0 always injects).
        h = ppermute_ring(out, axis)

    # Only the last stage holds real outputs; broadcast so every stage
    # returns the same (replicated) value — and the backward pass correctly
    # funnels cotangents to the last stage (psum transpose).
    outputs = lax.psum(
        jnp.where(idx == p - 1, outputs, jnp.zeros_like(outputs)), axis
    )
    if not with_aux:
        return outputs
    # Sum over stages; divide by M so per-microbatch means average to the
    # sequential full-batch value (each microbatch saw every layer once);
    # then mean over the batch shards (equal-sized, so mean-of-means is the
    # global mean the auto-sharded sequential path computes).
    aux_mean = lax.psum(aux_total, axis) / m
    for batch_axis in aux_reduce_axes:
        aux_mean = lax.pmean(aux_mean, batch_axis)
    return outputs, aux_mean


def pipeline_stage_slice(n_layers: int, axis_size: int, stage: int) -> slice:
    """Which layers stage ``stage`` owns (contiguous blocks)."""
    if n_layers % axis_size:
        raise ValueError(f"{n_layers} layers not divisible by {axis_size} stages")
    per = n_layers // axis_size
    return slice(stage * per, (stage + 1) * per)


def make_pipelined_apply(
    mesh,
    layer_fn: Callable[[Any, Any], Any],
    n_microbatches: int,
    axis: str = "pipe",
    batch_axes: tuple[str, ...] | None = None,
    with_aux: bool = False,
    seq_axis: str | None = None,
):
    """shard_map-wrapped pipelined layer stack over ``mesh``.

    Returns fn(stacked_params, x) where stacked_params is the global
    [L, ...] stack (sharded over ``axis`` on dim 0) and x is [M, mb, ...]
    (microbatch dim replicated across stages, batch dim sharded over
    ``batch_axes``). With ``with_aux``, fn returns (outputs, aux_mean).

    ``seq_axis`` composes sequence parallelism INSIDE the pipeline: x's
    third dim ([M, mb, T, ...]) is sharded over that axis, and because the
    shard_map binds every mesh axis, layer_fn can use the raw ring/Ulysses
    attention (parallel/ring.py) and collectives over ``seq_axis`` directly
    — PP x SP x DP in one program.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if batch_axes is None:
        batch_axes = tuple(
            n for n in mesh.axis_names
            if n not in (axis, "model", "expert", "seq")
        )
    if seq_axis is None:
        x_spec = P(None, batch_axes or None)
    else:
        x_spec = P(None, batch_axes or None, seq_axis)
    out_specs = (x_spec, P()) if with_aux else x_spec

    def fn(stacked_params, x):
        """Not jitted here — wrap in jax.jit (or call inside a jitted train
        step); jit caches by pytree structure so repeated calls are cheap."""
        p_spec = jax.tree.map(lambda _: P(axis), stacked_params)
        return shard_map(
            lambda sp, xx: pipeline_apply(
                layer_fn, sp, xx, n_microbatches, axis, with_aux=with_aux,
                aux_reduce_axes=(
                    batch_axes + ((seq_axis,) if seq_axis else ())
                ),
            ),
            mesh=mesh,
            in_specs=(p_spec, x_spec),
            out_specs=out_specs,
            check_vma=False,
        )(stacked_params, x)

    return fn
