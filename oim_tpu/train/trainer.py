"""The trainer: mesh-aware jitted train step + loop.

TPU-first shape of the step (SURVEY.md section 7.2 step 7):
- ONE jit'ed function per step, params/opt-state sharded by the rules table,
  batch sharded over the batch axes, previous state donated. Gradient
  allreduce, FSDP all-gathers, TP collectives: all inserted by XLA from the
  shardings — there is no hand-written communication in the step.
- The per-step Python does nothing but feed arrays and read back a scalar
  loss every ``log_every`` steps (async dispatch keeps the device busy;
  reading the loss is the only sync point).
- Long context: when the mesh has a "seq" axis > 1, attention inside the
  model is swapped for ring/Ulysses sequence-parallel attention
  (oim_tpu/parallel/ring.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from oim_tpu.common import metrics as M, tracing
from oim_tpu.common.logging import from_context
from oim_tpu.models import llama, resnet
from oim_tpu.ops.losses import softmax_cross_entropy
from oim_tpu.parallel import build_mesh
from oim_tpu.parallel.mesh import MeshAxes
from oim_tpu.parallel.ring import make_sequence_parallel_attention
from oim_tpu.parallel.sharding import (
    BATCH,
    DP_RULES,
    FSDP_RULES,
    PIPE_RULES,
    TP_SP_RULES,
    logical_sharding,
    param_shardings,
)
from oim_tpu.train.state import TrainState, make_optimizer

RULES = {
    "dp": DP_RULES,
    "fsdp": FSDP_RULES,
    "tp_sp": TP_SP_RULES,
    "pipe": PIPE_RULES,
}

# Peak bf16 FLOP/s per chip for MFU accounting.
PEAK_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,  # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v5": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}

# Peak HBM bandwidth per chip (bytes/s) for roofline accounting: a
# bandwidth-bound model (ResNet bf16) is honestly judged by fraction of
# this, not by MFU.
PEAK_HBM_BW = {
    "v4": 1.23e12,
    "v5 lite": 819e9,  # v5e
    "v5e": 819e9,
    "v5p": 2.765e12,
    "v5": 2.765e12,
    "v6 lite": 1.64e12,
    "v6e": 1.64e12,
}


def _lookup_peak(table: dict[str, float]) -> float:
    """The chip's published peak. A device the table does not know is an
    error, not a 0.0 default: a utilization figured against no peak
    reads as a measurement."""
    kind = jax.devices()[0].device_kind.lower()
    for key, val in table.items():
        if key in kind:
            return val
    raise KeyError(
        f"no published peak for device kind {kind!r} "
        f"(known: {sorted(table)})")


def peak_flops_per_device() -> float:
    return _lookup_peak(PEAK_FLOPS)


def peak_hbm_bw_per_device() -> float:
    return _lookup_peak(PEAK_HBM_BW)


@dataclasses.dataclass
class TrainConfig:
    model: str = "llama-tiny"  # llama-tiny | llama3-8b | resnet50
    rules: str = "dp"  # dp | fsdp | tp_sp | pipe
    seq_parallel: str = "ring"  # ring | zigzag | ulysses (mesh seq axis > 1;
    # zigzag = load-balanced causal ring: equal per-step work on every chip)
    microbatches: int = 4  # pipeline microbatch count (rules == "pipe")
    # "gpipe" (simple) or "1f1b" (PipeDream-flush: live activations O(P)
    # not O(M); needs microbatches % pipe == 0). Both compose with MoE
    # and with a seq axis inside the pipe (ring/ulysses/zigzag).
    pipeline_schedule: str = "gpipe"
    # Interleaved 1F1B: v virtual stages (layer chunks) per device,
    # bubble (P-1)/(v*M+P-1) instead of (P-1)/(M+P-1). Needs
    # pipeline_schedule="1f1b" and n_layers % (pipe * v) == 0.
    virtual_stages: int = 1
    remat: bool = False  # recompute activations in bwd (fit big configs)
    remat_policy: str = ""  # "", "dots", "dots_with_no_batch_dims", "nothing"
    accum_steps: int = 1  # gradient accumulation: split the batch, one update
    batch_size: int = 8
    seq_len: int = 128
    image_size: int = 224
    num_classes: int = 1000
    label_offset: int = 0  # added to every fed label before range-check
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    log_every: int = 10
    checkpoint_dir: str = ""
    checkpoint_every: int = 0
    eval_every: int = 0  # run an eval pass every N steps (0 = off)
    eval_steps: int = 8  # batches per eval pass
    seed: int = 0
    # dataclasses.replace overrides applied to the named model's config
    # (e.g. a tiny-depth llama3-8b for dryruns: full vocab, 2 layers).
    model_overrides: dict = dataclasses.field(default_factory=dict)

    def model_config(self):
        if self.model == "llama-tiny":
            mcfg = llama.tiny()
        elif self.model == "llama-tiny-moe":
            mcfg = llama.tiny(n_experts=4)
        elif self.model == "llama3-8b":
            mcfg = llama.LLAMA3_8B
        elif self.model == "resnet50":
            mcfg = resnet.Config(num_classes=self.num_classes)
        else:
            raise ValueError(f"unknown model {self.model!r}")
        if self.remat_policy and not self.remat:
            raise ValueError(
                "remat_policy without remat does nothing — pass remat=True "
                "(--remat) to enable policy-limited rematerialization"
            )
        if self.remat:
            mcfg = dataclasses.replace(mcfg, remat=True)
            if self.remat_policy:
                if not hasattr(mcfg, "remat_policy"):
                    raise ValueError(
                        f"model {self.model!r} does not support remat_policy"
                    )
                mcfg = dataclasses.replace(
                    mcfg, remat_policy=self.remat_policy)
        if self.model_overrides:
            mcfg = dataclasses.replace(mcfg, **self.model_overrides)
        return mcfg


def _llama_attn_fn(cfg: TrainConfig, mesh):
    """Sequence-parallel attention when the mesh shards the sequence."""
    if mesh.shape.get("seq", 1) > 1:
        sp = make_sequence_parallel_attention(
            mesh, kind=cfg.seq_parallel, axis="seq", causal=True
        )
        return lambda q, k, v, causal=True: sp(q, k, v)
    if mesh.size > 1 and cfg.rules != "pipe":
        # (The pipeline runs its stages — attention included — inside
        # its own shard_map already.)
        return _per_device_attention(cfg, mesh)
    return None  # model default (pallas flash / reference)


def _per_device_attention(cfg: TrainConfig, mesh):
    """The model's default attention under shard_map: XLA's SPMD pass
    cannot partition a Pallas (Mosaic) kernel, so on a multi-device mesh
    the jitted step refuses to compile on a TPU unless each device is
    handed its own slice — the batch over the rules' batch axes and,
    under tensor parallelism, whole GQA groups of heads over "model"."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from oim_tpu.ops.attention import attention
    from oim_tpu.parallel.sharding import HEAD

    rules = RULES[cfg.rules]

    def present(axes):
        # Rules name axes a mesh may hold at size 1 (or not at all).
        axes = axes if isinstance(axes, tuple) else (axes,) if axes else ()
        return tuple(a for a in axes if mesh.shape.get(a, 1) > 1) or None

    mcfg = cfg.model_config()
    heads = present(rules.axis_for(HEAD))
    if heads:
        width = int(np.prod([mesh.shape[a] for a in heads]))
        if mcfg.n_heads % width or mcfg.n_kv_heads % width:
            heads = None  # a split would cut through a GQA group
    spec = P(present(rules.axis_for(BATCH)), None, heads, None)
    local = shard_map(
        lambda q, k, v: attention(q, k, v, True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return lambda q, k, v, causal=True: local(q, k, v)


def _follow_param_shardings(abstract_tree, params_abstract, p_shardings, replicated):
    """Shardings for a params-shaped subtree buried inside another pytree
    (Adam moments, BN state): a leaf whose tree-path SUFFIX and shape/dtype
    match a parameter gets that parameter's sharding; everything else
    (scalars, counts) replicates. Path matching (not shape matching) keeps
    same-shaped but differently-sharded params apart (llama wq vs wo)."""
    from jax.tree_util import tree_flatten_with_path, tree_unflatten

    p_leaves = tree_flatten_with_path(params_abstract)[0]
    s_leaves = tree_flatten_with_path(p_shardings)[0]
    table = {
        tuple(str(k) for k in path): (leaf.shape, leaf.dtype, shard)
        for (path, leaf), (_, shard) in zip(p_leaves, s_leaves)
    }
    leaves, treedef = tree_flatten_with_path(abstract_tree)
    out = []
    for path, leaf in leaves:
        keys = tuple(str(k) for k in path)
        shard = replicated
        for i in range(len(keys)):
            ent = table.get(keys[i:])
            if ent is not None and ent[0] == leaf.shape and ent[1] == leaf.dtype:
                shard = ent[2]
                break
        out.append(shard)
    return tree_unflatten(treedef, out)


def make_train_step(
    cfg: TrainConfig, mesh, tx
) -> tuple[Callable, Any, Callable, Callable]:
    """Returns (jitted_step, state_shardings, init_fn, eval_fn).

    ``init_fn(rng)`` materializes the TrainState directly sharded (jit with
    out_shardings — an 8B model never exists unsharded anywhere).
    ``eval_fn(state, batch)`` is the forward-only loss: no grads, no state
    mutation, inference-mode model (ResNet uses running BN statistics).
    """
    rules = RULES[cfg.rules]
    mcfg = cfg.model_config()

    if cfg.model.startswith("llama"):
        logical = llama.param_logical_axes(mcfg)
        has_seq = mesh.shape.get("seq", 1) > 1
        # Pipe+seq uses raw ring/Ulysses INSIDE the pipeline's shard_map;
        # the standalone shard_map attention wrapper is for the other rules.
        pipe_with_seq = cfg.rules == "pipe" and has_seq
        attn_fn = None if pipe_with_seq else _llama_attn_fn(cfg, mesh)

        def init_params(rng):
            return llama.init(rng, mcfg), {}

        if cfg.rules == "pipe":
            if "pipe" not in mesh.shape:
                raise ValueError(
                    "pipe rules need a mesh with a 'pipe' axis "
                    f"(got axes {tuple(mesh.shape)}); e.g. --mesh data=2,pipe=2"
                )
            if cfg.pipeline_schedule not in ("gpipe", "1f1b"):
                raise ValueError(
                    f"unknown pipeline_schedule {cfg.pipeline_schedule!r} "
                    "(valid: 'gpipe', '1f1b')"
                )
            # GPipe loss always exists: it is the eval forward even when
            # the train step's gradients come from the 1F1B schedule.
            pipe_loss = llama.make_pipelined_loss(
                mesh, mcfg, cfg.microbatches, attn_fn,
                seq_axis="seq" if pipe_with_seq else None,
                seq_parallel=cfg.seq_parallel, with_stats=True,
            )

            def loss_fn(params, extra, batch):
                loss, stats = pipe_loss(params, batch["tokens"])
                return loss, (extra, stats)
        else:

            def loss_fn(params, extra, batch):
                loss, stats = llama.loss_and_stats(
                    params, batch["tokens"], mcfg, attn_fn)
                return loss, (extra, stats)

        def eval_stats_fn(params, extra, batch):
            # llama eval = same forward, no update; model telemetry
            # (moe_drop_frac, z_loss_term) rides along so eval CE stays
            # comparable across regularizer settings.
            loss, (_, stats) = loss_fn(params, extra, batch)
            return {
                "loss": loss.astype(jnp.float32),
                **{k: v.astype(jnp.float32) for k, v in stats.items()},
            }

        # Tokens arrive [B, T+1] — the +1 label shift makes the length
        # indivisible by a seq axis, so tokens stay batch-sharded only;
        # sequence sharding happens on activations inside the model
        # (shard_map in the attention fn).
        batch_logical = {"tokens": (BATCH, None)}
    elif cfg.model == "resnet50":
        if cfg.rules == "pipe":
            raise ValueError("pipe rules support llama-family models only")
        logical = resnet.param_logical_axes(mcfg)

        def init_params(rng):
            return resnet.init(rng, mcfg)

        def loss_fn(params, extra, batch):
            logits, new_extra = resnet.apply(
                params, extra, batch["images"], mcfg, training=True
            )
            loss = softmax_cross_entropy(logits, batch["labels"])
            return loss, (new_extra, {})

        def eval_stats_fn(params, extra, batch):
            # Inference mode: running BN statistics, state untouched.
            # Accuracy rides along — the honest config-3/4 metric for a
            # labeled OIM-fed classifier (loss alone can fall on garbage).
            logits, _ = resnet.apply(
                params, extra, batch["images"], mcfg, training=False
            )
            acc = jnp.mean(
                (jnp.argmax(logits, axis=-1) == batch["labels"]).astype(
                    jnp.float32)
            )
            return {
                "loss": softmax_cross_entropy(
                    logits, batch["labels"]).astype(jnp.float32),
                "accuracy": acc,
            }

        batch_logical = {
            "images": (BATCH, None, None, None),
            "labels": (BATCH,),
        }
    else:
        raise ValueError(f"unknown model {cfg.model!r}")

    p_shardings = param_shardings(mesh, rules, logical)
    replicated = logical_sharding(mesh, rules, ())

    def abstract_state(rng):
        params, extra = init_params(rng)
        return TrainState.create(params, tx, extra)

    state_shape = jax.eval_shape(abstract_state, jax.random.PRNGKey(0))
    state_shardings = TrainState(
        step=replicated,
        params=p_shardings,
        opt_state=_follow_param_shardings(
            state_shape.opt_state, state_shape.params, p_shardings, replicated
        ),
        extra=_follow_param_shardings(
            state_shape.extra, state_shape.params, p_shardings, replicated
        ),
    )
    batch_shardings = {
        k: logical_sharding(mesh, rules, v) for k, v in batch_logical.items()
    }

    init_fn = jax.jit(abstract_state, out_shardings=state_shardings)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    if (cfg.model.startswith("llama") and cfg.rules == "pipe"
            and cfg.pipeline_schedule == "1f1b"):
        # The 1F1B schedule computes its own gradients (manual interleaved
        # vjp — jax.grad over the tick loop would pin every microbatch's
        # activations and defeat the schedule). Same signature as grad_fn.
        vg_1f1b = llama.make_1f1b_loss(
            mesh, mcfg, cfg.microbatches, attn_fn,
            seq_axis="seq" if pipe_with_seq else None,
            seq_parallel=cfg.seq_parallel,
            n_virtual=max(1, cfg.virtual_stages),
            with_stats=True,
        )

        def grad_fn(params, extra, batch):  # noqa: F811 - deliberate override
            loss, grads, stats = vg_1f1b(params, batch["tokens"])
            return (loss, (extra, stats)), grads
    accum = max(1, cfg.accum_steps)

    def compute_grads(params, extra, batch):
        if accum == 1:
            return grad_fn(params, extra, batch)
        # Gradient accumulation: split the batch into `accum` microbatches
        # and scan, averaging grads/loss — one optimizer update per step,
        # activation memory of one microbatch. (For CE-mean losses the
        # average of microbatch grads equals the full-batch gradient.)
        b0 = jax.tree.leaves(batch)[0].shape[0]
        if b0 % accum:
            raise ValueError(
                f"batch {b0} not divisible by accum_steps {accum}"
            )
        micro = jax.tree.map(
            lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
            batch,
        )

        def body(carry, mb):
            gsum, extra, loss_sum = carry
            (loss, (new_extra, stats)), grads = grad_fn(params, extra, mb)
            # Accumulate in f32: a bf16 accumulator (param dtype) rounds
            # away low bits every add — the drift grows with accum_steps on
            # exactly the big-model configs accumulation exists for.
            gsum = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), gsum, grads
            )
            return (gsum, new_extra, loss_sum + loss), stats

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (gsum, new_extra, loss_sum), stats_stack = lax.scan(
            body, (zeros, extra, jnp.zeros((), jnp.float32)), micro
        )
        grads = jax.tree.map(
            lambda g, p: (g / accum).astype(p.dtype), gsum, params
        )
        stats = jax.tree.map(lambda s: jnp.mean(s), stats_stack)
        return (loss_sum / accum, (new_extra, stats)), grads

    def step_fn(state: TrainState, batch):
        (loss, (new_extra, model_stats)), grads = compute_grads(
            state.params, state.extra, batch
        )
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt,
            extra=new_extra,
        )
        stats = {
            "loss": loss.astype(jnp.float32),
            "grad_norm": optax.global_norm(grads).astype(jnp.float32),
            # Model telemetry (MoE routing drop fraction etc.) rides the
            # same stats dict the loop logs/exports.
            **{k: v.astype(jnp.float32) for k, v in model_stats.items()},
        }
        return new_state, stats

    jitted = jax.jit(
        step_fn,
        in_shardings=(state_shardings, batch_shardings),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )

    def eval_step(state: TrainState, batch):
        return eval_stats_fn(state.params, state.extra, batch)

    eval_fn = jax.jit(
        eval_step, in_shardings=(state_shardings, batch_shardings)
    )
    return jitted, state_shardings, init_fn, eval_fn


def _norm_spec(spec, ndim: int):
    """PartitionSpec -> rank-padded tuple-of-tuples for EQUIVALENCE
    comparison: P('data'), P('data', None) and P(('data',), None) all
    shard identically at a given rank but compare unequal as objects."""
    parts = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
    return tuple(
        () if p is None else tuple(p) if isinstance(p, (tuple, list)) else (p,)
        for p in parts
    )


def synthetic_batches(cfg: TrainConfig) -> Iterator[dict]:
    """Deterministic host-side batches for smoke runs and benchmarks."""
    rng = np.random.RandomState(cfg.seed)
    mcfg = cfg.model_config()
    while True:
        if cfg.model.startswith("llama"):
            yield {
                "tokens": rng.randint(
                    0, mcfg.vocab, (cfg.batch_size, cfg.seq_len + 1)
                ).astype(np.int32)
            }
        else:
            yield {
                "images": rng.rand(
                    cfg.batch_size, cfg.image_size, cfg.image_size, 3
                ).astype(np.float32),
                "labels": rng.randint(
                    0, cfg.num_classes, (cfg.batch_size,)
                ).astype(np.int32),
            }


def flops_per_step(cfg: TrainConfig) -> float:
    if cfg.model.startswith("llama"):
        mcfg = cfg.model_config()
        return (
            llama.num_flops_per_token(mcfg, cfg.seq_len)
            * cfg.batch_size * cfg.seq_len
        )
    # fwd+bwd ~= 3x fwd FLOPs.
    return 3 * resnet.num_flops_per_image(cfg.image_size) * cfg.batch_size


class Trainer:
    """Owns mesh + state + step; run() drives the loop with metrics and
    checkpointing."""

    def __init__(
        self,
        cfg: TrainConfig,
        mesh=None,
        axes: MeshAxes | None = None,
    ):
        self.cfg = cfg
        if mesh is None:
            n = len(jax.devices())
            mesh = build_mesh(axes or [("data", n)])
        self.mesh = mesh
        self.tx = make_optimizer(
            lr=cfg.lr,
            warmup_steps=cfg.warmup_steps,
            total_steps=cfg.total_steps,
            weight_decay=cfg.weight_decay,
        )
        (self.step_fn, self.state_shardings, self.init_fn,
         self.eval_fn) = make_train_step(cfg, mesh, self.tx)
        self.state: TrainState | None = None
        self.last_eval_stats: dict[str, float] = {}
        self._sharding_warned: set[str] = set()
        self.checkpointer = None
        if cfg.checkpoint_dir:
            from oim_tpu.train.checkpoint import Checkpointer

            self.checkpointer = Checkpointer(cfg.checkpoint_dir)

    def init_or_resume(self) -> int:
        """Returns the step resumed from (0 for a fresh start)."""
        log = from_context()
        if self.checkpointer is not None:
            latest = self.checkpointer.latest_step()
            if latest is not None:
                abstract = jax.tree.map(
                    lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                    jax.eval_shape(self.init_fn, jax.random.PRNGKey(0)),
                    self.state_shardings,
                )
                self.state = self.checkpointer.restore(abstract, latest)
                log.info("resumed", step=latest, dir=self.cfg.checkpoint_dir)
                return latest
        self.state = self.init_fn(jax.random.PRNGKey(self.cfg.seed))
        return 0

    def place_batch(self, batch: dict) -> dict:
        rules = RULES[self.cfg.rules]
        multihost = jax.process_count() > 1
        out = {}
        from jax.sharding import NamedSharding

        for k, v in batch.items():
            axes = (BATCH,) + (None,) * (np.ndim(v) - 1)
            if k == "tokens":
                axes = (BATCH, None)  # seq dim of the (T+1) batch stays host-split
            sharding = logical_sharding(self.mesh, rules, axes)
            if (isinstance(v, jax.Array)
                    and isinstance(v.sharding, NamedSharding)
                    and v.sharding.mesh == self.mesh):
                # Device-resident feed: the batch was staged straight
                # into HBM (the plane's sharded MapVolume scatter) with
                # a global sharding over THIS mesh already attached —
                # re-placing it would round-trip through the host (and
                # is impossible for a multi-host global array anyway).
                # Anything else (host arrays, stray single-device
                # device_puts) still goes through normal placement.
                # Trust is VERIFIED, not assumed: a feed sharded
                # differently from the step's expected (BATCH, None, ...)
                # spec would force XLA to insert a silent (and on the
                # wrong axis, wrong-result-free but slow) reshard every
                # step — or worse, feed a batch-split step replicated
                # data. Warn and reshard here, once, visibly.
                if _norm_spec(v.sharding.spec, np.ndim(v)) == _norm_spec(
                        sharding.spec, np.ndim(v)):
                    out[k] = v
                    continue
                # Warn ONCE per key: place_batch is per-step hot-loop
                # code — a persistently mis-sharded feed must not flood
                # the log at steps/sec rate (the reshard below still
                # runs every step; that cost is the bug being flagged).
                if k not in self._sharding_warned:
                    self._sharding_warned.add(k)
                    from_context().warning(
                        "device-resident feed sharding mismatch"
                        + ("" if multihost else "; resharding every step"),
                        key=k, got=str(v.sharding.spec),
                        want=str(sharding.spec),
                    )
                if multihost:
                    # A cross-process reshard of a global array would
                    # need collectives this loop doesn't own; let jit's
                    # in_shardings handle it.
                    out[k] = v
                else:
                    out[k] = jax.device_put(v, sharding)
                continue
            if multihost:
                # The mesh spans processes: each host holds the GLOBAL batch
                # (every feed is deterministic per volume) and contributes
                # only the shards its addressable devices own.
                v = np.asarray(v)
                out[k] = jax.make_array_from_callback(
                    v.shape, sharding, lambda idx, v=v: v[idx]
                )
            else:
                out[k] = jax.device_put(v, sharding)
        return out

    def evaluate(self, data: Iterator[dict], n_batches: int | None = None) -> float:
        """Forward-only mean loss over n_batches (inference-mode model).
        A finite iterator that runs dry mid-pass ends the pass (mean over
        what ran) instead of crashing training. Classifier models also
        report mean accuracy (``last_eval_stats`` / the EVAL_ACCURACY
        gauge)."""
        n = n_batches or self.cfg.eval_steps
        totals: dict[str, float] = {}
        ran = 0
        for _ in range(n):
            try:
                batch = next(data)
            except StopIteration:
                from_context().warning(
                    "eval data exhausted mid-pass", batches_run=ran
                )
                break
            stats = self.eval_fn(self.state, self.place_batch(batch))
            for k, v in stats.items():
                totals[k] = totals.get(k, 0.0) + float(v)
            ran += 1
        if ran == 0:
            # Zero batches is not a perfect loss: don't touch the gauge,
            # don't return a plausible-looking 0.0.
            return float("nan")
        self.last_eval_stats = {k: v / ran for k, v in totals.items()}
        loss = self.last_eval_stats["loss"]
        M.EVAL_LOSS.set(loss)
        if "accuracy" in self.last_eval_stats:
            M.EVAL_ACCURACY.set(self.last_eval_stats["accuracy"])
        return loss

    def run(self, steps: int | None = None, data: Iterator[dict] | None = None,
            eval_data: Iterator[dict] | None = None):
        log = from_context()
        cfg = self.cfg
        steps = steps or cfg.total_steps
        synthetic_default = None
        if data is None:
            data = synthetic_default = synthetic_batches(cfg)
        eval_every = cfg.eval_every
        if eval_every and eval_data is None:
            if data is not synthetic_default:
                # A real feed with no held-out stream: a synthetic fallback
                # would report loss on noise while LOOKING like a held-out
                # loss — skip eval loudly instead.
                log.warning(
                    "eval_every set but no eval_data supplied for a real "
                    "feed; skipping eval (pass eval_data to run())"
                )
                eval_every = 0
            else:
                # Synthetic training stream: a shifted seed never replays
                # the training batches.
                eval_data = synthetic_batches(
                    dataclasses.replace(cfg, seed=cfg.seed + 10_000)
                )
        restored = False
        if self.state is None:
            start_step = self.init_or_resume()
            restored = start_step > 0
        else:
            start_step = int(self.state.step)
        if restored and start_step < steps:
            # Fast-forward the feed to the resume point — ONLY when this
            # call restored from a checkpoint (an in-memory state carried
            # across run() calls means the caller's iterator is already
            # positioned). Deterministic feeds (cycling volumes, seeded
            # synthetic streams) then serve step N the same batch an
            # uninterrupted run would have — the loss trajectory CONTINUES
            # instead of replaying early batches (asserted by the
            # multi-host kill/resume e2e). Feeds exposing ``seek(n)``
            # (data/feeds.py SeekableFeed — whole-volume cycle feeds)
            # reposition at the source in index arithmetic; others replay
            # at O(start_step) host-side batch production.
            seek = getattr(data, "seek", None)
            if callable(seek):
                seek(start_step)
            else:
                try:
                    for _ in range(start_step):
                        next(data)
                except StopIteration:
                    raise RuntimeError(
                        f"feed exhausted while fast-forwarding to resume "
                        f"step {start_step}: the resumed feed must cover "
                        "at least as many batches as the original run "
                        "consumed"
                    ) from None
        fps = flops_per_step(cfg)
        # MFU is a device metric: figured on a TPU only (a CPU run has
        # no peak to divide by and logs no mfu).
        peak = (peak_flops_per_device() * self.mesh.size
                if jax.default_backend() == "tpu" else None)
        last_loss = float("nan")
        t_prev = time.monotonic()
        last_logged = start_step
        # Double-buffered feed: the batch for step i+1 is placed on device
        # while step i's (asynchronously dispatched) compute runs — the
        # per-step host work overlaps device time instead of serializing
        # with it (the hot-path-off-the-control-plane rule of SURVEY §3.5
        # applied to the batch loop).
        pending = self.place_batch(next(data)) if start_step < steps else None
        feed_wait = 0.0
        for i in range(start_step, steps):
            batch = pending
            with tracing.start_span("train.step", step=i + 1), \
                    jax.profiler.StepTraceAnnotation("train", step_num=i + 1):
                self.state, stats = self.step_fn(self.state, batch)
                if i + 1 < steps:
                    # Host time blocked on the feed: with async dispatch the
                    # device is still computing here, so this only becomes
                    # real step time when it exceeds the device step — the
                    # input-bound signal (oim_feed_wait_seconds).
                    t_feed = time.monotonic()
                    nxt = next(data)
                    feed_wait += time.monotonic() - t_feed
                    pending = self.place_batch(nxt)
            if (i + 1) % cfg.log_every == 0 or i + 1 == steps:
                last_loss = float(stats["loss"])  # sync point
                now = time.monotonic()
                n_steps = max(1, i + 1 - last_logged)
                dt = (now - t_prev) / n_steps
                t_prev = now
                last_logged = i + 1
                M.TRAIN_STEP_SECONDS.set(dt)
                M.TRAIN_EXAMPLES_PER_SEC.set(cfg.batch_size / dt)
                M.FEED_WAIT_SECONDS.set(feed_wait / n_steps)
                device_stats = {}
                if peak:
                    device_stats["mfu"] = round(fps / dt / peak, 4)
                    M.TRAIN_MFU.set(device_stats["mfu"])
                extra_stats = {}
                for k, v in stats.items():
                    if k in ("loss", "grad_norm"):
                        continue
                    val = float(v)
                    extra_stats[k] = round(val, 4)
                    if k == "moe_drop_frac":
                        M.MOE_DROP_FRAC.set(val)
                log.info(
                    "step", step=i + 1, loss=round(last_loss, 4),
                    grad_norm=round(float(stats["grad_norm"]), 4),
                    step_s=round(dt, 4),
                    feed_wait_s=round(feed_wait / n_steps, 4),
                    **device_stats, **extra_stats,
                )
                feed_wait = 0.0
            if eval_every and (i + 1) % eval_every == 0:
                eval_loss = self.evaluate(eval_data)
                log.info("eval", step=i + 1, eval_loss=round(eval_loss, 4))
                # Keep eval wall time out of the train step-timing window
                # (it would inflate step_s and understate MFU/examples-sec).
                # feed_wait resets with it: both divide by steps-since-last.
                t_prev = time.monotonic()
                last_logged = i + 1
                feed_wait = 0.0
            if (
                self.checkpointer is not None
                and cfg.checkpoint_every
                and (i + 1) % cfg.checkpoint_every == 0
            ):
                self.checkpointer.save(i + 1, self.state)
                log.info("checkpoint", step=i + 1, dir=cfg.checkpoint_dir)
        if self.checkpointer is not None:
            self.checkpointer.save(steps, self.state, wait=True)
        return last_loss
