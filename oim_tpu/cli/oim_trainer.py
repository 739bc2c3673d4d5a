"""oim-trainer: JAX training over OIM-staged data (new scope per
BASELINE.json — the reference has no trainer; this is ``cmd/oim-trainer``).

Data path options:
- --synthetic (default): host-generated batches, for smoke runs/benchmarks.
- --registry + --controller-id (+ --volume): publish the named volume
  through the feeder (the NodePublishVolume analog) and train on the staged
  array — the "CSI-mounted HBM shards" configuration.

Mesh options: --mesh "data=4,model=2" (axis order = ICI locality order);
default is pure DP over all visible devices. With --registry the mesh device
order follows the registry's topology map (oim_tpu/parallel/mesh.py).
"""

from __future__ import annotations

import argparse

from oim_tpu.cli.common import (
    add_common_flags,
    add_model_override_flag,
    add_observability_flags,
    add_registry_flag,
    device_memory,
    init_jax,
    load_tls_flags,
    parse_model_overrides,
    setup_logging,
    start_observability,
)
from oim_tpu.common.logging import from_context
# The feed layer lives in oim_tpu/data/feeds.py (the CLI is flag
# parsing only); the two public entry points stay importable from here.
from oim_tpu.data.feeds import eval_feed_args, feeder_batches  # noqa: F401
from oim_tpu.train import TrainConfig, Trainer


def parse_mesh(spec: str):
    """'data=4,model=2' -> [("data", 4), ("model", 2)]."""
    from oim_tpu.parallel.mesh import parse_axes

    try:
        return parse_axes(spec)
    except ValueError as e:
        raise SystemExit(f"--mesh: {e}") from e


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser("oim-trainer")
    parser.add_argument("--model", default="llama-tiny",
                        choices=("llama-tiny", "llama-tiny-moe", "llama3-8b",
                                 "resnet50"))
    parser.add_argument("--rules", default="dp",
                        choices=("dp", "fsdp", "tp_sp", "pipe"))
    parser.add_argument("--seq-parallel", default="ring",
                        choices=("ring", "zigzag", "ulysses"),
                        help="zigzag = load-balanced causal ring "
                             "(rules=tp_sp only)")
    parser.add_argument("--microbatches", type=int, default=4,
                        help="pipeline microbatch count (--rules pipe)")
    parser.add_argument("--pipeline-schedule", default="gpipe",
                        choices=("gpipe", "1f1b"),
                        help="1f1b bounds live activations by the pipe "
                             "depth instead of the microbatch count "
                             "(needs microbatches %% pipe == 0; both "
                             "schedules serve MoE and seq-in-pipe)")
    parser.add_argument("--virtual-stages", type=int, default=1,
                        help="interleaved 1F1B: virtual stages (layer "
                             "chunks) per device — bubble shrinks to "
                             "(P-1)/(v*M+P-1); needs --pipeline-schedule "
                             "1f1b and n_layers %% (pipe*v) == 0")
    add_model_override_flag(parser)
    parser.add_argument("--remat", action="store_true",
                        help="recompute activations in the backward pass "
                             "(fit bigger models/batches in HBM)")
    parser.add_argument("--remat-policy", default="",
                        choices=("", "dots", "dots_with_no_batch_dims",
                                 "nothing"),
                        help="what remat may SAVE: 'dots' keeps matmul "
                             "outputs and recomputes only elementwise work "
                             "(cheaper bwd than full remat, more memory)")
    parser.add_argument("--accum-steps", type=int, default=1,
                        help="gradient accumulation microbatches per update")
    parser.add_argument("--mesh", default="", help="e.g. data=4,model=2")
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--label-offset", type=int, default=0,
                        help="added to every fed label before the range "
                             "check (ImageNet-convention tf.Examples are "
                             "1-based: use -1, or --num-classes 1001)")
    parser.add_argument("--lr", type=float, default=3e-4)
    parser.add_argument("--warmup-steps", type=int, default=100)
    parser.add_argument("--log-every", type=int, default=10)
    parser.add_argument("--checkpoint-dir", default="")
    parser.add_argument("--checkpoint-every", type=int, default=0)
    parser.add_argument("--eval-every", type=int, default=0,
                        help="run a forward-only eval pass every N steps "
                             "(real feeds need --eval-volume-file; "
                             "synthetic runs get a held-out stream)")
    parser.add_argument("--eval-steps", type=int, default=8,
                        help="batches per eval pass")
    parser.add_argument("--eval-volume-file", default="",
                        help="held-out volume staged as '<volume>-eval' "
                             "and used for --eval-every in feeder mode")
    parser.add_argument("--eval-volume-tfrecord", default="",
                        help="held-out labeled TFRecord volume (tf.Examples)"
                             " for --eval-every in feeder mode")
    parser.add_argument("--eval-volume-webdataset", default="",
                        help="held-out webdataset shard list (comma-"
                             "separated) staged as '<volume>-eval' for "
                             "--eval-every: token shards for llama models "
                             "(--wds-ext), jpg/cls shards for vision "
                             "(the config-5 eval path)")
    parser.add_argument("--smoke", action="store_true",
                        help="tiny model, 5 steps, CPU-friendly")
    # Data source (feeder mode).
    parser.add_argument("--synthetic", action="store_true", default=False)
    add_registry_flag(parser, help_suffix="feeder data source")
    parser.add_argument("--controller-id", default="")
    parser.add_argument("--volume", default="train-data")
    parser.add_argument("--volume-file", default="",
                        help="stage this file as the training volume")
    parser.add_argument("--volume-tfrecord", default="",
                        help="comma-separated TFRecord paths (serialized "
                             "tf.Examples: image/encoded + image/class/label)"
                             " staged as a labeled image volume")
    parser.add_argument("--volume-webdataset", default="",
                        help="comma-separated webdataset shard URLs "
                             "(local paths or http(s)) to stage and train on")
    parser.add_argument("--wds-ext", default="bin",
                        help="sample extension holding int32 tokens")
    parser.add_argument("--shuffle", action="store_true",
                        help="shuffle records: whole-volume feeds permute "
                             "per epoch; windowed feeds run through a "
                             "bounded reservoir (--shuffle-buffer-records)")
    parser.add_argument("--shuffle-buffer-records", type=int, default=2048,
                        help="reservoir size (records) for shuffling "
                             "windowed/streaming feeds")
    parser.add_argument("--shuffle-seed", type=int, default=0)
    parser.add_argument("--augment", action="store_true",
                        help="host-side random flip + crop on image batches")
    parser.add_argument("--prefetch-batches", type=int, default=2,
                        help="feed batches decoded ahead in a background "
                             "thread (0 = synchronous feed)")
    parser.add_argument("--feed-window-bytes", type=int, default=64 << 20,
                        help="host-resident feed window; 0 = materialize "
                             "the whole volume (small volumes only)")
    parser.add_argument("--publish-timeout", type=float, default=60.0)
    parser.add_argument(
        "--no-direct-data", dest="direct_data", action="store_false",
        help="stream feed windows through the registry proxy instead of "
             "dialing the owning controller directly (direct is the "
             "default; the proxy always remains the fallback)")
    parser.add_argument("--profile", default="",
                        help="capture a jax.profiler trace of the train "
                             "loop into this directory")
    parser.add_argument(
        "--expected-hosts", type=int, default=1,
        help="multi-host: wait for this many controllers in the registry, "
             "derive ranks from the topology, jax.distributed.initialize",
    )
    parser.add_argument(
        "--coordinator-port", type=int, default=8476,
        help="port for the rank-0 jax.distributed coordinator (derived "
             "from the registry-elected rank-0 host's address)",
    )
    parser.add_argument(
        "--platform", default="",
        help="jax platform, overriding JAX_PLATFORMS: 'tpu' on a chip "
             "host (a missing chip is then an error, never a CPU "
             "fallback), 'cpu' for a virtual multi-device mesh via "
             "--xla_force_host_platform_device_count",
    )
    add_common_flags(parser)
    add_observability_flags(parser)
    args = parser.parse_args(argv)
    setup_logging(args)
    obs = start_observability(args, "oim-trainer")
    if args.registry:
        from oim_tpu.cli.common import start_telemetry_row

        telemetry_default = (
            f"{args.controller_id}.trainer" if args.controller_id else "")
        start_telemetry_row(
            obs, args.telemetry_id or telemetry_default, "trainer",
            args.registry, tls=load_tls_flags(args))
    log = from_context()

    init_jax(args.platform)

    if args.smoke:
        import jax

        args.model = "llama-tiny"
        args.steps = min(args.steps, 5)
        args.batch_size = min(args.batch_size, 2)
        args.seq_len = min(args.seq_len, 32)
        args.log_every = 1
        if not args.mesh:
            args.mesh = f"data={min(args.batch_size, len(jax.devices()))}"

    cfg = TrainConfig(
        model=args.model,
        rules=args.rules,
        seq_parallel=args.seq_parallel,
        microbatches=args.microbatches,
        pipeline_schedule=args.pipeline_schedule,
        virtual_stages=args.virtual_stages,
        remat=args.remat,
        remat_policy=args.remat_policy,
        accum_steps=args.accum_steps,
        batch_size=args.batch_size,
        seq_len=args.seq_len,
        image_size=args.image_size,
        num_classes=args.num_classes,
        label_offset=args.label_offset,
        lr=args.lr,
        warmup_steps=args.warmup_steps,
        total_steps=args.steps,
        log_every=args.log_every,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        eval_every=args.eval_every,
        eval_steps=args.eval_steps,
        model_overrides=parse_model_overrides(args.model_override),
    )

    data = None
    eval_data = None
    if args.registry:
        tls = load_tls_flags(args)
        if args.expected_hosts > 1:
            from oim_tpu.parallel.bootstrap import initialize_from_registry

            pid, n = initialize_from_registry(
                args.registry, args.controller_id, args.expected_hosts, tls,
                coordinator_port=args.coordinator_port,
            )
            log.info("distributed", process_id=pid, num_processes=n)
        if (args.feed_window_bytes <= 0 and args.checkpoint_dir
                and not args.augment):
            # Whole-volume feeds reposition in index arithmetic on
            # checkpoint resume (SeekableFeed.seek) instead of replaying
            # start_step batches of host decode. Windowed/augmented
            # streams keep the replay fallback (their state is not a
            # pure function of the batch index). The factory rebuilds
            # the whole chain INCLUDING the prefetcher, so seek() also
            # discards any batches decoded ahead of the old position —
            # but every rebuild shares ONE Feeder, whose publish cache
            # makes MapVolume a one-time cost (a seek repositions in
            # index space; it must not re-stage the volume).
            from oim_tpu.data.feeds import SeekableFeed
            from oim_tpu.feeder import Feeder

            feed_feeder = Feeder(
                registry_address=args.registry,
                controller_id=args.controller_id,
                tls=tls,
                direct_data=getattr(args, "direct_data", True),
            )

            def _make_feed(start):
                d = feeder_batches(args, cfg, tls, start, feeder=feed_feeder)
                if args.prefetch_batches > 0:
                    from oim_tpu.data.prefetch import prefetch_batches

                    d = prefetch_batches(d, depth=args.prefetch_batches)
                return d

            data = SeekableFeed(_make_feed)
        else:
            data = feeder_batches(args, cfg, tls)
        if args.shuffle and args.feed_window_bytes > 0:
            # Windowed feeds stream in volume order; a bounded record
            # reservoir restores sample randomness with fixed host memory.
            from oim_tpu.data.shuffle import shuffle_batches

            data = shuffle_batches(
                data, args.shuffle_buffer_records, seed=args.shuffle_seed)
        if args.eval_every:
            eval_args = eval_feed_args(args)
            if eval_args is not None:
                eval_data = feeder_batches(eval_args, cfg, tls)
    elif not args.synthetic:
        args.synthetic = True
    if args.augment:
        import dataclasses as _dc

        import jax

        from oim_tpu.data.augment import augment_batches
        from oim_tpu.train.trainer import synthetic_batches

        # Per-host decorrelated stream, offset from the shuffle seed so the
        # two RNGs never alias.
        aug_seed = (args.shuffle_seed + 1) * 1_000_003 + jax.process_index()
        if data is None and args.eval_every and eval_data is None:
            # Augmentation wraps the synthetic stream in a generator the
            # Trainer no longer recognizes as its own default — build the
            # shifted-seed held-out stream here so eval still runs instead
            # of being skipped with a misleading real-feed warning.
            eval_data = synthetic_batches(
                _dc.replace(cfg, seed=cfg.seed + 10_000)
            )
        data = augment_batches(
            data if data is not None else synthetic_batches(cfg),
            seed=aug_seed,
        )
    if (data is not None and args.prefetch_batches > 0
            and not hasattr(data, "seek")):
        # Fetch/decode of batch N+1 overlaps the train step on batch N.
        # (A SeekableFeed already prefetches inside its factory.)
        from oim_tpu.data.prefetch import prefetch_batches

        data = prefetch_batches(data, depth=args.prefetch_batches)

    from oim_tpu.common.profiling import profile_trace

    trainer = Trainer(cfg, axes=parse_mesh(args.mesh))
    try:
        with profile_trace(args.profile):
            loss = trainer.run(steps=args.steps, data=data, eval_data=eval_data)
        log.info("done", final_loss=round(loss, 4), **device_memory())
    finally:
        obs.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
