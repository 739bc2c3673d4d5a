"""oim-serve: the continuous-batching inference daemon (new scope — the
serving plane ROADMAP item 2 turns the storage control plane into a
weight-distribution system).

Weights come from exactly one of three sources:

* ``--checkpoint-dir`` (+ ``--model``) — restore a trainer checkpoint in
  process (no control plane; single-node serving and smoke tests).
  ``--pack-to FILE`` additionally writes the packed weights blob, the
  artifact every replica publishes from.
* ``--weights-file`` — a packed blob (serve/weights.py). The daemon
  PUBLISHES it as a volume through its feeder — local (``--backend``) or
  remote (``--registry`` + ``--controller-id``) — and restores from the
  staged bytes. Publishing is idempotent and content-addressed: the
  FIRST replica stages from source, every replica whose controller was
  prestaged (``--prestage PEER_ID``, repeatable, or a prior replica's
  ``--prestage``) boots from an O(1) stage-cache hit with zero source
  re-reads.
* ``--weights-volume`` alone (remote mode) — the volume is already
  mapped on this replica's controller; just restore from it.

Serving: a fixed ``[max-batch, max-seq]`` continuous batch
(serve/engine.py) behind the ``oim.v1.Serve`` streaming Generate RPC.
SIGTERM / Ctrl-C drains gracefully: residents finish, queued requests
close as "drained", new ones get UNAVAILABLE.

    oim-serve --checkpoint-dir /ckpt --model llama-tiny \
        --endpoint tcp://0.0.0.0:9002 --max-batch 8 --max-seq 256
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import signal
import threading

from oim_tpu.cli.common import (
    add_common_flags,
    add_model_override_flag,
    add_observability_flags,
    add_registry_flag,
    device_memory,
    init_jax,
    load_tls_flags,
    parse_model_overrides,
    restore_checkpoint_params,
    setup_logging,
    start_observability,
    start_telemetry_row,
)
from oim_tpu.common.logging import from_context

DEFAULT_VOLUME = "weights"
# --model names the trainer has none for: llama.py's constant of each.
SERVED_ONLY = {"joyai-llm-flash": "JOYAI_LLM_FLASH",
               "nemotron-3-nano-30b": "NEMOTRON_3_NANO_30B",
               "solar-open2-250b": "SOLAR_OPEN2_250B",
               "gigachat35-432b-a28b": "GIGACHAT35_432B",
               "zaya1-8b": "ZAYA1_8B"}


def _load_params(args, log):
    """The params tree + model config from whichever source was given.
    Returns (params, model_cfg, feeder) — feeder is None in
    checkpoint-dir mode and otherwise shared with the draft loader, so
    two weights volumes ride one control-plane connection."""
    from oim_tpu.train import TrainConfig

    # The trainer's own --model-override: the served depth is the depth
    # that was trained (a mismatch with the weights is an error below,
    # never a truncation).
    overrides = parse_model_overrides(args.model_override)
    if args.model in SERVED_ONLY:
        # Served only: the trainer has no name for a block it cannot
        # train yet (param_logical_axes refuses latent attention and a
        # hybrid pattern).
        from oim_tpu.models import llama

        mcfg = dataclasses.replace(
            getattr(llama, SERVED_ONLY[args.model]), **overrides)
    else:
        mcfg = TrainConfig(
            model=args.model, model_overrides=overrides).model_config()
    if args.checkpoint_dir:
        params, step = restore_checkpoint_params(
            args.checkpoint_dir, mcfg, "serve")
        log.info("restored checkpoint", step=step, model=args.model,
                 n_layers=mcfg.n_layers)
        if args.pack_to:
            from oim_tpu.serve.weights import save_packed

            size = save_packed(params, args.pack_to)
            log.info("packed weights", path=args.pack_to, bytes=size)
        return params, mcfg, None

    # Packed-blob modes need the model config to shape the KV cache; the
    # blob itself carries only the param tree.
    feeder = _make_feeder(args)
    from oim_tpu.serve.weights import (
        publish_weights,
        restore_weights,
        weights_request,
    )

    if args.weights_file:
        request = weights_request(
            args.weights_volume, args.weights_file,
            os.path.getsize(args.weights_file))
        publish_weights(feeder, args.weights_volume, args.weights_file)
        for peer in args.prestage:
            _prestage_peer(feeder, request, peer, log)
    params = restore_weights(feeder, args.weights_volume)
    _check_depth(params, mcfg, args.weights_volume)
    log.info("restored weights volume", volume=args.weights_volume,
             n_layers=mcfg.n_layers)
    return params, mcfg, feeder


def _check_depth(params, mcfg, volume: str) -> None:
    """The stacked layer leaves carry the depth the weights were packed
    at; the configured depth must equal it (decoding N layers out of a
    deeper stack would be a silent truncation)."""
    from oim_tpu.models.llama import layer_groups

    depth = sum(g["attn_norm"].shape[0] for g in layer_groups(params))
    if depth != mcfg.n_layers:
        raise SystemExit(
            f"weights volume {volume!r} holds {depth} layers, the "
            f"configured model has n_layers={mcfg.n_layers}; pass the "
            "trainer's --model-override n_layers=N")


def _load_draft_params(args, log, feeder=None):
    """The speculative-decoding draft model, from either draft source.
    A packed blob rides the exact same control-plane fan-out as the
    target weights — a SECOND content-addressed volume, published once,
    prestaged to the same peers, O(1) cache-hit boots on every warmed
    replica."""
    from oim_tpu.train import TrainConfig

    mcfg = TrainConfig(model=args.draft_model).model_config()
    if args.draft_checkpoint_dir:
        params, step = restore_checkpoint_params(
            args.draft_checkpoint_dir, mcfg, "speculate")
        log.info("restored draft checkpoint", step=step,
                 model=args.draft_model)
        return params, mcfg

    if feeder is None:  # target came from a checkpoint dir
        feeder = _make_feeder(args)
    from oim_tpu.serve.weights import (
        publish_weights,
        restore_weights,
        weights_request,
    )

    if args.draft_weights_file:
        request = weights_request(
            args.draft_weights_volume, args.draft_weights_file,
            os.path.getsize(args.draft_weights_file))
        publish_weights(feeder, args.draft_weights_volume,
                        args.draft_weights_file)
        for peer in args.prestage:
            _prestage_peer(feeder, request, peer, log)
    # else --draft-restore-only: the volume is already mapped on this
    # replica's controller (prestaged by a peer's publish) — no blob
    # file on local disk, no redundant re-publish.
    params = restore_weights(feeder, args.draft_weights_volume)
    log.info("restored draft weights volume",
             volume=args.draft_weights_volume)
    return params, mcfg


def _make_feeder(args):
    from oim_tpu.feeder import Feeder

    if args.backend:
        from oim_tpu.controller.controller import ControllerService

        if args.backend == "tpu":
            from oim_tpu.controller.tpu_backend import TPUBackend

            backend = TPUBackend()
        else:
            from oim_tpu.controller import MallocBackend

            backend = MallocBackend()
        return Feeder(controller=ControllerService(backend),
                      window_compress=args.window_compress)
    if not (args.registry and args.controller_id):
        raise SystemExit(
            "--weights-file/--weights-volume need --backend (local) or "
            "--registry + --controller-id (remote)"
        )
    return Feeder(
        registry_address=args.registry,
        controller_id=args.controller_id,
        tls=load_tls_flags(args),
        window_compress=args.window_compress,
    )


def _prestage_peer(feeder, request, peer: str, log) -> None:
    """Warm ``peer``'s stage cache with the weights content through the
    registry proxy, so that replica's later publish is an O(1) hit."""
    import grpc

    from oim_tpu.registry.registry import CONTROLLER_ID_META
    from oim_tpu.spec import ControllerStub

    try:
        ControllerStub(feeder._registry_channel()).PrestageVolume(
            request, metadata=[(CONTROLLER_ID_META, peer)], timeout=60.0)
        log.info("prestaged replica", peer=peer, volume=request.volume_id)
    except grpc.RpcError as err:
        log.warning("replica prestage failed", peer=peer,
                    error=err.code().name)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser("oim-serve")
    parser.add_argument(
        "--endpoint", default="tcp://0.0.0.0:9002",
        help="listen endpoint (tcp:// or unix://)",
    )
    parser.add_argument("--model", default="llama-tiny",
                        choices=("llama-tiny", "llama-tiny-moe", "llama3-8b",
                                 *SERVED_ONLY))
    add_model_override_flag(parser)
    parser.add_argument("--checkpoint-dir", default="",
                        help="restore a trainer checkpoint in process")
    parser.add_argument(
        "--pack-to", default="",
        help="with --checkpoint-dir: also write the packed weights blob "
             "(the file replicas publish with --weights-file)")
    parser.add_argument(
        "--weights-file", default="",
        help="packed weights blob to publish-and-restore through the "
             "control plane (idempotent; a prestaged replica's publish "
             "is an O(1) stage-cache hit)")
    parser.add_argument(
        "--weights-volume", default=DEFAULT_VOLUME,
        help="volume id for the weights (with --weights-file: publish "
             "under this id; alone in remote mode: restore the already-"
             "mapped volume)")
    parser.add_argument(
        "--weights-version", default="",
        help="weights version advertised in the serve/<id> row (rolling "
             "upgrades: the autoscaler drains replicas whose advertised "
             "version differs from the declared one, and routers pin a "
             "retried request to its first attempt's version). Empty = "
             "unversioned")
    parser.add_argument(
        "--restore-only", action="store_true",
        help="remote mode without --weights-file: restore "
             "--weights-volume as already mapped on the controller")
    parser.add_argument("--backend", default="",
                        choices=("", "malloc", "tpu"),
                        help="local mode: in-process controller backend")
    add_registry_flag(parser, help_suffix="remote mode")
    parser.add_argument("--controller-id", default="",
                        help="remote mode: this replica's controller")
    parser.add_argument(
        "--prestage", action="append", default=[],
        help="controller id to PrestageVolume the weights to after "
             "publishing (repeatable: fan the content out so each "
             "replica's own publish hits its stage cache)")
    parser.add_argument(
        "--serve-id", default="",
        help="register this replica in the routing table: a TTL-leased "
             "serve/<id> registry row with endpoint + load snapshot, "
             "re-published every --heartbeat seconds (needs --registry; "
             "under mTLS the id must be the host's controller id or "
             "'<controller-id>.<suffix>')")
    parser.add_argument(
        "--advertise", default="",
        help="endpoint routers dial for this replica (default: the "
             "bound listen address — override when clients reach this "
             "host through a different name/VIP; required when the "
             "listen endpoint binds a wildcard address)")
    parser.add_argument(
        "--heartbeat", type=float, default=10.0,
        help="seconds between serve/<id> row re-publishes; the row's "
             "lease is 2.5x this, so dead replicas vanish from routing "
             "after ~2.5 missed beats")
    parser.add_argument("--max-batch", type=int, default=8,
                        help="decode-batch slots (continuous batch width)")
    parser.add_argument("--max-seq", type=int, default=256,
                        help="KV cache length: prompt + generated tokens "
                             "per request must fit")
    parser.add_argument("--queue-depth", type=int, default=64,
                        help="bounded admission queue; full = new requests "
                             "answer RESOURCE_EXHAUSTED")
    parser.add_argument(
        "--prefix-cache-bytes", type=int, default=64 << 20,
        help="byte budget for the prompt-prefix KV cache (LRU; retired "
             "requests donate their prompt K/V, admissions with a "
             "cached prefix prefill only the tail). 0 disables prefix "
             "reuse")
    parser.add_argument(
        "--prefix-block", type=int, default=16,
        help="tokens per prefix-cache block: prefixes are shared at "
             "this granularity (smaller = finer reuse, more entries "
             "and more compiled prefill programs); routers and this "
             "replica hash identically, so the value is advertised in "
             "the serve/<id> row")
    parser.add_argument(
        "--kv-page-tokens", type=int, default=0,
        help="tokens per KV page (paged KV cache). Default 0 = "
             "--prefix-block, so a prefix block IS a page — the unit "
             "zero-copy prefix sharing needs; any other value requires "
             "--prefix-cache-bytes 0")
    parser.add_argument(
        "--kv-pool-tokens", type=int, default=0,
        help="total KV tokens in the page pool ALL slots share "
             "(default 0 = max-batch x max-seq, the dense-equivalent "
             "HBM). Size it smaller to overcommit decode slots against "
             "real prompt lengths: admission reserves only "
             "prompt+max_new pages, and an exhausted pool queues "
             "(RESOURCE_EXHAUSTED past --queue-depth) instead of "
             "OOMing")
    parser.add_argument(
        "--kv-host-bytes", type=int, default=0,
        help="host-RAM budget for demoted KV prefix pages (the second "
             "tier): prefix-store evictions under pressure copy D2H "
             "into an LRU here instead of dropping, and a later hit "
             "re-stages H2D. 0 disables tiering")
    parser.add_argument(
        "--kv-peer-fetch", action="store_true",
        help="resolve prefix misses against peer-exported KV volumes "
             "(content-addressed kvchain-* volumes on the control "
             "plane) before recomputing; any failure falls back to "
             "local recompute. Needs a feeder (--backend or remote "
             "mode)")
    parser.add_argument(
        "--kv-export", action="store_true",
        help="publish this replica's hot prefix chains as content-"
             "addressed KV volumes every --heartbeat seconds, so peers "
             "with --kv-peer-fetch skip the prefill. Needs a feeder")
    parser.add_argument(
        "--role", default="mixed",
        choices=("prefill", "decode", "mixed"),
        help="disaggregation role, advertised in the heartbeat row: "
             "prefill = prompt tier (big-batch chunked prefill; each "
             "retirement exports the finished chain as a content-"
             "addressed kvchain volume — needs a control plane), "
             "decode = stream tier (pair with --kv-peer-fetch to adopt "
             "shipped pages), mixed = unified legacy behavior. The "
             "router splits long-prompt requests across the tiers and "
             "falls back to decode-local prefill on any defect")
    parser.add_argument(
        "--prefill-chunk", type=int, default=0,
        help="chunked prefill: prefill long prompts in slices of this "
             "many tokens, one decode round over resident slots "
             "between slices and between two admissions, so no long "
             "prompt stalls the batch's decode cadence by more than a "
             "slice (byte-identical — chunking only changes dispatch "
             "order). 0 = one full-length prefill")
    parser.add_argument(
        "--window-compress", action="store_true",
        help="ask volume servers to zlib-compress ReadVolume window "
             "chunks (applied only when smaller; negotiated per stream "
             "so mixed versions interop). Off by default: weights and "
             "KV bytes are mostly incompressible, cold text-like "
             "extents are not")
    parser.add_argument(
        "--spec-tokens", type=int, default=0,
        help="speculative decoding: tokens the draft model proposes "
             "per verify round (0 disables). Needs exactly one draft "
             "source (--draft-checkpoint-dir or --draft-weights-file). "
             "Greedy output stays byte-identical to plain decode; "
             "sampled output is distribution-exact (acceptance ratio "
             "test); an adaptive valve falls back to plain decode when "
             "the rolling acceptance rate stops paying")
    parser.add_argument("--draft-model", default="llama-tiny",
                        choices=("llama-tiny", "llama-tiny-moe",
                                 "llama3-8b"),
                        help="draft model config (must share the "
                             "target's vocabulary)")
    parser.add_argument(
        "--draft-checkpoint-dir", default="",
        help="restore the draft model from a trainer checkpoint in "
             "process")
    parser.add_argument(
        "--draft-weights-file", default="",
        help="packed draft weights blob to publish-and-restore as a "
             "SECOND content-addressed volume (same --prestage fan-out "
             "as the target weights: publish once, O(1) cache-hit "
             "boots everywhere)")
    parser.add_argument(
        "--draft-weights-volume", default="draft-weights",
        help="volume id for the draft weights blob")
    parser.add_argument(
        "--draft-restore-only", action="store_true",
        help="remote mode without --draft-weights-file: restore "
             "--draft-weights-volume as already mapped on the "
             "controller (a warmed replica boots without the blob "
             "file — the --restore-only of the draft volume)")
    parser.add_argument(
        "--spec-pool-tokens", type=int, default=0,
        help="total KV tokens in the DRAFT model's page pool (default "
             "0 = the target pool's token count; the draft's pages are "
             "smaller in bytes). A request whose draft pages can't be "
             "mapped decodes plainly instead of waiting")
    parser.add_argument(
        "--shard", type=int, default=1,
        help="tensor-parallel width: ONE logical replica spans this "
             "many member devices over ICI (attention heads and MLP "
             "columns split Megatron-style, one allreduce per layer; "
             "greedy output stays byte-identical to --shard 1). With "
             "--serve-id, each member holds its own TTL lease under "
             "serve/<id>.member.<k>; a lapsed member flips the replica "
             "not-ready so routers rotate away")
    parser.add_argument(
        "--member-hbm-budget", type=int, default=0,
        help="per-member HBM byte budget: refuse to boot (with the "
             "shard width that WOULD fit) when one member's weight "
             "slice + KV pool slice exceeds it — a deterministic "
             "admission gate, not an OOM. 0 disables the check")
    parser.add_argument("--stream-tokens", type=int, default=1,
                        help="token-stream granularity: the first token "
                             "flushes immediately, later deltas batch up "
                             "to this many tokens per message (1 = every "
                             "token; raise to cut per-message serving "
                             "overhead on chatty streams)")
    parser.add_argument("--default-max-new", type=int, default=64,
                        help="decode budget when the request leaves "
                             "max_new_tokens unset")
    parser.add_argument("--drain-timeout", type=float, default=60.0,
                        help="graceful-drain budget on shutdown")
    parser.add_argument("--platform", default="",
                        help="jax platform, overriding JAX_PLATFORMS "
                             "(tpu | cpu); explicit, so a missing chip "
                             "is an error and never a CPU fallback")
    add_common_flags(parser)
    add_observability_flags(parser)
    args = parser.parse_args(argv)
    setup_logging(args)
    log = from_context()

    sources = bool(args.checkpoint_dir) + bool(args.weights_file) \
        + bool(args.restore_only)
    if sources != 1:
        raise SystemExit(
            "exactly one weights source required: --checkpoint-dir, "
            "--weights-file, or --restore-only (+ --weights-volume)"
        )
    draft_sources = bool(args.draft_checkpoint_dir) \
        + bool(args.draft_weights_file) + bool(args.draft_restore_only)
    if args.spec_tokens > 0 and draft_sources != 1:
        raise SystemExit(
            "--spec-tokens needs exactly one draft source: "
            "--draft-checkpoint-dir, --draft-weights-file, or "
            "--draft-restore-only (+ --draft-weights-volume)")
    if draft_sources and args.spec_tokens < 1:
        raise SystemExit(
            "a draft source without --spec-tokens >= 1 does nothing; "
            "set the proposal depth or drop the draft flags")
    if args.draft_restore_only and args.backend:
        raise SystemExit(
            "--draft-restore-only restores an already-mapped volume "
            "and needs remote mode (--registry + --controller-id)")
    if args.prestage and args.backend:
        # _prestage_peer routes through the registry proxy; a local
        # in-process backend has no registry to route through.
        raise SystemExit("--prestage needs remote mode (--registry + "
                         "--controller-id), not --backend")
    if args.serve_id and not args.registry:
        raise SystemExit("--serve-id registers in the routing table and "
                         "needs --registry")
    if (args.kv_peer_fetch or args.kv_export) and args.checkpoint_dir:
        # Both sides of fleet prefix sharing move KV bytes over the
        # control plane; checkpoint-dir mode has no feeder at all.
        raise SystemExit(
            "--kv-peer-fetch/--kv-export need a control plane "
            "(--backend or --registry + --controller-id), not "
            "--checkpoint-dir")
    if args.role == "prefill" and args.checkpoint_dir:
        # A prefill replica's entire product is the exported chain;
        # without a feeder there is nowhere to ship pages to.
        raise SystemExit(
            "--role prefill exports KV chains and needs a control "
            "plane (--backend or --registry + --controller-id), not "
            "--checkpoint-dir")
    init_jax(args.platform)
    obs = start_observability(args, "oim-serve")

    from oim_tpu.serve import ServeEngine, ServeService, serve_server

    params, mcfg, feeder = _load_params(args, log)
    draft_params, draft_mcfg = (None, None)
    if args.spec_tokens > 0:
        draft_params, draft_mcfg = _load_draft_params(
            args, log, feeder=feeder)
    kv_fetch = None
    if args.kv_peer_fetch:
        from oim_tpu.serve.kvvolume import (
            PeerPrefixFetcher,
            config_fingerprint,
        )

        page_tokens = args.kv_page_tokens or args.prefix_block
        kv_fetch = PeerPrefixFetcher(
            feeder, config_fingerprint(mcfg, page_tokens))
    engine = ServeEngine(
        params, mcfg,
        max_batch=args.max_batch,
        max_seq=args.max_seq,
        queue_depth=args.queue_depth,
        default_max_new=args.default_max_new,
        prefix_cache_bytes=args.prefix_cache_bytes,
        prefix_block=args.prefix_block,
        kv_page_tokens=args.kv_page_tokens,
        kv_pool_tokens=args.kv_pool_tokens,
        kv_host_bytes=args.kv_host_bytes,
        kv_fetch=kv_fetch,
        draft_params=draft_params,
        draft_cfg=draft_mcfg,
        spec_tokens=args.spec_tokens,
        spec_pool_tokens=args.spec_pool_tokens,
        shard=args.shard,
        member_hbm_budget=args.member_hbm_budget,
        role=args.role,
        prefill_chunk=args.prefill_chunk,
    )
    if args.role == "prefill" and feeder is not None:
        # The prefill tier exports at RETIREMENT, synchronously: the
        # decode pick is already waiting on the volume, so the lazy
        # --kv-export sweep (below) is the wrong vehicle for handoffs.
        from oim_tpu.serve.kvvolume import export_chain

        engine.set_handoff_export(
            lambda eng, hashes: export_chain(eng, feeder, hashes))
    server = serve_server(
        args.endpoint,
        ServeService(engine, stream_tokens=args.stream_tokens),
        tls=load_tls_flags(args))
    log.info(
        "oim-serve serving", endpoint=args.endpoint, addr=server.addr,
        model=args.model, n_layers=mcfg.n_layers, max_batch=args.max_batch,
        max_seq=args.max_seq, shard=args.shard,
        decode_attention=engine.decode_attention,
        prefill_attention=engine.prefill_attention, **device_memory(),
    )

    registration = None
    members = None
    if args.serve_id:
        from oim_tpu.serve import ServeRegistration

        advertise = args.advertise or server.addr
        host = advertise.rsplit(":", 1)[0]
        if host in ("0.0.0.0", "[::]", "::"):
            # Publishing the wildcard bind address would make every
            # router dial ITS OWN loopback (connection refused at best,
            # a different colocated replica at worst).
            raise SystemExit(
                f"--serve-id would advertise the wildcard address "
                f"{advertise!r}; pass --advertise host:port with the "
                f"address routers should dial")
        if args.shard > 1:
            # Member leases BEFORE the serve row's first beat, so the
            # row registers ready (the row's readiness folds in the
            # member census; a row published first would flap
            # not-ready -> ready on its opening beats).
            from oim_tpu.serve.shard import ShardMembers

            members = ShardMembers(
                args.serve_id, args.shard, args.registry,
                interval=args.heartbeat, tls=load_tls_flags(args))
            members.start()
            engine.set_member_watch(members.member_counts)
            log.info("member leases registered", shard=args.shard,
                     serve_id=args.serve_id)
        registration = ServeRegistration(
            args.serve_id, advertise, engine,
            args.registry, interval=args.heartbeat,
            tls=load_tls_flags(args), version=args.weights_version)
        registration.start()
        log.info("registered in routing table", serve_id=args.serve_id,
                 advertise=advertise, heartbeat_s=args.heartbeat)

    export_stop = threading.Event()
    if args.kv_export:
        from oim_tpu.serve.kvvolume import export_chain

        def _export_loop():
            elog = from_context()
            while not export_stop.wait(args.heartbeat):
                done = set(engine.exported_volumes())
                for chain in engine.hot_chains():
                    if not chain or chain[-1] in done:
                        continue
                    try:
                        # Returns None when the chain partially evicted
                        # since admission — not an error, just cold.
                        export_chain(engine, feeder, list(chain))
                    except Exception as err:  # noqa: BLE001 — keep beating
                        elog.warning("kv chain export failed",
                                     error=repr(err))

        threading.Thread(target=_export_loop, name="oim-kv-export",
                         daemon=True).start()
        log.info("kv chain exporter started", interval_s=args.heartbeat)

    telemetry_default = args.serve_id or (
        f"{args.controller_id}.serve" if args.controller_id else "")
    start_telemetry_row(
        obs, args.telemetry_id or telemetry_default, "serve",
        args.registry, tls=load_tls_flags(args))

    drained = threading.Event()

    def drain(*_):
        # Signal-safe: flip an event the main thread acts on.
        drained.set()

    signal.signal(signal.SIGTERM, drain)
    try:
        while not drained.wait(0.5):
            pass
    except KeyboardInterrupt:
        pass
    log.info("draining", active=engine.active_slots,
             queued=engine.queue_len)
    export_stop.set()
    if registration is not None:
        # ready: false FIRST, so routers rotate away while the residents
        # below finish on their still-open streams.
        registration.announce_draining()
    engine.stop(drain=True, timeout=args.drain_timeout)
    if registration is not None:
        registration.stop(deregister=True)
    if members is not None:
        members.stop(deregister=True)
    server.stop()
    obs.stop()
    log.info("stopped", **device_memory())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
