"""Prefill/decode disaggregation: tolerant role parsing (mixed-version
routing), chunked-prefill byte-identity across chunk sizes, the
prefill -> decode handoff held to solo generate(), the `oimctl --top`
ROLE column, and the split fleet end to end: under a bimodal mix with
long prompts in flight the router splits every long prompt, the decode
tier adopts the shipped chains, no routed token changes, and both tiers
drain to a zero-leak census."""

import numpy as np
import pytest


def teardown_module(_module):
    # This module compiles a lot of distinct executables (two 2-replica
    # clusters x prefill chunk buckets x adopt/resume paths). XLA's
    # in-process executable cache holds every one of them as live LLVM
    # code mappings, and the kernel caps a process at
    # vm.max_map_count (~65k) regions: leaving them cached pushes the
    # later serve smokes over the cap, which XLA answers with a
    # segfault mid-compile. Dropping the cache here costs the next
    # module a few recompiles and keeps the suite far from the cliff.
    import jax

    jax.clear_caches()


def test_replica_role_parse_tolerant():
    """The role rides the heartbeat row as plain JSON: a pre-role
    replica (key absent) and a buggy one (wrong type, unknown string)
    must BOTH read back as "mixed" — the router routes them exactly as
    before the tier split existed — while valid roles survive."""
    import json

    from oim_tpu.router.table import Replica

    def parse(extra):
        snap = {"endpoint": "127.0.0.1:1", "free_slots": 2}
        snap.update(extra)
        return Replica.parse("serve/r0", json.dumps(snap))

    assert parse({}).role == "mixed"            # pre-role heartbeat
    assert parse({"role": 7}).role == "mixed"   # wrong type
    assert parse({"role": "chef"}).role == "mixed"  # unknown string
    assert parse({"role": "prefill"}).role == "prefill"
    assert parse({"role": "decode"}).role == "decode"
    assert parse({"role": "mixed"}).role == "mixed"


def test_pick_skips_prefill_tier_unless_alone():
    """The stream pick must not pack decode work onto the prefill
    tier: a less-loaded prefill row loses to any non-prefill row — but
    an all-prefill table still routes (a prefill replica is a complete
    engine, just mis-packed), so a fleet mid-transition cannot strand
    requests."""
    from oim_tpu.router.router import RouterService
    from oim_tpu.router.table import Replica

    class FakeTable:
        def __init__(self, rows):
            self.rows = rows

        def replicas(self):
            return list(self.rows)

    prefill = Replica(replica_id="p0", endpoint="e0", free_slots=4,
                      max_batch=4, role="prefill")
    mixed = Replica(replica_id="m0", endpoint="e1", free_slots=1,
                    max_batch=4, role="mixed")
    svc = RouterService(FakeTable([prefill, mixed]))
    picked, _ = svc._pick_inner()
    assert picked.replica_id == "m0"
    svc_alone = RouterService(FakeTable([prefill]))
    picked, _ = svc_alone._pick_inner()
    assert picked.replica_id == "p0"


def _tiny_model(n_layers=2):
    import jax

    from oim_tpu.models import llama

    cfg = llama.tiny(vocab=64, dim=32, n_layers=n_layers)
    return llama.init(jax.random.PRNGKey(0), cfg), cfg


def _solo(params, cfg, prompt, n_new, temp, seed, max_seq):
    import jax

    from oim_tpu.models import generate as gen

    return gen.generate(
        params, np.asarray([prompt], np.int32), n_new, cfg,
        temperature=temp, rng=jax.random.PRNGKey(seed),
        max_seq=max_seq)[0, len(prompt):].tolist()


@pytest.mark.parametrize("chunk", [16, 13, 512])
def test_chunked_prefill_byte_identity(chunk):
    """--prefill-chunk must be invisible in the output: one block per
    slice, an odd size that never aligns with block boundaries, and a
    chunk >= the whole prompt (the no-op case) all produce the exact
    solo generate() tokens, greedy and sampled — while a resident
    decode stream interleaves between slices (the corruption the
    zeroed-row discipline exists to prevent)."""
    from oim_tpu.serve import ServeEngine

    params, cfg = _tiny_model()
    eng = ServeEngine(params, cfg, max_batch=2, max_seq=128,
                      queue_depth=8, prefix_block=16,
                      role="prefill", prefill_chunk=chunk)
    rng = np.random.RandomState(3)
    try:
        eng.submit([1, 2, 3], max_new=2).result(timeout=300)  # warm
        # A resident stream decoding WHILE the chunked prefill runs.
        resident_prompt = rng.randint(1, 64, size=5).tolist()
        resident = eng.submit(resident_prompt, max_new=24,
                              temperature=0.0, seed=9)
        for temp, seed in ((0.0, 1), (0.9, 2)):
            prompt = rng.randint(1, 64, size=49).tolist()
            toks = eng.submit(prompt, max_new=4, temperature=temp,
                              seed=seed).result(timeout=300)
            assert toks == _solo(params, cfg, prompt, 4, temp, seed,
                                 128), \
                f"chunk={chunk} temp={temp} diverged from solo"
        assert resident.result(timeout=300) == _solo(
            params, cfg, resident_prompt, 24, 0.0, 9, 128), \
            "the interleaved decode stream was corrupted"
    finally:
        eng.stop(drain=False, timeout=30)


def test_handoff_adopt_byte_identity_vs_solo():
    """The tentpole handoff at engine level: the prefill tier chunk-
    prefills a long prompt and its retirement exports the chain; a
    decode-tier engine that NEVER held the prefix adopts the shipped
    volume (the peer-fetch hit counter moves) and emits the exact solo
    generate() tokens, greedy and sampled."""
    from oim_tpu.common import metrics as M
    from oim_tpu.controller import MallocBackend
    from oim_tpu.controller.controller import ControllerService
    from oim_tpu.feeder import Feeder
    from oim_tpu.serve import ServeEngine
    from oim_tpu.serve.kvvolume import (
        PeerPrefixFetcher,
        config_fingerprint,
        export_chain,
    )

    params, cfg = _tiny_model()
    feeder = Feeder(controller=ControllerService(MallocBackend()))
    prefill = ServeEngine(params, cfg, max_batch=2, max_seq=128,
                          queue_depth=8, prefix_block=16,
                          role="prefill", prefill_chunk=16)
    decode = ServeEngine(params, cfg, max_batch=2, max_seq=128,
                         queue_depth=8, prefix_block=16, role="decode",
                         kv_fetch=PeerPrefixFetcher(
                             feeder, config_fingerprint(cfg, 16)))
    prefill.set_handoff_export(
        lambda eng, hashes: export_chain(eng, feeder, hashes))
    hit = M.SERVE_PREFIX_PEER_FETCHES.labels(outcome="hit")
    rng = np.random.RandomState(5)
    try:
        prompt = rng.randint(1, 64, size=49).tolist()  # 3 full blocks
        for eng in (prefill, decode):
            eng.submit([1, 2, 3], max_new=2).result(timeout=300)
        # Prompt phase on the prefill tier: retire ships the chain.
        prefill.submit(prompt, max_new=1).result(timeout=300)
        assert prefill.exported_volumes(), "retire exported nothing"
        for temp, seed in ((0.0, 4), (0.8, 5)):
            decode.evict_prefix_store()  # every trial truly peer-fetches
            before = hit.value
            toks = decode.submit(prompt, max_new=4, temperature=temp,
                                 seed=seed).result(timeout=300)
            assert hit.value > before, "decode never adopted the volume"
            assert toks == _solo(params, cfg, prompt, 4, temp, seed,
                                 128), \
                f"adopted output diverged from solo (temp={temp})"
    finally:
        prefill.stop(drain=False, timeout=30)
        decode.stop(drain=False, timeout=30)


def test_top_role_column_and_dash_degrade():
    """oimctl --top's ROLE column reads the oim_serve_role label whose
    sample is 1, and dash-degrades for pre-role scrapes (series
    absent) — while the KIND column (process kind) is untouched."""
    import json as json_mod

    from oim_tpu.cli.oimctl import render_top, top_row
    from oim_tpu.common.metrics import Registry

    def scrape(role=None):
        reg = Registry()
        reg.gauge("oim_serve_qps").set(1.0)
        if role is not None:
            reg.gauge("oim_serve_role",
                      labelnames=("role",)).labels(role=role).set(1)
        text = reg.render()
        ev = json_mod.dumps({"events": [], "dropped": 0})
        return lambda url, timeout=10.0: (
            ev if "/debug/events" in url else text)

    row = top_row("r0", "ALIVE", "serve", "127.0.0.1:1",
                  http_get=scrape(role="prefill"))
    assert row["tier"] == "prefill"
    rendered = render_top([row])
    assert "ROLE" in rendered and "KIND" in rendered
    assert "prefill" in rendered
    old = top_row("r0", "ALIVE", "serve", "127.0.0.1:1",
                  http_get=scrape())
    assert old["tier"] is None
    assert render_top([old]).count("serve") == 1  # KIND still renders


N_LONG = 3


@pytest.fixture(scope="module")
def split_fleet():
    """r0 the prompt tier (chunked prefill, a retired chain shipped as a
    content-addressed volume), r1 the stream tier (adopts shipped
    chains), behind one router; six short and three long requests, the
    longs arriving while the shorts decode."""
    from oim_tpu.common import metrics as M
    from oim_tpu.serve.kvvolume import (
        PeerPrefixFetcher,
        config_fingerprint,
        export_chain,
    )
    from tests import cluster as C

    facts = {}
    with C.cluster(replicas=2, max_seq=128, max_batch=4, prefix_block=16,
                   engine_kwargs=[dict(role="prefill", prefill_chunk=32),
                                  dict(role="decode")]) as sim:
        prefill, decode = C.engines(sim)
        feeder = sim.feeder()
        prefill.set_handoff_export(
            lambda eng, hashes: export_chain(eng, feeder, hashes))
        decode.set_kv_fetch(PeerPrefixFetcher(
            feeder, config_fingerprint(C.model()[1], 16)))
        sim.warm()
        split = M.SERVE_PREFILL_HANDOFFS.labels(outcome="split")
        hit = M.SERVE_PREFIX_PEER_FETCHES.labels(outcome="hit")
        before = split.value, hit.value
        # Fresh tokens a long prompt: a repeated one would hit a prefix
        # store and ship nothing.
        longs = C.mixed_requests(11, N_LONG, prompt_len=(49, 49),
                                 max_new=(4, 4))
        shorts = C.mixed_requests(12, 6)
        reqs = [r for trio in zip(shorts[::2], shorts[1::2], longs)
                for r in trio]
        results, errors = sim.routed_load(reqs, concurrency=5)
        exported = prefill.exported_volumes()
        facts.update(
            reqs=reqs, results=results, errors=errors,
            splits=split.value - before[0], hits=hit.value - before[1],
            exported=len(exported),
            solo=[C.solo(sim, *req) for req in reqs])

        pools = C.drain(sim)
        facts["left"] = {
            engine.role: (pool["used_pages"], engine.host_stats()["entries"],
                          engine.host_stats()["bytes"])
            for engine, pool in zip((prefill, decode), pools)}
        facts["after_unpublish"] = []
        for volume_id in exported.values():
            feeder.unpublish(volume_id)
            try:
                feeder.fetch_window(volume_id, 0, 16)
                facts["after_unpublish"].append("still served")
            except Exception as err:  # noqa: BLE001 - the test reads it
                facts["after_unpublish"].append(str(err))
    return facts


def test_router_splits_every_long_prompt_and_decode_adopts_it(split_fleet):
    assert split_fleet["splits"] == N_LONG, \
        "a long prompt skipped the prefill tier"
    assert split_fleet["hits"] == N_LONG, \
        "the decode tier recomputed a shipped chain"
    assert split_fleet["exported"] == N_LONG


def test_disagg_smoke_routed_streams_match_solo_generate(split_fleet):
    errors = split_fleet["errors"]
    assert not errors, f"a client saw the split: {errors[0]!r}"
    for req, tokens, solo in zip(split_fleet["reqs"], split_fleet["results"],
                                 split_fleet["solo"]):
        assert tokens == solo, f"routed {req} diverged from solo"


def test_both_tiers_drain_and_shipped_volumes_unpublish(split_fleet):
    assert split_fleet["left"] == {"prefill": (0, 0, 0),
                                   "decode": (0, 0, 0)}
    assert len(split_fleet["after_unpublish"]) == N_LONG
    for message in split_fleet["after_unpublish"]:
        assert "NOT_FOUND" in message
