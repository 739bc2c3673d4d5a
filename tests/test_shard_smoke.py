"""Sharded decode: one logical replica spans N members, tensor-parallel
over ICI.

* routed: a 2-member replica, whose weights and pool fit no single
  member's HBM budget, and a solo replica behind one router; every
  routed stream is its solo generate() run, the sharded replica serves
  its share, the ICI-allreduce histogram gains samples and no member
  pool keeps a page (the member-kill flip and its heal are the chaos
  rung ``shard_member_kill``, tests/test_chaos_smoke.py);
* the sharded restore reassembles byte-identically: concatenating every
  rank's slice along the Megatron split axes reproduces the full tree,
  and each rank staged exactly member_weight_bytes, not the blob;
* the engine's prefill/decode/spec-verify paths are byte-identical at
  shard 1 vs 2 (greedy AND sampled: the shard_map runs the same math,
  just distributed);
* the member-lease watch is what readiness folds in: a stale member
  flips stats()["ready"] false, moves the oim_serve_shard_members
  gauges, and emits exactly one lost/healed event pair per transition.
"""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def routed():
    import random

    from oim_tpu.chaos.ladder import _reqs
    from oim_tpu.chaos.sim import ClusterSim, model
    from oim_tpu.common import metrics as M
    from oim_tpu.serve.shard import member_weight_bytes

    # The full weights alone exhaust this budget: weights + pool fit one
    # member at shard 2 only.
    budget = member_weight_bytes(model()[0], 1)
    with ClusterSim(replicas=2, engine_kwargs=[
            dict(shard=2, member_hbm_budget=budget), {}]) as sim:
        sim.warm()
        sharded = sim.replicas[0]
        assert sharded.engine.stats()["shard_ready"] == 2
        reqs = _reqs(random.Random(20260809), 8, max_new=(4, 8))
        ici, done = M.SERVE_ICI_ALLREDUCE.labels().count, sharded.completed()
        results, errors = sim.routed_load(reqs, concurrency=4)
        yield (sim, reqs, results, errors, sharded.completed() - done,
               M.SERVE_ICI_ALLREDUCE.labels().count - ici)


def test_shard_smoke_routed_streams_match_solo_generate(routed):
    sim, reqs, results, errors, by_sharded, _ = routed
    assert not errors, f"{len(errors)} routed streams failed: {errors[0]!r}"
    assert sim.assert_byte_identity(reqs, results) == len(reqs)
    assert by_sharded >= 1, "the sharded replica served none of them"


def test_every_sharded_dispatch_times_one_allreduce(routed):
    assert routed[5] > 0, "oim_serve_ici_allreduce_seconds never observed"


def test_no_member_pool_keeps_a_page(routed):
    census = routed[0].leak_census()  # raises on a leak
    assert set(census["replicas"]) == {"r0", "r1"}


def test_sharded_restore_reassembles_byte_identically(tmp_path):
    import jax

    from oim_tpu.chaos.sim import model
    from oim_tpu.controller.controller import ControllerService
    from oim_tpu.controller.malloc_backend import MallocBackend
    from oim_tpu.feeder import Feeder
    from oim_tpu.serve import weights as W
    from oim_tpu.serve.shard import COL, ROW, member_weight_bytes

    params, _ = model()
    path = tmp_path / "w.oimw"
    W.save_packed(params, str(path))
    feeder = Feeder(controller=ControllerService(MallocBackend()))
    W.publish_weights(feeder, "reassembly-weights", str(path))
    full = W.restore_weights(feeder, "reassembly-weights")
    members = []
    for rank in range(2):
        members.append(W.restore_weights(
            feeder, "reassembly-weights", shard=2, rank=rank))
        # bytes_staged IS the member's HBM weight footprint: split
        # leaves contribute 1/shard, replicated leaves their full size.
        assert W.LAST_RESTORE["bytes_staged"] == member_weight_bytes(
            params, 2)
        assert W.LAST_RESTORE["rank"] == rank

    def leaves(tree):
        return {jax.tree_util.keystr(p): np.asarray(l)
                for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]}

    f = leaves(full)
    m0, m1 = (leaves(t) for t in members)
    assert set(f) == set(m0) == set(m1)
    for key, arr in f.items():
        name = key.rsplit("['", 1)[-1].rstrip("']")
        parts = [m0[key], m1[key]]
        if name in COL:
            joined = np.concatenate(parts, axis=-1)
        elif name in ROW:
            joined = np.concatenate(parts, axis=1)
        else:
            assert (parts[0] == parts[1]).all(), f"{key} diverged"
            joined = parts[0]
        assert joined.shape == arr.shape, key
        assert (joined == arr).all(), f"{key} does not reassemble"

    # Geometry that cannot split (dim 32 over 3 members) must refuse,
    # not truncate; rank outside the mesh likewise.
    with pytest.raises(ValueError):
        W.restore_weights(feeder, "reassembly-weights", shard=3, rank=0)
    with pytest.raises(ValueError):
        W.restore_weights(feeder, "reassembly-weights", shard=2, rank=2)


def _assert_shard_invariant(build):
    from oim_tpu.chaos.sim import model, solo_tokens
    from oim_tpu.serve import ServeEngine

    params, cfg = model()
    reqs = [([3, 1, 4, 1], 6, 0.0, 0),   # greedy: pinned to solo too
            ([2, 7, 1], 5, 0.7, 3)]      # sampled: shard-invariant
    if build:
        build = dict(draft_params=params, draft_cfg=cfg, spec_tokens=2)
    outs = {}
    for shard in (1, 2):
        eng = ServeEngine(params, cfg, max_batch=2, max_seq=64,
                          queue_depth=8, shard=shard, **build)
        try:
            outs[shard] = [
                eng.submit(p, max_new=n, temperature=t,
                           seed=s).result(timeout=300)
                for p, n, t, s in reqs]
        finally:
            eng.stop(drain=True, timeout=60)
        assert eng.pool_stats()["used_pages"] == 0
    assert outs[1] == outs[2], f"shard changed bytes ({build})"
    assert outs[2][0] == solo_tokens(reqs[0][0], reqs[0][1])


def test_engine_byte_identity_shard_1_vs_2():
    _assert_shard_invariant(build={})


@pytest.mark.slow
def test_spec_engine_byte_identity_shard_1_vs_2():
    # Same pin through the draft/verify path: 2 more engine builds, so
    # it rides the slow pass (`make pytest`) with the rest of the ladder.
    _assert_shard_invariant(build={"spec": True})


def test_member_hbm_budget_gate():
    from oim_tpu.chaos.sim import model
    from oim_tpu.serve import ServeEngine
    from oim_tpu.serve.shard import member_weight_bytes

    params, cfg = model()
    budget = member_weight_bytes(params, 1)  # weights fit, weights+pool don't
    with pytest.raises(ValueError, match="shard wider"):
        ServeEngine(params, cfg, max_batch=2, max_seq=64,
                    member_hbm_budget=budget)
    eng = ServeEngine(params, cfg, max_batch=2, max_seq=64, shard=2,
                      member_hbm_budget=budget)
    eng.stop(drain=False, timeout=30)


def test_member_watch_flips_readiness_gauges_and_events():
    from oim_tpu.chaos.sim import model
    from oim_tpu.common import events, metrics as M
    from oim_tpu.serve import ServeEngine

    events.configure(capacity=256)
    params, cfg = model()
    eng = ServeEngine(params, cfg, max_batch=2, max_seq=64,
                      queue_depth=4, shard=2)
    counts = {"ready": 2, "stale": 0, "total": 2}
    eng.set_member_watch(lambda: dict(counts))
    try:
        s = eng.stats()
        assert s["ready"] and s["shard_ready"] == 2 and s["shard_total"] == 2
        counts.update(ready=1, stale=1)
        s = eng.stats()
        assert not s["ready"], "stale member left the replica ready"
        assert s["shard_ready"] == 1
        assert M.SERVE_SHARD_MEMBERS.labels(state="ready").value == 1
        assert M.SERVE_SHARD_MEMBERS.labels(state="stale").value == 1
        counts.update(ready=2, stale=0)
        assert eng.stats()["ready"], "healed members never restored ready"
        # Repeated polls at a steady state must not re-emit.
        eng.stats()
        types = [e.type for e in events.recorder().events()]
        assert types.count(events.SHARD_MEMBER_LOST) == 1
        assert types.count(events.SHARD_MEMBER_HEALED) == 1
    finally:
        eng.stop(drain=False, timeout=30)


def test_top_shard_column_and_solo_dash():
    """oimctl --top renders the member census as ready/total — "1/2"
    IS the degraded-but-routed-away signal — and degrades to "-" for
    solo replicas (both gauges 0) and pre-shard scrapes (series
    absent), the PAGES/KV-TIER mixed-version stance."""
    import json as json_mod

    from oim_tpu.cli.oimctl import render_top, top_row
    from oim_tpu.common.metrics import Registry

    def scrape(ready=None, stale=None):
        reg = Registry()
        reg.gauge("oim_serve_qps").set(1.0)
        if ready is not None:
            g = reg.gauge("oim_serve_shard_members", labelnames=("state",))
            g.labels(state="ready").set(ready)
            g.labels(state="stale").set(stale)
        text = reg.render()
        ev = json_mod.dumps({"events": [], "dropped": 0})
        return lambda url, timeout=10.0: (
            ev if "/debug/events" in url else text)

    row = top_row("r0", "ALIVE", "serve", "127.0.0.1:1",
                  http_get=scrape(ready=1, stale=1))
    assert row["shard"] == (1.0, 2.0)
    rendered = render_top([row])
    assert "SHARD" in rendered and "1/2" in rendered
    # Solo replica: the canonical gauges exist but both read 0.
    solo = top_row("r0", "ALIVE", "serve", "127.0.0.1:1",
                   http_get=scrape(ready=0, stale=0))
    assert solo["shard"] is None
    # Pre-shard scrape: series absent entirely.
    old = top_row("r0", "ALIVE", "serve", "127.0.0.1:1",
                  http_get=scrape())
    assert old["shard"] is None
