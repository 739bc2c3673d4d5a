"""Ring-1 tests for the serving plane (oim_tpu/serve).

The invariants the continuous-batching engine must hold (engine.py
docstring): mid-flight admission produces BYTE-IDENTICAL tokens vs. a
solo ``generate()`` run per request (greedy and sampled); a retired
slot leaks nothing into its next occupant; the bounded admission queue
refuses (never silently queues); cancel evicts the slot. Plus the
weight-distribution path (pack -> publish -> prestage -> O(1) restore)
and the ``oim.v1.Serve`` gRPC surface, ending in the PR's acceptance
run: publish a checkpoint once, prestage 2 serving replicas (second
restore provably re-reads NOTHING from source), then 16+ concurrent
streaming requests admitted mid-flight, each byte-identical to solo.
"""

import dataclasses
import threading
import time
import unittest.mock

import grpc
import numpy as np
import pytest

import jax

from oim_tpu.common import metrics as M
from oim_tpu.common.meshcoord import MeshCoord
from oim_tpu.controller import malloc_backend
from oim_tpu.controller.controller import (
    Controller,
    ControllerService,
    controller_server,
)
from oim_tpu.controller.malloc_backend import MallocBackend
from oim_tpu.data import plane
from oim_tpu.feeder import Feeder
from oim_tpu.models import generate as gen, llama
from oim_tpu.registry.db import MemRegistryDB
from oim_tpu.registry.registry import CONTROLLER_ID_META, RegistryService, registry_server
from oim_tpu.serve import (
    Draining,
    QueueFull,
    ServeEngine,
    ServeService,
    pack_params,
    save_packed,
    unpack_params,
)
from oim_tpu.serve.service import serve_server
from oim_tpu.serve.weights import publish_weights, restore_weights, weights_request
from oim_tpu.spec import ControllerStub, RegistryStub, ServeStub, pb
from oim_tpu.common import tlsutil


def wait_for(predicate, timeout=15.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


@pytest.fixture(scope="module")
def model():
    """One tiny model for the whole module: every ServeEngine build pays
    a prefill+decode jit, so tests share params/config where they can."""
    cfg = llama.tiny(vocab=64, dim=32, n_layers=2)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    return params, cfg


def solo_tokens(params, cfg, prompt, n_new, temperature=0.0, seed=0,
                max_seq=64):
    """What a per-request generate() run yields — the byte-identity
    reference for every engine output."""
    out = gen.generate(
        params, np.asarray([prompt], np.int32), n_new, cfg,
        temperature=temperature, rng=jax.random.PRNGKey(seed),
        max_seq=max_seq)
    return out[0, len(prompt):].tolist()


@pytest.fixture
def engine(model):
    params, cfg = model
    eng = ServeEngine(params, cfg, max_batch=2, max_seq=64, queue_depth=8)
    yield eng
    eng.stop(drain=False, timeout=30)


class TestEngineInvariants:
    def test_stats_say_which_attention_the_decode_program_takes(
            self, engine, model):
        """The dispatch word of ops/paged_attention.py rides stats() (the
        serve/<id> row): on the CPU the reference path, and the kernel
        where the rule finds its shapes on a TPU. No option selects it."""
        assert engine.stats()["decode_attention"] == "jnp_gather"
        assert engine.stats()["prefill_attention"] == "jnp_gather"
        params, cfg = model  # head_dim 16: off the kernel's tiling
        wide = dataclasses.replace(cfg, head_dim=128)
        with unittest.mock.patch.object(
                jax, "default_backend", lambda: "tpu"):
            for c, want in ((cfg, ("jnp_gather", "jnp_gather")),
                            (wide, ("pallas_paged", "pallas_paged_prefill"))):
                eng = ServeEngine(llama.init(jax.random.PRNGKey(0), c), c,
                                  max_batch=2, max_seq=64, queue_depth=8)
                try:
                    assert (eng.decode_attention,
                            eng.prefill_attention) == want
                finally:
                    eng.stop(drain=False, timeout=30)

    def test_midflight_admission_byte_identical(self, model):
        """More requests than slots, mixed greedy/sampled, mixed lengths:
        every admission happens against a batch mid-decode, and every
        output must still match its solo run token-for-token."""
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=2, max_seq=64,
                          queue_depth=16)
        try:
            reqs = [
                ([1, 2, 3], 8, 0.0, 0),
                ([5, 6], 10, 0.7, 1),
                ([7, 8, 9, 10, 11], 6, 0.0, 2),
                ([12], 12, 1.3, 3),
                ([3, 1, 4, 1, 5, 9, 2, 6], 7, 0.0, 4),
                ([42, 17], 9, 0.5, 5),
            ]
            handles = [
                eng.submit(p, max_new=n, temperature=t, seed=s)
                for p, n, t, s in reqs
            ]
            outs = [h.result(timeout=120) for h in handles]
        finally:
            eng.stop(timeout=30)
        for (p, n, t, s), out in zip(reqs, outs):
            assert out == solo_tokens(params, cfg, p, n, t, s), (p, t, s)

    def test_chunked_prefill_puts_a_decode_round_between_two_admissions(
            self, model):
        """With ``prefill_chunk`` a resident never waits for more than one
        slice: between any two prefill dispatches (two slices of one
        prompt, or the last slice of one admission and the first of the
        next) the residents get a decode round. Tokens stay solo's."""
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=4, max_seq=64,
                          queue_depth=8, prefill_chunk=8)
        order = []
        prefill, decode = eng._prefill, eng._decode_once
        eng._prefill = lambda *a: (order.append("slice"), prefill(*a))[1]
        eng._decode_once = lambda **kw: (order.append("round"), decode(**kw))[1]
        try:
            first = eng.submit([1, 2, 3], max_new=40)
            assert wait_for(lambda: "round" in order)
            prompts = [list(range(1, 21)), list(range(30, 42)), [5, 6, 7]]
            # a round takes milliseconds: all three queue during one
            handles = [eng.submit(p, max_new=4) for p in prompts]
            outs = [h.result(timeout=120) for h in handles]
            first.result(timeout=120)
        finally:
            eng.stop(timeout=30)
        assert order.count("slice") == 1 + 3 + 2 + 1
        assert "slice slice" not in " ".join(order)
        for p, out in zip(prompts, outs):
            assert out == solo_tokens(params, cfg, p, 4)

    def test_chunked_prefill_keeps_the_device_a_dispatch_ahead(self, model):
        """No slice but a prompt's last is waited for: with a resident,
        the round is dispatched behind the slice in flight and the next
        slice behind the round, BEFORE the round's tokens are fetched and
        emitted — so the device never idles between them, and the host's
        time is in no resident's gap across a slice. Tokens stay solo's."""
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=4, max_seq=64,
                          queue_depth=8, prefill_chunk=8)
        order = []
        prefill, step, emit = eng._prefill, eng._step, eng._emit
        eng._prefill = lambda *a: (order.append("slice"), prefill(*a))[1]
        eng._step = lambda *a: (order.append("step"), step(*a))[1]
        eng._emit = lambda *a: (order.append("emit"), emit(*a))[1]
        try:
            first = eng.submit([1, 2, 3], max_new=40)
            assert wait_for(lambda: "step" in order)
            del order[:]
            prompt = list(range(1, 30))  # 4 slices: 8, 8, 8, 5
            out = eng.submit(prompt, max_new=4).result(timeout=120)
            first.result(timeout=120)
        finally:
            eng.stop(timeout=30)
        at = [i for i, what in enumerate(order) if what == "slice"]
        assert len(at) == 4
        for i in at[1:]:  # each later slice: right behind a round's dispatch
            assert order[i - 1] == "step" and order[i + 1] == "emit", order
        assert out == solo_tokens(params, cfg, prompt, 4)

    @staticmethod
    def _record(eng):
        """``order``: every decode step's and prefill's dispatch ("step",
        "slice"), every wait for a prompt's first token ("token"), every
        mirror read-back ("sync") and every emitted token (("emit", its
        request)), as the engine's thread makes them."""
        order = []
        prefill, step, emit = eng._prefill, eng._step, eng._emit
        token, sync = eng._count_expert_rungs, eng._sync_host
        eng._prefill = lambda *a: (order.append("slice"), prefill(*a))[1]
        eng._step = lambda *a: (order.append("step"), step(*a))[1]
        eng._emit = lambda req, t: (order.append(("emit", req)),
                                    emit(req, t))[1]
        eng._count_expert_rungs = lambda *a: (order.append("token"),
                                              token(*a))[1]
        eng._sync_host = lambda: (order.append("sync"), sync())[1]
        return order

    def test_a_round_is_dispatched_before_the_one_before_it_is_emitted(
            self, model):
        """Steady state, one stream: every step but the first after an
        upload is dispatched BEFORE the emit of the round before it, the
        step past the stream's last token too (the overrun step: its token
        is in no stream). The device never waits for the emit loop."""
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=2, max_seq=64)
        order = self._record(eng)
        try:
            h = eng.submit([1, 2, 3], max_new=10)
            out = h.result(timeout=120)
        finally:
            eng.stop(timeout=30)
        e = ("emit", h._req)
        assert order == (["slice", "token", "sync", e, "step"]
                         + ["step", e] * 9), order
        assert out == solo_tokens(params, cfg, [1, 2, 3], 10)
        assert len(out) == 10 and eng.stats()["overrun_rows"] == 1

    @pytest.mark.parametrize("chunk", [0, 8])
    def test_an_admission_lands_the_round_in_flight_before_it_waits(
            self, model, chunk):
        """With a resident, a prompt's first slice (and a one-shot prefill)
        is dispatched BEHIND a round in flight, and the host lands every
        round in flight — the resident's emits — BEFORE it waits for the
        prompt's token. The other order doubles the resident's gap across
        a last slice (slice + round + slice): the host would hold back a
        finished round's tokens for the whole of the slice behind it."""
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=4, max_seq=64,
                          queue_depth=8, prefill_chunk=chunk)
        order = self._record(eng)
        # The engine's thread waits inside the landing of the resident's
        # fifth token until the prompt is queued: a round is in flight.
        at_five, queued = hold_at_fifth_token(eng, max_new=40)
        try:
            first = eng.submit([1, 2, 3], max_new=40)
            assert at_five.wait(60)
            prompt = list(range(1, 30))  # chunk 8: slices of 8, 8, 8, 5
            late = eng.submit(prompt, max_new=4)
            queued.set()
            out = late.result(timeout=120)
            first.result(timeout=120)
        finally:
            eng.stop(timeout=30)
        e = ("emit", first._req)
        at = [i for i, what in enumerate(order) if what == "slice"][1:]
        assert len(at) == (4 if chunk else 1)
        # Behind a round in flight: one more step dispatched than landed.
        before = order[:at[0]]
        assert before.count("step") == before.count(e) - 1 + 1, before
        # The last (or only) slice, then the resident's rounds landed, and
        # only then the wait: one round in flight beside a one-shot prefill,
        # two behind a later slice (the round it was queued behind, and the
        # one before that, landed by the dispatching round itself).
        landed = [e, e] if chunk else [e]
        i = at[-1]
        assert order[i + 1:i + 2 + len(landed)] == landed + ["token"], order
        # After the admission: mirrors back, the prompt's own first token,
        # and a round dispatched onto an empty queue, nothing to land yet.
        assert order[i + 2 + len(landed):i + 5 + len(landed)] == [
            "sync", ("emit", late._req), "step"], order
        assert out == solo_tokens(params, cfg, prompt, 4)

    def test_the_counters_read_what_a_scripted_run_implies(self, model):
        """A resident of 20 tokens, a second request admitted beside it at
        its fifth (4 tokens: it leaves first), then a third alone that ends
        by EOS. Three admissions, each into a loop pass of its own, make
        three drained rounds (uploaded operands, nothing in flight); every
        other round is dispatched ahead. Each retirement is one overrun
        row, and each time the engine runs empty one round has stepped
        nothing else. ``stats()``, the exported counters and the stop line
        carry the same numbers."""
        import io

        from oim_tpu.common import logging as oim_logging

        params, cfg = model
        ref = solo_tokens(params, cfg, [7, 8], 8)
        eos = next(t for t in ref[1:] if t != ref[0])
        ref = ref[:ref.index(eos) + 1]
        eng = ServeEngine(params, cfg, max_batch=2, max_seq=64)
        rounds = {how: M.SERVE_DECODE_ROUNDS.labels(dispatch=how).value
                  for how in ("ahead", "drained")}
        overrun = M.SERVE_OVERRUN_ROWS.value
        at_five, queued = hold_at_fifth_token(eng, max_new=20)
        log = io.StringIO()
        try:
            first = eng.submit([1, 2, 3], max_new=20)
            assert at_five.wait(60)
            second = eng.submit([4, 5], max_new=4)
            queued.set()
            assert second.result(timeout=120) == solo_tokens(
                params, cfg, [4, 5], 4)
            assert len(first.result(timeout=120)) == 20
            by_eos = eng.submit([7, 8], max_new=8, eos=eos)
            assert by_eos.result(timeout=120) == ref
            assert by_eos.finish_reason == "eos"
        finally:
            with oim_logging.with_logger(oim_logging.Logger(output=log)):
                eng.stop(timeout=30)
        stats = eng.stats()
        # 19 rounds carry the resident's tokens, len(ref) - 1 the third's,
        # and one more behind each of the two runs steps a retired row only.
        steps = 19 + len(ref) - 1
        assert stats["target_steps"] == steps
        assert stats["decode_rounds_drained"] == 3
        assert stats["decode_rounds_ahead"] == steps + 2 - 3
        assert stats["overrun_rows"] == 3
        assert M.SERVE_DECODE_ROUNDS.labels(dispatch="drained").value \
            == rounds["drained"] + 3
        assert M.SERVE_DECODE_ROUNDS.labels(dispatch="ahead").value \
            == rounds["ahead"] + steps - 1
        assert M.SERVE_OVERRUN_ROWS.value == overrun + 3
        line = next(ln for ln in log.getvalue().splitlines()
                    if "decode rounds dispatched" in ln)
        assert f"ahead: {steps - 1}" in line and "drained: 3" in line
        assert "overrun_rows: 3" in line and "ahead_share: 0." in line
        assert "decode_attention: 'jnp_gather'" in line
        assert "prefill_attention: 'jnp_gather'" in line

    def test_slot_reuse_leaks_nothing(self, model):
        """A slot's next occupant sees a zero cache: with max_batch=1
        every request reuses THE slot, and each must still match solo —
        including a short prompt right after a long one (the pad tail
        and the old occupant's K/V both must not bleed in)."""
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=1, max_seq=64,
                          queue_depth=8)
        try:
            seq = [([9] * 40, 8), ([9], 8), ([5, 5, 5], 5)]
            for prompt, n_new in seq:
                out = eng.submit(prompt, max_new=n_new).result(timeout=120)
                assert out == solo_tokens(params, cfg, prompt, n_new), prompt
        finally:
            eng.stop(timeout=30)

    def test_queue_backpressure(self, model):
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=1, max_seq=512,
                          queue_depth=1)
        try:
            resident = eng.submit([1], max_new=400)
            assert wait_for(lambda: eng.active_slots == 1)
            eng.submit([2], max_new=400)  # fills the 1-deep queue
            before = M.SERVE_REQUESTS_TOTAL.labels(outcome="rejected").value
            with pytest.raises(QueueFull):
                eng.submit([3], max_new=2)
            after = M.SERVE_REQUESTS_TOTAL.labels(outcome="rejected").value
            assert after == before + 1
            assert resident.finish_reason == ""  # resident unharmed
        finally:
            eng.stop(drain=False, timeout=30)

    def test_cancel_evicts_slot_and_queued(self, model):
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=1, max_seq=512,
                          queue_depth=4)
        try:
            resident = eng.submit([1], max_new=400)
            assert wait_for(lambda: eng.active_slots == 1)
            queued = eng.submit([2], max_new=400)
            resident.cancel()
            queued.cancel()
            assert wait_for(
                lambda: eng.active_slots == 0 and eng.queue_len == 0)
            # Streams close; both retire as cancelled.
            resident.result(timeout=30)
            queued.result(timeout=30)
            assert resident.finish_reason == "cancelled"
            assert queued.finish_reason == "cancelled"
            # The freed slot serves the next request correctly.
            out = eng.submit([4, 5], max_new=4).result(timeout=120)
            assert out == solo_tokens(params, cfg, [4, 5], 4, max_seq=512)
        finally:
            eng.stop(timeout=30)

    def test_eos_retires_early(self, model):
        """Declaring the solo run's second token as EOS must retire the
        request right when it appears, with reason "eos"."""
        params, cfg = model
        ref = solo_tokens(params, cfg, [1, 2, 3], 8)
        eos = ref[1]
        expect = ref[:ref.index(eos) + 1]  # retire at FIRST occurrence
        assert len(expect) < len(ref)
        eng = ServeEngine(params, cfg, max_batch=2, max_seq=64)
        try:
            h = eng.submit([1, 2, 3], max_new=8, eos=eos)
            out = h.result(timeout=120)
            assert out == expect
            assert h.finish_reason == "eos"
        finally:
            eng.stop(timeout=30)

    def test_graceful_drain(self, model):
        """stop(drain=True): residents finish their full budget, the
        queued request closes as "drained", new submits refuse."""
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=1, max_seq=64,
                          queue_depth=4)
        # A budget long enough that the resident is still decoding
        # when the queued submit and the drain land (a 6-step request
        # can finish inside one 10ms poll on a warm engine, and then
        # the "queued" request would simply be admitted).
        resident = eng.submit([6, 7], max_new=48)
        assert wait_for(lambda: resident._req.admitted_at > 0,
                        interval=0.001)
        queued = eng.submit([8], max_new=6)
        eng.stop(drain=True, timeout=60)
        assert resident.result(timeout=5) == solo_tokens(
            params, cfg, [6, 7], 48)
        assert resident.finish_reason == "length"
        assert queued.result(timeout=5) == []
        assert queued.finish_reason == "drained"
        with pytest.raises(Draining):
            eng.submit([1], max_new=2)

    def test_inadmissible_requests(self, model):
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=1, max_seq=16)
        try:
            with pytest.raises(ValueError):
                eng.submit([], max_new=2)
            with pytest.raises(ValueError):
                eng.submit([1] * 10, max_new=8)  # 10 + 8 > max_seq 16
            with pytest.raises(ValueError):
                eng.submit([1], max_new=-1)
        finally:
            eng.stop(timeout=30)

    def test_occupancy_and_queue_metrics(self, model):
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=2, max_seq=512,
                          queue_depth=4)
        try:
            a = eng.submit([1], max_new=400)
            b = eng.submit([2], max_new=400)
            assert wait_for(lambda: eng.active_slots == 2)
            assert M.SERVE_SLOT_OCCUPANCY.value == 1.0
            c = eng.submit([3], max_new=400)
            assert eng.queue_len == 1
            assert M.SERVE_QUEUE_DEPTH.value >= 1.0
            for h in (a, b, c):
                h.cancel()
        finally:
            eng.stop(drain=False, timeout=30)


# -- one decode round in flight (ISSUE 38) ----------------------------------

RUNAHEAD_PAGE = 8
RUNAHEAD_SEQ = 64
# A model family at test size -> (its configuration, what its engine needs
# said). Recurrent state refuses a prefix store.
FAMILIES = {
    "dense": lambda: (llama.tiny(vocab=64, dim=32, n_layers=2), {}),
    "padded-experts": lambda: (
        llama.tiny(vocab=64, dim=32, n_layers=2, n_experts=4), {}),
    "latent": lambda: (llama.tiny_latent(vocab=64, n_layers=2), {}),
    "mamba-hybrid": lambda: (llama.tiny_hybrid(vocab=64, pattern="ME*E"),
                             {"prefix_cache_bytes": 0}),
    "kda-hybrid": lambda: (llama.tiny_kda(vocab=64, n_layers=4),
                           {"prefix_cache_bytes": 0}),
}
# name -> (prompt length, max_new, temperature, seed). B ends by length at
# a page edge (5 + 12 - 1 = 16: the position after its last token opens a
# page it never reserved), F at max_seq (26 + 38 = 64).
RUNAHEAD_REQS = {
    "A": (19, 12, 0.0, 0), "B": (5, 12, 0.8, 1), "C": (11, 12, 0.0, 2),
    "D": (17, 6, 0.7, 3), "F": (26, 38, 0.0, 4), "G": (3, 7, 0.9, 5),
}
_family_cache: dict = {}


def runahead_family(name):
    """(params, cfg, engine kwargs, {request: (prompt, its solo tokens)})
    of one family, built once a process. Solo is ``generate()`` where it
    runs the family; a hybrid pattern has no dense cache to generate from,
    so its solo run is the same engine geometry serving the request ALONE
    (same compiled programs, no batch-mate, no slot or page reused)."""
    if name in _family_cache:
        return _family_cache[name]
    cfg, kwargs = FAMILIES[name]()
    params = llama.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(38)
    prompts = {key: rng.integers(1, 64, n).tolist()
               for key, (n, _, _, _) in RUNAHEAD_REQS.items()}
    alone = None
    if cfg.pattern:
        alone = runahead_engine(params, cfg, kwargs, 0)
    solo = {}
    try:
        for key, (_, n_new, temp, seed) in RUNAHEAD_REQS.items():
            if alone is None:
                solo[key] = solo_tokens(params, cfg, prompts[key], n_new,
                                        temp, seed, max_seq=RUNAHEAD_SEQ)
            else:
                solo[key] = alone.submit(
                    prompts[key], max_new=n_new, temperature=temp,
                    seed=seed).result(timeout=300)
    finally:
        if alone is not None:
            alone.stop(timeout=30)
    _family_cache[name] = (params, cfg, kwargs,
                           {k: (prompts[k], solo[k]) for k in prompts})
    return _family_cache[name]


def runahead_engine(params, cfg, kwargs, chunk, **more):
    return ServeEngine(params, cfg, max_batch=3, max_seq=RUNAHEAD_SEQ,
                       queue_depth=16, prefix_block=RUNAHEAD_PAGE,
                       prefill_chunk=chunk, **kwargs, **more)


def submit_named(eng, reqs, key, **kw):
    _, n_new, temp, seed = RUNAHEAD_REQS[key]
    return eng.submit(reqs[key][0], max_new=n_new, temperature=temp,
                      seed=seed, **kw)


def assert_nothing_held(eng):
    """After a stopped engine: no round in flight, and the page pool, the
    draft pool and the state pool hold nothing (the prefix store's own
    references dropped first: they are the store's, not a leak)."""
    assert eng._inflight is None
    eng.evict_prefix_store()
    eng.evict_host_tier()
    pool = eng.pool_stats()
    assert pool["used_pages"] == 0, pool
    assert pool["state_slots_live"] == 0
    assert eng.spec_stats()["draft_used_pages"] == 0


def gate_emit(eng, when):
    """Replace ``eng._emit`` by one that calls ``when(req)`` after every
    emitted token, on the engine's thread, inside the landing of a round:
    the next round is already dispatched behind it."""
    emit = eng._emit

    def gated(req, token):
        emit(req, token)
        when(req)

    eng._emit = gated


def hold_at_fifth_token(eng, max_new):
    """(reached, go): the engine's thread sets ``reached`` inside the
    landing of the fifth token of the request that asked for ``max_new``
    and waits there for ``go``: what the test queues meanwhile is admitted
    beside that request, a round in flight."""
    reached, go = threading.Event(), threading.Event()
    gate_emit(eng, lambda req: req.emitted == 5 and req.max_new == max_new
              and (reached.set(), go.wait(30)))
    return reached, go


RUNAHEAD_CASES = ("eos-midbatch", "length-at-edges", "cancel", "stop-drain",
                  "stop-nodrain")


@pytest.mark.parametrize("case", RUNAHEAD_CASES)
@pytest.mark.parametrize("chunk", [0, 8])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_streams_stay_solo_with_a_round_in_flight(family, chunk, case):
    """The engine dispatches round n + 1 before it lands round n, so a row
    that retires when round n lands was stepped once more, with its old
    table row and state. Every stream still equals its solo run, the
    overrun token is in none of them, and nothing is left in any pool:

    * ``eos-midbatch``: a row ends by EOS in a full batch, the page pool
      sized so that the queued prompt needs the pages it frees: slot and
      pages go to that prompt in the same pass, behind the overrun step;
    * ``length-at-edges``: rows end by length with the overrun write at a
      page edge (unreserved: the scratch page) and at ``max_seq``;
    * ``cancel``: a row is cancelled inside a landing, a round in flight;
    * ``stop-drain`` / ``stop-nodrain``: ``stop()`` arrives inside a
      landing; residents finish whole, or are cut at a prefix of solo."""
    params, cfg, kwargs, reqs = runahead_family(family)
    solo = {key: tokens for key, (_, tokens) in reqs.items()}
    more = {}
    if case == "eos-midbatch":
        # A, B, C hold 4 + 2 + 3 pages of 11; D needs 3: B's two and one.
        more["kv_pool_tokens"] = 11 * RUNAHEAD_PAGE
    eng = runahead_engine(params, cfg, kwargs, chunk, **more)
    outs, handles, stopped = {}, {}, False
    before = eng.stats()["overrun_rows"]
    try:
        if case == "eos-midbatch":
            eos = next(t for t in solo["B"][1:] if t != solo["B"][0])
            solo["B"] = solo["B"][:solo["B"].index(eos) + 1]
            for key in "ABCD":
                handles[key] = submit_named(
                    eng, reqs, key, **({"eos": eos} if key == "B" else {}))
        elif case == "length-at-edges":
            for key in "BFCG":
                handles[key] = submit_named(eng, reqs, key)
        elif case == "cancel":
            cut = {}

            def when(req):
                if req is cut.get("req") and req.emitted >= 4 \
                        and "at" not in cut:
                    cut["at"] = req.emitted
                    req.cancelled.set()

            gate_emit(eng, when)
            handles["B"] = submit_named(eng, reqs, "B")
            cut["req"] = handles["B"]._req
            for key in "ACD":
                handles[key] = submit_named(eng, reqs, key)
        else:
            inside, go = threading.Event(), threading.Event()
            first = {}

            def when(req):
                if req is first.get("req") and req.emitted == 3:
                    inside.set()
                    go.wait(30)

            gate_emit(eng, when)
            handles["A"] = submit_named(eng, reqs, "A")
            first["req"] = handles["A"]._req
            for key in "BCD":
                handles[key] = submit_named(eng, reqs, key)
            assert inside.wait(60)
            stopper = threading.Thread(target=eng.stop, kwargs={
                "drain": case == "stop-drain", "timeout": 60})
            stopper.start()
            assert wait_for(lambda: eng._draining, interval=0.001)
            assert eng._inflight is not None  # stop found a round in flight
            go.set()
            stopper.join(90)
            stopped = True
        outs = {key: h.result(timeout=120) for key, h in handles.items()}
    finally:
        if not stopped:
            eng.stop(timeout=60)
    reasons = {key: h.finish_reason for key, h in handles.items()}
    if case == "cancel":
        solo["B"] = solo["B"][:cut["at"]]
        assert reasons == {"A": "length", "B": "cancelled", "C": "length",
                           "D": "length"}
    elif case == "eos-midbatch":
        assert reasons == {"A": "length", "B": "eos", "C": "length",
                           "D": "length"}
        assert len(solo["B"]) >= 2  # ended by a decode round, not its prefill
        assert handles["D"]._req.admitted_at >= handles["B"]._req.finished_at
    elif case == "stop-drain":
        assert reasons == {"A": "length", "B": "length", "C": "length",
                           "D": "drained"}
        solo["D"] = []
    elif case == "stop-nodrain":
        assert set(reasons.values()) == {"drained"}
        assert len(outs["A"]) >= 3
        solo = {key: solo[key][:len(outs[key])] for key in outs}
        assert outs["D"] == []
    else:
        assert set(reasons.values()) == {"length"}
    for key, out in outs.items():
        assert out == solo[key], (key, out, solo[key])
    if case != "stop-nodrain":  # every retirement by a round overran once
        assert eng.stats()["overrun_rows"] - before >= 1
    assert_nothing_held(eng)


class TestWeights:
    def test_pack_unpack_roundtrip(self, model):
        params, _ = model
        blob = pack_params(params)
        assert pack_params(params) == blob  # content-addressable
        tree = unpack_params(blob)
        ref = jax.tree_util.tree_flatten_with_path(params)[0]
        got = jax.tree_util.tree_flatten_with_path(tree)[0]
        assert [jax.tree_util.keystr(p) for p, _ in ref] == \
            [jax.tree_util.keystr(p) for p, _ in got]
        for (_, a), (_, b) in zip(ref, got):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)

    def test_bfloat16_leaves_pack_and_save(self, tmp_path):
        """The real-width dtype (llama.Config.dtype): the buffer protocol
        has no bfloat16 format, so packing must go through a byte view —
        the tiny f32 fixture never showed it (found on the chip, PR 22).
        save_packed streams leaf by leaf and must equal pack_params."""
        import jax.numpy as jnp

        params = {"layers": {"wq": jnp.full((2, 3, 4), 1.5, jnp.bfloat16)},
                  "norm": jnp.arange(4, dtype=jnp.float32)}
        blob = pack_params(params)
        path = tmp_path / "bf16.oimw"
        assert save_packed(params, str(path)) == len(blob)
        assert path.read_bytes() == blob
        tree = unpack_params(blob)
        assert tree["layers"]["wq"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(tree["layers"]["wq"], np.float32),
            np.full((2, 3, 4), 1.5, np.float32))
        np.testing.assert_array_equal(tree["norm"], np.arange(4.0))

    def test_unpack_is_zero_copy_over_arrays(self, model):
        params, _ = model
        buf = np.frombuffer(pack_params(params), np.uint8)
        tree = unpack_params(buf)
        leaf = tree["embed"]
        # A view into the staged buffer, not a copy.
        assert leaf.base is not None

    def test_bad_magic_refused(self):
        with pytest.raises(ValueError, match="magic"):
            unpack_params(b"\x00" * 64)

    def test_publish_restore_local(self, model, tmp_path):
        params, cfg = model
        path = tmp_path / "w.oimw"
        save_packed(params, str(path))
        feeder = Feeder(controller=ControllerService(MallocBackend()))
        publish_weights(feeder, "weights", str(path))
        tree = restore_weights(feeder, "weights")
        for (_, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(params)[0],
                jax.tree_util.tree_flatten_with_path(tree)[0]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestServeService:
    """The gRPC surface: streaming deltas, wire statuses, slot eviction
    on stream death."""

    @pytest.fixture
    def cluster(self, model):
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=1, max_seq=512,
                          queue_depth=1)
        server = serve_server("tcp://127.0.0.1:0", ServeService(eng))
        channel = tlsutil.dial(server.addr, None)
        yield eng, ServeStub(channel), params, cfg
        channel.close()
        server.force_stop()
        eng.stop(drain=False, timeout=30)

    def test_stream_matches_solo(self, cluster):
        eng, stub, params, cfg = cluster
        deltas = list(stub.Generate(
            pb.GenerateRequest(prompt=[1, 2, 3], max_new_tokens=6),
            timeout=120))
        toks = [t for d in deltas for t in d.tokens]
        assert toks == solo_tokens(params, cfg, [1, 2, 3], 6, max_seq=512)
        assert deltas[-1].done and deltas[-1].finish_reason == "length"
        assert all(not d.done for d in deltas[:-1])

    def test_queue_full_resource_exhausted(self, cluster):
        eng, stub, params, cfg = cluster
        resident = eng.submit([1], max_new=400)
        assert wait_for(lambda: eng.active_slots == 1)
        queued = eng.submit([2], max_new=400)
        with pytest.raises(grpc.RpcError) as err:
            list(stub.Generate(
                pb.GenerateRequest(prompt=[3], max_new_tokens=2),
                timeout=30))
        assert err.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
        resident.cancel()
        queued.cancel()

    def test_client_cancel_evicts_slot(self, cluster):
        eng, stub, params, cfg = cluster
        call = stub.Generate(
            pb.GenerateRequest(prompt=[5], max_new_tokens=400), timeout=120)
        next(call)  # stream is live, the slot is held
        call.cancel()
        assert wait_for(lambda: eng.active_slots == 0)

    def test_invalid_argument(self, cluster):
        _, stub, _, _ = cluster
        with pytest.raises(grpc.RpcError) as err:
            list(stub.Generate(
                pb.GenerateRequest(prompt=[], max_new_tokens=2), timeout=30))
        assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT

    def test_draining_unavailable(self, model):
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=1, max_seq=64)
        server = serve_server("tcp://127.0.0.1:0", ServeService(eng))
        channel = tlsutil.dial(server.addr, None)
        try:
            eng.stop(drain=True, timeout=30)
            with pytest.raises(grpc.RpcError) as err:
                list(ServeStub(channel).Generate(
                    pb.GenerateRequest(prompt=[1], max_new_tokens=2),
                    timeout=30))
            assert err.value.code() == grpc.StatusCode.UNAVAILABLE
        finally:
            channel.close()
            server.force_stop()


@pytest.fixture
def counted_reads(monkeypatch):
    """Counts source reads on both backend paths, so "zero source
    re-reads" is provable (same seam as test_stagecache.py)."""
    counts = {"reads": 0}
    orig_reader = plane.READERS["file"]

    def counting_reader(*args, **kwargs):
        counts["reads"] += 1
        return orig_reader(*args, **kwargs)

    orig_load = malloc_backend.load_source

    def counting_load(*args, **kwargs):
        counts["reads"] += 1
        return orig_load(*args, **kwargs)

    monkeypatch.setitem(plane.READERS, "file", counting_reader)
    monkeypatch.setattr(malloc_backend, "load_source", counting_load)
    return counts


class TestServeAcceptance:
    """The PR's end-to-end acceptance: one checkpoint publish, prestage
    fan-out to a second serving replica (its restore re-reads NOTHING
    from source — stage-cache hit counters prove it), then 16+
    concurrent streaming requests through the continuous-batching
    engine, admitted mid-flight, each byte-identical to its solo
    generate() run."""

    N_REQUESTS = 16

    def test_publish_prestage_serve(self, model, tmp_path, counted_reads):
        params, cfg = model
        path = tmp_path / "ckpt.oimw"
        save_packed(params, str(path))

        db = MemRegistryDB()
        registry = registry_server("tcp://localhost:0",
                                   RegistryService(db=db))
        backends = [MallocBackend(), MallocBackend()]
        controllers = [
            Controller(
                controller_id=f"host-{i}", backend=backends[i],
                controller_address="pending",
                registry_address=registry.addr, registry_delay=0.1,
                mesh_coord=MeshCoord.parse("0,0,0"),
            )
            for i in range(2)
        ]
        servers = [controller_server("tcp://localhost:0", c.service)
                   for c in controllers]
        for c, s in zip(controllers, servers):
            c.controller_address = s.addr
        engine = None
        try:
            for c in controllers:
                c.start()
            with grpc.insecure_channel(registry.addr) as ch:
                stub = RegistryStub(ch)
                assert wait_for(lambda: len([
                    v for v in stub.GetValues(
                        pb.GetValuesRequest(path="")).values
                    if v.path.endswith("/address")]) == 2)

            # Replica 0: publish ONCE (the only source read), then fan
            # the content out to replica 1's stage cache.
            request = weights_request("weights", str(path),
                                      path.stat().st_size)
            feeder0 = Feeder(registry_address=registry.addr,
                             controller_id="host-0")
            publish_weights(feeder0, "weights", str(path))
            assert counted_reads["reads"] > 0
            ControllerStub(feeder0._registry_channel()).PrestageVolume(
                request, metadata=[(CONTROLLER_ID_META, "host-1")],
                timeout=60.0)
            assert wait_for(lambda: len(backends[1].cache) == 1)
            # The fan-out stage above is the LAST time the source is
            # touched; replica 1's boot must add nothing.
            reads_after_fanout = counted_reads["reads"]

            # Replica 1 boots: its own publish of the identical content
            # is an O(1) cache hit — ZERO new source reads.
            hits_before = M.STAGE_CACHE_HITS.value
            feeder1 = Feeder(registry_address=registry.addr,
                             controller_id="host-1")
            publish_weights(feeder1, "weights", str(path))
            tree = restore_weights(feeder1, "weights")
            assert counted_reads["reads"] == reads_after_fanout, \
                "replica 1's restore must not touch the source"
            assert M.STAGE_CACHE_HITS.value == hits_before + 1

            # Serve through the restored tree: 16 concurrent streaming
            # requests into a 4-slot batch — admission is mid-flight by
            # construction (4x oversubscribed).
            engine = ServeEngine(tree, cfg, max_batch=4, max_seq=64,
                                 queue_depth=self.N_REQUESTS)
            server = serve_server("tcp://127.0.0.1:0", ServeService(engine))
            servers.append(server)
            reqs = [
                ([1 + i, 2 + i, 3 + i % 5], 6 + i % 5,
                 0.0 if i % 2 == 0 else 0.8, i)
                for i in range(self.N_REQUESTS)
            ]
            results: list[list[int] | None] = [None] * self.N_REQUESTS
            errors: list[Exception] = []

            def run(i):
                prompt, n_new, temp, seed = reqs[i]
                try:
                    with tlsutil.dial(server.addr, None) as ch:
                        deltas = list(ServeStub(ch).Generate(
                            pb.GenerateRequest(
                                prompt=prompt, max_new_tokens=n_new,
                                temperature=temp, seed=seed),
                            timeout=300))
                    results[i] = [t for d in deltas for t in d.tokens]
                except Exception as err:  # noqa: BLE001 - collected
                    errors.append(err)

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(self.N_REQUESTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert not errors, errors
            for (prompt, n_new, temp, seed), out in zip(reqs, results):
                assert out == solo_tokens(
                    params, cfg, prompt, n_new, temp, seed), (prompt, seed)
        finally:
            if engine is not None:
                engine.stop(drain=False, timeout=30)
            for c in controllers:
                c.stop()
            for s in servers:
                s.force_stop()
            registry.force_stop()
