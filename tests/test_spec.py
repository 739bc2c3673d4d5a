"""Ring-1 tests for speculative decoding (serve/spec.py +
models/generate.py verify_step + the engine's draft plumbing).

The invariants this PR must hold: the target's multi-token
``verify_step`` produces the SAME per-position results as a sequence of
single-token ``decode_step``s (the premise byte-identity stands on);
greedy output with speculation on is byte-identical to solo
``generate()`` whatever the draft proposes — self-draft, a genuinely
different draft, mixed spec/non-spec slots in one batch, reused slots
after retirement, and across an adaptive-valve fallback mid-request;
sampled acceptance follows the EXACT ratio test (accept d with
probability min(1, p(d)/q(d)), resample rejections from the normalized
residual max(p - q, 0)), pinned both mechanically (crafted
distributions with forced accept/reject) and statistically (the output
marginal equals the target distribution for a disagreeing draft); the
draft page pool leaks nothing on retirement, cancel, fallback, or
drain (the PR 11 refcount-census discipline applied to the second
pool); and a negative temperature is refused at submit time.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oim_tpu.common import events, metrics as M
from oim_tpu.models import generate as gen, llama
from oim_tpu.serve import AcceptanceValve, ServeEngine, accept_tokens


def wait_for(predicate, timeout=30.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


@pytest.fixture(scope="module")
def model():
    cfg = llama.tiny(vocab=64, dim=32, n_layers=2)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    return params, cfg


@pytest.fixture(scope="module")
def draft_model():
    """A genuinely DIFFERENT draft: same architecture and vocab,
    independent init — its proposals disagree with the target often,
    which is exactly what the correctness invariants must survive."""
    cfg = llama.tiny(vocab=64, dim=32, n_layers=2)
    params = llama.init(jax.random.PRNGKey(7), cfg)
    return params, cfg


def solo_tokens(params, cfg, prompt, n_new, temperature=0.0, seed=0,
                max_seq=64):
    out = gen.generate(
        params, np.asarray([prompt], np.int32), n_new, cfg,
        temperature=temperature, rng=jax.random.PRNGKey(seed),
        max_seq=max_seq)
    return out[0, len(prompt):].tolist()


# ---------------------------------------------------------------------------
# verify_step: the multi-token target forward.


class TestVerifyStep:
    def test_matches_sequential_decode_steps(self, model):
        """One verify_step over [prev, c1, c2, c3] must reproduce the
        logits (and therefore the argmax tokens) of four sequential
        decode_steps feeding the same tokens — the numerical premise
        byte-identical speculation stands on."""
        params, cfg = model
        page = 8
        prompt = [3, 1, 4, 1, 5]
        n = len(prompt)
        nb = 4  # 32 logical positions

        def fresh_state():
            pool = gen.init_page_pool(cfg, 9, page)  # 8 usable + scratch
            table = np.arange(1, nb + 1, dtype=np.int32)[None, :]
            toks = np.zeros((1, 8), np.int32)
            toks[0, :n] = prompt
            _, pool = gen.prefill_into_pages(
                params, jnp.asarray(toks), jnp.int32(n), pool,
                jnp.asarray(table[0]), jnp.int32(0), cfg, page)
            return pool, jnp.asarray(table)

        cand = [9, 2, 6, 5]  # prev token + 3 speculated candidates
        # Sequential reference: decode_step per candidate.
        pool, table = fresh_state()
        seq_logits = []
        for j, t in enumerate(cand):
            logits, pool = gen.decode_step(
                params, jnp.asarray([t], jnp.int32), pool, table,
                jnp.asarray([n + j], jnp.int32), cfg, page)
            seq_logits.append(np.asarray(logits[0]))
        # One verify_step over the whole candidate window.
        pool, table = fresh_state()
        v_logits, pool = gen.verify_step(
            params, jnp.asarray([cand], jnp.int32), pool, table,
            jnp.asarray([n], jnp.int32), cfg, page)
        v_logits = np.asarray(v_logits[0])
        for j in range(len(cand)):
            assert np.argmax(v_logits[j]) == np.argmax(seq_logits[j])
            np.testing.assert_allclose(
                v_logits[j], seq_logits[j], rtol=1e-5, atol=1e-5)

    def test_overflow_writes_never_touch_live_pages(self, model):
        """Candidates past the page table must DROP (and past a row's
        mapped pages land in scratch) — verifying near a request's end
        cannot corrupt another position's K/V. Pinned by comparing the
        pool bytes outside the written range before and after."""
        params, cfg = model
        page = 8
        pool = gen.init_page_pool(cfg, 9, page)
        table = np.zeros((1, 2), np.int32)  # 16 logical positions
        table[0, :] = [1, 2]
        before_k = np.asarray(pool["k"])[:, 3:]  # pages never mapped
        cand = [[5, 6, 7, 8, 9]]
        # Start at position 13: candidates 13..17 — 14,15 in page 2,
        # 16,17 past the table (dropped).
        _, pool = gen.verify_step(
            params, jnp.asarray(cand, jnp.int32), pool,
            jnp.asarray(table), jnp.asarray([13], jnp.int32), cfg, page)
        after_k = np.asarray(pool["k"])[:, 3:]
        np.testing.assert_array_equal(before_k, after_k)


# ---------------------------------------------------------------------------
# accept_tokens: the acceptance-sampling math (serve/spec.py).


def _logits_for(vocab, peaked):
    """[len(peaked), vocab] rows, each a near-point-mass at peaked[i]."""
    out = np.full((len(peaked), vocab), -30.0, np.float32)
    for i, t in enumerate(peaked):
        out[i, t] = 30.0
    return out


class TestAcceptTokens:
    V = 8

    def run(self, tgt, d, dlog, temps, spec=None, seed=0):
        B = len(temps)
        K = np.asarray(d).shape[1]
        keys = jax.vmap(jax.random.PRNGKey)(
            jnp.arange(seed, seed + B, dtype=jnp.uint32))
        mask = jnp.ones(B, bool) if spec is None else jnp.asarray(spec)
        out, n_emit, carry = accept_tokens(
            jnp.asarray(tgt, jnp.float32), jnp.asarray(d, jnp.int32),
            jnp.asarray(dlog, jnp.float32),
            jnp.asarray(temps, jnp.float32), keys, mask)
        assert np.asarray(carry).shape == (B, 2)
        assert 1 <= int(np.asarray(n_emit)[0]) <= K + 1
        return np.asarray(out), np.asarray(n_emit)

    def test_greedy_all_accept_plus_bonus(self):
        # Target argmaxes to exactly the proposals; bonus at the end.
        tgt = _logits_for(self.V, [2, 5, 1, 7])[None]  # [1, K+1, V]
        d = [[2, 5, 1]]
        dlog = _logits_for(self.V, [2, 5, 1])[None]
        out, n_emit = self.run(tgt, d, dlog, [0.0])
        assert n_emit[0] == 4
        assert out[0, :4].tolist() == [2, 5, 1, 7]

    def test_greedy_first_mismatch_corrects(self):
        tgt = _logits_for(self.V, [3, 5, 1, 7])[None]  # argmax_0 = 3
        d = [[2, 5, 1]]  # proposal 2 != 3 -> reject at 0
        dlog = _logits_for(self.V, [2, 5, 1])[None]
        out, n_emit = self.run(tgt, d, dlog, [0.0])
        assert n_emit[0] == 1
        assert out[0, 0] == 3  # the target's own token

    def test_greedy_mid_mismatch_keeps_prefix(self):
        tgt = _logits_for(self.V, [2, 6, 1, 7])[None]  # argmax_1 = 6
        d = [[2, 5, 1]]  # accept d1, reject d2
        dlog = _logits_for(self.V, [2, 5, 1])[None]
        out, n_emit = self.run(tgt, d, dlog, [0.0])
        assert n_emit[0] == 2
        assert out[0, :2].tolist() == [2, 6]

    def test_non_spec_row_is_a_plain_step(self):
        """spec_mask False ignores proposals entirely: one token, the
        target's own (greedy: argmax of position 0; sampled: drawn
        from p_0 — NOT the residual, which would skew the marginal)."""
        tgt = _logits_for(self.V, [3, 5, 1, 7])[None]
        d = [[3, 5, 1]]  # proposals AGREE — must still be ignored
        dlog = _logits_for(self.V, [3, 5, 1])[None]
        out, n_emit = self.run(tgt, d, dlog, [0.0], spec=[False])
        assert n_emit[0] == 1 and out[0, 0] == 3
        # Sampled non-spec: point-mass p_0 pins the draw.
        out, n_emit = self.run(tgt, d, dlog, [1.0], spec=[False])
        assert n_emit[0] == 1 and out[0, 0] == 3

    def test_ratio_certain_reject_samples_residual(self):
        """p(d) == 0 forces rejection for ANY uniform; the correction
        must come from the normalized residual max(p - q, 0) — crafted
        here as a point mass, so the outcome is deterministic."""
        V = self.V
        # q: point mass at 0 (that's the proposal); p: all mass at 4.
        tgt = np.stack([_logits_for(V, [4])[0], _logits_for(V, [5])[0]])
        d = [[0]]
        dlog = _logits_for(V, [0])[None]
        for seed in range(8):  # any key chain: rejection is certain
            out, n_emit = self.run(tgt[None], d, dlog, [1.0], seed=seed)
            assert n_emit[0] == 1
            assert out[0, 0] == 4  # the residual's point mass
        # Greedy with the same shapes corrects to argmax p_0 = 4 too.
        out, n_emit = self.run(tgt[None], d, dlog, [0.0])
        assert n_emit[0] == 1 and out[0, 0] == 4

    def test_ratio_certain_accept_when_p_equals_q(self):
        """p == q makes the ratio 1: every proposal accepted, and the
        bonus comes from the target's last position."""
        V = self.V
        peaked = [2, 5, 6]
        dlog = _logits_for(V, peaked[:2])[None]
        tgt = np.stack([_logits_for(V, peaked[:1])[0][0] * 0 + r
                        for r in _logits_for(V, peaked)])[None]
        d = [peaked[:2]]
        for seed in range(8):
            out, n_emit = self.run(tgt, d, dlog, [1.0], seed=seed)
            assert n_emit[0] == 3
            assert out[0, :3].tolist() == peaked

    def test_sampled_marginal_is_exactly_target(self):
        """The Leviathan identity, empirically: with a draft that
        DISAGREES with the target, the marginal of the first emitted
        token must still be the target distribution. B independent
        rows play B trials of K=1 speculation; the draft proposal is
        itself sampled from q per row (the theorem's premise)."""
        V = 4
        B = 4096
        p_probs = np.array([0.5, 0.25, 0.15, 0.1], np.float32)
        q_probs = np.array([0.1, 0.2, 0.3, 0.4], np.float32)
        tgt = np.broadcast_to(
            np.log(p_probs), (B, 2, V)).astype(np.float32)
        dlog = np.broadcast_to(
            np.log(q_probs), (B, 1, V)).astype(np.float32)
        dkeys = jax.random.split(jax.random.PRNGKey(123), B)
        d = jax.vmap(
            lambda k: jax.random.categorical(k, jnp.log(q_probs)))(
                dkeys)[:, None]
        keys = jax.random.split(jax.random.PRNGKey(321), B)
        out, n_emit, _ = accept_tokens(
            jnp.asarray(tgt), d.astype(jnp.int32), jnp.asarray(dlog),
            jnp.ones(B, jnp.float32), keys, jnp.ones(B, bool))
        first = np.asarray(out)[:, 0]
        freq = np.bincount(first, minlength=V) / B
        np.testing.assert_allclose(freq, p_probs, atol=0.03)


# ---------------------------------------------------------------------------
# AcceptanceValve: the adaptive fallback policy.


class TestAcceptanceValve:
    def test_closes_on_low_rate_and_reprobes(self):
        valve = AcceptanceValve(floor=0.5, window_rounds=4,
                                reprobe_rounds=3)
        assert valve.open
        closed = [valve.observe(4, 0) for _ in range(4)]
        assert closed == [False, False, False, True]  # closes ONCE
        assert not valve.open
        assert valve.observe(4, 4) is False  # ignored while closed
        ticks = [valve.tick_plain() for _ in range(3)]
        assert ticks == [False, False, True]  # reopens ONCE
        assert valve.open
        # A healthy window keeps it open.
        for _ in range(8):
            assert valve.observe(4, 4) is False
        assert valve.open

    def test_rate_and_validation(self):
        valve = AcceptanceValve(floor=0.5, window_rounds=2,
                                reprobe_rounds=1)
        assert valve.rate() is None
        valve.observe(4, 3)
        assert valve.rate() == 0.75
        with pytest.raises(ValueError):
            AcceptanceValve(floor=1.5)
        with pytest.raises(ValueError):
            AcceptanceValve(window_rounds=0)


# ---------------------------------------------------------------------------
# Engine integration: byte-identity, mixed slots, lifecycle, leaks.


@pytest.fixture(scope="module")
def spec_engine(model):
    """ONE self-draft engine shared by the read-mostly engine tests
    (each engine instance recompiles prefill/decode/propose/verify —
    the expensive part of every test here)."""
    params, cfg = model
    eng = ServeEngine(params, cfg, max_batch=2, max_seq=64,
                      queue_depth=16, draft_params=params,
                      draft_cfg=cfg, spec_tokens=3)
    yield eng
    eng.stop(drain=False, timeout=30)


class TestSpecEngine:
    def test_greedy_byte_identity_oversubscribed_self_draft(
            self, model, spec_engine):
        """5 greedy requests over 2 slots with a self-draft: slot reuse
        after retirement AND speculation together, every output
        byte-identical to solo generate()."""
        params, cfg = model
        eng = spec_engine
        reqs = [([1 + i, 5, 9, 2], 10, i) for i in range(5)]
        handles = [eng.submit(p, max_new=n, seed=s)
                   for p, n, s in reqs]
        for (p, n, s), h in zip(reqs, handles):
            assert h.result(timeout=300) == solo_tokens(
                params, cfg, p, n, seed=s)
        st = eng.stats()
        assert st["spec_accepted"] > 0
        assert st["decode_tokens"] > st["target_steps"]
        assert wait_for(
            lambda: eng.spec_stats()["draft_used_pages"] == 0)

    def test_greedy_byte_identity_disagreeing_draft(self, model,
                                                    draft_model):
        """A draft with different weights proposes mostly-wrong tokens;
        rejections must correct to EXACTLY the solo stream (greedy),
        and sampled requests in the same batch complete."""
        params, cfg = model
        dparams, dcfg = draft_model
        eng = ServeEngine(params, cfg, max_batch=4, max_seq=64,
                          queue_depth=16, draft_params=dparams,
                          draft_cfg=dcfg, spec_tokens=4)
        try:
            greedy = [([2 + i, 7, 3], 9, i) for i in range(3)]
            gh = [eng.submit(p, max_new=n, seed=s) for p, n, s in greedy]
            sh = eng.submit([9, 8, 7], max_new=9, temperature=0.9,
                            seed=42)
            for (p, n, s), h in zip(greedy, gh):
                assert h.result(timeout=300) == solo_tokens(
                    params, cfg, p, n, seed=s)
            assert len(sh.result(timeout=300)) == 9
            assert eng.stats()["spec_proposed"] > 0
        finally:
            eng.stop(drain=False, timeout=30)

    def test_chunked_prefill_behind_a_speculative_round(self, model):
        """With ``prefill_chunk`` a long prompt's next slice is queued on
        the device behind the residents' round — here a speculative one
        (propose + verify) — before that round's tokens are fetched:
        resident and newcomer both stay byte-identical to solo."""
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=2, max_seq=64,
                          queue_depth=16, draft_params=params,
                          draft_cfg=cfg, spec_tokens=3, prefill_chunk=8)
        try:
            resident = eng.submit([3, 1, 4], max_new=40, seed=1)
            assert wait_for(lambda: eng.stats()["spec_proposed"] > 0)
            prompt = [1 + i % 50 for i in range(29)]  # 4 slices: 8, 8, 8, 5
            late = eng.submit(prompt, max_new=6, seed=2)
            assert late.result(timeout=300) == solo_tokens(
                params, cfg, prompt, 6, seed=2)
            assert resident.result(timeout=300) == solo_tokens(
                params, cfg, [3, 1, 4], 40, seed=1)
        finally:
            eng.stop(drain=False, timeout=30)

    def test_mid_batch_mixed_spec_and_plain_slots(self, model):
        """A draft pool sized for ONE request: the second concurrent
        admission gets no draft slot and decodes plainly in the same
        lockstep batch — both byte-identical to solo."""
        params, cfg = model
        # 16-token prefix block = page; one request of prompt 3 +
        # max_new 16 needs ceil(18/16) = 2 pages; pool holds exactly 2.
        eng = ServeEngine(params, cfg, max_batch=2, max_seq=64,
                          queue_depth=8, draft_params=params,
                          draft_cfg=cfg, spec_tokens=3,
                          spec_pool_tokens=32)
        try:
            h1 = eng.submit([1, 2, 3], max_new=16, seed=0)
            h2 = eng.submit([4, 5, 6], max_new=16, seed=1)
            assert wait_for(lambda: eng.active_slots == 2)
            # Exactly one of the two holds draft pages.
            assert eng.spec_stats()["draft_used_pages"] == 2
            assert sum(eng._spec_row) == 1
            assert h1.result(timeout=300) == solo_tokens(
                params, cfg, [1, 2, 3], 16, seed=0)
            assert h2.result(timeout=300) == solo_tokens(
                params, cfg, [4, 5, 6], 16, seed=1)
            # Retirement returned the draft pages: the NEXT admission
            # speculates again (reused draft slot).
            h3 = eng.submit([7, 8, 9], max_new=16, seed=2)
            assert h3.result(timeout=300) == solo_tokens(
                params, cfg, [7, 8, 9], 16, seed=2)
        finally:
            eng.stop(drain=False, timeout=30)
        assert eng.spec_stats()["draft_used_pages"] == 0

    def test_a_speculative_round_runs_with_nothing_in_flight(self, model):
        """Plain rounds keep one round in flight; a speculative round is
        dispatched onto an empty queue and waited for. A draft pool with
        room for ONE short request: the long resident gets no draft pages
        and decodes plainly, a round ahead, until the short request beside
        it brings a draft row — from then on every round speculates, and
        each finds nothing in flight (``_spec_once`` lands a plain round
        before it runs; here the admission already had). Plain again, and
        ahead again, once the short request is gone. Tokens stay solo's and
        both pools end empty."""
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=2, max_seq=64,
                          queue_depth=8, draft_params=params,
                          draft_cfg=cfg, spec_tokens=3,
                          spec_pool_tokens=32)  # two 16-token pages
        in_flight = []
        propose = eng._propose
        eng._propose = lambda *a: (in_flight.append(eng._inflight),
                                   propose(*a))[1]
        # The engine's thread waits at the resident's fifth token until the
        # short request is queued: it is admitted BESIDE the resident.
        at_five, queued = threading.Event(), threading.Event()
        emit = eng._emit
        eng._emit = lambda req, t: (emit(req, t), req.emitted == 5
                                    and req.max_new == 40
                                    and (at_five.set(), queued.wait(30)))
        try:
            plain = eng.submit([1, 2, 3], max_new=40, seed=0)  # 3 pages
            assert at_five.wait(60)
            assert eng.stats()["decode_rounds_ahead"] >= 2
            assert not in_flight and not any(eng._spec_row)
            spec = eng.submit([4, 5, 6], max_new=12, seed=1)  # 1 page
            queued.set()
            assert spec.result(timeout=300) == solo_tokens(
                params, cfg, [4, 5, 6], 12, seed=1)
            assert plain.result(timeout=300) == solo_tokens(
                params, cfg, [1, 2, 3], 40, seed=0)
            stats = eng.stats()
        finally:
            eng.stop(timeout=30)
        assert in_flight and all(r is None for r in in_flight)
        assert stats["spec_rounds"] == len(in_flight)
        # Onto an empty queue: the first round, and the plain round behind
        # the last speculative one; every other plain round ran ahead.
        assert stats["decode_rounds_drained"] == 2
        assert stats["decode_rounds_ahead"] > stats["spec_rounds"]
        assert eng._inflight is None
        assert eng.spec_stats()["draft_used_pages"] == 0
        eng.evict_prefix_store()
        assert eng.pool_stats()["used_pages"] == 0

    def test_valve_fallback_and_reprobe_stay_byte_identical(
            self, model, draft_model):
        """A tiny valve window + a hostile floor force the adaptive
        fallback DURING a request: the spec_fallback event and counter
        fire, draft pages release immediately, the request's tail
        (decoded plainly) continues the exact solo stream — and after
        the cooldown's plain rounds, a NEW admission speculates
        again."""
        params, cfg = model
        dparams, dcfg = draft_model
        fallbacks_before = M.SERVE_SPEC_FALLBACK.value
        events_before = len(events.recorder().events(
            type_=events.SPEC_FALLBACK))
        eng = ServeEngine(params, cfg, max_batch=1, max_seq=64,
                          queue_depth=4, draft_params=dparams,
                          draft_cfg=dcfg, spec_tokens=4,
                          spec_accept_floor=0.999,
                          spec_window_rounds=3,
                          spec_reprobe_rounds=4)
        try:
            h = eng.submit([3, 1, 4], max_new=28, seed=0)
            got = h.result(timeout=300)
            assert got == solo_tokens(params, cfg, [3, 1, 4], 28,
                                      seed=0)
            st = eng.stats()
            assert st["spec_fallbacks"] >= 1
            assert M.SERVE_SPEC_FALLBACK.value > fallbacks_before
            assert len(events.recorder().events(
                type_=events.SPEC_FALLBACK)) > events_before
            assert eng.spec_stats()["draft_used_pages"] == 0
            # The first request's plain tail (window 3 of ~7 rounds,
            # then plain decode) outlasted the 4-round cooldown: the
            # valve reopened, so this admission speculates from the
            # start — and stays byte-identical.
            h2 = eng.submit([5, 9, 2], max_new=12, seed=3)
            assert h2.result(timeout=300) == solo_tokens(
                params, cfg, [5, 9, 2], 12, seed=3)
            assert eng.stats()["spec_rounds"] > st["spec_rounds"]
        finally:
            eng.stop(drain=False, timeout=30)

    def test_draft_alloc_failure_races_valve_close_same_request(
            self, model, draft_model):
        """The compound case PR 12 never covered: request X's draft
        allocation fails (the new spec.propose fault point — X demotes
        to plain decode at admission) while its batch-mate A's low
        acceptance CLOSES the valve mid-flight. The fallback's draft
        release must skip X (it holds no draft pages), both streams
        stay byte-identical, and neither pool leaks."""
        from oim_tpu.common import faultinject

        params, cfg = model
        dparams, dcfg = draft_model
        eng = ServeEngine(params, cfg, max_batch=2, max_seq=64,
                          queue_depth=8, draft_params=dparams,
                          draft_cfg=dcfg, spec_tokens=4,
                          spec_accept_floor=0.999,
                          spec_window_rounds=6,
                          spec_reprobe_rounds=10_000, name="race")
        try:
            # X goes FIRST, with the fault pre-armed: the very first
            # spec.propose call is X's admission, which consumes the
            # times=1 fault deterministically — no window in which A's
            # rounds can close the valve and short-circuit
            # _map_draft_slot before the fault point is reached.
            faultinject.arm("spec.propose", times=1, engine="race")
            h_x = eng.submit([5, 9, 2], max_new=12, seed=9)
            assert wait_for(
                lambda: faultinject.fired("spec.propose") == 1)
            # A admits after the fault is exhausted, takes the draft
            # slot, and its collapsing acceptance closes the valve
            # while demoted-X is still a plain row in the batch.
            h_a = eng.submit([3, 1, 4], max_new=28, seed=0)
            assert wait_for(
                lambda: eng.spec_stats()["draft_used_pages"] > 0)
            got_a = h_a.result(timeout=300)
            got_x = h_x.result(timeout=300)
            assert faultinject.fired("spec.propose") == 1, \
                "the draft-alloc fault never hit the admission"
            assert got_a == solo_tokens(params, cfg, [3, 1, 4], 28,
                                        seed=0)
            assert got_x == solo_tokens(params, cfg, [5, 9, 2], 12,
                                        seed=9)
            # The race actually happened: the valve closed while X (a
            # plain row by injected alloc failure) was in the batch.
            assert eng.stats()["spec_fallbacks"] >= 1
            assert eng.spec_stats()["spec_on"] is False
            assert eng.spec_stats()["draft_used_pages"] == 0
            assert eng.pool_stats()["used_pages"] == \
                eng.prefix_stats()["entries"]
        finally:
            faultinject.disarm("spec.propose")
            eng.stop(drain=False, timeout=30)

    def test_eos_mid_round_truncates_like_solo(self, model,
                                               spec_engine):
        """A verify round can emit several tokens at once; the engine
        must stop at the FIRST EOS exactly where solo retirement
        would."""
        params, cfg = model
        prompt, n = [2, 4, 6], 16
        solo = solo_tokens(params, cfg, prompt, n, seed=5)
        eos = solo[len(solo) // 2]  # a token mid-stream
        want = solo[:solo.index(eos) + 1]
        h = spec_engine.submit(prompt, max_new=n, seed=5, eos=eos)
        assert h.result(timeout=300) == want
        assert h.finish_reason == "eos"
        assert wait_for(
            lambda: spec_engine.spec_stats()["draft_used_pages"] == 0)

    def test_cancel_releases_draft_pages(self, model, spec_engine):
        eng = spec_engine
        h1 = eng.submit([1, 2, 3], max_new=40, seed=0)
        assert wait_for(
            lambda: eng.spec_stats()["draft_used_pages"] > 0)
        h1.cancel()
        assert wait_for(lambda: h1.finish_reason == "cancelled")
        assert wait_for(
            lambda: eng.spec_stats()["draft_used_pages"] == 0)

    def test_negative_temperature_refused_at_submit(self, model):
        params, cfg = model
        eng = ServeEngine(params, cfg, max_batch=1, max_seq=64,
                          queue_depth=4, prefix_cache_bytes=0)
        try:
            with pytest.raises(ValueError, match="temperature"):
                eng.submit([1, 2, 3], max_new=4, temperature=-0.5)
        finally:
            eng.stop(drain=False, timeout=30)

    def test_config_validation(self, model):
        params, cfg = model
        other = llama.tiny(vocab=32, dim=32, n_layers=2)
        with pytest.raises(ValueError, match="spec_tokens"):
            ServeEngine(params, cfg, max_batch=1, max_seq=64,
                        draft_params=params, draft_cfg=cfg)
        with pytest.raises(ValueError, match="spec_tokens"):
            ServeEngine(params, cfg, max_batch=1, max_seq=64,
                        spec_tokens=4)
        with pytest.raises(ValueError, match="draft_cfg"):
            ServeEngine(params, cfg, max_batch=1, max_seq=64,
                        draft_params=params, spec_tokens=4)
        with pytest.raises(ValueError, match="vocab"):
            ServeEngine(params, cfg, max_batch=1, max_seq=64,
                        draft_params=llama.init(jax.random.PRNGKey(1),
                                                other),
                        draft_cfg=other, spec_tokens=4)


# ---------------------------------------------------------------------------
# Surfaces: stats advertisement + oimctl --top ACCEPT column.


class TestSpecSurfaces:
    def test_stats_advertise_speculation_health(self, model,
                                                spec_engine):
        params, cfg = model
        spec_engine.submit([1, 2, 3], max_new=6,
                           seed=0).result(timeout=300)
        st = spec_engine.stats()
        assert st["spec_tokens"] == 3
        assert st["spec_rounds"] > 0
        assert st["spec_proposed"] > 0
        assert st["spec_accept_rate"] is not None
        assert st["spec_on"] is True
        # A plain engine advertises no spec keys (mixed-version
        # heartbeat rows stay parseable either way; nothing is ever
        # submitted, so no program compiles).
        plain = ServeEngine(params, cfg, max_batch=1, max_seq=64,
                            queue_depth=4)
        try:
            assert "spec_rounds" not in plain.stats()
        finally:
            plain.stop(drain=False, timeout=30)

    def test_top_accept_column_and_pre_spec_dash(self):
        """oimctl --top renders the rolling acceptance %% and degrades
        to "-" for scrapes that predate speculation (the PAGES /
        PREFIX-HIT mixed-version stance)."""
        import json as json_mod

        from oim_tpu.cli.oimctl import render_top, top_row
        from oim_tpu.common.metrics import Registry

        def scrape(with_spec):
            reg = Registry()
            reg.gauge("oim_serve_qps").set(1.0)
            if with_spec:
                reg.counter(
                    "oim_serve_spec_proposed_tokens_total").inc(80)
                reg.counter(
                    "oim_serve_spec_accepted_tokens_total").inc(60)
            text = reg.render()
            ev = json_mod.dumps({"events": [], "dropped": 0})
            return lambda url, timeout=10.0: (
                ev if "/debug/events" in url else text)

        row = top_row("r0", "ALIVE", "serve", "127.0.0.1:1",
                      http_get=scrape(True))
        assert row["accept"] == 0.75
        rendered = render_top([row])
        assert "ACCEPT" in rendered and "75%" in rendered
        old = top_row("r0", "ALIVE", "serve", "127.0.0.1:1",
                      http_get=scrape(False))
        assert old["accept"] is None
        assert "ACCEPT" in render_top([old])
