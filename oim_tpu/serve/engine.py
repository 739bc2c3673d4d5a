"""Continuous-batching decode engine: the serving tier's scheduler.

KV storage is a PAGED POOL (serve/pagepool.py): one
[L, n_pages, page_tokens] device pool shared by every live request,
addressed through per-slot page tables. Admission reserves only the
pages the request can actually use — ceil((prompt + max_new - 1) /
page_tokens) — never a dense ``max_seq`` slot, so short and long
prompts share one budget and a pool sized below ``max_batch x max_seq``
still fills every decode slot with short requests. When the pool cannot
cover the next admission, the request WAITS at the head of the bounded
queue (pool exhaustion backpressures through the existing QueueFull
path, never an OOM) until retirements return pages.

A request is admitted into a free batch row MID-FLIGHT — its prefill
(models/generate.py ``prefill_into_pages``, batch-1 numerics writing
straight through the slot's page table) runs between decode steps of
the residents, then the whole batch advances in lockstep through ONE
compiled decode program (``decode_step``, per-row positions + page
tables). Retirement is per-slot: an EOS token or the request's
max-tokens budget returns the slot's pages, so throughput is bounded by
pool and slot occupancy, not by the slowest request in a static batch.

Scheduling stays off the decode hot path: the engine thread's loop is
admit-if-free-slot, dispatch one device step, emit the step BEFORE it —
one decode round stays in flight while the host lands the one before
(``_plain_once``, ``_land``), so the device does not wait for the emit
loop. No locks are held across the device dispatch, and token streams
drain through per-request queues so a slow consumer never stalls the
batch.

Prompt-prefix KV reuse (serve/prefixcache.py): a retiring slot donates
its prompt's full-block pages to a content-addressed prefix store by
REFERENCE (chain hashes at ``prefix_block`` granularity — one block is
one page — LRU under ``prefix_cache_bytes``); an admission that matches
m blocks writes the store's page ids into its own page table and
prefills only the uncached tail. A hit therefore moves ZERO K/V bytes —
it is page-table writes plus a refcount — and divergence after the
shared prefix lands in fresh private pages (copy-on-write by write
discipline: a slot never writes a page it shares), without changing a
single output token (prefix K/V is a pure function of the prefix token
chain).

Speculative decoding (serve/spec.py): with a DRAFT model configured
(``draft_params``/``draft_cfg``/``spec_tokens=K``), a decode round
becomes draft-propose (K fused ``decode_step``s over the draft's own
small page pool) + target-verify (ONE multi-token ``verify_step``
forward scoring all K candidates) + acceptance — each slot advances
1..K+1 tokens per target dispatch. Greedy output stays byte-identical
to solo ``generate()`` by construction (every emitted token is a target
argmax); sampled output is distribution-exact under the standard ratio
test. The draft cache lifecycle rides the same admit/retire/cancel/
drain paths as the target's (a failed draft-page allocation demotes the
request to plain decode, never delays it), and an adaptive valve drops
to plain decode when the rolling acceptance rate stops paying for the
draft forwards.

Invariants the tests pin (tests/test_serve.py, tests/test_paged_pool.py,
tests/test_spec.py):
* outputs are byte-identical to a solo ``generate()`` run per request —
  admission order, batch-mates, slot reuse, and page sharing must not
  change a single token (greedy AND sampled: the per-request RNG chain
  splits exactly the way generate() does). With a DRAFT model
  configured the pin narrows to GREEDY requests: a speculating
  engine's sampled rows draw through the acceptance test's K+2-way
  round splits, so their streams are distribution-exact (the ratio
  test's guarantee, pinned by tests/test_spec.py) but not bytewise
  reproductions of the solo chain;
* a retired slot leaks nothing into its next occupant (stale bytes in a
  reused page sit strictly above the causal mask's horizon, where the
  softmax weighs them exactly zero);
* a full admission queue refuses new work (``QueueFull`` →
  RESOURCE_EXHAUSTED at the service layer) instead of queueing silently,
  and an exhausted page pool queues instead of allocating;
* cancel evicts the slot at the next step boundary and returns every
  page; ``stop(drain=True)`` finishes residents, fails the queue as
  "drained", and leaks no page either way.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import queue
import threading
import time
from typing import Any

import numpy as np

from oim_tpu.common import (
    events,
    faultinject,
    metrics as M,
    prefixhash,
    tracing,
)
from oim_tpu.common.logging import from_context
from oim_tpu.models.llama import Config
from oim_tpu.serve.kvtier import (
    HostTier,
    page_kv,
    stage_page,
    stage_pages,
)
from oim_tpu.serve.pagepool import PagePool
from oim_tpu.serve.prefixcache import PrefixStore
from oim_tpu.serve.spec import DRAFT_KEY_FOLD, AcceptanceValve, accept_tokens


class QueueFull(Exception):
    """The bounded admission queue is full — backpressure, never silent
    queueing (the service maps this to RESOURCE_EXHAUSTED)."""


class Draining(Exception):
    """The engine is draining/stopped and admits nothing new."""


_DONE = object()  # sentinel closing a request's token stream


@dataclasses.dataclass
class _Request:
    prompt: list[int]
    max_new: int
    temperature: float
    seed: int
    eos: int
    out: "queue.Queue[Any]" = dataclasses.field(
        default_factory=lambda: queue.Queue())
    cancelled: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    finish_reason: str = ""
    submitted_at: float = 0.0
    admitted_at: float = 0.0
    finished_at: float = 0.0
    emitted: int = 0
    last_emit_at: float = 0.0
    first_emit_at: float = 0.0
    trace_ctx: Any = None
    # Prompt tokens whose K/V came from the prefix cache (0 = the whole
    # prompt was prefilled): the per-request hit record.
    prefix_tokens: int = 0


@dataclasses.dataclass
class _Round:
    """One plain decode round on the device that the host has not landed
    yet (``ServeEngine._land``)."""
    tok: Any        # [B] device array: the tokens the step returns
    load: list      # a dropless expert model's load numbers, else []
    rows: list      # (slot, request) of the rows live at its dispatch
    operands: Any   # the step's three [B] operands, released at landing


class GenHandle:
    """Caller-side view of one submitted request: a token stream, a
    cancel switch, and the post-mortem stats the service puts on spans."""

    def __init__(self, req: _Request):
        self._req = req

    def tokens(self, timeout: float | None = None):
        """Yield token ids as the batch produces them; returns when the
        request finishes (see ``finish_reason``). ``timeout`` bounds the
        wait for EACH token, raising ``queue.Empty`` when it lapses."""
        while True:
            item = self._req.out.get(timeout=timeout)
            if item is _DONE:
                return
            yield item

    def result(self, timeout: float | None = None) -> list[int]:
        return list(self.tokens(timeout=timeout))

    def cancel(self) -> None:
        """Ask the engine to evict this request's slot at the next step
        boundary (idempotent; also unblocks a queued request)."""
        self._req.cancelled.set()

    @property
    def finish_reason(self) -> str:
        return self._req.finish_reason

    @property
    def stats(self) -> dict:
        r = self._req
        return {
            "queue_wait_s": max(r.admitted_at - r.submitted_at, 0.0)
            if r.admitted_at else 0.0,
            "tokens": r.emitted,
            "finish_reason": r.finish_reason,
            "prefix_tokens": r.prefix_tokens,
        }


def _counts_expert_load(cfg: Config) -> bool:
    """Whether the decode program reports the expert layers' load: the
    dropless dispatch is the one that counts it (models/moe.py)."""
    return bool(cfg.n_experts) and cfg.moe_dispatch == "ragged"


@functools.lru_cache(maxsize=64)
def _target_programs(cfg: Config, page: int, max_seq: int,
                     shard: int = 1):
    """The engine's two jitted target programs — one lockstep decode
    step, one bucketed prefill — built ONCE per geometry and shared by
    every ServeEngine in the process. jit caches on the function
    object, so per-engine closures would recompile byte-identical HLO
    for each instance (in-process bench replicas, restarted engines,
    the test suite's dozens of tiny engines all paid full XLA compiles
    for programs an identical engine had already built).

    Prefill compile discipline: ONE program per prompt-length BUCKET
    (tokens shape is static; buckets are powers of two, so
    log2(max_seq) programs cover every admissible prompt) — and that
    same program IS the prefix-cache hit path: on a hit ``tokens``
    carries only the uncached tail and ``start`` (a traced scalar) the
    cached depth, while the page table already references the store's
    pages. The page-table operand has ONE fixed shape, so there is no
    (tail x prefix) bucket product. The RNG chain matches solo
    generate(): one split after prefill, one per decode step.

    ``shard > 1`` runs the SAME programs tensor-parallel: the forward
    bodies move under a shard_map over the ``tp`` mesh (serve/shard.py)
    with the member-local cfg, while sampling stays outside on the
    replicated logits — so the RNG chain, the bucketing and the
    donation discipline are untouched and greedy output stays
    byte-identical to shard=1."""
    import jax
    import jax.numpy as jnp

    from oim_tpu.models import generate as gen

    if shard > 1:
        from oim_tpu.serve import shard as shardlib

        lcfg = gen.shard_config(cfg, shard)
        _decode = shardlib.wrap_forward(
            shard, lambda p, t, c, tb, ps: gen.decode_step(
                p, t, c, tb, ps, lcfg, page, axis="tp"), cache_arg=1)
        _prefill_fwd = shardlib.wrap_forward(
            shard, lambda p, t, n, c, tb, st: gen.prefill_into_pages(
                p, t, n, c, tb, st, lcfg, page, axis="tp"), cache_arg=2)
    else:
        def _decode(p, t, c, tb, ps):
            return gen.decode_step(p, t, c, tb, ps, cfg, page,
                                   with_load=_counts_expert_load(cfg))

        def _prefill_fwd(p, t, n, c, tb, st, *slot):
            return gen.prefill_into_pages(
                p, t, n, c, tb, st, cfg, page, None, *slot,
                with_rungs=True)

    def step(params, cache, tokens, pos, keys, temps, tables):
        logits, cache, *load = _decode(params, tokens, cache, tables, pos)
        with jax.named_scope("tok_head"):  # sampling: the model's head scope
            split = jax.vmap(jax.random.split)(keys)  # [B, 2, key]
            carry, subs = split[:, 0], split[:, 1]
            # Sampling matches generate() bit-for-bit per row: each slot
            # samples its OWN key against a [1, vocab] row — the shapes a
            # solo batch-1 run feeds categorical — so a sampled request's
            # tokens don't depend on its batch-mates. Greedy rows compute
            # the (discarded) sampled branch against temperature 1.
            safe = jnp.where(temps > 0, temps, 1.0)

            def samp(key, row, t):
                return jax.random.categorical(key, (row / t)[None, :])[0]

            sampled = jax.vmap(samp)(subs, logits, safe)
            greedy = jnp.argmax(logits, axis=-1)
            tok = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
        # The step returns its OWN next operands (tok / pos+1 / key
        # chain), so steady-state decode re-dispatches device arrays
        # instead of re-uploading host mirrors (see _decode_once).
        # pos advances for every row; idle rows' garbage positions are
        # clamped to max_seq so they can't drift without bound (a live
        # row retires before its position could reach the clamp, so
        # the clamp never alters a real request's numerics).
        # A dropless expert model's step also returns its expert load
        # [experts that got a row, fullest over mean], a fifth output the
        # engine fetches with the tokens; other models' steps are as ever.
        return (tok, cache, carry, jnp.minimum(pos + 1, max_seq), *load)

    def prefill(params, cache, tokens, n_tokens, table, start, key,
                temp, *slot):
        # ``slot``: the row of a hybrid's recurrent state this prompt
        # fills (models/generate.py); no other configuration is handed it.
        # A program whose routed products have a ladder (a held share's,
        # a whole set's bucket from moe.WHOLE_FROM_ROWS rows an expert) also
        # returns how many of its expert layers ran on each rung
        # (moe.capacity_ladder), a fourth output the engine fetches with the
        # prompt's first token; other programs are as ever.
        last, cache, *rungs = _prefill_fwd(
            params, tokens, n_tokens, cache, table, start, *slot)
        with jax.named_scope("tok_head"):
            carry, sub = jax.random.split(key)
            safe = jnp.where(temp > 0, temp, 1.0)
            sampled = jax.random.categorical(sub, (last / safe)[None, :])[0]
            tok = jnp.where(
                temp > 0, sampled, jnp.argmax(last)).astype(jnp.int32)
        return (tok, cache, carry, *rungs)

    return (jax.jit(step, donate_argnums=(1,)),
            jax.jit(prefill, donate_argnums=(1,)))


@functools.lru_cache(maxsize=64)
def _spec_programs(cfg: Config, dcfg: Config, page: int, max_seq: int,
                   K: int, shard: int = 1):
    """The three speculative-decoding programs — draft prefill, the
    scanned K+1-step draft propose, and the fused verify+accept —
    built once per (target cfg, draft cfg, geometry, K) and shared
    across engines exactly like :func:`_target_programs`.

    Under ``shard > 1`` only the TARGET verify forward moves under the
    shard_map (the draft is small by construction — replicating it
    trades a little HBM for zero draft-side ICI traffic); acceptance
    math runs on the replicated verify logits, so the accept/reject
    stream is byte-identical to shard=1."""
    import jax
    import jax.numpy as jnp

    from oim_tpu.models import generate as gen

    if shard > 1:
        from oim_tpu.serve import shard as shardlib

        lcfg = gen.shard_config(cfg, shard)
        _verify_fwd = shardlib.wrap_forward(
            shard, lambda p, s, c, tb, ps: gen.verify_step(
                p, s, c, tb, ps, lcfg, page, axis="tp"), cache_arg=1)
    else:
        def _verify_fwd(p, s, c, tb, ps):
            return gen.verify_step(p, s, c, tb, ps, cfg, page)

    def draft_prefill(dparams, dcache, tokens, n_tokens, table, start,
                      key):
        # The draft's cache fill at admission: same program shape as
        # the target prefill (bucketed tokens, traced start), its
        # logits discarded — the round's first input is always the
        # TARGET's last emission, so no temperature operand either.
        # The key splits once, mirroring the target chain's shape.
        _, dcache = gen.prefill_into_pages(
            dparams, tokens, n_tokens, dcache, table, start, dcfg,
            page)
        carry, _ = jax.random.split(key)
        return dcache, carry

    def propose(dparams, dcache, tokens, pos, keys, temps, tables):
        # K+1 draft decode steps in ONE program: each step feeds the
        # previous token, writes its K/V through the draft page tables
        # (overflow past a row's reservation lands in scratch page 0 —
        # decode_step's discipline), and samples the next proposal on
        # the DRAFT key chain (fold_in-decorrelated from the accept
        # chain). The EXTRA step ingests the last proposal d_K so its
        # K/V lands at pos+K: after an ALL-ACCEPT round the next round
        # starts at pos+K+1 and its scatter never revisits pos+K —
        # without this write the draft's context would hole exactly
        # when it performs best, silently eroding acceptance for the
        # request's rest (the step's own sampled token is discarded).
        safe = jnp.where(temps > 0, temps, 1.0)

        def one(carry, _):
            dcache_, tok, pos_, keys_ = carry
            logits, dcache_ = gen.decode_step(
                dparams, tok, dcache_, tables, pos_, dcfg, page)
            split = jax.vmap(jax.random.split)(keys_)
            carry_keys, subs = split[:, 0], split[:, 1]

            def samp(k, row, t):
                return jax.random.categorical(
                    k, (row / t)[None, :])[0]

            sampled = jax.vmap(samp)(subs, logits, safe)
            greedy = jnp.argmax(logits, axis=-1)
            nxt = jnp.where(
                temps > 0, sampled, greedy).astype(jnp.int32)
            return ((dcache_, nxt,
                     jnp.minimum(pos_ + 1, max_seq), carry_keys),
                    (nxt, logits))

        (dcache, _, _, keys), (toks, logits) = jax.lax.scan(
            one, (dcache, tokens, pos, keys), None, length=K + 1)
        # scan stacks along axis 0 = the step axis; the verify side
        # wants the K proposals as [B, K(, V)].
        return (jnp.swapaxes(toks[:K], 0, 1),
                jnp.swapaxes(logits[:K], 0, 1), dcache, keys)

    def verify(params_, cache, tokens, pos, keys, temps, tables,
               draft_toks, draft_logits, spec_mask):
        seq = jnp.concatenate([tokens[:, None], draft_toks],
                              axis=1)  # [B, K+1]
        logits, cache = _verify_fwd(params_, seq, cache, tables, pos)
        out, n_emit, carry = accept_tokens(
            logits, draft_toks, draft_logits, temps, keys, spec_mask)
        rows = jnp.arange(out.shape[0])
        final = out[rows, n_emit - 1]
        # Device state advances past every emitted token; a row the
        # host truncates (eos / max_new mid-round) retires, so its
        # stale device row is rewritten at the next admission like any
        # other freed slot.
        new_pos = jnp.minimum(pos + n_emit, max_seq)
        return out, n_emit, final, carry, cache, new_pos

    return (jax.jit(draft_prefill, donate_argnums=(1,)),
            jax.jit(propose, donate_argnums=(1,)),
            jax.jit(verify, donate_argnums=(1,)))


class ServeEngine:
    # Sliding window (seconds) behind the oim_serve_qps gauge.
    QPS_WINDOW_S = 10.0
    # Smallest prefill bucket: prompts are padded up to the next power of
    # two >= this, so a handful of compiled prefill programs serve every
    # prompt length (pad K/V never lands: prefill_into_pages drops the
    # pad scatters at the page-table boundary).
    MIN_PREFILL_BUCKET = 8

    # How many hot chain hashes a replica advertises in its heartbeat
    # row for the router's prefix-affinity pick (serve/registration.py).
    ADVERTISE_PREFIXES = 16

    def __init__(
        self,
        params,
        cfg: Config,
        max_batch: int = 8,
        max_seq: int = 256,
        queue_depth: int = 64,
        default_max_new: int = 64,
        prefix_cache_bytes: int = 64 << 20,
        prefix_block: int = 16,
        kv_page_tokens: int = 0,
        kv_pool_tokens: int = 0,
        kv_host_bytes: int = 0,
        kv_fetch=None,
        draft_params=None,
        draft_cfg: Config | None = None,
        spec_tokens: int = 0,
        spec_pool_tokens: int = 0,
        spec_accept_floor: float = 0.3,
        spec_window_rounds: int = 64,
        spec_reprobe_rounds: int = 256,
        shard: int = 1,
        member_hbm_budget: int = 0,
        role: str = "mixed",
        prefill_chunk: int = 0,
        name: str = "",
    ):
        import jax
        import jax.numpy as jnp

        from oim_tpu.models import generate as gen

        if max_batch < 1 or max_seq < 2:
            raise ValueError(f"need max_batch >= 1 and max_seq >= 2, got "
                             f"{max_batch}x{max_seq}")
        # Speculative decoding needs BOTH halves: a draft model and a
        # proposal depth (one without the other is a config typo, not a
        # preference — refuse it like every other bad knob).
        if (draft_params is None) != (spec_tokens < 1):
            raise ValueError(
                "speculative decoding needs draft_params AND "
                f"spec_tokens >= 1 together (got draft_params="
                f"{'set' if draft_params is not None else 'None'}, "
                f"spec_tokens={spec_tokens})")
        if draft_params is not None:
            if draft_cfg is None:
                raise ValueError("draft_params needs draft_cfg")
            if draft_cfg.vocab != cfg.vocab:
                raise ValueError(
                    f"draft vocab ({draft_cfg.vocab}) must equal the "
                    f"target vocab ({cfg.vocab}): the acceptance ratio "
                    f"test compares distributions over one vocabulary")
        # Tensor-parallel serving (serve/shard.py): shard > 1 runs this
        # engine's target programs over a tp mesh of that many member
        # devices. Validate the geometry NOW — indivisible head counts
        # and missing devices are config typos, not runtime surprises.
        self.shard = max(int(shard), 1)
        if cfg.kv_lora_rank and draft_params is not None:
            raise ValueError(
                "speculative decoding does not support latent attention "
                "yet (the verify program and a draft pool over a latent "
                "cache are untested)")
        if cfg.kv_lora_rank and str(role) != "mixed":
            raise ValueError(
                f"role {role!r} does not support latent attention yet "
                "(the prefill-to-decode handoff is tested over K/V pages "
                "only); serve it as role='mixed'")
        if cfg.state_leaves:
            # Something a SLOT keeps beside the pages (a hybrid's Mamba-2, KDA
            # or GatedDeltaNet state; the tail of a compressed-convolutional-
            # attention layer, whose next position reads the one before): what
            # follows cannot be right yet, one line of ROADMAP R3 each.
            for on, what, why in (
                    (prefix_cache_bytes > 0 and int(prefix_block) >= 1,
                     "a prefix store (prefix_cache_bytes > 0)",
                     "a page hit brings the attention layers' keys and "
                     "values and no state: pass prefix_cache_bytes=0"),
                    (int(kv_host_bytes) > 0, "a host tier (kv_host_bytes)",
                     "it demotes the prefix store's pages"),
                    (draft_params is not None, "speculative decoding",
                     "a rejected draft would have to roll the state back"),
                    (self.shard > 1, "shard > 1",
                     "the state and the mixers have no sharding rules"),
                    (str(role) != "mixed", f"role {role!r}",
                     "the prefill-to-decode handoff carries pages only")):
                if on:
                    raise ValueError(
                        f"{what} does not support recurrent state yet "
                        f"({why})")
        self.member_hbm_budget = max(int(member_hbm_budget), 0)
        if self.shard > 1:
            from oim_tpu.serve import shard as shardlib

            gen.shard_config(cfg, self.shard)  # head-divisibility check
            shardlib.tp_mesh(self.shard)       # device-count check
        # Prefill/decode disaggregation: the role is advertised in the
        # heartbeat snapshot (stats() below) so the router can split a
        # request across tiers — prefill replicas run big-batch chunked
        # prefill and export finished chains, decode replicas stream.
        # The engine itself stays role-agnostic on the data path: role
        # only changes what rides the heartbeat and whether the retire
        # hook exports (set_handoff_export).
        self.role = str(role)
        if self.role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"role must be prefill, decode or mixed, got {role!r}")
        # Chunked prefill: long prompts prefill in slices of this many
        # tokens, interleaving one decode step between slices so
        # resident streams never stall behind one long prompt. 0 = one
        # full-length prefill (today's behavior). Byte-identity holds:
        # chunking changes dispatch order, never attention math.
        self.prefill_chunk = max(0, int(prefill_chunk))
        self._jax, self._jnp = jax, jnp
        # The engine's name in fault-point context (ctx: engine=...): a
        # multi-replica process (bench clusters, the chaos sim) arms a
        # fault against ONE replica's engine by matching on it. "" for
        # engines that never meet targeted faults.
        self.name = str(name)
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.queue_depth = queue_depth
        self.default_max_new = default_max_new
        # Prompt-prefix KV reuse (serve/prefixcache.py): retired slots
        # donate their prompt's full-block pages by reference,
        # admissions map the longest cached prefix into their page table
        # and prefill only the tail. 0 bytes (or block < 1) disables it.
        self.prefix_block = max(1, int(prefix_block))
        prefix_on = prefix_cache_bytes > 0 and int(prefix_block) >= 1
        # Paged KV cache: pages default to the prefix-block size so a
        # prefix block IS a page (the unit zero-copy sharing needs);
        # the pool defaults to the dense-equivalent max_batch x max_seq
        # tokens — size it SMALLER to overcommit slots against real
        # prompt lengths instead of worst-case reservations.
        self.page_tokens = int(kv_page_tokens) or self.prefix_block
        if self.page_tokens < 1:
            raise ValueError(
                f"kv_page_tokens must be >= 1, got {self.page_tokens}")
        if prefix_on and self.page_tokens != self.prefix_block:
            raise ValueError(
                f"zero-copy prefix sharing needs kv_page_tokens "
                f"({self.page_tokens}) == prefix_block "
                f"({self.prefix_block}); set them equal or disable the "
                f"prefix cache (prefix_cache_bytes=0)")
        self.n_blocks = -(-max_seq // self.page_tokens)
        pool_tokens = int(kv_pool_tokens) or max_batch * max_seq
        if pool_tokens < self.page_tokens:
            # A flag typo must not boot a replica that then refuses
            # essentially all traffic from a silently-clamped 1-page
            # pool — reject it like every other bad knob.
            raise ValueError(
                f"kv_pool_tokens ({pool_tokens}) is smaller than one "
                f"{self.page_tokens}-token page")
        n_pages = pool_tokens // self.page_tokens
        page_bytes = gen.page_bytes(cfg, self.page_tokens)
        self._pagepool = PagePool(n_pages, self.page_tokens, page_bytes)
        # A hybrid's recurrent state: a fixed size a slot, max_batch rows
        # beside the page pool whatever the positions held (0 otherwise).
        self.state_bytes_by_kind = gen.state_bytes_by_kind(cfg, max_batch)
        self.state_bytes = sum(self.state_bytes_by_kind.values())
        self._state_resets = 0
        M.SERVE_STATE_BYTES.set(self.state_bytes)
        # Per-member HBM budget: a member holds 1/shard of the split
        # weight leaves, the replicated leaves whole, and 1/shard of
        # every page (the pool shards with the KV heads). A model that
        # does not fit is refused HERE, at boot — widening the mesh is
        # what makes it fit, the "refused at 1, serves at 2" gate.
        if self.member_hbm_budget:
            from oim_tpu.serve import shard as shardlib

            shardlib.check_member_budget(
                params, self.shard, n_pages * page_bytes + self.state_bytes,
                self.member_hbm_budget)
        # KV tiering (serve/kvtier.py): with a --kv-host-bytes budget,
        # evicting a store-only prefix page D2H-copies its block into
        # the host-RAM LRU instead of dropping the chain; a later chain
        # hit H2D-restages it (move semantics — one tier per block).
        self.kv_host_bytes = max(0, int(kv_host_bytes))
        self._host_tier = (
            HostTier(self.kv_host_bytes)
            if prefix_on and self.kv_host_bytes else None)
        self._prefix = (
            PrefixStore(prefix_cache_bytes, self.prefix_block,
                        self._pagepool,
                        demote=(self._demote_page
                                if self._host_tier is not None else None))
            if prefix_on else None)
        if self._host_tier is not None:
            self._pagepool.register_tier("host", self._host_tier.stats)
        # Fleet prefix sharing (serve/kvvolume.py): kv_fetch is the
        # peer-fetch callback — called with (chain, m) when the local
        # store + host tier matched only m blocks; whatever consecutive
        # blocks it returns are H2D-adopted into fresh pages. None /
        # empty / any failure => plain local recompute (the
        # byte-identity fallback).
        self._kv_fetch = kv_fetch if prefix_on else None
        # Chains this engine exported as content-addressed volumes
        # (deepest hash -> volume id), advertised in the heartbeat row
        # so peers and freshly booted replicas can resolve them.
        self._exported: dict[str, str] = {}
        # Prefill-tier handoff: when set (set_handoff_export), a
        # retiring slot's finished chain is exported synchronously from
        # the retire path — the decode pick is already waiting on the
        # volume, so the background --kv-export sweep is too slow.
        self._handoff_export = None
        M.SERVE_ROLE.labels(role=self.role).set(1)
        # Full cumulative-hash chains of recent admissions (deepest hash
        # -> ordered chain, MRU last). hot_prefixes() advertises bare
        # hashes; the volume exporter needs the ORDER that rebuilds a
        # chain, which only the admitting request ever knew.
        self._hot_chains: collections.OrderedDict[str, tuple] = \
            collections.OrderedDict()
        # A sharded replica commits host leaves straight to their mesh
        # slices below; landing them whole on the first device first
        # would need the full model in ONE member's HBM.
        self.params = params if self.shard > 1 else jax.tree.map(
            jnp.asarray, params)
        # +1 physical page: id 0 is the reserved scratch/null page every
        # unmapped table entry points at (see init_page_pool).
        self._cache = gen.init_page_pool(
            cfg, n_pages + 1, self.page_tokens)
        # The recurrent state rides in the same dict: donated to and
        # updated in place by the same programs as the pages.
        self._cache.update(gen.init_state_pool(cfg, max_batch))
        if self.shard > 1:
            # Commit params and pool to their mesh shardings up front:
            # each member device holds only its weight slice and its
            # KV-head slice of every page (the HBM accounting above),
            # and the step programs' donated cache buffers alias from
            # the very first dispatch instead of resharding once.
            from jax.sharding import NamedSharding

            from oim_tpu.serve import shard as shardlib

            mesh = shardlib.tp_mesh(self.shard)
            self.params = jax.device_put(
                self.params,
                jax.tree_util.tree_map_with_path(
                    lambda p, _: NamedSharding(
                        mesh, shardlib.leaf_spec(p[-1].key)),
                    self.params))
            self._cache = jax.device_put(
                self._cache,
                {k: NamedSharding(mesh, s)
                 for k, s in shardlib.pool_specs(self._cache).items()})
        page = self.page_tokens
        # Jitted programs are SHARED across engine instances of one
        # geometry (_target_programs / _spec_programs below): jit
        # caching keys on the function object, so per-engine closures
        # used to recompile byte-identical HLO for every engine built
        # in a process — in-process bench replicas and the test suite
        # paid seconds apiece for programs an identical engine had
        # already compiled.
        self._step, self._prefill = _target_programs(
            cfg, page, max_seq, self.shard)
        # Which attention the decode program and the largest prefill
        # program (the chunk, or the --max-seq bucket) take — the dispatch
        # rule of ops/paged_attention.py (ops/latent_attention.py for a
        # latent cache) on this engine's (member-local) shapes, the word
        # the program logs when it is traced. stats() carries both, so a
        # replica that silently missed a kernel can be told from its
        # serve/<id> row.
        from oim_tpu.ops import latent_attention, paged_attention

        self.cache_kind = "latent" if cfg.kv_lora_rank else "gqa"
        if cfg.kv_lora_rank:
            d = cfg.latent
            name = functools.partial(
                latent_attention.kernel_name, pool=self._cache["kv"], d=d)
            heads = (d.heads, d.nope + d.rope)
        else:
            lcfg = gen.shard_config(cfg, self.shard)
            pool_k = self._cache["k"]
            name = functools.partial(
                paged_attention.kernel_name, pk=jax.ShapeDtypeStruct(
                    pool_k.shape[:3] + (lcfg.n_kv_heads, cfg.head_dim),
                    pool_k.dtype))
            heads = (lcfg.n_heads, cfg.head_dim)
        self.decode_attention, self.prefill_attention = (
            name(jax.ShapeDtypeStruct((rows, t) + heads, cfg.dtype),
                 tables=jax.ShapeDtypeStruct((rows, self.n_blocks), np.int32))
            for rows, t in ((max_batch, 1), (1, self._bucket(
                self.prefill_chunk or max_seq))))
        # Expert load of a dropless expert model's decode steps, summed:
        # [steps counted, experts that got a row (mean over the expert
        # layers), rows of the fullest expert over the mean]. stats()
        # shows the sums; a reader takes the difference of two snapshots.
        self._expert_load = np.zeros(3, np.float64)
        # Rows of expert FFN work the target's programs were dispatched
        # with, by the dispatch generate._no_drop picked for each call's
        # token count (oim_serve_expert_rows_total; stats() shows the sums).
        self._expert_rows = {"dropless": 0, "padded": 0}
        self._rows_of = gen.expert_rows
        # Expert-layer calls of the prefill programs with a ladder by the rung
        # their routed products ran on (moe.capacity_ladder): tallied on
        # the device, fetched with each prompt's first token.
        from oim_tpu.models.moe import RUNG_NAMES

        self._rung_names = RUNG_NAMES
        self._expert_rungs = np.zeros(len(RUNG_NAMES), np.int64)

        # -- speculative decoding (serve/spec.py): draft propose K
        # tokens through its OWN small page pool (K lockstep decode
        # steps fused into one scanned program), target verifies all K
        # in ONE verify_step forward, acceptance math fused behind it.
        # Both programs compile once per K.
        self.spec_tokens = int(spec_tokens) if draft_params is not None \
            else 0
        if self.spec_tokens:
            K = self.spec_tokens
            dcfg = draft_cfg
            self._draft_cfg = dcfg
            self._draft_params = jax.tree.map(jnp.asarray, draft_params)
            draft_pool_tokens = int(spec_pool_tokens) or pool_tokens
            if draft_pool_tokens < self.page_tokens:
                raise ValueError(
                    f"spec_pool_tokens ({draft_pool_tokens}) is smaller "
                    f"than one {self.page_tokens}-token page")
            draft_page_bytes = gen.page_bytes(dcfg, self.page_tokens)
            n_draft_pages = draft_pool_tokens // self.page_tokens
            self._draft_pagepool = PagePool(
                n_draft_pages, self.page_tokens, draft_page_bytes,
                track_metrics=False)
            self._draft_cache = gen.init_page_pool(
                dcfg, n_draft_pages + 1, self.page_tokens)
            self._valve = AcceptanceValve(
                floor=spec_accept_floor,
                window_rounds=spec_window_rounds,
                reprobe_rounds=spec_reprobe_rounds)
            self._draft_prefill, self._propose, self._verify = \
                _spec_programs(cfg, dcfg, page, max_seq, K, self.shard)

        # Per-slot host state (the scheduler's view; device state is the
        # page pool + whatever the last step returned).
        self._slots: list[_Request | None] = [None] * max_batch
        self._tokens = np.zeros(max_batch, np.int32)
        self._pos = np.zeros(max_batch, np.int32)
        self._temps = np.zeros(max_batch, np.float32)
        # Zero keys for idle rows (their split/sample is discarded); a
        # slot's real key chain starts at PRNGKey(seed) on admission.
        self._keys = np.zeros((max_batch, 2), np.uint32)
        # Page tables: host-authored only (the device never mutates
        # them), uploaded lazily — _tables_dev invalidates on every
        # admission and retirement, so a freed page can never be
        # re-allocated while a stale device table still routes an idle
        # row's writes at it. Unmapped entries are 0 = the scratch page.
        self._tables = np.zeros((max_batch, self.n_blocks), np.int32)
        self._tables_dev = None
        self._slot_pages: list[list[int]] = [[] for _ in range(max_batch)]
        # Draft-side slot state (speculative decoding): a row with a
        # draft page table + pages is a SPEC row — it proposes every
        # verify round; a row whose draft allocation failed (or that
        # was admitted while the valve was closed) decodes at exactly
        # plain speed through the same verify program (spec_mask False
        # forces its accepted count to 0). All-zero draft tables route
        # non-spec and idle rows' draft writes to scratch page 0.
        self._spec_row = [False] * max_batch
        self._draft_tables = np.zeros((max_batch, self.n_blocks), np.int32)
        self._draft_tables_dev = None
        # Device mirror of _spec_row, cached like the page tables: the
        # mask only changes at admission / retirement / valve flips, so
        # steady-state verify rounds must not pay a per-round H2D
        # upload for it (invalidated exactly where _draft_tables_dev
        # is).
        self._spec_mask_dev = None
        self._draft_slot_pages: list[list[int]] = \
            [[] for _ in range(max_batch)]
        self._spec_keys = np.zeros((max_batch, 2), np.uint32)
        self._spec_keys_dev = None
        self._spec_rounds = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_fallbacks = 0
        self._target_steps = 0
        self._decode_tokens = 0
        # Debounces the page_pool_exhausted event: one per episode, not
        # one per engine-loop spin while blocked.
        self._pool_blocked = False
        # Device-resident step operands (tokens, pos, keys, temps): the
        # decode hot loop feeds each step the previous step's outputs and
        # never touches the host mirrors above — per-step host work drops
        # to ONE [B] token fetch (the emit). None = mirrors are fresher
        # (admission wrote a row): the next step re-uploads once.
        self._dev: tuple | None = None
        # The plain round on the device that the host has not landed yet
        # (_plain_once dispatches the next round before it lands this one;
        # _land). At most one, and none while _dev is None.
        self._inflight: _Round | None = None
        # Plain rounds by how they were dispatched (behind an unlanded round,
        # or onto an empty queue), and rows a round stepped once past their
        # last token (oim_serve_decode_rounds_total,
        # oim_serve_overrun_rows_total; stats() shows the sums).
        self._rounds = {"ahead": 0, "drained": 0}
        self._overrun_rows = 0
        self._pending: collections.deque[_Request] = collections.deque()
        # Engine-thread command queue: the device pool's buffers are
        # DONATED to the jitted step programs, so any D2H read of them
        # (chain snapshots for volume export) must interleave with the
        # engine's own dispatches — callers enqueue a thunk, the run
        # loop services it between steps (_call_on_engine).
        self._cmds: collections.deque = collections.deque()
        # Member-lease liveness (sharded replicas): stats() folds the
        # watch callback's ready count into the published readiness, so
        # ONE lapsed member lease flips the whole replica not-ready and
        # routers rotate away (serve/shard.py ShardMembers).
        self._member_watch = None
        self._members_ok = True
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._stopping = False
        self._draining = False
        self._completions: collections.deque[float] = collections.deque()
        # Lifetime finished-request count (any reason). _completions is
        # a sliding QPS WINDOW — its length is not monotone — so "did
        # traffic ever reach this engine" probes (the chaos sim) need
        # their own counter.
        self.finished_total = 0
        self._thread = threading.Thread(
            target=self._run, name="oim-serve-engine", daemon=True)
        self._thread.start()

    # -- submission ---------------------------------------------------------

    def submit(self, prompt, max_new: int = 0, temperature: float = 0.0,
               seed: int = 0, eos: int = -1) -> GenHandle:
        """Queue one request; returns immediately with its handle.
        Raises ``QueueFull`` (bounded queue) or ``Draining`` (engine
        stopping), and ``ValueError`` for an inadmissible request."""
        prompt = [int(t) for t in prompt]
        max_new = int(max_new) or self.default_max_new
        temperature = float(temperature)
        if not prompt:
            raise ValueError("empty prompt")
        if temperature < 0:
            # A negative temperature would flip the logit ordering
            # mid-stream (garbage sampling, not an error) — fail the
            # request at admission like every other bad argument.
            raise ValueError(
                f"temperature must be >= 0, got {temperature}")
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        if len(prompt) + max_new > self.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new}) "
                f"exceeds the engine's max_seq {self.max_seq}")
        # Chaos lever: arm a QueueFull/Draining INSTANCE to simulate
        # admission refusal (the service maps them to the wire statuses
        # the router's retry contract covers).
        try:
            faultinject.fire("serve.admit", engine=self.name)
        except QueueFull:
            # A simulated refusal must be indistinguishable from a real
            # one in /metrics (the real path below increments this; a
            # Draining injection mirrors the real Draining path, which
            # records nothing).
            M.SERVE_REQUESTS_TOTAL.labels(outcome="rejected").inc()
            raise
        need = self._blocks_needed(len(prompt), max_new)
        if need > self._pagepool.n_pages:
            # A request the whole pool can never hold would queue
            # forever — refuse it up front (pool exhaustion that CAN
            # clear backpressures through the queue instead).
            raise ValueError(
                f"request needs {need} KV pages "
                f"({self.page_tokens} tokens each) but the pool holds "
                f"{self._pagepool.n_pages}; raise kv_pool_tokens or "
                f"lower max_new_tokens")
        req = _Request(
            prompt=prompt, max_new=max_new, temperature=float(temperature),
            seed=int(seed), eos=int(eos),
            submitted_at=time.monotonic(),
            trace_ctx=tracing.current_context(),
        )
        with self._lock:
            if self._stopping or self._draining:
                raise Draining("engine is draining; not accepting requests")
            if len(self._pending) >= self.queue_depth:
                M.SERVE_REQUESTS_TOTAL.labels(outcome="rejected").inc()
                raise QueueFull(
                    f"admission queue full ({self.queue_depth} waiting)")
            self._pending.append(req)
            M.SERVE_QUEUE_DEPTH.set(len(self._pending))
            self._work.notify()
        return GenHandle(req)

    # -- lifecycle ----------------------------------------------------------

    def stop(self, drain: bool = True, timeout: float = 60.0,
             quiet: bool = False) -> None:
        """Shut the engine down. ``drain=True`` (graceful) finishes every
        RESIDENT request first; queued-but-unadmitted requests finish as
        "drained" either way (their stream closes with no tokens).
        ``quiet`` suppresses the flight-recorder event — for harnesses
        simulating a SIGKILL, where the real process would have emitted
        nothing."""
        with self._lock:
            active = sum(s is not None for s in self._slots)
            queued = len(self._pending)
        # Emit BEFORE flipping the drain flag: the first thing a drain
        # causes downstream is a Draining->UNAVAILABLE rejection, and
        # the flight recorder must show its cause (this event) strictly
        # before its effects (router_mark_failed/router_retry) — the
        # chaos ladder asserts that order. The counts are a snapshot
        # one instruction early, which is all they ever were.
        if not quiet:
            events.emit(events.REPLICA_DRAIN, graceful=drain,
                        active_slots=active, queued=queued)
        with self._lock:
            self._draining = True
            if not drain:
                self._stopping = True
            self._work.notify()
        self._thread.join(timeout=timeout)
        rounds = sum(self._rounds.values())
        if rounds:
            from_context().info(
                "decode rounds dispatched", **self._rounds,
                ahead_share=round(self._rounds["ahead"] / rounds, 4),
                overrun_rows=self._overrun_rows,
                decode_attention=self.decode_attention,
                prefill_attention=self.prefill_attention)
        if self.cfg.n_experts:
            rungs = self._rung_calls()
            calls = sum(rungs.values())
            shares = {f"{name}_share": round(n / calls, 4)
                      for name, n in rungs.items()} if calls else {}
            from_context().info("expert rows dispatched", **self._expert_rows,
                                **rungs, **shares)
        if self.state_bytes:
            from_context().info(
                "recurrent state held", state_bytes=self.state_bytes,
                resets=self._state_resets, **{
                    f"{kind}_bytes": n
                    for kind, n in self.state_bytes_by_kind.items()})

    @property
    def active_slots(self) -> int:
        with self._lock:
            return sum(s is not None for s in self._slots)

    @property
    def queue_len(self) -> int:
        with self._lock:
            return len(self._pending)

    def set_member_watch(self, fn) -> None:
        """Register the member-liveness poll (``ShardMembers.
        member_counts``) a sharded replica's stats() folds into its
        published readiness. The callback does a registry RPC, so
        stats() calls it OUTSIDE the engine lock."""
        self._member_watch = fn

    def stats(self) -> dict:
        """One consistent load snapshot — what a serve replica's registry
        heartbeat publishes and the request router routes on (free decode
        slots first, queued backlog as the tie-break)."""
        counts = None
        if self.shard > 1 and self._member_watch is not None:
            counts = self._member_watch()  # registry RPC: never under lock
        with self._lock:
            active = sum(s is not None for s in self._slots)
            snap = {
                "free_slots": self.max_batch - active,
                "active_slots": active,
                "queue_depth": len(self._pending),
                "queue_capacity": self.queue_depth,
                "max_batch": self.max_batch,
                "ready": not (self._draining or self._stopping),
                # Decode cadence accounting: tokens emitted by decode /
                # verify rounds over the rounds that produced them —
                # tokens_per_target_step > 1 is speculation paying off
                # (tests/test_spec_smoke.py holds it). Extra keys ride
                # the heartbeat row; pre-spec routers ignore them
                # (Replica.parse reads only the fields it knows).
                "target_steps": self._target_steps,
                "decode_tokens": self._decode_tokens,
                # Plain rounds dispatched behind an unlanded round / onto
                # an empty queue, and rows stepped once past their last
                # token (_plain_once, _land).
                "decode_rounds_ahead": self._rounds["ahead"],
                "decode_rounds_drained": self._rounds["drained"],
                "overrun_rows": self._overrun_rows,
                # Disaggregation role rides the heartbeat row; pre-role
                # routers ignore it, new routers split requests across
                # tiers (missing/malformed reads back as "mixed").
                "role": self.role,
                # Build facts, not loads: "pallas_paged" /
                # "pallas_paged_prefill" or "jnp_gather" (see __init__).
                "decode_attention": self.decode_attention,
                "prefill_attention": self.prefill_attention,
                # Pages in use, and the kind of cache they hold ("gqa": K
                # and V by head; "latent": one vector a position). Flat
                # scalars: readers of the serve/<id> row take no nesting.
                "cache_kind": self.cache_kind,
                "kv_pages_used": self._pagepool.used_pages,
            }
            if self.state_bytes:
                snap.update(state_bytes=self.state_bytes,
                            state_slots_live=active,
                            state_resets=self._state_resets)
            if self.cfg.n_experts:
                snap.update(
                    expert_rows_dropless=self._expert_rows["dropless"],
                    expert_rows_padded=self._expert_rows["padded"],
                    **self._rung_calls())
            if _counts_expert_load(self.cfg):
                steps, touched, fullest = self._expert_load
                snap.update(expert_load_steps=int(steps),
                            experts_touched_sum=float(touched),
                            expert_load_max_over_mean_sum=float(fullest))
            if self.role == "prefill":
                # A COLD prefill replica must still advertise its block
                # size: the router's split gate compares prompt length
                # against it, and registration only stamps the block
                # alongside a non-empty hot-prefix advertisement —
                # which a freshly booted prefill tier doesn't have yet.
                snap["prefix_block"] = self.prefix_block
            if self.shard > 1:
                # Shard keys ride the heartbeat row only on sharded
                # replicas (same stance as the spec keys): pre-shard
                # readers never see them, oimctl dash-degrades. ONE
                # lapsed member lease flips the whole replica
                # not-ready — a mesh missing a member cannot decode,
                # so the router must rotate away NOW, not at first
                # collective timeout.
                ready_members = (min(int(counts["ready"]), self.shard)
                                 if counts else self.shard)
                members_ok = ready_members >= self.shard
                snap["shard_total"] = self.shard
                snap["shard_ready"] = ready_members
                snap["ready"] = snap["ready"] and members_ok
                if counts is not None:
                    M.SERVE_SHARD_MEMBERS.labels(state="ready").set(
                        counts["ready"])
                    M.SERVE_SHARD_MEMBERS.labels(state="stale").set(
                        counts.get("stale", 0))
                if members_ok != self._members_ok:
                    events.emit(
                        events.SHARD_MEMBER_LOST if not members_ok
                        else events.SHARD_MEMBER_HEALED,
                        engine=self.name, ready=ready_members,
                        total=self.shard)
                    self._members_ok = members_ok
            if self.spec_tokens:
                proposed, accepted = self._spec_proposed, \
                    self._spec_accepted
                snap.update({
                    "spec_tokens": self.spec_tokens,
                    "spec_on": self._valve.open,
                    "spec_rounds": self._spec_rounds,
                    "spec_proposed": proposed,
                    "spec_accepted": accepted,
                    "spec_accept_rate": (
                        round(accepted / proposed, 4) if proposed
                        else None),
                    # The valve's window — what fallback decisions and
                    # --top's ACCEPT column track; the lifetime ratio
                    # above can mask a recent collapse.
                    "spec_accept_rate_rolling": (
                        round(r, 4)
                        if (r := self._valve.rate()) is not None
                        else None),
                    "spec_fallbacks": self._spec_fallbacks,
                })
            return snap

    def hot_prefixes(self, n: int | None = None) -> list[str]:
        """The hottest cached chain hashes (MRU first) — what the
        heartbeat re-publish advertises so the router can herd
        same-prefix requests here. Empty when the cache is disabled."""
        if self._prefix is None:
            return []
        return self._prefix.hot(self.ADVERTISE_PREFIXES if n is None
                                else n)

    def prefix_tiers(self, n: int | None = None) -> dict:
        """Hash -> tier ("hbm" | "host") for the heartbeat
        advertisement: the hottest store entries plus the hottest
        demoted blocks. A hash resident in both tiers cannot happen
        (move semantics), but hbm wins defensively. Empty when the
        prefix cache is disabled — the row then carries no tier map
        and old routers see exactly the pre-tier advertisement."""
        limit = self.ADVERTISE_PREFIXES if n is None else n
        out = {h: "hbm" for h in self.hot_prefixes(limit)}
        if self._host_tier is not None:
            for h in self._host_tier.hot(limit):
                out.setdefault(h, "host")
        return out

    def host_stats(self) -> dict:
        """Host-tier census (the chaos census' second rung); zeros
        when tiering is off."""
        if self._host_tier is None:
            return {"entries": 0, "bytes": 0, "capacity_bytes": 0,
                    "demotions": 0, "promotions": 0}
        return self._host_tier.stats()

    def evict_prefix_store(self) -> int:
        """Drop every prefix-store reference NOW (bench/census;
        store-only pages demote into the host tier first when tiering
        is on). The demote hook D2H-reads the donated pool buffers, so
        call only from the engine thread or on an idle/stopped engine.
        Returns pages freed."""
        if self._prefix is None:
            return 0
        return self._prefix.evict_all()

    def evict_host_tier(self) -> int:
        """Drop every demoted block NOW (drain/census). Returns blocks
        dropped."""
        if self._host_tier is None:
            return 0
        return self._host_tier.evict_all()

    def set_kv_fetch(self, fn) -> None:
        """(Re)wire the peer-fetch callback on a running engine — the
        chaos harness swaps in fault-injecting wrappers; boots pass
        ``kv_fetch`` to the ctor instead. No-op while the prefix cache
        is disabled (the callback would never fire)."""
        if self._prefix is not None:
            self._kv_fetch = fn

    def prefix_stats(self) -> dict:
        """Prefix-store census (tests, debugging); zeros when disabled."""
        if self._prefix is None:
            return {"entries": 0, "bytes": 0, "capacity_bytes": 0,
                    "block": self.prefix_block}
        return self._prefix.stats()

    def pool_stats(self) -> dict:
        """Page-pool census: totals, occupancy, sharing, and the peak
        watermark the paged-vs-dense acceptance compares against
        ``dense_equiv_pages`` (what a max_batch x max_seq dense cache
        would have reserved in page units)."""
        s = self._pagepool.stats()
        s["dense_equiv_pages"] = self.max_batch * self.n_blocks
        # Recurrent state beside the pages (0 without such layers): its
        # bytes are held whole from construction, a kind of layer ("mamba",
        # "kda", "gdn"; "cca": the tails of compressed convolutional
        # attention); a slot's row is live while a request decodes in it.
        s["state_bytes"] = self.state_bytes
        s["state_bytes_by_kind"] = dict(self.state_bytes_by_kind)
        s["state_slots_live"] = self.active_slots if self.state_bytes else 0
        return s

    def spec_stats(self) -> dict:
        """Speculation census: the draft pool's occupancy (the leak
        gate `make spec-smoke` drives to zero after drain) plus the
        valve state. Zeros when speculation is not configured."""
        if not self.spec_tokens:
            return {"enabled": False, "spec_tokens": 0,
                    "draft_total_pages": 0, "draft_used_pages": 0,
                    "draft_free_pages": 0, "draft_peak_used_pages": 0,
                    "spec_on": False}
        s = self._draft_pagepool.stats()
        return {
            "enabled": True,
            "spec_tokens": self.spec_tokens,
            "draft_total_pages": s["total_pages"],
            "draft_used_pages": s["used_pages"],
            "draft_free_pages": s["free_pages"],
            "draft_peak_used_pages": s["peak_used_pages"],
            "spec_on": self._valve.open,
        }

    def _blocks_needed(self, n_prompt: int, max_new: int) -> int:
        """Pages an admission reserves: the positions the request can
        actually write — prompt [0, n) plus decode [n, n + max_new - 1)
        (the final token is emitted, never written back) — NOT a dense
        max_seq slot. This is what lets short requests pack a pool a
        dense layout would have exhausted."""
        tokens = max(1, n_prompt + max_new - 1)
        return -(-tokens // self.page_tokens)

    # -- engine loop --------------------------------------------------------

    def _run(self) -> None:
        log = from_context()
        try:
            while True:
                with self._lock:
                    while (not self._pending and not self._cmds
                           and not any(s is not None for s in self._slots)
                           and not (self._stopping or self._draining)):
                        with tracing.annotate("serve.wait"):
                            self._work.wait()
                    if self._stopping or self._draining:
                        self._fail_pending_locked("drained")
                    stop_now = self._stopping
                    done = (self._stopping or self._draining) and not any(
                        s is not None for s in self._slots)
                if done:
                    self._land()  # rows past their last token: not waited for
                    self._fail_cmds()
                    return
                if stop_now:
                    self._evict_all("drained")
                    self._fail_cmds()
                    return
                self._service_cmds()
                with tracing.annotate("serve.admit"):
                    self._admit()
                if any(s is not None for s in self._slots):
                    self._decode_once()
                else:
                    # The round in flight stepped nothing but rows past
                    # their last token: nothing of it is waited for.
                    self._land()
        except Exception as err:  # noqa: BLE001 - the loop IS the process
            import traceback

            log.error("serve engine died; failing all requests",
                      error=repr(err), traceback=traceback.format_exc())
            self._evict_all("error")
            with self._lock:
                self._stopping = True
                self._fail_pending_locked("error")
            self._fail_cmds()

    def _fail_pending_locked(self, reason: str) -> None:
        while self._pending:
            req = self._pending.popleft()
            self._finish(req, reason)
        M.SERVE_QUEUE_DEPTH.set(0)

    # -- engine-thread command queue ----------------------------------------

    def _service_cmds(self) -> None:
        while True:
            with self._lock:
                if not self._cmds:
                    return
                fn, box = self._cmds.popleft()
            self._land()  # a command reads the pools with nothing in flight
            try:
                box["result"] = fn()
            except Exception as err:  # noqa: BLE001 - relayed to caller
                box["error"] = err
            box["done"].set()

    def _fail_cmds(self) -> None:
        while True:
            with self._lock:
                if not self._cmds:
                    return
                _, box = self._cmds.popleft()
            box["error"] = Draining("engine stopped before the command ran")
            box["done"].set()

    def _call_on_engine(self, fn, timeout: float = 30.0):
        """Run ``fn`` on the engine thread between steps and return its
        result — the only legal way for another thread to read the
        device pool (its buffers are donated to the step programs)."""
        if threading.current_thread() is self._thread:
            return fn()
        box: dict = {"done": threading.Event(), "result": None,
                     "error": None}
        with self._lock:
            if self._stopping or self._draining:
                raise Draining("engine is draining; not taking commands")
            self._cmds.append((fn, box))
            self._work.notify()
        if not box["done"].wait(timeout):
            raise TimeoutError(
                f"engine command did not run within {timeout}s")
        if box["error"] is not None:
            raise box["error"]
        return box["result"]

    # -- KV tiering / fleet prefix sharing -----------------------------------

    def snapshot_chain(self, hashes, timeout: float = 30.0):
        """D2H copies of a cached chain's blocks, in chain order —
        the export path's read (serve/kvvolume.py packs them). Runs on
        the engine thread via the command queue; the pages are pinned
        (ref'd) for the copy so no eviction can free them mid-read.
        None when the chain is not fully cached anymore."""
        hashes = list(hashes)
        if self._prefix is None or not hashes:
            return None

        def snap():
            pages = self._prefix.gather(hashes)
            if pages is None:
                return None
            self._pagepool.ref(pages)
            try:
                return [page_kv(self._cache, p) for p in pages]
            finally:
                self._pagepool.unref(pages)

        return self._call_on_engine(snap, timeout=timeout)

    def note_exported(self, deepest_hash: str, volume_id: str) -> None:
        """Record a chain this replica exported (heartbeat rows
        advertise the map so peers can resolve holder volumes)."""
        with self._lock:
            self._exported[str(deepest_hash)] = str(volume_id)

    def exported_volumes(self) -> dict:
        with self._lock:
            return dict(self._exported)

    def set_handoff_export(self, fn) -> None:
        """Arm the prefill-tier retire hook: ``fn(engine, hashes)``
        runs synchronously on the engine thread when a slot retires
        with an exportable chain (oim-serve wires export_chain here
        for --role prefill). The decode pick is already waiting on
        the volume, so this cannot ride the lazy --kv-export sweep.
        None disarms."""
        with self._lock:
            self._handoff_export = fn

    def hot_chains(self, n: int = 4) -> list[tuple]:
        """The full cumulative-hash chains of the most recent
        admissions, MRU first — what the background exporter walks.
        A returned chain may have partially evicted since admission;
        export_chain() re-checks full residency via snapshot_chain."""
        with self._lock:
            chains = list(self._hot_chains.values())
        chains.reverse()
        return chains[:max(0, int(n))]

    def _demote_page(self, key: str, page: int) -> None:
        """PrefixStore demote hook: D2H the evicting store-only page
        into the host tier (engine thread — every store mutation path
        runs here, which is what makes the device read legal)."""
        self._host_tier.put(key, *page_kv(self._cache, page))

    def _alloc_one(self) -> int | None:
        """One fresh page for a promotion/adoption, shedding cold
        store references first under pressure (the _map_slot valve)."""
        pages = self._pagepool.alloc(1)
        if pages is None and self._prefix is not None:
            self._prefix.release(1)
            pages = self._pagepool.alloc(1)
        return pages[0] if pages else None

    def _install_block(self, key: str, page: int,
                       shared: list[int]) -> None:
        """Index one freshly staged page: the store takes its own ref
        (install), the page's alloc-time ref becomes this admission's
        pin — the same two-ref shape a gather+ref hit holds."""
        self._prefix.install(key, page)
        shared.append(page)

    def _promote_tail(self, chain: list[str], m: int,
                      shared: list[int]) -> int:
        """Extend the HBM match with host-tier blocks: H2D re-stage
        each consecutive demoted block into a fresh page (move
        semantics — the host entry pops once the bytes are back on
        device). Stops at the first gap or on pool pressure; returns
        the new matched depth."""
        if self._host_tier is None:
            return m
        while m < len(chain):
            got = self._host_tier.get(chain[m])
            if got is None:
                break
            page = self._alloc_one()
            if page is None:
                break
            self._cache = stage_page(self._cache, page, *got)
            self._host_tier.pop(chain[m])
            self._install_block(chain[m], page, shared)
            m += 1
        return m

    def _adopt_peer(self, chain: list[str], m: int, shared: list[int],
                    req: _Request) -> int:
        """Fleet tier: ask the kv_fetch callback for the unmatched
        chain tail and H2D-adopt whatever consecutive blocks it
        returns. ANY failure — callback error, None, non-consecutive
        blocks, pool pressure mid-adoption — leaves a valid shorter
        prefix and the normal prefill computes the rest: fallback is
        recompute, never a misaligned resume."""
        try:
            fetched = self._kv_fetch(chain, m)
        except Exception as err:  # noqa: BLE001 - fallback is recompute
            events.emit(events.KV_FETCH_FALLBACK,
                        trace_id=self._trace_id(req), error=repr(err),
                        matched_blocks=m, chain_blocks=len(chain))
            return m
        if fetched is None:
            events.emit(events.KV_FETCH_FALLBACK,
                        trace_id=self._trace_id(req),
                        matched_blocks=m, chain_blocks=len(chain))
            return m
        keys, pages, blocks = [], [], []
        for key, block in fetched:
            if m + len(keys) >= len(chain) or key != chain[m + len(keys)]:
                break  # only a consecutive continuation may adopt
            page = self._alloc_one()
            if page is None:
                break
            keys.append(key)
            pages.append(page)
            blocks.append(block)
        if not keys:
            return m
        try:
            # One batched scatter for the whole adopted run — per-page
            # dispatch overhead would eat the prefill this path saves.
            self._cache = stage_pages(self._cache, pages, blocks)
        except Exception as err:  # noqa: BLE001 - e.g. peer shape skew
            self._pagepool.unref(pages)
            events.emit(events.KV_FETCH_FALLBACK,
                        trace_id=self._trace_id(req), error=repr(err),
                        matched_blocks=m, chain_blocks=len(chain))
            return m
        for key, page in zip(keys, pages):
            self._install_block(key, page, shared)
        m += len(keys)
        M.SERVE_PREFIX_PEER_TOKENS.inc(len(keys) * self.prefix_block)
        events.emit(events.KV_PEER_FETCH,
                    trace_id=self._trace_id(req), blocks=len(keys),
                    tokens=len(keys) * self.prefix_block)
        return m

    def _evict_all(self, reason: str) -> None:
        # The round in flight is discarded: its tokens reach no stream, and
        # nothing of it is waited for (no request is left to step).
        self._inflight = None
        for i, req in enumerate(self._slots):
            if req is not None:
                # Hard eviction (ungraceful stop / engine error): no
                # prefix donation, but every page MUST return — the
                # pool outlives the request and leaks are forever.
                self._release_slot(i, req, retain=False)
                self._slots[i] = None
                events.emit(events.SLOT_EVICTED,
                            trace_id=self._trace_id(req), slot=i,
                            reason=reason, tokens=req.emitted)
                self._finish(req, reason)
        self._occupancy()

    def _occupancy(self) -> None:
        live = sum(s is not None for s in self._slots)
        M.SERVE_SLOT_OCCUPANCY.set(live / self.max_batch)
        if self.state_bytes:
            M.SERVE_STATE_SLOTS_LIVE.set(live)

    def _finish(self, req: _Request, reason: str) -> None:
        req.finish_reason = reason
        req.finished_at = time.monotonic()
        self._record_phases(req)
        req.out.put(_DONE)
        self.finished_total += 1
        M.SERVE_REQUESTS_TOTAL.labels(outcome=reason).inc()
        now = req.finished_at
        self._completions.append(now)
        while (self._completions
               and now - self._completions[0] > self.QPS_WINDOW_S):
            self._completions.popleft()
        span = max(now - self._completions[0], 1e-3)
        M.SERVE_QPS.set(
            len(self._completions) / max(span, self.QPS_WINDOW_S / 2))

    @staticmethod
    def _trace_id(req: _Request) -> str:
        return req.trace_ctx.trace_id if req.trace_ctx is not None else ""

    def _record_phases(self, req: _Request) -> None:
        """Synthesize the request's phase spans at retirement — the
        boundaries (submit, admit, first token, finish) are monotonic
        bookkeeping, only complete now. ``oimctl --autopsy`` tiles the
        request's timeline from these plus the live prefill span; two
        ring appends per request, the flight-recorder cost class."""
        now_wall, now_mono = time.time(), time.monotonic()

        def wall(mono: float) -> float:
            return now_wall - (now_mono - mono)

        if req.admitted_at and req.admitted_at > req.submitted_at:
            tracing.record_phase(
                "serve.queue_wait", wall(req.submitted_at),
                req.admitted_at - req.submitted_at, parent=req.trace_ctx)
        if req.first_emit_at and req.finished_at > req.first_emit_at \
                and req.emitted > 1:
            duration = req.finished_at - req.first_emit_at
            tracing.record_phase(
                "serve.decode", wall(req.first_emit_at), duration,
                parent=req.trace_ctx, tokens=req.emitted - 1)

    def _emit(self, req: _Request, token: int) -> None:
        now = time.monotonic()
        base = req.last_emit_at or req.submitted_at
        # kind splits the SLO (submit->first token) from decode cadence;
        # the request's trace_id rides the bucket as an OpenMetrics
        # exemplar, so a slow p99 bucket names a concrete request.
        kind = "first" if req.emitted == 0 else "next"
        M.SERVE_TOKEN_LATENCY.labels(kind=kind).observe(
            now - base, self._trace_id(req))
        if kind == "first":
            # The prefix cache's latency win, one scrape away: the same
            # SLO latency split by whether this request's prefill
            # skipped a cached prefix.
            M.SERVE_FIRST_TOKEN.labels(
                prefix="hit" if req.prefix_tokens else "miss").observe(
                now - base, self._trace_id(req))
        M.SERVE_TOKENS_TOTAL.inc()
        if kind == "first":
            req.first_emit_at = now
        else:
            self._decode_tokens += 1
        req.last_emit_at = now
        req.emitted += 1
        req.out.put(int(token))

    def _count_expert_rows(self, n_tokens: int) -> None:
        """One target program over ``n_tokens`` tokens is on its way: its
        expert rows, from its shapes (no fetch, no device work)."""
        if not self.cfg.n_experts:
            return
        dispatch, rows = self._rows_of(self.cfg, n_tokens)
        self._expert_rows[dispatch] += rows
        M.SERVE_EXPERT_ROWS.labels(dispatch=dispatch).inc(rows)

    def _rung_calls(self) -> dict:
        """stats()' and the stop line's view of ``_expert_rungs`` (of an
        expert model: its callers ask for no other)."""
        return {f"expert_calls_{name}_rung": int(calls) for name, calls
                in zip(self._rung_names, self._expert_rungs)}

    def _count_expert_rungs(self, tok, key, rungs: list) -> tuple:
        """The prompt's first token and its RNG carry, fetched; with them,
        in the same wait, the rung tallies of the prompt's prefill calls
        (``rungs``: one a call of a program with a ladder, else [])."""
        tok, key, rungs = self._jax.device_get((tok, key, rungs))
        if rungs:
            calls = np.sum(rungs, axis=0)
            self._expert_rungs += calls
            for name, n in zip(self._rung_names, calls):
                M.SERVE_EXPERT_CALLS.labels(rung=name).inc(int(n))
        return int(tok), key

    def _bucket(self, n: int) -> int:
        b = self.MIN_PREFILL_BUCKET
        while b < n:
            b *= 2
        return min(b, self.max_seq)

    def _sync_host(self) -> None:
        """Pull the device-resident step operands back into the host
        mirrors (writable copies) before an admission mutates a row; the
        next decode step re-uploads the merged state once. Whatever round
        is in flight lands first: the mirrors are read behind no round
        whose tokens the host still holds back (an admission landed it
        while its prefill ran, so this finds none)."""
        self._land()
        with tracing.annotate("serve.sync"):
            if self._spec_keys_dev is not None:
                self._spec_keys = np.array(self._spec_keys_dev)
                self._spec_keys_dev = None
            if self._dev is None:
                return
            # one wait for the three, and writable copies of what came
            self._tokens, self._pos, self._keys = map(
                np.array, self._jax.device_get(self._dev[:3]))
            self._dev = None

    def _admit(self) -> None:
        """Insert queued requests into free slots (prefill between decode
        steps: new work overlaps residents' decoding at step granularity;
        with ``prefill_chunk`` one request a call, see the end). The map
        and the prompt's first dispatch happen with the residents' round
        still in flight; ``_prefill_slot`` lands it while the prefill runs.
        Admission reserves the request's pages first; an exhausted pool
        leaves the request AT THE HEAD of the queue (FIFO preserved) and
        returns — retirements free pages, the next loop pass retries.
        The head is PEEKED, not popped, until its pages are mapped: only
        this thread ever removes from the left, so the peek is safe, and
        a blocked admission never transiently shrinks the queue (which
        would let a submit slip past the queue-depth bound while the
        pool is the real bottleneck)."""
        while True:
            with self._lock:
                free = next(
                    (i for i, s in enumerate(self._slots) if s is None), None)
                if free is None or not self._pending:
                    return
                req = self._pending[0]
                cancelled = req.cancelled.is_set()
                if cancelled:
                    self._pending.popleft()
                    M.SERVE_QUEUE_DEPTH.set(len(self._pending))
            if cancelled:
                self._finish(req, "cancelled")
                continue
            n = len(req.prompt)
            m, shared = 0, []
            with tracing.annotate("serve.map"):
                if self._prefix is not None:
                    chain = prefixhash.usable_hashes(
                        req.prompt, self.prefix_block)
                    if chain:
                        with self._lock:
                            self._hot_chains[chain[-1]] = tuple(chain)
                            self._hot_chains.move_to_end(chain[-1])
                            while len(self._hot_chains) > \
                                    self.ADVERTISE_PREFIXES * 4:
                                self._hot_chains.popitem(last=False)
                    m = self._prefix.match(chain)
                    if m:
                        got = self._prefix.gather(chain[:m])
                        if got is None:
                            m = 0  # a link evicted between match and gather
                        else:
                            shared = got
                            # Pin the shared pages NOW: once referenced,
                            # no eviction (LRU or pressure valve) can free
                            # them out from under this admission.
                            self._pagepool.ref(shared)
                    # Tier walk for the unmatched tail: host-RAM blocks
                    # re-stage H2D (promotion), then the fleet tier may
                    # extend further with peer-exported blocks; both leave
                    # pinned HBM pages behind, exactly like a store hit.
                    if m < len(chain):
                        m = self._promote_tail(chain, m, shared)
                    if self._kv_fetch is not None and m < len(chain):
                        m = self._adopt_peer(chain, m, shared, req)
                if not self._map_slot(req, free, n, m, shared):
                    return  # still the queue head; retried next loop pass
                # The draft half of the slot, best-effort: a request whose
                # draft pages can't be mapped (draft pool pressure, valve
                # closed) decodes plainly in the same batch instead of
                # waiting — target pages are the admission contract, draft
                # pages only an accelerator.
                spec_row = self._map_draft_slot(req, free, n)
            with self._lock:
                self._pending.popleft()
                M.SERVE_QUEUE_DEPTH.set(len(self._pending))
            req.admitted_at = time.monotonic()
            # Admission backpressure, made visible: how long the bounded
            # queue (and, now, the page pool) held this request before
            # its prefill started (the request's trace_id rides the
            # bucket as an exemplar).
            M.SERVE_QUEUE_WAIT.observe(
                req.admitted_at - req.submitted_at, self._trace_id(req))
            tok, key = self._prefill_slot(req, free, n, m)
            dkey = self._draft_prefill_slot(req, free, n) if spec_row \
                else None
            self._sync_host()  # merge device state before writing the row
            self._keys[free] = key
            self._tokens[free] = tok
            self._pos[free] = n
            self._temps[free] = req.temperature
            self._spec_row[free] = spec_row
            if spec_row:
                self._spec_keys[free] = np.asarray(dkey)
            with self._lock:
                self._slots[free] = req
            self._occupancy()
            self._emit(req, tok)
            self._retire_if_done(free, req, tok)
            if self.prefill_chunk:
                # One admission a loop pass: the residents get a decode
                # round between this prompt's last slice and the next
                # prompt's first, as they do between two slices of one
                # prompt. No token waits for more than one slice.
                return

    def _map_slot(self, req: _Request, slot: int, n: int,
                  m: int, shared: list[int]) -> bool:
        """Build slot ``slot``'s page table: ``m`` shared prefix pages
        (already pinned by the caller) followed by freshly allocated
        private pages for the tail and decode blocks. On pool pressure
        the prefix store releases unreferenced pages first (never one a
        live slot still maps — the refcount forbids it); if the pool
        still cannot cover the request, every pin is undone and False
        backpressures the admission."""
        need = self._blocks_needed(n, req.max_new)
        private = self._pagepool.alloc(need - m)
        if private is None and self._prefix is not None:
            # Pressure valve: shed cold cache references back to the
            # pool. Store-only pages free immediately; pages shared
            # with live slots are skipped (freeing them is impossible
            # by refcount, dropping them would gain nothing).
            deficit = (need - m) - self._pagepool.free_pages
            self._prefix.release(deficit)
            private = self._pagepool.alloc(need - m)
        if private is None:
            if shared:
                self._pagepool.unref(shared)
            if not self._pool_blocked:
                self._pool_blocked = True
                events.emit(events.PAGE_POOL_EXHAUSTED,
                            trace_id=self._trace_id(req),
                            needed_pages=need - m,
                            free_pages=self._pagepool.free_pages,
                            total_pages=self._pagepool.n_pages,
                            queued=self.queue_len)
            return False
        self._pool_blocked = False
        pages = shared + private
        self._slot_pages[slot] = pages
        self._tables[slot, :] = 0
        self._tables[slot, :len(pages)] = pages
        self._tables_dev = None
        return True

    def _map_draft_slot(self, req: _Request, slot: int, n: int) -> bool:
        """Reserve the request's draft pages (same footprint math as
        the target: ceil((prompt + max_new - 1) / page) — the draft
        never needs positions the target can't use). Returns False —
        plain decode for this request — when speculation is off, the
        valve is closed, or the draft pool can't cover it; draft
        exhaustion must never delay an admission the target pool
        already accepted."""
        if not self.spec_tokens or not self._valve.open:
            return False
        try:
            # Chaos lever: an armed InjectedFault IS a draft-pool
            # allocation failure — the request demotes to plain decode
            # (speculation is an accelerator, never a dependency).
            faultinject.fire("spec.propose", engine=self.name)
        except faultinject.InjectedFault:
            return False
        need = self._blocks_needed(n, req.max_new)
        pages = self._draft_pagepool.alloc(need)
        if pages is None:
            return False
        self._draft_slot_pages[slot] = pages
        self._draft_tables[slot, :] = 0
        self._draft_tables[slot, :len(pages)] = pages
        self._draft_tables_dev = None
        self._spec_mask_dev = None
        return True

    def _draft_prefill_slot(self, req: _Request, slot: int, n: int):
        """Fill the draft model's cache with the prompt (full prefill —
        the draft keeps no prefix store; it is small by definition).
        Returns the row's draft RNG carry, fold_in-decorrelated from
        the target/accept chain that shares the request seed."""
        jnp = self._jnp
        padded = np.zeros((1, self._bucket(n)), np.int32)
        padded[0, :n] = req.prompt
        key = self._jax.random.fold_in(
            self._jax.random.PRNGKey(req.seed), DRAFT_KEY_FOLD)
        with tracing.start_span(
                "serve.draft_prefill", parent=req.trace_ctx, slot=slot,
                prompt_tokens=n):
            self._draft_cache, dkey = self._draft_prefill(
                self._draft_params, self._draft_cache,
                jnp.asarray(padded), jnp.int32(n),
                jnp.asarray(self._draft_tables[slot]), jnp.int32(0),
                key)
        return dkey

    def _release_draft(self, slot: int) -> None:
        """Return a slot's draft pages and zero its draft table (the
        now-idle row's draft writes go back to scratch page 0)."""
        pages = self._draft_slot_pages[slot]
        if pages:
            self._draft_pagepool.unref(pages)
        self._draft_slot_pages[slot] = []
        self._draft_tables[slot, :] = 0
        self._draft_tables_dev = None
        self._spec_row[slot] = False
        self._spec_mask_dev = None

    def _state_row(self, slot: int) -> tuple:
        """The prefill program's last operand: the slot's row of the
        recurrent state, for a configuration that has any."""
        return (self._jnp.int32(slot),) if self.state_bytes else ()

    def _prefill_slot(self, req: _Request, slot: int, n: int, m: int):
        """One request's prefill through slot ``slot``'s page table:
        the first ``m`` blocks are shared store pages read in place
        (ZERO K/V copies — the hit's device work is the tail forward
        alone), the tail lands in the slot's private pages. One
        program serves both (``start`` is traced). Returns (first
        token, RNG carry)."""
        jnp = self._jnp
        P = m * self.prefix_block
        tail = req.prompt[P:]
        if self.prefill_chunk and len(tail) > self.prefill_chunk:
            tok, key = self._prefill_chunked(req, slot, n, m)
        else:
            padded = np.zeros((1, self._bucket(len(tail))), np.int32)
            padded[0, :len(tail)] = tail
            span_attrs = {"slot": slot, "prompt_tokens": n}
            if P:
                span_attrs["prefix_tokens"] = P
            self._count_expert_rows(padded.shape[1])
            with tracing.start_span(
                    "serve.prefill", parent=req.trace_ctx, **span_attrs):
                tok, self._cache, key, *rungs = self._prefill(
                    self.params, self._cache, jnp.asarray(padded),
                    jnp.int32(len(tail)),
                    jnp.asarray(self._tables[slot]), jnp.int32(P),
                    self._jax.random.PRNGKey(req.seed),
                    jnp.float32(req.temperature), *self._state_row(slot))
                # The prefill is queued behind the round in flight: that
                # round lands while the prefill runs, and only then is the
                # prompt's token waited for (_land).
                self._land()
                tok, key = self._count_expert_rungs(tok, key, rungs)
        if self._prefix is not None:
            if P:
                req.prefix_tokens = P
                M.SERVE_PREFIX_HITS.inc()
                M.SERVE_PREFILL_TOKENS.labels(source="cache").inc(P)
            else:
                M.SERVE_PREFIX_MISSES.inc()
        M.SERVE_PREFILL_TOKENS.labels(source="compute").inc(n - P)
        if self.state_bytes:  # the slot's state began from zeros
            self._state_resets += 1
            M.SERVE_STATE_RESETS.inc()
        return tok, key

    def _prefill_chunked(self, req: _Request, slot: int, n: int, m: int):
        """The prompt tail in --prefill-chunk token slices, one decode
        round over the RESIDENT slots between slices — admission never
        stalls a long prompt behind the batch, and the batch's decode
        cadence never stalls behind a long prompt. No slice but the last
        is waited for: the first slice is dispatched behind the round in
        flight, each round behind the slice in flight and the next slice
        behind the round (``_decode_once``'s ``queue_behind``), so the
        device runs round, slice, round, slice back to back while the host
        lands the round before, and a resident's gap across a slice is the
        device's time alone. The round behind which the LAST slice was
        queued lands before that slice's token is waited for (``_land``:
        the host waits behind no round it has not landed, or the
        residents' tokens would wait for a second slice). Byte-identical to
        one full prefill: every slice runs the SAME compiled program
        over the same pages at shifted ``start`` (attention math is
        position-indexed, not dispatch-indexed), and every slice gets
        the ORIGINAL PRNGKey(seed) — the program splits it once
        internally, so keeping only the final slice's (token, carry)
        reproduces exactly what the one-shot path returns.

        While slices interleave with decode, this slot's target table
        row is ZEROED (prefill runs through a device copy of the row
        instead): the row is not yet in _slots, so lockstep decode
        treats it as idle — and an idle row's scatter at a stale
        position must land on scratch page 0, never in the freshly
        mapped pages (m of which are SHARED store pages other slots
        read). The draft row gets the same treatment."""
        jnp = self._jnp
        P = m * self.prefix_block
        tail = req.prompt[P:]
        chunk = self.prefill_chunk
        table_row = self._tables[slot].copy()
        self._tables[slot, :] = 0
        self._tables_dev = None
        draft_row = None
        if self.spec_tokens:
            draft_row = self._draft_tables[slot].copy()
            self._draft_tables[slot, :] = 0
            self._draft_tables_dev = None
        table_dev = jnp.asarray(table_row)
        slot_dev = self._state_row(slot)
        key0 = self._jax.random.PRNGKey(req.seed)
        rungs: list = []  # each slice's tally of rungs, on the device

        def dispatch(off: int):
            """One slice on its way: (token, RNG carry, when)."""
            piece = tail[off:off + chunk]
            padded = np.zeros((1, self._bucket(len(piece))), np.int32)
            padded[0, :len(piece)] = piece
            since = time.monotonic()
            self._count_expert_rows(padded.shape[1])
            with tracing.annotate("serve.prefill_chunk"):
                tok, self._cache, key, *tally = self._prefill(
                    self.params, self._cache, jnp.asarray(padded),
                    jnp.int32(len(piece)), table_dev,
                    jnp.int32(P + off), key0,
                    jnp.float32(req.temperature), *slot_dev)
            rungs.extend(tally)
            return tok, key, since

        def landed(since: float) -> None:
            M.SERVE_PREFILL_CHUNK_SECONDS.observe(
                time.monotonic() - since, self._trace_id(req))

        with tracing.start_span(
                "serve.prefill", parent=req.trace_ctx, slot=slot,
                prompt_tokens=n, chunk_tokens=chunk,
                chunks=-(-len(tail) // chunk)):
            tok, key, since = dispatch(0)
            for off in range(chunk, len(tail), chunk):
                nxt: list = []
                with self._lock:
                    resident = any(r is not None for r in self._slots)
                if resident:
                    self._decode_once(
                        queue_behind=lambda off=off: nxt.append(dispatch(off)))
                else:  # a round of rows past their last token: dropped
                    self._land()
                tok.block_until_ready()  # behind a landed round
                landed(since)
                tok, key, since = nxt[0] if nxt else dispatch(off)
            self._land()  # the round the last slice is queued behind
            # device sync: the prompt is in the pages HERE
            tok, key = self._count_expert_rungs(tok, key, rungs)
            landed(since)
        self._tables[slot, :] = table_row
        self._tables_dev = None
        if draft_row is not None:
            self._draft_tables[slot, :] = draft_row
            self._draft_tables_dev = None
        return tok, key

    def _release_slot(self, slot: int, req: _Request,
                      retain: bool = True) -> None:
        """Return a retiring slot's pages to the pool. With ``retain``,
        first donate the prompt's FULL blocks to the prefix store BY
        REFERENCE — the store refs the very pages the prefill wrote, no
        slice-out copy — then drop the slot's own references (donated
        pages stay resident under the store's ref; undonated ones free
        when this was the last ref). The page table row zeroes so the
        now-idle decode row writes scratch page 0, never a page the
        pool may hand to the next admission. Retained bytes are a pure
        function of the prompt's token chain: decode only writes
        positions >= len(prompt), which live in later pages."""
        pages = self._slot_pages[slot]
        if retain and self._prefix is not None and pages:
            hashes = prefixhash.chain_hashes(req.prompt, self.prefix_block)
            if hashes:
                self._prefix.retain(hashes, pages[:len(hashes)])
        if pages:
            self._pagepool.unref(pages)
        self._slot_pages[slot] = []
        self._tables[slot, :] = 0
        self._tables_dev = None
        if self.spec_tokens:
            self._release_draft(slot)

    def _retire_if_done(self, slot: int, req: _Request, token: int) -> bool:
        if req.cancelled.is_set():
            reason = "cancelled"
        elif req.eos >= 0 and token == req.eos:
            reason = "eos"
        elif req.emitted >= req.max_new:
            reason = "length"
        else:
            return False
        # Chaos lever: a crash AT retirement, before any page returns —
        # the hardest spot to leak from (the census tests prove the
        # engine's failure teardown still zeroes the pools).
        faultinject.fire("serve.retire", engine=self.name, reason=reason)
        self._release_slot(slot, req)
        with self._lock:
            self._slots[slot] = None
            export = self._handoff_export
        if export is not None and reason != "cancelled":
            # Prefill-tier handoff: the chain this retirement just
            # donated to the store exports NOW, on the engine thread
            # (synchronous D2H is legal here — _call_on_engine
            # short-circuits), before _finish closes the client
            # stream: when the stream ends, the decode pick's fetch
            # must already find the volume.
            self._export_handoff(req, export)
        if reason == "cancelled":
            # Normal retirement (eos/length) is the steady state, not an
            # incident; an eviction by client cancel/deadline is what the
            # flight recorder exists to explain.
            events.emit(events.SLOT_EVICTED, trace_id=self._trace_id(req),
                        slot=slot, reason=reason, tokens=req.emitted)
        self._occupancy()
        self._finish(req, reason)
        return True

    def _export_handoff(self, req: _Request, export) -> None:
        """Export the retiring request's prompt chain as a
        content-addressed volume. The chain is ``usable_hashes`` — the
        full-block prefix a decode admission will MATCH — not the raw
        chain_hashes: the volume id is the deepest hash the decode
        pick's fetcher probes, so the two sides must derive it from
        the same truncation. Dedup on the deepest hash: re-publishing
        an already-exported volume id is a feeder error, not a refresh."""
        hashes = prefixhash.usable_hashes(req.prompt, self.prefix_block)
        if not hashes:
            M.SERVE_PREFILL_HANDOFFS.labels(outcome="skipped").inc()
            return
        with self._lock:
            done = hashes[-1] in self._exported
        if done:
            M.SERVE_PREFILL_HANDOFFS.labels(outcome="skipped").inc()
            return
        try:
            volume_id = export(self, list(hashes))
        except Exception:  # noqa: BLE001 - handoff is best-effort
            from_context().warning(
                "prefill handoff export failed; decode falls back to "
                "local prefill", trace_id=self._trace_id(req))
            M.SERVE_PREFILL_HANDOFFS.labels(outcome="export_failed").inc()
            return
        M.SERVE_PREFILL_HANDOFFS.labels(
            outcome="exported" if volume_id else "export_failed").inc()

    def _decode_once(self, queue_behind=None) -> None:
        """One decode round over every resident slot: a speculative
        draft-propose / target-verify round when a draft model is
        configured, the valve is open and any live slot holds a draft
        cache; one plain lockstep decode step otherwise (a closed
        valve's plain rounds tick the re-probe cooldown). A plain round
        leaves ONE ROUND IN FLIGHT: it dispatches this round and lands the
        one before it (``_plain_once``, ``_land``); a speculative round is
        dispatched onto an empty queue and waited for.

        ``queue_behind`` is called between the round's dispatch and the
        fetch the host then waits in: what it dispatches runs on the device
        right behind the round, while the host fetches and emits (a
        chunked prefill's next slice: ``_prefill_chunked``)."""
        # Chaos lever: an armed fault here wedges the engine — the run
        # loop's catch-all fails every request and stops admissions (a
        # crashed-but-still-listening replica).
        faultinject.fire("serve.decode", engine=self.name)
        if self.spec_tokens:
            if self._valve.open:
                with self._lock:
                    any_spec = any(
                        r is not None and self._spec_row[i]
                        for i, r in enumerate(self._slots))
                if any_spec:
                    self._spec_once(queue_behind)
                    return
            elif self._valve.tick_plain():
                from_context().info(
                    "speculation re-probing after cooldown",
                    reprobe_rounds=self._valve.reprobe_rounds)
        self._plain_once(queue_behind)

    def _observe_ici(self, live) -> None:
        """One ICI-allreduce observation per landed round (sharded
        replicas only): the per-layer collectives are fused inside the
        jitted step and cannot be host-timed individually, so a tiny
        compiled psum over the SAME mesh is timed instead — the
        exemplar carries a live request's trace_id so a slow allreduce
        links back to the request it stalled. The probe blocks, behind
        whatever is queued: after a plain round's emit loop that is the
        round in flight, so a sharded replica's loop is still step + host
        and the observation holds that round's rest (ROADMAP S3)."""
        from oim_tpu.serve import shard as shardlib

        M.SERVE_ICI_ALLREDUCE.observe(
            shardlib.time_allreduce(self.shard),
            self._trace_id(live[0][1]) if live else "")

    def _spec_once(self, queue_behind=None) -> None:
        """One speculative round: the draft proposes K tokens per row
        (K fused decode steps over its own page pool), the target
        verifies all K in ONE multi-token forward, and each live row
        emits its accepted prefix plus one target-supplied token —
        1..K+1 tokens for a single target dispatch. Rows without a
        draft slot ride the same programs at plain-decode semantics
        (spec_mask pins their accepted count to 0), so mixed
        spec/non-spec batches stay lockstep."""
        jnp = self._jnp
        self._land()  # a plain round in flight: this one runs behind none
        with tracing.annotate("serve.upload"):
            if self._dev is None:
                self._dev = (
                    jnp.asarray(self._tokens), jnp.asarray(self._pos),
                    jnp.asarray(self._keys), jnp.asarray(self._temps))
            if self._tables_dev is None:
                self._tables_dev = jnp.asarray(self._tables)
            if self._draft_tables_dev is None:
                self._draft_tables_dev = jnp.asarray(self._draft_tables)
            if self._spec_keys_dev is None:
                self._spec_keys_dev = jnp.asarray(self._spec_keys)
        d_tokens, d_pos, d_keys, d_temps = self._dev
        with self._lock:
            live = [(i, r) for i, r in enumerate(self._slots)
                    if r is not None]
            # A True _spec_row implies a live slot (retirement clears
            # it via _release_draft), so the row list IS the mask.
            spec_rows = list(self._spec_row)
        if self._spec_mask_dev is None:
            self._spec_mask_dev = jnp.asarray(
                np.array(spec_rows, dtype=bool))
        with tracing.annotate("serve.dispatch"):
            draft_toks, draft_logits, self._draft_cache, \
                self._spec_keys_dev = self._propose(
                    self._draft_params, self._draft_cache, d_tokens, d_pos,
                    self._spec_keys_dev, d_temps, self._draft_tables_dev)
            out, n_emit, tok, keys, self._cache, pos = self._verify(
                self.params, self._cache, d_tokens, d_pos, d_keys, d_temps,
                self._tables_dev, draft_toks, draft_logits,
                self._spec_mask_dev)
        self._dev = (tok, pos, keys, d_temps)
        self._count_expert_rows(self.max_batch * (self.spec_tokens + 1))
        with tracing.annotate("serve.fetch"):
            out = np.asarray(out)  # forces the round; the per-round fetch
            n_emit = np.asarray(n_emit)
        self._target_steps += 1
        self._spec_rounds += 1
        if self.shard > 1:
            self._observe_ici(live)
        proposed = self.spec_tokens * sum(spec_rows)
        accepted = sum(int(n_emit[i]) - 1 for i, _ in live
                       if spec_rows[i])
        self._spec_proposed += proposed
        self._spec_accepted += accepted
        if proposed:
            M.SERVE_SPEC_PROPOSED.inc(proposed)
            if accepted:
                M.SERVE_SPEC_ACCEPTED.inc(accepted)
        closed_now = self._valve.observe(proposed, accepted)
        rolling = self._valve.rate()
        if rolling is not None:
            # The gauge tracks the valve's own window (the fallback
            # signal), not the lifetime counter ratio — a draft that
            # stopped predicting the current traffic must show up on
            # the operator surface the moment the valve sees it.
            M.SERVE_SPEC_ACCEPT_ROLLING.set(round(rolling, 4))
        if closed_now:
            # The draft has stopped predicting this traffic: K draft
            # forwards per round now cost more than the accepted
            # tokens repay. Fall back to plain decode — live rows
            # release their draft pages NOW (their caches would only
            # go stale through the plain rounds) and re-probe after
            # the cooldown.
            self._spec_fallbacks += 1
            M.SERVE_SPEC_FALLBACK.inc()
            events.emit(events.SPEC_FALLBACK,
                        accept_floor=self._valve.floor,
                        window_rounds=self._valve.window_rounds,
                        reprobe_rounds=self._valve.reprobe_rounds,
                        proposed_total=self._spec_proposed,
                        accepted_total=self._spec_accepted)
            for i, _ in live:
                if spec_rows[i]:
                    self._release_draft(i)
        with tracing.annotate("serve.emit"):
            for i, req in live:
                if req.cancelled.is_set():
                    self._release_slot(i, req)
                    with self._lock:
                        self._slots[i] = None
                    events.emit(events.SLOT_EVICTED,
                                trace_id=self._trace_id(req), slot=i,
                                reason="cancelled", tokens=req.emitted)
                    self._occupancy()
                    self._finish(req, "cancelled")
                    continue
                # The device advanced past every token of the round; the
                # host emits only what the request's budget admits and
                # stops at the first EOS — a truncated row retires, so its
                # stale device row is rewritten at the next admission.
                count = min(int(n_emit[i]), req.max_new - req.emitted)
                for t in out[i, :count]:
                    self._emit(req, int(t))
                    if self._retire_if_done(i, req, int(t)):
                        break
            # The round's operands die under the name, as in _plain_once.
            del d_tokens, d_pos, d_keys, draft_toks, draft_logits

    def _plain_once(self, queue_behind=None) -> None:
        """One lockstep decode step over every resident slot, dispatched
        BEFORE the round before it is landed: the engine keeps one round in
        flight, and the host fetches, emits and retires round n while the
        device runs round n + 1, so the loop's period is the longer of the
        step and the host's work, not their sum. Idle rows compute a
        discarded garbage token.

        The hot loop is device-resident: each step's outputs (token, pos,
        key chain) ARE the next step's operands, so round n + 1 needs
        nothing of round n on the host — no per-step host-mirror round
        trips (the mirrors re-sync only around admissions, in _sync_host,
        and the round after one starts from uploaded operands with nothing
        in flight: a DRAINED dispatch, where every other is AHEAD). With
        several engines in one process (bench --replicas, replica-packed
        hosts) the GIL-held Python slice per step is what bounds aggregate
        throughput, so this is the difference between replicas that scale
        and replicas that serialize."""
        jnp = self._jnp
        with tracing.annotate("serve.upload"):
            if self._dev is None:
                self._dev = (
                    jnp.asarray(self._tokens), jnp.asarray(self._pos),
                    jnp.asarray(self._keys), jnp.asarray(self._temps))
            if self._tables_dev is None:
                self._tables_dev = jnp.asarray(self._tables)
        d_tokens, d_pos, d_keys, d_temps = self._dev
        with self._lock:
            rows = [(i, r) for i, r in enumerate(self._slots) if r is not None]
        with tracing.annotate("serve.dispatch"):
            tok, self._cache, keys, pos, *load = self._step(
                self.params, self._cache, d_tokens, d_pos, d_keys, d_temps,
                self._tables_dev)
        self._dev = (tok, pos, keys, d_temps)
        how = "drained" if self._inflight is None else "ahead"
        self._rounds[how] += 1
        M.SERVE_DECODE_ROUNDS.labels(dispatch=how).inc()
        self._count_expert_rows(self.max_batch)
        this = _Round(tok, load, rows, (d_tokens, d_pos, d_keys))
        del d_tokens, d_pos, d_keys
        if queue_behind is not None:
            queue_behind()
        self._land(then=this)

    def _land(self, then: _Round | None = None) -> None:
        """Land the plain round in flight, if one is: fetch its tokens (the
        one wait for the device), emit and retire its rows, release its
        operands. ``then``, a round dispatched behind it, is in flight
        afterwards. Everything that waits for the device lands first what
        is in flight (an admission after its prompt's dispatch, _sync_host,
        a speculative round, a command): the host never blocks on anything
        queued behind a round whose tokens it still holds back.

        The overrun step. A row that retires here (EOS, length, cancel) was
        stepped once more by ``then``, with the table row it had. Every
        retirement takes that step, the one by length too, though the host
        knows it a round early (one path; splitting a retirement in two
        would save a step of a row that is stepped as an idle row anyway).
        It is harmless by construction:

        * its one write is at the position after the row's last token,
          which ``_blocks_needed`` reserves no page for: behind a page edge
          the table entry is 0, the scratch page; elsewhere it falls in the
          row's last page at an offset the row never read, and that page is
          the row's on the device's timeline: freed here, it may go to the
          next admission, whose prefill is dispatched after ``then`` and so
          ordered behind the stale write, above its causal horizon until it
          overwrites it;
        * the prefix store keeps a prompt's FULL blocks, and decode
          positions lie behind them;
        * a hybrid's state row is stepped once more and is dead where it
          lies: the next prefill at ``start`` = 0 begins from zeros
          (``generate.init_state_pool``);
        * ``pos`` is clamped at ``max_seq`` as an idle row's is, and the row
          IS an idle row from the round after (its table row zeroed here,
          uploaded before the next dispatch).

        The overrun token is in no stream and in no count of tokens: a row
        of a round is landed only while its request still holds its slot.
        The round's expert load counts every row, as it does idle rows."""
        rnd, self._inflight = self._inflight, then
        if rnd is None:
            return
        live = [(i, r) for i, r in rnd.rows if self._slots[i] is r]
        overrun = len(rnd.rows) - len(live)
        if overrun:
            self._overrun_rows += overrun
            M.SERVE_OVERRUN_ROWS.inc(overrun)
        if not live:
            return  # nothing to emit: not waited for
        with tracing.annotate("serve.fetch"):
            # Waits for the step; the only per-step fetch (a dropless expert
            # model's two load numbers ride the same round trip).
            if rnd.load:
                tok, load = self._jax.device_get((rnd.tok, rnd.load[0]))
                self._expert_load += (1.0, *load)
            else:
                tok = np.asarray(rnd.tok)
        self._target_steps += 1
        with tracing.annotate("serve.emit"):
            for i, req in live:
                if req.cancelled.is_set():
                    self._release_slot(i, req)
                    with self._lock:
                        self._slots[i] = None
                    events.emit(events.SLOT_EVICTED,
                                trace_id=self._trace_id(req), slot=i,
                                reason="cancelled", tokens=req.emitted)
                    self._occupancy()
                    self._finish(req, "cancelled")
                    continue
                self._emit(req, int(tok[i]))
                self._retire_if_done(i, req, int(tok[i]))
            # The round's operands die here, under the name, and not at the
            # frame's exit: three [B] device arrays cost 1-3 ms to release.
            rnd.operands = None
        if self.shard > 1:
            self._observe_ici(live)
