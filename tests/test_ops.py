"""Ring-0 tests for oim_tpu.ops: pallas kernels (interpret mode) vs the jnp
reference math."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oim_tpu.ops import (
    apply_rope,
    attention,
    flash_attention,
    mha_reference,
    layernorm,
    rmsnorm,
    rope_frequencies,
    softmax_cross_entropy,
)


def _qkv(b=2, t=256, h=4, hkv=None, d=64, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    hkv = hkv or h
    q = jnp.asarray(rng.randn(b, t, h, d), dtype)
    k = jnp.asarray(rng.randn(b, t, hkv, d), dtype)
    v = jnp.asarray(rng.randn(b, t, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, None, 64, 64, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_uneven_blocks_causal():
    # block_k > block_q: some k-blocks fully mask some q rows; exercises the
    # fully-masked-row path of the online softmax.
    q, k, v = _qkv(t=256)
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, None, 32, 128, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_gradients_flow():
    q, k, v = _qkv(b=1, t=64, h=2, d=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 32, 32, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bq,bk", [(64, 64), (32, 128), (128, 32)])
def test_flash_backward_matches_reference_vjp(causal, bq, bk):
    """The pallas bwd kernels (dQ, dK, dV) vs jax.vjp of the reference math,
    over uneven block shapes in both directions."""
    q, k, v = _qkv(b=2, t=128, h=2, d=32, seed=3)
    g = jnp.asarray(np.random.RandomState(4).randn(*q.shape), q.dtype)

    _, vjp_ref = jax.vjp(lambda q, k, v: mha_reference(q, k, v, causal), q, k, v)
    _, vjp_fl = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, causal, None, bq, bk, True),
        q, k, v,
    )
    for a, b, name in zip(vjp_fl(g), vjp_ref(g), "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4,
            err_msg=f"d{name} mismatch (causal={causal}, bq={bq}, bk={bk})",
        )


def test_flash_backward_decode_alignment():
    """tq < tk (bottom-right-aligned causal mask): grads must respect the
    q_offset the fwd kernel uses."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    g = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.float32)

    _, vjp_ref = jax.vjp(lambda q, k, v: mha_reference(q, k, v, True), q, k, v)
    _, vjp_fl = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, True, None, 32, 32, True),
        q, k, v,
    )
    for a, b, name in zip(vjp_fl(g), vjp_ref(g), "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4,
            err_msg=f"d{name} mismatch in decode alignment",
        )


def test_causal_decode_attends_full_cache():
    # tq=1 vs tk=64 (KV-cache decode): bottom-right-aligned mask must let the
    # single query attend to ALL keys, i.e. match non-causal attention.
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 1, 4, 32), jnp.float32)
    k = jnp.asarray(rng.randn(2, 64, 4, 32), jnp.float32)
    v = jnp.asarray(rng.randn(2, 64, 4, 32), jnp.float32)
    causal = mha_reference(q, k, v, causal=True)
    full = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(causal), np.asarray(full), atol=1e-6)


def test_flash_decode_shape_causal():
    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(1, 32, 2, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 2, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 2, 16), jnp.float32)
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, None, 32, 32, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_attention_dispatch_gqa():
    q, k, v = _qkv(h=8, hkv=2)
    ref = mha_reference(q, k, v, causal=True)
    out = attention(q, k, v, causal=True)  # CPU -> reference path
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_rmsnorm():
    x = jnp.asarray(np.random.RandomState(0).randn(4, 8, 16), jnp.float32)
    w = jnp.ones(16) * 2.0
    out = rmsnorm(x, w)
    expected = x / np.sqrt(np.mean(np.asarray(x) ** 2, -1, keepdims=True) + 1e-6) * 2.0
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)


def test_layernorm_zero_mean_unit_var():
    x = jnp.asarray(np.random.RandomState(0).randn(4, 16) * 3 + 5, jnp.float32)
    out = np.asarray(layernorm(x, jnp.ones(16), jnp.zeros(16)))
    np.testing.assert_allclose(out.mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(out.var(-1), 1.0, atol=1e-3)


def test_rope_preserves_norm_and_relative_phase():
    cos, sin = rope_frequencies(32, 128)
    x = jnp.asarray(np.random.RandomState(1).randn(2, 128, 4, 32), jnp.float32)
    out = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        atol=1e-4,
    )
    # Position 0 is the identity rotation.
    np.testing.assert_allclose(
        np.asarray(out[:, 0]), np.asarray(x[:, 0]), atol=1e-6
    )


def test_rope_explicit_positions():
    cos, sin = rope_frequencies(16, 64)
    x = jnp.asarray(np.random.RandomState(2).randn(1, 8, 2, 16), jnp.float32)
    default = apply_rope(x, cos, sin)
    explicit = apply_rope(x, cos, sin, positions=jnp.arange(8))
    np.testing.assert_allclose(np.asarray(default), np.asarray(explicit), atol=1e-6)


def test_cross_entropy_matches_naive():
    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.randn(6, 10), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 10, 6))
    loss = softmax_cross_entropy(logits, labels)
    p = jax.nn.softmax(logits, -1)
    naive = -np.mean(np.log(np.asarray(p)[np.arange(6), np.asarray(labels)]))
    np.testing.assert_allclose(float(loss), naive, atol=1e-5)


def test_cross_entropy_ignore_index():
    logits = jnp.zeros((4, 5), jnp.float32)
    labels = jnp.asarray([1, 2, -1, -1])
    loss = softmax_cross_entropy(logits, labels, ignore_index=-1)
    np.testing.assert_allclose(float(loss), np.log(5.0), atol=1e-5)


class TestChunkedCrossEntropy:
    """chunked_softmax_cross_entropy must equal the materialized-logits CE
    in value AND gradients (it is the same math, scanned over vocab)."""

    def _setup(self, dtype=jnp.float32, n=24, d=16, v=40):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(n, d), dtype)
        w = jnp.asarray(rng.randn(d, v) * 0.1, dtype)
        y = jnp.asarray(rng.randint(0, v, n), jnp.int32)
        return x, w, y

    def test_loss_matches_naive(self):
        from oim_tpu.ops.losses import (
            chunked_softmax_cross_entropy,
            softmax_cross_entropy,
        )

        x, w, y = self._setup()
        naive = float(softmax_cross_entropy(x @ w, y))
        # Includes chunk sizes that do NOT divide vocab=40 (the llama3
        # flagship regression: 16384 doesn't divide 128256) — the padded
        # tail chunk must be masked out of the logsumexp.
        for chunk in (8, 20, 40, 7, 23, 64):
            got = float(chunked_softmax_cross_entropy(x, w, y, chunk))
            np.testing.assert_allclose(got, naive, rtol=1e-6)

    def test_grads_match_with_nondivisible_chunk(self):
        from oim_tpu.ops.losses import (
            chunked_softmax_cross_entropy,
            softmax_cross_entropy,
        )

        x, w, y = self._setup()
        gx_n, gw_n = jax.grad(
            lambda x, w: softmax_cross_entropy(x @ w, y), argnums=(0, 1)
        )(x, w)
        gx_c, gw_c = jax.grad(
            lambda x, w: chunked_softmax_cross_entropy(x, w, y, 23),
            argnums=(0, 1),
        )(x, w)
        np.testing.assert_allclose(np.asarray(gx_c), np.asarray(gx_n), atol=1e-6)
        np.testing.assert_allclose(np.asarray(gw_c), np.asarray(gw_n), atol=1e-6)

    def test_grads_match_naive(self):
        from oim_tpu.ops.losses import (
            chunked_softmax_cross_entropy,
            softmax_cross_entropy,
        )

        x, w, y = self._setup()
        gx_n, gw_n = jax.grad(
            lambda x, w: softmax_cross_entropy(x @ w, y), argnums=(0, 1)
        )(x, w)
        gx_c, gw_c = jax.jit(jax.grad(
            lambda x, w: chunked_softmax_cross_entropy(x, w, y, 8),
            argnums=(0, 1),
        ))(x, w)
        np.testing.assert_allclose(np.asarray(gx_c), np.asarray(gx_n), atol=1e-6)
        np.testing.assert_allclose(np.asarray(gw_c), np.asarray(gw_n), atol=1e-6)

    def test_ignore_index_masking(self):
        from oim_tpu.ops.losses import (
            chunked_softmax_cross_entropy,
            softmax_cross_entropy,
        )

        x, w, y = self._setup()
        y = y.at[::3].set(-1)
        naive = float(softmax_cross_entropy(x @ w, y, ignore_index=-1))
        got = float(chunked_softmax_cross_entropy(x, w, y, 10, ignore_index=-1))
        np.testing.assert_allclose(got, naive, rtol=1e-6)

    def test_batched_shapes_and_llama_loss_path(self):
        import dataclasses

        from oim_tpu.models import llama

        cfg = llama.tiny()  # vocab 256
        ccfg = dataclasses.replace(cfg, vocab_chunk=64)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab)
        np.testing.assert_allclose(
            float(llama.loss_fn(params, tokens, ccfg)),
            float(llama.loss_fn(params, tokens, cfg)),
            rtol=1e-5,
        )
        g = jax.grad(lambda p: llama.loss_fn(p, tokens, cfg))(params)
        gc = jax.grad(lambda p: llama.loss_fn(p, tokens, ccfg))(params)
        np.testing.assert_allclose(
            np.asarray(gc["lm_head"]), np.asarray(g["lm_head"]), atol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(gc["embed"]), np.asarray(g["embed"]), atol=2e-5
        )


@pytest.mark.parametrize("hkv", [1, 2])
def test_flash_gqa_native_forward_and_backward(hkv):
    """GQA-native flash: kv heads ride the block index map (never expanded
    in HBM); fwd AND all three grads must match the reference, whose GQA
    path is an explicit jnp.repeat."""
    q, k, v = _qkv(b=2, t=128, h=4, hkv=hkv, d=32, seed=7)
    g = jnp.asarray(np.random.RandomState(8).randn(*q.shape), q.dtype)

    ref = mha_reference(q, k, v, True)
    out = flash_attention(q, k, v, True, None, 64, 64, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

    _, vjp_ref = jax.vjp(lambda q, k, v: mha_reference(q, k, v, True), q, k, v)
    _, vjp_fl = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, True, None, 64, 64, True),
        q, k, v,
    )
    for a, b, name in zip(vjp_fl(g), vjp_ref(g), "qkv"):
        assert a.shape == b.shape, f"d{name} shape {a.shape} vs {b.shape}"
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4,
            err_msg=f"d{name} mismatch (GQA hkv={hkv})",
        )


class TestAttentionWithLse:
    """The (out, lse) block interface ring attention merges across steps."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("hkv", [4, 2, 1])
    def test_ref_lse_matches_reference(self, causal, hkv):
        from oim_tpu.ops.attention import ref_attention_lse

        q, k, v = _qkv(t=64, h=4, hkv=hkv, seed=11)
        out, lse = ref_attention_lse(q, k, v, causal=causal)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        # lse must equal logsumexp of the (scaled, masked) score rows.
        scale = q.shape[-1] ** -0.5
        from oim_tpu.ops.attention import _expand_gqa

        ke, _ = _expand_gqa(q, k, v)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, ke) * scale
        if causal:
            t = q.shape[1]
            mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
            scores = jnp.where(mask[None, None], scores, -1e30)
        want = jax.nn.logsumexp(scores, axis=-1).transpose(0, 2, 1)  # [B,T,H]
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want), atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("hkv", [4, 2])
    def test_flash_lse_matches_ref_lse(self, causal, hkv):
        from oim_tpu.ops.attention import flash_attention_lse, ref_attention_lse

        q, k, v = _qkv(t=128, h=4, hkv=hkv, seed=12)
        out_f, lse_f = flash_attention_lse(q, k, v, causal, None, 64, 64, True)
        out_r, lse_r = ref_attention_lse(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r), atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse_f), np.asarray(lse_r), atol=2e-5)

    @pytest.mark.parametrize("hkv", [2, 4])
    def test_flash_lse_vjp_including_lse_cotangent(self, hkv):
        """Gradients must flow through BOTH outputs: a loss touching out and
        lse (exactly what the ring-step merge does) must match the jnp path."""
        from oim_tpu.ops.attention import flash_attention_lse, ref_attention_lse

        q, k, v = _qkv(b=1, t=64, h=4, hkv=hkv, d=32, seed=13)

        def loss(fn):
            def run(q, k, v):
                out, lse = fn(q, k, v)
                return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))
            return run

        g_fl = jax.grad(
            loss(lambda q, k, v: flash_attention_lse(q, k, v, True, None, 32, 32, True)),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_ref = jax.grad(
            loss(lambda q, k, v: ref_attention_lse(q, k, v, causal=True)),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b, name in zip(g_fl, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4,
                err_msg=f"d{name} mismatch with lse cotangent",
            )

    def test_two_block_merge_equals_full_attention(self):
        """Splitting K/V in two and merging (out, lse) pairs — the exact ring
        accumulation — must reproduce full attention."""
        from oim_tpu.ops.attention import ref_attention_lse

        q, k, v = _qkv(t=64, h=2, d=16, seed=14)
        half = 32
        o1, l1 = ref_attention_lse(q, k[:, :half], v[:, :half], causal=False)
        o2, l2 = ref_attention_lse(q, k[:, half:], v[:, half:], causal=False)
        lse = jnp.logaddexp(l1, l2)
        merged = (o1 * jnp.exp(l1 - lse)[..., None]
                  + o2 * jnp.exp(l2 - lse)[..., None])
        ref = mha_reference(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(merged), np.asarray(ref), atol=2e-5)


class TestVocabParallelCE:
    """ops/losses.py vocab_parallel_cross_entropy: CE with the LM head
    vocab-sharded over a mesh axis (the 1F1B pipeline's loss head) must
    match the dense CE exactly — value and gradients — including padding
    masks, with the full [.., V] logits never existing on any device."""

    def _sharded_fn(self, n=4):
        import functools

        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from oim_tpu.ops.losses import vocab_parallel_cross_entropy

        mesh = Mesh(np.array(jax.devices()[:n]), ("pipe",))

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(), P(None, "pipe"), P()), out_specs=P(),
            check_vma=False)
        def fn(y, w, labels):
            return vocab_parallel_cross_entropy(
                y, w, labels, "pipe", ignore_index=-1)

        return fn

    @pytest.mark.slow
    def test_matches_dense_value_and_grads(self):
        from oim_tpu.ops.losses import softmax_cross_entropy

        rng = np.random.RandomState(0)
        D, V, B, T = 16, 32, 2, 8
        y = jnp.asarray(rng.randn(B, T, D), jnp.float32)
        w = jnp.asarray(rng.randn(D, V) * 0.3, jnp.float32)
        labels = jnp.asarray(rng.randint(0, V, (B, T)), jnp.int32)
        labels = labels.at[0, :3].set(-1)  # padding mask
        fn = self._sharded_fn()
        loss = jax.jit(fn)(y, w, labels)
        ref = softmax_cross_entropy(y @ w, labels, ignore_index=-1)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)
        for arg in (0, 1):
            g = jax.grad(lambda *a: fn(*a, labels), argnums=arg)(y, w)
            gr = jax.grad(
                lambda *a: softmax_cross_entropy(
                    a[0] @ a[1], labels, ignore_index=-1),
                argnums=arg)(y, w)
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(gr), atol=1e-6)

    @pytest.mark.slow
    def test_extreme_logits_stay_finite(self):
        """The pmax shift must make the sharded softmax as stable as the
        dense logsumexp."""
        rng = np.random.RandomState(1)
        y = jnp.asarray(rng.randn(1, 4, 8) * 100.0, jnp.float32)
        w = jnp.asarray(rng.randn(8, 16) * 10.0, jnp.float32)
        labels = jnp.asarray(rng.randint(0, 16, (1, 4)), jnp.int32)
        loss = jax.jit(self._sharded_fn())(y, w, labels)
        assert np.isfinite(float(loss))


class TestZLoss:
    """z-loss (Megatron/PaLM logit-drift regularizer) across the three
    CE implementations: plain, chunked-vocab (custom VJP), and — via the
    pipeline suite's contract/equivalence gates — vocab-parallel."""

    def test_plain_matches_manual(self):
        from oim_tpu.ops.losses import softmax_cross_entropy

        rng = np.random.RandomState(0)
        logits = jnp.asarray(rng.randn(4, 7, 33), jnp.float32)
        labels = jnp.asarray(rng.randint(0, 33, (4, 7)), jnp.int32)
        base = softmax_cross_entropy(logits, labels)
        with_z = softmax_cross_entropy(logits, labels, z_loss=1e-2)
        logz = jax.nn.logsumexp(logits, axis=-1)
        np.testing.assert_allclose(
            float(with_z), float(base) + 1e-2 * float(jnp.mean(logz**2)),
            rtol=1e-6)

    def test_chunked_matches_plain_with_grads(self):
        """The chunked CE's custom VJP carries the logz cotangent (the
        z-loss path): value AND gradients must match the materialized
        implementation."""
        from oim_tpu.ops.losses import (
            chunked_softmax_cross_entropy,
            softmax_cross_entropy,
        )

        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(6, 16) * 0.5, jnp.float32)
        w = jnp.asarray(rng.randn(16, 50) * 0.3, jnp.float32)
        labels = jnp.asarray(rng.randint(0, 50, (6,)), jnp.int32)
        labels = labels.at[2].set(-1)  # ragged mask rides along

        def plain(x, w):
            return softmax_cross_entropy(
                x @ w, labels, ignore_index=-1, z_loss=1e-2)

        def chunked(x, w):
            return chunked_softmax_cross_entropy(
                x, w, labels, vocab_chunk=16, ignore_index=-1, z_loss=1e-2)

        np.testing.assert_allclose(
            float(chunked(x, w)), float(plain(x, w)), rtol=1e-5)
        gp = jax.grad(plain, argnums=(0, 1))(x, w)
        gc = jax.grad(chunked, argnums=(0, 1))(x, w)
        for a, b in zip(gp, gc):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5)

    def test_z_term_reported_separately(self):
        """return_z_term splits the regularizer from the CE so raw
        perplexity and logit drift stay observable: total == ce + term."""
        from oim_tpu.ops.losses import (
            chunked_softmax_cross_entropy,
            softmax_cross_entropy,
        )

        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(5, 16) * 0.5, jnp.float32)
        w = jnp.asarray(rng.randn(16, 48) * 0.3, jnp.float32)
        labels = jnp.asarray(rng.randint(0, 48, (5,)), jnp.int32)
        total, term = chunked_softmax_cross_entropy(
            x, w, labels, vocab_chunk=16, ignore_index=-1, z_loss=1e-2,
            return_z_term=True)
        ce = softmax_cross_entropy(x @ w, labels, ignore_index=-1)
        np.testing.assert_allclose(
            float(total) - float(term), float(ce), rtol=1e-5)
        assert float(term) > 0


# -- paged decode attention (ops/paged_attention.py) ------------------------

PAGE = 16
N_BLOCKS = 8  # S = 128 positions a row
S_PAGED = PAGE * N_BLOCKS


def _paged_case(name):
    """(tables [B, nb], pos [B]) of one scenario; page ids >= 1 are
    mapped, 0 is the scratch page."""
    full = np.arange(1, N_BLOCKS + 1, dtype=np.int32)

    def mapped(first, n):
        row = np.zeros(N_BLOCKS, np.int32)
        row[:n] = np.arange(first, first + n)
        return row

    if name == "ragged":  # a first position, both sides of a page edge, S - 1
        return (np.stack([mapped(1, 1), mapped(2, 1), mapped(3, 2), full + 4]),
                np.array([1, PAGE - 1, PAGE, S_PAGED - 1], np.int32))
    if name == "idle":  # all-zero tables at a stale and at the clamped pos
        return (np.stack([mapped(1, 3), mapped(0, 0), mapped(4, 5),
                          mapped(0, 0)]),
                np.array([40, 77, 70, S_PAGED], np.int32))
    if name == "past_table":  # pos >= S: every position of the table live
        return (np.stack([full, full + 8]),
                np.array([S_PAGED, S_PAGED + 5], np.int32))
    if name == "shared":  # two rows on the same three prefix pages
        a, b = mapped(1, 5), mapped(1, 5)
        a[3:5], b[3:5] = [9, 10], [11, 12]
        return np.stack([a, b]), np.array([70, 55], np.int32)
    raise AssertionError(name)


@pytest.mark.parametrize("kvh", [8, 2])  # 2: the --shard 4 member's view
@pytest.mark.parametrize(
    "case", ["ragged", "idle", "past_table", "shared"])
def test_paged_decode_kernel_matches_gather(case, kvh):
    """The Pallas kernel in interpret mode against gather +
    cache_attention on one pool. The kernel's pool has NaN wherever no
    live position lies (unmapped pages, the scratch page, past ``pos`` in
    a mapped page); the reference's has zeros there."""
    from oim_tpu.ops.paged_attention import _paged_decode, gather_attention

    tables, pos = _paged_case(case)
    B, g, hd, L, n_pages = len(pos), 4, 128, 2, 24
    rng = np.random.RandomState(len(case) + kvh)
    q = jnp.asarray(rng.randn(B, kvh * g, hd), jnp.bfloat16)
    shape = (L, n_pages, PAGE, kvh, hd)
    k, v = rng.randn(*shape), rng.randn(*shape)
    live = np.zeros((n_pages, PAGE), bool)
    for row, p in zip(tables, pos):
        if row[0]:
            n = min(p + 1, S_PAGED)
            live[row[np.arange(n) // PAGE], np.arange(n) % PAGE] = True
    assert not live[0].any() and live.any()
    clean = {n: jnp.asarray(np.where(live[None, :, :, None, None], x, 0.0),
                            jnp.bfloat16) for n, x in (("k", k), ("v", v))}
    dirty = {n: jnp.asarray(np.where(live[None, :, :, None, None], x, np.nan),
                            jnp.bfloat16) for n, x in (("k", k), ("v", v))}
    layer = jnp.int32(1)
    want = gather_attention(q[:, None], clean["k"], clean["v"], layer,
                            jnp.asarray(tables), jnp.asarray(pos))[:, 0]
    got = _paged_decode(q, dirty["k"], dirty["v"], layer,
                        jnp.asarray(tables), jnp.asarray(pos), pages=2,
                        interpret=True)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    idle = tables[:, 0] == 0
    assert np.isfinite(got).all()
    assert not got[idle].any()  # an idle row reads nothing
    # bf16 outputs of O(1): two roundings of the probabilities apart.
    np.testing.assert_allclose(got[~idle], want[~idle], atol=2e-2, rtol=2e-2)


PREFILL_T = 64  # query rows a call: four query blocks of 16


def _prefill_case(name):
    """(tables [B, nb], start [B], n_tokens [B]) of one scenario of the
    prefill kernel, at key blocks of two pages (32 positions)."""
    def mapped(pages):
        row = np.zeros(N_BLOCKS, np.int32)
        row[:len(pages)] = pages
        return row

    if name == "first":  # from 0; a block half pads, two of pads only
        return (np.stack([mapped([3, 4])]), np.array([0], np.int32),
                np.array([20], np.int32))
    if name == "shared":  # later slices over the same two prefix pages
        return (np.stack([mapped([1, 2, 5, 6, 7, 8, 9]),
                          mapped([1, 2, 10, 11, 12, 13])]),
                np.array([48, 32], np.int32), np.array([64, 50], np.int32))
    if name == "straddle":  # from mid page: the first key block straddles
        # the diagonal, and the last page holds 8 positions
        return (np.stack([mapped([2, 3, 4, 5, 6, 7])]),
                np.array([24], np.int32), np.array([64], np.int32))
    if name == "depths":  # rows at their own depths and lengths; the last
        # runs to the table's end
        return (np.stack([mapped([1, 2, 3, 4]), mapped([5, 6, 7, 8]),
                          mapped(np.arange(9, 17))]),
                np.array([0, 16, 64], np.int32),
                np.array([64, 33, 64], np.int32))
    raise AssertionError(name)


@pytest.mark.parametrize("kvh,g", [(2, 16), (8, 4)])
@pytest.mark.parametrize("case", ["first", "shared", "straddle", "depths"])
def test_paged_prefill_kernel_matches_gather(case, kvh, g):
    """The Pallas prefill kernel in interpret mode against gather +
    cache_attention on one pool, real rows only (the others are the
    caller's to discard: finite, and zeros where a whole query block is
    pads). The kernel's pool has NaN wherever no real row may read (other
    layers, unmapped pages, the scratch page, past a row's last real
    position in a mapped page); the reference's has zeros there."""
    from oim_tpu.ops.paged_attention import _paged_prefill, gather_attention

    tables, start, n_tokens = _prefill_case(case)
    B, T, hd, L, n_pages, layer = len(start), PREFILL_T, 128, 2, 24, 1
    rng = np.random.RandomState(len(case) + kvh)
    q = jnp.asarray(rng.randn(B, T, kvh * g, hd), jnp.bfloat16)
    shape = (L, n_pages, PAGE, kvh, hd)
    k, v = rng.randn(*shape), rng.randn(*shape)
    live = np.zeros((L, n_pages, PAGE), bool)
    for row, p, n in zip(tables, start, n_tokens):
        at = np.arange(min(p + n, S_PAGED))
        live[layer, row[at // PAGE], at % PAGE] = True
    assert not live[:, 0].any() and live.any()
    clean = {n: jnp.asarray(np.where(live[..., None, None], x, 0.0),
                            jnp.bfloat16) for n, x in (("k", k), ("v", v))}
    dirty = {n: jnp.asarray(np.where(live[..., None, None], x, np.nan),
                            jnp.bfloat16) for n, x in (("k", k), ("v", v))}
    args = (jnp.int32(layer), jnp.asarray(tables), jnp.asarray(start))
    want = gather_attention(q, clean["k"], clean["v"], *args)
    got = _paged_prefill(q, dirty["k"], dirty["v"], *args,
                         jnp.asarray(n_tokens), block_q=16, pages=2,
                         interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    real = np.arange(T)[None, :] < n_tokens[:, None]  # [B, T]
    # bf16 outputs of O(1): two roundings of the probabilities apart.
    np.testing.assert_allclose(got[real], want[real], atol=2e-2, rtol=2e-2)
    pads_only = np.arange(T)[None, :] >= -(-n_tokens[:, None] // 16) * 16
    assert not got[pads_only].any()  # such a block reads nothing


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_prompt_slices_through_the_prefill_kernel_match_the_gather(
        monkeypatch, family):
    """``prefill_into_pages`` end to end, a 45-token prompt in two 32-row
    slices (the second with 19 pad rows, from a traced ``start``): with the
    prefill kernel (interpret mode, query blocks of 16, so one of pads
    only) in the place of the gather, each slice's logits and the pool it
    leaves agree with the gather path's. The kernel's pool starts as NaN:
    whatever it reads past a real position shows."""
    from oim_tpu.models import generate as gen, llama
    from oim_tpu.ops import paged_attention as pa

    cfg = (llama.tiny(vocab=64, dim=32, n_layers=2) if family == "dense"
           else llama.tiny_hybrid(vocab=64))
    params = llama.init(jax.random.PRNGKey(0), cfg)
    page, nb, bucket = 8, 8, 32
    prompt = np.random.RandomState(5).randint(0, 64, 45)
    table = jnp.asarray(np.arange(1, nb + 1, dtype=np.int32))

    def through_kernel(q, pk, pv, layer, tables, pos, n_tokens=None):
        rows = q.shape[:1]
        return pa._paged_prefill(
            q, pk, pv, layer, tables, jnp.broadcast_to(pos, rows),
            jnp.broadcast_to(n_tokens, rows), block_q=16, pages=2,
            interpret=True)

    def run(fill):
        pool = {**jax.tree.map(lambda x: jnp.full_like(x, fill),
                               gen.init_page_pool(cfg, nb + 1, page)),
                **gen.init_state_pool(cfg, 1)}
        out = []
        for start in (0, bucket):
            piece = prompt[start:start + bucket]
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :len(piece)] = piece
            logits, pool = gen.prefill_into_pages(
                params, jnp.asarray(toks), jnp.int32(len(piece)), pool,
                table, jnp.int32(start), cfg, page)
            out.append(np.asarray(logits))
        real = np.asarray(table)[np.arange(len(prompt)) // page]
        at = np.arange(len(prompt)) % page
        return out, [np.asarray(pool[leaf])[:, real, at]
                     for leaf in ("k", "v")]

    want_logits, want_kv = run(0.0)
    monkeypatch.setattr(gen, "paged_attention", through_kernel)
    got_logits, got_kv = run(np.nan)
    for got, want in zip(got_logits + got_kv, want_logits + want_kv):
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


DISPATCH_SHAPES = {
    # name: (backend, T, head_dim, kv heads, page, pool dtype, the plan[,
    # query heads a kv head: 4])
    "cpu_step": ("cpu", 1, 128, 8, PAGE, "bfloat16", None),
    "step": ("tpu", 1, 128, 8, PAGE, "bfloat16", (1, 16)),
    "verify": ("tpu", 4, 128, 8, PAGE, "bfloat16", None),
    "cpu_slice": ("cpu", 1024, 128, 8, PAGE, "bfloat16", None),
    "slice": ("tpu", 1024, 128, 8, PAGE, "bfloat16", (512, 32)),
    # a --shard 4 member's 8 heads over 2
    "slice_of_a_member": ("tpu", 1024, 128, 2, PAGE, "bfloat16", (512, 32)),
    "small_bucket": ("tpu", 32, 128, 8, PAGE, "bfloat16", (32, 32)),
    # 28 heads over 4: the largest power of two under 2048 / 7 positions
    "seven_heads_a_group": ("tpu", 1024, 128, 4, PAGE, "bfloat16", (256, 32), 7),
    "narrow_head": ("tpu", 1024, 64, 8, PAGE, "bfloat16", None),
    "page_off_the_tiling": ("tpu", 1024, 128, 3, 4, "float32", None),
    "odd_heads_in_words": ("tpu", 1024, 128, 3, PAGE, "bfloat16", None),
}


@pytest.mark.parametrize("name", sorted(DISPATCH_SHAPES))
def test_paged_dispatch_reads_shapes_and_backend_only(monkeypatch, name):
    from oim_tpu.ops.paged_attention import _paged_plan, kernel_name

    backend, t, hd, kvh, page, dtype, want, *g = DISPATCH_SHAPES[name]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    q = jax.ShapeDtypeStruct((4, t, (g or [4])[0] * kvh, hd), jnp.bfloat16)
    pk = jax.ShapeDtypeStruct((2, 9, page, kvh, hd), jnp.dtype(dtype))
    tables = jax.ShapeDtypeStruct((4, 256), jnp.int32)
    assert _paged_plan(q, pk, tables) == want
    assert kernel_name(q, pk, tables) == (
        "jnp_gather" if want is None else
        "pallas_paged" if t == 1 else "pallas_paged_prefill")


# -- latent decode attention (ops/latent_attention.py) -----------------------


def _latent_case(name):
    if name == "all_idle":  # every table on the scratch page, stale positions
        return (np.zeros((3, N_BLOCKS), np.int32),
                np.array([0, 50, S_PAGED], np.int32))
    return _paged_case(name)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize(
    "case", ["ragged", "idle", "past_table", "shared", "all_idle"])
@pytest.mark.parametrize("span", [None, 2])
def test_latent_decode_kernel_matches_absorbed(case, dtype, layer, span):
    """The Pallas kernel over the latent pool in interpret mode against the
    ``jax.numpy`` absorbed sum (``_gathered_sum``: what ``_absorbed`` runs
    off a TPU). Blocks of two pages: ``ragged`` ends rows mid-page and
    mid-block, ``idle`` has idle rows between live ones, ``past_table`` a
    row at the table's full length. The kernel's pool has NaN wherever no
    live position lies (other layers, unmapped pages, the scratch page,
    past ``pos`` in a mapped page); the reference's has zeros there.
    ``span`` 2: a kernel call walks two table entries, so the calls' parts
    are merged over several passes, rows running out at different ones."""
    from oim_tpu.ops import latent_attention as la

    d = la.Dims(heads=8, rank=128, nope=32, rope=16, v=32)
    tables, pos = _latent_case(case)
    B, L, n_pages = len(pos), 3, 24
    rng = np.random.RandomState(len(case) + layer)
    dtype = jnp.dtype(dtype)
    qq = jnp.asarray(rng.randn(B, d.heads, d.width) * 0.5, dtype)
    entries = rng.randn(L, n_pages, PAGE, d.width)
    entries[..., d.rank + d.rope:] = 0.0  # the entry's pad
    live = np.zeros((L, n_pages, PAGE), bool)
    for row, p in zip(tables, pos):
        if row[0]:
            n = min(p + 1, S_PAGED)
            live[layer, row[np.arange(n) // PAGE], np.arange(n) % PAGE] = True
    assert not live[:, 0].any() and live.any() == (case != "all_idle")
    clean = jnp.asarray(np.where(live[..., None], entries, 0.0), dtype)
    dirty = jnp.asarray(np.where(live[..., None], entries, np.nan), dtype)
    args = (jnp.int32(layer), jnp.asarray(tables), jnp.asarray(pos), d)
    want = la._gathered_sum(qq, clean, *args)
    got = la._latent_decode(qq, dirty, *args, pages=2, interpret=True,
                            span=span)
    assert got.shape == (B, d.heads, d.rank) and got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    idle = tables[:, 0] == 0
    assert np.isfinite(got).all()
    assert not got[idle].any()  # an idle row reads nothing
    # Outputs of O(1). bf16: two roundings of the probabilities apart;
    # f32: the blocks' sums in another order.
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got[~idle], want[~idle], atol=tol, rtol=tol)
