#!/usr/bin/env python3
"""Capacity-factor QUALITY experiment on the real chip (VERDICT r4 weak
#4): the +19% step-speed knob (cf 1.25 -> 1.0, BASELINE.md r4 row) is
documented as a quality trade-off that nothing measured — this trains
the MoE flagship at both capacities TO EQUAL TOKENS and records final
held-out loss, dropped-assignment fraction, and step time.

Data must be LEARNABLE for the comparison to mean anything (uniform
random tokens pin every config at ln(vocab)): sequences are random
concatenations of a fixed bank of random template segments, so the model
learns the templates and capacity drops show up as lost learning.
Held-out eval uses fresh concatenations of the SAME bank
(in-distribution).

One subprocess per config, in turn (sweep_moe.py's rule: the parent stays
off JAX, each child owns the chip for its run)."""

from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

VOCAB = 32768
SEQ = 2048
BATCH = 2
STEPS = 300
CHUNK = 25
EVAL_BATCHES = 16
TEMPLATES = 64
TEMPLATE_LEN = 128

RUNS = [
    ("gather cf1.25 (default)", dict(moe_capacity_factor=1.25)),
    ("gather cf1.0  (fast)", dict(moe_capacity_factor=1.0)),
]


def template_tokens(rng: np.random.RandomState, n_seqs: int) -> np.ndarray:
    """[n, SEQ+1] int32: each row a random concatenation of template
    segments from the fixed bank (bank drawn from a child seed so train
    and eval share it)."""
    bank = np.random.RandomState(1234).randint(
        0, VOCAB, (TEMPLATES, TEMPLATE_LEN), dtype=np.int32)
    per_row = (SEQ + 1 + TEMPLATE_LEN - 1) // TEMPLATE_LEN
    picks = rng.randint(0, TEMPLATES, (n_seqs, per_row))
    rows = bank[picks].reshape(n_seqs, -1)[:, :SEQ + 1]
    return np.ascontiguousarray(rows)


def run_one(index: int) -> None:
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax

    from oim_tpu.models import llama
    from oim_tpu.train.state import make_optimizer
    from oim_tpu.train.trainer import peak_flops_per_device

    name, over = RUNS[index]
    cfg = dataclasses.replace(
        llama.Config(
            vocab=VOCAB, dim=2048, n_layers=8, n_heads=16, n_kv_heads=8,
            head_dim=128, mlp_dim=8192, max_seq=8192,
            n_experts=4, moe_top_k=2, moe_dispatch="gather",
            remat=True, remat_policy="dots_with_no_batch_dims",
        ),
        **over,
    )
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tx = make_optimizer(lr=3e-4, warmup_steps=20, total_steps=STEPS)
    opt_state = tx.init(params)

    train = jnp.asarray(template_tokens(
        np.random.RandomState(10), STEPS * BATCH
    ).reshape(STEPS, BATCH, SEQ + 1))
    evalb = jnp.asarray(template_tokens(
        np.random.RandomState(20), EVAL_BATCHES * BATCH
    ).reshape(EVAL_BATCHES, BATCH, SEQ + 1))

    def one_step(start, i, carry):
        params, opt_state, _ = carry
        toks = lax.dynamic_index_in_dim(train, start + i, keepdims=False)
        loss, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(p, toks, cfg))(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt, loss

    @jax.jit
    def eval_all(params):
        def body(i, acc):
            loss_a, drop_a = acc
            toks = lax.dynamic_index_in_dim(evalb, i, keepdims=False)
            loss, stats = llama.loss_and_stats(params, toks, cfg)
            return loss_a + loss, drop_a + stats["moe_drop_frac"]

        loss, drop = lax.fori_loop(
            0, EVAL_BATCHES, body,
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)))
        return loss / EVAL_BATCHES, drop / EVAL_BATCHES

    # Short chains with a completion fence each: the run is chunked (ONE
    # compile — the chunk start is a traced operand) and each chunk's
    # loss fetch bounds the in-flight work.
    chain = jax.jit(
        lambda p, o, start: lax.fori_loop(
            0, CHUNK, lambda i, c: one_step(start, i, c),
            (p, o, jnp.zeros((), jnp.float32))),
        donate_argnums=(0, 1))

    train_loss = float("nan")
    t0 = None
    for c in range(STEPS // CHUNK):
        params, opt_state, loss = chain(
            params, opt_state, jnp.int32(c * CHUNK))
        train_loss = float(loss)  # completion fence
        if c == 0:
            _ = float(eval_all(params)[0])  # compile the eval too
            t0 = time.monotonic()  # exclude the compile chunk
    dt = (time.monotonic() - t0) / (STEPS - CHUNK)
    eval_loss, eval_drop = (float(v) for v in eval_all(params))

    flops = llama.num_flops_per_token(cfg, SEQ) * BATCH * SEQ
    peak = peak_flops_per_device()
    mfu = flops / dt / peak
    print(
        f"{name:24s} tokens={STEPS * BATCH * SEQ} "
        f"eval_loss={eval_loss:.4f} train_loss={train_loss:.4f} "
        f"drop_frac={eval_drop:.4f} step={dt:.4f}s mfu={mfu:.4f}",
        flush=True,
    )


def main():
    import subprocess

    for i, (name, _) in enumerate(RUNS):
        proc = subprocess.run(
            [sys.executable, __file__, str(i)],
            capture_output=True, text=True, timeout=3000,
        )
        rows = [ln for ln in proc.stdout.splitlines() if "eval_loss=" in ln]
        if proc.returncode == 0 and rows:
            print(rows[-1], flush=True)
        else:
            tail = (proc.stderr or proc.stdout).strip().splitlines()[-4:]
            print(f"{name:24s} FAILED: {' | '.join(tail)}", flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1:
        run_one(int(sys.argv[1]))
    else:
        main()
