"""The table behind ``ops/paged_attention.py``'s prefill constants (PR 43,
PERF.md section 6):

    chiprun -- python3 scripts/paged_prefill_on_chip.py
    chiprun -- python3 scripts/paged_prefill_on_chip.py --shapes nemotron --depths 2048

The Pallas prefill kernel ALONE at the four GQA serving cells' shapes (one
slot's prompt slice of T rows at a depth, bfloat16, page 16, the cell's own
table length) against ``gather_attention``, over a sweep of the query block
and the key block: milliseconds a call (a loop of calls inside one program,
a layer of the pool a call) and the largest difference from the float32
reference (``cache_attention`` on the gathered cache in float32 at the
highest precision). The pool holds NaN wherever no real row may read, so a
read past a row's position shows as a NaN difference. ``--tiny`` rehearses
on the CPU in interpret mode at test sizes."""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PAGE = 16
# name: (query heads, kv heads, table entries a slot, slice lengths): the
# cells' member-local attention shapes (benchmarks/configs/*.json).
SHAPES = {
    "nemotron": (32, 2, 512, (1024, 512)),       # .agentbatch: 1024 slices
    "solar": (64, 8, 512, (1024, 512)),          # .agentbatch64: 1024 slices
    "mixtral": (32, 8, 128, (1024, 512, 128)),   # .batch: buckets 128-1024
    "mistral": (32, 8, 256, (4096, 512, 32)),    # .chat: buckets 32-4096
}
TINY = {"tiny": (8, 2, 8, (64,))}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=None)
    ap.add_argument("--depths", nargs="*", type=int, default=[0, 2048, 7168])
    ap.add_argument("--block-q", nargs="*", type=int,
                    default=[128, 256, 512])
    ap.add_argument("--block-k", nargs="*", type=int,
                    default=[128, 256, 512])
    ap.add_argument("--lengths", nargs="*", type=int, default=None,
                    help="slice lengths T (default: each shape's own)")
    ap.add_argument("--max-head-rows", type=int, default=16384,
                    help="skip query blocks of more rows over all heads")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default="chiprun_out/paged_prefill.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from oim_tpu.ops import paged_attention as pa

    shapes = TINY if args.tiny else SHAPES
    if args.tiny:
        args.depths, args.block_q, args.block_k, args.reps = (
            [0, 40], [16, 32], [32], 2)
    hd, layers = 128, 2
    rng = np.random.default_rng(args.seed)
    rows = []

    def looped(fn):
        """A program of ``reps`` calls of ``fn(layer, *operands)``, a layer
        of the pool a call; compiled once whatever the depth."""
        def loop(*operands):
            def body(i, acc):
                return acc + fn(lax.rem(i, layers), *operands).astype(
                    jnp.float32)
            return lax.fori_loop(0, args.reps, body,
                                 jnp.zeros(operands[0].shape, jnp.float32))
        return jax.jit(loop)

    def timed(run, *operands):
        jax.block_until_ready(run(*operands))  # compile + warm
        t0 = time.perf_counter()
        jax.block_until_ready(run(*operands))
        return (time.perf_counter() - t0) / args.reps * 1e3

    for name in args.shapes or sorted(shapes):
        H, kvh, nb, lengths = shapes[name]
        S = nb * PAGE
        n_pages = nb + 1
        tables = jnp.asarray(1 + np.arange(nb, dtype=np.int32))[None]
        for T in args.lengths or lengths:
            n_tokens = jnp.full((1,), T, jnp.int32)
            cases = {}
            for depth in args.depths:
                if depth + T > S:
                    continue
                q = jnp.asarray(rng.standard_normal((1, T, H, hd)),
                                jnp.bfloat16)
                live = np.zeros((n_pages, PAGE), bool)
                live.reshape(-1)[PAGE:PAGE + depth + T] = True
                pool = {}
                for leaf in ("k", "v"):
                    x = rng.standard_normal(
                        (layers, n_pages, PAGE, kvh, hd)).astype(np.float32)
                    x[:, ~live] = np.nan
                    pool[leaf] = jnp.asarray(x, jnp.bfloat16)
                cases[depth] = (q, pool, jnp.full((1,), depth, jnp.int32))
            one = jax.jit(lambda q, k, v, pos: pa.gather_attention(
                q, k, v, jnp.int32(1), tables, pos))
            many = looped(lambda l, q, k, v, pos: pa.gather_attention(
                q, k, v, l, tables, pos))
            want = {}
            for depth, (q, pool, pos) in cases.items():
                clean = {leaf: jnp.nan_to_num(x) for leaf, x in pool.items()}
                with jax.default_matmul_precision("highest"):
                    want[depth] = np.asarray(one(
                        q.astype(jnp.float32), clean["k"].astype(jnp.float32),
                        clean["v"].astype(jnp.float32), pos))
                got = np.asarray(one(q, clean["k"], clean["v"], pos),
                                 np.float32)
                row = {"shape": name, "heads": H, "kv_heads": kvh, "T": T,
                       "depth": depth, "kernel": "jnp_gather",
                       "ms": timed(many, q, clean["k"], clean["v"], pos),
                       "max_diff": float(np.abs(got - want[depth]).max())}
                rows.append(row)
                print("PREFILL", json.dumps(row), flush=True)
            for block_q in sorted({min(b, T) for b in args.block_q}):
                for block_k in args.block_k:
                    pages = max(block_k // PAGE, 1)
                    if (T % block_q or nb % pages
                            or H * block_q > args.max_head_rows):
                        continue
                    kernel = functools.partial(
                        pa._paged_prefill, tables=tables, n_tokens=n_tokens,
                        block_q=block_q, pages=pages, interpret=args.tiny)
                    one = jax.jit(lambda q, k, v, pos: kernel(
                        q, k, v, layer=jnp.int32(1), pos=pos))
                    many = looped(lambda l, q, k, v, pos: kernel(
                        q, k, v, layer=l, pos=pos))
                    for depth, (q, pool, pos) in cases.items():
                        row = {"shape": name, "heads": H, "kv_heads": kvh,
                               "T": T, "depth": depth,
                               "kernel": "pallas_paged_prefill",
                               "block_q": block_q, "block_k": pages * PAGE}
                        try:
                            got = np.asarray(
                                one(q, pool["k"], pool["v"], pos), np.float32)
                            row["max_diff"] = float(
                                np.abs(got - want[depth]).max())
                            row["ms"] = timed(
                                many, q, pool["k"], pool["v"], pos)
                        except Exception as e:  # noqa: BLE001 - a sweep
                            # point the compiler refuses is a finding
                            row.update(ms=None, max_diff=None,
                                       error=str(e)[-300:])
                        rows.append(row)
                        print("PREFILL", json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    bad = [r for r in rows if r["kernel"] != "jnp_gather"
           and not (r.get("max_diff") is not None and r["max_diff"] < 0.05)]
    print("PREFILL rows", len(rows), "refused or apart", len(bad))
    return 1 if bad and args.tiny else 0


if __name__ == "__main__":
    sys.exit(main())
