"""The measurement behind ``generate.DROPLESS_FROM_TOKENS``: the expert FFN
alone at Mixtral-8x7B's widths (E 8, k 2, D 4096, F 14336, 4 stacked layers
scanned as ``generate._scan_groups`` scans them), under the capacity-padded
dispatch at inference sizing (capacity = N) and under the dropless one, at
each token count a serving program can hold. Run it on the chip:

    chiprun -- python3 scripts/expert_dispatch_crossing.py

It prints one line a (dispatch, N): ms a layer and the share of the larger
of the NEEDED operations (3 products x 2 x k x N x D x F) over the bf16 peak
and the expert weights' bytes over the HBM peak, and writes the table to
``chiprun_out/expert_dispatch_crossing.json``. Nothing in the program reads
this file's output: the crossing is a constant with this table beside it.

``--sweep held`` is the measurement behind what a HELD SHARE's products
are sized to (``moe.CAPACITY_MULTIPLE``, ``moe.MIN_CAPACITY``), at
nemotron-3-nano-30b's widths as one rank of eight holds them (23 expert
layers of 16 held experts of 2688 x 1920, top-6 of 128, bf16; a 1024-token
slice: 6144 assignment rows, 768 of them held under uniform routing, 48 an
expert):

- ``lax.ragged_dot`` alone, 768 live rows in 16 groups, handed rows {6144,
  3072, 1536, 768} x groups {368 (the stack folded, this layer's 16 live),
  16}, the up and the down product, and the copy of one layer's two leaves
  out of the stack;
- the two batched products [16, C, D] x [16, D, F] x [16, F, D] of a
  layer alone, its leaves read where they lie in the stack, at C = {64 ...
  1024} rows an expert;
- the expert layer whole (``moe.apply`` scanned over the 23 layers, router
  to weighed sum, shared expert included): the grouped product over every
  row (the parent's form, the ladder's last rung), each capacity alone, and
  the rule, with the share of layers a rung.

It writes ``chiprun_out/held_share_products.json``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9  # one v5e chip (benchmarks/peaks.json)


def _ms(run, *operands, repeats: int) -> float:
    """Median milliseconds of one call of a jitted ``run``."""
    for _ in range(3):
        run(*operands).block_until_ready()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        run(*operands).block_until_ready()
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3


def held_share(args) -> int:
    """``--sweep held``: see the module's docstring."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from oim_tpu.models import llama, moe

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    model = dataclasses.replace(llama.NEMOTRON_3_NANO_30B, expert_rank="0/8")
    if args.tiny:  # a rehearsal on the CPU: the same code at toy widths
        model = dataclasses.replace(llama.tiny_hybrid(expert_rank="0/4"),
                                    dtype=jnp.bfloat16)
    cfg = model.moe
    L, E, k = model.n_expert_layers, cfg.n_held, cfg.top_k
    D, F = model.dim, moe.stored_width(model.moe_intermediate_size)
    n = args.slice_tokens
    rows_all, live = k * n, k * n * E // cfg.n_experts
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 8)

    def leaf(key, shape, scale, dtype=jnp.bfloat16):
        return jax.jit(lambda k: (jax.random.normal(k, shape, dtype) * scale
                                  ).astype(dtype))(key)

    whole = {"w_up": leaf(keys[0], (L, E, D, F), D**-0.5),
             "w_down": leaf(keys[1], (L, E, F, D), F**-0.5)}
    table = {"device": dev.device_kind, "args": vars(args), "products": [],
             "layers": []}

    # -- the product alone: ``inner`` calls a program, each fed the last
    # one's first value so that none is hoisted or dropped ------------------
    inner = args.inner
    sizes = jnp.full((E,), live // E, jnp.int32)

    def looped(product):
        def run(rows, leaves):
            def body(i, carry):
                rows, acc = carry
                rows = rows.at[0, 0].set(acc.astype(rows.dtype))
                out = product(rows, leaves, i % L)
                return rows, out[0, 0].astype(jnp.float32) * 1e-3
            return lax.fori_loop(0, inner, body, (rows, jnp.float32(0)))[1]
        return jax.jit(run)

    def folded(name):
        def product(rows, leaves, i):
            stack = leaves[name]
            group_sizes = lax.dynamic_update_slice(
                jnp.zeros((L * E,), jnp.int32), sizes, (i * E,))
            return lax.ragged_dot(
                rows, stack.reshape((L * E,) + stack.shape[2:]), group_sizes)
        return product

    def layer_only(name):
        def product(rows, leaves, i):
            return lax.ragged_dot(rows, leaves[name], sizes)
        return product

    one_layer = {k: v[0] for k, v in whole.items()}
    for r in (rows_all, rows_all // 2, rows_all // 4, live):
        for name, width in (("w_up", D), ("w_down", F)):
            rows = leaf(keys[2], (r, width), 1.0)
            for groups, product, leaves in (
                    (L * E, folded(name), whole),
                    (E, layer_only(name), one_layer)):
                ms = _ms(looped(product), rows, leaves,
                         repeats=args.repeats) / inner
                row = {"rows": r, "groups": groups, "product": name,
                       "live_rows": live, "ms": ms}
                table["products"].append(row)
                print(json.dumps(row), flush=True)

    def copied(leaves, i):  # one layer's leaves out of the stack
        def body(j, acc):
            cut = {k: lax.dynamic_index_in_dim(v, (i + j) % L, keepdims=False)
                   for k, v in leaves.items()}
            # a pass that reads the copy, as a product would
            return acc + sum(jnp.sum(lax.optimization_barrier(v)[:, :8, :128]
                                     .astype(jnp.float32))
                             for v in cut.values())
        return lax.fori_loop(0, inner, body, jnp.float32(0))

    ms = _ms(jax.jit(copied), whole, jnp.int32(0), repeats=args.repeats) / inner
    nbytes = sum(v[0].size * v.dtype.itemsize for v in whole.values())
    table["copy"] = {"bytes": nbytes, "ms": ms,
                     "bytes_per_s": nbytes / (ms / 1e3)}
    print(json.dumps({"copy": table["copy"]}), flush=True)

    # the batched products [E, C, D] x [E, D, F] x [E, F, D] of one layer,
    # its leaves read where they lie; each call's result is the next one's
    # rows, so every expert's product is computed whole
    def batched(x, leaves):
        def body(i, x):
            y = moe._batched_ffn({k: v[i % L] for k, v in leaves.items()}, x)
            return y * jnp.asarray(1e-3, y.dtype)
        return lax.fori_loop(0, inner, body, x)[0, 0, 0]

    table["batched"] = []
    for c in (64, 128, 256, 384, 512, 768, 1024):
        x = leaf(keys[2], (E, c, D), 1.0)
        ms = _ms(jax.jit(batched), x, whole, repeats=args.repeats) / inner
        row = {"capacity": c, "rows": E * c, "products": "w_up, w_down",
               "ms": ms}
        table["batched"].append(row)
        print(json.dumps(row), flush=True)

    # -- the expert layer whole, scanned over the stack as the slice does ---
    shared = cfg.shared_dim or cfg.n_shared * model.moe_intermediate_size
    layers = {
        "router": leaf(keys[3], (L, D, cfg.n_experts), D**-0.5, jnp.float32),
        "shared": {"w_up": leaf(keys[4], (L, D, shared), D**-0.5),
                   "w_down": leaf(keys[5], (L, shared, D), shared**-0.5)},
        **whole}
    if cfg.scoring == "sigmoid":
        layers["bias"] = leaf(keys[6], (L, cfg.n_experts), 0.01, jnp.float32)
    x = leaf(keys[7], (1, n, D), 1.0)

    def slice_of_layers(layers, x):
        sliced, stack = moe.keep_stacked({"moe": layers})

        def body(x, inp):
            layer, i = inp
            out, load = moe.apply(moe.at_layer(layer, stack, i)["moe"], x,
                                  cfg, with_load=True)
            x = x + out
            x = x * lax.rsqrt(jnp.mean(jnp.square(x.astype(jnp.float32)),
                                       axis=-1, keepdims=True)).astype(x.dtype)
            return x, load[4:]
        x, rungs = lax.scan(body, x, (sliced, jnp.arange(L)))
        return jnp.concatenate([jnp.sum(rungs, axis=0),
                                jnp.sum(x.astype(jnp.float32))[None]])

    rule = moe.capacity_ladder
    forms = [("the grouped product over every row (the parent's, the last "
              "rung)", lambda n_tokens, cfg: ())]
    forms += [(f"capacity {c} alone", lambda n_tokens, cfg, c=c: (c,))
              for c in (128, 256, 512, 1024) if c <= n]
    forms.append(("the rule", rule))
    for name, ladder_of in forms:
        moe.capacity_ladder = ladder_of
        try:  # a new function a form: jit keeps a trace a function
            run = jax.jit(lambda layers, x: slice_of_layers(layers, x))
            ms = _ms(run, layers, x, repeats=args.repeats) / L
            rungs = [int(v) for v in run(layers, x)[:len(moe.RUNG_NAMES)]]
            ladder = ladder_of(n, cfg)
        finally:
            moe.capacity_ladder = rule
        row = {"form": name, "tokens": n, "ladder": ladder, "ms_a_layer": ms,
               "layers_a_rung": dict(zip(moe.RUNG_NAMES, rungs))}
        table["layers"].append(row)
        print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/held_share_products.json", "w") as f:
        json.dump(table, f, indent=1)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", choices=("crossing", "held"),
                    default="crossing")
    ap.add_argument("--slice-tokens", type=int, default=1024)
    ap.add_argument("--inner", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--tokens",
                    default="32,64,128,256,512,640,768,896,1024,2048")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--dim", type=int, default=4096)
    ap.add_argument("--mlp-dim", type=int, default=14336)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.sweep == "held":
        return held_share(args)

    import jax
    import jax.numpy as jnp

    from oim_tpu.models import generate as gen
    from oim_tpu.models import llama, moe

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    L, D, F, E, k = (args.layers, args.dim, args.mlp_dim, args.experts,
                     args.top_k)
    cfg = llama.Config(dim=D, mlp_dim=F, n_layers=L, n_experts=E,
                       moe_top_k=k, vocab=256, dtype=jnp.bfloat16)

    # One fused draw a leaf, in the leaf's own dtype: no float32 copy of a
    # 3.8 GB array beside it.
    def leaf(key, shape, scale, dtype):
        return jax.jit(lambda k: (jax.random.normal(k, shape, dtype) * scale
                                  ).astype(dtype))(key)

    keys = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    experts = {
        "router": leaf(keys[0], (L, D, E), D**-0.5, jnp.float32),
        "w_gate": leaf(keys[1], (L, E, D, F), D**-0.5, jnp.bfloat16),
        "w_up": leaf(keys[2], (L, E, D, F), D**-0.5, jnp.bfloat16),
        "w_down": leaf(keys[3], (L, E, F, D), F**-0.5, jnp.bfloat16),
    }
    params = {"layers": {"moe": experts}}

    def program(cfg):
        def body(x, inp):
            out, _ = moe.apply(inp[0]["moe"], x, cfg.moe)
            return x + out, jnp.float32(0)

        def run(params, x):
            x, _ = gen._scan_groups(body, x, params, cfg)
            return x

        return jax.jit(run)

    weights = 3 * E * D * F * 2  # bytes of a layer's expert leaves
    rows = []
    for n in [int(t) for t in args.tokens.split(",")]:
        x = (jax.random.normal(keys[4], (1, n, D), jnp.float32)
             ).astype(jnp.bfloat16)
        least = max(3 * 2 * k * n * D * F / PEAK_FLOPS, weights / PEAK_BYTES)
        for name, run_cfg in (
                ("padded", dataclasses.replace(
                    cfg, moe_capacity_factor=E / k)),
                ("dropless", dataclasses.replace(cfg, moe_dispatch="ragged"))):
            ms = _ms(program(run_cfg), params, x, repeats=args.repeats) / L
            row = {"tokens": n, "dispatch": name, "ms_a_layer": ms,
                   "least_ms_a_layer": least * 1e3,
                   "roofline_share": least * 1e3 / ms,
                   "bound": "flops" if least > weights / PEAK_BYTES
                   else "bytes"}
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/expert_dispatch_crossing.json", "w") as f:
        json.dump({"device": dev.device_kind, "args": vars(args),
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
