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

``--sweep whole`` is the measurement behind what a WHOLE SET's products are
sized to (``moe.WHOLE_MULTIPLE``, ``moe.WHOLE_FROM_ROWS``,
``moe.WHOLE_UP_TO_ROWS``), at the widths of the two models that hold every
expert: joyai-llm-flash (E 256, k 8, D 2048, F 768, sigmoid router, a shared
expert) and mixtral-8x7b (E 8, k 2, D 4096, F 14336), two stacked layers
each, bf16:

- the three batched products [E, C, D] x [E, D, F] of a layer alone, its
  leaves read where they lie in the stack, at C = {64 ... 1024} rows an
  expert;
- the three grouped products alone over the k x N rows of a call of N tokens
  routed uniformly at random, the stack folded into the groups;
- the expert layer whole (``moe.apply`` scanned over the layers, router to
  weighed sum) at each N: the grouped product over every row (the parent's
  form), the rule's capacity (the batched products and, where an expert
  got more rows, those rows through the grouped product) and twice it, with
  the layers a rung; Mixtral's also capacity-padded with room for every
  token (what it runs under ``generate.DROPLESS_FROM_TOKENS``). Each under
  a router that sees independent tokens (fullest expert about 1.1-1.7 times
  the mean) and, ``--lean``, under tokens that share a direction, which
  skews it as the cells' seeded models are skewed.

It writes ``chiprun_out/whole_set_products.json``.

``--sweep skew --workload <cell>`` runs that cell of the benchmark
(``benchmarks/run.py``, ``--seconds`` of window) with a host callback in
``moe._routed_products`` and prints, for each token count of a prefill call,
how the fullest expert's rows over the rows uniform routing sends one (k x N /
E) were distributed over the run's expert-layer calls: the reading the
ladders' multiples are set from. It writes ``chiprun_out/routing_skew.<cell>.json``.
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


def _leaf(key, shape, scale, dtype=None):
    """One fused draw a leaf, in the leaf's own dtype (bfloat16 unless
    named): no float32 copy of a multi-GB array beside it."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.bfloat16
    return jax.jit(lambda k: (jax.random.normal(k, shape, dtype) * scale
                              ).astype(dtype))(key)


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

    whole = {"w_up": _leaf(keys[0], (L, E, D, F), D**-0.5),
             "w_down": _leaf(keys[1], (L, E, F, D), F**-0.5)}
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
            rows = _leaf(keys[2], (r, width), 1.0)
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
        x = _leaf(keys[2], (E, c, D), 1.0)
        ms = _ms(jax.jit(batched), x, whole, repeats=args.repeats) / inner
        row = {"capacity": c, "rows": E * c, "products": "w_up, w_down",
               "ms": ms}
        table["batched"].append(row)
        print(json.dumps(row), flush=True)

    # -- the expert layer whole, scanned over the stack as the slice does ---
    shared = cfg.shared_dim or cfg.n_shared * model.moe_intermediate_size
    layers = {
        "router": _leaf(keys[3], (L, D, cfg.n_experts), D**-0.5, jnp.float32),
        "shared": {"w_up": _leaf(keys[4], (L, D, shared), D**-0.5),
                   "w_down": _leaf(keys[5], (L, shared, D), shared**-0.5)},
        **whole}
    if cfg.scoring == "sigmoid":
        layers["bias"] = _leaf(keys[6], (L, cfg.n_experts), 0.01, jnp.float32)
    x = _leaf(keys[7], (1, n, D), 1.0)

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


WHOLE_MODELS = {
    # name: (E, k, D, F, scoring, n_shared, token counts, capacities)
    "joyai-llm-flash": (256, 8, 2048, 768, "sigmoid", 1,
                        (32, 64, 128, 256, 512, 1024, 2048),
                        (64, 128, 256, 512, 1024)),
    "mixtral-8x7b": (8, 2, 4096, 14336, "softmax", 0,
                     (256, 512, 640, 1024, 2048),
                     (128, 256, 384, 512, 1024, 2048)),
}


def whole_set(args) -> int:
    """``--sweep whole``: see the module's docstring."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from oim_tpu.models import moe

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    table = {"device": dev.device_kind, "args": vars(args), "models": {}}
    L, inner = 2, args.inner
    for name, (E, k, D, F, scoring, n_shared, tokens, caps) in \
            WHOLE_MODELS.items():
        if args.tiny:  # a rehearsal on the CPU: the same code at toy widths
            E, D, F = min(E, 16), 64, 128
            tokens, caps = tokens[:3], caps[:2]
        cfg = moe.MoEConfig(n_experts=E, top_k=k, dispatch="ragged",
                            scoring=scoring, n_shared=n_shared,
                            routed_scale=2.5 if scoring == "sigmoid" else 1.0)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 12)

        whole = {"w_gate": _leaf(keys[0], (L, E, D, F), D**-0.5),
                 "w_up": _leaf(keys[1], (L, E, D, F), D**-0.5),
                 "w_down": _leaf(keys[2], (L, E, F, D), F**-0.5)}
        nbytes = sum(v[0].size * v.dtype.itemsize for v in whole.values())
        rows_out = table["models"][name] = {
            "experts": E, "top_k": k, "dim": D, "mlp_dim": F,
            "layer_bytes": nbytes, "bytes_ms": nbytes / PEAK_BYTES * 1e3,
            "batched": [], "grouped": [], "layers": []}
        print(json.dumps({"model": name, "layer_bytes": nbytes,
                          "bytes_ms": rows_out["bytes_ms"]}), flush=True)

        # the batched set of one layer, its leaves read where they lie; each
        # call's result is the next one's rows
        def batched(x, leaves):
            def body(i, x):
                y = moe._batched_ffn(
                    {k: v[i % L] for k, v in leaves.items()}, x)
                return y * jnp.asarray(1e-3, y.dtype)
            return lax.fori_loop(0, inner, body, x)[0, 0, 0]

        for c in caps:
            x = _leaf(keys[3], (E, c, D), 1.0)
            ms = _ms(jax.jit(batched), x, whole, repeats=args.repeats) / inner
            flops = 3 * 2 * E * c * D * F
            row = {"capacity": c, "rows": E * c, "ms": ms,
                   "flops_ms": flops / PEAK_FLOPS * 1e3}
            rows_out["batched"].append(row)
            print(json.dumps({"model": name, "batched": row}), flush=True)

        # the grouped set alone, k x N rows routed uniformly at random
        def grouped(rows, leaves, sizes):
            def body(i, rows):
                y = moe.grouped_ffn({"stack": (leaves, i % L)}, rows, sizes)
                return y * jnp.asarray(1e-3, y.dtype)
            return lax.fori_loop(0, inner, body, rows)[0, 0]

        for n in tokens:
            pick = jax.random.randint(keys[4], (k * n,), 0, E)
            sizes = jnp.zeros((E,), jnp.int32).at[pick].add(1)
            rows = _leaf(keys[5], (k * n, D), 1.0)
            ms = _ms(jax.jit(grouped), rows, whole, sizes,
                     repeats=args.repeats) / inner
            row = {"tokens": n, "rows": k * n,
                   "groups_with_rows": int(jnp.sum(sizes > 0)),
                   "fullest": int(jnp.max(sizes)), "ms": ms}
            rows_out["grouped"].append(row)
            print(json.dumps({"model": name, "grouped": row}), flush=True)

        # the expert layer whole, scanned over the stack as a slice does
        layers = {"router": _leaf(keys[6], (L, D, E), D**-0.5, jnp.float32),
                  **whole}
        if scoring == "sigmoid":
            layers["bias"] = _leaf(keys[7], (L, E), 0.01, jnp.float32)
        if n_shared:
            layers["shared"] = {
                "w_gate": _leaf(keys[8], (L, D, F * n_shared), D**-0.5),
                "w_up": _leaf(keys[9], (L, D, F * n_shared), D**-0.5),
                "w_down": _leaf(keys[10], (L, F * n_shared, D), F**-0.5)}

        def slice_of_layers(layers, x, run_cfg):
            sliced, stack = moe.keep_stacked({"moe": layers})
            if run_cfg.dispatch != "ragged":
                sliced, stack = {"moe": layers}, {}

            def body(x, inp):
                layer, i = inp
                out, load = moe.apply(moe.at_layer(layer, stack, i)["moe"], x,
                                      run_cfg, with_load=True)
                x = x + out
                x = x * lax.rsqrt(jnp.mean(jnp.square(x.astype(
                    jnp.float32)), axis=-1, keepdims=True)).astype(x.dtype)
                return x, load[3:]
            x, loads = lax.scan(body, x, (sliced, jnp.arange(L)))
            return jnp.concatenate([jnp.sum(loads, axis=0),
                                    jnp.sum(x.astype(jnp.float32))[None]])

        rule = moe.capacity_ladder
        shared_direction = _leaf(keys[3], (1, 1, D), 1.0)
        if args.tokens:  # a narrower pass of the layer whole
            tokens = [int(t) for t in args.tokens.split(",") if int(t) in tokens]
        leans = sorted({0.0, *(float(v) for v in args.lean.split(","))})
        for n, lean in [(n, lean) for n in tokens for lean in leans]:
            x = _leaf(keys[11], (1, n, D), 1.0) + jnp.asarray(
                lean, jnp.bfloat16) * shared_direction
            ladder = rule(n, cfg)
            last = ladder[-1] if ladder else min(
                n, -(-moe.WHOLE_MULTIPLE * k * n // E // moe.ROW_TILE)
                * moe.ROW_TILE)
            forms = [("grouped", lambda n_tokens, cfg: (), cfg)]
            forms += [(f"capacity {c}", lambda n_tokens, cfg, c=c: (c,), cfg)
                      for c in sorted({last, min(2 * last, n)})]
            if scoring == "softmax" and n <= 1024:  # room for every token
                forms.append(("padded", rule, dataclasses.replace(
                    cfg, dispatch="gather", capacity_factor=E / k)))
            for form, ladder_of, run_cfg in forms:
                moe.capacity_ladder = ladder_of
                try:  # a new function a form: jit keeps a trace a function
                    run = jax.jit(lambda layers, x, run_cfg=run_cfg:
                                  slice_of_layers(layers, x, run_cfg))
                    ms = _ms(run, layers, x, repeats=args.repeats) / L
                    out = [float(v) for v in run(layers, x)]
                    took = (ladder_of(n, run_cfg)
                            if run_cfg.dispatch == "ragged" else ())
                finally:
                    moe.capacity_ladder = rule
                row = {"tokens": n, "lean": lean, "form": form,
                       "ladder": took,
                       "ms_a_layer": ms,
                       "fullest_over_mean_mean": out[0] / L,
                       "layers_a_rung": dict(zip(moe.RUNG_NAMES, out[1:-1]))}
                rows_out["layers"].append(row)
                print(json.dumps({"model": name, "layer": row}), flush=True)
        del whole, layers
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/whole_set_products.json", "w") as f:
        json.dump(table, f, indent=1)
    return 0


def routing_skew(args) -> int:
    """``--sweep skew``: see the module's docstring."""
    import collections

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import run
    from oim_tpu.cli import common as cli
    from oim_tpu.models import moe

    # A host callback's operands land on a CPU device: the run names the
    # TPU alone (``init_jax``), so name the CPU behind it, here and not in
    # the program.
    init = cli.init_jax
    cli.init_jax = lambda platform="": init(
        "tpu,cpu" if platform == "tpu" else platform)

    # (tokens of a call, rows uniform routing sends an expert) -> each
    # call's fullest expert's rows
    seen = collections.defaultdict(list)
    products = moe._routed_products

    def recorded(params, tokens, flat, order, counts, cfg):
        n = order.shape[0] // cfg.top_k
        if n >= args.from_tokens:  # static: a decode step calls nothing
            key = (n, cfg.top_k * n / cfg.n_experts)
            jax.debug.callback(
                lambda fullest, key=key: seen[key].append(int(fullest)),
                jnp.max(counts))
        return products(params, tokens, flat, order, counts, cfg)

    moe._routed_products = recorded
    code = run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "0"],
                    **({"platform": "cpu", "root": args.root}
                       if args.tiny else {}))
    table = {"workload": args.workload, "seed": args.seed, "calls": {}}
    for (n, expected), fullest in sorted(seen.items()):
        over = np.asarray(fullest, np.float64) / expected
        row = {"expected_rows": expected, "calls": len(fullest),
               "fullest_over_expected": {
                   f"p{q}": round(float(np.percentile(over, q)), 3)
                   for q in (5, 50, 80, 90, 95, 99, 100)},
               "share_up_to_multiple": {
                   str(m): round(float(np.mean(over <= m)), 4)
                   for m in (1.25, 1.5, 2, 3, 4, 5, 6, 8, 10)}}
        table["calls"][str(n)] = row
        print(json.dumps({"tokens": n, **row}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/routing_skew.{args.workload}.json", "w") as f:
        json.dump(table, f, indent=1)
    return code


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", choices=("crossing", "held", "whole", "skew"),
                    default="crossing")
    ap.add_argument("--workload", default="joyai-llm-flash.longctx")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--from-tokens", type=int, default=64)
    ap.add_argument("--lean", default="1.0",
                    help="--sweep whole: how far every token leans in one "
                    "shared direction (comma-separated; 0 is always run)")
    ap.add_argument("--root", help="--tiny: a benchmark root of toy cells")
    ap.add_argument("--slice-tokens", type=int, default=1024)
    ap.add_argument("--inner", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--tokens", help="comma-separated; default: the "
                    "sweep's own list")
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
    if args.sweep == "whole":
        return whole_set(args)
    if args.sweep == "skew":
        return routing_skew(args)

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

    keys = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    experts = {
        "router": _leaf(keys[0], (L, D, E), D**-0.5, jnp.float32),
        "w_gate": _leaf(keys[1], (L, E, D, F), D**-0.5, jnp.bfloat16),
        "w_up": _leaf(keys[2], (L, E, D, F), D**-0.5, jnp.bfloat16),
        "w_down": _leaf(keys[3], (L, E, F, D), F**-0.5, jnp.bfloat16),
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
    for n in [int(t) for t in (
            args.tokens or "32,64,128,256,512,640,768,896,1024,2048").split(",")]:
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
