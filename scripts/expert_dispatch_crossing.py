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


def main() -> int:
    ap = argparse.ArgumentParser()
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
            run = program(run_cfg)
            for _ in range(3):
                run(params, x).block_until_ready()
            times = []
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                run(params, x).block_until_ready()
                times.append(time.perf_counter() - t0)
            ms = statistics.median(times) * 1e3 / L
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
