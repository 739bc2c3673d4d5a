#!/usr/bin/env python3
"""MoE dispatch-tax sweep on the real chip (VERDICT r3 weak #4 evidence
for BASELINE.md): GShard einsum dispatch vs index-based gather dispatch,
and a capacity-factor ladder, on the r3 MoE flagship shape (4 experts
top-2, 638M active params, b2 s2048). Same chained-fori differencing as
bench.py / sweep_llama.py; MFU counts ACTIVE params only."""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from scripts.sweep_llama import measure  # noqa: E402


# Dispatch is EXPLICIT on every row: the Config default flipped to
# "gather" after the r4 measurement, and a row relying on the default
# would silently measure gather under an einsum label.
RUNS = [
    ("einsum cf1.25 (r3 baseline)", dict(moe_dispatch="einsum")),
    ("gather cf1.25", dict(moe_dispatch="gather")),
    ("einsum cf1.0", dict(moe_dispatch="einsum", moe_capacity_factor=1.0)),
    ("gather cf1.0", dict(moe_dispatch="gather", moe_capacity_factor=1.0)),
    ("gather cf2.0", dict(moe_dispatch="gather", moe_capacity_factor=2.0)),
]


def run_one(index: int) -> None:
    from oim_tpu.models import llama

    # remat (dots policy) on every row: the non-remat shape OOMs in this
    # harness for BOTH dispatch modes (einsum 17.4G, gather 23.8G vs
    # 15.75G hbm), so the comparison runs remat-equalized.
    base = llama.Config(
        vocab=32768, dim=2048, n_layers=8, n_heads=16, n_kv_heads=8,
        head_dim=128, mlp_dim=8192, max_seq=8192,
        n_experts=4, moe_top_k=2,
        remat=True, remat_policy="dots_with_no_batch_dims",
    )
    name, over = RUNS[index]
    cfg = dataclasses.replace(base, **over)
    mfu, dt = measure(cfg, batch=2, seq=2048, attn_fn=None)
    print(f"{name:32s} mfu={mfu:.4f} step={dt:.4f}s", flush=True)


def main():
    # One subprocess per row, in turn: each row owns the chip for its
    # own compile + run and frees all HBM on exit. This parent never
    # initializes a JAX backend (a chip belongs to one process at a time).
    import subprocess
    import sys as _sys

    for i, (name, _) in enumerate(RUNS):
        proc = subprocess.run(
            [_sys.executable, __file__, str(i)],
            capture_output=True, text=True, timeout=1200,
        )
        rows = [ln for ln in proc.stdout.splitlines() if "mfu=" in ln]
        if proc.returncode == 0 and rows:
            print(rows[-1], flush=True)
        else:
            tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
            print(f"{name:32s} FAILED: {' | '.join(tail)}", flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1:
        run_one(int(sys.argv[1]))
    else:
        main()
