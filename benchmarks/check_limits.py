"""Read the two numbers every limit of ``correct`` is set from, on the chip,
in one process: over a list of seeds, what SOUND runs of a cell's timed path
give against the reference, and what the CONTROL gives (the reference
itself computed in float8, the nearest precision below the bfloat16 the
configurations state). A limit belongs above the sound runs' largest and
below the control's smallest; this script only prints the readings, one JSON
line a seed — ``PERF.md`` records them and the limit chosen.

    python3 benchmarks/check_limits.py --workload mistral-7b.chat \
        --seeds 11,12,13 --seconds 15

The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmarks import common  # noqa: E402


def control_numbers(mix: dict, model: dict, opt, seed: int, result: dict) -> dict:
    from benchmarks.reference import llama_like as ref

    if mix["runner"] == "serve":
        gaps = np.concatenate([
            ref.served_gaps(seed, model, prompt, served, control=True)
            for prompt, served in result["check_sample"]])
        return {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean())}
    seen, want = result["check_sample"], result["reference"]
    low = ref.train_reference(seed, model, opt, seen["batches"], quant=True)
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(low["loss"], want["loss"])),
        "grad1_gap": ref.worst_leaf_gap(low["grad1"], want["grad1"]),
        "delta_gap": ref.worst_leaf_gap(low["delta"], want["delta"]),
        "grad1_diff": ref.worst_leaf_difference(
            low["grad1_sample"], want["grad1_sample"]),
    }


def main(argv=None, platform: str = "tpu", root: str = common.ROOT) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--seconds", type=float, default=15.0)
    args = p.parse_args(argv)

    _, cell, config, mix, runner = common.load_cell(root, args.workload)
    common.start_jax(platform, cell["chips"])
    model = common.model_dict(config, mix["runner"])
    opt = config[mix["runner"]].get("optimizer")
    for seed in (int(s) for s in args.seeds.split(",")):
        workdir = os.path.join(root, "_work", "bench", f"limits-{args.workload}")
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        ctx = common.Context(
            cell=cell, config=config, traffic=mix, seed=seed,
            seconds=args.seconds, trace=False, workdir=workdir,
            t0=time.monotonic(), platform=platform)
        result = runner.run(ctx)
        line = {"workload": args.workload, "seed": seed,
                "correct": result["correct"], "failed": result["failed"],
                "attempted": result["attempted"], "sound": result["compared"]}
        line["control"] = control_numbers(mix, model, opt, seed, result)
        shutil.rmtree(workdir, ignore_errors=True)
        print("LIMITS " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
