"""``check_limits.py`` for a cell whose runner brings its own reference
(``runners/serve_family.py``): over a list of seeds, what SOUND runs of the
cell's timed path give against the family's float32 reference, and what the
CONTROL gives (that reference computed in float8) on the same sample, held
to the configuration's limits by the runner's own comparison: ``correct``
of the sound run has to be true and ``control_correct`` false. One JSON
line a seed; ``PERF.md`` records them and the limit chosen.

    python3 benchmarks/check_limits_family.py \
        --workload joyai-llm-flash.longctx --seeds 11,12,13 --seconds 15

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

from benchmarks import common  # noqa: E402


def main(argv=None, platform: str = "tpu", root: str = common.ROOT) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--seconds", type=float, default=15.0)
    args = p.parse_args(argv)

    _, cell, config, mix, runner = common.load_cell(root, args.workload)
    if not hasattr(runner, "control_check"):
        raise SystemExit(f"runner {mix['runner']!r} brings no control: "
                         "use check_limits.py")
    common.start_jax(platform, cell["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        workdir = os.path.join(root, "_work", "bench", f"limits-{args.workload}")
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        ctx = common.Context(
            cell=cell, config=config, traffic=mix, seed=seed,
            seconds=args.seconds, trace=False, workdir=workdir,
            t0=time.monotonic(), platform=platform)
        result = runner.run(ctx)
        control = runner.control_check(ctx, result["check_sample"])
        line = {"workload": args.workload, "seed": seed,
                "correct": result["correct"], "failed": result["failed"],
                "attempted": result["attempted"], "sound": result["compared"],
                "control_correct": control["correct"],
                "control": control["numbers"]}
        shutil.rmtree(workdir, ignore_errors=True)
        print("LIMITS " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
