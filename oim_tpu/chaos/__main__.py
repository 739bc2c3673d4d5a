"""``python -m oim_tpu.chaos [--seed N]``: the whole ladder, `make chaos`.
Prints each rung's heal signature as JSON; raises on any divergence."""

import argparse
import json
import os

from oim_tpu.chaos import ladder

parser = argparse.ArgumentParser("oim_tpu.chaos")
parser.add_argument("--seed", type=int, default=ladder.DEFAULT_SEED,
                    help="same seed -> same heal-event sequence")
args = parser.parse_args()
# shard_member_kill spans two fake XLA devices: before the first jax import.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()
print(json.dumps(ladder.run_ladder(seed=args.seed)["event_signature"]))
