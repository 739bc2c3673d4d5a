"""Chaos engineering over the in-process cluster sim.

``sim`` assembles a parameterizable in-process cluster (replicated
registry pair, malloc controllers, serve replicas behind a router, a
feeder) with per-component kill/drain/restart/partition handles;
``ladder`` runs seeded, scripted fault schedules over it and asserts
the heal paths CONVERGE — expected events on ``/debug/events``, in
order, zero client-visible errors where the retry contract promises
them, byte-identical routed outputs, zero-leak censuses.

Entry points: ``make chaos`` = ``python -m oim_tpu.chaos [--seed N]`` (the
full ladder), tests/test_chaos_smoke.py (the trimmed tier-1 rungs).
"""

from oim_tpu.chaos.ladder import (  # noqa: F401
    RUNGS,
    SMOKE_RUNGS,
    Rung,
    run_ladder,
)
from oim_tpu.chaos.sim import ClusterSim  # noqa: F401
