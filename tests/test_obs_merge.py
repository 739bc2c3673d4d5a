"""Histogram merge algebra (oim_tpu/obs/merge.py): identity and
associativity of ``add``, counter-reset epoch handling, the merged
percentile matching the pooled-observation percentile on a seeded
workload, and the Histogram.snapshot()/merged_snapshot() bridge from
the live metrics registry into the wire format."""

from __future__ import annotations

import random

import pytest

from oim_tpu.common.metrics import Registry
from oim_tpu.obs import merge

LE = [0.01, 0.1, 1.0]


def snap(counts, total_sum=0.0, le=LE):
    return {"le": list(le), "counts": list(counts), "sum": total_sum}


class TestAlgebra:
    def test_zero_is_identity(self):
        s = snap([1, 3, 4, 6], 2.5)
        assert merge.add(merge.zero(LE), s) == s
        assert merge.add(s, merge.zero(LE)) == s

    def test_add_commutes_and_associates(self):
        a = snap([1, 2, 2, 3], 1.0)
        b = snap([0, 1, 4, 4], 2.0)
        c = snap([2, 2, 2, 9], 0.5)
        assert merge.add(a, b) == merge.add(b, a)
        assert merge.add(merge.add(a, b), c) == merge.add(a, merge.add(b, c))

    def test_grid_mismatch_rejected(self):
        with pytest.raises(ValueError):
            merge.add(snap([0, 0, 0, 0]), snap([0, 0, 0], le=[0.01, 0.1]))

    def test_validate_rejects_malformed(self):
        for bad in (
            "nope",
            {"le": LE},  # no counts
            snap([1, 2, 3]),  # wrong length
            snap([3, 2, 2, 3]),  # non-monotone cumulative
            snap([1, 2, 2, -3]),  # negative
            snap([0, 0, 0, 0], le=[0.1, 0.1, 1.0]),  # duplicate bound
            snap([0, 0, 0, 0], le=[1.0, 0.1, 0.01]),  # unsorted
            snap([0, 0, 0, 0], total_sum=float("nan")),
        ):
            with pytest.raises(ValueError):
                merge.validate(bad)

    def test_quantile_and_total(self):
        # 4 obs: 2 in (0, 0.01], 1 in (0.01, 0.1], 1 above 1.0 (+Inf).
        s = snap([2, 3, 3, 4], 1.5)
        assert merge.total(s) == 4
        assert merge.quantile(s, 0.5) == pytest.approx(0.01)
        # Above the last bound the estimate clamps to the bound.
        assert merge.quantile(s, 0.999) == pytest.approx(1.0)
        assert merge.quantile(merge.zero(LE), 0.5) != merge.quantile(
            merge.zero(LE), 0.5)  # NaN on empty

    def test_good_count_snaps_down(self):
        s = snap([2, 5, 7, 9])
        assert merge.good_count(s, 0.1) == 5
        assert merge.good_count(s, 0.5) == 5  # between bounds: down
        assert merge.good_count(s, 0.005) == 0


class TestCounterReset:
    def test_reset_starts_new_epoch_never_negative(self):
        fleet = merge.FleetHistogram()
        fleet.update("r0", snap([1, 2, 2, 5], 10.0))
        # Restart: lower cumulative count republishes from near zero.
        fleet.update("r0", snap([0, 1, 1, 2], 3.0))
        merged = fleet.merged()
        assert merged["counts"] == [1, 3, 3, 7]
        assert merged["sum"] == pytest.approx(13.0)

    def test_same_count_lower_sum_is_a_reset(self):
        fleet = merge.FleetHistogram()
        fleet.update("r0", snap([0, 0, 0, 2], 10.0))
        fleet.update("r0", snap([0, 0, 0, 2], 1.0))
        assert merge.total(fleet.merged()) == 4

    def test_monotone_growth_is_not_a_reset(self):
        fleet = merge.FleetHistogram()
        fleet.update("r0", snap([1, 1, 1, 1], 0.005))
        fleet.update("r0", snap([1, 2, 2, 3], 1.2))
        assert merge.total(fleet.merged()) == 3

    def test_grid_change_drops_old_epoch(self):
        fleet = merge.FleetHistogram()
        fleet.update("r0", snap([5, 5, 5, 5], 0.01))
        fleet.update("r0", {"le": [0.5, 5.0], "counts": [1, 1, 1],
                            "sum": 0.1})
        assert merge.total(fleet.merged()) == 1

    def test_forget_banks_history_monotone(self):
        """Deregistration closes the epoch WITHOUT deflating the fleet
        cumulative: the burn-rate series differences merged totals, so
        a routine drain must never make them go down (a drop would
        zero every window delta until fresh traffic re-exceeded the
        forgotten history — alerting blind after a rolling restart)."""
        fleet = merge.FleetHistogram()
        fleet.update("r0", snap([0, 0, 0, 4], 2.0))
        fleet.update("r1", snap([0, 0, 0, 6], 3.0))
        assert merge.total(fleet.merged()) == 10
        fleet.forget("r1")
        assert merge.total(fleet.merged()) == 10  # banked, not dropped
        assert fleet.replicas() == ["r0"]
        # A re-registering id starts a FRESH epoch on top of the bank.
        fleet.update("r1", snap([0, 0, 0, 2], 1.0))
        assert merge.total(fleet.merged()) == 12
        fc = merge.FleetCounter()
        fc.update("r0", {"eos": 5, "rejected": 1})
        fc.forget("r0")
        assert fc.merged() == {"eos": 5.0, "rejected": 1.0}
        fc.update("r0", {"eos": 2})
        assert fc.merged()["eos"] == pytest.approx(7.0)

    def test_merge_snapshots_majority_grid(self):
        merged = merge.merge_snapshots([
            snap([0, 0, 0, 1]),
            snap([0, 0, 0, 2]),
            {"le": [9.0], "counts": [1, 1], "sum": 9.0},
            None,
            {"bad": True},
        ])
        assert merged["le"] == LE and merge.total(merged) == 3
        assert merge.merge_snapshots([None, "x"]) is None


class TestFleetCounter:
    def test_reset_epochs_and_merge(self):
        fc = merge.FleetCounter()
        fc.update("r0", {"eos": 10, "rejected": 2})
        fc.update("r1", {"eos": 5})
        fc.update("r0", {"eos": 1})  # restart: eos dropped 10 -> 1
        merged = fc.merged()
        assert merged["eos"] == pytest.approx(16)
        assert merged["rejected"] == pytest.approx(2)
        fc.forget("r1")  # banked: the merged cumulative stays monotone
        assert fc.merged()["eos"] == pytest.approx(16)

    def test_garbage_values_skipped(self):
        fc = merge.FleetCounter()
        fc.update("r0", {"eos": 3, "bad": float("nan"), "neg": -1,
                         "inf": float("inf"), "flag": True})
        assert fc.merged() == {"eos": 3.0}


class TestPooledEquivalence:
    def test_merged_percentile_matches_pooled_with_restart(self):
        """The acceptance algebra: N replicas' private histograms, one
        restarting mid-workload, merged — the fleet p50/p99 must land in
        the same bucket as the pooled-observation percentile (bucket
        resolution is all a histogram promises)."""
        rng = random.Random(7)
        buckets = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5)
        fleet = merge.FleetHistogram()
        pooled = []
        for rid, restarts, slow_frac in (
                ("a", 1, 0.0), ("b", 2, 0.05), ("c", 1, 0.2)):
            for _ in range(restarts):
                hist = Registry().histogram("ft", buckets=buckets)
                for _ in range(300):
                    v = (rng.uniform(0.2, 2.0) if rng.random() < slow_frac
                         else rng.uniform(0.002, 0.09))
                    hist.observe(v)
                    pooled.append(v)
                fleet.update(rid, hist.merged_snapshot())
        merged = fleet.merged()
        assert merge.total(merged) == len(pooled)
        ordered = sorted(pooled)
        for q in (0.5, 0.9, 0.99):
            truth = ordered[int(q * (len(ordered) - 1))]
            estimate = merge.quantile(merged, q)
            drift = abs(merge.bucket_index(merged, estimate)
                        - merge.bucket_index(merged, truth))
            assert drift <= 1, (q, truth, estimate)


class TestMetricsBridge:
    def test_histogram_snapshot_is_cumulative_and_valid(self):
        reg = Registry()
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.05, 0.5, 5.0):
            h.observe(v)
        snap_ = h.merged_snapshot()
        assert snap_ == {"le": [0.1, 1.0], "counts": [2, 3, 4],
                         "sum": pytest.approx(5.6)}
        merge.validate(snap_)

    def test_labeled_family_merges_and_filters(self):
        reg = Registry()
        h = reg.histogram("tok_seconds", labelnames=("kind",),
                          buckets=(0.1, 1.0))
        h.labels(kind="first").observe(0.05)
        h.labels(kind="first").observe(0.5)
        h.labels(kind="next").observe(0.01)
        first = h.merged_snapshot({"kind": "first"})
        assert first["counts"] == [1, 2, 2]
        both = h.merged_snapshot()
        assert both["counts"] == [2, 3, 3]
        # A filter matching nothing is the zero snapshot, not an error.
        assert h.merged_snapshot({"kind": "zzz"})["counts"] == [0, 0, 0]

    def test_round_trips_through_json(self):
        import json

        reg = Registry()
        h = reg.histogram("j_seconds", buckets=(0.1, 1.0))
        h.observe(0.2)
        wire = json.loads(json.dumps(h.merged_snapshot()))
        fleet = merge.FleetHistogram()
        fleet.update("r0", wire)
        assert merge.total(fleet.merged()) == 1


class TestIncrementalFoldEquivalence:
    """The --top --watch fold's correctness contract: for ANY sequence
    of contributor updates — restarts (counter resets), departures,
    grid changes — the incremental fold must equal the from-scratch
    oracle at every step. Bucket counts compare exactly (integer sums);
    the observation sum tolerates float patch-out jitter."""

    GRIDS = ((0.01, 0.1, 1.0), (0.005, 0.05, 0.5, 5.0))

    def _rand_snap(self, rng, le=None):
        le = list(le if le is not None else rng.choice(self.GRIDS))
        counts, c = [], 0
        for _ in range(len(le) + 1):
            c += rng.randrange(0, 5)
            counts.append(c)
        return {"le": le, "counts": counts,
                "sum": round(rng.uniform(0, 10), 6)}

    @staticmethod
    def _same(inc, scratch):
        if scratch is None or merge.total(scratch) == 0:
            assert inc is None or merge.total(inc) == merge.total(
                scratch or {"le": [], "counts": [0], "sum": 0.0})
            return
        assert inc is not None
        assert inc["le"] == scratch["le"]
        assert inc["counts"] == scratch["counts"]
        assert abs(inc["sum"] - scratch["sum"]) < 1e-6

    def test_snapshot_fold_matches_scratch_every_step(self):
        rng = random.Random(11)
        fold = merge.SnapshotFold()
        live: dict[str, dict] = {}
        for _ in range(300):
            key = f"r{rng.randrange(8)}"
            if rng.random() < 0.25 and live:
                victim = rng.choice(sorted(live))
                fold.drop(victim)
                live.pop(victim)
            else:
                s = self._rand_snap(rng)  # may also CHANGE key's grid
                fold.set(key, s)
                live[key] = s
            self._same(fold.merged(),
                       merge.merge_snapshots(list(live.values())))

    def test_fleet_histogram_incremental_matches_scratch_oracle(self):
        """FleetHistogram.merged() (SnapshotFold-backed) against its
        own merged_scratch() through restart epochs and departures —
        the two sides `oim_top_merge_seconds{mode}` times."""
        rng = random.Random(13)
        fleet = merge.FleetHistogram()
        hists: dict[str, object] = {}
        grid = self.GRIDS[0]
        for step in range(200):
            rid = f"r{rng.randrange(6)}"
            roll = rng.random()
            if roll < 0.1 and rid in hists:
                fleet.forget(rid)
                hists.pop(rid)
            else:
                if rid not in hists or roll < 0.2:
                    # Fresh registry = a restart: counters reset.
                    hists[rid] = Registry().histogram(
                        "ft", buckets=grid)
                hists[rid].observe(rng.uniform(0.001, 2.0))
                fleet.update(rid, hists[rid].merged_snapshot())
            inc, scratch = fleet.merged(), fleet.merged_scratch()
            self._same(inc, scratch)
