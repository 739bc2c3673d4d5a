"""Whole passes of ``run.main`` at tiny size on the CPU (the tests' entry
names the platform; the command line cannot), through cells ADDED to a copy
of the benchmark as new files and entries."""

import json
import os
import re

import pytest

import benchmark_tiny as tiny
from benchmarks import run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def test_chat_cell_reports_its_end_to_end_metrics(root, capsys):
    line = tiny.run_cell(root, "tiny-dense.chat", 2**31 + 11, 1.5, 0, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 18  # rate x seconds, and the pre-roll
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert line["device"]["platform"] == "cpu"  # and no device metric
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}


def test_batch_cell_traced_reports_host_layers_only_on_the_cpu(root, capsys):
    line = tiny.run_cell(root, "tiny-moe.batch", 5, 1.5, 1, capsys)
    assert line["correct"] is True and line["attempted"] > 6
    # no device plane on the CPU: the trace readers find nothing to read
    # and their metrics are left out of the line; the host's stand
    assert "breakdown" in line
    assert set(line["metrics"]) == {
        "slice_rate.batch", "stall_share.batch", "out_tokens_per_s.batch",
        "itl_p50_ms.batch", "itl_p90_ms.batch", "itl_p97_ms.batch"}
    assert line["metrics"]["slice_rate.batch"]["value"] > 0
    assert (0 < line["metrics"]["itl_p50_ms.batch"]["value"]
            <= line["metrics"]["itl_p90_ms.batch"]["value"]
            <= line["metrics"]["itl_p97_ms.batch"]["value"])
    assert line["metrics"]["stall_share.batch"]["value"] < 100
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device",
                         "breakdown"}
    assert line["device"]["platform"] == "cpu"


def test_batch_cell_reports_a_gap_percentile_end_to_end(root, capsys):
    line, text = tiny.run_cell(root, "tiny-moe.batch", 2**31 + 5, 1.5, 0,
                               capsys, earlier=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    p95 = float(re.search(r"token gap percentiles.* p95=([0-9.]+)",
                          text).group(1))
    assert 0 < line["metrics"]["itl_p95_ms"]["value"] == pytest.approx(
        p95, abs=0.06)
    # untraced, both rates are on an earlier line only
    assert "whole_window_tokens_per_s=" in text
    assert "slice_median_tokens_per_s=" in text


def test_batch_rate_is_every_token_of_the_window_over_all_of_its_time(
        root, capsys):
    """Per layer since the driver's check of PR 25 (a second of standstill
    is 2 % of it): what it measures did not change."""
    line, text = tiny.run_cell(root, "tiny-moe.batch", 2**31 + 6, 1.5, 1,
                               capsys, earlier=True)
    tokens = int(re.search(r"window closed .*out_tokens=(\d+)", text).group(1))
    assert tokens > 0
    assert line["metrics"]["out_tokens_per_s.batch"]["value"] == pytest.approx(
        tokens / 1.5, rel=1e-9)
    assert line["metrics"]["out_tokens_per_s.batch"]["unit"] == "tokens/s"


def test_a_token_altered_where_it_is_produced_is_not_correct(
        root, capsys, monkeypatch):
    from oim_tpu.serve import engine

    emit = engine.ServeEngine._emit
    monkeypatch.setattr(
        engine.ServeEngine, "_emit",
        lambda self, req, token: emit(self, req, (token + 1) % 256))
    line = tiny.run_cell(root, "tiny-dense.chat", 7, 1.0, 0, capsys)
    assert line["correct"] is False and line["failed"] == 0


def test_the_command_line_entry_never_falls_back(root, monkeypatch, capsys):
    """``platform`` defaults to the TPU: on this box that is an error and
    no result line is printed."""
    with pytest.raises(BaseException) as err:
        run.main(["--workload", "tiny-dense.chat", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], root=root)
    assert not isinstance(err.value, AssertionError)
    assert "metrics" not in capsys.readouterr().out


def test_a_new_reader_and_metric_are_found_by_name(root, capsys):
    """A per-layer metric added as a reader file, a metric file and an
    entry — no edit to a file that is there."""
    with open(os.path.join(root, "benchmarks", "readers", "count_stat.py"), "w") as f:
        f.write("def read(result, args):\n"
                "    return float(len(result['stats'][args['stat']]))\n")
    with open(os.path.join(root, "benchmarks", "metrics", "first_tokens.json"), "w") as f:
        json.dump({"unit": "requests", "reader": "count_stat",
                   "args": {"stat": "ttft_ms"}}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"].append({
        "name": "first_tokens", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "itl_p95_ms", "workloads": ["tiny-dense.chat"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    line = tiny.run_cell(root, "tiny-dense.chat", 3, 1.0, 1, capsys)
    assert line["metrics"]["first_tokens"] == {"value": 12.0, "unit": "requests"}
    assert line["metrics"]["gen_lateness_p95_ms.chat"]["value"] >= 0
    assert (line["metrics"]["itl_p90_ms.chat"]["value"]
            <= line["metrics"]["itl_p97_ms.chat"]["value"])
    # nothing from a device trace or a device's peaks off the TPU
    assert not {"device_idle.chat", "decode_step_ms.chat", "decode_roofline.chat",
                "prefill_share.chat"} & set(line["metrics"])
