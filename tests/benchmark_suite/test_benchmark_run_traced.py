"""The training cell's traced pass at tiny size on the CPU: the profiler is
started and stopped inside the window, its stop is left out of the rate
that ``stall_share`` is held against, and only the host's layers are
reported."""

import benchmark_tiny as tiny


def test_train_cell_traced_reports_host_layers_only_on_the_cpu(
        tmp_path, capsys):
    root = tiny.make_root(str(tmp_path / "bench"))
    line = tiny.run_cell(root, "tiny-dense.train", 11, 1.5, 1, capsys)
    assert line["correct"] is True and line["attempted"] >= 2
    assert set(line["metrics"]) == {"feed_wait_ms.train", "stall_share.train",
                                    "slice_rate.train"}
    assert line["metrics"]["slice_rate.train"]["value"] > 0
    assert line["metrics"]["feed_wait_ms.train"]["value"] >= 0
    assert abs(line["metrics"]["stall_share.train"]["value"]) < 100
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device",
                         "breakdown"}
